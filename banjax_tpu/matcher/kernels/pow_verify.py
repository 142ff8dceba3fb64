"""Batched sha256 PoW verification kernel (challenge plane, ROADMAP item 3).

The sha-inv challenge accepts a cookie when
``leading_zero_bits(sha256(hmac[20] || solution[32])) >= N``
(crypto/challenge.py:validate_sha_inv_cookie).  The hashed message is
always exactly 52 bytes, which pads to a SINGLE 64-byte SHA-256 block —
so a batch of B candidate solutions is one embarrassingly-parallel
[16, B] uint32 problem: each lane runs the 64-round compression from the
fixed IV and counts the digest's leading zero bits in-kernel, returning
one int32 per candidate.  No per-candidate host hashing, one dispatch
per micro-batch.

Layout follows fused_match_window.py: 2-D refs ([16, B] message words
in, [1, B] zero-bit counts out), batch padded to the 128-wide TPU lane
so every shape is static, and a cached pallas_call builder per (B,
interpret).  All arithmetic is uint32 with wrapping adds; rotr is the
two-shift form and clz is a portable bit-length cascade (no lax.clz —
see /opt/skills/guides/pallas_guide.md on lowering portability).

``pow_selftest`` proves the kernel against hashlib + the pure-Python
count_zero_bits_from_left before the verifier routes real traffic to
it; a selftest failure downgrades the verifier to the CPU oracle (the
scan_selftest pattern), never changing an accept/reject decision.
"""

from __future__ import annotations

import functools
import hashlib
from typing import List, Sequence, Tuple

import numpy as np

# 52-byte message = hmac[20] || solution[32]; one padded SHA-256 block:
# 13 data words, 0x80 terminator word, zero word, 416-bit length word.
POW_MESSAGE_BYTES = 20 + 32
_PAD_WORD_80 = 0x80000000
_LEN_BITS = POW_MESSAGE_BYTES * 8
LANE = 128  # TPU lane width — batch dim padded to a multiple of this

_H0 = (0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
       0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19)

_K = (
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5,
    0x3956C25B, 0x59F111F1, 0x923F82A4, 0xAB1C5ED5,
    0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174,
    0xE49B69C1, 0xEFBE4786, 0x0FC19DC6, 0x240CA1CC,
    0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7,
    0xC6E00BF3, 0xD5A79147, 0x06CA6351, 0x14292967,
    0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85,
    0xA2BFE8A1, 0xA81A664B, 0xC24B8B70, 0xC76C51A3,
    0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5,
    0x391C0CB3, 0x4ED8AA4A, 0x5B9CCA4F, 0x682E6FF3,
    0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
)


def _rotr(x, n: int):
    import jax.numpy as jnp

    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _clz32(x):
    """Leading zeros of a [1, B] uint32 via a bit-length cascade."""
    import jax.numpy as jnp

    bl = jnp.zeros(x.shape, jnp.int32)
    y = x
    for shift in (16, 8, 4, 2, 1):
        cond = (y >> jnp.uint32(shift)) > jnp.uint32(0)
        bl = bl + jnp.where(cond, shift, 0).astype(jnp.int32)
        y = jnp.where(cond, y >> jnp.uint32(shift), y)
    bl = bl + (y > jnp.uint32(0)).astype(jnp.int32)
    return jnp.int32(32) - bl


def _pow_kernel(msg_ref, out_ref):
    import jax.numpy as jnp

    # rolling 16-word schedule keeps VMEM at 16 rows, not 64
    w = [msg_ref[i : i + 1, :] for i in range(16)]
    a, b, c, d, e, f, g, h = (jnp.full_like(w[0], jnp.uint32(v)) for v in _H0)
    for i in range(64):
        if i < 16:
            wi = w[i]
        else:
            w15 = w[(i - 15) % 16]
            w2 = w[(i - 2) % 16]
            s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> jnp.uint32(3))
            s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> jnp.uint32(10))
            wi = w[i % 16] + s0 + w[(i - 7) % 16] + s1
            w[i % 16] = wi
        ch = (e & f) ^ (~e & g)
        t1 = h + (_rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)) + ch \
            + jnp.uint32(_K[i]) + wi
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = (_rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)) + maj
        a, b, c, d, e, f, g, h = t1 + t2, a, b, c, d + t1, e, f, g

    digest = [x + jnp.uint32(v)
              for x, v in zip((a, b, c, d, e, f, g, h), _H0)]
    total = jnp.zeros(digest[0].shape, jnp.int32)
    live = jnp.ones(digest[0].shape, jnp.bool_)
    for word in digest:
        total = total + jnp.where(live, _clz32(word), 0)
        live = live & (word == jnp.uint32(0))
    out_ref[0:1, :] = total


@functools.lru_cache(maxsize=16)
def _pow_call(batch: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        _pow_kernel,
        out_shape=jax.ShapeDtypeStruct((1, batch), jnp.int32),
        interpret=interpret,
    )


def pack_pow_messages(payloads: Sequence[bytes]) -> Tuple[np.ndarray, int]:
    """[16, B_padded] uint32 big-endian message words for a batch of
    52-byte hmac||solution payloads; returns (words, real_count).
    Padding lanes hash a zero message — harmless, their counts are
    sliced off."""
    n = len(payloads)
    padded = max(LANE, -(-n // LANE) * LANE)
    words = np.zeros((16, padded), dtype=np.uint32)
    buf = np.zeros((padded, 64), dtype=np.uint8)
    for j, payload in enumerate(payloads):
        if len(payload) != POW_MESSAGE_BYTES:
            raise ValueError(
                f"payload {j}: want {POW_MESSAGE_BYTES} bytes, "
                f"got {len(payload)}"
            )
        buf[j, :POW_MESSAGE_BYTES] = np.frombuffer(payload, np.uint8)
    words[:, :] = (
        buf.reshape(padded, 16, 4)
        .astype(np.uint32)
        .transpose(1, 0, 2)
        @ np.asarray([1 << 24, 1 << 16, 1 << 8, 1], np.uint32)
    )
    words[13, :] = _PAD_WORD_80
    words[14, :] = 0
    words[15, :] = _LEN_BITS
    return words, n


def leading_zero_bits_batch(
    payloads: Sequence[bytes], interpret: bool = False
) -> np.ndarray:
    """Leading-zero-bit counts of sha256(payload) for each 52-byte
    payload, one kernel dispatch."""
    words, n = pack_pow_messages(payloads)
    import jax.numpy as jnp

    out = _pow_call(words.shape[1], bool(interpret))(jnp.asarray(words))
    return np.asarray(out)[0, :n]


def _default_interpret() -> bool:
    import jax

    return jax.default_backend() != "tpu"


def pow_selftest(interpret: bool = None) -> None:
    """Differential proof vs hashlib before the kernel sees traffic.
    Raises RuntimeError on any mismatch; the verifier downgrades to the
    CPU oracle on failure (scan_selftest pattern)."""
    from banjax_tpu.crypto.challenge import count_zero_bits_from_left

    if interpret is None:
        interpret = _default_interpret()
    rng = np.random.default_rng(0x51A)
    payloads: List[bytes] = [
        rng.integers(0, 256, POW_MESSAGE_BYTES, np.uint8).tobytes()
        for _ in range(24)
    ]
    # force easy leading-zero structure into some lanes so the clz
    # cascade's word-boundary handling is actually exercised
    payloads.append(b"\x00" * POW_MESSAGE_BYTES)
    payloads.append(b"\x00" * 51 + b"\x01")
    got = leading_zero_bits_batch(payloads, interpret=interpret)
    for payload, bits in zip(payloads, got.tolist()):
        digest = hashlib.sha256(payload).digest()
        want = count_zero_bits_from_left(digest)
        if bits != want:
            raise RuntimeError(
                f"pow_verify selftest mismatch: payload "
                f"{payload[:8].hex()}… kernel={bits} hashlib={want}"
            )
