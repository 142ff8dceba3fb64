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
interpret).  All arithmetic is uint32 with wrapping adds.  The 64 rounds
are two rolled loops (0-15 over the message, 16-63 extending a rolling
16-word schedule in place) over VMEM scratch, constants in SMEM: XLA's
CPU compiler does not finish the unrolled straight-line graph, and the
same body runs interpreted on the CPU and through Mosaic on the chip.

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

# int32 bit patterns: the kernel reads them as SMEM scalars, which are signed
_K = np.asarray((
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
), np.uint32).view(np.int32)


def _rotr(x, n: int):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _pow_kernel(k_ref, msg_ref, out_ref, w_ref, s_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    w_ref[...] = msg_ref[...]
    for j, v in enumerate(_H0):
        s_ref[j : j + 1, :] = jnp.full((1, s_ref.shape[1]), v, jnp.uint32)

    def w_row(i):
        return w_ref[pl.ds(i & 15, 1), :]

    def sha_round(i, carry):
        a, b, c, d, e, f, g, h = (s_ref[j : j + 1, :] for j in range(8))
        ch = (e & f) ^ (~e & g)
        t1 = h + (_rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)) + ch \
            + k_ref[i].astype(jnp.uint32) + w_row(i)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = (_rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)) + maj
        for j, x in enumerate((t1 + t2, a, b, c, d + t1, e, f, g)):
            s_ref[j : j + 1, :] = x
        return carry

    def extend_schedule_then_round(i, carry):
        w15, w2 = w_row(i - 15), w_row(i - 2)
        s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> jnp.uint32(3))
        s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> jnp.uint32(10))
        w_ref[pl.ds(i & 15, 1), :] = w_row(i) + s0 + w_row(i - 7) + s1
        return sha_round(i, carry)

    jax.lax.fori_loop(0, 16, sha_round, 0)
    jax.lax.fori_loop(16, 64, extend_schedule_then_round, 0)

    total = jnp.zeros((1, s_ref.shape[1]), jnp.int32)
    live = jnp.ones(total.shape, jnp.bool_)
    for j, v in enumerate(_H0):
        word = s_ref[j : j + 1, :] + jnp.uint32(v)
        total = total + jnp.where(live, jax.lax.clz(word).astype(jnp.int32), 0)
        live = live & (word == jnp.uint32(0))
    out_ref[0:1, :] = total


@functools.lru_cache(maxsize=16)
def _pow_call(batch: int, interpret: bool):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call(
        _pow_kernel,
        out_shape=jax.ShapeDtypeStruct((1, batch), np.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((16, batch), np.uint32),
            pltpu.VMEM((8, batch), np.uint32),
        ],
        interpret=interpret,
    )
    return functools.partial(call, _K)


def pack_pow_messages(payloads: Sequence[bytes]) -> Tuple[np.ndarray, int]:
    """[16, B_padded] uint32 big-endian message words for a batch of
    52-byte hmac||solution payloads; returns (words, real_count).
    Padding lanes hash a zero message — harmless, their counts are
    sliced off."""
    n = len(payloads)
    padded = max(LANE, -(-n // LANE) * LANE)
    buf = np.zeros((padded, 64), dtype=np.uint8)
    for j, payload in enumerate(payloads):
        if len(payload) != POW_MESSAGE_BYTES:
            raise ValueError(
                f"payload {j}: want {POW_MESSAGE_BYTES} bytes, "
                f"got {len(payload)}"
            )
        buf[j, :POW_MESSAGE_BYTES] = np.frombuffer(payload, np.uint8)
    words = (
        buf.reshape(padded, 16, 4)
        .astype(np.uint32)
        .transpose(1, 0, 2)
        @ np.asarray([1 << 24, 1 << 16, 1 << 8, 1], np.uint32)
    )
    words[13, :] = _PAD_WORD_80
    words[15, :] = _LEN_BITS
    return words, n


def leading_zero_bits_batch(
    payloads: Sequence[bytes], interpret: bool = False
) -> np.ndarray:
    """Leading-zero-bit counts of sha256(payload) for each 52-byte
    payload, one kernel dispatch."""
    words, n = pack_pow_messages(payloads)
    out = _pow_call(words.shape[1], bool(interpret))(words)
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
    # the all-zero message is also what the padding lanes hash
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
