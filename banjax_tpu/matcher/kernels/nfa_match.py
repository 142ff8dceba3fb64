"""Pallas TPU kernel: batched bit-parallel NFA match.

This is the hand-scheduled version of banjax_tpu/matcher/nfa_jax.py — the
device replacement for the reference's serial per-(line, rule) regexp loop
(/root/reference/internal/regex_rate_limiter.go:216-269). The XLA scan in
nfa_jax is correct but its per-byte `jnp.take(b_table, cls)` gather is at
the mercy of XLA's gather lowering; this kernel instead

  * keeps the NFA state and the whole transition table resident in VMEM
    across the full byte scan — per block, HBM traffic is one read of the
    encoded lines and one write of the accept words, nothing else;
  * performs the byte-class → transition-mask gather as a one-hot matmul on
    the MXU: the uint32 table is split into four 8-bit planes stored as
    int8 biased by -128 (so 0..255 fits the signed range), and
    `table[4W, C] @ onehot[C, block]` is exact because every one-hot
    column selects a single row value; the +128 bias is added back on the
    VPU during plane recombination. int8 runs the MXU at twice the bf16
    rate (measured 2.0x on v5e);
  * skips byte tiles entirely once every line in the block has ended: the
    per-block tile count is a scalar-prefetch operand, so with
    length-sorted batches (match_batch_pallas sorts internally) short
    blocks run only the tiles they need instead of the padded maximum;
  * advances all rules at once with uint32 shift-and ops on the VPU.

Layout is TRANSPOSED versus nfa_jax: state is [W, block] — NFA words on
sublanes, lines on lanes. That makes the cross-word carry a sublane roll,
lets every mask slice be tiling-aligned (wps_p is a KERNEL_WORD_ALIGN = 32
multiple: the int8 sublane tile, which every in-kernel slice satisfies),
and gives the per-byte column DMA a [cols, block] tile. The byte position is the
innermost (sequential) grid axis: the Pallas pipeline double-buffers each
byte-row tile while the previous one computes; NFA state lives in VMEM
scratch across grid steps (reset at byte 0), accept bits accumulate into
the revisited output block.

Sharding: rule shards (rulec guarantees no branch straddles a shard
boundary) map to a grid axis — each (line-block, shard) pair scans an
independent word slab, so the same kernel serves the single-chip path and
the per-device body of the rp-sharded mesh path.

The `interpret=True` mode runs the identical kernel as plain JAX on CPU —
the CI path (SURVEY.md §4 carry-over (f)).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from banjax_tpu.matcher.rulec import KERNEL_WORD_ALIGN, CompiledRules

# mask-column indices in the packed [W, 8] uint32 mask tensor
_SHIFT_IN, _INJ_ALWAYS, _INJ_START, _SELFLOOP, _ACC_ANY, _ACC_END = range(6)

_LANE = 128            # TPU lane width
_SUBLANE = 8           # int32/f32 sublane tile
_COLS_PER_STEP = 8     # byte columns processed per grid step (one sublane tile)
_DEFAULT_BLOCK_B = 256
_MAX_WORDS_PER_SHARD = 2048  # VMEM guard: beyond this, fall back to nfa_jax


class PallasUnsupported(ValueError):
    """Ruleset shape the kernel refuses (caller falls back to nfa_jax)."""


@dataclasses.dataclass(frozen=True)
class PallasRules:
    """Kernel-ready repack of CompiledRules (padded, shard-major, transposed)."""

    n_rules: int
    n_shards: int
    wps: int             # original words per shard
    wps_p: int           # padded to a KERNEL_WORD_ALIGN (32) multiple
    n_classes_p: int     # padded to a lane multiple (it's the dot's lane axis)
    btab_t: jnp.ndarray  # [n_shards * 4 * wps_p, C_p] int8 — 4 byte planes, biased -128
    masks_t: jnp.ndarray  # [n_shards * wps_p, 8] uint32
    # extraction arrays (word indices remapped into the padded word space)
    acc_word: jnp.ndarray     # [n_branches] int32
    acc_mask: jnp.ndarray     # [n_branches] uint32
    branch_rule: jnp.ndarray  # [n_branches] int32
    always_match: jnp.ndarray  # [n_rules] bool
    empty_only: jnp.ndarray    # [n_rules] bool
    # carry_free (see prepare()): word-aligned branches let the kernel drop
    # the cross-word carry — 3 of ~13 VPU ops per byte column
    carry_free: bool = False
    # jitted device_matcher per (B, L_p, block_b, interpret) — a mutable
    # cache inside a frozen dataclass, keyed per ruleset by construction
    _fns: dict = dataclasses.field(default_factory=dict, compare=False, repr=False)

    @property
    def total_words(self) -> int:
        return self.n_shards * self.wps_p

    def jitted(self, B: int, L_p: int, block_b: int, interpret: bool,
               pack: bool = False, cols: int = _COLS_PER_STEP):
        key = (B, L_p, block_b, interpret, pack, cols)
        fn = self._fns.get(key)
        if fn is None:
            fn = jax.jit(
                device_matcher(self, B, L_p, block_b, interpret, pack, cols)
            )
            self._fns[key] = fn
        return fn


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def prepare(compiled: CompiledRules) -> PallasRules:
    """Repack a compiled ruleset for the kernel.

    Each shard's `wps` words are padded independently to a KERNEL_WORD_ALIGN multiple so
    a grid step over shard j addresses a self-contained, aligned word slab;
    accept-word indices are remapped to match. Padding words carry all-zero
    masks, so any state bit shifted into them is annihilated by `& bmask`.

    (Accept-absorption was tried and REVERTED: making accept-any bits'
    b_table rows class-independent persists accepted bits, but it also
    lets the shift-in ENTER the accept position without checking the byte
    — a prefix of a literal followed by any pad byte falsely accepts.
    Separating "enter" from "persist" costs the same 2 VPU ops the trick
    would save, so the per-column accumulation stays.)
    """
    ns, wps = compiled.n_shards, compiled.words_per_shard
    # pad each slab to the int8 sublane tile (32), not the full lane (128):
    # every in-kernel slice stays tiling-aligned (btab plane slices at
    # multiples of W with 4W a 128-multiple; [W, 8] mask rows and the
    # [W, block] state need only 8) and the VPU scan — the measured
    # critical path — runs 4x fewer word rows for a ~40-word stage-1
    # automaton. BANJAX_NFA_WORD_ALIGN=128 restores the conservative pad.
    wps_p = max(KERNEL_WORD_ALIGN, _pad_to(wps, KERNEL_WORD_ALIGN))
    if wps_p > _MAX_WORDS_PER_SHARD:
        raise PallasUnsupported(
            f"{wps_p} words/shard exceeds the VMEM budget "
            f"({_MAX_WORDS_PER_SHARD}); use more rule shards or nfa_jax"
        )
    C = compiled.n_classes
    C_p = max(_LANE, _pad_to(C, _LANE))

    # int8 planes biased by -128: row value v is stored as v-128, and the
    # kernel adds the bias back after the dot (every one-hot column selects
    # exactly one row, including pad columns, which select the all-zero
    # class-0 row stored as -128).
    btab_t = np.full((ns * 4 * wps_p, C_p), -128, dtype=np.int16)
    masks_t = np.zeros((ns * wps_p, 8), dtype=np.uint32)
    b = compiled.b_table  # [C, ns * wps] uint32
    mask_rows = [
        compiled.shift_in, compiled.inject_always, compiled.inject_start,
        compiled.selfloop, compiled.accept_any, compiled.accept_end,
    ]
    for j in range(ns):
        sl = slice(j * wps, (j + 1) * wps)
        for plane in range(4):
            vals = ((b[:, sl] >> np.uint32(8 * plane)) & np.uint32(0xFF)).astype(
                np.int16
            )  # [C, wps]
            base = j * 4 * wps_p + plane * wps_p
            btab_t[base : base + wps, :C] = vals.T - 128
        for r, row in enumerate(mask_rows):
            masks_t[j * wps_p : j * wps_p + wps, r] = row[sl]

    shard_of = compiled.acc_word // wps if compiled.acc_word.size else compiled.acc_word
    acc_word_p = (shard_of * wps_p + compiled.acc_word % wps).astype(np.int32)

    return PallasRules(
        n_rules=compiled.n_rules,
        n_shards=ns,
        wps=wps,
        wps_p=wps_p,
        n_classes_p=C_p,
        btab_t=jnp.asarray(btab_t, dtype=jnp.int8),
        masks_t=jnp.asarray(masks_t),
        acc_word=jnp.asarray(acc_word_p),
        acc_mask=jnp.asarray(compiled.acc_mask),
        branch_rule=jnp.asarray(compiled.branch_rule),
        always_match=jnp.asarray(compiled.always_match),
        empty_only=jnp.asarray(compiled.empty_only),
        carry_free=compiled.carry_free,
    )


def _kernel(maxtile_ref, cls_rows_ref, lens_ref, btab_ref, masks_ref,
            out_ref, d_ref, *, C, W, use_roll, cols, carry=True):
    """One (line-block, rule-shard, byte-tile) grid step: `cols` byte columns."""
    i = pl.program_id(0)
    t = pl.program_id(2)
    bB = cls_rows_ref.shape[1]
    zero = jnp.uint32(0)

    @pl.when(t == 0)
    def _init():
        d_ref[:] = jnp.zeros((W, bB), dtype=jnp.uint32)
        out_ref[:] = jnp.zeros((W, bB), dtype=jnp.uint32)

    # Once every line in this block has ended, the remaining byte columns
    # are all pad (class 0, all-zero masks): state would only collapse, so
    # skipping the tile outright is exact.
    @pl.when(t < maxtile_ref[i])
    def _body():
        shift_in = masks_ref[:, _SHIFT_IN : _SHIFT_IN + 1]      # [W, 1]
        inj_always = masks_ref[:, _INJ_ALWAYS : _INJ_ALWAYS + 1]
        inj_start = masks_ref[:, _INJ_START : _INJ_START + 1]
        selfloop = masks_ref[:, _SELFLOOP : _SELFLOOP + 1]
        acc_any = masks_ref[:, _ACC_ANY : _ACC_ANY + 1]
        acc_end = masks_ref[:, _ACC_END : _ACC_END + 1]

        last_col = lens_ref[:] - 1  # [1, bB]
        cls_iota = jax.lax.broadcasted_iota(jnp.int32, (C, bB), 0)
        d = d_ref[:]
        acc = out_ref[:]
        for k in range(cols):
            cls_row = cls_rows_ref[k : k + 1, :]                # [1, bB]
            onehot = (cls_row == cls_iota).astype(jnp.int8)     # [C, bB]
            # MXU gather at the int8 rate: each one-hot column selects one
            # biased row value v-128; +128 restores the exact byte plane.
            # One dot per 8-bit plane keeps the int32 transient at [W, bB]
            # (a single [4W, C] dot would transiently hold 4x that in VMEM,
            # which caps block_b at small sizes).
            # Recombine biased planes in wrapping int32 arithmetic: mod 2^32,
            # Σ (v_k - 128) << 8k  =  (Σ v_k << 8k) - 0x80808080, so adding
            # 0x80808080 back yields exactly the OR of the unbiased byte
            # planes (they occupy disjoint bit lanes).
            s = None
            for plane in range(4):
                p = jax.lax.dot_general(
                    btab_ref[plane * W : (plane + 1) * W, :], onehot,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32,
                )  # [W, bB] values in [-128, 127]
                p = p << (8 * plane) if plane else p
                s = p if s is None else s + p
            bmask = (s + jnp.int32(-0x7F7F7F80)).astype(jnp.uint32)
            if carry:
                c31 = d >> 31
                if use_roll:
                    sub0 = jax.lax.broadcasted_iota(jnp.int32, (W, bB), 0) == 0
                    carry_bits = pltpu.roll(c31, shift=1, axis=0)
                    carry_bits = jnp.where(sub0, zero, carry_bits)
                else:  # interpret mode: plain-JAX equivalent of the sublane roll
                    carry_bits = jnp.concatenate(
                        [jnp.zeros((1, bB), jnp.uint32), c31[:-1, :]], axis=0
                    )
                shifted = ((d << 1) | carry_bits) & shift_in
            else:
                # carry-free packing: no branch straddles a word, so the
                # shifted-out bit 31 could only land on a branch-start or
                # padding bit, both outside shift_in — drop the whole carry
                shifted = (d << 1) & shift_in
            if k == 0:
                inject = jnp.where(t == 0, inj_always | inj_start, inj_always)
            else:
                inject = inj_always
            d = ((shifted | inject) | (d & selfloop)) & bmask
            acc = acc | (d & acc_any)
            l = t * cols + k
            acc = acc | jnp.where(last_col == l, d & acc_end, zero)
        d_ref[:] = d
        out_ref[:] = acc


def device_matcher(prep: PallasRules, B: int, L_p: int,
                   block_b: int = _DEFAULT_BLOCK_B, interpret: bool = False,
                   pack: bool = False, cols: int = _COLS_PER_STEP):
    """Build the traceable device step: fn(cls_t [L_p, B], lens [B]) →
    matched [B, n_rules] uint8 (or [B, ceil(n_rules/8)] bit-packed when
    `pack` — 8× less device→host traffic for the runner's bitmap pull).
    Composable inside an outer jit (the bench harness chains it; the
    runner jits it standalone). `cols` = byte columns per grid step:
    wider tiles amortize the per-step Mosaic overhead (measured ~10-15µs
    per step on v5e) at the cost of L_p padding up to a `cols` multiple."""
    call = _build_raw_call(
        B, L_p, prep.n_classes_p, prep.n_shards, prep.wps_p, block_b,
        interpret, cols, carry=not prep.carry_free,
    )
    acc_word, acc_mask = prep.acc_word, prep.acc_mask
    branch_rule = prep.branch_rule
    always_match, empty_only = prep.always_match, prep.empty_only
    n_rules = prep.n_rules
    btab_t, masks_t = prep.btab_t, prep.masks_t

    def fn(cls_t, lens):
        # per-line-block byte-tile counts for the kernel's tile skip
        maxtile = jnp.asarray(
            -(-lens.reshape(B // block_b, block_b).max(axis=1) // cols),
            dtype=jnp.int32,
        )
        acc_t = call(maxtile, cls_t, lens[None, :], btab_t, masks_t)  # [ns*wps_p, B]
        acc = acc_t.T
        matched = jnp.zeros((B, n_rules), dtype=jnp.uint8)
        if acc_word.shape[0] > 0:
            sel = (acc[:, acc_word] & acc_mask) != 0
            matched = matched.at[:, branch_rule].max(sel.astype(jnp.uint8))
        matched = matched | always_match.astype(jnp.uint8)[None, :]
        empty = (lens == 0)[:, None]
        matched = matched | (
            empty_only.astype(jnp.uint8)[None, :] & empty.astype(jnp.uint8)
        )
        if pack:
            return jnp.packbits(matched.astype(jnp.bool_), axis=1)
        return matched

    return fn


@functools.lru_cache(maxsize=64)
def _build_raw_call(
    B: int, L_p: int, C: int, ns: int, wps_p: int, block_b: int,
    interpret: bool, cols: int = _COLS_PER_STEP,
    force_roll: "bool | None" = None,
    carry: bool = True,
):
    """`carry=False` is only sound against tensors packed word-aligned
    (prepare() reported carry_free) — pass prep's own flag. The safe
    default (carry on) is merely redundant work against aligned tensors,
    never wrong."""
    if B % block_b or L_p % cols:
        # a floor-divided grid would silently skip the tail of the batch
        raise PallasUnsupported(
            f"B={B} must be a multiple of block_b={block_b} and "
            f"L_p={L_p} a multiple of cols={cols} (pad first, "
            "as match_batch_pallas does)"
        )
    grid = (B // block_b, ns, L_p // cols)
    # the pltpu.roll carry is what production (compiled Mosaic) runs; it
    # also works under interpret, which is how CI covers the exact
    # production branch (tests/unit/test_nfa_pallas.py::test_roll_branch) —
    # the concatenate fallback stays for interpreters where roll regresses
    use_roll = (not interpret) if force_roll is None else force_roll
    kern = functools.partial(
        _kernel, C=C, W=wps_p, use_roll=use_roll, cols=cols, carry=carry,
    )
    call = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # maxtile [B // block_b] int32
            grid=grid,
            in_specs=[
                # cls transposed [L_p, B]: one tile of byte rows per step
                pl.BlockSpec(
                    (cols, block_b), lambda i, j, t, mt: (t, i),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, block_b), lambda i, j, t, mt: (0, i),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (4 * wps_p, C), lambda i, j, t, mt: (j, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (wps_p, 8), lambda i, j, t, mt: (j, 0),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (wps_p, block_b), lambda i, j, t, mt: (j, i),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[pltpu.VMEM((wps_p, block_b), jnp.uint32)],
        ),
        out_shape=jax.ShapeDtypeStruct((ns * wps_p, B), jnp.uint32),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * B * L_p * C * 4 * wps_p * ns,
            bytes_accessed=B * L_p * 4 + B * ns * wps_p * 4,
            transcendentals=0,
        ),
    )
    return call


def match_batch_pallas(
    prep: PallasRules,
    cls_ids,
    lens,
    *,
    block_b: int = _DEFAULT_BLOCK_B,
    interpret: bool = False,
    packed: bool = False,
    cols: int = _COLS_PER_STEP,
) -> np.ndarray:
    """[B, L] encoded lines → [B, n_rules] uint8 match bits via the kernel
    (bit-packed along the rule axis when `packed`).

    Pads the batch up to a block multiple and sorts lines by length so the
    kernel's per-block tile skip pays off (the output is returned in the
    caller's original line order); semantics identical to
    nfa_jax.match_batch (differentially tested in tests/unit/test_nfa_pallas.py).
    """
    if not interpret and block_b % _LANE:
        raise PallasUnsupported(f"block_b {block_b} must be a multiple of {_LANE}")
    cls_ids = np.asarray(cls_ids, dtype=np.int32)
    lens = np.asarray(lens, dtype=np.int32)
    B, L = cls_ids.shape
    order = np.argsort(lens, kind="stable")
    Bp = max(block_b, _pad_to(B, block_b))
    # trim the scan to the batch's longest line (columns past every line's
    # end are pad-class and can't change state), rounded to a multiple of
    # 32 so the number of jitted L_p variants stays small
    max_len = int(lens.max()) if B else 0
    round_to = max(32, cols)
    L_p = max(cols, min(_pad_to(L, cols), _pad_to(max_len, round_to)))
    cls_t = np.zeros((L_p, Bp), dtype=np.int32)
    cls_t[: min(L, L_p), :B] = cls_ids[order, : min(L, L_p)].T
    lens_sorted = lens[order]
    if Bp != B:
        lens_sorted = np.pad(lens_sorted, (0, Bp - B))
    run = prep.jitted(Bp, L_p, block_b, interpret, packed, cols)
    out = np.asarray(run(jnp.asarray(cls_t), jnp.asarray(lens_sorted)))[:B]
    unsorted = np.empty_like(out)
    unsorted[order] = out
    return unsorted
