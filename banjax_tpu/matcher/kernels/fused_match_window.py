"""Single-kernel fused match + window commit: one device program per
chunk, one pull, no host decision between the match and the commit.

The classic protocol round-trips the match through the host: the drain
pulls a bitmap (a fixed d2h round trip), rebuilds it dense and pushes it
back for the window apply.  A fused path split into a stateless match
program and a later commit program would still need a HOST decision
between them — pull the flags, check overflow, only then dispatch the
commit.  This module needs neither.  One device program per chunk does

    match (the two-stage Pallas NFA scan, prefilter._match_core)
      → sparse (row, rule) pairs from stage 2's PACKED words, masked by
        host first (prefilter.pairs_from_core), and the always-columns' bits
      → the window events, listed from exactly those: one per pair, one
        per set always-bit, each masked by gather with the per-row live
        mask (staleness/abandon composed as an input), the real-row count
        and the rules active for the line's host — no nonzero, sort or
        sum over rows x rules anywhere
      → window-hit accumulation + threshold-fire against the HBM-resident
        per-slot window state (windows._apply_events, state donated —
        tiles of it stage through VMEM inside the scan kernel below)
      → IN-KERNEL overflow gate: candidate / pair / event overflow (or a
        gated predecessor, see the chain scalar) drops every state write,
        so the donated state passes through bit-identical and the host
        replays the chunk through the existing classic fallback
      → the dense caller-order bitmap, assembled by scatter for that
        replay alone (windows._apply_core compacts ITS events out of it)

and returns only a compact buffer — the [4] flags word ‖ sparse match
pairs ‖ always-rule bits ‖ the fired-event records — plus the
device-resident dense bitmap for the fallback.  The dense intermediate
never crosses the host boundary, there is no inter-program host turn,
and the drain dispatches nothing: resolve is a pure d2h pull of a
buffer whose async copy started at submit.

Ordering: the state commit happens at submit, and submits are already
serialized (one device thread, chunks in admission order, under the
windows lock; the window table's queued evictions and restores ride the
same program, at its head), so device apply order == log order by
construction, with no host turn between a chunk's match and its commit.
The overflow hazard of committing at submit — chunk N
overflows, its classic re-apply would land AFTER an already-dispatched
chunk N+1 — is closed DEVICE-SIDE by the chain scalar: every kernel
takes its predecessor's ok flag and gates its own commit on it, so an
overflow poisons every already-dispatched successor in-device (they
pass state through untouched and replay classically, in order, on the
host).  The chain reseeds once no poisoned chunk is outstanding.

The window-transition recurrence runs as a Pallas kernel (`_scan_kernel`
— the "native tier" obligation of PAPER.md §0): event records staged
through SMEM in tiles, a fori_loop carry over the key-sorted events
calling the SAME `windows._window_step` the XLA lax.scan lowers, so the
two paths cannot drift.  `interpret=True` runs it as plain JAX — the CI
path; tests/unit/test_tpu_compile.py compiles it for a described v5e.
`scan_selftest` proves the active lowering bit-identical to lax.scan at
matcher construction — a failure leaves the matcher on the classic
bitmap protocol (health-registry note).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from banjax_tpu.matcher import sitemask, windows as W
from banjax_tpu.matcher.prefilter import le_bytes


# ---- the Pallas window-scan kernel ----


# events per grid step: 16 arrays x 2 pipeline buffers x 4 B x 2048 =
# 256 KiB of the v5e's 1 MiB SMEM, whatever max_events is
_SCAN_TILE = 2048


def _scan_kernel(b_ref, gh_ref, gs_ref, gn_ref, gv_ref, ts_ref, tn_ref,
                 lim_ref, ivs_ref, ivn_ref, pad_ref,
                 h_out, s_out, n_out, mt_out, ex_out, carry_ref):
    """Sequential fixed-window recurrence over the key-sorted event list.

    All refs are [T] int32 tiles in SMEM: the recurrence reads and writes
    one scalar per event, which Mosaic only allows in scalar memory
    (VMEM refs refuse scalar stores).  It is inherently serial — a window
    restart depends on every earlier event of the segment — so the loop
    carries the (hits, start_s, start_ns) triple exactly like the
    lax.scan, handed from tile to tile through `carry_ref`; the body is
    windows._window_step itself, shared with the XLA path."""
    T = b_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        for i in range(3):
            carry_ref[i] = jnp.int32(0)

    def body(k, carry):
        xs = (
            b_ref[k] != 0,
            gh_ref[k], gs_ref[k], gn_ref[k],
            gv_ref[k] != 0,
            ts_ref[k], tn_ref[k],
            lim_ref[k], ivs_ref[k], ivn_ref[k],
            pad_ref[k] != 0,
        )
        carry, (h2, s1, n1, mtype, exceeded) = W._window_step(carry, xs)
        h_out[k] = h2
        s_out[k] = s1
        n_out[k] = n1
        mt_out[k] = mtype
        ex_out[k] = exceeded.astype(jnp.int32)
        return carry

    carry = jax.lax.fori_loop(
        0, T, body, (carry_ref[0], carry_ref[1], carry_ref[2])
    )
    for i in range(3):
        carry_ref[i] = carry[i]


def _scan_tiling(E: int) -> tuple:
    """(padded event count, tile) for E events: one lane-aligned tile up
    to _SCAN_TILE, whole tiles beyond."""
    if E <= _SCAN_TILE:
        Ep = -(-E // 128) * 128
        return Ep, Ep
    return -(-E // _SCAN_TILE) * _SCAN_TILE, _SCAN_TILE


@functools.lru_cache(maxsize=16)
def _scan_call(Ep: int, T: int, interpret: bool):
    shape = jax.ShapeDtypeStruct((Ep,), jnp.int32)
    tile = pl.BlockSpec((T,), lambda i: (i,), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _scan_kernel,
        out_shape=(shape,) * 5,
        grid=(Ep // T,),
        in_specs=[tile] * 11,
        out_specs=(tile,) * 5,
        scratch_shapes=[pltpu.SMEM((3,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name="window_scan",
        interpret=interpret,
    )


def window_scan(interpret: bool):
    """A `scan_fn` for windows._apply_events: same contract as the
    lax.scan over _window_step (the recurrence always starts from the
    zero carry, so `init` is ignored), lowered through the Pallas
    kernel above.  Events are padded to whole tiles with inert pad
    events (is_pad leaves the carry untouched)."""

    def scan(init, xs):
        del init  # the recurrence starts from the zero carry
        E = int(xs[0].shape[0])
        Ep, T = _scan_tiling(E)
        ins = [jnp.asarray(x).astype(jnp.int32) for x in xs]
        if Ep != E:
            ins = [jnp.pad(x, (0, Ep - E)) for x in ins]
            ins[-1] = ins[-1].at[E:].set(1)
        h, s, n, mt, ex = _scan_call(Ep, T, bool(interpret))(*ins)
        return h[:E], s[:E], n[:E], mt[:E], ex[:E] != 0

    return scan


def scan_selftest(interpret: bool, E: int = 64) -> None:
    """Prove the active scan lowering (compiled Mosaic on TPU, interpret
    elsewhere) reproduces the lax.scan recurrence bit-for-bit on a
    deterministic stimulus covering boundaries, pads, restarts and
    exceeds.  Raises on a lowering failure or any value mismatch — the
    matcher then stays on the classic protocol (graceful downgrade)."""
    rng = np.random.default_rng(7)
    pad = np.zeros(E, dtype=bool)
    pad[-max(1, E // 8):] = True
    xs = (
        jnp.asarray(rng.integers(0, 2, E).astype(bool)),     # boundary
        jnp.asarray(rng.integers(0, 6, E).astype(np.int32)),  # g_hits
        jnp.asarray(rng.integers(0, 40, E).astype(np.int32)),  # g_ss
        jnp.asarray(rng.integers(0, 1000, E).astype(np.int32)),  # g_sns
        jnp.asarray(rng.integers(0, 2, E).astype(bool)),     # g_valid
        jnp.asarray(rng.integers(0, 60, E).astype(np.int32)),  # e_ts_s
        jnp.asarray(rng.integers(0, 1000, E).astype(np.int32)),  # e_ts_ns
        jnp.asarray(rng.integers(0, 4, E).astype(np.int32)),  # limit
        jnp.asarray(rng.integers(1, 20, E).astype(np.int32)),  # iv_s
        jnp.asarray(rng.integers(0, 1000, E).astype(np.int32)),  # iv_ns
        jnp.asarray(pad),                                     # pad
    )
    init = (jnp.int32(0), jnp.int32(0), jnp.int32(0))
    _, want = jax.lax.scan(W._window_step, init, xs)
    got = window_scan(interpret)(init, xs)
    for name, w, g in zip(
        ("hits", "start_s", "start_ns", "match_type", "exceeded"), want, got
    ):
        if not np.array_equal(np.asarray(w), np.asarray(g)):
            raise RuntimeError(
                f"pallas window-scan selftest mismatch on {name!r}"
            )


# ---- the single fused program ----


def build_single_program(
    pf, windows, active_table, n_rules: int, Bp: int, L_p: int, *,
    f_idx, a_idx, aw, ae, scan_fn, skip_table=None, KL: tuple = (),
    sketch=None,
):
    """One jitted device program: match core + event list from the pairs
    and always-columns + overflow/chain gate + window commit + compact
    output + the dense bitmap for the replay.

    Returns (fn, K, P, E) where
      fn(state, chain_ok, combined, n_real, host_idx, slots, ts_s,
         ts_ns, live, ev_slots, restore_rows)
         -> (new_state, chain_ok_out, buf, bits_dev)
    with `state` donated (the HBM-resident window arrays mutate in
    place) and `buf` the single uint8 pull.  `ev_slots` s32[Bp] and
    `restore_rows` s32[5, windows._restore_room(Bp)] are the window table's
    queued maintenance (DeviceWindows._run_maintenance_locked hands them
    over, all padding when nothing is queued): the program runs
    windows._evict and then windows._restore on them at its head (the
    restore rows past the first chunk under a `cond`: only where a key
    lies there), outside the overflow / chain gate — a chunk that commits nothing still
    evicts and restores, as when the two were dispatches of their own in
    front of it.  With `KL` (longrows.operands'
    pairs) `fn` takes one more argument for each, last: the chunk's long
    rows as longrows.assemble lays them out (prefilter._match_core scans
    and merges them), and nothing else of the program or its output
    changes.  With `sketch` (obs/sketch.py TrafficSketch) the program
    carries the chunk's traffic-sketch fold as well:
      fn(state, sketch_state, chain_ok, combined, n_real, host_idx, slots,
         ts_s, ts_ns, live, ev_slots, restore_rows, row_hashes,
         *long operands)
         -> (new_state, chain_ok_out, buf, bits_dev, new_sketch_state)
    with `sketch_state` = (cm, hll) donated like the window state and
    `row_hashes` u32[Bp], a row's address hash.  The fold is
    unconditional — outside the overflow / chain gate and the live mask:
    every row below n_real counts once, at its chunk's dispatch.  The
    buffer's layout:

      flags[4 × i32: ok, n_cand, n_pairs, n_events]
      ‖ (row, rule) pairs [4P]
      ‖ always-rule bits [Bp * na8]            (when the plan has any)
      ‖ ev line/rule/hits/start_s/start_ns [5 × 4E]
      ‖ ev match_type/exceeded/seen_ip [3 × E]
      ‖ long rows stage 2 scanned, their bytes [2 × 4]   (KL, filters)
      ‖ hits per factor bucket [4F]            (when the plan filters)

    The head (flags ‖ pairs ‖ always bits) and the event tail are laid
    out back to back: fused_windows._decode_head reads the first and
    FusedWindowsPipeline.collect the second."""
    # the event ceiling follows the rows and the ruleset (always-columns
    # can fire on every row), not a constant of the window table
    block, K, P, max_events = pf.program_capacities(Bp)
    core = pf._match_core(Bp, L_p, K, block, KL)
    # stage 2's rows: the K candidate slots, then every long operand's
    Kx = K + sum(rows for _, rows in KL) if K else 0
    plan = pf.plan
    n_always = plan.n_always
    n_filt = pf._n_filt
    R8 = pf._nf8 * 8
    limits, iv_s, iv_ns = windows._limits, windows._iv_s, windows._iv_ns
    # rules of single sites: each candidate's packed row is ANDed with its
    # host's packed active row BEFORE the pairs are counted and listed
    # (matcher/sitemask.py); None for a ruleset of global rules, whose
    # program is built without the gather
    site_mask = sitemask.packed_rows(active_table, skip_table, f_idx)
    if site_mask is not None:
        site_mask = jnp.asarray(site_mask)                  # [hosts+1, nf8]
    active_table = jnp.asarray(active_table)

    def match_and_commit(state, chain_ok, combined, n_real, host_idx, slots,
                         ts_s, ts_ns, live, ev_slots, restore_rows,
                         *long_ops):
        with jax.named_scope("window-maintenance"):
            # the restore rows fill from the front: past the first chunk
            # the scatters run only where a key lies there, so a chunk
            # that restores a few addresses pays for one chunk's padding
            # and not for its whole room
            kr = W._RESTORE_CHUNK
            state = W._restore(W._evict(state, ev_slots), restore_rows[:, :kr])
            if restore_rows.shape[1] > kr:
                rest = restore_rows[:, kr:]
                state = jax.lax.cond(
                    (rest[0] < state.slot_gen.shape[0]).any(),
                    lambda st: W._restore(st, rest), lambda st: st, state,
                )
        c = core(combined, *long_ops)
        keep = None
        if site_mask is not None:
            with jax.named_scope("site-mask"):
                # an unused candidate slot names row Bp and holds no bits
                keep = site_mask[
                    host_idx[jnp.minimum(c["idx_caller_k"], Bp - 1)]
                ]
        pairs, n_pairs, pair_bits = pf.pairs_from_core(c, Kx, P, keep)
        # dense caller-order bitmap, assembled on device
        bits = jnp.zeros((Bp, n_rules), dtype=jnp.uint8)
        if n_filt:
            m2 = pair_bits[:, :n_filt].astype(jnp.uint8)      # [K, n_filt]
            filt = jnp.zeros((Bp + 1, n_filt), dtype=jnp.uint8)
            filt = filt.at[c["idx_caller_k"]].set(m2)[:Bp]    # row Bp = dump
            bits = bits.at[:, f_idx].set(filt)
        ab = None
        if n_always:
            ab = c["ab_caller"] | aw[None, :]
            empty = (c["lens_raw"] == 0).astype(jnp.uint8)[:, None]
            ab = ab | (ae[None, :] * empty)
            bits = bits.at[:, a_idx].set(ab)
        real = jax.lax.iota(jnp.int32, Bp) < n_real
        bits = bits * real[:, None].astype(jnp.uint8)
        # the window events, straight from what the match left: one per
        # (row, rule) pair and one per set always-column bit — never a
        # reduction over the dense rows x rules bitmap above, which leaves
        # the program untouched as the classic replay's input
        ev_line, ev_rule, ev_on = [], [], []
        if n_always:
            ev_line.append(jnp.repeat(jax.lax.iota(jnp.int32, Bp), n_always))
            ev_rule.append(jnp.tile(a_idx, Bp))
            ev_on.append((ab != 0).reshape(-1))
        if n_filt:
            on = pairs >= 0
            row = jnp.where(on, pairs // R8, 0)
            ev_line.append(row)
            ev_rule.append(f_idx[jnp.where(on, pairs - row * R8, 0)])
            ev_on.append(on)
        line = jnp.concatenate(ev_line)
        rule = jnp.concatenate(ev_rule)
        # what the dense path multiplies into the bitmap, read per event:
        # pad rows, the live mask (staleness/abandon composed INTO the
        # commit: a row the caller dropped contributes no event and no
        # state write) and the rules inactive for the line's host
        on = (
            jnp.concatenate(ev_on) & (line < n_real) & (live[line] != 0)
            & active_table[host_idx[line], rule]
        )
        n_events = on.sum(dtype=jnp.int32)
        if on.shape[0] > max_events:
            # only under _MAX_EVENT_CAPACITY (rows x always-columns alone
            # pass it): keep the first max_events that fire
            (keep,) = jnp.nonzero(on, size=max_events, fill_value=0)
            line, rule = line[keep], rule[keep]
            on = jax.lax.iota(jnp.int32, max_events) < n_events
        else:
            short = max_events - on.shape[0]
            line, rule, on = (jnp.pad(x, (0, short)) for x in (line, rule, on))
        self_ok = (
            (c["n_cand"] <= K) & (n_pairs <= P) & (n_events <= max_events)
        )
        # chain gate: a gated predecessor (overflow anywhere earlier in
        # the submit chain) gates THIS commit too, keeping device apply
        # order == log order across the host's classic replays
        ok = self_ok & (chain_ok != 0)
        new_state, ev = W._apply_events(
            state, line, rule, on, slots, ts_s, ts_ns,
            limits, iv_s, iv_ns, n_rules=n_rules, gate=ok, scan_fn=scan_fn,
        )
        flags = jnp.stack(
            [ok.astype(jnp.int32), c["n_cand"], n_pairs, n_events]
        )
        parts = [le_bytes(flags), le_bytes(pairs)]
        if n_always:
            parts.append(
                jnp.packbits(ab.astype(jnp.bool_), axis=1).reshape(-1)
            )
        for key in ("line", "rule", "hits", "start_s", "start_ns"):
            parts.append(le_bytes(ev[key]))
        parts.append(ev["match_type"].astype(jnp.uint8))
        parts.append(ev["exceeded"].astype(jnp.uint8))
        parts.append(ev["seen_ip"].astype(jnp.uint8))
        if c.get("long_cand") is not None:
            # behind the event tail, in front of the bucket hits: the
            # long rows stage 2 scanned and their bytes
            parts.append(le_bytes(c["long_cand"]))
        if c["bucket_hits"] is not None:
            # last, where prefilter.bucket_hits_of reads them whatever
            # the head and the event tail before them hold
            parts.append(le_bytes(c["bucket_hits"]))
        return new_state, ok.astype(jnp.int32), jnp.concatenate(parts), bits

    if sketch is None:
        single = jax.jit(match_and_commit, donate_argnums=(0,))
        return single, K, P, max_events

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def single(state, sketch_state, chain_ok, combined, n_real, host_idx,
               slots, ts_s, ts_ns, live, ev_slots, restore_rows, row_hashes,
               *long_ops):
        out = match_and_commit(state, chain_ok, combined, n_real, host_idx,
                               slots, ts_s, ts_ns, live, ev_slots,
                               restore_rows, *long_ops)
        with jax.named_scope("sketch-fold"):
            return out + (sketch.fold(*sketch_state, row_hashes, n_real),)

    return single, K, P, max_events
