"""Fused matcher + device-windows pipeline, split into two device programs
so chunks can OVERLAP without ever reordering window updates.

Why fused at all: with device windows on, the naive path round-trips the
match bitmap through the host — the matcher pulls its sparse result down
(a fixed d2h round trip per pull), the runner rebuilds a dense
[B, n_rules] bitmap, and apply_bitmap pushes those ~16 MB back up for the
window scan. Here the dense caller-order bitmap never exists on the host.

Why two programs (PERF.md "path to 5M" 3c): a single fused program forces
strict chunk serialization — if chunk N overflows (its state writes gated
off), its classic re-apply would land on the device stream AFTER an
already-submitted chunk N+1, reordering window updates. Splitting fixes it:

  program A — MATCH (stateless): two-stage match (prefilter._match_core),
    dense caller-order bitmap assembly, and ALL overflow flags — candidate
    count, match-pair count, and the window-event count (it takes
    host_idx + active_table precisely so the event count is known before
    any state is touched). Outputs: one sparse host buffer (flags ‖
    (row, rule) match pairs ‖ always-rule bits) and the device-resident
    bitmap. A dispatches freely, any number of chunks ahead.

  program B — APPLY (window state donated): the window segmented scan
    (windows._apply_core) over A's bitmap. B for chunk i is dispatched
    only after chunk i's A-flags are known ok AND every earlier chunk's
    apply (B or classic fallback) has completed its dispatch — so
    device-stream order equals log order, always. Overflowing chunks never
    dispatch B: the caller drains all earlier chunks, then replays through
    the classic splitting path (state untouched, output identical).

Both pulls (A's sparse buffer, B's event buffer) are async and overlap
later chunks' compute, hiding the fixed d2h latency.

Ordering machinery: submit() assigns a sequence number; resolve() and
collect() each gate on it (resolve order = B dispatch order = device apply
order; collect order = host-shadow write order). The shadow must absorb
batches in device-apply order or an eviction could restore stale counters.

Event order parity: bits are scattered into CALLER row order before the
window apply, so the event compaction's row-major (line, rule) order — the
reference's per-site-then-global processing order — is preserved exactly
as in the classic path.

Single-kernel mode (`pallas_single_kernel`, kernels/fused_match_window.py)
collapses A+B into ONE program dispatched at submit: the window commit is
gated IN-KERNEL on the overflow flags and on a device-side chain scalar
(an overflow poisons every already-dispatched successor, which then
replays classically in order), so the host decision between the programs
— and with it the ~65 ms resolve pull — disappears.  submit() returns an
already-final chunk; resolve() is a pure pull of the one combined buffer;
staleness/abandon compose as a per-row live-mask INPUT to submit.  The
two-program protocol below stays intact as the differential oracle and
the fallback when the Pallas window-scan kernel can't lower.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from banjax_tpu.matcher import windows as W
from banjax_tpu.obs import trace
from banjax_tpu.matcher.prefilter import FusedPrefilter
from banjax_tpu.matcher.windows import DeviceWindows, EventBatch

log = logging.getLogger(__name__)

_SHIFTS = (0, 8, 16, 24)


@dataclasses.dataclass
class _Pend:
    """One chunk in flight. States: submitted → resolved → done, or
    submitted → overflow → (caller fallback) → done."""

    seq: int
    sparse_buf: object     # program A's buffer (async pull in flight);
    #                        single-kernel mode: THE one combined buffer
    bits_dev: object       # [Bp, n_rules] uint8 device-resident
    slots: np.ndarray      # caller-order, pins held
    ts_s: np.ndarray       # padded to Bp
    ts_ns: np.ndarray      # padded to Bp
    host_idx: np.ndarray   # padded to Bp
    B: int                 # real rows
    Bp: int
    K: int
    P: int
    E: int                 # window-event capacity of the chunk's program
    state: str = "submitted"
    flags: Optional[np.ndarray] = None     # [4] after resolve
    events_buf: object = None              # program B's buffer, or (single-
    #                                        kernel) the decoded host buffer
    events_off: int = 0                    # event-record offset into it
    # decoded at resolve (from the A pull)
    matched_pairs: Optional[np.ndarray] = None
    always_bits: Optional[np.ndarray] = None
    # transfer accounting (obs/stats.py note_xfer): what this chunk moved
    # across the host boundary — the fusion-win witness is the ABSENCE of
    # the dense [B, n_rules] bitmap from h2d_bytes
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    # state-aware settlement: each order turn and the slot pins are
    # released EXACTLY once no matter which combination of resolve/
    # collect/fallback_done/abandon settles the chunk (a submit-failure
    # abandon racing a teardown abort used to mark a turn dead twice,
    # which could advance a counter past a live chunk's turn)
    pins_released: bool = False
    turns_freed: dict = dataclasses.field(
        default_factory=lambda: {"_resolve_seq": False, "_collect_seq": False}
    )


@dataclasses.dataclass
class FusedWindowsResult:
    """Outcome of one collected chunk."""

    events: EventBatch
    matched_pairs: Optional[np.ndarray]   # int32 caller_row * R8 + bit col
    always_bits: Optional[np.ndarray]     # [B, na8] packed always-rule bits


class PipelineOverflow(RuntimeError):
    """resolve() found an overflow: the caller must finish this chunk via
    the classic fallback (then call fallback_done)."""

    def __init__(self, candidate_overflow: bool):
        super().__init__(
            "candidate capacity exceeded" if candidate_overflow
            else "match-pair/event capacity exceeded"
        )
        # True: stage 2 never saw the excess lines — even the dense bitmap
        # is incomplete and must be recomputed single-stage
        self.candidate_overflow = candidate_overflow


class FusedWindowsPipeline:
    """Built by TpuMatcher when the fused prefilter and device windows are
    both active and every rule is device-decidable.

    Contract: submit in chunk order; resolve and collect each in that same
    order (they gate on it). Pins are owned by the pipeline from submit()
    until collect() completes — except after PipelineOverflow, where the
    caller's fallback apply (which releases them) takes over, followed by
    fallback_done() to release the order turns."""

    def __init__(self, prefilter: FusedPrefilter, windows: DeviceWindows,
                 active_table, n_rules: int, single_kernel: bool = False,
                 scan_interpret: bool = True, traffic_sketch=None):
        self.pf = prefilter
        self.windows = windows
        self.active_table = jnp.asarray(active_table)
        self.n_rules = n_rules
        # traffic introspection (obs/sketch.py): every submitted chunk
        # folds into the device-resident count-min/HLL/rule-pressure
        # sketches as one more stateless array op — telemetry only, no
        # interaction with window state or results
        self._traffic_sketch = traffic_sketch
        self._match_fns = {}
        self._apply_fns = {}
        # single-kernel mode (kernels/fused_match_window.py): submit
        # dispatches ONE program doing match + window commit (state
        # donated, overflow/chain gated in-kernel) and the chunk is final
        # on return; resolve/collect become pure decodes of the one
        # async-pulled buffer.  False = the two-program A/B protocol,
        # which stays intact as the differential oracle and the fallback
        # when the Pallas window-scan kernel can't lower.
        self.single_kernel = bool(single_kernel)
        self._scan_interpret = bool(scan_interpret)
        # device-side ok chain: each kernel's commit gates on its
        # predecessor's ok scalar, so an overflow poisons every already-
        # dispatched successor WITHOUT a host round-trip; None = seed the
        # next submit with a fresh ok (no poisoned chunk outstanding)
        self._chain_ok = None
        self.sk_chunks = 0          # single-kernel chunks committed
        self.sk_fallbacks = 0       # routed to the classic fallback
        self.sk_d2h_bytes_total = 0  # the one-pull d2h witness
        # fused dispatches that committed nothing, by what overflowed:
        # the chunk's own candidates / (row, rule) pairs / window events,
        # or `chain` — gated by an overflowing predecessor's chain scalar
        self.overflow_causes = {
            "candidates": 0, "pairs": 0, "events": 0, "chain": 0,
        }
        plan = prefilter.plan
        self._f_idx = jnp.asarray(plan.f_idx, dtype=jnp.int32)
        self._a_idx = jnp.asarray(plan.a_idx, dtype=jnp.int32)
        na = plan.n_always
        self._aw = jnp.asarray(
            np.asarray(plan.stage1.always_match[:na], dtype=np.uint8)
        )
        self._ae = jnp.asarray(
            np.asarray(plan.stage1.empty_only[:na], dtype=np.uint8)
        )
        self.fused_batches = 0
        self.fallback_batches = 0
        self._cv = threading.Condition()
        self._next_seq = 0      # assigned at submit
        self._resolve_seq = 0   # B-dispatch order
        self._collect_seq = 0   # shadow-write order
        # turns of chunks that died before taking them (resolve failure,
        # abandon): swept lazily when the counter reaches them — advancing
        # out of turn would steal an earlier live chunk's turn
        self._dead = {"_resolve_seq": set(), "_collect_seq": set()}

    # ---- program A: stateless match + flags ----

    def _match_prog(self, Bp: int, L_p: int):
        key = (Bp, L_p)
        hit = self._match_fns.get(key)
        if hit is not None:
            return hit
        pf = self.pf
        plan = pf.plan
        block, K, P, E = pf.program_capacities(Bp)
        core = pf._match_core(Bp, L_p, K, block)
        n_rules, n_filt = self.n_rules, pf._n_filt
        n_always = plan.n_always
        f_idx, a_idx = self._f_idx, self._a_idx
        aw, ae = self._aw, self._ae
        active_table = self.active_table
        shifts = jnp.asarray(_SHIFTS, dtype=jnp.int32)

        @jax.jit
        def match(combined, n_real, host_idx):
            c = core(combined)
            # sparse (row, rule) pair output — the shared encoding
            # (prefilter.pairs_from_core): one int32 per set stage-2 bit
            # instead of a packed row bitmap per matched line (~30x less
            # d2h volume). pair_bits doubles as the dense
            # per-candidate form for the bitmap assembly below.
            pairs, n_pairs, pair_bits = pf.pairs_from_core(c, K, P)
            # dense caller-order bitmap, assembled on device
            bits = jnp.zeros((Bp, n_rules), dtype=jnp.uint8)
            if n_filt:
                m2 = pair_bits[:, :n_filt].astype(jnp.uint8)     # [K, n_filt]
                filt = jnp.zeros((Bp + 1, n_filt), dtype=jnp.uint8)
                filt = filt.at[c["idx_caller_k"]].set(m2)[:Bp]   # row Bp = dump
                bits = bits.at[:, f_idx].set(filt)
            ab = None
            if n_always:
                ab = c["ab_caller"] | aw[None, :]
                empty = (c["lens_raw"] == 0).astype(jnp.uint8)[:, None]
                ab = ab | (ae[None, :] * empty)
                bits = bits.at[:, a_idx].set(ab)
            # padding rows (row >= n_real) can still carry bits — e.g. an
            # always_match rule's column is all-ones — and MUST NOT reach
            # the window apply: their pad slot id belongs to a real IP
            real = jax.lax.iota(jnp.int32, Bp) < n_real
            bits = bits * real[:, None].astype(jnp.uint8)
            # the window-event count, computed HERE so every overflow
            # condition is known before any state is touched
            fire = (bits != 0) & active_table[host_idx]
            n_events = fire.sum(dtype=jnp.int32)
            ok = (
                (c["n_cand"] <= K) & (n_pairs <= P)
                & (n_events <= E)
            )
            flags = jnp.stack([
                ok.astype(jnp.int32), c["n_cand"], n_pairs, n_events,
            ])
            parts = [
                ((flags[:, None] >> shifts[None, :]) & 0xFF)
                .astype(jnp.uint8).reshape(-1),
                ((pairs[:, None] >> shifts[None, :]) & 0xFF)
                .astype(jnp.uint8).reshape(-1),
            ]
            if n_always:
                # sparse rows cover only the filterable rules; replay
                # bookkeeping needs the completed always-rule bits too
                parts.append(
                    jnp.packbits(ab.astype(jnp.bool_), axis=1).reshape(-1)
                )
            return jnp.concatenate(parts), bits

        self._match_fns[key] = (match, K, P, E)
        return match, K, P, E

    # ---- single-kernel program: match + window commit in ONE dispatch ----

    def _single_prog(self, Bp: int, L_p: int):
        """The fused match+window program (single-kernel mode), cached in
        the same per-(Bp, L_p) table as the two-program match — the modes
        are exclusive per pipeline, so the cache never mixes kinds."""
        key = (Bp, L_p)
        hit = self._match_fns.get(key)
        if hit is not None:
            return hit
        from banjax_tpu.matcher.kernels import fused_match_window as fmw

        hit = fmw.build_single_program(
            self.pf, self.windows, self.active_table, self.n_rules,
            Bp, L_p, f_idx=self._f_idx, a_idx=self._a_idx,
            aw=self._aw, ae=self._ae,
            scan_fn=fmw.window_scan(self._scan_interpret),
        )
        self._match_fns[key] = hit
        return hit

    def _submit_single(self, combined, Bp: int, L_p: int, B: int,
                       slots_p, ts_s_p, ts_ns_p, host_idx_p,
                       live: Optional[np.ndarray]) -> _Pend:
        """Dispatch the single fused program for one chunk: the window
        state commit happens HERE (gated in-kernel on overflow and on the
        chain scalar), so the returned chunk is already final — its
        resolve is a pure pull.  Runs under the windows lock: maintenance
        (evictions/restores) drains first, exactly as the two-program
        resolve did, and the state-chain order == seq order because both
        are taken inside the same critical section."""
        fn, K, P, E = self._single_prog(Bp, L_p)
        live_p = np.zeros(Bp, dtype=np.uint8)
        live_p[:B] = 1 if live is None else np.asarray(live, dtype=np.uint8)
        wnd = self.windows
        with wnd._lock:
            with self._cv:
                seq = self._next_seq
                # quiescent chain reseed: every submitted chunk resolved
                # ⟹ every poisoned chunk's classic fallback has applied,
                # so a fresh ok seed cannot reorder window updates
                if seq == self._resolve_seq:
                    self._chain_ok = None
                self._next_seq += 1
                chain = self._chain_ok
            wnd._run_maintenance_locked()
            new_state, chain_out, buf, bits_dev = fn(
                wnd._state,
                chain if chain is not None else jnp.int32(1),
                jnp.asarray(combined), jnp.int32(B),
                jnp.asarray(host_idx_p), jnp.asarray(slots_p),
                jnp.asarray(ts_s_p), jnp.asarray(ts_ns_p),
                jnp.asarray(live_p),
            )
            wnd._state = new_state
            with self._cv:
                self._chain_ok = chain_out
        try:
            buf.copy_to_host_async()
        except AttributeError:
            pass
        return _Pend(
            seq=seq, sparse_buf=buf, bits_dev=bits_dev,
            slots=slots_p,  # caller overwrites with the unpadded view
            ts_s=ts_s_p, ts_ns=ts_ns_p, host_idx=host_idx_p,
            B=B, Bp=Bp, K=K, P=P, E=E,
            # the whole h2d for the chunk: encoded classes + per-row
            # window metadata + the live mask + the chain scalar — still
            # no dense [B, n_rules] bitmap
            h2d_bytes=combined.nbytes + 4 * 3 * Bp + Bp + 4,
        )

    # ---- program B: window apply on a device-resident bitmap ----

    def _apply_prog(self, Bp: int, max_events: int):
        hit = self._apply_fns.get(Bp)
        if hit is not None:
            return hit
        wnd = self.windows
        n_rules = self.n_rules
        limits, iv_s, iv_ns = wnd._limits, wnd._iv_s, wnd._iv_ns
        active_table = self.active_table
        shifts = jnp.asarray(_SHIFTS, dtype=jnp.int32)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def apply(state, bits, slots, ts_s, ts_ns, host_idx, live):
            # `live` gates rows that aged past the staleness cutoff while
            # queued in the streaming pipeline: the deferred commit drops
            # them HERE (a handful of bytes h2d) instead of re-uploading a
            # row-filtered dense bitmap
            bits = bits * live[:, None]
            new_state, ev = W._apply_core(
                state, bits, active_table, host_idx, slots, ts_s, ts_ns,
                limits, iv_s, iv_ns,
                n_rules=n_rules, max_events=max_events,
            )
            parts = [
                ((ev["line"][:, None] >> shifts[None, :]) & 0xFF)
                .astype(jnp.uint8).reshape(-1),
                ((ev["rule"][:, None] >> shifts[None, :]) & 0xFF)
                .astype(jnp.uint8).reshape(-1),
                ((ev["hits"][:, None] >> shifts[None, :]) & 0xFF)
                .astype(jnp.uint8).reshape(-1),
                ((ev["start_s"][:, None] >> shifts[None, :]) & 0xFF)
                .astype(jnp.uint8).reshape(-1),
                ((ev["start_ns"][:, None] >> shifts[None, :]) & 0xFF)
                .astype(jnp.uint8).reshape(-1),
                ev["match_type"].astype(jnp.uint8),
                ev["exceeded"].astype(jnp.uint8),
                ev["seen_ip"].astype(jnp.uint8),
            ]
            return new_state, jnp.concatenate(parts)

        self._apply_fns[Bp] = apply
        return apply

    # ---- host API (submit → resolve → collect, each in chunk order) ----

    def submit(
        self, cls_ids: np.ndarray, lens: np.ndarray, slots: np.ndarray,
        ts_s: np.ndarray, ts_ns: np.ndarray, host_idx: np.ndarray,
        live: Optional[np.ndarray] = None,
    ) -> _Pend:
        """Dispatch program A for one chunk (slot pins held by the caller,
        ownership passes to the pipeline). Any number of chunks may be
        submitted ahead of their resolves.

        Single-kernel mode: the ONE fused program (match + window commit,
        overflow/chain gated in-kernel) dispatches here instead and the
        chunk returns already final; `live` (bool [B], default all-true)
        is the commit mask — the caller's staleness/abandon drop composed
        as a kernel input (the two-program path takes it at resolve)."""
        pf = self.pf
        cls_ids = np.asarray(cls_ids, dtype=np.int32)
        lens = np.asarray(lens, dtype=np.int32)
        B = cls_ids.shape[0]
        combined, Bp, L_p = pf._assemble(cls_ids, lens, self._match_fns)

        def pad(a, fill=0):
            a = np.asarray(a)
            if Bp == len(a):
                return a
            return np.concatenate(
                [a, np.full(Bp - len(a), fill, dtype=a.dtype)]
            )

        host_idx_p = pad(host_idx).astype(np.int32)
        if self.single_kernel:
            p = self._submit_single(
                combined, Bp, L_p, B,
                pad(np.asarray(slots, dtype=np.int32)),
                pad(ts_s).astype(np.int32), pad(ts_ns).astype(np.int32),
                host_idx_p, live,
            )
            p.slots = np.asarray(slots)
            self._sketch_update(p)
            return p
        match, K, P, E = self._match_prog(Bp, L_p)
        sparse_buf, bits_dev = match(
            jnp.asarray(combined), jnp.int32(B), jnp.asarray(host_idx_p)
        )
        try:
            sparse_buf.copy_to_host_async()
        except AttributeError:
            pass
        with self._cv:
            seq = self._next_seq
            self._next_seq += 1
        p = _Pend(
            seq=seq, sparse_buf=sparse_buf, bits_dev=bits_dev,
            slots=np.asarray(slots),
            ts_s=pad(ts_s).astype(np.int32),
            ts_ns=pad(ts_ns).astype(np.int32),
            host_idx=host_idx_p, B=B, Bp=Bp, K=K, P=P, E=E,
            # the whole host→device traffic for this chunk: the encoded
            # class array + the per-row window metadata — crucially NOT a
            # dense [B, n_rules] bitmap
            h2d_bytes=combined.nbytes + 4 * 3 * Bp,
        )
        self._sketch_update(p)
        return p

    def _sketch_update(self, p: _Pend) -> None:
        """Fold one submitted chunk's rows into the count-min/HLL
        sketches (keyed on the slot ids already bound for the device).
        Unconditional at submit — an overflowed chunk's classic replay
        does NOT re-fold, so each line counts exactly once on this
        path."""
        if self._traffic_sketch is None:
            return
        try:
            self._traffic_sketch.update(p.slots, p.B)
        except Exception:  # noqa: BLE001 — telemetry must never cost a chunk
            log.exception("traffic sketch update failed")

    def _wait_turn(self, p: _Pend, attr: str) -> None:
        with self._cv:
            if getattr(self, attr) == p.seq:
                return
        # the drain thread blocking on an out-of-order turn is exactly
        # the stall a trace must show; the fast path above stays lock+
        # check only (the span records nothing when tracing is off)
        with trace.span("turn-wait", args={"seq": p.seq, "gate": attr}):
            with self._cv:
                while getattr(self, attr) != p.seq:
                    self._cv.wait()

    def _sweep_locked(self, attr: str, v: int) -> None:
        dead = self._dead[attr]
        while v in dead:
            dead.discard(v)
            v += 1
        setattr(self, attr, v)
        self._cv.notify_all()

    def _free_turn(self, p: _Pend, attr: str) -> None:
        """Release one of p's order turns EXACTLY once (state-aware: a
        chunk settled by two paths — e.g. a submit-failure abandon racing
        a teardown abort — must not mark its turn dead twice, which
        would leave a stale entry that could swallow a LATER chunk's
        legitimate turn when seq numbers wrap past it)."""
        with self._cv:
            if p.turns_freed[attr]:
                return
            p.turns_freed[attr] = True
            cur = getattr(self, attr)
            if cur == p.seq:
                self._sweep_locked(attr, p.seq + 1)
            else:
                self._dead[attr].add(p.seq)
                self._sweep_locked(attr, cur)

    def _release_chunk_pins(self, p: _Pend) -> None:
        """Release p's slot pins exactly once.  Double release is the
        REAL hazard the per-chunk flag closes: pins count per slot, so a
        second decrement would release a pin held by a DIFFERENT in-
        flight chunk on the same slot and let the LRU evict state whose
        events are still queued."""
        if p.pins_released:
            return
        p.pins_released = True
        self.windows.release_pins(p.slots)

    def abandon(self, p: _Pend) -> None:
        """Settle a chunk whose apply will never run (pipeline teardown,
        a failed submit burst, or a fully-stale chunk at drain): release
        its pins and both order turns, each exactly once (idempotent —
        see _free_turn/_release_chunk_pins).  Two-program mode: program A
        is stateless, so an abandoned chunk leaves no trace.  Single-
        kernel mode: the commit already happened at submit, so abandon
        only settles the host-side bookkeeping (teardown paths mark the
        chunk's lines as errors)."""
        if p.state in ("done", "failed", "resolved"):
            return
        p.state = "failed"
        self._release_chunk_pins(p)
        self._free_turn(p, "_resolve_seq")
        self._free_turn(p, "_collect_seq")

    def idle(self) -> bool:
        """True when no submitted chunk is awaiting its apply/collect."""
        with self._cv:
            return self._next_seq == self._collect_seq

    def _decode_head(self, p: _Pend, buf: np.ndarray) -> int:
        """Decode the match head (flags ‖ pairs ‖ always bits) shared
        byte-for-byte by program A's buffer and the single-kernel buffer;
        returns the offset just past it (the single-kernel event tail)."""
        P = p.P
        R8 = self.pf._nf8 * 8
        flags = np.frombuffer(buf[:16].tobytes(), dtype="<i4")
        p.flags = flags
        off = 16
        pairs = np.frombuffer(
            buf[off : off + 4 * P].tobytes(), dtype="<i4"
        )
        off += 4 * P
        na8 = self.pf._na8
        if na8:
            p.always_bits = (
                buf[off : off + p.Bp * na8].reshape(-1, na8)[: p.B]
            )
            off += p.Bp * na8
        else:
            p.always_bits = None
        n_pairs = int(flags[2])
        if n_pairs <= P and P:
            live_pairs = pairs[:n_pairs]
            rows_idx = live_pairs // R8
            cols = live_pairs - rows_idx * R8
            # same invariant as prefilter.collect: row in range AND
            # col within the true rule count, so matched_pairs is a
            # clean invariant at the source (consumers may index f_idx
            # with it directly)
            keep = (
                (rows_idx >= 0) & (rows_idx < p.B)
                & (cols < self.pf._n_filt)
            )
            p.matched_pairs = live_pairs[keep]
        return off

    def _overflow(self, p: _Pend) -> "PipelineOverflow":
        """Count a chunk that committed nothing by its cause and build
        the exception that sends it to the classic fallback."""
        _, n_cand, n_pairs, n_events = (int(x) for x in p.flags)
        if n_cand > p.K:
            cause = "candidates"
        elif n_pairs > p.P:
            cause = "pairs"
        elif n_events > p.E:
            cause = "events"
        else:
            cause = "chain"
        self.overflow_causes[cause] += 1
        p.state = "overflow"
        self.fallback_batches += 1
        return PipelineOverflow(candidate_overflow=cause == "candidates")

    def _resolve_single(self, p: _Pend) -> None:
        """Single-kernel resolve: a PURE d2h pull — the commit already
        happened in-kernel at submit, so all that remains is forcing the
        (async-copied) buffer and reading the flags word.  Not-ok chunks
        (own overflow, or gated by a poisoned predecessor) take the
        classic fallback exactly like a two-program overflow; the resolve
        turn is held until fallback_done, so later chunks' replays stay
        behind this chunk's classic apply."""
        try:
            buf = np.asarray(p.sparse_buf)
            p.d2h_bytes += buf.nbytes
            off = self._decode_head(p, buf)
            flags = p.flags
            if not flags[0]:
                self.sk_fallbacks += 1
                raise self._overflow(p)
            p.events_buf = buf
            p.events_off = off
            p.state = "resolved"
            self.fused_batches += 1
            self.sk_chunks += 1
            self.sk_d2h_bytes_total += buf.nbytes
        except PipelineOverflow:
            raise  # turns advance via fallback_done after the fallback
        except Exception:
            p.state = "failed"
            self._release_chunk_pins(p)
            self._free_turn(p, "_resolve_seq")
            self._free_turn(p, "_collect_seq")
            raise
        self._free_turn(p, "_resolve_seq")

    def resolve(self, p: _Pend, live: Optional[np.ndarray] = None) -> None:
        """Order-gated: decode chunk p's A-flags; when ok, dispatch program
        B (the window apply) — B dispatches therefore happen strictly in
        chunk order. `live` (bool [B], default all-true) gates rows out of
        the window commit — the streaming pipeline's drain-time staleness
        drop composed with the deferred apply. Raises PipelineOverflow when
        the chunk must take the classic fallback; the resolve turn is NOT
        advanced until the caller completes the fallback (fallback_done),
        keeping later chunks' applies behind this chunk's.

        Single-kernel mode: the commit already ran at submit (live was an
        input there); this is a pure pull + flags check — `live` must be
        None."""
        self._wait_turn(p, "_resolve_seq")
        if p.state != "submitted":
            return
        if self.single_kernel:
            assert live is None, "single-kernel commit takes live at submit"
            return self._resolve_single(p)
        try:
            buf = np.asarray(p.sparse_buf)
            p.d2h_bytes += buf.nbytes
            self._decode_head(p, buf)
            flags = p.flags
            if not flags[0]:
                raise self._overflow(p)

            wnd = self.windows
            apply = self._apply_prog(p.Bp, p.E)
            slots_p = p.slots.astype(np.int32)
            if p.Bp != p.B:
                slots_p = np.concatenate(
                    [slots_p, np.zeros(p.Bp - p.B, dtype=np.int32)]
                )
            live_p = np.ones(p.Bp, dtype=np.uint8)
            if live is not None:
                live_p[: p.B] = np.asarray(live, dtype=np.uint8)
            p.h2d_bytes += live_p.nbytes
            with wnd._lock:
                wnd._run_maintenance_locked()
                new_state, ebuf = apply(
                    wnd._state, p.bits_dev, jnp.asarray(slots_p),
                    jnp.asarray(p.ts_s), jnp.asarray(p.ts_ns),
                    jnp.asarray(p.host_idx), jnp.asarray(live_p),
                )
                wnd._state = new_state
            try:
                ebuf.copy_to_host_async()
            except AttributeError:
                pass
            p.events_buf = ebuf
            p.state = "resolved"
            self.fused_batches += 1
        except PipelineOverflow:
            raise  # turns advance via fallback_done after the fallback
        except Exception:
            # the chunk is dead: free its order turns (a stuck turn would
            # deadlock every later resolve/collect forever) and the pins.
            # The resolve turn is held by this call (current == p.seq) so
            # _free_turn advances it directly; the collect turn may still
            # belong to an EARLIER uncollected chunk and sweeps lazily.
            p.state = "failed"
            self._release_chunk_pins(p)
            self._free_turn(p, "_resolve_seq")
            self._free_turn(p, "_collect_seq")
            raise
        self._free_turn(p, "_resolve_seq")

    def fallback_done(self, p: _Pend) -> None:
        """The caller's classic fallback for an overflowing chunk is fully
        applied (device + shadow + pins released by apply_bitmap): release
        both order turns.  The pins are marked settled so a later abandon
        (teardown racing the fallback) cannot release them a second time."""
        p.state = "done"
        p.pins_released = True  # apply_bitmap released them
        self._free_turn(p, "_resolve_seq")
        self._free_turn(p, "_collect_seq")
        if self.single_kernel:
            # quiescent chain reseed (see _submit_single): if no later
            # chunk is outstanding, every poisoned chunk has now applied
            # classically, so the next submit may start a fresh ok chain
            with self._cv:
                if self._next_seq == self._resolve_seq:
                    self._chain_ok = None

    def collect(self, p: _Pend) -> FusedWindowsResult:
        """Order-gated on the collect turn: decode chunk p's window events,
        absorb the final counter states into the host shadow, release the
        pins. Only valid for resolved chunks (collect() resolves first on
        the serial convenience path).  Single-kernel mode decodes the
        event tail of the ONE buffer resolve already pulled (no second
        d2h — the event layout is byte-identical to program B's)."""
        if p.state == "submitted":
            self.resolve(p)  # may raise PipelineOverflow to the caller
        assert p.state == "resolved", p.state
        self._wait_turn(p, "_collect_seq")
        wnd = self.windows
        try:
            if self.single_kernel:
                buf = p.events_buf  # already host-side, pulled at resolve
                off = p.events_off
            else:
                buf = np.asarray(p.events_buf)
                p.d2h_bytes += buf.nbytes
                off = 0
            me = p.E

            def take_i32(n):
                nonlocal off
                out = np.frombuffer(
                    buf[off : off + 4 * n].tobytes(), dtype="<i4"
                )
                off += 4 * n
                return out

            ev_line = take_i32(me)
            ev_rule = take_i32(me)
            ev_hits = take_i32(me)
            ev_ss = take_i32(me)
            ev_sns = take_i32(me)
            ev_mtype = buf[off : off + me]; off += me
            ev_exc = buf[off : off + me]; off += me
            ev_seen = buf[off : off + me]; off += me

            # events arrive in key-sorted (scan) order; reference order is
            # (line, rule_id) ascending — per-site ids precede global
            live = np.flatnonzero(ev_rule >= 0)
            live = live[np.lexsort((ev_rule[live], ev_line[live]))]
            events = EventBatch(
                line=ev_line[live], rule=ev_rule[live],
                match_type=ev_mtype[live], exceeded=ev_exc[live] != 0,
                seen_ip=ev_seen[live] != 0,
            )
            # Collect order == apply order, so concurrent chunks can't
            # interleave stale values in the shadow.
            with wnd._lock:
                wnd._absorb_events_locked(
                    p.slots, events.line, events.rule, ev_hits[live],
                    ev_ss[live], ev_sns[live],
                )
            p.state = "done"
            return FusedWindowsResult(
                events=events, matched_pairs=p.matched_pairs,
                always_bits=p.always_bits,
            )
        finally:
            self._release_chunk_pins(p)
            self._free_turn(p, "_collect_seq")
