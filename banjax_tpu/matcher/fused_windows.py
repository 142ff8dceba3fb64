"""Fused matcher + device-windows pipeline: ONE device program per chunk
does the match and the window commit, dispatched at submit.

Why fused at all: with device windows on, the naive path round-trips the
match bitmap through the host — the matcher pulls its sparse result down
(a fixed d2h round trip per pull), the runner rebuilds a dense
[B, n_rules] bitmap, and apply_bitmap pushes those ~16 MB back up for the
window scan. Here the dense caller-order bitmap never exists on the host.

The program (kernels/fused_match_window.py): two-stage match
(prefilter._match_core), the sparse (row, rule) pairs read out of stage
2's packed words, the window events listed straight from those pairs and
the always-columns' bits — masked per event by the per-row live mask (the
caller's staleness drop, an INPUT to submit), the real-row count and the
host's active rules; where the ruleset has rules of single sites, stage
2's packed rows are ANDed with their host's packed active row before the
pairs are counted, so a pattern many sites share yields one pair a line —
every overflow flag (candidate count, match-pair
count, window-event count) and the window segmented scan
(windows._apply_events, state donated) whose commit is gated IN the
program on those flags and on a device-side chain scalar.  Nothing in it
reduces over rows x rules.  Output: one host buffer (flags ‖ (row, rule)
match pairs ‖ always-rule bits ‖ the fired-event records), pulled
asynchronously, and the device-resident dense caller-order bitmap, which
only the overflow replay reads.

Order: submits are serialized under the windows lock, so device apply
order == sequence order == log order.  A chunk that overflows commits
nothing and its chain scalar poisons every already-dispatched successor
(they commit nothing either): the caller replays each classically, in
order (runner._pipeline_fallback_entry), and the chain reseeds once no
chunk is outstanding.

Settlement: submit() assigns a sequence number and resolve() gates on it
— chunk i's buffer is read only after every earlier chunk is settled
(collected, replayed classically, or abandoned).  One gate carries both
orders that matter: an overflowing chunk's classic replay lands on the
device after every earlier chunk's, and the host shadow absorbs chunks in
device-apply order (or an eviction could restore stale counters).

Event order parity: an event carries its CALLER row, and the window
apply orders by the ordinal line * n_rules + rule — the reference's
per-site-then-global (line, rule) processing order — so the order the
events are listed in (always-columns first, then pairs in candidate-slot
order) does not matter, exactly as a row-major compaction of the dense
bitmap would not.  banjax_fused_event_feed_total{source} counts the
committed events by which of the two lists they came from.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from banjax_tpu.obs import trace
from banjax_tpu.matcher import longrows
from banjax_tpu.matcher.prefilter import FusedPrefilter
from banjax_tpu.matcher.windows import DeviceWindows, EventBatch


@dataclasses.dataclass
class _Pend:
    """One chunk in flight. States: submitted → resolved → done, or
    submitted → overflow → (caller fallback) → done; `failed` when it
    died on the way."""

    seq: int
    sparse_buf: object     # THE one combined buffer (async pull in flight)
    bits_dev: object       # [Bp, n_rules] uint8 device-resident: the
    #                        overflow's classic replay takes its bitmap here
    slots: np.ndarray      # caller-order, pins held
    B: int                 # real rows
    Bp: int
    K: int
    P: int
    E: int                 # window-event capacity of the chunk's program
    KL: tuple = ()         # the long operands of the chunk's program
    state: str = "submitted"
    flags: Optional[np.ndarray] = None     # [4] after resolve
    events_buf: object = None              # the decoded host buffer
    events_off: int = 0                    # event-record offset into it
    # decoded at resolve
    matched_pairs: Optional[np.ndarray] = None
    always_bits: Optional[np.ndarray] = None
    # transfer accounting (obs/stats.py note_xfer): what this chunk moved
    # across the host boundary — the fusion-win witness is the ABSENCE of
    # the dense [B, n_rules] bitmap from h2d_bytes
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    # state-aware settlement: the order turn and the slot pins are
    # released EXACTLY once no matter which combination of resolve/
    # collect/fallback_done/abandon settles the chunk (a submit-failure
    # abandon racing a teardown abort used to mark a turn dead twice,
    # which could advance the counter past a live chunk's turn)
    pins_released: bool = False
    turn_freed: bool = False


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
    both active, every rule is device-decidable and the window-scan
    kernel passed its selftest.

    Contract: submit in chunk order; resolve and collect in that same
    order (resolve gates on it). Pins are owned by the pipeline from
    submit() until collect() completes — except after PipelineOverflow,
    where the caller's fallback apply (which releases them) takes over,
    followed by fallback_done() to release the order turn."""

    def __init__(self, prefilter: FusedPrefilter, windows: DeviceWindows,
                 active_table, n_rules: int,
                 scan_interpret: bool = True, traffic_sketch=None,
                 skip_table=None):
        self.pf = prefilter
        self.windows = windows
        self.active_table = jnp.asarray(active_table)
        # [hosts + 1, rules] like the active table: rules that apply on
        # the host but are skipped there (hosts_to_skip) — no event, but
        # a pair, because the drain owes the line a skip_host result
        self.skip_table = skip_table
        self.n_rules = n_rules
        # traffic introspection (obs/sketch.py): every submitted chunk
        # folds into the device-resident count-min/HLL sketches inside
        # its own program (built without the fold when this is None) —
        # telemetry only, no interaction with window state or results
        self._traffic_sketch = traffic_sketch
        self._progs = {}            # (Bp, L_p) → build_single_program's
        # (Bp, L_p) → the program that takes a long operand beside the
        # short one, and whether this process has met a line over the
        # short width.  From the first such line on every chunk dispatches
        # a program of this table (real traffic has one in every chunk,
        # and a warm-up then builds what the stream will use); a process
        # that never meets one builds and dispatches none of them
        self._progs_long = {}
        self.long_rows_seen = False
        self._scan_interpret = bool(scan_interpret)
        # device-side ok chain: each program's commit gates on its
        # predecessor's ok scalar, so an overflow poisons every already-
        # dispatched successor WITHOUT a host round-trip; None = seed the
        # next submit with a fresh ok (no poisoned chunk outstanding)
        self._chain_ok = None
        self._chain_seed = jnp.int32(1)  # made once, never donated
        self.fused_batches = 0      # chunks committed by the fused program
        self.fallback_batches = 0   # routed to the classic fallback
        self.sk_d2h_bytes_total = 0  # the one-pull d2h witness
        # fused dispatches that committed nothing, by what overflowed:
        # the chunk's own candidates / (row, rule) pairs / window events,
        # or `chain` — gated by an overflowing predecessor's chain scalar;
        # and `long_rows`, the caller's count: batches it cut into
        # smaller chunks because a chunk held more lines over the short
        # width than its long operand has room for (nothing replayed)
        self.overflow_causes = {
            "candidates": 0, "pairs": 0, "events": 0, "chain": 0,
            "long_rows": 0,
        }
        # window events committed fused, by where the program took them
        # from: its (row, rule) pairs or its always-columns' set bits
        self.event_feed = {"pairs": 0, "always": 0}
        # (row, rule) pairs the programs counted (flag n_pairs of every
        # dispatch read, one that overflowed included): with rules of
        # single sites, what the site mask left of stage 2's set bits
        self.pairs_total = 0
        # long rows stage 1's gate passed on to stage 2, and their bytes
        # (what the second stage-2 launch scanned)
        self.long_candidates = 0
        self.long_candidate_bytes = 0
        plan = prefilter.plan
        self._is_always = np.zeros(max(1, n_rules), dtype=bool)
        self._is_always[np.asarray(plan.a_idx, dtype=np.int64)] = True
        self._f_idx = jnp.asarray(plan.f_idx, dtype=jnp.int32)
        self._a_idx = jnp.asarray(plan.a_idx, dtype=jnp.int32)
        na = plan.n_always
        self._aw = jnp.asarray(
            np.asarray(plan.stage1.always_match[:na], dtype=np.uint8)
        )
        self._ae = jnp.asarray(
            np.asarray(plan.stage1.empty_only[:na], dtype=np.uint8)
        )
        self._cv = threading.Condition()
        self._next_seq = 0      # assigned at submit
        self._turn = 0          # the chunk whose settlement comes next
        # turns of chunks that died before taking them (abandon): swept
        # lazily when the counter reaches them — advancing out of turn
        # would steal an earlier live chunk's turn
        self._dead = set()

    # ---- the program: match + window commit in ONE dispatch ----

    def _single_prog(self, Bp: int, L_p: int, KL: tuple = ()):
        """The fused match+window program for one (rows, line length)
        bucket, built on first use; with `KL` (longrows.operands'
        pairs), the one that takes the chunk's long rows beside."""
        key = (Bp, L_p)
        progs = self._progs_long if KL else self._progs
        hit = progs.get(key)
        if hit is not None:
            return hit
        from banjax_tpu.matcher.kernels import fused_match_window as fmw

        hit = fmw.build_single_program(
            self.pf, self.windows, self.active_table, self.n_rules,
            Bp, L_p, f_idx=self._f_idx, a_idx=self._a_idx,
            aw=self._aw, ae=self._ae,
            scan_fn=fmw.window_scan(self._scan_interpret),
            skip_table=self.skip_table, KL=KL, sketch=self._traffic_sketch,
        )
        progs[key] = hit
        # ... and the programs of a maintenance run its chunks cannot carry
        self.windows.build_maintenance_steps(Bp)
        return hit

    # ---- host API (submit → resolve → collect, each in chunk order) ----

    def submit(
        self, cls_ids: np.ndarray, lens: np.ndarray, slots: np.ndarray,
        ts_s: np.ndarray, ts_ns: np.ndarray, host_idx: np.ndarray,
        live: Optional[np.ndarray] = None, long_rows=None,
        row_hashes: Optional[np.ndarray] = None,
    ) -> _Pend:
        """Dispatch the fused program for one chunk (slot pins held by
        the caller, ownership passes to the pipeline).  The window state
        commit happens HERE (gated in the program on overflow and on the
        chain scalar), so the returned chunk is already final — its
        resolve is a pure pull — and any number of chunks may be
        submitted ahead of their resolves.  `live` (bool [B], default
        all-true) is the commit mask — the caller's staleness drop
        composed as a program input.  `long_rows` = (rows, lens, class
        ids back to back) of the chunk's lines over the short width, no
        more of a width than longrows.operands has room for (the caller's
        check): rows whose `lens` entry is 0.  `row_hashes` (uint32 [B]):
        each row's address hash, what the traffic sketch folds the chunk
        under, in this dispatch — every real row, live or not, whatever
        the program's gate says; not read without a sketch.  The dispatch
        runs under the windows
        lock: the table's queued maintenance (evictions, then restores)
        goes in with it as operands, and the state-chain order == seq
        order because both are taken inside the same critical section."""
        pf = self.pf
        lap = trace.lap()  # the caller's `operands` phase runs on
        cls_ids = np.asarray(cls_ids, dtype=np.int32)
        lens = np.asarray(lens, dtype=np.int32)
        B = cls_ids.shape[0]
        if long_rows is not None and len(long_rows[0]):
            self.long_rows_seen = True
        KL = long_op = ()
        if self.long_rows_seen:
            combined, Bp, L_p = pf._assemble(
                cls_ids, lens, self._progs_long, full_width=True)
            KL = longrows.operands(pf, Bp)
            long_op = longrows.assemble(pf, KL, long_rows, pad_row=Bp)
        else:
            combined, Bp, L_p = pf._assemble(cls_ids, lens, self._progs)

        def pad(a):
            a = np.asarray(a, dtype=np.int32)
            if Bp == len(a):
                return a
            return np.concatenate([a, np.zeros(Bp - len(a), dtype=np.int32)])

        fn, K, P, E = self._single_prog(Bp, L_p, KL)
        host_idx_p, slots_p = pad(host_idx), pad(slots)
        ts_s_p, ts_ns_p = pad(ts_s), pad(ts_ns)
        live_p = np.zeros(Bp, dtype=np.uint8)
        live_p[:B] = 1 if live is None else np.asarray(live, dtype=np.uint8)
        sk = self._traffic_sketch
        if sk is not None:
            hashes_p = np.zeros(Bp, dtype=np.uint32)
            hashes_p[:B] = row_hashes
        wnd = self.windows
        lap.mark("dispatch")
        with wnd._lock:
            with self._cv:
                seq = self._next_seq
                # quiescent chain reseed: every submitted chunk settled
                # ⟹ every poisoned chunk's classic fallback has applied,
                # so a fresh ok seed cannot reorder window updates
                if seq == self._turn:
                    self._chain_ok = None
                self._next_seq += 1
                chain = self._chain_ok
            # the table's queued evictions and restores ride this
            # dispatch as two operands (what does not fit them runs here)
            lap.mark("maintenance")
            maintenance = wnd._run_maintenance_locked(carry_rows=Bp)
            lap.mark("dispatch")
            # every operand goes in as it is, a numpy array or scalar:
            # the call transfers them itself, and the chunk is ONE trip
            # through the runtime
            operands = (
                self._chain_seed if chain is None else chain,
                combined, np.int32(B), host_idx_p, slots_p, ts_s_p, ts_ns_p,
                live_p, *maintenance,
            )
            try:
                if sk is None:
                    out = fn(wnd._state, *operands, *long_op)
                else:
                    # the sketch's state lock inside the windows lock,
                    # held across the dispatch that donates both states
                    def run(sketch_state):
                        *out, sketch_state = fn(
                            wnd._state, sketch_state, *operands, hashes_p,
                            *long_op)
                        return sketch_state, out

                    out = sk.dispatch_fold(run, B, "fused")
            except BaseException:
                # nothing carried the table's maintenance: it is not lost
                # with the chunk — and nobody will settle the chunk's turn
                ev_slots, restore_rows = maintenance
                wnd._maintenance_steps_locked(ev_slots, restore_rows[None])
                with self._cv:
                    self._dead.add(seq)
                    self._sweep_locked(self._turn)
                raise
            trace.runtime_calls()
            wnd._state, chain_out, buf, bits_dev = out
            with self._cv:
                self._chain_ok = chain_out
        try:
            buf.copy_to_host_async()
        except AttributeError:
            pass
        p = _Pend(
            seq=seq, sparse_buf=buf, bits_dev=bits_dev,
            slots=np.asarray(slots), B=B, Bp=Bp, K=K, P=P, E=E, KL=KL,
            # the whole h2d for the chunk: encoded classes + per-row
            # window metadata + the live mask + the chain scalar (+ the
            # rows' hashes for the sketch) — still no dense [B, n_rules]
            # bitmap.  The table's maintenance operands are not the
            # chunk's: no path counts them, carried or dispatched alone
            h2d_bytes=combined.nbytes + 4 * 3 * Bp + Bp + 4
            + sum(x.nbytes for x in long_op)
            + (0 if sk is None else hashes_p.nbytes),
        )
        lap.mark("other")
        return p

    def _wait_turn(self, p: _Pend) -> None:
        with self._cv:
            if self._turn == p.seq:
                return
        # the drain thread blocking on an out-of-order turn is exactly
        # the stall a trace must show; the fast path above stays lock+
        # check only (the span records nothing when tracing is off)
        with trace.span("turn-wait", args={"seq": p.seq}):
            with self._cv:
                while self._turn != p.seq:
                    self._cv.wait()

    def _sweep_locked(self, v: int) -> None:
        while v in self._dead:
            self._dead.discard(v)
            v += 1
        self._turn = v
        self._cv.notify_all()

    def _free_turn(self, p: _Pend) -> None:
        """Release p's order turn EXACTLY once (state-aware: a chunk
        settled by two paths — e.g. a submit-failure abandon racing a
        teardown abort — must not mark its turn dead twice, which would
        leave a stale entry that could swallow a LATER chunk's
        legitimate turn when seq numbers wrap past it)."""
        with self._cv:
            if p.turn_freed:
                return
            p.turn_freed = True
            if self._turn == p.seq:
                self._sweep_locked(p.seq + 1)
            else:
                self._dead.add(p.seq)
                self._sweep_locked(self._turn)

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
        """Settle a chunk whose events will never be collected (pipeline
        teardown, a failed submit burst): release its pins and its order
        turn, each exactly once (idempotent — see _free_turn/
        _release_chunk_pins).  The commit already happened at submit, so
        abandon only settles the host-side bookkeeping (teardown paths
        mark the chunk's lines as errors)."""
        if p.state in ("done", "failed", "resolved"):
            return
        p.state = "failed"
        self._release_chunk_pins(p)
        self._free_turn(p)

    def idle(self) -> bool:
        """True when no submitted chunk is awaiting its collect."""
        with self._cv:
            return self._next_seq == self._turn

    def _decode_head(self, p: _Pend, buf: np.ndarray) -> int:
        """Decode the match head (flags ‖ pairs ‖ always bits); returns
        the offset just past it (the event tail)."""
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
        self.pf.note_bucket_hits(p.B, buf)
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

    def resolve(self, p: _Pend) -> None:
        """Order-gated: once every earlier chunk is settled, force chunk
        p's (async-copied) buffer and read its flags word — a PURE d2h
        pull, the commit already happened in the program at submit.  A
        not-ok chunk (own overflow, or gated by a poisoned predecessor)
        raises PipelineOverflow: the caller replays it classically and
        calls fallback_done.  The turn is held until then — or, for an ok
        chunk, until its collect — so later chunks' replays and shadow
        writes stay behind this chunk's."""
        self._wait_turn(p)
        if p.state != "submitted":
            return
        try:
            buf = np.asarray(p.sparse_buf)
            p.d2h_bytes += buf.nbytes
            off = self._decode_head(p, buf)
            # the rows stage 2 scanned: the gate's candidates, as far
            # as the program has room for them
            self.pf.candidates_total += min(int(p.flags[1]), p.K)
            self.pairs_total += int(p.flags[2])
            if p.KL and p.K:
                at = len(buf) - 4 * self.pf.n_buckets - 8
                rows, nbytes = np.frombuffer(
                    buf[at : at + 8].tobytes(), dtype="<i4")
                self.pf.candidates_total += int(rows)
                self.long_candidates += int(rows)
                self.long_candidate_bytes += int(nbytes)
            if not p.flags[0]:
                raise self._overflow(p)
            p.events_buf = buf
            p.events_off = off
            p.state = "resolved"
            self.fused_batches += 1
            self.sk_d2h_bytes_total += buf.nbytes
        except PipelineOverflow:
            raise  # the turn advances via fallback_done after the fallback
        except Exception:
            # the chunk is dead: free its turn (a stuck turn would
            # deadlock every later resolve forever) and the pins
            p.state = "failed"
            self._release_chunk_pins(p)
            self._free_turn(p)
            raise

    def fallback_done(self, p: _Pend) -> None:
        """The caller's classic fallback for an overflowing chunk is fully
        applied (device + shadow + pins released by apply_bitmap): release
        the order turn.  The pins are marked settled so a later abandon
        (teardown racing the fallback) cannot release them a second time."""
        p.state = "done"
        p.pins_released = True  # apply_bitmap released them
        self._free_turn(p)
        # quiescent chain reseed (see submit): if no later chunk
        # is outstanding, every poisoned chunk has now applied
        # classically, so the next submit may start a fresh ok chain
        with self._cv:
            if self._next_seq == self._turn:
                self._chain_ok = None

    def collect(self, p: _Pend) -> FusedWindowsResult:
        """Decode chunk p's window events from the event tail of the ONE
        buffer resolve pulled (no second d2h), absorb the final counter
        states into the host shadow, release the pins and the turn. Only
        valid for resolved chunks (collect() resolves first on the serial
        convenience path)."""
        if p.state == "submitted":
            self.resolve(p)  # may raise PipelineOverflow to the caller
        assert p.state == "resolved", p.state
        wnd = self.windows
        try:
            buf = p.events_buf  # already host-side, pulled at resolve
            off = p.events_off
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
            n_always = int(np.count_nonzero(self._is_always[events.rule]))
            self.event_feed["always"] += n_always
            self.event_feed["pairs"] += len(events) - n_always
            # Collect order == apply order (the turn is held since
            # resolve), so concurrent chunks can't interleave stale
            # values in the shadow.
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
            self._free_turn(p)
