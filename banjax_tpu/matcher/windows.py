"""Device-side fixed-window rate-limit counters (SURVEY.md §7.1 hard part #3).

The reference applies its per-(IP, rule) fixed-window counters serially, one
matched line at a time, under a mutex (/root/reference/internal/
rate_limit.go:37-78). This module keeps the counters resident on the TPU as
flat [capacity * n_rules] arrays and folds a whole batch of match events into
them in one jitted step:

  an event list (line, rule): the classic path compacts it out of a match
    bitmap [B, R] (masked by per-host rule applicability / hosts_to_skip,
    then a fixed-capacity nonzero: _apply_core); the fused program builds
    it from the sparse (row, rule) pairs and always-column bits it already
    holds, the same masks gathered per event, and never scans rows x rules
    (kernels/fused_match_window.py) — both hand it to _apply_events
    → stable-sort by (slot, rule) key, ties on the ordinal line * R + rule:
      (line, rule) ascending IS the reference's processing order
      (per-site rule ids precede global ids, so it equals the
      per-site-then-global loop of regex_rate_limiter.go:175-211), whatever
      order the events were listed in
    → one lax.scan over the sorted events: per segment, load the persistent
      (hits, start) state, replay the exact window transitions, flag
      exceeded events, write the segment's final state back
    → return the compact per-event (match_type, exceeded, seen_ip) plus the
      bit-packed match bitmap for host-side result reconstruction.

Exactness: the host oracle (decisions/rate_limit.py, itself a port of
rate_limit.go) compares int64 nanoseconds; TPUs have no native int64, so
timestamps ride as (seconds, nanoseconds) int32 pairs and every comparison
uses borrow arithmetic — bit-identical to the int64 path, including the
contract quirks: window restart strictly-greater-than interval, hits reset
to 0 (not 1) on exceed, FirstTime/OutsideInterval/InsideInterval match
types, and seen_ip = "the IP had any state before this event".

IP slots are assigned host-side (dict + LRU); evicting a slot queues a
device-side generation bump that runs in the next maintenance step (one
element per evicted slot, whatever the rule count), so the device never
needs a host round-trip mid-batch. Eviction is LOSSLESS: a host-side
shadow (updated from each batch's event-final states, which the scan
computes anyway) holds every (ip, rule) counter, and a re-admitted IP's
rows are scattered back onto the device before its next events — beyond
`matcher_window_capacity` distinct IPs the matcher degrades to slower,
never to wrong (rate_limit.go:37-78 never forgets state, so neither do we).

The shadow is one record per address — its counters in first-event order
— that moves between three homes: with the address while it is resident,
the warm tier once it is evicted, and a dict keyed by address where no
tier takes it.  Two forms of the same logic: with the native libraries
(`_sm is not None`) the residents' records live in a slot-indexed C
mirror (native/shmstate.c sh_*) and absorb, spill, refill and the restore
rows are one C call each over arrays; without them everything is the
dict, which is also the oracle the parity tests hold the mirror to
(tests/unit/test_shadow_mirror.py).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from banjax_tpu.config.schema import RegexWithRate
from banjax_tpu.decisions.rate_limit import (
    NumHitsAndIntervalStart,
    RateLimitMatchType,
)
from banjax_tpu.obs import trace

_NS_PER_S = 1_000_000_000

_MIN_ROW_BUCKET = 64
_MIN_MAINT_BUCKET = 256


def _bucket(n: int, floor: int) -> int:
    """`floor` doubled until it holds n: operand lengths are trace keys of
    the jitted steps, so they come from a bounded set of classes."""
    b = floor
    while b < n:
        b <<= 1
    return b


def _bucket_rows(n: int) -> int:
    """Pad batch row counts to powers of two: _apply_step is jitted with the
    batch arrays' shapes as trace keys, so unbucketed sizes would compile a
    fresh segmented-scan program per distinct B (unbounded jit-cache growth
    in the hot path). Pad rows carry bits=0 and so produce no events."""
    return _bucket(n, _MIN_ROW_BUCKET)


def split_ns(ts_ns) -> Tuple[np.ndarray, np.ndarray]:
    """int64 ns → (seconds, subsecond ns) int32 pair; exact for epoch times."""
    ts_ns = np.asarray(ts_ns, dtype=np.int64)
    s, ns = np.divmod(ts_ns, _NS_PER_S)  # floored divmod: ns always in [0, 1e9)
    return s.astype(np.int32), ns.astype(np.int32)


def _pair_gt(a_s, a_ns, b_s, b_ns):
    """(a_s, a_ns) > (b_s, b_ns) lexicographically — int64 compare, split."""
    return (a_s > b_s) | ((a_s == b_s) & (a_ns > b_ns))


def _pair_sub(a_s, a_ns, b_s, b_ns):
    """(a - b) as a normalized (s, ns) pair with borrow; may be negative s."""
    ds = a_s - b_s
    dns = a_ns - b_ns
    borrow = dns < 0
    return ds - borrow.astype(ds.dtype), dns + borrow.astype(dns.dtype) * _NS_PER_S


@dataclasses.dataclass
class DeviceWindowState:
    """The donated device arrays (flat key = slot * n_rules + rule).

    State exists for a key iff `key_gen[key] == slot_gen[key // n_rules]`:
    a write stamps the key with its slot's generation, and evicting a slot
    bumps the one `slot_gen` entry, which invalidates all n_rules keys of
    the slot at once.  A fresh table is slot_gen 1 / key_gen 0.  Both are
    int32 on purpose: a stale key must never read as valid again, and a
    slot that turns over every few seconds would wrap a 16-bit generation
    within days, an int32 one in centuries."""

    hits: jnp.ndarray      # [cap * R] int32
    start_s: jnp.ndarray   # [cap * R] int32
    start_ns: jnp.ndarray  # [cap * R] int32
    key_gen: jnp.ndarray   # [cap * R] int32 — slot generation at last write
    slot_gen: jnp.ndarray  # [cap] int32 — bumped at every eviction
    ip_seen: jnp.ndarray   # [cap] bool — slot has any state (seen_ip flag)


@jax.jit
def _count_events(bits, active_table, host_idx):
    """Pre-pass: event count — the overflow check before any state mutation."""
    fire = (bits != 0) & active_table[host_idx]
    return fire.sum(dtype=jnp.int32)


def _window_step(carry, xs):
    """One event of the fixed-window recurrence (rate_limit.go:37-78 with
    the reset-to-0-on-exceed quirk), segment boundaries reloading the
    persistent state.  Module-level and pure on purpose: the XLA
    `lax.scan` below and the Pallas single-kernel scan
    (kernels/fused_match_window.py) both lower from THIS definition, so
    the two paths cannot drift semantically."""
    c_hits, c_ss, c_sns = carry
    (b, gh, gs, gn, gv, ets, etn, lim, ivs, ivn, is_pad) = xs
    h0 = jnp.where(b, gh, c_hits)
    s0 = jnp.where(b, gs, c_ss)
    n0 = jnp.where(b, gn, c_sns)
    have = jnp.where(b, gv, True)

    ds, dns = _pair_sub(ets, etn, s0, n0)
    outside = have & _pair_gt(ds, dns, ivs, ivn)
    restart = ~have | outside
    h1 = jnp.where(restart, jnp.int32(1), h0 + 1)
    s1 = jnp.where(restart, ets, s0)
    n1 = jnp.where(restart, etn, n0)
    exceeded = h1 > lim
    h2 = jnp.where(exceeded, jnp.int32(0), h1)
    mtype = jnp.where(
        ~have, jnp.int32(0), jnp.where(outside, jnp.int32(1), jnp.int32(2))
    )
    # padding events must not perturb the carry (they share key cap_r,
    # so they're their own segment — but keep them inert regardless)
    h2 = jnp.where(is_pad, c_hits, h2)
    s1 = jnp.where(is_pad, c_ss, s1)
    n1 = jnp.where(is_pad, c_sns, n1)
    return (h2, s1, n1), (h2, s1, n1, mtype, exceeded)


def _apply_core(
    state: DeviceWindowState,
    bits: jnp.ndarray,         # [B, R] uint8/bool match bitmap (device)
    active_table: jnp.ndarray,  # [H, R] bool — rule applicable & not hosts_to_skip
    host_idx: jnp.ndarray,     # [B] int32 row of active_table per line
    slot_ids: jnp.ndarray,     # [B] int32 (slot per line)
    ts_s: jnp.ndarray,         # [B] int32
    ts_ns: jnp.ndarray,        # [B] int32
    limits: jnp.ndarray,       # [R] int32 hits_per_interval
    iv_s: jnp.ndarray,         # [R] int32 interval seconds part
    iv_ns: jnp.ndarray,        # [R] int32 interval ns part
    *,
    n_rules: int,
    max_events: int,
    gate=None,
):
    """The classic window apply: the events of a DENSE match bitmap, found
    by a fixed-capacity nonzero over rows x rules in row-major (= reference
    processing) order, handed to _apply_events.  _apply_step (apply_bitmap,
    the overflow's replay) is its one caller; the fused program holds its
    matches as sparse pairs already and feeds _apply_events itself
    (kernels/fused_match_window.py).  Caller guarantees evictions/restores
    already ran (_run_maintenance_locked)."""
    fire = (bits != 0) & active_table[host_idx]
    lines, rules = jnp.nonzero(
        fire, size=max_events, fill_value=(jnp.int32(-1), jnp.int32(-1))
    )
    return _apply_events(
        state, lines, rules, lines >= 0, slot_ids, ts_s, ts_ns,
        limits, iv_s, iv_ns, n_rules=n_rules, gate=gate,
    )


def _apply_events(
    state: DeviceWindowState,
    lines: jnp.ndarray,        # [E] int32 line of each event
    rules: jnp.ndarray,        # [E] int32 rule of each event
    alive: jnp.ndarray,        # [E] bool — False = a pad, whatever it holds
    slot_ids: jnp.ndarray,     # [B] int32 (slot per line)
    ts_s: jnp.ndarray,         # [B] int32
    ts_ns: jnp.ndarray,        # [B] int32
    limits: jnp.ndarray,       # [R] int32 hits_per_interval
    iv_s: jnp.ndarray,         # [R] int32 interval seconds part
    iv_ns: jnp.ndarray,        # [R] int32 interval ns part
    *,
    n_rules: int,
    gate=None,                 # scalar bool: False drops EVERY state write
    scan_fn=None,              # None = lax.scan over _window_step
):
    """The traceable window-apply body over an EVENT LIST: distinct
    (line, rule) pairs in any order, pads anywhere.  Both extractions end
    here — the dense bitmap's nonzero (_apply_core) and the fused program's
    pairs and always-columns — so the window semantics live once.

    The reference processes events in (line, rule) order, and that order
    is carried by the ordinal line * n_rules + rule, not by an event's
    position: the stable sort by (slot, rule) key breaks ties on it, and
    seen_ip compares it.  `gate` supports overflow handling under buffer
    donation: when False, all scatters drop (indices pushed out of range)
    so the donated state passes through bit-identical and the caller can
    rerun the batch through the splitting path — no state copy needed.
    `scan_fn(init, xs) -> (f_hits, f_ss, f_sns, mtype, exceeded)` swaps
    the event recurrence for an alternative lowering of _window_step —
    the single-kernel path passes the Pallas scan from
    kernels/fused_match_window.py; None keeps the XLA lax.scan."""
    cap_r = state.hits.shape[0]
    n_slots = state.ip_seen.shape[0]
    if slot_ids.shape[0] * n_rules >= 2**31:
        raise ValueError(
            f"batch {slot_ids.shape[0]} x {n_rules} rules overflows the "
            "int32 (line, rule) event ordinal — lower matcher_batch_lines"
        )
    ip_seen = state.ip_seen

    pad = ~alive
    lines = jnp.where(pad, jnp.int32(-1), lines)
    rules = jnp.where(pad, jnp.int32(-1), rules)
    slot = jnp.where(pad, jnp.int32(0), slot_ids[lines])
    key = jnp.where(pad, jnp.int32(cap_r), slot * n_rules + rules)  # pad sorts last
    seq = lines * n_rules + rules  # the reference's processing order

    # 1. stable sort by key (ties keep (line, rule) order)
    order = jnp.lexsort((seq, key))
    key_s = key[order]
    slot_s = slot[order]
    lines_s = lines[order]
    rules_s = jnp.where(key_s >= cap_r, jnp.int32(0), rules[order])
    e_ts_s = ts_s[jnp.maximum(lines_s, 0)]
    e_ts_ns = ts_ns[jnp.maximum(lines_s, 0)]
    pad_s = key_s >= cap_r

    # seen_ip: slot already seen on device, or an earlier event in this batch
    # touched the slot (reference: the per-IP dict exists, rate_limit.go:72-79)
    never = jnp.iinfo(jnp.int32).max
    first_seq = jnp.full((n_slots,), never, dtype=jnp.int32)
    first_seq = first_seq.at[slot].min(
        jnp.where(pad, never, seq), mode="drop"
    )
    seen_ip_ev = ip_seen[slot] | (seq > first_seq[slot])  # post-eviction flags
    seen_ip_s = seen_ip_ev[order]

    # 2. segment boundaries + persistent state gather per event
    prev_key = jnp.concatenate([jnp.full((1,), -1, dtype=key_s.dtype), key_s[:-1]])
    boundary = key_s != prev_key
    g_hits = state.hits[jnp.minimum(key_s, cap_r - 1)]
    g_ss = state.start_s[jnp.minimum(key_s, cap_r - 1)]
    g_sns = state.start_ns[jnp.minimum(key_s, cap_r - 1)]
    gen_s = state.slot_gen[slot_s]
    g_valid = (state.key_gen[jnp.minimum(key_s, cap_r - 1)] == gen_s) & ~pad_s

    lim_e = limits[rules_s]
    ivs_e = iv_s[rules_s]
    ivns_e = iv_ns[rules_s]

    init = (jnp.int32(0), jnp.int32(0), jnp.int32(0))
    xs = (
        boundary, g_hits, g_ss, g_sns, g_valid,
        e_ts_s, e_ts_ns, lim_e, ivs_e, ivns_e, pad_s,
    )
    if scan_fn is None:
        _, (f_hits, f_ss, f_sns, mtype, exceeded) = jax.lax.scan(
            _window_step, init, xs
        )
    else:
        f_hits, f_ss, f_sns, mtype, exceeded = scan_fn(init, xs)

    # 3. write back each segment's final state (last event of each key)
    next_key = jnp.concatenate([key_s[1:], jnp.full((1,), -2, dtype=key_s.dtype)])
    is_last = (key_s != next_key) & ~pad_s
    wb_key = jnp.where(is_last, key_s, jnp.int32(cap_r))  # drop non-last
    seen_idx = jnp.where(pad, n_slots, slot)
    if gate is not None:
        wb_key = jnp.where(gate, wb_key, jnp.int32(cap_r))
        seen_idx = jnp.where(gate, seen_idx, n_slots)
    hits = state.hits.at[wb_key].set(f_hits, mode="drop")
    start_s = state.start_s.at[wb_key].set(f_ss, mode="drop")
    start_ns = state.start_ns.at[wb_key].set(f_sns, mode="drop")
    key_gen = state.key_gen.at[wb_key].set(gen_s, mode="drop")
    ip_seen = ip_seen.at[seen_idx].set(True, mode="drop")

    new_state = DeviceWindowState(
        hits=hits, start_s=start_s, start_ns=start_ns, key_gen=key_gen,
        slot_gen=state.slot_gen, ip_seen=ip_seen,
    )
    out = {
        "line": lines_s,
        "rule": jnp.where(pad_s, jnp.int32(-1), rules_s),
        "match_type": mtype,
        "exceeded": exceeded & ~pad_s,
        "seen_ip": seen_ip_s,
        # per-event FINAL counter state: feeds the host shadow that makes
        # eviction lossless (last event per key carries the written state)
        "hits": f_hits,
        "start_s": f_ss,
        "start_ns": f_sns,
    }
    return new_state, out


@functools.partial(
    jax.jit,
    static_argnames=("n_rules", "max_events"),
    donate_argnums=(0,),
)
def _apply_step(state, bits, active_table, host_idx, slot_ids, ts_s, ts_ns,
                limits, iv_s, iv_ns, *, n_rules, max_events):
    return _apply_core(
        state, bits, active_table, host_idx, slot_ids, ts_s, ts_ns,
        limits, iv_s, iv_ns, n_rules=n_rules, max_events=max_events,
    )


# restored keys go to the device in chunks of this many: ONE program,
# whatever a batch brings back.  A class per power of two (as the evicted
# slots have) is a program per class, and with rules that fire on every
# line a batch restores hundreds of addresses, so the classes it reaches
# differ from batch to batch and the rare ones would be built minutes into
# a run, each build a stall of the whole pipeline.
_RESTORE_CHUNK = 1024


def _restore_room(carry_rows: int) -> int:
    """Restored keys a fused chunk of `carry_rows` rows carries beside its
    evictions: a key a row, in whole restore chunks and one at least.  A
    batch that brings 750 addresses back restores 1.4 counters an address
    where every line is a window event (`default.flood`: three runs in
    five were past one chunk, PR 49), so one chunk's room is too little
    for the batch the pipeline runs at, and a key a row is what a batch
    cannot reach by its addresses alone.  The program runs the scatters
    past the first chunk only where a key lies there."""
    return _RESTORE_CHUNK * max(1, carry_rows // _RESTORE_CHUNK)


def _evict(state: DeviceWindowState, ev_slots: jnp.ndarray):
    """Evict K slots ([K] int32, cap = none): two [K] scatters — the
    generation bump invalidates every rule's key of a slot without
    touching one of them; nothing here is sized by n_rules.  A slot
    evicted twice between two steps appears twice and is bumped twice,
    which is as good as once."""
    return dataclasses.replace(
        state,
        slot_gen=state.slot_gen.at[ev_slots].add(1, mode="drop"),
        ip_seen=state.ip_seen.at[ev_slots].set(False, mode="drop"),
    )


def _restore(state: DeviceWindowState, rows: jnp.ndarray):
    """Restore Kr keys: five [Kr] scatters.  `rows` is [5, Kr] int32 —
    each key's slot (cap = none), flat key (cap * n_rules = none), hits,
    start_s, start_ns — one operand, so one host-to-device transfer.
    Run AFTER the evictions of the same maintenance run: a slot can be
    evicted and at once reassigned and restored between two apply steps,
    and the restored keys are stamped with the generation after the
    bump."""
    r_slots, r_keys, r_hits, r_ss, r_sns = rows
    cap = state.slot_gen.shape[0]
    r_gen = state.slot_gen[jnp.minimum(r_slots, cap - 1)]
    return dataclasses.replace(
        state,
        hits=state.hits.at[r_keys].set(r_hits, mode="drop"),
        start_s=state.start_s.at[r_keys].set(r_ss, mode="drop"),
        start_ns=state.start_ns.at[r_keys].set(r_sns, mode="drop"),
        key_gen=state.key_gen.at[r_keys].set(r_gen, mode="drop"),
        ip_seen=state.ip_seen.at[r_slots].set(True, mode="drop"),
    )


# a maintenance run's two steps have two carriers: a fused chunk's program
# traces `_evict` and `_restore` at its head (kernels/fused_match_window.py)
# and every other caller dispatches them as the programs below
_evict_step = jax.jit(_evict, donate_argnums=(0,))
_restore_step = jax.jit(_restore, donate_argnums=(0,))


jax.tree_util.register_dataclass(
    DeviceWindowState,
    data_fields=[
        "hits", "start_s", "start_ns", "key_gen", "slot_gen", "ip_seen",
    ],
    meta_fields=[],
)


@dataclasses.dataclass
class WindowEvent:
    """One applied (line, rule) window transition, in reference order."""

    line: int
    rule_id: int
    match_type: RateLimitMatchType
    exceeded: bool
    seen_ip: bool


@dataclasses.dataclass
class EventBatch:
    """One chunk's applied window transitions as arrays, in reference
    order ((line, rule_id) ascending).  At one event per log line a
    Python object per event is what the drain thread spends its time on,
    so the fused path keeps events columnar: the replay touches only the
    `exceeded` ones, and per-line results are built from these arrays
    when something reads them.  Iterating yields WindowEvents."""

    line: np.ndarray        # int32 [n]
    rule: np.ndarray        # int32 [n]
    match_type: np.ndarray  # uint8 [n]
    exceeded: np.ndarray    # bool [n]
    seen_ip: np.ndarray     # bool [n]

    @classmethod
    def concat(cls, a: "EventBatch", b: "EventBatch", b_line0: int):
        """`a` then `b`, `b`'s lines shifted by `b_line0` (two halves of
        one batch applied in order)."""
        return cls(
            line=np.concatenate([a.line, b.line + np.int32(b_line0)]),
            rule=np.concatenate([a.rule, b.rule]),
            match_type=np.concatenate([a.match_type, b.match_type]),
            exceeded=np.concatenate([a.exceeded, b.exceeded]),
            seen_ip=np.concatenate([a.seen_ip, b.seen_ip]),
        )

    def __len__(self) -> int:
        return len(self.line)

    def __getitem__(self, k: int) -> WindowEvent:
        return WindowEvent(
            line=int(self.line[k]),
            rule_id=int(self.rule[k]),
            match_type=RateLimitMatchType(int(self.match_type[k])),
            exceeded=bool(self.exceeded[k]),
            seen_ip=bool(self.seen_ip[k]),
        )

    def __iter__(self):
        return (self[k] for k in range(len(self.line)))


@dataclasses.dataclass
class Resolution:
    """What one pass over a batch's DISTINCT addresses found and did
    (DeviceWindows.resolve_addresses).  Index arrays index the address
    list the pass was given.  `ips` is that list as it came: strings, or
    a slotmgr.AddressSpans, which makes strings when one is read."""

    ips: Sequence[str]
    admit: np.ndarray              # bool [n] — the gate's verdict
    refused: np.ndarray            # int64 [r] — addresses the gate refused
    # uint32 [r] base hashes of the refused (what the sketch folds them
    # under); None when the gate asked the sketch about nothing
    refused_hashes: Optional[np.ndarray] = None
    # int32 [n]: the slot of every admitted address once `placed`, -1 for
    # a refused one; None when placement refused (every eviction
    # candidate pinned: the caller splits the batch, as ever)
    slots: Optional[np.ndarray] = None
    placed: bool = False
    # uint32 [n] base hashes of all addresses, from the pass's one
    # encoding (the sketch's slot table); None off the native path
    hashes: Optional[np.ndarray] = None
    # the pass's working state between its probe and its placement
    _enc: Optional[tuple] = None
    _seq: int = 0
    _miss_idx: Optional[np.ndarray] = None   # int64 [m], ascending
    _in_shadow: Optional[np.ndarray] = None  # bool [m]
    _in_warm: Optional[np.ndarray] = None    # bool [m]
    _sketch_admitted: Optional[np.ndarray] = None  # int64, into ips


class _StageTimedLock:
    """`DeviceWindows._lock`: a `threading.Lock` that counts what the
    pipeline's stages waited for it.  An acquire that finds the lock free
    costs one call more than the bare lock and reads no clock; one that
    finds it held times its blocking acquire and books it under the stage
    the waiting thread said it runs (`trace.stage_thread`: the
    scheduler's device thread says `submit`, its drain thread `drain`; a
    thread that said nothing is not counted) —
    `banjax_windows_lock_wait_seconds_total{stage}` and
    `banjax_windows_lock_contended_total{stage}`, whose quotient is the
    mean wait of one contention.  The tallies are written by the thread
    that has just taken the lock."""

    __slots__ = ("_lock", "wait_s", "contended")

    def __init__(self):
        self._lock = threading.Lock()
        self.wait_s = {"submit": 0.0, "drain": 0.0}
        self.contended = {"submit": 0, "drain": 0}

    def __enter__(self) -> None:
        if self._lock.acquire(False):
            return
        t0 = time.perf_counter()
        self._lock.acquire()
        waited = time.perf_counter() - t0
        stage = trace.thread_stage()
        if stage is not None:
            self.wait_s[stage] = self.wait_s.get(stage, 0.0) + waited
            self.contended[stage] = self.contended.get(stage, 0) + 1

    def __exit__(self, *exc) -> None:
        self._lock.release()


class DeviceWindows:
    """Device-resident RegexRateLimitStates with host slot management.

    Authoritative when `matcher_device_windows: true`; mirrors the host
    class's introspection surface (`get`, `format_states`, `__len__`) by
    pulling only the requested slots back from the device.
    """

    # auto-size memory budget: device state is 16 bytes per (slot, rule)
    # (hits, start_s, start_ns and key_gen, int32 each) plus 5 per slot
    # (slot_gen, ip_seen); cap the flat arrays well under the v5e-1's
    # 16 GB HBM so the matcher never squeezes the kernels' working set
    AUTO_START_CAPACITY = 16384
    AUTO_MEM_BUDGET_BYTES = 2 << 30

    def __init__(
        self,
        rules: Sequence[RegexWithRate],
        capacity: int = 16384,  # matcher_window_capacity; 0 = auto-size
        max_events: int = 4096,
        native_slotmgr: bool = True,
        warm_tier=None,             # pre-built tier object (tests inject)
        warm_tier_enabled: bool = False,
        warm_tier_capacity: int = 1 << 20,
    ):
        self.n_rules = max(1, len(rules))
        # capacity 0 = auto: start small, double on occupancy pressure
        # (observed distinct-IP rate) up to the memory budget — an eviction
        # is forced only once the budget ceiling is reached
        self.auto_grow = capacity <= 0
        if self.auto_grow:
            # the budget is a CEILING: a huge ruleset shrinks both the
            # ceiling and the start size (the 256-slot floor just keeps the
            # table functional); the start never exceeds the budget
            self.max_capacity = max(
                256, int(self.AUTO_MEM_BUDGET_BYTES // (16 * self.n_rules))
            )
            capacity = min(self.AUTO_START_CAPACITY, self.max_capacity)
        else:
            self.max_capacity = capacity
        self.grow_count = 0
        self.capacity = capacity
        # a single line can fire every rule; max_events >= n_rules makes the
        # overflow split terminate at B=1
        self.max_events = max(max_events, self.n_rules)
        self._lock = _StageTimedLock()

        limits = np.zeros(self.n_rules, dtype=np.int32)
        iv_s = np.zeros(self.n_rules, dtype=np.int32)
        iv_ns = np.zeros(self.n_rules, dtype=np.int32)
        iv_total = np.zeros(self.n_rules, dtype=np.int64)
        self._rule_names: List[str] = []
        for i, r in enumerate(rules):
            limits[i] = r.hits_per_interval
            iv_s[i], iv_ns[i] = divmod(int(r.interval_ns), _NS_PER_S)
            iv_total[i] = int(r.interval_ns)
            self._rule_names.append(r.rule)
        self._limits = jnp.asarray(limits)
        self._iv_s = jnp.asarray(iv_s)
        self._iv_ns = jnp.asarray(iv_ns)
        # host copies for the refused-row window apply (apply_host_events
        # replicates _window_step in exact int64 arithmetic)
        self._limits_np = limits
        self._iv_total_np = iv_total

        # --- mega-state tiering (warm tier + cold-tier admission) ---
        # Warm tier: evicted hot-tier state spills HERE (shadow entry
        # moves into the bounded shm table) instead of accumulating in
        # the unbounded host shadow; a returning IP refills
        # byte-identically on slot claim.  None = warm tier off — the
        # pre-tiering behavior (shadow keeps everything) is unchanged.
        self._warm = warm_tier
        if self._warm is None and warm_tier_enabled:
            from banjax_tpu.native.shm import create_warm_tier

            # steal horizon: twice the widest rule window — an entry
            # whose every window could have expired is semantically a
            # restart-as-first-seen, so stealing it loses nothing
            expiry = max(60 * _NS_PER_S, 2 * int(iv_total.max() or 0))
            self._warm = create_warm_tier(
                capacity=warm_tier_capacity,
                max_rules=self.n_rules,
                expiry_ns=expiry,
            )
        # the tier as the mirror can move records into and out of it in
        # C; None with the Python tier (shm creation failed, or a test
        # injects one), which takes them record by record
        from banjax_tpu.native.shm import ShmWarmTier

        self._warm_c = (
            self._warm if isinstance(self._warm, ShmWarmTier) else None
        )
        self.warm_spills = 0
        self.warm_refills = 0
        # Cold-tier admission bookkeeping (admission_mask): refused rows
        # are counted, never dropped — the runner still matches and
        # host-applies them.  FP accounting: a slot claimed on a sketch
        # estimate is marked; if its tenure ends with the IP having
        # matched nothing, the admission was a sketch overcount.
        self.slot_refusals = 0
        self.sketch_admissions = 0
        self.sketch_fp_evaluated = 0
        self.sketch_fp_count = 0
        self._sketch_pending: set = set()
        self._sketch_slots: Dict[int, bool] = {}
        # the submit stage's address resolution (resolve_addresses):
        # distinct addresses by what the pass found them to be, keys
        # handed to each table by any caller (the pass, admission_mask),
        # and batches whose gate verdict needed no sketch
        self.resolve_outcomes: Dict[str, int] = dict.fromkeys(
            ("hit", "shadow", "warm", "unseen", "refused"), 0
        )
        self.resolve_probes: Dict[str, int] = {"slots": 0, "warm": 0}
        self.gate_derived_batches = 0
        # passes, by the form their addresses came in and were worked on
        self.resolve_passes: Dict[str, int] = {"spans": 0, "strings": 0}

        self._slots: Dict[str, int] = {}  # ip → slot
        # batch-granular recency per slot (see slots_for_unique_ips)
        self._last_used = np.zeros(capacity, dtype=np.int64)
        self._batch_seq = 0
        self._slot_ip: Dict[int, str] = {}
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        # native slot manager (native/slotmgr.c): the whole per-distinct-
        # IP assignment loop — hash lookup, free-stack pop, LRU eviction —
        # runs as one C call per batch over the unique-IP array, with
        # exact Python-path parity (tests/unit/test_slotmgr.py).  The
        # dict loop below stays as the fallback (no C compiler) and the
        # differential oracle.  The manager is the one owner of slot→ip
        # where it is there (`_sm.keys_of`); _slot_ip, _slots and _free
        # are the dict path's and stay empty beside it.
        self._sm = None
        # the residents' shadow records by slot (native/shmstate.c sh_*):
        # there whenever the native manager is — the native placement
        # hands it the victims' keys and spans, so neither goes alone
        self._mirror = None
        self.slotmgr_native = False
        if native_slotmgr:
            from banjax_tpu.native import shm as _shm, slotmgr as _slotmgr

            sm = _slotmgr.create(capacity)
            mirror = None if sm is None else _shm.create_shadow_mirror(capacity)
            if mirror is not None:
                self._sm, self._mirror = sm, mirror
                self.slotmgr_native = True
        self._pending_evict: List[int] = []
        # returning addresses whose counters re-enter the device at the
        # next maintenance step.  Dict form: (slot, ip).  Native form:
        # (slots int32 [k], stamps int64 [k]) a placement — the stamp
        # names the record the restore was queued for
        self._pending_restore: list = []
        # slots handed out by slots_for_ips stay pinned until the matching
        # apply_bitmap consumes them, so a second caller's allocation can
        # never evict a slot whose events are still in flight
        self._pin_counts = np.zeros(capacity, dtype=np.int32)
        # spill-on-evict: the host shadow below keeps every counter, so
        # eviction only costs performance (a restore on re-admission), never
        # correctness; this counter surfaces the capacity pressure
        self.eviction_count = 0
        # maintenance runs, by what carried their steps to the device — a
        # fused chunk's program or dispatches of their own — and the int32
        # elements handed to the device by them (padding included): elems
        # / evictions says whether the step stayed O(evicted slots) — a
        # few, not a multiple of n_rules
        self.maintenance_carried = {"fused": 0, "own": 0}
        self.maintenance_elems = 0
        # window events committed by device applies (fused or classic)
        self.device_events = 0
        # ... and those of them whose rule belongs to one site: rule ids
        # below n_site_rules (the matcher lays per-site rules first)
        self.n_site_rules = 0
        self.site_events = 0
        # Host shadow of the device counters: ip → (rule_id → (hits, s, ns)),
        # both dicts in first-event insertion order — exactly the reference
        # host dict's shape (rate_limit.go:37-78, which never forgets).
        # Updated from every batch's event-final states (the scan computes
        # them anyway for the device write-back), so it costs O(events) host
        # work per batch, not a device pull. It is the authoritative source
        # for introspection (get/format_states/__len__) and the restore
        # source when an evicted IP is re-admitted. Memory is O(distinct
        # (ip, rule) pairs with events) — the reference's own asymptotic.
        # With the mirror this dict is the home of NON-resident records
        # that no tier took (warm tier off, a dropped put, a refused
        # row's state), each with the sequence stamp its record carries
        # in the mirror (_shadow_stamp), so format_states keeps one order.
        self._shadow: "Dict[str, OrderedDict]" = {}
        self._shadow_stamp: Dict[str, int] = {}
        # events absorbed and records spilled / refilled / restored, by
        # the form that handled them: "dict" stays 0 where the mirror does
        self.shadow_records: Dict[str, Dict[str, int]] = {
            op: {"native": 0, "dict": 0}
            for op in ("absorb", "spill", "refill", "restore")
        }
        self._state = self._fresh_state()

    def _fresh_state(self) -> DeviceWindowState:
        cap_r = self.capacity * self.n_rules
        return DeviceWindowState(
            hits=jnp.zeros((cap_r,), dtype=jnp.int32),
            start_s=jnp.zeros((cap_r,), dtype=jnp.int32),
            start_ns=jnp.zeros((cap_r,), dtype=jnp.int32),
            key_gen=jnp.zeros((cap_r,), dtype=jnp.int32),
            slot_gen=jnp.ones((self.capacity,), dtype=jnp.int32),
            ip_seen=jnp.zeros((self.capacity,), dtype=jnp.bool_),
        )

    # ---- slot management (host) ----

    def slot_for_ip(self, ip: str) -> Optional[int]:
        """Slot for one IP, or None if every slot is pinned by in-flight
        batches (transient: retry after those batches' apply_bitmap runs)."""
        slots = self.slots_for_ips([ip])
        if slots is None:
            return None
        self._release_pins(slots)  # lookup only — no apply_bitmap will follow
        return int(slots[0])

    def release_pins(self, slot_ids) -> None:
        """Release a batch's pins when apply_bitmap will NOT be called
        (apply_bitmap releases its own batch's pins on every path — call
        exactly one of the two, never both)."""
        self._release_pins(slot_ids)

    def slots_for_ips(self, ips: Sequence[str]) -> Optional[np.ndarray]:
        """Assign a slot per IP for one batch, atomically.

        Slots touched by THIS batch are pinned: evicting and reusing a slot
        mid-batch would fold two different IPs' counters into the same
        (slot, rule) keys in one scan. If an allocation would have to evict
        a pinned slot, returns None — the caller must split the batch.
        """
        # dedup first: batches repeat IPs heavily, and every per-line dict
        # touch (get + move_to_end + pin bookkeeping) at 65k lines costs
        # more than the device apply itself. One slot decision per DISTINCT
        # ip, then a vectorized gather back to line order. LRU semantics
        # are unchanged: each distinct ip is marked used once per batch
        # (intra-batch recency order among members is not observable).
        uniq: "OrderedDict[str, int]" = OrderedDict()
        inv = np.empty(len(ips), dtype=np.int32)
        for i, ip in enumerate(ips):
            k = uniq.get(ip)
            if k is None:
                k = len(uniq)
                uniq[ip] = k
            inv[i] = k
        uslots = self.slots_for_unique_ips(list(uniq))
        if uslots is None:
            return None
        return uslots[inv] if len(ips) else np.empty(0, dtype=np.int32)

    def slots_for_unique_ips(
        self, ips: Sequence[str]
    ) -> Optional[np.ndarray]:
        """slots_for_ips for a DISTINCT ip list (one slot decision + one
        pin per entry). Callers that already hold a unique table + inverse
        (the runner's vectorized gate) use this directly and gather.

        Recency is batch-granular: hits record this batch's sequence
        number in a vectorized `last_used` array (no per-hit order-list
        churn); eviction scans argmin(last_used) over evictable slots —
        O(capacity) but evictions are rare by design (auto-grow absorbs
        distinct-IP pressure first), and which victim is chosen is not a
        parity surface (spill is lossless either way — though the native
        manager reproduces the argmin victim exactly, so the parity fuzz
        can compare slot ids verbatim)."""
        with self._lock:
            if self._sm is not None:
                # the pass with no gate: probe, then place every miss
                res = self._probe_locked(ips, None, 1, None, False)
                self._place_locked(res)
                return res.slots
            self._batch_seq += 1
            out = np.empty(len(ips), dtype=np.int32)
            misses: List[int] = []
            get = self._slots.get
            for i, ip in enumerate(ips):
                slot = get(ip)
                if slot is None:
                    misses.append(i)
                    out[i] = -1
                else:
                    out[i] = slot
            if len(misses) < len(ips):
                hits = out[out >= 0]
                self._last_used[hits] = self._batch_seq
            for i in misses:
                ip = ips[i]
                if (
                    not self._free
                    and self.auto_grow
                    and self.capacity < self.max_capacity
                ):
                    self._grow_locked(
                        min(self.capacity * 2, self.max_capacity)
                    )
                if not self._free:
                    slot = self._evict_one_locked(out)
                    if slot is None:
                        return None  # every slot pinned
                else:
                    slot = self._free.pop()
                self._slots[ip] = slot
                self._slot_ip[slot] = ip
                self._last_used[slot] = self._batch_seq
                if self._sketch_pending and ip in self._sketch_pending:
                    self._sketch_pending.discard(ip)
                    self._sketch_slots[slot] = True
                if ip in self._shadow:
                    # previously-evicted IP returns: its counters re-enter
                    # the device in the next maintenance step, BEFORE any
                    # of this batch's events for it are applied
                    self._pending_restore.append((slot, ip))
                elif self._warm is not None and len(self._warm):
                    self._refill_from_warm_locked(slot, ip)
                out[i] = slot
            # out holds DISTINCT slots (distinct ips map to distinct
            # slots), so a vectorized increment pins each exactly once
            self._pin_counts[out] += 1
            return out

    def admission_mask(
        self,
        ips: Sequence[str],
        estimates: Optional[np.ndarray] = None,
        min_estimate: int = 1,
        counts: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Cold-tier slot admission over a DISTINCT ip list: bool [n],
        True = the IP may claim a hot-tier slot this batch.

        Admission order (first hit wins):
          1. already hot (slot assigned) — membership probe only, NO
             recency stamp, so a refused batch cannot refresh its probe
             victims' LRU position;
          2. known state elsewhere (host shadow or warm tier) — a
             returning IP always re-enters (the refill path needs the
             slot);
          3. unseen: admitted iff the traffic sketch plausibly puts it
             over the cheapest rule threshold (estimates[i] >=
             min_estimate).  The count-min estimate never undercounts,
             so a real offender is delayed at most min_estimate lines —
             never missed.

        `estimates=None` admits every unseen IP (admission off).
        `counts` (per-ip row counts) weights the refusal counter by
        rows, not distinct IPs.  Refused rows are NOT dropped — the
        runner matches them device-statelessly and applies their window
        transitions host-side (apply_host_events)."""
        n = len(ips)
        with self._lock:
            if n == 0:
                return np.zeros(0, dtype=bool)
            if self._sm is not None:
                admit = self._sm.contains_batch(ips)
            else:
                slots = self._slots
                admit = np.fromiter(
                    (ip in slots for ip in ips), dtype=bool, count=n
                )
            self.resolve_probes["slots"] += n
            unknown = np.flatnonzero(~admit)
            if len(unknown):
                shadow = self._shadow
                if shadow:
                    sh = np.fromiter(
                        (ips[int(i)] in shadow for i in unknown),
                        dtype=bool, count=len(unknown),
                    )
                    admit[unknown[sh]] = True
                    unknown = unknown[~sh]
            if (
                len(unknown)
                and self._warm is not None
                and len(self._warm)
            ):
                wm = self._warm.contains_batch(
                    [ips[int(i)] for i in unknown]
                )
                self.resolve_probes["warm"] += len(unknown)
                admit[unknown[wm]] = True
                unknown = unknown[~wm]
            if len(unknown):
                if estimates is None:
                    admit[unknown] = True
                else:
                    est_ok = (
                        np.asarray(estimates)[unknown]
                        >= min_estimate
                    )
                    admitted = unknown[est_ok]
                    admit[admitted] = True
                    for i in admitted:
                        self._sketch_pending.add(ips[int(i)])
                    self.sketch_admissions += int(est_ok.sum())
                    refused = unknown[~est_ok]
                    if counts is not None:
                        self.slot_refusals += int(
                            np.asarray(counts)[refused].sum()
                        )
                    else:
                        self.slot_refusals += len(refused)
            return admit

    def resolve_addresses(
        self,
        ips: Sequence[str],
        counts: Optional[np.ndarray] = None,
        min_estimate: int = 1,
        sketch=None,
        gate: bool = False,
    ) -> Resolution:
        """One pass over a batch's DISTINCT addresses: each is found hot
        (slot assigned; recency stamped, as slots_for_unique_ips does),
        in the host shadow, in the warm tier, or unseen; the slot-
        admission gate (`gate`; admission_mask's rules, read from these
        answers) gives its verdict; and the admitted misses are placed —
        one encoding of the addresses, one probe of the slot table, one
        of the warm tier over the misses alone.

        The sketch is asked only where its answer can matter: about
        unseen addresses, and not at all when `min_estimate` <= 1 (a
        distinct address of a batch has at least one row, so `estimate +
        rows >= 1` whatever the estimate).  `counts` are the per-address
        row counts of the batch.  `sketch` also says that the caller
        wants the addresses' base hashes (Resolution.hashes).

        A refused address is a miss that is not passed to placement: it
        leaves no recency stamp and claims no slot.  When any address is
        refused the pass returns BEFORE placing (Resolution.placed
        False): the caller applies the refused rows (apply_host_events
        homes their state in the warm tier, as it did between
        admission_mask and slots_for_unique_ips) and then calls
        place_resolved.  With nothing refused — every batch of a ruleset
        whose cheapest rule bans on the first hit — it is one call."""
        if self._sm is None:
            return self._resolve_dict(ips, counts, min_estimate, sketch, gate)
        with self._lock:
            res = self._probe_locked(ips, counts, min_estimate, sketch, gate)
            if not len(res.refused):
                self._place_locked(res)
        return res

    def probe_addresses(
        self,
        ips: Sequence[str],
        counts: Optional[np.ndarray] = None,
        min_estimate: int = 1,
        sketch=None,
    ) -> Resolution:
        """The pass's probe alone: the slot-admission gate's verdict over
        a batch's distinct addresses, nothing placed whatever it is —
        for a batch whose placement is not one call (its chunks place
        their own rows; the classic replay places at the drain).  An
        address admitted on the sketch's word waits in `_sketch_pending`
        for that placement, as after admission_mask."""
        if self._sm is None:
            return self._probe_dict(ips, counts, min_estimate, sketch, True)
        with self._lock:
            res = self._probe_locked(ips, counts, min_estimate, sketch, True)
            if res._sketch_admitted is not None:
                self._sketch_pending.update(
                    ips[i] for i in res._sketch_admitted.tolist()
                )
        return res

    def place_resolved(self, res: Resolution) -> None:
        """The placement a resolve_addresses with refused addresses left
        open (after the caller applied the refused rows)."""
        if res.placed:
            return
        if self._sm is None:
            self._place_dict(res)
            return
        with self._lock:
            self._place_locked(res)

    def _resolve_dict(self, ips, counts, min_estimate, sketch, gate):
        """resolve_addresses without the native manager: today's
        per-step calls (admission_mask, then the dict loop), which are
        also what the native pass is compared with."""
        res = self._probe_dict(ips, counts, min_estimate, sketch, gate)
        if not len(res.refused):
            self._place_dict(res)
        return res

    def _probe_dict(self, ips, counts, min_estimate, sketch, gate):
        n = len(ips)
        admit = np.ones(n, dtype=bool)
        hashes = None
        with self._lock:
            self.resolve_passes["strings"] += 1
        if gate and n:
            if sketch is not None and min_estimate > 1:
                hashes = sketch.base_hashes(ips)
                est = sketch.estimate_ips(ips, hashes=hashes) + (
                    1 if counts is None else counts
                )
                admit = self.admission_mask(
                    ips, estimates=est, min_estimate=min_estimate,
                    counts=counts,
                )
            else:
                with self._lock:
                    self.gate_derived_batches += 1
        refused = np.flatnonzero(~admit)
        return Resolution(
            ips=ips, admit=admit, refused=refused,
            refused_hashes=None if hashes is None else hashes[refused],
        )

    def _place_dict(self, res: Resolution) -> None:
        res.placed = True
        if not len(res.refused):
            res.slots = self.slots_for_unique_ips(res.ips)
            return
        adm = np.flatnonzero(res.admit)
        got = self.slots_for_unique_ips([res.ips[i] for i in adm.tolist()])
        if got is not None:
            res.slots = np.full(len(res.ips), -1, dtype=np.int32)
            res.slots[adm] = got

    def _probe_locked(self, ips, counts, min_estimate, sketch, gate):
        """The pass's first half (native manager; caller holds the lock):
        take the addresses' one encoding — the byte spans they came as
        (AddressSpans), or one made of the strings here — look every
        address up in the slot table (hits stamped), ask the shadow and
        the warm tier about the misses, and read the gate's verdict from
        the answers.  A string is made of an address only where a dict
        is asked about it (a non-empty dict shadow, the sketch's estimate
        of an unseen one)."""
        from banjax_tpu.native.slotmgr import (
            AddressSpans, crc32_spans, encode_ips,
        )

        n = len(ips)
        self._batch_seq += 1
        seq = self._batch_seq
        admit = np.ones(n, dtype=bool)
        refused = np.empty(0, dtype=np.int64)
        hashes = None
        if isinstance(ips, AddressSpans):
            enc, hashes = ips.enc, ips.hashes
            self.resolve_passes["spans"] += 1
        else:
            enc = encode_ips(ips)
            self.resolve_passes["strings"] += 1
        slots, miss_idx, _ = self._sm.lookup_batch(
            ips, seq, self._last_used, enc=enc
        )
        res = Resolution(
            ips=ips, admit=admit, refused=refused, slots=slots,
            _enc=enc, _seq=seq, _miss_idx=miss_idx,
        )
        if sketch is not None and n:
            res.hashes = crc32_spans(enc) if hashes is None else hashes
        m = len(miss_idx)
        self.resolve_probes["slots"] += n
        tally = self.resolve_outcomes
        tally["hit"] += n - m
        in_shadow = np.zeros(m, dtype=bool)
        in_warm = np.zeros(m, dtype=bool)
        asked_sketch = False
        if m:
            shadow = self._shadow
            if shadow:
                in_shadow = np.fromiter(
                    (ips[i] in shadow for i in miss_idx.tolist()),
                    dtype=bool, count=m,
                )
            warm = self._warm
            if warm is not None and len(warm):
                ask = np.flatnonzero(~in_shadow)
                if len(ask):
                    at = miss_idx[ask]
                    in_warm[ask] = warm.contains_batch(
                        None, spans=(enc[0], enc[1][at], enc[2][at])
                    )
                    self.resolve_probes["warm"] += len(ask)
            unseen = miss_idx[~(in_shadow | in_warm)]
            if gate and len(unseen) and sketch is not None \
                    and min_estimate > 1:
                # admission rule 3: an unseen address claims a slot iff
                # the sketch plausibly puts it over the cheapest rule's
                # threshold, this batch's rows included
                asked_sketch = True
                h_u = res.hashes[unseen]
                rows = 1 if counts is None else np.asarray(counts)[unseen]
                ok = sketch.estimate_ips(
                    [ips[i] for i in unseen.tolist()], hashes=h_u
                ) + rows >= min_estimate
                res._sketch_admitted = unseen[ok]
                self.sketch_admissions += int(ok.sum())
                refused = unseen[~ok]
                if len(refused):
                    admit[refused] = False
                    res.refused = refused
                    res.refused_hashes = h_u[~ok]
                    self.slot_refusals += int(
                        len(refused) if counts is None
                        else np.asarray(counts)[refused].sum()
                    )
            tally["shadow"] += int(in_shadow.sum())
            tally["warm"] += int(in_warm.sum())
            tally["unseen"] += len(unseen) - len(refused)
            tally["refused"] += len(refused)
        if gate and not asked_sketch:
            self.gate_derived_batches += 1
        res._in_shadow = in_shadow
        res._in_warm = in_warm
        return res

    def _place_locked(self, res: Resolution) -> None:
        """The pass's second half (caller holds the lock): the Python
        growth chain, one C placement of the admitted misses (free stack,
        then the oldest evictable slots off the manager's kept order), the
        evicted addresses' spills in one warm-tier call, the returning ones'
        refills in one more, and the pins.  No Python object is made per
        address: slot -> address is the manager's to know."""
        res.placed = True
        sm = self._sm
        slots = res.slots
        place_idx = res._miss_idx
        in_shadow, in_warm = res._in_shadow, res._in_warm
        if len(res.refused):
            keep = res.admit[place_idx]
            place_idx = place_idx[keep]
            in_shadow, in_warm = in_shadow[keep], in_warm[keep]
        n_miss = len(place_idx)
        if n_miss:
            # replicate the dict path's per-miss doubling chain: grow
            # while the free pool cannot absorb the remaining misses and
            # the ceiling allows — the same final capacity the
            # grow-on-empty loop reaches
            new_cap = self.capacity
            free_cnt = new_cap - sm.assigned()
            steps = 0
            while (
                free_cnt < n_miss
                and self.auto_grow
                and new_cap < self.max_capacity
            ):
                step = min(new_cap * 2, self.max_capacity)
                free_cnt += step - new_cap
                new_cap = step
                steps += 1
            if new_cap != self.capacity:
                self._grow_locked(new_cap)
                # one coalesced realloc, but DeviceWindowsGrows counts
                # logical doublings — keep the metric comparable with the
                # dict path's grow-per-miss loop
                self.grow_count += steps - 1
        placed_idx, evicted, ev_keys, ok = sm.place_misses(
            res._enc, slots, place_idx, res._seq, self._pin_counts,
            self._last_used,
        )
        if len(evicted):
            self._note_evictions_locked(evicted, ev_keys)
            self._pending_evict.extend(evicted.tolist())
            if self.eviction_count == 0:
                self._warn_first_eviction()
            self.eviction_count += len(evicted)
        n_placed = len(placed_idx)
        if n_placed:
            ips = res.ips
            slot_a = slots[placed_idx]
            pend_sketch = self._sketch_pending
            if pend_sketch:  # admitted by an admission_mask call
                for slot, i in zip(slot_a.tolist(), placed_idx.tolist()):
                    if ips[i] in pend_sketch:
                        pend_sketch.discard(ips[i])
                        self._sketch_slots[slot] = True
            # returning addresses, in placement order (placed_idx is a
            # prefix of place_idx: placement goes in ip order and stops
            # at a refusal): a warm resident's record moves from the
            # tier into the mirror at its new slot, all in one call, and
            # one waiting in the dict likewise; their counters re-enter
            # the device in the next maintenance step, BEFORE any of
            # this batch's events for them are applied
            stamps = np.zeros(n_placed, dtype=np.int64)
            w_pos = np.flatnonzero(in_warm[:n_placed])
            if len(w_pos):
                self._refill_locked(
                    res._enc, placed_idx, slot_a, ips, w_pos, stamps
                )
            for k in np.flatnonzero(in_shadow[:n_placed]).tolist():
                ip = ips[int(placed_idx[k])]
                vec = self._shadow.pop(ip, None)
                stamp = self._shadow_stamp.pop(ip, 0)
                if vec:
                    stamps[k] = self._mirror.install(
                        int(slot_a[k]), vec, stamp)
            back = np.flatnonzero(stamps)
            if len(back):
                self._pending_restore.append((slot_a[back], stamps[back]))
        sa = res._sketch_admitted
        if sa is not None and len(sa):
            # sketch-admitted tenures are FP-evaluated at eviction; one
            # the placement did not reach waits in _sketch_pending for
            # the caller's retry, as after admission_mask
            for i, slot in zip(sa.tolist(), slots[sa].tolist()):
                if slot >= 0:
                    self._sketch_slots[slot] = True
                else:
                    self._sketch_pending.add(res.ips[i])
        if not ok:
            res.slots = None  # every eviction candidate pinned — split
            return
        self._pin_counts[
            slots[res.admit] if len(res.refused) else slots
        ] += 1

    def _warn_first_eviction(self) -> None:
        import logging

        hint = (
            "auto-size hit its memory-budget ceiling — "
            "more HBM or fewer rules would raise it"
            if self.auto_grow else
            "raise matcher_window_capacity (or set 0 = "
            "auto-size) to avoid the churn"
        )
        logging.getLogger(__name__).warning(
            "device-windows capacity (%d slots) exceeded; "
            "evicting LRU IP state to the host shadow "
            "(restored on re-admission — %s)",
            self.capacity, hint,
        )

    def _evict_one_locked(self, batch_slots: np.ndarray) -> Optional[int]:
        """Pick and evict the oldest evictable slot: assigned, not pinned
        by an in-flight batch, and not already handed to THIS batch
        (reusing one mid-batch would fold two IPs' counters together)."""
        used = np.full(self.capacity, np.iinfo(np.int64).max, dtype=np.int64)
        assigned = list(self._slot_ip)
        used[assigned] = self._last_used[assigned]
        used[self._pin_counts > 0] = np.iinfo(np.int64).max
        mine = batch_slots[batch_slots >= 0]
        if mine.size:
            used[mine] = np.iinfo(np.int64).max
        victim = int(np.argmin(used))
        if used[victim] == np.iinfo(np.int64).max:
            return None
        victim_ip = self._slot_ip.pop(victim)
        self._slots.pop(victim_ip)
        self._note_eviction_locked(victim, victim_ip)
        self._pending_evict.append(victim)
        if self.eviction_count == 0:
            self._warn_first_eviction()
        self.eviction_count += 1
        return victim

    def _note_eviction_locked(self, slot: int, ip: Optional[str]) -> None:
        """Tiering bookkeeping at hot-tier eviction: FP-evaluate a
        sketch-admitted tenure (no state at eviction = the sketch
        overcounted) and spill the victim's shadow entry into the warm
        tier.  On a warm-tier drop (probe window full of live records)
        the shadow KEEPS the entry — pre-tiering lossless behavior; the
        tier's `dropped` counter surfaces the sizing pressure."""
        if self._sketch_slots.pop(slot, False):
            self.sketch_fp_evaluated += 1
            if ip is None or ip not in self._shadow:
                self.sketch_fp_count += 1
        if self._warm is None or ip is None:
            return
        od = self._shadow.get(ip)
        if not od:
            return
        entries = [(rid, h, s, ns) for rid, (h, s, ns) in od.items()]
        if self._warm.put(ip, entries, time.time_ns()):
            del self._shadow[ip]
            self.warm_spills += 1
            self.shadow_records["spill"]["dict"] += 1

    def _note_evictions_locked(self, slots: np.ndarray, keys) -> None:
        """_note_eviction_locked for all of one native placement's
        victims (slots int64 [k], eviction order; `keys` = the slot
        manager's evict_keys): the records of those that hold one move
        from the mirror into the warm tier in ONE C call.  A record the
        tier did not take — a dropped put, the warm tier off, a tier
        that is not the C table — moves into the dict, keyed by its
        address, which is read off the manager's keys then: the state
        stays on the host, as ever."""
        status = self._mirror.spill(
            self._warm_c, slots, keys, time.time_ns()
        )
        if self._sketch_slots:
            took = self._sketch_slots.pop
            for slot, held in zip(slots.tolist(), status.tolist()):
                if took(slot, False):
                    self.sketch_fp_evaluated += 1
                    if not held:
                        self.sketch_fp_count += 1
        landed = int(np.count_nonzero(status == 1))
        self.warm_spills += landed
        self.shadow_records["spill"]["native"] += landed
        left = np.flatnonzero(status == 2)
        if not len(left):
            return
        warm = self._warm if self._warm_c is None else None  # a Python tier
        ips = self._sm.evicted_keys(keys)
        stamps, vecs = self._mirror.export(slots[left], drop=True)
        for k, stamp, vec in zip(left.tolist(), stamps, vecs):
            ip = ips[k]
            if warm is not None and warm.put(
                ip, [(r, h, s, ns) for r, (h, s, ns) in vec.items()],
                time.time_ns(),
            ):
                self.warm_spills += 1
                self.shadow_records["spill"]["dict"] += 1
                continue
            self._shadow[ip] = vec
            self._shadow_stamp[ip] = stamp

    def _refill_locked(self, enc, placed_idx, slot_a, ips, w_pos, stamps):
        """The warm residents among one native placement's addresses
        (positions w_pos of the placed) take their records back: tier →
        mirror at the new slot in ONE C call; stamps[w_pos] names the
        records made (0 where the tier had none after all)."""
        if self._warm_c is None:  # no C table to move records out of
            for k in w_pos.tolist():
                ent = self._warm.take(ips[int(placed_idx[k])])
                if ent is not None:
                    stamps[k] = self._mirror.install(
                        int(slot_a[k]),
                        OrderedDict((r, (h, s, ns)) for r, h, s, ns in ent),
                    )
            path = "dict"
        else:
            at = placed_idx[w_pos]
            stamps[w_pos] = self._mirror.refill(
                self._warm_c, slot_a[w_pos], (enc[0], enc[1][at], enc[2][at])
            )
            path = "native"
        got = int(np.count_nonzero(stamps[w_pos]))
        self.warm_refills += got
        self.shadow_records["refill"][path] += got

    def _refill_from_warm_locked(self, slot: int, ip: str) -> bool:
        """Move one IP's window vector warm → shadow and queue the device
        restore (the same next-maintenance path a shadow hit takes, so
        the counters re-enter the device BEFORE any of this batch's
        events for the IP)."""
        ent = self._warm.take(ip)
        if ent is None:
            return False
        self._shadow[ip] = OrderedDict(
            (rid, (h, s, ns)) for rid, h, s, ns in ent
        )
        self._pending_restore.append((slot, ip))
        self.warm_refills += 1
        self.shadow_records["refill"]["dict"] += 1
        return True

    def _grow_locked(self, new_capacity: int) -> None:
        """Double the slot table in place (auto-size): pad the flat device
        arrays (zeros; `slot_gen` with its fresh value 1, so every new key
        reads invalid) and free-list the new high slots. Existing slot
        ids, pending evictions/restores, and the shadow (the mirror gets
        empty rows for the new slots) are untouched; the
        only cost is one recompile of the apply programs at the new state
        shape (geometric growth bounds that to ~log2(max/start) compiles
        over the process lifetime)."""
        old_cap = self.capacity
        add = new_capacity - old_cap
        if add <= 0:
            return
        s = self._state
        pad_r = add * self.n_rules
        self._state = DeviceWindowState(
            hits=jnp.concatenate([s.hits, jnp.zeros(pad_r, jnp.int32)]),
            start_s=jnp.concatenate([s.start_s, jnp.zeros(pad_r, jnp.int32)]),
            start_ns=jnp.concatenate(
                [s.start_ns, jnp.zeros(pad_r, jnp.int32)]
            ),
            key_gen=jnp.concatenate([s.key_gen, jnp.zeros(pad_r, jnp.int32)]),
            slot_gen=jnp.concatenate([s.slot_gen, jnp.ones(add, jnp.int32)]),
            ip_seen=jnp.concatenate(
                [s.ip_seen, jnp.zeros(add, jnp.bool_)]
            ),
        )
        # pop() takes from the end: keep existing (lower) slots there so
        # allocation order is unchanged; new high slots drain last (the
        # native manager's free stack replicates the same order)
        if self._sm is not None:
            self._sm.grow(new_capacity)
            self._mirror.grow(new_capacity)
        else:
            self._free = (
                list(range(new_capacity - 1, old_cap - 1, -1)) + self._free
            )
        self._last_used = np.concatenate(
            [self._last_used, np.zeros(add, dtype=np.int64)]
        )
        self._pin_counts = np.concatenate(
            [self._pin_counts, np.zeros(add, dtype=np.int32)]
        )
        self.capacity = new_capacity
        self.grow_count += 1
        import logging

        logging.getLogger(__name__).info(
            "device-windows auto-grow: %d -> %d slots (distinct-IP "
            "pressure; ceiling %d)",
            old_cap, new_capacity, self.max_capacity,
        )

    def _release_pins(self, slot_ids) -> None:
        with self._lock:
            # np.unique, not set(tolist()): per-line slot arrays repeat
            # heavily; one vectorized decrement per distinct slot
            uniq = np.unique(np.asarray(slot_ids, dtype=np.int64))
            self._pin_counts[uniq] -= 1
            np.maximum(self._pin_counts, 0, out=self._pin_counts)

    @property
    def occupancy(self) -> int:
        """IP slots currently assigned (capacity-pressure gauge)."""
        with self._lock:
            if self._sm is not None:
                return self._sm.assigned()
            return len(self._slot_ip)

    def slot_addresses(self) -> Dict[int, str]:
        """slot -> address of every assigned slot, whichever form owns
        the table: the native manager's keys, or the dict path's own
        mirror.  Introspection; no window's."""
        with self._lock:
            if self._sm is None:
                return dict(self._slot_ip)
            slots = np.sort(self._sm.order())
            return dict(zip(slots.tolist(), self._sm.keys_of(slots)))

    @property
    def eviction_scanned_slots(self) -> int:
        """Slots the native placements read to find their victims (the
        members of every run of the kept order they sorted included);
        0 on the dict path, whose argmin reads the whole table."""
        return self._sm.scanned() if self._sm is not None else 0

    def clear(self) -> None:
        """Hot-reload semantics: drop all counters (decision.go Clear analog)."""
        with self._lock:
            self._slots.clear()
            self._slot_ip.clear()
            self._shadow.clear()
            self._shadow_stamp.clear()
            if self._sm is not None:
                self._sm.clear()
                self._mirror.clear()
            else:
                self._free = list(range(self.capacity - 1, -1, -1))
            self._pending_evict = []
            self._pending_restore = []
            self._pin_counts = np.zeros(self.capacity, dtype=np.int32)
            self._last_used = np.zeros(self.capacity, dtype=np.int64)
            if self._warm is not None:
                self._warm.clear()
            self._sketch_pending.clear()
            self._sketch_slots.clear()
            # the old table goes before the new one is made: at 10,000
            # rules x 65,536 slots one is 10.5 GB of a 16 GB chip
            for leaf in jax.tree_util.tree_leaves(self._state):
                leaf.delete()
            self._state = self._fresh_state()

    def __len__(self) -> int:
        # parity with RegexRateLimitStates.__len__: IPs with any state —
        # including evicted ones (the reference never forgets; the
        # mirror's, the dict's and the warm tier's populations are
        # disjoint by construction)
        with self._lock:
            warm = len(self._warm) if self._warm is not None else 0
            held = len(self._mirror) if self._mirror is not None else 0
            return held + len(self._shadow) + warm

    def lock_waits(self) -> Dict[str, Tuple[float, int]]:
        """{stage: (seconds waited for the windows lock, acquires that
        found it held)} — see _StageTimedLock."""
        lk = self._lock
        return {stage: (lk.wait_s[stage], lk.contended.get(stage, 0))
                for stage in list(lk.wait_s)}

    # ---- tier gauges (obs/stats.py snapshot surface) ----

    @property
    def warm_occupancy(self) -> int:
        return len(self._warm) if self._warm is not None else 0

    @property
    def warm_capacity(self) -> int:
        return int(self._warm.capacity) if self._warm is not None else 0

    @property
    def warm_dropped(self) -> int:
        return int(self._warm.dropped) if self._warm is not None else 0

    @property
    def warm_bytes_written(self) -> int:
        """Bytes the tier's puts wrote (128 a record + 24 a counter + 8 a
        further block, in either implementation)."""
        return int(getattr(self._warm, "bytes_written", 0))

    @property
    def table_bytes(self) -> int:
        """Device bytes of the window table: 16 a (slot, rule) — hits,
        start_s, start_ns and key_gen, int32 each — and 5 a slot."""
        return self.capacity * (16 * self.n_rules + 5)

    @property
    def warm_probes(self) -> int:
        # the C table's own counts; the Python fallback has no records
        # apart from its dict and reports 0 for both
        return int(getattr(self._warm, "probes", 0))

    @property
    def warm_record_reads(self) -> int:
        return int(getattr(self._warm, "record_reads", 0))

    @property
    def sketch_admission_fp_rate(self) -> float:
        """Of sketch-admitted slots whose tenure ENDED (evicted), the
        fraction that never matched any rule — the realized cost of
        count-min overcounting, measurable without ground truth."""
        if not self.sketch_fp_evaluated:
            return 0.0
        return self.sketch_fp_count / self.sketch_fp_evaluated

    # ---- the batch step ----

    def apply_bitmap(
        self,
        bits,                      # [B, R] device or host array
        slot_ids: np.ndarray,      # [B] int32
        ts_s: np.ndarray,
        ts_ns: np.ndarray,
        active_table,              # [H, R] bool (device-resident, cached by caller)
        host_idx: np.ndarray,      # [B] int32 — row of active_table per line
    ) -> EventBatch:
        """Apply one batch; returns the events in reference order.

        The event count is checked BEFORE any state mutation; a batch with
        more matched events than max_events is split in half and each half
        applied in order (a single line can produce at most n_rules events,
        so max_events >= n_rules guarantees termination). On return (even on
        error) the batch's slot pins from slots_for_ips are released."""
        try:
            return self._apply_bitmap_inner(
                bits, slot_ids, ts_s, ts_ns, active_table, host_idx
            )
        finally:
            self._release_pins(slot_ids)

    def _apply_bitmap_inner(
        self, bits, slot_ids, ts_s, ts_ns, active_table, host_idx
    ) -> EventBatch:
        bits = jnp.asarray(bits)
        active_table = jnp.asarray(active_table)
        host_idx = np.asarray(host_idx, dtype=np.int32)

        # bucket B up to a power of two so _count_events/_apply_step compile
        # once per bucket, not once per batch size (pad rows fire no events)
        B = bits.shape[0]
        Bp = _bucket_rows(B)
        if Bp != B:
            bits = jnp.pad(bits, ((0, Bp - B), (0, 0)))
            slot_ids = np.pad(np.asarray(slot_ids, dtype=np.int32), (0, Bp - B))
            ts_s = np.pad(np.asarray(ts_s, dtype=np.int32), (0, Bp - B))
            ts_ns = np.pad(np.asarray(ts_ns, dtype=np.int32), (0, Bp - B))
            host_idx = np.pad(host_idx, (0, Bp - B))

        n = _count_events(bits, active_table, jnp.asarray(host_idx))
        if int(n) > self.max_events:
            mid = B // 2
            ev1 = self._apply_bitmap_inner(
                bits[:mid], slot_ids[:mid], ts_s[:mid], ts_ns[:mid],
                active_table, host_idx[:mid],
            )
            ev2 = self._apply_bitmap_inner(
                bits[mid:B], slot_ids[mid:B], ts_s[mid:B], ts_ns[mid:B],
                active_table, host_idx[mid:B],
            )
            return EventBatch.concat(ev1, ev2, mid)

        with self._lock:
            self._run_maintenance_locked()
            new_state, out = _apply_step(
                self._state,
                bits,
                active_table,
                jnp.asarray(host_idx),
                jnp.asarray(slot_ids, dtype=jnp.int32),
                jnp.asarray(ts_s, dtype=jnp.int32),
                jnp.asarray(ts_ns, dtype=jnp.int32),
                self._limits,
                self._iv_s,
                self._iv_ns,
                n_rules=self.n_rules,
                max_events=self.max_events,
            )
            self._state = new_state

            # The event pull AND the shadow update stay inside THIS lock
            # window: with two concurrent batches, writing the shadow in a
            # later acquisition could land the batches' final states in the
            # opposite order of their device application, and an eviction
            # would then restore the stale value as authoritative.
            line = np.asarray(out["line"])
            rule = np.asarray(out["rule"])
            mtype = np.asarray(out["match_type"])
            exceeded = np.asarray(out["exceeded"])
            seen = np.asarray(out["seen_ip"])
            f_hits = np.asarray(out["hits"])
            f_ss = np.asarray(out["start_s"])
            f_sns = np.asarray(out["start_ns"])
            # reference order: by (line, rule_id) — per-site ids precede global
            live = np.flatnonzero(rule >= 0)
            live = live[np.lexsort((rule[live], line[live]))]
            self._absorb_events_locked(
                slot_ids, line[live], rule[live], f_hits[live], f_ss[live],
                f_sns[live],
            )
        return EventBatch(
            line=line[live], rule=rule[live],
            match_type=mtype[live].astype(np.uint8),
            exceeded=exceeded[live] != 0, seen_ip=seen[live] != 0,
        )

    def _absorb_events_locked(
        self, slot_ids, line, rule, hits, ss, sns
    ) -> None:
        """Fold one applied chunk's per-event final counter states into
        the host shadow (caller holds the lock; the arrays hold live
        events only, in (line, rule) order).  That is the reference's
        processing order, so a record's counters and the records
        themselves keep the host path's first-matched-event order
        (format_states parity; slot numbering follows batch appearance,
        which can differ).  Each (ip, rule)'s last write is still its
        chronologically-last event, i.e. the segment-final state written
        on device.  Native form: one C call, the slot is the key.  Dict
        form: one dict store per event."""
        self.device_events += len(line)
        if not len(line):
            return
        if self.n_site_rules:
            self.site_events += int(np.count_nonzero(rule < self.n_site_rules))
        if self._mirror is not None:
            self.shadow_records["absorb"]["native"] += self._mirror.absorb(
                slot_ids, self._last_used, line, rule, hits, ss, sns
            )
            return
        slot_ip = self._slot_ip
        shadow = self._shadow
        taken = 0
        for slot, rid, h, s, ns in zip(
            np.asarray(slot_ids)[line].tolist(), rule.tolist(),
            hits.tolist(), ss.tolist(), sns.tolist(),
        ):
            ip = slot_ip.get(slot)
            if ip is None:  # unreachable while the batch is pinned
                continue
            od = shadow.get(ip)
            if od is None:
                od = shadow[ip] = OrderedDict()
            od[rid] = (h, s, ns)
            taken += 1
        self.shadow_records["absorb"]["dict"] += taken

    def _run_maintenance_locked(self, carry_rows: Optional[int] = None):
        """Drain queued evictions, then restores, into the device state
        (caller holds the lock).  What goes to the device is two int32 per
        evicted slot and five per restored key — never a per-(slot, rule)
        expansion; padded entries scatter out of range and drop.

        Two carriers, chosen by what the caller has.  A caller about to
        dispatch a fused chunk of `carry_rows` rows gets the queued work
        back as that program's two maintenance operands — (ev_slots int32
        [carry_rows], restore rows int32 [5, _restore_room(carry_rows)]),
        all padding when nothing is queued — and the program runs `_evict`
        and `_restore` on them at its head, outside its commit gate: no
        dispatch and no transfer of their own.  Every other caller (the
        classic apply, a test) gets them run here, as `_evict_step` over
        the evicted slots padded to a power of two (five classes up to a
        4,096-line batch) and `_restore_step` over the restored keys in
        chunks of _RESTORE_CHUNK (one program), so the jit cache stays
        bounded.  So does a run that does not fit a chunk's operands —
        more evicted slots than its rows, more live keys than its room —
        whole, in front of the chunk's dispatch, which then carries
        padding."""
        queued = bool(self._pending_evict or self._pending_restore)
        if not queued and carry_rows is None:
            return None
        room = _RESTORE_CHUNK if carry_rows is None else _restore_room(
            carry_rows)
        pend_ev, self._pending_evict = self._pending_evict, []
        rows = self._restore_rows_locked(self._pending_restore, room)
        self._pending_restore = []
        rides = (
            carry_rows is not None and len(pend_ev) <= carry_rows
            and len(rows) <= 1
        )
        if queued:
            self.maintenance_carried["fused" if rides else "own"] += 1
            if rides:
                self.maintenance_elems += 2 * carry_rows + 5 * room
        if not rides:
            ks = _bucket(len(pend_ev), _MIN_MAINT_BUCKET) if pend_ev else 0
            self._maintenance_steps_locked(self._ev_slots(pend_ev, ks), rows)
            pend_ev, rows = [], rows[:0]
        if carry_rows is None:
            return None
        return self._ev_slots(pend_ev, carry_rows), (
            rows[0] if len(rows) else self._no_restore(room))

    def _ev_slots(self, evicted, size: int) -> np.ndarray:
        """`evicted` slots as an evict operand of `size`, padded with the
        slot that is none."""
        out = np.full((size,), self.capacity, dtype=np.int32)
        out[: len(evicted)] = evicted
        return out

    def _no_restore(self, room: int) -> np.ndarray:
        """Restore rows for `room` keys that restore nothing: all padding."""
        rows = np.zeros((5, room), dtype=np.int32)
        rows[0] = self.capacity
        rows[1] = self.capacity * self.n_rules
        return rows

    def _maintenance_steps_locked(self, ev_slots: np.ndarray, rows) -> None:
        """The evict step over `ev_slots` (none when empty), then a
        restore step for each _RESTORE_CHUNK keys of `rows` ([c, 5, whole
        chunks]; a chunk that is all padding is left out), as dispatches
        of their own (caller holds the lock): a maintenance run without a
        fused chunk to carry it — or one whose chunk's dispatch failed
        before it ran."""
        if len(ev_slots):
            self.maintenance_elems += 2 * len(ev_slots)
            self._state = _evict_step(self._state, jnp.asarray(ev_slots))
            trace.runtime_calls(2)  # the transfer, the step
        parts = rows.reshape(
            len(rows), 5, rows.shape[2] // _RESTORE_CHUNK, _RESTORE_CHUNK
        ).swapaxes(1, 2).reshape(-1, 5, _RESTORE_CHUNK)
        for part in parts[(parts[:, 0] < self.capacity).any(axis=1)]:
            self.maintenance_elems += part.size
            self._state = _restore_step(self._state, jnp.asarray(part))
            trace.runtime_calls(2)

    def build_maintenance_steps(self, max_rows: int) -> None:
        """Build the separate steps' programs a run of up to `max_rows`
        evicted slots dispatches — every evict class up to it, the one
        restore chunk — by running each on padding, which leaves the table
        as it was.  For whoever builds a fused program of that many rows:
        with the fused carrier the separate steps are the rare way, and
        the first run that takes it must not find its programs unbuilt
        minutes into a stream."""
        ks = _MIN_MAINT_BUCKET
        sizes = [ks]
        while ks < max_rows:
            ks <<= 1
            sizes.append(ks)
        with self._lock:
            for ks in sizes:
                self._state = _evict_step(self._state, self._ev_slots((), ks))
            self._state = _restore_step(
                self._state, self._no_restore(_RESTORE_CHUNK))
        trace.runtime_calls(len(sizes) + 1)

    @property
    def maintenance_steps(self) -> int:
        """Maintenance runs that had work, whatever carried them."""
        return sum(self.maintenance_carried.values())

    def _restore_rows_locked(self, pending, kr: int) -> np.ndarray:
        """int32 [c, 5, kr]: the counters of the queued restores that are
        still live, as `_restore` takes them — (slot, flat key, hits,
        start_s, start_ns) a column, `kr` keys a chunk, padded out of
        range.  Read NOW, at the maintenance step, not when the
        restore was queued: an earlier in-flight chunk's absorb may have
        landed in between.  A restore is stale once its slot was
        re-evicted (and possibly reassigned to a DIFFERENT address) —
        scattering the old address's counters would resurrect them into
        the new owner's rows: the dict form knows by the slot's owner,
        the mirror by the record's stamp."""
        cap_r = self.capacity * self.n_rules
        if self._mirror is not None:
            if not pending:
                return np.zeros((0, 5, kr), dtype=np.int32)
            rows, records = self._mirror.restore_rows(
                np.concatenate([p[0] for p in pending]),
                np.concatenate([p[1] for p in pending]),
                self.n_rules, kr, self.capacity, cap_r,
            )
            self.shadow_records["restore"]["native"] += records
            return rows
        restored: List[Tuple[int, int, int, int, int]] = []
        for slot, ip in pending:
            od = self._shadow.get(ip) if self._slot_ip.get(slot) == ip else None
            if not od:
                continue
            self.shadow_records["restore"]["dict"] += 1
            base = slot * self.n_rules
            for rid, (h, s, ns) in od.items():
                restored.append((slot, base + rid, h, s, ns))
        rows = np.zeros((-(-len(restored) // kr), 5, kr), dtype=np.int32)
        rows[:, 0] = self.capacity
        rows[:, 1] = cap_r
        for c in range(len(rows)):
            part = restored[c * kr : (c + 1) * kr]
            rows[c, :, : len(part)] = np.asarray(part, dtype=np.int32).T
        return rows

    # ---- refused-row host apply (cold-tier path) ----

    def apply_host_events(
        self, events: Sequence[Tuple[int, int, str, int]]
    ) -> EventBatch:
        """Window transitions for REFUSED rows — the slot-admission
        gate's classic per-line path.  `events` is a list of
        (row, rule_id, ip, ts_ns), pre-sorted by (row, rule_id)
        ascending — the reference processing order (per-site rule ids
        precede global ids, so this IS the per-site-then-global loop).

        Replicates _window_step exactly, in int64 nanoseconds (the host
        oracle's own arithmetic — the (s, ns) split on device is
        bit-identical to this by construction): restart strictly-
        greater-than interval, hits reset to 0 (not 1) on exceed,
        FirstTime/OutsideInterval/InsideInterval, seen_ip = "the IP had
        any state before this event".

        State home: the touched vectors are written back to the warm
        tier (shadow when the warm tier is off or the put drops), so a
        refused IP that matched anything is warm-resident — and
        therefore ADMITTED next batch (admission rule 2), which bounds
        the ban delay to the single batch in which the sketch estimate
        first crossed the threshold."""
        mtypes: List[int] = []
        exceeds: List[bool] = []
        seens: List[bool] = []
        with self._lock:
            touched: "Dict[str, OrderedDict]" = {}
            warm = self._warm
            warm_live = warm is not None and len(warm) > 0
            for row, rid, ip, ts_ns in events:
                od = touched.get(ip)
                if od is None:
                    od = self._shadow.get(ip)
                    if od is None and warm_live:
                        ent = warm.take(ip)
                        if ent is not None:
                            od = OrderedDict(
                                (r, (h, s, ns)) for r, h, s, ns in ent
                            )
                    if od is None:
                        od = OrderedDict()
                    touched[ip] = od
                seen = bool(od)
                st = od.get(rid)
                have = st is not None
                outside = False
                if have:
                    h0, s0, n0 = st
                    outside = (
                        int(ts_ns) - (s0 * _NS_PER_S + n0)
                        > int(self._iv_total_np[rid])
                    )
                if not have or outside:
                    h1 = 1
                    s1, n1 = divmod(int(ts_ns), _NS_PER_S)
                else:
                    h1 = h0 + 1
                    s1, n1 = s0, n0
                exceeded = h1 > int(self._limits_np[rid])
                od[rid] = (0 if exceeded else h1, s1, n1)
                mtypes.append(0 if not have else (1 if outside else 2))
                exceeds.append(exceeded)
                seens.append(seen)
            now_ns = time.time_ns()
            for ip, od in touched.items():
                if warm is not None:
                    entries = [
                        (rid, h, s, ns) for rid, (h, s, ns) in od.items()
                    ]
                    if warm.put(ip, entries, now_ns):
                        self._shadow.pop(ip, None)
                        self._shadow_stamp.pop(ip, None)
                        self.warm_spills += 1
                        continue
                # warm off (or the put dropped): the shadow is the home —
                # the dict, in either form: a refused address is not
                # resident, so the mirror has no row for it
                self._shadow[ip] = od
                if self._mirror is not None and ip not in self._shadow_stamp:
                    self._shadow_stamp[ip] = self._mirror.next_stamp()
        n = len(events)
        return EventBatch(
            line=np.fromiter((e[0] for e in events), np.int32, n),
            rule=np.fromiter((e[1] for e in events), np.int32, n),
            match_type=np.asarray(mtypes, dtype=np.uint8),
            exceeded=np.asarray(exceeds, dtype=bool),
            seen_ip=np.asarray(seens, dtype=bool),
        )

    # ---- introspection parity with RegexRateLimitStates ----
    # The host shadow (updated from every batch's event-final states) is the
    # authoritative introspection source: no device pull, and it includes
    # evicted IPs — the reference host dict never forgets, so neither do we.

    def _shadow_rows_locked(self) -> List[Tuple[str, OrderedDict]]:
        """Every record the host holds outside the warm tier, (ip,
        rule_id -> (hits, start_s, start_ns)), in the order the dict
        form's one dict has by insertion: addresses by their record's
        first event (or its refill), counters by their first event.
        The mirror keeps no such order per event; its records carry a
        sequence stamp and are sorted here, at the read."""
        if self._mirror is None:
            return list(self._shadow.items())
        live = self._mirror.live_slots()
        stamps, vecs = self._mirror.export(live)
        held = [
            (stamp, ip, vec)
            for stamp, ip, vec in zip(stamps, self._sm.keys_of(live), vecs)
            if ip is not None
        ]
        held += [
            (self._shadow_stamp.get(ip, 0), ip, od)
            for ip, od in self._shadow.items()
        ]
        held.sort(key=lambda r: r[0])
        return [(ip, od) for _, ip, od in held]

    def shadow_items(self) -> "OrderedDict[str, OrderedDict]":
        """The host shadow as one mapping, whichever form holds it (the
        warm tier's records are not in it): what tests and debugging
        read in place of the form's own structures."""
        with self._lock:
            return OrderedDict(self._shadow_rows_locked())

    def get(self, ip: str) -> Tuple[Dict[str, NumHitsAndIntervalStart], bool]:
        with self._lock:
            od = None
            if self._mirror is not None:  # resident: the slot's record
                slot = int(self._sm.find_batch([ip])[0])
                if slot >= 0:
                    od = self._mirror.export([slot])[1][0]
            if not od:
                od = self._shadow.get(ip)
            if not od and self._warm is not None:
                ent = self._warm.peek(ip)
                if ent:
                    od = OrderedDict(
                        (r, (h, s, ns)) for r, h, s, ns in ent
                    )
            if not od:
                return {}, False  # seen at parse time but no event yet
            return {
                self._rule_names[rid]: NumHitsAndIntervalStart(
                    h, s * _NS_PER_S + ns
                )
                for rid, (h, s, ns) in od.items()
            }, True

    def format_states(self) -> str:
        with self._lock:
            rows = [
                (ip, list(od.items())) for ip, od in self._shadow_rows_locked()
            ]
            if self._warm is not None and len(self._warm):
                # warm-resident IPs are disjoint from the shadow (spill
                # deletes the shadow entry), so this is a plain append
                for ip in self._warm.keys():
                    ent = self._warm.peek(ip)
                    if ent:
                        rows.append(
                            (ip, [(r, (h, s, ns)) for r, h, s, ns in ent])
                        )
        if not rows:
            return ""
        lines: List[str] = []
        for ip, states in rows:
            lines.append(f"{ip}:")
            for rid, (h, s, ns) in states:
                lines.append(f"\t{self._rule_names[rid]}:")
                lines.append(
                    f"\t\tNumHitsAndIntervalStart({h}, {s * _NS_PER_S + ns})"
                )
            lines.append("")
        return "\n".join(lines) + ("\n" if lines else "")
