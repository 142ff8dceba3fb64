"""Traffic introspection plane: device-resident streaming sketches.

PR 5/6 made the *engine* observable (spans, /metrics, provenance, SLO
burn, incident bundles); this module makes the *traffic* observable
mid-flood, before any ban fires: who the heavy hitters are, how many
distinct sources are active, and which rules are under pressure.

Three classic streaming structures live as flat device arrays and fold
every matcher chunk in-stream, inside the fused match+window dispatch
(zero interaction with window state —
the differential suite proves sketch-on == sketch-off on ban-log bytes,
result stream and window state):

  * a count–min sketch (Cormode & Muthukrishnan, 2005) over client-IP
    hashes — [depth * width] int32, conservative point estimates that
    never undercount, so the host-side top-K heap ranks heavy hitters
    from periodic compact pulls;
  * a HyperLogLog register array (Flajolet et al., 2007) — 2^p int32
    registers for distinct-source cardinality at ~1.04/sqrt(2^p)
    relative error.

Per-rule match-pressure accumulators (the "which rule is absorbing the
flood" view) ride the HOST side instead: every fired (line, rule)
window event already crosses to the host for the Banner replay, on
every path — fused commit, overflow fallback, classic apply — so
counting there is exact even for chunks whose device bitmap was
incomplete (candidate overflow), at O(events) cost the replay already
pays.

Keyed on the rows' hashes: the submit stage's one pass over a batch's
distinct addresses has each address's base hash (one C call over the
encoding the slot manager walks anyway), so a row's hash is one gather,
and a chunk's fold is a few more lines of the fused match+window program
that is dispatched for the chunk anyway — one more per-row operand, no
dispatch of its own (`fold`; kernels/fused_match_window.py).  What is
not dispatched fused (the classic protocol's window apply) runs the same
arithmetic as a program of its own (`update`).

Pulls are PERIODIC, never per-batch: `pull()` is throttled by
`traffic_sketch_pull_seconds` (one compact d2h of ~depth*width*4 +
2^p*4 + n_rules*4 bytes, traced as a `sketch-pull` span), and every
consumer — `GET /traffic/top`, the 29 s line, /metrics, flight-recorder
bundles — reads the cached summary between refreshes.

This is deliberately the read-only half of ROADMAP item 1 (mega-state):
the cold-admission decision the mega-state PR needs can gate on exactly
these estimates; building the sketch first as telemetry de-risks it.
"""

from __future__ import annotations

import functools
import heapq
import math
import threading
import time
import zlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from banjax_tpu.obs import trace

# xor seeds decorrelating the count-min rows (any fixed distinct values
# work: the row hash is fmix32(ip_hash ^ seed_j));  the golden-ratio
# constant seeds the independent HLL hash
_CM_SEEDS = (0x0000_0000, 0x7F4A_7C15, 0x94D0_49BB, 0xDE82_4AD5,
             0x1B87_3593, 0xC2B2_AE35, 0x27D4_EB2F, 0x1656_67B1)
_HLL_SEED = 0x9E37_79B9

_MIN_ROW_BUCKET = 64


def _bucket(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


def _fmix32_np(h: np.ndarray) -> np.ndarray:
    """murmur3 finalizer, numpy uint32 — the HOST mirror of the device
    mix below; the two must agree bit-for-bit or point estimates read
    the wrong buckets."""
    h = h.astype(np.uint32, copy=True)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EB_CA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2_AE35)
    h ^= h >> np.uint32(16)
    return h


def _fmix32_jnp(h):
    h = h.astype(jnp.uint32)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EB_CA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2_AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _fold(seeds, hll_p: int, cm, hll, row_hashes, n_real):
    """The fold's arithmetic, traceable: `row_hashes` uint32 [Bp], one
    base hash a row, rows from `n_real` on masked; `cm` is depth
    (= len(seeds)) rows of buckets back to back."""
    depth = seeds.shape[0]
    width = cm.shape[0] // depth
    low_bits = 32 - hll_p
    h = row_hashes
    Bp = h.shape[0]
    real = jax.lax.iota(jnp.int32, Bp) < n_real
    inc = real.astype(jnp.int32)
    # count-min: one bucket increment per row per line (scatter-add
    # accumulates duplicate indices — repeated IPs in a batch land their
    # full count)
    hx = h[None, :] ^ seeds[:, None]                     # [depth, Bp]
    col = (_fmix32_jnp(hx) % jnp.uint32(width)).astype(jnp.int32)
    flat = col + jnp.arange(depth, dtype=jnp.int32)[:, None] * width
    cm = cm.at[flat.reshape(-1)].add(
        jnp.broadcast_to(inc[None, :], (depth, Bp)).reshape(-1)
    )
    # HLL: register = top p bits of an independent mix, rho = leading
    # zeros of the remaining bits + 1 (bit-smear + popcount gives the MSB
    # position exactly — no float log)
    g = _fmix32_jnp(h ^ jnp.uint32(_HLL_SEED))
    reg = (g >> jnp.uint32(low_bits)).astype(jnp.int32)
    fill = g & jnp.uint32((1 << low_bits) - 1)
    for s in (1, 2, 4, 8, 16):
        fill = fill | (fill >> jnp.uint32(s))
    msb_cnt = jax.lax.population_count(fill).astype(jnp.int32)
    rho = low_bits - msb_cnt + 1
    hll = hll.at[reg].max(jnp.where(real, rho, 0))
    return cm, hll


def hash_ip(ip: str) -> int:
    """The 32-bit base hash of one client-IP string (crc32 of the utf-8
    bytes).  Every derived hash — count-min rows, the HLL register pick
    — mixes from THIS value, on host and device alike."""
    return zlib.crc32(ip.encode("utf-8", "surrogatepass")) & 0xFFFF_FFFF


def hll_estimate(registers: np.ndarray) -> float:
    """Standard bias-corrected HyperLogLog estimate with the
    small-range (linear counting) correction; the large-range 32-bit
    correction is omitted on purpose — at 2^30+ distinct sources the
    answer "effectively unbounded" is the operational truth."""
    m = registers.size
    alpha = 0.7213 / (1.0 + 1.079 / m)
    raw = alpha * m * m / float(np.sum(np.exp2(-registers.astype(np.float64))))
    if raw <= 2.5 * m:
        zeros = int(np.count_nonzero(registers == 0))
        if zeros:
            return m * math.log(m / zeros)
    return raw


class TrafficSketch:
    """Device-resident traffic sketches with a host-side top-K view.

    Thread-safe, under three locks that guard unrelated things.  The
    state lock (`_lock`) serializes the dispatches that donate
    `(cm, hll)` — it is held ACROSS a fused dispatch, inside the windows
    lock (order: windows lock, then this one; `pull` takes this one
    alone) — and the pull.  The candidate log and the per-rule pressure
    have a lock each, so that neither the submit stage's append nor the
    drain's `note_rule_events` ever queues behind a dispatch.
    """

    def __init__(
        self,
        rule_names: Sequence[str],
        *,
        depth: int = 4,
        width: int = 8192,
        hll_p: int = 12,
        pull_seconds: float = 5.0,
        topk: int = 32,
        max_candidates: int = 8192,
    ):
        if not 1 <= depth <= len(_CM_SEEDS):
            raise ValueError(f"sketch depth must be 1..{len(_CM_SEEDS)}")
        if width < 16:
            raise ValueError("sketch width must be >= 16")
        if not 4 <= hll_p <= 16:
            raise ValueError("hll_p must be 4..16")
        self.depth = int(depth)
        self.width = int(width)
        self.hll_p = int(hll_p)
        self.m = 1 << self.hll_p
        self.pull_seconds = max(0.0, float(pull_seconds))
        self.topk = max(1, int(topk))
        self.max_candidates = max(self.topk, int(max_candidates))
        self.rule_names = list(rule_names)
        self._n_rules = max(1, len(self.rule_names))

        self._lock = threading.Lock()
        self._rule_lock = threading.Lock()
        self._cand_lock = threading.Lock()
        # donated device state: (cm [depth*width], hll [m])
        self._state = (
            jnp.zeros((self.depth * self.width,), dtype=jnp.int32),
            jnp.zeros((self.m,), dtype=jnp.int32),
        )
        # per-rule pressure: host-side exact counts of fired (line, rule)
        # window events (note_rule_events, fed from the Banner replay)
        self._rule_hits = np.zeros(self._n_rules, dtype=np.int64)
        # HOST count-min mirror for slot-REFUSED rows: a refused row has
        # no slot, so it never reaches the device update — its count
        # accrues here (fold_refused) in the same bucket geometry.  An
        # unseen IP's own rows therefore land EXACTLY in this array, and
        # the device sketch contributes only collisions, so
        # estimate_ips >= the IP's true refused-row count no matter how
        # stale the cached device pull is — the conservatism the
        # admission gate's bounded-delay argument needs.
        self._cm_host = np.zeros((self.depth, self.width), dtype=np.int64)
        self.refused_rows_folded = 0
        # last-pulled device count-min (host copy): estimate_ips reads
        # this CACHE — the admission gate runs per batch and must never
        # force a d2h pull
        self._cm_cache: Optional[np.ndarray] = None
        # candidate heavy hitters: LRU of recently-seen distinct IPs and
        # their base hashes — the enumerable key set a count-min sketch
        # itself cannot provide.  A true heavy hitter recurs every batch,
        # so it cannot age out of a bound >> topk.  Recency is written in
        # bulk: a batch appends its (ips, hashes) to `_cand_log`, one
        # store whatever its size, and the LRU is brought up to date from
        # the log where somebody reads it (a pull: _candidates_locked);
        # where nobody does, the log is kept short by dropping batches
        # in the hashes' domain, no string touched (_trim_log_locked).
        self._cand_lru: "OrderedDict[str, int]" = OrderedDict()
        self._cand_log: List[tuple] = []
        self._cand_log_len = 0
        # the fold, traceable: fold(cm, hll, row_hashes, n_real) ->
        # (cm, hll).  Both dispatches trace this one function: the fused
        # match+window program (kernels/fused_match_window.py) and the
        # program of its own for what is not dispatched fused (jit keeps
        # one executable a row bucket).  Neither closes over the sketch
        fold = self.fold = functools.partial(
            _fold,
            jnp.asarray(np.asarray(_CM_SEEDS[: self.depth], dtype=np.uint32)),
            self.hll_p,
        )
        self._standalone = jax.jit(
            lambda state, row_hashes, n_real: fold(*state, row_hashes, n_real),
            donate_argnums=(0,),
        )

        self.lines_total = 0          # lines folded into the sketch
        self.update_count = 0
        # chunks folded, by the dispatch that carried the fold
        # (banjax_sketch_updates_total{path})
        self.updates_by_path = {"fused": 0, "standalone": 0}
        self.pull_count = 0
        self.pull_bytes_total = 0
        self._last_pull_mono: Optional[float] = None
        self._summary: Optional[dict] = None

    # ---- host bookkeeping (candidates) ----

    def _candidates_locked(self) -> "OrderedDict[str, int]":
        """The candidate LRU with every logged batch folded in (caller
        holds the candidate lock): the `max_candidates` most recently
        seen distinct IPs, oldest first, an IP's place given by the last
        batch that had it and its position there — what a per-address
        move-to-end walk of each batch, trimmed after each, leaves behind
        (a trimmed IP is older than `max_candidates` others and stays so
        until seen again).  Built in C-speed dict passes: newest first, a
        dict keeps each IP where it is met first, i.e. at its last
        sighting."""
        if self._cand_log:
            newest_first: Dict[str, int] = {}
            for ips, hashes in reversed(self._cand_log):
                # update() leaves a key it already has where it is
                newest_first.update(
                    zip(reversed(ips), reversed(hashes.tolist()))
                )
                if len(newest_first) >= self.max_candidates:
                    break
            else:
                lru = self._cand_lru
                newest_first.update(
                    zip(reversed(lru), reversed(lru.values()))
                )
            keep = list(newest_first.items())[: self.max_candidates]
            self._cand_lru = OrderedDict(reversed(keep))
            self._cand_log = []
            self._cand_log_len = 0
        return self._cand_lru

    def _trim_log_locked(self) -> None:
        """Bound the log where nobody reads it (caller holds the
        candidate lock), in the hashes' domain: once the newest k batches
        hold `max_candidates` distinct hashes — hence as many distinct
        addresses — _candidates_locked's walk stops there, and the older
        batches are dropped unread: numpy over the uint32 arrays the log
        holds, no string touched.  Only a log that long with fewer
        distinct hashes (few addresses, many small batches) is folded
        the exact way here."""
        log = self._cand_log
        k = raw = 0
        need = self.max_candidates
        while k < len(log):
            while k < len(log) and raw < need:
                k += 1
                raw += len(log[-k][1])
            distinct = np.unique(np.concatenate([h for _, h in log[-k:]]))
            if distinct.size >= self.max_candidates:
                del log[:-k]
                self._cand_log_len = raw
                break
            need = 2 * raw
        if self._cand_log_len > 4 * self.max_candidates:
            self._candidates_locked()

    @property
    def _candidates(self) -> "OrderedDict[str, int]":
        with self._cand_lock:
            return self._candidates_locked()

    def note_assignments(
        self, ips: Sequence[str], hashes: Optional[np.ndarray] = None,
    ) -> None:
        """Remember one batch's DISTINCT addresses as heavy-hitter
        candidates: one append to the candidate log.  `hashes`: the
        addresses' base hashes where the caller has them from its one
        encoding of the batch (native/slotmgr.py crc32_spans); without
        them each address is hashed here."""
        n = len(ips)
        if n == 0:
            return
        if hashes is None:
            hashes = self.base_hashes(ips)
        with self._cand_lock:
            self._cand_log.append((ips, hashes))
            self._cand_log_len += n
            if self._cand_log_len > 4 * self.max_candidates:
                self._trim_log_locked()

    # ---- the per-chunk device update ----

    def dispatch_fold(self, run, n_real: int, path: str):
        """One chunk's fold, dispatched by `run(state) -> (state', rest)`
        under the state lock — the donated `(cm, hll)` goes in and its
        successor is stored back before anyone else can dispatch or
        pull.  Returns `rest`."""
        with self._lock:
            self._state, rest = run(self._state)
            self.lines_total += n_real
            self.update_count += 1
            self.updates_by_path[path] += 1
        return rest

    def update(self, row_hashes, n_real: int) -> None:
        """Fold one chunk's rows as a program of its own — what is not
        dispatched fused: `row_hashes` per row (rows beyond `n_real` are
        masked; the row bucket pads to a power of two so the jit cache
        stays bounded).  One stateless donated-array dispatch, its
        operands passed as they are (the call transfers them itself, one
        trip through the runtime); nothing is read back."""
        h = np.asarray(row_hashes, dtype=np.uint32)
        Bp = _bucket(max(len(h), 1), _MIN_ROW_BUCKET)
        if len(h) != Bp:
            h = np.concatenate([h, np.zeros(Bp - len(h), dtype=np.uint32)])
        n_real = min(int(n_real), Bp)
        trace.runtime_calls()
        self.dispatch_fold(
            lambda state: (self._standalone(state, h, np.int32(n_real)), None),
            n_real, "standalone",
        )

    def note_rule_events(self, rule_ids) -> None:
        """Fold fired (line, rule) window events into the per-rule
        pressure accumulators — called from the Banner replay with the
        event list every path already decodes, so pressure is EXACT even
        for chunks whose device bitmap overflowed."""
        if isinstance(rule_ids, np.ndarray):
            ids = rule_ids.astype(np.int64)
        else:
            ids = np.fromiter((int(r) for r in rule_ids), dtype=np.int64)
        if not ids.size:
            return
        counts = np.bincount(
            ids[(ids >= 0) & (ids < self._n_rules)],
            minlength=self._n_rules,
        )
        with self._rule_lock:
            self._rule_hits += counts

    # ---- the periodic compact pull ----

    def pull(self, force: bool = False) -> dict:
        """Refresh (throttled by `pull_seconds`) and return the host
        summary: top-K heavy hitters with conservative count-min
        estimates, the HLL distinct-IP estimate, per-rule pressure, and
        pull bookkeeping.  Between refreshes every consumer shares the
        cached summary — the sketch is pulled on a sampling interval,
        never per batch."""
        with self._lock:
            now_m = time.monotonic()
            if (
                not force
                and self._summary is not None
                and self._last_pull_mono is not None
                and now_m - self._last_pull_mono < self.pull_seconds
            ):
                return self._summary
            # a pull belongs to no admission batch: it gets its own
            # trace id (like shed instants), so the Perfetto view shows
            # WHEN the compact d2h ran relative to the batch spans
            sp = trace.begin(
                "sketch-pull", trace.new_trace(),
                args={"forced": bool(force)},
            )
            try:
                cm = np.asarray(self._state[0]).reshape(
                    self.depth, self.width
                )
                hll = np.asarray(self._state[1])
            finally:
                trace.end(sp)
            with self._rule_lock:
                rule_hits = self._rule_hits.copy()  # host-side, no pull
            self._cm_cache = cm  # refresh the admission gate's cache
            self.pull_bytes_total += cm.nbytes + hll.nbytes
            self.pull_count += 1
            self._last_pull_mono = time.monotonic()

            top: List[dict] = []
            cand = self._candidates
            if cand:
                ips = list(cand)
                base = np.fromiter(
                    cand.values(), dtype=np.uint32, count=len(ips)
                )
                est = None
                for j in range(self.depth):
                    col = _fmix32_np(base ^ np.uint32(_CM_SEEDS[j])) \
                        % np.uint32(self.width)
                    ci = col.astype(np.int64)
                    # device buckets + the refused-row host mirror: the
                    # estimate covers ALL of an IP's rows, slotted or not
                    vals = cm[j, ci] + self._cm_host[j, ci]
                    est = vals if est is None else np.minimum(est, vals)
                for k in heapq.nlargest(
                    self.topk, range(len(ips)), key=lambda i: int(est[i])
                ):
                    if est[k] <= 0:
                        break
                    top.append({"ip": ips[k], "est_count": int(est[k])})

            distinct = hll_estimate(hll)
            lines = self.lines_total
            share = (
                round(top[0]["est_count"] / lines, 4)
                if top and lines else 0.0
            )
            pressure = [
                {"rule": name, "index": i, "events": int(rule_hits[i])}
                for i, name in enumerate(self.rule_names)
                if i < rule_hits.size and rule_hits[i] > 0
            ]
            pressure.sort(key=lambda r: -r["events"])
            self._summary = {
                "top": top,
                "k_max": self.topk,
                "distinct_ips_estimate": round(distinct, 1),
                "heavy_hitter_share": share,
                "lines_total": lines,
                "rule_pressure": pressure,
                "sketch": {
                    "depth": self.depth,
                    "width": self.width,
                    "hll_registers": self.m,
                    "candidates": len(cand),
                    "pull_count": self.pull_count,
                    "pull_bytes_total": self.pull_bytes_total,
                },
            }
            return self._summary

    def pull_age_seconds(self) -> Optional[float]:
        with self._lock:
            if self._last_pull_mono is None:
                return None
            return time.monotonic() - self._last_pull_mono

    def estimate_ip(self, ip: str) -> int:
        """Point estimate for one IP from the LAST pulled count-min
        state (tests; /traffic debugging).  Conservative: >= the true
        count folded in before that pull."""
        self.pull()
        with self._lock:
            cm = np.asarray(self._state[0]).reshape(self.depth, self.width)
            cm_host = self._cm_host
        base = np.uint32(hash_ip(ip))
        est = None
        for j in range(self.depth):
            col = int(
                _fmix32_np(np.asarray([base ^ np.uint32(_CM_SEEDS[j])],
                                      dtype=np.uint32))[0]
            ) % self.width
            v = int(cm[j, col]) + int(cm_host[j, col])
            est = v if est is None else min(est, v)
        return int(est or 0)

    # ---- the cold-tier admission surface (mega-state tiering) ----

    @staticmethod
    def base_hashes(ips: Sequence[str]) -> np.ndarray:
        """uint32 [n] base hashes for a distinct-ip list — computed once
        per batch by the runner and shared between estimate_ips and
        fold_refused (the crc32 walk is the per-unseen-ip host cost)."""
        return np.fromiter(
            (hash_ip(ip) for ip in ips), dtype=np.uint32, count=len(ips)
        )

    def _columns(self, base: np.ndarray) -> np.ndarray:
        """int64 [depth, n] count-min column per row for base hashes."""
        cols = np.empty((self.depth, len(base)), dtype=np.int64)
        for j in range(self.depth):
            cols[j] = (
                _fmix32_np(base ^ np.uint32(_CM_SEEDS[j]))
                % np.uint32(self.width)
            ).astype(np.int64)
        return cols

    def estimate_ips(
        self, ips: Sequence[str], hashes: Optional[np.ndarray] = None
    ) -> np.ndarray:
        """Conservative count estimates, int64 [n], from the CACHED
        last-pulled device count-min plus the exact refused-row host
        mirror.  Never forces a pull — this runs in the admission gate,
        once per batch.  An unseen IP's own rows are all in the host
        mirror (fold_refused), so staleness of the device cache can only
        UNDER-estimate collision noise, never the IP's true count."""
        n = len(ips)
        if n == 0:
            return np.zeros(0, dtype=np.int64)
        base = self.base_hashes(ips) if hashes is None else hashes
        cols = self._columns(base)
        with self._lock:
            cache = self._cm_cache
            est: Optional[np.ndarray] = None
            for j in range(self.depth):
                vals = self._cm_host[j, cols[j]]
                if cache is not None:
                    vals = vals + cache[j, cols[j]]
                est = vals if est is None else np.minimum(est, vals)
        return est

    def fold_refused(
        self,
        ips: Sequence[str],
        counts: np.ndarray,
        hashes: Optional[np.ndarray] = None,
    ) -> None:
        """Fold one batch's REFUSED rows into the host count-min mirror:
        `counts[i]` rows for distinct ip `ips[i]`.  Exact (int64 adds,
        no sampling) — these rows never reach the device sketch, and the
        admission gate's bounded-delay argument needs every one of them
        counted."""
        n = len(ips)
        if n == 0:
            return
        base = self.base_hashes(ips) if hashes is None else hashes
        cols = self._columns(base)
        counts = np.asarray(counts, dtype=np.int64)
        with self._lock:
            for j in range(self.depth):
                np.add.at(self._cm_host[j], cols[j], counts)
            self.refused_rows_folded += int(counts.sum())

    def incident_snapshot(self) -> dict:
        """The flight-recorder view (`traffic.json`): a FORCED pull so
        the bundle shows the flood as of the incident, not the last
        sampling tick."""
        out = dict(self.pull(force=True))
        out["enabled"] = True
        return out
