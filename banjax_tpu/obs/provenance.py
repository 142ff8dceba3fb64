"""Decision provenance ledger: why did banjax ban/challenge this IP?

The reference engine's whole value is *attributable* decisions from four
sources (PAPER.md §0): static config lists, the regex rate limiter,
Kafka commands from Baskerville, and repeated challenge failures.  PR 5
made the pipeline visible (spans, histograms) but an operator under
attack still couldn't answer the first question they ask: what exactly
made this IP blocked?  This module is the attribution layer — every
Decision insertion (and every expiry) appends one fixed-size record into
a lock-cheap per-source ring, queryable by IP through
``GET /decisions/explain?ip=…``.

Design constraints, in the trace recorder's mold (obs/trace.py):

  * **Off ≈ free.**  ``provenance_enabled`` gates every record path on a
    single attribute check.  On is the default (unlike tracing): records
    fire only on decision events — bans, list hits, expiries — which are
    orders of magnitude rarer than log lines.
  * **On = lock-cheap.**  One lock acquisition per record — or per
    applied chunk, whose bans arrive as one ``record_many`` — a tuple
    store into a preallocated per-source ring (oldest overwritten), and
    one counter bump for the ``banjax_decision_inserts_total{source,
    decision}`` family.  Nothing is formatted per record; ``explain()``
    pays the formatting cost at query time.
  * **Passive by construction.**  Recording reads its inputs and writes
    only ledger-private state — the differential suite
    (tests/differential/test_provenance_differential.py) proves the
    enabled ledger is byte-identical on ban-log output.

Record fields (fixed tuple, one per insertion):
    ip, decision (string form), source, rule name, rule index,
    window hit count at fire time, trace id of the admitting batch
    (from the ambient span when the insert happens on a traced drain
    thread), monotonic timestamp, wall timestamp, origin node id,
    origin trace id.

The last two fields are the fleet join (PR 20): when the banned line
was tailed on ANOTHER node and forwarded here by the fabric, the
installed origin resolver (obs/fleet.py OriginIndex, fed by the
owner-side chunk handlers) maps the IP back to the forwarding node and
the trace id its router allocated at admission — so
``/decisions/explain`` on the owner shard answers with the origin
batch's trace id, joinable against the origin node's /debug/trace
ring.  Locally-tailed bans leave them empty ("" / 0) and the explain
payload omits the keys entirely.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from banjax_tpu.obs import trace

DEFAULT_RING_SIZE = 2048

# the decision sources the reference attributes bans to (PAPER.md §0),
# plus the ledger-only lifecycle source for expiries
SOURCE_STATIC = "static_list"
SOURCE_UA = "ua_list"
SOURCE_RATE_LIMIT = "rate_limit"
SOURCE_KAFKA = "kafka"
SOURCE_CHALLENGE = "challenge_failure"
SOURCE_EXPIRY = "expiry"

SOURCES = (
    SOURCE_STATIC,
    SOURCE_UA,
    SOURCE_RATE_LIMIT,
    SOURCE_KAFKA,
    SOURCE_CHALLENGE,
    SOURCE_EXPIRY,
)


class ProvenanceLedger:
    """Process-wide decision ledger; every method is thread-safe, and
    when ``enabled`` is False each one is a single attribute check."""

    def __init__(self, enabled: bool = True,
                 ring_size: int = DEFAULT_RING_SIZE):
        self.enabled = bool(enabled)
        self.ring_size = max(16, int(ring_size))
        # per-source ring + its own lock: sources fire from different
        # threads (drain thread, request handlers, kafka reader, the
        # sweeper) and must not contend on one global lock
        self._rings: Dict[str, List[Optional[tuple]]] = {
            s: [None] * self.ring_size for s in SOURCES
        }
        self._ns: Dict[str, int] = {s: 0 for s in SOURCES}
        self._locks: Dict[str, threading.Lock] = {
            s: threading.Lock() for s in SOURCES
        }
        self._counter_lock = threading.Lock()
        # (source, decision-string) -> monotone insert count; the
        # banjax_decision_inserts_total{source,decision} family
        self._counters: Dict[Tuple[str, str], int] = {}

    # ---- recording ----

    def record(self, source: str, ip: str, decision, rule: str = "",
               rule_index: int = -1, hits: Optional[int] = None,
               trace_id: Optional[int] = None) -> None:
        """Append one decision record.

        ``decision`` may be a Decision enum or string; stored in string
        form so the ledger never imports the decisions package.
        ``trace_id`` defaults to the ambient span's trace id — a ban
        fired on a traced pipeline drain thread is attributed to the
        admitting batch with no plumbing at the call site."""
        self.record_many(
            source, ((ip, decision, rule, rule_index, hits),), trace_id
        )

    def record_many(
        self, source: str,
        items: Sequence[Tuple[str, object, str, int, Optional[int]]],
        trace_id: Optional[int] = None,
    ) -> None:
        """`record` for each (ip, decision, rule, rule_index, hits) in
        order — the decisions one applied chunk inserted: one ambient
        trace id, one pair of timestamps, one hold of the source ring's
        lock and one of the counter lock for all of them."""
        if not self.enabled or not items:
            return
        if source not in self._rings:
            source = SOURCE_STATIC  # never raise from a record path
        if trace_id is None:
            trace_id = trace.current_trace_id()
        trace_id = int(trace_id)
        t_mono, t_wall = time.monotonic(), time.time()
        resolver = _origin_resolver
        recs = []
        for ip, decision, rule, rule_index, hits in items:
            origin_node, origin_trace = "", 0
            if resolver is not None:
                try:
                    origin = resolver(ip)
                    if origin:
                        origin_node = str(origin[0])
                        origin_trace = int(origin[1])
                except Exception:  # resolution must never break a record path
                    pass
            recs.append((ip, str(decision), source, rule, int(rule_index),
                         hits, trace_id, t_mono, t_wall,
                         origin_node, origin_trace))
        ring, size = self._rings[source], self.ring_size
        with self._locks[source]:
            n = self._ns[source]
            for rec in recs:
                ring[n % size] = rec
                n += 1
            self._ns[source] = n
        counters = self._counters
        with self._counter_lock:
            for rec in recs:
                key = (source, rec[1])
                counters[key] = counters.get(key, 0) + 1

    # ---- queries ----

    def _source_records(self, source: str) -> List[tuple]:
        """One source's ring, oldest-first."""
        with self._locks[source]:
            n = self._ns[source]
            ring = self._rings[source]
            if n <= self.ring_size:
                recs = list(ring[:n])
            else:
                cut = n % self.ring_size
                recs = ring[cut:] + ring[:cut]
        return [r for r in recs if r is not None]

    @staticmethod
    def _to_dict(rec: tuple) -> dict:
        (ip, decision, source, rule, rule_index, hits, tid, t_mono,
         t_wall, origin_node, origin_trace) = rec
        out = {
            "ip": ip,
            "decision": decision,
            "source": source,
            "rule": rule,
            "rule_index": rule_index,
            "hits": hits,
            "trace_id": tid,
            "t_monotonic": round(t_mono, 6),
            "time_unix": round(t_wall, 6),
        }
        if origin_node:
            out["origin_node"] = origin_node
            out["origin_trace_id"] = origin_trace
        return out

    def explain(self, ip: str) -> List[dict]:
        """Full ledger history for one IP across every source, oldest
        first (the /decisions/explain payload)."""
        out = []
        for source in SOURCES:
            out.extend(r for r in self._source_records(source) if r[0] == ip)
        out.sort(key=lambda r: r[7])  # monotonic timestamp
        return [self._to_dict(r) for r in out]

    def tail(self, n: int = 256) -> List[dict]:
        """Newest ``n`` records across all sources, oldest-first — the
        flight recorder's provenance capture."""
        recs: List[tuple] = []
        for source in SOURCES:
            recs.extend(self._source_records(source))
        recs.sort(key=lambda r: r[7])
        return [self._to_dict(r) for r in recs[-max(0, int(n)):]]

    def counters(self) -> Dict[Tuple[str, str], int]:
        """{(source, decision): total inserts} — the exposition family."""
        with self._counter_lock:
            return dict(self._counters)

    def total_records(self) -> int:
        return sum(self._ns[s] for s in SOURCES)


# ---- process-wide ledger ---------------------------------------------------

_ledger = ProvenanceLedger(enabled=True)

# ip -> (origin_node_id, origin_trace_id) | None: installed by the
# fabric wiring (obs/fleet.py OriginIndex.resolve) so forwarded-line
# bans carry their cross-host admission attribution; survives a
# configure() ledger swap
_origin_resolver: Optional[Callable[[str], Optional[Tuple[str, int]]]] = None


def set_origin_resolver(
    fn: Optional[Callable[[str], Optional[Tuple[str, int]]]],
) -> None:
    global _origin_resolver
    _origin_resolver = fn


def get_ledger() -> ProvenanceLedger:
    return _ledger


def configure(enabled: bool = True,
              ring_size: int = DEFAULT_RING_SIZE) -> ProvenanceLedger:
    """(Re)configure the process ledger — called by cli.BanjaxApp from
    config (`provenance_enabled`, `provenance_ring_size`) and by tests.
    Swaps the singleton so a disabled ledger keeps the one-attribute-
    check fast path."""
    global _ledger
    _ledger = ProvenanceLedger(enabled=enabled, ring_size=ring_size)
    return _ledger


# module-level delegates: call sites read the CURRENT singleton each time

def enabled() -> bool:
    return _ledger.enabled


def record(source: str, ip: str, decision, rule: str = "",
           rule_index: int = -1, hits: Optional[int] = None,
           trace_id: Optional[int] = None) -> None:
    _ledger.record(source, ip, decision, rule, rule_index, hits, trace_id)


def record_many(
    source: str,
    items: Sequence[Tuple[str, object, str, int, Optional[int]]],
    trace_id: Optional[int] = None,
) -> None:
    _ledger.record_many(source, items, trace_id)
