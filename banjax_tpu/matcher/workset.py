"""Array-backed work batches for the TPU matcher's host path.

The reference's consumeLine walks one Go struct per line
(/root/reference/internal/regex_rate_limiter.go:126-157); a literal port
builds a Python object + several strings per line, which at 65k-line
batches costs ~300 ms — far more than the device match itself (the r3
end-to-end wall). This module keeps the batch COLUMNAR end to end:

  * `NativeWork` holds numpy row indices + the distinct-address and
    distinct-host tables from the native gate (banjax_tpu/native).  The
    addresses stay byte spans of the parse blob (`SpanStrings`): a string
    is made of one when something asks for it.  Per-row Python objects
    materialize lazily, only for rows something actually touches —
    matched rows, ban logging, error paths — a few percent of traffic.
  * `ListWork` wraps the per-line-parsed fallback path (no native lib,
    deferred timestamps) in the same interface, so every consumer
    (window-slot scaffolding, the fused pipeline, replay) is agnostic.

The interface both provide:
  len(work); work[int] -> (orig_index, line); work[slice] -> same kind;
  iteration over (orig_index, line); unique_ips() -> (list[str], inverse);
  unique_ip_spans() -> the same as byte spans (slotmgr.AddressSpans,
  inverse), or None from a work set that holds its addresses as strings;
  host_idx(host_row) -> np.int32 per row; ts_array() -> np.int64 per row;
  rest_bytes(ks) -> the rows' regex haystacks as bytes, back to back.
"""

from __future__ import annotations

import itertools
import operator
from collections import OrderedDict
from typing import Dict, List, Sequence, Tuple

import numpy as np

from banjax_tpu.matcher.api import ConsumeLineResult
from banjax_tpu.matcher.encode import ParsedLine
from banjax_tpu.native.slotmgr import merge_spans


class _Row:
    """What was written to one row of a LazyResults.  It knows neither
    the vector nor its index, so a batch's rows and its vector die by
    reference count, with nothing left for the cyclic collector."""

    __slots__ = ("error", "old_line", "exempted", "rr")

    def __init__(self):
        self.error = self.old_line = self.exempted = False
        self.rr = None  # the rule_results list, once something asks for it


class _LineResult(ConsumeLineResult):
    """One row of a LazyResults, as a view: it holds the vector and the
    row's index and no state of its own, the vector holds no view.  So
    reading a flag of every row of a batch leaves no object behind — a
    view dies when its reader drops it — and one that a reader keeps
    stays true (it keeps its vector alive).  `rule_results` may still be
    owed by a replayed chunk (LazyResults.defer) and is filled the first
    time anything reads it."""

    def __init__(self, owner: "LazyResults", i: int):
        self._owner = owner
        self._i = i

    @property
    def error(self) -> bool:
        row = self._owner._items[self._i]
        return False if row is None else row.error

    @error.setter
    def error(self, value: bool) -> None:
        self._owner._row(self._i).error = value

    @property
    def old_line(self) -> bool:
        row = self._owner._items[self._i]
        return False if row is None else row.old_line

    @old_line.setter
    def old_line(self, value: bool) -> None:
        self._owner._row(self._i).old_line = value

    @property
    def exempted(self) -> bool:
        row = self._owner._items[self._i]
        return False if row is None else row.exempted

    @exempted.setter
    def exempted(self, value: bool) -> None:
        self._owner._row(self._i).exempted = value

    @property
    def rule_results(self) -> list:
        owner = self._owner
        if owner._deferred:
            owner.flush()
        return owner.owed(self._i)

    @rule_results.setter
    def rule_results(self, value: list) -> None:
        self._owner._row(self._i).rr = value

    def __eq__(self, other):
        if not isinstance(other, ConsumeLineResult):
            return NotImplemented
        return (
            self.error, self.old_line, self.exempted, self.rule_results
        ) == (
            other.error, other.old_line, other.exempted, other.rule_results
        )

    __hash__ = None


class LazyResults:
    """List-compatible ConsumeLineResult vector that keeps a record only
    for the rows something wrote to. consume_lines must return one result
    per line, but production (cli._consume_lines) only reads them in
    debug mode, and the pipeline's observer reads a flag of each —
    eager construction of 65k dataclasses per batch costs more than the
    whole vectorized gate, and an object kept per row read is a survivor
    of every young collection, which brings the collector's full passes
    on.  The same goes for the entries' `rule_results`: a replayed chunk
    hands in a fill (`defer`) that runs when one is read."""

    __slots__ = ("_items", "_n_set", "_deferred")

    def __init__(self, n: int):
        self._items = [None] * n  # a _Row where something was written
        self._n_set = 0
        self._deferred: list = []

    def defer(self, fill) -> None:
        """`fill(results)` will append the rule results it owes through
        `owed(i)`; fills run in the order they were handed in."""
        self._deferred.append(fill)

    def flush(self) -> None:
        fills, self._deferred = self._deferred, []
        for fill in fills:
            fill(self)

    def _row(self, i: int) -> _Row:
        row = self._items[i]
        if row is None:
            row = self._items[i] = _Row()
            self._n_set += 1
        return row

    def owed(self, i: int) -> list:
        """Entry i's rule_results list, for a fill to append to."""
        row = self._row(i)
        if row.rr is None:
            row.rr = []
        return row.rr

    def __len__(self) -> int:
        return len(self._items)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [
                _LineResult(self, k)
                for k in range(*i.indices(len(self._items)))
            ]
        self._items[i]  # an index out of range raises as a list's does
        return _LineResult(self, i)

    def __iter__(self):
        n = len(self._items)
        return map(_LineResult, itertools.repeat(self, n), range(n))

    def absorb(self, other: "LazyResults", row0: int) -> None:
        """Take over what was written to `other`'s rows, at row offset
        `row0` (the sharded-encode merge step; a finished batch of a
        synchronous call of several), and the fills it is still owed;
        untouched rows stay untouched.  A shard of clean traffic writes
        nothing during the gate — the counter makes that common case
        O(1) instead of a scan."""
        self._deferred += [_Rebased(fill, row0) for fill in other._deferred]
        other._deferred = []
        if other._n_set == 0:
            return
        dst = self._items
        for i, row in enumerate(other._items):
            if row is not None:
                if dst[row0 + i] is None:
                    self._n_set += 1
                dst[row0 + i] = row


class _Rebased:
    """A fill absorbed from another LazyResults: it runs against this
    one's rows, `row0` further on."""

    __slots__ = ("fill", "row0", "res")

    def __init__(self, fill, row0: int):
        self.fill, self.row0 = fill, row0

    def __call__(self, res) -> None:
        self.res = res
        self.fill(self)

    def owed(self, i: int) -> list:
        return self.res.owed(self.row0 + i)


class StringCount:
    """How many strings the SpanStrings of one matcher have made
    (banjax_gate_address_strings_total).  Plain adds, no lock: in a
    running pipeline one thread reads them (the drain, `lines_at`)."""

    __slots__ = ("n",)

    def __init__(self):
        self.n = 0


def _decode_span(blob: bytes, off: int, length: int) -> str:
    # a parse blob is made with surrogatepass: any line's str comes back
    return blob[off : off + length].decode("utf-8", "surrogatepass")


def decode_spans(blob: bytes, offs: np.ndarray, lens: np.ndarray) -> List[str]:
    """The strings of (offset, length) spans of a parse blob."""
    return [
        _decode_span(blob, o, n) for o, n in zip(offs.tolist(), lens.tolist())
    ]


class SpanStrings:
    """A gated batch's distinct addresses as the list of `str` they used
    to be, made on demand: `len`, an index (negative too), a slice (a
    list), iteration and `==` against a list give what the eager list
    gave, and each string is decoded from its span of `blob` when it is
    asked for — the rows that exceeded a limit, the dict path, the
    allowlist; the hot path reads the spans (NativeWork.ip_spans).

    `blob` is the batch's immutable bytes and `offs` / `lens` are this
    sequence's own (the gate's output, never a parse scratch), so a
    string read after the matcher's scratch served another batch is
    still this batch's.  `count` is told of every string made."""

    __slots__ = ("_blob", "_offs", "_lens", "_count")

    def __init__(self, blob: bytes, offs: np.ndarray, lens: np.ndarray,
                 count: "StringCount | None" = None):
        self._blob = blob
        self._offs = offs
        self._lens = lens
        self._count = count if count is not None else StringCount()

    def __len__(self) -> int:
        return len(self._offs)

    def _made(self, offs, lens) -> List[str]:
        self._count.n += len(offs)
        return decode_spans(self._blob, offs, lens)

    def __getitem__(self, j):
        if isinstance(j, slice):
            return self._made(self._offs[j], self._lens[j])
        j = operator.index(j)
        if not -len(self._offs) <= j < len(self._offs):
            raise IndexError("list index out of range")
        self._count.n += 1
        return _decode_span(
            self._blob, int(self._offs[j]), int(self._lens[j])
        )

    def __iter__(self):
        return iter(self._made(self._offs, self._lens))

    def __eq__(self, other):
        if not isinstance(other, (list, SpanStrings)):
            return NotImplemented
        return list(self) == list(other)

    __hash__ = None

    def __repr__(self) -> str:
        return f"SpanStrings({list(self)!r})"


class LazyLine:
    """ParsedLine-compatible view over one native-parsed row.

    `rest` (the regex haystack, only needed for ban logging and host-regex
    fallback) decodes from the parse blob on first touch. `error`/
    `old_line` are class-level False: rows with either flag never enter a
    work set."""

    __slots__ = ("timestamp_ns", "ip", "host", "_nb", "_nbrow", "_rest")

    error = False
    old_line = False

    def __init__(self, nb, nbrow: int, ip: str, host: str, ts_ns: int):
        self.timestamp_ns = ts_ns
        self.ip = ip
        self.host = host
        self._nb = nb
        self._nbrow = nbrow
        self._rest = None

    @property
    def rest(self) -> str:
        if self._rest is None:
            self._rest = self._nb.rest(self._nbrow)
        return self._rest


class NativeWork:
    """(orig_index, line) sequence backed by the native ParsedBatch.

    `rows` are indices into the parse batch (== original line indices);
    `ip_inv`/`host_inv` index the shared distinct-address and
    distinct-host tables: `ips_u` a SpanStrings (a string an address
    when one is asked for; a list where the gate had to make them
    anyway), `hosts_u` a list — few, and `host_idx` reads every one for
    every chunk.  Slicing shares the tables (compaction happens in
    unique_ips, where a stale entry would otherwise leak a slot pin).
    `ip_spans` is the key bytes of `ips_u`, entry for entry, as (buf
    uint8, offs int64, lens int64): what the submit stage's address
    pass works on (None: it takes the strings)."""

    __slots__ = (
        "nb", "rows", "ips_u", "ip_inv", "hosts_u", "host_inv", "ts_ns",
        "defer_map", "ip_spans",
    )

    def __init__(self, nb, rows, ips_u, ip_inv, hosts_u, host_inv, ts_ns,
                 defer_map, ip_spans=None):
        self.nb = nb
        self.rows = rows                  # np.int64 [n] — nb/original rows
        self.ips_u: Sequence[str] = ips_u
        self.ip_inv = ip_inv              # np.int64 [n] -> ips_u
        self.hosts_u: List[str] = hosts_u
        self.host_inv = host_inv          # np.int64 [n] -> hosts_u
        self.ts_ns = ts_ns                # np.int64 [n]
        # python-parsed lines for FLAG_DEFER rows, keyed by nb row
        self.defer_map: Dict[int, ParsedLine] = defer_map
        self.ip_spans = ip_spans

    def __len__(self) -> int:
        return len(self.rows)

    def __getitem__(self, k):
        if isinstance(k, slice):
            return NativeWork(
                self.nb, self.rows[k], self.ips_u, self.ip_inv[k],
                self.hosts_u, self.host_inv[k], self.ts_ns[k],
                self.defer_map, self.ip_spans,
            )
        nbrow = int(self.rows[k])
        p = self.defer_map.get(nbrow)
        if p is None:
            p = LazyLine(
                self.nb, nbrow, self.ips_u[self.ip_inv[k]],
                self.hosts_u[self.host_inv[k]], int(self.ts_ns[k]),
            )
        return nbrow, p

    def __iter__(self):
        for k in range(len(self.rows)):
            yield self[k]

    def lines_at(self, ks) -> list:
        """[self[k] for k in ks] off one pass over the columns: the
        replay's route to the few rows of a chunk that have an effect."""
        ks = np.asarray(ks, dtype=np.int64)
        ips_u, hosts_u, nb, deferred = (
            self.ips_u, self.hosts_u, self.nb, self.defer_map
        )
        out = []
        for nbrow, ip_j, host_j, ts in zip(
            self.rows[ks].tolist(), self.ip_inv[ks].tolist(),
            self.host_inv[ks].tolist(), self.ts_ns[ks].tolist(),
        ):
            p = deferred.get(nbrow)
            if p is None:
                p = LazyLine(nb, nbrow, ips_u[ip_j], hosts_u[host_j], ts)
            out.append((nbrow, p))
        return out

    def take(self, idx) -> "NativeWork":
        """Arbitrary-row subset (index array) — same table-sharing
        semantics as slicing; the pipeline's drain-time staleness filter
        uses this to drop aged-out rows before the window pass."""
        idx = np.asarray(idx, dtype=np.int64)
        return NativeWork(
            self.nb, self.rows[idx], self.ips_u, self.ip_inv[idx],
            self.hosts_u, self.host_inv[idx], self.ts_ns[idx],
            self.defer_map, self.ip_spans,
        )

    def unique_ip_spans(self):
        """unique_ips() by bytes: (AddressSpans, per-row inverse), the
        same addresses in the same order, or None without the spans."""
        if self.ip_spans is None:
            return None
        return merge_spans([(self.ip_spans, self.ip_inv)])

    def unique_ips(self) -> Tuple[Sequence[str], np.ndarray]:
        """(distinct ips present in THIS view, per-row inverse). Compacts
        the shared table so a slice never allocates (and pins) window
        slots for ips that aren't in it."""
        present, inv = np.unique(self.ip_inv, return_inverse=True)
        if present.size == len(self.ips_u):
            # unsliced view (or one covering every table entry): ids are
            # already compact — skip the per-entry re-list
            return self.ips_u, self.ip_inv
        ips_u = self.ips_u
        return [ips_u[j] for j in present.tolist()], inv

    def orig_rows(self) -> np.ndarray:
        """Original line index per row (the results vector's index)."""
        return self.rows

    def host_idx(self, host_row: Dict[str, int]) -> np.ndarray:
        tbl = np.asarray(
            [host_row.get(h, 0) for h in self.hosts_u], dtype=np.int32
        )
        return tbl[self.host_inv] if len(self.hosts_u) else np.zeros(
            len(self.rows), dtype=np.int32
        )

    def ts_array(self) -> np.ndarray:
        return self.ts_ns

    def rest_bytes(self, ks) -> Tuple[np.ndarray, np.ndarray]:
        """(uint8 flat, int32 lens): the `rest` of rows `ks` as bytes,
        one after the other — slices of the parse blob joined, no string
        per row (the long rows' way to the device: a few dozen rows a
        chunk, for which this beats an index array a byte)."""
        ks = np.asarray(ks, dtype=np.int64)
        nbrows = self.rows[ks]
        if self.defer_map and np.isin(
            nbrows, np.fromiter(self.defer_map, np.int64, len(self.defer_map))
        ).any():
            return _rest_bytes_of_lines(self.lines_at(ks))
        blob = self.nb.blob
        off = self.nb.rest_off[nbrows].tolist()
        ln = self.nb.rest_len[nbrows].tolist()
        return _flat([blob[o : o + n] for o, n in zip(off, ln)])


def _flat(raws) -> Tuple[np.ndarray, np.ndarray]:
    return (
        np.frombuffer(b"".join(raws), dtype=np.uint8),
        np.fromiter(map(len, raws), dtype=np.int32, count=len(raws)),
    )


def _rest_bytes_of_lines(lines) -> Tuple[np.ndarray, np.ndarray]:
    """rest_bytes over materialized (index, line) pairs."""
    return _flat([p.rest.encode("utf-8", "surrogatepass") for _, p in lines])


class ListWork(list):
    """The [(orig_index, ParsedLine)] fallback path (python parse / no
    native lib) wearing the same interface as NativeWork."""

    def unique_ips(self) -> Tuple[List[str], np.ndarray]:
        uniq: "OrderedDict[str, int]" = OrderedDict()
        inv = np.empty(len(self), dtype=np.int64)
        for k, (_, p) in enumerate(self):
            j = uniq.get(p.ip)
            if j is None:
                j = len(uniq)
                uniq[p.ip] = j
            inv[k] = j
        return list(uniq), inv

    def unique_ip_spans(self):
        return None  # a Python parse holds strings

    def orig_rows(self) -> np.ndarray:
        return np.fromiter((i for i, _ in self), np.int64, len(self))

    def host_idx(self, host_row: Dict[str, int]) -> np.ndarray:
        return np.asarray(
            [host_row.get(p.host, 0) for _, p in self], dtype=np.int32
        )

    def ts_array(self) -> np.ndarray:
        # Python float()*1e9 can exceed int64; clamp exactly like the
        # native gate does for deferred rows — the columnar array only
        # feeds the device windows, while replay reads the exact Python
        # int from the ParsedLine
        lo, hi = -(2**63), 2**63 - 1
        return np.asarray(
            [min(max(p.timestamp_ns, lo), hi) for _, p in self],
            dtype=np.int64,
        )

    def __getitem__(self, k):
        if isinstance(k, slice):
            return ListWork(super().__getitem__(k))
        return super().__getitem__(k)

    def lines_at(self, ks) -> list:
        return [list.__getitem__(self, int(k)) for k in ks]

    def take(self, idx) -> "ListWork":
        """Arbitrary-row subset (index array) — NativeWork.take parity."""
        return ListWork(self.lines_at(idx))

    def rest_bytes(self, ks) -> Tuple[np.ndarray, np.ndarray]:
        return _rest_bytes_of_lines(self.lines_at(ks))


class CompositeWork:
    """Strict line-order concatenation of per-shard work sets — the merge
    half of the sharded encode pool (pipeline/scheduler.py).

    Each part is a NativeWork/ListWork built over ONE contiguous row
    shard of the admission batch; `offsets[j]` is the batch row its
    shard started at.  Indices surfaced to consumers — the (orig_index,
    line) pairs, and therefore results rows, window-event lines, and
    replay order — are GLOBAL batch rows, so every downstream consumer
    (slot scaffolding, the fused pipeline, replay, staleness take) is
    agnostic to whether the encode ran sharded or single-threaded.

    unique_ips() merges the per-shard first-appearance tables in shard
    order, which IS global first-appearance order over the kept rows —
    the property window-slot LRU assignment order (a parity surface)
    depends on.  Positional subsets (slice/take) expect ascending
    indices, which is what every caller passes (chunking, staleness
    keep-masks, binary splits)."""

    __slots__ = ("parts", "offsets", "_starts")

    def __init__(self, parts: List, offsets: List[int]):
        self.parts = parts        # non-empty work sets, shard order
        self.offsets = offsets    # first batch row of each part's shard
        self._starts = np.cumsum([0] + [len(w) for w in parts])

    def __len__(self) -> int:
        return int(self._starts[-1])

    def __getitem__(self, k):
        if isinstance(k, slice):
            return self.take(
                np.arange(*k.indices(len(self)), dtype=np.int64)
            )
        j = int(np.searchsorted(self._starts, k, side="right")) - 1
        i, p = self.parts[j][k - int(self._starts[j])]
        return self.offsets[j] + i, p

    def __iter__(self):
        for j, w in enumerate(self.parts):
            off = self.offsets[j]
            for i, p in w:
                yield off + i, p

    def lines_at(self, ks) -> list:
        """[self[k] for k in ks]: one search for all of them, then each
        part's own route for the rows that fall in it."""
        ks = np.asarray(ks, dtype=np.int64)
        part = np.searchsorted(self._starts, ks, side="right") - 1
        out: list = [None] * len(ks)
        for j in np.unique(part).tolist():
            at = np.flatnonzero(part == j)
            off = self.offsets[j]
            for k, (i, p) in zip(
                at.tolist(),
                self.parts[j].lines_at(ks[at] - int(self._starts[j])),
            ):
                out[k] = (off + i, p)
        return out

    def take(self, idx) -> "CompositeWork | ListWork":
        idx = np.asarray(idx, dtype=np.int64)
        parts: List = []
        offsets: List[int] = []
        for j, w in enumerate(self.parts):
            lo, hi = int(self._starts[j]), int(self._starts[j + 1])
            sel = idx[(idx >= lo) & (idx < hi)] - lo
            if sel.size:
                parts.append(w.take(sel))
                offsets.append(self.offsets[j])
        if not parts:
            return ListWork()
        if len(parts) == 1 and offsets[0] == 0:
            return parts[0]
        return CompositeWork(parts, offsets)

    def unique_ips(self) -> Tuple[List[str], np.ndarray]:
        tables = [
            (list(ips_u), inv)
            for ips_u, inv in (w.unique_ips() for w in self.parts)
        ]
        # dict.fromkeys keeps each string where it is met first: shard
        # order, then the shard's own first-appearance order
        strings = list(dict.fromkeys(
            itertools.chain.from_iterable(t[0] for t in tables)
        ))
        at = dict(zip(strings, range(len(strings)))).__getitem__
        return strings, np.concatenate([
            np.fromiter(map(at, ips_u), dtype=np.int64, count=len(ips_u))[
                np.asarray(inv, dtype=np.int64)
            ]
            for ips_u, inv in tables
        ])

    def unique_ip_spans(self):
        """unique_ips() by bytes: the shards' tables merged by one C
        dedup over their spans, in the same order — (AddressSpans,
        per-row inverse); None when a shard holds strings only."""
        tables = []
        for w in self.parts:
            enc = getattr(w, "ip_spans", None)
            if enc is None:
                return None
            tables.append((enc, w.ip_inv))
        return merge_spans(tables)

    def orig_rows(self) -> np.ndarray:
        return np.concatenate([
            np.asarray(w.orig_rows(), dtype=np.int64) + off
            for w, off in zip(self.parts, self.offsets)
        ])

    def host_idx(self, host_row: Dict[str, int]) -> np.ndarray:
        return np.concatenate([w.host_idx(host_row) for w in self.parts])

    def ts_array(self) -> np.ndarray:
        return np.concatenate([w.ts_array() for w in self.parts])

    def rest_bytes(self, ks) -> Tuple[np.ndarray, np.ndarray]:
        """`ks` ascending, as every positional subset here."""
        ks = np.asarray(ks, dtype=np.int64)
        part = np.searchsorted(self._starts, ks, side="right") - 1
        got = [
            self.parts[j].rest_bytes(ks[part == j] - int(self._starts[j]))
            for j in np.unique(part).tolist()
        ]
        if not got:
            return np.zeros(0, dtype=np.uint8), np.zeros(0, dtype=np.int32)
        return (
            np.concatenate([g[0] for g in got]),
            np.concatenate([g[1] for g in got]),
        )
