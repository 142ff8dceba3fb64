"""Native (C) batch parse+encode for the tailer hot path.

Loads fastparse.c as a ctypes shared library, compiling it with the system
C compiler on first use (cached beside the source; no pybind11/setuptools
needed). If no compiler is available the module degrades to None and the
callers keep the pure-Python path — semantics are identical either way
(the C side defers any line it cannot prove it parses identically).

This is the framework's native runtime tier for host-side IO (the Pallas
kernel being the device tier): at the 5M lines/s north star the Python
per-line parse loop is the host bottleneck; this runs it at memory speed.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import sysconfig
import tempfile
import threading
from typing import Optional

import numpy as np

from banjax_tpu.matcher.longrows import LONG_WIDTH

log = logging.getLogger(__name__)

FLAG_ERROR = 1
FLAG_OLD = 2
FLAG_DEFER = 4
FLAG_HOST_EVAL = 8
FLAG_LONG = 16  # beside HOST_EVAL: ASCII and within longrows.LONG_WIDTH

_SRC = os.path.join(os.path.dirname(__file__), "fastparse.c")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _so_path() -> str:
    plat = sysconfig.get_platform().replace("-", "_")
    cache_dir = os.environ.get(
        "BANJAX_NATIVE_CACHE", os.path.join(tempfile.gettempdir(), "banjax-native")
    )
    os.makedirs(cache_dir, exist_ok=True)
    # named by what the source says, not by when it was written: two
    # checkouts unpacked in the same second (a parent and a change, one
    # cache directory) must not load each other's build
    with open(_SRC, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()[:16]
    return os.path.join(cache_dir, f"fastparse_{plat}_{digest}.so")


def _compile(so: str) -> bool:
    for cc in (os.environ.get("CC"), "cc", "gcc", "clang"):
        if not cc:
            continue
        cmd = [cc, "-O3", "-shared", "-fPIC", "-o", so, _SRC, "-lm"]
        try:
            r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        except (OSError, subprocess.TimeoutExpired):
            continue
        if r.returncode == 0:
            return True
        log.debug("native compile with %s failed: %s", cc, r.stderr[-500:])
    return False


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("BANJAX_NO_NATIVE"):
            return None
        so = _so_path()
        if not os.path.exists(so) and not _compile(so):
            log.info("no C compiler available; using the Python parse path")
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("could not load %s: %s", so, e)
            return None
        # every pointer argument is a plain address: the blob goes in as
        # the bytes object it is, an array as the address its scratch
        # took once (ParseScratch / DedupScratch), with nothing to cast
        ptr, i64 = ctypes.c_void_p, ctypes.c_int64
        lib.fp_split_lines.restype = i64
        lib.fp_split_lines.argtypes = [ptr, i64, ptr, ptr, i64]
        lib.fp_parse_encode.restype = i64
        lib.fp_parse_encode.argtypes = [
            ptr, i64, ptr, ptr, i64,
            ptr, ctypes.c_int32, ctypes.c_int32, ctypes.c_double,
            ctypes.c_double,
            ptr, ptr, ptr, ptr, ptr, ptr, ptr, ptr, ptr, ptr,
        ]
        lib.fp_dedup_spans.restype = i64
        lib.fp_dedup_spans.argtypes = [
            ptr, i64, ptr, ptr, i64, ptr, i64, ptr, ptr,
        ]
        lib.fp_gate.restype = i64
        lib.fp_gate.argtypes = [
            ptr, i64, ptr, ptr, ptr, ptr, ptr, ptr, ptr, ctypes.c_int32,
            ptr, i64, ptr,
        ]
        _LIB = lib
        log.info("native fastparse loaded (%s)", so)
        return _LIB


def available() -> bool:
    return _load() is not None


class ParsedBatch:
    """Column-oriented result of one native parse+encode pass.

    String fields stay as (offset, length) spans into the blob; `.ip(i)`,
    `.host(i)`, `.rest(i)` materialize Python strings lazily — most lines
    only ever need ip/host (allowlist + active-table lookups)."""

    __slots__ = (
        "blob", "n", "ts_ns", "flags", "ip_off", "ip_len",
        "host_off", "host_len", "rest_off", "rest_len", "cls_ids", "lens",
        "addrs",
    )

    def __init__(self, blob, n, ts_ns, flags, ip_off, ip_len, host_off,
                 host_len, rest_off, rest_len, cls_ids, lens, addrs=None):
        self.blob = blob
        self.n = n
        self.ts_ns = ts_ns
        self.flags = flags
        self.ip_off, self.ip_len = ip_off, ip_len
        self.host_off, self.host_len = host_off, host_len
        self.rest_off, self.rest_len = rest_off, rest_len
        self.cls_ids = cls_ids
        self.lens = lens
        # the columns' addresses as their scratch holds them
        # (ParseScratch.addrs): what gate() hands to C
        self.addrs = addrs

    def _span(self, off, ln, i) -> str:
        o = int(off[i])
        return self.blob[o : o + int(ln[i])].decode("utf-8", "surrogatepass")

    def ip(self, i: int) -> str:
        return self._span(self.ip_off, self.ip_len, i)

    def host(self, i: int) -> str:
        return self._span(self.host_off, self.host_len, i)

    def rest(self, i: int) -> str:
        return self._span(self.rest_off, self.rest_len, i)


class ParseScratch:
    """Reusable output buffers for parse_encode_batch.

    Fresh numpy allocations cost ~15 ms in page faults per 65k-line batch
    (the [n, max_len] int32 class matrix alone is 33 MB at max_len 128;
    its width is the SHORT width whatever the longest line — a longer
    rest is flagged and left in the blob); a caller that
    parses batch after batch should own ONE scratch and pass it in. The
    returned ParsedBatch views alias the scratch — they are valid until
    the next parse_encode_batch call with the same scratch (the TpuMatcher
    consumes each batch fully before parsing the next)."""

    def __init__(self):
        self.cap = 0
        self.max_len = 0

    def ensure(self, n: int, max_len: int) -> None:
        if n <= self.cap and max_len == self.max_len:
            return
        cap = max(n, self.cap, 1024)
        self.cap, self.max_len = cap, max_len
        self.starts = np.empty(cap, dtype=np.int64)
        self.ends = np.empty(cap, dtype=np.int64)
        self.ts_ns = np.empty(cap, dtype=np.int64)
        self.flags = np.empty(cap, dtype=np.uint8)
        self.ip_off = np.empty(cap, dtype=np.int64)
        self.ip_len = np.empty(cap, dtype=np.int32)
        self.host_off = np.empty(cap, dtype=np.int64)
        self.host_len = np.empty(cap, dtype=np.int32)
        self.rest_off = np.empty(cap, dtype=np.int64)
        self.rest_len = np.empty(cap, dtype=np.int32)
        self.cls_ids = np.empty((cap, max_len), dtype=np.int32)
        self.lens = np.empty(cap, dtype=np.int32)
        # the arrays' addresses, taken here once and good until a buffer
        # grows: starts, ends, then fp_parse_encode's outputs in its
        # argument order; and the bytes a row of each
        cols = (
            self.starts, self.ends, self.ts_ns, self.flags, self.ip_off,
            self.ip_len, self.host_off, self.host_len, self.rest_off,
            self.rest_len, self.cls_ids, self.lens,
        )
        self.addrs = tuple(a.ctypes.data for a in cols)
        self.strides = tuple(a.strides[0] for a in cols)


# where a column's address lies in ParseScratch.addrs
_STARTS, _ENDS, _TS_NS, _FLAGS, _IP_OFF, _IP_LEN, _HOST_OFF, _HOST_LEN, \
    _REST_OFF, _REST_LEN = range(10)

# parse threads: fp_parse_encode is row-parallel and ctypes releases the
# GIL, so splitting the row range across a few threads scales the 14.5 ms
# (65k lines) C pass down to ~4-7 ms
_PARSE_THREADS = min(4, os.cpu_count() or 1)
_MIN_ROWS_PER_THREAD = 4096


def parse_encode_batch(
    lines, byte_to_class: np.ndarray, max_len: int,
    now_unix: float, old_cutoff: float,
    scratch: Optional[ParseScratch] = None,
    max_threads: Optional[int] = None,
) -> Optional[ParsedBatch]:
    """One native pass over a batch of log lines; None if the native
    library is unavailable (caller uses the Python path). With `scratch`,
    outputs alias the caller-owned buffers (see ParseScratch).
    `max_threads` caps the internal row-parallel fan-out — callers that
    are themselves one shard of a worker pool (the pipeline's sharded
    encode) pass 1 so the pool's parallelism isn't multiplied.
    An ASCII rest over `max_len` and within longrows.LONG_WIDTH gets
    FLAG_LONG beside FLAG_HOST_EVAL (its bytes stay in the blob:
    `rest_off`)."""
    lib = _load()
    if lib is None:
        return None
    blob = "\n".join(lines).encode("utf-8", "surrogatepass")
    n = len(lines)
    if n == 0:
        empty64 = np.zeros(0, dtype=np.int64)
        empty32 = np.zeros(0, dtype=np.int32)
        return ParsedBatch(blob, 0, empty64, np.zeros(0, np.uint8), empty64,
                           empty32, empty64, empty32, empty64, empty32,
                           np.zeros((0, max_len), np.int32), empty32)

    s = scratch if scratch is not None else ParseScratch()
    s.ensure(n, max_len)
    addrs = s.addrs
    got = lib.fp_split_lines(blob, len(blob), addrs[_STARTS], addrs[_ENDS], n)
    # embedded newline inside a "line" (callers pass tailer lines, which
    # cannot contain one) would shift every subsequent span: fall back
    # rather than misattribute. Detection rides the split itself (no extra
    # blob scan): extra newlines make the capped split stop short of the
    # blob end (or, for a trailing newline, return n-1 lines).
    if got != n or int(s.ends[n - 1]) != len(blob):
        return None

    table = np.ascontiguousarray(byte_to_class[:256], dtype=np.int32)
    table_addr = table.ctypes.data

    def run_range(i0: int, cnt: int) -> None:
        a = addrs if i0 == 0 else tuple(
            p + i0 * st for p, st in zip(addrs, s.strides)
        )
        lib.fp_parse_encode(
            blob, len(blob), a[_STARTS], a[_ENDS], cnt,
            table_addr, max_len, LONG_WIDTH, now_unix, old_cutoff, *a[2:],
        )

    limit = _PARSE_THREADS if max_threads is None else max(1, max_threads)
    nt = min(limit, max(1, n // _MIN_ROWS_PER_THREAD))
    if nt <= 1:
        run_range(0, n)
    else:
        bounds = [n * t // nt for t in range(nt + 1)]
        threads = [
            threading.Thread(
                target=run_range, args=(bounds[t], bounds[t + 1] - bounds[t])
            )
            for t in range(1, nt)
        ]
        for t in threads:
            t.start()
        run_range(bounds[0], bounds[1] - bounds[0])
        for t in threads:
            t.join()

    return ParsedBatch(blob, n, s.ts_ns[:n], s.flags[:n], s.ip_off[:n],
                       s.ip_len[:n], s.host_off[:n], s.host_len[:n],
                       s.rest_off[:n], s.rest_len[:n], s.cls_ids[:n],
                       s.lens[:n], addrs)


class DedupScratch:
    """Reusable hash-table + output buffers for dedup_spans and gate."""

    def __init__(self):
        self.cap = 0

    def ensure(self, n: int) -> None:
        if n <= self.cap:
            return
        cap = max(n, 1024)
        self.cap = cap
        tcap = 1
        while tcap < 2 * cap:
            tcap <<= 1
        self.table = np.empty(tcap, dtype=np.int64)
        self.ids = np.empty(cap, dtype=np.int64)
        self.first = np.empty(cap, dtype=np.int64)
        # (table, ids, first): addresses good until the next growth
        self.addrs = (
            self.table.ctypes.data, self.ids.ctypes.data,
            self.first.ctypes.data,
        )


def dedup_spans(blob, offs, lens, scratch=None):
    """(ids[n] first-appearance-ordered, first_rows[n_uniq]) for byte
    spans of `blob` — C open-addressing dedup; None when the native
    library is unavailable (caller falls back to the numpy path)."""
    lib = _load()
    if lib is None:
        return None
    n = len(offs)
    s = scratch if scratch is not None else DedupScratch()
    s.ensure(n)
    if n == 0:
        return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64)
    offs = np.ascontiguousarray(offs, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    table, ids, first = s.addrs
    n_uniq = lib.fp_dedup_spans(
        bytes(blob), len(blob), offs.ctypes.data, lens.ctypes.data, n,
        table, len(s.table), ids, first,
    )
    # copies, NOT views: a second dedup with the same scratch (the
    # reference gate runs ip then host spans back to back) must not
    # clobber the first call's result
    return s.ids[:n].copy(), s.first[:n_uniq].copy()


class GatedBatch:
    """What one fp_gate call leaves of a ParsedBatch: the candidate rows
    (neither error nor old nor deferred) in row order and, row for row,
    their `ts`, `ip_inv` / `host_inv` (first-appearance ids of the
    address and of the host) and `host_eval` / `long_len` (as
    longrows.long_lens has it); the distinct addresses' and hosts' spans
    in the blob, id order, int64; and how many rows carried ERROR, OLD,
    DEFER and (among the candidates) HOST_EVAL.  Every array is a view
    of one allocation of this call's own — nothing aliases a scratch."""

    __slots__ = (
        "rows", "ts", "ip_inv", "host_inv", "ip_off", "ip_len", "host_off",
        "host_len", "long_len", "host_eval", "n_err", "n_old", "n_defer",
        "n_host_eval",
    )


def gate(nb: ParsedBatch, scratch: Optional[DedupScratch] = None
         ) -> Optional[GatedBatch]:
    """The gate's columnar half over a parsed batch, one foreign call
    (fastparse.c fp_gate); None without the native library or over a
    batch that did not come off a ParseScratch."""
    lib = _load()
    n = nb.n
    if lib is None or nb.addrs is None or n == 0:
        return None
    s = scratch if scratch is not None else DedupScratch()
    s.ensure(n)
    a = nb.addrs
    out = np.empty(10 * n + 8, dtype=np.int64)
    lib.fp_gate(
        nb.blob, n, a[_FLAGS], a[_TS_NS], a[_IP_OFF], a[_IP_LEN],
        a[_HOST_OFF], a[_HOST_LEN], a[_REST_LEN], LONG_WIDTH,
        s.addrs[0], len(s.table), out.ctypes.data,
    )
    g = GatedBatch()
    c, n_ip, n_host, g.n_err, g.n_old, g.n_defer, g.n_host_eval, _ = \
        out[10 * n :].tolist()
    g.rows = out[:c]
    g.ts = out[n : n + c]
    g.ip_inv = out[2 * n : 2 * n + c]
    g.host_inv = out[3 * n : 3 * n + c]
    g.ip_off = out[4 * n : 4 * n + n_ip]
    g.ip_len = out[5 * n : 5 * n + n_ip]
    g.host_off = out[6 * n : 6 * n + n_host]
    g.host_len = out[7 * n : 7 * n + n_host]
    g.long_len = out[8 * n : 9 * n].view(np.int32)[:c]
    g.host_eval = out[9 * n : 10 * n].view(np.bool_)[:c]
    return g
