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
import logging
import os
import subprocess
import sysconfig
import tempfile
import threading
from typing import Optional

import numpy as np

from banjax_tpu.matcher.longrows import LONG_WIDTH
from banjax_tpu.native.cptr import array_ptr

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
    src_mtime = int(os.stat(_SRC).st_mtime)
    return os.path.join(cache_dir, f"fastparse_{plat}_{src_mtime}.so")


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
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.fp_split_lines.restype = ctypes.c_int64
        lib.fp_split_lines.argtypes = [u8p, ctypes.c_int64, i64p, i64p, ctypes.c_int64]
        lib.fp_parse_encode.restype = ctypes.c_int64
        lib.fp_parse_encode.argtypes = [
            u8p, ctypes.c_int64, i64p, i64p, ctypes.c_int64,
            i32p, ctypes.c_int32, ctypes.c_int32, ctypes.c_double,
            ctypes.c_double,
            i64p, u8p, i64p, i32p, i64p, i32p, i64p, i32p, i32p, i32p,
        ]
        lib.fp_dedup_spans.restype = ctypes.c_int64
        lib.fp_dedup_spans.argtypes = [
            u8p, ctypes.c_int64, i64p, i32p, ctypes.c_int64,
            i64p, ctypes.c_int64, i64p, i64p,
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
        "_text",
    )

    def __init__(self, blob, n, ts_ns, flags, ip_off, ip_len, host_off,
                 host_len, rest_off, rest_len, cls_ids, lens):
        self.blob = blob
        self.n = n
        self.ts_ns = ts_ns
        self.flags = flags
        self.ip_off, self.ip_len = ip_off, ip_len
        self.host_off, self.host_len = host_off, host_len
        self.rest_off, self.rest_len = rest_off, rest_len
        self.cls_ids = cls_ids
        self.lens = lens
        self._text = False  # False = not computed; None = non-ascii blob

    def text(self):
        """The whole blob as ONE str when it is pure ASCII (byte offsets
        == str offsets, so span strings are plain slices — ~10x cheaper
        than per-span bytes.decode), else None. Decoded once, cached."""
        if self._text is False:
            self._text = (
                self.blob.decode("ascii") if self.blob.isascii() else None
            )
        return self._text

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
    buf = np.frombuffer(blob, dtype=np.uint8)
    if n == 0:
        empty64 = np.zeros(0, dtype=np.int64)
        empty32 = np.zeros(0, dtype=np.int32)
        return ParsedBatch(blob, 0, empty64, np.zeros(0, np.uint8), empty64,
                           empty32, empty64, empty32, empty64, empty32,
                           np.zeros((0, max_len), np.int32), empty32)

    s = scratch if scratch is not None else ParseScratch()
    s.ensure(n, max_len)
    starts, ends = s.starts[:n], s.ends[:n]
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)

    P = array_ptr
    blob_ptr = array_ptr(buf, u8p) if buf.size else ctypes.cast(
        ctypes.c_char_p(b""), u8p
    )
    got = lib.fp_split_lines(blob_ptr, len(blob), P(starts, i64p), P(ends, i64p), n)
    # embedded newline inside a "line" (callers pass tailer lines, which
    # cannot contain one) would shift every subsequent span: fall back
    # rather than misattribute. Detection rides the split itself (no extra
    # blob scan): extra newlines make the capped split stop short of the
    # blob end (or, for a trailing newline, return n-1 lines).
    if got != n or int(ends[n - 1]) != len(blob):
        return None

    table = np.ascontiguousarray(byte_to_class[:256], dtype=np.int32)

    def run_range(i0: int, cnt: int) -> None:
        lib.fp_parse_encode(
            blob_ptr, len(blob),
            P(s.starts[i0:], i64p), P(s.ends[i0:], i64p), cnt,
            P(table, i32p), max_len, LONG_WIDTH, now_unix, old_cutoff,
            P(s.ts_ns[i0:], i64p), P(s.flags[i0:], u8p),
            P(s.ip_off[i0:], i64p), P(s.ip_len[i0:], i32p),
            P(s.host_off[i0:], i64p), P(s.host_len[i0:], i32p),
            P(s.rest_off[i0:], i64p), P(s.rest_len[i0:], i32p),
            P(s.cls_ids[i0:], i32p), P(s.lens[i0:], i32p),
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
                       s.lens[:n])


class DedupScratch:
    """Reusable hash-table + output buffers for dedup_spans."""

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
    buf = np.frombuffer(blob, dtype=np.uint8)
    offs = np.ascontiguousarray(offs, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    tcap = len(s.table)
    n_uniq = lib.fp_dedup_spans(
        array_ptr(buf, u8p), len(blob),
        array_ptr(offs, i64p), array_ptr(lens, i32p), n,
        array_ptr(s.table, i64p), tcap,
        array_ptr(s.ids, i64p), array_ptr(s.first, i64p),
    )
    # copies, NOT views: a second dedup with the same scratch (the gate
    # runs ip then host spans back to back) must not clobber the first
    # call's result
    return s.ids[:n].copy(), s.first[:n_uniq].copy()
