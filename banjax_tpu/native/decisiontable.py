"""Loader + wrapper for the shared decision table (decisiontable.c).

The compiled /auth_request fast path's data plane: a shm-resident,
seqlock-read table of already-decided IPs.  The primary process owns the
segment and mirrors every `DynamicDecisionLists` mutation into it
(decisions/dynamic_lists.py `set_mirror`); fastserve workers attach by
name and answer hot lookups with one lock-free probe instead of the
Python decision chain.

Compiled with the same on-demand ctypes pattern as shmstate (native/
shm.py); no compiler => `PyDecisionTable`, an in-process dict with the
same refusal/expiry semantics, keeps single-process deployments on the
fast path.  Every entry point fails open: a closed table, a torn read,
or a refused insert only ever means "serve it through the Python chain".
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import sysconfig
import tempfile
import threading
import time
from multiprocessing import shared_memory
from typing import List, Optional, Sequence, Tuple

import numpy as np

from banjax_tpu.native.cptr import array_ptr

log = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "decisiontable.c")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

KEY_MAX = 64
SLOT_BYTES = 96
HEADER_BYTES = 128
MAX_PROBE = 64

FLAG_FROM_BASKERVILLE = 0x01

_I32P = ctypes.POINTER(ctypes.c_int32)
_U8P = ctypes.POINTER(ctypes.c_uint8)


def _so_path() -> str:
    plat = sysconfig.get_platform().replace("-", "_")
    cache_dir = os.environ.get(
        "BANJAX_NATIVE_CACHE", os.path.join(tempfile.gettempdir(), "banjax-native")
    )
    os.makedirs(cache_dir, exist_ok=True)
    src_mtime = int(os.stat(_SRC).st_mtime)
    return os.path.join(cache_dir, f"decisiontable_{plat}_{src_mtime}.so")


def _compile(so: str) -> bool:
    for cc in (os.environ.get("CC"), "cc", "gcc", "clang"):
        if not cc:
            continue
        cmd = [cc, "-O3", "-shared", "-fPIC", "-o", so, _SRC]
        try:
            r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        except (OSError, subprocess.TimeoutExpired):
            continue
        if r.returncode == 0:
            return True
        log.debug("decisiontable compile with %s failed: %s", cc, r.stderr[-500:])
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
            log.info("no C compiler; native decision table unavailable")
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("could not load %s: %s", so, e)
            return None
        vp = ctypes.c_void_p
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        dp = ctypes.POINTER(ctypes.c_double)
        lib.dt_init.restype = ctypes.c_int64
        lib.dt_init.argtypes = [vp, ctypes.c_int64]
        lib.dt_check.restype = ctypes.c_int64
        lib.dt_check.argtypes = [vp]
        lib.dt_put.restype = ctypes.c_int32
        lib.dt_put.argtypes = [
            vp, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_uint32, ctypes.c_double,
            ctypes.c_double,
        ]
        lib.dt_put_many.restype = ctypes.c_int32
        lib.dt_put_many.argtypes = [
            vp, ctypes.c_int32, ctypes.c_char_p, _I32P, ctypes.c_char_p,
            _I32P, _U8P, ctypes.c_int32, ctypes.c_double, ctypes.c_double,
        ]
        lib.dt_get.restype = ctypes.c_int32
        lib.dt_get.argtypes = [
            vp, ctypes.c_char_p, ctypes.c_int32, u8p, u8p, u32p, dp,
        ]
        lib.dt_del.restype = ctypes.c_int32
        lib.dt_del.argtypes = [vp, ctypes.c_char_p, ctypes.c_int32]
        lib.dt_clear.restype = None
        lib.dt_clear.argtypes = [vp]
        lib.dt_len.restype = ctypes.c_int64
        lib.dt_len.argtypes = [vp]
        lib.dt_dropped.restype = ctypes.c_int64
        lib.dt_dropped.argtypes = [vp]
        lib.dt_session_add.restype = ctypes.c_int64
        lib.dt_session_add.argtypes = [vp, ctypes.c_int64]
        lib.dt_session_count.restype = ctypes.c_int64
        lib.dt_session_count.argtypes = [vp]
        lib.dt_site_hash.restype = ctypes.c_uint32
        lib.dt_site_hash.argtypes = [ctypes.c_char_p, ctypes.c_int32]
        lib.dt_set_steal_ns.restype = None
        lib.dt_set_steal_ns.argtypes = [ctypes.c_int64]
        lib.dt_test_wedge_slot.restype = None
        lib.dt_test_wedge_slot.argtypes = [vp, ctypes.c_char_p, ctypes.c_int32]
        lib.dt_test_unwedge_slot.restype = None
        lib.dt_test_unwedge_slot.argtypes = [
            vp, ctypes.c_char_p, ctypes.c_int32,
        ]
        _LIB = lib
        return _LIB


def available() -> bool:
    return _load() is not None


def _round_pow2(capacity: int) -> int:
    cap = 2
    while cap < max(2, capacity):
        cap *= 2
    return cap


def _offsets(parts: List[bytes]) -> np.ndarray:
    """int32 [n + 1]: where each part starts in their join, and its end."""
    offs = np.zeros(len(parts) + 1, dtype=np.int32)
    np.cumsum([len(b) for b in parts], out=offs[1:])
    return offs


def _key(ip: str) -> bytes:
    # a zero-length key marks a slot free in the C table; the empty
    # client IP maps to a one-NUL sentinel no real IP collides with
    return ip.encode("utf-8", "surrogatepass")[:KEY_MAX] or b"\x00"


class ShmDecisionTable:
    """The native table over a POSIX shared-memory segment.

    `get(ip)` is the serving hot path: lock-free, one bounded probe, and
    any fault (torn read, closed handle) reads as a miss — the caller
    falls open to the chain.  Mutations take the in-segment writer lock.
    """

    def __init__(self, name: Optional[str] = None, capacity: int = 65536):
        lib = _load()
        if lib is None:
            raise RuntimeError("native decisiontable unavailable (no C compiler?)")
        self._lib = lib
        self._out = threading.local()
        self.capacity = _round_pow2(capacity)
        size = HEADER_BYTES + self.capacity * SLOT_BYTES
        if name is None:
            self._shm = shared_memory.SharedMemory(create=True, size=size)
            self.owner = True
            self._map_base()
            if lib.dt_init(self._base_ptr, self.capacity) < 0:
                raise ValueError(f"capacity {self.capacity} not a power of two")
        else:
            self._shm = shared_memory.SharedMemory(name=name)
            self.owner = False
            # Python ≤3.12: attaching registers the segment with THIS
            # process's resource tracker, which unlinks it when this
            # process exits — yanking the table out from under the
            # primary and the other workers.  Only the creator unlinks.
            try:
                from multiprocessing import resource_tracker

                resource_tracker.unregister(self._shm._name, "shared_memory")
            except Exception:  # noqa: BLE001 — tracker internals shifted
                pass
            self._map_base()
            cap = lib.dt_check(self._base_ptr)
            if cap < 0:
                raise RuntimeError(f"shm segment {name} is not a dt table")
            self.capacity = int(cap)

    @property
    def name(self) -> str:
        return self._shm.name

    def _map_base(self) -> None:
        tmp = (ctypes.c_char * 1).from_buffer(self._shm.buf)
        self._base_ptr = ctypes.c_void_p(ctypes.addressof(tmp))
        del tmp

    def put(self, ip: str, decision: int, expires: float,
            from_baskerville: bool = False, domain: str = "",
            now: Optional[float] = None) -> bool:
        base = self._base_ptr
        if base is None:
            return False
        key = _key(ip)
        flags = FLAG_FROM_BASKERVILLE if from_baskerville else 0
        dk = domain.encode("utf-8", "surrogatepass")
        site_hash = self._lib.dt_site_hash(dk, len(dk)) if dk else 0
        if now is None:
            now = time.time()
        return self._lib.dt_put(
            base, key, len(key), int(decision), flags, site_hash,
            float(expires), float(now),
        ) == 0

    def put_many(self, entries: Sequence[Tuple[str, int, str]],
                 expires: float, from_baskerville: bool = False,
                 now: Optional[float] = None) -> int:
        """`put` for each (ip, decision, domain) in order, in one native
        call over packed arrays → how many were stored."""
        base = self._base_ptr
        if base is None or not entries:
            return 0
        keys = [_key(ip) for ip, _, _ in entries]
        doms = [d.encode("utf-8", "surrogatepass") for _, _, d in entries]
        key_offs = _offsets(keys)
        dom_offs = _offsets(doms)
        decisions = np.fromiter(
            (d for _, d, _ in entries), dtype=np.uint8, count=len(entries)
        )
        if now is None:
            now = time.time()
        return int(self._lib.dt_put_many(
            base, len(entries), b"".join(keys), array_ptr(key_offs, _I32P),
            b"".join(doms), array_ptr(dom_offs, _I32P),
            array_ptr(decisions, _U8P),
            FLAG_FROM_BASKERVILLE if from_baskerville else 0,
            float(expires), float(now),
        ))

    def get(self, ip: str) -> Optional[Tuple[int, float, bool]]:
        """(decision, expires, from_baskerville) or None — a torn-read
        fault also reads as None (fail-open, the chain serves it).

        The out-params are preallocated per thread: get() runs once per
        request on the serving hot path, and four ctypes allocations per
        call cost more than the probe itself.
        """
        base = self._base_ptr
        if base is None:
            return None
        key = ip.encode("utf-8", "surrogatepass")
        if len(key) > KEY_MAX or not key:
            key = key[:KEY_MAX] or b"\x00"
        out = self._out
        try:
            cells = out.cells
        except AttributeError:
            cells = out.cells = (
                ctypes.c_uint8(0), ctypes.c_uint8(0),
                ctypes.c_uint32(0), ctypes.c_double(0.0),
            )
            out.refs = tuple(ctypes.byref(c) for c in cells)
        decision, flags, _site_hash, expires = cells
        rc = self._lib.dt_get(base, key, len(key), *out.refs)
        if rc != 0:
            return None
        return (
            int(decision.value),
            float(expires.value),
            bool(flags.value & FLAG_FROM_BASKERVILLE),
        )

    def delete(self, ip: str) -> bool:
        base = self._base_ptr
        if base is None:
            return False
        key = _key(ip)
        return self._lib.dt_del(base, key, len(key)) == 0

    def clear(self) -> None:
        base = self._base_ptr
        if base is not None:
            self._lib.dt_clear(base)

    def __len__(self) -> int:
        base = self._base_ptr
        return int(self._lib.dt_len(base)) if base is not None else 0

    @property
    def dropped(self) -> int:
        base = self._base_ptr
        return int(self._lib.dt_dropped(base)) if base is not None else 0

    def session_add(self, delta: int) -> int:
        base = self._base_ptr
        if base is None:
            return 0
        return int(self._lib.dt_session_add(base, delta))

    def session_count(self) -> int:
        base = self._base_ptr
        return int(self._lib.dt_session_count(base)) if base is not None else 0

    # --- fault-test hooks (tests/unit/test_decisiontable.py) ---

    def set_steal_ns(self, ns: int) -> None:
        self._lib.dt_set_steal_ns(ns)

    def _test_wedge(self, ip: str) -> None:
        key = _key(ip)
        self._lib.dt_test_wedge_slot(self._base_ptr, key, len(key))

    def _test_unwedge(self, ip: str) -> None:
        key = _key(ip)
        self._lib.dt_test_unwedge_slot(self._base_ptr, key, len(key))

    def close(self) -> None:
        self._base_ptr = None
        self._shm.close()

    def unlink(self) -> None:
        if self.owner:
            try:
                self._shm.unlink()
            except FileNotFoundError:
                pass


class PyDecisionTable:
    """In-process fallback with identical semantics: bounded capacity,
    refusal (never eviction of a live entry) when full, expired-entry
    reuse, and the same session counter.  Single-process layouts only —
    it cannot be shared across workers."""

    def __init__(self, capacity: int = 65536):
        self.capacity = _round_pow2(capacity)
        self.owner = True
        self._lock = threading.Lock()
        self._entries = {}  # ip -> (decision, expires, from_baskerville)
        self._dropped = 0
        self._sessions = 0
        self._closed = False

    @property
    def name(self) -> None:  # no shm segment to attach to
        return None

    def put(self, ip: str, decision: int, expires: float,
            from_baskerville: bool = False, domain: str = "",
            now: Optional[float] = None) -> bool:
        with self._lock:
            if self._closed:
                return False
            if ip not in self._entries and len(self._entries) >= self.capacity:
                if now is None:
                    import time

                    now = time.time()
                stale = next(
                    (k for k, v in self._entries.items() if now - v[1] > 0),
                    None,
                )
                if stale is None:
                    self._dropped += 1
                    return False
                del self._entries[stale]
            self._entries[ip] = (int(decision), float(expires),
                                 bool(from_baskerville))
            return True

    def put_many(self, entries: Sequence[Tuple[str, int, str]],
                 expires: float, from_baskerville: bool = False,
                 now: Optional[float] = None) -> int:
        return sum(
            self.put(ip, decision, expires, from_baskerville, domain, now)
            for ip, decision, domain in entries
        )

    def get(self, ip: str) -> Optional[Tuple[int, float, bool]]:
        with self._lock:
            # closed reads as a miss, same as the shm table's nulled base
            if self._closed:
                return None
            return self._entries.get(ip)

    def delete(self, ip: str) -> bool:
        with self._lock:
            if self._closed:
                return False
            return self._entries.pop(ip, None) is not None

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._sessions = 0

    def __len__(self) -> int:
        with self._lock:
            return 0 if self._closed else len(self._entries)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def session_add(self, delta: int) -> int:
        with self._lock:
            if self._closed:
                return 0
            self._sessions = max(0, self._sessions + delta)
            return self._sessions

    def session_count(self) -> int:
        with self._lock:
            return 0 if self._closed else self._sessions

    def close(self) -> None:
        self._closed = True

    def unlink(self) -> None:
        pass


def create_decision_table(capacity: int = 65536,
                          name: Optional[str] = None):
    """Factory: the shm table when the native lib is available, else the
    Python fallback (create only — ATTACHING by name requires the native
    lib; returns None so the worker simply serves through the chain)."""
    if available():
        try:
            return ShmDecisionTable(name=name, capacity=capacity)
        except Exception:  # noqa: BLE001 — never block startup on the table
            log.exception("shm decision table unavailable; falling back")
    if name is not None:
        return None
    return PyDecisionTable(capacity=capacity)
