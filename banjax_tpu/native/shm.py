"""Loader + wrapper for the shared-memory rate-limit table (shmstate.c).

`ShmFailedChallengeStates` is a drop-in for
`banjax_tpu.decisions.rate_limit.FailedChallengeRateLimitStates` whose
state lives in a POSIX shared-memory segment, so N SO_REUSEPORT worker
processes count an IP's failed challenges exactly once — the
multi-process twin of the reference's mutex-guarded map
(/root/reference/internal/rate_limit.go:105-156).

Compiled with the same on-demand ctypes pattern as fastparse (see
native/__init__.py); unavailable compiler => callers keep the
single-process Python path.
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
from typing import List, Optional, Tuple

import numpy as np

from banjax_tpu.decisions.rate_limit import RateLimitMatchType, RateLimitResult
from banjax_tpu.native.cptr import array_ptr

log = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "shmstate.c")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

KEY_MAX = 104
SLOT_BYTES = 128
HEADER_BYTES = 128

MATCH_MASK = 0x0F
EXCEEDED_BIT = 0x10
DROPPED_BIT = 0x100


def _so_path() -> str:
    plat = sysconfig.get_platform().replace("-", "_")
    cache_dir = os.environ.get(
        "BANJAX_NATIVE_CACHE", os.path.join(tempfile.gettempdir(), "banjax-native")
    )
    os.makedirs(cache_dir, exist_ok=True)
    src_mtime = int(os.stat(_SRC).st_mtime)
    return os.path.join(cache_dir, f"shmstate_{plat}_{src_mtime}.so")


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
        log.debug("shmstate compile with %s failed: %s", cc, r.stderr[-500:])
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
            log.info("no C compiler; shared-memory rate-limit state unavailable")
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("could not load %s: %s", so, e)
            return None
        vp = ctypes.c_void_p
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.fc_init.restype = ctypes.c_int64
        lib.fc_init.argtypes = [vp, ctypes.c_int64]
        lib.fc_check.restype = ctypes.c_int64
        lib.fc_check.argtypes = [vp]
        lib.fc_apply.restype = ctypes.c_int32
        lib.fc_apply.argtypes = [
            vp, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, i32p,
        ]
        lib.fc_count.restype = ctypes.c_int64
        lib.fc_count.argtypes = [vp]
        lib.fc_dropped.restype = ctypes.c_int64
        lib.fc_dropped.argtypes = [vp]
        lib.fc_snapshot.restype = ctypes.c_int64
        lib.fc_snapshot.argtypes = [
            vp, ctypes.c_char_p, i32p, i32p, i64p, ctypes.c_int64,
        ]
        lib.fc_set_steal_ns.restype = None
        lib.fc_set_steal_ns.argtypes = [ctypes.c_int64]
        lib.fc_test_lock_slot.restype = None
        lib.fc_test_lock_slot.argtypes = [vp, ctypes.c_int64, ctypes.c_int32]
        lib.fc_test_slot_owner.restype = ctypes.c_int32
        lib.fc_test_slot_owner.argtypes = [vp, ctypes.c_int64]
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.wt_init.restype = ctypes.c_int64
        lib.wt_init.argtypes = [
            vp, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.wt_check.restype = ctypes.c_int64
        lib.wt_check.argtypes = [vp]
        for fn in (lib.wt_max_rules, lib.wt_len, lib.wt_dropped,
                   lib.wt_probes, lib.wt_record_reads, lib.wt_bytes_written,
                   lib.wt_blocks_used):
            fn.restype = ctypes.c_int64
            fn.argtypes = [vp]
        lib.wt_clear.restype = None
        lib.wt_clear.argtypes = [vp]
        lib.wt_put.restype = ctypes.c_int64
        lib.wt_put.argtypes = [
            vp, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_int64, i32p, i32p, i64p, i64p, ctypes.c_int64,
        ]
        lib.wt_take.restype = ctypes.c_int64
        lib.wt_take.argtypes = [
            vp, ctypes.c_char_p, ctypes.c_int32, i32p, i32p, i64p, i64p,
        ]
        lib.wt_get.restype = ctypes.c_int64
        lib.wt_get.argtypes = [
            vp, ctypes.c_char_p, ctypes.c_int32, i32p, i32p, i64p, i64p,
        ]
        lib.wt_snapshot_keys.restype = ctypes.c_int64
        lib.wt_snapshot_keys.argtypes = [
            vp, ctypes.c_char_p, i32p, ctypes.c_int64,
        ]
        lib.wt_contains_batch.restype = ctypes.c_int64
        lib.wt_contains_batch.argtypes = [
            vp, u8p, i64p, i64p, ctypes.c_int64, u8p,
        ]
        i64 = ctypes.c_int64
        lib.sh_create.restype = vp
        lib.sh_create.argtypes = [i64]
        lib.sh_destroy.restype = None
        lib.sh_destroy.argtypes = [vp]
        lib.sh_clear.restype = None
        lib.sh_clear.argtypes = [vp]
        lib.sh_grow.restype = i64
        lib.sh_grow.argtypes = [vp, i64]
        lib.sh_records.restype = i64
        lib.sh_records.argtypes = [vp]
        lib.sh_next_stamp.restype = i64
        lib.sh_next_stamp.argtypes = [vp]
        lib.sh_absorb.restype = i64
        lib.sh_absorb.argtypes = [
            vp, i32p, i64, i64p, i32p, i32p, i32p, i32p, i32p, i64,
        ]
        lib.sh_install.restype = i64
        lib.sh_install.argtypes = [vp, i64, i64, i32p, i32p, i64p, i64p, i64]
        lib.sh_spill.restype = i64
        lib.sh_spill.argtypes = [
            vp, vp, i64p, i64, u8p, i32p, i64, i64, i64, u8p,
        ]
        lib.sh_refill.restype = i64
        lib.sh_refill.argtypes = [vp, vp, i32p, u8p, i64p, i64p, i64, i64p]
        lib.sh_live_slots.restype = i64
        lib.sh_live_slots.argtypes = [vp, i32p]
        lib.sh_counts.restype = i64
        lib.sh_counts.argtypes = [vp, i32p, i64, i32p, i64p]
        lib.sh_export.restype = None
        lib.sh_export.argtypes = [
            vp, i32p, i64, i32p, i32p, i64p, i64p, ctypes.c_int32,
        ]
        lib.sh_restore_count.restype = i64
        lib.sh_restore_count.argtypes = [vp, i32p, i64p, i64]
        lib.sh_restore_rows.restype = i64
        lib.sh_restore_rows.argtypes = [vp, i32p, i64p, i64, i64, i64, i32p]
        _LIB = lib
        return _LIB


def available() -> bool:
    return _load() is not None


class ShmFailedChallengeStates:
    """Failed-challenge rate limiter over a shared-memory table.

    Same `apply(ip, config) -> RateLimitResult` / `__len__` /
    `format_states()` interface as the Python class; iteration order of
    format_states is table order (hash order), not insertion order — the
    route's output contract does not pin an order.
    """

    def __init__(self, name: Optional[str] = None, capacity: int = 65536):
        lib = _load()
        if lib is None:
            raise RuntimeError("native shmstate unavailable (no C compiler?)")
        self._lib = lib
        self.capacity = capacity
        size = HEADER_BYTES + capacity * SLOT_BYTES
        if name is None:
            self._shm = shared_memory.SharedMemory(create=True, size=size)
            self.owner = True
            self._map_base()
            if lib.fc_init(self._base_ptr, capacity) != 0:
                raise ValueError(f"capacity {capacity} not a power of two")
        else:
            self._shm = shared_memory.SharedMemory(name=name)
            self.owner = False
            # Python ≤3.12: attaching registers the segment with THIS
            # process's resource tracker, which unlinks it when this
            # process exits — yanking the table out from under the primary
            # and the other workers.  Only the creator may unlink.
            try:
                from multiprocessing import resource_tracker

                resource_tracker.unregister(self._shm._name, "shared_memory")
            except Exception:  # noqa: BLE001 — tracker internals shifted
                pass
            self._map_base()
            cap = lib.fc_check(self._base_ptr)
            if cap < 0:
                raise RuntimeError(f"shm segment {name} is not an fc table")
            self.capacity = int(cap)

    @property
    def name(self) -> str:
        return self._shm.name

    def _map_base(self) -> None:
        # extract the raw mapping address once; the transient from_buffer
        # export is dropped immediately so close() can release the mmap.
        # The address stays valid while self._shm is open (object lifetime).
        tmp = (ctypes.c_char * 1).from_buffer(self._shm.buf)
        self._base_ptr = ctypes.c_void_p(ctypes.addressof(tmp))
        del tmp

    def _base(self) -> ctypes.c_void_p:
        return self._base_ptr

    def apply(self, ip: str, config) -> RateLimitResult:
        # a zero-length key would mark the slot "empty" in the C table, so
        # an empty client IP maps to a one-NUL sentinel (no real IP
        # collides with it); the Python limiter counts "" normally and so
        # must we
        key = ip.encode("utf-8", "surrogatepass")[:KEY_MAX] or b"\x00"
        interval_ns = (
            config.too_many_failed_challenges_interval_seconds * 1_000_000_000
        )
        threshold = config.too_many_failed_challenges_threshold
        base = self._base()
        if base is None:  # closed (shutdown); NULL would segfault in C
            return RateLimitResult()
        hits = ctypes.c_int32(0)
        rc = self._lib.fc_apply(
            base, key, len(key), time.time_ns(), interval_ns,
            threshold, ctypes.byref(hits),
        )
        return RateLimitResult(
            match_type=RateLimitMatchType(rc & MATCH_MASK),
            exceeded=bool(rc & EXCEEDED_BIT),
        )

    def __len__(self) -> int:
        base = self._base()
        return int(self._lib.fc_count(base)) if base is not None else 0

    @property
    def dropped(self) -> int:
        base = self._base()
        return int(self._lib.fc_dropped(base)) if base is not None else 0

    def _entries(self) -> List[Tuple[str, int, int]]:
        if self._base() is None:
            return []
        cap = self.capacity
        blob = ctypes.create_string_buffer(cap * KEY_MAX)
        key_lens = np.zeros(cap, dtype=np.int32)
        hits = np.zeros(cap, dtype=np.int32)
        starts = np.zeros(cap, dtype=np.int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        n = self._lib.fc_snapshot(
            self._base(), blob, array_ptr(key_lens, i32p),
            array_ptr(hits, i32p), array_ptr(starts, i64p), cap,
        )
        out = []
        for i in range(int(n)):
            raw = blob.raw[i * KEY_MAX : i * KEY_MAX + int(key_lens[i])]
            if raw == b"\x00":
                raw = b""  # the empty-ip sentinel (see apply)
            out.append(
                (raw.decode("utf-8", "surrogatepass"), int(hits[i]), int(starts[i]))
            )
        return out

    def format_states(self) -> str:
        # same line format as FailedChallengeRateLimitStates.format_states
        return "".join(
            f"{ip},: interval_start: {start}, num hits: {hits}\n"
            for ip, hits, start in self._entries()
        )

    # --- fault-test hooks (tests/faults/test_shm_lock_steal.py) ---

    def set_steal_ns(self, ns: int) -> None:
        """Lower the lock-steal bound (process-wide, test-only)."""
        self._lib.fc_set_steal_ns(ns)

    def _test_lock_slot(self, idx: int, tag: int) -> None:
        """Plant a raw owner tag on slot idx, simulating a holder that
        died (dead pid tag) or wedged (live pid tag) mid-critical-section."""
        self._lib.fc_test_lock_slot(self._base(), idx, tag)

    def _test_slot_owner(self, idx: int) -> int:
        return int(self._lib.fc_test_slot_owner(self._base(), idx))

    def close(self) -> None:
        self._base_ptr = None
        self._shm.close()

    def unlink(self) -> None:
        if self.owner:
            try:
                self._shm.unlink()
            except FileNotFoundError:
                pass


# ---------------------------------------------------------------------------
# Warm-tier IP window store (mega-state tiering).
#
# The middle tier of the three-tier hierarchy: device slots (hot) spill a
# victim's full per-rule (num_hits, interval_start) vector here on
# eviction, and a returning IP refills byte-identically on slot claim.
# Entry order inside a record is preserved (the hot tier's shadow is an
# OrderedDict; insertion order is part of the round-trip contract).
#
# Two implementations with one interface:
#   ShmWarmTier — the C table appended to shmstate.c (wt_*), backed by a
#       shared-memory segment; O(1) probe-bounded put/take and a single
#       batched membership call per admission check.  Probes walk a dense
#       index of one 8-byte tag per position and read a record only where
#       the tag is the key's, so an absent key costs no record page.
#   PyWarmTier  — bounded-OrderedDict fallback when no C compiler is
#       available; same steal-iff-expired / drop-and-count overflow
#       policy, approximated globally instead of per probe window (it
#       drops strictly less often, never more).
#
# Both are externally locked by DeviceWindows, like slotmgr.


# (rule_id, num_hits, interval_start_s, interval_start_ns) — exactly the
# shadow map's value tuple with the rule id made explicit
WarmEntries = List[Tuple[int, int, int, int]]

WT_KEY_MAX = 104
WT_TAG_BYTES = 8
WT_HEAD_BYTES = 8
WT_REC_HEADER_BYTES = 128
WT_ENTRY_BYTES = 24
# a record is a chain of 256-byte blocks: the first holds the record
# header and 5 entries, each further one 10 (wt_rec / wt_cont)
WT_BLOCK_BYTES = 256
WT_HEAD_ENTRIES = 5
WT_CONT_ENTRIES = 10
# the arena holds a full record for every position, at most this many
# blocks a position (4 KiB: what a record of the fixed-stride layout
# made resident, a page of its own); past it a put is dropped and counted
WT_MAX_BLOCKS_PER_POSITION = 16


def wt_record_blocks(n_entries: int) -> int:
    """Blocks a record of `n_entries` counters takes."""
    return 1 + -(-max(0, n_entries - WT_HEAD_ENTRIES) // WT_CONT_ENTRIES)


def wt_record_bytes(n_entries: int) -> int:
    """What a put of `n_entries` counters writes (wt_fill's count)."""
    return (WT_REC_HEADER_BYTES + WT_ENTRY_BYTES * n_entries
            + 8 * (wt_record_blocks(n_entries) - 1))


def _wt_key(ip: str) -> bytes:
    # same empty-key sentinel as the fc table: key_len 0 means "empty
    # slot" in C, so the empty ip maps to one NUL byte
    return ip.encode("utf-8", "surrogatepass")[:WT_KEY_MAX] or b"\x00"


class ShmWarmTier:
    """Warm-tier table over a shared-memory segment (wt_* in shmstate.c).

    All calls must be externally locked — DeviceWindows holds its own
    lock around every use, the slotmgr convention.
    """

    def __init__(
        self,
        capacity: int = 1 << 20,
        max_rules: int = 16,
        expiry_ns: int = 300 * 1_000_000_000,
        name: Optional[str] = None,
    ):
        lib = _load()
        if lib is None:
            raise RuntimeError("native shmstate unavailable (no C compiler?)")
        self._lib = lib
        cap = 1
        while cap < max(2, capacity):
            cap *= 2
        self.capacity = cap
        self.max_rules = max(1, int(max_rules))
        self.expiry_ns = int(expiry_ns)
        n_blocks = cap * min(
            wt_record_blocks(self.max_rules), WT_MAX_BLOCKS_PER_POSITION
        )
        size = (HEADER_BYTES + cap * (WT_TAG_BYTES + WT_HEAD_BYTES)
                + n_blocks * WT_BLOCK_BYTES)
        if name is None:
            self._shm = shared_memory.SharedMemory(create=True, size=size)
            self.owner = True
            self._map_base()
            if lib.wt_init(self._base_ptr, cap, self.max_rules, n_blocks) != 0:
                raise ValueError(f"bad warm-tier geometry {cap}x{max_rules}")
        else:
            self._shm = shared_memory.SharedMemory(name=name)
            self.owner = False
            try:
                from multiprocessing import resource_tracker

                resource_tracker.unregister(self._shm._name, "shared_memory")
            except Exception:  # noqa: BLE001 — tracker internals shifted
                pass
            self._map_base()
            cap = int(lib.wt_check(self._base_ptr))
            if cap < 0:
                self.close()
                raise RuntimeError(f"shm segment {name} is not a wt table")
            # the segment's geometry, not the caller's guess: the scratch
            # arrays below are what wt_take writes max_rules entries into
            self.capacity = cap
            self.max_rules = int(lib.wt_max_rules(self._base_ptr))
        # scratch arrays reused by put/take/get, with their C pointers:
        # with rules that fire on every line each eviction is a put, so
        # the per-call array and pointer construction would be the cost
        self._rid = np.zeros(self.max_rules, dtype=np.int32)
        self._hits = np.zeros(self.max_rules, dtype=np.int32)
        self._ss = np.zeros(self.max_rules, dtype=np.int64)
        self._sns = np.zeros(self.max_rules, dtype=np.int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        self._ptrs = (
            array_ptr(self._rid, i32p), array_ptr(self._hits, i32p),
            array_ptr(self._ss, i64p), array_ptr(self._sns, i64p),
        )

    @property
    def name(self) -> str:
        return self._shm.name

    _map_base = ShmFailedChallengeStates._map_base

    def put(self, ip: str, entries: WarmEntries, now_ns: int) -> bool:
        """Spill one IP's window vector; False when the put was dropped
        (probe window full of live, unexpired records)."""
        base = self._base_ptr
        if base is None or not entries:
            return False
        key = _wt_key(ip)
        n = min(len(entries), self.max_rules)
        rid, hits, ss, sns = zip(*entries[:n])
        self._rid[:n] = rid
        self._hits[:n] = hits
        self._ss[:n] = ss
        self._sns[:n] = sns
        rc = self._lib.wt_put(
            base, key, len(key), now_ns, self.expiry_ns, *self._ptrs, n,
        )
        return rc == 0

    def _read(self, ip: str, fn) -> Optional[WarmEntries]:
        base = self._base_ptr
        if base is None:
            return None
        key = _wt_key(ip)
        n = int(fn(base, key, len(key), *self._ptrs))
        if n < 0:
            return None
        return list(zip(
            self._rid[:n].tolist(), self._hits[:n].tolist(),
            self._ss[:n].tolist(), self._sns[:n].tolist(),
        ))

    def take(self, ip: str) -> Optional[WarmEntries]:
        """Refill read: the record is deleted (move semantics — the state
        now lives in the hot tier's shadow again)."""
        return self._read(ip, self._lib.wt_take)

    def peek(self, ip: str) -> Optional[WarmEntries]:
        """Non-deleting read for introspection (get/format_states)."""
        return self._read(ip, self._lib.wt_get)

    def _spans(self, ips, spans):
        """(the arrays to keep alive over the call, their C pointers) for
        the keys: the caller's spans of an encoding it already made
        (`encode_ips` of a list these ips were taken from), else an
        encoding of `ips`.  The empty address is keyed by one NUL byte,
        the byte `encode_ips` ends every blob with; the C side cuts a
        key at WT_KEY_MAX."""
        from banjax_tpu.native.slotmgr import encode_ips

        buf, offs, lens = encode_ips(ips) if spans is None else spans
        empty = lens == 0
        if empty.any():
            offs = np.where(empty, buf.size - 1, offs)
            lens = np.where(empty, 1, lens)
        offs = np.ascontiguousarray(offs, dtype=np.int64)
        lens = np.ascontiguousarray(lens, dtype=np.int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        return (buf, offs, lens), (
            array_ptr(buf, u8p), array_ptr(offs, i64p),
            array_ptr(lens, i64p),
        )

    def contains_batch(self, ips, spans=None) -> np.ndarray:
        """bool [n] membership over a distinct-ip list — one C call.
        `spans`: (buf, offs, lens) of these ips inside an encoding the
        caller already holds, so the batch is encoded once (`ips` may
        then be None: no string is read)."""
        n = len(ips) if spans is None else len(spans[1])
        out = np.zeros(n, dtype=np.uint8)
        base = self._base_ptr
        if n == 0 or base is None:
            return out.astype(bool)
        _keep, ptrs = self._spans(ips, spans)
        self._lib.wt_contains_batch(
            base, *ptrs, n,
            array_ptr(out, ctypes.POINTER(ctypes.c_uint8)),
        )
        return out.astype(bool)

    def __contains__(self, ip: str) -> bool:
        return bool(self.contains_batch([ip])[0])

    def keys(self) -> List[str]:
        base = self._base_ptr
        if base is None:
            return []
        cap = self.capacity
        blob = ctypes.create_string_buffer(cap * WT_KEY_MAX)
        key_lens = np.zeros(cap, dtype=np.int32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        n = int(self._lib.wt_snapshot_keys(
            base, blob, array_ptr(key_lens, i32p), cap
        ))
        out = []
        for i in range(n):
            raw = blob.raw[i * WT_KEY_MAX : i * WT_KEY_MAX + int(key_lens[i])]
            if raw == b"\x00":
                raw = b""
            out.append(raw.decode("utf-8", "surrogatepass"))
        return out

    def _header(self, fn) -> int:
        base = self._base_ptr
        return int(fn(base)) if base is not None else 0

    def __len__(self) -> int:
        return self._header(self._lib.wt_len)

    @property
    def dropped(self) -> int:
        return self._header(self._lib.wt_dropped)

    @property
    def probes(self) -> int:
        """Keys looked up by put/take/peek/contains_batch."""
        return self._header(self._lib.wt_probes)

    @property
    def record_reads(self) -> int:
        """Records whose memory those lookups read (the rest stopped in
        the tag index)."""
        return self._header(self._lib.wt_record_reads)

    @property
    def bytes_written(self) -> int:
        """Bytes puts wrote into the arena: 128 a record + 24 a counter
        + 8 a further block (wt_record_bytes)."""
        return self._header(self._lib.wt_bytes_written)

    @property
    def blocks_used(self) -> int:
        """256-byte arena blocks live records hold."""
        return self._header(self._lib.wt_blocks_used)

    def clear(self) -> None:
        base = self._base_ptr
        if base is not None:
            self._lib.wt_clear(base)

    def close(self) -> None:
        self._base_ptr = None
        self._shm.close()

    def unlink(self) -> None:
        if self.owner:
            try:
                self._shm.unlink()
            except FileNotFoundError:
                pass


class PyWarmTier:
    """Pure-Python warm tier (no C compiler): bounded OrderedDict with
    the same steal-iff-expired overflow policy, evaluated globally — the
    stalest record overall is the steal candidate, so this path drops at
    most as often as the probe-window-bounded C table."""

    def __init__(
        self,
        capacity: int = 1 << 20,
        max_rules: int = 16,
        expiry_ns: int = 300 * 1_000_000_000,
    ):
        cap = 1
        while cap < max(2, capacity):
            cap *= 2
        self.capacity = cap
        self.max_rules = max(1, int(max_rules))
        self.expiry_ns = int(expiry_ns)
        self._dropped = 0
        self.bytes_written = 0  # as the C table counts them
        # ip -> (stamp_ns, entries); order = last-touch (stalest first)
        from collections import OrderedDict

        self._d: "OrderedDict[str, Tuple[int, WarmEntries]]" = OrderedDict()

    def put(self, ip: str, entries: WarmEntries, now_ns: int) -> bool:
        if not entries:
            return False
        entries = entries[: self.max_rules]
        if ip in self._d:
            self._d[ip] = (now_ns, entries)
            self._d.move_to_end(ip)
            self.bytes_written += wt_record_bytes(len(entries))
            return True
        if len(self._d) >= self.capacity:
            stale_ip, (stamp, _) = next(iter(self._d.items()))
            if now_ns - stamp > self.expiry_ns:
                del self._d[stale_ip]
                self._dropped += 1
            else:
                self._dropped += 1
                return False
        self._d[ip] = (now_ns, entries)
        self.bytes_written += wt_record_bytes(len(entries))
        return True

    def take(self, ip: str) -> Optional[WarmEntries]:
        v = self._d.pop(ip, None)
        return None if v is None else v[1]

    def peek(self, ip: str) -> Optional[WarmEntries]:
        v = self._d.get(ip)
        return None if v is None else v[1]

    def contains_batch(self, ips, spans=None) -> np.ndarray:
        if ips is None:  # as the C tier: the caller's spans alone
            from banjax_tpu.native.slotmgr import decode_spans

            ips = decode_spans(spans)
        d = self._d
        return np.fromiter((ip in d for ip in ips), bool, count=len(ips))

    def __contains__(self, ip: str) -> bool:
        return ip in self._d

    def keys(self) -> List[str]:
        return list(self._d)

    def __len__(self) -> int:
        return len(self._d)

    @property
    def dropped(self) -> int:
        return self._dropped

    def clear(self) -> None:
        self._d.clear()
        self._dropped = 0

    def close(self) -> None:
        self._d.clear()

    def unlink(self) -> None:
        pass


def create_warm_tier(
    capacity: int = 1 << 20,
    max_rules: int = 16,
    expiry_ns: int = 300 * 1_000_000_000,
):
    """A warm tier: the shm-backed C table when the native library is
    available, else the Python fallback — same interface either way."""
    if available():
        try:
            return ShmWarmTier(
                capacity=capacity, max_rules=max_rules, expiry_ns=expiry_ns
            )
        except Exception:  # noqa: BLE001 — shm creation can fail (rlimits)
            log.exception("shm warm tier unavailable; Python fallback")
    return PyWarmTier(
        capacity=capacity, max_rules=max_rules, expiry_ns=expiry_ns
    )


# ---------------------------------------------------------------------------
# The host shadow's native form (sh_* in shmstate.c): the window counters
# of RESIDENT addresses, a record per device slot in the warm tier's
# block layout.  DeviceWindows moves records through it as arrays — one C
# call a batch for the absorb of a chunk's events, the spill of a
# placement's victims into the warm tier, the refill of its returning
# addresses out of it, and the rows of the device restore — and makes no
# Python object per event, per record or per counter.  The dict shadow
# (matcher/windows.py, `_sm is None`) is the same logic in Python and the
# oracle the parity tests compare this with.

_PTR = {
    np.dtype(t): ctypes.POINTER(c)
    for t, c in ((np.int32, ctypes.c_int32), (np.int64, ctypes.c_int64),
                 (np.uint8, ctypes.c_uint8))
}


def _p(a: np.ndarray):
    """The C pointer of a contiguous array, typed by its dtype (an array
    of another dtype than the call declares is refused by ctypes)."""
    if not a.flags.c_contiguous:
        raise ValueError("a native call needs a contiguous array")
    return array_ptr(a, _PTR[a.dtype])


def _i32(a) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.int32)


class ShadowMirror:
    """Slot-indexed mirror of the device window counters.  Externally
    locked by DeviceWindows, the slotmgr convention."""

    def __init__(self, lib: ctypes.CDLL, handle: int):
        self._lib = lib
        self._h = handle

    def close(self) -> None:
        if self._h:
            self._lib.sh_destroy(self._h)
            self._h = None

    def __del__(self):  # noqa: D105
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    def clear(self) -> None:
        self._lib.sh_clear(self._h)

    def grow(self, new_capacity: int) -> None:
        if self._lib.sh_grow(self._h, new_capacity) != 0:
            raise MemoryError("shadow mirror grow failed")

    def __len__(self) -> int:
        """Slots that hold a record."""
        return int(self._lib.sh_records(self._h))

    def next_stamp(self) -> int:
        """A sequence stamp for a record the caller makes in its dict."""
        return int(self._lib.sh_next_stamp(self._h))

    def absorb(self, slot_of_line, last_used, line, rule, hits, ss, sns):
        """Upsert a chunk's live events, given in (line, rule) order, into
        their slots' records (sh_absorb); returns the events taken in."""
        slot_of_line = _i32(slot_of_line)
        got = self._lib.sh_absorb(
            self._h, _p(slot_of_line), len(slot_of_line), _p(last_used),
            _p(_i32(line)), _p(_i32(rule)), _p(_i32(hits)), _p(_i32(ss)),
            _p(_i32(sns)), len(line),
        )
        if got < 0:
            raise MemoryError("shadow mirror out of memory")
        return int(got)

    def spill(self, warm: "Optional[ShmWarmTier]", slots, keys, now_ns):
        """Move the victims' records (slots int64 [n], eviction order)
        into `warm` under their keys (`keys` = slotmgr's evict_keys: the
        key bytes, one stride a victim, and their lengths).  uint8 [n]:
        0 = the slot held no record, 1 = the put landed, 2 = the record
        is still here and wants a home (the put was dropped, or there is
        no C tier: `warm` None)."""
        from banjax_tpu.native.slotmgr import EVICT_KEY_STRIDE

        status = np.empty(len(slots), dtype=np.uint8)
        if len(slots):
            self._lib.sh_spill(
                self._h, None if warm is None else warm._base_ptr,
                _p(slots), len(slots), _p(keys[0]), _p(keys[1]),
                EVICT_KEY_STRIDE, now_ns,
                0 if warm is None else warm.expiry_ns, _p(status),
            )
        return status

    def refill(self, warm: "ShmWarmTier", slots, spans) -> np.ndarray:
        """Move the records of the keys `spans` (of an `encode_ips`) out
        of `warm` into `slots` (int32 [n], placement order).  int64 [n]:
        each new record's stamp, 0 where the tier had none."""
        slots = _i32(slots)
        stamps = np.zeros(len(slots), dtype=np.int64)
        if len(slots) and warm._base_ptr is not None:
            _keep, ptrs = warm._spans((), spans)
            self._lib.sh_refill(
                self._h, warm._base_ptr, _p(slots), *ptrs, len(slots),
                _p(stamps),
            )
        return stamps

    def install(self, slot: int, vector, stamp: int = 0) -> int:
        """Make `slot`'s record from a dict-shadow vector (rule_id ->
        (num_hits, start_s, start_ns), insertion order), under `stamp`
        or a new one.  Returns the stamp; 0 for an empty vector."""
        n = len(vector)
        rid = np.fromiter(vector, dtype=np.int32, count=n)
        state = np.array(list(vector.values()), dtype=np.int64).reshape(n, 3)
        got = self._lib.sh_install(
            self._h, slot, stamp, _p(rid), _p(_i32(state[:, 0])),
            _p(np.ascontiguousarray(state[:, 1])),
            _p(np.ascontiguousarray(state[:, 2])), n,
        )
        if got < 0:
            raise MemoryError(f"shadow mirror: no record for slot {slot}")
        return int(got)

    def live_slots(self) -> np.ndarray:
        """int32: every slot that holds a record, ascending."""
        out = np.empty(len(self), dtype=np.int32)
        self._lib.sh_live_slots(self._h, _p(out))
        return out

    def export(self, slots, drop: bool = False):
        """(stamps, vectors) of the slots' records as the dict shadow
        holds them (an OrderedDict rule_id -> (num_hits, start_s,
        start_ns); None and stamp 0 where a slot has no record).  With
        `drop` the records leave the mirror: the caller is their home.
        Introspection and the paths with no C tier to go to; never a
        cell's traffic."""
        from collections import OrderedDict

        slots = _i32(slots)
        n = len(slots)
        counts = np.empty(n, dtype=np.int32)
        stamps = np.empty(n, dtype=np.int64)
        total = self._lib.sh_counts(
            self._h, _p(slots), n, _p(counts), _p(stamps)
        )
        rid = np.empty(total, dtype=np.int32)
        hits = np.empty(total, dtype=np.int32)
        ss = np.empty(total, dtype=np.int64)
        sns = np.empty(total, dtype=np.int64)
        self._lib.sh_export(
            self._h, _p(slots), n, _p(rid), _p(hits), _p(ss), _p(sns),
            int(drop),
        )
        rids = rid.tolist()
        states = list(zip(hits.tolist(), ss.tolist(), sns.tolist()))
        vectors, a = [], 0
        for b in np.cumsum(counts).tolist():
            vectors.append(
                OrderedDict(zip(rids[a:b], states[a:b])) if b > a else None
            )
            a = b
        return stamps.tolist(), vectors

    def restore_rows(self, slots, stamps, n_rules, chunk, pad_slot, pad_key):
        """(rows int32 [c, 5, chunk], records): `_restore_step`'s operand
        for the queued restores (slots, stamps) still live — the slot
        holds the record the restore was queued for — with today's
        values, in chunks padded with (pad_slot, pad_key, 0, 0, 0)."""
        slots = _i32(slots)
        stamps = np.ascontiguousarray(stamps, dtype=np.int64)
        args = (self._h, _p(slots), _p(stamps), len(slots))
        total = self._lib.sh_restore_count(*args)
        rows = np.zeros((-(-total // chunk), 5, chunk), dtype=np.int32)
        rows[:, 0] = pad_slot
        rows[:, 1] = pad_key
        records = self._lib.sh_restore_rows(
            *args, n_rules, chunk, _p(rows)
        ) if total else 0
        return rows, int(records)


def create_shadow_mirror(capacity: int) -> Optional[ShadowMirror]:
    """A ShadowMirror, or None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    h = lib.sh_create(capacity)
    return ShadowMirror(lib, h) if h else None
