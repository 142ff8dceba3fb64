"""ctypes shim for the native slot manager (native/slotmgr.c).

`create(capacity)` returns a SlotManager, or None when no C compiler is
available — callers (matcher/windows.py) keep the Python dict+LRU path,
which doubles as the differential oracle (tests/unit/test_slotmgr.py).

Same compile-on-first-use convention as banjax_tpu/native/__init__.py
(cached .so keyed by platform + source mtime; BANJAX_NO_NATIVE disables).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import sysconfig
import tempfile
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

from banjax_tpu.native.cptr import array_ptr

log = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "slotmgr.c")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

# bytes a victim's key takes in place_misses' evict_keys (slotmgr.c
# SM_EVICT_KEY_STRIDE = the warm tier's WT_KEY_MAX)
EVICT_KEY_STRIDE = 104

_u8p = ctypes.POINTER(ctypes.c_uint8)
_u32p = ctypes.POINTER(ctypes.c_uint32)
_i32p = ctypes.POINTER(ctypes.c_int32)
_i64p = ctypes.POINTER(ctypes.c_int64)
_vpp = ctypes.POINTER(ctypes.c_void_p)


_P = array_ptr


def _so_path() -> str:
    plat = sysconfig.get_platform().replace("-", "_")
    cache_dir = os.environ.get(
        "BANJAX_NATIVE_CACHE",
        os.path.join(tempfile.gettempdir(), "banjax-native"),
    )
    os.makedirs(cache_dir, exist_ok=True)
    src_mtime = int(os.stat(_SRC).st_mtime)
    return os.path.join(cache_dir, f"slotmgr_{plat}_{src_mtime}.so")


def _compile(so: str) -> bool:
    for cc in (os.environ.get("CC"), "cc", "gcc", "clang"):
        if not cc:
            continue
        cmd = [cc, "-O3", "-shared", "-fPIC", "-o", so, _SRC]
        try:
            r = subprocess.run(
                cmd, capture_output=True, text=True, timeout=120
            )
        except (OSError, subprocess.TimeoutExpired):
            continue
        if r.returncode == 0:
            return True
        log.debug("slotmgr compile with %s failed: %s", cc, r.stderr[-500:])
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
            log.info("no C compiler available; Python slot-manager path")
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("could not load %s: %s", so, e)
            return None
        lib.sm_create.restype = ctypes.c_void_p
        lib.sm_create.argtypes = [ctypes.c_int64]
        lib.sm_destroy.restype = None
        lib.sm_destroy.argtypes = [ctypes.c_void_p]
        lib.sm_clear.restype = None
        lib.sm_clear.argtypes = [ctypes.c_void_p]
        lib.sm_assigned.restype = ctypes.c_int64
        lib.sm_assigned.argtypes = [ctypes.c_void_p]
        lib.sm_free_count.restype = ctypes.c_int64
        lib.sm_free_count.argtypes = [ctypes.c_void_p]
        lib.sm_grow.restype = ctypes.c_int64
        lib.sm_grow.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.sm_lookup_batch.restype = ctypes.c_int64
        lib.sm_lookup_batch.argtypes = [
            ctypes.c_void_p, _u8p, _i64p, _i64p, ctypes.c_int64,
            ctypes.c_int64, _i64p, _i32p, _i64p,
        ]
        lib.sm_place_misses.restype = ctypes.c_int64
        lib.sm_place_misses.argtypes = [
            ctypes.c_void_p, _u8p, _i64p, _i64p, ctypes.c_int64,
            _i32p, _i64p, _i32p, _i64p, ctypes.c_int64, _i64p, _i64p,
            _u8p, _i32p,
        ]
        lib.sm_find_batch.restype = None
        lib.sm_find_batch.argtypes = [
            ctypes.c_void_p, _u8p, _i64p, _i64p, ctypes.c_int64, _i32p,
        ]
        lib.sm_crc32_batch.restype = None
        lib.sm_crc32_batch.argtypes = [
            _u8p, _i64p, _i64p, ctypes.c_int64, _u32p,
        ]
        lib.sm_merge_spans.restype = ctypes.c_int64
        lib.sm_merge_spans.argtypes = [
            ctypes.c_int64, _vpp, _vpp, _vpp, _i64p, _vpp, _i64p,
            _i64p, ctypes.c_int64, _i64p, _u8p, _i64p, _i64p, _u32p, _i64p,
        ]
        lib.sm_scanned.restype = ctypes.c_int64
        lib.sm_scanned.argtypes = [ctypes.c_void_p]
        lib.sm_order.restype = ctypes.c_int64
        lib.sm_order.argtypes = [ctypes.c_void_p, _i64p, _i32p]
        lib.sm_keys_of.restype = ctypes.c_int64
        lib.sm_keys_of.argtypes = [
            ctypes.c_void_p, _i32p, ctypes.c_int64, _i32p, _u8p,
            ctypes.c_int64,
        ]
        lib.sm_dead_count.restype = ctypes.c_int64
        lib.sm_dead_count.argtypes = [ctypes.c_void_p]
        lib.sm_empty_victim.restype = ctypes.c_int64
        lib.sm_empty_victim.argtypes = [ctypes.c_void_p]
        lib.sm_dead_key.restype = ctypes.c_void_p
        lib.sm_dead_key.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, _i64p, _i32p,
        ]
        lib.sm_test_select_order.restype = None
        lib.sm_test_select_order.argtypes = [
            _i64p, _i32p, ctypes.c_int64, ctypes.c_int64, _i32p,
        ]
        _LIB = lib
        log.info("native slotmgr loaded (%s)", so)
        return _LIB


def encode_ips(
    ips: Sequence[str],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(buf uint8, offs int64 [n], lens int64 [n]): one blob + (offset,
    length) spans for a distinct-ip list — THE encoding of a batch's
    addresses, made once and handed to the slot table, the warm tier
    and the sketch's hash alike.  The common all-ASCII case is one join
    + one encode; byte lengths equal char lengths so the per-ip work is
    a C-speed map(len).  The blob ends with one NUL past the last span:
    the warm tier keys the empty address by that byte."""
    n = len(ips)
    joined = "".join(ips)
    blob = joined.encode("utf-8", "surrogatepass")
    if len(blob) == len(joined):
        lens = np.fromiter(map(len, ips), dtype=np.int64, count=n)
    else:  # non-ASCII ip strings (oracle inputs, not real traffic)
        lens = np.fromiter(
            (len(ip.encode("utf-8", "surrogatepass")) for ip in ips),
            dtype=np.int64, count=n,
        )
    offs = np.zeros(n, dtype=np.int64)
    if n > 1:
        np.cumsum(lens[:-1], out=offs[1:])
    return np.frombuffer(blob + b"\x00", dtype=np.uint8), offs, lens


def _decode(raw: bytes) -> str:
    return raw.decode("utf-8", "surrogatepass")


def decode_spans(enc) -> list:
    """The strings `encode_ips` would have made the spans from."""
    buf, offs, lens = enc
    raw = buf.tobytes()
    spans = zip(offs.tolist(), lens.tolist())
    if raw.isascii():  # byte offsets are character offsets: plain slices
        text = raw.decode("ascii")
        return [text[o : o + n] for o, n in spans]
    return [_decode(raw[o : o + n]) for o, n in spans]


class AddressSpans:
    """A batch's DISTINCT addresses as byte spans — `enc` = (buf, offs,
    lens) as `encode_ips` lays them out, `hashes` their zlib CRC-32s —
    wearing the string list's interface: whatever reads a string of it
    (the refused path, a dict shadow, the sketch's candidate walk) has
    them made then, all at once and once; the pass itself hands the
    arrays to the slot table, the warm tier and the sketch as they are."""

    __slots__ = ("enc", "hashes", "_strings")

    def __init__(self, enc, hashes):
        self.enc = enc
        self.hashes = hashes
        self._strings = None

    def strings(self) -> list:
        if self._strings is None:
            self._strings = decode_spans(self.enc)
        return self._strings

    def __len__(self) -> int:
        return len(self.enc[1])

    def __getitem__(self, i):
        return self.strings()[i]

    def __iter__(self):
        return iter(self.strings())

    def __reversed__(self):
        return reversed(self.strings())


def merge_spans(parts) -> Optional[tuple]:
    """(AddressSpans, per-row inverse int64) of one batch from its
    shards: `parts` = [(enc of the shard's distinct-address table, the
    shard's rows -> that table), ...] in shard order.  One C dedup by
    bytes, in the string merge's first-appearance order (shard order,
    then the shard table's own; an entry no row holds is left out) —
    slotmgr.c sm_merge_spans.  None when the native library is
    unavailable."""
    lib = _load()
    if lib is None:
        return None
    k = len(parts)
    encs = [
        (np.ascontiguousarray(buf, dtype=np.uint8),
         np.ascontiguousarray(offs, dtype=np.int64),
         np.ascontiguousarray(lens, dtype=np.int64))
        for (buf, offs, lens), _ in parts
    ]
    invs = [np.ascontiguousarray(inv, dtype=np.int64) for _, inv in parts]
    for (buf, offs, lens), inv in zip(encs, invs):
        # the C side reads what these say: hold them to their arrays
        if len(offs) != len(lens) or (len(inv) and not (
                0 <= int(inv.min()) and int(inv.max()) < len(offs))):
            raise ValueError("merge_spans: a row outside its shard's table")
        if len(offs) and not (
                0 <= int(offs.min()) and 0 <= int(lens.min())
                and int((offs + lens).max()) <= len(buf)):
            raise ValueError("merge_spans: a span outside its shard's blob")
    table_n = np.fromiter((len(enc[1]) for enc in encs), np.int64, k)
    rows_n = np.fromiter(map(len, invs), np.int64, k)
    total = int(table_n.sum())
    n_bytes = sum(int(enc[2].sum()) for enc in encs)
    table_cap = 64
    while table_cap < 2 * total:
        table_cap <<= 1
    table = np.empty(table_cap, dtype=np.int64)
    scratch = np.empty(max(int(table_n.max(initial=0)), 1), dtype=np.int64)
    buf = np.empty(n_bytes + 1, dtype=np.uint8)
    offs = np.empty(total, dtype=np.int64)
    lens = np.empty(total, dtype=np.int64)
    hashes = np.empty(total, dtype=np.uint32)
    inverse = np.empty(int(rows_n.sum()), dtype=np.int64)

    def ptrs(arrays):
        return (ctypes.c_void_p * k)(*[a.ctypes.data for a in arrays])

    n = int(lib.sm_merge_spans(
        k, ptrs([enc[0] for enc in encs]), ptrs([enc[1] for enc in encs]),
        ptrs([enc[2] for enc in encs]), _P(table_n, _i64p), ptrs(invs),
        _P(rows_n, _i64p), _P(table, _i64p), table_cap, _P(scratch, _i64p),
        _P(buf, _u8p), _P(offs, _i64p), _P(lens, _i64p), _P(hashes, _u32p),
        _P(inverse, _i64p),
    ))
    # the blob ends with its NUL (a table entry no row holds left room)
    used = int(offs[n - 1] + lens[n - 1]) + 1 if n else 1
    return AddressSpans((buf[:used], offs[:n], lens[:n]), hashes[:n]), inverse


def crc32_spans(enc) -> Optional[np.ndarray]:
    """uint32 [n] zlib CRC-32 of every span of an `encode_ips` result —
    obs/sketch.py's `hash_ip` of every address, in one C call.  None
    when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    buf, offs, lens = enc
    out = np.empty(len(offs), dtype=np.uint32)
    if len(offs):
        lib.sm_crc32_batch(
            _P(buf, _u8p), _P(offs, _i64p), _P(lens, _i64p), len(offs),
            _P(out, _u32p),
        )
    return out


def select_order(lu: np.ndarray, slot: np.ndarray, chunk: int) -> np.ndarray:
    """Test hook: the eviction order of candidates (last_used, slot) as
    sm_place_misses' selection builds it, `chunk` at a time."""
    lu = np.ascontiguousarray(lu, dtype=np.int64)
    slot = np.ascontiguousarray(slot, dtype=np.int32)
    out = np.empty(len(slot), dtype=np.int32)
    _load().sm_test_select_order(
        _P(lu, _i64p), _P(slot, _i32p), len(slot), chunk, _P(out, _i32p)
    )
    return out


class SlotManager:
    """One native ip->slot table.  All calls must be externally locked —
    DeviceWindows holds its own lock around every use, exactly as it does
    for the Python dict path."""

    def __init__(self, lib: ctypes.CDLL, handle: int, capacity: int):
        self._lib = lib
        self._h = handle
        self.capacity = capacity

    def close(self) -> None:
        if self._h:
            self._lib.sm_destroy(self._h)
            self._h = None

    def __del__(self):  # noqa: D105
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    def clear(self) -> None:
        self._lib.sm_clear(self._h)

    def assigned(self) -> int:
        return int(self._lib.sm_assigned(self._h))

    def free_count(self) -> int:
        return int(self._lib.sm_free_count(self._h))

    def scanned(self) -> int:
        """Slots the placements' victim walks have read, the members of
        every run they sorted included."""
        return int(self._lib.sm_scanned(self._h))

    def order(self, last_used: Optional[np.ndarray] = None) -> np.ndarray:
        """int32: the assigned slots in the kept eviction order, oldest
        first.  With `last_used` every run is put in slot order on the
        way: the table's full (last_used, slot) order."""
        out = np.empty(self.assigned(), dtype=np.int32)
        n = self._lib.sm_order(
            self._h, None if last_used is None else _P(last_used, _i64p),
            _P(out, _i32p),
        )
        return out[:n]

    def keys_of(self, slots) -> list:
        """The address of each slot, None for one that is unassigned —
        this table is the one owner of slot -> address."""
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        n = len(slots)
        lens = np.empty(n, dtype=np.int32)
        buf = np.empty(max(n, 1) * 48, dtype=np.uint8)
        for _ in range(2):
            total = int(self._lib.sm_keys_of(
                self._h, _P(slots, _i32p), n, _P(lens, _i32p), _P(buf, _u8p),
                len(buf),
            ))
            if total <= len(buf):
                break
            buf = np.empty(total, dtype=np.uint8)
        held = np.maximum(lens, 0).astype(np.int64)
        offs = np.zeros(n, dtype=np.int64)
        if n > 1:
            np.cumsum(held[:-1], out=offs[1:])
        return [
            s if ln >= 0 else None for s, ln in zip(
                decode_spans((buf[:total], offs, held)), lens.tolist())
        ]

    def evicted_long_keys(self) -> list:
        """[(k, key bytes)]: the last placement's victims whose key
        `place_misses`' evict_keys cut at its stride, whole."""
        out = []
        k, ln = ctypes.c_int64(), ctypes.c_int32()
        for j in range(int(self._lib.sm_dead_count(self._h))):
            p = self._lib.sm_dead_key(
                self._h, j, ctypes.byref(k), ctypes.byref(ln))
            out.append((k.value, ctypes.string_at(p, ln.value)))
        return out

    def grow(self, new_capacity: int) -> None:
        if self._lib.sm_grow(self._h, new_capacity) != 0:
            raise MemoryError("slotmgr grow failed")
        self.capacity = new_capacity

    def lookup_batch(
        self, ips: Sequence[str], batch_seq: int, last_used: np.ndarray,
        enc=None,
    ):
        """Pass 1 over a DISTINCT ip list: resolve hits (stamping their
        recency with batch_seq) and collect misses.  Returns (slots
        int32 [n] with -1 per miss, miss_idx int64 [m], ctx) — pass ctx
        straight to place_misses; it is `enc`, the caller's
        `encode_ips(ips)`, when given.  The caller may grow the manager
        (and its device arrays) between the two passes; the passes
        re-take the array pointers, so reallocation in between is safe."""
        n = len(ips)
        slots = np.empty(n, dtype=np.int32)
        if n == 0:
            return slots, np.empty(0, np.int64), None
        buf, offs, lens = encode_ips(ips) if enc is None else enc
        miss_idx = np.empty(n, dtype=np.int64)
        n_miss = int(self._lib.sm_lookup_batch(
            self._h, _P(buf, _u8p), _P(offs, _i64p), _P(lens, _i64p), n,
            batch_seq, _P(last_used, _i64p), _P(slots, _i32p),
            _P(miss_idx, _i64p),
        ))
        return slots, miss_idx[:n_miss], (buf, offs, lens)

    def place_misses(
        self,
        ctx,
        slots: np.ndarray,
        miss_idx: np.ndarray,
        batch_seq: int,
        pin_counts: np.ndarray,
        last_used: np.ndarray,
    ):
        """Pass 2: place every miss, in ip order (free stack first, then
        minimum-(last_used, slot) eviction).  Returns (placed_miss_idx,
        evict_slots, evict_keys, ok).  ok=False is the refusal (every
        eviction candidate pinned): placements made BEFORE the refusal
        persist, and placed_miss_idx/evict_slots report exactly those —
        the caller must bookkeep them (slot->ip mirror, pending device
        evictions) before splitting the batch, as in the Python path.
        evict_keys = (uint8 [k * EVICT_KEY_STRIDE], int32 [k]): the
        victims' address bytes as the warm tier keys them, one stride a
        victim, and their lengths (`evicted_keys` makes strings of
        them)."""
        n_miss = len(miss_idx)
        if n_miss == 0:
            none = np.empty(0, np.int64)
            return none, none, (np.empty(0, np.uint8),
                                np.empty(0, np.int32)), True
        buf, offs, lens = ctx
        evict = np.empty(n_miss, dtype=np.int64)
        keys = np.empty(n_miss * EVICT_KEY_STRIDE, dtype=np.uint8)
        key_lens = np.empty(n_miss, dtype=np.int32)
        counts = np.zeros(2, dtype=np.int64)
        rc = int(self._lib.sm_place_misses(
            self._h, _P(buf, _u8p), _P(offs, _i64p), _P(lens, _i64p),
            batch_seq, _P(pin_counts, _i32p), _P(last_used, _i64p),
            _P(slots, _i32p), _P(miss_idx, _i64p), n_miss,
            _P(evict, _i64p), _P(counts, _i64p), _P(keys, _u8p),
            _P(key_lens, _i32p),
        ))
        k = int(counts[0])
        return (miss_idx[: int(counts[1])], evict[:k],
                (keys, key_lens[:k]), rc == 0)

    def evicted_keys(self, evict_keys) -> list:
        """The victims' addresses as strings, from the last placement's
        evict_keys (a key the stride cut is read whole)."""
        keys, key_lens = evict_keys
        k = len(key_lens)
        if not k:  # no victim: the placement may not have run at all
            return []
        offs = np.arange(k, dtype=np.int64) * EVICT_KEY_STRIDE
        out = decode_spans((keys, offs, key_lens.astype(np.int64)))
        empty = int(self._lib.sm_empty_victim(self._h))
        if empty >= 0:  # it travels as the warm tier's key for it, one NUL
            out[empty] = ""
        for j, raw in self.evicted_long_keys():
            out[j] = _decode(raw)
        return out

    def find_batch(self, ips: Sequence[str]) -> np.ndarray:
        """int32 [n]: the slot of every ip of a DISTINCT list, -1 where
        it has none, with NO recency stamp."""
        n = len(ips)
        out = np.empty(n, dtype=np.int32)
        if n:
            buf, offs, lens = encode_ips(ips)
            self._lib.sm_find_batch(
                self._h, _P(buf, _u8p), _P(offs, _i64p), _P(lens, _i64p),
                n, _P(out, _i32p),
            )
        return out

    def contains_batch(self, ips: Sequence[str]) -> np.ndarray:
        """bool [n] membership over a DISTINCT ip list, with NO recency
        stamp — the slot-admission gate's hot-tier check (a refused
        batch must not refresh its probe victims' LRU position)."""
        return self.find_batch(ips) >= 0


def create(capacity: int) -> Optional[SlotManager]:
    """A SlotManager, or None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    h = lib.sm_create(capacity)
    if not h:
        return None
    return SlotManager(lib, h, capacity)
