"""The warm tier's C table (wt_* in native/shmstate.c) by itself.

Until PR 29 it was reached only through DeviceWindows.  Here: a
differential fuzz of ShmWarmTier against PyWarmTier with keys forced into
one probe window, attach by name, refusal of a foreign layout, and the
property the tag index exists for — a lookup of an absent key reads no
record — stated with the table's own counters and the segment's size,
not with a clock.
"""

import os
import random
import struct
from multiprocessing import shared_memory

import numpy as np
import pytest

from banjax_tpu.native import shm, slotmgr

WINDOW = 64  # WT_MAX_PROBE


@pytest.fixture()
def tiers():
    """ShmWarmTier factory; every tier made is closed and unlinked."""
    if not shm.available():
        pytest.skip("native shmstate unavailable (no C compiler)")
    made = []

    def make(**kw):
        t = shm.ShmWarmTier(**kw)
        made.append(t)
        return t

    yield make
    for t in reversed(made):
        t.close()
        t.unlink()


def _home(ip: str, capacity: int) -> int:
    h = 0xCBF29CE484222325  # fc_hash: FNV-1a 64
    for c in ip.encode():
        h = ((h ^ c) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h & (capacity - 1)


def _ips_sharing_a_home(capacity: int, n: int) -> list:
    out, home, i = [], None, 0
    while len(out) < n:
        ip = f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"
        i += 1
        if home is None:
            home = _home(ip, capacity)
        if _home(ip, capacity) == home:
            out.append(ip)
    return out


def _entries(rng, max_rules):
    n = rng.randint(1, max_rules + 2)  # past max_rules: both truncate
    rules = rng.sample(range(1000), n)  # insertion order, not sorted
    return [
        (r, rng.randint(1, 1 << 30), rng.randint(0, 1 << 40),
         rng.randint(0, 999_999_999))
        for r in rules
    ]


# capacity, pool, expiry_ns, what the run has to have met.  "whole": the
# table is one probe window, so PyWarmTier's global policy at capacity 64
# is the C table's, record for record.  "home": 96 keys of one home in a
# larger table — their window is 64 of its positions, the same reference
# holds, and the positions past the window stay genuinely empty.
# "spread": random homes, nothing overflows.
_FUZZ = [
    pytest.param(64, "whole", 1_500_000, {"steal", "drop", "reuse"}, 1,
                 id="whole-table-s1"),
    pytest.param(64, "whole", 1_500_000, {"steal", "drop", "reuse"}, 2,
                 id="whole-table-s2"),
    pytest.param(1 << 10, "home", 1_500_000, {"steal", "drop", "reuse"}, 3,
                 id="one-home-s3"),
    pytest.param(1 << 10, "home", 1_500_000, {"steal", "drop", "reuse"}, 4,
                 id="one-home-s4"),
    pytest.param(1 << 10, "home", 1 << 60, {"drop", "reuse"}, 5,
                 id="one-home-never-expires"),
    pytest.param(1 << 10, "home", 0, {"steal", "reuse"}, 6,
                 id="one-home-always-expired"),
    pytest.param(1 << 12, "spread", 5_000, {"reuse"}, 7, id="spread"),
]


@pytest.mark.parametrize("capacity,pool,expiry_ns,expect,seed", _FUZZ)
def test_fuzz_against_py_warm_tier(tiers, capacity, pool, expiry_ns, expect,
                                   seed):
    rng = random.Random(seed)
    max_rules = 5
    c = tiers(capacity=capacity, max_rules=max_rules, expiry_ns=expiry_ns)
    if pool == "home":
        ips = _ips_sharing_a_home(capacity, 96)
    elif pool == "whole":
        ips = [f"192.0.2.{i}" for i in range(96)] + [""]
    else:
        ips = [f"198.51.{i >> 8}.{i & 255}" for i in range(600)]
    py_cap = WINDOW if pool != "spread" else capacity
    py = shm.PyWarmTier(capacity=py_cap, max_rules=max_rules,
                        expiry_ns=expiry_ns)
    met = set()
    taken = False
    now = 1_000_000
    for step in range(6000):
        # strictly rising stamps; now and then a pause that outlasts the
        # expiry, so that a full window meets both a steal and a drop
        now += rng.choice((1, 7, 300, 9_000))
        if rng.random() < 0.003:
            now += 3_000_000
        # phases: fill the window, then churn it
        op = rng.random() if step > 200 else 0.0
        ip = rng.choice(ips)
        if op < 0.55:
            ent = _entries(rng, max_rules)
            new = ip not in py
            d0 = py.dropped
            ok = py.put(ip, ent, now)
            assert c.put(ip, ent, now) is ok
            if py.dropped > d0:
                met.add("steal" if ok else "drop")
            elif new and taken:
                met.add("reuse")  # an insert after a delete in the window
        elif op < 0.80:
            want = py.take(ip)
            assert c.take(ip) == want  # entries in insertion order
            taken = taken or want is not None
        elif op < 0.90:
            assert c.peek(ip) == py.peek(ip)
        elif op < 0.995:
            some = rng.sample(ips, 20)
            assert c.contains_batch(some).tolist() == \
                py.contains_batch(some).tolist()
            assert (ip in c) == (ip in py)
        else:
            py.clear()
            c.clear()
            taken = False
        assert len(c) == len(py)
        assert c.dropped == py.dropped
        if step % 97 == 0:
            assert sorted(c.keys()) == sorted(py.keys())
    assert sorted(c.keys()) == sorted(py.keys())
    for ip in py.keys():
        assert c.take(ip) == py.peek(ip)
    assert len(c) == 0 and c.keys() == []
    assert met >= expect, (met, expect)


def test_first_tombstone_is_reused_and_empty_stops_the_walk(tiers):
    """Table order is observable through keys(): a put after two takes
    lands on the FIRST tombstone of the window, not on the later one and
    not on the empty behind the window's live records."""
    cap = 1 << 10
    ips = _ips_sharing_a_home(cap, 6)
    c = tiers(capacity=cap, max_rules=2, expiry_ns=1 << 60)
    ent = [(7, 1, 2, 3)]
    for ip in ips[:5]:
        assert c.put(ip, ent, 10)
    assert c.keys() == ips[:5]  # probe order = table order from the home
    assert c.take(ips[1]) == ent and c.take(ips[3]) == ent
    assert ips[4] in c  # found past two tombstones
    assert c.put(ips[5], ent, 11)
    assert c.keys() == [ips[0], ips[5], ips[2], ips[4]]
    assert c.put(ips[4], [(8, 9, 9, 9)], 12)  # update in place, past a tombstone
    assert c.keys() == [ips[0], ips[5], ips[2], ips[4]]
    assert c.peek(ips[4]) == [(8, 9, 9, 9)]


def test_steal_takes_the_stalest_of_the_window_iff_expired(tiers):
    cap = 1 << 10
    ips = _ips_sharing_a_home(cap, WINDOW + 2)
    c = tiers(capacity=cap, max_rules=2, expiry_ns=1000)
    ent = [(1, 1, 1, 1)]
    for i, ip in enumerate(ips[:WINDOW]):
        assert c.put(ip, ent, 100 + i)
    assert c.put(ips[10], ent, 50)  # refreshed to an OLDER stamp: the stalest
    reads = c.record_reads
    assert not c.put(ips[WINDOW], ent, 1050)  # 1050 - 50 is not > 1000
    assert c.record_reads - reads == WINDOW  # the stamps, and only here
    assert (c.dropped, len(c)) == (1, WINDOW)
    assert c.put(ips[WINDOW], ent, 1051)
    assert (c.dropped, len(c)) == (2, WINDOW)
    assert ips[10] not in c and ips[WINDOW] in c
    assert c.keys()[10] == ips[WINDOW]  # in the victim's position
    # equal stamps: the first of the window
    assert c.put(ips[0], ent, 101)
    assert c.put(ips[WINDOW + 1], ent, 5000)
    assert ips[0] not in c and ips[1] in c


def test_a_second_process_view_reads_what_the_first_wrote(tiers):
    a = tiers(capacity=1 << 8, max_rules=40, expiry_ns=1 << 60)
    ent = [(r, r + 1, r + 2, r + 3) for r in (31, 4, 15, 9)]
    assert a.put("203.0.113.9", ent, 77)
    b = tiers(name=a.name)  # geometry comes from the segment
    assert (b.capacity, b.max_rules, b.owner) == (1 << 8, 40, False)
    assert len(b) == 1 and b.keys() == ["203.0.113.9"]
    assert b.peek("203.0.113.9") == ent
    assert b.take("203.0.113.9") == ent
    assert len(a) == 0 and "203.0.113.9" not in a
    assert b.put("203.0.113.10", ent[:1], 78)
    assert a.take("203.0.113.10") == ent[:1]
    assert a.probes == b.probes == 6  # one header


@pytest.mark.parametrize(
    "magic", [b"bjxwt001", b"bjxwt002", b"bjxhsm02", b"\0" * 8],
    ids=["old-layout", "fixed-stride-layout", "fc-table", "zeroed"])
def test_a_segment_of_another_layout_is_refused(magic):
    if not shm.available():
        pytest.skip("native shmstate unavailable (no C compiler)")
    seg = shared_memory.SharedMemory(create=True, size=1 << 16)
    try:
        # the header an old wt table of 64 x 2 rules would have had
        seg.buf[:40] = struct.pack("<8s4q", magic[::-1], 64, 2, 0, 0)
        with pytest.raises(RuntimeError, match="not a wt table"):
            shm.ShmWarmTier(name=seg.name)
    finally:
        seg.close()
        seg.unlink()


def _segment_bytes(name: str):
    path = os.path.join("/dev/shm", name.lstrip("/"))
    return os.stat(path).st_blocks * 512 if os.path.exists(path) else None


def test_absent_keys_read_no_record_at_the_deployed_geometry(tiers):
    """2^20 positions x 1,000 rules is `crs1k-edge`'s table: a 4.3 GB
    mapping (16 blocks a position) of which nothing is resident until
    written."""
    c = tiers(capacity=1 << 20, max_rules=1000, expiry_ns=1 << 60)
    absent = [f"172.{16 + (i >> 16)}.{(i >> 8) & 255}.{i & 255}"
              for i in range(20_000)]
    before = _segment_bytes(c.name)
    assert not c.contains_batch(absent).any()
    for ip in absent:
        assert c.take(ip) is None
        assert c.peek(ip) is None
    assert (c.probes, c.record_reads) == (60_000, 0)
    after = _segment_bytes(c.name)
    if before is not None:
        # tag pages at most (8 MB); a record page per key would be 80 MB
        assert after - before <= (1 << 20) * 8 + 8192, (before, after)

    present = [f"100.64.{i >> 8}.{i & 255}" for i in range(500)]
    ent = [(r, 1, 2, 3) for r in range(1000)]
    for i, ip in enumerate(present):
        assert c.put(ip, ent, 1000 + i)
    assert (c.probes, c.record_reads) == (60_500, 0)  # fresh keys: tags only
    assert c.contains_batch(present).all()
    assert c.probes == 61_000 and 500 <= c.record_reads <= 502
    reads = c.record_reads
    assert not c.contains_batch(absent).any()
    assert c.record_reads - reads <= 2  # a 64-bit tag shared with an absent key
    assert c.peek(present[7]) == ent

    reads, grown = c.record_reads, _segment_bytes(c.name)
    c.clear()  # zeroes the tags; walks no record
    assert c.record_reads == reads and _segment_bytes(c.name) == grown
    assert len(c) == 0 and c.keys() == []
    assert not c.contains_batch(present).any()
    assert c.record_reads == reads  # stale records behind empty tags are never read


# ---- PR 33: a record takes the room its counters need ----


def _vector(rng, n, max_rules):
    rids = rng.sample(range(max_rules), n)  # insertion order, not sorted
    return [(r, rng.randrange(1, 9), 1_790_000_000 + rng.randrange(10_000),
             rng.randrange(1_000_000_000)) for r in rids]


@pytest.mark.parametrize("n_entries", [1, 2, 5, 6, 15, 16, 400, 10_000])
def test_round_trip_is_identical_at_ten_thousand_rules(tiers, n_entries):
    """`upstream-stress10k`'s tier: max_rules 10,000.  Whatever a record
    holds comes back entry for entry in insertion order, by every way of
    reading it, and what a put writes follows the counters held."""
    c = tiers(capacity=1 << 10, max_rules=10_000, expiry_ns=1 << 60)
    rng = random.Random(n_entries)
    ent = _vector(rng, n_entries, 10_000)
    od = {r: (h, s, ns) for r, h, s, ns in ent}
    assert c.put("198.51.100.7", ent, 5)
    assert c.bytes_written == shm.wt_record_bytes(n_entries)
    assert c.bytes_written <= 128 + 24 * n_entries + 8 * (n_entries // 10 + 1)
    assert c.blocks_used == shm.wt_record_blocks(n_entries)
    assert c.peek("198.51.100.7") == ent
    assert c.take("198.51.100.7") == ent
    assert c.blocks_used == 0 and len(c) == 0
    # through the mirror's batched moves, beside a record of another size
    other = _vector(rng, 3, 10_000)
    mirror = shm.create_shadow_mirror(4)
    mirror.install(0, od)
    mirror.install(1, {r: (h, s, ns) for r, h, s, ns in other})
    keys = np.zeros(2 * 104, dtype=np.uint8)
    keys[:12] = np.frombuffer(b"198.51.100.7", np.uint8)
    keys[104:116] = np.frombuffer(b"198.51.100.8", np.uint8)
    status = mirror.spill(c, np.arange(2, dtype=np.int64),
                          (keys, np.full(2, 12, np.int32)), 6)
    assert status.tolist() == [1, 1] and len(mirror) == 0
    asked = ["198.51.100.8", "203.0.113.1", "198.51.100.7"]
    stamps = mirror.refill(c, np.arange(3, dtype=np.int32),
                           slotmgr.encode_ips(asked))
    got = mirror.export(np.arange(3, dtype=np.int32))[1]
    assert got[1] is None and (stamps > 0).tolist() == [True, False, True]
    assert list(got[2].items()) == list(od.items())
    assert [(r, *v) for r, v in got[0].items()] == other
    assert c.blocks_used == 0


def test_mapping_and_resident_bytes_follow_the_counters_not_the_ruleset(tiers):
    """The shipped capacity at 10,000 rules: the fixed-stride layout
    mapped 2^20 x 240,136 bytes (252 GB) and gave every record a page of
    its own; a block-chained record of two counters is 256 bytes."""
    c = tiers(capacity=1 << 20, max_rules=10_000, expiry_ns=1 << 60)
    assert c._shm.size <= (1 << 20) * (16 + 16 * 256) + 128 < 5 << 30
    before = _segment_bytes(c.name)
    n = 1 << 15
    ips = [f"100.{64 + (i >> 16)}.{(i >> 8) & 255}.{i & 255}" for i in range(n)]
    vec = [(7, 1, 2, 3), (9_999, 2, 3, 4)]
    assert all(c.put(ip, vec, 1) for ip in ips)
    assert c.bytes_written == n * (128 + 2 * 24)
    assert c.blocks_used == n
    if before is not None:
        # 8 MB of blocks and at most all 16 MB of tags and heads; a page
        # a record, as the fixed stride had it, were 128 MB
        assert _segment_bytes(c.name) - before <= n * 256 + (17 << 20)
    assert (c.probes, c.record_reads) == (n, 0)  # fresh keys: tags only
    assert all(c.take(ip) == vec for ip in ips)
    assert c.probes == 2 * n and n <= c.record_reads <= n + 4


def test_an_update_reuses_lengthens_and_cuts_its_chain(tiers):
    c = tiers(capacity=64, max_rules=200, expiry_ns=1 << 60)
    rng = random.Random(5)
    for n in (3, 47, 200, 12, 5, 6):
        ent = _vector(rng, n, 200)
        assert c.put("192.0.2.1", ent, n)
        assert len(c) == 1
        assert c.blocks_used == shm.wt_record_blocks(n)
        assert c.peek("192.0.2.1") == ent
    assert c.put("192.0.2.2", _vector(rng, 30, 200), 1)
    assert c.blocks_used == 2 + shm.wt_record_blocks(30)
    c.clear()
    assert (len(c), c.blocks_used) == (0, 0)
    assert c.put("192.0.2.1", ent, 1) and c.peek("192.0.2.1") == ent


def test_an_exhausted_arena_drops_the_put_and_keeps_no_stale_copy(tiers):
    """The arena holds 16 blocks a position: 64 positions x 16 = 1,024
    blocks; a full record of 1,000 counters takes 101."""
    c = tiers(capacity=64, max_rules=1000, expiry_ns=1 << 60)
    full = [(r, 1, 2, 3) for r in range(1000)]
    ips = [f"10.9.0.{i}" for i in range(12)]
    stored = [c.put(ip, full, 1) for ip in ips]
    assert stored == [True] * 10 + [False] * 2   # 10 x 101 = 1,010 blocks
    assert (len(c), c.dropped, c.blocks_used) == (10, 2, 1010)
    assert c.put("10.9.1.1", full[:100], 1)      # 11 blocks: they fit
    assert not c.put("10.9.1.2", full[:100], 1)
    # an update that cannot grow is dropped, and the older copy with it:
    # the caller keeps the state where it was, so none may stay here
    assert not c.put("10.9.1.1", full, 2)
    assert "10.9.1.1" not in c and len(c) == 10
    assert c.take(ips[0]) == full                # blocks come back
    assert c.put("10.9.1.1", full, 3) and c.peek("10.9.1.1") == full


def test_a_steal_that_finds_the_arena_exhausted_is_one_drop(tiers):
    """64 positions are one probe window; each holds 16 blocks, so the
    arena is full too.  A put that would steal an expired record and
    cannot have the blocks it needs is dropped, the victim stays, and the
    loss is counted once."""
    c = tiers(capacity=64, max_rules=1000, expiry_ns=10)
    sixteen_blocks = [(r, 1, 2, 3) for r in range(155)]
    ips = [f"10.8.0.{i}" for i in range(64)]
    assert all(c.put(ip, sixteen_blocks, 1) for ip in ips)
    assert (len(c), c.dropped, c.blocks_used) == (64, 0, 1024)
    assert not c.put("10.8.1.1", sixteen_blocks + [(999, 1, 2, 3)], 100)
    assert (len(c), c.dropped, c.blocks_used) == (64, 1, 1024)
    assert all(ip in c for ip in ips)
    # the same blocks as its victim: the steal goes through, one loss more
    assert c.put("10.8.1.1", sixteen_blocks, 100)
    assert (len(c), c.dropped, c.blocks_used) == (64, 2, 1024)
    assert c.peek("10.8.1.1") == sixteen_blocks


def test_py_warm_tier_counts_the_same_bytes():
    py = shm.PyWarmTier(capacity=64, max_rules=10_000)
    assert py.put("a", [(1, 1, 2, 3)] * 2, 1)
    assert py.put("b", [(r, 1, 2, 3) for r in range(17)], 1)
    assert py.bytes_written == (128 + 48) + (128 + 17 * 24 + 2 * 8)
    assert shm.wt_record_blocks(5) == 1 and shm.wt_record_blocks(6) == 2
