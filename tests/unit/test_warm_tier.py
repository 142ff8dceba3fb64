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

import pytest

from banjax_tpu.native import shm

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


@pytest.mark.parametrize("magic", [b"bjxwt001", b"bjxhsm02", b"\0" * 8],
                         ids=["old-layout", "fc-table", "zeroed"])
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
    """2^20 positions x 1,000 rules is `crs1k-edge`'s table: 24,128-byte
    records, a 25 GB mapping of which nothing is resident until written."""
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
