"""The submit stage's address pass on byte spans, and the slot manager as
the one owner of slot -> address and of the eviction order (ISSUE 45).

Three hand-overs are held here, each against what it replaced, which
stays in the tree: the shards' distinct-address tables merged by bytes
(slotmgr.merge_spans) against the string merge
(CompositeWork.unique_ips); the eviction order kept between batches
against the selection over the whole table (slotmgr.select_order, the
oracle) and the dict path's argmin; and introspection read from the
manager's keys against the dict path's own mirror.  The span pass itself
runs case for case beside the string pass in test_slotmgr.py and
test_resolve_addresses.py (their `form` fixture).
"""

import random

import numpy as np
import pytest

from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.matcher.windows import DeviceWindows
from banjax_tpu.matcher.workset import CompositeWork, ListWork
from banjax_tpu.native import shm, slotmgr
from banjax_tpu.obs.sketch import TrafficSketch, hash_ip
from tests.differential.test_host_parallel_differential import _build
from tests.shadow_access import plant, shadow, spans_of
from tests.unit.test_slotmgr import (
    assert_same_state,
    ip_of,
    lockstep,
    make_pair,
    make_rule,
)

pytestmark = pytest.mark.skipif(
    slotmgr.create(8) is None or not shm.available(),
    reason="native slotmgr / shmstate unavailable (no C compiler)",
)

LONG = slotmgr.EVICT_KEY_STRIDE  # a slot's bytes of the key slab


def long_ip(i: int, extra: int = 40) -> str:
    """An address past the slab's stride: it keeps its own allocation."""
    return f"{ip_of(i)}-" + "x" * (LONG + extra - len(ip_of(i)) - 1)


# ------------------------------------------------- the kept eviction order


def assert_full_order(nat, ctx=""):
    """The kept order, every run sorted where it lies, is the selection's
    (last_used, slot) order over the assigned slots."""
    lu = nat._last_used
    assigned = np.sort(nat._sm.order())
    want = slotmgr.select_order(lu[assigned], assigned.astype(np.int32), 7)
    np.testing.assert_array_equal(nat._sm.order(lu), want, err_msg=ctx)
    # sorting the runs moved no slot out of its run
    np.testing.assert_array_equal(nat._sm.order(), want, err_msg=ctx)


@pytest.mark.parametrize("capacity,seed,pin_share,long_share", [
    (16, 41, 0.3, 0.0), (64, 42, 0.0, 0.1), (64, 43, 0.6, 0.1),
    (256, 44, 0.3, 0.02), (128, 45, 0.9, 0.0), (32, 46, 0.5, 0.5),
])
def test_kept_order_is_the_full_selection(capacity, seed, pin_share,
                                          long_share):
    """Random streams through the native manager and the dict path in
    lockstep — batches held in flight (pins; refusals with their partial
    state), clears, addresses past the slab's stride — with the kept
    order looked at as it lies after every batch (assert_same_state) and
    sorted out against the selection every few: the same victims in the
    same order whether or not a look sorted the runs early."""
    rng = random.Random(seed)
    nat, ora = make_pair(capacity)
    pool = [
        long_ip(i) if rng.random() < long_share else ip_of(i)
        for i in range(capacity * 3)
    ]
    held = []
    refusals = 0
    for step in range(150):
        k = rng.randrange(1, capacity // 2 + 2)
        form = spans_of if rng.random() < 0.5 else None
        s = lockstep(nat, ora, rng.sample(pool, k), f"step {step}", form)
        if s is None:
            refusals += 1
        elif rng.random() < pin_share:
            held.append(s)
        else:
            nat.release_pins(s), ora.release_pins(s)
        while held and (s is None or rng.random() < 0.25):
            h = held.pop(rng.randrange(len(held)))
            nat.release_pins(h), ora.release_pins(h)
        if step % 7 == 3:
            assert_full_order(nat, f"step {step}")
        if rng.random() < 0.02:
            held.clear()
            nat.clear(), ora.clear()
            assert_same_state(nat, ora, f"step {step} clear")
            assert len(nat._sm.order()) == 0
    for h in held:
        nat.release_pins(h), ora.release_pins(h)
    assert_same_state(nat, ora, "final")
    assert_full_order(nat, "final")
    assert nat.eviction_count > capacity
    if pin_share >= 0.9:
        assert refusals, "the stream never ran out of evictable slots"


def test_kept_order_survives_the_grow_chain(monkeypatch):
    """Auto-grow: the order's links are extended with the table and the
    slots a grow adds join the order when they are first placed."""
    monkeypatch.setattr(DeviceWindows, "AUTO_START_CAPACITY", 32)
    monkeypatch.setattr(DeviceWindows, "AUTO_MEM_BUDGET_BYTES", 16 * 256)
    rng = random.Random(3)
    nat, ora = make_pair(0)
    assert nat.max_capacity == 256
    pool = [ip_of(i) for i in range(900)]
    for step in range(60):
        ips = rng.sample(pool, rng.randrange(1, 90))
        s = lockstep(nat, ora, ips, f"step {step}", spans_of)
        assert s is not None
        nat.release_pins(s), ora.release_pins(s)
        if step % 5 == 0:
            assert_full_order(nat, f"step {step}")
    assert nat.capacity == 256 and nat.grow_count == 3
    assert nat.eviction_count > 256
    assert_full_order(nat, "final")


def test_pinned_slots_are_passed_where_they_lie_and_stay():
    """The re-validation skips: the oldest slots are pinned by a batch in
    flight, so the walk passes them at the head of the order — in this
    call and in the next — and takes the ones behind; released, they are
    the next victims, in slot order."""
    nat, ora = make_pair(8)
    old = lockstep(nat, ora, [ip_of(i) for i in range(4)], "old")
    young = lockstep(nat, ora, [ip_of(i) for i in range(4, 8)], "young")
    nat.release_pins(young), ora.release_pins(young)
    for rnd in range(2):  # slots 0-3 stay pinned: 4, 5 go, then 6, 7
        s = lockstep(
            nat, ora, [ip_of(100 + 2 * rnd), ip_of(101 + 2 * rnd)],
            f"round {rnd}", spans_of)
        assert s.tolist() == [4 + 2 * rnd, 5 + 2 * rnd]
        nat.release_pins(s), ora.release_pins(s)
        assert nat._sm.order().tolist()[:4] == [0, 1, 2, 3]
    # nothing evictable is left behind the pinned four: refusal, and the
    # same partial state on both sides
    held = lockstep(nat, ora, [ip_of(100 + i) for i in range(4)], "hold")
    assert lockstep(nat, ora, [ip_of(200)], "refusal", spans_of) is None
    nat.release_pins(held), ora.release_pins(held)
    nat.release_pins(old), ora.release_pins(old)
    s = lockstep(nat, ora, [ip_of(300 + i) for i in range(3)], "after")
    assert s.tolist() == [0, 1, 2]
    nat.release_pins(s), ora.release_pins(s)
    assert_full_order(nat)


def test_a_sequence_number_that_goes_back_is_walked_to_its_place():
    """No caller of the product's stamps with a number that goes back;
    the manager by itself keeps the order true if one does."""
    sm = slotmgr.create(8)
    lu = np.zeros(8, dtype=np.int64)
    pins = np.zeros(8, dtype=np.int32)

    def batch(ips, seq):
        slots, miss, ctx = sm.lookup_batch(ips, seq, lu)
        sm.place_misses(ctx, slots, miss, seq, pins, lu)
        return slots.tolist()

    assert batch([ip_of(i) for i in range(3)], 5) == [0, 1, 2]
    assert batch([ip_of(3), ip_of(4)], 9) == [3, 4]
    assert sm.order(lu).tolist() == [0, 1, 2, 3, 4]   # both runs sorted
    assert batch([ip_of(5), ip_of(1)], 7) == [5, 1]   # between the two
    assert batch([ip_of(6)], 5) == [6]                # into a sorted run
    assert batch([ip_of(7)], 9) == [7]                # ... and the last
    assert lu.tolist() == [5, 7, 5, 9, 9, 7, 5, 9]
    want = [0, 2, 6, 1, 5, 3, 4, 7]
    assert sm.order().tolist() == sorted(want, key=lambda s: (lu[s], s))
    assert sm.order(lu).tolist() == want
    # and the victims come in that order
    slots, miss, ctx = sm.lookup_batch([ip_of(50 + i) for i in range(8)],
                                       10, lu)
    _, evicted, _, ok = sm.place_misses(ctx, slots, miss, 10, pins, lu)
    assert ok and evicted.tolist() == want


# --------------------------------------------- keys: the slab and past it


@pytest.mark.parametrize("warm", [False, True])
def test_keys_past_the_stride_and_the_empty_address(warm):
    """A key longer than the slab's stride keeps its own allocation, is
    found, evicted and read back whole; the empty address is a key like
    any other.  With the warm tier off a victim's record has no tier to
    go to and is homed in the dict under its address — read off the
    manager's keys, whole, also where evict_keys cut it."""
    kw = dict(warm_tier_enabled=True, warm_tier_capacity=64) if warm else {}
    nat = DeviceWindows([make_rule()], capacity=4, **kw)
    ora = DeviceWindows([make_rule()], capacity=4, native_slotmgr=False,
                        **kw)
    twins = [long_ip(1, 0), long_ip(1, 1)]  # equal over the whole stride
    assert twins[0][:LONG] == twins[1][:LONG] and len(twins[0]) == LONG
    first = ["", twins[0], twins[1], "\x00"]
    vec = {0: (2, 1_700_000_000, 5)}
    s = lockstep(nat, ora, first, "first", spans_of)
    assert s.tolist() == [0, 1, 2, 3]
    nat.release_pins(s), ora.release_pins(s)
    assert nat.slot_addresses() == dict(enumerate(first))
    assert nat._sm.keys_of([3, 9, 1, -1]) == ["\x00", None, twins[0], None]
    for w in (nat, ora):
        for ip in first:
            plant(w, ip, vec)
    for ip in first:
        assert nat.get(ip) == ora.get(ip) and nat.get(ip)[1]
    s = lockstep(nat, ora, [ip_of(i) for i in range(4)], "evict", spans_of)
    nat.release_pins(s), ora.release_pins(s)
    assert nat._pending_evict == [0, 1, 2, 3]
    # every victim's record is where the dict path has it, under its whole
    # address: the warm tier (which cuts a key at its stride, in both
    # forms) or the dict
    assert dict(shadow(nat)) == dict(shadow(ora))
    if not warm:
        assert sorted(shadow(nat)) == sorted(first)
        assert nat._sm.evicted_long_keys() == [
            (2, twins[1].encode())]  # the one key longer than the stride
    assert nat.format_states() == ora.format_states()
    back = [twins[1], "", "\x00", twins[0]]
    s = lockstep(nat, ora, back, "back", spans_of)
    nat.release_pins(s), ora.release_pins(s)
    assert_same_state(nat, ora, "back")
    for ip in first:
        assert nat.get(ip) == ora.get(ip), repr(ip)
    # a placement with no miss does not reach the C side: no victims, and
    # none read off what the last one left there
    hits = nat._sm.lookup_batch(back, 99, nat._last_used)
    assert len(hits[1]) == 0
    none = nat._sm.place_misses(hits[2], hits[0], hits[1], 99,
                                nat._pin_counts, nat._last_used)
    assert nat._sm.evicted_keys(none[2]) == []


# ------------------------------- introspection without a Python slot -> ip


@pytest.mark.parametrize("warm,seed", [(False, 51), (True, 52), (True, 53)])
def test_introspection_reads_the_managers_keys(warm, seed):
    """get, shadow_items, format_states, len and occupancy after a stream
    with evictions, returns and planted records: the native windows (no
    `_slot_ip`; addresses read from the manager) say what the dict path
    says."""
    rng = random.Random(seed)
    kw = dict(warm_tier_enabled=True, warm_tier_capacity=512) if warm else {}
    rules = [make_rule("a"), make_rule("b", hits=5)]
    nat = DeviceWindows(rules, capacity=16, **kw)
    ora = DeviceWindows(rules, capacity=16, native_slotmgr=False, **kw)
    pool = [ip_of(i) for i in range(60)] + ["καφές", long_ip(7), ""]
    for step in range(80):
        ips = rng.sample(pool, rng.randrange(1, 12))
        form = spans_of if step % 2 else None
        s = lockstep(nat, ora, ips, f"step {step}", form)
        for ip in rng.sample(ips, rng.randrange(0, len(ips) + 1)):
            vec = {rng.randrange(2): (step + 1, 1_700_000_000 + step, step)}
            for w in (nat, ora):
                plant(w, ip, vec)
        nat.release_pins(s), ora.release_pins(s)
        assert nat.occupancy == ora.occupancy == len(nat.slot_addresses())
        assert len(nat) == len(ora)
    assert not nat._slot_ip
    assert nat.eviction_count > 16
    assert dict(nat.shadow_items()) == dict(ora.shadow_items())
    for ip in pool:
        assert nat.get(ip) == ora.get(ip), repr(ip)

    def blocks(w):  # format_states' records; the two forms order the
        return sorted(w.format_states().split("\n\n"))  # tiers' alike

    assert blocks(nat) == blocks(ora)
    if not warm:
        assert nat.format_states() == ora.format_states()


# --------------------------------------------------------- the byte merge


def _gate_lines(now, seed, n=400):
    """Lines whose addresses repeat inside and across shards, some
    Python-parsed (a timestamp the C parse defers), some not ASCII."""
    rng = random.Random(seed)
    odd = ["10.0.0.é", "καφές", "10.0.0.1 "]
    lines = []
    for i in range(n):
        ip = (rng.choice(odd) if rng.random() < 0.05
              else f"10.0.{rng.randrange(3)}.{rng.randrange(40)}")
        ts = "1_0.5" if rng.random() < 0.06 else f"{now:f}"
        lines.append(
            f"{ts} {ip} GET example.com GET /page{i % 7} HTTP/1.1 ua -")
    return lines


@pytest.fixture(scope="module")
def matcher():
    m, *_ = _build(TpuMatcher, device_windows=True)
    yield m
    m.close()


def _sharded(matcher, lines, now, cuts):
    edges = [0, *cuts, len(lines)]
    shards = [
        (a, matcher.encode_shard(lines[a:b], now))
        for a, b in zip(edges, edges[1:])
    ]
    return matcher.pipeline_begin_from_shards(lines, now, shards)["work"]


def assert_spans_are_the_strings(work, ctx=""):
    want_ips, want_inv = work.unique_ips()
    spans, inv = work.unique_ip_spans()
    assert spans.strings() == list(want_ips), ctx
    np.testing.assert_array_equal(inv, want_inv, err_msg=ctx)
    buf, offs, lens = spans.enc
    raw = [ip.encode("utf-8", "surrogatepass") for ip in want_ips]
    assert [bytes(buf[o:o + n]) for o, n in zip(offs, lens)] == raw, ctx
    assert buf[-1] == 0 and len(buf) == sum(map(len, raw)) + 1, ctx
    assert spans.hashes.tolist() == [hash_ip(ip) for ip in want_ips], ctx


@pytest.mark.parametrize("seed,cuts", [
    (61, []), (62, [100, 200, 300]), (63, [1, 399]), (64, [37, 38, 250]),
])
def test_byte_merge_is_the_string_merge(matcher, seed, cuts):
    """CompositeWork.unique_ip_spans() against unique_ips() over shards
    with repeated, deferred and non-ASCII addresses: the same addresses
    in the same first-appearance order, the same per-row inverse, the
    sketch's hashes — also over a slice (a chunk of a cut batch), a take
    (rows the gate split off) and a slice of a take."""
    now = 1_700_000_000.0
    lines = _gate_lines(now, seed)
    work = _sharded(matcher, lines, now, cuts)
    assert isinstance(work, CompositeWork) == bool(cuts)
    assert any(w.defer_map for w in getattr(work, "parts", [work]))
    assert_spans_are_the_strings(work, "whole")
    n = len(work)
    rng = random.Random(seed)
    for k in range(6):
        a = rng.randrange(n - 1)
        b = rng.randrange(a + 1, n + 1)
        assert_spans_are_the_strings(work[a:b], f"slice {a}:{b}")
        keep = np.flatnonzero(
            np.asarray([rng.random() < 0.5 for _ in range(n)]))
        if len(keep) > 2:
            taken = work.take(keep)
            assert_spans_are_the_strings(taken, f"take {k}")
            assert_spans_are_the_strings(
                taken[1:len(keep) - 1], f"slice of take {k}")


def test_a_work_set_of_strings_has_no_spans(matcher):
    """A Python parse keeps strings, alone or as one shard of a batch,
    and the pass takes those; the dict path is handed strings too."""
    now = 1_700_000_000.0
    lines = _gate_lines(now, 65, n=60)
    native = _sharded(matcher, lines, now, [20])
    lw = ListWork(list(native.parts[0]))
    assert lw.unique_ip_spans() is None
    mixed = CompositeWork([lw, native.parts[1]], [0, 20])
    assert mixed.unique_ip_spans() is None
    assert mixed.unique_ips()[0] == native.unique_ips()[0]
    got, inv = matcher._distinct_addresses(native)
    assert isinstance(got, slotmgr.AddressSpans)
    assert isinstance(matcher._distinct_addresses(mixed)[0], list)
    matcher.device_windows.slotmgr_native = False  # what the dict path says
    try:
        assert isinstance(matcher._distinct_addresses(native)[0], list)
    finally:
        matcher.device_windows.slotmgr_native = True


# ----------------------------------------- strings only where one is read


def test_spans_make_strings_once_and_only_when_asked():
    ips = [ip_of(i) for i in range(50)] + ["καφές", ""]
    spans = spans_of(ips)
    others = spans_of([ip_of(100 + i) for i in range(40)])
    dw = DeviceWindows([make_rule()], capacity=64,
                       warm_tier_enabled=True, warm_tier_capacity=64)
    sk = TrafficSketch(["r"], width=64, depth=2)
    for _ in range(3):  # hits, misses, evictions, spills — no string
        for batch in (others, spans):
            res = dw.resolve_addresses(batch, sketch=sk, gate=True)
            dw.release_pins(res.slots)
    assert res.ips is spans and spans._strings is None
    assert res.hashes is spans.hashes and others._strings is None
    assert dw.eviction_count > 100 and dw.warm_spills == 0
    assert len(spans) == 52 and spans._strings is None
    assert spans[51] == "" and spans._strings == ips
    assert list(reversed(spans)) == ips[::-1] and list(spans) == ips


@pytest.mark.parametrize("bound,seed", [(16, 1), (64, 2)])
def test_the_candidate_log_keeps_spans_and_decodes_at_the_read(bound, seed):
    """The sketch's candidate set from batches noted as spans is the set
    from the same batches noted as strings, and the log decodes only the
    batches its walk reaches."""
    rng = random.Random(seed)
    a = TrafficSketch(["r"], width=64, depth=2, max_candidates=bound)
    b = TrafficSketch(["r"], width=64, depth=2, max_candidates=bound)
    pool = [ip_of(i) for i in range(4 * bound)]
    noted = []
    for _ in range(40):
        ips = rng.sample(pool, rng.randrange(1, bound))
        spans = spans_of(ips)
        noted.append(spans)
        a.note_assignments(ips, spans.hashes)
        b.note_assignments(spans, spans.hashes)
    logged = [ips for ips, _ in b._cand_log]
    assert all(s._strings is None for s in noted)
    assert b._candidates == a._candidates
    assert list(b._candidates) == list(a._candidates)
    read = [s for s in logged if s._strings is not None]
    assert 0 < len(read) < len(noted)


# ------------------------------------------------------------ the counters


def test_passes_count_by_form_and_the_scan_follows_the_victims():
    """`resolve_passes` counts a pass by the form its addresses came in
    (the dict path's pass is `strings` whatever it is handed), and the
    victims' walk reads about the victims and the runs it sorts — not
    the table."""
    cap = 4096
    nat = DeviceWindows([make_rule()], capacity=cap)
    ora = DeviceWindows([make_rule()], capacity=8, native_slotmgr=False)
    assert nat.eviction_scanned_slots == ora.eviction_scanned_slots == 0
    fill = [ip_of(i) for i in range(cap)]
    for a in range(0, cap, 512):
        s = nat.slots_for_unique_ips(fill[a:a + 512])
        nat.release_pins(s)
    assert nat.resolve_passes == {"spans": 0, "strings": 8}
    assert nat.eviction_scanned_slots == 0  # free slots: nothing to find
    evicted = 0
    for k in range(20):
        res = nat.resolve_addresses(
            spans_of([ip_of(10_000 + 100 * k + i) for i in range(100)]))
        nat.release_pins(res.slots)
        evicted += 100
        assert nat.eviction_count == evicted
    assert nat.resolve_passes == {"spans": 20, "strings": 8}
    # 2,000 victims out of four runs of 512: each victim read once, each
    # run's members once more when it was sorted
    assert nat.eviction_scanned_slots == 2000 + 4 * 512
    assert nat.eviction_scanned_slots < 20 * cap // 8
    res = ora.resolve_addresses(spans_of([ip_of(1), ip_of(2)]))
    assert res.slots.tolist() == [0, 1]
    assert ora.resolve_passes == {"spans": 0, "strings": 1}
