"""Native slot manager vs the Python dict+LRU path: exact parity.

The C manager (native/slotmgr.c) replaces the per-distinct-IP Python
loop in DeviceWindows.slots_for_unique_ips; the dict loop stays as the
fallback and THE differential oracle.  Parity here is stronger than the
spill-is-lossless argument needs: slot ids, eviction victims and their
order, restore triggers, refusal points, growth chains, and free-stack
order must all match verbatim, so the two modes are interchangeable
mid-deployment (a box without a C compiler produces the same device
layout as one with it).
"""

import random
import re

import numpy as np
import pytest

from banjax_tpu.config.schema import Decision, RegexWithRate
from banjax_tpu.matcher.windows import DeviceWindows
from banjax_tpu.native import slotmgr
from tests.shadow_access import FORMS, pending_restore_slots, plant, shadow

pytestmark = pytest.mark.skipif(
    slotmgr.create(8) is None,
    reason="native slotmgr unavailable (no C compiler)",
)

NS = 1_000_000_000


def make_rule(name="r", interval_s=5.0, hits=2) -> RegexWithRate:
    return RegexWithRate(
        rule=name, regex_string="x", regex=re.compile("x"),
        interval_ns=int(interval_s * NS), hits_per_interval=hits,
        decision=Decision.NGINX_BLOCK,
    )


@pytest.fixture(params=sorted(FORMS))
def form(request):
    """What the native side is handed of a batch's addresses: the strings,
    or the byte spans the submit stage's pass takes (the dict path, the
    oracle, always takes strings)."""
    return FORMS[request.param]


def make_pair(capacity):
    """(native, dict-oracle) DeviceWindows at the same capacity."""
    nat = DeviceWindows([make_rule()], capacity=capacity,
                        native_slotmgr=True)
    assert nat.slotmgr_native, "native manager failed to engage"
    ora = DeviceWindows([make_rule()], capacity=capacity,
                        native_slotmgr=False)
    assert not ora.slotmgr_native
    return nat, ora


def assert_same_state(nat: DeviceWindows, ora: DeviceWindows, ctx=""):
    assert nat.capacity == ora.capacity, ctx
    assert nat.slot_addresses() == ora.slot_addresses(), ctx
    assert nat._pending_evict == ora._pending_evict, ctx
    assert pending_restore_slots(nat) == pending_restore_slots(ora), ctx
    assert nat.eviction_count == ora.eviction_count, ctx
    assert nat.grow_count == ora.grow_count, ctx
    assert nat.occupancy == ora.occupancy, ctx
    assert nat._sm.assigned() == len(ora._slots), ctx
    assert nat._sm.free_count() == len(ora._free), ctx
    np.testing.assert_array_equal(
        nat._pin_counts, ora._pin_counts, err_msg=ctx
    )
    np.testing.assert_array_equal(
        nat._last_used, ora._last_used, err_msg=ctx
    )
    # the manager alone owns slot -> address and the eviction order: no
    # Python copy beside it, and the order as it lies (nothing sorted for
    # the look) holds every assigned slot once, oldest stamp first
    assert not nat._slot_ip and not nat._slots, ctx
    kept = nat._sm.order()
    assert sorted(kept.tolist()) == sorted(ora._slot_ip), ctx
    assert (np.diff(nat._last_used[kept]) >= 0).all(), ctx


def lockstep(nat, ora, ips, ctx="", form=None):
    """One identical batch through both paths; returns the slots (or
    None on a matching refusal).  `form` makes of the strings what the
    native side is handed in their place (tests.shadow_access.spans_of:
    the byte spans the submit stage's pass takes); the dict path always
    takes the strings."""
    if form is None:
        a = nat.slots_for_unique_ips(ips)
    else:
        a = nat.resolve_addresses(form(ips)).slots
    b = ora.slots_for_unique_ips(ips)
    assert (a is None) == (b is None), f"{ctx}: refusal diverged"
    if a is not None:
        np.testing.assert_array_equal(a, b, err_msg=ctx)
    assert_same_state(nat, ora, ctx)
    return a


def ip_of(i: int) -> str:
    return f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"


def test_basic_assign_hit_and_free_order(form):
    nat, ora = make_pair(8)
    s1 = lockstep(nat, ora, [ip_of(i) for i in range(5)], form=form)
    # free stack pops ascending — list(range(cap-1,-1,-1)).pop() parity
    assert s1.tolist() == [0, 1, 2, 3, 4]
    nat.release_pins(s1), ora.release_pins(s1)
    # hits keep their slots and stamp recency; one new ip takes slot 5
    s2 = lockstep(nat, ora, [ip_of(3), ip_of(0), ip_of(99)], form=form)
    assert s2.tolist() == [3, 0, 5]
    nat.release_pins(s2), ora.release_pins(s2)
    assert_same_state(nat, ora)


def test_eviction_victim_and_order_parity(form):
    """At capacity, victims are min-(last_used, slot) over unpinned
    slots untouched by this batch — both paths, identical sequence."""
    nat, ora = make_pair(4)
    s = lockstep(nat, ora, [ip_of(i) for i in range(4)], form=form)
    nat.release_pins(s), ora.release_pins(s)
    # refresh slots 2, 3 so 0 and 1 are the LRU victims, in slot order
    s = lockstep(nat, ora, [ip_of(2), ip_of(3)], form=form)
    nat.release_pins(s), ora.release_pins(s)
    s = lockstep(nat, ora, [ip_of(100), ip_of(101)], form=form)
    assert s.tolist() == [0, 1]
    assert nat._pending_evict == [0, 1]
    assert nat.eviction_count == 2
    nat.release_pins(s), ora.release_pins(s)


def test_refusal_when_all_pinned_leaves_partial_state(form):
    """Every slot pinned by an in-flight batch: a new distinct ip must
    refuse (None) in both paths, with identical partial placements."""
    nat, ora = make_pair(2)
    # pins both slots
    s = lockstep(nat, ora, [ip_of(0), ip_of(1)], form=form)
    # one hit + two misses: the hit resolves, the first miss has no free
    # slot and no evictable victim -> refusal after identical state
    out = lockstep(
        nat, ora, [ip_of(0), ip_of(7), ip_of(8)], "refusal", form=form)
    assert out is None
    nat.release_pins(s), ora.release_pins(s)
    # after the split-retry pins are gone, the same ips place fine
    s2 = lockstep(nat, ora, [ip_of(7), ip_of(8)], form=form)
    assert s2 is not None


def test_grow_free_stack_order_parity(monkeypatch, form):
    """Grown slots drain AFTER every pre-grow free slot, ascending —
    the Python free-list splice order, replicated by sm_grow."""
    monkeypatch.setattr(DeviceWindows, "AUTO_START_CAPACITY", 32)
    nat, ora = make_pair(0)  # auto-grow mode
    cap0 = nat.capacity
    n = cap0 + 3  # force one doubling
    s = lockstep(nat, ora, [ip_of(i) for i in range(n)], form=form)
    assert s.tolist() == list(range(n))
    assert nat.capacity == cap0 * 2
    assert nat.grow_count == ora.grow_count == 1
    nat.release_pins(s), ora.release_pins(s)


def test_shadow_restore_trigger_parity(form):
    """A previously-evicted ip (present in the host shadow) re-admitting
    must append the same (slot, ip) restore in both modes."""
    nat, ora = make_pair(2)
    s = lockstep(nat, ora, [ip_of(0), ip_of(1)], form=form)
    nat.release_pins(s), ora.release_pins(s)
    for w in (nat, ora):  # counters spilled for ip 0, as apply would
        plant(w, ip_of(0), {0: (1, 1_700_000_000, 0)})
    s = lockstep(nat, ora, [ip_of(2), ip_of(3)], form=form)  # evicts 0 and 1
    nat.release_pins(s), ora.release_pins(s)
    s = lockstep(nat, ora, [ip_of(0)], form=form)  # returns: restore fires
    assert pending_restore_slots(nat) == pending_restore_slots(ora)
    assert len(pending_restore_slots(nat)) == 1
    assert nat.slot_addresses()[pending_restore_slots(nat)[0]] == ip_of(0)
    nat.release_pins(s), ora.release_pins(s)


def test_clear_parity(form):
    nat, ora = make_pair(4)
    s = lockstep(nat, ora, [ip_of(i) for i in range(4)], form=form)
    nat.release_pins(s), ora.release_pins(s)
    nat.clear(), ora.clear()
    assert nat._sm.assigned() == 0
    assert nat._sm.free_count() == 4
    s = lockstep(nat, ora, [ip_of(9), ip_of(8)], form=form)
    assert s.tolist() == [0, 1]  # full stack again, ascending
    nat.release_pins(s), ora.release_pins(s)


def test_non_ascii_ip_strings(form):
    """Oracle inputs (not real traffic) may be non-ASCII; the utf-8 span
    encode must keep parity."""
    nat, ora = make_pair(4)
    ips = ["1.2.3.4", "καφές", "1.2.3.4é", "漢字"]
    s = lockstep(nat, ora, ips, form=form)
    nat.release_pins(s), ora.release_pins(s)
    s = lockstep(nat, ora, ["καφές", "漢字", "x"], form=form)
    assert s.tolist()[:2] == [1, 3]
    nat.release_pins(s), ora.release_pins(s)


@pytest.mark.parametrize("capacity,seed", [(16, 1), (16, 2), (64, 3)])
def test_parity_fuzz_eviction_churn(capacity, seed, form):
    """Randomized lockstep: batches drawn from an ip pool ~4x capacity
    (constant eviction/restore churn), pins held across batches at
    random (refusal + partial-state parity), periodic shadow spills and
    clears.  Every batch asserts full-state equality."""
    rng = random.Random(seed)
    nat, ora = make_pair(capacity)
    pool = [ip_of(i) for i in range(capacity * 4)]
    held = []  # slots pinned by "in-flight" batches, released randomly
    for step in range(200):
        k = rng.randrange(1, capacity + 4)
        ips = rng.sample(pool, min(k, len(pool)))
        s = lockstep(nat, ora, ips, f"step {step}", form=form)
        if s is not None:
            if rng.random() < 0.7:
                nat.release_pins(s), ora.release_pins(s)
            else:
                held.append(s)
        while held and (s is None or rng.random() < 0.4):
            # a refusal means the runner splits — free an old batch so
            # the stream can make progress, exactly as apply_bitmap does
            h = held.pop(rng.randrange(len(held)))
            nat.release_pins(h), ora.release_pins(h)
        if rng.random() < 0.15:
            ip = rng.choice(pool)
            plant(nat, ip, {0: (step + 1, 1_700_000_000, 0)})
            plant(ora, ip, {0: (step + 1, 1_700_000_000, 0)})
        if rng.random() < 0.02:
            held.clear()
            nat.clear(), ora.clear()
            assert_same_state(nat, ora, f"step {step} clear")
    for h in held:
        nat.release_pins(h), ora.release_pins(h)
    assert_same_state(nat, ora, "final")
    assert nat.eviction_count > 0, "fuzz never churned an eviction"
    assert pending_restore_slots(nat) or nat.eviction_count > 0


def test_parity_fuzz_autogrow_chain(monkeypatch, form):
    """Auto-grow mode: the native path's one-shot doubling chain must
    land at the same capacity the dict path's grow-per-miss loop
    reaches, with identical slot ids before and after."""
    monkeypatch.setattr(DeviceWindows, "AUTO_START_CAPACITY", 64)
    rng = random.Random(7)
    nat, ora = make_pair(0)
    next_ip = 0
    for step in range(12):
        k = rng.randrange(50, 400)
        ips = [ip_of(next_ip + i) for i in range(k)]
        next_ip += k
        s = lockstep(nat, ora, ips, f"grow step {step}", form=form)
        assert s is not None
        nat.release_pins(s), ora.release_pins(s)
    assert nat.grow_count > 0


# ------------------------------------------------------- warm-tier hooks


def make_warm_pair(capacity, warm_capacity=256):
    """(native, dict-oracle) pair with the warm spill/refill hooks armed."""
    nat = DeviceWindows([make_rule()], capacity=capacity,
                        native_slotmgr=True, warm_tier_enabled=True,
                        warm_tier_capacity=warm_capacity)
    assert nat.slotmgr_native and nat._warm is not None
    ora = DeviceWindows([make_rule()], capacity=capacity,
                        native_slotmgr=False, warm_tier_enabled=True,
                        warm_tier_capacity=warm_capacity)
    assert not ora.slotmgr_native and ora._warm is not None
    return nat, ora


def assert_same_warm_state(nat: DeviceWindows, ora: DeviceWindows, ctx=""):
    """Warm-tier side of the parity: identical membership, identical
    per-IP window vectors, identical spill/refill/drop accounting, and
    identical shadow residency (a drop must keep the shadow entry in
    BOTH modes)."""
    assert nat.warm_spills == ora.warm_spills, ctx
    assert nat.warm_refills == ora.warm_refills, ctx
    assert nat.warm_dropped == ora.warm_dropped, ctx
    assert sorted(shadow(nat)) == sorted(shadow(ora)), ctx
    nk, ok_ = sorted(nat._warm.keys()), sorted(ora._warm.keys())
    assert nk == ok_, ctx
    for ip in nk:
        assert nat._warm.peek(ip) == ora._warm.peek(ip), (ctx, ip)


@pytest.mark.parametrize("capacity,seed", [(16, 11), (16, 12), (64, 13)])
def test_parity_fuzz_warm_spill_hooks(capacity, seed, form):
    """test_parity_fuzz_eviction_churn with the warm tier armed: shadow
    entries seeded with REAL window vectors so every eviction exercises
    the spill hook (shadow -> warm put) and every return exercises the
    refill hook (warm take -> shadow -> pending restore), natively and
    through the dict oracle in lockstep.  Each step also runs
    admission_mask over a random probe batch — the three membership
    passes (sm_contains_batch / shadow / warm.contains_batch) plus the
    estimate gate must agree bit-for-bit between the two modes."""
    rng = random.Random(seed)
    nat, ora = make_warm_pair(capacity)
    pool = [ip_of(i) for i in range(capacity * 4)]
    held = []
    seeded = 0
    for step in range(200):
        k = rng.randrange(1, capacity + 4)
        ips = rng.sample(pool, min(k, len(pool)))
        s = lockstep(nat, ora, ips, f"step {step}", form=form)
        assert_same_warm_state(nat, ora, f"step {step}")
        if s is not None:
            if rng.random() < 0.7:
                nat.release_pins(s), ora.release_pins(s)
            else:
                held.append(s)
        while held and (s is None or rng.random() < 0.4):
            h = held.pop(rng.randrange(len(held)))
            nat.release_pins(h), ora.release_pins(h)
        if rng.random() < 0.35:
            # spill payload: a real (rule_id -> (hits, start_s, start_ns))
            # vector, distinct per seeding so a content mismatch is loud
            ip = rng.choice(pool)
            seeded += 1
            vec = {0: (seeded, 1_700_000_000 + seeded, seeded * 7)}
            for w in (nat, ora):
                plant(w, ip, vec)
        if rng.random() < 0.5:
            probe = rng.sample(pool, rng.randrange(1, capacity))
            est = np.zeros(len(probe), dtype=np.int64)
            a = nat.admission_mask(probe, estimates=est, min_estimate=1)
            b = ora.admission_mask(probe, estimates=est, min_estimate=1)
            np.testing.assert_array_equal(a, b, err_msg=f"step {step}")
            assert nat.slot_refusals == ora.slot_refusals, f"step {step}"
            assert nat.sketch_admissions == ora.sketch_admissions
    for h in held:
        nat.release_pins(h), ora.release_pins(h)
    assert_same_state(nat, ora, "final")
    assert_same_warm_state(nat, ora, "final")
    assert nat.eviction_count > 0, "fuzz never churned an eviction"
    assert nat.warm_spills > 0, "no eviction ever spilled to warm"
    assert nat.warm_refills > 0, "no returning IP ever refilled"
    assert nat.slot_refusals > 0, "admission probes never refused"


def test_warm_drop_keeps_shadow_in_both_modes(form):
    """A warm tier too small to place a spill: both modes must keep the
    shadow entry (lossless), report the drop, and stay in lockstep."""
    nat, ora = make_warm_pair(2, warm_capacity=1)
    vec = {0: (3, 1_700_000_123, 42)}
    n = 10
    for i in range(n):
        ip = ip_of(i)
        for w in (nat, ora):
            plant(w, ip, vec)
        s = lockstep(nat, ora, [ip], f"fill {i}", form=form)
        nat.release_pins(s), ora.release_pins(s)
        assert_same_warm_state(nat, ora, f"fill {i}")
    assert nat.warm_dropped > 0, "tiny tier never dropped"
    # lossless: every evicted ip's vector is in the warm tier OR shadow
    for i in range(n - 2):
        ip = ip_of(i)
        in_warm = nat._warm.peek(ip) is not None
        assert in_warm or ip in shadow(nat), ip
