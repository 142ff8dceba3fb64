"""The host shadow's native form against its dict form (ISSUE 38).

With the native libraries the window counters of RESIDENT addresses live
in a slot-indexed C mirror (native/shmstate.c sh_*), and absorb, spill,
refill and the restore rows are one C call each over arrays; without
them the shadow is one dict of OrderedDicts (matcher/windows.py).  The
dict form is the oracle: the same sequences of batches, evictions,
returns and maintenance steps through a native and a dict DeviceWindows
have to give the same `get`, the same `format_states` bytes (address and
counter order), the same warm-tier records, the same rows to the device
restore, the same device table and the same counters — the `lockstep`
pattern of test_slotmgr.py, one layer up.  At the end: the default-edge
rules through scheduler, fused program and drain on both forms against
the benchmark's plain reference, with bans that rest on a refill.
"""

import json
import random
import time

import numpy as np
import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher import windows as W
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.matcher.windows import DeviceWindows, split_ns
from banjax_tpu.native import shm, slotmgr
from banjax_tpu.pipeline import PipelineScheduler
from tests.shadow_access import pending_restore_slots, plant, shadow
from tests.unit.test_slotmgr import ip_of, make_rule

pytestmark = pytest.mark.skipif(
    slotmgr.create(8) is None or not shm.available(),
    reason="native libraries unavailable (no C compiler)",
)

NS = 1_000_000_000
T0 = 1_700_000_000


class Pair:
    """A native and a dict DeviceWindows driven with the same calls."""

    def __init__(self, n_rules=3, capacity=8, warm="on", warm_capacity=256,
                 limit=1000):
        rules = [make_rule(f"r{i}", 60.0, limit) for i in range(n_rules)]
        kw = dict(capacity=capacity, warm_tier_enabled=warm != "off",
                  warm_tier_capacity=warm_capacity)
        self.nat = DeviceWindows(rules, native_slotmgr=True, **kw)
        self.ora = DeviceWindows(rules, native_slotmgr=False, **kw)
        assert self.nat._mirror is not None and self.ora._mirror is None
        self.n_rules = n_rules
        self.active = np.ones((1, n_rules), dtype=bool)
        self.both = (self.nat, self.ora)

    def place(self, ips, ctx=""):
        a = self.nat.slots_for_unique_ips(ips)
        b = self.ora.slots_for_unique_ips(ips)
        assert (a is None) == (b is None), ctx
        if a is not None:
            np.testing.assert_array_equal(a, b, err_msg=ctx)
        return a

    def release(self, slots):
        for w in self.both:
            w.release_pins(slots)

    def absorb(self, slots, events):
        """A chunk's event-final states folded in as a device apply's
        would be: events = [(line, rule, hits, start_s, start_ns)] in
        (line, rule) order."""
        ev = np.asarray(events, dtype=np.int32).reshape(-1, 5)
        for w in self.both:
            with w._lock:
                w._absorb_events_locked(slots, *ev.T)

    def apply(self, slots, bits, t_ns, ctx=""):
        """One batch through the classic device apply of both; the events
        are the device's, so they have to agree before the shadows can."""
        ts_s, ts_ns = split_ns(np.asarray(t_ns, dtype=np.int64))
        host = np.zeros(len(slots), dtype=np.int32)
        out = [w.apply_bitmap(bits, slots, ts_s, ts_ns, self.active, host)
               for w in self.both]
        for f in ("line", "rule", "match_type", "exceeded", "seen_ip"):
            np.testing.assert_array_equal(
                getattr(out[0], f), getattr(out[1], f), err_msg=f"{ctx} {f}")
        return out[0]

    def maintain(self):
        """Run the maintenance step of both, → the rows each handed to
        the device restore."""
        rows = []
        for w in self.both:
            seen = []
            real = W._restore_step
            W._restore_step = lambda st, r: (seen.append(np.asarray(r)),
                                             real(st, r))[1]
            try:
                with w._lock:
                    w._run_maintenance_locked()
            finally:
                W._restore_step = real
            rows.append(seen)
        assert len(rows[0]) == len(rows[1])
        for a, b in zip(*rows):
            np.testing.assert_array_equal(a, b)
        return rows[0]

    def check(self, probe=(), ctx="", same_homes=True):
        """Both forms hold the same state and answer alike.  With
        `same_homes` (no put was dropped) every record is also in the
        same home on both sides, in the same place."""
        nat, ora = self.both
        assert len(nat) == len(ora), ctx
        assert pending_restore_slots(nat) == pending_restore_slots(ora), ctx
        assert nat._pending_evict == ora._pending_evict, ctx
        for f in ("eviction_count", "device_events", "sketch_fp_evaluated",
                  "sketch_fp_count"):
            assert getattr(nat, f) == getattr(ora, f), (ctx, f)
        for ip in probe:
            assert nat.get(ip) == ora.get(ip), (ctx, ip)
        a, b = (w.format_states().split("\n\n") for w in self.both)
        assert sorted(a) == sorted(b), ctx
        if not same_homes:
            # a tier with no room drops other puts when it is asked miss
            # by miss (the dict form: a take in between makes room) than
            # when a placement spills all and then refills all
            return
        assert list(shadow(nat).items()) == list(shadow(ora).items()), ctx
        # the host's records first, in their order, byte for byte; the
        # warm tier's follow in ITS table's order
        held = len(shadow(nat))
        assert a[:held] == b[:held], ctx
        for f in ("warm_spills", "warm_refills", "warm_dropped",
                  "warm_bytes_written"):
            assert getattr(nat, f) == getattr(ora, f), (ctx, f)
        for op, by in nat.shadow_records.items():
            assert by["dict"] == 0 and ora.shadow_records[op]["native"] == 0
            assert by["native"] == ora.shadow_records[op]["dict"], (ctx, op)
        if nat._warm is not None:
            nk = sorted(nat._warm.keys())
            assert nk == sorted(ora._warm.keys()), ctx
            for ip in nk:
                assert nat._warm.peek(ip) == ora._warm.peek(ip), (ctx, ip)

    def device(self):
        """The two device tables' live keys and their values."""
        out = []
        for w in self.both:
            st = w._state
            shape = (w.capacity, w.n_rules)
            valid = (np.asarray(st.key_gen).reshape(shape)
                     == np.asarray(st.slot_gen)[:, None])
            out.append((valid, *(np.where(valid, np.asarray(a).reshape(shape), 0)
                                 for a in (st.hits, st.start_s, st.start_ns))))
        for a, b in zip(*out):
            np.testing.assert_array_equal(a, b)
        return out[0]


def _hold(pair, ips, counters, t=T0):
    """Place `ips` and give each the counters {rule: hits} by an absorb."""
    slots = pair.place(ips)
    pair.absorb(slots, [(line, r, h, t + line, 7 * r)
                        for line in range(len(ips))
                        for r, h in sorted(counters.items())])
    pair.release(slots)
    return slots


# ------------------------------------------------------------- the fuzz


@pytest.mark.parametrize("warm,seed", [
    ("on", 1), ("on", 2), ("on", 3), ("off", 4), ("off", 5),
    ("tiny", 6), ("tiny", 7),
])
def test_random_batches_keep_both_forms_in_lockstep(warm, seed):
    """Random batches over a pool four times the table: applies, pins
    held across batches and released later (placements whose restores go
    stale), evictions that spill, returns that refill, puts a tiny tier
    drops.  After every step both forms answer alike."""
    rng = random.Random(seed)
    cap = 16
    pair = Pair(n_rules=3, capacity=cap, warm=warm,
                warm_capacity=4 if warm == "tiny" else 256, limit=5)
    pool = [ip_of(i) for i in range(cap * 4)]
    t = T0 * NS
    for step in range(120):
        ctx = f"step {step}"
        ips = rng.sample(pool, rng.randrange(1, cap - 2))
        slots = pair.place(ips, ctx)
        if slots is None:
            pair.check(ctx=ctx, same_homes=warm != "tiny")
            continue
        if rng.random() < 0.2:
            # placed and never applied: what it queued stays queued, and
            # the next placement may evict it before any maintenance
            pair.release(slots)
        else:
            bits = (np.random.RandomState(seed * 1000 + step)
                    .random((len(ips), 3)) < 0.6).astype(np.uint8)
            t += rng.randrange(1, 3 * NS)
            pair.apply(slots, bits, np.full(len(ips), t), ctx)
        pair.check(probe=rng.sample(pool, 6), ctx=ctx,
                   same_homes=warm != "tiny")
    pair.maintain()
    pair.device()
    nat = pair.nat
    assert nat.eviction_count > 100
    if warm == "off":
        assert nat.warm_spills == 0 and len(nat._shadow) > 0
    else:
        assert nat.warm_spills > 0 and nat.warm_refills > 0
        assert nat.shadow_records["restore"]["native"] > 0
    if warm == "tiny":
        assert nat.warm_dropped > 0 and len(nat._shadow) > 0
    assert nat.shadow_records["absorb"]["native"] == nat.device_events > 0


# ------------------------------------------------------ the named cases


def test_a_dropped_put_keeps_the_state():
    """A tier with no room drops the put: the record moves into the dict,
    keyed by its address, and comes back from there — counted by the
    tier, read by `get`, restored to the device."""
    pair = Pair(n_rules=2, capacity=2, warm_capacity=1)
    vec = {0: 3, 1: 4}
    for i in range(0, 12, 2):
        _hold(pair, [ip_of(i), ip_of(i + 1)], vec)
        pair.check(probe=[ip_of(j) for j in range(i + 2)], ctx=f"round {i}")
    nat = pair.nat
    assert nat.warm_dropped > 0 and len(nat._shadow) > 0
    # every record is somewhere, and says what was absorbed
    for j in range(12):
        got, found = nat.get(ip_of(j))
        assert found and [v.num_hits for v in got.values()] == [3, 4], j
    kept = next(iter(nat._shadow))           # one the tier dropped
    slots = pair.place([kept])
    assert kept not in nat._shadow and kept in shadow(nat)
    rows = pair.maintain()
    assert len(rows) == 1
    assert rows[0][2, :2].tolist() == [3, 4]           # its hits
    assert (rows[0][0, :2] == slots[0]).all()
    pair.release(slots)
    pair.check(probe=[kept])


@pytest.mark.parametrize("then", ["re-evicted", "another with state",
                                  "another without"])
def test_a_stale_restore_restores_nothing(then):
    """A restore queued for an address whose slot is evicted again —
    and perhaps given to another address — before the maintenance step
    runs must not scatter the old counters into the slot."""
    pair = Pair(n_rules=2, capacity=2)
    _hold(pair, [ip_of(0), ip_of(1)], {0: 5})
    _hold(pair, [ip_of(2), ip_of(3)], {1: 9})          # 0 and 1 spill
    s = pair.place([ip_of(0)])                         # refill: queued
    assert pending_restore_slots(pair.nat) == s.tolist()
    pair.release(s)                                    # never applied
    if then == "another with state":
        s2 = pair.place([ip_of(1), ip_of(3)])          # 1 takes 0's slot
    else:
        s2 = pair.place([ip_of(7), ip_of(3)])
    pair.release(s2)
    assert s2[0] == s[0]
    pair.check(probe=[ip_of(i) for i in range(4)])
    rows = pair.maintain()
    if then == "another with state":
        # two queued for the slot, one live: ip 1's counter, once
        assert len(pending_restore_slots(pair.nat)) == 0
        assert len(rows) == 1
        live = rows[0][:, rows[0][0] < 2]
        assert live.tolist() == [[s[0]], [s[0] * 2 + 0], [5], [T0 + 1], [0]]
    else:
        assert rows == []
    valid = pair.device()[0]
    assert valid[s[0]].tolist() == [then == "another with state", False]
    assert pair.nat.shadow_records["restore"]["native"] == (
        1 if then == "another with state" else 0)


def test_an_absorb_between_refill_and_maintenance_is_what_is_restored():
    """The rows are read at the maintenance step, not at the refill: an
    earlier chunk's events that land in between are in them, a counter
    the record did not have included."""
    pair = Pair(n_rules=3, capacity=2)
    _hold(pair, [ip_of(0), ip_of(1)], {0: 5})
    _hold(pair, [ip_of(2), ip_of(3)], {0: 1})
    s = pair.place([ip_of(0)])
    pair.absorb(s, [(0, 0, 6, T0 + 50, 1), (0, 2, 1, T0 + 50, 1)])
    rows = pair.maintain()
    live = rows[0][:, rows[0][0] < 2]
    assert live[1].tolist() == [s[0] * 3, s[0] * 3 + 2]
    assert live[2].tolist() == [6, 1] and live[3].tolist() == [T0 + 50] * 2
    pair.release(s)
    pair.check(probe=[ip_of(0)])


def test_with_the_warm_tier_off_evicted_records_wait_in_the_dict():
    pair = Pair(n_rules=2, capacity=2, warm="off")
    _hold(pair, [ip_of(0), ip_of(1)], {0: 2, 1: 3})
    _hold(pair, [ip_of(2), ip_of(3)], {1: 1})
    nat = pair.nat
    assert sorted(nat._shadow) == [ip_of(0), ip_of(1)] and len(nat._mirror) == 2
    pair.check(probe=[ip_of(i) for i in range(4)])
    s = pair.place([ip_of(1)])
    assert ip_of(1) not in nat._shadow and len(nat._mirror) == 2
    rows = pair.maintain()
    assert rows[0][2, :2].tolist() == [2, 3]
    pair.release(s)
    pair.check(probe=[ip_of(i) for i in range(4)])
    assert nat.shadow_records["spill"] == {"native": 0, "dict": 0}


def test_clear_empties_every_home():
    pair = Pair(n_rules=2, capacity=2)
    _hold(pair, [ip_of(0), ip_of(1)], {0: 2})
    _hold(pair, [ip_of(2), ip_of(3)], {1: 1})
    for w in pair.both:
        w.clear()
        assert len(w) == 0 and w.format_states() == "" and not shadow(w)
    assert len(pair.nat._mirror) == 0
    # an event of a chunk the clear overtook finds no owner and is dropped
    pair.absorb(np.zeros(1, np.int32), [(0, 0, 9, T0, 0)])
    pair.check()
    assert len(pair.nat) == 0
    _hold(pair, [ip_of(5)], {1: 4})
    pair.check(probe=[ip_of(5), ip_of(0)])


def test_growing_the_table_keeps_the_records(monkeypatch):
    monkeypatch.setattr(DeviceWindows, "AUTO_START_CAPACITY", 4)
    pair = Pair(n_rules=2, capacity=0)
    _hold(pair, [ip_of(i) for i in range(4)], {0: 1, 1: 2})
    _hold(pair, [ip_of(i) for i in range(4, 11)], {1: 7})   # 4 → 16 slots
    assert pair.nat.capacity == pair.ora.capacity == 16
    assert pair.nat.eviction_count == 0 and len(pair.nat._mirror) == 11
    pair.check(probe=[ip_of(i) for i in range(11)])
    assert pair.nat.get(ip_of(10))[0]["r1"].num_hits == 7


@pytest.mark.parametrize("n_counters", [10, 11, 25, 101])
def test_a_record_of_more_counters_than_one_block(n_counters):
    """Ten counters a block: records that fill one, pass it by one, and
    take eleven, through absorb, spill, refill and restore — counters in
    first-event order, an update in place."""
    pair = Pair(n_rules=128, capacity=2)
    order = random.Random(n_counters).sample(range(128), n_counters)
    s = pair.place([ip_of(0), ip_of(1)])
    for k, r in enumerate(order):          # one counter a chunk, any order
        pair.absorb(s, [(0, r, k + 1, T0, r)])
    pair.absorb(s, [(0, order[0], 77, T0 + 1, 0)])       # an update
    pair.release(s)
    got = list(shadow(pair.nat)[ip_of(0)].items())
    assert [r for r, _ in got] == order and got[0][1] == (77, T0 + 1, 0)
    pair.check(probe=[ip_of(0)])
    _hold(pair, [ip_of(2), ip_of(3)], {0: 1})            # spills it
    assert [e[0] for e in pair.nat._warm.peek(ip_of(0))] == order
    assert pair.nat.warm_bytes_written == shm.wt_record_bytes(n_counters)
    pair.check(probe=[ip_of(0)])
    s = pair.place([ip_of(0)])
    rows = pair.maintain()
    assert len(rows) == 1
    assert rows[0][1, :n_counters].tolist() == [s[0] * 128 + r for r in order]
    assert (rows[0][0, n_counters:] == 2).all()          # the pads
    pair.release(s)
    pair.check(probe=[ip_of(0)])


def test_ten_thousand_rules():
    """The rule axis at the upstream stress test's size: a counter costs
    24 B wherever it lives, the flat key reaches the table's end, and a
    restore of more keys than one chunk goes in two."""
    pair = Pair(n_rules=10_000, capacity=4, warm_capacity=64)
    rids = [9_999, 0, 5_000] + list(range(100, 1_300))
    s = pair.place([ip_of(0), ip_of(1), ip_of(2), ip_of(3)])
    pair.absorb(s, sorted((3, r, 1 + r % 7, T0 + r, r) for r in rids))
    pair.absorb(s, [(1, 9_999, 2, T0, 0)])
    pair.release(s)
    pair.check(probe=[ip_of(3), ip_of(1)])
    s2 = pair.place([ip_of(k) for k in range(4, 8)])     # all four spill
    pair.release(s2)
    assert pair.nat.warm_spills == 2
    assert pair.nat.warm_bytes_written == (
        shm.wt_record_bytes(len(rids)) + shm.wt_record_bytes(1))
    pair.check(probe=[ip_of(3), ip_of(1)])
    s3 = pair.place([ip_of(1), ip_of(3)])
    rows = pair.maintain()
    assert [r.shape for r in rows] == [(5, W._RESTORE_CHUNK)] * 2
    keys = np.concatenate([r[1] for r in rows])[: 1 + len(rids)]
    assert keys[0] == s3[0] * 10_000 + 9_999
    assert sorted(keys[1:].tolist()) == sorted(s3[1] * 10_000 + r for r in rids)
    pair.release(s3)
    pair.check(probe=[ip_of(3), ip_of(1)])
    assert pair.device()[0].sum() == 1 + len(rids)


def test_format_states_keeps_first_event_order_across_the_homes():
    """Addresses by the first event of their record (a refill makes a
    new record, so it goes last), counters by their first event — the
    dict form has it from insertion, the mirror from a stamp sorted at
    the read."""
    pair = Pair(n_rules=3, capacity=3, warm="off")
    s = pair.place([ip_of(0), ip_of(1), ip_of(2)])
    pair.absorb(s, [(2, 1, 1, T0, 0), (0, 2, 1, T0, 0), (2, 0, 1, T0, 0)])
    pair.absorb(s, [(0, 0, 1, T0, 0), (1, 1, 1, T0, 0), (2, 1, 5, T0, 0)])
    pair.release(s)
    assert [(ip, list(od)) for ip, od in shadow(pair.nat).items()] == [
        (ip_of(2), [1, 0]), (ip_of(0), [2, 0]), (ip_of(1), [1])]
    s = pair.place([ip_of(3)])          # evicts ip 0: it waits in the dict
    pair.absorb(s, [(0, 0, 1, T0, 0)])
    pair.release(s)
    assert list(shadow(pair.nat)) == [ip_of(2), ip_of(0), ip_of(1), ip_of(3)]
    pair.check(probe=[ip_of(i) for i in range(4)])
    s = pair.place([ip_of(0)])          # back from the dict: its old place
    pair.release(s)
    assert list(shadow(pair.nat)) == [ip_of(0), ip_of(1), ip_of(3)] or \
        list(shadow(pair.nat)) == [ip_of(2), ip_of(0), ip_of(1), ip_of(3)]
    pair.check(probe=[ip_of(i) for i in range(4)])
    text = pair.nat.format_states()
    assert text.index(ip_of(0) + ":") < text.index(ip_of(1) + ":")


def test_get_reads_a_record_in_any_of_its_three_homes():
    pair = Pair(n_rules=2, capacity=2, warm_capacity=1)
    for i in range(0, 6, 2):
        _hold(pair, [ip_of(i), ip_of(i + 1)], {0: i + 1})
    nat = pair.nat
    homes = {"mirror": [ip for ip in map(ip_of, range(6))
                        if ip in nat.slot_addresses().values()],
             "dict": list(nat._shadow), "warm": nat._warm.keys()}
    assert all(homes.values()) and sum(map(len, homes.values())) == 6
    for i in range(6):
        got, found = nat.get(ip_of(i))
        assert found and got["r0"].num_hits == i - i % 2 + 1
    assert nat.get("203.0.113.9") == ({}, False)
    pair.check(probe=[ip_of(i) for i in range(6)])


def test_a_sketch_admitted_tenure_is_judged_by_whether_the_slot_holds_a_record():
    pair = Pair(n_rules=2, capacity=2)
    s = pair.place([ip_of(0), ip_of(1)])
    for w in pair.both:
        w._sketch_slots.update({int(s[0]): True, int(s[1]): True})
    pair.absorb(s, [(0, 1, 1, T0, 0)])       # ip 0 matched, ip 1 never
    pair.release(s)
    pair.release(pair.place([ip_of(2), ip_of(3)]))
    pair.check()
    assert (pair.nat.sketch_fp_evaluated, pair.nat.sketch_fp_count) == (2, 1)


# ------------------------------- the stream end to end, on both forms


RULES = [
    # default-edge's three (benchmark/configs/default-edge.json): two
    # anchored literals that fire on 95 lines in 100 and one rare one.
    # The limits are cut to what 6,000 lines from 1,200 addresses can
    # cross ACROSS evictions of a 512-slot table: 800/30 s and 45/60 s
    # are crossed only by the head of the draw, which is never evicted
    {"rule": "All GET requests", "regex": "^GET", "interval": 30,
     "hits_per_interval": 6, "decision": "nginx_block"},
    {"rule": "POST flood", "regex": "^POST", "interval": 60,
     "hits_per_interval": 2, "decision": "iptables_block"},
    {"rule": "instant challenge (demo)", "regex": ".*challengeme.*",
     "interval": 1, "hits_per_interval": 0, "decision": "challenge",
     "_attack": {"method": "GET|POST", "path": "/%s/challengeme/%s"}},
]
COMPARED = ("ban_records_missing", "ban_records_extra", "ips_out_of_order",
            "ban_keys_differing")


@pytest.fixture(scope="module")
def rehearsal():
    """→ (rules, log, now, want): the cell's rehearsal stream (its
    generator, its traffic file's `rehearse` block) and the plain
    reference's ban log for it."""
    from benchmark.harness import cellrun, found, genproc, reference, stream

    traffic = cellrun.overlay(found.data("traffic", "flood-dense"),
                              found.data("traffic", "flood-dense")["rehearse"])
    seed = 3838
    rests, n_benign, _ = genproc.build_pools(RULES, traffic, seed)
    strm = stream.Stream(traffic, n_benign, len(rests) - n_benign, seed)
    ips, ridx = strm.block(0)
    now = time.time()
    log = [f"{now - 4 + i * 5e-4:.6f} {ip} {rests[r]}"
           for i, (ip, r) in enumerate(zip(ips[:6144], ridx[:6144]))]
    want = [json.dumps({"client_ip": d["client_ip"], "trigger": d["trigger"],
                        "action": d["action"]})
            for d in map(json.loads, reference.run(
                RULES, log, lambda ip: True, procs=1)["bans"])]
    assert len(want) > 100
    return RULES, log, now, want


def _through_the_pipeline(rehearsal, native):
    from benchmark.harness import found, reference
    from tests.unit.test_multisite import _Banner

    import yaml

    rules, log, now, want = rehearsal
    cfg = config_from_yaml_text(yaml.safe_dump(
        {"regexes_with_rates": found.product_rules(rules)}))
    cfg.matcher_device_windows = True
    cfg.matcher_window_capacity = 512
    cfg.matcher_batch_lines = 256
    cfg.matcher_max_line_len = 256
    cfg.warm_tier_enabled = True
    cfg.warm_tier_capacity = 4096
    cfg.slotmgr_native = native
    m = TpuMatcher(cfg, _Banner(), StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    dw = m.device_windows
    assert m._fw_pipeline is not None and dw.slotmgr_native == native
    assert (dw._mirror is not None) == native
    sched = PipelineScheduler(lambda: m, max_batch=256, now_fn=lambda: now)
    sched.start()
    for i in range(0, len(log), 256):
        sched.submit(log[i:i + 256])
    assert sched.flush(300)
    sched.stop()
    got = [json.dumps({**d, "action": reference.DECISION_STRING[
        d["action"].lower()]}) for d in map(json.loads, m.banner.regex_ban_logs)]
    return m, reference.compare(got, want)


@pytest.mark.parametrize("native", [True, False], ids=["mirror", "dict"])
def test_default_edge_stream_equals_the_reference(rehearsal, native):
    m, cmp_ = _through_the_pipeline(rehearsal, native)
    assert {k: cmp_[k] for k in COMPARED} == dict.fromkeys(COMPARED, 0)
    assert m.pipelined_fused_chunks >= 20 and m.pipelined_fused_fallbacks == 0
    dw = m.device_windows
    mine, other = ("native", "dict") if native else ("dict", "native")
    rec = dw.shadow_records
    assert all(by[other] == 0 for by in rec.values())
    # 95 lines in 100 are a window event, and each went through the form
    assert rec["absorb"][mine] == dw.device_events > 0.9 * 6144
    assert rec["spill"][mine] == dw.warm_spills > 500
    assert rec["refill"][mine] == dw.warm_refills > 100
    assert 0 < rec["restore"][mine] <= dw.warm_refills
    m.close()


@pytest.mark.parametrize("native", [True, False], ids=["mirror", "dict"])
def test_bans_of_the_stream_rest_on_refills(rehearsal, native, monkeypatch):
    """The control: returning addresses find their records thrown away
    (the fault `benchmark/checks/test_broken_path.py` plants, at the
    point where each form refills) — ban records go missing, so the run
    above had bans whose counters crossed an eviction."""
    lost = {"n": 0}
    if native:
        real = shm.ShadowMirror.refill

        def forgetful(self, warm, slots, spans):
            got = real(self, warm, slots, spans)
            lost["n"] += int(np.count_nonzero(got))
            self.export(slots, drop=True)
            return np.zeros_like(got)

        monkeypatch.setattr(shm.ShadowMirror, "refill", forgetful)
    else:
        real = shm.ShmWarmTier.take

        def forgetful(self, ip):
            lost["n"] += real(self, ip) is not None
            return None

        monkeypatch.setattr(shm.ShmWarmTier, "take", forgetful)
    m, cmp_ = _through_the_pipeline(rehearsal, native)
    assert lost["n"] > 100
    assert cmp_["ban_records_missing"] > 0
    m.close()
