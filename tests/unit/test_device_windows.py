"""DeviceWindows vs RegexRateLimitStates differential (SURVEY.md §4 carry-over
(d): generalize the reference's generative stress test into a byte-identical
harness for the device path — here for the window counters of
/root/reference/internal/rate_limit.go:37-78)."""

import functools
import random
import re

import numpy as np
import pytest

from banjax_tpu.config.schema import RegexWithRate, Decision
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.matcher.windows import DeviceWindows, split_ns

NS = 1_000_000_000


def make_rule(name: str, interval_s: float, hits: int) -> RegexWithRate:
    return RegexWithRate(
        rule=name,
        regex_string="x",
        regex=re.compile("x"),
        interval_ns=int(interval_s * NS),
        hits_per_interval=hits,
        decision=Decision.NGINX_BLOCK,
    )


def drive_oracle(rules, batches):
    """Replay (ip, rule_id, ts_ns) events through the host-semantics class."""
    states = RegexRateLimitStates()
    out = []
    for bits, ips, ts in batches:
        for line in range(bits.shape[0]):
            for rid in range(bits.shape[1]):
                if not bits[line, rid]:
                    continue
                seen, res = states.apply(ips[line], rules[rid], int(ts[line]))
                out.append((line, rid, int(res.match_type), res.exceeded, seen))
    return states, out


def drive_device(rules, batches, capacity=64, max_events=512, **kw):
    dw = DeviceWindows(rules, capacity=capacity, max_events=max_events,
                       **kw)
    active = np.ones((1, len(rules)), dtype=bool)
    out = []

    def apply(bits, ips, ts, base=0):
        """Mirror the runner: split when allocation refuses (more distinct
        IPs than free+evictable slots in one batch)."""
        slots = dw.slots_for_ips(ips)
        if slots is None:
            assert len(ips) > 1, "single line must always fit"
            mid = len(ips) // 2
            return (apply(bits[:mid], ips[:mid], ts[:mid], base)
                    + apply(bits[mid:], ips[mid:], ts[mid:], base + mid))
        ts_s, ts_ns = split_ns(ts)
        events = dw.apply_bitmap(
            bits, slots, ts_s, ts_ns, active,
            np.zeros(len(ips), dtype=np.int32),
        )
        return [
            (e.line + base, e.rule_id, int(e.match_type), e.exceeded, e.seen_ip)
            for e in events
        ]

    for bits, ips, ts in batches:
        out.extend(apply(bits, ips, ts))
    return dw, out


def random_batches(rng, n_rules, n_ips, n_batches, batch, density=0.2,
                   base_ns=1_700_000_000 * NS):
    ips = [f"10.0.0.{i}" for i in range(n_ips)]
    t = base_ns
    batches = []
    for _ in range(n_batches):
        bits = (rng.random((batch, n_rules)) < density).astype(np.uint8)
        ip_col = [ips[rng.integers(0, n_ips)] for _ in range(batch)]
        ts = []
        for _ in range(batch):
            t += rng.integers(0, 2 * NS)  # 0..2s steps, ns granularity
            ts.append(t)
        batches.append((bits, ip_col, np.array(ts, dtype=np.int64)))
    return batches


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_differential_random(seed):
    rng = np.random.default_rng(seed)
    rules = [
        make_rule("fast", 1.0, 2),
        make_rule("slow", 30.0, 5),
        make_rule("zero", 0.5, 0),   # hits_per_interval 0: every hit exceeds
        make_rule("wide", 300.0, 3),
    ]
    batches = random_batches(rng, len(rules), n_ips=6, n_batches=4, batch=40)
    states, want = drive_oracle(rules, batches)
    dw, got = drive_device(rules, batches)
    assert got == want

    # final counter state identical per (ip, rule)
    for i in range(6):
        ip = f"10.0.0.{i}"
        host_states, host_ok = states.get(ip)
        dev_states, dev_ok = dw.get(ip)
        assert host_ok == dev_ok
        assert set(host_states) == set(dev_states)
        for rule, s in host_states.items():
            d = dev_states[rule]
            assert (s.num_hits, s.interval_start_time_ns) == (
                d.num_hits, d.interval_start_time_ns
            ), (ip, rule)


def test_window_restart_and_reset_quirk():
    """Window restarts strictly after interval; exceed resets hits to 0."""
    rules = [make_rule("r", 10.0, 2)]
    base = 1_700_000_000 * NS
    one = np.ones((1, 1), dtype=np.uint8)
    # 4 hits inside one window: 1,2,3>2 → exceeded, reset to 0; then 1
    ts_list = [base, base + 1 * NS, base + 2 * NS, base + 3 * NS,
               # exactly interval later than start: NOT outside (strict >)
               base + 10 * NS,
               # strictly beyond: restart
               base + 10 * NS + 1]
    batches = [(one, ["1.2.3.4"], np.array([t], dtype=np.int64)) for t in ts_list]
    _, want = drive_oracle(rules, batches)
    _, got = drive_device(rules, batches)
    assert got == want
    exceeded_seq = [e[3] for e in got]
    assert exceeded_seq == [False, False, True, False, False, False]


def test_active_table_masks_events():
    """Per-host applicability: masked rules produce no events or state."""
    rules = [make_rule("a", 5.0, 1), make_rule("b", 5.0, 1)]
    dw = DeviceWindows(rules, capacity=8)
    active = np.array([[True, False], [True, True]])  # host 0 masks rule b
    bits = np.ones((2, 2), dtype=np.uint8)
    ts = np.array([1_700_000_000 * NS, 1_700_000_000 * NS + 1], dtype=np.int64)
    slots = dw.slots_for_ips(["a.a", "b.b"])
    ts_s, ts_ns = split_ns(ts)
    events = dw.apply_bitmap(
        bits, slots, ts_s, ts_ns, active, np.array([0, 1], dtype=np.int32)
    )
    assert [(e.line, e.rule_id) for e in events] == [(0, 0), (1, 0), (1, 1)]
    states, ok = dw.get("a.a")
    assert ok and set(states) == {"a"}


def test_overflow_splits_batch():
    """More events than max_events → recursive halving, same results."""
    rules = [make_rule("r", 10.0, 3)]
    batches_rng = np.random.default_rng(7)
    batches = random_batches(batches_rng, 1, n_ips=3, n_batches=2, batch=64,
                             density=1.0)
    _, want = drive_oracle(rules, batches)
    _, got = drive_device(rules, batches, capacity=16, max_events=16)
    assert got == want


def test_eviction_spills_and_restores():
    """LRU eviction spills counters to the host shadow; re-admission
    restores them, so state is NEVER forgotten (rate_limit.go:37-78 — the
    reference host dict never forgets; VERDICT r2 weak #5)."""
    rules = [make_rule("r", 10.0, 100)]
    dw = DeviceWindows(rules, capacity=2)
    one = np.ones((1, 1), dtype=np.uint8)
    active = np.ones((1, 1), dtype=bool)
    base = 1_700_000_000 * NS

    def hit(ip, t):
        slots = dw.slots_for_ips([ip])
        ts_s, ts_ns = split_ns(np.array([t], dtype=np.int64))
        ev = dw.apply_bitmap(one, slots, ts_s, ts_ns, active,
                             np.zeros(1, dtype=np.int32))
        return ev[0]

    hit("ip-a", base)
    hit("ip-a", base + 1)
    hit("ip-b", base + 2)
    e = hit("ip-c", base + 3)       # evicts ip-a (LRU)
    assert e.seen_ip is False       # ip-c itself is genuinely new
    assert dw.eviction_count == 1
    states, ok = dw.get("ip-a")
    assert ok and states["r"].num_hits == 2  # spilled, not forgotten
    e = hit("ip-a", base + 4)        # evicts ip-b; ip-a RESTORES
    assert e.seen_ip is True
    assert int(e.match_type) == 2    # INSIDE_INTERVAL: the window survived
    states, ok = dw.get("ip-a")
    assert ok and states["r"].num_hits == 3
    # ip-b's counters also survived its eviction
    states, ok = dw.get("ip-b")
    assert ok and states["r"].num_hits == 1
    assert len(dw) == 3              # every IP with state counts


def test_batch_slot_pinning():
    """slots_for_ips never evicts a slot pinned by the same batch (the
    within-batch reuse would merge two IPs' counters into one key), and the
    TpuMatcher recovers by splitting the batch — here we check the refusal."""
    rules = [make_rule("r", 10.0, 100)]
    dw = DeviceWindows(rules, capacity=2)
    assert dw.slots_for_ips(["a", "b", "c"]) is None  # 3 distinct IPs, 2 slots
    slots = dw.slots_for_ips(["a", "b", "a", "b"])    # repeats are fine
    assert slots is not None and slots[0] == slots[2] and slots[1] == slots[3]


def test_capacity_overflow_batch_splits_identically():
    """End-to-end: more distinct IPs than capacity still matches the oracle
    (the TpuMatcher splits work; here we emulate by per-line batches)."""
    rules = [make_rule("r", 10.0, 2)]
    rng = np.random.default_rng(3)
    batches = random_batches(rng, 1, n_ips=10, n_batches=1, batch=50, density=0.9)
    # split each 50-line batch into per-line batches for the 4-slot device
    bits, ips, ts = batches[0]
    per_line = [
        (bits[i : i + 1], [ips[i]], ts[i : i + 1]) for i in range(len(ips))
    ]
    _, want = drive_oracle(rules, per_line)
    _, got = drive_device(rules, per_line, capacity=4)
    # spill/restore makes eviction lossless: FULL equality with the host
    # oracle even at 10 IPs > 4 slots (VERDICT r2 item 6: no excluded fields)
    assert got == want


def test_stale_restore_does_not_resurrect_into_new_owner():
    """A restore queued for (slot, ip) must be dropped if the slot has been
    re-evicted and handed to a DIFFERENT ip before maintenance ran —
    otherwise an innocent new IP inherits the old IP's counters."""
    rules = [make_rule("r", 30.0, 100)]
    dw = DeviceWindows(rules, capacity=2)
    one = np.ones((1, 1), dtype=np.uint8)
    active = np.ones((1, 1), dtype=bool)
    base = 1_700_000_000 * NS

    def hit(ip, t):
        slots = dw.slots_for_ips([ip])
        ts_s, ts_ns = split_ns(np.array([t], dtype=np.int64))
        return dw.apply_bitmap(one, slots, ts_s, ts_ns, active,
                               np.zeros(1, dtype=np.int32))[0]

    hit("X", base)
    hit("X", base + 1)          # X: 2 hits
    hit("Y", base + 2)
    hit("Z", base + 3)          # evicts X
    # X re-admitted by a lookup that never reaches apply_bitmap (the
    # runner's pre-handoff failure path): restore stays queued
    slots = dw.slots_for_ips(["X"])   # evicts Y, queues restore for X
    dw.release_pins(slots)
    hit("Z", base + 4)          # Z most recent; X is LRU again
    e = hit("A", base + 5)      # evicts X; A takes X's old slot
    assert e.seen_ip is False and int(e.match_type) == 0, (
        "new IP must not inherit the evicted IP's restored counters"
    )
    states, ok = dw.get("A")
    assert ok and states["r"].num_hits == 1
    # X's state is still intact in the shadow for ITS next admission
    states, ok = dw.get("X")
    assert ok and states["r"].num_hits == 2


@pytest.mark.parametrize("seed", [11, 12])
def test_eviction_churn_differential(seed):
    """Sustained rotation through many more IPs than slots — heavy
    evict/spill/restore churn — still matches the host oracle exactly."""
    rules = [make_rule("fast", 5.0, 2), make_rule("slow", 60.0, 4)]
    rng = np.random.default_rng(seed)
    batches = random_batches(rng, 2, n_ips=24, n_batches=6, batch=16,
                             density=0.5)
    _, want = drive_oracle(rules, batches)
    dw, got = drive_device(rules, batches, capacity=8)
    assert dw.eviction_count > 0, "test must actually exercise eviction"
    assert got == want


def test_varying_batch_sizes_share_one_compile():
    """apply_bitmap buckets B to powers of two before the jitted step, so
    traffic with varying batch sizes must not grow the jit cache per size
    (ADVICE r1: unbounded recompiles of the segmented-scan program)."""
    from banjax_tpu.matcher import windows as W

    rules = [make_rule("r", 10.0, 100)]
    dw = DeviceWindows(rules, capacity=64)
    active = np.ones((1, 1), dtype=bool)
    base = 1_700_000_000 * NS
    # whatever an earlier test of this process built: counted from none
    W._apply_step.clear_cache()
    count_before = W._apply_step._cache_size()
    for i, b in enumerate([1, 3, 5, 17, 33, 63, 64]):  # all bucket to 64
        bits = np.ones((b, 1), dtype=np.uint8)
        ips = [f"9.9.{i}.{j}" for j in range(b)]
        slots = dw.slots_for_ips(ips)
        ts = np.arange(b, dtype=np.int64) + base + i * NS
        ts_s, ts_ns = split_ns(ts)
        events = dw.apply_bitmap(
            bits, slots, ts_s, ts_ns, active, np.zeros(b, dtype=np.int32)
        )
        assert len(events) == b
        assert all(0 <= e.line < b for e in events)
    assert W._apply_step._cache_size() - count_before == 1


def test_in_flight_slots_not_evicted_and_pins_release():
    """Slots assigned by slots_for_ips stay pinned (unevictable) until their
    apply_bitmap runs, then the pins release so eviction works again."""
    rules = [make_rule("r", 10.0, 100)]
    dw = DeviceWindows(rules, capacity=2)
    active = np.ones((1, 1), dtype=bool)
    base = 1_700_000_000 * NS

    slots_ab = dw.slots_for_ips(["a", "b"])  # fills capacity, pins both
    assert dw.slots_for_ips(["c"]) is None   # nothing evictable while pinned
    assert dw.eviction_count == 0

    ts_s, ts_ns = split_ns(np.array([base, base + 1], dtype=np.int64))
    dw.apply_bitmap(np.ones((2, 1), dtype=np.uint8), slots_ab, ts_s, ts_ns,
                    active, np.zeros(2, dtype=np.int32))
    slots_c = dw.slots_for_ips(["c"])        # pins released → LRU evictable
    assert slots_c is not None
    assert dw.eviction_count == 1


def test_auto_grow_absorbs_distinct_ip_pressure():
    """capacity=0 (auto-size): the slot table doubles on pressure instead
    of evicting, existing counters and slot ids survive the growth, and
    the ceiling still evicts (VERDICT r3 item 4)."""
    rules = [make_rule("r", 10.0, 100)]
    dw = DeviceWindows(rules, capacity=0)
    assert dw.auto_grow and dw.capacity == dw.AUTO_START_CAPACITY
    # shrink the knobs so the test exercises growth cheaply
    dw.capacity = 2
    dw.max_capacity = 4
    dw._free = [1, 0]
    dw._pin_counts = np.zeros(2, dtype=np.int32)
    dw._last_used = np.zeros(2, dtype=np.int64)
    dw._state = dw._fresh_state()
    if dw._sm is not None:  # rebuild the native manager at the shrunk size
        from banjax_tpu.native import slotmgr as _slotmgr

        dw._sm.close()
        dw._sm = _slotmgr.create(2)
    one = np.ones((1, 1), dtype=np.uint8)
    active = np.ones((1, 1), dtype=bool)
    base = 1_700_000_000 * NS

    def hit(ip, t):
        slots = dw.slots_for_ips([ip])
        ts_s, ts_ns = split_ns(np.array([t], dtype=np.int64))
        dw.apply_bitmap(one, slots, ts_s, ts_ns, active,
                        np.zeros(1, dtype=np.int32))

    hit("ip-a", base)
    hit("ip-b", base + 1)
    hit("ip-c", base + 2)            # pressure → grow 2→4, NOT evict
    assert dw.grow_count == 1 and dw.capacity == 4
    assert dw.eviction_count == 0
    hit("ip-d", base + 3)
    # earlier counters survived the growth in place (no spill/restore)
    states, ok = dw.get("ip-a")
    assert ok and states["r"].num_hits == 1
    hit("ip-a", base + 4)
    states, ok = dw.get("ip-a")
    assert ok and states["r"].num_hits == 2
    # at the ceiling the LRU spill path takes over
    hit("ip-e", base + 5)
    assert dw.capacity == 4 and dw.eviction_count == 1
    assert len(dw) == 5


def test_concurrent_consume_reload_metrics_soak():
    """Race-detection soak (SURVEY.md §5): consume_lines on one thread,
    static-list hot reloads (allow-cache invalidation) and metrics
    snapshots on others. No exceptions, no torn state, and the allowlist
    flip must take effect on the batch after the reload."""
    import threading
    import time as _time

    import yaml as _yaml

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from banjax_tpu.obs.stats import MatcherStats  # noqa: F401 — via matcher
    from tests.mock_banner import MockBanner

    base = {
        "regexes_with_rates": [
            {"rule": "hit", "regex": ".*attackpath.*", "interval": 60,
             "hits_per_interval": 2, "decision": "nginx_block"},
        ],
    }
    cfg = config_from_yaml_text(_yaml.safe_dump(base))
    cfg.matcher_device_windows = True
    cfg.matcher_batch_lines = 256
    sl = StaticDecisionLists(cfg)
    m = TpuMatcher(cfg, MockBanner(), sl, RegexRateLimitStates())
    now = _time.time()
    lines = [
        f"{now:.6f} 10.1.{i % 16}.{i % 7} GET h.com GET "
        f"/{'attackpath' if i % 9 == 0 else 'ok'}{i} HTTP/1.1 UA -"
        for i in range(512)
    ]
    errors = []
    stop = threading.Event()

    def reloader():
        flip = False
        while not stop.is_set():
            try:
                alt = dict(base)
                if flip:
                    alt = {**base, "global_decision_lists": {
                        "allow": ["10.1.0.0", "10.1.1.1"]}}
                sl.update_from_config(
                    config_from_yaml_text(_yaml.safe_dump(alt))
                )
                flip = not flip
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            _time.sleep(0.002)

    def metrics():
        while not stop.is_set():
            try:
                m.stats.snapshot(m.device_windows, m)
                m.device_windows.occupancy
                len(m.device_windows)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            _time.sleep(0.001)

    threads = [threading.Thread(target=reloader),
               threading.Thread(target=metrics)]
    for t in threads:
        t.start()
    try:
        for _ in range(30):
            m.consume_lines(lines, now)
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors, errors[:3]

    # determinism epilogue: with the allow list pinned ON, the flip must
    # be visible immediately (generation-keyed cache)
    sl.update_from_config(config_from_yaml_text(_yaml.safe_dump(
        {**base, "global_decision_lists": {"allow": ["10.1.2.2"]}}
    )))
    r = m.consume_lines(
        [f"{now:.6f} 10.1.2.2 GET h.com GET /attackpathZ HTTP/1.1 UA -"],
        now,
    )[0]
    assert r.exempted


# ---------------------------------------------------------------- warm tier


def test_warm_tier_round_trip_byte_identical():
    """Eviction spill into the warm tier and re-admission refill carry
    the per-rule (num_hits, interval_start) vectors BYTE-identically —
    the ISSUE 14 lossless-spill contract, asserted on the raw entry
    tuples, not just on continued-counting behavior."""
    rules = [make_rule("fast", 5.0, 100), make_rule("slow", 60.0, 100)]
    dw = DeviceWindows(rules, capacity=2, warm_tier_enabled=True,
                       warm_tier_capacity=64)
    assert dw._warm is not None
    active = np.ones((1, 2), dtype=bool)
    base = 1_700_000_000 * NS + 123_456_789  # odd ns: both words matter

    def hit(ip, t, bits):
        slots = dw.slots_for_ips([ip])
        ts_s, ts_ns = split_ns(np.array([t], dtype=np.int64))
        return dw.apply_bitmap(
            np.array([bits], dtype=np.uint8), slots, ts_s, ts_ns,
            active, np.zeros(1, dtype=np.int32),
        )

    hit("ip-a", base, [1, 1])
    hit("ip-a", base + 7, [1, 0])      # fast=2, slow=1, starts at base
    hit("ip-b", base + 8, [0, 1])
    snap = {r: (s.num_hits, s.interval_start_time_ns)
            for r, s in dw.get("ip-a")[0].items()}
    assert snap == {"fast": (2, base), "slow": (1, base)}

    hit("ip-c", base + 9, [1, 0])      # evicts ip-a -> SPILL to warm
    assert dw.warm_spills == 1
    assert dw.warm_occupancy == 1
    ent = dw._warm.peek("ip-a")
    assert ent is not None
    got = {rules[rid].rule: (h, s * NS + ns) for rid, h, s, ns in ent}
    assert got == snap                  # the raw spilled vectors
    assert "ip-a" not in dw.shadow_items()     # warm is the home, not a copy

    hit("ip-a", base + 10, [1, 1])     # returns -> REFILL from warm;
    #                                    its slot claim evicts ip-b,
    #                                    which spills in turn
    assert dw.warm_refills == 1
    assert dw.warm_spills == 2
    assert dw.warm_occupancy == 1       # take(), not a copy: only ip-b
    assert dw._warm.peek("ip-a") is None
    assert dw._warm.peek("ip-b") is not None
    after = {r: (s.num_hits, s.interval_start_time_ns)
             for r, s in dw.get("ip-a")[0].items()}
    assert after == {"fast": (3, base), "slow": (2, base)}


@pytest.mark.parametrize("seed", [3, 4])
def test_warm_tier_churn_differential(seed):
    """The eviction-churn differential with the warm tier as the spill
    home: event streams and final per-(ip, rule) state still match the
    host oracle exactly, and the run actually spilled and refilled."""
    rules = [make_rule("fast", 5.0, 2), make_rule("slow", 60.0, 4)]
    rng = np.random.default_rng(seed)
    batches = random_batches(rng, 2, n_ips=24, n_batches=6, batch=16,
                             density=0.5)
    states, want = drive_oracle(rules, batches)
    dw, got = drive_device(rules, batches, capacity=8,
                           warm_tier_enabled=True, warm_tier_capacity=64)
    assert dw.eviction_count > 0
    assert dw.warm_spills > 0, "churn never spilled into the warm tier"
    assert dw.warm_refills > 0, "no returning IP ever refilled"
    assert got == want
    for i in range(24):
        ip = f"10.0.0.{i}"
        host_states, host_ok = states.get(ip)
        dev_states, dev_ok = dw.get(ip)
        assert host_ok == dev_ok, ip
        for rule, s in host_states.items():
            d = dev_states[rule]
            assert (s.num_hits, s.interval_start_time_ns) == (
                d.num_hits, d.interval_start_time_ns
            ), (ip, rule)


def test_warm_tier_drop_keeps_shadow_entry():
    """When the warm tier cannot place a spill (probe window full of
    live records), the shadow KEEPS the entry — pre-tiering lossless
    behavior — and the tier's dropped counter surfaces the pressure."""
    rules = [make_rule("r", 600.0, 100)]  # wide window: no expiry steals
    dw = DeviceWindows(rules, capacity=2, warm_tier_enabled=True,
                       warm_tier_capacity=1)  # tiny tier: drops fast
    active = np.ones((1, 1), dtype=bool)
    one = np.ones((1, 1), dtype=np.uint8)
    base = 1_700_000_000 * NS

    def hit(ip, t):
        slots = dw.slots_for_ips([ip])
        ts_s, ts_ns = split_ns(np.array([t], dtype=np.int64))
        dw.apply_bitmap(one, slots, ts_s, ts_ns, active,
                        np.zeros(1, dtype=np.int32))

    n = 12
    for i in range(n):  # constant churn: every placement evicts
        hit(f"ip-{i}", base + i)
    spilled_or_kept = 0
    for i in range(n - 2):  # the last 2 are hot-resident
        states, ok = dw.get(f"ip-{i}")
        assert ok and states["r"].num_hits == 1, f"ip-{i} state lost"
        spilled_or_kept += 1
    assert spilled_or_kept == n - 2
    assert dw.warm_dropped > 0, "tiny tier never reported drop pressure"
    # every dropped spill fell back to the shadow (lossless)
    assert dw.warm_spills + len(dw.shadow_items()) >= n - 2


# ------------------------------------------------- validity by generation


def _device_view(dw):
    """→ (valid, hits) as [capacity, n_rules] host arrays: a key holds
    state iff its generation equals its slot's."""
    st = dw._state
    shape = (dw.capacity, dw.n_rules)
    key_gen = np.asarray(st.key_gen).reshape(shape)
    slot_gen = np.asarray(st.slot_gen)
    return key_gen == slot_gen[:, None], np.asarray(st.hits).reshape(shape)


def _hitter(dw, n_rules):
    active = np.ones((1, n_rules), dtype=bool)

    def hit(ip, t, bits):
        slots = dw.slots_for_ips([ip])
        ts_s, ts_ns = split_ns(np.array([t], dtype=np.int64))
        return dw.apply_bitmap(
            np.array([bits], dtype=np.uint8), slots, ts_s, ts_ns,
            active, np.zeros(1, dtype=np.int32),
        )

    return hit


@pytest.mark.parametrize("n_rules", [3, 1000])
def test_maintenance_operands_do_not_scale_with_rules(n_rules, monkeypatch):
    """Evicting K slots hands the device K-sized operands whatever the
    rule count: one generation bump per slot, no per-(slot, rule) keys."""
    from banjax_tpu.matcher import windows as W

    rules = [make_rule(f"r{i}", 30.0, 100) for i in range(n_rules)]
    cap, k_evict = 512, 300
    dw = DeviceWindows(rules, capacity=cap)
    active = np.ones((1, n_rules), dtype=bool)
    base = 1_700_000_000 * NS

    def batch(prefix, n, t):
        bits = np.zeros((n, n_rules), dtype=np.uint8)
        bits[:, 0] = 1
        slots = dw.slots_for_ips([f"{prefix}.{i}" for i in range(n)])
        ts_s, ts_ns = split_ns(np.full(n, t, dtype=np.int64))
        return dw.apply_bitmap(bits, slots, ts_s, ts_ns, active,
                               np.zeros(n, dtype=np.int32))

    batch("a", cap, base)                  # fills the table
    seen = []

    def spy(name):
        real = getattr(W, name)

        def step(state, *operands):
            seen.append([name] + [list(o.shape) for o in operands])
            return real(state, *operands)

        monkeypatch.setattr(W, name, step)

    spy("_evict_step")
    spy("_restore_step")
    events = batch("b", k_evict, base + 1)  # evicts k_evict slots at once
    assert dw.eviction_count == k_evict
    assert all(int(e.match_type) == 0 and not e.seen_ip for e in events)
    # slots: 300 -> 512 evicted; nothing came back, so no restore step
    assert seen == [["_evict_step", [512]]]
    assert dw.maintenance_steps == 1
    assert dw.maintenance_elems == 2 * 512
    # the evicted addresses come back: their keys go in one fixed chunk
    # whatever their number (one program), after the evictions they force
    del seen[:]
    batch("a", 40, base + 2)
    assert seen == [["_evict_step", [256]],
                    ["_restore_step", [5, W._RESTORE_CHUNK]]]
    valid, _ = _device_view(dw)
    assert valid.sum() == cap               # one live key a slot, no more


@pytest.mark.parametrize("new_owner", ["fresh", "shadow", "warm"])
def test_reclaimed_slot_never_shows_previous_owner(new_owner):
    """A slot evicted and claimed by another IP in ONE maintenance step:
    the previous owner's counters are gone for every rule, also for the
    rules the new owner does not touch or bring back — whether the new
    owner is new, or restored from the shadow or the warm tier with a
    different rule subset."""
    rules = [make_rule("r0", 60.0, 100), make_rule("r1", 60.0, 100)]
    dw = DeviceWindows(rules, capacity=2,
                       warm_tier_enabled=(new_owner == "warm"),
                       warm_tier_capacity=64)
    hit = _hitter(dw, 2)
    base = 1_700_000_000 * NS
    if new_owner != "fresh":
        hit("C", base, [0, 1])             # C holds r1 only
        hit("f1", base + 1, [1, 0])
        hit("f2", base + 2, [1, 0])        # evicts C: shadow or warm tier
        assert ("C" in dw.shadow_items()) == (new_owner == "shadow")
    hit("A", base + 3, [1, 1])
    hit("A", base + 4, [1, 1])             # A: r0=2, r1=2
    hit("B", base + 5, [1, 0])
    slot_a = dw.slot_for_ip("A")           # a lookup makes A most recent...
    hit("B", base + 6, [1, 0])             # ...and this makes it LRU again

    steps = dw.maintenance_steps
    slots = dw.slots_for_ips(["C"])        # evicts A, claims its slot
    assert int(slots[0]) == slot_a
    with dw._lock:
        dw._run_maintenance_locked()       # evict + claim: one step
    assert dw.maintenance_steps == steps + 1
    valid, hits = _device_view(dw)
    if new_owner == "fresh":
        assert not valid[slot_a].any()
    else:
        assert valid[slot_a].tolist() == [False, True]
        assert hits[slot_a, 1] == 1        # C's own r1, not A's 2
    ts_s, ts_ns = split_ns(np.array([base + 7], dtype=np.int64))
    ev = dw.apply_bitmap(np.array([[1, 1]], dtype=np.uint8), slots, ts_s,
                         ts_ns, np.ones((1, 2), dtype=bool),
                         np.zeros(1, dtype=np.int32))
    by_rule = {e.rule_id: int(e.match_type) for e in ev}
    # r0: first time for C in every variant (A's r0=2 must not show)
    assert by_rule[0] == 0
    assert by_rule[1] == (0 if new_owner == "fresh" else 2)
    got = {r: s.num_hits for r, s in dw.get("C")[0].items()}
    assert got == {"r0": 1, "r1": 1 if new_owner == "fresh" else 2}
    # A's state rests where evictions put it, intact
    assert {r: s.num_hits for r, s in dw.get("A")[0].items()} == {
        "r0": 2, "r1": 2}


@pytest.mark.parametrize("native", [True, False])
def test_evict_restore_evict_keeps_last_owner_only(native):
    """One slot, two IPs alternating: every step evicts one and restores
    the other; after each, the device holds the current owner's keys and
    nothing of the other's."""
    rules = [make_rule("r0", 60.0, 100), make_rule("r1", 60.0, 100)]
    dw = DeviceWindows(rules, capacity=1, native_slotmgr=native)
    hit = _hitter(dw, 2)
    base = 1_700_000_000 * NS

    def device():
        valid, hits = _device_view(dw)
        return [int(h) if v else None for v, h in zip(valid[0], hits[0])]

    hit("A", base, [1, 0])
    hit("A", base + 1, [1, 0])
    assert device() == [2, None]
    (e,) = hit("B", base + 2, [0, 1])      # evict A
    assert int(e.match_type) == 0 and not e.seen_ip
    assert device() == [None, 1]
    (e,) = hit("A", base + 3, [0, 1])      # evict B, restore A (r0=2)
    assert int(e.match_type) == 0 and e.seen_ip
    assert device() == [2, 1]              # A's r0 and A's new r1
    (e,) = hit("B", base + 4, [1, 0])      # evict A, restore B (r1=1)
    assert int(e.match_type) == 0 and e.seen_ip
    assert device() == [1, 1]              # B's r0=1 — not A's 2 + 1
    (e,) = hit("B", base + 5, [0, 1])
    assert int(e.match_type) == 2          # B's own r1 came back
    assert device() == [1, 2]
    assert {r: s.num_hits for r, s in dw.get("A")[0].items()} == {
        "r0": 2, "r1": 1}
    assert np.asarray(dw._state.slot_gen).tolist() == [1 + dw.eviction_count]


@pytest.mark.parametrize("n_rules", [1, 3])
def test_grow_keeps_live_keys_valid_and_new_keys_invalid(n_rules):
    rules = [make_rule(f"r{i}", 60.0, 100) for i in range(n_rules)]
    dw = DeviceWindows(rules, capacity=2)
    hit = _hitter(dw, n_rules)
    base = 1_700_000_000 * NS
    first = [1] + [0] * (n_rules - 1)
    hit("A", base, [1] * n_rules)
    hit("B", base + 1, first)
    hit("C", base + 2, first)              # evicts A: slot_gen moves off 1
    before_valid, before_hits = _device_view(dw)
    assert before_valid.sum() == 2
    with dw._lock:
        dw._grow_locked(4)
    valid, hits = _device_view(dw)
    assert (valid[:2] == before_valid).all()
    assert (hits[:2] == before_hits).all()
    assert not valid[2:].any()
    assert np.asarray(dw._state.slot_gen)[2:].tolist() == [1, 1]
    assert not np.asarray(dw._state.key_gen)[2 * n_rules:].any()
    (e,) = hit("B", base + 3, first)       # a live key still counts on
    assert int(e.match_type) == 2
    ev = hit("D", base + 4, [1] * n_rules)  # a new slot starts empty
    assert all(int(e.match_type) == 0 for e in ev)


@pytest.mark.parametrize("gate", [False, True])
def test_gate_passes_generations_through(gate):
    """gate=False drops every state write: the donated state comes back
    bit-identical, generations included; gate=True stamps the written
    keys with their slot's generation and leaves slot_gen alone."""
    import jax
    import jax.numpy as jnp

    from banjax_tpu.matcher import windows as W

    rules = [make_rule("r0", 60.0, 100), make_rule("r1", 60.0, 100)]
    dw = DeviceWindows(rules, capacity=2)
    hit = _hitter(dw, 2)
    base = 1_700_000_000 * NS
    hit("A", base, [1, 0])
    hit("B", base + 1, [0, 1])
    hit("C", base + 2, [1, 0])             # evicts A: generations differ
    before = jax.tree_util.tree_map(np.asarray, dw._state)
    ts_s, ts_ns = split_ns(np.array([base + 3, base + 3], dtype=np.int64))
    new_state, out = jax.jit(
        functools.partial(W._apply_core, n_rules=2, max_events=8)
    )(
        dw._state, jnp.ones((2, 2), jnp.uint8), jnp.ones((1, 2), bool),
        jnp.zeros(2, jnp.int32), jnp.array([0, 1], jnp.int32),
        jnp.asarray(ts_s), jnp.asarray(ts_ns), dw._limits, dw._iv_s,
        dw._iv_ns, gate=jnp.bool_(gate),
    )
    after = jax.tree_util.tree_map(np.asarray, new_state)
    assert (after.slot_gen == before.slot_gen).all()
    assert (np.asarray(out["rule"]) >= 0).sum() == 4   # events either way
    if not gate:
        for name in ("hits", "start_s", "start_ns", "key_gen", "ip_seen"):
            assert (getattr(after, name) == getattr(before, name)).all(), name
    else:
        assert (after.key_gen.reshape(2, 2)
                == after.slot_gen[:, None]).all()


@pytest.mark.parametrize("stage", ["submit", None],
                         ids=["stage-thread", "undeclared-thread"])
def test_a_wait_for_the_windows_lock_is_counted_under_the_waiters_stage(stage):
    """`DeviceWindows._lock` times only an acquire that finds it held, and
    books the wait under the stage the waiting thread said it runs
    (`trace.stage_thread`): the drain holds it for 50 ms, the submit
    stage's thread asks meanwhile — one contention and 40 ms or more
    under `submit`, nothing elsewhere; a waiting thread that declared no stage
    is not counted, and an acquire that finds the lock free reads no
    clock."""
    import threading
    import time

    from banjax_tpu.obs import trace

    dw = DeviceWindows([make_rule("r", 5, 2)], capacity=16)
    holding, waited = threading.Event(), []

    def drain():
        trace.stage_thread("drain")
        with dw._lock:
            holding.set()
            time.sleep(0.05)

    def waiter():
        if stage is not None:
            trace.stage_thread(stage)
        assert trace.thread_stage() == stage
        assert holding.wait(10)
        t0 = time.perf_counter()
        with dw._lock:
            waited.append(time.perf_counter() - t0)

    threads = [threading.Thread(target=drain),
               threading.Thread(target=waiter)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    with dw._lock:      # free: not timed, whoever asks
        pass
    got = dw.lock_waits()
    assert waited[0] >= 0.040
    assert set(got) == {"submit", "drain"} and got["drain"] == (0.0, 0)
    if stage is None:
        assert got["submit"] == (0.0, 0)
        return
    # no upper limit: how long a sleep of 50 ms lasts is the machine's
    seconds, contentions = got["submit"]
    assert contentions == 1 and seconds >= 0.040
    assert seconds <= waited[0]
