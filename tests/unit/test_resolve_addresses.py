"""The submit stage's one pass over a batch's distinct addresses
(DeviceWindows.resolve_addresses) and the pieces under it.

The pass answers, from one encoding and one probe of each table, what
admission_mask + slots_for_unique_ips answered from four encodings and two
probes each.  Everything here compares it with those per-step calls, which
stay in the tree as the dict path and as the fallback: same verdict, same
slot for every address, same victims in the same order, same warm-tier
records, same pending evict / restore lists.  Beside it: the selection
that replaced the sort of every evictable slot, the batched spill and
refill calls, the C hash of the sketch, and the sketch's candidate set
written in bulk.
"""

import random
import zlib
from collections import OrderedDict

import numpy as np
import pytest

from banjax_tpu.matcher.windows import DeviceWindows
from banjax_tpu.native import shm, slotmgr
from banjax_tpu.obs.sketch import TrafficSketch, hash_ip
from tests.shadow_access import plant, shadow
from tests.unit.test_slotmgr import (  # noqa: F401 — `form` is a fixture
    assert_same_state,
    assert_same_warm_state,
    form,
    ip_of,
    lockstep,
    make_pair,
    make_rule,
    make_warm_pair,
)

pytestmark = pytest.mark.skipif(
    slotmgr.create(8) is None or not shm.available(),
    reason="native slotmgr / shmstate unavailable (no C compiler)",
)


# ------------------------------------------------------------ the selection


@pytest.mark.parametrize("n,chunk,seed", [
    (1, 1, 0), (2, 1, 1), (7, 3, 2), (64, 1, 3), (64, 5, 4), (64, 64, 5),
    (1000, 7, 6), (1000, 300, 7), (1000, 999, 8), (4096, 1100, 9),
])
def test_selection_in_chunks_is_the_full_sort(n, chunk, seed):
    """The victims' order is the (last_used, slot) sort's however many
    candidates a placement asks for at a time — also when it has to go on
    past its first selection, to the last evictable slot."""
    rng = random.Random(seed)
    # few distinct stamps: the slot has to break the ties
    lu = np.asarray([rng.randrange(1, 6) for _ in range(n)], dtype=np.int64)
    slot = np.asarray(rng.sample(range(4 * n), n), dtype=np.int32)
    want = slot[np.lexsort((slot, lu))]
    np.testing.assert_array_equal(slotmgr.select_order(lu, slot, chunk), want)


@pytest.mark.parametrize("capacity,seed,pin_share", [
    (256, 21, 0.0), (256, 22, 0.3), (512, 23, 0.6), (128, 24, 0.9),
])
def test_parity_fuzz_selection_under_pins(capacity, seed, pin_share, form):
    """Native against the dict path at capacities where the placement
    selects (evictable slots ≫ misses), with batches held in flight so
    that pins and this batch's own touches thin the candidates — down to
    refusals, which must leave the same partial state on both sides."""
    rng = random.Random(seed)
    nat, ora = make_pair(capacity)
    pool = [ip_of(i) for i in range(capacity * 3)]
    held = []
    refusals = 0
    for step in range(120):
        k = rng.randrange(1, capacity // 2)
        s = lockstep(nat, ora, rng.sample(pool, k), f"step {step}", form=form)
        if s is None:
            refusals += 1
        elif rng.random() < pin_share:
            held.append(s)
        else:
            nat.release_pins(s), ora.release_pins(s)
        while held and (s is None or rng.random() < 0.25):
            h = held.pop(rng.randrange(len(held)))
            nat.release_pins(h), ora.release_pins(h)
    for h in held:
        nat.release_pins(h), ora.release_pins(h)
    assert_same_state(nat, ora, "final")
    assert nat.eviction_count > capacity
    if pin_share >= 0.9:
        assert refusals, "the fuzz never ran out of evictable slots"


# ------------------------------------------------------------- edge shapes


def _fill(nat, ora, n, form=None):
    s = lockstep(nat, ora, [ip_of(i) for i in range(n)], "fill", form=form)
    nat.release_pins(s), ora.release_pins(s)


@pytest.mark.parametrize("shape", [
    "one-address", "all-hits", "all-misses", "empty",
    "misses-over-free-and-evictable",
])
def test_edge_shapes_match_the_dict_path(shape, form):
    nat, ora = make_pair(8)
    _fill(nat, ora, 8, form)
    if shape == "one-address":
        batches = [[ip_of(3)], [ip_of(100)]]
    elif shape == "all-hits":
        batches = [[ip_of(i) for i in (5, 1, 7, 0)]]
    elif shape == "all-misses":
        batches = [[ip_of(100 + i) for i in range(8)]]
    elif shape == "empty":
        batches = [[]]
    else:
        # five slots held by a batch in flight: three are evictable, the
        # batch brings one hit and four misses — refusal at the fourth,
        # with the first three placed on both sides
        held = lockstep(
            nat, ora, [ip_of(i) for i in range(5)], "hold", form=form)
        out = lockstep(
            nat, ora, [ip_of(6)] + [ip_of(200 + i) for i in range(4)],
            "refusal", form=form,
        )
        assert out is None
        assert nat.eviction_count == 2  # slots 5 and 7; 6 is this batch's
        nat.release_pins(held), ora.release_pins(held)
        batches = [[ip_of(200 + i) for i in range(4)]]
    for k, ips in enumerate(batches):
        s = lockstep(nat, ora, ips, f"{shape} {k}", form=form)
        assert s is not None and len(s) == len(ips)
        nat.release_pins(s), ora.release_pins(s)
    assert_same_state(nat, ora, shape)


def test_dropped_put_of_a_batch_keeps_its_shadow_entry(form):
    """A warm tier whose probe window is full of live records drops the
    put; the batched spill leaves exactly those addresses in the shadow
    and deletes exactly the ones that landed, in lockstep with the dict
    path's one put per eviction."""
    nat, ora = make_warm_pair(4, warm_capacity=2)
    vec = {0: (3, 1_700_000_123, 42)}
    for rnd in range(6):
        ips = [ip_of(10 * rnd + i) for i in range(4)]
        for w in (nat, ora):
            for ip in ips:
                plant(w, ip, vec)
        s = lockstep(nat, ora, ips, f"round {rnd}", form=form)
        nat.release_pins(s), ora.release_pins(s)
        assert_same_warm_state(nat, ora, f"round {rnd}")
    assert nat.warm_dropped > 0 and nat.warm_spills > 0
    for rnd in range(5):  # every evicted vector is somewhere
        for i in range(4):
            ip = ip_of(10 * rnd + i)
            assert nat._warm.peek(ip) is not None or ip in shadow(nat), ip


# ------------------------------------------ batched spill / refill calls


def _vec(entries):
    return None if entries is None else {e[0]: e[1:] for e in entries}


def _records(rng, n, max_rules):
    out = []
    for i in range(n):
        k = rng.choice([0, 1, 1, 2, max_rules, max_rules + 3])
        rules = rng.sample(range(1000), k)
        out.append((
            "" if i == 3 else f"k{rng.randrange(400)}-{'x' * rng.randrange(3)}",
            [(r, rng.randint(1, 1 << 30), rng.randint(0, 1 << 40),
              rng.randint(0, 999_999_999)) for r in rules],
        ))
    return out


def _keys(ips):
    """The keys as the slot manager hands a placement's victims over:
    one stride an address, cut at it, the empty address one NUL."""
    stride = slotmgr.EVICT_KEY_STRIDE
    blob = np.zeros(len(ips) * stride, dtype=np.uint8)
    lens = np.empty(len(ips), dtype=np.int32)
    for k, ip in enumerate(ips):
        key = shm._wt_key(ip)
        blob[k * stride : k * stride + len(key)] = np.frombuffer(key, np.uint8)
        lens[k] = len(key)
    return blob, lens


@pytest.mark.parametrize("capacity,seed", [(64, 1), (64, 2), (1024, 3)])
def test_spill_and_refill_of_the_mirror_are_the_per_record_calls(
    capacity, seed
):
    """Random records — repeated keys, the empty key, no entries, more
    entries than the record holds, a table that overflows — out of a
    mirror through sh_spill / back through sh_refill on one table and
    through put / take, one call a record, on another: same result per
    record, same table."""
    rng = random.Random(seed)
    mk = dict(capacity=capacity, max_rules=4, expiry_ns=10**15)
    a, b = shm.ShmWarmTier(**mk), shm.ShmWarmTier(**mk)
    mirror = shm.create_shadow_mirror(64)
    try:
        for rnd in range(30):
            recs = _records(rng, rng.randrange(1, 40), 4)
            now = 1_000 + rnd
            slots = np.arange(len(recs), dtype=np.int64)
            for k, (_, ents) in enumerate(recs):
                mirror.install(k, OrderedDict((e[0], e[1:]) for e in ents))
            held = len(mirror)
            ips = [ip for ip, _ in recs]
            status = mirror.spill(a, slots, _keys(ips), now)
            want = [b.put(ip, e, now) for ip, e in recs]
            assert (status == 1).tolist() == want, rnd
            # a dropped put keeps its record in the mirror; no entries,
            # no record
            assert [st == 0 for st in status.tolist()] == \
                [not e for _, e in recs], rnd
            assert len(mirror) == held - sum(want), rnd
            mirror.export(slots, drop=True)
            assert sorted(a.keys()) == sorted(b.keys()), rnd
            assert (len(a), a.dropped) == (len(b), b.dropped), rnd
            asked = [ip for ip, _ in rng.sample(recs, len(recs) // 4 + 1)]
            asked += [f"absent{rnd}", asked[0]]  # absent; a key twice
            to = np.arange(len(asked), dtype=np.int32)
            stamps = mirror.refill(a, to, slotmgr.encode_ips(asked))
            want = [_vec(b.take(ip)) for ip in asked]
            assert mirror.export(to, drop=True)[1] == want, rnd
            assert (stamps > 0).tolist() == [v is not None for v in want], rnd
            # the same spans out of a larger encoding, as the pass gives them
            more = [ip for ip, _ in recs]
            enc = slotmgr.encode_ips(["pad"] + more)
            idx = np.arange(1, len(more) + 1)
            spans = (enc[0], enc[1][idx], enc[2][idx])
            assert a.contains_batch(more, spans=spans).tolist() == \
                b.contains_batch(more).tolist(), rnd
            few = slice(0, None, 5)
            spans = tuple(x[few] if k else x for k, x in enumerate(spans))
            to = np.arange(len(more[few]), dtype=np.int32)
            mirror.refill(a, to, spans)
            assert mirror.export(to, drop=True)[1] == \
                [_vec(b.take(ip)) for ip in more[few]], rnd
            assert len(mirror) == 0, rnd
        assert a.dropped > 0 or capacity > 64
    finally:
        for t in (a, b):
            t.close()
            t.unlink()


def test_a_python_tier_behind_the_mirror_moves_record_by_record(form):
    """A tier that is not the C table (the fallback, or one a test
    injects) has no arena the mirror could copy into: spills and refills
    go through its put / take, one record each, counted as the dict
    form's, and nothing is lost on the way."""
    py = shm.PyWarmTier(capacity=8, max_rules=4)
    dw = DeviceWindows([make_rule()], capacity=2, warm_tier=py)
    assert dw.slotmgr_native

    def slots_for(ips):
        if form is None:
            return dw.slots_for_unique_ips(ips)
        return dw.resolve_addresses(form(ips)).slots

    vecs = {ip_of(i): {0: (i + 1, 1_700_000_000 + i, 7 * i)} for i in range(2)}
    s = slots_for(list(vecs))
    dw.release_pins(s)
    for ip, vec in vecs.items():
        plant(dw, ip, vec)
    s = slots_for([ip_of(8), ip_of(9)])   # evicts both
    dw.release_pins(s)
    assert {ip: _vec(py.peek(ip)) for ip in vecs} == vecs
    assert not shadow(dw) and dw.warm_spills == 2
    s = slots_for([ip_of(1), ip_of(0)])   # and back
    dw.release_pins(s)
    assert dict(shadow(dw)) == {ip_of(1): vecs[ip_of(1)],
                                ip_of(0): vecs[ip_of(0)]}
    assert len(py) == 0 and dw.warm_refills == 2
    assert dw.shadow_records["spill"] == {"native": 0, "dict": 2}
    assert dw.shadow_records["refill"] == {"native": 0, "dict": 2}


# ------------------------------------------------- the gate from the pass


def _seed_states(rng, wins, pool, step):
    """Window vectors in the shadow of every DeviceWindows of `wins`, so
    that evictions spill and returns refill."""
    ip = rng.choice(pool)
    vec = {0: (step + 1, 1_700_000_000 + step, step * 7)}
    for w in wins:
        plant(w, ip, vec)


@pytest.mark.parametrize("threshold,seed", [
    (1, 31), (1, 32), (2, 33), (2, 34), (5, 35), (5, 36),
])
def test_gate_verdict_and_slots_equal_the_per_step_calls(
        threshold, seed, form):
    """resolve_addresses on the native manager against admission_mask +
    slots_for_unique_ips on the dict path, over random batches with
    shadow and warm residents: the same verdict, the same slot for every
    admitted address, the same tables afterwards — and a refused address
    has no slot and moved no recency stamp (the two sides' `last_used`
    stay equal, and the per-step side never stamps a refused address)."""
    rng = random.Random(seed)
    nat, ora = make_warm_pair(32)
    sk = TrafficSketch(["r"], width=64, depth=2)
    pool = [ip_of(i) for i in range(160)]
    refused_total = 0
    for step in range(150):
        ips = rng.sample(pool, rng.randrange(1, 40))
        counts = np.asarray(
            [rng.randrange(1, 4) for _ in ips], dtype=np.int64
        )
        est = sk.estimate_ips(ips) + counts
        want = ora.admission_mask(
            ips, estimates=None if threshold <= 1 else est,
            min_estimate=threshold, counts=counts,
        )
        res = nat.resolve_addresses(
            ips if form is None else form(ips), counts=counts,
            min_estimate=threshold, sketch=sk, gate=True,
        )
        np.testing.assert_array_equal(res.admit, want, err_msg=f"step {step}")
        assert res.placed == (not len(res.refused))
        refused = [ips[i] for i in res.refused.tolist()]
        if refused:
            # what the runner does between the verdict and the placement
            sk.fold_refused(refused, counts[res.refused],
                            hashes=res.refused_hashes)
            np.testing.assert_array_equal(
                res.refused_hashes, sk.base_hashes(refused)
            )
            nat.place_resolved(res)
        refused_total += len(refused)
        got = ora.slots_for_unique_ips(
            [ip for ip, a in zip(ips, want) if a]
        )
        assert (res.slots is None) == (got is None), f"step {step}"
        if got is not None:
            np.testing.assert_array_equal(res.slots[want], got)
            assert (res.slots[~want] == -1).all()
            if rng.random() < 0.8:
                nat.release_pins(got), ora.release_pins(got)
        else:
            nat.clear(), ora.clear()  # every slot pinned: start over
        hot = set(nat.slot_addresses().values())
        assert not hot & set(refused), f"step {step}"
        assert_same_state(nat, ora, f"step {step}")
        assert_same_warm_state(nat, ora, f"step {step}")
        assert nat.slot_refusals == ora.slot_refusals
        if threshold > 1:
            assert nat.sketch_admissions == ora.sketch_admissions
        if rng.random() < 0.5:
            _seed_states(rng, (nat, ora), pool, step)
    assert nat.warm_spills > 0 and nat.warm_refills > 0
    if threshold == 1:
        # the verdict is "admit" by arithmetic: the sketch was not asked,
        # so nothing counts as a sketch admission
        assert refused_total == 0 and nat.sketch_admissions == 0
        assert nat.gate_derived_batches == 150
    else:
        assert refused_total > 0 and nat.sketch_admissions > 0
        assert nat.gate_derived_batches < 150
    asked = nat.resolve_probes
    tally = nat.resolve_outcomes
    distinct = sum(tally.values())
    assert asked["slots"] == distinct
    assert asked["warm"] <= distinct - tally["hit"] - tally["shadow"]
    assert tally["refused"] == refused_total


def test_pass_with_no_gate_is_slots_for_unique_ips(form):
    """`gate=False` (slot admission off): every address is admitted and
    the pass is the slot assignment alone."""
    nat, ora = make_warm_pair(8)
    rng = random.Random(5)
    pool = [ip_of(i) for i in range(40)]
    for step in range(60):
        ips = rng.sample(pool, rng.randrange(1, 8))
        res = nat.resolve_addresses(ips if form is None else form(ips))
        got = ora.slots_for_unique_ips(ips)
        assert res.admit.all() and res.placed and not len(res.refused)
        np.testing.assert_array_equal(res.slots, got)
        nat.release_pins(got), ora.release_pins(got)
        assert_same_state(nat, ora, f"step {step}")
        _seed_states(rng, (nat, ora), pool, step)
    assert nat.gate_derived_batches == 0


def test_dict_path_resolves_through_the_per_step_calls(form):
    """Without the native manager the same call gives the same answers
    (it is admission_mask and the dict loop, called in turn)."""
    sk = TrafficSketch(["r"], width=64, depth=2)
    a = DeviceWindows([make_rule()], capacity=8, native_slotmgr=False)
    b = DeviceWindows([make_rule()], capacity=8, native_slotmgr=True)
    rng = random.Random(9)
    pool = [ip_of(i) for i in range(30)]
    for step in range(40):
        ips = rng.sample(pool, rng.randrange(1, 8))
        counts = np.ones(len(ips), dtype=np.int64)
        ra = a.resolve_addresses(ips, counts, 3, sk, gate=True)
        rb = b.resolve_addresses(
            ips if form is None else form(ips), counts, 3, sk, gate=True)
        np.testing.assert_array_equal(ra.admit, rb.admit)
        if len(ra.refused):
            sk.fold_refused([ips[i] for i in ra.refused.tolist()],
                            counts[ra.refused], hashes=ra.refused_hashes)
            a.place_resolved(ra), b.place_resolved(rb)
        np.testing.assert_array_equal(ra.slots, rb.slots)
        pinned = ra.slots[ra.slots >= 0]
        a.release_pins(pinned), b.release_pins(pinned)
        assert_same_state(b, a, f"step {step}")


# ------------------------------------------------------ the sketch's side


def test_crc32_of_the_encoding_is_hash_ip():
    ips = ["", "1.2.3.4", "2001:db8::1", "ünï", "\udc80raw", "a" * 300]
    ips += [ip_of(i) for i in range(500)]
    enc = slotmgr.encode_ips(ips)
    got = slotmgr.crc32_spans(enc)
    assert got.dtype == np.uint32
    assert got.tolist() == [hash_ip(ip) for ip in ips]
    assert hash_ip("1.2.3.4") == zlib.crc32(b"1.2.3.4")
    assert enc[0][-1] == 0 and enc[0].size == int(enc[2].sum()) + 1


@pytest.mark.parametrize("bound,seed", [(16, 1), (64, 2), (64, 3)])
def test_candidates_written_in_bulk_are_the_per_address_lru(bound, seed):
    """The candidate set — which addresses are remembered, and in what
    recency order — is what the per-address move-to-end walk of every
    batch, trimmed after each, would hold; read at random moments, so
    that folds of the log start from every kind of state."""
    rng = random.Random(seed)
    sk = TrafficSketch(["r"], width=64, depth=2, topk=4,
                       max_candidates=bound)
    ref: "OrderedDict[str, int]" = OrderedDict()
    pool = [ip_of(i) for i in range(200)]
    for step in range(80):
        ips = rng.sample(pool, rng.randrange(1, 3 * bound // 2))
        hashes = None
        if rng.random() < 0.5:
            hashes = slotmgr.crc32_spans(slotmgr.encode_ips(ips))
        sk.note_assignments(ips, hashes=hashes)
        for ip in ips:
            ref[ip] = hash_ip(ip)
            ref.move_to_end(ip)
        while len(ref) > sk.max_candidates:
            ref.popitem(last=False)
        if rng.random() < 0.2:
            assert list(sk._candidates.items()) == list(ref.items()), step
    assert list(sk._candidates.items()) == list(ref.items())
    assert sk.pull(force=True)["sketch"]["candidates"] == len(ref)


# ----------------------------------------------- through the submit stage


import io
import threading
import time

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import Banner
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.pipeline import PipelineScheduler
from banjax_tpu.resilience import failpoints
from tests.differential.test_tpu_matcher import CONFIG_YAML, result_key


def _stream(now, n, seed):
    """Heavy returning clients over a table of 32 slots, one-shot
    addresses that match rule1, a few POSTs: hits, evictions with
    counters to spill, refills, unseen addresses, every batch."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        k = rng.random()
        if k < 0.5:
            ip, req = f"30.0.0.{rng.randrange(90)}", "GET example.com GET /a"
        elif k < 0.8:
            ip, req = f"31.{i >> 8}.0.{i & 255}", "GET example.com GET /once"
        elif k < 0.9:
            ip, req = f"32.0.0.{rng.randrange(6)}", "POST example.com POST /s"
        elif k < 0.95:
            ip, req = f"33.{i >> 8}.0.{i & 255}", "GET news.net GET /benign"
        else:  # matches nothing, comes back: admitted on the sketch's word
            ip, req = f"34.0.0.{rng.randrange(8)}", "GET news.net GET /benign"
        out.append(f"{now:f} {ip} {req} HTTP/1.1 ua -")
    return out


def _run_stream(lines, now, *, min_estimate=0, batch_lines=None, step=64,
                break_pass=0, arm=None):
    """One stream through the scheduler and the fused matcher.  →
    (per-line results, ban log, matcher, batches submitted)."""
    cfg = config_from_yaml_text(CONFIG_YAML)
    cfg.matcher_device_windows = True
    cfg.matcher_window_capacity = 32
    cfg.traffic_sketch_enabled = True
    cfg.slot_admission_enabled = True
    cfg.slot_admission_min_estimate = min_estimate
    cfg.warm_tier_enabled = True
    cfg.warm_tier_capacity = 4096
    if batch_lines:
        cfg.matcher_batch_lines = batch_lines
    ban_log = io.StringIO()
    banner = Banner(DynamicDecisionLists(start_sweeper=False), ban_log,
                    io.StringIO(), ipset_instance=None)
    m = TpuMatcher(cfg, banner, StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    if break_pass:
        real, left = m.device_windows.resolve_addresses, [break_pass]

        def flaky(*a, **kw):
            if kw.get("gate") and left[0]:
                left[0] -= 1
                raise RuntimeError("injected: the pass fails")
            return real(*a, **kw)

        m.device_windows.resolve_addresses = flaky
    got, lock = [], threading.Lock()

    def sink(ls, rs):
        with lock:
            got.append((ls, rs))

    sched = PipelineScheduler(lambda: m, on_results=sink, now_fn=lambda: now)
    sched.start()
    batches = 0
    for i in range(0, len(lines), step):
        if arm and i == arm[1]:
            failpoints.arm(arm[0], count=1)
        sched.submit(lines[i : i + step])
        assert sched.flush(120)  # one submit, one batch
        batches += 1
    sched.stop()
    results = {}
    for ls, rs in got:
        for line, r in zip(ls, rs or [None] * len(ls)):
            results.setdefault(line, []).append(r and result_key(r))
    return results, ban_log.getvalue(), m, batches


@pytest.fixture()
def no_failpoints():
    failpoints.disarm()
    yield
    failpoints.disarm()


def test_threshold_one_asks_the_sketch_nothing_and_probes_once():
    """CONFIG_YAML has a rule that bans on the first hit, so the derived
    threshold is 1: every batch's verdict is derived, nothing is a sketch
    admission, and each table is handed a batch's distinct addresses
    (misses, for the warm tier) once."""
    now = time.time()
    _, log, m, batches = _run_stream(_stream(now, 1500, 1), now)
    dw = m.device_windows
    assert m._admission_min_estimate == 1 and log
    assert dw.gate_derived_batches == batches
    assert dw.sketch_admissions == 0 and dw.slot_refusals == 0
    assert dw.sketch_fp_evaluated == 0
    tally, asked = dw.resolve_outcomes, dw.resolve_probes
    distinct = sum(tally.values())
    assert asked["slots"] == distinct
    assert 0 < asked["warm"] <= distinct - tally["hit"]
    assert tally["hit"] and tally["warm"] and tally["unseen"]
    assert tally["refused"] == 0
    # a batch with more distinct addresses than free + evictable slots is
    # refused and resolved again in halves: found twice, refilled once
    assert dw.warm_spills > 0 and 0 < dw.warm_refills <= tally["warm"]
    assert m.submit_resolve_s > 0
    assert (dw._pin_counts == 0).all()
    m.close()


@pytest.mark.parametrize("threshold", [2, 5])
def test_threshold_over_one_still_asks_and_refuses(threshold):
    """With a threshold of 2 or more the gate decides as before: unseen
    addresses are admitted on the sketch's word (and counted as such) or
    refused, refused rows are applied on the host, and what is banned is
    what the ungated run bans."""
    now = time.time()
    lines = _stream(now, 1500, 2)
    res1, log1, m1, _ = _run_stream(lines, now)
    resn, logn, mn, batches = _run_stream(lines, now, min_estimate=threshold)
    dw = mn.device_windows
    assert dw.slot_refusals > 0 and dw.sketch_admissions > 0
    assert dw.resolve_outcomes["refused"] > 0
    assert dw.gate_derived_batches < batches
    assert sorted(logn.splitlines()) == sorted(log1.splitlines())
    assert resn == res1
    hot = set(dw.slot_addresses().values())
    assert len(hot) <= 32 and (dw._pin_counts == 0).all()
    m1.close(), mn.close()


@pytest.mark.parametrize("how", ["pass-fails-once", "pass-fails-often",
                                 "many-chunks"])
def test_fallbacks_give_the_same_stream(how):
    """A failing pass admits the batch and leaves it to the per-step
    calls; a batch of several chunks takes them anyway.  Same results,
    same ban log as the run where every batch is resolved in one pass."""
    now = time.time()
    lines = _stream(now, 1200, 3)
    want_res, want_log, m0, _ = _run_stream(lines, now)
    kw = {"many-chunks": dict(batch_lines=64, step=200),
          "pass-fails-once": dict(break_pass=1),
          "pass-fails-often": dict(break_pass=7)}[how]
    res, log, m, _ = _run_stream(lines, now, **kw)
    assert res == want_res
    if how == "many-chunks":  # other batches: other victims, same bans
        assert sorted(log.splitlines()) == sorted(want_log.splitlines())
    else:
        assert log == want_log
    assert (m.device_windows._pin_counts == 0).all()
    m0.close(), m.close()


@pytest.mark.parametrize("point", ["matcher.device", "pipeline.submit"])
def test_a_failing_submit_gives_the_pass_its_pins_back(no_failpoints, point):
    """The pass pins the batch's slots before the fused program is
    dispatched; a submit that fails in between must not leak them (a
    leaked pin is a slot that can never be evicted again)."""
    now = time.time()
    lines = _stream(now, 640, 4)
    res, _, m, _ = _run_stream(lines, now, arm=(point, 320))
    assert failpoints.fired_count(point) == 1
    assert (m.device_windows._pin_counts == 0).all()
    assert len(res) == len(set(lines))
    m.close()
