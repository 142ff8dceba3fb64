"""The traffic sketch's fold rides the fused dispatch (ISSUE 44): a
chunk's count-min / HLL fold is keyed on its rows' hashes and is part of
the chunk's fused program; whatever is not dispatched fused runs the same
arithmetic as a standalone program.

  * the differential: the same stream through the matcher's real paths
    (fused, and the standalone fold of the classic protocol) leaves
    `(cm, hll)` bit-identical to a standalone fold of the rows' hashes
    and to what PR 43's `note_assignments` + `update` (the slot -> hash
    table and its gather) left for that stream — digests recorded from
    that commit;
  * the locks: the drain's `note_rule_events` does not wait for the
    state lock, which is held across a dispatch;
  * the candidate log is compacted where it is read, and stays bounded
    where nobody reads;
  * `traffic_sketch_enabled: false` builds and dispatches the program
    without the sketch's operands;
  * `banjax_sketch_updates_total{path}`.
"""

import hashlib
import threading

import numpy as np
import pytest

from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import (
    FailedChallengeRateLimitStates,
    RegexRateLimitStates,
)
from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.obs import exposition
from banjax_tpu.obs.sketch import TrafficSketch, hash_ip
from tests.classic_downgrade import scan_selftest_failing
from tests.unit.test_longline import (
    BATCH,
    _build,
    _filler,
    _of_len,
    _rest,
    _run_pipelined,
    _stamp,
)

NOW = 1_790_000_000.0   # the digests do not depend on it: a row's key is
#                         its address, and every line below is fresh


def _metrics(m) -> str:
    return exposition.render_prometheus(
        DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
        FailedChallengeRateLimitStates(), matcher=m)


def _digest(sk) -> str:
    cm, hll = (np.asarray(a) for a in sk._state)
    assert cm.dtype == hll.dtype == np.int32
    return hashlib.sha256(cm.tobytes() + hll.tobytes()).hexdigest()[:16]


def _ips_of(lines):
    return [ln.split(" ", 2)[1] for ln in lines]


def _benign(n, n_ips, salt=0):
    return [(f"9.{salt}.{k % n_ips // 200}.{k % n_ips % 200}",
             _of_len(100 + (k * 7 + salt) % 120, salt=k)) for k in range(n)]


# ---- the streams: (matcher, lines whose rows the sketch must hold) ----


def _padded_and_cut(entry):
    """300 lines in batches of 128: two whole chunks and one of 44 rows
    in a program of 64 (padded rows masked by n_real)."""
    lines = _stamp(NOW, _benign(300, 47, salt=1))
    m, _ = _build(TpuMatcher, matcher_batch_lines=128)
    if entry == "sync":
        m.consume_lines(lines, now_unix=NOW)   # one call, cut into chunks
        assert m._fw_pipeline.fused_batches == 3
    else:
        _run_pipelined(m, lines, NOW, 128)
        assert m.pipelined_fused_chunks == 3
    return m, lines


def _stale_under_the_live_mask(entry):
    """Rows the submit-time staleness cut masks out of the commit count
    in the sketch all the same: a stale row counts."""
    del entry
    old = [f"{NOW - 8:.6f} 9.9.9.{i} {_of_len(90 + i)}" for i in range(9)]
    fresh = [f"{NOW:.6f} 8.8.8.{i} {_of_len(80 + i)}" for i in range(11)]
    m, _ = _build(TpuMatcher)
    state = m.pipeline_begin(old + fresh, NOW)
    m.pipeline_submit(state, now=NOW + 3)
    assert state.get("fused")
    m.pipeline_collect(state)
    _, n_stale = m.pipeline_finish(state, NOW + 3)
    assert n_stale == 9
    return m, old + fresh


def _overflow_replays(entry):
    """Every chunk of the flood passes the candidate capacity and replays
    classically: counted once, at its dispatch, not again at the replay."""
    flood = _stamp(NOW, [
        (f"7.7.{k % 3}.{k % 19}", _rest(f"/x{k}/wp.php?id=7{k % 90}"))
        for k in range(128)])
    tail = _stamp(NOW, _benign(64, 13, salt=2))
    m, _ = _build(TpuMatcher, matcher_prefilter_cand_frac=1.0 / 64)
    if entry == "sync":
        m.consume_lines(flood, now_unix=NOW)
        m.consume_lines(tail, now_unix=NOW)
    else:
        _run_pipelined(m, flood + tail, NOW)
    assert m._fw_pipeline.fallback_batches >= 2
    return m, flood + tail


def _long_rows_cut_the_batch(entry):
    """Long rows ride the long operands, and more of them than a chunk's
    operand holds cut the batch into several chunks, each placed and
    folded by itself."""
    ip_rests = []
    for k in range(30):
        ip_rests.append(("7.7.5.1", _rest(
            f"/{_filler(300 + 9 * k, k)}/wp.php?id=7{k}")))
        ip_rests.append((f"9.9.4.{k % 11}", _of_len(100 + k)))
    lines = _stamp(NOW, ip_rests)
    m, _ = _build(TpuMatcher)
    if entry == "sync":
        m.consume_lines(lines, now_unix=NOW)
    else:
        _run_pipelined(m, lines, NOW)
    assert m._fw_pipeline.overflow_causes["long_rows"] == 1
    assert m.unfused_batches == {"line_length": 0, "non_ascii": 0}
    assert m.long_lines == 30
    return m, lines


def _one_slot_two_owners(entry):
    """Sixteen slots under sixty addresses: a slot changes hands from one
    batch to the next, and each owner's rows count under its own hash."""
    lines = _stamp(NOW, [
        (f"6.6.{k % 60 // 8}.{k % 60 % 8}", _of_len(100 + k % 100, salt=k))
        for k in range(480)])
    m, _ = _build(TpuMatcher, matcher_window_capacity=16,
                  matcher_batch_lines=8)
    if entry == "sync":
        for s in range(0, len(lines), 8):
            m.consume_lines(lines[s:s + 8], now_unix=NOW)
    else:
        _run_pipelined(m, lines, NOW, 8)
    assert m.device_windows.eviction_count > 100
    return m, lines


def _classic_protocol(entry):
    """Nothing dispatched fused: the classic protocol's window apply
    folds every batch through the standalone program."""
    lines = _stamp(NOW, _benign(200, 31, salt=3) + [
        ("7.7.5.1", _rest(f"/{_filler(300, k)}/wp.php?id=7{k}"))
        for k in range(5)])
    with scan_selftest_failing():
        m, _ = _build(TpuMatcher)
    assert m.describe()["fused_protocol"] == "classic"
    if entry == "sync":
        m.consume_lines(lines, now_unix=NOW)
    else:
        _run_pipelined(m, lines, NOW)
    return m, lines


def _an_unfused_batch_between_fused_ones(entry):
    """A byte over 0x7F takes its batch the classic way (the standalone
    fold, at the drain) between batches that commit fused."""
    ip_rests = _benign(3 * BATCH, 29, salt=4)
    ip_rests[BATCH + 5] = ("5.5.5.5", _rest("/café/" + _filler(40)))
    lines = _stamp(NOW, ip_rests)
    m, _ = _build(TpuMatcher)
    if entry == "sync":
        for s in range(0, len(lines), BATCH):
            m.consume_lines(lines[s:s + BATCH], now_unix=NOW)
    else:
        _run_pipelined(m, lines, NOW)
    assert m.unfused_batches["non_ascii"] == 1
    # (pipelined, a batch behind it may join it on the classic path)
    assert m._fw_pipeline.fused_batches >= 1
    return m, lines


# (stream, entry) -> the digest of (cm, hll) that PR 43's tree left
# (`note_assignments` + `update` through its slot -> hash table)
PARENT_DIGESTS = {
    (_padded_and_cut, "sync"): "a42b37dda982a094",
    (_padded_and_cut, "pipeline"): "a42b37dda982a094",
    (_stale_under_the_live_mask, "pipeline"): "cfcde6f6538797c7",
    (_overflow_replays, "sync"): "ef201ee9f5349d25",
    (_overflow_replays, "pipeline"): "ef201ee9f5349d25",
    (_long_rows_cut_the_batch, "sync"): "c29ad2692c36b88e",
    (_long_rows_cut_the_batch, "pipeline"): "c29ad2692c36b88e",
    (_one_slot_two_owners, "sync"): "4bd6a036396e14fc",
    (_one_slot_two_owners, "pipeline"): "4bd6a036396e14fc",
    (_classic_protocol, "sync"): "9109da2e817b6338",
    (_classic_protocol, "pipeline"): "9109da2e817b6338",
    (_an_unfused_batch_between_fused_ones, "sync"): "14f2477584565848",
    (_an_unfused_batch_between_fused_ones, "pipeline"): "14f2477584565848",
}


def _standalone_digest(lines) -> str:
    """A fresh sketch, the rows' hashes, the program of its own — in
    chunks of 50, so that padded rows and several row buckets run."""
    sk = TrafficSketch(["r"])
    h = np.asarray([hash_ip(ip) for ip in _ips_of(lines)], dtype=np.uint32)
    for s in range(0, len(h), 50):
        sk.update(h[s:s + 50], len(h[s:s + 50]))
    assert sk.lines_total == len(lines)
    return _digest(sk)


@pytest.mark.parametrize(
    "stream,entry", list(PARENT_DIGESTS),
    ids=[f"{fn.__name__.strip('_')}-{entry}" for fn, entry in PARENT_DIGESTS],
)
def test_fused_and_standalone_folds_leave_the_parents_state(stream, entry):
    m, lines = stream(entry)
    sk = m.traffic_sketch
    try:
        assert sk.lines_total == len(lines)
        got = _digest(sk)
        assert got == _standalone_digest(lines)
        assert got == PARENT_DIGESTS[stream, entry]
        by_path = sk.updates_by_path
        assert sum(by_path.values()) == sk.update_count
        if stream is _classic_protocol:
            assert by_path["fused"] == 0 and by_path["standalone"] > 0
        elif stream is _an_unfused_batch_between_fused_ones:
            assert by_path["fused"] >= 1 and by_path["standalone"] >= 1
        elif (stream, entry) == (_one_slot_two_owners, "pipeline"):
            # the batches in flight pin the sixteen slots: a placement
            # that refuses sends its batch the classic way
            assert by_path["fused"] > 0
        else:
            # an overflowed chunk's replay folds nothing again
            assert by_path["standalone"] == 0 and by_path["fused"] > 0
    finally:
        m.close()


def test_two_owners_of_one_slot_are_counted_apart_in_one_fused_stream():
    """The point estimates of _one_slot_two_owners' stream: sixty
    addresses of eight rows each through sixteen slots."""
    m, lines = _one_slot_two_owners("sync")
    try:
        sk = m.traffic_sketch
        for ip in sorted(set(_ips_of(lines)))[:12]:
            assert sk.estimate_ip(ip) == 8, ip
    finally:
        m.close()


def test_the_updates_counter_is_exported_by_path():
    m, lines = _an_unfused_batch_between_fused_ones("sync")
    try:
        text = _metrics(m)
        by_path = m.traffic_sketch.updates_by_path
        assert by_path == {"fused": 2, "standalone": 1}
        for path, v in by_path.items():
            assert f'banjax_sketch_updates_total{{path="{path}"}} {v}' in text
        assert "banjax_traffic_sketch_lines_total 192" in text
    finally:
        m.close()


def test_sketch_off_builds_and_dispatches_the_program_without_operands(
    monkeypatch,
):
    """`traffic_sketch_enabled: false`: the fused program takes no sketch
    state and no row hashes, and the stream's output is the CPU
    matcher's."""
    from banjax_tpu.matcher.kernels import fused_match_window as fmw

    built = []
    real = fmw.build_single_program

    def spy(*a, sketch=None, **kw):
        fn, *caps = real(*a, sketch=sketch, **kw)

        def counted(*args):
            built[-1][1].append(len(args))
            return fn(*args)

        built.append((sketch, []))
        return (counted, *caps)

    monkeypatch.setattr(fmw, "build_single_program", spy)
    lines = _stamp(NOW, _benign(100, 17, salt=5) + [
        ("7.7.5.1", _rest(f"/a{k}/wp.php?id=7{k}")) for k in range(6)])
    on, log_on = _build(TpuMatcher)
    off, log_off = _build(TpuMatcher, traffic_sketch_enabled=False)
    cpu, log_cpu = _build(CpuMatcher)
    try:
        assert off.traffic_sketch is None
        for m in (on, off):
            for s in range(0, len(lines), BATCH):
                m.consume_lines(lines[s:s + BATCH], now_unix=NOW)
        for ln in lines:
            cpu.consume_line(ln, now_unix=NOW)
        assert log_on.getvalue() == log_off.getvalue() == log_cpu.getvalue()
        assert log_cpu.getvalue()
        assert off._fw_pipeline.fused_batches == 2
        with_sk = [n for sk, ns in built if sk is not None for n in ns]
        without = [n for sk, ns in built if sk is None for n in ns]
        # state, chain, combined, n_real, host, slots, ts_s, ts_ns, live,
        # the window table's evicted slots and restore rows
        assert without and set(without) == {11}
        # ... and the sketch's state and the rows' hashes
        assert with_sk and set(with_sk) == {13}
        assert "banjax_sketch_updates_total{" not in _metrics(off)
    finally:
        on.close(), off.close()


def test_note_rule_events_returns_while_the_state_lock_is_held():
    """The state lock is held across a fused dispatch; the drain's rule
    pressure has a lock of its own and does not queue behind it."""
    sk = TrafficSketch(["a", "b"])
    done = threading.Event()
    with sk._lock:
        t = threading.Thread(
            target=lambda: (sk.note_rule_events(np.asarray([0, 1, 1])),
                            sk.note_assignments(["1.1.1.1"]),
                            done.set()))
        t.start()
        assert done.wait(5.0), "queued behind the state lock"
    t.join()
    pressure = sk.pull(force=True)["rule_pressure"]
    assert {r["rule"]: r["events"] for r in pressure} == {"a": 1, "b": 2}
    assert sk.pull(force=True)["sketch"]["candidates"] == 1


def test_the_candidate_log_is_not_compacted_at_batch_cadence(monkeypatch):
    """A flood's batches (thousands of distinct addresses each, a reader
    that never comes): the log stays bounded by dropping whole batches in
    the hashes' domain, and the exact compaction — a dict pass over
    strings — runs only where the candidates are read."""
    sk = TrafficSketch(["r"], max_candidates=512)
    compactions = []
    real = TrafficSketch._candidates_locked

    def spy(self):
        compactions.append(len(self._cand_log))
        return real(self)

    monkeypatch.setattr(TrafficSketch, "_candidates_locked", spy)
    rng = np.random.default_rng(5)
    longest = 0
    for step in range(400):
        ids = np.unique(rng.integers(0, 40_000, 300))
        sk.note_assignments([f"10.{i >> 8}.{i & 255}.9" for i in ids.tolist()])
        longest = max(longest, sk._cand_log_len)
    assert compactions == []
    assert longest <= 4 * sk.max_candidates + 300
    assert len(sk._cand_log) < 12
    # the reader's compaction finds what the per-address walk would hold
    cand = sk._candidates
    assert compactions == [len(cand) and compactions[0]]
    assert len(cand) == 512
    assert list(cand)[-1] == f"10.{ids[-1] >> 8}.{ids[-1] & 255}.9"


def test_a_log_of_few_addresses_stays_bounded_without_a_reader():
    """Many small batches over a handful of addresses: fewer distinct
    hashes than the bound, so no batch may be dropped unread — the log is
    folded the exact way once it is long, not at every batch."""
    sk = TrafficSketch(["r"], max_candidates=64)
    pool = [f"172.16.0.{i}" for i in range(20)]
    for step in range(600):
        sk.note_assignments([pool[(step + j) % 20] for j in range(5)])
        assert sk._cand_log_len <= 4 * 64 + 5
    assert set(sk._candidates) == set(pool)
    assert list(sk._candidates)[-1] == pool[(599 + 4) % 20]


@pytest.mark.parametrize("bound,pool,most,seed", [
    (16, 40, 30, 1),      # a pool near the bound: few batches may go
    (16, 2000, 12, 2),    # every batch new addresses: most batches go
    (64, 100, 90, 3),     # batches larger than the bound
    (64, 5000, 40, 4),
    (32, 20, 6, 5),       # fewer addresses than the bound: the exact fold
    (32, 300, 50, 6),
])
def test_a_log_trimmed_unread_holds_the_per_address_lru(
    bound, pool, most, seed,
):
    """No reader until the end: whatever the trim dropped on the way, the
    candidates are those of the per-address move-to-end walk."""
    import random
    from collections import OrderedDict

    rng = random.Random(seed)
    sk = TrafficSketch(["r"], width=64, depth=2, topk=4,
                       max_candidates=bound)
    names = [f"10.1.{i >> 8}.{i & 255}" for i in range(pool)]
    ref: "OrderedDict[str, int]" = OrderedDict()
    for _ in range(300):
        ips = rng.sample(names, rng.randrange(1, min(most, pool) + 1))
        sk.note_assignments(ips)
        for ip in ips:
            ref[ip] = hash_ip(ip)
            ref.move_to_end(ip)
        while len(ref) > bound:
            ref.popitem(last=False)
        assert sk._cand_log_len <= 4 * bound + most
    assert list(sk._candidates.items()) == list(ref.items())
