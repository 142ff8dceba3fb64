"""Traffic-sketch accuracy (ISSUE 8): count-min top-K recall and HLL
relative error fuzzed on skewed (Zipf) and all-distinct synthetic
feeds against exact host-side counts, the conservative-estimate
invariant, two owners of one window slot counted apart, and the
matcher-level sampling surface (pull throttle, /traffic summary shape)."""

import time

import numpy as np
import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.obs import registry
from banjax_tpu.obs.sketch import TrafficSketch, hash_ip, hll_estimate
from tests.mock_banner import MockBanner

RULES_YAML = """
regexes_with_rates:
  - decision: nginx_block
    rule: heavy
    regex: 'GET /attack.*'
    interval: 60
    hits_per_interval: 5
  - decision: nginx_block
    rule: quiet
    regex: 'POST /never.*'
    interval: 60
    hits_per_interval: 5
"""


def _sketch(**kw):
    kw.setdefault("depth", 4)
    kw.setdefault("width", 8192)
    kw.setdefault("hll_p", 12)
    kw.setdefault("pull_seconds", 0.0)
    kw.setdefault("topk", 32)
    kw.setdefault("max_candidates", 8192)
    return TrafficSketch(["heavy", "quiet"], **kw)


def _row_hashes(ips):
    return np.asarray([hash_ip(ip) for ip in ips], dtype=np.uint32)


def _feed_ids(sketch, ids, pool, batch=1024):
    """Stream integer ip-ids through the sketch the way the matcher
    does: a batch's distinct addresses noted as candidates, then one
    row-level update keyed on the rows' address hashes."""
    hashes = _row_hashes(pool)
    for s in range(0, len(ids), batch):
        chunk = ids[s : s + batch]
        distinct = list(dict.fromkeys(chunk.tolist()))  # first appearance
        sketch.note_assignments(
            [pool[i] for i in distinct], hashes[distinct]
        )
        sketch.update(hashes[chunk], len(chunk))


def test_zipf_topk_recall_and_conservative_estimates():
    """The acceptance shape: top-K recall >= 0.9 at k=32 on a Zipf feed
    vs exact counts, and every count-min point estimate conservative
    (never below the true count)."""
    rng = np.random.default_rng(11)
    n_pool = 4096
    pool = [f"10.{i >> 16 & 255}.{i >> 8 & 255}.{i & 255}" for i in range(n_pool)]
    ids = np.minimum(rng.zipf(1.15, 131072) - 1, n_pool - 1)
    exact = np.bincount(ids, minlength=n_pool)

    sk = _sketch()
    _feed_ids(sk, ids, pool)
    summary = sk.pull(force=True)
    assert summary["lines_total"] == len(ids)

    k = 32
    kth = np.sort(exact)[-k]
    # ties at the boundary make "the" true top-K ambiguous: a predicted
    # entry is a hit when its TRUE count reaches the kth-largest count
    predicted = [row["ip"] for row in summary["top"][:k]]
    assert len(predicted) == k
    ip_to_id = {ip: i for i, ip in enumerate(pool)}
    hits = sum(1 for ip in predicted if exact[ip_to_id[ip]] >= kth)
    recall = hits / k
    assert recall >= 0.9, f"top-{k} recall {recall} < 0.9"

    # conservative: estimates never undercount (count-min invariant)
    for row in summary["top"]:
        assert row["est_count"] >= exact[ip_to_id[row["ip"]]]
    # the single heaviest source is ranked first
    assert ip_to_id[predicted[0]] == int(np.argmax(exact))
    # heavy-hitter share is its estimate over the folded lines
    assert summary["heavy_hitter_share"] == pytest.approx(
        summary["top"][0]["est_count"] / len(ids), abs=1e-3
    )

    # HLL on the skewed feed: distinct present, not line volume
    true_distinct = int((exact > 0).sum())
    est = summary["distinct_ips_estimate"]
    assert abs(est - true_distinct) / true_distinct < 0.15


def test_all_distinct_hll_relative_error():
    """The all-distinct worst case (rotating-proxy shape): every line a
    new source; HLL must track cardinality within a few percent while
    count-min sees no heavy hitter."""
    n = 32768
    pool = [f"203.{i >> 16 & 255}.{i >> 8 & 255}.{i & 255}" for i in range(n)]
    ids = np.arange(n)
    sk = _sketch()
    _feed_ids(sk, ids, pool)
    summary = sk.pull(force=True)
    est = summary["distinct_ips_estimate"]
    assert abs(est - n) / n < 0.15, f"HLL estimate {est} vs true {n}"
    # no source sent more than one line; conservative estimates stay small
    assert summary["top"][0]["est_count"] <= 32


def test_two_owners_of_one_slot_are_counted_apart():
    """A window slot evicted and handed to a new IP counts for the NEW
    IP: a row is keyed on its own address's hash, whatever slot the
    address holds (through the matcher, with real eviction churn:
    test_sketch_fused.py)."""
    sk = _sketch(width=1024)
    sk.note_assignments(["1.1.1.1"])
    sk.update(_row_hashes(["1.1.1.1"] * 10), 10)
    # its slot evicted and handed to 2.2.2.2
    sk.note_assignments(["2.2.2.2"])
    sk.update(_row_hashes(["2.2.2.2"] * 5), 5)
    assert sk.estimate_ip("1.1.1.1") >= 10
    assert sk.estimate_ip("2.2.2.2") >= 5
    # conservative but not conflated (different hashes, different buckets
    # with overwhelming probability at width 1024 x depth 4)
    assert sk.estimate_ip("2.2.2.2") < 15


def test_candidate_lru_is_bounded():
    sk = _sketch(max_candidates=64)
    pool = [f"9.9.{i >> 8}.{i & 255}" for i in range(512)]
    _feed_ids(sk, np.arange(512), pool, batch=128)
    assert len(sk._candidates) <= 64
    # the most recent IPs are the ones retained
    assert pool[-1] in sk._candidates


def test_rule_pressure_is_exact_from_events():
    sk = _sketch()
    sk.note_rule_events([0, 0, 1, 0])
    sk.note_rule_events(iter([1]))
    summary = sk.pull(force=True)
    pressure = {r["rule"]: r["events"] for r in summary["rule_pressure"]}
    assert pressure == {"heavy": 3, "quiet": 2}
    # out-of-range ids are dropped, not crashed on
    sk.note_rule_events([99, -3])
    assert sk.pull(force=True)["rule_pressure"][0]["events"] == 3


def test_pull_is_throttled_to_the_sampling_interval():
    sk = _sketch(pull_seconds=3600.0)
    sk.note_assignments(["4.4.4.4"])
    sk.update(_row_hashes(["4.4.4.4"] * 8), 8)
    first = sk.pull()
    assert sk.pull_count == 1
    sk.update(_row_hashes(["4.4.4.4"] * 8), 8)
    # within the interval: the cached summary is shared, no new d2h
    assert sk.pull() is first
    assert sk.pull_count == 1
    # force refreshes regardless (the incident-bundle path)
    forced = sk.incident_snapshot()
    assert sk.pull_count == 2
    assert forced["enabled"] is True
    assert forced["lines_total"] == 16


def test_hll_estimate_small_range_correction():
    regs = np.zeros(4096, dtype=np.int32)
    assert hll_estimate(regs) == 0.0
    regs[:100] = 1
    est = hll_estimate(regs)
    assert 50 < est < 300  # linear-counting regime, loose sanity


def test_hash_ip_is_stable_and_32bit():
    h = hash_ip("192.0.2.7")
    assert h == hash_ip("192.0.2.7")
    assert 0 <= h <= 0xFFFF_FFFF
    assert h != hash_ip("192.0.2.8")


# ---- matcher-level integration -------------------------------------------


def _matcher(**cfg_over):
    cfg = config_from_yaml_text(RULES_YAML)
    cfg.matcher_device_windows = True
    for k, v in cfg_over.items():
        setattr(cfg, k, v)
    return TpuMatcher(
        cfg, MockBanner(), StaticDecisionLists(cfg), RegexRateLimitStates()
    ), cfg


def test_matcher_sketch_sees_skewed_flood():
    """Replayed skewed flood through the real fused matcher path: the
    hot IP tops /traffic/top's heap, the distinct estimate tracks the
    pool, and the attacked rule carries the pressure."""
    m, _ = _matcher()
    assert m.traffic_sketch is not None
    now = time.time()
    lines = []
    for i in range(600):
        if i % 3 == 0:
            ip = "66.66.66.66"                      # the heavy hitter
            lines.append(
                f"{now:.6f} {ip} GET h.com GET /attack{i} HTTP/1.1 ua -"
            )
        else:
            ip = f"10.0.{(i // 3) % 4}.{(i // 3) % 50}"
            lines.append(
                f"{now:.6f} {ip} GET h.com GET /page{i} HTTP/1.1 ua -"
            )
    m.consume_lines(lines, now)
    summary = m.traffic_sketch.pull(force=True)
    assert summary["lines_total"] == 600
    assert summary["top"][0]["ip"] == "66.66.66.66"
    assert summary["top"][0]["est_count"] >= 200
    pressure = {r["rule"]: r["events"] for r in summary["rule_pressure"]}
    assert pressure.get("heavy", 0) == 200
    assert "quiet" not in pressure
    true_distinct = len({l.split(" ")[1] for l in lines})
    assert (
        abs(summary["distinct_ips_estimate"] - true_distinct)
        / true_distinct < 0.2
    )


def test_matcher_sketch_disabled_by_config():
    m, _ = _matcher(traffic_sketch_enabled=False)
    assert m.traffic_sketch is None


def test_traffic_keys_on_snapshot_and_registry():
    m, _ = _matcher()
    now = time.time()
    m.consume_lines(
        [f"{now:.6f} 7.7.7.{i % 9} GET h.com GET /q HTTP/1.1" for i in range(64)],
        now,
    )
    snap = m.stats.peek(m.device_windows, m)
    for key in ("TrafficSketchLines", "TrafficDistinctIpsEst",
                "TrafficHeavyHitterShare", "TrafficSketchPullBytes",
                "TrafficSketchPullAgeSeconds"):
        assert key in snap, key
        assert registry.is_declared_line_key(key), key
    assert snap["TrafficSketchLines"] == 64
    assert snap["TrafficSketchPullBytes"] > 0


def test_pull_records_a_trace_span():
    from banjax_tpu.obs import trace

    tracer = trace.configure(enabled=True, ring_size=64)
    try:
        sk = _sketch()
        sk.update(np.zeros(4, dtype=np.uint32), 4)
        sk.pull(force=True)
        names = [s["name"] for s in tracer.snapshot()]
        assert "sketch-pull" in names
    finally:
        trace.configure(enabled=False)
