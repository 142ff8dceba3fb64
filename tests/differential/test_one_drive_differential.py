"""One drive of a batch through the matcher, two callers (PR 46).

`TpuMatcher.consume_lines` runs its batches through `pipeline_begin /
pipeline_submit / pipeline_collect / pipeline_finish` on its own thread;
the scheduler calls the same four from its stage threads.  Here a twin of
the matcher under test is driven through the four BY HAND, batch by
batch, and the two must agree line for line — `ConsumeLineResult`s,
ban-log bytes, window state (device counters and the host shadow), no pin
and no order turn left — over every way a batch can go:

  * fused, one chunk; a call above `matcher_batch_lines` (several
    batches); a batch cut into several chunks for its long rows;
  * the classic `pend`: a rule only the host's `re` decides, the fused
    path switched off (`consume_lines_serial`), no device windows at all;
  * a chunk whose program overflows and replays classically at its
    settle;
  * more distinct addresses than window slots: placement refuses, nothing
    is dispatched, the classic replay halves the batch;
  * the slot-admission gate with a threshold of 2 — refused rows in a
    batch of one chunk and of several (the pass's probe alone) — and the
    gate failing open: the pass raises, the whole batch is admitted, and
    the results are the ungated engine's.

And the synchronous entry's failure contract where the two callers
differ: a chunk that fails at its settle (`matcher.resolve`) is one
breaker failure and a CPU rerun of the batch, with nothing leaked.
"""

import io
import random
import time

import pytest

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import Banner
from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.resilience import failpoints
from tests import shadow_access
from tests.differential.test_tpu_matcher import CONFIG_YAML, result_key

BATCH = 64

HOST_RULE_YAML = CONFIG_YAML.replace("global_decision_lists:", r"""
  unsupported.com:
    - decision: challenge
      hits_per_interval: 1
      interval: 5
      regex: '(GET /a)+x'
      rule: "group-repeat"
global_decision_lists:""")


def _stream(now, n, seed, long_share=0.0, one_shot=0.30):
    """Returning clients that cross rule1's and rule2's limits, one-shot
    addresses that match rule1 once (`one_shot` of the lines: what a gate
    with a threshold of 2 refuses), instant per-site blocks, the
    allowlisted address, garbage, a line too old, lines for the rule only
    the host decides — and, for `long_share` of the lines, a request
    string past the short width."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        k = rng.random()
        pad = ""
        if rng.random() < long_share:
            pad = "/" + "abcdefghij" * rng.randrange(30, 90)
        if rng.random() < one_shot:
            ip, req = f"41.{i >> 8}.0.{i & 255}", f"GET example.com GET /once{pad}"
        elif k < 0.45:
            ip, req = f"40.0.0.{rng.randrange(12)}", f"GET example.com GET /a{pad}"
        elif k < 0.70:
            ip, req = f"42.0.0.{rng.randrange(5)}", "POST example.com POST /s"
        elif k < 0.76:
            ip, req = f"43.{i >> 8}.0.{i & 255}", "GET per-site.com GET /blockme"
        elif k < 0.82:
            ip, req = f"44.0.0.{rng.randrange(3)}", "GET unsupported.com GET /aGET /ax"
        elif k < 0.85:
            ip, req = "12.12.12.12", "GET example.com GET /allowed"
        elif k < 0.88:
            out.append("short garbage")
            continue
        elif k < 0.91:
            out.append(f"{now - 100:f} 45.0.0.1 GET example.com GET /old HTTP/1.1 ua -")
            continue
        else:
            ip, req = f"46.{i >> 8}.0.{i & 255}", "GET news.net GET /benign"
        out.append(f"{now:f} {ip} {req} HTTP/1.1 ua -")
    return out


def _build(cls=TpuMatcher, yaml_text=CONFIG_YAML, **over):
    cfg = config_from_yaml_text(yaml_text)
    for k, v in {
        "matcher_device_windows": True, "matcher_window_capacity": 256,
        "matcher_batch_lines": BATCH, "traffic_sketch_enabled": True,
        "warm_tier_enabled": True, "warm_tier_capacity": 4096,
        # rule1 hits most rows: room for all of them as candidates
        "matcher_prefilter_cand_frac": 1.0, **over,
    }.items():
        setattr(cfg, k, v)
    ban_log = io.StringIO()
    banner = Banner(DynamicDecisionLists(start_sweeper=False), ban_log,
                    io.StringIO(), ipset_instance=None)
    m = cls(cfg, banner, StaticDecisionLists(cfg), RegexRateLimitStates())
    return m, ban_log


def _by_hand(m, lines, now, fused_ok=True):
    """`lines` through the four stages as the scheduler calls them — fresh
    buffers, the drain's finish — in batches of matcher_batch_lines."""
    out = []
    for row0 in range(0, len(lines), BATCH):
        state = m.pipeline_begin(lines[row0:row0 + BATCH], now)
        if not fused_ok:
            state.pop("fused_eligible", None)
        m.pipeline_submit(state, now)
        m.pipeline_collect(state)
        results, n_stale = m.pipeline_finish(state, now)
        assert n_stale == 0  # one clock: old_line is the gate's alone
        out.extend(results)
    return out


def _break_the_pass(m, times):
    """The next `times` gate passes raise (probe and whole pass alike)."""
    dw, left = m.device_windows, [times]

    def flaky(real):
        def call(*a, **kw):
            if left[0] and (real is probe or kw.get("gate")):
                left[0] -= 1
                raise RuntimeError("injected: the pass fails")
            return real(*a, **kw)
        return call

    probe = dw.probe_addresses
    dw.probe_addresses = flaky(probe)
    dw.resolve_addresses = flaky(dw.resolve_addresses)
    return left


def _settled(m):
    """No pin and no order turn outlives a call."""
    if m.device_windows is not None:
        assert (m.device_windows._pin_counts == 0).all()
    if m._fw_pipeline is not None:
        assert m._fw_pipeline.idle()
    assert m._drain_window_batches == 0


def _window_state(m):
    dw = m.device_windows
    if dw is None:
        return m.rate_limit_states.format_states()
    return dw.format_states(), shadow_access.shadow(dw)


CASES = {
    # name: (build overrides, stream kwargs, calls of how many lines,
    #        what the case must have exercised)
    "one-fused-chunk": ({}, {}, [60, 60, 60], "fused"),
    "above-batch-lines": ({}, {}, [300, 200], "fused"),
    "cut-for-long-rows": (
        {}, {"long_share": 0.5}, [64, 64, 64], "cut"),
    "chunks-overflow": (
        {"matcher_prefilter_cand_frac": 0.125}, {}, [60, 200], "overflow"),
    "host-evaluated-rule": (
        {"yaml_text": HOST_RULE_YAML}, {}, [60, 200], "classic"),
    "fused-off": ({}, {}, [60, 200], "serial"),
    "host-windows": (
        {"matcher_device_windows": False}, {}, [60, 200], "classic"),
    "more-addresses-than-slots": (
        {"matcher_window_capacity": 16}, {"one_shot": 0.7}, [60, 60, 120],
        "refused-placement"),
    "gate-one-chunk": (
        {"slot_admission_enabled": True, "slot_admission_min_estimate": 2,
         "matcher_window_capacity": 32}, {}, [60] * 6, "gate"),
    "gate-several-chunks": (
        {"slot_admission_enabled": True, "slot_admission_min_estimate": 2,
         "matcher_window_capacity": 32}, {"long_share": 0.5}, [64] * 6,
        "gate-cut"),
    "gate-above-batch-lines-classic": (
        {"slot_admission_enabled": True, "slot_admission_min_estimate": 2,
         "matcher_window_capacity": 32, "yaml_text": HOST_RULE_YAML}, {},
        [200, 160], "gate-classic"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_consume_lines_is_the_four_stages_called_in_turn(case):
    over, stream_kw, calls, exercised = CASES[case]
    now = time.time()
    lines = _stream(now, sum(calls), seed=len(case), **stream_kw)
    sync, sync_log = _build(**over)
    hand, hand_log = _build(**over)
    fused_ok = exercised != "serial"
    probes = []  # the pass's probe alone, asked by the synchronous drive
    if sync.device_windows is not None:
        real = sync.device_windows.probe_addresses
        sync.device_windows.probe_addresses = (
            lambda *a, **kw: probes.append(len(a[0])) or real(*a, **kw))
    at = 0
    for n in calls:
        part = lines[at:at + n]
        at += n
        if fused_ok:
            got = sync.consume_lines(part, now_unix=now)
        else:
            got = sync.consume_lines_serial(part, now_unix=now)
        want = _by_hand(hand, part, now, fused_ok)
        assert len(got) == len(want) == len(part)
        for i, (a, b) in enumerate(zip(got, want)):
            assert result_key(a) == result_key(b), (case, at, i, part[i][:90])
        assert sync_log.getvalue() == hand_log.getvalue()
        assert _window_state(sync) == _window_state(hand)
        _settled(sync), _settled(hand)
    assert sync_log.getvalue(), "the stream bans"
    assert sync.fallback_batches == 0 and sync.breaker._failures == 0

    # the case went the way its name says, on both matchers alike
    fw, dw = sync._fw_pipeline, sync.device_windows
    for m in (sync, hand):
        assert m.pipelined_fused_chunks == sync.pipelined_fused_chunks
        assert m.pipelined_fused_fallbacks == sync.pipelined_fused_fallbacks
    # a synchronous call is recorded once, whatever it was cut into
    assert sync.stats.batches_total == len(calls)
    assert sync.stats.lines_total == hand.stats.lines_total == len(lines)
    if exercised in ("fused", "cut"):
        assert sync.pipelined_fused_chunks >= len(lines) // BATCH
    if exercised in ("cut", "gate-cut"):
        assert fw.overflow_causes["long_rows"] > 0
    if exercised == "overflow":
        # more candidates than the compaction holds: the program commits
        # nothing and the chunk replays classically at its settle
        assert fw.overflow_causes["candidates"] > 0
        assert sync.pipelined_fused_fallbacks == fw.fallback_batches > 0
    if exercised in ("classic", "serial", "gate-classic"):
        assert sync.pipelined_fused_chunks == 0
    if exercised == "refused-placement":
        # the later calls hold more distinct addresses than the table:
        # nothing of them is dispatched fused, the classic replay halves
        assert sync.pipelined_fused_chunks < len(calls)
        assert dw.eviction_count > 0
    # the probe alone is for a gated batch that is not one fused chunk
    assert bool(probes) == (exercised in ("gate-cut", "gate-classic"))
    if exercised.startswith("gate"):
        assert sync._admission_min_estimate == 2
        assert dw.slot_refusals > 0 and dw.sketch_admissions > 0
        assert dw.slot_refusals == hand.device_windows.slot_refusals
        assert dw.warm_refills > 0  # a refused address came back, admitted


@pytest.mark.parametrize("shape", ["one-chunk", "several-chunks", "classic"])
def test_a_failing_gate_admits_the_whole_batch(shape):
    """The gate's fail-open has one place (_resolve_submit): the pass
    raises — its probe alone for a batch of several chunks or a classic
    one, the whole pass for a batch of one fused chunk — the batch is
    admitted whole, and what it bans is what the ungated engine bans."""
    over = {"slot_admission_enabled": True, "slot_admission_min_estimate": 2,
            "matcher_window_capacity": 512}
    if shape == "classic":
        over["yaml_text"] = HOST_RULE_YAML
    now = time.time()
    lines = _stream(now, 256, seed=11,
                    long_share=0.5 if shape == "several-chunks" else 0.0)
    ungated, ungated_log = _build(**{**over, "slot_admission_enabled": False})
    want = ungated.consume_lines(lines, now_unix=now)
    m, log = _build(**over)
    left = _break_the_pass(m, times=10**6)
    got = m.consume_lines(lines, now_unix=now)
    assert left[0] < 10**6, "no pass was asked"
    for i, (a, b) in enumerate(zip(got, want)):
        assert result_key(a) == result_key(b), (i, lines[i][:90])
    assert log.getvalue() == ungated_log.getvalue() and log.getvalue()
    assert m.device_windows.slot_refusals == 0
    assert _window_state(m) == _window_state(ungated)
    assert m.fallback_batches == 0 and m.breaker._failures == 0
    _settled(m)


@pytest.fixture()
def no_failpoints():
    failpoints.disarm()
    yield
    failpoints.disarm()


def test_a_chunk_failing_at_its_settle_is_the_synchronous_batchs_failure(
        no_failpoints):
    """Under the scheduler a chunk that fails at its settle costs its own
    lines and the stream goes on (tests/faults/test_single_kernel_faults);
    under the synchronous entry the same failure leaves consume_lines'
    drive: one breaker failure, the batch re-run on the CPU reference,
    and the chunks behind the failing one given up — no pin, no order
    turn left for a later batch to wait on."""
    now = time.time()
    lines = _stream(now, 3 * BATCH, seed=5)
    cpu, cpu_log = _build(CpuMatcher)
    want = cpu.consume_lines(lines[:BATCH], now_unix=now)
    m, log = _build()
    failpoints.arm("matcher.resolve", count=1)
    got = m.consume_lines(lines[:BATCH], now_unix=now)
    assert failpoints.fired_count("matcher.resolve") == 1
    assert m.breaker._failures == 1 and m.fallback_batches == 1
    assert m.pipelined_fused_chunks == 0
    _settled(m)
    for i, (a, b) in enumerate(zip(got, want)):
        assert result_key(a) == result_key(b), (i, lines[i][:90])
    assert log.getvalue() == cpu_log.getvalue() and log.getvalue()
    # and the device path serves the next call: nothing was left held
    m.consume_lines(lines[BATCH:], now_unix=now)
    assert m.pipelined_fused_chunks == 2 and m.breaker._failures == 0
    _settled(m)
