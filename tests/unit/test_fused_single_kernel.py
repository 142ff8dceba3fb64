"""Single-kernel fused match+window path (matcher/kernels/
fused_match_window.py + matcher/fused_windows.py), interpret-mode on
CPU — tier-1.

Covers the kernel itself (the Pallas window-scan vs the lax.scan it must
reproduce bit-for-bit), the threshold-fire edges of the fixed-window
recurrence, the in-kernel overflow flag routing to the classic fallback,
the submit-time live-mask staleness cut, chain reseeding after an
overflow burst, the scan selftest's downgrade to the classic protocol,
and config files that still carry the three removed protocol keys."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.matcher import windows as W
from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.matcher.kernels import fused_match_window as fmw
from banjax_tpu.matcher.runner import TpuMatcher
from banjax_tpu.resilience.health import HealthRegistry, HealthStatus
from banjax_tpu.scenarios import synth
from tests.mock_banner import MockBanner


def _rules_yaml(patterns, hits=3, interval=20):
    return yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"r{i}", "regex": p, "interval": interval,
             "hits_per_interval": hits, "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })


def _mk(cls, yaml_text, health=None, **ov):
    cfg = config_from_yaml_text(yaml_text)
    for k, v in ov.items():
        setattr(cfg, k, v)
    banner = MockBanner()
    kwargs = {"health": health} if health is not None else {}
    return cls(
        cfg, banner, StaticDecisionLists(cfg), RegexRateLimitStates(),
        **kwargs,
    ), banner


def _key(res):
    return [
        (x.rule_name, x.regex_match, x.skip_host, x.seen_ip,
         None if x.rate_limit_result is None else
         (int(x.rate_limit_result.match_type), x.rate_limit_result.exceeded))
        for x in res.rule_results
    ]


# ---------------------------------------------------------------------------
# the Pallas window-scan kernel itself
# ---------------------------------------------------------------------------


def test_window_scan_kernel_matches_lax_scan_randomized():
    """The interpret-mode kernel must reproduce the XLA lax.scan over
    windows._window_step bit-for-bit — including boundaries, invalid
    segments, pads, restarts, and the reset-to-0-on-exceed quirk."""
    rng = np.random.default_rng(123)
    for E in (8, 64, 256):
        pad = np.zeros(E, dtype=bool)
        pad[rng.integers(0, E, max(1, E // 5))] = True
        xs = (
            jnp.asarray(rng.integers(0, 2, E).astype(bool)),
            jnp.asarray(rng.integers(0, 9, E).astype(np.int32)),
            jnp.asarray(rng.integers(0, 100, E).astype(np.int32)),
            jnp.asarray(rng.integers(0, 10**9, E).astype(np.int32)),
            jnp.asarray(rng.integers(0, 2, E).astype(bool)),
            jnp.asarray(rng.integers(0, 120, E).astype(np.int32)),
            jnp.asarray(rng.integers(0, 10**9, E).astype(np.int32)),
            jnp.asarray(rng.integers(0, 5, E).astype(np.int32)),
            jnp.asarray(rng.integers(0, 30, E).astype(np.int32)),
            jnp.asarray(rng.integers(0, 10**9, E).astype(np.int32)),
            jnp.asarray(pad),
        )
        init = (jnp.int32(0), jnp.int32(0), jnp.int32(0))
        _, want = jax.lax.scan(W._window_step, init, xs)
        got = fmw.window_scan(True)(init, xs)
        for name, w, g in zip(
            ("hits", "ss", "sns", "mtype", "exceeded"), want, got
        ):
            assert np.array_equal(np.asarray(w), np.asarray(g)), (E, name)


def test_scan_selftest_passes_and_is_cheap():
    fmw.scan_selftest(True)       # the matcher-construction gate
    fmw.scan_selftest(True, E=8)  # smallest shape


def test_window_scan_under_jit():
    """The kernel must compose inside an outer jit — that is how the
    single program uses it (windows._apply_core scan_fn)."""
    E = 32
    xs = tuple(
        jnp.asarray(np.zeros(E, dtype=np.int32)) for _ in range(11)
    )

    @jax.jit
    def run(xs):
        return fmw.window_scan(True)(None, xs)

    out = run(xs)
    assert all(np.asarray(o).shape == (E,) for o in out)


# ---------------------------------------------------------------------------
# threshold-fire edges (driven through the full single-kernel matcher)
# ---------------------------------------------------------------------------


def _edge_pair(interval, hits):
    y = _rules_yaml([r"GET /edge.*"], hits=hits, interval=interval)
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(TpuMatcher, y, matcher_device_windows=True,
                  matcher_prefilter_cand_frac=1.0)
    assert tpu.describe()["fused_protocol"] == "single-kernel"
    return cpu, cb, tpu, tb


@pytest.mark.parametrize("offsets,hits", [
    # exactly-at-interval is INSIDE (window restart is strictly greater
    # than interval); epsilon past it restarts the window
    ((0.0, 5.0, 5.25), 2),
    # hits_per_interval=0: the very first hit fires, counter resets to 0
    ((0.0, 0.25, 0.5), 0),
    # hits=1: every second hit inside the window fires
    ((0.0, 0.25, 0.5, 0.75), 1),
])
def test_threshold_fire_edges(offsets, hits):
    cpu, cb, tpu, tb = _edge_pair(interval=5, hits=hits)
    now = 1_700_000_000.0  # integer-second base: offsets stay float-exact
    lines = [
        f"{now + off:.6f} 6.6.6.6 GET h.com GET /edge{k} HTTP/1.1 ua -"
        for k, off in enumerate(offsets)
    ]
    want = [cpu.consume_line(l, now + 9) for l in lines]
    got = tpu.consume_lines(lines, now + 9)
    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans and cb.regex_ban_logs == tb.regex_ban_logs
    assert cpu.rate_limit_states.format_states() == \
        tpu.device_windows.format_states()
    assert tpu._fw_pipeline.fused_batches > 0  # really took the single kernel


# ---------------------------------------------------------------------------
# overflow flags → classic fallback (in-kernel gate)
# ---------------------------------------------------------------------------


def test_event_overflow_flag_routes_to_classic_fallback(monkeypatch):
    """More window events than the program's event capacity (which follows
    rows x always-columns up to a cap; the cap is lowered here so that the
    `.*` rule overflows it): the kernel's gate drops every state write (the
    donated state passes through untouched) and the chunk replays
    classically — output identical to the CPU oracle."""
    from banjax_tpu.matcher import prefilter

    monkeypatch.setattr(prefilter, "_MAX_EVENT_CAPACITY", 64)
    patterns = synth.generate_rules(30, seed=33) + [r".*"]
    now = time.time()
    rests = synth.generate_lines(256, patterns[:-1], seed=3, attack_rate=0.1)
    lines = [
        f"{now + i * 0.0005:.6f} 10.9.{i % 24}.1 {r}"
        for i, r in enumerate(rests)
    ]
    y = _rules_yaml(patterns)
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(TpuMatcher, y, matcher_device_windows=True,
                  matcher_batch_lines=256, matcher_prefilter_cand_frac=1.0)
    assert tpu.describe()["fused_protocol"] == "single-kernel"
    tpu.device_windows.max_events = max(tpu.compiled.n_rules, 64)
    want = [cpu.consume_line(l, now + 1) for l in lines]
    got = tpu.consume_lines(lines, now + 1)
    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans
    assert tpu._fw_pipeline.fallback_batches > 0
    assert tpu._fw_pipeline.overflow_causes["events"] > 0


def test_candidate_overflow_flag_with_tight_slot_capacity():
    """Candidate overflow (all-matching burst) composed with a slot table
    too small for the distinct-IP load (eviction churn + split retries):
    the overflow flag routes to the single-stage recompute and spill
    stays lossless — byte-identical to the oracle."""
    patterns = synth.generate_rules(20, seed=36)
    now = time.time()
    rests = synth.generate_lines(300, patterns, seed=10, attack_rate=1.0)
    lines = [
        f"{now + i * 0.0005:.6f} 10.9.{i % 90}.1 {r}"
        for i, r in enumerate(rests)
    ]
    y = _rules_yaml(patterns)
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(
        TpuMatcher, y, matcher_device_windows=True,
        matcher_batch_lines=64, matcher_prefilter_cand_frac=1.0 / 64,
        matcher_window_capacity=16,
    )
    assert tpu.describe()["fused_protocol"] == "single-kernel"
    want = [cpu.consume_line(l, now + 1) for l in lines]
    # in pieces the 16 slots can hold: a batch with more distinct
    # addresses than the table has slots dispatches nothing fused (its
    # placement is refused and the classic replay halves it)
    got = [
        r for i in range(0, len(lines), 16)
        for r in tpu.consume_lines(lines[i : i + 16], now + 1)
    ]
    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans
    assert tpu._fw_pipeline.fallback_batches > 0
    assert tpu.device_windows.eviction_count > 0
    assert cpu.rate_limit_states.format_states() == \
        tpu.device_windows.format_states()


def test_chain_reseeds_after_quiescence():
    """An overflow poisons the device-side ok chain for in-flight chunks;
    once the burst drains (quiescence), the chain reseeds and the next
    batch commits through the single kernel again."""
    patterns = [r"POST /x[a-z0-9]*"]
    now = time.time()
    y = _rules_yaml(patterns, hits=50)
    tpu, _ = _mk(TpuMatcher, y, matcher_device_windows=True,
                 matcher_batch_lines=64,
                 matcher_prefilter_cand_frac=1.0 / 64)
    assert tpu.describe()["fused_protocol"] == "single-kernel"
    flood = [
        f"{now:.6f} 7.7.7.{i % 9} POST h.com POST /x{i} HTTP/1.1 ua -"
        for i in range(128)
    ]
    tpu.consume_lines(flood, now)  # every chunk overflows candidates
    assert tpu._fw_pipeline.fallback_batches > 0
    benign = [
        f"{now:.6f} 8.8.8.{i % 9} GET h.com GET /quiet{i} HTTP/1.1 ua -"
        for i in range(64)
    ]
    before = tpu._fw_pipeline.fused_batches
    tpu.consume_lines(benign, now)  # quiescent start → fresh chain
    assert tpu._fw_pipeline.fused_batches > before, "chain never reseeded"


# ---------------------------------------------------------------------------
# live-mask staleness (submit-time cut on the split protocol)
# ---------------------------------------------------------------------------


def test_live_mask_staleness_at_submit():
    """The single-kernel analog of the drain-stale test: the 10 s cutoff
    is evaluated at SUBMIT (the kernel's live-mask input) — rows already
    old there contribute no event and no state write, fresh rows in the
    SAME chunk commit normally, and the drain replays from the carried
    mask (no drain-time re-cut)."""
    patterns = [r"GET /blockme.*"]
    now = time.time()
    y = _rules_yaml(patterns, hits=0, interval=1)
    m, banner = _mk(TpuMatcher, y, matcher_device_windows=True,
                    matcher_prefilter_cand_frac=1.0)
    assert m.describe()["fused_protocol"] == "single-kernel"
    old = [
        f"{now - 8:.6f} 9.9.9.{i} GET h.com GET /blockme HTTP/1.1 ua -"
        for i in range(5)
    ]
    fresh = [
        f"{now:.6f} 8.8.8.{i} GET h.com GET /blockme HTTP/1.1 ua -"
        for i in range(5)
    ]
    state = m.pipeline_begin(old + fresh, now)
    assert state.get("fused_eligible")
    m.pipeline_submit(state, now=now + 3)  # old rows now 11 s stale
    assert state.get("fused"), "single-kernel entries missing"
    m.pipeline_collect(state)
    results, n_stale = m.pipeline_finish(state, now + 3)
    assert n_stale == 5
    assert all(r.old_line and not r.rule_results for r in results[:5])
    assert all(not r.old_line and r.rule_results for r in results[5:])
    view = m.device_windows.format_states()
    assert "9.9.9.0" not in view and "8.8.8.0" in view
    assert len(banner.bans) == 5  # hits=0: every fresh line fires once


def test_fully_stale_chunk_commits_nothing():
    patterns = [r"GET /blockme.*"]
    now = time.time()
    y = _rules_yaml(patterns, hits=0, interval=1)
    m, banner = _mk(TpuMatcher, y, matcher_device_windows=True,
                    matcher_prefilter_cand_frac=1.0)
    lines = [
        f"{now - 8:.6f} 9.9.9.{i} GET h.com GET /blockme HTTP/1.1 ua -"
        for i in range(8)
    ]
    state = m.pipeline_begin(lines, now)
    m.pipeline_submit(state, now=now + 5)
    m.pipeline_collect(state)
    results, n_stale = m.pipeline_finish(state, now + 5)
    assert n_stale == 8
    assert all(r.old_line for r in results)
    assert banner.bans == []
    assert len(m.device_windows) == 0
    # a later fresh batch still drains (no leaked turns/pins)
    fresh = [
        f"{now + 5:.6f} 8.8.8.{i} GET h.com GET /blockme HTTP/1.1 ua -"
        for i in range(4)
    ]
    state2 = m.pipeline_begin(fresh, now + 5)
    m.pipeline_submit(state2, now=now + 5)
    m.pipeline_collect(state2)
    results2, _ = m.pipeline_finish(state2, now + 5)
    assert all(r.rule_results for r in results2)
    assert (m.device_windows._pin_counts == 0).all()


# ---------------------------------------------------------------------------
# config resolution + downgrade note
# ---------------------------------------------------------------------------


def test_single_kernel_engages_on_cpu():
    y = _rules_yaml([r"GET /a.*"])
    health = HealthRegistry()
    m, _ = _mk(TpuMatcher, y, health=health, matcher_device_windows=True)
    assert m._fw_pipeline is not None  # the scan runs interpreted on CPU
    assert m.describe()["fused_protocol"] == "single-kernel"
    comp = health.get("matcher-single-kernel")
    assert comp is not None
    assert comp.effective_status()[0] == HealthStatus.HEALTHY


def _boom(*a, **k):
    raise RuntimeError("synthetic lowering failure")


def test_downgrade_leaves_health_note(monkeypatch):
    """A window-scan kernel that cannot lower must downgrade to the
    classic protocol and leave a DEGRADED note on the health registry —
    never fail matcher construction."""
    monkeypatch.setattr(fmw, "scan_selftest", _boom)
    y = _rules_yaml([r"GET /a.*"])
    health = HealthRegistry()
    m, _ = _mk(TpuMatcher, y, health=health, matcher_device_windows=True)
    assert m._fw_pipeline is None
    comp = health.get("matcher-single-kernel")
    status, detail, _ = comp.effective_status()
    assert status == HealthStatus.DEGRADED
    assert "classic" in detail


def _drive_split(m, lines, now, batch):
    """The scheduler's four calls, one batch at a time."""
    out = []
    for s in range(0, len(lines), batch):
        state = m.pipeline_begin(lines[s : s + batch], now)
        m.pipeline_submit(state, now=now)
        m.pipeline_collect(state)
        results, n_stale = m.pipeline_finish(state, now)
        assert n_stale == 0
        out.extend(results)
    return out


@pytest.mark.parametrize("entry", ["sync", "pipelined"])
def test_selftest_failure_runs_the_classic_protocol_exactly(monkeypatch, entry):
    """Scan selftest fails → `fused_protocol: classic`, exactly one
    degraded note, and the ban log equals CpuMatcher's — through the sync
    entry and through the scheduler's split protocol (where the classic
    batch's window apply waits for its drain turn)."""
    monkeypatch.setattr(fmw, "scan_selftest", _boom)
    patterns = synth.generate_rules(25, seed=41) + [r".*"]
    now = time.time()
    rests = synth.generate_lines(320, patterns[:-1], seed=5, attack_rate=0.3)
    lines = [
        f"{now + i * 0.0005:.6f} 10.9.{i % 24}.1 {r}"
        for i, r in enumerate(rests)
    ]
    y = _rules_yaml(patterns)
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(TpuMatcher, y, matcher_device_windows=True,
                  matcher_batch_lines=64)
    d = tpu.describe()
    assert d["fused_protocol"] == "classic" and d["scan_interpret"] is None
    assert len(d["downgrades"]) == 1 and "classic" in d["downgrades"][0]
    want = [cpu.consume_line(l, now + 1) for l in lines]
    if entry == "sync":
        got = []
        for s in range(0, len(lines), 128):
            got.extend(tpu.consume_lines(lines[s : s + 128], now + 1))
    else:
        got = _drive_split(tpu, lines, now + 1, 128)
    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans and len(cb.bans) > 0
    assert cb.regex_ban_logs == tb.regex_ban_logs
    assert tpu.pipelined_fused_chunks == 0
    assert cpu.rate_limit_states.format_states() == \
        tpu.device_windows.format_states()


@pytest.mark.parametrize("key,value", [
    ("pallas_single_kernel", "auto"),
    ("pipeline_fused", False),
    ("drain_resolve_depth", 3),
])
def test_config_file_with_a_removed_protocol_key_runs_the_single_kernel(
    key, value
):
    """The three protocol options are gone from the schema; a file that
    still carries one (both benchmark configurations carry
    `pallas_single_kernel: auto`) loads — the loader ignores keys it does
    not know — and the matcher runs the one fused protocol."""
    doc = yaml.safe_load(_rules_yaml([r"GET /blockme.*"], hits=0, interval=1))
    doc[key] = value
    cfg = config_from_yaml_text(yaml.safe_dump(doc))
    assert not hasattr(cfg, key)
    cfg.matcher_device_windows = True
    banner = MockBanner()
    m = TpuMatcher(cfg, banner, StaticDecisionLists(cfg),
                   RegexRateLimitStates())
    assert m.describe()["fused_protocol"] == "single-kernel"
    now = time.time()
    lines = [
        f"{now:.6f} 8.8.8.{i} GET h.com GET /blockme HTTP/1.1 ua -"
        for i in range(4)
    ]
    results = _drive_split(m, lines, now, 4)
    assert all(r.rule_results for r in results)
    assert m.pipelined_fused_chunks == 1 and len(banner.bans) == 4


# ---------------------------------------------------------------------------
# the matcher's description + no quiet way off the device path (PR 24)
# ---------------------------------------------------------------------------


def _raise_unsupported(*a, **k):
    from banjax_tpu.matcher.kernels.nfa_match import PallasUnsupported

    raise PallasUnsupported("synthetic")


def _raise_runtime(*a, **k):
    raise RuntimeError("synthetic")


def _mesh_xla_only(orig):
    def init(self, *a, backend="pallas", **k):
        if backend != "xla":
            _raise_unsupported()
        orig(self, *a, backend=backend, **k)

    return init


@pytest.mark.parametrize("site", [
    "pallas-asked-off-tpu", "pallas-prep", "prefilter-unavailable",
    "plan-build", "single-kernel", "mesh-plan", "mesh-pallas",
])
def test_every_init_downgrade_leaves_a_degraded_note(monkeypatch, site):
    """Each init-time step off the intended device path must show as a
    DEGRADED note on the `matcher` health component — and survive the
    per-batch breaker accounting — and in describe()['downgrades']."""
    from banjax_tpu.matcher import prefilter
    from banjax_tpu.matcher.kernels import nfa_match
    from banjax_tpu.parallel import mesh

    ov = {"matcher_backend": "pallas-interpret"}
    if site == "pallas-asked-off-tpu":
        ov = {"matcher_backend": "pallas"}
    elif site == "pallas-prep":
        monkeypatch.setattr(nfa_match, "prepare", _raise_unsupported)
    elif site == "prefilter-unavailable":
        monkeypatch.setattr(
            prefilter.FusedPrefilter, "__init__", _raise_unsupported
        )
    elif site == "plan-build":
        monkeypatch.setattr(prefilter, "build_plan", _raise_runtime)
    elif site == "single-kernel":
        monkeypatch.setattr(fmw, "scan_selftest", _raise_runtime)
        ov["matcher_device_windows"] = True
    elif site == "mesh-plan":
        monkeypatch.setattr(prefilter, "build_plan", _raise_runtime)
        ov = {"matcher_mesh_devices": 2}
    elif site == "mesh-pallas":
        monkeypatch.setattr(
            mesh.ShardedMatchBackend, "__init__",
            _mesh_xla_only(mesh.ShardedMatchBackend.__init__),
        )
        ov.update(matcher_mesh_devices=2)
    health = HealthRegistry()
    m, _ = _mk(TpuMatcher, _rules_yaml([r"GET /a.*"]), health=health, **ov)
    try:
        comp = health.get("matcher")
        status, detail, _ = comp.effective_status()
        assert status == HealthStatus.DEGRADED, (site, detail)
        assert "; ".join(m.describe()["downgrades"]) == detail
        now = time.time()
        m.consume_lines([f"{now:.6f} 1.2.3.4 GET h.com GET /a HTTP/1.1 ua -"], now)
        assert comp.effective_status()[0] == HealthStatus.DEGRADED
    finally:
        m.close()


def test_description_is_honest_on_the_cpu_backend():
    """auto on a CPU backend is the XLA scan with an interpreted window
    scan: describe() says so, no downgrade is noted, and /healthz's
    matcher entry carries the same description."""
    health = HealthRegistry()
    m, _ = _mk(TpuMatcher, _rules_yaml([r"GET /a.*"]), health=health,
               matcher_device_windows=True)
    try:
        d = m.describe()
        assert d["platform"] == "cpu" and d["device_count"] == len(jax.devices())
        assert d["nfa_backend"] == "xla" and d["match_interpret"] is None
        assert d["fused_protocol"] == "single-kernel"
        assert d["scan_interpret"] is True
        assert d["prefilter"] is True and d["mesh_shape"] is None
        assert d["downgrades"] == []
        entry = health.snapshot()["components"]["matcher"]
        assert entry["status"] == "healthy" and entry["info"] == d
    finally:
        m.close()

    interp, _ = _mk(TpuMatcher, _rules_yaml([r"GET /a.*"]),
                    matcher_backend="pallas-interpret")
    try:
        d = interp.describe()
        assert d["nfa_backend"] == "pallas" and d["match_interpret"] is True
        assert d["fused_protocol"] == "classic"
    finally:
        interp.close()
