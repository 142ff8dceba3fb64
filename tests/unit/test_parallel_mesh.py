"""Multi-device sharded matcher vs single-device reference (8-dev CPU mesh).

conftest.py forces xla_force_host_platform_device_count=8, the same
mechanism the driver uses to validate multi-chip sharding without hardware.
"""

import re

import jax
import numpy as np
import pytest

from banjax_tpu.matcher import nfa_jax
from banjax_tpu.matcher.encode import encode_for_match
from banjax_tpu.matcher.rulec import compile_rules
from banjax_tpu.parallel.mesh import make_mesh, shard_params, sharded_match_fn

PATTERNS = [
    r"GET /wp-login\.php",
    r"POST /xmlrpc\.php",
    r"(GET|POST) /[a-z-]*\.php",
    r"^GET .* HTTP/1\.1$",
    r"Mozilla/\d+\.\d+",
    r"a+b",
    r"[0-9]{2,4}",
    r".*",
    r"^$",
    r"wp-admin",
]

LINES = [
    "GET example.com GET /wp-login.php HTTP/1.1",
    "POST example.com POST /xmlrpc.php HTTP/1.1",
    "GET example.com GET / HTTP/1.1",
    "aaab and 123",
    "Mozilla/5.0 something",
    "",
    "nothing interesting here",
    "GET site.org GET /wp-admin/panel HTTP/1.1",
] * 4  # 32 lines, divisible by dp


@pytest.mark.parametrize("dp,rp", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_sharded_matches_single_device(dp, rp):
    if len(jax.devices()) < dp * rp:
        pytest.skip("needs 8 virtual devices")
    compiled = compile_rules(PATTERNS, n_shards=rp)
    mesh = make_mesh(dp * rp, rp=rp)
    fn = sharded_match_fn(compiled, mesh)
    params = shard_params(compiled, mesh)
    cls_ids, lens, host_eval = encode_for_match(compiled, LINES, 128)
    assert not host_eval.any()
    got = np.asarray(fn(params, cls_ids, lens))

    ref_compiled = compile_rules(PATTERNS, n_shards=1)
    ref = np.asarray(
        nfa_jax.match_batch(
            nfa_jax.match_params(ref_compiled),
            *encode_for_match(ref_compiled, LINES, 128)[:2],
            ref_compiled.n_rules,
        )
    )
    assert (got == ref).all()
    # and both equal the re oracle
    for j, pat in enumerate(PATTERNS):
        rx = re.compile(pat)
        for i, line in enumerate(LINES):
            assert bool(got[i, j]) == (rx.search(line) is not None)


@pytest.mark.parametrize("dp,rp", [(4, 2), (2, 4)])
def test_sharded_pallas_backend_matches_oracle(dp, rp):
    """The production mesh path: Pallas kernel per device (interpret mode on
    the CPU mesh), via the batch-level ShardedMatchBackend."""
    from banjax_tpu.parallel.mesh import ShardedMatchBackend

    if len(jax.devices()) < dp * rp:
        pytest.skip("needs 8 virtual devices")
    compiled = compile_rules(PATTERNS, n_shards=rp)
    mesh = make_mesh(dp * rp, rp=rp)
    backend = ShardedMatchBackend(
        compiled, mesh, 128, backend="pallas-interpret", block_b=8
    )
    cls_ids, lens, host_eval = encode_for_match(compiled, LINES, 128)
    assert not host_eval.any()
    got = backend.match_bits(cls_ids, lens)
    for j, pat in enumerate(PATTERNS):
        rx = re.compile(pat)
        for i, line in enumerate(LINES):
            assert bool(got[i, j]) == (rx.search(line) is not None), (pat, line)


@pytest.mark.parametrize("n_lines", [1, 3, 7, 13])
def test_sharded_backend_dp_remainder(n_lines):
    """Batches not divisible by dp * block_b pad transparently and return
    results in input order."""
    from banjax_tpu.parallel.mesh import ShardedMatchBackend

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    rp = 2
    compiled = compile_rules(PATTERNS, n_shards=rp)
    mesh = make_mesh(8, rp=rp)
    backend = ShardedMatchBackend(
        compiled, mesh, 128, backend="pallas-interpret", block_b=8
    )
    lines = LINES[:n_lines]
    cls_ids, lens, _ = encode_for_match(compiled, lines, 128)
    got = backend.match_bits(cls_ids, lens)
    assert got.shape == (n_lines, compiled.n_rules)
    for j, pat in enumerate(PATTERNS):
        rx = re.compile(pat)
        for i, line in enumerate(lines):
            assert bool(got[i, j]) == (rx.search(line) is not None), (pat, line)


def test_sharded_backend_xla_parity():
    """XLA mesh body and Pallas mesh body agree bit-for-bit."""
    from banjax_tpu.parallel.mesh import ShardedMatchBackend

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    rp = 4
    compiled = compile_rules(PATTERNS, n_shards=rp)
    mesh = make_mesh(8, rp=rp)
    cls_ids, lens, _ = encode_for_match(compiled, LINES, 128)
    a = ShardedMatchBackend(
        compiled, mesh, 128, backend="pallas-interpret", block_b=8
    ).match_bits(cls_ids, lens)
    b = ShardedMatchBackend(compiled, mesh, 128, backend="xla").match_bits(
        cls_ids, lens
    )
    assert (a == b).all()


@pytest.mark.parametrize("backend,dp,rp", [
    ("xla", 4, 2), ("xla", 2, 4), ("pallas-interpret", 4, 2),
])
def test_fused_mesh_prefilter_parity(backend, dp, rp):
    """VERDICT r2 item 5: the mesh path runs stage-1 gating — the fused
    two-stage sharded matcher must be bit-identical to the single-stage
    sharded matcher (and to Python re) on a filterable ruleset, including
    always-rules and empty lines."""
    from banjax_tpu.scenarios import synth

    from banjax_tpu.matcher.prefilter import build_plan
    from banjax_tpu.parallel.mesh import ShardedMatchBackend

    if len(jax.devices()) < dp * rp:
        pytest.skip("needs 8 virtual devices")
    patterns = synth.generate_rules(40, seed=5) + [r".*", r"^$"]
    lines = synth.generate_lines(64, patterns, seed=6, attack_rate=0.3) + [""]
    compiled = compile_rules(patterns, n_shards=rp)
    plan = build_plan(
        patterns,
        byte_classes=(compiled.byte_to_class, compiled.n_classes),
        stage2_shards=rp,
    )
    assert plan is not None and plan.n_always >= 2
    mesh = make_mesh(dp * rp, rp=rp)
    block = 8
    fused = ShardedMatchBackend(
        compiled, mesh, 128, backend=backend, block_b=block, plan=plan,
        cand_frac=1.0,
    )
    single = ShardedMatchBackend(
        compiled, mesh, 128, backend=backend, block_b=block
    )
    cls_ids, lens, host_eval = encode_for_match(compiled, lines, 128)
    assert not host_eval.any()
    got = fused.match_bits(cls_ids, lens)
    want = single.match_bits(cls_ids, lens)
    for rid in plan.unsupported:
        want[:, rid] = 0
    np.testing.assert_array_equal(got, want)
    assert fused.fused_batches == 1 and fused.fallback_batches == 0


def test_fused_mesh_overflow_falls_back():
    """Per-dp-shard candidate overflow reruns the batch single-stage —
    identical output, fallback counter ticks."""
    from banjax_tpu.scenarios import synth

    from banjax_tpu.matcher.prefilter import build_plan
    from banjax_tpu.parallel.mesh import ShardedMatchBackend

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    patterns = synth.generate_rules(30, seed=8)
    # every line matches: candidates exceed any fractional capacity
    lines = synth.generate_lines(64, patterns, seed=9, attack_rate=1.0)
    rp = 2
    compiled = compile_rules(patterns, n_shards=rp)
    plan = build_plan(
        patterns,
        byte_classes=(compiled.byte_to_class, compiled.n_classes),
        stage2_shards=rp,
    )
    assert plan is not None
    mesh = make_mesh(8, rp=rp)
    fused = ShardedMatchBackend(
        compiled, mesh, 128, backend="xla", block_b=8, plan=plan,
        cand_frac=1.0 / 64,
    )
    single = ShardedMatchBackend(compiled, mesh, 128, backend="xla", block_b=8)
    cls_ids, lens, _ = encode_for_match(compiled, lines, 128)
    got = fused.match_bits(cls_ids, lens)
    want = single.match_bits(cls_ids, lens)
    for rid in plan.unsupported:
        want[:, rid] = 0
    np.testing.assert_array_equal(got, want)
    assert fused.fallback_batches == 1


def test_rp_mismatch_rejected():
    """A ruleset compiled for K shards cannot ride a mesh with rp != K."""
    from banjax_tpu.parallel.mesh import ShardedMatchBackend, sharded_pallas_fn
    from banjax_tpu.matcher.kernels import nfa_match

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    compiled = compile_rules(PATTERNS, n_shards=2)
    mesh = make_mesh(8, rp=4)
    with pytest.raises(ValueError, match="shards"):
        sharded_match_fn(compiled, mesh)
    with pytest.raises(ValueError, match="shards"):
        sharded_pallas_fn(nfa_match.prepare(compiled), mesh, 32, 8, 8)


def test_mesh_tpu_matcher_consume_lines_matches_cpu_oracle():
    """TpuMatcher in mesh mode (the config-driven product path) produces the
    identical ConsumeLineResult stream + Banner effects as CpuMatcher."""
    import time

    from tests.mesh_oracle import assert_mesh_matches_cpu_oracle

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    yaml_text = r"""
regexes_with_rates:
  - decision: nginx_block
    rule: 'rule1'
    regex: 'GET example\.com GET .*'
    interval: 5
    hits_per_interval: 2
  - decision: challenge
    rule: 'rule2'
    regex: 'POST .*'
    interval: 5
    hits_per_interval: 1
"""
    now = time.time()
    lines = [
        f"{now:.6f} 10.1.1.{i % 4} GET example.com GET /x{i} HTTP/1.1"
        for i in range(20)
    ] + [
        f"{now:.6f} 10.1.1.9 POST example.com POST /submit HTTP/1.1"
        for _ in range(4)
    ]
    assert_mesh_matches_cpu_oracle(yaml_text, lines, now, 8, 2, interpret=True)


def test_mesh_long_line_near_max_len():
    """A line at exactly matcher_max_line_len survives the L_p trim (the
    mesh path must column-slice both sides of the copy)."""
    import time

    from tests.mesh_oracle import assert_mesh_matches_cpu_oracle

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    yaml_text = (
        "regexes_with_rates:\n"
        "  - decision: nginx_block\n"
        "    rule: tail\n"
        "    regex: 'zzz$'\n"
        "    interval: 5\n"
        "    hits_per_interval: 2\n"
        "matcher_max_line_len: 100\n"
    )
    now = time.time()
    rest = "GET h.com GET /" + "a" * 82 + "zzz"  # rest is exactly 100 chars
    assert len(rest) == 100
    lines = [f"{now:.6f} 5.6.7.8 {rest}", f"{now:.6f} 5.6.7.8 GET h.com GET /"]
    assert_mesh_matches_cpu_oracle(yaml_text, lines, now, 8, 2, interpret=True)


def test_mesh_more_devices_than_available_degrades():
    """matcher_mesh_devices beyond the attached device count falls back to
    the single-device path with a warning, not a crash."""
    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from tests.mock_banner import MockBanner

    cfg = config_from_yaml_text(
        "regexes_with_rates:\n"
        "  - decision: nginx_block\n"
        "    rule: r\n"
        "    regex: 'GET .*'\n"
        "    interval: 5\n"
        "    hits_per_interval: 2\n"
    )
    cfg.matcher_mesh_devices = 4096
    m = TpuMatcher(
        cfg, MockBanner(), StaticDecisionLists(cfg), RegexRateLimitStates()
    )
    assert m._mesh_matcher is None
    r = m.consume_line(f"{__import__('time').time():.6f} 1.2.3.4 GET h.com GET /")
    assert not r.error


def test_sharded_backend_bounded_compile_cache():
    """Varying batch sizes and line lengths share power-of-two buckets, so
    the per-(Bp, L_p) jit cache stays bounded in the hot path."""
    from banjax_tpu.parallel.mesh import ShardedMatchBackend

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    rp = 2
    compiled = compile_rules(PATTERNS, n_shards=rp)
    mesh = make_mesh(8, rp=rp)
    backend = ShardedMatchBackend(
        compiled, mesh, 128, backend="pallas-interpret", block_b=8
    )
    for n in (1, 3, 9, 17, 25, 31, 32):
        lines = LINES[:n]
        cls_ids, lens, _ = encode_for_match(compiled, lines, 128)
        out = backend.match_bits(cls_ids, lens)
        assert out.shape == (n, compiled.n_rules)
    assert len(backend._fns) == 1  # all bucket to (32, 64)


def test_sharded_submit_collect_split_and_shard_merge():
    """The pipeline's sharded submit/drain seam: submit dispatches without
    forcing, overlapped submits stay independent, collect merges per-shard
    pulls back into caller line order identically to match_bits, and the
    per-shard merge latencies/counters are recorded."""
    from banjax_tpu.parallel.mesh import ShardedMatchBackend

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    rp = 2
    compiled = compile_rules(PATTERNS, n_shards=rp)
    mesh = make_mesh(8, rp=rp)
    backend = ShardedMatchBackend(
        compiled, mesh, 128, backend="pallas-interpret", block_b=8
    )
    cls_ids, lens, _ = encode_for_match(compiled, LINES, 128)
    want = backend.match_bits(cls_ids, lens)

    # two batches in flight at once, collected out of submit order
    p1 = backend.submit(cls_ids, lens)
    p2 = backend.submit(cls_ids[:7], lens[:7])
    got2 = backend.collect(p2)
    got1 = backend.collect(p1)
    assert (got1 == want).all()
    assert (got2 == want[:7]).all()

    # per-shard merge really happened: one timed pull per dp member
    assert len(backend.last_shard_merge_ms) >= 1
    assert backend.submit_ms_ewma is not None
    assert backend.merge_ms_ewma is not None
    assert p1["h2d_bytes"] > 0 and p1["d2h_bytes"] > 0


def test_pipelined_mesh_stream_matches_cpu_oracle():
    """The full tentpole seam on the 8-device CPU mesh: the streaming
    pipeline scheduler driving a mesh-mode TpuMatcher — sharded submit,
    per-shard merge at collect, ordered device-window commit at drain —
    byte-identical to the CPU reference (shared harness with the driver's
    dryrun_multichip)."""
    import time as _time

    import yaml as _yaml

    from tests.mesh_oracle import assert_pipelined_mesh_matches_cpu_oracle

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    rules_yaml = _yaml.safe_dump({
        "regexes_with_rates": [
            {"decision": "nginx_block", "rule": f"rule{j}", "regex": pat,
             "interval": 5, "hits_per_interval": 2}
            for j, pat in enumerate(PATTERNS)
        ]
    })
    now = _time.time()
    log_lines = [
        f"{now:.6f} 10.0.0.{i % 3} {line}" for i, line in enumerate(LINES)
    ]
    assert_pipelined_mesh_matches_cpu_oracle(
        rules_yaml, log_lines, now, 8, 2,
        interpret=True, device_windows=True,
    )
