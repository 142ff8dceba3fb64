"""Config schema parsing (reference: internal/config_test.go, config.go:87-131)."""

import pytest

from banjax_tpu.config.schema import Config, config_from_yaml_text
from banjax_tpu.decisions.model import Decision


REGEX_RULE_YAML = """
regexes_with_rates:
  - decision: nginx_block
    hits_per_interval: 800
    interval: 30
    regex: .*
    rule: "All sites/methods: 800 req/30 sec"
    hosts_to_skip:
      example.com: true
  - decision: challenge
    hits_per_interval: 45
    interval: 60
    regex: "^POST .*"
    rule: "All sites/POST: 45 req/60 sec"
"""


def test_regex_with_rate_unmarshal():
    cfg = config_from_yaml_text(REGEX_RULE_YAML)
    assert len(cfg.regexes_with_rates) == 2
    r0 = cfg.regexes_with_rates[0]
    assert r0.rule == "All sites/methods: 800 req/30 sec"
    assert r0.decision is Decision.NGINX_BLOCK
    assert r0.hits_per_interval == 800
    assert r0.interval_ns == 30 * 1_000_000_000
    assert r0.hosts_to_skip == {"example.com": True}
    assert r0.regex.search("anything at all")

    r1 = cfg.regexes_with_rates[1]
    assert r1.regex.search("POST /login HTTP/1.1")
    assert not r1.regex.search("GET /login HTTP/1.1")


def test_fractional_interval_truncates_like_go():
    cfg = config_from_yaml_text(
        """
regexes_with_rates:
  - decision: allow
    hits_per_interval: 1
    interval: 0.5
    regex: x
    rule: r
"""
    )
    assert cfg.regexes_with_rates[0].interval_ns == 500_000_000


def test_bad_regex_fails_load():
    with pytest.raises(ValueError):
        config_from_yaml_text(
            """
regexes_with_rates:
  - decision: allow
    hits_per_interval: 1
    interval: 1
    regex: "(?invalid"
    rule: bad
"""
        )


def test_bad_decision_fails_load():
    with pytest.raises(ValueError):
        config_from_yaml_text(
            """
regexes_with_rates:
  - decision: obliterate
    hits_per_interval: 1
    interval: 1
    regex: x
    rule: bad
"""
        )


def test_per_site_regexes():
    cfg = config_from_yaml_text(
        """
per_site_regexes_with_rates:
  localhost:
    - decision: nginx_block
      hits_per_interval: 0
      interval: 1
      regex: .*blockme.*
      rule: "instant block"
"""
    )
    assert list(cfg.per_site_regexes_with_rates) == ["localhost"]
    assert cfg.per_site_regexes_with_rates["localhost"][0].decision is Decision.NGINX_BLOCK


def test_scalar_and_map_keys():
    cfg = config_from_yaml_text(
        """
config_version: 2021-03-22_00:00:00
expiring_decision_ttl_seconds: 300
iptables_ban_seconds: 300
kafka_brokers:
  - localhost:9094
sha_inv_expected_zero_bits: 10
sitewide_sha_inv_list:
  example.com: block
use_user_agent_in_cookie:
  localhost: true
"""
    )
    assert cfg.expiring_decision_ttl_seconds == 300
    assert cfg.kafka_brokers == ["localhost:9094"]
    assert cfg.sha_inv_expected_zero_bits == 10
    assert cfg.sitewide_sha_inv_list == {"example.com": "block"}
    assert cfg.use_user_agent_in_cookie == {"localhost": True}
    # defaults for untouched keys
    assert cfg.matcher == "cpu"
    assert cfg.debug is False


def test_re2_incompatible_constructs_rejected():
    # Go's RE2 rejects lookaround and backreferences; so must we
    for bad in [r"(?=bot).*crawl", r"(a)\1", r"(?<!x)y", r"(?P<g>a)(?P=g)"]:
        with pytest.raises(ValueError):
            config_from_yaml_text(
                f"""
regexes_with_rates:
  - decision: allow
    hits_per_interval: 1
    interval: 1
    regex: '{bad}'
    rule: bad
"""
            )
    # but the same tokens inside a character class are literal and fine
    cfg = config_from_yaml_text(
        """
regexes_with_rates:
  - decision: allow
    hits_per_interval: 1
    interval: 1
    regex: '[(?=]+x'
    rule: ok
"""
    )
    assert cfg.regexes_with_rates[0].regex.search("(?=x")


def test_wrong_typed_scalars_fail_load():
    # Go yaml.v2 fails the load on type mismatches; so do we
    with pytest.raises(ValueError):
        config_from_yaml_text('sha_inv_expected_zero_bits: "10"')
    with pytest.raises(ValueError):
        config_from_yaml_text("iptables_ban_seconds: banana")
    with pytest.raises(ValueError):
        config_from_yaml_text("debug: 1")
    with pytest.raises(ValueError):
        config_from_yaml_text("kafka_brokers: not-a-list")


def test_python311_only_regex_constructs_rejected():
    # atomic groups and possessive quantifiers are RE2-invalid
    for bad in [r"(?>abc)x", r"a*+b", r"a++", r"x{2,3}+"]:
        with pytest.raises(ValueError):
            config_from_yaml_text(
                f"""
regexes_with_rates:
  - {{decision: allow, hits_per_interval: 1, interval: 1, regex: '{bad}', rule: r}}
"""
            )
    # a literal closing brace before + is valid RE2 and must pass
    cfg = config_from_yaml_text(
        """
regexes_with_rates:
  - {decision: allow, hits_per_interval: 1, interval: 1, regex: 'a}+', rule: r}
"""
    )
    assert cfg.regexes_with_rates[0].regex.search("a}}}")


def test_provenance_slo_flightrec_keys_defaults_and_validation():
    cfg = config_from_yaml_text("")
    assert cfg.provenance_enabled is True
    assert cfg.provenance_ring_size == 2048
    assert cfg.slo_enabled is True
    assert cfg.slo_sample_seconds == 15.0
    assert cfg.slo_batch_latency_target == 0.99
    assert cfg.slo_shed_ratio_max == 0.001
    assert cfg.flightrec_dir == ""
    assert cfg.flightrec_min_interval_s == 60.0
    assert cfg.flightrec_keep == 16

    cfg = config_from_yaml_text(
        "provenance_ring_size: 128\n"
        "slo_batch_latency_target: 0.999\n"
        "flightrec_dir: /tmp/incidents\n"
        "flightrec_keep: 4\n"
    )
    assert cfg.provenance_ring_size == 128
    assert cfg.slo_batch_latency_target == 0.999
    assert cfg.flightrec_dir == "/tmp/incidents"
    assert cfg.flightrec_keep == 4

    for bad in (
        "provenance_ring_size: 0",
        "slo_batch_latency_target: 1.0",
        "slo_batch_latency_target: 0",
        "slo_shed_ratio_max: 0",
        "slo_stale_ratio_max: -1",
        "slo_breaker_open_ratio_max: 0",
        "slo_budget_trip_ratio_max: 0",
        "slo_sample_seconds: -1",
        "flightrec_min_interval_s: -1",
        "flightrec_keep: 0",
        "flightrec_provenance_records: 0",
        'provenance_enabled: "yes"',
    ):
        with pytest.raises(ValueError):
            config_from_yaml_text(bad)


def test_failpoints_admin_key_default_and_typing():
    cfg = config_from_yaml_text("")
    assert cfg.failpoints_admin_enabled is True

    cfg = config_from_yaml_text("failpoints_admin_enabled: false\n")
    assert cfg.failpoints_admin_enabled is False

    with pytest.raises(ValueError, match="failpoints_admin_enabled"):
        config_from_yaml_text('failpoints_admin_enabled: "yes"\n')


def test_mega_state_tiering_keys_defaults_and_validation():
    cfg = config_from_yaml_text("")
    assert cfg.slot_admission_enabled is False
    assert cfg.slot_admission_min_estimate == 0
    assert cfg.warm_tier_enabled is False
    assert cfg.warm_tier_capacity == 1 << 20

    cfg = config_from_yaml_text(
        "matcher_device_windows: true\n"
        "traffic_sketch_enabled: true\n"
        "slot_admission_enabled: true\n"
        "slot_admission_min_estimate: 9\n"
        "warm_tier_enabled: true\n"
        "warm_tier_capacity: 4096\n"
    )
    assert cfg.slot_admission_enabled is True
    assert cfg.slot_admission_min_estimate == 9
    assert cfg.warm_tier_enabled is True
    assert cfg.warm_tier_capacity == 4096

    for bad in (
        # admission requires both the sketch and device windows
        "slot_admission_enabled: true",
        # ... sketch on by default, so it must be REFUSED when off
        "slot_admission_enabled: true\nmatcher_device_windows: true\n"
        "traffic_sketch_enabled: false",
        "slot_admission_enabled: true\ntraffic_sketch_enabled: true",
        # warm tier requires device windows
        "warm_tier_enabled: true",
        "warm_tier_capacity: 0",
        "warm_tier_capacity: -4",
        # Go yaml.v2 strictness: wrong-typed values fail the load
        'slot_admission_enabled: "yes"',
        'slot_admission_min_estimate: "9"',
        "warm_tier_capacity: banana",
    ):
        with pytest.raises(ValueError):
            config_from_yaml_text(bad)


def test_challenge_plane_keys_defaults_and_validation():
    cfg = config_from_yaml_text("")
    assert cfg.challenge_device_verify is False
    assert cfg.challenge_verify_batch_max == 256
    assert cfg.challenge_failure_state_max == 0  # unbounded = reference

    cfg = config_from_yaml_text(
        "challenge_device_verify: true\n"
        "challenge_verify_batch_max: 64\n"
        "challenge_failure_state_max: 4096\n"
    )
    assert cfg.challenge_device_verify is True
    assert cfg.challenge_verify_batch_max == 64
    assert cfg.challenge_failure_state_max == 4096

    for bad in (
        "challenge_verify_batch_max: 0",
        "challenge_verify_batch_max: -1",
        "challenge_failure_state_max: -1",
        # Go yaml.v2 strictness: wrong-typed values fail the load
        'challenge_device_verify: "yes"',
        'challenge_verify_batch_max: "64"',
        "challenge_failure_state_max: banana",
    ):
        with pytest.raises(ValueError):
            config_from_yaml_text(bad)


def test_serve_fastpath_and_ipset_keys_defaults_and_validation():
    cfg = config_from_yaml_text("")
    assert cfg.serve_fastpath_enabled is True
    assert cfg.serve_decision_table_capacity == 65536
    assert cfg.ipset_netlink_enabled is True

    cfg = config_from_yaml_text(
        "serve_fastpath_enabled: false\n"
        "serve_decision_table_capacity: 1024\n"
        "ipset_netlink_enabled: false\n"
    )
    assert cfg.serve_fastpath_enabled is False
    assert cfg.serve_decision_table_capacity == 1024
    assert cfg.ipset_netlink_enabled is False

    for bad in (
        "serve_decision_table_capacity: 0",
        "serve_decision_table_capacity: -1",
        # Go yaml.v2 strictness: wrong-typed values fail the load
        'serve_fastpath_enabled: "yes"',
        'serve_decision_table_capacity: "1024"',
        "ipset_netlink_enabled: banana",
    ):
        with pytest.raises(ValueError):
            config_from_yaml_text(bad)


def test_fleet_observability_keys_defaults_and_validation():
    """ISSUE 20: the fleet observability plane's four config keys."""
    cfg = config_from_yaml_text("")
    assert cfg.fabric_trace_propagation is False
    assert cfg.fleet_metrics_enabled is False
    assert cfg.fleet_scrape_timeout_ms == 750.0
    assert cfg.flightrec_fleet_capture is False

    cfg = config_from_yaml_text(
        "fabric_trace_propagation: true\n"
        "fleet_metrics_enabled: true\n"
        "fleet_scrape_timeout_ms: 250\n"
        "flightrec_fleet_capture: true\n"
    )
    assert cfg.fabric_trace_propagation is True
    assert cfg.fleet_metrics_enabled is True
    assert cfg.fleet_scrape_timeout_ms == 250.0
    assert cfg.flightrec_fleet_capture is True

    for bad in (
        "fleet_scrape_timeout_ms: 0",
        "fleet_scrape_timeout_ms: -5",
        'fleet_metrics_enabled: "yes"',
        'fabric_trace_propagation: "on"',
        'flightrec_fleet_capture: 1.5',
    ):
        with pytest.raises(ValueError):
            config_from_yaml_text(bad)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
    monkeypatch, tmp_path, from_env
):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; unset,
    the cache goes to the fixed <checkout>/.jax_cache."""
    import os

    import jax

    from banjax_tpu import cli

    was = jax.config.jax_compilation_cache_dir
    sentinel = str(tmp_path / "untouched")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert cli.place_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == sentinel
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(cli.__file__))),
                ".jax_cache",
            )
            assert cli.place_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
