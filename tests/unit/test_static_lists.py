"""Static decision lists: exact IP + CIDR matching (reference: internal/decision.go:88-374)."""

from banjax_tpu.config.schema import config_from_yaml_text
from banjax_tpu.decisions.model import Decision, FailAction
from banjax_tpu.decisions.static_lists import StaticDecisionLists


YAML = """
global_decision_lists:
  allow:
    - 20.20.20.20
    - 10.0.0.0/8
  iptables_block:
    - 30.40.50.60
  nginx_block:
    - 70.80.90.100
    - 192.168.0.0/16
  challenge:
    - 8.8.8.8
per_site_decision_lists:
  example.com:
    allow:
      - 90.90.90.90
    challenge:
      - 91.91.91.91
      - 172.16.0.0/12
sitewide_sha_inv_list:
  example.com: block
  foobar.com: no_block
"""


def make_lists():
    return StaticDecisionLists(config_from_yaml_text(YAML))


def test_global_exact():
    lists = make_lists()
    assert lists.check_global("20.20.20.20") == (Decision.ALLOW, True)
    assert lists.check_global("30.40.50.60") == (Decision.IPTABLES_BLOCK, True)
    assert lists.check_global("8.8.8.8") == (Decision.CHALLENGE, True)
    assert lists.check_global("1.1.1.1") == (None, False)


def test_global_cidr():
    lists = make_lists()
    assert lists.check_global("10.1.2.3") == (Decision.ALLOW, True)
    assert lists.check_global("192.168.55.1") == (Decision.NGINX_BLOCK, True)


def test_per_site():
    lists = make_lists()
    assert lists.check_per_site("example.com", "90.90.90.90") == (Decision.ALLOW, True)
    assert lists.check_per_site("example.com", "91.91.91.91") == (Decision.CHALLENGE, True)
    assert lists.check_per_site("example.com", "172.20.1.1") == (Decision.CHALLENGE, True)
    assert lists.check_per_site("example.com", "1.1.1.1") == (None, False)
    assert lists.check_per_site("other.com", "90.90.90.90") == (None, False)


def test_sitewide_sha_inv():
    lists = make_lists()
    assert lists.check_sitewide_sha_inv("example.com") == (FailAction.BLOCK, True)
    assert lists.check_sitewide_sha_inv("foobar.com") == (FailAction.NO_BLOCK, True)
    fa, ok = lists.check_sitewide_sha_inv("nope.com")
    assert not ok


def test_check_is_allowed():
    lists = make_lists()
    # global exact allow
    assert lists.check_is_allowed("anything.com", "20.20.20.20")
    # global CIDR allow
    assert lists.check_is_allowed("anything.com", "10.9.9.9")
    # per-site exact allow
    assert lists.check_is_allowed("example.com", "90.90.90.90")
    # challenge is not allow
    assert not lists.check_is_allowed("anything.com", "8.8.8.8")
    assert not lists.check_is_allowed("example.com", "91.91.91.91")
    assert not lists.check_is_allowed("anything.com", "4.4.4.4")


def test_hot_reload_swaps_snapshot():
    lists = make_lists()
    assert lists.check_global("20.20.20.20") == (Decision.ALLOW, True)
    new_cfg = config_from_yaml_text(
        """
global_decision_lists:
  nginx_block:
    - 20.20.20.20
"""
    )
    lists.update_from_config(new_cfg)
    assert lists.check_global("20.20.20.20") == (Decision.NGINX_BLOCK, True)
    assert lists.check_global("30.40.50.60") == (None, False)


def test_filter_order_allow_wins_over_block():
    # an IP covered by both an allow CIDR and a block CIDR: the filter scan
    # order Allow→Challenge→NginxBlock→IptablesBlock means allow wins
    cfg = config_from_yaml_text(
        """
global_decision_lists:
  iptables_block:
    - 10.0.0.0/8
  allow:
    - 10.1.0.0/16
"""
    )
    lists = StaticDecisionLists(cfg)
    assert lists.check_global("10.1.2.3") == (Decision.ALLOW, True)
    assert lists.check_global("10.2.2.3") == (Decision.IPTABLES_BLOCK, True)


def test_has_any_allow_entries():
    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.static_lists import StaticDecisionLists

    base = """
regexes_with_rates: []
"""
    sl = StaticDecisionLists(config_from_yaml_text(base))
    assert not sl.has_any_allow_entries()

    for yaml_frag in (
        "global_decision_lists:\n  allow:\n    - 1.1.1.1\n",
        "global_decision_lists:\n  allow:\n    - 10.0.0.0/8\n",
        "per_site_decision_lists:\n  a.com:\n    allow:\n      - 2.2.2.2\n",
        "per_site_decision_lists:\n  a.com:\n    allow:\n      - 2.2.0.0/16\n",
    ):
        sl2 = StaticDecisionLists(config_from_yaml_text(base + yaml_frag))
        assert sl2.has_any_allow_entries(), yaml_frag
    # a list spelled out empty is no source (the shipped deploy/banjax-config.yaml has `allow: []`):
    # its filter allows nothing, and the matcher's gate may skip its check
    for yaml_frag in (
        "global_decision_lists:\n  allow: []\n  challenge: []\n",
        "per_site_decision_lists:\n  a.com:\n    allow: []\n",
    ):
        sl4 = StaticDecisionLists(config_from_yaml_text(base + yaml_frag))
        assert not sl4.has_any_allow_entries(), yaml_frag
        assert not sl4.check_is_allowed("a.com", "1.1.1.1")
    # non-allow lists alone do not count
    sl3 = StaticDecisionLists(config_from_yaml_text(
        base + "global_decision_lists:\n  nginx_block:\n    - 3.3.3.3\n"
    ))
    assert not sl3.has_any_allow_entries()


def test_ipfilter_fast_path_differential():
    """The inet_pton membership fast path agrees with the ipaddress-module
    slow path on every accept/reject edge case (IPFilter.allowed)."""
    import ipaddress

    from banjax_tpu.decisions.static_lists import IPFilter

    entries = [
        "20.20.20.20", "10.0.0.0/8", "192.168.1.0/24", "2001:db8::1",
        "2001:db8:1::/48", "255.255.255.255", "0.0.0.0/0 oops", "garbage",
    ]
    f = IPFilter([e for e in entries if "oops" not in e])

    def slow(ip_string):
        try:
            addr = ipaddress.ip_address(ip_string)
        except ValueError:
            return False
        nets = [
            ipaddress.ip_network(e, strict=False)
            for e in entries
            if "/" in e and "oops" not in e
        ]
        singles = {
            ipaddress.ip_address(e)
            for e in entries
            if "/" not in e and e not in ("garbage",)
        }
        return addr in singles or any(addr in n for n in nets)

    cases = [
        "20.20.20.20", "20.20.20.21", "10.1.2.3", "11.1.2.3",
        "192.168.1.77", "192.168.2.77", "2001:db8::1", "2001:db8::2",
        "2001:db8:1::ffff", "2001:db8:2::ffff", "255.255.255.255",
        # reject-form edge cases: both paths must agree on rejection
        "01.2.3.4", "1.2.3", "1.2.3.4.5", " 1.2.3.4", "1.2.3.4 ",
        "256.1.1.1", "1.2.3.04", "", "::", "::1", "not-an-ip",
        "10.0.0.0/8",  # a CIDR is not an address
        "0x0a.1.2.3",
    ]
    import random

    rng = random.Random(5)
    for _ in range(500):
        cases.append(
            f"{rng.randint(0, 299)}.{rng.randint(0, 299)}"
            f".{rng.randint(0, 299)}.{rng.randint(0, 299)}"
        )
    for ip in cases:
        assert f.allowed(ip) == slow(ip), ip


def test_ipfilter_scoped_ipv6_slow_path():
    """Scoped IPv6 input falls back to ipaddress-module semantics."""
    from banjax_tpu.decisions.static_lists import IPFilter

    f = IPFilter(["fe80::1"])
    assert f.allowed("fe80::1") is True
    # a scoped input is not equal to the unscoped single (ipaddress
    # equality includes the zone), so it must NOT match
    assert f.allowed("fe80::1%eth0") is False


def test_the_shipped_configuration_has_no_allow_source():
    """deploy/banjax-config.yaml — what the benchmark's cells and a fresh
    deployment run — lists every decision with no address: the matcher's
    gate makes no string of an address for its allowlist there."""
    import os

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.static_lists import IPFilter, StaticDecisionLists

    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "..", "..", "deploy", "banjax-config.yaml")
    with open(path, encoding="utf-8") as f:
        cfg = config_from_yaml_text(f.read())
    assert cfg.global_decision_lists.get("allow") == []
    assert not StaticDecisionLists(cfg).has_any_allow_entries()
    assert not IPFilter([]) and not IPFilter(["", "junk"])
    assert IPFilter(["10.0.0.0/8"]) and IPFilter(["::1"])
