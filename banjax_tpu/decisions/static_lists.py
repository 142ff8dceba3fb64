"""Static (config-sourced) decision lists.

Reference behavior: /root/reference/internal/decision.go:88-374 — an
immutable snapshot of per-site and global IP→Decision maps. Plain IPs go into
exact-match dicts; every list (plain IPs AND CIDRs) also populates one filter
per decision, checked in the fixed order Allow → Challenge → NginxBlock →
IptablesBlock (first filter containing the IP wins). The snapshot also holds
the sitewide SHA-inv site→FailAction map and the UA pattern lists.

`check_is_allowed` is the allowlist exemption used by the log tailer
(decision.go:185-216); in the TPU matcher the same allowlist is materialized
as a device-side mask over (ip, host) pairs before the window counters run.
"""

from __future__ import annotations

import ipaddress
import socket
from typing import Dict, List, Optional, Tuple

from banjax_tpu.config.schema import Config
from banjax_tpu.decisions.model import Decision, FailAction, parse_decision, parse_fail_action
from banjax_tpu.decisions.ua_lists import (
    UARules,
    build_per_site_ua_rules,
    build_ua_rules,
    check_ua_decision,
)

# The iteration order of per-decision CIDR filters (decision.go:127,149).
_FILTER_CHECK_ORDER = (
    Decision.ALLOW,
    Decision.CHALLENGE,
    Decision.NGINX_BLOCK,
    Decision.IPTABLES_BLOCK,
)


def _fast_parse_ip(ip_string: str) -> Optional[Tuple[int, int]]:
    """(version, address-int) via inet_pton — ~15x faster than the
    ipaddress module on the request hot path, with identical accept/reject
    behavior for unscoped addresses (leading zeros, short forms, stray
    whitespace and out-of-range octets all rejected the same way).  Scoped
    IPv6 ("%zone", which ipaddress accepts but inet_pton rejects) returns
    None so callers take the slow exact-semantics path."""
    # OSError: not parseable; ValueError: embedded NUL / non-str input.
    # byteorder is explicit: it only defaults to 'big' on Python >= 3.11,
    # and this parser must work on 3.10 too.
    try:
        return 4, int.from_bytes(socket.inet_pton(socket.AF_INET, ip_string), "big")
    except (OSError, ValueError):
        pass
    try:
        return 6, int.from_bytes(socket.inet_pton(socket.AF_INET6, ip_string), "big")
    except (OSError, ValueError):
        return None


class IPFilter:
    """Membership test over a mixed list of plain IPs and CIDR blocks.

    Equivalent of the reference's per-decision `ipfilter` instance
    (decision.go:300-303): the filter is built from the FULL list for a
    decision — plain IPs included — so a plain-IP entry also matches here.
    Unparseable entries are skipped (ipfilter tolerates them silently).

    Membership runs on plain ints (version, address) parsed with
    inet_pton; build time keeps the ipaddress module (entries are
    config-sourced and may use forms inet_pton rejects, e.g. host bits
    set on a CIDR).
    """

    __slots__ = ("_singles", "_networks", "_slow_singles", "_slow_networks")

    def __init__(self, entries: List[str]):
        self._singles = set()  # (version, int) — unscoped entries only
        self._networks: List[Tuple[int, int, int]] = []  # (version, net, mask)
        self._slow_singles = set()  # ipaddress objects (original semantics)
        self._slow_networks = []
        for entry in entries:
            entry = entry.strip()
            if not entry:
                continue
            try:
                if "/" in entry:
                    net = ipaddress.ip_network(entry, strict=False)
                    self._slow_networks.append(net)
                    self._networks.append((
                        net.version,
                        int(net.network_address),
                        int(net.netmask),
                    ))
                else:
                    addr = ipaddress.ip_address(entry)
                    self._slow_singles.add(addr)
                    if getattr(addr, "scope_id", None) is None:
                        # a scoped entry can never equal an unscoped input,
                        # and fast-path inputs are always unscoped
                        self._singles.add((addr.version, int(addr)))
            except ValueError:
                continue

    def __bool__(self) -> bool:
        """Whether any entry was taken: a filter built from an empty (or
        wholly unparseable) list allows nothing."""
        return bool(self._slow_singles or self._slow_networks)

    def allowed(self, ip_string: str) -> bool:
        parsed = _fast_parse_ip(ip_string)
        if parsed is None:
            return self._allowed_slow(ip_string)
        if parsed in self._singles:
            return True
        version, addr = parsed
        return any(
            v == version and (addr & mask) == net
            for v, net, mask in self._networks
        )

    def _allowed_slow(self, ip_string: str) -> bool:
        # inputs inet_pton cannot parse: either garbage (reject, like the
        # reference's ipfilter) or scoped IPv6, where the ipaddress module
        # defines the semantics
        try:
            addr = ipaddress.ip_address(ip_string)
        except ValueError:
            return False
        if addr in self._slow_singles:
            return True
        return any(addr in net for net in self._slow_networks)


class _Snapshot:
    """Immutable contents (decision.go:256-276)."""

    __slots__ = (
        "global_decision_lists",
        "per_site_decision_lists",
        "sitewide_sha_inv_list",
        "global_ip_filters",
        "per_site_ip_filters",
        "per_site_ua_rules",
        "global_ua_rules",
    )

    def __init__(self) -> None:
        self.global_decision_lists: Dict[str, Decision] = {}
        self.per_site_decision_lists: Dict[str, Dict[str, Decision]] = {}
        self.sitewide_sha_inv_list: Dict[str, FailAction] = {}
        self.global_ip_filters: Dict[Decision, IPFilter] = {}
        self.per_site_ip_filters: Dict[str, Dict[Decision, IPFilter]] = {}
        self.per_site_ua_rules: Dict[str, UARules] = {}
        self.global_ua_rules: UARules = {}


def _snapshot_from_config(config: Config) -> _Snapshot:
    """Port of newStaticDecisionListsFromConfig (decision.go:278-374)."""
    out = _Snapshot()

    for decision_string, ips in config.global_decision_lists.items():
        decision = parse_decision(decision_string)
        for ip in ips or []:
            if "/" not in ip:
                out.global_decision_lists[ip] = decision
        # filter is built from the full list, plain IPs included
        out.global_ip_filters[decision] = IPFilter(list(ips or []))

    for site, decision_to_ips in config.per_site_decision_lists.items():
        for decision_string, ips in decision_to_ips.items():
            decision = parse_decision(decision_string)
            for ip in ips or []:
                out.per_site_decision_lists.setdefault(site, {})
                out.per_site_ip_filters.setdefault(site, {})
                if "/" not in ip:
                    out.per_site_decision_lists[site][ip] = decision
            if ips:
                # decision.go:330-337: only init the filter for non-empty lists
                out.per_site_ip_filters.setdefault(site, {})[decision] = IPFilter(list(ips))

    for site, fail_action_string in config.sitewide_sha_inv_list.items():
        out.sitewide_sha_inv_list[site] = parse_fail_action(fail_action_string)

    if config.global_user_agent_decision_lists:
        out.global_ua_rules = build_ua_rules(config.global_user_agent_decision_lists)
    if config.per_site_user_agent_decision_lists:
        out.per_site_ua_rules = build_per_site_ua_rules(
            config.per_site_user_agent_decision_lists
        )

    return out


class StaticDecisionLists:
    """Atomically-swapped snapshot of config-sourced decisions."""

    def __init__(self, config: Config):
        self._snapshot = _snapshot_from_config(config)
        # public change counter: callers caching per-(host, ip) results
        # (TpuMatcher's allowlist cache) key on this and must discard on
        # any bump — never on identity of private internals
        self.generation = 0

    def update_from_config(self, config: Config) -> None:
        # Build fully, then swap — readers never see a partial snapshot.
        self._snapshot = _snapshot_from_config(config)
        self.generation += 1

    def check_per_site(self, site: str, client_ip: str) -> Tuple[Optional[Decision], bool]:
        """decision.go:115-139 — exact map first, then per-decision filters in order."""
        c = self._snapshot
        site_map = c.per_site_decision_lists.get(site)
        if site_map is not None and client_ip in site_map:
            return site_map[client_ip], True
        site_filters = c.per_site_ip_filters.get(site)
        if site_filters:
            for decision in _FILTER_CHECK_ORDER:
                f = site_filters.get(decision)
                if f is not None and f.allowed(client_ip):
                    return decision, True
        return None, False

    def check_global(self, client_ip: str) -> Tuple[Optional[Decision], bool]:
        """decision.go:141-162."""
        c = self._snapshot
        if client_ip in c.global_decision_lists:
            return c.global_decision_lists[client_ip], True
        for decision in _FILTER_CHECK_ORDER:
            f = c.global_ip_filters.get(decision)
            if f is not None and f.allowed(client_ip):
                return decision, True
        return None, False

    def check_per_site_user_agent(self, site: str, user_agent: str) -> Tuple[Optional[Decision], bool]:
        """decision.go:164-171."""
        rules = self._snapshot.per_site_ua_rules.get(site)
        if rules is None:
            return None, False
        return check_ua_decision(rules, user_agent)

    def check_global_user_agent(self, user_agent: str) -> Tuple[Optional[Decision], bool]:
        """decision.go:173-176."""
        return check_ua_decision(self._snapshot.global_ua_rules, user_agent)

    def check_sitewide_sha_inv(self, site: str) -> Tuple[Optional[FailAction], bool]:
        """decision.go:178-183."""
        fa = self._snapshot.sitewide_sha_inv_list.get(site)
        return fa, fa is not None

    def has_any_allow_entries(self) -> bool:
        """True when ANY allow source exists (exact or CIDR, global or any
        site). When False, check_is_allowed is False for every input — the
        matcher gate skips its per-distinct-(host, ip) loop entirely.  A
        filter with no entry is no source: the shipped configuration
        spells its lists out empty (`allow: []`), and the global filters
        are built for every key that is there."""
        c = self._snapshot
        if any(d == Decision.ALLOW for d in c.global_decision_lists.values()):
            return True
        if c.global_ip_filters.get(Decision.ALLOW):
            return True
        for site_map in c.per_site_decision_lists.values():
            if any(d == Decision.ALLOW for d in site_map.values()):
                return True
        for filters in c.per_site_ip_filters.values():
            if filters.get(Decision.ALLOW):
                return True
        return False

    def check_is_allowed(self, site: str, client_ip: str) -> bool:
        """Allowlist exemption for the log tailer (decision.go:185-216)."""
        c = self._snapshot
        site_map = c.per_site_decision_lists.get(site)
        if site_map is not None and site_map.get(client_ip) == Decision.ALLOW:
            return True
        site_filters = c.per_site_ip_filters.get(site)
        if site_filters:
            f = site_filters.get(Decision.ALLOW)
            if f is not None and f.allowed(client_ip):
                return True
        if c.global_decision_lists.get(client_ip) == Decision.ALLOW:
            return True
        f = c.global_ip_filters.get(Decision.ALLOW)
        if f is not None and f.allowed(client_ip):
            return True
        return False

    # for /decision_lists formatting
    def format_lists(self) -> Tuple[Dict[str, Dict[str, Decision]], Dict[str, Decision]]:
        c = self._snapshot
        return c.per_site_decision_lists, c.global_decision_lists
