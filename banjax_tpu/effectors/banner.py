"""Banner: the ban effector every decision source streams into.

Reference behavior: /root/reference/internal/iptables.go:117-331 — an
interface (mockable in tests) whose implementation (1) inserts an expiring
Decision into the dynamic lists with TTL expiring_decision_ttl_seconds,
(2) escalates IptablesBlock to an ipset add (skipping localhost, standalone
testing, and already-banned IPs), and (3) writes structured JSON ban-log
lines — to banning_log_file, or to the `_temp` variant when the host is in
disable_logging (filebeat routes those to a to-be-deleted ES index).

This is the "Decision-list populator boundary" the TPU matcher streams
candidate decisions through (BASELINE.json).
"""

from __future__ import annotations

import json
import logging
import math
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, TextIO, Tuple

from banjax_tpu.config.schema import Config
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.model import Decision
from banjax_tpu.effectors.ipset import IpsetInstance
from banjax_tpu.obs import provenance

log = logging.getLogger(__name__)

# json.dumps(..., separators=(",", ":")) builds an encoder like this one on
# every call; the output is the same
_ENCODE = json.JSONEncoder(separators=(",", ":")).encode

# Field order matches the reference LogJson struct (iptables.go:164-177) so
# the serialized lines are byte-identical.
def _log_json(
    path: str,
    timestring: str,
    trigger: str,
    client_ua: str,
    client_ip: str,
    rule_type: str,
    http_method: str,
    http_schema: str,
    http_host: str,
    action: str,
    number_of_fails: int,
    disable_logging: int,
) -> str:
    return _ENCODE(
        {
            "path": path,
            "timestring": timestring,
            "trigger": trigger,
            "client_ua": client_ua,
            "client_ip": client_ip,
            "rule_type": rule_type,
            "client_request_method": http_method,
            "http_request_scheme": http_schema,
            "client_request_host": http_host,
            "action": action,
            "number_of_fails": number_of_fails,
            "disable_logging": disable_logging,
        }
    )


def _format_ban_time(unix_seconds: float) -> str:
    # Go layout "2006-01-02T15:04:05" (iptables.go:187) — local time
    return time.strftime("%Y-%m-%dT%H:%M:%S", time.localtime(unix_seconds))


class RegexBan(NamedTuple):
    """One exceeded window event of the regex rate limiter, as the banner
    takes it: what the decision insert, the ban-log line and the
    provenance record of one ban need between them."""

    ip: str
    host: str
    decision: Decision
    rule_name: str
    rule_index: int
    hits: int  # the window's count when the rule fired
    log_time_unix: float
    rest: str  # the log line after timestamp and address


class BannerInterface:
    """iptables.go:117-126. Subclasses: Banner (real), MockBanner (tests)."""

    def apply_regex_bans(
        self, config: Config, records: Sequence[RegexBan],
    ) -> List[Tuple[int, Exception]]:
        """The exceeded events of one applied chunk, in reference order:
        the decision insert, the ban-log line and the provenance record of
        each.  → (index, what it raised) for every record whose effect
        failed; the others are applied, once.  This body takes them one by
        one through the three single-record calls; `Banner` takes the
        chunk as one batch."""
        failed = []
        for k, r in enumerate(records):
            try:
                self.ban_or_challenge_ip(config, r.ip, r.decision, r.host)
                self.log_regex_ban(
                    config, r.log_time_unix, r.ip, r.rule_name, r.rest,
                    r.decision,
                )
                # the ambient drain span supplies the admitting batch's
                # trace id
                provenance.record(
                    provenance.SOURCE_RATE_LIMIT, r.ip, r.decision,
                    rule=r.rule_name, rule_index=r.rule_index, hits=r.hits,
                )
            except Exception as e:  # noqa: BLE001 — a failing effector loses one line, not the batch
                failed.append((k, e))
        return failed

    def ban_or_challenge_ip(self, config: Config, ip: str, decision: Decision, domain: str) -> None:
        raise NotImplementedError

    def log_regex_ban(
        self, config: Config, log_time_unix: float, ip: str, rule_name: str,
        log_line_rest: str, decision: Decision,
    ) -> None:
        raise NotImplementedError

    def log_failed_challenge_ban(
        self, config: Config, ip: str, challenge_type: str, host: str, path: str,
        too_many_failed_challenges_threshold: int, user_agent: str,
        decision: Decision, method: str,
    ) -> None:
        raise NotImplementedError

    def ipset_add(self, config: Config, ip: str) -> None:
        raise NotImplementedError

    def ipset_test(self, config: Config, ip: str) -> bool:
        raise NotImplementedError

    def ipset_list(self) -> List[str]:
        raise NotImplementedError

    def ipset_del(self, ip: str) -> None:
        raise NotImplementedError


class Banner(BannerInterface):
    def __init__(
        self,
        decision_lists: DynamicDecisionLists,
        ban_log_file: TextIO,
        ban_log_file_temp: TextIO,
        ipset_instance: Optional[IpsetInstance],
        netlink_writer=None,
    ):
        self.decision_lists = decision_lists
        self.regex_ban_records = 0  # ban-log lines of the regex rate limiter
        self.regex_ban_batches = 0  # calls of apply_regex_bans
        # one `write` and `flush` of a ban-log file each, by file
        self.ban_log_writes: Dict[str, int] = {"main": 0, "temp": 0}
        self._ban_log = ban_log_file
        self._ban_log_temp = ban_log_file_temp
        self._ipset = ipset_instance
        # batched kernel-edge writer (effectors/ipset_netlink.py): adds
        # ride the coalesced netlink queue; the admin-surface reads
        # (test/list/del) keep the subprocess shim
        self.netlink_writer = netlink_writer
        self._log_lock = threading.Lock()

    @property
    def ipset_batching(self) -> bool:
        return self.netlink_writer is not None and self._ipset is not None

    def apply_regex_bans(
        self, config: Config, records: Sequence[RegexBan],
    ) -> List[Tuple[int, Exception]]:
        """One pass a step over the whole chunk, so that what gives the
        interpreter up or takes a lock — the decision lists' lock and the
        mirror's native call, the write and the flush of a ban-log file,
        the ledger's locks — happens once a chunk and not once a record.
        Lists, files and ledger end as the one-by-one loop leaves them,
        and every line is written and flushed before this returns."""
        self.regex_ban_batches += 1
        if log.isEnabledFor(logging.INFO):
            for r in records:
                log.info("BANNER: ban_or_challenge_ip %s %s", r.ip, r.decision)
        expires = time.time() + config.expiring_decision_ttl_seconds
        self.decision_lists.update_many(
            [(r.ip, r.decision, r.host) for r in records], expires
        )
        failed: Dict[int, Exception] = {}
        by_target: Tuple[list, list] = ([], [])  # main, temp: (index, line)
        timestrings: Dict[int, str] = {}
        for k, r in enumerate(records):
            try:
                if r.decision == Decision.IPTABLES_BLOCK:
                    _ban_ip(config, r.ip, self)
                built = self._regex_ban_line(
                    config, r.log_time_unix, r.ip, r.rule_name, r.rest,
                    r.decision, timestrings,
                )
            except Exception as e:  # noqa: BLE001 — a failing effector loses one line, not the batch
                failed[k] = e
                continue
            if built is not None:
                by_target[built[1]].append((k, built[0]))
        for disable_logging, lines in enumerate(by_target):
            if not lines:
                continue
            try:
                self._write([line for _, line in lines], disable_logging)
            except Exception as e:  # noqa: BLE001 — the lines of this write, not the batch
                failed.update((k, e) for k, _ in lines)
            else:
                self.regex_ban_records += len(lines)

        provenance.record_many(provenance.SOURCE_RATE_LIMIT, [
            (r.ip, r.decision, r.rule_name, r.rule_index, r.hits)
            for k, r in enumerate(records) if k not in failed
        ])
        return sorted(failed.items())

    def ban_or_challenge_ip(self, config: Config, ip: str, decision: Decision, domain: str) -> None:
        """iptables.go:273-294."""
        log.info("BANNER: ban_or_challenge_ip %s %s", ip, decision)
        expires = time.time() + config.expiring_decision_ttl_seconds
        self.decision_lists.update(ip, expires, decision, False, domain)
        if decision == Decision.IPTABLES_BLOCK:
            _ban_ip(config, ip, self)

    def log_regex_ban(
        self, config: Config, log_time_unix: float, ip: str, rule_name: str,
        log_line_rest: str, decision: Decision,
    ) -> None:
        built = self._regex_ban_line(
            config, log_time_unix, ip, rule_name, log_line_rest, decision, {}
        )
        if built is not None:
            self._write([built[0]], built[1])
            self.regex_ban_records += 1

    def _regex_ban_line(
        self, config: Config, log_time_unix: float, ip: str, rule_name: str,
        log_line_rest: str, decision: Decision, timestrings: Dict[int, str],
    ) -> Optional[Tuple[str, int]]:
        """iptables.go:179-228 → (the ban-log line, disable_logging); None
        for a line of fewer than six words.

        log_line_rest looks like: `GET localhost:8081 GET /x HTTP/1.1 agent`
        words: [method, host, method, path, proto, ua(+ optional | status)].
        `timestrings` keeps the time string of each whole second for the
        caller's other records."""
        words = log_line_rest.split(" ", 5)
        if len(words) < 6:
            log.warning("log_regex_ban: not enough words")
            return None

        disable_logging = 1 if config.disable_logging.get(words[1]) else 0
        # the nginx banjax_format appends "| <status>" after the UA for some
        # rules; keep only what's left of the first vertical bar
        client_ua = words[5].split("|", 1)[0].strip()
        # localtime() rounds down too
        second = math.floor(log_time_unix)
        timestring = timestrings.get(second)
        if timestring is None:
            timestring = timestrings[second] = _format_ban_time(second)

        line = _log_json(
            path=words[3],
            timestring=timestring,
            trigger=rule_name,
            client_ua=client_ua,
            client_ip=ip,
            rule_type="regex",
            http_method=words[0],
            http_schema="https",  # reference hardcodes https (iptables.go:213)
            http_host=words[1],
            action=str(decision),
            number_of_fails=1,
            disable_logging=disable_logging,
        )
        return line, disable_logging

    def log_failed_challenge_ban(
        self, config: Config, ip: str, challenge_type: str, host: str, path: str,
        too_many_failed_challenges_threshold: int, user_agent: str,
        decision: Decision, method: str,
    ) -> None:
        """iptables.go:230-271."""
        disable_logging = 1 if config.disable_logging.get(host) else 0
        line = _log_json(
            path=path,
            timestring=_format_ban_time(time.time()),
            trigger=f"failed challenge {challenge_type}",
            client_ua=user_agent,
            client_ip=ip,
            rule_type="failed_challenge",
            http_method=method,
            http_schema="https",
            http_host=host,
            action=str(decision),
            number_of_fails=too_many_failed_challenges_threshold,
            disable_logging=disable_logging,
        )
        self._write([line], disable_logging)

    def _write(self, lines: List[str], disable_logging: int) -> None:
        """One write and one flush of the file these lines belong in."""
        name, target = (
            ("temp", self._ban_log_temp) if disable_logging == 1
            else ("main", self._ban_log)
        )
        with self._log_lock:
            target.write("\n".join(lines) + "\n")
            target.flush()
            self.ban_log_writes[name] += 1

    def ipset_add(self, config: Config, ip: str) -> None:
        if self._ipset is None:
            return
        if self.netlink_writer is not None:
            # never blocks, never raises: overflow sheds (counted) and
            # netlink failures fall back to the subprocess shim inside
            # the writer's drain thread
            self.netlink_writer.enqueue(ip, config.iptables_ban_seconds)
            return
        self._ipset.add(ip, config.iptables_ban_seconds)

    def ipset_test(self, config: Config, ip: str) -> bool:
        # iptables.go:300-303: `banned, _ := b.IPSetInstance.Test(ip)` —
        # errors are ignored and read as "not banned"
        if self._ipset is None:
            return False
        try:
            return self._ipset.test(ip)
        except Exception:  # noqa: BLE001 — mirror the ignored error
            return False

    def ipset_list(self) -> List[str]:
        if self._ipset is None:
            return []
        return self._ipset.list_entries()

    def ipset_del(self, ip: str) -> None:
        if self._ipset is not None:
            self._ipset.delete(ip)


def _ban_ip(config: Config, ip: str, banner: BannerInterface) -> None:
    """iptables.go:313-331 — skip localhost, skip in testing, no double ban."""
    log.info("ban_ip: %s timeout %s", ip, config.iptables_ban_seconds)
    if ip == "127.0.0.1":
        log.info("ban_ip: not going to block localhost")
        return
    if config.standalone_testing:
        log.info("ban_ip: not calling ipset in testing")
        return
    if getattr(banner, "ipset_batching", False):
        # the batched writer's adds are idempotent (`-exist` semantics on
        # both the netlink and subprocess paths), so the pre-add Test —
        # one extra fork per ban — buys nothing; skip straight to enqueue
        banner.ipset_add(config, ip)
        return
    if banner.ipset_test(config, ip):
        log.info("ban_ip: no double ban %s", ip)
        return
    try:
        banner.ipset_add(config, ip)
    except Exception as e:  # reference logs and continues (iptables.go:328-330)
        log.error("ban_ip ipset add failed: %s", e)
