"""Dynamic (runtime) expiring decision lists.

Reference behavior: /root/reference/internal/decision.go:379-604 — two
mutex-protected maps (ip → ExpiringDecision, session_id → ExpiringDecision)
with: monotonic-severity updates (a new decision ≤ the existing one is a
no-op), lazy expiry on read, a 9-second background sweep, per-domain listing
for the /banned API, and Clear() on hot reload.

This host-side dict stays the single source of truth for Decisions (the
acceptance bar is byte-identical Decision output); the TPU matcher produces
*candidate* decisions that are merged through the same `update()` below.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from banjax_tpu.decisions.model import Decision
from banjax_tpu.obs import provenance

SWEEP_INTERVAL_SECONDS = 9  # decision.go:396


@dataclasses.dataclass
class ExpiringDecision:
    """decision.go:60-66."""

    decision: Decision
    expires: float  # unix seconds
    ip_address: str
    from_baskerville: bool
    domain: str


@dataclasses.dataclass
class BannedEntry:
    """Entry of the /banned API response (config.go:133-148)."""

    ip_or_session_id: str
    domain: str
    decision: str
    expires: float
    from_baskerville: bool


class DynamicDecisionLists:
    def __init__(self, start_sweeper: bool = True):
        self._lock = threading.Lock()
        self._by_ip: Dict[str, ExpiringDecision] = {}
        self._by_session_id: Dict[str, ExpiringDecision] = {}
        self._mirror = None  # native decision table (set_mirror)
        self._stop = threading.Event()
        if start_sweeper:
            t = threading.Thread(target=self._sweep_loop, name="dynamic-lists-sweeper", daemon=True)
            t.start()

    def close(self) -> None:
        self._stop.set()

    def set_mirror(self, table) -> None:
        """Attach the native decision table (native/decisiontable.py):
        every mutation below is mirrored into it UNDER this list's lock,
        so the serving fast path and this dict move together.  Only the
        authoritative instance mirrors (the primary's list; worker
        replicas attach the shm segment read-only) — a replica mirroring
        too would double-apply every broadcast insert."""
        with self._lock:
            self._mirror = table

    # The mirror is an accelerator, never an authority: any table error
    # degrades to "the fast path misses and the chain serves it", so
    # mirror calls swallow everything (counted by the serving stats).
    def _mirror_put(self, ed: ExpiringDecision) -> None:
        if self._mirror is None:
            return
        try:
            self._mirror.put(
                ed.ip_address, int(ed.decision), ed.expires,
                ed.from_baskerville, ed.domain,
            )
        except Exception:  # noqa: BLE001
            self._note_mirror_error()

    def _mirror_put_many(self, eds: List[ExpiringDecision]) -> None:
        """A batch's inserts in their order, in one call of the table:
        they share `expires` and `from_baskerville` (update_many)."""
        if self._mirror is None or not eds:
            return
        try:
            self._mirror.put_many(
                [(ed.ip_address, int(ed.decision), ed.domain) for ed in eds],
                eds[0].expires, eds[0].from_baskerville,
            )
        except Exception:  # noqa: BLE001
            self._note_mirror_error()

    def _mirror_del(self, ip: str) -> None:
        if self._mirror is None:
            return
        try:
            self._mirror.delete(ip)
        except Exception:  # noqa: BLE001
            self._note_mirror_error()

    def _mirror_session(self, delta: int) -> None:
        if self._mirror is None:
            return
        try:
            self._mirror.session_add(delta)
        except Exception:  # noqa: BLE001
            self._note_mirror_error()

    @staticmethod
    def _note_mirror_error() -> None:
        try:
            from banjax_tpu.httpapi.serve_stats import get_stats

            get_stats().note_mirror_error()
        except Exception:  # noqa: BLE001
            pass

    def _insert_locked(
        self,
        ip: str,
        expires: float,
        new_decision: Decision,
        from_baskerville: bool,
        domain: str,
    ) -> Optional[ExpiringDecision]:
        """Monotonic-severity insert (decision.go:404-439) → the entry it
        made, None where the held decision is as severe or more."""
        existing = self._by_ip.get(ip)
        if existing is not None and new_decision <= existing.decision:
            return None
        ed = self._by_ip[ip] = ExpiringDecision(
            new_decision, expires, ip, from_baskerville, domain
        )
        return ed

    def update(
        self,
        ip: str,
        expires: float,
        new_decision: Decision,
        from_baskerville: bool,
        domain: str,
    ) -> None:
        with self._lock:
            ed = self._insert_locked(
                ip, expires, new_decision, from_baskerville, domain
            )
            if ed is not None:
                self._mirror_put(ed)

    def update_many(
        self,
        items: Sequence[Tuple[str, Decision, str]],
        expires: float,
        from_baskerville: bool = False,
    ) -> None:
        """`update` for each (ip, decision, domain) in order, under one
        hold of the lock: severity resolves as it does one by one, an
        address that comes twice included; the mirror takes the inserts
        that got through, in that order, in one call."""
        with self._lock:
            inserted = []
            for ip, decision, domain in items:
                ed = self._insert_locked(
                    ip, expires, decision, from_baskerville, domain
                )
                if ed is not None:
                    inserted.append(ed)
            self._mirror_put_many(inserted)

    def update_by_session_id(
        self,
        ip: str,
        session_id: str,
        expires: float,
        new_decision: Decision,
        from_baskerville: bool,
        domain: str,
    ) -> None:
        """decision.go:441-472."""
        with self._lock:
            existing = self._by_session_id.get(session_id)
            if existing is not None and new_decision <= existing.decision:
                return
            self._by_session_id[session_id] = ExpiringDecision(
                new_decision, expires, ip, from_baskerville, domain
            )
            if existing is None:
                # the fast path only needs to KNOW session entries exist
                # (its session guard defers any cookie-bearing request to
                # the chain); a count is enough, no session keys in shm
                self._mirror_session(1)

    def check(self, session_id: str, client_ip: str) -> Tuple[Optional[ExpiringDecision], bool]:
        """Session id first, then IP; lazy expiry on read (decision.go:474-500).

        Quirk preserved: a *found-but-expired* session entry returns
        (entry, False) without falling through to the IP lookup, exactly as
        the Go early-return at decision.go:487 does.
        """
        now = time.time()
        with self._lock:
            if session_id:
                ed = self._by_session_id.get(session_id)
                if ed is not None:
                    if now - ed.expires > 0:
                        del self._by_session_id[session_id]
                        self._mirror_session(-1)
                        provenance.record(
                            provenance.SOURCE_EXPIRY, ed.ip_address,
                            ed.decision, rule="session-lazy",
                        )
                        return ed, False
                    return ed, True
            ed = self._by_ip.get(client_ip)
            if ed is not None:
                if now - ed.expires > 0:
                    del self._by_ip[client_ip]
                    self._mirror_del(client_ip)
                    provenance.record(
                        provenance.SOURCE_EXPIRY, client_ip, ed.decision,
                        rule="lazy",
                    )
                    return ed, False
                return ed, True
        return None, False

    def peek(self, ip: str) -> Optional[ExpiringDecision]:
        """Read-only lookup for introspection (/decisions/explain): no
        lazy-expiry side effect — an admin read must not mutate the list
        (check() deletes expired entries and records their expiry)."""
        with self._lock:
            return self._by_ip.get(ip)

    def check_by_domain(self, domain: str) -> List[BannedEntry]:
        """decision.go:502-530 — entries with severity ≥ Challenge for a domain."""
        out: List[BannedEntry] = []
        with self._lock:
            for ip, ed in self._by_ip.items():
                if ed.domain == domain and ed.decision >= Decision.CHALLENGE:
                    out.append(BannedEntry(ip, ed.domain, str(ed.decision), ed.expires, ed.from_baskerville))
            for sid, ed in self._by_session_id.items():
                if ed.domain == domain and ed.decision >= Decision.CHALLENGE:
                    out.append(BannedEntry(sid, ed.domain, str(ed.decision), ed.expires, ed.from_baskerville))
        return out

    def remove_by_ip(self, ip: str) -> None:
        with self._lock:
            self._by_ip.pop(ip, None)
            self._mirror_del(ip)

    def clear(self) -> None:
        with self._lock:
            self._by_ip.clear()
            self._by_session_id.clear()
            if self._mirror is not None:
                try:
                    self._mirror.clear()
                except Exception:  # noqa: BLE001
                    self._note_mirror_error()

    def metrics(self) -> Tuple[int, int]:
        """(len_expiring_challenges, len_expiring_blocks) — decision.go:548-564."""
        challenges = 0
        blocks = 0
        with self._lock:
            for ed in self._by_ip.values():
                if ed.decision == Decision.CHALLENGE:
                    challenges += 1
                elif ed.decision in (Decision.NGINX_BLOCK, Decision.IPTABLES_BLOCK):
                    blocks += 1
        return challenges, blocks

    def format_ip_entries(self) -> Dict[str, ExpiringDecision]:
        with self._lock:
            return dict(self._by_ip)

    def _remove_expired(self) -> None:
        now = time.time()
        with self._lock:
            for ip in [ip for ip, ed in self._by_ip.items() if now - ed.expires > 0]:
                ed = self._by_ip.pop(ip)
                self._mirror_del(ip)
                provenance.record(
                    provenance.SOURCE_EXPIRY, ip, ed.decision, rule="sweep"
                )

    def _sweep_loop(self) -> None:
        while not self._stop.wait(SWEEP_INTERVAL_SECONDS):
            self._remove_expired()
