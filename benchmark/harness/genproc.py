"""The line generator: a process of its own that never imports JAX or the
program.  It builds its pools while the product warms up, appends stamped
`banjax_format` lines to the tailed log when told to go, and leaves a
record of every write (due time, time written, lines, backlog before).

    python benchmark/harness/genproc.py <args.json>
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import numpy as np  # noqa: E402

from benchmark.harness import ctl as ctl_mod  # noqa: E402
from benchmark.harness import found, lines, stream  # noqa: E402


def build_pools(rules: list, traffic: dict, seed: int):
    """→ (rests: benign pool then attack pool, n_benign, attack rule index
    per attack line)."""
    spec = traffic["lines"]
    cap = int(spec["max_rest_len"])
    hosts = None
    if traffic.get("hosts"):
        hosts = lines.SiteHosts(rules, traffic["hosts"], seed)
    elif any(r.get("_site") for r in rules):
        raise SystemExit("the ruleset has per-site rules: the traffic file "
                         "needs a `hosts` block")
    benign = lines.benign_pool(int(spec["benign_pool"]), spec["method_mix"],
                               cap, seed, hosts)
    n_attack = int(spec.get("attack_pool", 0))
    attack = (lines.attack_pool(n_attack, rules, cap, seed, hosts)
              if n_attack else [])
    return (benign + [r for _, r in attack], len(benign),
            [i for i, _ in attack])


class Feed:
    """What a feed kind sees: write(n, due), backlog(), stopped(), t_go."""

    def __init__(self, ctl, fd: int, strm: stream.Stream, rests: list):
        self.ctl = ctl
        self.fd = fd
        self.strm = strm
        self.rests = rests
        self.written = 0
        self.base = 0
        self.t_go = 0.0
        self._k = 0
        self._ips, self._ridx = strm.block(0)
        self._pos = 0
        self.rec = []  # (due, t_written, n, backlog_before)

    def stopped(self) -> bool:
        return self.ctl.get(ctl_mod.STOP) != 0

    def backlog(self) -> int:
        return self.written - (self.ctl.get(ctl_mod.PROCESSED) - self.base)

    def note(self) -> None:
        """A look at the backlog with nothing written."""
        self.rec.append((0.0, time.time(), 0, self.backlog()))

    def _take(self, n: int):
        ips, ridx = [], []
        while n:
            if self._pos == stream.BLOCK:
                self._k += 1
                self._ips, self._ridx = self.strm.block(self._k)
                self._pos = 0
            m = min(n, stream.BLOCK - self._pos)
            ips += self._ips[self._pos:self._pos + m]
            ridx += self._ridx[self._pos:self._pos + m]
            self._pos += m
            n -= m
        return ips, ridx

    def write(self, n: int, due: float) -> None:
        before = self.backlog()
        ips, ridx = self._take(n)
        rests = self.rests
        buf = "".join([
            f"{due + i * 1e-6:.6f} {ip} {rests[r]}\n"
            for i, (ip, r) in enumerate(zip(ips, ridx))
        ])
        os.write(self.fd, buf.encode("ascii"))
        self.written += n
        self.ctl.put(ctl_mod.WRITTEN, self.written)
        self.rec.append((due, time.time(), n, before))


def main(argv) -> int:
    with open(argv[1], encoding="utf-8") as f:
        a = json.load(f)
    traffic, seed = a["traffic"], int(a["seed"])
    c = ctl_mod.Ctl(a["ctl"])
    rules = found.ruleset(a["ruleset"])
    rests, n_benign, _ = build_pools(rules, traffic, seed)
    strm = stream.Stream(traffic, n_benign, len(rests) - n_benign, seed)
    fd = os.open(a["log"], os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
    feed = Feed(c, fd, strm, rests)
    kind = found.module("kinds", traffic["feed"]["kind"])
    c.put(ctl_mod.GEN_READY, 1)
    try:
        while c.get_f(ctl_mod.T_GO) == 0.0:
            if feed.stopped():
                return 0
            time.sleep(0.002)
        feed.t_go = c.get_f(ctl_mod.T_GO)
        feed.base = c.get(ctl_mod.PROCESSED)
        wait = feed.t_go - time.time()
        if wait > 0:
            time.sleep(wait)
        kind.run(feed, traffic["feed"])
    finally:
        os.close(fd)
        np.save(a["report"], np.asarray(feed.rec, dtype=np.float64).reshape(-1, 4))
        c.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
