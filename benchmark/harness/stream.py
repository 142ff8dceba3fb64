"""The line stream of a cell: which request string and which client IP
each line carries, as arrays drawn from the seed in blocks.

Two processes build the same stream from the same seed: the generator
process, which writes it, and (for the control only) the benchmark's main
process, which reads off which rule the stream crosses first.  No import
of the program, no JAX.

Client IPs.  `zipf`: rank r of `pool` addresses has weight r^-s (s = 0.99,
YCSB's default constant); ranks are scattered over 11.0.0.0/12 by a
seeded bijection so the head is no run of neighbours.  `uniform`: every
address of `pool` equally likely.  Attackers are `11.255.x.y`; the first
`heavy` of them send `heavy_share` of the attack lines.

Slow attackers (`slow_attackers` = {"count": n, "every_lines": k}) are
`11.254.x.y`.  Every k-th line of the stream is one of theirs, taken in
turn, so each comes back after n * k lines with the same request string,
which one rule matches.  n * k is set above the lines the slot table takes
to turn over, so each of their hits finds the address evicted: its
counters went to the warm tier and have to come back from it, and the
ban lands on the third visit only if they did.
"""

from __future__ import annotations

import numpy as np

BLOCK = 1 << 18
IP_BASE = 11  # traffic; warm-up uses 10.x and the table fill 10.200.x
_SPACE = 1 << 20


def seed32(*parts: int) -> int:
    """Mix whole numbers of any size into a 32-bit seed."""
    h = 0x9E3779B97F4A7C15
    for p in parts:
        h = (h ^ (int(p) & 0xFFFFFFFFFFFFFFFF)) * 0xBF58476D1CE4E5B9
        h &= 0xFFFFFFFFFFFFFFFF
        h ^= h >> 31
    return h & 0x7FFFFFFF


def ip_string(base: int, i: int) -> str:
    return f"{base}.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"


def attacker_ips(n: int, base: int = IP_BASE) -> list:
    return [f"{base}.255.{250 + (i >> 8)}.{i & 255}" for i in range(n)]


def slow_attacker_ips(n: int, base: int = IP_BASE) -> list:
    return [f"{base}.254.{i >> 8}.{i & 255}" for i in range(n)]


class IpDraw:
    """Benign client addresses for `ips` = {"draw": "zipf"|"uniform",
    "pool": n, "s": constant}."""

    def __init__(self, ips: dict, seed: int):
        self.pool = int(ips["pool"])
        if not 0 < self.pool <= _SPACE:
            raise SystemExit(f"ips.pool must be 1..{_SPACE}")
        self.kind = ips["draw"]
        if self.kind == "zipf":
            w = np.arange(1, self.pool + 1, dtype=np.float64) ** -float(ips["s"])
            self.cdf = np.cumsum(w)
            self.cdf /= self.cdf[-1]
        elif self.kind != "uniform":
            raise SystemExit(f"unknown ips.draw {self.kind!r}")
        # odd multiplier + offset: a bijection on 2**20 addresses
        self.mul = (seed32(seed, 1) | 1) % _SPACE
        self.add = seed32(seed, 2) % _SPACE

    def ranks(self, rng: np.random.Generator, n: int) -> np.ndarray:
        if self.kind == "zipf":
            return np.searchsorted(self.cdf, rng.random(n)).astype(np.int64)
        return rng.integers(0, self.pool, n)

    def address_index(self, ranks: np.ndarray) -> np.ndarray:
        return (ranks * self.mul + self.add) % _SPACE


class Stream:
    """Blocks of (ip strings, rest indices).  `rests` is the benign pool
    followed by the attack pool; an attack line goes to an attacker
    address where the traffic has attackers, else to a benign draw."""

    def __init__(self, traffic: dict, n_benign: int, n_attack: int, seed: int):
        lines = traffic["lines"]
        self.seed = seed
        self.n_benign = n_benign
        self.n_attack = n_attack
        self.attack_share = float(lines.get("attack_share", 0.0))
        att = traffic.get("attackers", {})
        self.attackers = attacker_ips(int(att.get("count", 0)))
        self.heavy = int(att.get("heavy", 0))
        self.heavy_share = float(att.get("heavy_share", 0.0))
        slow = traffic.get("slow_attackers", {})
        self.slow = slow_attacker_ips(int(slow.get("count", 0)))
        self.slow_every = int(slow.get("every_lines", 0))
        if self.slow and not (n_attack and 0 < self.slow_every <= BLOCK):
            raise SystemExit("slow_attackers needs attack lines and "
                             f"every_lines in 1..{BLOCK}")
        # the one attack line each slow attacker sends, fixed by the seed
        self.slow_rest = (n_benign + np.random.default_rng(
            seed32(seed, 5)).integers(0, max(1, n_attack), len(self.slow)))
        self.draw = IpDraw(traffic["ips"], seed)

    def block(self, k: int):
        """→ (ips: list[str], rest_idx: list[int]) for lines
        [k*BLOCK, (k+1)*BLOCK)."""
        rng = np.random.default_rng([seed32(self.seed, 3), k])
        n = BLOCK
        is_attack = rng.random(n) < self.attack_share if self.n_attack else \
            np.zeros(n, dtype=bool)
        rest = rng.integers(0, self.n_benign, n)
        n_att = int(is_attack.sum())
        rest[is_attack] = self.n_benign + rng.integers(0, max(1, self.n_attack), n_att)
        addr = self.draw.address_index(self.draw.ranks(rng, n))
        uniq, inv = np.unique(addr, return_inverse=True)
        table = [ip_string(IP_BASE, int(i)) for i in uniq]
        ips = [table[j] for j in inv.tolist()]
        if self.attackers and n_att:
            heavy = rng.random(n_att) < self.heavy_share
            who = np.where(
                heavy,
                rng.integers(0, max(1, self.heavy), n_att),
                rng.integers(min(self.heavy, len(self.attackers) - 1),
                             len(self.attackers), n_att),
            )
            for pos, w in zip(np.flatnonzero(is_attack).tolist(), who.tolist()):
                ips[pos] = self.attackers[w]
        rest = rest.tolist()
        if self.slow:
            first = -(-k * BLOCK // self.slow_every)  # visits before this block
            for v in range(first, -(-(k + 1) * BLOCK // self.slow_every)):
                pos, j = v * self.slow_every - k * BLOCK, v % len(self.slow)
                ips[pos] = self.slow[j]
                rest[pos] = int(self.slow_rest[j])
        return ips, rest
