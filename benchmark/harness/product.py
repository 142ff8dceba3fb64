"""The system under test, driven through its own entry point.

`BanjaxApp(config)` + `start_background()`: the real tailer, pipeline
scheduler, `TpuMatcher`, banner and fastserve, in STANDALONE mode (no root,
tails `testing-log-file.txt` in the working directory, takes an
`/auth_request`'s client address from `X-Client-IP`).  Port 8081 is fixed in
the product, so one run at a time on a machine.

From the program this module takes the app, its public `/metrics` and
`/healthz`, the matcher's `describe()`, `compile_events()`, and the
pipeline's drain observer (`_on_results`, the only per-line completion
stamp the program offers today).  The warm-up recipe is `chip_smoke.py`'s,
cut to the shapes a cell's traffic file lists.
"""

from __future__ import annotations

import collections
import http.client
import json
import os
import time

from benchmark.harness import ctl as ctl_mod
from benchmark.harness import found, lines as lines_mod

PORT = 8081
LOG_NAME = "testing-log-file.txt"
BAN_LOG = "banning-log-file.txt"
WARM_BASE = 10


class NotReady(Exception):
    """Set-up could not bring the product to a warm, healthy state."""


def place_cache(jax) -> str:
    """JAX's persistent compile cache, before the first jit: where
    JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache (a fixed
    path; the path is part of the key).  Programs that compile in under a
    second are cached too: a warm start then loads every program."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(found.REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def write_config(workdir: str, config: dict, rules: list, extra: dict) -> str:
    """The shipped deploy config with the configuration's overrides."""
    import yaml

    with open(os.path.join(found.REPO, "deploy", "banjax-config.yaml"),
              encoding="utf-8") as f:
        cfg = yaml.safe_load(f)
    # schema default, whatever the deploy file ships (PoW is off this path)
    cfg.pop("challenge_device_verify", None)
    for key in ("global_decision_lists", "per_site_decision_lists"):
        lists = cfg.get(key) or {}
        if any(v for v in (lists.values() if isinstance(lists, dict) else [])):
            raise SystemExit(f"deploy config has {key}; the reference has none")
    cfg.update(config["product_config"])
    cfg.update(extra)
    # the product lays per-site rules first, so its column is not the
    # harness's rule index (the generator's); nothing here assumes it is
    per_site = {}
    cfg["regexes_with_rates"] = []
    for rule, record in zip(rules, found.product_rules(rules)):
        if rule.get("_site"):
            per_site.setdefault(rule["_site"], []).append(record)
        else:
            cfg["regexes_with_rates"].append(record)
    if per_site:
        cfg["per_site_regexes_with_rates"] = per_site
    path = os.path.join(workdir, "banjax-config.yaml")
    with open(path, "w", encoding="utf-8") as f:
        yaml.safe_dump(cfg, f, sort_keys=False)
    return path


def http_get(path: str, headers: dict | None = None, timeout: float = 10.0):
    conn = http.client.HTTPConnection("127.0.0.1", PORT, timeout=timeout)
    try:
        conn.request("GET", path, headers=headers or {})
        r = conn.getresponse()
        return r.status, r.getheader("X-Accel-Redirect") or "", r.read()
    finally:
        conn.close()


def probe(ip: str) -> tuple:
    status, redirect, _ = http_get(
        "/auth_request?path=/",
        {"X-Client-IP": ip, "Host": "example.com",
         "User-Agent": "benchmark-probe/1"})
    return status, redirect


class Product:
    def __init__(self, config_path: str, ctl):
        from banjax_tpu.cli import BanjaxApp

        self.ctl = ctl
        self.batches = []  # (wall time drained, lines as one text, old flags)
        self.app = BanjaxApp(config_path, standalone_testing=True)
        self.app.start_background(timeout=600.0)
        if not self.app.tailer.opened.wait(60):
            raise NotReady("tailer never opened its log")
        self.app.pipeline._on_results = self._observe
        self._log = open(LOG_NAME, "a", encoding="ascii")
        self._sent = 0
        self._fresh = 0
        self.tail_lines = 0

    # the drain observer: one stamp and one store per batch.  The lines
    # are kept as one text and the results not at all: millions of objects
    # held here would make the collector's full passes, which stop every
    # thread of the product, longer as the run goes on.  A line can be
    # dropped as older than 10 s when it is parsed, submitted or drained,
    # and only the last two are counted on /metrics: its result says so
    def _observe(self, lines, results) -> None:
        old = [r.old_line for r in results]
        self.batches.append((time.time(), "\n".join(lines),
                             old if any(old) else None))
        self.ctl.put(ctl_mod.PROCESSED,
                     self.app.pipeline.stats.processed_lines)

    def drained(self, since: float) -> list:
        """→ [(wall time drained, lines, lines less those it dropped as
        too old)] of the batches drained from `since` on."""
        out = []
        for t, text, old in self.batches:
            if t >= since:
                lines = text.split("\n")
                out.append((t, lines, lines if old is None else [
                    ln for ln, o in zip(lines, old) if not o]))
        return out

    @property
    def matcher(self):
        return self.app._matcher

    def counters(self) -> dict:
        st = self.app.pipeline.stats
        m = self.matcher
        return {
            "processed": st.processed_lines,
            "admitted": st.admitted_lines,
            "stale": st.stale_dropped_lines,
            "shed": st.shed_lines,
            "drain_errors": st.drain_error_lines,
            "generic_batches": st.fallback_batches,
            "builds": m.compile_events() if m is not None else 0,
            "evictions": getattr(getattr(m, "device_windows", None),
                                 "eviction_count", 0),
            "budget_trips": getattr(m, "budget_trips", 0),
            "cpu_fallback_batches": getattr(m, "fallback_batches", 0),
        }

    def send(self, ip_rests: list, timeout: float = 900.0) -> tuple:
        """Set-up only: stamp now, append, wait until drained.
        → (wall seconds, counter deltas)."""
        before = self.counters()
        t0 = time.time()
        self._log.write("".join(
            f"{t0 + i * 1e-6:.6f} {ip} {rest}\n"
            for i, (ip, rest) in enumerate(ip_rests)))
        self._log.flush()
        self._sent += len(ip_rests)
        deadline = time.monotonic() + timeout
        st = self.app.pipeline.stats
        while st.admitted_lines < self._sent:
            if time.monotonic() > deadline:
                raise NotReady("the tailer did not pick up the lines in time")
            time.sleep(0.005)
        if not self.app.pipeline.flush(max(1.0, deadline - time.monotonic())):
            raise NotReady("the pipeline did not drain in time")
        after = self.counters()
        return time.time() - t0, {k: after[k] - before[k] for k in after}

    def write_tail(self, rest: str, n: int = 8) -> None:
        """A few lines from a warm-up address behind the feed's last."""
        t = time.time()
        self._log.write("".join(
            f"{t + i * 1e-6:.6f} {WARM_BASE}.0.0.{i} {rest}\n" for i in range(n)))
        self._log.flush()
        self.tail_lines += n

    def fresh_ips(self, n: int) -> list:
        """Never-seen client addresses (10.200.0.0 upward)."""
        i0, self._fresh = self._fresh, self._fresh + n
        return [f"{WARM_BASE}.{200 + (i >> 16)}.{(i >> 8) & 255}.{i & 255}"
                for i in range(i0, i0 + n)]

    def warm_up(self, traffic: dict, rests: list, capacity: int,
                batch_lines: int) -> dict:
        """Load or build every device program the cell's traffic reaches.

        The product cuts what it reads into batches of its sizer's current
        target, so one send is one batch only while the target allows it:
        a chunk therefore holds lines of one length bucket only (whatever
        the cut, every piece has the chunk's line length), the table is
        filled before the larger row buckets are asked for (the fill
        drives the sizer to the target it will hold under load), and each
        send is repeated until it drains with nothing built, dropped or
        cut.  Lines older than 10 s are dropped and a cold Mosaic build
        takes longer, so each send is stamped when written."""
        warm = traffic["warm"]
        by_lp = collections.defaultdict(list)
        for rest in rests:
            by_lp[lines_mod.line_bucket(rest)].append(rest)
        obs = {"loads": 0, "load_s": 0.0, "programs": [], "cut": [],
               "unsettled": []}
        ips = [f"{WARM_BASE}.0.{(i >> 8) & 255}.{i & 255}" for i in range(1 << 14)]
        lps = sorted(k for k in by_lp if by_lp[k])
        long_rests = by_lp[lps[-1]]

        def chunk(n: int, lp: int) -> list:
            return [(ips[i % len(ips)], by_lp[lp][i % len(by_lp[lp])])
                    for i in range(n)]

        def fresh(n: int) -> list:
            return [(ip, long_rests[i % len(long_rests)])
                    for i, ip in enumerate(self.fresh_ips(n))]

        def lost(d: dict) -> list:
            """Why a send proved nothing: lines dropped or rerouted."""
            return [k for k in ("stale", "shed", "drain_errors",
                                "generic_batches", "budget_trips") if d[k]]

        def warm_one(label: str, rows, must: bool = True) -> None:
            """Send until a send drains whole, as one batch, with nothing
            built.  `rows`: a list, or a function that makes the next
            try's.  A send the sizer cut, or (`must` false) one that never
            drained clean, is noted and left to the run-in."""
            for _ in range(6):
                n_before = len(self.batches)
                wall, d = self.send(rows() if callable(rows) else rows)
                cut = len(self.batches) - n_before > 1
                why = lost(d) + (["built"] if d["builds"] else [])
                if why:
                    obs["loads"] += 1
                    obs["load_s"] += wall
                    obs["programs"].append(
                        f"{label}:{wall:.1f}s" + "".join(
                            f"({k})" for k in why if k != "built"))
                elif not cut:
                    return
            if not cut and must:
                raise NotReady(f"warm-up: {label} never drained warm")
            obs["cut" if cut else "unsettled"].append(label)

        def wanted(b: int) -> list:
            out = list(warm["line_buckets"])
            if b <= warm.get("small_rows_max", 0):
                out += warm["small_line_buckets"]
            return [lp for lp in sorted(set(out)) if lp in by_lp]

        small = [b for b in warm["rows"] if b <= warm.get("small_rows_max", 0)]
        # the first send also builds the matcher (rule compile, self-tests)
        warm_one("start", chunk(100, lps[-1]))
        for b in small:
            for lp in wanted(b):
                warm_one(f"rows<={b},L{lp}", chunk(max(2, int(b * 0.9)), lp))
        # the table as a deployment holds it: full.  A send that a build
        # held up for over 10 s is dropped as stale and fills nothing, so
        # the fill ends on the product's own count of evictions
        t0 = time.time()
        sent, c_fill = 0, self.counters()
        while sent < capacity or self.counters()["evictions"] == c_fill["evictions"]:
            if sent >= 4 * capacity:
                raise NotReady("warm-up: the slot table never filled")
            n = min(capacity, 4 * batch_lines)
            self.send(fresh(n))
            sent += n
        obs["fill_s"] = time.time() - t0
        obs["fill_lines"] = sent
        obs["fill_builds"] = self.counters()["builds"] - c_fill["builds"]
        for b in warm["rows"]:
            if b not in small and b <= batch_lines:
                for lp in wanted(b):
                    warm_one(f"rows<={b},L{lp}",
                             chunk(max(2, int(b * 0.9)), lp))
        # a batch of n never-seen IPs evicts n slots of the full table; the
        # maintenance step is built per pair of power-of-two classes
        # (n x rules keys, n slots), and with 1,000 rules the two change
        # class at different n: 261 gives (262144, 512) where 256 gives
        # (262144, 256).  Cold, each pair from 65536 keys up is a build
        # of 5-8 s, and the feed reaches the rarer pairs minutes apart.
        # Largest first: the sizer stands at its largest target right
        # after the rows above, and these sends, every line a miss, cost
        # more per line than it has seen there and make it shrink
        for n in sorted(warm["evict_sizes"], reverse=True):
            if n <= batch_lines:
                warm_one(f"evict{n}", lambda n=n: fresh(n), must=False)
        return obs

    def metrics_text(self) -> str:
        status, _, body = http_get("/metrics", timeout=60.0)
        if status != 200:
            raise NotReady(f"/metrics answered {status}")
        return body.decode("utf-8")

    def healthz(self) -> dict:
        return json.loads(http_get("/healthz")[2])

    def ban_log(self, ip_prefix: str) -> list:
        self.app._banning_log_file.flush()
        with open(BAN_LOG, encoding="utf-8") as f:
            return [x for x in f.read().splitlines()
                    if json.loads(x)["client_ip"].startswith(ip_prefix)]

    def stop(self) -> None:
        try:
            self._log.close()
        finally:
            self.app.stop_background()
