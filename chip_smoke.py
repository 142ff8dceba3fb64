#!/usr/bin/env python3
"""chip_smoke.py — the main path, once, on one TPU chip.

    python chip_smoke.py [--seed N] [--rehearse] [--mesh4] [OUTDIR]

Drives the product through its own entry point — `BanjaxApp(config)` +
`start_background()`, so the real tailer, pipeline scheduler, `TpuMatcher`,
banner and fastserve run — at a size an operator would call real: 1,000
rate-limit rules (`scenarios.synth.generate_rules`, BASELINE.json config 3), 65,536
device window slots, four windows of 65,536 access-log lines with 2 % attack
lines and more than 100,000 distinct client IPs (BASELINE.json config 4; more
IPs than slots, so LRU spill and warm-tier refill are on the path).

The app runs in STANDALONE mode (`standalone_testing=True`): that mode needs
no root (ipset is skipped), tails `testing-log-file.txt` in the working
directory and takes the client IP of an `/auth_request` probe from the
`X-Client-IP` header, with no change to the product.  Port 8081 is
hard-coded in the product; the script runs from OUTDIR (default
`<checkout>/chip_smoke_out`).

What is checked (results, not timings): the ban-log lines the product wrote
for the four checked windows equal, as a multiset and in per-IP order, the
ones `CpuMatcher` (banjax_tpu/matcher/cpu_ref.py) writes for the same lines
with the same timestamps; `/auth_request` answers the ban for banned IPs and
allow for clean ones; the matcher reports compiled Pallas kernels and the
fused protocol it resolved to; the CPU fallback and the breaker never
engaged; nothing is `degraded` in `/healthz`; no line of a checked window was
shed or dropped as stale.  Device programs built inside a checked window are
counted and printed (`builds`; 0 when the warm-up reached every size class —
programs that compile in under a second are never in JAX's persistent cache,
so a stray one costs a fraction of a second, not a stale drop).

One process uses the chip: no probe in a child, no CPU fallback.  The
reference runs in spawned children that never touch JAX.  Without a TPU the
script exits non-zero before anything else.  `--rehearse` runs the same flow
at a tiny size on the CPU backend and ends with `"ok": false`, so it can
never be mistaken for a chip run.  `--mesh4` runs ONLY the four-chip phase:
`TpuMatcher` with `matcher_mesh_devices: 4` against `CpuMatcher` on one
65,536-line stream, plus a check that every device holds its own shard of
the rule words and of the rows.

The last line of standard output is the result object and nothing else:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Earlier lines are smoke observations (cold/warm start-up seconds, wall
seconds per window), not metrics.
"""

from __future__ import annotations

import argparse
import collections
import http.client
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

FULL = dict(
    n_rules=1000, window_lines=65536, n_windows=4, ip_pool=150_000,
    capacity=65536, batch_lines=4096, min_distinct_ips=100_000,
    attackers=64, heavy_attackers=16, sleepers=24, pad_lines=100,
    max_warm_windows=10,
    ref_procs=12, min_banned_probes=3,
)
TINY = dict(
    n_rules=12, window_lines=1024, n_windows=2, ip_pool=900,
    capacity=256, batch_lines=256, min_distinct_ips=300,
    attackers=8, heavy_attackers=2, sleepers=3, pad_lines=40,
    max_warm_windows=8,
    ref_procs=2, min_banned_probes=1,
)
ATTACK_RATE = 0.02
CLEAN_IPS = ["192.0.2.1", "192.0.2.2", "192.0.2.3"]  # never in the stream


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------- config


def make_rules(n_rules: int, seed: int) -> list:
    """`scenarios.synth.generate_rules` patterns with rate limits an attacker in the
    stream crosses: one rule in a hundred bans on the first hit (as the
    shipped config's demo rule does), the rest on the third hit inside
    five minutes; decisions alternate between the two whose effect
    /auth_request shows without root."""
    from banjax_tpu.scenarios import synth

    rules = []
    for i, regex in enumerate(synth.generate_rules(n_rules, seed)):
        instant = i % 100 == 7
        rules.append({
            "rule": f"smoke-{i:04d}",
            "regex": regex,
            "interval": 1 if instant else 300,
            "hits_per_interval": 0 if instant else 2,
            "decision": "challenge" if i % 2 else "nginx_block",
        })
    return rules


def write_config(outdir: str, rules: list, sz: dict, **overrides) -> str:
    import yaml

    with open(os.path.join(HERE, "deploy", "banjax-config.yaml")) as f:
        cfg = yaml.safe_load(f)
    # schema default, whatever the deploy file ships: the PoW arm is off
    # the main path
    cfg.pop("challenge_device_verify", None)
    cfg.update(
        config_version="chip-smoke",
        regexes_with_rates=rules,
        matcher="tpu",
        matcher_backend="auto",
        matcher_batch_lines=sz["batch_lines"],
        matcher_max_line_len=256,
        matcher_device_windows=True,
        matcher_window_capacity=sz["capacity"],
        matcher_prefilter=True,
        pipeline_enabled=True,
        http_workers=0,
        disable_kafka=True,
    )
    cfg.update(overrides)
    path = os.path.join(outdir, "banjax-config.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f, sort_keys=False)
    return path


# --------------------------------------------------------------- traffic


def _ip(base: int, i: int) -> str:
    return f"{base}.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"


class Traffic:
    """(ip, rest) lines from `scenarios.synth.generate_lines`: attack lines go to a
    fixed small set of attacker IPs (a quarter of them send three
    quarters of the attack lines), benign lines to IPs drawn from a pool
    larger than the slot table.  The last `sleepers` attackers send only
    in every third window: idle for two windows they are evicted from
    the slot table, so their window state spills to the warm tier and
    is refilled when they return."""

    def __init__(self, patterns: list, sz: dict, seed: int, base: int):
        from banjax_tpu.scenarios import synth

        self._synth = synth
        self.patterns = patterns
        self.sz = sz
        self.rng = random.Random(seed * 7919 + base)
        self.pool = [_ip(base, i) for i in range(sz["ip_pool"])]
        self.attackers = [
            f"{base}.255.{250 + (i >> 8)}.{i & 255}"
            for i in range(sz["attackers"])
        ]
        self.benign = set(synth.generate_lines(20000, [], seed=seed))

    def lines(self, n: int, seed: int, k: int = 0) -> list:
        """Window number k of a sequence (sleepers send when k % 3 == 0)."""
        rests = self._synth.generate_lines(
            n, self.patterns, seed=seed, attack_rate=ATTACK_RATE
        )
        rng, heavy = self.rng, self.sz["heavy_attackers"]
        active = len(self.attackers) - (self.sz["sleepers"] if k % 3 else 0)
        out = []
        for rest in rests:
            if rest in self.benign:
                ip = self.pool[rng.randrange(len(self.pool))]
            elif rng.random() < 0.75:
                ip = self.attackers[rng.randrange(heavy)]
            else:
                ip = self.attackers[rng.randrange(heavy, active)]
            out.append((ip, rest))
        return out


def l_p_of(rest: str) -> int:
    """The matcher's line-length bucket (multiples of 32) for one line."""
    return max(64, -(-len(rest) // 32) * 32)


# ------------------------------------------------------------- reference


class _RecordingLog:
    """The ban log of the reference: keeps (line index, text)."""

    def __init__(self):
        self.idx = -1
        self.records = []

    def write(self, text: str) -> None:
        for line in text.splitlines():
            if line:
                self.records.append((self.idx, line))

    def flush(self) -> None:
        pass


def _reference_shard(args):
    """One shard of the plain reference, in a spawned child that never
    touches JAX: `CpuMatcher` line by line, `now` held at each line's own
    stamp so staleness cannot differ from the product's run."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    config_path, indexed_lines = args
    sys.path.insert(0, HERE)
    import logging

    logging.disable(logging.CRITICAL)
    from banjax_tpu.config.holder import ConfigHolder
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.effectors.banner import Banner
    from banjax_tpu.matcher.cpu_ref import CpuMatcher

    config = ConfigHolder(config_path, standalone_testing=True).get()
    ban_log = _RecordingLog()
    dyn = DynamicDecisionLists()
    banner = Banner(dyn, ban_log, _RecordingLog(), None)
    matcher = CpuMatcher(
        config, banner, StaticDecisionLists(config), RegexRateLimitStates()
    )
    n_old = n_err = 0
    for idx, line in indexed_lines:
        ban_log.idx = idx
        r = matcher.consume_line(line, now_unix=float(line.split(" ", 1)[0]))
        n_old += bool(r.old_line)
        n_err += bool(r.error)
    dyn.close()
    return ban_log.records, n_old, n_err


def run_reference(config_path: str, lines: list, procs: int) -> tuple:
    """All `lines` through CpuMatcher, sharded by client IP (window state
    and bans are per IP, so shards are independent); ban-log records come
    back merged in line order."""
    import multiprocessing as mp
    import zlib

    procs = max(1, min(procs, (os.cpu_count() or 2) - 1))
    shards = [[] for _ in range(procs)]
    for idx, line in enumerate(lines):
        ip = line.split(" ", 2)[1]
        shards[zlib.crc32(ip.encode()) % procs].append((idx, line))
    with mp.get_context("spawn").Pool(procs) as pool:
        parts = pool.map(
            _reference_shard, [(config_path, s) for s in shards]
        )
    records, n_old, n_err = [], 0, 0
    for recs, o, e in parts:
        records.extend(recs)
        n_old += o
        n_err += e
    records.sort(key=lambda r: r[0])  # stable: per-line order kept
    return [text for _, text in records], n_old, n_err


def ban_key(line: str) -> tuple:
    d = json.loads(line)
    return d["client_ip"], d["trigger"], d["action"]


def strip_time(line: str) -> str:
    d = json.loads(line)
    d.pop("timestring", None)
    return json.dumps(d, sort_keys=True)


def compare_ban_logs(got: list, want: list) -> None:
    """Equal apart from timestamps: same multiset of lines, same order per
    client IP (slot admission may interleave different IPs differently
    from the serial reference; it never reorders one IP's lines)."""
    g = collections.Counter(strip_time(x) for x in got)
    w = collections.Counter(strip_time(x) for x in want)
    if g != w:
        missing = list((w - g).elements())[:3]
        extra = list((g - w).elements())[:3]
        raise SmokeFailure(
            f"ban log differs from CpuMatcher's: {sum((w - g).values())} "
            f"missing e.g. {missing}, {sum((g - w).values())} extra e.g. "
            f"{extra}"
        )

    def per_ip(lines):
        out = collections.defaultdict(list)
        for x in lines:
            out[json.loads(x)["client_ip"]].append(strip_time(x))
        return out

    require(per_ip(got) == per_ip(want),
            "ban log: per-IP order differs from CpuMatcher's")
    require({ban_key(x) for x in got} == {ban_key(x) for x in want},
            "ban set (ip, rule, decision) differs from CpuMatcher's")


# ------------------------------------------------------- the one-chip run


class Feeder:
    """Appends stamped lines to the tailed access log and waits for the
    pipeline to drain them; counts what the drain stage reports."""

    def __init__(self, app, log_path: str):
        self.app = app
        self.f = open(log_path, "a", encoding="utf-8")
        self.sent = 0
        self.old = self.errors = self.results = 0
        # the scheduler's observer hook (tests and the benchmark use it): every
        # drained batch's per-line results, in admission order
        app.pipeline._on_results = self._observe

    def _observe(self, lines, results) -> None:
        if results is None:
            return
        self.results += len(results)
        for r in results:
            self.old += bool(r.old_line)
            self.errors += bool(r.error)

    def counters(self) -> dict:
        st = self.app.pipeline.stats
        m = self.app._matcher
        return {
            "old": self.old,
            "stale": st.stale_dropped_lines,
            "shed": st.shed_lines + st.drain_error_lines,
            "generic_batches": st.fallback_batches,
            "budget_trips": getattr(m, "budget_trips", 0),
            "builds": m.compile_events() if m is not None else 0,
            "cpu_fallback_batches": getattr(m, "fallback_batches", 0),
            "processed": st.processed_lines,
        }

    def send(self, ip_rests: list, timeout: float = 900.0) -> tuple:
        """Stamp with the wall clock NOW, append, wait until drained.
        → (stamped lines, wall seconds, counter deltas)."""
        before = self.counters()
        t0 = time.time()
        lines = [
            f"{t0 + i * 1e-6:.6f} {ip} {rest}"
            for i, (ip, rest) in enumerate(ip_rests)
        ]
        self.f.write("\n".join(lines) + "\n")
        self.f.flush()
        self.sent += len(lines)
        st = self.app.pipeline.stats
        deadline = time.monotonic() + timeout
        while st.admitted_lines < self.sent:
            require(time.monotonic() < deadline,
                    "the tailer did not pick up the lines in time")
            time.sleep(0.01)
        require(self.app.pipeline.flush(max(1.0, deadline - time.monotonic())),
                "the pipeline did not drain in time")
        wall = time.time() - t0
        after = self.counters()
        delta = {k: after[k] - before[k] for k in after}
        m = self.app._matcher
        if m is not None:
            require(m.breaker.state == "closed",
                    f"breaker left closed: {m.breaker.state}")
        return lines, wall, delta

    def close(self) -> None:
        self.f.close()


def clean(delta: dict) -> bool:
    """Nothing dropped, nothing built, nothing off the device path."""
    return not (delta["old"] or delta["stale"] or delta["shed"]
                or delta["budget_trips"] or delta["generic_batches"]
                or delta["builds"])


def warm_up(feeder: Feeder, warm: Traffic, sz: dict) -> dict:
    """Warm every (rows, L_p) program the checked windows can use.

    Lines older than 10 s are dropped as stale and a cold Mosaic compile
    is longer than that, so nothing here is checked: every chunk is
    stamped when written, and traffic is re-sent until it drains with no
    program built (matcher/compile_watch.py counts them), nothing stale
    and nothing shed."""
    app = feeder.app
    seeds = iter(range(10_000, 20_000))
    pool = warm.lines(4 * sz["batch_lines"] + 4096, next(seeds))
    by_lp = collections.defaultdict(list)
    for ip, rest in pool:
        by_lp[l_p_of(rest)].append((ip, rest))
    lps = sorted(by_lp)
    sizer = app.pipeline._sizer
    obs = {"slow_sends": 0, "slow_seconds": 0.0, "programs": []}

    def chunk(n: int, lp: int) -> list:
        """n lines whose longest falls in bucket lp."""
        short = [x for k, v in by_lp.items() if k <= lp for x in v]
        out = [short[i % len(short)] for i in range(n - 1)]
        return out + [by_lp[lp][0]]

    def note_slow(label: str, wall: float) -> None:
        obs["slow_sends"] += 1
        obs["slow_seconds"] += wall
        obs["programs"].append(f"{label}:{wall:.1f}s")

    def warm_one(label: str, rows: list) -> None:
        for attempt in range(6):
            _, wall, d = feeder.send(rows)
            if clean(d):
                return
            say(f"warm-up {label} ({len(rows)} lines) try {attempt}: "
                f"{wall:.2f}s {json.dumps(d)}")
            note_slow(label, wall)
        raise SmokeFailure(f"warm-up: {label} never drained warm")

    # the first send also builds the matcher (rule compile, self-tests)
    warm_one("start", chunk(sz["pad_lines"], lps[-1]))
    # tail batches can be any size: every row bucket, and for the small
    # ones every line-length bucket
    b = 128
    while b <= sz["batch_lines"]:
        for lp in (x for x in lps if x >= 96 or b <= 256):
            warm_one(f"rows<={b},L{lp}", chunk(int(b * 0.9), lp))
        b <<= 1
    # throwaway full windows: the sizer settles, the slot table fills,
    # eviction, warm-tier spill and refill all run before anything is
    # checked
    fresh = (f"10.200.{(i >> 8) & 255}.{i & 255}" for i in range(1 << 16))
    for k in range(sz["max_warm_windows"]):
        if k == 4:
            # the table is full: a batch of n never-seen IPs evicts n
            # slots, and the eviction and slot-hash scatters are built
            # per power-of-two size class — visit each class once, so a
            # short tail batch of a checked window finds its program
            n = 1
            while n <= sz["batch_lines"]:
                rows = [(next(fresh), rest) for _, rest in chunk(n, lps[-1])]
                _, wall, d = feeder.send(rows)
                if not clean(d):
                    note_slow(f"evict{n}", wall)
                n <<= 1
        _, wall, d = feeder.send(
            warm.lines(sz["window_lines"], next(seeds), k)
        )
        dw = app._matcher.device_windows
        say(f"warm-up window {k}: {wall:.2f}s clean={clean(d)} "
            f"spills={dw.warm_spills} refills={dw.warm_refills} "
            f"{json.dumps(d)} {json.dumps(sizer.snapshot())}")
        if clean(d):
            if k >= 4 and dw.warm_refills > 0:
                break
        else:
            note_slow(f"window{k}", wall)
    else:
        raise SmokeFailure("warm-up: no clean full window with refills")
    return obs


def probe(ip: str) -> tuple:
    conn = http.client.HTTPConnection("127.0.0.1", 8081, timeout=10)
    try:
        conn.request("GET", "/auth_request?path=/",
                     headers={"X-Client-IP": ip, "Host": "example.com"})
        r = conn.getresponse()
        r.read()
        return r.status, r.getheader("X-Accel-Redirect") or ""
    finally:
        conn.close()


def healthz() -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", 8081, timeout=10)
    try:
        conn.request("GET", "/healthz")
        r = conn.getresponse()
        return json.loads(r.read())
    finally:
        conn.close()


def run_one_chip(args, sz: dict, outdir: str, jax) -> None:
    from banjax_tpu.cli import BanjaxApp, place_compile_cache

    cache_dir = place_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir} ({n_cached} entries at start)")

    rules = make_rules(sz["n_rules"], args.seed)
    config_path = write_config(outdir, rules, sz)
    patterns = [r["regex"] for r in rules]
    warm = Traffic(patterns, sz, args.seed, base=10)
    checked = Traffic(patterns, sz, args.seed, base=11)
    windows = [
        checked.lines(sz["window_lines"], args.seed + 100 + k, k)
        for k in range(sz["n_windows"])
    ]
    distinct = {ip for w in windows for ip, _ in w}
    say(f"config: {config_path}; {len(rules)} rules, "
        f"{sz['capacity']} window slots, {len(windows)} windows x "
        f"{sz['window_lines']} lines, {len(distinct)} distinct client IPs")
    require(len(distinct) >= sz["min_distinct_ips"],
            f"only {len(distinct)} distinct IPs")

    t0 = time.time()
    app = BanjaxApp(config_path, standalone_testing=True)
    app.start_background(timeout=600.0)
    try:
        require(app.tailer.opened.wait(60), "tailer never opened its log")
        feeder = Feeder(app, "testing-log-file.txt")
        obs = warm_up(feeder, warm, sz)
        matcher = app._matcher
        desc = matcher.describe()
        say(f"matcher: {json.dumps(desc)}")
        say(f"start-up ({n_cached} compile-cache entries at start): "
            f"{time.time() - t0:.1f}s to warm, of which "
            f"{obs['slow_seconds']:.1f}s in {obs['slow_sends']} batches "
            f"that compiled [{', '.join(obs['programs'])}] "
            "(smoke observation)")

        # ---- the checked windows
        sent, totals = [], collections.Counter()
        for k, w in enumerate(windows):
            lines, wall, d = feeder.send(w)
            sent.extend(lines)
            totals.update(d)
            say(f"window {k}: {len(lines)} lines in {wall:.3f}s wall "
                f"(smoke observation) {json.dumps(d)}")
            require(d["old"] == 0 and d["stale"] == 0,
                    f"window {k}: {d['old'] + d['stale']} lines dropped stale")
            require(d["shed"] == 0, f"window {k}: {d['shed']} lines shed")
        st = app.pipeline.stats
        require(st.admitted_lines == st.processed_lines,
                f"admitted {st.admitted_lines} != processed "
                f"{st.processed_lines}")
        require(st.shed_lines + st.drain_error_lines == 0, "lines were shed")

        # ---- /auth_request: every attacker and three clean IPs, now,
        # while the bans are live; judged against the reference below
        answers = {ip: probe(ip) for ip in checked.attackers + CLEAN_IPS}
        health = healthz()

        with open("banning-log-file.txt", encoding="utf-8") as f:
            got = [x for x in f.read().splitlines()
                   if json.loads(x)["client_ip"].startswith("11.")]

        # ---- the plain reference
        t_ref = time.time()
        want, ref_old, ref_err = run_reference(
            config_path, sent, sz["ref_procs"]
        )
        say(f"reference: CpuMatcher over {len(sent)} lines in "
            f"{time.time() - t_ref:.1f}s, {len(want)} ban-log lines, "
            f"{ref_old} stale, {ref_err} unparsable")
        require(ref_old == 0, "reference dropped lines as stale")
        require(len(want) > 0, "the stream crossed no rate limit")
        compare_ban_logs(got, want)
        bans = {ban_key(x) for x in want}
        say(f"bans: {len(want)} ban-log lines, {len(bans)} distinct "
            f"(ip, rule, decision), equal to CpuMatcher's")

        # final decision per IP: the severest one (dynamic lists are
        # monotonic in severity)
        final = {}
        for ip, _, action in bans:
            if final.get(ip) != "NginxBlock":
                final[ip] = action
        expect = {"NginxBlock": (403, "@access_denied"),
                  "Challenge": (429, "")}
        for ip in checked.attackers:
            want_answer = expect.get(final.get(ip), (200, "@access_granted"))
            require(answers[ip] == want_answer,
                    f"/auth_request for {ip} ({final.get(ip)}): "
                    f"{answers[ip]} != {want_answer}")
        # three to show, one of each decision first
        banned = sorted(final, key=lambda ip: checked.attackers.index(ip))
        shown = list({final[ip]: ip for ip in reversed(banned)}.values())
        shown += [ip for ip in banned if ip not in shown]
        shown = shown[:3]
        require(len(shown) >= sz["min_banned_probes"],
                f"only {len(shown)} banned IPs to probe")
        for ip in shown:
            say(f"/auth_request {ip} ({final[ip]}): {answers[ip]}")
        for ip in CLEAN_IPS:
            require(answers[ip] == (200, "@access_granted"),
                    f"/auth_request for clean {ip}: {answers[ip]}")
            say(f"/auth_request {ip} (clean): {answers[ip]}")

        # ---- which path ran
        fw = matcher._fw_pipeline
        say(f"counters: lines_processed={st.processed_lines} "
            f"fallback_batches={matcher.fallback_batches} "
            f"pipelined_fused_chunks={matcher.pipelined_fused_chunks} "
            f"pipelined_fused_fallbacks={matcher.pipelined_fused_fallbacks} "
            f"single_kernel_chunks={fw.fused_batches if fw else 0} "
            f"single_kernel_fallbacks={fw.fallback_batches if fw else 0} "
            f"budget_trips={matcher.budget_trips} "
            f"generic_drains={st.fallback_batches} "
            f"breaker={matcher.breaker.state} "
            f"warm_spills={matcher.device_windows.warm_spills} "
            f"warm_refills={matcher.device_windows.warm_refills} "
            f"checked_window_deltas={json.dumps(dict(totals))}")
        say(f"healthz: {json.dumps(health)}")
        require(matcher.fallback_batches == 0,
                f"CPU fallback served {matcher.fallback_batches} batches")
        require(matcher.breaker.state == "closed",
                f"breaker is {matcher.breaker.state}")
        require(totals["cpu_fallback_batches"] == 0
                and totals["generic_batches"] == 0,
                "a checked window left the device path")
        require(matcher.pipelined_fused_chunks > 0,
                "the fused pipeline committed no chunk")
        bad = {k: v for k, v in health["components"].items()
               if v["status"] != "healthy"}
        require(not bad and health["status"] == "healthy",
                f"/healthz is not healthy: {bad}")
        require(health["components"]["matcher"].get("info") == desc,
                "/healthz does not carry the matcher's description")
        require(not desc["downgrades"], f"downgrades: {desc['downgrades']}")
        if not args.rehearse:
            require(desc["platform"] == "tpu", "matcher is not on the TPU")
            require(desc["nfa_backend"] == "pallas"
                    and desc["match_interpret"] is False,
                    "match kernels are not compiled Pallas")
            require(desc["prefilter"], "prefilter is off")
            require(desc["fused_protocol"] == "single-kernel"
                    and desc["scan_interpret"] is False,
                    f"fused protocol is {desc['fused_protocol']}, scan "
                    f"interpret={desc['scan_interpret']}")
        say(f"fused protocol: {desc['fused_protocol']}")
        feeder.close()
    finally:
        app.stop_background()
        if os.path.exists("testing-log-file.txt"):
            os.remove("testing-log-file.txt")  # tens of MB, nothing reads it


# ------------------------------------------------------ the four-chip run


def run_mesh4(args, sz: dict, outdir: str, jax) -> None:
    """Only this phase: TpuMatcher over a 2x2 (dp x rp) mesh against
    CpuMatcher on one stream, and where the shards live."""
    import numpy as np

    from banjax_tpu.cli import place_compile_cache
    from banjax_tpu.config.holder import ConfigHolder
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.effectors.banner import Banner
    from banjax_tpu.matcher.runner import TpuMatcher
    from banjax_tpu.resilience.health import HealthRegistry

    require(len(jax.devices()) >= 4,
            f"--mesh4 needs four devices, found {len(jax.devices())}")
    say(f"compile cache: {place_compile_cache()}")
    rules = make_rules(sz["n_rules"], args.seed)
    config_path = write_config(
        outdir, rules, sz, matcher_mesh_devices=4, matcher_mesh_rp=2,
        pipeline_enabled=False,
    )
    config = ConfigHolder(config_path, standalone_testing=True).get()
    traffic = Traffic([r["regex"] for r in rules], sz, args.seed, base=11)
    ip_rests = traffic.lines(sz["window_lines"], args.seed + 100)

    health = HealthRegistry()
    ban_log = _RecordingLog()
    dyn = DynamicDecisionLists()
    banner = Banner(dyn, ban_log, _RecordingLog(), None)
    t0 = time.time()
    matcher = TpuMatcher(
        config, banner, StaticDecisionLists(config), RegexRateLimitStates(),
        health=health,
    )
    try:
        desc = matcher.describe()
        say(f"matcher: {json.dumps(desc)} (built in {time.time() - t0:.1f}s)")
        require(desc["mesh_shape"] == {"dp": 2, "rp": 2},
                f"mesh is {desc['mesh_shape']}")
        require(not desc["downgrades"], f"downgrades: {desc['downgrades']}")
        if not args.rehearse:
            require(desc["nfa_backend"] == "pallas"
                    and desc["match_interpret"] is False,
                    "mesh kernels are not compiled Pallas")

        # where the shards live
        mm = matcher._mesh_matcher
        devs = {d.id for d in jax.devices()[:4]}
        for name, arr in sorted(mm._params.items()):
            per_dev = {s.device.id: tuple(s.data.shape)
                       for s in arr.addressable_shards}
            say(f"rule words {name} {tuple(arr.shape)}: {per_dev}")
            require(set(per_dev) == devs, f"{name} is not on all four")
        words = max(mm._params.values(), key=lambda a: a.size)
        shard_elems = {s.data.size for s in words.addressable_shards}
        require(max(shard_elems) * 2 <= words.size,
                "the widest rule tensor is not split across rp")

        now = time.time()
        lines = [f"{now + i * 1e-6:.6f} {ip} {rest}"
                 for i, (ip, rest) in enumerate(ip_rests)]
        step = sz["batch_lines"] * 4
        t0 = time.time()
        first = None
        for s in range(0, len(lines), step):
            tb = time.time()
            matcher.consume_lines(lines[s : s + step], now_unix=now)
            first = first if first is not None else time.time() - tb
        say(f"mesh stream: {len(lines)} lines in {time.time() - t0:.1f}s "
            f"wall, first batch (compiles) {first:.1f}s (smoke observation)")

        # rows: one batch through the sharded backend, shards inspected
        from banjax_tpu.matcher.encode import encode_for_match

        cls_ids, lens, _ = encode_for_match(
            matcher.compiled, [r for _, r in ip_rests[: sz["batch_lines"]]],
            256,
        )
        pend = mm.submit(np.asarray(cls_ids), np.asarray(lens))
        rows = pend["bits_d"] if pend.get("fused") else pend["out_d"]
        per_dev = {s.device.id: tuple(s.data.shape)
                   for s in rows.addressable_shards}
        say(f"rows {tuple(rows.shape)}: {per_dev}")
        require(set(per_dev) == devs, "row output is not on all four")
        require(all(sh[0] * 2 == rows.shape[0] for sh in per_dev.values()),
                "rows are not split across dp")
        mm.collect(pend)

        got = [text for _, text in ban_log.records]
        want, ref_old, _ = run_reference(config_path, lines, sz["ref_procs"])
        require(ref_old == 0, "reference dropped lines as stale")
        require(len(want) > 0, "the stream crossed no rate limit")
        compare_ban_logs(got, want)
        say(f"bans: {len(want)} ban-log lines equal to CpuMatcher's; "
            f"mesh fused_batches={mm.fused_batches} "
            f"fallback_batches={mm.fallback_batches}; "
            f"cpu fallback_batches={matcher.fallback_batches} "
            f"breaker={matcher.breaker.state}")
        require(matcher.fallback_batches == 0, "CPU fallback engaged")
        snap = health.snapshot()
        bad = {k: v for k, v in snap["components"].items()
               if v["status"] != "healthy"}
        require(not bad, f"health: {bad}")
    finally:
        matcher.close()
        dyn.close()


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU backend; ends with ok:false")
    ap.add_argument("--mesh4", action="store_true",
                    help="only the four-chip mesh phase")
    ap.add_argument("outdir", nargs="?",
                    default=os.path.join(HERE, "chip_smoke_out"))
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.mesh4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            ).strip()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {device})", file=sys.stderr)
        return 2

    sys.path.insert(0, HERE)
    outdir = os.path.abspath(args.outdir)
    os.makedirs(outdir, exist_ok=True)
    os.chdir(outdir)
    for name in ("testing-log-file.txt", "banning-log-file.txt",
                 "banning-log-file.txt.tmp", "gin.log", "list-metrics.log"):
        if os.path.exists(name):
            os.remove(name)
    sz = TINY if args.rehearse else FULL
    import logging

    logging.basicConfig(
        filename="chip_smoke.log", level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    logging.getLogger("banjax_tpu.effectors.banner").setLevel(logging.WARNING)
    say(f"device: {json.dumps(device)}; seed {args.seed}; "
        f"{'REHEARSAL (not a chip run)' if args.rehearse else 'chip run'}"
        f"{' --mesh4' if args.mesh4 else ''}")
    try:
        (run_mesh4 if args.mesh4 else run_one_chip)(args, sz, outdir, jax)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say("all phases passed")
    print(json.dumps({"ok": not args.rehearse, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
