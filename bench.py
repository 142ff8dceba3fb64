"""Benchmark: log-lines/sec classified against 1k regex rules (BASELINE.json).

Measures, on whatever accelerator is attached (the real TPU chip under the
driver; CPU otherwise), the replacement for the reference's serial
per-(line, rule) regexp loop (/root/reference/internal/regex_rate_limiter.go:216-269):

  * the single-stage Pallas NFA kernel (device-resident, chained) and the
    XLA-scan fallback — the raw device classification rate;
  * the fused two-stage prefilter (matcher/prefilter.py FusedPrefilter),
    both device-resident AND pipelined through submit/collect — the rate
    INCLUDING host<->device transport, whose fixed cost per
    device→host pull must be overlapped to matter;
  * the end-to-end TpuMatcher consume_lines path (native C parse + encode
    + fused match + device windows + Banner), with per-batch latency
    p50/p99 — the production numbers BASELINE.md names;
  * the sharded mesh path (parallel/mesh.py) executed compiled (not
    interpreted) on the attached chip with a degenerate dp=1/rp=1 mesh;
  * the five-config BASELINE.json ladder (tests/perf shapes).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "lines/sec", "vs_baseline": N / 5e6, ...}
vs_baseline is against the BASELINE.md north-star target of 5M lines/sec
@1k rules on v5e-1 (the reference itself publishes no numbers — see
BASELINE.md; its serial Go loop is the functional, not numerical, baseline).

Wedged-device resilience (the r1-r3 failure mode): the measurements run in
a WORKER subprocess that persists every section's result to
BENCH_partial.json the moment it completes (atomic rename), stamped with
the backend it ran on and when. The supervisor (this file's main) never
touches the device itself: it probes, launches the worker under a hard
timeout, and composes the final JSON from the partial file — preferring
TPU-measured sections over CPU ones and labeling every merged section with
its measurement time. A device that wedges mid-round (or mid-worker) can
therefore cost at most the section in flight, never the whole artifact.
Sections whose data came from an earlier process run (not the live worker)
are listed in `merged_from_partial`, and `final_probe_backend` records
what the end-of-round probe actually saw.

Env knobs: BENCH_CPU=1 forces the host backend; BENCH_NO_LADDER=1 skips the
ladder; BENCH_BUDGET_S caps worker wall time (default 480 s) — sections
past the deadline are skipped and marked; BENCH_SECTIONS=a,b runs only
those sections (worker dev loop).

Streaming modes: `bench.py --sync` / `bench.py --pipeline` measure the
end-to-end tailer-shaped feed through the synchronous consume path vs
the streaming pipeline scheduler (banjax_tpu/pipeline/), emit the same
one-line JSON schema, and merge both rows (plus the speedup) into
BENCH_pipeline.json.  Knobs: BENCH_STREAM_{RULES,LINES,CHUNK,BUDGET_MS},
BENCH_CPU=1 for the host backend.

Single-kernel mode: `bench.py --single-kernel` A/Bs the one-program
fused match+window path (pallas_single_kernel on — one dispatch, one
pull, no program-B turn) against the two-program A/B path on the
--fused-pipeline stream shape, banking lines/s, d2h bytes/batch and
the resolve-pull elimination into BENCH_single_kernel.json.  Knobs:
the BENCH_STREAM_* set, BENCH_CPU=1 for the host backend.

Host-parallel mode: `bench.py --host-parallel` A/Bs the sharded
encode-worker pool (workers 0 vs N) and the native slot manager (C vs
Python dict) at the all-distinct-IP host worst case, merging
core-count-keyed rows into BENCH_host_parallel.json.  Knobs:
BENCH_HOST_{LINES,WORKERS,ITERS,SLOT_BATCH}.

Trace-overhead mode: `bench.py --trace-overhead` A/Bs the pipelined
stream with the span recorder (obs/trace.py) off vs on — off → on →
off so run-order effects don't masquerade as recorder cost — banking
both rows and the delta into BENCH_trace_overhead.json (PERF round 9).
Knobs: BENCH_TRACE_{RING,ITERS} plus the BENCH_STREAM_* set.

Provenance-overhead mode: `bench.py --provenance-overhead` — the same
off → on → off protocol for the decision provenance ledger
(obs/provenance.py), on a ban-heavy IP rotation so the ledger actually
records, banked into BENCH_provenance_overhead.json.  The acceptance
gate (ISSUE 6): the ledger-on row must sit inside the off-run noise
band on the --pipeline-shaped feed.

Sketch-overhead mode: `bench.py --sketch-overhead` — the same
off → on → off protocol for the device traffic sketch (obs/sketch.py:
count-min heavy hitters + HLL cardinality + rule pressure), on the
ban-storm IP rotation so the sketch is actually populated (the banked
on-row carries sketch_lines/top1 as the witness), banked into
BENCH_sketch_overhead.json.  Acceptance gate (ISSUE 8): the sketch-on
row inside the off-run noise band.

Scenario mode: `bench.py --scenarios` — the adversarial scenario
harness (banjax_tpu/scenarios/): one row per named attack shape (flash
crowd, slow drip, rotating proxies, command flood, challenge storm,
log rotation through a real tailer, benign) with lines/s, shed ratio,
ban precision/recall vs the generator's oracle and SLO burn peaks,
plus a seeded chaos-soak row with per-failpoint-episode evidence —
banked into BENCH_scenarios.json.  Knobs: BENCH_SCEN_{SCALE,SEED},
BENCH_CPU=1.

Mega-state mode: `bench.py --mega-state` — the mega-state tiering A/B
(README "Mega-state tiering"): the streaming 10M-distinct-IP rotation
(scenarios/shapes.py mega_rotating_proxies_stream) driven through
consume_lines with the slot-admission gate OFF then ON, same stream,
slot capacity pinned at the 65k worst-case shape.  Banks both rows —
lines/s, ban precision/recall vs the offender-only oracle, slot
refusals, sketch admissions + FP rate, warm-tier spill/refill — into
BENCH_mega_state.json.  Acceptance (ISSUE 14): p/r 1.0 both rows and
the admission-on row's lines/s >= the admission-off row's.  Knobs:
BENCH_MEGA_{DISTINCT,CHUNK,SEED,CAPACITY,SKETCH_WIDTH}, BENCH_CPU=1.

Fabric mode: `bench.py --fabric` — the multi-host decision fabric
scaling run (banjax_tpu/fabric/harness.py): one dryrun episode per
shard count (N=1 baseline; N=2 and N=4 with one shard SIGKILLed
mid-flood and consistent-hash takeover), banking per-N lines/s plus
the takeover-window shed ratio into BENCH_fabric.json.  Every row is
recall-gated at 1.0 vs the oracle.  Knobs:
BENCH_FABRIC_{SHAPE,SEED,SCALE,NS}.

Fleet-obs mode: `bench.py --fleet-obs` — fleet observability overhead
on the N=2 fabric feed: off → on → off where "on" arms origin trace
propagation on every forwarded frame plus the worker fleet surfaces
(T_EXPLAIN / T_FLIGHTREC / T_STATS metrics).  The on-arm ban log is
byte-compared against off, and the banked row carries a live-plane
witness: a forwarded-line ban whose explain provenance joins the
origin trace id allocated at the tailing shard's admission.  Banked
into BENCH_fleet_obs.json.  Knobs: BENCH_FABRIC_{SHAPE,SEED,SCALE}.

Challenge mode: `bench.py --challenge` — the challenge plane
(banjax_tpu/challenge/): (a) PoW cookie verification throughput
(cookies/s) as a CPU-reference vs device-batched A/B over the same
pre-solved cookie set, accept counts forced identical; (b) a
challenge_storm row driving >= 1M DISTINCT cookieless challengers plus
scripted repeat offenders through the real decision-chain stage
(send_or_validate_sha_challenge), gated on bounded failure state
(entries <= challenge_failure_state_max) and failed-challenge ban
precision/recall 1.0 vs the scripted oracle.  Banked into
BENCH_challenge.json.  Knobs:
BENCH_CHAL_{COOKIES,ZERO_BITS,BATCH,DISTINCT,OFFENDERS,STATE_MAX,SEED},
BENCH_CPU=1.

Serve mode: `bench.py --serve` — the compiled /auth_request serving
path (httpapi/fastpath.py + native/decisiontable.c): (a) an in-process
decision-stage A/B (userspace nine-step chain vs shm-table template
path, identical already-decided workload) gated at fast path >= 5x
chain rps; (b) a byte-identity witness over a mixed allow / block /
challenge / expiring workload including live expiry-boundary
crossings, gated at 0 mismatches; (c) the real standalone server
driven by a concurrent raw-socket keepalive capacity client, chain-only
vs fast-path config, with rps + p50/p99 + the per-tier hit / per-reason
miss counters.  Banked into BENCH_serve.json.  Knobs:
BENCH_SERVE_{SEED,ITERS,WITNESS,NPC,CONC,TABLE_CAP}.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import time

import numpy as np


N_RULES = 1000
MAX_LEN = 128
WARMUP = 3
ITERS = 10
TARGET = 5_000_000.0

_DIR = os.path.dirname(os.path.abspath(__file__))
PARTIAL_PATH = os.path.join(_DIR, "BENCH_partial.json")

# Workload fingerprint: partial-file sections are only trusted when they
# were measured on the same workload this bench would run.
WORKLOAD = {"n_rules": N_RULES, "max_len": MAX_LEN, "rule_seed": 7}

SECTIONS = ("single_stage", "fused", "e2e", "mesh", "http", "ladder")

# Backend init can hang, and killing a client mid-device-op can leave
# jax.devices() hanging for every later process (observed r3, after a
# timeout-killed Mosaic compile). So: probe in a subprocess with a GENEROUS
# timeout, retry with backoff, and fall back to CPU rather than kill
# aggressively.
BACKEND_PROBE_TIMEOUT_S = 240
BACKEND_PROBE_RETRIES = 2


def _probe_backend() -> "tuple[str, str | None]":
    """Decide the backend without initializing jax in this process."""
    if os.environ.get("BENCH_CPU"):
        return "cpu", None
    err = None
    for attempt in range(BACKEND_PROBE_RETRIES):
        if attempt:
            time.sleep(20 * attempt)
        try:
            r = subprocess.run(
                [sys.executable, "-c",
                 "import jax; print(jax.devices()[0].platform)"],
                capture_output=True, text=True,
                timeout=BACKEND_PROBE_TIMEOUT_S,
            )
            if r.returncode == 0 and r.stdout.strip():
                return r.stdout.strip().splitlines()[-1], None
            err = f"probe rc={r.returncode}: {r.stderr.strip()[-300:]}"
        except subprocess.TimeoutExpired:
            err = (f"probe timeout after {BACKEND_PROBE_TIMEOUT_S}s "
                   "(backend init hang — terminal session likely wedged)")
    return "cpu", err


# ---------------------------------------------------------------------------
# workload generation (imported by tests/perf and the unit suites)
# ---------------------------------------------------------------------------

def generate_rules(n: int, seed: int = 7) -> list:
    """OWASP-CRS-shaped synthetic ruleset (BASELINE.json configs[2]):
    literal attack paths, method+path prefixes, scanner UA tokens, char
    classes and bounded quantifiers — the pattern shapes of
    banjax-config.yaml's production rules."""
    rng = random.Random(seed)
    words = [
        "admin", "login", "wp", "xmlrpc", "shell", "config", "backup", "env",
        "passwd", "phpmyadmin", "setup", "install", "api", "token", "debug",
        "console", "cgi", "bin", "upload", "include", "vendor", "composer",
    ]
    exts = ["php", "asp", "aspx", "jsp", "cgi", "sh", "bak", "sql", "old"]
    patterns = []
    while len(patterns) < n:
        kind = rng.random()
        w1, w2 = rng.choice(words), rng.choice(words)
        ext = rng.choice(exts)
        if kind < 0.3:
            p = rf"GET /{w1}-{w2}/[a-z0-9_-]+\.{ext}"
        elif kind < 0.5:
            p = rf"(GET|POST) /{w1}/{w2}\.{ext}"
        elif kind < 0.65:
            p = rf"POST /{w1}[a-z]*/{w2}{rng.randint(0, 99)}"
        elif kind < 0.8:
            p = rf"/{w1}\.{ext}\?[a-z]+={rng.randint(0, 9)}[0-9]{{1,4}}"
        elif kind < 0.9:
            p = rf"(?i){w1}scan|{w2}bot/{rng.randint(1, 9)}\.[0-9]+"
        else:
            p = rf"^(GET|POST|HEAD) [a-z.-]+\.(com|org|net) .*/{w1}{w2}"
        patterns.append(p)
    return patterns


def synthesize_match(pattern: str, rng: random.Random) -> str:
    """Build a string the compiled rule actually matches (attack traffic)."""
    from banjax_tpu.matcher.rulec import compile_rule

    prog = compile_rule(pattern)
    if not prog.branches:
        return "GET example.com GET / HTTP/1.1 x -"
    br = rng.choice(prog.branches)
    chars = []
    for pos in br.positions:
        # prefer printable ASCII members of the byte class
        for lo, hi in ((0x61, 0x7A), (0x30, 0x39), (0x20, 0x7E)):
            cands = [b for b in range(lo, hi + 1) if (pos.cs >> b) & 1]
            if cands:
                break
        chars.append(chr(rng.choice(cands or [0x61])))
    body = "".join(chars)
    prefix = "" if br.anchored_start else "GET example.com "
    suffix = "" if br.anchored_end else " HTTP/1.1 ua -"
    return prefix + body + suffix


def generate_lines(n: int, patterns: list, seed: int = 11, attack_rate: float = 0.02) -> list:
    """Mostly benign traffic with ~attack_rate lines synthesized to match a
    random rule — the realistic shape of the tailer's input stream."""
    rng = random.Random(seed)
    hosts = ["example.com", "site.org", "news.net", "shop.com"]
    paths = [
        "/", "/index.html", "/assets/app.js", "/img/logo.png", "/about",
        "/api/v1/items", "/search?q=red4321", "/contact", "/news/2026/07",
    ]
    uas = ["Mozilla/5.0 (X11; Linux x86_64)", "curl/8.1", "Safari/604.1"]
    out = []
    for _ in range(n):
        if patterns and rng.random() < attack_rate:
            out.append(synthesize_match(rng.choice(patterns), rng))
            continue
        method = rng.choice(["GET", "GET", "GET", "POST", "HEAD"])
        out.append(
            f"{method} {rng.choice(hosts)} {method} {rng.choice(paths)} "
            f"HTTP/1.1 {rng.choice(uas)} -"
        )
    return out


def _time_chained(step, args, batch, iters=ITERS):
    """Throughput with a serial dependency between iterations (the popcount
    carries), so pipelined dispatch can't fake the timing."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    s = step(jnp.int32(0), *args)
    s.block_until_ready()
    first_call_s = time.perf_counter() - t0
    for _ in range(WARMUP):
        s = step(s, *args)
    s.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        s = step(s, *args)
    s.block_until_ready()
    elapsed = time.perf_counter() - t0
    return batch * iters / elapsed, elapsed / iters, first_call_s


# ---------------------------------------------------------------------------
# partial-file persistence
# ---------------------------------------------------------------------------

def _load_partial() -> dict:
    try:
        with open(PARTIAL_PATH) as f:
            p = json.load(f)
        if p.get("workload") != WORKLOAD:
            return {"workload": WORKLOAD, "sections": {}}
        return p
    except (OSError, json.JSONDecodeError):
        return {"workload": WORKLOAD, "sections": {}}


def _save_section(name: str, backend: str, data: dict) -> None:
    """Merge one section into BENCH_partial.json (atomic rename).

    Best-evidence rule: a CPU measurement never clobbers an existing TPU
    one; TPU overwrites TPU (newer code wins); CPU overwrites CPU."""
    p = _load_partial()
    prev = p["sections"].get(name)
    # 'meta' is bookkeeping (skip lists) and 'http' never touches the
    # device — neither is chip evidence, so newest always wins for them
    # (also migrates any http row a pre-fix tpu worker mislabeled).
    if (name not in ("meta", "http") and prev
            and prev.get("backend") == "tpu" and backend != "tpu"):
        return
    p["sections"][name] = {
        "backend": backend,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "data": data,
    }
    tmp = PARTIAL_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(p, f, indent=1)
    os.replace(tmp, PARTIAL_PATH)


# ---------------------------------------------------------------------------
# worker sections (run inside the worker subprocess, jax initialized)
# ---------------------------------------------------------------------------

class _Deadline:
    def __init__(self, budget_s: float):
        self.t0 = time.monotonic()
        self.budget = budget_s
        self.skipped: list = []

    def over(self, section: str) -> bool:
        if time.monotonic() - self.t0 > self.budget:
            self.skipped.append(section)
            return True
        return False


def _sec_single_stage(jax, ctx, backend, deadline, out) -> dict:
    """Single-stage device NFA classification (the r1/r2 headline path)."""
    import jax.numpy as jnp

    from banjax_tpu.matcher import nfa_jax
    from banjax_tpu.matcher.encode import encode_for_match
    from banjax_tpu.matcher.kernels import nfa_match
    from banjax_tpu.matcher.rulec import compile_rules

    patterns = ctx["patterns"]
    batch = ctx["batch"]
    t0 = time.perf_counter()
    compiled = compile_rules(patterns, n_shards="auto")
    out["rule_compile_s"] = round(time.perf_counter() - t0, 2)
    out["rules_on_device"] = int(compiled.device_ok.sum())
    out["nfa_words"] = compiled.n_words
    out["nfa_shards"] = compiled.n_shards
    ctx["compiled"] = compiled

    lines = generate_lines(batch, patterns)
    cls_ids, lens, host_eval = encode_for_match(compiled, lines, MAX_LEN)
    assert not host_eval.any()
    order = np.argsort(lens, kind="stable")
    cls_ids, lens = cls_ids[order], lens[order]
    L_p = max(32, -(-int(lens.max()) // 32) * 32)
    cls_ids = np.ascontiguousarray(cls_ids[:, :L_p])
    lens_dev = jax.device_put(lens)

    params = nfa_jax.match_params(compiled)
    cls_dev = jax.device_put(cls_ids)

    @jax.jit
    def chained_xla(s, cls, ln):
        o = nfa_jax.match_batch(params, cls, ln, compiled.n_rules)
        return s + o.astype(jnp.int32).sum()

    xla_lps, xla_lat, xla_first = _time_chained(
        chained_xla, (cls_dev, lens_dev), batch
    )
    out["xla_lines_per_sec"] = round(xla_lps, 1)
    out["xla_batch_latency_ms"] = round(xla_lat * 1e3, 3)

    want = np.asarray(
        nfa_jax.match_batch(params, cls_dev, lens_dev, compiled.n_rules)
    )
    out["line_match_rate"] = round(float(want.any(axis=1).mean()), 4)
    out["first_call_s"] = round(xla_first, 2)
    out["pallas_lines_per_sec"] = None

    if backend == "tpu" and not deadline.over("pallas_single_stage"):
        prep = nfa_match.prepare(compiled)
        dev_fn = nfa_match.device_matcher(prep, batch, L_p, 512, cols=32)
        cls_t_dev = jax.device_put(np.ascontiguousarray(cls_ids.T))

        @jax.jit
        def chained_pallas(s, cls_t, ln):
            o = dev_fn(cls_t, ln)
            return s + o.astype(jnp.int32).sum()

        pallas_lps, pallas_lat, pallas_first = _time_chained(
            chained_pallas, (cls_t_dev, lens_dev), batch
        )
        out["pallas_lines_per_sec"] = round(pallas_lps, 1)
        out["pallas_batch_latency_ms"] = round(pallas_lat * 1e3, 3)
        out["first_call_s"] = round(pallas_first, 2)
        got = nfa_match.match_batch_pallas(prep, cls_ids, lens, cols=32)
        assert (got == want).all(), "pallas/XLA match bitmap divergence"
    return out


def _sec_fused(jax, ctx, backend, deadline, out) -> dict:
    """Fused two-stage prefilter: device-resident (chained, no per-iter
    transport) AND pipelined submit/collect (the honest
    classified-through-transport rate)."""
    import jax.numpy as jnp

    from banjax_tpu.matcher.encode import encode_for_match
    from banjax_tpu.matcher.prefilter import FusedPrefilter, build_plan
    from banjax_tpu.matcher import nfa_jax
    from banjax_tpu.matcher.rulec import compile_rules

    patterns = ctx["patterns"]
    compiled = ctx.get("compiled")
    if compiled is None:
        compiled = compile_rules(patterns, n_shards="auto")
        ctx["compiled"] = compiled

    plan = build_plan(
        patterns, byte_classes=(compiled.byte_to_class, compiled.n_classes)
    )
    if plan is None:
        return out
    out["prefilter_stage1_words"] = plan.stage1.n_words
    out["prefilter_stage2_words"] = plan.stage2.n_words
    fp = FusedPrefilter(plan, "pallas" if backend == "tpu" else "xla")
    ctx["plan"] = plan

    batch = ctx["batch"]
    lines = generate_lines(batch, patterns, seed=23)
    cls_ids, lens, _ = encode_for_match(compiled, lines, MAX_LEN)
    bits = fp.match_bits_encoded(cls_ids, lens)  # compile + parity data
    # parity vs the single-stage oracle on this batch
    params = nfa_jax.match_params(compiled)
    want = np.asarray(
        nfa_jax.match_batch(
            params, jax.device_put(cls_ids), jax.device_put(lens),
            compiled.n_rules,
        )
    )
    for rid in plan.unsupported:
        want[:, rid] = 0
    assert (bits == want).all(), "fused/single-stage divergence"
    out["prefilter_candidate_fraction"] = round(
        float(want.any(axis=1).mean()), 4
    )
    if getattr(fp, "last_n_cand", None) is not None:
        # stage-1 gate rate: what fraction of lines actually reached
        # stage 2 (true matches + factor/superimposition false positives)
        out["prefilter_gate_fraction"] = round(fp.last_n_cand / batch, 4)

    # --- device-resident rate: the input uploaded once, chained on-device;
    # what the kernels deliver with transport out of the picture entirely
    best_resident = None
    for dr_batch in ctx["resident_batches"]:
        if deadline.over(f"fused_resident_{dr_batch}"):
            break
        dlines = generate_lines(dr_batch, patterns, seed=29)
        dcls, dlens, _ = encode_for_match(compiled, dlines, MAX_LEN)
        combined, Bp, L_p = fp._assemble(dcls, dlens)
        fn, K, P = fp._fused(Bp, L_p)
        dev_in = jax.device_put(combined)

        @jax.jit
        def chained(s, x):
            # sum the WHOLE output buffer: a partial slice would let XLA
            # dead-code-eliminate the stages that don't feed it
            return s + fn(x).astype(jnp.int32).sum()

        lps, lat, _ = _time_chained(chained, (dev_in,), dr_batch, iters=6)
        out[f"fused_device_resident_{dr_batch}"] = round(lps, 1)
        if best_resident is None or lps > best_resident:
            best_resident = lps
            out["fused_device_resident_lines_per_sec"] = round(lps, 1)
            out["fused_device_resident_batch"] = dr_batch
            out["fused_device_resident_latency_ms"] = round(lat * 1e3, 3)

    # --- pipelined submit/collect at the largest resident batch that fits
    # the budget: throughput INCLUDING transport, pulls overlapped
    pipe_batch = out.get("fused_device_resident_batch", batch)
    if pipe_batch != batch:
        plines = generate_lines(pipe_batch, patterns, seed=23)
        cls_ids, lens, _ = encode_for_match(compiled, plines, MAX_LEN)
    for _ in range(2):  # warm
        fp.collect(fp.submit(cls_ids, lens))
    n_iters = 8
    t0 = time.perf_counter()
    pend = fp.submit(cls_ids, lens)
    for _ in range(n_iters - 1):
        nxt = fp.submit(cls_ids, lens)
        fp.collect(pend)
        pend = nxt
    fp.collect(pend)
    elapsed = time.perf_counter() - t0
    lps = pipe_batch * n_iters / elapsed
    out["fused_pipelined_lines_per_sec"] = round(lps, 1)
    out["fused_pipelined_batch"] = pipe_batch
    out["fused_batch_latency_ms"] = round(elapsed / n_iters * 1e3, 3)
    return out


def _sec_e2e(jax, ctx, backend, deadline, out) -> dict:
    """End-to-end consume_lines: native parse + encode + fused device match
    + device windows + Banner replay. Reports throughput and the per-batch
    latency distribution (p50/p99) — the p99 Decision latency proxy: a
    line's decision lands at most one batch window behind its arrival."""
    import yaml as _yaml

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from tests.mock_banner import MockBanner

    patterns = ctx["patterns"]
    # one consume_lines burst of several chunks exercises the overlapped
    # two-program pipeline (chunk N's pulls hide behind N+1's compute)
    batch = ctx["e2e_batch"] if backend == "tpu" else 2048
    burst_chunks = ctx["e2e_chunks"] if backend == "tpu" else 3
    n_batches = 6 if backend == "tpu" else 3
    rules_yaml = _yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"crs{i}", "regex": p, "interval": 60,
             "hits_per_interval": 50, "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })
    cfg = config_from_yaml_text(rules_yaml)
    cfg.matcher_batch_lines = batch
    cfg.matcher_device_windows = True
    banner = MockBanner()
    m = TpuMatcher(cfg, banner, StaticDecisionLists(cfg), RegexRateLimitStates())

    now = time.time()
    burst = batch * burst_chunks
    rests = generate_lines(burst, patterns, seed=31)
    lines = [
        f"{now:.6f} 10.{i % 64}.{(i >> 6) % 256}.{(i >> 14) % 256} {r}"
        for i, r in enumerate(rests)
    ]
    m.consume_lines(lines[:256], now)  # warm compile
    m.consume_lines(lines, now)
    lats = []
    t0 = time.perf_counter()
    for _ in range(n_batches):
        tb = time.perf_counter()
        m.consume_lines(lines, now)
        lats.append(time.perf_counter() - tb)
    elapsed = time.perf_counter() - t0
    lats.sort()
    out["e2e_lines_per_sec"] = round(burst * n_batches / elapsed, 1)
    out["e2e_batch"] = batch
    out["e2e_burst_chunks"] = burst_chunks
    # burst latencies measured as-is (dividing by chunks would silently
    # change the meaning of the old per-batch keys)
    out["e2e_burst_latency_ms_p50"] = round(lats[len(lats) // 2] * 1e3, 2)
    out["e2e_burst_latency_ms_p99"] = round(lats[-1] * 1e3, 2)
    out["e2e_staleness_budget_used"] = round(
        lats[-1] / 10.0, 4
    )  # full burst latency vs the 10 s drop window
    fw = getattr(m, "_fw_pipeline", None)
    if fw is not None:
        out["e2e_pipeline_fused"] = fw.fused_batches
        out["e2e_pipeline_fallback"] = fw.fallback_batches

    # realistic-traffic variant: heavy IP repetition (2k distinct) — the
    # default burst above is near-worst-case (every line a fresh IP, the
    # config4 shape), which stresses the per-distinct-ip host work; real
    # edges see orders of magnitude more reuse
    lines_r = [
        f"{now:.6f} 10.9.{(i % 2048) >> 8}.{i % 256} {r}"
        for i, r in enumerate(rests)
    ]
    m.consume_lines(lines_r, now)
    t0 = time.perf_counter()
    for _ in range(n_batches):
        m.consume_lines(lines_r, now)
    out["e2e_repeat_ip_lines_per_sec"] = round(
        burst * n_batches / (time.perf_counter() - t0), 1
    )
    return out


def _sec_mesh(jax, ctx, backend, deadline, out) -> dict:
    """The sharded mesh path executed COMPILED on the attached backend with
    a degenerate dp=1/rp=1 mesh — the execution record that parallel/mesh.py
    runs the same code path the 8-device dryrun validates, on real silicon
    when a chip is attached."""
    from banjax_tpu.matcher.encode import encode_for_match
    from banjax_tpu.parallel import mesh as pmesh
    from banjax_tpu.matcher.prefilter import build_plan
    from banjax_tpu.matcher.rulec import compile_rules

    patterns = ctx["patterns"]
    compiled = ctx.get("compiled")
    if compiled is None:
        compiled = compile_rules(patterns, n_shards="auto")
    # the mesh fused path needs stage 2 packed for exactly rp shards
    plan = build_plan(
        patterns, byte_classes=(compiled.byte_to_class, compiled.n_classes),
        stage2_shards=1,
    )
    m = pmesh.make_mesh(1, rp=1)
    be = pmesh.ShardedMatchBackend(
        compiled, m, MAX_LEN,
        backend="pallas" if backend == "tpu" else "xla",
        block_b=128, plan=plan,
    )
    batch = 16384 if backend == "tpu" else 2048
    lines = generate_lines(batch, patterns, seed=37)
    cls_ids, lens, _ = encode_for_match(compiled, lines, MAX_LEN)
    be.match_bits(cls_ids, lens)  # compile
    n = 4
    t0 = time.perf_counter()
    for _ in range(n):
        be.match_bits(cls_ids, lens)
    elapsed = time.perf_counter() - t0
    # labeled single-device row: this is NOT a parallel measurement — it
    # proves the sharded code path compiles + runs on the attached silicon
    out["mesh_singledev_lines_per_sec"] = round(batch * n / elapsed, 1)
    out["mesh_singledev_shape"] = {"dp": 1, "rp": 1}
    out["mesh_singledev_backend"] = backend
    out["mesh_batch"] = batch
    out["mesh_fused_batches"] = be.fused_batches

    # the real multi-device execution record: dp=2 x rp=4 COMPILED (XLA,
    # non-interpret) over 8 virtual CPU devices in a fresh subprocess.
    # Scaling numbers on virtual devices are meaningless (one physical
    # core) — the row proves compiled multi-device execution and is
    # labeled with its backend so it can never masquerade as a chip number.
    if deadline.over("mesh_multidev"):
        out["mesh_multidev"] = None
        return out
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags)
    env.pop("BENCH_SECTIONS", None)
    try:
        r = subprocess.run(
            [sys.executable, "-c", _MESH_MULTIDEV_CHILD, _DIR],
            capture_output=True, text=True, timeout=600, env=env,
        )
        if r.returncode == 0:
            out["mesh_multidev"] = json.loads(
                r.stdout.strip().splitlines()[-1]
            )
        else:
            out["mesh_multidev"] = {"error": (r.stderr or "no output")[-500:]}
    except Exception as exc:  # noqa: BLE001 — empty stdout / timeout /
        # bad JSON must not zero the section's singledev row
        out["mesh_multidev"] = {"error": f"{type(exc).__name__}: {exc}"}
    return out


_MESH_MULTIDEV_CHILD = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1])
import jax
jax.config.update("jax_platforms", "cpu")
import bench
from banjax_tpu.matcher.encode import encode_for_match
from banjax_tpu.matcher.prefilter import build_plan
from banjax_tpu.matcher.rulec import compile_rules
from banjax_tpu.parallel import mesh as pmesh

assert len(jax.devices()) >= 8, jax.devices()
patterns = bench.generate_rules(bench.N_RULES)
# the rp axis shards the packed word dimension: compile with n_shards=rp
# so every shard is padded to the same width (what the dryrun does too)
compiled = compile_rules(patterns, n_shards=4)
plan = build_plan(
    patterns, byte_classes=(compiled.byte_to_class, compiled.n_classes),
    stage2_shards=4,
)
m = pmesh.make_mesh(8, rp=4)
be = pmesh.ShardedMatchBackend(
    compiled, m, bench.MAX_LEN, backend="xla", block_b=128, plan=plan,
)
batch = 4096
lines = bench.generate_lines(batch, patterns, seed=41)
cls_ids, lens, _ = encode_for_match(compiled, lines, bench.MAX_LEN)
be.match_bits(cls_ids, lens)  # compile
n = 3
t0 = time.perf_counter()
for _ in range(n):
    be.match_bits(cls_ids, lens)
elapsed = time.perf_counter() - t0
print(json.dumps({
    "lines_per_sec": round(batch * n / elapsed, 1),
    "shape": {"dp": 2, "rp": 4},
    "backend": "cpu-virtual-8dev",
    "compiled": True,
    "interpret": False,
    "batch": batch,
    "fused_batches": be.fused_batches,
}))
"""


def _sec_ladder(jax, ctx, backend, deadline, out) -> dict:
    """The five BASELINE.json configs (tests/perf shapes) on the attached
    backend; one config failing keeps the rest."""
    import io
    from contextlib import redirect_stdout

    from tests.perf import test_baseline_ladder as ladder

    lad = {}
    for n, fn in (
        (1, ladder.test_config1_single_rule_replay_cpu_reference),
        (2, ladder.test_config2_default_ruleset_batch),
        (3, ladder.test_config3_1k_rules_batch),
        (4, ladder.test_config4_fused_ua_path_100k_ips),
        (5, ladder.test_config5_kafka_fed_stream_device_windows),
    ):
        if deadline.over(f"ladder_config{n}"):
            lad[f"config{n}"] = None
            out["ladder"] = lad
            continue
        buf = io.StringIO()
        try:
            with redirect_stdout(buf):
                fn()
            lps = json.loads(
                buf.getvalue().strip().splitlines()[-1]
            )["lines_per_sec"]
            lad[f"config{n}"] = lps
            lad[f"config{n}_target_fraction"] = round((lps or 0) / TARGET, 4)
            out["ladder"] = lad
        except Exception as exc:  # noqa: BLE001 — one config failing keeps the rest
            measured = None
            for line in reversed(buf.getvalue().strip().splitlines()):
                try:
                    measured = json.loads(line).get("lines_per_sec")
                    break
                except (json.JSONDecodeError, AttributeError):
                    continue
            lad[f"config{n}"] = {
                "lines_per_sec": measured,
                "error": f"{type(exc).__name__}: {exc}",
            }
            lad[f"config{n}_target_fraction"] = round(
                (measured or 0) / TARGET, 4
            )
            out["ladder"] = lad
    # machine-readable progress toward BASELINE.md's >=5M lines/s: the
    # best ladder fraction (config3 is the 1k-rule north-star shape)
    fracs = [v for k, v in lad.items() if k.endswith("_target_fraction")]
    out["ladder_best_target_fraction"] = max(fracs) if fracs else None
    return out


def _sec_http(jax, ctx, backend, deadline, out) -> dict:
    """The reference's OWN headline harnesses (BenchmarkAuthRequest /
    BenchmarkProtectedPaths, banjax_performance_test.go:18-67) through the
    real standalone server — recorded as requests/sec."""
    import io
    from contextlib import redirect_stdout

    import pytest as _pytest

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = _pytest.main([
            os.path.join(_DIR, "tests", "perf", "test_http_benchmarks.py"),
            "-q", "-s", "-p", "no:cacheprovider",
        ])
    for line in buf.getvalue().splitlines():
        # pytest's progress dots can prefix the payload ('.{"benchmark"...')
        brace = line.find("{")
        if brace < 0:
            continue
        try:
            row = json.loads(line[brace:])
        except json.JSONDecodeError:
            continue
        if row.get("benchmark") == "auth_request":
            out["auth_request_rps"] = row["rps"]
        elif row.get("benchmark") == "protected_paths":
            out["protected_paths_rps"] = row["rps"]
        elif row.get("benchmark") == "auth_request_capacity":
            out["auth_request_capacity_rps"] = row["rps"]
            out["http_cpu_count"] = row.get("cpu_count")
        elif row.get("benchmark") == "auth_request_capacity_workers":
            out["auth_request_capacity_workers_rps"] = row["rps"]
            out["http_workers"] = row.get("http_workers")
    out["http_bench_rc"] = int(rc)
    return out


_SECTION_FNS = {
    "single_stage": _sec_single_stage,
    "fused": _sec_fused,
    "e2e": _sec_e2e,
    "mesh": _sec_mesh,
    "http": _sec_http,
    "ladder": _sec_ladder,
}


def worker_main(backend: str, budget_s: float, only: "list | None") -> None:
    import jax

    if backend == "cpu":
        # the supervisor chose the host backend for this worker
        jax.config.update("jax_platforms", "cpu")
    actual = jax.devices()[0].platform
    deadline = _Deadline(budget_s)
    ctx = {
        "patterns": generate_rules(N_RULES),
        "batch": 32768 if actual == "tpu" else 8192,
        "resident_batches": (65536, 131072) if actual == "tpu" else (8192,),
        "e2e_batch": 32768,
        "e2e_chunks": 3,
    }
    sections = [s for s in SECTIONS if not only or s in only]
    if os.environ.get("BENCH_NO_LADDER") and "ladder" in sections:
        sections.remove("ladder")
    for name in sections:
        if deadline.over(name):
            continue
        data: dict = {}
        try:
            _SECTION_FNS[name](jax, ctx, actual, deadline, data)
        except Exception as exc:  # noqa: BLE001 — persist the failure AND
            # whatever the section measured before it (e.g. the XLA numbers
            # survive a Mosaic lowering reject later in the same section)
            data["error"] = f"{type(exc).__name__}: {exc}"
        data["section_elapsed_s"] = round(time.monotonic() - deadline.t0, 1)
        # the http section never touches the device: label it cpu always,
        # so a tpu-worker run can't freeze it under the best-evidence rule
        _save_section(name, "cpu" if name == "http" else actual, data)
        print(f"[bench-worker] {name} done on {actual}", file=sys.stderr)
    if deadline.skipped:
        _save_section(
            "meta", actual, {"sections_skipped_on_budget": deadline.skipped}
        )


# ---------------------------------------------------------------------------
# streaming modes: --pipeline vs --sync (the scheduler's acceptance bench)
# ---------------------------------------------------------------------------

STREAM_PATH = os.path.join(_DIR, "BENCH_pipeline.json")
FUSED_STREAM_PATH = os.path.join(_DIR, "BENCH_fused_pipeline.json")
HOST_PARALLEL_PATH = os.path.join(_DIR, "BENCH_host_parallel.json")
TRACE_OVERHEAD_PATH = os.path.join(_DIR, "BENCH_trace_overhead.json")


def _trace_overhead_mode() -> None:
    """`bench.py --trace-overhead`: A/B the pipelined stream with the
    span recorder (obs/trace.py) disabled vs enabled and bank both rows
    plus the relative delta into BENCH_trace_overhead.json.

    The acceptance gate is the OFF row: the instrumented hot path with
    `trace_enabled: false` must cost ≤1% vs enabled tracing being the
    only difference — the disabled fast path is one attribute check per
    call site.  Same workload shape as `--pipeline` (tailer-shaped
    chunks through the scheduler), fresh matcher per mode, warm pass
    before every timed pass so compiles never land in the timing.
    """
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import yaml as _yaml

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from banjax_tpu.obs import trace as trace_mod
    from banjax_tpu.pipeline import PipelineScheduler
    from tests.mock_banner import MockBanner

    backend = jax.devices()[0].platform
    n_rules = int(os.environ.get("BENCH_STREAM_RULES", str(N_RULES)))
    total = int(os.environ.get(
        "BENCH_STREAM_LINES", "131072" if backend == "tpu" else "32768"
    ))
    feed_chunk = int(os.environ.get("BENCH_STREAM_CHUNK", "64"))
    budget_ms = float(os.environ.get("BENCH_STREAM_BUDGET_MS", "180"))
    ring_size = int(os.environ.get("BENCH_TRACE_RING", "4096"))
    iters = int(os.environ.get("BENCH_TRACE_ITERS", "3"))

    patterns = generate_rules(n_rules)
    rules_yaml = _yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"crs{i}", "regex": p, "interval": 60,
             "hits_per_interval": 50, "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })
    now = time.time()
    rests = generate_lines(total, patterns, seed=43)
    lines = [
        f"{now:.6f} 10.9.{(i % 2048) >> 8}.{i % 256} {r}"
        for i, r in enumerate(rests)
    ]
    chunks = [lines[i : i + feed_chunk] for i in range(0, total, feed_chunk)]

    def run_mode(enabled: bool) -> dict:
        trace_mod.configure(enabled=enabled, ring_size=ring_size)
        cfg = config_from_yaml_text(rules_yaml)
        matcher = TpuMatcher(
            cfg, MockBanner(), StaticDecisionLists(cfg),
            RegexRateLimitStates()
        )
        sched = PipelineScheduler(
            lambda: matcher, latency_budget_ms=budget_ms,
            buffer_lines=max(131072, total), now_fn=lambda: now,
        )
        sched.start()
        for c in chunks:  # warm pass: compiles + sizer settle
            sched.submit(c)
        assert sched.flush(600), "trace-overhead warm pass did not drain"
        best = 0.0
        for _ in range(iters):
            t0 = time.perf_counter()
            for c in chunks:
                sched.submit(c)
            assert sched.flush(600), "trace-overhead pass did not drain"
            best = max(best, total / (time.perf_counter() - t0))
        spans = len(trace_mod.get_tracer().snapshot())
        sched.stop()
        matcher.close()
        trace_mod.configure(enabled=False)
        return {
            "trace_enabled": enabled,
            "value": round(best, 1),
            "unit": "lines/sec",
            "backend": backend,
            "n_rules": n_rules,
            "n_lines": total,
            "feed_chunk_lines": feed_chunk,
            "iters_best_of": iters,
            "spans_in_ring": spans,
        }

    # off → on → off: the second off run controls for run-order effects
    # (in-process compile caches, sizer settle, thermal drift) that can
    # otherwise dwarf the ≤1% effect being measured; each mode reports
    # its best pass, off takes the best of both bracketing runs
    off_a = run_mode(False)
    on = run_mode(True)
    off_b = run_mode(False)
    off = max(off_a, off_b, key=lambda r: r["value"])
    book = {
        "metric": "pipelined lines/sec, span recorder off vs on",
        "off": off,
        "on": on,
        "off_runs": [off_a["value"], off_b["value"]],
        "trace_ring_size": ring_size,
        # on-vs-off: the full cost of RECORDING every stage span;
        # negative = within run-to-run noise
        "on_vs_off_overhead_pct": round(
            (off["value"] - on["value"]) / off["value"] * 100.0, 2
        ),
    }
    tmp = TRACE_OVERHEAD_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, TRACE_OVERHEAD_PATH)
    print(json.dumps(book))


PROVENANCE_OVERHEAD_PATH = os.path.join(
    _DIR, "BENCH_provenance_overhead.json"
)


def _provenance_overhead_mode() -> None:
    """`bench.py --provenance-overhead`: A/B the pipelined stream with
    the decision provenance ledger (obs/provenance.py) disabled vs
    enabled, same off → on → off bracketing protocol as
    --trace-overhead, banked into BENCH_provenance_overhead.json.

    Unlike the trace A/B, the workload must actually FIRE bans or the
    ledger sits idle and the measurement is vacuous: the feed rotates a
    small IP pool (BENCH_PROV_IPS, default 256) against a low
    hits_per_interval so every IP bans repeatedly through the run —
    `records_in_ledger` in the banked row witnesses the exercised path.
    """
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import yaml as _yaml

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from banjax_tpu.obs import provenance as prov_mod
    from banjax_tpu.obs import trace as trace_mod
    from banjax_tpu.pipeline import PipelineScheduler
    from tests.mock_banner import MockBanner

    trace_mod.configure(enabled=False)  # isolate the ledger's cost
    backend = jax.devices()[0].platform
    n_rules = int(os.environ.get("BENCH_STREAM_RULES", str(N_RULES)))
    total = int(os.environ.get(
        "BENCH_STREAM_LINES", "131072" if backend == "tpu" else "32768"
    ))
    feed_chunk = int(os.environ.get("BENCH_STREAM_CHUNK", "64"))
    budget_ms = float(os.environ.get("BENCH_STREAM_BUDGET_MS", "180"))
    ring_size = int(os.environ.get("BENCH_PROV_RING", "2048"))
    n_ips = int(os.environ.get("BENCH_PROV_IPS", "256"))
    hits_per_interval = int(os.environ.get("BENCH_PROV_HITS", "10"))
    attack_rate = float(os.environ.get("BENCH_PROV_ATTACK", "0.05"))
    iters = int(os.environ.get("BENCH_TRACE_ITERS", "3"))

    patterns = generate_rules(n_rules)
    rules_yaml = _yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"crs{i}", "regex": p, "interval": 60,
             "hits_per_interval": hits_per_interval,
             "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })
    now = time.time()
    # rate limiting is per (ip, rule): the generic 2% attack mix spread
    # over 1000 rules never re-hits one pair, so the ledger would sit
    # idle.  Concentrate attack_rate of the stream on rule 0 from a
    # small rotating IP pool — every IP re-crosses the threshold again
    # and again, which is exactly the ban-storm shape the ledger must
    # absorb without slowing the pipeline.
    rng = random.Random(43)
    benign = generate_lines(total, patterns, seed=43, attack_rate=0.0)
    attack_rest = synthesize_match(patterns[0], rng)
    rests = [
        attack_rest if rng.random() < attack_rate else benign[i]
        for i in range(total)
    ]
    lines = [
        f"{now:.6f} 10.9.{(i % n_ips) >> 8}.{(i % n_ips) & 0xFF} {r}"
        for i, r in enumerate(rests)
    ]
    chunks = [lines[i : i + feed_chunk] for i in range(0, total, feed_chunk)]

    def run_mode(enabled: bool) -> dict:
        prov_mod.configure(enabled=enabled, ring_size=ring_size)
        cfg = config_from_yaml_text(rules_yaml)
        matcher = TpuMatcher(
            cfg, MockBanner(), StaticDecisionLists(cfg),
            RegexRateLimitStates()
        )
        sched = PipelineScheduler(
            lambda: matcher, latency_budget_ms=budget_ms,
            buffer_lines=max(131072, total), now_fn=lambda: now,
        )
        sched.start()
        for c in chunks:  # warm pass: compiles + sizer settle
            sched.submit(c)
        assert sched.flush(600), "provenance warm pass did not drain"
        best = 0.0
        for _ in range(iters):
            t0 = time.perf_counter()
            for c in chunks:
                sched.submit(c)
            assert sched.flush(600), "provenance pass did not drain"
            best = max(best, total / (time.perf_counter() - t0))
        records = prov_mod.get_ledger().total_records()
        sched.stop()
        matcher.close()
        prov_mod.configure(enabled=True)
        return {
            "provenance_enabled": enabled,
            "value": round(best, 1),
            "unit": "lines/sec",
            "backend": backend,
            "n_rules": n_rules,
            "n_lines": total,
            "n_distinct_ips": n_ips,
            "hits_per_interval": hits_per_interval,
            "feed_chunk_lines": feed_chunk,
            "iters_best_of": iters,
            "records_in_ledger": records,
        }

    # off → on → off bracketing, exactly like --trace-overhead: the
    # second off run controls for run-order effects (compile caches,
    # sizer settle) that can dwarf the effect being measured
    off_a = run_mode(False)
    on = run_mode(True)
    off_b = run_mode(False)
    off = max(off_a, off_b, key=lambda r: r["value"])
    noise_band_pct = round(
        abs(off_a["value"] - off_b["value"])
        / max(off_a["value"], off_b["value"]) * 100.0, 2
    )
    overhead_pct = round(
        (off["value"] - on["value"]) / off["value"] * 100.0, 2
    )
    book = {
        "metric": "pipelined lines/sec, provenance ledger off vs on",
        "off": off,
        "on": on,
        "off_runs": [off_a["value"], off_b["value"]],
        "provenance_ring_size": ring_size,
        "on_vs_off_overhead_pct": overhead_pct,
        # the off↔off spread IS the noise band; the acceptance gate is
        # on_within_off_noise_band (ISSUE 6)
        "off_run_noise_band_pct": noise_band_pct,
        "on_within_off_noise_band": bool(
            overhead_pct <= max(noise_band_pct, 1.0)
        ),
    }
    tmp = PROVENANCE_OVERHEAD_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, PROVENANCE_OVERHEAD_PATH)
    print(json.dumps(book))


SKETCH_OVERHEAD_PATH = os.path.join(_DIR, "BENCH_sketch_overhead.json")


def _sketch_overhead_mode() -> None:
    """`bench.py --sketch-overhead`: A/B the pipelined stream with the
    device traffic sketch (obs/sketch.py) disabled vs enabled, same
    off → on → off bracketing protocol as --provenance-overhead, on the
    SAME ban-storm shape (rotating IP pool, concentrated single-rule
    attack) so the sketch actually works: heavy hitters recur, slots
    churn the hash table, and rule pressure accumulates.  The banked
    row carries a populated-sketch witness (`sketch_lines`, `top1`) so
    an accidentally-idle sketch can't bank a vacuous "no overhead"."""
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import yaml as _yaml

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from banjax_tpu.obs import trace as trace_mod
    from banjax_tpu.pipeline import PipelineScheduler
    from tests.mock_banner import MockBanner

    trace_mod.configure(enabled=False)  # isolate the sketch's cost
    backend = jax.devices()[0].platform
    n_rules = int(os.environ.get("BENCH_STREAM_RULES", str(N_RULES)))
    total = int(os.environ.get(
        "BENCH_STREAM_LINES", "131072" if backend == "tpu" else "32768"
    ))
    feed_chunk = int(os.environ.get("BENCH_STREAM_CHUNK", "64"))
    budget_ms = float(os.environ.get("BENCH_STREAM_BUDGET_MS", "180"))
    n_ips = int(os.environ.get("BENCH_PROV_IPS", "256"))
    hits_per_interval = int(os.environ.get("BENCH_PROV_HITS", "10"))
    attack_rate = float(os.environ.get("BENCH_PROV_ATTACK", "0.05"))
    iters = int(os.environ.get("BENCH_TRACE_ITERS", "3"))

    patterns = generate_rules(n_rules)
    rules_yaml = _yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"crs{i}", "regex": p, "interval": 60,
             "hits_per_interval": hits_per_interval,
             "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })
    now = time.time()
    rng = random.Random(43)
    benign = generate_lines(total, patterns, seed=43, attack_rate=0.0)
    attack_rest = synthesize_match(patterns[0], rng)
    rests = [
        attack_rest if rng.random() < attack_rate else benign[i]
        for i in range(total)
    ]
    lines = [
        f"{now:.6f} 10.9.{(i % n_ips) >> 8}.{(i % n_ips) & 0xFF} {r}"
        for i, r in enumerate(rests)
    ]
    chunks = [lines[i : i + feed_chunk] for i in range(0, total, feed_chunk)]

    def run_mode(enabled: bool) -> dict:
        cfg = config_from_yaml_text(rules_yaml)
        # the sketch rides the device-windows fused path (its update keys
        # on the window slot ids) — both arms run that path
        cfg.matcher_device_windows = True
        cfg.traffic_sketch_enabled = enabled
        matcher = TpuMatcher(
            cfg, MockBanner(), StaticDecisionLists(cfg),
            RegexRateLimitStates()
        )
        sched = PipelineScheduler(
            lambda: matcher, latency_budget_ms=budget_ms,
            buffer_lines=max(131072, total), now_fn=lambda: now,
        )
        sched.start()
        for c in chunks:  # warm pass: compiles + sizer settle
            sched.submit(c)
        assert sched.flush(600), "sketch warm pass did not drain"
        best = 0.0
        for _ in range(iters):
            t0 = time.perf_counter()
            for c in chunks:
                sched.submit(c)
            assert sched.flush(600), "sketch pass did not drain"
            best = max(best, total / (time.perf_counter() - t0))
        row = {
            "sketch_enabled": enabled,
            "value": round(best, 1),
            "unit": "lines/sec",
            "backend": backend,
            "n_rules": n_rules,
            "n_lines": total,
            "n_distinct_ips": n_ips,
            "hits_per_interval": hits_per_interval,
            "feed_chunk_lines": feed_chunk,
            "iters_best_of": iters,
        }
        if enabled:
            # the populated-sketch witness: lines actually folded, and a
            # ranked heavy hitter with a conservative estimate
            summary = matcher.traffic_sketch.pull(force=True)
            row["sketch_lines"] = matcher.traffic_sketch.lines_total
            row["top1"] = summary["top"][0] if summary["top"] else None
            row["distinct_ips_estimate"] = summary["distinct_ips_estimate"]
            row["rule_pressure_events"] = sum(
                r["events"] for r in summary["rule_pressure"]
            )
        sched.stop()
        matcher.close()
        return row

    # off → on → off bracketing, exactly like --provenance-overhead: the
    # second off run controls for run-order effects (compile caches,
    # sizer settle) that can dwarf the effect being measured
    off_a = run_mode(False)
    on = run_mode(True)
    off_b = run_mode(False)
    off = max(off_a, off_b, key=lambda r: r["value"])
    noise_band_pct = round(
        abs(off_a["value"] - off_b["value"])
        / max(off_a["value"], off_b["value"]) * 100.0, 2
    )
    overhead_pct = round(
        (off["value"] - on["value"]) / off["value"] * 100.0, 2
    )
    book = {
        "metric": "pipelined lines/sec, traffic sketch off vs on",
        "off": off,
        "on": on,
        "off_runs": [off_a["value"], off_b["value"]],
        "on_vs_off_overhead_pct": overhead_pct,
        # the off↔off spread IS the noise band; the acceptance gate is
        # on_within_off_noise_band (ISSUE 8)
        "off_run_noise_band_pct": noise_band_pct,
        "on_within_off_noise_band": bool(
            overhead_pct <= max(noise_band_pct, 1.0)
        ),
    }
    tmp = SKETCH_OVERHEAD_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, SKETCH_OVERHEAD_PATH)
    print(json.dumps(book))


def _host_parallel_mode() -> None:
    """`bench.py --host-parallel`: A/B the two host-path optimizations.

    (a) encode stage, workers 0 vs N: times the scheduler's host stage
        (parse + gate + encode, matcher.pipeline_begin) directly —
        single-thread vs the sharded worker pool — on the all-distinct-IP
        worst case from PERF round 4.  Device time is deliberately out of
        the measurement: this is the stage the PR parallelizes.
    (b) slot manager, native C vs Python dict: per-batch cost of
        slots_for_unique_ips at the all-distinct-IP shape (every batch
        all-new ips — the ~15 ms/batch residual in PERF r4's table), plus
        the all-hit warm shape.

    Provenance is honest by construction: rows are keyed by the host's
    core count, so the 1-core CI row (where worker scaling CANNOT
    manifest — the acceptance there is "within noise") never masquerades
    as a multi-core chip-host row.
    """
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import yaml as _yaml

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from banjax_tpu.pipeline import PipelineScheduler
    from banjax_tpu.pipeline.scheduler import resolve_encode_workers
    from tests.mock_banner import MockBanner

    backend = jax.devices()[0].platform
    cores = os.cpu_count() or 1
    n_rules = int(os.environ.get("BENCH_STREAM_RULES", str(N_RULES)))
    n_lines = int(os.environ.get("BENCH_HOST_LINES", "32768"))
    workers = int(os.environ.get(
        "BENCH_HOST_WORKERS", str(max(2, resolve_encode_workers(-1)))
    ))
    iters = int(os.environ.get("BENCH_HOST_ITERS", "6"))

    patterns = generate_rules(n_rules)
    rules_yaml = _yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"crs{i}", "regex": p, "interval": 60,
             "hits_per_interval": 50, "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })
    cfg = config_from_yaml_text(rules_yaml)
    matcher = TpuMatcher(
        cfg, MockBanner(), StaticDecisionLists(cfg), RegexRateLimitStates()
    )
    now = time.time()
    rests = generate_lines(n_lines, patterns, seed=53)
    # all-distinct IPs: the host-stage worst case (PERF r4) — every line
    # a fresh entry in the unique-IP table
    lines = [
        f"{now:.6f} 10.{(i >> 16) & 63}.{(i >> 8) & 255}.{i & 255} {r}"
        for i, r in enumerate(rests)
    ]

    # --- (a) encode stage: workers 0 vs N over the identical batch ---
    # resolved_default_workers is what encode_workers=-1 (the config
    # default) picks on THIS host: 0 on a 1-core box — the A/B's forced
    # worker row there measures pure fan-out overhead a production
    # deployment never pays
    encode = {
        "n_lines": n_lines,
        "workers_ab": workers,
        "resolved_default_workers": resolve_encode_workers(-1),
    }
    for w in (0, workers):
        sched = PipelineScheduler(lambda: matcher, encode_workers=w,
                                  now_fn=lambda: now)
        sched.start()  # creates the worker pool; stage threads idle
        for _ in range(2):
            sched._begin_state(matcher, lines)  # warm (parse caches, jit)
        t0 = time.perf_counter()
        for _ in range(iters):
            sched._begin_state(matcher, lines)
        elapsed = time.perf_counter() - t0
        snap = sched.stats.snapshot()
        sched.stop()
        key = "workers0" if w == 0 else f"workers{w}"
        encode[f"{key}_lines_per_sec"] = round(n_lines * iters / elapsed, 1)
        encode[f"{key}_batch_ms"] = round(elapsed / iters * 1e3, 2)
        if w:
            encode["sharded_batches"] = snap["EncodeShardedBatches"]
            encode["shard_ms_max"] = snap["EncodeShardMsMax"]
            encode["worker_utilization"] = snap["EncodeWorkerUtilization"]
    encode["workers_speedup"] = round(
        encode[f"workers{workers}_lines_per_sec"]
        / max(1.0, encode["workers0_lines_per_sec"]), 3
    )

    # --- (b) slot manager: native vs dict at the all-distinct shape ---
    from banjax_tpu.matcher.windows import DeviceWindows
    from banjax_tpu.native import slotmgr as _slotmgr

    slot_batch = int(os.environ.get("BENCH_HOST_SLOT_BATCH", "65536"))
    slot_iters = 4
    slotmgr = {
        "batch_unique_ips": slot_batch,
        "native_available": _slotmgr.create(8) is not None,
    }
    for native in ((True, False) if slotmgr["native_available"] else (False,)):
        dw = DeviceWindows(
            [matcher._entries[0][1]],
            capacity=slot_batch * slot_iters, native_slotmgr=native,
        )
        mode = "native" if native else "python"
        ip_batches = [
            [f"{j}.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}"
             for i in range(slot_batch)]
            for j in range(slot_iters)
        ]
        # cold: every batch all-new ips (miss + placement per entry)
        t0 = time.perf_counter()
        for ips in ip_batches:
            slots = dw.slots_for_unique_ips(ips)
            dw.release_pins(slots)
        slotmgr[f"{mode}_all_distinct_ms_per_batch"] = round(
            (time.perf_counter() - t0) / slot_iters * 1e3, 2
        )
        # warm: the same ips again (pure hit path)
        t0 = time.perf_counter()
        for ips in ip_batches:
            slots = dw.slots_for_unique_ips(ips)
            dw.release_pins(slots)
        slotmgr[f"{mode}_all_hit_ms_per_batch"] = round(
            (time.perf_counter() - t0) / slot_iters * 1e3, 2
        )
    if slotmgr["native_available"]:
        slotmgr["native_vs_python_cost_ratio"] = round(
            slotmgr["native_all_distinct_ms_per_batch"]
            / max(1e-9, slotmgr["python_all_distinct_ms_per_batch"]), 3
        )

    row = {
        "backend": backend,
        "cpu_count": cores,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "n_rules": n_rules,
        "encode": encode,
        "slotmgr": slotmgr,
        "provenance_note": (
            "1-core host: the worker pool CANNOT scale here (acceptance "
            "is 'within noise of single-thread'); scaling evidence must "
            "come from a multi-core row"
            if cores == 1 else
            f"{cores}-core host: workers_speedup is a real scaling "
            "measurement"
        ),
    }
    try:
        with open(HOST_PARALLEL_PATH) as f:
            book = json.load(f)
    except (OSError, json.JSONDecodeError):
        book = {}
    book.setdefault(
        "metric",
        "host-path A/B: sharded encode workers + native slot manager",
    )
    # rows keyed by core count: the 1-core CI row and the multi-core
    # chip-host row coexist instead of clobbering each other
    book[f"{cores}core"] = row
    tmp = HOST_PARALLEL_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, HOST_PARALLEL_PATH)
    print(json.dumps({"metric": book["metric"], **row}))


def _fused_pipeline_mode() -> None:
    """`bench.py --fused-pipeline`: the streaming pipeline with DEVICE
    WINDOWS on, fused two-phase (program A at submit, window commit at
    drain — matcher/fused_windows.py driven by pipeline/scheduler.py)
    versus the classic bitmap split protocol (pipeline_fused: false),
    same chunk stream.  Records both rows plus the h2d bytes/batch
    witness into BENCH_fused_pipeline.json: the fused row must match or
    beat the classic rate AND show the dense [B, n_rules] re-upload
    (~16 MB per 65k batch at 1k rules) gone from the h2d counter."""
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import yaml as _yaml

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from banjax_tpu.pipeline import PipelineScheduler
    from tests.mock_banner import MockBanner

    backend = jax.devices()[0].platform
    n_rules = int(os.environ.get("BENCH_STREAM_RULES", str(N_RULES)))
    total = int(os.environ.get(
        "BENCH_STREAM_LINES", "131072" if backend == "tpu" else "16384"
    ))
    feed_chunk = int(os.environ.get("BENCH_STREAM_CHUNK", "256"))
    budget_ms = float(os.environ.get("BENCH_STREAM_BUDGET_MS", "180"))

    patterns = generate_rules(n_rules)
    rules_yaml = _yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"crs{i}", "regex": p, "interval": 60,
             "hits_per_interval": 50, "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })
    now = time.time()
    rests = generate_lines(total, patterns, seed=47)
    lines = [
        f"{now:.6f} 10.7.{(i % 2048) >> 8}.{i % 256} {r}"
        for i, r in enumerate(rests)
    ]
    chunks = [lines[i : i + feed_chunk] for i in range(0, total, feed_chunk)]

    rows = {}
    for label, fused in (("fused", True), ("classic", False)):
        cfg = config_from_yaml_text(rules_yaml)
        cfg.matcher_device_windows = True
        cfg.pipeline_fused = fused
        matcher = TpuMatcher(
            cfg, MockBanner(), StaticDecisionLists(cfg),
            RegexRateLimitStates(),
        )
        assert matcher._fw_pipeline is not None, (
            "fused matcher+windows pipeline did not engage"
        )
        sched = PipelineScheduler(
            lambda: matcher, latency_budget_ms=budget_ms,
            buffer_lines=max(131072, total), now_fn=lambda: now,
        )
        sched.start()
        for c in chunks:  # warm pass: compile every bucket
            sched.submit(c)
        assert sched.flush(600), f"{label} warm pass did not drain"
        h2d0 = matcher.stats.h2d_bytes_total
        batches0 = matcher.stats.batches_total
        t0 = time.perf_counter()
        for c in chunks:
            sched.submit(c)
        assert sched.flush(600), f"{label} timed pass did not drain"
        elapsed = time.perf_counter() - t0
        snap = sched.snapshot()
        sched.stop()
        n_batches = max(1, matcher.stats.batches_total - batches0)
        rows[label] = {
            "mode": f"pipeline+device_windows ({label})",
            "backend": backend,
            "value": round(total / elapsed, 1),
            "unit": "lines/sec",
            "vs_baseline": round(total / elapsed / TARGET, 4),
            "elapsed_s": round(elapsed, 2),
            "n_rules": n_rules,
            "n_lines": total,
            "h2d_bytes_per_batch": round(
                (matcher.stats.h2d_bytes_total - h2d0) / n_batches, 1
            ),
            "pipelined_fused_chunks": matcher.pipelined_fused_chunks,
            "pipelined_fused_fallbacks": matcher.pipelined_fused_fallbacks,
            "pipeline_batches": snap.get("PipelineBatches"),
            "pipeline_shed_lines": snap.get("PipelineShedLines"),
        }

    book = {
        "metric": "log-lines/sec, streaming pipeline + device windows "
                  "(fused two-phase vs classic bitmap)",
        "fused": rows["fused"],
        "classic": rows["classic"],
        "fused_vs_classic_speedup": round(
            rows["fused"]["value"] / max(1.0, rows["classic"]["value"]), 3
        ),
        # the fusion-win witness: classic re-uploads the dense bitmap
        # (n_rules bytes/line); fused must not
        "dense_reupload_eliminated": (
            rows["fused"]["h2d_bytes_per_batch"]
            < 0.5 * rows["classic"]["h2d_bytes_per_batch"]
        ),
    }
    tmp = FUSED_STREAM_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, FUSED_STREAM_PATH)
    print(json.dumps(book))


SINGLE_KERNEL_PATH = os.path.join(_DIR, "BENCH_single_kernel.json")
SCENARIOS_PATH = os.path.join(_DIR, "BENCH_scenarios.json")


def _scenarios_mode() -> None:
    """`bench.py --scenarios`: one banked row per named attack shape
    (banjax_tpu/scenarios/) plus a seeded chaos-soak row.

    Every row carries lines/s, shed ratio, ban precision/recall against
    the generator's ground-truth oracle, per-SLO peak burn rates, and
    the structural-invariant verdicts — so every future perf PR is
    judged on hostile shapes, not just the happy-path feed.  The chaos
    row additionally records each injected failpoint episode (point,
    fired count, flight-recorder bundle).  Knobs: BENCH_SCEN_SCALE
    (default 1.0), BENCH_SCEN_SEED, BENCH_CPU=1 for the host backend.
    """
    import tempfile

    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from banjax_tpu.scenarios import (
        SHAPES,
        ChaosSchedule,
        ScenarioRunner,
        generate,
    )

    backend = jax.devices()[0].platform
    scale = float(os.environ.get("BENCH_SCEN_SCALE", "1.0"))
    seed = int(os.environ.get("BENCH_SCEN_SEED", "20260804"))

    rows = {}
    with tempfile.TemporaryDirectory(prefix="bench-scen-") as scen_tmp:
        for name in sorted(SHAPES):
            sc = generate(name, seed=seed, scale=scale)
            kwargs = {}
            if name == "log_rotation":
                # the rotation shape runs through a REAL file + tailer so
                # the banked row exercises the reopen-by-inode path
                kwargs = {
                    "via_tailer": True,
                    "tmp_dir": os.path.join(scen_tmp, name),
                }
                os.makedirs(kwargs["tmp_dir"], exist_ok=True)
            rep = ScenarioRunner(sc, **kwargs).run()
            rows[name] = rep.row()
            print(json.dumps({
                "scenario": name,
                "lines_per_sec": rep.lines_per_sec,
                "shed_ratio": rep.shed_ratio,
                "precision": rep.precision,
                "recall": rep.recall,
                "invariants_ok": rep.ok(),
            }), flush=True)

    # the seeded chaos soak: failpoint episodes over the rotating-proxy
    # worst case, flight recorder armed — banked with per-episode
    # evidence (this is the row the breaker/shed defaults derive from)
    chaos_rows = {}
    with tempfile.TemporaryDirectory() as fr_dir:
        for name in ("flash_crowd", "rotating_proxies"):
            sc = generate(name, seed=seed + 1, scale=scale)
            chaos = ChaosSchedule(
                seed=seed + 1, n_events=len(sc.events), episodes=5
            )
            rep = ScenarioRunner(
                sc, chaos=chaos,
                flightrec_dir=os.path.join(fr_dir, name),
            ).run()
            chaos_rows[name] = rep.row()

    # derived defaults (PERF.md round 13): breaker window from the
    # observed episode cadence, latency budget from the clean-shape
    # device p99 discipline (3x p99, floor 50 ms — the PR 2 rule, now
    # fed by hostile-shape data instead of a guess)
    burn_peaks = [
        max(r["slo_burn_peak"].values() or [0.0])
        for r in rows.values()
    ]
    book = {
        "metric": "scenario harness: per-shape rows + seeded chaos soak",
        "backend": backend,
        "seed": seed,
        "scale": scale,
        "measured_at": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        ),
        "scenarios": rows,
        "chaos": chaos_rows,
        "summary": {
            "shapes": len(rows),
            "all_invariants_ok": all(
                all(r["invariants"].values())
                for r in list(rows.values()) + list(chaos_rows.values())
            ),
            "clean_precision_min": min(
                r["precision"] for r in rows.values()
            ),
            "clean_recall_min": min(r["recall"] for r in rows.values()),
            "benign_slo_breached": any(
                rows["benign"]["slo_breached"].values()
            ),
            "max_clean_burn_peak": max(burn_peaks) if burn_peaks else 0.0,
            "chaos_episodes": sum(
                len(r["episodes"]) for r in chaos_rows.values()
            ),
            "chaos_bundles": sum(
                sum(1 for ep in r["episodes"] if ep["bundle"])
                for r in chaos_rows.values()
            ),
        },
    }
    tmp = SCENARIOS_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, SCENARIOS_PATH)
    print(json.dumps({"metric": book["metric"], **book["summary"]}))


MEGA_STATE_PATH = os.path.join(_DIR, "BENCH_mega_state.json")


def _mega_state_mode() -> None:
    """`bench.py --mega-state`: the mega-state tiering A/B.

    One streamed pass of the 10M-distinct rotation per arm (admission
    off, then on — same generator args, so byte-identical streams),
    slot capacity pinned at 65536 (the ISSUE 14 worst-case shape) so
    the OFF arm actually pays the all-distinct slot churn the gate
    exists to remove.  Both arms run the warm tier and a sketch wide
    enough that the refused-fold mass (one row per distinct IP) keeps
    conservative estimates under the derived admission threshold —
    width is a knob so the banked row records the sizing that held.
    """
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from banjax_tpu.scenarios import oracle as oracle_mod
    from banjax_tpu.scenarios.runtime import RecordingBanner
    from banjax_tpu.scenarios.shapes import (
        RULES_YAML,
        RUN_NOW,
        mega_offenders,
        mega_rotating_proxies_stream,
    )

    backend = jax.devices()[0].platform
    n_distinct = int(os.environ.get("BENCH_MEGA_DISTINCT", "10000000"))
    chunk = int(os.environ.get("BENCH_MEGA_CHUNK", "16384"))
    seed = int(os.environ.get("BENCH_MEGA_SEED", "20260804"))
    capacity = int(os.environ.get("BENCH_MEGA_CAPACITY", "65536"))
    sketch_width = int(
        os.environ.get("BENCH_MEGA_SKETCH_WIDTH", str(1 << 22))
    )

    def build(admission: bool):
        cfg = config_from_yaml_text(RULES_YAML)
        cfg.matcher = "tpu"
        cfg.matcher_device_windows = True
        cfg.matcher_batch_lines = chunk
        cfg.matcher_window_capacity = capacity
        cfg.traffic_sketch_enabled = True
        cfg.traffic_sketch_width = sketch_width
        cfg.slot_admission_enabled = admission
        cfg.warm_tier_enabled = True
        banner = RecordingBanner()
        matcher = TpuMatcher(
            cfg, banner, StaticDecisionLists(cfg), RegexRateLimitStates()
        )
        return cfg, matcher, banner

    # the oracle: offenders only — the mega noise is rule-neutral by
    # construction, so per-(ip, rule) fixed windows make the full
    # stream's expected multiset equal the offender sub-stream's
    oracle_cfg = config_from_yaml_text(RULES_YAML)
    oracle_bans = oracle_mod.expected_bans(
        mega_offenders(seed), oracle_cfg
    )

    rows = {}
    for arm in ("admission_off", "admission_on"):
        admission = arm == "admission_on"
        cfg, matcher, banner = build(admission)
        n_lines = 0
        t0 = time.perf_counter()
        for lines in mega_rotating_proxies_stream(
            seed, n_distinct, chunk=chunk
        ):
            matcher.consume_lines(lines, now_unix=RUN_NOW)
            n_lines += len(lines)
        elapsed = time.perf_counter() - t0
        dw = matcher.device_windows
        precision, recall, _ = oracle_mod.precision_recall(
            banner.regex_ban_logs, oracle_bans
        )
        rows[arm] = {
            "lines": n_lines,
            "distinct_ips": n_distinct,
            "elapsed_s": round(elapsed, 3),
            "lines_per_sec": round(n_lines / elapsed, 1),
            "engine_bans": len(banner.regex_ban_logs),
            "oracle_bans": len(oracle_bans),
            "precision": precision,
            "recall": recall,
            "slot_refusals": dw.slot_refusals,
            "sketch_admissions": dw.sketch_admissions,
            "sketch_admission_fp_rate": round(
                dw.sketch_admission_fp_rate, 6
            ),
            "slot_occupancy": dw.occupancy,
            "slot_capacity": capacity,
            "warm_spills": dw.warm_spills,
            "warm_refills": dw.warm_refills,
            "warm_dropped": dw.warm_dropped,
            "warm_occupancy": dw.warm_occupancy,
        }
        matcher.close()
        print(json.dumps({"arm": arm, **rows[arm]}), flush=True)

    on, off = rows["admission_on"], rows["admission_off"]
    book = {
        "metric": (
            "mega-state tiering: sketch-gated slot admission A/B at "
            f"{n_distinct} distinct IPs"
        ),
        "backend": backend,
        "seed": seed,
        "chunk_lines": chunk,
        "sketch_width": sketch_width,
        "sketch_depth": int(oracle_cfg.traffic_sketch_depth),
        "admission_min_estimate_derived": (
            min(
                r.hits_per_interval
                for r in oracle_cfg.regexes_with_rates
            )
            + 1
        ),
        "measured_at": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        ),
        "rows": rows,
        "summary": {
            "speedup_on_vs_off": round(
                on["lines_per_sec"] / off["lines_per_sec"], 4
            ),
            "acceptance_on_not_slower": (
                on["lines_per_sec"] >= off["lines_per_sec"]
            ),
            "acceptance_ban_parity": all(
                r[k] == 1.0
                for r in (on, off)
                for k in ("precision", "recall")
            ),
        },
    }
    tmp = MEGA_STATE_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, MEGA_STATE_PATH)
    print(json.dumps({"metric": book["metric"], **book["summary"]}))


FABRIC_PATH = os.path.join(_DIR, "BENCH_fabric.json")


def _forward_micro(transport: str, n_lines: int) -> dict:
    """Forwarding-plane micro-row: one shard's transport to one remote
    peer (FabricNode on a loopback socket / shm ring), fed ONE LINE at
    a time.  The json arm is PR 11's wire verbatim — a synchronous
    JSON request/response per line, so every line pays a full RTT plus
    two JSON codecs.  The v2/shm arms push the same per-line stream
    through the windowed LinePipe: submissions coalesce into binary
    batched frames and up to 8 ride unacked, so the RTT amortizes
    across thousands of lines.  Matching layers on both sides (no
    router, no matcher) keeps this a measurement of the wire alone.
    Best-of-3: on the 1-core bench box a single scheduler hiccup inside
    the timed loop can swing an arm 30%+, so each arm runs three times
    and banks its fastest trial (per-trial numbers are kept for the
    variance-curious)."""
    import threading

    from banjax_tpu.fabric import wire as fwire
    from banjax_tpu.fabric.node import FabricNode
    from banjax_tpu.fabric.peer import LinePipe, PeerClient
    from banjax_tpu.fabric.stats import FabricStats

    lines = [
        f"{1000 + i * 0.001:.3f} 10.{(i >> 8) & 255}.{i & 255}.{i % 251} "
        f"GET fwd.example GET /a HTTP/1.1 x -"
        for i in range(n_lines)
    ]

    def _once():
        got = {"lines": 0, "frames": 0}
        lock = threading.Lock()

        def h_lines(payload):
            batch = payload.get("lines", [])
            with lock:
                got["lines"] += len(batch)
                got["frames"] += 1
            ack = {"n": len(batch)}
            if "seq" in payload:
                ack["seq"] = payload["seq"]
            return fwire.T_ACK, ack

        def h_lines_v2(fr):
            with lock:
                got["lines"] += len(fr.lines)
                got["frames"] += 1
            return fwire.T_ACK, {"seq": fr.seq, "n": len(fr.lines)}

        node = FabricNode("127.0.0.1", 0, handlers={
            fwire.T_LINES: h_lines, fwire.T_LINES_V2: h_lines_v2,
        }).start()
        stats = FabricStats()
        if transport == "json":
            client = PeerClient("b", "127.0.0.1", node.port)
            t0 = time.perf_counter()
            for ln in lines:
                client.request(fwire.T_LINES, {"lines": [ln]})
            dt = time.perf_counter() - t0
            client.close()
        else:
            pipe = LinePipe(
                "b", "127.0.0.1", node.port, node_id="a",
                shm=(transport == "shm"), stats=stats,
            )
            t0 = time.perf_counter()
            for ln in lines:
                pipe.submit([ln])
            assert pipe.flush(300.0), f"{transport}: flush did not drain"
            dt = time.perf_counter() - t0
            pipe.close()
        node.stop()
        assert got["lines"] == n_lines, (
            f"{transport}: {got['lines']} of {n_lines} lines crossed"
        )
        peek = stats.peek()
        return {
            "transport": transport,
            "lines": n_lines,
            "seconds": round(dt, 3),
            "lines_per_sec": round(n_lines / dt, 1),
            "frames": got["frames"],
            "lines_per_frame": round(n_lines / max(1, got["frames"]), 1),
            "frame_bytes_sent": peek.get("FabricFrameBytes", 0),
        }

    trials = [_once() for _ in range(3)]
    best = max(trials, key=lambda r: r["lines_per_sec"])
    best["trial_lines_per_sec"] = [r["lines_per_sec"] for r in trials]
    return best


def _fabric_mode() -> None:
    """`bench.py --fabric`: the multi-host decision fabric scaling run.

    One dryrun episode per shard count — N=1 (no kill: the single-shard
    baseline, every line local), N=2 and N=4 (one shard SIGKILLed
    mid-flood, consistent-hash takeover) — over the same seeded scenario
    stream, banking lines/s per N plus the takeover-window shed ratio
    (lines shed between the kill and the successors finishing the
    journal replay, over lines fed in that window).  Every row must hold
    recall 1.0 vs the oracle; the kill rows must also prove the takeover
    happened and duplicates were suppressed.

    Churn rows (`churn_n2`/`churn_n4`) run the gossip-membership episode
    on top: SIGKILL with the feed paused (detection is gossip's alone —
    the kill→confirmed-dead seconds per survivor are banked as the
    detection distribution), an automatic join with snapshot sync and no
    fleet restart, a slow-node suspect/refute cycle, and a graceful
    leave with zero shed / zero replay.  Knobs:
    Forward-path rows (ISSUE 18): `forward_path` pits the wire v2
    windowed transport (tcp + shm-ring arms) against the PR 11
    per-line sync-JSON wire on a pure forwarding workload, in-process
    (the v2 arm is gated at >= 10x the json arm); `forward_path_e2e`
    repeats the shape through real worker processes at chunk
    granularity, where the synchronous driver RTT — not the wire —
    bounds every arm (banked for honesty, see PERF.md round 17).
    Knobs: BENCH_FABRIC_{SHAPE,SEED,SCALE,NS,CHURN_NS,FWD_LINES},
    BENCH_CPU=1 (workers always pin the CPU backend themselves)."""
    from banjax_tpu.fabric.harness import run_fabric, run_forward_path

    shape = os.environ.get("BENCH_FABRIC_SHAPE", "flash_crowd")
    seed = int(os.environ.get("BENCH_FABRIC_SEED", "20260804"))
    scale = float(os.environ.get("BENCH_FABRIC_SCALE", "1.0"))
    ns = [
        int(n)
        for n in os.environ.get("BENCH_FABRIC_NS", "1,2,4").split(",")
    ]
    churn_ns = [
        int(n)
        for n in os.environ.get("BENCH_FABRIC_CHURN_NS", "2,4").split(",")
        if n.strip()
    ]

    rows = {}
    for n in ns:
        kill = n > 1
        report = run_fabric(
            n_workers=n, shape=shape, seed=seed, scale=scale, kill=kill,
        )
        bad = [k for k, ok in report["invariants"].items() if not ok]
        assert not bad, f"fabric invariants failed at n={n}: {bad}"
        takeover = report.get("takeover") or {}
        rows[f"n{n}"] = {
            "n_workers": n,
            "transport": report["transport"],
            "killed": report["killed"],
            "lines": report["n_lines"],
            "feed_s": report["feed_s"],
            "lines_per_sec": report["lines_per_sec"],
            "engine_bans": report["engine_bans"],
            "oracle_bans": report["oracle_bans"],
            "precision": report["precision"],
            "recall": report["recall"],
            "duplicates_suppressed": report["duplicates_suppressed"],
            "takeover_window_s": takeover.get("window_s"),
            "takeover_shed_ratio": takeover.get("shed_ratio_in_window"),
            "takeover_replayed_lines": (
                takeover.get("driver_replayed_lines")
            ),
        }
        if kill:
            # the n2 duplicate-ban regression gate: takeover replay
            # must never mint a ban the oracle doesn't have
            assert report["precision"] == 1.0, (
                f"n={n}: precision {report['precision']} != 1.0 "
                f"({report['engine_bans']} vs {report['oracle_bans']})"
            )
        print(json.dumps({"arm": f"n{n}", **rows[f"n{n}"]}), flush=True)

    for n in churn_ns:
        report = run_fabric(
            n_workers=n, shape=shape, seed=seed, scale=scale, churn=True,
        )
        bad = [k for k, ok in report["invariants"].items() if not ok]
        assert not bad, f"fabric churn invariants failed at n={n}: {bad}"
        takeover = report.get("takeover") or {}
        detect = takeover.get("detect_s") or {}
        rows[f"churn_n{n}"] = {
            "n_workers": n,
            "mode": "membership_churn",
            "killed": report["killed"],
            "recall": report["recall"],
            "precision": report["precision"],
            "detection_s": detect,
            "max_detection_s": takeover.get("max_detect_s"),
            "suspect_timeout_s": takeover.get("suspect_timeout_s"),
            "gossip_interval_s": takeover.get("gossip_interval_s"),
            "takeover_window_s": takeover.get("window_s"),
            "join_synced_decisions": report["join"]["synced_decisions"],
            "join_wave_exactly_once": (
                report["join"]["invariants"]["wave_exactly_once"]
            ),
            "refuted": report["suspect_refute"]["refuted_delta"],
            "leave_zero_shed": (
                report["leave"]["invariants"]["zero_shed"]
            ),
            "leave_zero_replay": (
                report["leave"]["invariants"]["zero_replay"]
            ),
            "leave_drain_ms": report["leave"]["drain_ms"],
        }
        assert report["precision"] == 1.0, (
            f"churn n={n}: precision {report['precision']} != 1.0"
        )
        print(json.dumps(
            {"arm": f"churn_n{n}", **rows[f"churn_n{n}"]}
        ), flush=True)

    # forwarding-plane micro: per-line submission, 100% remote lines
    fwd_lines = int(os.environ.get("BENCH_FABRIC_FWD_LINES", "50000"))
    fwd = {t: _forward_micro(t, fwd_lines) for t in ("json", "v2", "shm")}
    speedup = round(
        fwd["v2"]["lines_per_sec"] / fwd["json"]["lines_per_sec"], 1
    )
    rows["forward_path"] = {
        "mode": "in_process_per_line",
        "arms": fwd,
        "v2_over_json": speedup,
        "shm_over_json": round(
            fwd["shm"]["lines_per_sec"] / fwd["json"]["lines_per_sec"], 1
        ),
    }
    assert speedup >= 10.0, (
        f"forward_path: v2 {fwd['v2']['lines_per_sec']} l/s is only "
        f"{speedup}x the per-line JSON wire "
        f"({fwd['json']['lines_per_sec']} l/s); gate is 10x"
    )
    print(json.dumps({"arm": "forward_path", **rows["forward_path"]}),
          flush=True)

    # same shape end-to-end through real worker processes, chunked:
    # banked so nobody mistakes the micro for an e2e claim — at chunk
    # granularity the sync driver's RTT bounds all three arms alike
    e2e = {}
    for t in ("json", "v2", "shm"):
        r = run_forward_path(transport=t)
        assert all(r["invariants"].values()), f"forward e2e {t}: {r}"
        e2e[t] = {
            "lines_per_sec": r["lines_per_sec"],
            "n_lines": r["n_lines"],
            "chunk_lines": r["chunk_lines"],
            "peer_transport": r["peer_transport"],
            "frames_sent": r["frames_sent"],
        }
    rows["forward_path_e2e"] = {
        "mode": "worker_processes_chunked",
        "note": (
            "driver-RTT-bound: the synchronous chunk feed, not the "
            "wire, is the bottleneck at this granularity"
        ),
        "arms": e2e,
    }
    print(json.dumps({"arm": "forward_path_e2e", **rows["forward_path_e2e"]}),
          flush=True)

    kill_rows = [r for r in rows.values() if r.get("killed")]
    book = {
        "metric": (
            "decision fabric: lines/s vs shard count with one shard "
            "SIGKILLed mid-flood (N>1), recall gated at 1.0"
        ),
        "shape": shape,
        "seed": seed,
        "scale": scale,
        "measured_at": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        ),
        "rows": rows,
        "summary": {
            "recall_one_all_rows": all(
                r["recall"] == 1.0 for r in rows.values()
                if "recall" in r
            ),
            "forward_path_v2_over_json": rows["forward_path"][
                "v2_over_json"
            ],
            "max_takeover_shed_ratio": max(
                (r.get("takeover_shed_ratio") or 0.0) for r in kill_rows
            ) if kill_rows else None,
            "max_takeover_window_s": max(
                (r.get("takeover_window_s") or 0.0) for r in kill_rows
            ) if kill_rows else None,
            "max_gossip_detection_s": max(
                (r.get("max_detection_s") or 0.0) for r in kill_rows
            ) if kill_rows else None,
        },
    }
    tmp = FABRIC_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, FABRIC_PATH)
    print(json.dumps({"metric": book["metric"], **book["summary"]}))


FLEET_OBS_PATH = os.path.join(_DIR, "BENCH_fleet_obs.json")


def _fleet_obs_witness(tmp_dir: str) -> dict:
    """Non-vacuity witness for the fleet-obs rows: two real workers with
    trace propagation armed, a probe flood tailed at w0 whose IP hashes
    to w1, and the resulting ban's provenance on w1 joined back to w0's
    `fabric.route` admission span by origin trace id.  Returns the
    joined evidence; raises if the join never happens — an idle
    observability plane must not bank a vacuous "no overhead"."""
    from banjax_tpu.fabric import wire as fwire
    from banjax_tpu.fabric.harness import _fake_broker, _spawn
    from banjax_tpu.fabric.hashring import ConsistentHashRing
    from banjax_tpu.scenarios.shapes import T0

    ring = ConsistentHashRing(("w0", "w1"), vnodes=64)
    i = 0
    while True:
        ip = f"10.{(i >> 8) & 255}.{i & 255}.7"
        if ring.owner(ip) == "w1":
            break
        i += 1

    broker = _fake_broker()
    broker.start()
    workers = {}
    try:
        for wid in ("w0", "w1"):
            workers[wid] = _spawn(
                wid, broker.port, os.path.join(tmp_dir, f"{wid}.err"),
                extra_args=("--trace-propagation", "1"),
            )
        for w in workers.values():
            w.read_ready(420.0)
        hello = {
            "peers": {
                w.wid: ["127.0.0.1", w.port] for w in workers.values()
            },
            "vnodes": 64, "send_timeout_ms": 2000.0, "grace_ms": 200.0,
            "inflight_frames": 8, "wire_v2": True, "shm": False,
            "trace_propagation": True,
        }
        for w in workers.values():
            w.request(fwire.T_HELLO, hello)
        lines = [
            f"{T0 + j * 0.1:.6f} {ip} GET example.com GET "
            "/wp-login.php HTTP/1.1 scanner -"
            for j in range(20)
        ]
        workers["w0"].request(fwire.T_LINES, {"lines": lines, "route": True})
        for w in workers.values():
            w.request(fwire.T_FLUSH, {"timeout": 600})
        explain = {}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            explain = workers["w1"].request(fwire.T_EXPLAIN, {"ip": ip})
            if explain.get("records"):
                break
            time.sleep(0.25)
        recs = [
            r for r in explain.get("records", ())
            if r.get("origin_node") == "w0"
        ]
        assert recs, f"no forwarded-line ban recorded for {ip}: {explain}"
        origin_tid = recs[0]["origin_trace_id"]
        assert origin_tid > 0, recs[0]
        cap = workers["w0"].request(
            fwire.T_FLIGHTREC, {"incident": "bench-witness", "from": "b"}
        )
        route_tids = {
            e["args"]["trace_id"]
            for e in json.loads(cap["files"]["trace.json"])["traceEvents"]
            if e["name"] == "fabric.route"
        }
        assert origin_tid in route_tids, (origin_tid, route_tids)
        return {
            "banned_ip": ip,
            "origin_node": recs[0]["origin_node"],
            "origin_trace_id": origin_tid,
            "explain_joins_origin_trace": True,
            "decision": recs[0].get("decision"),
        }
    finally:
        for w in workers.values():
            try:
                w.shutdown()
            except Exception:  # noqa: BLE001 — teardown best-effort
                w.proc.kill()
        broker.stop()


def _fleet_obs_mode() -> None:
    """`bench.py --fleet-obs`: fleet observability overhead on the N=2
    fabric feed — the same off → on → off bracketing protocol as the
    other obs A/Bs, where "on" arms origin trace propagation on every
    forwarded frame plus the worker-side fleet surfaces
    (T_EXPLAIN / T_FLIGHTREC / T_STATS metrics).  Decisions must not
    change: the on-arm ban log is byte-compared against the off arm.
    The banked witness row proves the plane was live — a forwarded-line
    ban on w1 whose /decisions/explain provenance joins the origin
    trace id allocated at w0's admission.  Banked into
    BENCH_fleet_obs.json.  Knobs: BENCH_FABRIC_{SHAPE,SEED,SCALE}."""
    import tempfile

    from banjax_tpu.fabric.harness import run_fabric

    shape = os.environ.get("BENCH_FABRIC_SHAPE", "flash_crowd")
    seed = int(os.environ.get("BENCH_FABRIC_SEED", "20260804"))
    scale = float(os.environ.get("BENCH_FABRIC_SCALE", "1.0"))

    def run_arm(fleet_obs: bool) -> dict:
        report = run_fabric(
            n_workers=2, shape=shape, seed=seed, scale=scale,
            kill=False, fleet_obs=fleet_obs,
        )
        bad = [k for k, ok in report["invariants"].items() if not ok]
        assert not bad, f"fleet-obs arm invariants failed: {bad}"
        return report

    def row(report: dict, fleet_obs: bool) -> dict:
        return {
            "fleet_obs": fleet_obs,
            "lines_per_sec": report["lines_per_sec"],
            "lines": report["n_lines"],
            "feed_s": report["feed_s"],
            "engine_bans": report["engine_bans"],
            "oracle_bans": report["oracle_bans"],
            "precision": report["precision"],
            "recall": report["recall"],
        }

    def ban_log_bytes(report: dict) -> bytes:
        return ("\n".join(report["ban_log"]) + "\n").encode()

    off_a_rep = run_arm(False)
    on_rep = run_arm(True)
    off_b_rep = run_arm(False)
    assert ban_log_bytes(on_rep) == ban_log_bytes(off_a_rep), (
        "fleet-obs changed the ban log"
    )
    off_a, on, off_b = (
        row(off_a_rep, False), row(on_rep, True), row(off_b_rep, False)
    )
    off = max(off_a, off_b, key=lambda r: r["lines_per_sec"])
    noise_band_pct = round(
        abs(off_a["lines_per_sec"] - off_b["lines_per_sec"])
        / max(off_a["lines_per_sec"], off_b["lines_per_sec"]) * 100.0, 2
    )
    overhead_pct = round(
        (off["lines_per_sec"] - on["lines_per_sec"])
        / off["lines_per_sec"] * 100.0, 2
    )

    with tempfile.TemporaryDirectory() as td:
        witness = _fleet_obs_witness(td)

    book = {
        "metric": (
            "N=2 fabric feed lines/s, fleet observability off vs on "
            "(origin trace propagation + fleet surfaces)"
        ),
        "shape": shape,
        "seed": seed,
        "scale": scale,
        "measured_at": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        ),
        "off": off,
        "on": on,
        "off_runs": [off_a["lines_per_sec"], off_b["lines_per_sec"]],
        "on_vs_off_overhead_pct": overhead_pct,
        "off_run_noise_band_pct": noise_band_pct,
        "on_within_off_noise_band": bool(
            overhead_pct <= max(noise_band_pct, 1.0)
        ),
        "ban_log_byte_identical": True,
        "witness": witness,
    }
    tmp = FLEET_OBS_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, FLEET_OBS_PATH)
    print(json.dumps({
        "metric": book["metric"],
        "off_lines_per_sec": off["lines_per_sec"],
        "on_lines_per_sec": on["lines_per_sec"],
        "on_vs_off_overhead_pct": overhead_pct,
        "off_run_noise_band_pct": noise_band_pct,
        "on_within_off_noise_band": book["on_within_off_noise_band"],
        "witness": witness,
    }))


CHALLENGE_PATH = os.path.join(_DIR, "BENCH_challenge.json")


def _challenge_mode() -> None:
    """`bench.py --challenge`: the challenge plane A/B + storm row.

    Throughput: one pre-solved cookie population (every cookie passes
    the wire stage, so the arms diverge only at the PoW zero-bit count),
    verified once with the pure-CPU reference (`verify_sha_inv`,
    device=None) and once through the device-batched path (wire stage
    inline + `DeviceVerifier.verify_batch` kernel dispatches).  Accepts
    must agree lane for lane — the A/B is about speed, never decisions.

    Storm: >= 1M distinct cookieless challengers (each fails once —
    below any threshold, so the oracle bans NONE of them) interleaved
    with scripted repeat offenders who fail past the threshold inside
    one rate window.  Everything flows through the REAL
    send_or_validate_sha_challenge stage with the bounded failure state
    from the deploy config, so the banked row witnesses the ISSUE 17
    acceptance: entries <= challenge_failure_state_max under 1M+
    distinct clients AND ban precision/recall 1.0 vs the scripted
    oracle (bounded-state drops may delay nothing here — offender
    evidence rides the spill tier).
    """
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from banjax_tpu.challenge.failures import make_failed_challenge_states
    from banjax_tpu.challenge.verifier import DeviceVerifier, verify_sha_inv
    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.crypto.challenge import (
        new_challenge_cookie_at,
        parse_cookie,
        solve_challenge_for_testing,
        validate_expiration_and_hmac,
    )
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.model import FailAction
    from banjax_tpu.decisions.protected_paths import PasswordProtectedPaths
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.httpapi.decision_chain import (
        ChainState,
        RequestInfo,
        send_or_validate_sha_challenge,
    )
    from banjax_tpu.scenarios.runtime import RecordingBanner

    backend = jax.devices()[0].platform
    n_cookies = int(os.environ.get("BENCH_CHAL_COOKIES", "2048"))
    zero_bits = int(os.environ.get("BENCH_CHAL_ZERO_BITS", "8"))
    batch_max = int(os.environ.get("BENCH_CHAL_BATCH", "256"))
    n_distinct = int(os.environ.get("BENCH_CHAL_DISTINCT", "1000000"))
    n_offenders = int(os.environ.get("BENCH_CHAL_OFFENDERS", "64"))
    state_max = int(os.environ.get("BENCH_CHAL_STATE_MAX", "65536"))
    seed = int(os.environ.get("BENCH_CHAL_SEED", "20260804"))
    secret = f"bench-challenge-{seed}"

    # ---- throughput A/B: one solved population, two PoW paths ----
    now = int(time.time())
    cookies = []
    t0 = time.perf_counter()
    for k in range(n_cookies):
        ip = f"198.51.{(k >> 8) & 0xFF}.{k & 0xFF}"
        cookies.append((ip, solve_challenge_for_testing(
            new_challenge_cookie_at(secret, now + 3600, ip), zero_bits
        )))
    solve_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    cpu_accepts = 0
    for ip, cookie in cookies:
        verify_sha_inv(secret, cookie, now, ip, zero_bits, device=None)
        cpu_accepts += 1
    cpu_s = time.perf_counter() - t0

    from banjax_tpu.matcher.kernels.pow_verify import _default_interpret

    device = DeviceVerifier(batch_max=batch_max)
    assert device.available(), device.counters()["disabled_reason"]
    interpret = _default_interpret()
    t0 = time.perf_counter()
    payloads = []
    for ip, cookie in cookies:
        hmac_bytes, solution, expiry = parse_cookie(cookie)
        validate_expiration_and_hmac(secret, expiry, now, hmac_bytes, ip)
        payloads.append(hmac_bytes + solution)
    bits = device.verify_batch(payloads)
    device_s = time.perf_counter() - t0
    device_accepts = sum(1 for b in bits if b >= zero_bits)
    assert device_accepts == cpu_accepts == n_cookies, (
        device_accepts, cpu_accepts
    )
    dev_counters = device.counters()
    assert dev_counters["faults"] == 0, dev_counters

    verify_rows = {
        "cpu": {
            "cookies": n_cookies,
            "elapsed_s": round(cpu_s, 4),
            "cookies_per_sec": round(n_cookies / cpu_s, 1),
            "accepts": cpu_accepts,
        },
        "device": {
            "cookies": n_cookies,
            "elapsed_s": round(device_s, 4),
            "cookies_per_sec": round(n_cookies / device_s, 1),
            "accepts": device_accepts,
            "batch_max": batch_max,
            "kernel_dispatches": dev_counters["dispatches"],
            "lanes_verified": dev_counters["lanes_verified"],
            # interpret-mode rows (cpu backend) measure the kernel
            # EMULATOR, not device silicon — not a speedup claim
            "kernel_interpret_mode": interpret,
        },
    }
    print(json.dumps({"section": "verify_ab", "solve_s": round(solve_s, 2),
                      **verify_rows}), flush=True)

    # ---- storm row: >= 1M distinct challengers, bounded state ----
    cfg = config_from_yaml_text(f"""
regexes_with_rates: []
too_many_failed_challenges_interval_seconds: 3600
too_many_failed_challenges_threshold: 3
sha_inv_cookie_ttl_seconds: 300
sha_inv_expected_zero_bits: {zero_bits}
hmac_secret: {secret}
disable_kafka: true
challenge_failure_state_max: {state_max}
""")
    threshold = cfg.too_many_failed_challenges_threshold
    banner = RecordingBanner()
    dyn = DynamicDecisionLists(start_sweeper=False)
    chain = ChainState(
        config=cfg,
        static_lists=StaticDecisionLists(cfg),
        dynamic_lists=dyn,
        protected_paths=PasswordProtectedPaths(cfg),
        failed_challenge_states=make_failed_challenge_states(cfg),
        banner=banner,
        challenge_verifier=None,  # cookieless storm never reaches PoW
    )
    offender_ips = [
        f"203.0.{k >> 8}.{k & 0xFF}" for k in range(n_offenders)
    ]
    # each offender fails threshold+1 times, round-robin through live
    # eviction churn.  An offender re-fails every n_offenders * stride
    # churners; keeping that gap under the LRU cap is the precision-
    # safety shape (a retrying bot re-touches its window entry before
    # cap-many distinct clients push it out), so the oracle comparison
    # stays exact while eviction pressure runs the whole time.
    offender_stream = offender_ips * (threshold + 1)
    stride = max(1, state_max // (2 * n_offenders))

    t0 = time.perf_counter()
    n_requests = 0
    oi = 0
    for i in range(n_distinct):
        req = RequestInfo(
            client_ip=f"10.{(i >> 16) & 0xFF}.{(i >> 8) & 0xFF}.{i & 0xFF}",
            requested_host="bench.example", requested_path="/",
            cookies={},
        )
        send_or_validate_sha_challenge(chain, req, FailAction.BLOCK)
        n_requests += 1
        if i % stride == stride - 1 and oi < len(offender_stream):
            req = RequestInfo(
                client_ip=offender_stream[oi], requested_host="bench.example",
                requested_path="/", cookies={},
            )
            send_or_validate_sha_challenge(chain, req, FailAction.BLOCK)
            n_requests += 1
            oi += 1
    while oi < len(offender_stream):  # drain any tail offender failures
        req = RequestInfo(
            client_ip=offender_stream[oi], requested_host="bench.example",
            requested_path="/", cookies={},
        )
        send_or_validate_sha_challenge(chain, req, FailAction.BLOCK)
        n_requests += 1
        oi += 1
    storm_s = time.perf_counter() - t0

    banned = {ip for ip, _ in banner.failed_challenge_ban_logs}
    oracle = set(offender_ips)
    precision = (len(banned & oracle) / len(banned)) if banned else 1.0
    recall = (len(banned & oracle) / len(oracle)) if oracle else 1.0
    state_counters = chain.failed_challenge_states.counters()
    storm_row = {
        "distinct_challengers": n_distinct + n_offenders,
        "requests": n_requests,
        "elapsed_s": round(storm_s, 3),
        "requests_per_sec": round(n_requests / storm_s, 1),
        "offenders": n_offenders,
        "banned": len(banned),
        "ban_precision": precision,
        "ban_recall": recall,
        "failure_state_entries": state_counters["entries"],
        "failure_state_max": state_max,
        "evictions_total": state_counters["evictions_total"],
        "spill_writes": state_counters["spill_writes"],
        "spill_refills": state_counters["spill_refills"],
        "gate_skips": state_counters["gate_skips"],
    }
    print(json.dumps({"section": "challenge_storm", **storm_row}),
          flush=True)

    book = {
        "metric": (
            "challenge plane: PoW verify CPU vs device A/B + "
            f"{n_distinct + n_offenders}-distinct challenger storm"
        ),
        "backend": backend,
        "seed": seed,
        "zero_bits": zero_bits,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": {"verify": verify_rows, "challenge_storm": storm_row},
        "summary": {
            "cpu_cookies_per_sec": verify_rows["cpu"]["cookies_per_sec"],
            "device_cookies_per_sec": (
                verify_rows["device"]["cookies_per_sec"]
            ),
            "speedup_device_vs_cpu": round(
                verify_rows["device"]["cookies_per_sec"]
                / verify_rows["cpu"]["cookies_per_sec"], 4
            ),
            "acceptance_accepts_identical": device_accepts == cpu_accepts,
            "acceptance_state_bounded": (
                storm_row["failure_state_entries"] <= state_max
            ),
            "acceptance_ban_parity": precision == 1.0 and recall == 1.0,
            "acceptance_distinct_1m": (
                storm_row["distinct_challengers"] >= 1_000_000
            ),
        },
    }
    tmp = CHALLENGE_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, CHALLENGE_PATH)
    print(json.dumps({"metric": book["metric"], **book["summary"]}))


SERVE_PATH = os.path.join(_DIR, "BENCH_serve.json")


def _serve_mode() -> None:
    """`bench.py --serve`: the compiled /auth_request serving path.

    Three sections banked into BENCH_serve.json:

      decision_stage — the per-request serving cost in process: the
      userspace nine-step chain (decision_for_nginx + the decision-log
      serialization + serialize_response, exactly what
      fastserve._auth_request runs) vs the compiled fast path
      (AuthFastPath.try_serve: one shm decision-table probe, one
      session HMAC, a template splice) over the identical
      already-decided workload.  The ISSUE 19 acceptance gate lives
      here: fast path >= 5x the chain's requests/sec.

      witness — decision identity over a mixed allow / block /
      challenge / expiring workload, including live expiry-boundary
      crossings: every fast-path response must byte-equal the chain's
      for the same request (minted session cookies and challenge
      payloads normalized — both sides draw fresh randomness);
      `mismatches` must be 0.

      http_capacity — the end-to-end number: the REAL standalone server
      on 127.0.0.1:8081 (BanjaxApp, fastserve layout) driven by a
      concurrent raw-socket keepalive client over the same workload
      mix, chain-only config vs fast-path config — rps, per-request
      p50/p99, and the per-tier hit / per-reason miss counters from
      httpapi/serve_stats on the fast-path arm.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import asyncio
    import re
    import shutil
    import tempfile
    import types

    from banjax_tpu.config.holder import _PAGES_DIR
    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.crypto.session import new_session_cookie
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.model import Decision
    from banjax_tpu.decisions.protected_paths import PasswordProtectedPaths
    from banjax_tpu.decisions.rate_limit import FailedChallengeRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.httpapi.decision_chain import (
        ChainState,
        DecisionListResult,
        RequestInfo,
        decision_for_nginx,
    )
    from banjax_tpu.httpapi.fastpath import AuthFastPath
    from banjax_tpu.httpapi.fastserve import serialize_response
    from banjax_tpu.httpapi.serve_stats import get_stats
    from banjax_tpu.native.decisiontable import available, create_decision_table
    from banjax_tpu.scenarios.runtime import RecordingBanner
    from banjax_tpu.utils import go_query_escape, go_query_unescape

    seed = int(os.environ.get("BENCH_SERVE_SEED", "20260807"))
    iters = int(os.environ.get("BENCH_SERVE_ITERS", "20000"))
    witness_n = int(os.environ.get("BENCH_SERVE_WITNESS", "400"))
    n_per_conn = int(os.environ.get("BENCH_SERVE_NPC", "300"))
    conc = int(os.environ.get("BENCH_SERVE_CONC", "16"))
    table_cap = int(os.environ.get("BENCH_SERVE_TABLE_CAP", "65536"))
    rng = random.Random(seed)
    session_secret = "bench-serve-session-secret"

    cfg = config_from_yaml_text(f"""
config_version: bench-serve-1
global_decision_lists:
  allow:
    - 20.20.20.20
iptables_ban_seconds: 10
kafka_brokers: [localhost:9092]
server_log_file: /tmp/banjax-bench-serve.log
expiring_decision_ttl_seconds: 300
too_many_failed_challenges_interval_seconds: 60
too_many_failed_challenges_threshold: 1000000
password_cookie_ttl_seconds: 14400
sha_inv_cookie_ttl_seconds: 14400
sha_inv_expected_zero_bits: 10
hmac_secret: bench-serve-hmac
session_cookie_hmac_secret: {session_secret}
session_cookie_ttl_seconds: 3600
disable_kafka: true
""")
    cfg.challenger_bytes = (
        _PAGES_DIR / "sha-inverse-challenge.html").read_bytes()

    dyn = DynamicDecisionLists(start_sweeper=False)
    table = create_decision_table(capacity=table_cap)
    dyn.set_mirror(table)

    class _Holder:
        def get(self):
            return cfg

    deps = types.SimpleNamespace(
        config_holder=_Holder(),
        static_lists=StaticDecisionLists(cfg),
        dynamic_lists=dyn,
        protected_paths=PasswordProtectedPaths(cfg),
        failed_challenge_states=FailedChallengeRateLimitStates(),
        banner=RecordingBanner(),
        challenge_verifier=None,
        decision_table=table,
    )
    fp = AuthFastPath(deps)
    chain_state = ChainState(
        config=cfg, static_lists=deps.static_lists, dynamic_lists=dyn,
        protected_paths=deps.protected_paths,
        failed_challenge_states=deps.failed_challenge_states,
        banner=deps.banner, challenge_verifier=None,
    )

    class _Req:
        __slots__ = ("headers", "method", "keep_alive")

        def __init__(self, headers, method="GET"):
            self.headers = headers
            self.method = method
            self.keep_alive = True

        def header(self, name):
            return self.headers.get(name, "")

    def chain_serve(req):
        """What fastserve._auth_request runs for /auth_request: cookie
        parse, RequestInfo, the nine-step chain, the decision-log
        serialization, wire serialization."""
        cookies = {}
        raw = req.headers.get("cookie", "")
        if raw:
            for part in raw.split(";"):
                name, eq, value = part.strip().partition("=")
                if not eq:
                    continue
                try:
                    cookies[name] = go_query_unescape(value)
                except ValueError:
                    continue
        info = RequestInfo(
            client_ip=req.headers.get("x-client-ip", ""),
            requested_host=req.headers.get("x-requested-host", ""),
            requested_path=req.headers.get("x-requested-path", ""),
            client_user_agent=req.headers.get("x-client-user-agent", ""),
            method=req.method,
            cookies=cookies,
        )
        resp, result = decision_for_nginx(chain_state, info)
        if result.decision_list_result != DecisionListResult.NO_MENTION:
            result.to_json()  # the decision-log line fastserve emits
        return serialize_response(resp, req.keep_alive,
                                  head_only=req.method == "HEAD")

    def _hdrs(ip, host="bench.example.net", **extra):
        h = {
            "x-client-ip": ip, "x-requested-host": host,
            "x-requested-path": "/", "x-client-user-agent": "mozilla",
        }
        h.update(extra)
        return h

    def _clean_cookie(ip, secret=session_secret, ttl=3600):
        # base64 cookies can carry '+', which QueryUnescape turns into
        # a space on the echo path (both layouts share the mangle);
        # draw until clean so echoed bytes are deterministic
        while True:
            c = new_session_cookie(secret, ttl, ip)
            if "+" not in c and "%" not in c:
                return c

    # ---- seed the decided population (the mirror fills the table) ----
    now = time.time()
    allow_ips = [f"10.1.{k >> 8}.{k & 0xFF}" for k in range(256)]
    block_ips = [f"10.2.0.{k}" for k in range(64)]
    for ip in allow_ips:
        dyn.update(ip, now + 3600, Decision.ALLOW, False, "bench")
    for ip in block_ips:
        dyn.update(ip, now + 3600, Decision.NGINX_BLOCK, False, "bench")

    # ---- decision_stage A/B: identical ring through both arms ----
    ring = []
    for k in range(512):
        ip = allow_ips[k % 256] if k % 4 else block_ips[(k // 4) % 64]
        ring.append(_Req(_hdrs(
            ip, cookie=f"deflect_session={go_query_escape(_clean_cookie(ip))}"
        )))
    for req in ring[:64]:  # warm both arms
        assert fp.try_serve(req) is not None, "fast path must hit the ring"
        chain_serve(req)
    t0 = time.perf_counter()
    for i in range(iters):
        chain_serve(ring[i % 512])
    chain_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(iters):
        fp.try_serve(ring[i % 512])
    fast_s = time.perf_counter() - t0
    decision_row = {
        "iters": iters,
        "chain_rps": round(iters / chain_s, 1),
        "fastpath_rps": round(iters / fast_s, 1),
        "speedup": round(chain_s / fast_s, 2),
        "native_table": available(),
    }
    print(json.dumps({"section": "decision_stage", **decision_row}),
          flush=True)

    # ---- witness: byte identity over the mixed workload ----
    # minted session cookies and challenge payloads are fresh randomness
    # on BOTH sides; mask exactly those spans before comparing
    _cpat = re.compile(
        rb"(deflect_challenge3=)([^;]+)|(X-Deflect-Session: )(\S+)"
        rb"|(deflect_session=)([^;]+)"
    )

    def _norm(b):
        return _cpat.sub(
            lambda m: (m.group(1) or m.group(3) or m.group(5)) + b"<X>", b)

    mismatches = 0
    witness_requests = 0

    def _compare(headers, normalize=False, expect_hit=None):
        nonlocal mismatches, witness_requests
        witness_requests += 1
        fast = fp.try_serve(_Req(dict(headers)))   # prod order: fast first,
        cb = chain_serve(_Req(dict(headers)))      # chain lazy-expires after
        if fast is None:
            if expect_hit:
                mismatches += 1
            return None
        a, b = (_norm(fast[0]), _norm(cb)) if normalize else (fast[0], cb)
        if a != b:
            mismatches += 1
        return fast

    tiers = {"allow": 0, "block": 0, "challenge": 0, "expired": 0, "miss": 0}
    expired_ips = [f"10.5.0.{k}" for k in range(8)]
    for ip in expired_ips:
        dyn.update(ip, now - 1.0, Decision.NGINX_BLOCK, False, "bench")
    chal_n = 0
    for _ in range(witness_n):
        p = rng.random()
        if p < 0.40:
            ip = rng.choice(allow_ips)
            _compare(_hdrs(ip, cookie=(
                f"deflect_session={go_query_escape(_clean_cookie(ip))}"
            )), expect_hit=True)
            tiers["allow"] += 1
        elif p < 0.55:
            _compare(_hdrs(rng.choice(allow_ips)), normalize=True,
                     expect_hit=True)  # cookieless: both arms mint
            tiers["allow"] += 1
        elif p < 0.72:
            ip = rng.choice(block_ips)
            _compare(_hdrs(ip, cookie=(
                f"deflect_session={go_query_escape(_clean_cookie(ip))}"
            )), expect_hit=True)
            tiers["block"] += 1
        elif p < 0.82:
            ip = f"10.3.{chal_n >> 8}.{chal_n & 0xFF}"
            chal_n += 1
            dyn.update(ip, now + 3600, Decision.CHALLENGE, False, "bench")
            _compare(_hdrs(ip), normalize=True, expect_hit=True)
            tiers["challenge"] += 1
        elif p < 0.92:
            _compare(_hdrs(rng.choice(expired_ips)), normalize=True)
            tiers["expired"] += 1
        else:
            _compare(_hdrs(
                f"172.16.{rng.randint(0, 255)}.{rng.randint(1, 254)}"
            ), normalize=True)
            tiers["miss"] += 1

    # live expiry-boundary crossing: entries expire mid-sweep; every
    # sample must agree (hit -> identical bytes, then both flip to the
    # post-expiry decision)
    boundary_ips = [f"10.4.0.{k}" for k in range(4)]
    flip_at = time.time() + 1.0
    for ip in boundary_ips:
        dyn.update(ip, flip_at, Decision.ALLOW, False, "bench")
    boundary_samples = 0
    boundary_flips = 0
    was_hit = dict.fromkeys(boundary_ips)
    while time.time() < flip_at + 0.4:
        for ip in boundary_ips:
            fast = _compare(_hdrs(ip), normalize=True)
            hit = fast is not None
            if was_hit[ip] and not hit:
                boundary_flips += 1
            was_hit[ip] = hit
            boundary_samples += 1
        time.sleep(0.03)

    witness_row = {
        "requests": witness_requests,
        "mismatches": mismatches,
        "tiers": tiers,
        "boundary_samples": boundary_samples,
        "boundary_flips": boundary_flips,
        "fastpath_counters": get_stats().prom_snapshot(),
    }
    print(json.dumps({"section": "witness", **witness_row}), flush=True)

    get_stats().reset()
    dyn.close()
    table.close()
    if hasattr(table, "unlink"):
        table.unlink()

    # ---- http_capacity: the real server, chain-only vs fast path ----
    fixture = os.path.join(_DIR, "tests", "fixtures",
                           "banjax-config-test.yaml")
    with open(fixture) as f:
        base_yaml = f.read()

    def _http_arm(enabled):
        from banjax_tpu.cli import BanjaxApp

        tmp_dir = tempfile.mkdtemp(prefix="bench-serve-")
        cwd = os.getcwd()
        os.chdir(tmp_dir)
        cfg_path = os.path.join(tmp_dir, "banjax-config.yaml")
        with open(cfg_path, "w") as f:
            f.write(base_yaml + "\nserve_fastpath_enabled: "
                    + ("true" if enabled else "false") + "\n")
        get_stats().reset()
        app = BanjaxApp(cfg_path, standalone_testing=True, debug=False)
        app.start_background()
        try:
            now2 = time.time()
            h_allow = [f"10.11.{k >> 8}.{k & 0xFF}" for k in range(64)]
            h_block = [f"10.12.0.{k}" for k in range(16)]
            h_chal = [f"10.13.0.{k}" for k in range(4)]
            h_expired = [f"10.14.0.{k}" for k in range(8)]
            for ip in h_allow:
                app.dynamic_lists.update(ip, now2 + 3600, Decision.ALLOW,
                                         False, "bench")
            for ip in h_block:
                app.dynamic_lists.update(ip, now2 + 3600,
                                         Decision.NGINX_BLOCK, False, "bench")
            for ip in h_chal:
                app.dynamic_lists.update(ip, now2 + 3600, Decision.CHALLENGE,
                                         False, "bench")
            for ip in h_expired:
                app.dynamic_lists.update(ip, now2 - 1.0, Decision.ALLOW,
                                         False, "bench")

            def _raw(ip, cookie=None):
                lines = [
                    "GET /auth_request?path=%2F HTTP/1.1",
                    "Host: bench.example.net",
                    f"X-Client-IP: {ip}",
                ]
                if cookie is not None:
                    lines.append(
                        f"Cookie: deflect_session={go_query_escape(cookie)}")
                lines.append("Connection: keep-alive")
                return ("\r\n".join(lines) + "\r\n\r\n").encode()

            arm_rng = random.Random(seed + 1)  # same workload both arms
            reqs = []
            for _ in range(1024):
                p = arm_rng.random()
                if p < 0.70:
                    ip = arm_rng.choice(h_allow)
                    reqs.append(_raw(ip, _clean_cookie(ip, "session_secret")))
                elif p < 0.78:
                    reqs.append(_raw(arm_rng.choice(h_allow)))
                elif p < 0.88:
                    ip = arm_rng.choice(h_block)
                    reqs.append(_raw(ip, _clean_cookie(ip, "session_secret")))
                elif p < 0.92:
                    reqs.append(_raw(arm_rng.choice(h_expired)))
                elif p < 0.97:
                    reqs.append(_raw(
                        f"172.17.{arm_rng.randint(0, 255)}"
                        f".{arm_rng.randint(1, 254)}"))
                else:
                    reqs.append(_raw(arm_rng.choice(h_chal)))

            async def _worker(items, lats):
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", 8081)
                for raw in items:
                    t_req = time.perf_counter()
                    writer.write(raw)
                    await writer.drain()
                    hdr = await reader.readuntil(b"\r\n\r\n")
                    clen = 0
                    for line in hdr.split(b"\r\n"):
                        if line.lower().startswith(b"content-length:"):
                            clen = int(line.split(b":")[1])
                    if clen:
                        await reader.readexactly(clen)
                    lats.append(time.perf_counter() - t_req)
                writer.close()

            async def _drive(n_each):
                lats = []
                t_run = time.perf_counter()
                await asyncio.gather(*[
                    _worker([reqs[(w * 131 + i) % 1024]
                             for i in range(n_each)], lats)
                    for w in range(conc)
                ])
                return lats, time.perf_counter() - t_run

            asyncio.run(_drive(40))  # warm
            get_stats().reset()
            if getattr(app, "decision_table", None) is not None:
                get_stats().set_table(app.decision_table)
            lats, elapsed = asyncio.run(_drive(n_per_conn))
            lats.sort()
            row = {
                "requests": len(lats),
                "rps": round(len(lats) / elapsed, 1),
                "p50_us": round(lats[len(lats) // 2] * 1e6, 1),
                "p99_us": round(lats[min(len(lats) - 1,
                                         int(len(lats) * 0.99))] * 1e6, 1),
                "conc": conc,
                "n_per_conn": n_per_conn,
                "fastpath_enabled": enabled,
            }
            if enabled:
                row["fastpath_counters"] = get_stats().prom_snapshot()
            return row
        finally:
            app.stop_background()
            os.chdir(cwd)
            shutil.rmtree(tmp_dir, ignore_errors=True)

    row_chain = _http_arm(False)
    print(json.dumps({"section": "http_chain_only", **row_chain}), flush=True)
    row_fast = _http_arm(True)
    print(json.dumps({"section": "http_fastpath", **row_fast}), flush=True)

    book = {
        "metric": ("compiled /auth_request fast path vs userspace chain "
                   "(shm decision table + byte templates)"),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "seed": seed,
        "rows": {
            "decision_stage": decision_row,
            "witness": witness_row,
            "http_capacity": {
                "chain_only": row_chain,
                "fastpath": row_fast,
                "speedup": round(row_fast["rps"] / row_chain["rps"], 3),
            },
        },
        "summary": {
            "chain_rps": decision_row["chain_rps"],
            "fastpath_rps": decision_row["fastpath_rps"],
            "speedup_fastpath_vs_chain": decision_row["speedup"],
            "witness_requests": witness_requests,
            "witness_mismatches": mismatches,
            "http_rps_chain_only": row_chain["rps"],
            "http_rps_fastpath": row_fast["rps"],
            "acceptance_speedup_5x": decision_row["speedup"] >= 5.0,
            "acceptance_witness_clean": mismatches == 0,
        },
    }
    tmp_path = SERVE_PATH + ".tmp"
    with open(tmp_path, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp_path, SERVE_PATH)
    print(json.dumps({"metric": book["metric"], **book["summary"]}))


def _single_kernel_mode() -> None:
    """`bench.py --single-kernel`: the streaming pipeline + device
    windows with the single-kernel fused program ON (one dispatch, one
    pull, no program-B turn) vs OFF (the two-program A/B path with its
    depth-2 resolve-ahead), same chunk stream.  Banks both rows into
    BENCH_single_kernel.json with the acceptance witnesses: lines/s (the
    on-row must match or beat the banked --fused-pipeline row), d2h
    bytes/batch (one combined buffer vs A+B pulls), and the resolve-pull
    elimination — the off-row's DrainResolveOverlapMs is the decode+
    replay wall the two-program drain hides behind program B; the on-row
    has no B left to hide behind, so the metric stays unset (≈ 0)."""
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import yaml as _yaml

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from banjax_tpu.pipeline import PipelineScheduler
    from tests.mock_banner import MockBanner

    backend = jax.devices()[0].platform
    n_rules = int(os.environ.get("BENCH_STREAM_RULES", str(N_RULES)))
    total = int(os.environ.get(
        "BENCH_STREAM_LINES", "131072" if backend == "tpu" else "16384"
    ))
    feed_chunk = int(os.environ.get("BENCH_STREAM_CHUNK", "256"))
    budget_ms = float(os.environ.get("BENCH_STREAM_BUDGET_MS", "180"))

    patterns = generate_rules(n_rules)
    rules_yaml = _yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"crs{i}", "regex": p, "interval": 60,
             "hits_per_interval": 50, "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })
    now = time.time()
    rests = generate_lines(total, patterns, seed=47)
    lines = [
        f"{now:.6f} 10.7.{(i % 2048) >> 8}.{i % 256} {r}"
        for i, r in enumerate(rests)
    ]
    chunks = [lines[i : i + feed_chunk] for i in range(0, total, feed_chunk)]

    rows = {}
    for label, mode in (("single_kernel", "on"), ("two_program", "off")):
        cfg = config_from_yaml_text(rules_yaml)
        cfg.matcher_device_windows = True
        cfg.pallas_single_kernel = mode
        matcher = TpuMatcher(
            cfg, MockBanner(), StaticDecisionLists(cfg),
            RegexRateLimitStates(),
        )
        fw = matcher._fw_pipeline
        assert fw is not None, "fused matcher+windows pipeline missing"
        assert fw.single_kernel == (mode == "on"), (
            f"pallas_single_kernel={mode} did not resolve as requested "
            "(Pallas window-scan unavailable on this backend?)"
        )
        sched = PipelineScheduler(
            lambda: matcher, latency_budget_ms=budget_ms,
            buffer_lines=max(131072, total), now_fn=lambda: now,
        )
        sched.start()
        # warm until compiles AND the adaptive sizer settle: the
        # single-kernel program compiles one bigger variant per (rows,
        # line-len) bucket than A/B, and a first-visit compile poisons
        # the sizer's per-line record for that bucket until its decay
        # retry (sizer._RETRY_BLOCKED) — one warm pass would bank the
        # convergence transient, not steady state.  Fixed pass count
        # (non-adaptive: the transient plateaus, so a rate-delta exit
        # fires early); both rows use the identical protocol.
        warm_passes = int(os.environ.get("BENCH_SK_WARM_PASSES", "6"))
        for _ in range(max(1, warm_passes)):
            for c in chunks:
                sched.submit(c)
            assert sched.flush(600), f"{label} warm pass did not drain"
        # several timed passes, best banked: on the 1-core build box the
        # adaptive sizer's trajectory wobbles batch sizes between passes
        # (PERF round 9 measured 6.6% run-to-run spread on this exact
        # workload) — the best pass is the steady-state estimate, the
        # full list is kept for the spread
        timed_passes = int(os.environ.get("BENCH_SK_TIMED_PASSES", "3"))
        pass_rates = []
        h2d0 = matcher.stats.h2d_bytes_total
        d2h0 = matcher.stats.d2h_bytes_total
        batches0 = matcher.stats.batches_total
        elapsed_total = 0.0
        for _ in range(max(1, timed_passes)):
            t0 = time.perf_counter()
            for c in chunks:
                sched.submit(c)
            assert sched.flush(600), f"{label} timed pass did not drain"
            dt = time.perf_counter() - t0
            elapsed_total += dt
            pass_rates.append(round(total / dt, 1))
        snap = sched.snapshot()
        sched.stop()
        overlap = matcher.drain_resolve_overlap_ms_ewma
        rows[label] = {
            "mode": f"pipeline+device_windows ({label})",
            "backend": backend,
            "value": max(pass_rates),
            "unit": "lines/sec",
            "vs_baseline": round(max(pass_rates) / TARGET, 4),
            "pass_rates": pass_rates,
            "elapsed_s": round(elapsed_total, 2),
            "n_rules": n_rules,
            "n_lines": total,
            "h2d_bytes_per_batch": round(
                (matcher.stats.h2d_bytes_total - h2d0)
                / max(1, matcher.stats.batches_total - batches0), 1
            ),
            "d2h_bytes_per_batch": round(
                (matcher.stats.d2h_bytes_total - d2h0)
                / max(1, matcher.stats.batches_total - batches0), 1
            ),
            "pipelined_fused_chunks": matcher.pipelined_fused_chunks,
            "pipelined_fused_fallbacks": matcher.pipelined_fused_fallbacks,
            "single_kernel_chunks": fw.sk_chunks,
            "single_kernel_fallbacks": fw.sk_fallbacks,
            "drain_resolve_overlap_ms": (
                None if overlap is None else round(overlap, 3)
            ),
            "pipeline_batches": snap.get("PipelineBatches"),
            "pipeline_shed_lines": snap.get("PipelineShedLines"),
        }

    banked_fused = None
    try:
        with open(FUSED_STREAM_PATH) as f:
            banked_fused = json.load(f).get("fused", {}).get("value")
    except (OSError, ValueError):
        pass
    on, off = rows["single_kernel"], rows["two_program"]
    book = {
        "metric": "log-lines/sec, streaming pipeline + device windows "
                  "(single-kernel fused program vs two-program A/B)",
        "single_kernel": on,
        "two_program": off,
        "single_vs_two_program_speedup": round(
            on["value"] / max(1.0, off["value"]), 3
        ),
        # the resolve-pull witness: the off row's drain hides this many
        # ms of decode+replay behind program B per chunk; the on row has
        # no B dispatch — the pull is GONE from the drain critical path,
        # not overlapped (DrainResolveOverlapMs ≈ 0 / unset)
        "resolve_pull_ms_eliminated": off["drain_resolve_overlap_ms"],
        "resolve_pull_removed": on["drain_resolve_overlap_ms"] in (None, 0),
        # acceptance vs the banked --fused-pipeline row (same stream
        # shape): >= 1.0 means the single-kernel row matches or beats it
        "banked_fused_pipeline_lines_per_sec": banked_fused,
        "vs_banked_fused_pipeline": (
            None if not banked_fused
            else round(on["value"] / banked_fused, 3)
        ),
    }
    tmp = SINGLE_KERNEL_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, SINGLE_KERNEL_PATH)
    print(json.dumps(book))


def _stream_mode(mode: str) -> None:
    """End-to-end throughput of the tailer→matcher path under a
    tailer-shaped feed.

    `feed_chunk_lines` models ARRIVAL granularity: the reference consumes
    per line (regex_rate_limiter.go:58-76); a poll-based tailer keeping up
    with its stream delivers small reads (default 16 lines — one 50 ms
    poll at moderate rate).  The two modes consume the identical chunk
    stream:

    --sync     : the pre-pipeline behavior — one synchronous
                 consume_lines call per arriving chunk, so batch size is
                 COUPLED to arrival granularity and every chunk pays the
                 full submit→wait→collect fixed cost.
    --pipeline : the same chunks through banjax_tpu/pipeline/ — the
                 scheduler coalesces arrivals into adaptive batches
                 (decoupling batch size from arrival granularity, the
                 continuous-batching move) and overlaps
                 encode/device/drain across its stage threads.

    Emits one JSON line in the BENCH_r0x schema and merges the row into
    BENCH_pipeline.json (plus the pipeline/sync speedup once both modes
    have run on the same backend).
    """
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import yaml as _yaml

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from banjax_tpu.pipeline import PipelineScheduler
    from tests.mock_banner import MockBanner

    backend = jax.devices()[0].platform
    n_rules = int(os.environ.get("BENCH_STREAM_RULES", str(N_RULES)))
    total = int(os.environ.get(
        "BENCH_STREAM_LINES", "131072" if backend == "tpu" else "32768"
    ))
    feed_chunk = int(os.environ.get("BENCH_STREAM_CHUNK", "16"))
    budget_ms = float(os.environ.get("BENCH_STREAM_BUDGET_MS", "180"))

    patterns = generate_rules(n_rules)
    rules_yaml = _yaml.safe_dump({
        "regexes_with_rates": [
            {"rule": f"crs{i}", "regex": p, "interval": 60,
             "hits_per_interval": 50, "decision": "nginx_block"}
            for i, p in enumerate(patterns)
        ]
    })
    cfg = config_from_yaml_text(rules_yaml)
    # BENCH_STREAM_DEVICE_WINDOWS=1: run the stream against the
    # device-resident window counters — with --pipeline this drives the
    # fused two-phase path (see --fused-pipeline for the full A/B)
    device_windows = bool(os.environ.get("BENCH_STREAM_DEVICE_WINDOWS"))
    cfg.matcher_device_windows = device_windows
    banner = MockBanner()
    matcher = TpuMatcher(
        cfg, banner, StaticDecisionLists(cfg), RegexRateLimitStates()
    )
    now = time.time()
    rests = generate_lines(total, patterns, seed=43)
    lines = [
        f"{now:.6f} 10.8.{(i % 2048) >> 8}.{i % 256} {r}"
        for i, r in enumerate(rests)
    ]
    chunks = [lines[i : i + feed_chunk] for i in range(0, total, feed_chunk)]

    out = {
        "metric": f"log-lines/sec end-to-end, tailer-shaped feed ({mode})",
        "unit": "lines/sec",
        "mode": mode,
        "backend": backend,
        "n_rules": n_rules,
        "n_lines": total,
        "feed_chunk_lines": feed_chunk,
        "latency_budget_ms": budget_ms,
    }
    if mode == "sync":
        matcher.consume_lines(chunks[0], now)  # warm compile at the chunk bucket
        t0 = time.perf_counter()
        for c in chunks:
            matcher.consume_lines(c, now)
        elapsed = time.perf_counter() - t0
    else:
        sched = PipelineScheduler(
            lambda: matcher, latency_budget_ms=budget_ms,
            buffer_lines=max(131072, total), now_fn=lambda: now,
        )
        sched.start()
        # warm pass: compiles every bucket the sizer will settle through,
        # so the timed pass measures steady state, not Mosaic/XLA compiles
        for c in chunks:
            sched.submit(c)
        assert sched.flush(600), "pipeline warm pass did not drain"
        t0 = time.perf_counter()
        for c in chunks:
            sched.submit(c)
        assert sched.flush(600), "pipeline timed pass did not drain"
        elapsed = time.perf_counter() - t0
        snap = sched.snapshot()
        sched.stop()
        out["pipeline_batch_target"] = snap.get("PipelineBatchTarget")
        out["pipeline_batches"] = snap.get("PipelineBatches")
        out["pipeline_shed_lines"] = snap.get("PipelineShedLines")
        out["pipeline_stale_dropped"] = snap.get("PipelineStaleDroppedLines")
        out["pipeline_device_p99_ms"] = snap.get("PipelineDeviceP99Ms")
        for k in ("Encode", "Device", "Drain"):
            out[f"pipeline_stage_{k.lower()}_ewma_ms"] = snap.get(
                f"PipelineStage{k}EwmaMs"
            )
        if device_windows:
            out["device_windows"] = True
            out["pipelined_fused_chunks"] = matcher.pipelined_fused_chunks
            out["pipelined_fused_fallbacks"] = (
                matcher.pipelined_fused_fallbacks
            )
            out["h2d_bytes_per_batch"] = round(
                matcher.stats.h2d_bytes_per_batch(), 1
            )
    lps = total / elapsed
    out["value"] = round(lps, 1)
    out["vs_baseline"] = round(lps / TARGET, 4)
    out["elapsed_s"] = round(elapsed, 2)
    if mode == "pipeline" and device_windows:
        # the acceptance row: --pipeline with device windows banks a
        # fused-pipelined row in BENCH_fused_pipeline.json — and ONLY
        # there: its workload (device windows on) is not comparable to
        # BENCH_pipeline.json's host-window sync row, so it must not
        # clobber that book's pipeline row or its speedup
        try:
            with open(FUSED_STREAM_PATH) as f:
                fbook = json.load(f)
        except (OSError, json.JSONDecodeError):
            fbook = {}
        fbook["pipeline_device_windows_row"] = out
        ftmp = FUSED_STREAM_PATH + ".tmp"
        with open(ftmp, "w") as f:
            json.dump(fbook, f, indent=1)
        os.replace(ftmp, FUSED_STREAM_PATH)
        head = ["metric", "value", "unit", "vs_baseline", "backend", "mode"]
        ordered = {k: out[k] for k in head if k in out}
        ordered.update({k: v for k, v in out.items() if k not in ordered})
        print(json.dumps(ordered))
        return

    # merge into BENCH_pipeline.json (atomic) and report the speedup when
    # both modes have been measured on this backend
    try:
        with open(STREAM_PATH) as f:
            book = json.load(f)
    except (OSError, json.JSONDecodeError):
        book = {}
    book[mode] = out
    other = book.get("pipeline" if mode == "sync" else "sync")
    if other and other.get("backend") == backend and other.get("value"):
        pipe = out["value"] if mode == "pipeline" else other["value"]
        sync = out["value"] if mode == "sync" else other["value"]
        book["pipeline_vs_sync_speedup"] = round(pipe / sync, 2)
        out["pipeline_vs_sync_speedup"] = book["pipeline_vs_sync_speedup"]
    tmp = STREAM_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(book, f, indent=1)
    os.replace(tmp, STREAM_PATH)

    head = ["metric", "value", "unit", "vs_baseline", "backend", "mode"]
    ordered = {k: out[k] for k in head if k in out}
    ordered.update({k: v for k, v in out.items() if k not in ordered})
    print(json.dumps(ordered))


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

def _compose(partial: dict, live_sections: "set", probe: str,
             probe_err: "str | None") -> dict:
    secs = partial.get("sections", {})
    out: dict = {}
    merged_from_partial = []
    sec_meta = {}
    any_tpu = False
    for name in (*SECTIONS, "meta"):
        ent = secs.get(name)
        if not ent:
            continue
        out.update(ent["data"])
        sec_meta[name] = {
            "backend": ent["backend"], "measured_at": ent["measured_at"],
        }
        if ent["backend"] == "tpu" and name != "meta":
            any_tpu = True
        if name not in live_sections:
            merged_from_partial.append(name)

    out["backend"] = "tpu" if any_tpu else probe
    out["final_probe_backend"] = probe
    if probe_err:
        out["backend_error"] = probe_err
    if merged_from_partial:
        out["merged_from_partial"] = merged_from_partial
    out["section_provenance"] = sec_meta

    candidates = [
        out.get("pallas_lines_per_sec"),
        out.get("xla_lines_per_sec"),
        out.get("fused_device_resident_lines_per_sec"),
        out.get("fused_pipelined_lines_per_sec"),
    ]
    candidates = [v for v in candidates if v]
    best = max(candidates) if candidates else 0.0
    out["value"] = round(best, 1)
    out["vs_baseline"] = round(best / TARGET, 4)
    out["metric"] = "log-lines/sec classified @1k rules (device NFA match)"
    out["unit"] = "lines/sec"
    out["batch_latency_ms"] = (
        out.get("fused_device_resident_latency_ms")
        or out.get("pallas_batch_latency_ms")
        or out.get("fused_batch_latency_ms")
        or out.get("xla_batch_latency_ms")
    )
    return out


def main() -> None:
    if "--trace-overhead" in sys.argv:
        _trace_overhead_mode()
        return
    if "--provenance-overhead" in sys.argv:
        _provenance_overhead_mode()
        return
    if "--sketch-overhead" in sys.argv:
        _sketch_overhead_mode()
        return
    if "--host-parallel" in sys.argv:
        _host_parallel_mode()
        return
    if "--fused-pipeline" in sys.argv:
        _fused_pipeline_mode()
        return
    if "--single-kernel" in sys.argv:
        _single_kernel_mode()
        return
    if "--mega-state" in sys.argv:
        _mega_state_mode()
        return
    if "--fabric" in sys.argv:
        _fabric_mode()
        return
    if "--fleet-obs" in sys.argv:
        _fleet_obs_mode()
        return
    if "--challenge" in sys.argv:
        _challenge_mode()
        return
    if "--serve" in sys.argv:
        _serve_mode()
        return
    if "--scenarios" in sys.argv:
        _scenarios_mode()
        return
    if "--pipeline" in sys.argv:
        _stream_mode("pipeline")
        return
    if "--sync" in sys.argv:
        _stream_mode("sync")
        return
    if "--worker" in sys.argv:
        backend = "cpu"
        if "--backend" in sys.argv:
            backend = sys.argv[sys.argv.index("--backend") + 1]
        budget = float(os.environ.get("BENCH_BUDGET_S", "480"))
        only = None
        if os.environ.get("BENCH_SECTIONS"):
            only = os.environ["BENCH_SECTIONS"].split(",")
        worker_main(backend, budget, only)
        return

    probe, probe_err = _probe_backend()
    budget = float(os.environ.get("BENCH_BUDGET_S", "480"))
    live_sections: set = set()

    before = _load_partial().get("sections", {})
    before_stamp = {
        k: v.get("measured_at") for k, v in before.items()
    }
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--backend", probe],
            timeout=budget + 180, capture_output=True, text=True,
        )
        if r.returncode != 0:
            probe_err = probe_err or (
                f"worker rc={r.returncode}: {r.stderr.strip()[-300:]}"
            )
    except subprocess.TimeoutExpired:
        probe_err = probe_err or (
            f"worker timeout after {budget + 180:.0f}s — composing from "
            "sections persisted before the hang"
        )
    after = _load_partial()
    for k, v in after.get("sections", {}).items():
        if before_stamp.get(k) != v.get("measured_at"):
            live_sections.add(k)

    result = _compose(after, live_sections, probe, probe_err)
    # key order: metric/value first for human eyeballs
    head = ["metric", "value", "unit", "vs_baseline", "backend"]
    ordered = {k: result[k] for k in head if k in result}
    ordered.update({k: v for k, v in result.items() if k not in ordered})
    print(json.dumps(ordered))


if __name__ == "__main__":
    main()
