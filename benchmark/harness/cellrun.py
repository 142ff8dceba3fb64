"""One run of one cell: set-up, window, drain, comparison, result line."""

from __future__ import annotations

import json
import logging
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from benchmark.harness import ctl as ctl_mod, found, genproc
from benchmark.harness import prom, reference, stream

WORK = ".bench_work"
DRAIN_WAIT_S = 10.0  # the reference rule drops a line older than 10 s
COMPARED = ("ban_records_missing", "ban_records_extra", "ips_out_of_order",
            "ban_keys_differing")  # each with the limit 0
CLEAN_IPS = ["192.0.2.1", "192.0.2.2", "192.0.2.3"]  # never in any stream
EXPECTED_ANSWER = {
    "NginxBlock": (403, "@access_denied"),
    "IptablesBlock": (403, "@access_denied"),
    "Challenge": (429, ""),
    None: (200, "@access_granted"),
}


def overlay(base: dict, over: dict) -> dict:
    """`over`'s keys replace `base`'s; `product_config` is merged."""
    out = {k: v for k, v in base.items() if k != "rehearse"}
    for k, v in over.items():
        out[k] = {**out[k], **v} if k == "product_config" else v
    return out


def control_rules(rules: list, traffic: dict, seed: int, kind: str) -> tuple:
    """The comparison the check has to fail: the two sides are given
    rulesets that differ in one rule's `hits_per_interval`, for a rule the
    stream crosses.  The product keeps the configuration's (its device
    programs close over the limits, so a changed limit would rebuild every
    one of them); the reference gets the changed one.  The product's run is
    the same either way, so one run serves the sound comparison and the
    control's.  → (changed rules, name)"""
    if kind != "limit":
        raise SystemExit(f"unknown control {kind!r}")
    rests, n_benign, attack_rule = genproc.build_pools(rules, traffic, seed)
    strm = stream.Stream(traffic, n_benign, len(rests) - n_benign, seed)
    ips, ridx = strm.block(0)
    hits = {}
    for ip, r in zip(ips, ridx):
        if r >= n_benign:
            key = (ip, attack_rule[r - n_benign])
            hits[key] = hits.get(key, 0) + 1
    crossed = sorted((k for k, v in hits.items()
                      if v > rules[k[1]]["hits_per_interval"]),
                     key=lambda k: (rules[k[1]]["interval"] <= 1, -hits[k]))
    idx = crossed[0][1] if crossed else 0
    out = [dict(r) for r in rules]
    out[idx]["hits_per_interval"] += 1
    return out, rules[idx]["rule"]


def spawn(module_file: str, args: dict, workdir: str, name: str):
    path = os.path.join(workdir, f"{name}.args.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(args, f)
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"  # it imports no JAX; were it to, no chip
    err = open(os.path.join(workdir, f"{name}.err"), "w")
    return subprocess.Popen([sys.executable, module_file, path],
                            stdout=err, stderr=err, env=env), err


def sleep_until(t: float) -> None:
    while True:
        d = t - time.time()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


def run(cell, args, seconds, device, jax, t_process, say) -> int:
    from benchmark.harness import product as product_mod

    config, traffic = cell["config"], cell["traffic"]
    if args.rehearse:
        config = overlay(config, config.get("rehearse", {}))
        traffic = overlay(traffic, traffic.get("rehearse", {}))
    seed = int(args.seed)
    workdir = os.path.join(found.REPO, WORK, cell["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    os.chdir(workdir)
    logging.basicConfig(
        filename="bench.log", level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    logging.getLogger("banjax_tpu.effectors.banner").setLevel(logging.WARNING)
    if args.keep_log:
        jax.config.update("jax_log_compiles", True)
    cache_dir = product_mod.place_cache(jax)
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"device {json.dumps(device)}; cell {cell['name']}; seed {seed}; "
        f"{seconds:g} s window; trace {args.trace}; compile cache {cache_dir} "
        f"({n_cached} entries)"
        + ("; REHEARSAL on the CPU, not a chip run" if args.rehearse else ""))

    rules = found.ruleset(config["ruleset"])
    control = control_rules(rules, traffic, seed, args.control) \
        if args.control else None

    ctl_path = os.path.join(workdir, "ctl.bin")
    ctl = ctl_mod.Ctl(ctl_path, create=True)
    log_path = os.path.join(workdir, product_mod.LOG_NAME)
    open(log_path, "a").close()
    procs = []
    gen_report = os.path.join(workdir, "gen.npy")
    procs.append(spawn(genproc.__file__, {
        "traffic": traffic, "ruleset": config["ruleset"], "seed": seed,
        "ctl": ctl_path, "log": log_path, "report": gen_report,
    }, workdir, "gen"))

    prod = None
    try:
        extra = ({"trace_enabled": True, "trace_jax_annotations": True}
                 if args.trace else {})
        config_path = product_mod.write_config(workdir, config, rules, extra)
        prod = product_mod.Product(config_path, ctl)
        warm_rests, _, _ = genproc.build_pools(rules, {**traffic, "lines": {
            **traffic["lines"], "benign_pool": 2048,
            "attack_pool": min(64, int(traffic["lines"].get("attack_pool", 0))),
        }}, stream.seed32(seed, 7))
        pc = config["product_config"]
        obs = prod.warm_up(traffic, warm_rests,
                           int(pc["matcher_window_capacity"]),
                           int(pc["matcher_batch_lines"]))
        say(f"warm-up: {obs['loads']} sends loaded or built a program in "
            f"{obs['load_s']:.1f} s [{', '.join(obs['programs'])}]; table "
            f"fill {obs['fill_s']:.1f} s ({obs['fill_lines']} lines, "
            f"{obs['fill_builds']} programs); the sizer cut "
            f"[{', '.join(obs['cut'])}], never clean "
            f"[{', '.join(obs['unsettled'])}]")
        m0 = prod.matcher
        if m0.breaker.state != "closed" or m0.fallback_batches:
            raise product_mod.NotReady(
                f"after warm-up the breaker is {m0.breaker.state} and "
                f"{m0.fallback_batches} batches went to the CPU")
        deadline = time.time() + 300
        while not ctl.get(ctl_mod.GEN_READY):
            for p, _ in procs:
                if p.poll() is not None:
                    raise product_mod.NotReady(
                        f"a generator ended early (exit {p.returncode}); see "
                        f"{workdir}/*.err")
            if time.time() > deadline:
                raise product_mod.NotReady("generators never became ready")
            time.sleep(0.01)
        desc = prod.matcher.describe()
        say(f"matcher: {json.dumps(desc)}")

        # ---- feed: run-in (set-up), then the window
        # set-up still: the real feed runs for `run_in_s`, and for whole
        # `run_in_step_s` more while it is not steady: the slot table,
        # filled with one-off addresses in warm-up, has not yet turned
        # over into the stream's own resident set (`run_in_evictions`,
        # the table's capacity: until then more lines evict than will
        # later); a device program was built or loaded in the last
        # `run_in_s`; or nothing drained for a second with lines waiting
        # (a cold build in progress stalls the pipeline and only counts as
        # built when it ends)
        run_in = float(traffic["run_in_s"])
        step = float(traffic.get("run_in_step_s", run_in))
        turnover = int(traffic.get("run_in_evictions", 0))
        c_go = prod.counters()
        t_go = time.time() + 0.25
        ctl.put_f(ctl_mod.T_GO, t_go)
        t0, unsteady_at = t_go + run_in, t_go
        seen, progress_at = c_go, t_go
        while True:
            # a look can itself take seconds while a program is built (the
            # product's counters wait for it), so the end is decided from
            # what was seen, never from the clock alone
            time.sleep(0.05)
            now, c = time.time(), prod.counters()
            if c["processed"] != seen["processed"]:
                progress_at = now
            waiting = ctl.get(ctl_mod.WRITTEN) > c["processed"] - c_go["processed"]
            if (c["builds"] != seen["builds"]
                    or (waiting and now - progress_at > 1.0)
                    or c["evictions"] - c_go["evictions"] < turnover):
                unsteady_at = now
            seen = c
            if now < t0 - 0.1:
                continue
            if unsteady_at <= t0 - run_in and now < t0 - 0.04:
                break
            t0 = max(t0, now) + step
            if t0 - t_go > 900:
                raise product_mod.NotReady(
                    "the feed is still not steady after 900 s")
        say(f"run-in {t0 - t_go:.1f} s ({seen['builds'] - c_go['builds']} "
            f"programs built or loaded, "
            f"{seen['evictions'] - c_go['evictions']} evictions in it)")
        t1 = t0 + seconds
        logging.info("feed began %.3f, window %.3f to %.3f", t_go, t0, t1)
        sleep_until(t0 - 0.03)
        prom0 = prom.parse(prod.metrics_text())
        c0 = prod.counters()
        sleep_until(t0)
        setup_s = t0 - t_process
        trace = None
        if args.trace:
            trace = os.path.join(workdir, "trace")
            sleep_until(t0 + min(2.0, seconds / 4))
            po = jax.profiler.ProfileOptions()
            po.python_tracer_level = 0
            po.host_tracer_level = 2
            # the stamps lie inside the two calls, which take their time:
            # lines drained between them are set against device time
            # recorded between them
            jax.profiler.start_trace(trace, profiler_options=po)
            t_tr0 = time.time()
            time.sleep(min(3.0, seconds / 2))
            t_tr1 = time.time()
            jax.profiler.stop_trace()
        sleep_until(t1 - 0.03)
        text1 = prod.metrics_text()
        with open("metrics_at_close.txt", "w", encoding="utf-8") as f:
            f.write(text1)  # for a look by hand; nothing reads it
        prom1 = prom.parse(text1)
        c1 = prod.counters()
        sleep_until(t1)
        ctl.put(ctl_mod.STOP, 1)
        for p, _ in procs:
            p.wait(timeout=30)
        # the feed's last batch may be a few short lines, a (rows, line
        # length) shape no window uses; a long line behind it keeps that
        # batch in the shapes that are warm
        prod.write_tail(max(warm_rests, key=len))
        gen = np.load(gen_report)
        writes = gen[gen[:, 2] > 0]
        written = int(writes[:, 2].sum())
        # a line not drained 10 s after the window closes has failed.  With
        # lines still in flight then, the comparison waits on for the
        # pipeline to come to rest, a minute past the close at most: the
        # drained batches (the reference's feed) and the ban log are read
        # at one point, and a batch that drains between two reads would
        # put its bans on one side only.  What drains late is late, not
        # wrong: it is failed, and both sides have it
        target = c_go["processed"] + written + prod.tail_lines

        def at_rest(until: float) -> bool:
            while (prod.counters()["processed"] < target
                   and time.time() < until):
                time.sleep(0.02)
            return prod.app.pipeline.flush(max(0.1, until - time.time()))

        prefix = f"{stream.IP_BASE}."
        late_from = t1 + DRAIN_WAIT_S
        rested = at_rest(late_from) or at_rest(t1 + 60.0)
        c_end = prod.counters()
        batches = prod.drained(t_go)
        ban_lines = prod.ban_log(prefix)
        peak = jax.devices()[0].memory_stats() or {}

        # ---- what the window measured
        # a line dropped as too old was not served: it does not count
        drained_in_window = sum(len(b[2]) for b in batches if t0 <= b[0] < t1)
        too_old = sum(len(b[1]) - len(b[2]) for b in batches if b[0] >= t0)
        gen_seen = sum(ln[18:21] == prefix for b in batches
                       if b[0] <= late_from for ln in b[1])
        in_window = (writes[:, 0] >= t0) & (writes[:, 0] < t1)
        offered = int(writes[in_window, 2].sum())
        delta = {k: c_end[k] - c_go[k] for k in c_end}
        undrained = max(0, written - gen_seen)
        failed_lines = (too_old + c_end["drain_errors"] - c0["drain_errors"]
                        + undrained)
        e2e = {"setup_s": setup_s,
               "lines_per_s": drained_in_window / seconds}
        attempted, failed = offered, failed_lines
        slices = np.histogram([b[0] for b in batches], weights=[
            len(b[2]) for b in batches], bins=np.arange(t0, t1 + 1e-6, 5.0))[0]
        say("lines drained per second in each 5 s of the window: "
            f"{[round(float(x) / 5.0) for x in slices]}")
        say(f"window: {offered} lines offered, {drained_in_window} drained "
            f"inside it, {written} written in all, {gen_seen} seen drained, "
            f"{too_old} dropped as too old since the window began; "
            f"deltas since the feed began {json.dumps(delta)}")

        # ---- per-layer metrics (traced run)
        metrics = {}
        breakdown = None
        dev_out = dict(device, memory_peak_bytes=int(
            peak.get("peak_bytes_in_use", 0)))
        if args.trace:
            from benchmark.harness import xplane

            reduced = None
            try:
                path = xplane.newest(trace)
                if args.keep_trace:
                    os.makedirs(os.path.dirname(args.keep_trace) or ".",
                                exist_ok=True)
                    shutil.copy(path, args.keep_trace)
                reduced = xplane.reduce(path)
            except (FileNotFoundError, ValueError) as e:
                say(f"trace: {e}")
            if reduced:
                dev_out["busy_s"] = reduced["busy_s"]
                dev_out["window_s"] = reduced["window_s"]
                breakdown = {
                    "device_ops": [[k[:160], v] for k, v in reduced["device_ops"]],
                    "idle_gaps": reduced["idle_gaps"]}
                say("trace: " + json.dumps(
                    {k: v for k, v in reduced.items() if k != "kernel_ops"}))
            # lines whose batch drained while the profiler was on
            traced = [ln for b in batches if t_tr0 <= b[0] < t_tr1
                      for ln in b[1]]
            in_trace = len(traced)
            # a log line is `<17-char stamp> <ip> <rest>`; the matcher
            # scans `rest`
            mean_len = (sum(len(ln) - ln.index(" ", 18) - 1 for ln in traced)
                        / in_trace) if in_trace else 0.0
            ctx = {
                "prom0": prom0, "prom1": prom1, "c0": c0, "c1": c1,
                "trace": reduced, "trace_lines": in_trace,
                "mean_len": mean_len,
                "gen": gen[(gen[:, 1] >= t0) & (gen[:, 1] < t1)],
                "writes": writes[in_window],
                "traffic": traffic, "config": config, "device": device,
                "seconds": seconds, "matcher": prod.matcher,
                "rehearse": args.rehearse,
            }
            for m in cell["per_layer"]:
                v = found.module("layers", m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            for m in cell["end_to_end"]:
                if m["name"] in e2e:
                    metrics[m["name"]] = {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
        say(f"end to end (all the harness took): {json.dumps(e2e)}")

        # ---- correct: the ban log against the plain reference
        checks = []  # (name, value, limit, ok)

        def check(name, value, limit, ok=None):
            ok = (value <= limit) if ok is None else ok
            checks.append((name, value, limit, ok))
            say(f"check {name}: {value} (limit {limit}) "
                f"{'ok' if ok else 'FAILED'}")

        # the regex rate limiter's records are compared; what /auth_request
        # itself bans (failed challenges) is outside the guarantee and only
        # enters the answers expected of the probes
        ban_log = [reference.product_record(x) for x in ban_lines]
        got = [x for x in ban_log if json.loads(x)["rule_type"] == "regex"]
        # the lines the product drained, in admission order, less those it
        # reported stale (they are in `failed`); lines it never drained are
        # in `failed` too and reach neither side
        log_lines = []
        for _, _, fresh in batches:
            log_lines.extend(fresh)
        banned_ips = {json.loads(x)["client_ip"] for x in got}
        attackers = set(stream.attacker_ips(
            int(traffic.get("attackers", {}).get("count", 0))))
        # probes first, while the bans are live; judged below
        probe_ips = (sorted(attackers)[:32] + sorted(banned_ips - attackers)[:16]
                     + CLEAN_IPS)
        answers = {ip: product_mod.probe(ip) for ip in probe_ips}
        # read after the probes: a probe of a challenged address is itself a
        # failed challenge and can be the one that escalates it
        other = [x for x in map(reference.product_record, prod.ban_log(prefix))
                 if json.loads(x)["rule_type"] != "regex"]
        health = prod.healthz()
        t_ref = time.time()
        # every line of every client address of the stream
        ref = reference.run(rules, log_lines,
                            lambda ip: ip.startswith(prefix),
                            int(config["reference"]["procs"]))
        cmp_ = reference.compare(got, ref["bans"])
        site_rules = {r["rule"] for r in rules if r.get("_site")}
        n_site = sum(json.loads(x)["trigger"] in site_rules
                     for x in ref["bans"])
        say(f"reference: {ref['lines']} lines of the stream's client IPs "
            f"({ref['distinct']} distinct request strings) in "
            f"{time.time() - t_ref:.1f} s; {len(ref['bans'])} ban records "
            f"({n_site} of per-site rules), "
            f"product {len(got)} (+{len(other)} not of the regex limiter); "
            f"{ref['errors']} unparsable")
        for k in COMPARED:
            check(k, cmp_[k], 0)
        check("lines_in_flight_at_comparison", int(not rested), 0)
        if cmp_["example_missing"] or cmp_["example_extra"]:
            say(f"  e.g. missing {cmp_['example_missing']} extra "
                f"{cmp_['example_extra']}")
        check("ban_records_in_reference", len(ref["bans"]), ">=1",
              len(ref["bans"]) >= 1)
        if traffic.get("slow_attackers"):
            # the part of the comparison that rests on window state kept
            # across evictions: a slow attacker's counting rule fires on
            # its third visit, each after the address was evicted
            counting = {r["rule"] for r in rules if r["hits_per_interval"] > 0}
            slow = set(stream.slow_attacker_ips(
                int(traffic["slow_attackers"]["count"])))
            n_slow = sum(d["client_ip"] in slow and d["trigger"] in counting
                         for d in map(json.loads, ref["bans"]))
            check("ban_records_after_refill_in_reference", n_slow, ">=1",
                  n_slow >= 1)
            refills = prod.matcher.device_windows.warm_refills
            check("warm_tier_refills", refills, ">=1", refills >= 1)
        final = reference.final_decisions(ref["bans"] + other)
        wrong = [ip for ip in probe_ips
                 if answers[ip] != EXPECTED_ANSWER[final.get(ip)]]
        check("auth_probes_wrong", len(wrong), 0)
        if wrong:
            say(f"  e.g. {wrong[0]}: {answers[wrong[0]]} for "
                f"{final.get(wrong[0])}")
        m = prod.matcher
        check("cpu_fallback_batches", m.fallback_batches, 0)
        check("generic_drain_batches", delta["generic_batches"], 0)
        check("fused_chunks_committed", m.pipelined_fused_chunks, ">=1",
              m.pipelined_fused_chunks >= 1)
        check("breaker_closed", m.breaker.state, "closed",
              m.breaker.state == "closed")
        bad = {k: v["status"] for k, v in health["components"].items()
               if v["status"] != "healthy"}
        check("healthz_unhealthy_components", len(bad), 0,
              not bad and health["status"] == "healthy")
        check("downgrades", len(desc["downgrades"]), 0)
        if not args.rehearse:
            check("compiled_pallas_on_tpu", json.dumps(
                [desc["platform"], desc["nfa_backend"], desc["match_interpret"]]),
                '["tpu", "pallas", false]',
                desc["platform"] == "tpu" and desc["nfa_backend"] == "pallas"
                and desc["match_interpret"] is False)
            for k, v in config.get("expect", {}).items():
                check(f"describe.{k}", json.dumps(desc.get(k)), json.dumps(v),
                      desc.get(k) == v)
        say(f"builds inside the window: {c1['builds'] - c0['builds']}; "
            f"fused chunks {m.pipelined_fused_chunks}, fused fallbacks "
            f"{m.pipelined_fused_fallbacks}; warm-tier spills "
            f"{m.device_windows.warm_spills} refills "
            f"{m.device_windows.warm_refills}")
        correct = all(c[3] for c in checks)
        if args.rehearse:
            say("rehearsal: correct is false by construction")
            correct = False
        result = {"correct": bool(correct), "attempted": int(attempted),
                  "failed": int(failed), "metrics": metrics,
                  "device": dev_out,
                  "checks_failed": [c[0] for c in checks if not c[3]]}
        if breakdown:
            result["breakdown"] = breakdown
        compared = {c[0]: {"value": c[1], "limit": c[2]} for c in checks}
        if control:
            say(f"CONTROL: the same ban log against a reference whose limit "
                f"of rule {control[1]!r} is one more than the product's; "
                "this comparison has to fail")
            cmp_c = reference.compare(got, reference.run(
                control[0], log_lines, lambda ip: ip.startswith(prefix),
                table=ref["table"])["bans"])
            checks = []
            for k in COMPARED:
                check(f"control.{k}", cmp_c[k], 0)
            result["control"] = {
                "correct": all(c[3] for c in checks),
                "checks_failed": [c[0] for c in checks if not c[3]]}
        # every number compared beside its limit: last in the result's
        # line, and the last lines on standard error
        result["compared"] = compared
    except product_mod.NotReady as e:
        print(f"benchmark: set-up failed: {e}", file=sys.stderr)
        return 1
    finally:
        ctl.put(ctl_mod.STOP, 1)
        for p, err in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            err.close()
        if prod is not None:
            prod.stop()
        ctl.close()
        if os.path.exists(log_path):
            os.remove(log_path)  # hundreds of MB; nothing reads it again
        if args.keep_log:
            os.makedirs(os.path.dirname(args.keep_log), exist_ok=True)
            shutil.copy("bench.log", args.keep_log)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
