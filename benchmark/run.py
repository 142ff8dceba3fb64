#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --selfcheck          (no chip: reductions, shape function, peaks)
    python3 benchmark/run.py --rehearse --workload <cell> ...   (tiny, CPU, ends correct:false)

A cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`benchmark/configs/<name>.json`) under a traffic mix
(`benchmark/traffic/<name>.json`).  Everything that belongs to one cell,
one feed kind, one ruleset generator or one per-layer metric is a file
found by name (harness/found.py); this file branches on none of them.

One run: start the generators (own processes, no JAX), start the product,
warm the shapes the cell's traffic reaches and fill the slot table (all
set-up), feed for `run_in_s`, measure for `--seconds`, stop, drain, compare
the ban log with the plain reference, print one JSON line.  A run that
finds no TPU (or fewer chips than the cell asks for) exits 2 before
anything else.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import found  # noqa: E402


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--selfcheck", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU backend; ends correct:false")
    ap.add_argument("--control", default="",
                    help="'limit': after the run's own comparison, compare "
                         "the same ban log with a reference that has one "
                         "rule's hits_per_interval changed; that comparison "
                         "has to fail (key `control` of the result)")
    ap.add_argument("--keep-trace", default="",
                    help="copy the run's .xplane.pb here (for a first look)")
    ap.add_argument("--keep-log", default="",
                    help="log every program JAX compiles or loads, with the "
                         "feed's stamps, and copy the run's bench.log here")
    args = ap.parse_args(argv)

    if args.selfcheck:
        from benchmark.harness import selfcheck

        return selfcheck.main()
    if not args.workload:
        ap.error("--workload is required")
    if not os.path.isdir(os.path.join(found.REPO, "banjax_tpu")):
        print("benchmark: the program (banjax_tpu/) is not in this checkout",
              file=sys.stderr)
        return 2
    if args.keep_trace:
        args.keep_trace = os.path.abspath(args.keep_trace)
    if args.keep_log:
        args.keep_log = os.path.abspath(args.keep_log)
    cell = found.cell(args.workload)
    seconds = args.seconds if args.seconds is not None else float(
        found.benchmark_json()["run_seconds"])

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and (
            device["platform"] != "tpu" or len(devs) < cell["chips"]):
        print(f"benchmark: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {device}", file=sys.stderr)
        return 2

    from benchmark.harness import cellrun

    return cellrun.run(cell, args, seconds, device, jax, T_PROCESS, say)


if __name__ == "__main__":
    sys.exit(main())
