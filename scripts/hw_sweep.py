"""Hardware tuning sweep. Sweeps the fused prefilter's (block_b, cols) tiling and
the device-resident batch size on the real chip, printing one JSON line per
configuration; the best configuration can then be pinned in
prefilter.FusedPrefilter's defaults and bench re-run.

Usage: python scripts/hw_sweep.py [budget_seconds]
"""

import json
import sys
import time

sys.path.insert(0, ".")


def main() -> None:
    budget = float(sys.argv[1]) if len(sys.argv) > 1 else 600.0
    t_start = time.monotonic()

    import os

    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import bench
    from banjax_tpu.matcher.encode import encode_for_match
    from banjax_tpu.matcher.prefilter import FusedPrefilter, build_plan
    from banjax_tpu.matcher.rulec import compile_rules

    backend = jax.devices()[0].platform
    print(json.dumps({"sweep": "start", "backend": backend}))
    patterns = bench.generate_rules(1000)
    compiled = compile_rules(patterns, n_shards="auto")
    plan = build_plan(
        patterns, byte_classes=(compiled.byte_to_class, compiled.n_classes)
    )

    def measure(B, block_b, cols):
        lines = bench.generate_lines(B, patterns, seed=29)
        cls, lens, _ = encode_for_match(compiled, lines, 128)
        fp = FusedPrefilter(
            plan, "pallas" if backend == "tpu" else "xla",
            block_b=block_b, cols=cols,
        )
        combined, Bp, L_p = fp._assemble(cls, lens)
        fn, K, P = fp._fused(Bp, L_p)
        dev_in = jax.device_put(combined)

        @jax.jit
        def chained(s, x):
            return s + fn(x).astype(jnp.int32).sum()

        lps, lat, first = bench._time_chained(chained, (dev_in,), B, iters=6)
        return lps, lat, first

    results = []
    # tiling sweep at the r3 reference batch, then batch sweep at the best
    for block_b, cols in ((512, 32), (512, 64), (1024, 32), (256, 32),
                          (512, 16), (1024, 16)):
        if time.monotonic() - t_start > budget:
            break
        try:
            lps, lat, first = measure(65536, block_b, cols)
            row = {"B": 65536, "block_b": block_b, "cols": cols,
                   "lines_per_sec": round(lps, 1),
                   "latency_ms": round(lat * 1e3, 2),
                   "first_call_s": round(first, 1)}
        except Exception as exc:  # noqa: BLE001 — one config failing keeps the sweep
            row = {"B": 65536, "block_b": block_b, "cols": cols,
                   "error": f"{type(exc).__name__}: {exc}"[:200]}
        results.append(row)
        print(json.dumps(row), flush=True)

    ok = [r for r in results if "lines_per_sec" in r]
    if ok:
        best = max(ok, key=lambda r: r["lines_per_sec"])
        for B in (32768, 131072, 262144):
            if time.monotonic() - t_start > budget:
                break
            try:
                lps, lat, first = measure(B, best["block_b"], best["cols"])
                row = {"B": B, "block_b": best["block_b"],
                       "cols": best["cols"],
                       "lines_per_sec": round(lps, 1),
                       "latency_ms": round(lat * 1e3, 2)}
            except Exception as exc:  # noqa: BLE001
                row = {"B": B, "error": f"{type(exc).__name__}: {exc}"[:200]}
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
