"""Host wall milliseconds of the submit stage's phase `operands` per thousand
lines drained: preparing the fused program's operands (the stale mask, the
split timestamps, the host index, the encoded classes assembled and padded, the
program looked up).

One of six phases that partition the stage
(`banjax_submit_phase_seconds_total{phase}`, the program's lap clock):
their wall sums to the submit part of `devstage_ms_per_kline`.  A wall in one
thread, not a cost: `submit_wait_share` says how much of the stage its thread
ran.  None from a program without the family."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_submit_phase_seconds_total",
         {"phase": "operands"}),
        ("banjax_pipeline_processed_lines_total", {}), 1e6)
