"""Host wall milliseconds of the submit stage's phase `dispatch` per thousand
lines drained: the call of the fused program with its operands converted, the
state swap, the chain bookkeeping, the start of the pull and the pending
chunk's record; the wait for the windows lock lies in it.

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
         {"phase": "dispatch"}),
        ("banjax_pipeline_processed_lines_total", {}), 1e6)
