"""Host wall milliseconds of the submit stage's phase `other` per thousand lines
drained: what no mark names: the eligibility checks, a partition at the
admission gate, the classic submit of a batch that cannot commit fused, the
scheduler's glue.  Small, or a boundary is in the wrong place.

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
         {"phase": "other"}),
        ("banjax_pipeline_processed_lines_total", {}), 1e6)
