"""Host wall milliseconds of the submit stage's phase `pass` per thousand lines
drained: the one pass over the batch's distinct client addresses (encode, the
slot table's and the warm tier's probes, the gate's verdict, placement, spills
and refills; refused rows split off and applied where a gate refuses).

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
         {"phase": "pass"}),
        ("banjax_pipeline_processed_lines_total", {}), 1e6)
