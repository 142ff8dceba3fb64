"""Host wall milliseconds of the submit stage's phase `sketch` per thousand lines
drained: the traffic sketch: the slot table's note of the batch's assignments
(`note_assignments`, inside the pass's `submit-resolve` span) and the fold of
the submitted chunk (`FusedWindows._sketch_update`).

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
         {"phase": "sketch"}),
        ("banjax_pipeline_processed_lines_total", {}), 1e6)
