"""Moves of the scheduler's batch-size target inside the window, up and down
together (`banjax_pipeline_batch_target_changes_total{direction}`).  0 while the
sizer stays in one row bucket, as `batch_lines_mean` (4,089-4,093 in every
accepted run) says it does; anything else is a window in which part of the lines
ran in smaller batches.  None from a program without the counter."""
from benchmark.harness import prom


def read(ctx):
    return prom.delta(ctx["prom0"], ctx["prom1"],
                      "banjax_pipeline_batch_target_changes_total")
