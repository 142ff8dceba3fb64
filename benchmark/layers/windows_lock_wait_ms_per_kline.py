"""Milliseconds the submit stage's thread waited for the device windows'
lock per thousand lines drained (`banjax_windows_lock_wait_seconds_total
{stage="submit"}`: timed only when the lock was held; the drain's absorb is the
other taker).  The wait lies inside the phases, it is no seventh.  None from a
program without the counter."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_windows_lock_wait_seconds_total", {"stage": "submit"}),
        ("banjax_pipeline_processed_lines_total", {}), 1e6)
