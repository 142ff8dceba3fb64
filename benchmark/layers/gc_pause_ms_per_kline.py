"""Milliseconds the cyclic garbage collector took per thousand lines drained:
the pauses of all three generations (`banjax_gc_pause_seconds_total
{generation}`, one `gc.callbacks` entry of the program timing every collection)
between the window's two scrapes.  A collection stops every thread of the
process, so this lies inside every stage's wall at once — `encode_`, `devstage_`
and `drain_ms_per_kline` each hold their share of it — and is no stage of its
own.  None from a program without the family."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_gc_pause_seconds_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e6)
