"""Host wall milliseconds inside the submit stage's `submit-resolve` spans
(one pass over a batch's distinct client addresses: encode, slot-table and
warm-tier probes, the admission gate's verdict, placement, spills and
refills) per thousand lines drained: the part of `devstage_ms_per_kline`
that follows the distinct addresses and the misses of a batch.  None from a
program that does not export the spans' sum."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_submit_resolve_seconds_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e6)
