"""Share of the window's batches that went the classic way, whole, because
of ONE line — a byte over 0x7F, or a length the program cannot hold:
`banjax_matcher_unfused_batches_total` (both causes) over
`banjax_pipeline_batches_total`.  Such a batch is never dispatched fused,
so `fused_fallback_share` (overflows over dispatches) cannot see it, and
its long or non-ASCII lines are each decided by every rule's `re.search`
on the drain thread.  0 in `longline1k.flood`: every line of the stream is
ASCII and under 8,192 bytes.  None from a program without the counter."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_matcher_unfused_batches_total", {}),
        ("banjax_pipeline_batches_total", {}), 100.0)
