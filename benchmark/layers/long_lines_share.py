"""Share of the lines drained in the window that were LONG: over the short
width (`matcher_max_line_len`, 256) and the fused program's long operand's
to decide — `banjax_matcher_long_lines_total` over lines processed.  An
invariant of the stream, not a cost (the convention `always_events_share`
follows): `flood-long`'s pools put it near 3.1 % (PERF.md §4 has the
generator's own count), and a reading off it means lines were cut, routed
to the host's `re`, or counted twice.  None from a program without the
counter (one that takes every such line's batch off the fused path)."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_matcher_long_lines_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 100.0)
