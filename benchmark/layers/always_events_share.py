"""Share of the window events fused programs committed that came from an
always-column's set bit, not from a (row, rule) pair of the filtered
rules: `banjax_fused_event_feed_total{source="always"}` over both sources.
An invariant of the load, not a cost: in `capped1k.flood` the rate cap and
the challenge-all rule fire on about 860 lines of 1,000 and the
signatures on about 27, so a reading outside its band (PERF.md §3) means
the program dropped or doubled work.  None from a program that does not
count events by their source."""
from benchmark.harness import prom


def read(ctx):
    always = prom.delta(ctx["prom0"], ctx["prom1"],
                        "banjax_fused_event_feed_total", source="always")
    both = prom.delta(ctx["prom0"], ctx["prom1"],
                      "banjax_fused_event_feed_total")
    if always is None or not both:
        return None
    return 100.0 * always / both
