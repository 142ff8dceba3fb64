"""Warm-tier refills per thousand lines drained: addresses that came back
after an eviction and had their window counters restored to the device.
Above 0 wherever the traffic has slow attackers; the bans they earn are
the part of `correct` that rests on state kept across evictions."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_warm_tier_refills_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e3)
