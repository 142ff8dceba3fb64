"""Evicted slots whose window counters went to the warm tier, per thousand
lines drained.  Equal to `evictions_per_kline` where every evicted address
holds live counters; a handful where most evicted slots are empty."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_warm_tier_spills_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e3)
