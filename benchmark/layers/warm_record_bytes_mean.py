"""Bytes the warm tier wrote for a spilled address, on average:
`banjax_warm_tier_bytes_written_total` over `banjax_warm_tier_spills_total`
(128 a record + 24 a counter + 8 a further 256-byte block).  It follows
the counters an evicted address holds, not the ruleset's size.  None from
a program that does not export the bytes, or where nothing was spilled."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_warm_tier_bytes_written_total", {}),
        ("banjax_warm_tier_spills_total", {}))
