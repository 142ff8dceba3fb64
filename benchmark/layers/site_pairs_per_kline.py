"""(row, rule) pairs the fused programs counted per thousand lines
drained: `banjax_fused_pairs_total`, the `n_pairs` flag of every fused
dispatch, which the program takes AFTER the site mask.  A program has
room for 250 a thousand rows (`pair_frac` 0.25); one that extracts its
pairs before it knows the line's host reads a pair for every site that
carries the matching pattern, hundreds of times that.  None from a
program that does not export the counter."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_fused_pairs_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e3)
