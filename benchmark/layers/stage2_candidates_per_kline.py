"""Lines stage 1's factor gate passed on to stage 2 (the rows the full
automaton of the filterable rules scanned) per thousand lines drained:
`banjax_prefilter_candidates_total`.  At least the lines that match a
filterable rule; the rest is what merged factors and benign text that
happens to carry a factor cost stage 2.  None from a program that does not
export the counter."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_prefilter_candidates_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e3)
