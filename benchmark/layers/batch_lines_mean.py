"""Lines per batch the scheduler formed in the window."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(ctx["prom0"], ctx["prom1"],
                      ("banjax_pipeline_processed_lines_total", {}),
                      ("banjax_pipeline_batches_total", {}))
