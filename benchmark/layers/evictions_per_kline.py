"""Slot-table evictions per thousand lines drained."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_device_windows_evictions_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e3)
