"""Window events ((line, rule) transitions) committed on the device per
thousand lines drained.  About 27 with 1,000 sparse rules; about 950 where
the rules fire on nearly every line.  None from a program that does not
count them."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_device_windows_events_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e3)
