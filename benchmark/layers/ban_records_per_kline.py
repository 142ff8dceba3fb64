"""Ban-log records the regex rate limiter wrote per thousand lines drained:
the banner's load (decision insert, ban-log line, provenance) per line of
traffic.  None from a program that does not count them."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_regex_ban_records_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e3)
