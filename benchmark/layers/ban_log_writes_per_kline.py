"""Writes of a ban-log file per thousand lines drained: each is one `write`
and one `flush` of every line an applied chunk had for that file (both files
together: `banjax_ban_log_writes_total{target}`).  Every one gives the
interpreter up on the drain thread, so a program that writes a record at a
time reads `ban_records_per_kline` here, and one that takes a chunk's records
as one batch a write or two a chunk.  None from a program without the
family."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_ban_log_writes_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e3)
