"""Drain (event pull, window bookkeeping, banner effects): stage
milliseconds per thousand lines."""
from benchmark.harness import prom


def read(ctx):
    return prom.stage_ms_per_kline(ctx, "drain")
