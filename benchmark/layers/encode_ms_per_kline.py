"""Host encode (parse, gate, class ids): stage milliseconds per thousand
lines drained in the window."""
from benchmark.harness import prom


def read(ctx):
    return prom.stage_ms_per_kline(ctx, "encode")
