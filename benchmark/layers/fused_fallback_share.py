"""Share of fused dispatches that overflowed and were replayed off the
fused path: fallbacks over chunks committed plus fallbacks."""
from benchmark.harness import prom


def read(ctx):
    fb = prom.delta(ctx["prom0"], ctx["prom1"],
                    "banjax_pipelined_fused_fallbacks_total")
    ok = prom.delta(ctx["prom0"], ctx["prom1"],
                    "banjax_pipelined_fused_chunks_total")
    if fb is None or ok is None or fb + ok <= 0:
        return None
    return 100.0 * fb / (fb + ok)
