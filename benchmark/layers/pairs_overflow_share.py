"""Share of fused dispatches that committed nothing because their (row,
rule) pairs passed the program's pair capacity and were replayed
classically: `banjax_fused_overflows_total{cause="pairs"}` over chunks
committed plus fallbacks.  None from a program that does not export the
counter by cause."""
from benchmark.harness import prom


def read(ctx):
    over = prom.delta(ctx["prom0"], ctx["prom1"],
                      "banjax_fused_overflows_total", cause="pairs")
    fb = prom.delta(ctx["prom0"], ctx["prom1"],
                    "banjax_pipelined_fused_fallbacks_total")
    ok = prom.delta(ctx["prom0"], ctx["prom1"],
                    "banjax_pipelined_fused_chunks_total")
    if over is None or fb is None or ok is None or fb + ok <= 0:
        return None
    return 100.0 * over / (fb + ok)
