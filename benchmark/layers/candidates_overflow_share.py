"""Share of fused dispatches that committed nothing because stage 1's gate
passed more rows than the compaction holds and were replayed single-stage:
`banjax_fused_overflows_total{cause="candidates"}` over chunks committed
plus fallbacks.  0 where the plan runs every rule whose factor is hot as
an always-column.  None from a program that does not export the counter
by cause."""
from benchmark.harness import prom


def read(ctx):
    over = prom.delta(ctx["prom0"], ctx["prom1"],
                      "banjax_fused_overflows_total", cause="candidates")
    fb = prom.delta(ctx["prom0"], ctx["prom1"],
                    "banjax_pipelined_fused_fallbacks_total")
    ok = prom.delta(ctx["prom0"], ctx["prom1"],
                    "banjax_pipelined_fused_chunks_total")
    if over is None or fb is None or ok is None or fb + ok <= 0:
        return None
    return 100.0 * over / (fb + ok)
