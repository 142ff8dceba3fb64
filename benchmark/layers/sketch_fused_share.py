"""Share of the window's traffic-sketch folds that rode their chunk's fused
match+window dispatch: `banjax_sketch_updates_total{path="fused"}` over both
paths.  A chunk folds once, in the dispatch that carries it: `fused`, a few
more lines of the program that is dispatched for the chunk anyway, or
`standalone`, a program of its own for what is not dispatched fused (a batch
taken the classic way, the classic protocol).  100 wherever every batch
commits fused; beside `submit_sketch_ms_per_kline`, which then times a gather
and an append.  None from a program without the counter (PR 44's parent:
every fold was a dispatch of its own)."""
from benchmark.harness import prom


def read(ctx):
    family = "banjax_sketch_updates_total"
    return prom.ratio(ctx["prom0"], ctx["prom1"],
                      (family, {"path": "fused"}), (family, {}), 100.0)
