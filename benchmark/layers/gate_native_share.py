"""Share of the window's encode shards (a batch gated unsharded is one) whose
gate was the one native call: `banjax_encode_gate_shards_total{path="native"}`
over both paths.  A shard counts once, by what turned its parsed lines into a
work set: `native`, one call into C that leaves the candidate rows, the
first-appearance tables of addresses and hosts and the per-row columns as
arrays; or `python`, the per-line loop (no native library, a line with a
newline in it).  100 wherever the library loaded and the tailer cut the
lines; beside `encode_cpu_ms_per_kline`, which says what the gate costs.
None from a program without the counter (PR 50's parent: the same shards
gated by a composition of numpy calls, uncounted)."""
from benchmark.harness import prom


def read(ctx):
    family = "banjax_encode_gate_shards_total"
    return prom.ratio(ctx["prom0"], ctx["prom1"],
                      (family, {"path": "native"}), (family, {}), 100.0)
