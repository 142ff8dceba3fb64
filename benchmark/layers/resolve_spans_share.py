"""Share of the window's passes over a batch's distinct addresses that took
them as byte spans: `banjax_submit_resolve_passes_total{form="spans"}` over
both forms.  A pass counts once, by the form its addresses came in and were
worked on: `spans`, the parse blob's bytes merged by bytes and handed to the
slot table, the warm tier and the sketch as they are, no string made of an
address; or `strings`, encoded for the pass (a Python parse, the sync entry,
the dict path).  100 wherever every batch is parsed natively and placed by
the native slot manager; beside `submit_pass_ms_per_kline`, which times the
pass.  None from a program without the counter (PR 45's parent: every pass
took strings)."""
from benchmark.harness import prom


def read(ctx):
    family = "banjax_submit_resolve_passes_total"
    return prom.ratio(ctx["prom0"], ctx["prom1"],
                      (family, {"form": "spans"}), (family, {}), 100.0)
