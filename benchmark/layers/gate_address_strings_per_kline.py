"""Address strings the program made from its gated batches' byte spans, per
thousand lines drained: `banjax_gate_address_strings_total` over
`banjax_pipeline_processed_lines_total`.  The submit stage's pass, the warm
tier, the slot manager and the candidate log work on the spans; a string is
made of an address where something asks for one — the lines of the rows that
exceeded a limit, at the drain (about `ban_records_per_kline`), a deferred
row's patch, an allowlisted deployment's check, the dict path.  Near the ban
records where the hot path makes none.  None from a program without the
counter (PR 50's parent: one string a distinct address of every shard, 500
to 1,000 a kline by its code, uncounted)."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(ctx["prom0"], ctx["prom1"],
                      ("banjax_gate_address_strings_total", {}),
                      ("banjax_pipeline_processed_lines_total", {}), 1e3)
