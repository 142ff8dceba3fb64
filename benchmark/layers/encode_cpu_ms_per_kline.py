"""CPU milliseconds the encode side ran per thousand lines drained: the
threads' own clocks (`banjax_thread_cpu_seconds_total{thread}`, read at scrape
time) of `pipeline-encode` — which takes the lines off the admission buffer
and merges the shards — and of the encode pool (`pipeline-encode-worker`,
the shards' parse and gate), between the window's two scrapes.  Beside
`encode_ms_per_kline`, the stage's wall: the wall holds the stage's queueing
for the interpreter behind the pipeline's other threads, this holds what the
stage itself ran — with the interpreter (strings, lists, glue) and without
it (the C parse and gate).  What the encode side takes off the interpreter
the submitting thread does not wait behind.  None from a program without the
family (PR 39's parent)."""
from benchmark.harness import prom

_THREADS = ("pipeline-encode", "pipeline-encode-worker")


def read(ctx):
    ran = [prom.delta(ctx["prom0"], ctx["prom1"],
                      "banjax_thread_cpu_seconds_total", thread=t)
           for t in _THREADS]
    lines = prom.delta(ctx["prom0"], ctx["prom1"],
                       "banjax_pipeline_processed_lines_total")
    if all(s is None for s in ran) or not lines:
        return None
    return sum(s or 0.0 for s in ran) / lines * 1e6
