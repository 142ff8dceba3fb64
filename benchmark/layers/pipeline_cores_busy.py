"""Cores the pipeline's threads kept busy over the window: the CPU seconds of
`pipeline-encode`, the encode pool, `pipeline-device` and `pipeline-drain`
(`banjax_thread_cpu_seconds_total{thread}`, the threads' own clocks read at
scrape time) over the seconds between the scrapes.  1.0 = what one interpreter
can give them; above it, native code ran with the interpreter released.  None
from a program without the family."""
from benchmark.harness import prom

_THREADS = ("pipeline-encode", "pipeline-encode-worker", "pipeline-device",
            "pipeline-drain")


def read(ctx):
    ran = [prom.delta(ctx["prom0"], ctx["prom1"],
                      "banjax_thread_cpu_seconds_total", thread=t)
           for t in _THREADS]
    if all(s is None for s in ran):
        return None
    return sum(s or 0.0 for s in ran) / ctx["seconds"]
