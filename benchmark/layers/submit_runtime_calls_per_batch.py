"""Calls into the device runtime the submitting thread made, per batch the
scheduler formed in the window: `banjax_submit_runtime_calls_total` (every
dispatch of a program and every explicit host-to-device transfer inside the
submit stage, counted where the program makes it) over
`banjax_pipeline_batches_total`.

Each such call gives the interpreter up and queues for it again behind the
pipeline's other threads, on the thread that sets every cell's rate.  1
where a batch is one fused chunk whose program carries the window table's
evictions and restores and takes its operands as they are; above it by a
batch cut into several chunks, a maintenance run past one chunk's operands,
a batch taken the classic way.  Beside `submit_dispatch_ms_per_kline` and
`submit_maintenance_ms_per_kline`, which time what these calls cost.  None
from a program without the counter (PR 49's parent: 11 to 15 a batch by its
code, uncounted)."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(ctx["prom0"], ctx["prom1"],
                      ("banjax_submit_runtime_calls_total", {}),
                      ("banjax_pipeline_batches_total", {}))
