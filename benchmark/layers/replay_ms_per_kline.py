"""Host wall milliseconds inside the drain's `effector-replay` spans (event
decode, shadow bookkeeping, banner replay of committed fused chunks) per
thousand lines drained: the part of `drain_ms_per_kline` that follows the
event density.  None from a program that does not export the spans' sum."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_effector_replay_seconds_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e6)
