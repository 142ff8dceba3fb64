"""The scheduler's "device" stage: host wall from submit to collect, per
thousand lines.  Not device busy time, and never reported under a device
name."""
from benchmark.harness import prom


def read(ctx):
    return prom.stage_ms_per_kline(ctx, "device")
