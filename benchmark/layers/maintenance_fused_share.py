"""Share of the window's maintenance runs of the device window table (queued
evictions, then restores, drained into the device state) that rode a fused
chunk's dispatch:
`banjax_device_windows_maintenance_steps_by_carrier_total{carrier="fused"}`
over both carriers.  A run counts once, by what carried its steps to the
device: `fused`, two operands of the chunk's own match+window program — no
dispatch and no transfer of their own — or `own`, an evict step and a restore
step a 1,024 keys dispatched by themselves (the classic apply, a run with
more live keys to restore than one chunk's operand holds).  100 wherever
every batch commits fused and restores under 1,024 keys; beside
`refills_per_kline`, which says how many addresses came back.  None from a
program without the counter (PR 49's parent: every run dispatched its own
steps)."""
from benchmark.harness import prom


def read(ctx):
    family = "banjax_device_windows_maintenance_steps_by_carrier_total"
    return prom.ratio(ctx["prom0"], ctx["prom1"],
                      (family, {"carrier": "fused"}), (family, {}), 100.0)
