"""Slots the slot manager's placements read to find their eviction victims,
per thousand lines drained: `banjax_slot_eviction_scanned_slots_total` — every
slot a victims' walk looks at, the members of each run of the kept
(last_used, slot) order it sorts included.  With the order kept between
batches that is about the victims and one batch's leftover run a batch
(`evictions_per_kline` and about as much again); a placement that scans the
table to pick its victims reads its capacity a batch, 16,000 a kline at 65,536
slots and 4,096-line batches.  None from a program without the counter
(PR 45's parent: a scan a batch)."""
from benchmark.harness import prom


def read(ctx):
    return prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_slot_eviction_scanned_slots_total", {}),
        ("banjax_pipeline_processed_lines_total", {}), 1e3)
