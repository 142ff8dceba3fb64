"""Stage 1 of the NFA match (the kernel run over every line) as a share of
its roofline on the traced span.

Each match kernel event's HLO text carries its operand shapes
(trace_names.json, `match_kernel_shapes`): NFA words, line columns, padded
line length, byte classes.  Stage 1 is the launch with the fewest NFA
words: it scans every line of a batch, stage 2 only the candidates it
leaves.  The work the algorithm needs (`harness/roofline.py`) is taken
over the lines drained in the span at their mean length, not over the
padded columns and the padded length, and set against the published int8
and HBM peaks; the larger of the two least times over stage 1's device
time is the share.  Stage 2 has no share yet: the program exports no count
of candidates, and its padded columns would read as lines (PERF.md, Open
questions)."""
import re

from benchmark.harness import roofline, xplane


def read(ctx):
    tr, n = ctx["trace"], ctx["trace_lines"]
    if not tr or not n or not ctx["mean_len"]:
        return None
    shape = re.compile(xplane.names()["match_kernel_shapes"])
    ops = []
    for name, seconds, launches in tr["kernel_ops"].get("match_kernel", []):
        m = shape.match(name)
        if m is None:
            return None  # a kernel this table cannot size: say nothing
        ops.append((int(m["words"]), int(m["classes"]), int(m["line_len"]),
                    seconds, launches))
    if not ops:
        return None
    words = min(op[0] for op in ops)
    stage1 = [op for op in ops if op[0] == words]
    seconds = sum(op[3] for op in stage1)
    if seconds <= 0:
        return None
    mean = min(ctx["mean_len"], max(op[2] for op in stage1))
    work = roofline.match_kernel_work(
        n * mean, n, sum(op[4] for op in stage1), words, stage1[0][1])
    return roofline.share(work, seconds, ctx["device"]["kind"])[0]
