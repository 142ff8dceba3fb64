"""Device time of stage 2 of the NFA match (the full automaton of the
filterable rules, run over the candidates stage 1 leaves) per thousand
lines drained in the traced span.  Both stages are launches of one kernel
(trace_names.json, `match_kernel`); their HLO text carries the NFA words
they scan, and stage 2's are the launches with more words than stage 1's,
which has the fewest (`match_stage1_roofline.py` reads those).  None where
the trace shows one width only: a plan that is stage 1 alone."""
import re

from benchmark.harness import xplane


def stage2_ops(trace):
    """→ [(words, classes, seconds, launches)] of stage 2's launches, or
    None where the trace cannot tell the stages apart."""
    shape = re.compile(xplane.names()["match_kernel_shapes"])
    ops = []
    for name, seconds, launches in trace["kernel_ops"].get("match_kernel", []):
        m = shape.match(name)
        if m is None:
            return None  # a kernel this table cannot size: say nothing
        ops.append((int(m["words"]), int(m["classes"]), seconds, launches))
    if not ops:
        return None
    stage1 = min(op[0] for op in ops)
    return [op for op in ops if op[0] > stage1] or None


def read(ctx):
    tr, n = ctx["trace"], ctx["trace_lines"]
    if not tr or not n:
        return None
    ops = stage2_ops(tr)
    if not ops:
        return None
    return sum(op[2] for op in ops) * 1e9 / n
