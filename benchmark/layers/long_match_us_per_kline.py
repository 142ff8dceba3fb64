"""Device time of the NFA match kernel's launches over LONG rows (lines
over the short width, scanned apart from the others in operands of few
rows x 1,024 and x 8,192 columns) per thousand lines drained in the traced
span.  They are launches
of the one match kernel (trace_names.json, `match_kernel`); their HLO text
carries the padded line length they scan, and the long ones are those
whose length is over the configuration's `matcher_max_line_len`.  Both
stages' long launches count.  None where the trace shows no such launch: a
program without the long operand, or a span in which no long line came."""
import re

from benchmark.harness import xplane


def long_ops(ctx):
    """→ [(words, classes, seconds, launches)] of the long launches, or
    None where there is none or a launch cannot be sized."""
    tr = ctx["trace"]
    if not tr:
        return None
    short = int(ctx["config"]["product_config"]["matcher_max_line_len"])
    shape = re.compile(xplane.names()["match_kernel_shapes"])
    ops = []
    for name, seconds, launches in tr["kernel_ops"].get("match_kernel", []):
        m = shape.match(name)
        if m is None:
            return None  # a kernel this table cannot size: say nothing
        if int(m["line_len"]) > short:
            ops.append((int(m["words"]), int(m["classes"]), seconds,
                        launches))
    return ops or None


def read(ctx):
    ops, n = long_ops(ctx), ctx["trace_lines"]
    if not ops or not n:
        return None
    return sum(op[2] for op in ops) * 1e9 / n
