"""Device time of the NFA match kernels' trace events per thousand lines
drained in the traced span (event names: trace_names.json)."""


def read(ctx):
    tr, n = ctx["trace"], ctx["trace_lines"]
    if not tr or not n or not tr["kernel_s"].get("match_kernel"):
        return None
    return tr["kernel_s"]["match_kernel"] * 1e9 / n
