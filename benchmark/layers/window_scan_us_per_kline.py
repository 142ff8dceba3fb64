"""Device time of the window-scan kernel's trace events per thousand lines
drained in the traced span."""


def read(ctx):
    tr, n = ctx["trace"], ctx["trace_lines"]
    if not tr or not n or not tr["kernel_s"].get("window_scan"):
        return None
    return tr["kernel_s"]["window_scan"] * 1e9 / n
