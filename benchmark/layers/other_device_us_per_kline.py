"""Device busy time outside the two kernels (eviction and slot-hash
scatters, sort, sketch fold, copies) per thousand lines in the traced
span."""


def read(ctx):
    tr, n = ctx["trace"], ctx["trace_lines"]
    if not tr or not n:
        return None
    k = tr["kernel_s"]
    other = tr["busy_s"] - k.get("match_kernel", 0.0) - k.get("window_scan", 0.0)
    return max(0.0, other) * 1e9 / n
