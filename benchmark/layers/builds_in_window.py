"""Device programs built or loaded inside the window (`compile_events()`
delta).  0 is the expectation; printed, not required."""


def read(ctx):
    return float(ctx["c1"]["builds"] - ctx["c0"]["builds"])
