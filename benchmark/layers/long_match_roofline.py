"""The match kernel's launches over LONG rows as a share of their roofline
on the traced span.

The work is what the algorithm needs for the long lines of the span, not
what a launch was padded to: stage 1 (the launches with the fewest NFA
words) over every long line's own bytes — the window's
`banjax_matcher_long_line_bytes_total` and `..._long_lines_total` per line
drained, times the lines drained in the span — and stage 2 over the long
lines stage 1's gate passed on (`banjax_matcher_long_candidates_total`,
`..._long_candidate_bytes_total`), each over its stage's NFA words and byte
classes as the launches' operand shapes give them.  A launch of 128 rows x
8,192 columns that holds five lines of 3,000 bytes reads low here, and one
that scanned only their bytes would read higher for the same time.

`long_work` is the arithmetic of `harness/roofline.py: match_kernel_work`
(the same kernel), kept here because this file brings the metric; the
peaks are `peaks.json`'s, through `roofline.share`.  None from a program
without the counters or a trace without long launches."""
from benchmark.harness import found, prom, roofline


def long_work(line_bytes: float, lines: float, calls: float,
              words: int, classes: int) -> dict:
    """`line_bytes`: bytes scanned; `lines`: lines scanned; `calls`:
    kernel launches; `words`, `classes`: the stage's padded NFA words and
    byte classes.  → {"int8_ops", "hbm_bytes"}"""
    return {
        "int8_ops": 2.0 * 4 * words * classes * line_bytes,
        "hbm_bytes": (line_bytes + lines * words * 4
                      + calls * (4 * words * classes + words * 32)),
    }


def read(ctx):
    n = ctx["trace_lines"]
    ops = found.module("layers", "long_match_us_per_kline").long_ops(ctx)
    if not ops or not n:
        return None
    seconds = sum(op[2] for op in ops)
    if seconds <= 0:
        return None

    def per_line(name):
        return prom.ratio(ctx["prom0"], ctx["prom1"], (name, {}),
                          ("banjax_pipeline_processed_lines_total", {}))

    stage1 = min(op[0] for op in ops)
    work = {"int8_ops": 0.0, "hbm_bytes": 0.0}
    for stage, n_name, b_name in (
        ([op for op in ops if op[0] == stage1],
         "banjax_matcher_long_lines_total",
         "banjax_matcher_long_line_bytes_total"),
        ([op for op in ops if op[0] > stage1],
         "banjax_matcher_long_candidates_total",
         "banjax_matcher_long_candidate_bytes_total"),
    ):
        if not stage:
            continue
        lines, line_bytes = per_line(n_name), per_line(b_name)
        if lines is None or line_bytes is None:
            return None
        w = long_work(line_bytes * n, lines * n, sum(op[3] for op in stage),
                      max(op[0] for op in stage), stage[0][1])
        for k in work:
            work[k] += w[k]
    return roofline.share(work, seconds, ctx["device"]["kind"])[0]
