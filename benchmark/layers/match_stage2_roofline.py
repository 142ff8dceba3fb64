"""Stage 2 of the NFA match as a share of its roofline on the traced span.

The work is what the algorithm needs for the lines stage 1 passed on, not
what a launch was padded to: the candidates of the span (the window's
`banjax_prefilter_candidates_total` per line drained, times the lines
drained in the span) at the span's mean line length, over stage 2's NFA
words and byte classes as its launches' operand shapes give them.  So the
share reads the same work whatever implements stage 2: a launch that scans
512 padded columns for 100 candidates reads low here, and one that scanned
only the candidates would read five times higher for the same time per
candidate.

`stage2_work` is the arithmetic of `harness/roofline.py: match_kernel_work`
(the same kernel: 2 * 4W * C int8 operations per line byte on the MXU; one
read of the line bytes, one write of W accept words a line, one read of the
table and the masks a launch), kept here because this file brings the
metric; the peaks are `peaks.json`'s, through `roofline.share`.  None from
a program without the counter, or from a trace with one stage."""
from benchmark.harness import found, prom, roofline


def stage2_work(candidates: float, mean_len: float, calls: float,
                words: int, classes: int) -> dict:
    """`candidates`: lines scanned; `mean_len`: their mean length in
    bytes; `calls`: kernel launches; `words`, `classes`: stage 2's padded
    NFA words and byte classes.  → {"int8_ops", "hbm_bytes"}"""
    line_bytes = candidates * mean_len
    return {
        "int8_ops": 2.0 * 4 * words * classes * line_bytes,
        "hbm_bytes": (line_bytes + candidates * words * 4
                      + calls * (4 * words * classes + words * 32)),
    }


def read(ctx):
    tr, n = ctx["trace"], ctx["trace_lines"]
    if not tr or not n or not ctx["mean_len"]:
        return None
    per_line = prom.ratio(
        ctx["prom0"], ctx["prom1"],
        ("banjax_prefilter_candidates_total", {}),
        ("banjax_pipeline_processed_lines_total", {}))
    if per_line is None:
        return None
    ops = found.module("layers", "match_stage2_us_per_kline").stage2_ops(tr)
    if not ops:
        return None
    seconds = sum(op[2] for op in ops)
    if seconds <= 0:
        return None
    words, classes = max(ops)[:2]
    work = stage2_work(per_line * n, ctx["mean_len"],
                       sum(op[3] for op in ops), words, classes)
    return roofline.share(work, seconds, ctx["device"]["kind"])[0]
