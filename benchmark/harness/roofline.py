"""Published peaks, and the operations and bytes the match kernel's
algorithm needs: the arithmetic a roofline share is made of.

The NFA match kernel (`kernels/nfa_match.py`) advances every rule's state
over each byte of each line.  Per byte column it gathers the byte class's
transition masks as a one-hot matmul on the MXU: `table[4W, C] @
onehot[C, lines]`, int8, W = NFA words (padded as the kernel pads them),
C = byte classes (padded to the lane width).  That is 2 * 4W * C int8
operations per line byte.  HBM traffic per call is one read of the encoded
lines (one byte per line byte), one read of the table (4W * C bytes) and
the mask words (W * 8 * 4), and one write of the accept words (W * 4 bytes
per line): the state stays in VMEM.  The shift-and updates run on the VPU,
for which no peak is published; a kernel bound by them shows as a low share
here, which is the honest reading against published peaks.
"""

from __future__ import annotations

import json
import os

from benchmark.harness import found


def peaks(device_kind: str) -> dict:
    with open(os.path.join(found.ROOT, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"peaks.json has {sorted(table)}")
    return table[device_kind]


def match_kernel_work(line_bytes: float, lines: float, calls: float,
                      words: int, classes: int) -> dict:
    """`line_bytes`: bytes scanned (sum of line lengths); `lines`: lines
    scanned; `calls`: kernel launches; `words`, `classes`: the padded NFA
    words and byte classes of the stage.  → {"int8_ops", "hbm_bytes"}"""
    return {
        "int8_ops": 2.0 * 4 * words * classes * line_bytes,
        "hbm_bytes": (line_bytes + lines * words * 4
                      + calls * (4 * words * classes + words * 32)),
    }


def share(work: dict, seconds: float, device_kind: str) -> tuple:
    """→ (percent of the roofline, which bound): the least time the chip
    could take for `work` over the time it took."""
    p = peaks(device_kind)
    t_mxu = work["int8_ops"] / p["int8_ops_per_s"]
    t_hbm = work["hbm_bytes"] / p["hbm_bytes_per_s"]
    if seconds <= 0:
        raise ValueError("kernel time must be above 0")
    return (100.0 * max(t_mxu, t_hbm) / seconds,
            "mxu" if t_mxu >= t_hbm else "hbm")
