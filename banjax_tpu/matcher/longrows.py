"""Lines over the short width: the host's half of the long operands.

The host's dense class matrix and the fused program's first operand are
`matcher_max_line_len` (256) columns wide, which 97 % of an access log's
lines fit.  A longer line — tracking and search queries, long User-Agents,
a scanner's payload URL of kilobytes — is a LONG row: it rides the first
operand as an empty row, its bytes stay in the parse blob until its chunk
is dispatched, and it travels in one of two further operands of the same
fused program, by its own length: up to 1,024 bytes in one of a sixteenth
of the chunk's rows x 1,024 columns, up to 8,192 in one of one kernel
block's rows (128) x 8,192 columns.  The program scans each with one more
launch of each stage's
kernel (prefilter._match_core).  A line's length so never decides the path
of the lines around it, and a long line costs the host a slot of the width
that holds it: no matrix is ever rows x 8,192.

What stays the host's: a line with a byte over 0x7F (nginx's log escaping
writes `\\xNN` for those, so an access log has none) and a line past
LONG_WIDTH still take their batch the classic way, each decided by the
host's `re` (runner._fused_rows_ok counts them by cause).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple, Union

import numpy as np

# The long operands' widths.  The last is the longest request string nginx
# can log: its `large_client_header_buffers` default is `4 8k` and "a
# request line cannot exceed the size of one buffer", so method, host, URI,
# protocol and User-Agent of one `banjax_format` line end under 8,192
# bytes.  The first holds what a log is full of past the short width —
# search and tracking URLs, long User-Agents — at an eighth of the bytes a
# row.  Two widths in ONE program, not a ladder of programs: every (rows,
# widths) pair a chunk could choose between is a Mosaic build of its own
# that the first such chunk would wait for, and on the device the kernel's
# tile skip already ends a block's scan at its longest line.
LONG_WIDTHS = (1024, 8192)
LONG_WIDTH = LONG_WIDTHS[-1]


def long_lens(
    lines: Sequence[Union[str, bytes]], host_eval: np.ndarray,
) -> np.ndarray:
    """int32 [B] beside `encode.encode_lines`' host_eval: for a row the
    dense matrix does not hold, its byte length where it is ASCII and at
    most LONG_WIDTH bytes (a LONG row), -1 where it is longer than that,
    0 for every other row (a short row, or one with a byte over 0x7F).
    The native parse says the same with FLAG_LONG and `rest_len`."""
    out = np.zeros(len(lines), dtype=np.int32)
    for i in np.flatnonzero(host_eval).tolist():
        s = lines[i]
        raw = s.encode("utf-8", "surrogatepass") if isinstance(s, str) else s
        if len(raw) > LONG_WIDTH:
            out[i] = -1
        elif raw.isascii():
            out[i] = len(raw)
    return out


def operands(pf, B: int) -> Tuple[Tuple[int, int], ...]:
    """((width, rows), ...): the long operands of a B-row chunk's program
    of `pf` (a FusedPrefilter), narrowest first.  A long row travels in
    the narrowest that holds it.  The first has a sixteenth of the chunk's
    rows (real access logs put 3 % of lines past 256 bytes), the last one
    kernel block whatever the chunk's — payload requests of kilobytes are
    a handful in thousands of lines, the launch that scans them costs its
    longest line whatever its other lanes hold, and fewer rows than a
    block would leave lanes idle that it pays for (32 rows were tried on
    the chip: a quarter of the bytes to send, and a flood of payload lines
    dispatched 32 rows at a time — PERF.md §6, PR 43).  Rows are whole
    kernel blocks.  A batch with more long rows of either kind in a chunk
    is dispatched as smaller chunks (runner._fused_chunks)."""
    lo = 128 if pf._pallas and not pf.interpret else pf._block
    mid = min(B, max(lo, B // 16))
    block = pf._block_for(mid)
    return (
        (LONG_WIDTHS[0], -(-mid // block) * block),
        (LONG_WIDTH, lo),
    )


def operand_of(long_len: np.ndarray) -> np.ndarray:
    """int [n]: which of LONG_WIDTHS' operands carries each row of a
    `long_lens` vector — 0, 1, ... for a LONG row, -1 for every other."""
    return np.where(
        long_len > 0, np.searchsorted(LONG_WIDTHS, long_len), -1)


def assemble(pf, spec, long_rows, pad_row: int) -> List[np.ndarray]:
    """→ one [rows, 2 + width/4 | width] int32 per (width, rows) of `spec`
    (operands'), the long operands of prefilter._match_core: col 0 = a
    row's length, col 1 = its caller row (`pad_row`, past the chunk, on a
    slot no row fills), then its class ids laid out as the short
    operand's are.  `long_rows`: (rows, lens, the rows' class ids back to
    back), or None for a chunk without one — the host's cost follows
    these bytes and the operands' fixed sizes, never rows x width."""
    rows, lens, cls_flat = long_rows or (np.zeros(0, dtype=np.int32),) * 3
    which = operand_of(lens)
    if (which < 0).any() or (which >= len(spec)).any():
        raise ValueError("a long row no operand is wide enough for")
    ends = np.cumsum(lens)
    out = []
    for j, (width, cap) in enumerate(spec):
        pick = np.flatnonzero(which == j)
        n = len(pick)
        if n > cap:
            raise ValueError("long rows beyond the operand's capacity")
        if pf._pack_input:
            # four class ids an int32, little-endian: the ids as bytes,
            # the first two int32 of a row skipped
            op = np.zeros((cap, 2 + -(-width // 4)), dtype=np.int32)
            ids = op.view(np.uint8)[:, 8:]
        else:
            op = np.zeros((cap, 2 + width), dtype=np.int32)
            ids = op[:, 2:]
        for k, i in enumerate(pick.tolist()):
            ids[k, : lens[i]] = cls_flat[ends[i] - lens[i] : ends[i]]
        op[:, 1] = pad_row
        op[:n, 0] = lens[pick]
        op[:n, 1] = rows[pick]
        out.append(op)
    return out
