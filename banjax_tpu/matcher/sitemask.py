"""The site mask in front of the fused program's pair extraction.

Per-site rules are columns of the one device program, and the match does
not know a line's host: a pattern that hundreds of sites carry, each under
limits of its own, sets hundreds of stage-2 bits for one matching line.
Counted as (row, rule) pairs they pass the program's pair capacity and the
chunk replays classically.  So where the ruleset has rules of single sites
(or a `hosts_to_skip`), the rows of the active table are packed the way
stage 2 packs its accept bits, and the program ANDs each candidate's
packed row with the packed row of its host before it counts and lists the
pairs (kernels/fused_match_window.py, prefilter.pairs_from_core `keep`).
"""

from __future__ import annotations

import numpy as np


def packed_rows(active_table, skip_table, f_idx):
    """→ [hosts + 1, ceil(n_filt / 8)] uint8, MSB-first like stage 2's
    m2p: bit c of row h is set where filtered column c's rule applies on
    host row h — or None where the table has its one shared row alone (no
    per-site rule, no `hosts_to_skip`), and the program is built without
    the gather.

    A rule skipped on the host (`skip_table`, laid as the active table is)
    keeps its bit: the drain owes the line a `skip_host` result for it, and
    the program's per-event mask, the active table itself, keeps it out of
    the window events."""
    shown = np.asarray(active_table)
    if shown.shape[0] <= 1 or not len(f_idx):
        return None
    if skip_table is not None:
        shown = shown | np.asarray(skip_table)
    return np.packbits(shown[:, np.asarray(f_idx)], axis=1)
