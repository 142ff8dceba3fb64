"""What the prefilter plan knows, and what it sees, about how often a
rule's literal factor fires.

The two-stage plan (matcher/prefilter.py) filters a rule behind a literal
factor on the strength of a guess: benign traffic rarely carries the
literal.  Where the guess is wrong the rule's bucket alone sends more
rows to stage 2 than the compaction holds, and every chunk overflows its
candidates and replays classically — exact, and an order of magnitude
slower.

Known at plan time (`weak_gate`): a gate of a few bytes (`GET `) is what
log lines carry en masse, and a rule whose whole automaton is no wider
than a factor's word gains nothing from being filtered.  The plan runs
such a rule whole in stage 1, as it runs `^GET` (prefilter.
_stage1_decides).

Seen at run time (`hottest_bucket`): every fused program returns, beside
its candidate count, how many rows each factor bucket hit (prefilter.
_match_core `bucket_hits`).  /metrics exports the hottest bucket's share
of the last batch, and a `candidates` overflow names that bucket and the
rules behind it in the log line that reports it — what an operator needs
to see which rule's literal their traffic carries.  Nothing re-plans from
it: a rule with a long literal that the traffic carries all the same
still overflows and replays.

Nothing here is configured.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

# A gate of four bytes or fewer (`GET `, `.php`, `/api`) is what log lines
# carry en masse: Hyperscan keeps literals that short out of its literal
# matcher for the same reason.
WEAK_GATE_LEN = 4


def weak_gate(prog, factors: List[Tuple]) -> bool:
    """True for a rule like `GET .* /`: its weakest branch gates on a
    literal of at most WEAK_GATE_LEN bytes, and its whole automaton fits
    one 32-bit word — so run whole in stage 1 it costs the scan no more
    than its factors' word would, and filtered it gains nothing and puts
    every line that carries the literal into the compaction.  `prog` is
    the rule's rulec.RuleProgram, `factors` its required factors."""
    return (
        min(len(f) for f in factors) <= WEAK_GATE_LEN
        and sum(len(br.positions) for br in prog.branches) <= 32
    )


def hottest_bucket(plan, seen) -> Optional[Tuple[int, float, np.ndarray]]:
    """(bucket, its share of the rows, ids of the rules that gate on it)
    for the factor bucket that hit most rows of a batch; `seen` is a
    FusedPrefilter's `last_bucket_hits` (rows, hits per bucket) — None
    while nothing was read back or the plan filters nothing."""
    if seen is None:
        return None
    rows, hits = seen
    if not rows or not len(hits):
        return None
    b = int(np.argmax(hits))
    return b, float(hits[b]) / rows, plan.rules_of_bucket(b)
