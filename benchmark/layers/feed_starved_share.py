"""Share of the window's time in which the backlog (lines written and not
yet drained, as the generator saw it at each write and each idle look) lay
under the feed's low mark.  Only a feed that has a low mark reports it: a
starved feed would read as a slow product."""
import numpy as np


def read(ctx):
    low = ctx["traffic"]["feed"].get("low_mark")
    looks = ctx["gen"]
    if low is None or len(looks) < 2:
        return None
    dt = np.diff(looks[:, 1])
    under = looks[:-1, 3] < low
    return 100.0 * float(dt[under].sum() / dt.sum())
