"""Feed kind `backlog`: keep the product's backlog (lines written and not
yet drained) between two marks, so the measured quantity is capacity.

Parameters (a traffic file's `feed`): `low_mark`, `high_mark`, `top_up`
(lines).  A top-up is written whenever it fits under the high mark; every
line is stamped with the time of its top-up (its due time is when it is
written).  The share of time the backlog lay under the low mark is the
cell's `feed_starved_share`: a starved feed would read as a slow product.
"""

import time


def run(feed, p: dict) -> None:
    high, top = int(p["high_mark"]), int(p["top_up"])
    while not feed.stopped():
        if feed.backlog() <= high - top:
            feed.write(top, due=time.time())
        else:
            feed.note()
            time.sleep(0.001)
