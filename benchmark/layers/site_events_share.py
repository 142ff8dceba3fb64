"""Share of the window events committed on the device whose rule belongs
to one site: `banjax_window_events_total{scope="site"}` over both scopes.
An invariant of the load, not a cost: `multisite.botnet` draws its attack
rules uniformly among 1,000 global and 9,000 per-site rules, so a reading
outside 85-95 % means the program dropped or doubled work.  None from a
program that does not count events by scope."""
from benchmark.harness import prom


def read(ctx):
    site = prom.delta(ctx["prom0"], ctx["prom1"],
                      "banjax_window_events_total", scope="site")
    both = prom.delta(ctx["prom0"], ctx["prom1"],
                      "banjax_window_events_total")
    if site is None or not both:
        return None
    return 100.0 * site / both
