"""Rules the prefilter plan runs whole in stage 1 although they have a
factor (`banjax_plan_rules{route="promoted"}` at the window's close): a
gate of four bytes or fewer in front of an automaton of one word.  An
invariant of the ruleset: `capped1k-edge` has one such rule (`GET .* /`),
and the configuration's `expect.plan_promoted` holds the program to that
list by name — none promoted or thirty promoted is `correct` false, not a
better or worse reading.  `better: lower` only because each one is a whole
automaton in the scan over every byte.  None from a program that does not
export its plan's routes."""
from benchmark.harness import prom


def read(ctx):
    return prom.value(ctx["prom1"], "banjax_plan_rules", route="promoted")
