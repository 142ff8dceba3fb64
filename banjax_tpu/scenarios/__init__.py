"""Adversarial traffic-scenario harness (ROADMAP item 4).

A uniform tailer-shaped feed is the easy case; the reference's real
workload is hostile — rotating-proxy botnets, slow
drips under many user agents, Baskerville command floods, challenge
storms, log rotation mid-burst.  This package turns those shapes into
deterministic, oracle-checked evidence:

  * shapes.py   — named attack-shape generators.  Same seed → byte-
                  identical line stream + identical ground-truth oracle.
  * oracle.py   — an independent reference-semantics simulator (fixed
                  windows with the Go quirks) producing the expected
                  (ip, rule) ban multiset for any line stream.
  * runtime.py  — ScenarioRunner: feeds a scenario through the real
                  engine (TpuMatcher + PipelineScheduler, device windows
                  on), measures lines/s, shed ratio, ban precision/recall
                  vs the oracle and SLO burn peaks, and asserts the
                  structural invariants (admitted == processed + shed,
                  zero leaked fused turns/pins, benign ⇒ no SLO breach).
  * chaos.py    — seeded chaos schedules arming resilience/failpoints.py
                  points mid-stream, one flight-recorder bundle per
                  injected episode.
  * stats.py    — last-run summary the /metrics exposition renders as
                  the banjax_scenario_* families.

Entry points: `tests/soak/` runs a short seeded chaos pass in tier-1 and
a long one behind `-m slow`; synth.py holds the seeded rule/line
generators the smoke run and the tests share.
"""

from banjax_tpu.scenarios.chaos import ChaosSchedule  # noqa: F401
from banjax_tpu.scenarios.oracle import expected_bans  # noqa: F401
from banjax_tpu.scenarios.runtime import ScenarioRunner  # noqa: F401
from banjax_tpu.scenarios.shapes import (  # noqa: F401
    SHAPES,
    CommandBatch,
    LineChunk,
    Rotation,
    Scenario,
    generate,
)
