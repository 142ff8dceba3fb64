"""Seeded chaos soak: the scenario harness driving the REAL engine.

Tier-1 runs the short pass on every PR (bounded wall-clock: small
scales, two chaos runs); the full-length pass across every shape at
scale 1.0 rides behind `-m slow`.

What every run asserts (ScenarioReport.invariants):

  * admitted == processed + shed + drain_errors  (the PR 2 contract)
  * zero leaked fused order turns, zero leaked device-window slot pins
  * benign shapes: zero bans AND banjax_slo_breached == 0 end to end
  * chaos runs: one flight-recorder bundle per injected episode
"""

import json
import os

import pytest

from banjax_tpu.resilience import failpoints
from banjax_tpu.scenarios import ChaosSchedule, ScenarioRunner, generate
from banjax_tpu.scenarios.chaos import KAFKA_POINTS, TAILER_POINTS
from tests.fake_kafka_broker import FakeKafkaBroker

SEED = 20260804  # the committed soak seed: every CI run replays it


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.disarm()
    yield
    failpoints.disarm()


def _assert_invariants(report):
    assert report.invariants, "no invariants evaluated"
    bad = {k: v for k, v in report.invariants.items() if not v}
    assert not bad, (
        f"scenario {report.name} invariant failures: {bad}\n"
        f"{json.dumps(report.row(), indent=1, default=str)}"
    )


def test_clean_flash_crowd_matches_oracle_exactly():
    rep = ScenarioRunner(generate("flash_crowd", SEED, scale=0.25)).run()
    _assert_invariants(rep)
    assert rep.precision == 1.0 and rep.recall == 1.0
    assert rep.oracle_bans > 0  # non-vacuous
    assert rep.shed_lines == 0 and rep.drain_error_lines == 0


def test_clean_slow_drip_does_not_ban_the_paced_drippers():
    """Precision bait: 90+ paced drippers stay unbanned, the greedy
    set bans — exactly the oracle's multiset."""
    rep = ScenarioRunner(generate("slow_drip", SEED, scale=0.3)).run()
    _assert_invariants(rep)
    assert rep.precision == 1.0 and rep.recall == 1.0
    assert 0 < rep.oracle_bans < 10  # only the greedy few


def test_benign_scenario_zero_bans():
    """The differential check: the benign shape produces ZERO bans and
    a clean SLO board."""
    rep = ScenarioRunner(generate("benign", SEED, scale=0.1)).run()
    _assert_invariants(rep)
    assert rep.engine_bans == 0
    assert not any(rep.slo_breached.values())


def test_challenge_storm_drives_the_real_challenge_plane(tmp_path):
    """challenge_storm's second act: every storm client goes through the
    REAL issuance -> solve -> verify -> failure loop (decision_chain +
    challenge/*), not a simulation.  Scripted solvers must all pass,
    every non-solver must ban (exact precision/recall vs the scripted
    split), the bounded failure state must hold its cap with zero
    recall loss, and the eviction storm must leave a loadable
    flight-recorder bundle."""
    rep = ScenarioRunner(
        generate("challenge_storm", SEED, scale=0.25),
        flightrec_dir=str(tmp_path / "flightrec"),
        # cap far below the attacker count so the LRU + spill machinery
        # is actually on trial during the bans
        cfg_overrides={"challenge_failure_state_max": 4},
    ).run()
    _assert_invariants(rep)  # includes challenge_ban_exact + bounded
    ch = rep.challenge
    assert ch is not None
    assert ch["solvers"] > 0 and ch["attackers"] > 0
    assert ch["solver_passes"] == ch["solvers"]
    assert ch["banned"] == ch["attackers"]
    assert ch["ban_precision"] == 1.0 and ch["ban_recall"] == 1.0
    assert ch["failure_state_entries"] <= 4
    # the storm's eviction pressure left at least one complete bundle
    assert rep.incidents >= 1
    fdir = str(tmp_path / "flightrec")
    bundles = [n for n in os.listdir(fdir) if not n.startswith(".")]
    assert bundles
    with open(os.path.join(fdir, bundles[0], "meta.json")) as f:
        meta = json.load(f)
    assert meta["reason"]


def test_command_flood_drains_every_command_in_take_max_batches():
    rep = ScenarioRunner(generate("command_flood", SEED, scale=0.3)).run()
    _assert_invariants(rep)
    assert rep.command_items == rep.n_commands > 0
    assert rep.precision == 1.0 and rep.recall == 1.0


def test_command_flood_through_real_kafka_reader():
    """The PR 9 chaos gap, clean half: command_flood produced into an
    in-process broker and drained by a REAL KafkaReader over the wire
    protocol into the pipeline — every command lands, every per-batch
    report comes back out through the KafkaWriter."""
    broker = FakeKafkaBroker().start()
    try:
        rep = ScenarioRunner(
            generate("command_flood", SEED, scale=0.3), kafka_broker=broker
        ).run()
        _assert_invariants(rep)
        assert rep.mode == "kafka"
        assert rep.command_items == rep.n_commands > 0
        assert rep.precision == 1.0 and rep.recall == 1.0
        assert broker.log_end_offset("scenario.reports", 0) > 0
    finally:
        broker.stop()


def test_kafka_chaos_soak_fires_kafka_failpoints(tmp_path):
    """The PR 9 chaos gap, chaotic half: kafka.read/kafka.send episodes
    over the kafka-fed command_flood — the reconnect and held-report
    loops take faults while real traffic flows, invariants hold, every
    episode leaves a bundle.  Arming only the two kafka points makes
    the shuffled rotation cover both within the shape's few events
    (KAFKA_POINTS mixes in the pipeline points for longer soaks)."""
    sc = generate("command_flood", SEED, scale=0.3)
    assert set(KAFKA_POINTS) >= {"kafka.read", "kafka.send"}
    chaos = ChaosSchedule(
        seed=SEED + 2, n_events=len(sc.events),
        points=("kafka.read", "kafka.send"),
        episodes=min(4, len(sc.events) - 1),
    )
    broker = FakeKafkaBroker().start()
    try:
        rep = ScenarioRunner(
            sc, chaos=chaos, kafka_broker=broker,
            flightrec_dir=str(tmp_path / "flightrec"),
        ).run()
    finally:
        broker.stop()
    _assert_invariants(rep)
    assert all(ep["bundle"] for ep in rep.episodes)
    armed_points = {ep["point"] for ep in rep.episodes}
    assert {"kafka.read", "kafka.send"} <= armed_points
    # the writer's held-report retry converges: every produced report
    # reached the broker despite kafka.send faults
    assert broker.log_end_offset("scenario.reports", 0) > 0


def test_short_seeded_chaos_soak(tmp_path):
    """The tier-1 chaos pass: a seeded failpoint schedule over the
    flash-crowd shape, flight recorder armed — invariants hold, every
    injected episode leaves a bundle, armed episodes actually fired."""
    sc = generate("flash_crowd", SEED, scale=0.25)
    chaos = ChaosSchedule(seed=SEED, n_events=len(sc.events), episodes=3)
    rep = ScenarioRunner(
        sc, chaos=chaos, flightrec_dir=str(tmp_path / "flightrec")
    ).run()
    _assert_invariants(rep)
    assert len(rep.episodes) >= 2
    assert all(ep["bundle"] for ep in rep.episodes)
    assert sum(ep["fired"] for ep in rep.episodes) > 0
    assert rep.incidents >= len(rep.episodes)
    # nothing left armed after the soak
    assert failpoints.snapshot() == [] or all(
        fp["count"] == 0 for fp in failpoints.snapshot()
    )
    # bundles are complete (rename-atomic contract): each has meta.json
    fdir = str(tmp_path / "flightrec")
    for name in os.listdir(fdir):
        assert not name.startswith(".")
        assert os.path.exists(os.path.join(fdir, name, "meta.json"))


def test_chaos_over_tailer_rotation(tmp_path):
    """Chaos + a real rotating log file: tailer.open faults and pipeline
    faults layered over the rotation scenario — the accounting and leak
    invariants must still hold, and nothing the tailer delivered may
    vanish silently (admitted == processed + shed holds by invariant)."""
    sc = generate("log_rotation", SEED, scale=0.2)
    chaos = ChaosSchedule(
        seed=SEED + 1, n_events=len(sc.events),
        points=TAILER_POINTS, episodes=3,
    )
    rep = ScenarioRunner(
        sc, chaos=chaos, via_tailer=True, tmp_dir=str(tmp_path),
        flightrec_dir=str(tmp_path / "flightrec"),
    ).run()
    _assert_invariants(rep)
    assert all(ep["bundle"] for ep in rep.episodes)


@pytest.mark.slow
def test_full_soak_every_shape_clean_and_chaotic(tmp_path):
    """The full-length soak (-m slow): every named shape at scale 1.0
    clean, then chaos passes over the two nastiest shapes."""
    from banjax_tpu.scenarios import SHAPES

    for name in sorted(SHAPES):
        rep = ScenarioRunner(generate(name, SEED, scale=1.0)).run()
        _assert_invariants(rep)
        if not rep.name == "benign":
            assert rep.precision == 1.0 and rep.recall == 1.0, name
    for name in ("rotating_proxies", "command_flood"):
        sc = generate(name, SEED, scale=1.0)
        chaos = ChaosSchedule(
            seed=SEED, n_events=len(sc.events), episodes=6
        )
        rep = ScenarioRunner(
            sc, chaos=chaos,
            flightrec_dir=str(tmp_path / f"fr-{name}"),
        ).run()
        _assert_invariants(rep)
        assert all(ep["bundle"] for ep in rep.episodes)
