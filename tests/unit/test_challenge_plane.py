"""Unit tier for the challenge plane's two engines:

  * matcher/kernels/pow_verify.py — the batched sha256 leading-zero-bits
    kernel against hashlib + the O(1) bit counter, across lane-padding
    edge shapes and degenerate payloads;
  * challenge/failures.py — the bounded failed-challenge state: exact
    reference transitions, the LRU bound, lossless spill/refill, the
    spill-priority protection (offender evidence beats churner noise),
    and the construction seam.

The end-to-end differentials live in
tests/differential/test_challenge_differential.py.
"""

import hashlib
import threading
import time

import numpy as np
import pytest

from banjax_tpu.challenge.failures import (
    BoundedFailedChallengeStates,
    make_failed_challenge_states,
)
from banjax_tpu.challenge.verifier import DeviceVerifier, cpu_zero_bits
from banjax_tpu.crypto.challenge import count_zero_bits_from_left
from banjax_tpu.decisions.rate_limit import FailedChallengeRateLimitStates
from banjax_tpu.matcher.kernels.pow_verify import (
    POW_MESSAGE_BYTES,
    leading_zero_bits_batch,
    pack_pow_messages,
    pow_selftest,
)

# ---------------------------------------------------------------- kernel


def _ref_bits(payload: bytes) -> int:
    return count_zero_bits_from_left(hashlib.sha256(payload).digest())


def test_pow_selftest_passes_on_interpret():
    pow_selftest(interpret=True)


@pytest.mark.parametrize("batch", [1, 5, 127, 128, 130])
def test_kernel_matches_hashlib_across_lane_padding_shapes(batch):
    """Batch sizes straddling the 128-lane boundary: padding lanes must
    never leak into real results."""
    rng = np.random.default_rng(batch)
    payloads = [rng.bytes(POW_MESSAGE_BYTES) for _ in range(batch)]
    got = leading_zero_bits_batch(payloads, interpret=True)
    assert got.shape == (batch,)
    assert [int(b) for b in got] == [_ref_bits(p) for p in payloads]


# "banjax-pr26-pow-" + a counter, zero-filled to 52 bytes: found once by
# counting up (2^34 digests for the last); hashlib is the check here
_PAYLOAD_HEAD_BY_ZERO_BITS = {
    0: "62616e6a61782d707232362d706f772d0000000000000000",
    8: "62616e6a61782d707232362d706f772d0000000062010000",
    31: "62616e6a61782d707232362d706f772d01000000cc5010b4",
    32: "62616e6a61782d707232362d706f772d0000000097cc260e",
    33: "62616e6a61782d707232362d706f772d0100000000a8820001",
}


@pytest.mark.parametrize("bits", sorted(_PAYLOAD_HEAD_BY_ZERO_BITS))
def test_kernel_counts_known_zero_bits_across_the_word_boundary(bits):
    """Digests with a known count on both sides of the first digest
    word's end: the rolled rounds' digest words and the live-word masking
    of the count are checked against values, not only random payloads
    (whose counts rarely pass 8)."""
    head = bytes.fromhex(_PAYLOAD_HEAD_BY_ZERO_BITS[bits])
    payload = head.ljust(POW_MESSAGE_BYTES, b"\x00")
    assert _ref_bits(payload) == bits
    got = leading_zero_bits_batch([payload], interpret=True)
    assert got.tolist() == [bits]


def test_kernel_degenerate_payloads():
    """All-zero and all-ones payloads plus near-misses."""
    payloads = [
        b"\x00" * POW_MESSAGE_BYTES,
        b"\xff" * POW_MESSAGE_BYTES,
        b"\x00" * (POW_MESSAGE_BYTES - 1) + b"\x01",
        b"\x80" + b"\x00" * (POW_MESSAGE_BYTES - 1),
    ]
    got = leading_zero_bits_batch(payloads, interpret=True)
    assert [int(b) for b in got] == [_ref_bits(p) for p in payloads]
    assert all(cpu_zero_bits(p) == _ref_bits(p) for p in payloads)


def test_pack_rejects_wrong_length_payloads():
    with pytest.raises(ValueError):
        pack_pow_messages([b"short"])


def test_pack_pads_to_full_lanes():
    words, n = pack_pow_messages([b"\x01" * POW_MESSAGE_BYTES] * 3)
    assert n == 3
    assert words.shape[0] == 16
    assert words.shape[1] % 128 == 0


def test_concurrent_submits_all_get_correct_bits():
    """Leader/follower micro-batching under real thread contention:
    every caller gets its own payload's answer — from the device batch,
    or CPU-inline when the bounded queue refuses it (the HTTP-path
    contract, same as verify_sha_inv's fallback)."""
    from banjax_tpu.challenge.verifier import DeviceUnavailable

    device = DeviceVerifier(batch_max=8, interpret=True)
    rng = np.random.default_rng(7)
    payloads = [rng.bytes(POW_MESSAGE_BYTES) for _ in range(24)]
    results = [None] * len(payloads)

    def work(i):
        try:
            results[i] = device.submit(payloads[i])
        except DeviceUnavailable:
            results[i] = cpu_zero_bits(payloads[i])

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(len(payloads))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [_ref_bits(p) for p in payloads]
    assert device.counters()["lanes_verified"] > 0


def test_selftest_failure_permanently_disables_device(monkeypatch):
    """A kernel that disagrees with hashlib must never verify real
    traffic: the first-use differential trips and the device path stays
    off for the verifier's lifetime."""
    def bad_selftest(interpret=None):
        raise RuntimeError("mismatch")

    # the verifier lazy-imports the selftest from the kernel module, so
    # the patch goes on the source
    monkeypatch.setattr(
        "banjax_tpu.matcher.kernels.pow_verify.pow_selftest", bad_selftest
    )
    device = DeviceVerifier(batch_max=4, interpret=True)
    assert not device.available()
    assert "selftest" in (device.counters()["disabled_reason"] or "")


# --------------------------------------------------------- bounded state


class _Cfg:
    too_many_failed_challenges_interval_seconds = 30
    too_many_failed_challenges_threshold = 3
    challenge_failure_state_max = 0


class _Clock:
    def __init__(self, start_ns=1_700_000_000_000_000_000):
        self.ns = start_ns

    def __call__(self):
        return self.ns


def test_bounded_matches_reference_transitions_exactly():
    """No eviction pressure: every apply() is bit-identical to the
    reference port, including the strictly-greater window restart and
    the exceed-resets-to-0 quirk."""
    cfg = _Cfg()
    clock = _Clock()
    bounded = BoundedFailedChallengeStates(64, now_ns_fn=clock)
    reference = FailedChallengeRateLimitStates()
    ref_clock = {"ns": clock.ns}

    def ref_apply(ip):
        real = time.time_ns
        time.time_ns = lambda: ref_clock["ns"]
        try:
            return reference.apply(ip, cfg)
        finally:
            time.time_ns = real

    steps = [("a", 0), ("a", 1), ("a", 1), ("a", 1),       # exceed at 4th
             ("a", 31), ("b", 0), ("b", 40), ("b", 0)]     # restarts
    for ip, advance_s in steps:
        clock.ns += advance_s * 1_000_000_000
        ref_clock["ns"] = clock.ns
        got = bounded.apply(ip, cfg)
        want = ref_apply(ip)
        assert (got.match_type, got.exceeded) == (want.match_type, want.exceeded)
    assert sorted(bounded.format_states().splitlines()) == sorted(
        reference.format_states().splitlines()
    )


def test_bound_holds_and_spilled_offender_refills_losslessly():
    """Past the cap the LRU evicts; an offender with real evidence
    (hits >= 2) parks in the spill tier and its EXACT (hits, start)
    state comes back on re-entry — the ban lands on the same apply() it
    would have unbounded."""
    cfg = _Cfg()
    clock = _Clock()
    bounded = BoundedFailedChallengeStates(4, now_ns_fn=clock)

    bounded.apply("offender", cfg)       # hits 1
    bounded.apply("offender", cfg)       # hits 2
    for i in range(64):                  # churn the offender out
        bounded.apply(f"churn-{i}", cfg)
    assert len(bounded) <= 4
    assert bounded.counters()["evictions_total"] >= 60
    assert bounded.counters()["spill_writes"] >= 1
    # hits 3 then 4 > 3: the exceed fires exactly as unbounded would
    assert not bounded.apply("offender", cfg).exceeded
    assert bounded.apply("offender", cfg).exceeded
    assert bounded.counters()["spill_refills"] >= 1


def test_spill_priority_keeps_the_stronger_entry():
    """Slot collision: the entry with more hits wins the slot; the
    weaker one is the counted loss.  Exercised directly so the test
    does not depend on finding natural collisions under the LRU."""
    from banjax_tpu.decisions.rate_limit import NumHitsAndIntervalStart

    bounded = BoundedFailedChallengeStates(4)
    mask = bounded._sp_mask
    slot_of = lambda ip: (bounded._fingerprint(ip) >> 17) & mask
    strong = "10.0.0.1"
    weak = None
    for i in range(200_000):
        cand = f"11.{(i >> 16) & 0xFF}.{(i >> 8) & 0xFF}.{i & 0xFF}"
        if slot_of(cand) == slot_of(strong) and cand != strong:
            weak = cand
            break
    assert weak is not None, "no colliding ip found in the search space"

    bounded._spill_put(strong, NumHitsAndIntervalStart(3, 100))
    bounded._spill_put(weak, NumHitsAndIntervalStart(1, 200))
    assert bounded.counters()["spill_drops"] == 1
    kept = bounded._spill_take(strong)
    assert kept is not None and kept.num_hits == 3
    assert bounded._spill_take(weak) is None


def test_one_shot_churners_never_touch_the_spill_table():
    """The sketch gate: distinct one-time failers (the 1M-flood
    population) are evicted without a spill write, so parked offender
    state cannot be displaced by churn volume."""
    cfg = _Cfg()
    bounded = BoundedFailedChallengeStates(8, sketch_width=1 << 16)
    for i in range(512):
        bounded.apply(f"12.0.{(i >> 8) & 0xFF}.{i & 0xFF}", cfg)
    c = bounded.counters()
    assert c["entries"] <= 8
    assert c["gate_skips"] > 0
    assert c["spill_writes"] == 0


def test_factory_dispatches_on_the_config_cap():
    cfg = _Cfg()
    assert isinstance(
        make_failed_challenge_states(cfg), FailedChallengeRateLimitStates
    )
    cfg.challenge_failure_state_max = 100
    bounded = make_failed_challenge_states(cfg)
    assert isinstance(bounded, BoundedFailedChallengeStates)
    assert bounded._max == 100
    with pytest.raises(ValueError):
        BoundedFailedChallengeStates(0)
