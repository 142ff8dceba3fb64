"""The fused program's event feed (PR 34): window events taken from the
(row, rule) pairs and the always-columns the program holds, never from a
reduction over the dense rows x rules bitmap.

Parity is held at the program: the single program's event records and
the window state it leaves equal, bit for bit, what `_apply_step` — the
classic extraction, a nonzero over the dense bitmap — makes of the same
batch from the same state.  The dense bitmap the program returns for
the replay is checked against Python's `re` on the way."""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
from banjax_tpu.matcher import prefilter as PF
from banjax_tpu.matcher import windows as W
from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.matcher.encode import encode_lines
from banjax_tpu.matcher.kernels import fused_match_window as fmw
from banjax_tpu.matcher.rulec import compile_rules
from banjax_tpu.matcher.runner import TpuMatcher
from tests.unit.test_device_windows import make_rule
from tests.unit.test_fused_windows import _key, _mk, _rules_yaml

FILTERED = [rf"/f{c}{c}{c}[0-9]+" for c in "abcdefgh"]
ALWAYS = [r"^GET", r"^POST", r".*", r"^$"]
EV_FIELDS = ("line", "rule", "hits", "start_s", "start_ns")
EV_FLAGS = ("match_type", "exceeded", "seen_ip")


class _Program:
    """One ruleset's fused program pieces on the CPU backend: the plan,
    a window table, and `run`, which puts one batch through the single
    program and through `_apply_step` from copies of the same state."""

    def __init__(self, patterns, capacity=32, n_hosts=1, seed=0,
                 cand_frac=1.0):
        self.patterns = patterns
        comp = compile_rules(patterns, n_shards=1)
        self.comp = comp
        plan = PF.build_plan(
            patterns, byte_classes=(comp.byte_to_class, comp.n_classes)
        )
        assert plan is not None and not plan.unsupported
        self.pf = PF.FusedPrefilter(plan, "xla", cand_frac=cand_frac)
        self.R = len(patterns)
        rng = np.random.default_rng(seed)
        self.dw = W.DeviceWindows(
            [make_rule(f"r{i}", float(rng.integers(1, 4)),
                       int(rng.integers(0, 3))) for i in range(self.R)],
            capacity=capacity,
        )
        self.active = np.ones((n_hosts, self.R), dtype=bool)
        if n_hosts > 1:
            self.active = rng.random((n_hosts, self.R)) < 0.6
        self.state = self.dw._fresh_state()
        self._progs = {}

    def encode(self, rests):
        mat, lens, host_eval = encode_lines(rests, 256)
        assert not host_eval.any()
        return self.comp.byte_to_class[mat].astype(np.int32), lens

    def program(self, Bp, L_p):
        key = (Bp, L_p)
        if key not in self._progs:
            plan = self.pf.plan
            na = plan.n_always
            self._progs[key] = fmw.build_single_program(
                self.pf, self.dw, self.active, self.R, Bp, L_p,
                f_idx=jnp.asarray(plan.f_idx, jnp.int32),
                a_idx=jnp.asarray(plan.a_idx, jnp.int32),
                aw=jnp.asarray(np.asarray(
                    plan.stage1.always_match[:na], dtype=np.uint8)),
                ae=jnp.asarray(np.asarray(
                    plan.stage1.empty_only[:na], dtype=np.uint8)),
                scan_fn=fmw.window_scan(True),
            )
        return self._progs[key]

    def run(self, rests, slots, ts_ns, host_idx=None, live=None,
            maintenance=None):
        """→ (flags, K, P, E).  Asserts the whole parity and, where the
        program committed, carries its state on to the next batch.
        `maintenance` = (evicted slots, restore rows [5, _restore_room(Bp)]):
        the program carries them as operands, the reference runs the
        separate steps in front of its apply."""
        B = len(rests)
        cls_ids, lens = self.encode(rests)
        combined, Bp, L_p = self.pf._assemble(cls_ids, lens)
        fn, K, P, E = self.program(Bp, L_p)

        def pad(a, dt=np.int32):
            out = np.zeros(Bp, dtype=dt)
            out[:B] = a
            return jnp.asarray(out)

        ts_s, ts_n = W.split_ns(np.asarray(ts_ns, dtype=np.int64))
        host_idx = np.zeros(B, np.int32) if host_idx is None else host_idx
        live = np.ones(B, np.uint8) if live is None else live
        args = (pad(host_idx), pad(slots), pad(ts_s), pad(ts_n))
        live_p = pad(live, np.uint8)
        copy = lambda: jax.tree_util.tree_map(jnp.array, self.state)  # noqa: E731
        # nothing is queued on this table: all padding
        ev_slots, restore_rows = self.dw._run_maintenance_locked(
            carry_rows=Bp)
        if maintenance is not None:
            ev_slots[: len(maintenance[0])] = maintenance[0]
            restore_rows = maintenance[1]

        def start():
            """The state the separate steps leave in front of the chunk."""
            if maintenance is None:
                return copy()
            return W._restore_step(
                W._evict_step(copy(), jnp.asarray(ev_slots)),
                jnp.asarray(restore_rows))

        before = jax.tree_util.tree_map(np.asarray, start())

        new_state, chain, buf, bits_dev = fn(
            copy(), jnp.int32(1), jnp.asarray(combined), jnp.int32(B),
            args[0], args[1], args[2], args[3], live_p, ev_slots,
            restore_rows,
        )
        buf = np.asarray(buf)
        bits = np.asarray(bits_dev)
        flags = np.frombuffer(buf[:16].tobytes(), dtype="<i4")

        # the dense bitmap handed to the replay: what `re` says, rows
        # past n_real empty (past the candidate capacity stage 2 never
        # saw the excess lines, and the replay matches again single-stage)
        want_bits = np.zeros((Bp, self.R), dtype=np.uint8)
        for i, rest in enumerate(rests):
            for r, pat in enumerate(self.patterns):
                want_bits[i, r] = re.search(pat, rest) is not None
        assert flags[1] > K or np.array_equal(bits, want_bits)

        if not flags[0]:
            # an overflow commits nothing at all (the table's
            # maintenance is no part of the chunk's commit: it applies)
            after = jax.tree_util.tree_map(np.asarray, new_state)
            for f in ("hits", "start_s", "start_ns", "key_gen", "slot_gen",
                      "ip_seen"):
                assert np.array_equal(getattr(after, f), getattr(before, f)), f
            assert int(chain) == 0
            return flags, K, P, E

        ref_state, out = W._apply_step(
            start(), jnp.asarray(bits * np.asarray(live_p)[:, None]),
            jnp.asarray(self.active), *args,
            self.dw._limits, self.dw._iv_s, self.dw._iv_ns,
            n_rules=self.R, max_events=E,
        )
        for f in ("hits", "start_s", "start_ns", "key_gen", "slot_gen",
                  "ip_seen"):
            assert np.array_equal(
                np.asarray(getattr(new_state, f)),
                np.asarray(getattr(ref_state, f)),
            ), f
        off = 16 + 4 * P + Bp * self.pf._na8
        # head, event tail, then the hits of each factor bucket (PR 41)
        assert len(buf) == off + 23 * E + 4 * self.pf.n_buckets
        alive = np.asarray(out["rule"]) >= 0
        assert flags[3] == alive.sum()
        for f in EV_FIELDS:
            got = np.frombuffer(buf[off : off + 4 * E].tobytes(), dtype="<i4")
            assert np.array_equal(got, np.asarray(out[f])), f
            off += 4 * E
        for f in EV_FLAGS:
            want = np.asarray(out[f]).astype(np.uint8)
            assert np.array_equal(buf[off : off + E], want), f
            off += E
        self.state = new_state
        return flags, K, P, E


def _rests(rng, n, hit_rate=0.3, empty_rate=0.0):
    """Request strings of mixed lengths: some carry one or several of the
    filtered rules' words, some are empty."""
    out = []
    for _ in range(n):
        if rng.random() < empty_rate:
            out.append("")
            continue
        method = "GET" if rng.random() < 0.7 else "POST"
        words = [
            f"/f{c}{c}{c}{rng.integers(0, 99)}" for c in "abcdefgh"
            if rng.random() < hit_rate / 2
        ]
        filler = "x" * int(rng.integers(0, 120))
        out.append(f"{method} " + " ".join(words) + f" /{filler} HTTP/1.1")
    return out


def _batch(rng, n, n_slots, t0):
    slots = rng.integers(0, n_slots, n).astype(np.int32)
    ts = t0 + np.sort(rng.integers(0, 2_000_000_000, n)).astype(np.int64)
    return slots, ts


BASE = 1_700_000_000 * 1_000_000_000

CASES = {
    # name: (patterns, kwargs of the scenario)
    "always_and_filtered": (FILTERED + ALWAYS, {}),
    "always_and_filtered_b": (ALWAYS[:2] + FILTERED, {"seed": 11}),
    "always_only": (ALWAYS, {"empty_rate": 0.1}),
    "pairs_only": (FILTERED, {"hit_rate": 0.4}),
    "inactive_rules_per_host": (FILTERED + ALWAYS, {"n_hosts": 3}),
    "live_mask_with_holes": (FILTERED + ALWAYS, {"live_rate": 0.6}),
    "rows_short_of_the_bucket": (FILTERED + ALWAYS, {"n": 37}),
    "one_slot_on_many_lines": (FILTERED + ALWAYS[:3], {"n_slots": 2}),
    "length_order_against_caller_order": (
        FILTERED + ALWAYS[:2], {"lengths": "descending", "hit_rate": 0.4}),
    "evicted_and_restored_slot": (FILTERED + ALWAYS[:2], {"evict": True}),
    "empty_lines_and_empty_only": (
        FILTERED + ALWAYS, {"empty_rate": 0.3, "n_slots": 3}),
    "everything_at_256_rows": (
        FILTERED + ALWAYS,
        {"n": 200, "n_hosts": 4, "live_rate": 0.8, "empty_rate": 0.05,
         "n_slots": 9, "evict": True, "hit_rate": 0.1}),
}


@pytest.mark.parametrize("name", list(CASES))
def test_fused_events_equal_the_dense_extraction(name):
    patterns, kw = CASES[name]
    seed = kw.get("seed", sum(map(ord, name)))
    rng = np.random.default_rng(seed)
    prog = _Program(patterns, n_hosts=kw.get("n_hosts", 1), seed=seed)
    n = kw.get("n", 60)
    n_slots = kw.get("n_slots", 20)
    t0 = BASE
    n_events = 0
    for step in range(3):
        rests = _rests(rng, n, kw.get("hit_rate", 0.3),
                       kw.get("empty_rate", 0.0))
        if kw.get("lengths") == "descending":
            rests.sort(key=len, reverse=True)
        slots, ts = _batch(rng, n, n_slots, t0)
        t0 = int(ts[-1]) + 1
        host_idx = rng.integers(0, prog.active.shape[0], n).astype(np.int32)
        live = (rng.random(n) < kw.get("live_rate", 1.0)).astype(np.uint8)
        maintenance = None
        if kw.get("evict") and step:
            # a slot loses its keys and gets some back, as the maintenance
            # run queues them between two batches
            s = int(slots[0])
            rows = np.full((5, W._RESTORE_CHUNK), -1, np.int32)
            rows[0, :] = prog.dw.capacity
            rows[1, :] = prog.dw.capacity * prog.R
            rows[:, 0] = (s, s * prog.R + 1, 2, int(t0 // 10**9) - 1, 5)
            maintenance = ([s], rows)
        flags, K, P, E = prog.run(rests, slots, ts, host_idx, live,
                                  maintenance)
        assert flags[0] == 1
        n_events += int(flags[3])
    assert n_events > 0
    if "pairs_only" in name:
        assert prog.pf.plan.n_always == 0
    if "always_only" in name:
        assert prog.pf._n_filt == 0


def test_pairs_exactly_at_capacity_commit_fused():
    """n_pairs == P is not an overflow: every candidate slot taken, every
    candidate matching every filtered rule."""
    pats = FILTERED[:4]
    prog = _Program(pats, cand_frac=0.125)
    _, K = prog.pf.capacities(64)
    P = prog.pf.pair_capacity(64, K)
    assert P == K * len(pats)
    hot = "GET " + " ".join(f"/f{c}{c}{c}1" for c in "abcd") + " HTTP/1.1"
    rests = ["GET /quiet HTTP/1.1"] * 64
    for i in range(K):
        rests[5 + 7 * i] = hot + "y" * i
    rng = np.random.default_rng(5)
    slots, ts = _batch(rng, 64, 6, BASE)
    flags, k, p, _ = prog.run(rests, slots, ts)
    assert (k, p) == (K, P)
    assert list(flags[:3]) == [1, K, P] and flags[3] == P
    # one pair more and the program commits nothing
    rests[0] = hot
    flags, *_ = prog.run(rests, slots, ts + 10**10)
    assert flags[0] == 0 and flags[1] == K + 1


def test_events_past_a_lowered_cap_commit_nothing(monkeypatch):
    """Rows x always-columns over _MAX_EVENT_CAPACITY: the program keeps
    what fits, counts all that fire and gates its commit."""
    monkeypatch.setattr(PF, "_MAX_EVENT_CAPACITY", 128)
    prog = _Program(FILTERED + ALWAYS[:3])
    rng = np.random.default_rng(9)
    rests = _rests(rng, 64)
    slots, ts = _batch(rng, 64, 12, BASE)
    live = np.zeros(64, np.uint8)
    live[:30] = 1           # 30 rows x (^GET|^POST, .*) = 60 always events
    flags, _, _, E = prog.run(rests, slots, ts, live=live)
    assert E == 128 and flags[0] == 1 and 60 <= flags[3] <= 128
    flags, *_ = prog.run(rests, slots, ts + 10**10)   # all 64 rows live
    assert flags[0] == 0 and flags[3] > 128


def test_event_ordinal_guard():
    """The (line, rule) ordinal is int32: a batch whose rows x rules pass
    2^31 is refused where the program is traced."""
    state = W.DeviceWindowState(*[jnp.zeros(4, jnp.int32)] * 5,
                                ip_seen=jnp.zeros(4, bool))
    z = jnp.zeros(4, jnp.int32)
    rows = jax.ShapeDtypeStruct((1 << 16,), jnp.int32)
    with pytest.raises(ValueError, match="event ordinal"):
        jax.eval_shape(
            lambda s, r: W._apply_events(
                s, z, z, z == 0, r, r, r, z, z, z, n_rules=1 << 15),
            state, rows,
        )


# ---- the pair extraction alone ------------------------------------------


@pytest.mark.parametrize("n_filt,K,P,density,stray", [
    (37, 16, 64, 0.05, False),     # nf8 = 5: one byte over a 32-bit word
    (1000, 64, 128, 0.001, True),  # nf8 = 125, the crs1k width
    (10, 8, 4, 0.2, True),         # n_pairs > P: the head of the stream
    (33, 8, 300, 0.3, True),       # P past every set bit
    (64, 8, 16, 0.0, False),       # nothing set
    (9999, 16, 256, 0.0005, True),  # nf8 = 1250, not a multiple of 4
])
def test_pairs_from_packed_words_equal_unpackbits_nonzero(
    n_filt, K, P, density, stray
):
    """Same pairs in the same (candidate slot, column) order, the true
    n_pairs past P, pad bits masked — against np.unpackbits + np.nonzero."""
    import types

    nf8 = -(-n_filt // 8)
    R8 = 8 * nf8
    rng = np.random.default_rng(n_filt + K)
    m = (rng.random((K, R8)) < density).astype(np.uint8)
    m[:, n_filt:] = 0
    packed = np.packbits(m, axis=1)
    if stray and R8 > n_filt:
        packed[K // 2, -1] |= 1          # column R8 - 1: a pad bit
    caller = rng.permutation(4 * K)[:K].astype(np.int32)
    fake = types.SimpleNamespace(_n_filt=n_filt, _nf8=nf8)
    pairs, n_pairs, bits = jax.jit(
        lambda c: PF.FusedPrefilter.pairs_from_core(fake, c, K, P)
    )({"m2p": jnp.asarray(packed), "idx_caller_k": jnp.asarray(caller)})
    dense = np.unpackbits(packed, axis=1)
    dense[:, n_filt:] = 0
    k, col = np.nonzero(dense)
    ref = caller[k] * R8 + col
    want = np.full(P, -1, dtype=np.int32)
    want[: min(P, len(ref))] = ref[:P]
    assert int(n_pairs) == len(ref)
    assert np.array_equal(np.asarray(pairs), want)
    assert np.array_equal(np.asarray(bits), dense)


# ---- the three overflow routes, through the matcher --------------------


@pytest.mark.parametrize("cause", ["candidates", "pairs", "events"])
def test_overflow_routes_replay_classically_to_the_same_state(
    cause, monkeypatch
):
    """Each overflow commits nothing in the fused program and replays
    through the classic path: results, bans and the window table's view
    equal the serial reference's."""
    pats = [rf"GET /multi.*{c}" for c in "abcdefghij"]
    ov = {"matcher_batch_lines": 64, "matcher_prefilter_cand_frac": 1.0}
    hot_rate = 1.0
    if cause == "candidates":
        ov["matcher_prefilter_cand_frac"] = 1.0 / 64
    elif cause == "events":
        monkeypatch.setattr(PF, "_MAX_EVENT_CAPACITY", 64)
        pats = pats + [r".*"]
        hot_rate = 0.05
    now = time.time()
    rng = np.random.default_rng(3)
    lines = []
    for i in range(256):
        path = "/multi-abcdefghij" if rng.random() < hot_rate else "/idle"
        lines.append(
            f"{now + i * 0.0005:.6f} 10.7.{i % 11}.1 GET h.com GET {path}{i} "
            "HTTP/1.1 ua -"
        )
    y = _rules_yaml(pats)
    cpu, cb = _mk(CpuMatcher, y)
    tpu, tb = _mk(TpuMatcher, y, matcher_device_windows=True, **ov)
    assert tpu.describe()["fused_protocol"] == "single-kernel"
    want = [cpu.consume_line(l, now + 1) for l in lines]
    got = tpu.consume_lines(lines, now + 1)

    assert [_key(a) for a in want] == [_key(b) for b in got]
    assert cb.bans == tb.bans and len(cb.bans) > 0
    assert cb.regex_ban_logs == tb.regex_ban_logs
    assert tpu._fw_pipeline.overflow_causes[cause] > 0
    assert tpu._fw_pipeline.fused_batches == 0 or cause == "events"
    assert cpu.rate_limit_states.format_states() == \
        tpu.device_windows.format_states()


# ---- the counter the feed brings ----------------------------------------


@pytest.mark.parametrize("patterns,only", [
    ([r"^GET", r"^POST", r".*challengeme.*"], None),   # the shipped rules
    ([rf"GET /only[0-9]*{c}" for c in "abc"], "pairs"),
    ([r"^GET", r"^POST"], "always"),
])
def test_event_feed_counter_splits_the_fused_events(patterns, only):
    """banjax_fused_event_feed_total{source}: its two series sum to the
    events the fused programs committed, and each source reads 0 where
    the plan has no such column."""
    from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
    from banjax_tpu.decisions.rate_limit import FailedChallengeRateLimitStates
    from banjax_tpu.obs.exposition import parse_text_format, render_prometheus

    now = time.time()
    lines = []
    for i in range(96):
        path = ("/challengeme" if i % 9 == 0 else
                f"/only{i}abc" if i % 4 == 0 else f"/page{i}")
        method = "POST" if i % 5 == 0 else "GET" if i % 7 else "HEAD"
        lines.append(
            f"{now + i * 0.001:.6f} 10.3.{i % 13}.1 {method} h.com {method} "
            f"{path} HTTP/1.1 ua -"
        )
    y = _rules_yaml(patterns, hits=50)
    cpu, _ = _mk(CpuMatcher, y)
    tpu, _ = _mk(TpuMatcher, y, matcher_device_windows=True,
                 matcher_batch_lines=32, matcher_prefilter_cand_frac=1.0)
    try:
        fw = tpu._fw_pipeline
        assert fw is not None
        want = [cpu.consume_line(l, now + 1) for l in lines]
        tpu.consume_lines(lines, now + 1)
        n_events = sum(len(r.rule_results) for r in want)
        assert fw.fallback_batches == 0 and n_events > 0
        assert fw.event_feed["pairs"] + fw.event_feed["always"] == n_events
        assert tpu.device_windows.device_events == n_events
        for source in ("pairs", "always"):
            if only is None:
                assert fw.event_feed[source] > 0
            elif source != only:
                assert fw.event_feed[source] == 0
        fams = parse_text_format(render_prometheus(
            DynamicDecisionLists(start_sweeper=False), RegexRateLimitStates(),
            FailedChallengeRateLimitStates(), matcher=tpu,
        ))
        got = {
            labels["source"]: v
            for _, labels, v in fams["banjax_fused_event_feed_total"]["samples"]
        }
        assert got == {k: float(v) for k, v in fw.event_feed.items()}
    finally:
        tpu.close()
