"""Literal prefilter: factor soundness + two-stage bitmap equivalence.

The invariant under test (matcher/prefilter.py): for every pattern, every
match of a branch contains its required factor's classes consecutively, so
gating stage 2 on "any factor hit" never drops a true match — the two-stage
bitmap equals the single-stage one bit for bit.
"""

import random
import re

import numpy as np
import pytest

from banjax_tpu.matcher import nfa_jax
from banjax_tpu.matcher.encode import encode_for_match
from banjax_tpu.matcher.prefilter import PrefilterMatcher, build_plan
from banjax_tpu.matcher.rulec import (
    compile_rule,
    compile_rules,
    required_factors,
)


def factor_to_str(factor):
    """Pick one concrete byte per class (for eyeballing/containment checks)."""
    return "".join(chr(min(b for b in range(256) if (p.cs >> b) & 1))
                   for p in factor)


class TestRequiredFactors:
    def test_plain_literal(self):
        f = required_factors(compile_rule(r"GET /wp-login\.php"))
        assert f is not None and len(f) == 1
        assert factor_to_str(f[0]) in "GET /wp-login.php"

    def test_alternation_has_factor_per_branch(self):
        f = required_factors(compile_rule(r"(GET|POST) /xmlrpc\.php"))
        assert f is not None and len(f) == 2

    def test_runs_break_at_selfloop(self):
        # `admin[a-z]+panel` — the + position may repeat, so no factor may
        # span it; both sides are valid factors though
        f = required_factors(compile_rule(r"admin[a-z]+panel"))
        assert f is not None
        assert factor_to_str(f[0]) in ("admin", "panel")

    def test_wide_class_blocks_factor(self):
        assert required_factors(compile_rule(r"[a-z]{8}")) is None
        assert required_factors(compile_rule(r"ab[0-9]cd")) is None  # runs of 2

    def test_case_fold_pairs_allowed(self):
        f = required_factors(compile_rule(r"(?i)sqlmap"))
        assert f is not None
        assert factor_to_str(f[0]).lower() in "sqlmap"

    def test_always_match_rule_has_no_factor(self):
        assert required_factors(compile_rule(r".*")) is None

    def test_truncation_keeps_middle(self):
        f = required_factors(compile_rule("a" * 30), max_len=8)
        assert f is not None and len(f[0]) == 8

    def test_factor_is_contained_in_random_matches(self):
        """Generative soundness: synthesize matches, assert factor presence."""
        rng = random.Random(5)
        patterns = [
            r"GET /admin/[a-z]+\.php", r"(?i)nikto|nessus",
            r"POST /login[0-9]{1,3}", r"^HEAD /x\.cgi$",
        ]
        for pat in patterns:
            prog = compile_rule(pat)
            factors = required_factors(prog)
            assert factors is not None, pat
            for br, factor in zip(prog.branches, factors):
                # synthesize a concrete match for this branch
                s = ""
                for p in br.positions:
                    b = min(b for b in range(256) if (p.cs >> b) & 1)
                    s += chr(b) * (1 + (2 if p.loop and rng.random() < 0.5 else 0))
                assert re.search(pat, s), (pat, s)
                # the factor's classes must appear consecutively somewhere
                ok = any(
                    all((factor[j].cs >> ord(s[k + j])) & 1 for j in range(len(factor)))
                    for k in range(len(s) - len(factor) + 1)
                )
                assert ok, (pat, s, factor_to_str(factor))


class TestTwoStageEquivalence:
    def _bench_rules_and_lines(self, n_rules=60, n_lines=500, seed=9):
        from banjax_tpu.scenarios import synth

        patterns = synth.generate_rules(n_rules, seed=seed)
        lines = synth.generate_lines(n_lines, patterns, seed=seed + 1,
                                     attack_rate=0.3)
        return patterns, lines

    def test_plan_builds_for_crs_shaped_rules(self):
        patterns, _ = self._bench_rules_and_lines()
        plan = build_plan(patterns)
        assert plan is not None
        assert plan.stage1.n_words < plan.stage2.n_words
        # stage 1 packs word-aligned so the kernel drops the cross-word
        # carry; factors are <= 12 positions so this must always hold
        assert plan.stage1.carry_free
        assert plan.n_always + len(plan.f_idx) == len(
            [p for i, p in enumerate(patterns) if i not in plan.unsupported]
        )

    @pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
    def test_bitmap_equals_single_stage(self, backend):
        patterns, lines = self._bench_rules_and_lines()
        plan = build_plan(patterns)
        assert plan is not None
        pf = PrefilterMatcher(plan, backend, max_len=128, max_batch=256)
        bits, host_eval = pf.match_bits(lines)
        assert not host_eval.any()

        compiled = compile_rules(patterns)
        params = nfa_jax.match_params(compiled)
        cls_ids, lens, he = encode_for_match(compiled, lines, 128)
        want = np.asarray(
            nfa_jax.match_batch(params, cls_ids, lens, compiled.n_rules)
        )
        for rid in plan.unsupported:
            want[:, rid] = 0  # host-fallback columns are zero in both paths
        assert (bits == want).all()

    def test_default_rule_lands_in_always_group(self):
        patterns = [r".*", r"GET /wp-login\.php", r"POST /xmlrpc\.php",
                    r"/\.env", r"(?i)sqlmap"]
        plan = build_plan(patterns, min_filterable_fraction=0.5)
        assert plan is not None
        assert 0 in set(plan.a_idx)
        bits, _ = PrefilterMatcher(plan, "xla", max_len=64).match_bits(
            ["GET x.com GET / HTTP/1.1"]
        )
        assert bits[0, 0] == 1  # .* matches everything, no factor needed

    def test_unprofitable_ruleset_returns_none(self):
        assert build_plan([r".*", r"[a-z]+", r"\d+"]) is None


def _shared_plan(patterns, **plan_kw):
    """compiled + plan with shared byte classes (FusedPrefilter contract)."""
    compiled = compile_rules(patterns, n_shards="auto")
    plan = build_plan(
        patterns,
        byte_classes=(compiled.byte_to_class, compiled.n_classes),
        **plan_kw,
    )
    return compiled, plan


def _single_stage_oracle(compiled, plan, lines, max_len=128):
    """(cls_ids, lens, host_eval, want-bitmap) with unsupported columns
    zeroed — the invariant every fused path must reproduce."""
    params = nfa_jax.match_params(compiled)
    cls_ids, lens, he = encode_for_match(compiled, lines, max_len)
    want = np.asarray(
        nfa_jax.match_batch(params, cls_ids, lens, compiled.n_rules)
    )
    for rid in plan.unsupported:
        want[:, rid] = 0
    return cls_ids, lens, he, want


class TestFusedFuzz:
    """Generative soundness sweep: random RE2-subset rulesets and random
    line streams through FusedPrefilter vs the single-stage oracle. Catches
    factor-extraction unsoundness (a factor that is not actually required
    would silently drop matches) across pattern shapes no hand-written
    case enumerates."""

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_random_rulesets(self, seed):
        from banjax_tpu.matcher.prefilter import FusedPrefilter

        rng = random.Random(seed * 7919)
        words = ["wp", "admin", "login", "env", "cgi", "bak", "shell", "sql"]

        def gen_pattern():
            kind = rng.random()
            w1, w2 = rng.choice(words), rng.choice(words)
            if kind < 0.3:
                return rf"GET /{w1}/{w2}\.php"
            if kind < 0.5:
                return rf"({w1.upper()}|{w2}) /[a-z0-9]+/{w1}"
            if kind < 0.65:
                return rf"(?i){w1}{w2}[0-9]{{1,3}}"
            if kind < 0.75:
                return rf"^{w1} .*{w2}$"
            if kind < 0.85:
                return rf"/{w1}\.(php|asp|jsp)\?x={rng.randint(0, 9)}"
            if kind < 0.95:
                return rf"{w1}[a-z]*{w2}+"
            return rng.choice([r".*", rf"[a-z]{{{rng.randint(2, 6)}}}"])

        patterns = [gen_pattern() for _ in range(40)]
        compiled, plan = _shared_plan(patterns, min_filterable_fraction=0.1)
        if plan is None:
            pytest.skip("ruleset draw not filterable")

        # line stream: benign noise + substrings assembled from the same
        # vocabulary (maximizes near-miss factor hits)
        lines = []
        for _ in range(300):
            n = rng.randint(0, 5)
            parts = [rng.choice(words + ["GET", "/", ".php", "xyz", "123"])
                     for _ in range(n)]
            sep = rng.choice(["", " ", "/"])
            lines.append(sep.join(parts))
        cls_ids, lens, _, want = _single_stage_oracle(
            compiled, plan, lines, max_len=96
        )
        fp = FusedPrefilter(plan, "xla", cand_frac=1.0, pair_frac=1.0)
        got = fp.match_bits_encoded(cls_ids, lens)
        np.testing.assert_array_equal(got, want)
        # oracle the oracle: spot-check against Python re
        import re as _re

        for j in rng.sample(range(len(patterns)), 8):
            if j in plan.unsupported or not compiled.device_ok[j]:
                continue
            rx = _re.compile(patterns[j])
            for i in rng.sample(range(len(lines)), 20):
                if lens[i] < len(lines[i]):  # over-length: host path
                    continue
                assert bool(got[i, j]) == bool(rx.search(lines[i])), (
                    patterns[j], lines[i]
                )


class TestFusedPrefilter:
    """The single-device-call two-stage pipeline (FusedPrefilter): shared
    byte classes, on-device gate/compaction, sparse matched-row output."""

    def _plan(self, patterns):
        return _shared_plan(patterns)

    def _oracle(self, compiled, plan, lines, max_len=128):
        return _single_stage_oracle(compiled, plan, lines, max_len)

    @pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
    def test_parity_with_single_stage(self, backend):
        from banjax_tpu.matcher.prefilter import FusedPrefilter

        from banjax_tpu.scenarios import synth

        patterns = synth.generate_rules(60, seed=9)
        lines = synth.generate_lines(300, patterns, seed=10, attack_rate=0.3)
        compiled, plan = self._plan(patterns)
        assert plan is not None
        cls_ids, lens, he, want = self._oracle(compiled, plan, lines)
        assert not he.any()
        fp = FusedPrefilter(plan, backend, cand_frac=1.0, pair_frac=1.0)
        bits = fp.match_bits_encoded(cls_ids, lens)
        np.testing.assert_array_equal(bits, want)

    def test_always_rules_and_empty_lines(self):
        from banjax_tpu.matcher.prefilter import FusedPrefilter

        patterns = [r".*", r"^$", r"GET /wp-login\.php", r"/xmlrpc\.php",
                    r"/\.env", r"(?i)sqlmap", r"POST /login[0-9]+"]
        lines = ["", "GET x.com GET /wp-login.php -", "plain benign line",
                 "POST a.b POST /login77 -", "SQLMAP probe"]
        compiled, plan = self._plan(patterns)
        assert plan is not None and plan.n_always >= 2
        cls_ids, lens, he, want = self._oracle(compiled, plan, lines, 64)
        fp = FusedPrefilter(plan, "xla")
        bits = fp.match_bits_encoded(cls_ids, lens)
        np.testing.assert_array_equal(bits, want)

    @pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
    def test_short_batch_rides_the_wider_program_already_built(self, backend):
        """A partial batch of short lines meets a line-length class no full
        batch does; a first use is a build in the hot path, so it runs on
        the narrowest wider program of its row bucket — same bits — and a
        new program is built only where none holds the batch."""
        from banjax_tpu.matcher.prefilter import FusedPrefilter

        from banjax_tpu.scenarios import synth

        patterns = synth.generate_rules(40, seed=21)
        lines = synth.generate_lines(200, patterns, seed=22, attack_rate=0.3)
        compiled, plan = self._plan(patterns)
        assert plan is not None
        fp = FusedPrefilter(plan, backend, cand_frac=1.0, pair_frac=1.0)
        short = [ln[:20] for ln in lines[:50]]
        cls_s, lens_s, _, want_s = self._oracle(compiled, plan, short)
        np.testing.assert_array_equal(fp.match_bits_encoded(cls_s, lens_s),
                                      want_s)
        (key_short,) = fp._fns  # nothing wider existed: built as asked
        cls_l, lens_l, _, want_l = self._oracle(compiled, plan, lines[:50])
        np.testing.assert_array_equal(fp.match_bits_encoded(cls_l, lens_l),
                                      want_l)
        key_long = max(fp._fns, key=lambda k: k[1])
        assert key_long[0] == key_short[0] and key_long[1] > key_short[1]
        assert len(fp._fns) == 2
        mid = [ln[:50] for ln in lines[50:100]]  # a class between the two
        cls_m, lens_m, _, want_m = self._oracle(compiled, plan, mid)
        assert key_short[1] < -(-int(lens_m.max()) // 32) * 32 < key_long[1]
        np.testing.assert_array_equal(fp.match_bits_encoded(cls_m, lens_m),
                                      want_m)
        assert len(fp._fns) == 2  # rode key_long
        _, Bp, L_p = fp._assemble(cls_m, lens_m, fp._fns)
        assert (Bp, L_p) == key_long
        np.testing.assert_array_equal(fp.match_bits_encoded(cls_s, lens_s),
                                      want_s)
        assert len(fp._fns) == 2  # an exact fit is still taken first

    def test_unpacked_input_path_parity(self):
        """The plain-int32 input layout (used when a byte partition doesn't
        fit uint8) must match the packed default bit-for-bit."""
        from banjax_tpu.matcher.prefilter import FusedPrefilter

        from banjax_tpu.scenarios import synth

        patterns = synth.generate_rules(30, seed=12)
        lines = synth.generate_lines(200, patterns, seed=13, attack_rate=0.2)
        compiled, plan = self._plan(patterns)
        assert plan is not None
        cls_ids, lens, _, want = self._oracle(compiled, plan, lines)
        fp = FusedPrefilter(plan, "xla", cand_frac=1.0, pair_frac=1.0)
        assert fp._pack_input  # packed is the default on LE hosts
        packed = fp.match_bits_encoded(cls_ids, lens)
        fp2 = FusedPrefilter(plan, "xla", cand_frac=1.0, pair_frac=1.0)
        fp2._pack_input = False
        unpacked = fp2.match_bits_encoded(cls_ids, lens)
        np.testing.assert_array_equal(packed, want)
        np.testing.assert_array_equal(unpacked, want)

    def test_overflow_raises(self):
        from banjax_tpu.matcher.prefilter import (
            FusedPrefilter,
            PrefilterOverflow,
        )

        patterns = [r"GET /wp-login\.php", r"/xmlrpc\.php", r"/\.env"]
        compiled, plan = self._plan(patterns)
        assert plan is not None
        # every line matches → matched rows exceed E = K/4
        lines = ["GET x GET /wp-login.php -"] * 256
        cls_ids, lens, _, _ = self._oracle(compiled, plan, lines, 64)
        fp = FusedPrefilter(plan, "xla", cand_frac=1.0)
        with pytest.raises(PrefilterOverflow):
            fp.match_bits_encoded(cls_ids, lens)

    def test_submit_collect_pipeline(self):
        from banjax_tpu.matcher.prefilter import FusedPrefilter

        from banjax_tpu.scenarios import synth

        patterns = synth.generate_rules(40, seed=3)
        compiled, plan = self._plan(patterns)
        assert plan is not None
        fp = FusedPrefilter(plan, "xla", cand_frac=1.0, pair_frac=1.0)
        batches = [
            synth.generate_lines(100, patterns, seed=s, attack_rate=0.2)
            for s in (1, 2, 3)
        ]
        encoded = [self._oracle(compiled, plan, b) for b in batches]
        pending = [fp.submit(cls, lens) for cls, lens, _, _ in encoded]
        for p, (_, _, _, want) in zip(pending, encoded):
            np.testing.assert_array_equal(fp.collect(p), want)

    def test_runner_overflow_falls_back_single_stage(self):
        """TpuMatcher output is unchanged when the fused prefilter
        overflows (adversarial all-matching traffic)."""
        from banjax_tpu.config.schema import Config, RegexWithRate
        from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
        from banjax_tpu.decisions.static_lists import StaticDecisionLists
        from banjax_tpu.matcher.runner import TpuMatcher
        from tests.mock_banner import MockBanner

        rules = [
            RegexWithRate.from_yaml_dict(
                {"rule": f"r{i}", "regex": rx, "interval": 10,
                 "hits_per_interval": 10**6, "decision": "nginx_block"}
            )
            for i, rx in enumerate(
                [r"GET /wp-login\.php", r"/xmlrpc\.php", r"/\.env"]
            )
        ]
        now = 1700000000.0
        lines = [
            f"{now} 1.2.3.{i % 16} GET x.com GET /wp-login.php HTTP/1.1"
            for i in range(200)
        ]

        def run(prefilter):
            cfg = Config(
                regexes_with_rates=rules, matcher_backend="xla",
                matcher_prefilter=prefilter, matcher_batch_lines=256,
            )
            m = TpuMatcher(
                cfg, MockBanner(), StaticDecisionLists(cfg),
                RegexRateLimitStates(),
            )
            if prefilter and m._prefilter is not None:
                # force a tiny matched-row capacity so the batch overflows
                m._prefilter.cand_frac = 1.0 / 64
            return m.consume_lines(lines, now_unix=now)

        with_pf, without_pf = run(True), run(False)
        for a, b in zip(with_pf, without_pf):
            assert [r.rule_name for r in a.rule_results] == [
                r.rule_name for r in b.rule_results
            ]


class TestFactorMerging:
    """Teddy-style equal-length superimposition (prefilter._merge_factors)."""

    def _factors(self, pats):
        distinct = {}
        for pat in pats:
            fs = required_factors(compile_rule(pat))
            assert fs is not None, pat
            for f in fs:
                distinct.setdefault(tuple(p.cs for p in f), f)
        return list(distinct.values())

    def test_members_subset_of_bucket(self):
        """Every original factor maps into some bucket position-wise:
        same length, member class ⊆ merged class — the soundness
        precondition ("bucket missed ⟹ member absent")."""
        from banjax_tpu.matcher.prefilter import _merge_factors

        pats = [rf"GET /admin-{w}/x\.php" for w in
                ("alpha", "bravo", "civic", "delta", "eagle")]
        factors = _merge_factors(self._factors(pats), max_merge=8)
        originals = self._factors(pats)
        for f in originals:
            assert any(
                len(m) == len(f)
                and all(f[i].cs & ~m[i].cs == 0 for i in range(len(f)))
                for m in factors
            ), f
        # the five same-shape factors actually share buckets
        assert len(factors) < len(originals)

    def test_unequal_lengths_never_merge(self):
        from banjax_tpu.matcher.prefilter import _merge_factors

        factors = self._factors([r"abcdef", r"abcdefgh"])
        merged = _merge_factors(factors, max_merge=8)
        assert sorted(len(m) for m in merged) == sorted(
            len(f) for f in factors
        )

    def test_sel_budget_stops_wide_merges(self):
        """(?i) case-pair factors OR into wide classes; the sel guard must
        stop the bucket before it covers most of the alphabet."""
        from banjax_tpu.matcher.prefilter import _merge_factors, _pos_prob

        pats = [rf"(?i){a}{b}{c}scan" for a in "abcdef" for b in "klmnop"
                for c in "uvwxyz"]
        merged = _merge_factors(self._factors(pats), max_merge=64,
                                sel_max=1e-5)
        for m in merged:
            sel = 1.0
            for p in m:
                sel *= _pos_prob(p.cs)
            assert sel <= 1e-5

    def test_merge_disabled_is_identity(self):
        from banjax_tpu.matcher.prefilter import _merge_factors

        factors = self._factors([r"abcdef", r"uvwxyz"])
        assert _merge_factors(factors, max_merge=1) == factors

    def test_merged_plan_bitmap_still_exact(self):
        """End-to-end: an aggressively merged plan still produces the
        single-stage bitmap bit for bit (stage 2 pays for every stage-1
        false positive)."""
        patterns = (
            [rf"GET /admin-{w}/[a-z]+\.php" for w in
             ("alpha", "bravo", "civic", "delta")]
            + [rf"POST /login{d}[0-9]{{2}}" for d in range(4)]
            + [r"(?i)sqlmap|nikto"]
        )
        plan = build_plan(patterns, min_filterable_fraction=0.4,
                          factor_merge=64, factor_sel_max=1e-3)
        assert plan is not None
        from banjax_tpu.scenarios import synth

        lines = synth.generate_lines(512, patterns, seed=3,
                                      attack_rate=0.3)
        pf = PrefilterMatcher(plan, "xla", max_len=128, max_batch=256)
        bits, host_eval = pf.match_bits(lines)
        assert not host_eval.any()
        compiled = compile_rules(patterns)
        params = nfa_jax.match_params(compiled)
        cls_ids, lens, _ = encode_for_match(compiled, lines, 128)
        want = np.asarray(
            nfa_jax.match_batch(params, cls_ids, lens, compiled.n_rules)
        )
        for rid in plan.unsupported:
            want[:, rid] = 0
        assert (bits == want).all()
