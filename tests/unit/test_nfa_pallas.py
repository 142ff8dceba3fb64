"""Differential tests: the Pallas NFA kernel vs the XLA scan vs Python re.

The Pallas kernel (banjax_tpu/matcher/kernels/nfa_match.py) must produce a
match bitmap identical to nfa_jax.match_batch for any compiled ruleset —
that invariant is what lets TpuMatcher switch device backends without any
observable Decision change. Tests run the kernel in interpret mode (plain
JAX on the CPU backend); tests/unit/test_tpu_compile.py compiles the
kernel for a described v5e, and `chip_smoke.py` and the benchmark run it
on the chip.
"""

import random
import re

import numpy as np
import pytest

from banjax_tpu.matcher import nfa_jax
from banjax_tpu.matcher.encode import encode_for_match
from banjax_tpu.matcher.kernels import nfa_match
from banjax_tpu.matcher.rulec import UnsupportedPattern, compile_rule, compile_rules

REALISTIC_RULES = [
    r"GET /wp-login\.php",
    r"POST /xmlrpc\.php",
    r"(GET|POST) /[a-z-]*\.php",
    r"^GET .* HTTP/1\.1$",
    r"Mozilla/\d+\.\d+ \(compatible; [A-Za-z]+/\d+",
    r"POST /[a-z0-9/]*login[a-z0-9/]*",
    r"[0-9]{1,3}(\.[0-9]{1,3}){3}",
    r"(?i)sqlmap|nikto|nessus",
    r"/\.env$",
    r"/(wp-content|wp-includes)/.*\.php",
    r"HTTP/1\.[01]$",
    r"(admin|administrator|phpmyadmin)/",
]

REALISTIC_LINES = [
    "GET example.com GET /wp-login.php HTTP/1.1",
    "POST example.com POST /xmlrpc.php HTTP/1.1",
    "GET example.com GET /index.html HTTP/1.1",
    "POST example.com POST /user/login HTTP/1.1",
    "GET example.com GET /.env HTTP/1.1",
    "GET example.com GET /wp-content/plugins/x.php HTTP/1.1",
    "GET example.com GET /assets/app.js HTTP/1.1",
    "GET example.com GET /phpmyadmin/ HTTP/1.0",
    "sqlmap/1.5 probe run",
    "client 10.22.0.19 did a thing",
    "",
    "x",
]


def run_both(patterns, lines, n_shards=1, max_len=96, block_b=256):
    compiled = compile_rules(patterns, n_shards=n_shards)
    cls_ids, lens, host_eval = encode_for_match(compiled, lines, max_len)
    assert not host_eval.any(), "test lines must be device-evaluable"
    ref = np.asarray(
        nfa_jax.match_batch(
            nfa_jax.match_params(compiled), cls_ids, lens, compiled.n_rules
        )
    )
    prep = nfa_match.prepare(compiled)
    got = nfa_match.match_batch_pallas(
        prep, cls_ids, lens, block_b=block_b, interpret=True
    )
    return got, ref, compiled


def assert_equal_and_oracle(patterns, lines, **kw):
    got, ref, compiled = run_both(patterns, lines, **kw)
    np.testing.assert_array_equal(got, ref)
    for j, pat in enumerate(patterns):
        if not compiled.device_ok[j]:
            continue
        rx = re.compile(pat)
        for i, line in enumerate(lines):
            assert bool(got[i, j]) == (rx.search(line) is not None), (pat, line)


class TestPallasKernel:
    def test_realistic_rules_single_shard(self):
        assert_equal_and_oracle(REALISTIC_RULES, REALISTIC_LINES)

    @pytest.mark.parametrize("n_shards", [2, 4])
    def test_sharded_grid(self, n_shards):
        assert_equal_and_oracle(REALISTIC_RULES, REALISTIC_LINES, n_shards=n_shards)

    def test_batch_padding(self):
        # batch sizes around the block boundary: 1, block-1, block, block+1
        for n in (1, 3, 8):
            lines = (REALISTIC_LINES * 3)[:n]
            assert_equal_and_oracle(REALISTIC_RULES, lines, block_b=8)

    def test_long_branch_cross_word_carry(self):
        # a 90-char literal spans 3 words: exercises the lane-roll carry
        lit = "abcdefghij" * 9
        pats = [re.escape(lit), re.escape(lit[:40]) + r"\d+" + re.escape(lit[50:])]
        lines = [lit, lit[:40] + "123" + lit[50:], lit[:-1], "zzz" + lit + "zzz"]
        assert_equal_and_oracle(pats, lines, max_len=128)

    def test_anchors_and_empty(self):
        pats = [r"^abc", r"abc$", r"^abc$", r"^$", r"a*"]
        lines = ["abc", "xabc", "abcx", "", "a", "zz"]
        assert_equal_and_oracle(pats, lines)

    def test_fuzz_vs_xla_scan(self):
        rng = random.Random(20260730)
        alphabet = "abxy01 /."

        def gen_pattern():
            parts = []
            for _ in range(rng.randint(1, 5)):
                atom = rng.choice(
                    [re.escape(rng.choice(alphabet)), r"\d", r"[ab]", ".", r"\w"]
                )
                if rng.random() < 0.25:
                    atom += rng.choice(["*", "+", "?"])
                parts.append(atom)
            p = "".join(parts)
            if rng.random() < 0.15:
                p = "^" + p
            if rng.random() < 0.15:
                p = p + "$"
            return p

        patterns = []
        while len(patterns) < 50:
            p = gen_pattern()
            try:
                re.compile(p)
                compile_rule(p)
            except (UnsupportedPattern, re.error):
                continue
            patterns.append(p)
        lines = [
            "".join(rng.choice(alphabet) for _ in range(rng.randint(0, 30)))
            for _ in range(100)
        ]
        assert_equal_and_oracle(patterns, lines, n_shards=2, block_b=64)

    def test_vmem_guard(self):
        compiled = compile_rules([r"a{4000,5000}b{4000,5000}c{4000,5000}" + "d" * 120000])
        if compiled.device_ok[0]:
            with pytest.raises(nfa_match.PallasUnsupported):
                nfa_match.prepare(compiled)

    def test_roll_branch_matches_fallback(self):
        """The pltpu.roll carry path — the branch compiled Mosaic runs in
        production — must agree bit-for-bit with the concatenate fallback.
        Uses 90-char literals so state genuinely crosses word boundaries
        (the roll is exactly the cross-word carry)."""
        import jax.numpy as jnp

        lit = "abcdefghij" * 9
        pats = [re.escape(lit), re.escape(lit[:40]) + r"\d+" + re.escape(lit[50:])]
        lines = [lit, lit[:40] + "123" + lit[50:], lit[:-1], "zz" + lit + "zz"]
        compiled = compile_rules(pats)
        cls_ids, lens, _ = encode_for_match(compiled, lines, 128)
        prep = nfa_match.prepare(compiled)
        B, L = 8, 96
        cls_t = np.zeros((L, B), dtype=np.int32)
        cls_t[: cls_ids.shape[1], : len(lines)] = cls_ids[:, :L].T
        lens_p = np.zeros(B, dtype=np.int32)
        lens_p[: len(lines)] = lens
        outs = {}
        for roll in (False, True):
            call = nfa_match._build_raw_call(
                B, L, prep.n_classes_p, prep.n_shards, prep.wps_p,
                block_b=8, interpret=True, cols=8, force_roll=roll,
            )
            maxtile = np.asarray([-(-int(lens_p.max()) // 8)], dtype=np.int32)
            outs[roll] = np.asarray(
                call(jnp.asarray(maxtile), jnp.asarray(cls_t),
                     jnp.asarray(lens_p[None, :]), prep.btab_t, prep.masks_t)
            )
        np.testing.assert_array_equal(outs[True], outs[False])
        assert outs[True].any(), "carry test must produce accept bits"

    @pytest.mark.parametrize("cols", [8, 32])
    def test_wide_byte_tiles(self, cols):
        """cols=32 (the TPU production tile width) is semantics-identical
        to the default 8-column tile."""
        compiled = compile_rules(REALISTIC_RULES)
        cls_ids, lens, _ = encode_for_match(compiled, REALISTIC_LINES, 96)
        prep = nfa_match.prepare(compiled)
        got = nfa_match.match_batch_pallas(
            prep, cls_ids, lens, block_b=8, interpret=True, cols=cols
        )
        ref = np.asarray(
            nfa_jax.match_batch(
                nfa_jax.match_params(compiled), cls_ids, lens, compiled.n_rules
            )
        )
        np.testing.assert_array_equal(got, ref)


class TestRunnerBackend:
    def test_tpu_matcher_pallas_interpret_end_to_end(self):
        """TpuMatcher with the pallas-interpret backend produces the same
        RuleResults as with the XLA backend."""
        from banjax_tpu.config.schema import Config, RegexWithRate
        from banjax_tpu.decisions.model import Decision
        from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
        from banjax_tpu.decisions.static_lists import StaticDecisionLists
        from banjax_tpu.matcher.runner import TpuMatcher
        from tests.mock_banner import MockBanner

        rule = RegexWithRate.from_yaml_dict(
            {
                "rule": "wp probe",
                "regex": r"GET /wp-login\.php",
                "interval": 10,
                "hits_per_interval": 1,
                "decision": "nginx_block",
            }
        )

        def mk(backend):
            cfg = Config(
                regexes_with_rates=[rule], matcher_backend=backend
            )
            banner = MockBanner()
            m = TpuMatcher(
                cfg, banner, StaticDecisionLists(cfg), RegexRateLimitStates()
            )
            now = 1700000000.0
            lines = [
                f"{now} 1.2.3.4 GET example.com GET /wp-login.php HTTP/1.1",
                f"{now} 1.2.3.4 GET example.com GET /wp-login.php HTTP/1.1",
                f"{now} 5.6.7.8 GET example.com GET /ok.html HTTP/1.1",
            ]
            results = m.consume_lines(lines, now_unix=now)
            return results, banner

        r_xla, b_xla = mk("xla")
        r_pal, b_pal = mk("pallas-interpret")
        assert b_pal.bans == b_xla.bans and b_pal.bans
        for a, b in zip(r_xla, r_pal):
            assert len(a.rule_results) == len(b.rule_results)
            for ra, rb in zip(a.rule_results, b.rule_results):
                assert ra.rule_name == rb.rule_name
                assert ra.regex_match == rb.regex_match


def test_word_align_32_and_128_agree(monkeypatch):
    """The sub-lane (32) and conservative lane (128) shard paddings produce
    identical match bitmaps — the padding is dead words only (interpret
    mode)."""
    from banjax_tpu.matcher import rulec as rulec_mod
    from banjax_tpu.matcher.kernels import nfa_match as nm

    patterns = [r"GET /admin/[a-z]+\.php", r"(?i)sqlmap", r"POST /wp[0-9]{1,3}"]
    lines = ["GET /admin/shell.php x", "Mozilla SQLMap/1.0", "POST /wp42",
             "benign / nothing", ""]
    outs = {}
    for align in (32, 128):
        monkeypatch.setattr(rulec_mod, "KERNEL_WORD_ALIGN", align)
        monkeypatch.setattr(nm, "KERNEL_WORD_ALIGN", align)
        compiled = compile_rules(patterns, n_shards="auto")
        prep = nm.prepare(compiled)
        assert prep.wps_p % align == 0
        cls, lens, _ = encode_for_match(compiled, lines, 64)
        outs[align] = nm.match_batch_pallas(prep, cls, lens, interpret=True)
    assert (outs[32] == outs[128]).all()
