"""Native fastparse vs the Python reference parse/encode — byte-identical
on every line, including the adversarial timestamp/shape corner cases the
C side must defer on."""

import time

import numpy as np
import pytest

from banjax_tpu import native
from banjax_tpu.matcher.encode import encode_for_match, parse_line
from banjax_tpu.matcher.rulec import compile_rules

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C compiler in this environment"
)

COMPILED = compile_rules([r"GET /wp-login\.php", r"(GET|POST) /[a-z]*\.php", r".*"])
MAX_LEN = 96
NOW = 1_753_800_000.0

LINES = [
    f"{NOW:.6f} 1.2.3.4 GET example.com GET /wp-login.php HTTP/1.1 UA",
    f"{NOW - 5:.3f} 10.0.0.1 POST site.org POST /x.php HTTP/1.1 -",
    f"{NOW - 11:.6f} 9.9.9.9 GET old.com GET / HTTP/1.1 UA",  # stale
    "not enough",                       # 1 space: parse error
    "",                                 # empty: error
    f"{NOW:.6f} 5.5.5.5 nospace",       # rest with no space: error
    f"{NOW:.6f} 5.5.5.5 a b",           # rest with 1 space: error
    f"{NOW:.6f} 5.5.5.5 a b ",          # trailing space: 3 parts, empty rest2
    "nan 1.2.3.4 GET h.com GET /",      # nan ts: Python error (defer path)
    "inf 1.2.3.4 GET h.com GET /",      # inf ts: Python error
    "1_700_000_000 1.2.3.4 GET h.com GET /",  # underscores: Python ACCEPTS
    "1e30 1.2.3.4 GET h.com GET /",     # int64 overflow: Python error
    "-5.5 1.2.3.4 GET h.com GET /",     # negative ts: valid, very old
    f"{NOW:.6f} 8.8.8.8 GET h.com GET /café HTTP/1.1",  # non-ASCII
    f"{NOW:.6f} 7.7.7.7 GET h.com GET /{'a' * 200} HTTP/1.1",  # over max_len
    f"{NOW:.6f} 6.6.6.6 GET h.com GET / HTTP/1.1 " + "x" * (MAX_LEN - 30),
    f"  {NOW:.6f} 1.2.3.4 GET h.com GET /",  # leading space: empty ts field
]


def test_differential_vs_python_reference():
    nb = native.parse_encode_batch(
        LINES, COMPILED.byte_to_class, MAX_LEN, NOW, 10.0
    )
    assert nb is not None and nb.n == len(LINES)
    for i, line in enumerate(LINES):
        want = parse_line(line, NOW, 10.0)
        f = int(nb.flags[i])
        if f & native.FLAG_DEFER:
            continue  # contract: caller re-parses with Python — always safe
        assert bool(f & native.FLAG_ERROR) == want.error, (i, line)
        if want.error:
            continue
        assert bool(f & native.FLAG_OLD) == want.old_line, (i, line)
        assert nb.ip(i) == want.ip
        assert int(nb.ts_ns[i]) == want.timestamp_ns, (i, line)
        if want.old_line:
            continue
        assert nb.host(i) == want.host
        assert nb.rest(i) == want.rest
        cls_ref, lens_ref, host_eval_ref = encode_for_match(
            COMPILED, [want.rest], MAX_LEN
        )
        assert bool(f & native.FLAG_HOST_EVAL) == bool(host_eval_ref[0]), (i, line)
        if not host_eval_ref[0]:
            assert nb.lens[i] == lens_ref[0]
            assert (nb.cls_ids[i] == cls_ref[0]).all(), (i, line)


def test_defer_covers_python_divergences():
    """Every line whose timestamp text C cannot prove plain must defer —
    in particular the underscore form Python float() accepts."""
    nb = native.parse_encode_batch(
        LINES, COMPILED.byte_to_class, MAX_LEN, NOW, 10.0
    )
    for i, line in enumerate(LINES):
        ts_field = line.split(" ", 1)[0] if " " in line else line
        exotic = any(c in ts_field for c in "_") or ts_field.lower() in (
            "nan", "inf", "-inf", "+inf", "infinity",
        ) or ts_field == "1e30"
        if exotic:
            assert int(nb.flags[i]) & native.FLAG_DEFER, (i, line)


def test_random_fuzz_against_reference():
    rng = np.random.default_rng(0)
    charset = list("abc ./:0123456789eE+-_é")
    lines = []
    for _ in range(500):
        n = int(rng.integers(0, 60))
        lines.append("".join(charset[int(k)] for k in rng.integers(0, len(charset), n)))
    nb = native.parse_encode_batch(lines, COMPILED.byte_to_class, MAX_LEN, NOW, 10.0)
    for i, line in enumerate(lines):
        f = int(nb.flags[i])
        if f & native.FLAG_DEFER:
            continue
        want = parse_line(line, NOW, 10.0)
        assert bool(f & native.FLAG_ERROR) == want.error, repr(line)
        if want.error:
            continue
        assert bool(f & native.FLAG_OLD) == want.old_line, repr(line)
        assert int(nb.ts_ns[i]) == want.timestamp_ns, repr(line)
        if not want.old_line:
            assert nb.ip(i) == want.ip and nb.host(i) == want.host
            assert nb.rest(i) == want.rest


def test_throughput_beats_python_parse():
    """The native pass must be well ahead of the Python loop (the point)."""
    lines = [
        f"{NOW:.6f} 10.{i % 256}.{i % 17}.{i % 251} GET example.com GET "
        f"/path/{i} HTTP/1.1 Mozilla/5.0 | 200"
        for i in range(20_000)
    ]
    t0 = time.perf_counter()
    nb = native.parse_encode_batch(lines, COMPILED.byte_to_class, MAX_LEN, NOW, 10.0)
    native_s = time.perf_counter() - t0
    assert not (np.asarray(nb.flags) & native.FLAG_DEFER).any()
    t0 = time.perf_counter()
    parsed = [parse_line(l, NOW, 10.0) for l in lines]
    encode_for_match(COMPILED, [p.rest for p in parsed], MAX_LEN)
    python_s = time.perf_counter() - t0
    print(f"native {len(lines)/native_s:,.0f} lps vs python {len(lines)/python_s:,.0f} lps")
    assert native_s * 2 < python_s  # conservative: usually 10-30x


def test_fast_timestamp_path_bit_identical_to_python_float():
    """The C fast_ts integer fast path must agree bit-for-bit with Python
    int(float(ts) * 1e9) on every shape it accepts; shapes it rejects must
    defer/error into the Python re-parse path (exactness contract of
    fastparse.c). Fuzzes plain, long-fraction, huge-mantissa, exponent,
    and malformed timestamps."""
    import random

    import numpy as np

    from banjax_tpu import native
    from banjax_tpu.native import FLAG_DEFER, FLAG_ERROR, ParseScratch

    rng = random.Random(1234)
    cases = []
    for _ in range(2000):
        kind = rng.random()
        if kind < 0.3:
            cases.append(
                f"{rng.randrange(10**9, 2 * 10**9)}.{rng.randrange(10**6):06d}"
            )
        elif kind < 0.5:
            fd = rng.randrange(1, 18)
            cases.append(f"{rng.randrange(10**9)}.{rng.randrange(10**fd):0{fd}d}")
        elif kind < 0.6:
            cases.append(str(rng.randrange(10 ** rng.randrange(1, 19))))
        elif kind < 0.7:  # mantissa past 2^53: must take the strtod path
            cases.append(f"{rng.randrange(10**17, 10**18)}.{rng.randrange(10**6):06d}")
        elif kind < 0.8:  # exponent form: strtod path
            cases.append(f"{rng.randrange(10**9)}e{rng.randrange(-3, 4)}")
        elif kind < 0.9:
            cases.append(f"{rng.randrange(10**9)}.{'9' * rng.randrange(1, 25)}")
        else:
            cases.append(rng.choice(
                ["1_000.5", "inf", "nan", "0x1p3",
                 f".{rng.randrange(10**6)}", f"{rng.randrange(10**6)}."]
            ))
    # deterministic int64-overflow boundary shapes: the fast-path mantissa
    # accumulator must bail BEFORE m*10 wraps (a wrapped value can sneak
    # under the 2^53 check and silently misparse)
    cases += [
        "922337203685477580", "9223372036854775807", "9223372036854775808",
        "92233720368547758089", "92233720368547758085.5",
        "922337203685477580.8", "18446744073709551616",
    ]
    b2c = np.zeros(257, dtype=np.int32)
    lines = [f"{ts} 1.2.3.4 GET h.com GET / x" for ts in cases]
    pb = native.parse_encode_batch(lines, b2c, 64, 2e9, 1e18, ParseScratch())
    if pb is None:
        pytest.skip("no C compiler in this environment")
    for i, ts in enumerate(cases):
        if int(pb.flags[i]) & (FLAG_DEFER | FLAG_ERROR):
            continue  # python re-parse path: exact by construction
        want = int(float(ts) * 1e9)  # raises -> C wrongly accepted it
        assert int(pb.ts_ns[i]) == want, ts


def test_unique_spans_fallback_matches_native():
    """The scalar fallback of the reference gate's unique_spans (native
    lib absent) produces the same first-appearance-ordered tables as the
    C dedup."""
    import numpy as np

    from tests.gate_reference import unique_spans

    blob = b"zz one two one three two zz one"
    words = blob.split(b" ")
    offs, lens, pos = [], [], 0
    for w in words:
        offs.append(pos)
        lens.append(len(w))
        pos += len(w) + 1
    offs = np.asarray(offs, dtype=np.int64)
    lens = np.asarray(lens, dtype=np.int32)

    def decode(k):
        return blob[int(offs[k]) : int(offs[k]) + int(lens[k])].decode()

    s_fallback, inv_fallback, _ = unique_spans(offs, lens, decode)  # no blob
    assert s_fallback == ["zz", "one", "two", "three"]
    assert inv_fallback.tolist() == [0, 1, 2, 1, 3, 2, 0, 1]

    from banjax_tpu import native

    if native.available():
        s_nat, inv_nat, _ = unique_spans(
            offs, lens, decode, blob=blob, text=blob.decode()
        )
        assert s_nat == s_fallback
        assert inv_nat.tolist() == inv_fallback.tolist()


def test_allowlist_cache_invalidated_on_reload():
    """The (host, ip) allowlist cache must drop when the static lists are
    rebuilt (hot reload): an IP removed from the allow list must stop
    being exempted immediately."""
    import time as _time

    from banjax_tpu.config.schema import config_from_yaml_text
    from banjax_tpu.decisions.rate_limit import RegexRateLimitStates
    from banjax_tpu.decisions.static_lists import StaticDecisionLists
    from banjax_tpu.matcher.runner import TpuMatcher
    from tests.mock_banner import MockBanner

    yaml_a = """
regexes_with_rates:
  - decision: nginx_block
    rule: insta
    regex: .*hitme.*
    interval: 60
    hits_per_interval: 0
global_decision_lists:
  allow:
    - 7.7.7.7
"""
    cfg = config_from_yaml_text(yaml_a)
    sl = StaticDecisionLists(cfg)
    m = TpuMatcher(cfg, MockBanner(), sl, RegexRateLimitStates())
    now = _time.time()
    line = f"{now:.6f} 7.7.7.7 GET h.com GET /hitme HTTP/1.1 UA"
    r1 = m.consume_lines([line], now)[0]
    assert r1.exempted

    # reload: allow list emptied
    cfg2 = config_from_yaml_text(yaml_a.replace("    - 7.7.7.7\n", ""))
    sl.update_from_config(cfg2)
    r2 = m.consume_lines([line], now)[0]
    assert not r2.exempted and r2.rule_results
