"""`longline1k.flood` (PR 43), by hand like `test_capped1k.py`; the cases
that start no product run in tier-1 through
`tests/unit/test_benchmark_checks.py`.

Port-free: `crs_long`'s rules are `crs_shaped`'s letter for letter but for
the recipes of one rule in twenty, whose filler is of the stated bytes and
lengths and lies where the file says; the pools' lengths are the ones
`PERF.md` §4 gives (3.0 % of benign lines over 255 bytes, none over 582;
5 % of attack lines long, under 8,190) and the stream's own share of long
lines is the number `long_lines_share` is held to; the plain reference's
ban log over the rehearsal stream has records that rest on a match past
byte 256 and on nothing else — a reference that scans the first 256 bytes
of a line alone (the planted fault, on the reference's side) loses exactly
those, and the comparison says so.

With the product (port 8081, CPU): the sound rehearsal ends with all four
comparisons at 0 and no batch off the fused path for a line's sake; and
with the same fault planted in the product (the long
operand cut to each row's first 256 bytes) the comparison fails."""

import json
import os
import re
import sys

import numpy as np

from benchmark.harness import found, genproc, reference, stream
from benchmark.rulesets import crs_long, crs_shaped

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SHORT = 256  # the product's matcher_max_line_len: a longer line is long
# the stream's own share of lines over SHORT, in percent: this file's
# count over the first 262,144 lines at full size reads 3.09; the band is
# what `long_lines_share` has to read (PERF.md §3: within 0.2 of it)
LONG_SHARE_BAND = (2.89, 3.29)
COMPARED = ("ban_records_missing", "ban_records_extra", "ips_out_of_order",
            "ban_keys_differing")


def _cell(rehearse=False):
    from benchmark.harness.cellrun import overlay

    cell = found.cell("longline1k.flood")
    config, traffic = cell["config"], cell["traffic"]
    if rehearse:
        config = overlay(config, config["rehearse"])
        traffic = overlay(traffic, traffic["rehearse"])
    return config, traffic


def _before_the_match(rule) -> bool:
    """Whether `crs_long` puts a long rule's filler in front of the part
    its regex matches."""
    return "path" not in crs_shaped_recipe(rule) or \
        rule["regex"].startswith(("/", "^"))


_SHAPED = {r["rule"]: r for r in crs_shaped.build(1000, 7)}


def crs_shaped_recipe(rule) -> dict:
    return _SHAPED[rule["rule"]]["_attack"]


def test_rules_are_crs_shapeds_and_one_recipe_in_twenty_is_lengthened():
    config, _ = _cell()
    rules = found.ruleset(config["ruleset"])
    shaped = crs_shaped.build(1000, 7)
    assert len(rules) == 1000
    assert found.product_rules(rules) == found.product_rules(shaped)
    charset = re.compile(r"[a-z0-9/_=&+-]+\Z")
    n_long = n_before = 0
    sizes = []
    for i, (mine, theirs) in enumerate(zip(rules, shaped)):
        if not crs_long.long_rule(i):
            assert mine["_attack"] == theirs["_attack"], i
            continue
        n_long += 1
        recipe, plain = mine["_attack"], theirs["_attack"]
        n = recipe["filler_bytes"]
        sizes.append(n)
        assert crs_long.LO <= n <= crs_long.HI
        assert {k: v for k, v in recipe.items()
                if k not in ("path", "filler_bytes")} == {
            k: v for k, v in plain.items() if k != "path"}
        if "path" not in plain:                     # User-Agent kind
            filler, before = recipe["path"][1:], True
        elif recipe["path"].endswith(plain["path"]):
            filler = recipe["path"][1:-len(plain["path"])]
            before = True
        else:
            assert recipe["path"].startswith(plain["path"] + "?")
            filler, before = recipe["path"][len(plain["path"]) + 1:], False
        assert len(filler) == n and charset.match(filler), i
        assert before == _before_the_match(mine), (i, mine["regex"])
        n_before += before
    assert (n_long, n_before) == (50, 10)
    # log-uniform over 300-7,600: as many under a kilobyte as over two
    assert sum(s < 1024 for s in sizes) >= 15
    assert sum(s > 2048 for s in sizes) >= 15
    assert config["product_config"]["matcher_max_line_len"] == SHORT


def test_pools_have_the_lengths_the_configuration_states():
    config, traffic = _cell()
    assert traffic["lines"]["max_rest_len"] == 8190
    rules = found.ruleset(config["ruleset"])
    rests, n_benign, attack_rule = genproc.build_pools(rules, traffic, 4343)
    benign = np.asarray([len(r) for r in rests[:n_benign]])
    attack = np.asarray([len(r) for r in rests[n_benign:]])
    assert len(benign) == 24576 and len(attack) == 4096
    assert 150 <= np.median(benign) <= 165
    assert 2.6 <= 100.0 * np.mean(benign > 255) <= 3.4
    assert benign.max() <= 582
    from_long = np.asarray([crs_long.long_rule(i) for i in attack_rule])
    assert 4.0 <= 100.0 * from_long.mean() <= 6.0
    assert attack[from_long].min() >= 400 and attack.max() < 8190
    assert (attack[~from_long] <= 330).all()
    # nothing was cut: the longest line is far past what the other five
    # cells' traffic files allow
    assert attack.max() > 7000
    assert all(r.isascii() for r in rests)


def _stream_lines(rules, traffic, seed, n, dt):
    rests, n_benign, _ = genproc.build_pools(rules, traffic, seed)
    strm = stream.Stream(traffic, n_benign, len(rests) - n_benign, seed)
    ips, ridx = strm.block(0)
    return [f"{1_700_000_000 + i * dt:.6f} {ip} {rests[r]}"
            for i, (ip, r) in enumerate(zip(ips[:n], ridx[:n]))], rests


def test_the_streams_own_share_of_long_lines_lies_in_the_band():
    """The generator's own count, which `long_lines_share` is held to:
    lines over 256 bytes of request string in the stream's first block."""
    config, traffic = _cell()
    rules = found.ruleset(config["ruleset"])
    lines, _ = _stream_lines(rules, traffic, 4344, stream.BLOCK, 1e-5)
    sizes = np.asarray([len(ln.split(" ", 2)[2]) for ln in lines])
    share = 100.0 * np.mean(sizes > SHORT)
    assert LONG_SHARE_BAND[0] + 0.1 < share < LONG_SHARE_BAND[1] - 0.1, share
    # a 4,096-line chunk: about 126 of them, and never none
    per_chunk = (sizes > SHORT).reshape(-1, 4096).sum(axis=1)
    assert 110 < per_chunk.mean() < 140 and per_chunk.min() >= 80
    assert per_chunk.max() < 256  # the long operand's rows at 4,096
    # and the payload lines: about five a chunk
    assert 3.0 < (sizes > 600).reshape(-1, 4096).sum(axis=1).mean() < 8.0


def test_a_reference_that_scans_256_bytes_loses_the_bans_past_them():
    """The planted fault on the reference's side.  Over the rehearsal
    stream the plain reference (Python `re` over the whole request string)
    writes ban records of the four long rules (64 rules in the rehearsal:
    3, 23, 43 and 63).  Three of them match at the path's first byte, the
    filler behind the match: cut or not, they fire.  The fourth's match
    (`/config.php?...`) lies behind 846 bytes of filler, and a reference
    that is handed each line cut to 256 bytes writes none of its records
    (nor those of a User-Agent token behind 256 bytes of anything): the
    comparison fails on at least that many."""
    config, traffic = _cell(rehearse=True)
    rules = found.ruleset(config["ruleset"])
    long_names = {r["rule"] for i, r in enumerate(rules)
                  if crs_long.long_rule(i)}
    assert len(long_names) == 4
    lines, _ = _stream_lines(rules, traffic, 4345, 1 << 15, 1e-4)
    checked = lambda ip: ip.startswith(f"{stream.IP_BASE}.")  # noqa: E731
    sound = reference.run(rules, lines, checked, procs=1)
    of_long = [x for x in sound["bans"]
               if json.loads(x)["trigger"] in long_names]
    assert len(of_long) >= 20
    assert all(len(json.loads(x)["path"]) > SHORT for x in of_long)

    def cut(line):
        stamp, ip, rest = line.split(" ", 2)
        return f"{stamp} {ip} {rest[:SHORT]}"

    faulty = reference.run(rules, [cut(ln) for ln in lines], checked,
                           procs=1)
    # a line cut short keeps its method, host and the head of its path: the
    # records differ in `path`/`client_ua`, so compare what fired
    fired = lambda bans: sorted(  # noqa: E731
        (d["client_ip"], d["trigger"]) for d in map(json.loads, bans))
    lost = len(fired(sound["bans"])) - len(fired(faulty["bans"]))
    behind = {r["rule"] for i, r in enumerate(rules)
              if crs_long.long_rule(i) and _before_the_match(r)}
    n_behind = sum(json.loads(x)["trigger"] in behind for x in of_long)
    # and beside them whatever else matched past byte 256: a scanner's
    # User-Agent token at the end of a line of 257 bytes or more
    assert len(behind) == 1 and lost >= n_behind >= 5
    cmp_ = reference.compare(faulty["bans"], sound["bans"])
    assert cmp_["ban_records_missing"] >= lost
    same = reference.compare(sound["bans"], reference.run(
        rules, lines, checked, table=sound["table"])["bans"])
    assert [same[k] for k in COMPARED] == [0, 0, 0, 0]


# ---- with the product (port 8081; by hand) ----


def _assert_sound(result):
    assert result["checks_failed"] == [], result["checks_failed"]
    assert result["attempted"] > 0
    m = result["metrics"]
    assert m["unfused_batches_share"]["value"] == 0
    # at the rehearsal's size a chunk cut to 8 rows has room for 8
    # candidates: one in some hundreds overflows and replays (exact)
    assert m["fused_fallback_share"]["value"] < 1
    assert m["evictions_per_kline"]["value"] > 100
    return m


def test_sound_rehearsal_commits_every_batch_fused(cell_runner):
    result = cell_runner("--workload", "longline1k.flood", "--seed",
                         "4343434343", "--trace", "1")
    m = _assert_sound(result)
    # the rehearsal's 5 % attack lines on 64 rules, four of them long
    assert 3.0 <= m["long_lines_share"]["value"] <= 6.5
    for k in COMPARED:
        assert result["compared"][k]["value"] == 0


def test_a_product_that_scans_256_bytes_of_a_long_row_fails_the_comparison(
        monkeypatch, capsys, tmp_path):
    """The fault planted in the product: the long operands carry each long
    row's first 256 bytes and no more.  The run ends, every batch
    still commits fused, and the ban log lacks the records that rest on a
    match past byte 256: `correct` is false by that comparison."""
    sys.path.insert(0, REPO)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    from banjax_tpu.matcher import longrows
    from benchmark import run

    whole = longrows.assemble

    def first_256(pf, spec, long_rows, pad_row):
        ops = whole(pf, spec, long_rows, pad_row)
        for op in ops:
            op[:, 0] = np.minimum(op[:, 0], SHORT)  # the length scanned
            ids = op.view(np.uint8)[:, 8:] if pf._pack_input else op[:, 2:]
            ids[:, SHORT:] = 0                      # and nothing behind it
        return ops

    monkeypatch.setattr(longrows, "assemble", first_256)
    cwd = os.getcwd()
    try:
        rc = run.main(["--rehearse", "--workload", "longline1k.flood",
                       "--seed", "4343434345", "--seconds", "3",
                       "--trace", "1"])
    finally:
        os.chdir(cwd)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "ban_records_missing" in result["checks_failed"]
    assert result["compared"]["ban_records_missing"]["value"] >= 5
    # (a hit that went uncounted moves which later hit of its address
    # crosses the limit: a few records differ in their `path` beside)
    assert result["metrics"]["unfused_batches_share"]["value"] == 0
