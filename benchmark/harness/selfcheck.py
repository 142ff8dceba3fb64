"""`run.py --selfcheck`: the yardstick checked against itself, no chip.

The trace reduction on a small recorded trace (selfcheck/trace.xplane.pb,
taken on a TPU v5e) and on handmade events, the /metrics parser, the match
kernel's operation and byte function, the peak table, the plain
reference's window semantics, the attack recipes, and that every name in
BENCHMARK.json finds its file."""

from __future__ import annotations

import json
import os
import random

from benchmark.harness import found, lines, prom, reference, roofline, xplane


def _close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-300)


def check_reduction() -> None:
    ev = [("while", 0, 100), ("fusion.1", 10, 30), ("kernel_a", 50, 40),
          ("copy", 150, 50)]
    own = dict(xplane._self_times(ev))
    assert own == {"while": 30, "fusion.1": 30, "kernel_a": 40, "copy": 50}, own
    assert xplane._union([(0, 100), (10, 40), (150, 200)]) == [
        [0, 100], [150, 200]]
    host = [("encode", 90, 30), ("drain", 118, 40)]
    assert xplane._host_doing(host, 100, 150) == "drain"
    assert xplane._host_doing(host, 300, 400) == "none"
    rec = os.path.join(found.ROOT, "selfcheck", "trace.xplane.pb")
    with open(os.path.join(found.ROOT, "selfcheck", "expected.json"),
              encoding="utf-8") as f:
        want = json.load(f)
    got = xplane.reduce(rec)
    for k in ("busy_s", "window_s"):
        assert _close(got[k], want[k], 1e-6), (k, got[k], want[k])
    for k, v in want["kernel_s"].items():
        assert _close(got["kernel_s"][k], v, 1e-6), (k, got["kernel_s"][k], v)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["device_ops"][0][0] == want["device_ops"][0][0]


def check_prom() -> None:
    text = ('# TYPE x counter\nx_total 5\ny_sum{stage="a"} 2.5\n'
            'h_bucket{le="0.1"} 1\nh_bucket{le="0.2"} 3\nh_bucket{le="+Inf"} 4\n')
    a = prom.parse(text)
    b = prom.parse(text.replace("x_total 5", "x_total 9")
                   .replace("2.5", "4.5"))
    assert prom.delta(a, b, "x_total") == 4
    assert prom.ratio(a, b, ("y_sum", {"stage": "a"}), ("x_total", {})) == 0.5
    assert prom.value(a, "nope") is None


def check_roofline() -> None:
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        roofline.peaks("TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind has to be an error")
    w = roofline.match_kernel_work(line_bytes=1000, lines=10, calls=1,
                                   words=64, classes=128)
    assert w["int8_ops"] == 2 * 4 * 64 * 128 * 1000
    assert w["hbm_bytes"] == 1000 + 10 * 64 * 4 + 4 * 64 * 128 + 64 * 32
    pct, bound = roofline.share(w, 1e-6, "TPU v5 lite")
    assert bound == "mxu" and _close(pct, 100 * (w["int8_ops"] / 393e12) / 1e-6)


def check_reference() -> None:
    rules = [{"rule": "r", "regex": "GET /x", "interval": 10,
              "hits_per_interval": 2, "decision": "nginx_block"}]
    rest = "GET h.com GET /x HTTP/1.1 ua -"

    def bans(stamps):
        out = reference.run(
            rules, [f"{t:.6f} 11.0.0.1 {rest}" for t in stamps],
            lambda ip: True, procs=1)
        return len(out["bans"])

    assert bans([0, 1]) == 0
    assert bans([0, 1, 2]) == 1          # third hit exceeds 2
    assert bans([0, 1, 2, 3, 4]) == 1    # hits reset to 0, not 1
    assert bans([0, 1, 2, 3, 4, 5]) == 2
    assert bans([0, 1, 11.5, 12]) == 0   # window restarts past the interval
    got = [reference.ban_record("11.0.0.1", rules[0], rest)]
    assert reference.compare(got, got)["ban_records_missing"] == 0
    assert reference.compare([], got)["ban_records_missing"] == 1


def check_recipes() -> None:
    bj = found.benchmark_json()
    for c in bj["configs"]:
        with open(os.path.join(found.REPO, c["file"]), encoding="utf-8") as f:
            config = json.load(f)
        rules = found.ruleset(config["ruleset"])
        rng = random.Random(1)
        for r in rules:
            if r.get("_attack"):
                lines.attack_line(r, rng, 255)  # raises when re disagrees


def check_names() -> None:
    bj = found.benchmark_json()
    for w in bj["workloads"]:
        found.cell(w["name"])
    for m in bj["per_layer"]:
        mod = found.module("layers", m["name"])
        assert callable(mod.read), m["name"]
    for w in bj["workloads"]:
        kind = found.data("traffic", w["traffic"])["feed"]["kind"]
        assert callable(found.module("kinds", kind).run)


def main() -> int:
    for fn in (check_reduction, check_prom, check_roofline, check_reference,
               check_recipes, check_names):
        fn()
        print(f"selfcheck: {fn.__name__} ok")
    return 0
