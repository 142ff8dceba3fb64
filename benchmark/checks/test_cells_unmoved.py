"""The four cells are fed and configured as before PR 35, byte for byte.

PR 35 let a rule record carry a site and a traffic file a `hosts` block.
None of the four cells has either, so for each, at one fixed seed, the
SHA-256 of the pools of request strings, of the first four blocks of
`(ip, rest)` the generator would write, and of the product configuration
`write_config` emits (parsed, without `pallas_single_kernel`, a key the
schema dropped in PR 30 and PR 35 took out of two configuration files)
equals what PR 35's parent gave: the constants below were computed on
commit ed86a25 by this file's `digests`.  Needs no port and no JAX.

A PR that means to change what a cell is fed is a `benchmark` PR and says
so; it computes the constants anew on its own parent first.
"""

import hashlib
import json
import os
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import found, genproc, product, stream  # noqa: E402

SEED = 3535353535
AT_PARENT = {
    "crs1k.flood": {
        "pools": "d5bad83180a497e0499d260ee270ffbeb7b2d93f27f9ef69307b4255d1b8b147",
        "stream": "1351d7fa0d7730532bc19dbbc2aadf99cd818c5331bbbf7189b5c54536c4344a",
        "config": "2b1ccf59138a471b0daac663b5e41c450e97f4f3c95c7871df5c5bdd6eef03bf",
    },
    "crs1k.botnet": {
        "pools": "d5bad83180a497e0499d260ee270ffbeb7b2d93f27f9ef69307b4255d1b8b147",
        "stream": "7dad651f161efaea65866f1ec4501e228732df6caf028d897e95d75e77162d1d",
        "config": "2b1ccf59138a471b0daac663b5e41c450e97f4f3c95c7871df5c5bdd6eef03bf",
    },
    "default.flood": {
        "pools": "6e66c8d7c370562ec4d0e8eabe29dda05cc9e9ee774fcceab2b32d6759a29bae",
        "stream": "1af179e6e344e06fd8fc7197f025b0e6325206e33c8f29bf67eeef258c490eb3",
        "config": "2db5bf8457466997123a1b1ab828af64fb594770fc28c39b509872b685285383",
    },
    "stress10k.botnet": {
        "pools": "7fefdc040f9e021b238c1bb5f697967d08530af68b2959efff10876d58367e06",
        "stream": "79beb0ecd32eeda5700a4224a6edbec74bb49f62626297aad85d1f3a7e4b4a36",
        "config": "206340cf76614a63599b5d46561343699008d1b3a14a39c66673b0a068c22030",
    },
}


def digests(name: str) -> dict:
    import yaml

    cell = found.cell(name)
    config, traffic = cell["config"], cell["traffic"]
    rules = found.ruleset(config["ruleset"])
    rests, n_benign, attack_rule = genproc.build_pools(rules, traffic, SEED)
    pools = hashlib.sha256(
        json.dumps([rests, n_benign, attack_rule]).encode()).hexdigest()
    strm = stream.Stream(traffic, n_benign, len(rests) - n_benign, SEED)
    h = hashlib.sha256()
    for k in range(4):
        ips, ridx = strm.block(k)
        h.update("".join(
            f"{ip} {rests[r]}\n" for ip, r in zip(ips, ridx)).encode())
    with tempfile.TemporaryDirectory() as d:
        with open(product.write_config(d, config, rules, {}),
                  encoding="utf-8") as f:
            cfg = yaml.safe_load(f)
    cfg.pop("pallas_single_kernel", None)
    conf = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode()).hexdigest()
    return {"pools": pools, "stream": h.hexdigest(), "config": conf}


@pytest.mark.parametrize("name", sorted(AT_PARENT))
def test_cell_is_fed_and_configured_as_at_the_parent(name):
    assert digests(name) == AT_PARENT[name]


def test_every_cell_of_the_benchmark_is_held():
    assert {w["name"] for w in found.benchmark_json()["workloads"]} \
        == set(AT_PARENT)
