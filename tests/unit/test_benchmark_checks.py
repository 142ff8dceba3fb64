"""The port-free cases of `benchmark/checks/`, counted by tier-1 (D20,
owed since PR 35): the plain reference's per-site tables and its order
control (`test_per_site.py`), and the cells' feeds and product
configurations held to their SHA-256 constants (`test_cells_unmoved.py`).
Imported, not copied: a case changed there is changed here.  The cases
that start the product on port 8081 stay by-hand checks.

`test_cells_unmoved.py` holds the four cells of PR 35 and says that they
are every cell; it is a file of the benchmark and not this PR's to edit,
so `multisite.botnet`, the fifth, is held here: constants computed on the
tree that added it (PR 37), by that file's `digests`; `capped1k.flood`,
the sixth, likewise (PR 41), with the port-free cases of
`test_capped1k.py`; `longline1k.flood`, the seventh, likewise (PR 43),
with those of `test_longline1k.py`."""

import pytest

from benchmark.checks import test_cells_unmoved as unmoved
from benchmark.checks.test_cells_unmoved import (  # noqa: F401
    test_cell_is_fed_and_configured_as_at_the_parent,
)
from benchmark.checks.test_capped1k import (  # noqa: F401
    test_a_benign_get_meets_the_cap_and_nothing_else_of_the_front,
    test_front_rules_are_the_fixtures_and_names_are_distinct,
    test_the_control_with_the_cap_at_46_fails,
    test_the_references_own_always_share_lies_in_the_band,
)
from benchmark.checks.test_longline1k import (  # noqa: F401
    test_a_reference_that_scans_256_bytes_loses_the_bans_past_them,
    test_pools_have_the_lengths_the_configuration_states,
    test_rules_are_crs_shapeds_and_one_recipe_in_twenty_is_lengthened,
    test_the_streams_own_share_of_long_lines_lies_in_the_band,
)
from benchmark.checks.test_per_site import (  # noqa: F401
    test_control_and_compare_take_per_site_records,
    test_fixture_lines_go_where_their_rules_apply,
    test_global_first_fails_the_order_table,
    test_per_site_rules_need_a_hosts_block,
    test_reference_global_rulesets_read_as_before,
    test_reference_per_site_tables,
    test_reference_refuses_two_records_of_one_name,
)
from benchmark.harness import found

WHEN_ADDED = {
    "multisite.botnet": {
        "pools": "e1489a2bbd4ce6acc25c04653cbd37cb09508c52d5456b8c7dd4818aa12ee59a",
        "stream": "da64aca6e16f59c7aa87234285507a7f240d97165f97a8d036cb2b64051f310e",
        "config": "634178690492b27486ec9181192dae707924eabd709c175e460cc85a7930f840",
    },
    "capped1k.flood": {
        "pools": "123c8549962b391fe550c1d9abb1fd6ab41e7628b46f51b383082e5e34ae7226",
        "stream": "8692c448f6d87dd7b590e5eb7b3770d273e911ebe3d75f422af6eea5ebdf527f",
        "config": "3bc94f7d99daedba26577cd5302fe18d958585ea61e274f0d6ad0a268bed3728",
    },
    "longline1k.flood": {
        "pools": "4a08e885d0565a26f15bf887e1e08f37b1042d12b84d21b802b67569dfc8ea6f",
        "stream": "13c7ba5f74ad85e052d4a6873f5e24a1b9424ab38fc2c0f9717b35ba7d3c414a",
        "config": "a04079f04a53d9355654dbd434f9387cba3058d6d789e4c2396b83cbeeb7bf80",
    },
}


@pytest.mark.parametrize("cell", sorted(WHEN_ADDED))
def test_cell_is_fed_and_configured_as_when_added(cell):
    assert unmoved.digests(cell) == WHEN_ADDED[cell]


def test_every_cell_of_the_benchmark_is_held_here_or_there():
    assert {w["name"] for w in found.benchmark_json()["workloads"]} \
        == set(unmoved.AT_PARENT) | set(WHEN_ADDED)
