"""The main path's kernels compile for a TPU v5e at the real widths.

No chip is attached: the TPU compiler is installed and compiles for a chip
that is described (on-chip-measurement guide §2.3).  A compile that passes
is not a chip run — it guards what interpret mode cannot see: the
window-scan kernel passed every interpret-mode test since PR 7 and was
refused by Mosaic ("Cannot store scalars to VMEM") until PR 24 moved its
refs to SMEM.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every xdist worker imports
every test file), the compiles run in this process, and the persistent
compile cache is off around them (an entry compiled for a described chip
cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N_RULES = 1000
B = 4096
L_P = 256
K = 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def rules():
    from banjax_tpu.scenarios import synth

    return synth.generate_rules(N_RULES)


@pytest.fixture(scope="module")
def prefilter(rules):
    from banjax_tpu.matcher.prefilter import FusedPrefilter, build_plan
    from banjax_tpu.matcher.rulec import compile_rules

    comp = compile_rules(rules, n_shards=1)
    plan = build_plan(
        rules, byte_classes=(comp.byte_to_class, comp.n_classes)
    )
    return FusedPrefilter(plan, "pallas")


def _compile(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_single_stage_nfa_compiles(one_chip, rules):
    from banjax_tpu.matcher.kernels import nfa_match
    from banjax_tpu.matcher.rulec import compile_rules

    prep = nfa_match.prepare(compile_rules(rules, n_shards="auto"))
    fn = nfa_match.device_matcher(
        prep, B, L_P, 512, interpret=False, pack=True, cols=32
    )
    _compile(fn, one_chip, ((L_P, B), jnp.int32), ((B,), jnp.int32))


def test_fused_stage1_raw_compiles(one_chip, prefilter):
    block, k = prefilter.capacities(B)
    assert (block, k) == (512, K)
    fn = prefilter._stage1_raw(B, L_P, block)
    _compile(fn, one_chip, ((L_P, B), jnp.int32), ((B,), jnp.int32))


def test_fused_stage2_compiles(one_chip, prefilter):
    fn = prefilter._stage2(K, L_P, 512)
    _compile(fn, one_chip, ((L_P, K), jnp.int32), ((K,), jnp.int32))


def test_window_scan_compiles(one_chip):
    from banjax_tpu.matcher.kernels import fused_match_window as fmw

    # the event capacity follows rows x always-columns + pairs: 128-1,024
    # with 1,000 sparse rules (rows 128-4,096), 10,000 at a 10k-rule scale
    for events in (128, 256, 512, 1024, 4096, 10000):
        ep, tile = fmw._scan_tiling(events)
        call = fmw._scan_call(ep, tile, False)
        _compile(call, one_chip, *[((ep,), jnp.int32)] * 11)


def test_default_rules_programs_compile(one_chip):
    """The shipped default rules (two always-columns, one filtered rule):
    stage 1, stage 2 and the window scan at every row bucket's sizes."""
    from banjax_tpu.matcher.kernels import fused_match_window as fmw
    from banjax_tpu.matcher.prefilter import FusedPrefilter, build_plan
    from banjax_tpu.matcher.rulec import compile_rules

    pats = ["^GET", "^POST", ".*challengeme.*"]
    comp = compile_rules(pats, n_shards=1)
    pf = FusedPrefilter(
        build_plan(pats, byte_classes=(comp.byte_to_class, comp.n_classes)),
        "pallas",
    )
    assert pf.plan.n_always == 2 and pf._n_filt == 1
    for rows in (128, 512, 4096):
        block, k = pf.capacities(rows)
        _compile(pf._stage1_raw(rows, L_P, block), one_chip,
                 ((L_P, rows), jnp.int32), ((rows,), jnp.int32))
        _compile(pf._stage2(k, L_P, min(block, k)), one_chip,
                 ((L_P, k), jnp.int32), ((k,), jnp.int32))
        events = pf.event_capacity(rows, pf.pair_capacity(rows, k))
        assert events == 2 * rows + min(k, max(128, rows // 4))
        ep, tile = fmw._scan_tiling(events)
        _compile(fmw._scan_call(ep, tile, False), one_chip,
                 *[((ep,), jnp.int32)] * 11)


def test_pow_sha256_compiles(one_chip):
    from banjax_tpu.matcher.kernels import pow_verify

    _compile(
        pow_verify._pow_call(256, False), one_chip, ((16, 256), jnp.uint32)
    )
