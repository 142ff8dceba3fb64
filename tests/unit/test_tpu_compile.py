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


def test_long_rows_launches_compile(one_chip, prefilter):
    """One more launch of each stage's kernel for each long operand, over
    a chunk's lines past the short width: up to 1,024 bytes at a sixteenth
    of the chunk's rows (256 of 4,096; one block of 128 lanes in the
    smaller row buckets), up to 8,192 at one block."""
    from banjax_tpu.matcher import longrows

    assert longrows.LONG_WIDTHS == (1024, 8192)
    assert [longrows.operands(prefilter, rows)
            for rows in (128, 2048, 4096)] == [
        ((1024, 128), (8192, 128)), ((1024, 128), (8192, 128)),
        ((1024, 256), (8192, 128))]
    launches = {op for b in (128, 4096)
                for op in longrows.operands(prefilter, b)}
    assert launches == {(1024, 128), (1024, 256), (8192, 128)}
    for width, kl in sorted(launches):
        block = prefilter._block_for(kl)
        assert block == 128
        _compile(prefilter._stage1_raw(kl, width, block),
                 one_chip, ((width, kl), jnp.int32), ((kl,), jnp.int32))
        _compile(prefilter._stage2(kl, width, block),
                 one_chip, ((width, kl), jnp.int32), ((kl,), jnp.int32))


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


# ---- PR 33: upstream-stress10k, 10,000 rules x 65,536 slots on one chip ----

STRESS_RULES = 10_000
STRESS_SLOTS = 65_536
HBM_BYTES = 15.75e9  # what a v5e's 16 GB leaves a program


@pytest.fixture(scope="module")
def stress_prefilter():
    from banjax_tpu.matcher.prefilter import FusedPrefilter, build_plan
    from banjax_tpu.matcher.rulec import compile_rules
    from benchmark.rulesets import stress_distinct

    pats = [r["regex"] for r in stress_distinct.build(STRESS_RULES, seed=7)]
    comp = compile_rules(pats, n_shards=1)
    plan = build_plan(pats, byte_classes=(comp.byte_to_class, comp.n_classes))
    assert not plan.unsupported and plan.n_always == 0
    # stage 2 in slabs the kernel's VMEM budget holds, a dozen at least
    assert plan.stage2.n_shards >= 12 and plan.stage2.words_per_shard <= 512
    return FusedPrefilter(plan, "pallas")


def _stress_state(one_chip):
    from banjax_tpu.matcher import windows as W

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    keys = (STRESS_SLOTS * STRESS_RULES,)
    return sds, W.DeviceWindowState(
        hits=sds(keys, jnp.int32), start_s=sds(keys, jnp.int32),
        start_ns=sds(keys, jnp.int32), key_gen=sds(keys, jnp.int32),
        slot_gen=sds((STRESS_SLOTS,), jnp.int32),
        ip_seen=sds((STRESS_SLOTS,), jnp.bool_),
    )


def _maintenance_operands(sds):
    """A full chunk's evicted slots and its room of restore rows, as the
    fused program takes them (windows._run_maintenance_locked)."""
    from banjax_tpu.matcher import windows as W

    return sds((B,), jnp.int32), sds((5, W._restore_room(B)), jnp.int32)


def _fits_with_the_table_aliased(compiled):
    m = compiled.memory_analysis()
    table = 16 * STRESS_SLOTS * STRESS_RULES
    # the 10.49 GB table is donated: the program writes it in place
    assert m.alias_size_in_bytes >= table
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert table < peak < HBM_BYTES, m
    # and one copy more would not fit
    assert peak + table > HBM_BYTES
    return m


@pytest.fixture(scope="module")
def stress_single(one_chip, stress_prefilter):
    """The one fused program (match, dense bitmap, window commit) at the
    full batch, compiled: 4,096 rows x 10,000 rules, 76 stage-2 slabs."""
    import types

    from banjax_tpu.matcher.kernels import fused_match_window as fmw
    from banjax_tpu.obs.sketch import TrafficSketch

    pf = stress_prefilter
    sds, state = _stress_state(one_chip)
    win = types.SimpleNamespace(
        _limits=jnp.full((STRESS_RULES,), 2, jnp.int32),
        _iv_s=jnp.full((STRESS_RULES,), 300, jnp.int32),
        _iv_ns=jnp.zeros((STRESS_RULES,), jnp.int32),
    )
    # as the product builds it by default: the chunk's traffic-sketch fold
    # in the program, its (cm, hll) donated beside the window table
    sketch = TrafficSketch(["r"])
    fn, k, p, e = fmw.build_single_program(
        pf, win, jnp.ones((1, STRESS_RULES), bool), STRESS_RULES, B, L_P,
        f_idx=jnp.asarray(pf.plan.f_idx, jnp.int32),
        a_idx=jnp.asarray(pf.plan.a_idx, jnp.int32), aw=None, ae=None,
        scan_fn=fmw.window_scan(False), sketch=sketch,
    )
    assert (k, p, e) == (K, 1024, 1024)
    vec = sds((B,), jnp.int32)
    sketch_state = (sds((sketch.depth * sketch.width,), jnp.int32),
                    sds((sketch.m,), jnp.int32))
    return fn.lower(
        state, sketch_state, sds((), jnp.int32),
        sds((B, 1 + L_P // 4), jnp.int32),
        sds((), jnp.int32), vec, vec, vec, vec, sds((B,), jnp.uint8),
        *_maintenance_operands(sds), sds((B,), jnp.uint32),
    ).compile()


def test_stress10k_single_program_holds_the_table_in_place(
    stress_single, stress_prefilter
):
    """The window table is an argument aliased to the program's output."""
    # the (row, rule) pair encoding is int32: rows x packed rule columns
    assert B * stress_prefilter._nf8 * 8 < 2**31 // 50
    text = stress_single.as_text()
    assert text.count("tpu_custom_call") >= 3
    # the table's evictions and restores at the program's head (PR 49)
    assert "window-maintenance" in text
    # ... restoring past the first 1,024 keys only where a key lies there
    assert " conditional(" in text
    m = _fits_with_the_table_aliased(stress_single)
    assert m.temp_size_in_bytes < 1e9
    # the sketch's state (count-min 4 x 8,192 and 4,096 HLL registers,
    # int32) is written in place too
    table = 16 * STRESS_SLOTS * STRESS_RULES
    assert m.alias_size_in_bytes >= table + 4 * (4 * 8192 + 4096)


def _ops_over(text: str, ops, dtype: str, sizes) -> list:
    """Instructions of `text` (a compiled module) whose opcode is in `ops`
    and whose result or an operand is a `dtype` array with an element
    count in `sizes`.  Operand shapes are looked up by name inside the
    instruction's own computation."""
    import math
    import re

    shape_re = re.compile(rf"\b{dtype}\[([0-9,]+)\]")
    inst_re = re.compile(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([a-z][a-z\-]*)\((.*)$"
    )

    def hits(shape_text: str) -> bool:
        return any(
            math.prod(int(d) for d in dims.split(",")) in sizes
            for dims in shape_re.findall(shape_text)
        )

    found = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text):
        insts = [m.groups() for m in map(inst_re.match, comp.splitlines()) if m]
        shapes = {name: shape for name, shape, _, _ in insts}
        for name, shape, op, rest in insts:
            if op not in ops:
                continue
            operands = re.findall(r"%[\w.\-]+", rest.split("), ")[0])
            if hits(shape) or any(hits(shapes.get(o, "")) for o in operands):
                found.append(f"{name} = {shape} {op}")
    return found


def test_stress10k_single_program_reduces_over_no_rows_x_rules_array(
    stress_single, stress_prefilter
):
    """The window events come from the pairs the program holds: nothing
    in it scans, sorts or reduces an int32 array the size of the dense
    match matrix (rows x rules) or of the unpacked candidate bits
    (candidate slots x 8 nf8) — the two `nonzero`s that were 88 % of the
    device's time at 10,000 rules (PR 34) cannot come back unnoticed."""
    text = stress_single.as_text()
    sizes = {B * STRESS_RULES, K * 8 * stress_prefilter._nf8}
    ops = ("reduce-window", "sort", "fusion")
    assert _ops_over(text, ops, "s32", sizes) == []
    # the helper does see what it looks for: the dense bitmap the replay
    # reads is still assembled, as bytes
    assert _ops_over(text, ("fusion",), "u8", {B * STRESS_RULES})
    assert "reduce-window" in text and " sort(" in text


def test_stress10k_maintenance_steps_hold_the_table_in_place(one_chip):
    from banjax_tpu.matcher import windows as W

    sds, state = _stress_state(one_chip)
    for step, operand in (
        (W._evict_step, sds((4096,), jnp.int32)),
        (W._restore_step, sds((5, W._RESTORE_CHUNK), jnp.int32)),
    ):
        m = _fits_with_the_table_aliased(step.lower(state, operand).compile())
        assert m.temp_size_in_bytes < 1e8


# ---- multisite-edge: 750 sites' rules, the site mask before the pairs ----

MULTISITE_HOSTS = 751


@pytest.fixture(scope="module")
def multisite_single(one_chip):
    """The fused program of `multisite-edge` at the full batch, compiled:
    9,000 per-site columns (24 patterns, each on about 375 sites) before
    1,000 global ones, a [751, 10000] active table, and the packed site
    mask gathered by the candidates' hosts in front of the pairs."""
    import types

    import numpy as np

    from banjax_tpu.matcher.kernels import fused_match_window as fmw
    from banjax_tpu.matcher.prefilter import FusedPrefilter, build_plan
    from banjax_tpu.matcher.rulec import compile_rules
    from benchmark.harness import found

    rules = found.ruleset(found.data("configs", "multisite-edge")["ruleset"])
    own = [r for r in rules if r.get("_site")]       # the product's order
    rules = own + [r for r in rules if not r.get("_site")]
    assert len(rules) == STRESS_RULES and len(own) == 9000
    pats = [r["regex"] for r in rules]
    comp = compile_rules(pats, n_shards=1)
    plan = build_plan(pats, byte_classes=(comp.byte_to_class, comp.n_classes))
    assert not plan.unsupported and plan.n_always == 0
    assert plan.stage2.words_per_shard <= 512
    pf = FusedPrefilter(plan, "pallas")
    row = {s: i + 1 for i, s in enumerate(sorted({r["_site"] for r in own}))}
    active = np.zeros((MULTISITE_HOSTS, STRESS_RULES), bool)
    active[:, len(own):] = True
    for i, r in enumerate(own):
        active[row[r["_site"]], i] = True
    sds, state = _stress_state(one_chip)
    win = types.SimpleNamespace(
        _limits=jnp.full((STRESS_RULES,), 2, jnp.int32),
        _iv_s=jnp.full((STRESS_RULES,), 300, jnp.int32),
        _iv_ns=jnp.zeros((STRESS_RULES,), jnp.int32),
    )
    fn, k, p, e = fmw.build_single_program(
        pf, win, active, STRESS_RULES, B, L_P,
        f_idx=jnp.asarray(pf.plan.f_idx, jnp.int32),
        a_idx=jnp.asarray(pf.plan.a_idx, jnp.int32), aw=None, ae=None,
        scan_fn=fmw.window_scan(False), skip_table=np.zeros_like(active),
    )
    assert (k, p, e) == (K, 1024, 1024)   # no larger buffer for the sites
    vec = sds((B,), jnp.int32)
    return pf, fn.lower(
        state, sds((), jnp.int32), sds((B, 1 + L_P // 4), jnp.int32),
        sds((), jnp.int32), vec, vec, vec, vec, sds((B,), jnp.uint8),
        *_maintenance_operands(sds),
    ).compile()


def test_multisite_single_program_masks_by_site_and_fits(multisite_single):
    pf, compiled = multisite_single
    text = compiled.as_text()
    assert "site-mask" in text and text.count("tpu_custom_call") >= 3
    m = _fits_with_the_table_aliased(compiled)
    assert m.temp_size_in_bytes < 1e9
    # the mask is a gather of packed rows and an AND: nothing in front of
    # the pairs reduces over rows x rules or the unpacked candidate bits
    sizes = {B * STRESS_RULES, K * 8 * pf._nf8}
    assert _ops_over(text, ("reduce-window", "sort", "fusion"),
                     "s32", sizes) == []


def test_a_ruleset_of_global_rules_compiles_without_the_site_mask(
    stress_single
):
    """One row in the active table (the four older cells): the program is
    built as before, no gather by host in front of the pairs."""
    assert "site-mask" not in stress_single.as_text()
