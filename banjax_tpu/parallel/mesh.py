"""Device-mesh sharding for the batched NFA matcher.

The reference scales horizontally by running N independent banjax+nginx
edges with no shared state (SURVEY.md §2.3); the TPU-native equivalent is a
`jax.sharding.Mesh` over two axes:

  * `dp` — data parallel over the line batch: each device classifies a
    shard of the encoded lines (the "log shards across cores" strategy of
    BASELINE.json's "one pmap'd pass").
  * `rp` — rule parallel over the packed NFA word axis: each device holds a
    slice of the transition masks (the VMEM budget constraint of SURVEY.md
    §7.3 hard part 3). rulec lays branches out so none straddles an `rp`
    shard boundary, so the in-shard packed shift never needs a cross-device
    carry; the only collective is one `psum` of accept bits over `rp`,
    riding ICI.

The per-device body is the SAME Pallas kernel the single-chip product path
runs (matcher/kernels/nfa_match.py) — each rp member scans its own word
slab with a one-shard grid; `backend="xla"` swaps in the nfa_jax scan and
`backend="pallas-interpret"` runs the kernel as plain JAX (the CPU-mesh CI
and dryrun path). `ShardedMatchBackend` is the batch-level wrapper
TpuMatcher plugs into `_match_bits` when a mesh is configured.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from banjax_tpu.obs import trace

from banjax_tpu.matcher import nfa_jax
from banjax_tpu.matcher.kernels import nfa_match as pallas_nfa
from banjax_tpu.matcher.rulec import CompiledRules


def make_mesh(n_devices: int, rp: int = 1) -> Mesh:
    """Mesh of shape (dp = n_devices // rp, rp)."""
    if n_devices % rp != 0:
        raise ValueError(f"n_devices {n_devices} not divisible by rp {rp}")
    devices = np.array(jax.devices()[:n_devices]).reshape(n_devices // rp, rp)
    return Mesh(devices, axis_names=("dp", "rp"))


def _param_specs() -> Dict[str, P]:
    return {
        "b_table": P(None, "rp"),
        "shift_in": P("rp"),
        "inject_always": P("rp"),
        "inject_start": P("rp"),
        "selfloop": P("rp"),
        "accept_any": P("rp"),
        "accept_end": P("rp"),
        # branch/extraction arrays are replicated; each rp member selects its
        # own branches by word-index range
        "acc_word": P(),
        "acc_mask": P(),
        "branch_rule": P(),
        "always_match": P(),
        "empty_only": P(),
    }


def _extract_local(
    acc,                 # [b, W_local] uint32 — this shard's accept words
    lens_local,          # [b] int32
    acc_word, acc_mask, branch_rule, always_match, empty_only,
    n_rules: int,
    words_per_shard: int,
):
    """Shard-local accept extraction + the rp psum combine (shared by the
    XLA and Pallas bodies — the only collective in the device step)."""
    shard = jax.lax.axis_index("rp")
    local_w = acc_word - shard * words_per_shard
    in_shard = (local_w >= 0) & (local_w < words_per_shard)
    gw = jnp.clip(local_w, 0, words_per_shard - 1)
    b = acc.shape[0]
    if acc_word.shape[0] > 0:
        sel = (acc[:, gw] & acc_mask) != 0  # [b, n_br]
        sel = jnp.where(in_shard[None, :], sel, False)
        sel = jax.lax.psum(sel.astype(jnp.uint8), "rp")
        matched = jnp.zeros((b, n_rules), dtype=jnp.uint8)
        matched = matched.at[:, branch_rule].max((sel > 0).astype(jnp.uint8))
    else:
        matched = jax.lax.psum(
            jnp.zeros((b, n_rules), dtype=jnp.uint8), "rp"
        )
    matched = matched | always_match.astype(jnp.uint8)[None, :]
    empty = (lens_local == 0)[:, None].astype(jnp.uint8)
    matched = matched | (empty_only.astype(jnp.uint8)[None, :] * empty)
    return matched


def sharded_match_fn(compiled: CompiledRules, mesh: Mesh):
    """Build the jitted multi-device match step (XLA-scan body).

    Returns fn(params, cls_ids [B, L], lens [B]) → matched [B, n_rules]
    uint8, with B divisible by the dp axis size and compiled.n_shards equal
    to the rp axis size.
    """
    rp = mesh.shape["rp"]
    if compiled.n_shards != rp:
        raise ValueError(
            f"ruleset compiled for {compiled.n_shards} shards, mesh rp={rp}"
        )
    n_rules = compiled.n_rules
    words_per_shard = compiled.words_per_shard

    def local_step(params, cls_local, lens_local):
        # state scan over this device's word slice only
        acc = nfa_jax.nfa_scan(params, cls_local, lens_local)  # [b, W_local]
        return _extract_local(
            acc, lens_local,
            params["acc_word"], params["acc_mask"], params["branch_rule"],
            params["always_match"], params["empty_only"],
            n_rules, words_per_shard,
        )

    fn = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(_param_specs(), P("dp", None), P("dp")),
        out_specs=P("dp", None),
        # the scan carry inside nfa_scan starts as a plain jnp.zeros; skip
        # the varying-manual-axes check rather than pcast-ing the carry
        check_vma=False,
    )
    return jax.jit(fn)


def shard_params(
    compiled: CompiledRules, mesh: Mesh
) -> Dict[str, jnp.ndarray]:
    """Device-put the match params with the mesh sharding applied."""
    params = nfa_jax.match_params(compiled)
    specs = _param_specs()
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }


# ---- Pallas per-device body (the production kernel under the mesh) ----


def _pallas_specs() -> Dict[str, P]:
    # btab_t rows are shard-major ([ns * 4 * wps_p, C_p]), masks_t likewise
    # ([ns * wps_p, 8]): sharding axis 0 over rp hands each device exactly
    # its own shard's slab
    return {
        "btab_t": P("rp", None),
        "masks_t": P("rp", None),
        "acc_word": P(),
        "acc_mask": P(),
        "branch_rule": P(),
        "always_match": P(),
        "empty_only": P(),
    }


def shard_pallas_params(
    prep: pallas_nfa.PallasRules, mesh: Mesh
) -> Dict[str, jnp.ndarray]:
    """Device-put the kernel tensors with the mesh sharding applied."""
    params = {
        "btab_t": prep.btab_t,
        "masks_t": prep.masks_t,
        "acc_word": prep.acc_word,
        "acc_mask": prep.acc_mask,
        "branch_rule": prep.branch_rule,
        "always_match": prep.always_match,
        "empty_only": prep.empty_only,
    }
    specs = _pallas_specs()
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }


def sharded_pallas_fn(
    prep: pallas_nfa.PallasRules,
    mesh: Mesh,
    B: int,
    L_p: int,
    block_b: int,
    interpret: bool = False,
):
    """Multi-device match step whose per-device body is the Pallas kernel.

    fn(params, cls_t [L_p, B], lens [B]) → matched [B, n_rules] uint8.
    B must be divisible by dp * block_b; prep.n_shards must equal rp.
    """
    dp, rp = mesh.shape["dp"], mesh.shape["rp"]
    if prep.n_shards != rp:
        raise ValueError(
            f"ruleset prepared for {prep.n_shards} shards, mesh rp={rp}"
        )
    if B % (dp * block_b):
        raise ValueError(
            f"batch {B} must be a multiple of dp*block_b = {dp * block_b}"
        )
    b_local = B // dp
    n_rules = prep.n_rules
    wps_p = prep.wps_p
    call = pallas_nfa._build_raw_call(
        b_local, L_p, prep.n_classes_p, 1, wps_p, block_b, interpret,
        carry=not prep.carry_free,
    )

    def local_step(params, cls_t_local, lens_local):
        lens_row = lens_local[None, :]
        maxtile = jnp.asarray(
            -(-lens_local.reshape(b_local // block_b, block_b).max(axis=1)
              // pallas_nfa._COLS_PER_STEP),
            dtype=jnp.int32,
        )
        acc_t = call(
            maxtile, cls_t_local, lens_row, params["btab_t"], params["masks_t"]
        )  # [wps_p, b_local]
        return _extract_local(
            acc_t.T, lens_local,
            params["acc_word"], params["acc_mask"], params["branch_rule"],
            params["always_match"], params["empty_only"],
            n_rules, wps_p,
        )

    fn = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(_pallas_specs(), P(None, "dp"), P("dp")),
        out_specs=P("dp", None),
        check_vma=False,
    )
    return jax.jit(fn)


# ---- fused two-stage prefilter under the mesh ----
#
# Stage 1 (the narrow factor/always automaton) is REPLICATED: every device
# scans its dp row's line shard against the whole stage-1 NFA — it is ~5x
# narrower than the full ruleset, so replicating it costs less than any
# resharding would. The candidate gate and compaction are dp-shard-local
# (identical across the rp members of a row, so no collective is needed to
# agree). Stage 2 (the full filterable-rule NFA) stays rp-sharded exactly
# like the single-stage path and runs ONLY on the compacted candidates; the
# one psum over rp of accept bits remains the only collective in the step.


def sharded_fused_fn(
    plan,                       # prefilter.PrefilterPlan (stage2 packed rp-sharded)
    mesh: Mesh,
    B: int,
    L_p: int,
    block_b: int,
    backend: str,               # xla | pallas | pallas-interpret
    cand_frac: float = 0.125,
):
    """Multi-device fused two-stage match step.

    Returns (fn, params, K_local) where fn(params1, params2, cls, lens) →
    (bits [B, n_rules] uint8 — always-rule static flags NOT yet applied,
    n_cand [dp] int32 — per-shard candidate counts for the overflow check).
    """
    from banjax_tpu.matcher.prefilter import gate_masks

    dp, rp = mesh.shape["dp"], mesh.shape["rp"]
    if plan.stage2.n_shards != rp:
        raise ValueError(
            f"plan stage2 packed for {plan.stage2.n_shards} shards, mesh rp={rp}"
        )
    b_local = B // dp
    block = min(block_b, b_local)
    K = min(b_local, max(block, -(-int(b_local * cand_frac) // block) * block))
    n_rules = plan.n_rules
    n_filt = plan.stage2.n_rules
    n_always = plan.n_always
    a_idx = jnp.asarray(plan.a_idx, dtype=jnp.int32)
    f_idx = jnp.asarray(plan.f_idx, dtype=jnp.int32)
    pallas = backend in ("pallas", "pallas-interpret")
    interpret = backend == "pallas-interpret"

    if pallas:
        prep1 = pallas_nfa.prepare(plan.stage1)
        prep2 = pallas_nfa.prepare(plan.stage2)
        fmask_np, a_word, a_mask, a_rule = gate_masks(plan, prep1)
        wps2 = prep2.wps_p
        cols = pallas_nfa._COLS_PER_STEP
        # stage 1 may itself be packed into several shards ("auto"); the
        # replicated body runs them as the kernel's shard grid axis
        call1 = pallas_nfa._build_raw_call(
            b_local, L_p, prep1.n_classes_p, prep1.n_shards, prep1.wps_p,
            block, interpret,
            carry=not prep1.carry_free,
        )
        # stage 2: each rp member owns exactly one word slab → local ns=1
        call2 = pallas_nfa._build_raw_call(
            K, L_p, prep2.n_classes_p, 1, wps2, min(block, K), interpret,
            carry=not prep2.carry_free,
        )
        params1 = {"btab_t": prep1.btab_t, "masks_t": prep1.masks_t}
        params2 = shard_pallas_params(prep2, mesh)
    else:
        fmask_np, a_word, a_mask, a_rule = gate_masks(plan)
        wps2 = plan.stage2.words_per_shard
        params1 = nfa_jax.match_params(plan.stage1)
        params2 = shard_params(plan.stage2, mesh)
    fmask = jnp.asarray(fmask_np)
    a_word_j = jnp.asarray(a_word)
    a_mask_j = jnp.asarray(a_mask)
    a_rule_j = jnp.asarray(a_rule)

    def _gate_and_compact(acc1, cls_rows_local, lens_local):
        """acc1 [b, W1]; cls_rows_local [b, L_p] → candidate gather."""
        cand = (acc1 & fmask[None, :]).max(axis=1) > 0
        n_cand = jnp.sum(cand.astype(jnp.int32))
        (idx,) = jnp.nonzero(cand, size=K, fill_value=0)
        valid = jax.lax.iota(jnp.int32, K) < n_cand
        cls2 = jnp.take(cls_rows_local, idx, axis=0)
        lens2 = jnp.where(valid, jnp.take(lens_local, idx), 0)
        return idx, valid, n_cand, cls2, lens2

    def _always_bits(acc1):
        """[b, n_always] uint8 from stage-1 accept words (dynamic part)."""
        b = acc1.shape[0]
        ab = jnp.zeros((b, max(1, n_always)), dtype=jnp.uint8)
        if n_always and a_word_j.shape[0] > 0:
            sel = (acc1[:, a_word_j] & a_mask_j) != 0  # [b, n_abr]
            ab = ab.at[:, a_rule_j].max(sel.astype(jnp.uint8))
        return ab

    def _merge(idx, valid, m2, ab, b):
        m2 = m2 & (valid[:, None] * jnp.uint8(0xFF))
        filt = jnp.zeros((b, n_filt), dtype=jnp.uint8).at[idx].max(m2)
        bits = jnp.zeros((b, n_rules), dtype=jnp.uint8)
        if n_always:
            bits = bits.at[:, a_idx].set(ab[:, :n_always])
        bits = bits.at[:, f_idx].set(filt)
        return bits

    if pallas:

        def local_step(p1, p2, cls_t_local, lens_local):
            lens_row = lens_local[None, :]
            maxtile1 = jnp.asarray(
                -(-lens_local.reshape(b_local // block, block).max(axis=1)
                  // cols),
                dtype=jnp.int32,
            )
            acc1 = call1(
                maxtile1, cls_t_local, lens_row, p1["btab_t"], p1["masks_t"]
            ).T  # [b, W1p]
            idx, valid, n_cand, cls2_t, lens2 = _gate_and_compact(
                acc1, cls_t_local.T, lens_local
            )
            blk2 = min(block, K)
            maxtile2 = jnp.asarray(
                -(-lens2.reshape(K // blk2, blk2).max(axis=1) // cols),
                dtype=jnp.int32,
            )
            acc2 = call2(
                maxtile2, cls2_t.T, lens2[None, :],
                p2["btab_t"], p2["masks_t"],
            ).T  # [K, wps2]
            m2 = _extract_local(
                acc2, lens2,
                p2["acc_word"], p2["acc_mask"], p2["branch_rule"],
                p2["always_match"], p2["empty_only"],
                n_filt, wps2,
            )
            bits = _merge(idx, valid, m2, _always_bits(acc1), b_local)
            return bits, n_cand[None]

        in_specs = (
            {"btab_t": P(), "masks_t": P()}, _pallas_specs(),
            P(None, "dp"), P("dp"),
        )
    else:

        def local_step(p1, p2, cls_local, lens_local):
            acc1 = nfa_jax.nfa_scan(p1, cls_local, lens_local)  # [b, W1]
            idx, valid, n_cand, cls2, lens2 = _gate_and_compact(
                acc1, cls_local, lens_local
            )
            acc2 = nfa_jax.nfa_scan(p2, cls2, lens2)            # [K, W2l]
            m2 = _extract_local(
                acc2, lens2,
                p2["acc_word"], p2["acc_mask"], p2["branch_rule"],
                p2["always_match"], p2["empty_only"],
                n_filt, wps2,
            )
            bits = _merge(idx, valid, m2, _always_bits(acc1), b_local)
            return bits, n_cand[None]

        p1_specs = {k: P() for k in params1}
        in_specs = (p1_specs, _param_specs(), P("dp", None), P("dp"))

    fn = shard_map(
        local_step,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P("dp", None), P("dp")),
        check_vma=False,
    )
    return jax.jit(fn), (params1, params2), K


class ShardedMatchBackend:
    """Batch-level mesh matcher: the drop-in device backend for TpuMatcher.

    match_bits pads/permutes an encoded batch onto the dp axis (length-
    sorted round-robin so every device gets a balanced mix of line lengths
    for the kernel's tile skip), runs the sharded device step, and returns
    the bitmap in the caller's original line order.
    """

    def __init__(
        self,
        compiled: CompiledRules,
        mesh: Mesh,
        max_len: int,
        backend: str = "pallas",   # pallas | pallas-interpret | xla
        block_b: int = 128,
        plan=None,                 # prefilter.PrefilterPlan (stage2 rp-packed)
        cand_frac: float = 0.125,
        health=None,               # resilience.health.ComponentHealth
    ):
        self.health = health
        self.mesh = mesh
        self.dp = mesh.shape["dp"]
        self.rp = mesh.shape["rp"]
        self.backend = backend
        self.n_rules = compiled.n_rules
        self.max_len = max_len
        self.block_b = block_b
        self.cand_frac = cand_frac
        self._fns: Dict[Tuple[int, int], object] = {}
        self._fused_fns: Dict[Tuple[int, int], object] = {}
        self.plan = plan
        # counters for observability: how often the fused path ran vs fell
        # back to the single-stage sharded NFA (candidate overflow)
        self.fused_batches = 0
        self.fallback_batches = 0
        # sharded submit/drain latency (metrics line): dispatch wall time,
        # the per-shard d2h pulls of the last drain, and their EWMAs
        self.submit_ms_ewma: Optional[float] = None
        self.merge_ms_ewma: Optional[float] = None
        self.last_shard_merge_ms: list = []
        if backend == "xla":
            self._prep = None
            self._params = shard_params(compiled, mesh)
            self._compiled = compiled
        else:
            self._prep = pallas_nfa.prepare(compiled)
            self._params = shard_pallas_params(self._prep, mesh)
            self._compiled = compiled

    def _fn(self, B: int, L_p: int):
        key = (B, L_p)
        fn = self._fns.get(key)
        if fn is None:
            if self.backend == "xla":
                fn = sharded_match_fn(self._compiled, self.mesh)
            else:
                fn = sharded_pallas_fn(
                    self._prep, self.mesh, B, L_p, self.block_b,
                    interpret=self.backend == "pallas-interpret",
                )
            self._fns[key] = fn
        return fn

    def _fused(self, B: int, L_p: int):
        key = (B, L_p)
        hit = self._fused_fns.get(key)
        if hit is None:
            hit = sharded_fused_fn(
                self.plan, self.mesh, B, L_p, self.block_b, self.backend,
                cand_frac=self.cand_frac,
            )
            self._fused_fns[key] = hit
        return hit

    def _dispatch(self, fn, params, cls_dev, lens_dev):
        trace.runtime_calls(3)  # two transfers, the dispatch
        if self.backend == "xla":
            return fn(params, jnp.asarray(cls_dev), jnp.asarray(lens_dev))
        cls_t = np.ascontiguousarray(cls_dev.T)
        return fn(params, jnp.asarray(cls_t), jnp.asarray(lens_dev))

    @staticmethod
    def _async_copy(arr) -> None:
        try:
            arr.copy_to_host_async()
        except AttributeError:
            pass

    def _ewma(self, attr: str, value_ms: float) -> None:
        prev = getattr(self, attr)
        setattr(
            self, attr,
            value_ms if prev is None else prev + 0.2 * (value_ms - prev),
        )

    def submit(self, cls_ids: np.ndarray, lens: np.ndarray) -> dict:
        """Dispatch the sharded device step for one batch WITHOUT forcing
        any device→host transfer — the streaming pipeline's submit stage.
        Returns a pend dict for collect(); the async host copies are
        already in flight so collect()'s pull overlaps later submits."""
        t0 = time.perf_counter()
        cls_ids = np.asarray(cls_ids, dtype=np.int32)
        lens = np.asarray(lens, dtype=np.int32)
        B, L = cls_ids.shape
        # bucket the padded batch to power-of-two multiples of dp*block_b so
        # varying batch sizes share a bounded set of compiled programs
        chunk = self.dp * self.block_b
        Bp = chunk
        while Bp < B:
            Bp <<= 1

        # trim the scan to the longest real line (pad columns can't change
        # state); power-of-two buckets bound the jitted L_p variants
        max_len = int(lens.max()) if B else 0
        L_cap = pallas_nfa._pad_to(L, pallas_nfa._COLS_PER_STEP)
        L_p = 32
        while L_p < max_len:
            L_p <<= 1
        L_p = max(pallas_nfa._COLS_PER_STEP, min(L_cap, L_p))

        # length-sorted round-robin over dp: device d gets sorted lines
        # d, d+dp, d+2*dp, ... — balanced tile-skip work per device
        order = np.argsort(lens, kind="stable")
        perm = np.empty(Bp, dtype=np.int64)
        rows_per_dev = Bp // self.dp
        pos = 0
        for d in range(self.dp):
            idx = np.arange(d, Bp, self.dp)
            perm[pos : pos + rows_per_dev] = idx
            pos += rows_per_dev
        # perm[k] = which padded-sorted row device-major slot k takes
        cls_sorted = np.zeros((Bp, L_p), dtype=np.int32)
        cls_sorted[:B, : min(L, L_p)] = cls_ids[order, : min(L, L_p)]
        lens_sorted = np.zeros(Bp, dtype=np.int32)
        lens_sorted[:B] = lens[order]
        cls_dev = cls_sorted[perm]
        lens_dev = lens_sorted[perm]

        pend = {
            "B": B, "Bp": Bp, "L_p": L_p, "order": order, "perm": perm,
            "lens_dev": lens_dev, "cls_dev": cls_dev, "fused": False,
            "h2d_bytes": cls_dev.nbytes + lens_dev.nbytes, "d2h_bytes": 0,
        }
        fused = None
        if self.plan is not None:
            # fused two-stage: stage-1 gate per dp shard, stage-2 on the
            # compacted candidates only; per-shard candidate overflow
            # (adversarial all-matching traffic) falls back to the
            # single-stage sharded NFA — never under-matches
            try:
                fused = self._fused(Bp, L_p)
            except pallas_nfa.PallasUnsupported as e:
                # e.g. stage-1 word alignment pushed a shard past the VMEM
                # budget: a kernel-shape refusal at first use must degrade
                # to the single-stage path, not kill consume_lines
                import logging

                msg = f"fused mesh prefilter unavailable ({e}); single-stage"
                logging.getLogger(__name__).info(msg)
                if self.health is not None:
                    self.health.degraded(msg)
                self.plan = None
        if fused is not None:
            fn, params, K = fused
            with trace.span("mesh-submit",
                            args={"dp": self.dp, "fused": True}):
                bits_d, n_cand = self._dispatch(
                    lambda p, c, ln: fn(*p, c, ln), params, cls_dev, lens_dev
                )
                self._async_copy(n_cand)
                self._async_copy(bits_d)
            pend.update(fused=True, K=K, bits_d=bits_d, n_cand=n_cand)
            if self.health is not None:
                self.health.beat()
        else:
            fn = self._fn(Bp, L_p)
            with trace.span("mesh-submit",
                            args={"dp": self.dp, "fused": False}):
                out_d = self._dispatch(fn, self._params, cls_dev, lens_dev)
                self._async_copy(out_d)
            pend["out_d"] = out_d
        self._ewma("submit_ms_ewma", (time.perf_counter() - t0) * 1e3)
        return pend

    def collect(self, pend: dict) -> np.ndarray:
        """Force a submit()ted batch: pull each dp shard's rows, merge them
        back into the caller's line order, apply the host-side always-rule
        flags.  The per-shard pull latencies land in last_shard_merge_ms
        (metrics: MeshShardMergeMsMax)."""
        t0 = time.perf_counter()
        B, Bp = pend["B"], pend["Bp"]
        order, perm = pend["order"], pend["perm"]
        out = None
        if pend["fused"]:
            if int(np.asarray(pend["n_cand"]).max()) <= pend["K"]:
                out = self._pull_shards(pend["bits_d"])
                self.fused_batches += 1
                if self.health is not None:
                    self.health.ok()
                # always-rule static flags (host-applied, like the
                # single-device collect())
                plan = self.plan
                if plan is not None and plan.n_always:
                    aw = np.asarray(plan.stage1.always_match[: plan.n_always])
                    ae = np.asarray(plan.stage1.empty_only[: plan.n_always])
                    if aw.any():
                        out[:, plan.a_idx[aw]] = 1
                    if ae.any():
                        empty_rows = np.flatnonzero(pend["lens_dev"] == 0)
                        out[np.ix_(empty_rows, plan.a_idx[ae])] = 1
            else:
                self.fallback_batches += 1
                if self.health is not None:
                    # correctness-preserving but slower: the single-stage
                    # sharded NFA reruns the whole batch
                    self.health.degraded(
                        f"fused prefilter overflow x{self.fallback_batches}; "
                        "single-stage rerun"
                    )
        if out is None:
            if "out_d" not in pend:
                fn = self._fn(Bp, pend["L_p"])
                pend["out_d"] = self._dispatch(
                    fn, self._params, pend["cls_dev"], pend["lens_dev"]
                )
            out = self._pull_shards(pend["out_d"])
        pend["d2h_bytes"] += out.nbytes

        # undo the device permutation, then the length sort
        unperm = np.empty(Bp, dtype=np.int64)
        unperm[perm] = np.arange(Bp)
        out_sorted = out[unperm][:B]
        unsorted = np.empty_like(out_sorted)
        unsorted[order] = out_sorted
        self._ewma("merge_ms_ewma", (time.perf_counter() - t0) * 1e3)
        return unsorted

    def _pull_shards(self, arr) -> np.ndarray:
        """Per-shard device→host pull into one writable host array: each dp
        member's row block lands at its own index (rp replicas of the same
        rows are pulled once), timed per shard."""
        self.last_shard_merge_ms = []
        try:
            shards = list(arr.addressable_shards)
        except (AttributeError, TypeError):
            shards = []
        if not shards:
            t0 = time.perf_counter()
            out = np.array(arr)
            self.last_shard_merge_ms.append(
                (time.perf_counter() - t0) * 1e3
            )
            return out
        out = np.empty(arr.shape, dtype=arr.dtype)
        seen = set()
        for sh in shards:
            idx = sh.index
            key = tuple(
                (sl.start, sl.stop, sl.step) if isinstance(sl, slice) else sl
                for sl in idx
            )
            if key in seen:
                continue  # an rp replica of rows already merged
            seen.add(key)
            t0 = time.perf_counter()
            # one span per device shard's d2h pull (child of the ambient
            # collect/drain span when a traced pipeline batch drives this)
            with trace.span("mesh-shard-pull",
                            args={"shard": len(seen) - 1}):
                data = np.asarray(sh.data)
            self.last_shard_merge_ms.append((time.perf_counter() - t0) * 1e3)
            out[idx] = data
        return out

    def match_bits(self, cls_ids: np.ndarray, lens: np.ndarray) -> np.ndarray:
        """[B, L] encoded lines → [B, n_rules] uint8, any B (dp remainder
        handled by padding; output order matches input order).  The
        synchronous convenience form of submit()/collect()."""
        return self.collect(self.submit(cls_ids, lens))
