"""TpuMatcher: the batched device-backed implementation of the Matcher seam.

One drive of a batch through the matcher, four stages (SURVEY.md §7.1 /
BASELINE.json north star):

  begin    host parse (encode.parse_line, the exact consumeLine splits),
           allowlist gate, byte-class encode
  submit   the one pass over the batch's distinct addresses (slot-
           admission gate + window slots), then the device dispatch:
           match AND window commit in one fused program a chunk, or the
           match bitmap alone (classic: a rule or a line only the host's
           `re` decides, a refused placement, no device windows)
  collect  wait for the device
  finish   the window events (fused: pulled; classic: applied here, on
           the device or in the host's RegexRateLimitStates) replayed
           into results and Banner side effects (BanOrChallengeIp +
           LogRegexBan), the call sequence of the CPU reference path

and two callers of it: the streaming scheduler's stage threads
(pipeline/scheduler.py), and the synchronous consume_lines, which calls
the four in turn on its own thread.  Cases the device can't decide
exactly (rules rulec can't lower; lines with a byte over 0x7F or past
8,192 bytes — matcher/longrows.py) fall back to host `re` per rule or
per line, so the observable Decision stream is byte-identical to
CpuMatcher for any input.

Selected by `matcher: tpu` in banjax-config.yaml (the Matcher interface
flag named in BASELINE.json); CpuMatcher remains the default.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from banjax_tpu.config.schema import Config, RegexWithRate
from banjax_tpu.matcher.kernels import nfa_match as pallas_nfa
from banjax_tpu.decisions.rate_limit import (
    RateLimitMatchType,
    RateLimitResult,
    RegexRateLimitStates,
)
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import BannerInterface, RegexBan
from banjax_tpu.matcher import compile_watch, longrows, nfa_jax, selectivity
from banjax_tpu.matcher.api import ConsumeLineResult, Matcher, RuleResult
from banjax_tpu.matcher.cpu_ref import OLD_LINE_CUTOFF_SECONDS
from banjax_tpu.matcher.encode import ParsedLine, encode_for_match, parse_line
from banjax_tpu.matcher.longrows import LONG_WIDTH
from banjax_tpu.matcher.workset import (
    CompositeWork,
    LazyResults,
    ListWork,
    NativeWork,
    SpanStrings,
    StringCount,
    decode_spans,
)
from banjax_tpu.matcher.rulec import compile_rules
from banjax_tpu.matcher.rulecache import RuleCache
from banjax_tpu.obs import flightrec, trace
from banjax_tpu.resilience import failpoints
from banjax_tpu.resilience.breaker import CLOSED, CircuitBreaker
from banjax_tpu.resilience.health import HealthRegistry, HealthStatus

log = logging.getLogger(__name__)

_MIN_BUCKET = 64


class TpuMatcher(Matcher):
    def __init__(
        self,
        config: Config,
        banner: BannerInterface,
        decision_lists: StaticDecisionLists,
        rate_limit_states: RegexRateLimitStates,
        n_shards: int = 1,
        health: Optional[HealthRegistry] = None,
    ):
        self.config = config
        self.banner = banner
        self.decision_lists = decision_lists
        self.rate_limit_states = rate_limit_states

        # circuit breaker around the device batch path: consecutive device
        # failures (or latency-budget breaches) trip it OPEN and every
        # batch routes to the CPU reference matcher until a half-open
        # probe succeeds — a wedged TPU degrades throughput, never drops
        # log lines (resilience/breaker.py)
        self.breaker = CircuitBreaker(
            failure_threshold=getattr(config, "breaker_failure_threshold", 3),
            recovery_seconds=getattr(config, "breaker_recovery_seconds", 30.0),
            window_size=getattr(config, "breaker_window_size", 0),
            name="matcher-device",
            # breaker trips land in the trace ring as instant events so a
            # Perfetto view shows WHEN degraded mode started relative to
            # the batch spans around it, and arm the incident flight
            # recorder (debounced; no-op when none is installed)
            on_trip=self._on_breaker_trip,
        )
        self._latency_budget_s = (
            getattr(config, "matcher_latency_budget_ms", 0.0) or 0.0
        ) / 1e3
        # when the config budget is unset, the pipeline scheduler installs
        # a source deriving it from the measured device p99 (ROADMAP
        # breaker-tuning item; obs/stats.py suggested_latency_budget_s)
        self._latency_budget_source = None
        self.fallback_batches = 0  # batches served by the CPU fallback
        # latency-budget breaches counted as breaker failures — the
        # observable validation of the derived budget the ROADMAP carried
        # (banjax_matcher_budget_trips_total; feeds the SLO engine)
        self.budget_trips = 0
        # fused chunks (match + window commit in one program at submit)
        # drained through the streaming pipeline, and how often one
        # overflowed into the classic replay mid-pipeline
        self.pipelined_fused_chunks = 0
        self.pipelined_fused_fallbacks = 0
        # lines over the short width (matcher_max_line_len) that the fused
        # program's long operand can decide, and their bytes, counted
        # where a batch is encoded; and the batches that could not go
        # fused for a line's sake, by cause: a byte over 0x7F, or a length
        # past LONG_WIDTH
        self.long_lines = 0
        self.long_line_bytes = 0
        self.unfused_batches = {"line_length": 0, "non_ascii": 0}
        # encode shards (an unsharded batch is one) by what gated them:
        # the one native call, or the per-line Python loop (no library,
        # a line with a newline in it) — added up where the encode thread
        # merges the shards; and the address strings made from spans
        # since (workset.SpanStrings), whoever asked
        self.gate_shards = {"native": 0, "python": 0}
        self._address_strings = StringCount()
        # host wall seconds inside the drain's `effector-replay` spans
        # (event decode + shadow absorb + Banner replay of committed chunks)
        self.effector_replay_s = 0.0
        # host wall seconds inside the submit stage's `submit-resolve`
        # spans (the one pass over a batch's distinct addresses)
        self.submit_resolve_s = 0.0
        # batches whose device-window apply is deferred to their drain
        # turn (classic-pend fallbacks): while any is outstanding, the
        # fused path must not commit at submit (see
        # _single_kernel_ordered) or window updates would cross batches
        # out of admission order
        self._drain_window_lock = threading.Lock()
        self._drain_window_batches = 0
        self._cpu_fallback = None
        self._health_registry = health
        self._health = health.register("matcher") if health is not None else None
        # init-time steps off the intended device path (see
        # _note_downgrade): kept so the health note outlives the
        # per-batch breaker accounting in _note_health
        self._downgrades: List[str] = []
        self._scan_interpret = False

        # Rule table: per-site rules first, then global — rule id i here is
        # column i of the device match bitmap, end to end.
        self._entries: List[Tuple[Optional[str], RegexWithRate]] = []
        self._per_site_idx: Dict[str, List[int]] = {}
        for site, rules in config.per_site_regexes_with_rates.items():
            for r in rules:
                self._per_site_idx.setdefault(site, []).append(len(self._entries))
                self._entries.append((site, r))
        self._global_idx: List[int] = []
        for r in config.regexes_with_rates:
            self._global_idx.append(len(self._entries))
            self._entries.append((None, r))

        # mesh mode: rule-parallel degree fixes the compile shard count so
        # each rp member owns exactly one self-contained word slab
        self._mesh = None
        self._mesh_rp = 0
        mesh_devices = getattr(config, "matcher_mesh_devices", 0) or 0
        if mesh_devices > 0:
            n_avail = len(jax.devices())
            if mesh_devices > n_avail:
                log.warning(
                    "matcher_mesh_devices=%d but only %d JAX devices are "
                    "attached; running single-device", mesh_devices, n_avail,
                )
            else:
                rp = getattr(config, "matcher_mesh_rp", 0) or 0
                if rp == 0:
                    rp = 1
                    while rp * 2 <= min(4, mesh_devices) and mesh_devices % (rp * 2) == 0:
                        rp *= 2
                if mesh_devices % rp != 0:
                    raise ValueError(
                        f"matcher_mesh_rp {rp} does not divide "
                        f"matcher_mesh_devices {mesh_devices}"
                    )
                self._mesh_rp = rp
                n_shards = rp

        # the three compiled forms of the ruleset, loaded from beside the
        # compile cache where an earlier start of this ruleset left them
        # (matcher/rulecache.py; `rules_cache.seconds` / `.source` are
        # banjax_rules_compile_seconds{source})
        patterns = [r.regex_string for _, r in self._entries]
        self.rules_cache = RuleCache(self._entries)
        self.compiled = self.rules_cache.get(
            f"single-{n_shards}",
            lambda: compile_rules(patterns, n_shards=n_shards),
        )
        for i, reason in self.compiled.unsupported.items():
            log.info(
                "rule %r falls back to the host regex path: %s",
                self._entries[i][1].rule, reason,
            )
        self._host_rule_idx = [
            i for i in range(len(self._entries)) if not self.compiled.device_ok[i]
        ]
        self._params = nfa_jax.match_params(self.compiled)
        # a byte's class as the long operand carries it (one byte a class
        # id wherever the fused program packs its input)
        self._class_of_byte = np.asarray(
            self.compiled.byte_to_class[:256],
            dtype=np.uint8 if self.compiled.n_classes <= 256 else np.int32,
        )
        self._max_len = config.matcher_max_line_len
        self._max_batch = max(_MIN_BUCKET, config.matcher_batch_lines)

        # native C batch parse+encode (banjax_tpu/native): ~16x the Python
        # per-line parse loop; per-line semantics identical (defer contract)
        self._native = False
        self._parse_scratch = None
        self._dedup_scratch = None
        # allowlist results per distinct (host, ip), valid for one
        # static-lists snapshot (cleared on hot reload / size bound)
        self._allow_cache: Dict[Tuple[str, str], bool] = {}
        self._allow_cache_snap = None
        if getattr(config, "matcher_native_parse", True):
            from banjax_tpu import native as _native

            self._native = _native.available()
            if self._native:
                # reused output buffers: fresh allocations cost ~15 ms in
                # page faults per 65k batch; each batch is fully consumed
                # (all reads are copies) before the next parse reuses them
                self._parse_scratch = _native.ParseScratch()
                self._dedup_scratch = _native.DedupScratch()
            else:
                log.info("native fastparse unavailable; Python parse path")

        # device backend: the Pallas kernel where it pays (TPU), the XLA
        # scan elsewhere; "pallas-interpret" is the CI path
        backend = getattr(config, "matcher_backend", "auto") or "auto"
        self._pallas_prep = None
        self._pallas_interpret = backend == "pallas-interpret"
        if backend == "pallas" and jax.default_backend() != "tpu":
            # compiled Mosaic can't lower off-TPU; failing per-batch at
            # runtime would drop every log line, so degrade at init instead
            self._note_downgrade(
                "matcher_backend=pallas requested but the JAX backend is "
                f"{jax.default_backend()}; falling back to the XLA scan",
                logging.WARNING,
            )
            backend = "xla"
        want_pallas = backend in ("pallas", "pallas-interpret") or (
            backend == "auto" and jax.default_backend() == "tpu"
        )
        # device-resident window counters (matcher/windows.py): authoritative
        # for the regex rules when enabled; the host RegexRateLimitStates is
        # bypassed (introspection goes through self.device_windows)
        self.device_windows = None
        self._active_table = None
        self.traffic_sketch = None
        self._slot_admission = False
        self._admission_min_estimate = 1
        # hosts with rules of their own or named in a hosts_to_skip get a
        # row of the per-host tables; every other host shares row 0
        hosts = sorted(
            set(self._per_site_idx)
            | {h for _, r in self._entries for h in r.hosts_to_skip}
        )
        self._host_row: Dict[str, int] = {
            h: i + 1 for i, h in enumerate(hosts)
        }
        if getattr(config, "matcher_device_windows", False):
            from banjax_tpu.matcher.windows import DeviceWindows

            self.device_windows = DeviceWindows(
                [r for _, r in self._entries],
                capacity=getattr(config, "matcher_window_capacity", 0),
                native_slotmgr=getattr(config, "slotmgr_native", True),
                warm_tier_enabled=getattr(config, "warm_tier_enabled", False),
                warm_tier_capacity=getattr(
                    config, "warm_tier_capacity", 1 << 20
                ),
            )
            self.device_windows.n_site_rules = (
                len(self._entries) - len(self._global_idx)
            )
            # active_table[h, rid]: rule rid applies to lines of host row h
            # (per-site rules of that host + global rules), minus
            # hosts_to_skip — the per-site-then-global loop of
            # regex_rate_limiter.go:175-211 as a device mask
            n_rules = len(self._entries)
            table = np.zeros((len(hosts) + 1, max(1, n_rules)), dtype=bool)
            for row_host, row in [(None, 0)] + list(self._host_row.items()):
                ids = (
                    self._per_site_idx.get(row_host, []) if row_host else []
                ) + self._global_idx
                for idx in ids:
                    if row_host and self._entries[idx][1].hosts_to_skip.get(row_host):
                        continue
                    table[row, idx] = True
            self._active_table = jnp.asarray(table)

            # traffic introspection plane (obs/sketch.py): count-min +
            # HLL + per-rule pressure folded in-stream per chunk, keyed
            # on the window slot ids already bound for the device — a
            # read-only telemetry sibling of the window state (ROADMAP
            # mega-state item 1 builds its cold admission on the same
            # structure)
            if getattr(config, "traffic_sketch_enabled", True):
                from banjax_tpu.obs.sketch import TrafficSketch

                self.traffic_sketch = TrafficSketch(
                    [r.rule for _, r in self._entries],
                    depth=getattr(config, "traffic_sketch_depth", 4),
                    width=getattr(config, "traffic_sketch_width", 8192),
                    hll_p=getattr(config, "traffic_sketch_hll_p", 12),
                    pull_seconds=getattr(
                        config, "traffic_sketch_pull_seconds", 5.0
                    ),
                    topk=getattr(config, "traffic_sketch_topk", 32),
                    max_candidates=getattr(
                        config, "traffic_sketch_candidates", 8192
                    ),
                )

            # cold-tier slot admission (mega-state tiering): an UNSEEN ip
            # claims a hot-tier slot only when the sketch estimate says it
            # plausibly crosses the cheapest rule threshold.  Requires the
            # sketch (the estimates) — admission silently stays off
            # without it.  min_estimate 0 derives the cheapest threshold
            # from the ruleset: min(hits_per_interval) + 1 is the
            # earliest row count at which ANY rule can fire.
            self._slot_admission = bool(
                getattr(config, "slot_admission_enabled", False)
            ) and self.traffic_sketch is not None
            me = int(getattr(config, "slot_admission_min_estimate", 0))
            if me <= 0:
                me = max(
                    1,
                    min(
                        (r.hits_per_interval for _, r in self._entries),
                        default=0,
                    ) + 1,
                )
            self._admission_min_estimate = me

        self._mesh_matcher = None
        if self._mesh_rp:
            from banjax_tpu.parallel.mesh import ShardedMatchBackend, make_mesh

            self._mesh = make_mesh(mesh_devices, rp=self._mesh_rp)
            if self._pallas_interpret:
                mesh_backend = "pallas-interpret"
            elif want_pallas:
                mesh_backend = "pallas"
            else:
                mesh_backend = "xla"

            # fused two-stage under the mesh: stage 1 replicated, stage 2
            # packed to exactly rp word slabs, shared byte classes with the
            # full single-stage tensors (one encode feeds everything)
            mesh_plan = None
            if getattr(config, "matcher_prefilter", True):
                from banjax_tpu.matcher.prefilter import build_plan

                try:
                    mesh_plan = build_plan(
                        [r.regex_string for _, r in self._entries],
                        byte_classes=(
                            self.compiled.byte_to_class,
                            self.compiled.n_classes,
                        ),
                        stage2_shards=self._mesh_rp,
                    )
                except Exception as e:  # noqa: BLE001 — plan bug must not kill the matcher
                    log.exception("mesh prefilter plan failed")
                    self._note_downgrade(
                        f"mesh prefilter plan failed ({e}); single-stage"
                    )
                if mesh_plan is not None and mesh_plan.stage2 is None:
                    # stage 1 decides every rule: nothing to shard over rp
                    mesh_plan = None

            # block granularity only matters for the compiled kernel; the
            # XLA/interpret bodies shouldn't pad every batch to dp*128 rows
            mesh_health = (
                self._health_registry.register("matcher-mesh")
                if self._health_registry is not None else None
            )

            def _mk(backend):
                return ShardedMatchBackend(
                    self.compiled, self._mesh, self._max_len, backend=backend,
                    block_b=128 if backend == "pallas" else 8,
                    plan=mesh_plan, health=mesh_health,
                )

            try:
                self._mesh_matcher = _mk(mesh_backend)
            except pallas_nfa.PallasUnsupported as e:
                self._note_downgrade(
                    f"mesh pallas backend unavailable ({e}); XLA-scan mesh"
                )
                self._mesh_matcher = _mk("xla")
            log.info(
                "matcher mesh: dp=%d rp=%d backend=%s prefilter=%s",
                self._mesh.shape["dp"], self._mesh_rp,
                self._mesh_matcher.backend, mesh_plan is not None,
            )

        if want_pallas and self._mesh_matcher is None:
            try:
                # re-shard for the kernel's VMEM/padding economics; byte
                # classes are shard-independent by rulec construction —
                # encode uses self.compiled's table, so check the invariant
                # rather than trust it
                comp = self.rules_cache.get(
                    "slabs", lambda: compile_rules(patterns, n_shards="auto")
                )
                if not np.array_equal(
                    comp.byte_to_class, self.compiled.byte_to_class
                ):
                    raise pallas_nfa.PallasUnsupported(
                        "byte-class table changed across re-shard"
                    )
                self._pallas_prep = pallas_nfa.prepare(comp)
            except pallas_nfa.PallasUnsupported as e:
                self._note_downgrade(
                    f"pallas matcher backend unavailable ({e}); using XLA scan"
                )

        # two-stage literal prefilter (matcher/prefilter.py): compile-time
        # rearrangement, bit-identical output; auto-disabled when the
        # ruleset has too few filterable rules. The fused variant shares
        # this matcher's byte classes, so the native parse's encode feeds
        # it directly and the whole two-stage pipeline is one device call.
        self._prefilter = None
        self._overflow_logged_at = 0.0  # _log_hottest_bucket's last line
        if getattr(config, "matcher_prefilter", True) and self._mesh_matcher is None:
            from banjax_tpu.matcher.prefilter import FusedPrefilter, build_plan

            try:
                plan = self.rules_cache.get("plan", lambda: build_plan(
                    patterns,
                    byte_classes=(
                        self.compiled.byte_to_class, self.compiled.n_classes
                    ),
                ))
            except Exception as e:  # noqa: BLE001 — a plan bug must not kill the matcher
                log.exception("prefilter plan construction failed")
                self._note_downgrade(
                    f"prefilter plan construction failed ({e}); single-stage"
                )
                plan = None
            if plan is not None:
                if self._pallas_interpret:
                    pf_backend = "pallas-interpret"
                elif self._pallas_prep is not None:
                    pf_backend = "pallas"
                else:
                    pf_backend = "xla"
                try:
                    self._prefilter = FusedPrefilter(
                        plan, pf_backend,
                        cand_frac=getattr(
                            config, "matcher_prefilter_cand_frac", 0.125
                        ),
                    )
                except pallas_nfa.PallasUnsupported as e:
                    self._note_downgrade(
                        f"prefilter unavailable ({e}); single-stage"
                    )

        # per-host per-site-then-global rule order as index arrays, so the
        # replay loops touch only matched rules instead of iterating the
        # whole ruleset per line (regex_rate_limiter.go:175-211 order)
        self._rule_pos_cache: Dict[str, Dict[int, int]] = {}
        self._global_pos = {int(x): k for k, x in enumerate(self._global_idx)}
        # the same order as tables over (host row, rule id), for the
        # columnar replay: rule ids ascending IS per-site-then-global
        # order (per-site ids precede global ids in self._entries), so a
        # row's results are its applicable matched ids, ascending.  Row 0
        # is every host without per-site rules or a hosts_to_skip entry.
        n_ent = max(1, len(self._entries))
        self._applies = np.zeros((len(self._host_row) + 1, n_ent), dtype=bool)
        self._skips = np.zeros_like(self._applies)
        for row_host, row in [(None, 0)] + list(self._host_row.items()):
            for idx in (
                self._per_site_idx.get(row_host, []) if row_host else []
            ) + self._global_idx:
                self._applies[row, idx] = True
                if row_host and self._entries[idx][1].hosts_to_skip.get(row_host):
                    self._skips[row, idx] = True
        self._rule_names = [r.rule for _, r in self._entries]

        # fused matcher+windows pipeline: one device dispatch per chunk
        # (match + window commit) when both the fused prefilter and device
        # windows are on, every rule is device-decidable (host-fallback
        # rules need the classic bitmap path) and the window-scan kernel
        # lowers on this backend
        self._fw_pipeline = None
        if (
            self.device_windows is not None
            and self._prefilter is not None
            and not self._host_rule_idx
            and self._resolve_single_kernel()
        ):
            from banjax_tpu.matcher.fused_windows import FusedWindowsPipeline

            self._fw_pipeline = FusedWindowsPipeline(
                self._prefilter, self.device_windows, self._active_table,
                self.compiled.n_rules, scan_interpret=self._scan_interpret,
                traffic_sketch=self.traffic_sketch,
                skip_table=self._skips,
            )
            log.info("fused matcher+windows pipeline active (single-kernel)")
        if self._health is not None:
            self._health.info = self.describe()

    def _resolve_single_kernel(self) -> bool:
        """Whether the fused program may run here: the Pallas window-scan
        kernel (compiled Mosaic on TPU, interpret-mode elsewhere — the CI
        path) must reproduce the XLA lax.scan bit for bit in a selftest.
        A lowering or selftest failure leaves the matcher on the classic
        protocol — the same exact path an overflowing chunk takes — with
        a health-registry note, so a Mosaic regression costs throughput,
        never correctness."""
        self._scan_interpret = bool(
            self._pallas_interpret or jax.default_backend() != "tpu"
        )
        comp = (
            self._health_registry.register("matcher-single-kernel")
            if self._health_registry is not None else None
        )
        try:
            from banjax_tpu.matcher.kernels import fused_match_window

            fused_match_window.scan_selftest(self._scan_interpret)
        except Exception as e:  # noqa: BLE001 — downgrade, never fail the matcher
            msg = (
                f"single-kernel window-scan unavailable ({e}); "
                "classic bitmap protocol"
            )
            self._note_downgrade(msg, logging.WARNING)
            if comp is not None:
                comp.degraded(msg)
            return False
        if comp is not None:
            comp.ok(
                "single-kernel fused path active "
                + ("(interpret scan)" if self._scan_interpret
                   else "(compiled scan)")
            )
        return True

    def _note_downgrade(self, msg: str, level: int = logging.INFO) -> None:
        """An init-time step off the intended device path: logged as
        before, and left as a DEGRADED note on the `matcher` health
        component so /healthz shows which path this process took."""
        log.log(level, msg)
        self._downgrades.append(msg)
        if self._health is not None:
            self._health.degraded("; ".join(self._downgrades))

    def describe(self) -> dict:
        """Read-only description of the device path this matcher
        resolved to at construction (the matcher component's `info` in
        /healthz)."""
        dev = jax.devices()[0]
        fw = self._fw_pipeline
        mm = self._mesh_matcher
        if mm is not None:
            backend = mm.backend
        elif self._prefilter is not None:
            backend = self._prefilter.backend
        elif self._pallas_prep is None:
            backend = "xla"
        else:
            backend = "pallas-interpret" if self._pallas_interpret else "pallas"
        nfa = "xla" if backend == "xla" else "pallas"
        return {
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "nfa_backend": nfa,
            "match_interpret": (
                backend == "pallas-interpret" if nfa == "pallas" else None
            ),
            "scan_interpret": (
                bool(self._scan_interpret) if fw is not None else None
            ),
            "fused_protocol": "classic" if fw is None else "single-kernel",
            "prefilter": self._prefilter is not None or (
                mm is not None and mm.plan is not None
            ),
            # the plan's own selection: rules stage 1 decides by itself
            # (prefilter._stage1_decides) among its always-columns
            "stage1_decided_rules": (
                self._prefilter.plan.n_decided
                if self._prefilter is not None else None
            ),
            # how many rules the plan runs by each route, and by name the
            # ones it runs whole in stage 1 although they have a factor
            # (too weak to gate on: selectivity.weak_gate)
            "plan_routes": (
                self._prefilter.plan.routes()
                if self._prefilter is not None else None
            ),
            "plan_promoted": (
                [self._rule_names[i] for i in self._prefilter.plan.p_idx]
                if self._prefilter is not None else None
            ),
            "mesh_shape": (
                dict(self._mesh.shape) if self._mesh is not None else None
            ),
            "downgrades": list(self._downgrades),
        }

    # ---- Matcher API ----

    def consume_line(self, line_text: str, now_unix: Optional[float] = None) -> ConsumeLineResult:
        return self.consume_lines([line_text], now_unix)[0]

    def consume_lines(
        self, lines: Sequence[str], now_unix: Optional[float] = None,
        _fused_ok: bool = True,
    ) -> List[ConsumeLineResult]:
        """Breaker-guarded batch entry point.

        OPEN → the batch goes straight to the CPU reference matcher (the
        correctness oracle: byte-identical Decision stream, host-only).
        CLOSED/HALF_OPEN → the device path runs; a device exception or a
        latency-budget breach records a failure, and an excepting batch is
        re-run on the CPU fallback so its lines are never dropped.  Device
        dispatch happens before any Banner side effect fires, so the
        failure-then-fallback rerun cannot double-apply effects.
        """
        t0 = time.perf_counter()
        builds0 = compile_watch.count()
        try:
            if not self.breaker.allow():
                return self._fallback_consume(lines, now_unix)
            try:
                results = self._consume_lines_inner(
                    lines, now_unix, fused_ok=_fused_ok
                )
            except Exception:  # noqa: BLE001 — device failure → breaker + fallback
                log.exception(
                    "device matcher batch failed; re-running batch on the "
                    "CPU reference matcher"
                )
                self.breaker.record_failure()
                return self._fallback_consume(lines, now_unix)
            budget = self.effective_latency_budget_s()
            if (
                budget and time.perf_counter() - t0 > budget
                # a batch that built a device program is not a latency
                # sample (matcher/compile_watch.py)
                and compile_watch.count() == builds0
            ):
                self.budget_trips += 1
                self.breaker.record_failure()
            else:
                self.breaker.record_success()
            self._note_health()
            return results
        finally:
            self.stats.record_batch(len(lines), time.perf_counter() - t0)

    def consume_lines_serial(
        self, lines: Sequence[str], now_unix: Optional[float] = None
    ) -> List[ConsumeLineResult]:
        """consume_lines with the fused path off — the streaming
        scheduler's generic drain uses this: a generic batch drains on the
        drain thread while LATER batches' fused chunks already hold
        fused-pipeline order turns, so a fused chunk dispatched here
        would wait on turns that only release after this very drain
        completes (deadlock).  The batch rides the classic `pend`
        instead, differentially proven byte-identical; while it owes its
        window apply, batches submitted behind it go classic too
        (_single_kernel_ordered)."""
        return self.consume_lines(lines, now_unix, _fused_ok=False)

    def effective_latency_budget_s(self) -> float:
        """The breaker's per-batch latency budget: the configured
        `matcher_latency_budget_ms` when set, else the pipeline-derived
        value (3x EWMA device p99, floor 1 s) when a scheduler has
        installed a source, else 0 (budget check disabled)."""
        if self._latency_budget_s:
            return self._latency_budget_s
        src = self._latency_budget_source
        if src is None:
            return 0.0
        try:
            return max(0.0, float(src()))
        except Exception:  # noqa: BLE001 — a stats bug must not break consume
            log.exception("latency budget source failed; budget disabled")
            return 0.0

    def set_latency_budget_source(self, fn) -> None:
        self._latency_budget_source = fn

    # programs built so far: the scheduler compares it around a batch
    compile_events = staticmethod(compile_watch.count)

    def note_device_outcome(self, elapsed_s: float, ok: bool,
                            compiled: bool = False) -> None:
        """Breaker + health accounting for an externally-driven device
        dispatch (the pipeline scheduler's submit/collect stages).
        `compiled`: the batch built a device program, so its latency is
        not held against the budget (matcher/compile_watch.py)."""
        if not ok:
            self.breaker.record_failure()
        else:
            budget = self.effective_latency_budget_s()
            if budget and elapsed_s > budget and not compiled:
                self.budget_trips += 1
                self.breaker.record_failure()
            else:
                self.breaker.record_success()
        self._note_health()

    def _on_breaker_trip(self, name: str) -> None:
        trace.instant("breaker-trip", {"breaker": name})
        flightrec.notify("breaker-trip", name)

    def _fallback_matcher(self):
        if self._cpu_fallback is None:
            from banjax_tpu.matcher.cpu_ref import CpuMatcher

            self._cpu_fallback = CpuMatcher(
                self.config, self.banner, self.decision_lists,
                self.rate_limit_states,
            )
        return self._cpu_fallback

    def _fallback_consume(self, lines, now_unix) -> List[ConsumeLineResult]:
        """CPU-reference degraded mode.  Note: with device windows enabled
        the fallback counts in the host RegexRateLimitStates, so window
        state diverges from the on-device counters for the duration of the
        outage — under-counting briefly, exactly like the reference
        restarting."""
        self.fallback_batches += 1
        self._note_health()
        return self._fallback_matcher().consume_lines(list(lines), now_unix)

    def _note_health(self) -> None:
        if self._health is None:
            return
        state = self.breaker.state
        if state == CLOSED:
            if self._downgrades:
                self._health.degraded("; ".join(self._downgrades))
            else:
                self._health.ok()
        else:
            self._health.set_status(
                HealthStatus.DEGRADED,
                f"breaker {state}; batches on CPU reference matcher",
            )

    @property
    def gate_address_strings(self) -> int:
        """Address strings made from gated batches' spans so far."""
        return self._address_strings.n

    def _gate(self, lines, now, results, use_scratch=True,
              parse_threads=None):
        """Step 1: host parse + allowlist exemption
        (regex_rate_limiter.go:131-172) — one native C pass when available
        (banjax_tpu/native), with the Python reference path per deferred
        line and as fallback.  The gate stays COLUMNAR (workset.py): flag
        masks, unique-string tables, and a per-distinct-(host, ip)
        allowlist check, so no per-line Python objects exist on the hot
        path.  `use_scratch=False` (the pipeline split path) allocates
        fresh parse/dedup buffers: with batches in flight concurrently,
        batch N's work set must not alias buffers batch N+1's parse
        reuses.  → (work, pre_encoded, path): `path` says what gated the
        lines — "native" (the one call) or "python" (the per-line loop
        below) — for the caller to count (gate_shards)."""
        pre_encoded = None
        nb = None
        if self._native:
            from banjax_tpu import native

            nb = native.parse_encode_batch(
                lines, self.compiled.byte_to_class, self._max_len, now,
                OLD_LINE_CUTOFF_SECONDS,
                scratch=self._parse_scratch if use_scratch else None,
                max_threads=parse_threads,
            )
        if nb is not None:
            work, pre_encoded = self._native_gate(
                nb, lines, now, results, use_scratch=use_scratch
            )
        else:
            lw = ListWork()
            for i, text in enumerate(lines):
                p = parse_line(text, now, OLD_LINE_CUTOFF_SECONDS)
                if p.error:
                    log.warning("could not parse log line: %r", text)
                    results[i].error = True
                    continue
                if p.old_line:
                    results[i].old_line = True
                    continue
                if self.decision_lists.check_is_allowed(p.host, p.ip):
                    results[i].exempted = True
                    continue
                lw.append((i, p))
            work = lw
        return work, pre_encoded, "python" if nb is None else "native"

    def _consume_lines_inner(
        self, lines: Sequence[str], now_unix: Optional[float] = None,
        fused_ok: bool = True,
    ) -> List[ConsumeLineResult]:
        """The synchronous drive of the four stages (see the split
        protocol below): `lines` in consecutive batches of at most
        matcher_batch_lines, each run to its end on this thread before
        the next begins.  One reading of the clock serves the gate, the
        submit and the finish, so no line ages between the stages and
        `old_line` is the gate's alone."""
        now = time.time() if now_unix is None else now_unix
        mb = self._max_batch
        if len(lines) <= mb:
            return self._consume_batch(lines, now, fused_ok)
        results = LazyResults(len(lines))
        for row0 in range(0, len(lines), mb):
            results.absorb(
                self._consume_batch(lines[row0 : row0 + mb], now, fused_ok),
                row0,
            )
        return results

    def _consume_batch(self, lines, now: float, fused_ok: bool):
        """One batch through begin → submit → collect → finish under the
        synchronous entry's failure contract: what a stage raises leaves
        here, once the batch's remaining order turns and pins are free —
        consume_lines records the breaker failure and re-runs the lines
        on the CPU reference.  `fused_ok=False` (consume_lines_serial):
        the batch rides the classic `pend` and applies its windows in the
        finish."""
        state = self.pipeline_begin(lines, now, use_scratch=True)
        if not fused_ok:
            state.pop("fused_eligible", None)
        try:
            self.pipeline_submit(state, now)
            self.pipeline_collect(state)
            return self._finish_batch(state, now)[0]
        except Exception:
            self.pipeline_abort(state)
            raise

    def _apply_host_windows(self, work, bits, results) -> None:
        """Host window pass in original line order: per-site rules for the
        line's host first, then global rules (regex_rate_limiter.go:175-211).
        Lines with no match at all (the overwhelming majority) are
        skipped wholesale; matched lines touch only their matched rule
        ids, in order — O(matches), not O(lines × rules) Python."""
        row_any = bits.any(axis=1)
        for row in np.flatnonzero(row_any):
            i, p = work[int(row)]
            pos = self._rule_pos(p.host)
            ids = np.nonzero(bits[row])[0].tolist()
            try:
                for idx in sorted(
                    (x for x in ids if x in pos), key=pos.__getitem__
                ):
                    _, rule = self._entries[idx]
                    results[i].rule_results.append(
                        self._apply_matched_rule(rule, p)
                    )
            except Exception:  # noqa: BLE001 — a failing effector loses one line, not the batch
                log.exception("error applying rules to log line")
                results[i].error = True

    def close(self) -> None:
        """No buffered state: a batch's four stages have run to their end
        when consume_lines returns, and the scheduler drains its own."""

    # ---- the four stages (module docstring): one batch's drive ----
    #
    # The scheduler runs them on its stage threads, any number of batches
    # overlapped, and serializes the finishes in admission order; the
    # synchronous consume_lines (_consume_batch) calls them in turn.
    # Two device protocols ride the same four calls:
    #
    #   * classic bitmap — _match_bits_submit/collect, dense [B, n_rules]
    #     pulled to host, window apply (device or host) entirely at finish.
    #   * fused (matcher/fused_windows.py) — when the fused
    #     matcher+windows pipeline is active and every row of the batch
    #     is the device's to decide (_fused_rows_ok: a line over the
    #     short width rides its chunk's long operand), submit dispatches
    #     ONE program per chunk (match + window commit, gated in the
    #     program on its overflow flags), any number of batches ahead;
    #     finish pulls each chunk's buffer in admission order and replays
    #     its events.  The dense bitmap never crosses the host boundary,
    #     and staleness is cut at submit as a per-row live mask.
    #     Overflowing chunks replay classically mid-pipeline (the order
    #     turn held until the fallback applies).

    def pipeline_begin(self, lines: Sequence[str], now: float,
                       use_scratch: bool = False) -> dict:
        """Encode stage: parse + gate + byte-class encode.  Fresh (non-
        scratch) buffers — see _gate — because batches overlap in flight;
        a caller that runs each batch to its end before the next begins
        (the synchronous entry) may reuse the matcher's."""
        results = LazyResults(len(lines))
        work, pre_encoded, path = self._gate(
            lines, now, results, use_scratch=use_scratch
        )
        self.gate_shards[path] += 1
        return self._pipeline_state(lines, results, work, pre_encoded)

    def encode_shard(self, lines: Sequence[str], now: float):
        """One row shard of the encode stage: parse + gate + encode over
        a contiguous slice of the admission batch, fresh buffers (shards
        run concurrently on the scheduler's worker pool — the native
        parse and the columnar gate are GIL-free/thread-safe).  Returned
        indices are LOCAL to the shard; pipeline_begin_from_shards
        rebases them, and counts the shard by the `path` it returns
        (no counter is touched on the pool's threads)."""
        results = LazyResults(len(lines))
        work, pre_encoded, path = self._gate(
            lines, now, results, use_scratch=False, parse_threads=1
        )
        return work, pre_encoded, results, path

    def pipeline_begin_from_shards(
        self, lines: Sequence[str], now: float, shards
    ) -> dict:
        """Merge encode_shard outputs back into the exact state
        pipeline_begin would have produced single-threaded.  `shards` is
        [(row0, encode_shard's output), ...] in row order, covering
        `lines` exactly.  The merge is strict line order end to end:
        results rows rebase by row0, work sets concatenate positionally
        (workset.CompositeWork), the encoded arrays concatenate row-wise,
        and the merged unique-IP table is in global first-appearance
        order — so slot assignment, window events, and ban-log bytes are
        byte-identical to the single-thread path
        (tests/differential/test_host_parallel_differential.py)."""
        results = LazyResults(len(lines))
        parts, offsets, pres = [], [], []
        native_pre = True
        for row0, (work, pre, shard_results, path) in shards:
            self.gate_shards[path] += 1
            results.absorb(shard_results, row0)
            if not len(work):
                continue
            parts.append(work)
            offsets.append(row0)
            if pre is None:
                native_pre = False
            else:
                pres.append(pre)
        if not parts:
            work, pre_encoded = ListWork(), None
        elif len(parts) == 1 and offsets[0] == 0:
            work = parts[0]
            pre_encoded = pres[0] if (native_pre and pres) else None
        else:
            work = CompositeWork(parts, offsets)
            # a python-parsed shard (no native lib mid-flight) has no
            # encoded arrays: the merged batch re-encodes from rests —
            # correctness first, the fast path needs every shard native
            pre_encoded = None
            if native_pre:
                pre_encoded = (
                    *(np.concatenate([p[k] for p in pres]) for k in range(3)),
                    _long_len_of_shards(pres),
                )
        return self._pipeline_state(lines, results, work, pre_encoded)

    def _pipeline_state(self, lines, results, work, pre_encoded) -> dict:
        state = {
            "lines": lines, "results": results, "work": work,
            "pre": pre_encoded, "pend": None, "bits": None,
            "fused": None,  # list of in-flight fused chunk entries
        }
        if self._fw_pipeline is not None and len(work):
            if pre_encoded is None:
                pre_encoded = state["pre"] = self._encode_work(work)
            if self._fused_rows_ok(pre_encoded):
                state["fused_eligible"] = True
        return state

    def _encode_work(self, work) -> tuple:
        """The Python encode of a work batch, as the native gate leaves
        it: (cls_ids [n, max_len], lens, host_eval, long_len) — the last
        as longrows.long_lens has it, or None for a batch whose every row
        the dense matrix holds (the rule; nothing then looks at it)."""
        rests = [p.rest for _, p in work]
        pre = encode_for_match(self.compiled, rests, self._max_len)
        if not pre[2].any():
            return (*pre, None)
        return (*pre, longrows.long_lens(rests, pre[2]))

    def _fused_rows_ok(self, pre_encoded) -> bool:
        """Whether every row of an encoded batch is the fused program's
        to decide.  A row of the short matrix is, and so is a LONG row
        (ASCII, over the short width, within LONG_WIDTH: it travels in its
        chunk's long operand, _fused_chunks); a row with a byte over 0x7F
        or past LONG_WIDTH is the host's, and one such row takes its batch
        the classic way, counted by cause.  Counts the batch's long rows
        on the way."""
        host_eval, long_len = pre_encoded[2], pre_encoded[3]
        if long_len is None:
            return True
        is_long = long_len > 0
        self.long_lines += int(is_long.sum())
        self.long_line_bytes += int(long_len[is_long].sum())
        if (host_eval & ~is_long).any():
            cause = "line_length" if (long_len < 0).any() else "non_ascii"
            self.unfused_batches[cause] += 1
            return False
        return True

    def _fused_chunks(self, pre_encoded) -> List[Tuple[int, int]]:
        """[(start, stop)]: the rows of an encoded batch as fused chunks,
        matcher_batch_lines rows each — and fewer (halved until it fits)
        where a chunk would hold more long rows of a width than its
        operand of that width has room for (longrows.operands).  A line's
        length so never takes a batch off the fused path: a burst of long
        lines costs more, smaller dispatches.  More chunks than the
        batch's size alone asks for is a cut batch (_count_cut)."""
        n, mb = len(pre_encoded[1]), self._max_batch
        if pre_encoded[3] is None:
            return [(s, min(n, s + mb)) for s in range(0, n, mb)]
        pf = self._prefilter
        which = longrows.operand_of(pre_encoded[3])
        # long rows of each operand's width before every row
        upto = [
            np.concatenate([[0], np.cumsum(which == j)])
            for j in range(len(longrows.LONG_WIDTHS))
        ]
        out, s = [], 0
        while s < n:
            size = min(mb, n - s)
            while any(
                u[s + size] - u[s] > rows for u, (_, rows) in zip(
                    upto, longrows.operands(pf, pf._row_bucket(size)))
            ):
                size = max(1, size // 2)
            out.append((s, s + size))
            s += size
        return out

    def _count_cut(self, chunks, n: int) -> None:
        """Count a batch of `n` rows that is dispatched as `chunks`
        (_fused_chunks') if its long rows cut it (overflow cause
        `long_rows`; nothing replays)."""
        if len(chunks) > -(-n // self._max_batch):
            self._fw_pipeline.overflow_causes["long_rows"] += 1

    # the scheduler passes its now_fn() into pipeline_submit when this
    # attribute is set — the fused path commits window state at submit,
    # so the staleness live mask is evaluated HERE (deterministic under
    # an injected clock), not at drain
    pipeline_submit_takes_now = True

    def pipeline_submit(self, state: dict, now: Optional[float] = None) -> None:
        if not len(state["work"]):
            return
        if self.device_windows is not None:
            # the one pass over the batch's distinct addresses: the
            # slot-admission gate's verdict, and for a batch that commits
            # as one fused chunk its slots too
            self._resolve_submit(state)
            if not len(state["work"]):
                return
        if "slots" in state or (
            state.get("fused_eligible") and self._single_kernel_ordered()
        ):
            if self._submit_fused_pipeline(state, now):
                return
        state["pend"] = self._match_bits_submit(state["work"], state["pre"])
        if self.device_windows is not None:
            # this batch's window apply happens at ITS drain turn: gate
            # later fused commits (which happen at submit, i.e.
            # EARLIER than this batch's drain) until it completes, or
            # cross-batch window updates would reorder
            with self._drain_window_lock:
                self._drain_window_batches += 1
            state["window_at_drain"] = True

    @contextlib.contextmanager
    def _resolving(self, lap):
        """A `submit-resolve` span over a stretch of the pass.  Its wall,
        which `banjax_submit_resolve_seconds_total` exports, lies between
        the lap clock's mark that opens the stretch and the one that
        closes it: the phase `pass` plus the sketch's note inside it."""
        with trace.span("submit-resolve"):
            lap.mark("pass")
            t0 = lap.t
            try:
                yield
            finally:
                lap.mark("other")
                self.submit_resolve_s += lap.t - t0

    def _resolve_submit(self, state: dict) -> None:
        """The submit stage's one pass over the batch's distinct
        addresses (DeviceWindows.resolve_addresses): the slot-admission
        gate's verdict from one encoding and one probe of each table.
        Rows the gate refused are split off and applied here,
        synchronously — submits are sequential, so this batch's warm-tier
        writes land before the NEXT batch's probe and a refused address
        can never race its own state.  Their results ride
        `state["results"]` out at the finish; the shrunk work has no row
        the batch did not have, so its fused eligibility stands and its
        chunks are cut anew at dispatch.

        A batch that commits as ONE fused chunk is placed by the same
        pass, after the refused rows' apply: `state["slots"]` — the
        admitted rows' (slots, pinned; address hashes for the traffic
        sketch, None without one), or None when placement refused (the
        batch then goes the classic way).  Any other batch asks for the
        probe alone, and only where the verdict is not "admit" by
        arithmetic: its placement is its chunks' (_slots_for_work) or the
        classic replay's (_with_window_slots).

        The gate fails open: a pass that raises is logged and the whole
        batch admitted; a placement that then raises too is the batch's
        failure."""
        dw = self.device_windows
        sk = self.traffic_sketch
        gate = self._slot_admission
        # a distinct address of a batch has at least one row, so `estimate
        # + rows >= 1` whatever the sketch says: with a rule that bans on
        # the first hit the verdict is "admit", unasked
        asks = gate and self._admission_min_estimate > 1
        whole = bool(
            state.get("fused_eligible")
            and len(self._fused_chunks(state["pre"])) == 1
            and self._single_kernel_ordered()
        )
        if not (whole or asks):
            return
        lap = trace.lap()

        def keep_slots(uinv):
            state["slots"] = None
            if res.slots is not None:
                state["slots"] = (
                    res.slots[uinv],
                    self._sketch_row_hashes(uips, res, uinv, lap),
                )

        with self._resolving(lap):
            uips, uinv = self._distinct_addresses(state["work"])
            counts = None
            if asks:
                counts = np.bincount(
                    uinv, minlength=len(uips)
                ).astype(np.int64)
            try:
                if whole:
                    res = dw.resolve_addresses(
                        uips, counts=counts,
                        min_estimate=self._admission_min_estimate,
                        sketch=sk, gate=gate,
                    )
                else:
                    res = dw.probe_addresses(
                        uips, counts, self._admission_min_estimate, sk
                    )
            except Exception:  # noqa: BLE001 — the gate is an optimization; fail open
                log.exception("slot-admission gate failed; admitting batch")
                return
            if not len(res.refused):
                if whole:
                    keep_slots(uinv)
                return
        # a threshold of 2 or more only
        lap.mark("pass")
        sk.fold_refused(
            [uips[i] for i in res.refused.tolist()],
            counts[res.refused], hashes=res.refused_hashes,
        )
        state["work"], state["pre"], work_r, pre_r, adm = self._split_rows(
            state["work"], state["pre"], res.admit[uinv]
        )
        self._consume_refused(work_r, pre_r, state["results"])
        lap.mark("other")
        if whole:
            with self._resolving(lap):
                dw.place_resolved(res)
                keep_slots(uinv[adm])

    def _distinct_addresses(self, work):
        """(a batch's distinct addresses, the per-row inverse) for the
        pass over them, in the form the work set holds them: byte spans
        off the native parse (workset unique_ip_spans; what the native
        slot manager works on), else strings (a Python parse, the dict
        path)."""
        got = None
        if self.device_windows.slotmgr_native:
            got = work.unique_ip_spans()
        return work.unique_ips() if got is None else got

    def _sketch_row_hashes(self, uips, res, uinv, lap):
        """What the traffic sketch takes of a resolved batch, timed as the
        phase `sketch`: one append of the admitted distinct addresses to
        its candidate log, and the rows' address hashes by one gather —
        the operand a chunk's fold is keyed on (None without a sketch)."""
        sk = self.traffic_sketch
        if sk is None:
            return None
        was = lap.phase
        lap.mark("sketch")
        hashes = res.hashes
        if hashes is None:  # the pass ran off the native path
            hashes = sk.base_hashes(uips)
        try:
            if len(res.refused):
                adm = np.flatnonzero(res.admit)
                sk.note_assignments(
                    [uips[i] for i in adm.tolist()], hashes[adm])
            else:
                sk.note_assignments(uips, hashes)
        except Exception:  # noqa: BLE001 — sketch is passive by contract
            log.exception("traffic sketch candidate note failed")
        row_hashes = hashes[uinv]
        lap.mark(was)
        return row_hashes

    def _single_kernel_ordered(self) -> bool:
        """Commit-at-submit is only order-safe while no EARLIER admitted
        batch still owes a drain-time window apply (a classic-pend
        fallback from slot refusal or a row only the host's `re`
        decides).  While one is
        outstanding, this batch joins the classic path too — the single
        drain thread then applies everything in admission order."""
        with self._drain_window_lock:
            return self._drain_window_batches == 0

    def _submit_fused_pipeline(self, state: dict,
                               now: Optional[float] = None) -> bool:
        """Dispatch the fused match+window program for every chunk of
        the batch.  Each chunk is final on return, and the 10 s staleness
        cutoff is applied here as the program's live-mask input (`now`,
        from the scheduler's clock; falls back to wall time on the
        direct-call path).  Returns False — nothing dispatched — when
        slot allocation refuses, so the caller falls
        back to the classic bitmap protocol for this batch.  Any other
        failure abandons the entries and re-raises (the scheduler then
        drains the batch generically: an already-committed chunk's
        generic rerun can double-count window hits, never Banner
        effects)."""
        # every chunk's slots, pinned, before any chunk is dispatched: a
        # chunk commits at its dispatch, so a batch that went the classic
        # way after one had would count that chunk's hits twice.  One
        # chunk (the rule, unless long rows cut the batch): the submit
        # stage's pass has them (`state["slots"]`, with the rows' hashes
        # beside).  Pins not yet handed to a chunk's submit are given
        # back on every way out
        from banjax_tpu.matcher.windows import split_ns

        placed: list = []
        entries = []
        n_stale = 0
        try:
            failpoints.check("matcher.device")
            work = state["work"]
            pre = state["pre"]
            if now is None:
                now = time.time()
            lap = trace.lap()
            chunks = self._fused_chunks(pre)
            if "slots" in state:
                got = state.pop("slots")
                if got is None:
                    return False  # placement refused
                if len(chunks) == 1:
                    placed = [got]
                else:
                    # the pass placed the batch as ONE chunk, and the rows
                    # it split off since left a smaller one, with a
                    # smaller long operand: placed again, chunk by chunk
                    self.device_windows.release_pins(got[0])
            if not placed:
                # several chunks, or one whose pass failed open
                lap.mark("pass")
                for s, stop in chunks:
                    got = self._slots_for_work(work[s:stop])
                    if got is None:
                        # more distinct IPs than free+unpinned slots
                        lap.mark("other")
                        return False
                    placed.append(got)
            self._count_cut(chunks, len(work))
            placed.reverse()
            for s, stop in chunks:
                lap.mark("operands", row0=s)
                wc = work[s:stop]
                live = stale = None
                ages_s = now - wc.ts_array() / 1e9
                st = ages_s > OLD_LINE_CUTOFF_SECONDS
                if st.any():
                    stale, live = st, ~st
                    n_stale += int(st.sum())
                # a phase ends before a span opens over what follows it
                lap.mark("other")
                with trace.span("program-ab-fused", args={"row0": s}):
                    lap.mark("operands")
                    slots, row_hashes = placed[-1]
                    pc = _rows_of(pre, slice(s, stop))
                    ts_s, ts_ns = split_ns(wc.ts_array())
                    host_idx = wc.host_idx(self._host_row)
                    pend = self._fw_pipeline.submit(
                        pc[0], pc[1], slots, ts_s, ts_ns, host_idx,
                        live=live, long_rows=self._long_rows_of(wc, pc[3]),
                        row_hashes=row_hashes,
                    )
                    placed.pop()  # the pins are the pipeline's now
                entries.append({
                    "work": wc, "pre": pc, "slots": slots, "ts_s": ts_s,
                    "ts_ns": ts_ns, "host_idx": host_idx, "pend": pend,
                    "row0": s, "live": live, "stale": stale,
                })
        except Exception:
            for prev in entries:
                self._fw_pipeline.abandon(prev["pend"])
            raise
        finally:
            for left in placed:
                self.device_windows.release_pins(left[0])
        state["fused"], state["n_stale"] = entries, n_stale
        return True

    def pipeline_collect(self, state: dict) -> None:
        if state.get("fused") is not None:
            # wait for every chunk's program (compute only — the buffer's
            # pull is async and lands before resolve needs it); on failure
            # free the chunks' order turns and pins so the generic-drain
            # rerun cannot deadlock later fused batches
            try:
                for e in state["fused"]:
                    buf = e["pend"].sparse_buf
                    try:
                        buf.block_until_ready()
                    except AttributeError:
                        np.asarray(buf)
            except Exception:
                for e in state["fused"]:
                    self._fw_pipeline.abandon(e["pend"])
                state["fused"] = None
                raise
            return
        if state["pend"] is not None:
            state["bits"] = self._match_bits_collect(state["pend"])

    def pipeline_abort(self, state: dict) -> None:
        """Settle a batch the drain stage will never finish (drain-stage
        failure): free the fused chunks' order turns and slot pins so
        later batches' resolves can't deadlock.  Idempotent."""
        entries = state.get("fused")
        state["fused"] = None
        self._drain_window_done(state)
        if entries:
            for e in entries:
                try:
                    self._fw_pipeline.abandon(e["pend"])
                except Exception:  # noqa: BLE001 — abort must settle every entry
                    log.exception("fused pipeline abandon failed")

    def _drain_window_done(self, state: dict) -> None:
        """Release one drain-time window-apply slot exactly once per
        batch (pipeline_finish's finally AND pipeline_abort may both
        run for a failing batch)."""
        if state.pop("window_at_drain", False):
            with self._drain_window_lock:
                self._drain_window_batches -= 1

    def pipeline_finish(self, state: dict, now: float):
        """Drain stage: _finish_batch under the drain's failure contract.
        A fused chunk that fails at its settle costs its own lines —
        marked `error`, one breaker failure — and the batch goes on with
        the chunks after it: a dead chunk must not wedge the stream.  Any
        other failure is the batch's and leaves here (the scheduler
        counts its lines shed and aborts it)."""
        t0 = time.perf_counter()
        try:
            while True:
                try:
                    out = self._finish_batch(state, now)
                except _ChunkFailed as failed:
                    log.exception(
                        "single-kernel chunk failed at its drain; chunk "
                        "lines marked error"
                    )
                    e = failed.chunk
                    orig = np.asarray(e["work"].orig_rows())
                    if e["live"] is not None:
                        orig = orig[e["live"]]
                    for i in orig.tolist():
                        state["results"][i].error = True
                    self.note_device_outcome(0.0, ok=False)
                    continue
                self._note_health()
                return out
        finally:
            self.stats.record_batch(
                len(state["lines"]), time.perf_counter() - t0
            )

    def _finish_batch(self, state: dict, now: float):
        """The finish stage's work, raising what it meets: the staleness
        re-check at EFFECTOR DRAIN time (the reference's 10 s cutoff,
        regex_rate_limiter.go:164-167, applied end-to-end — a line that
        aged out while queued in the pipeline is dropped here, marked
        old_line, and counted), then the window pass + Banner replay.
        Returns (results, n_stale_dropped).

        Fused chunks committed at submit (live mask = submit-time
        staleness): their finish is pure event pull + replay, no
        drain-time re-cut, chunk by chunk in admission order.  A chunk
        leaves `state["fused"]` as its settle begins and a failing one
        raises _ChunkFailed, so whoever catches finds the chunks after
        it still there: to go on with (pipeline_finish calls again) or
        to free (pipeline_abort, as the synchronous entry does)."""
        results = state["results"]
        work, bits = state["work"], state["bits"]
        n_stale = 0
        try:
            if not len(work):
                return results, 0
            entries = state.get("fused")
            if entries is not None:
                while entries:
                    self._settle_chunk(entries.pop(0), results)
                return results, state["n_stale"]
            ages_s = now - work.ts_array() / 1e9
            stale = ages_s > OLD_LINE_CUTOFF_SECONDS
            if stale.any():
                n_stale = int(stale.sum())
                _mark_old(work, stale, results)
                keep = np.flatnonzero(~stale)
                work = work.take(keep)
                bits = bits[keep]
                if not len(work):
                    return results, n_stale
            if self.device_windows is not None:
                self._apply_device_windows(work, bits, results)
            else:
                self._apply_host_windows(work, bits, results)
            return results, n_stale
        finally:
            self._drain_window_done(state)

    def _settle_chunk(self, e, results) -> None:
        """Settle one fused chunk at its order turn: the window commit
        already ran in the program at submit (the live mask carried the
        submit-time 10 s staleness cut), so this is a pure d2h pull
        (async since submit) + decode + Banner replay.  An overflow /
        chain-gated chunk replays classically through the fallback (its
        program committed nothing — its own gate).  A failure leaves as
        _ChunkFailed; on every way out the chunk's pins and order turn
        are free."""
        from banjax_tpu.matcher.fused_windows import PipelineOverflow

        fw = self._fw_pipeline
        pend, live = e["pend"], e["live"]
        if e["stale"] is not None:
            _mark_old(e["work"], e["stale"], results)
        try:
            failpoints.check("matcher.resolve")
            try:
                fw.resolve(pend)
            except PipelineOverflow as ov:
                trace.instant("fused-overflow-fallback", {"row0": e["row0"]})
                self.pipelined_fused_fallbacks += 1
                self._pipeline_fallback_entry(e, ov, results, live=live)
                return
            t0 = time.perf_counter()
            try:
                with trace.span("effector-replay", args={"row0": e["row0"]}):
                    res = fw.collect(pend)
                    self._replay_window_events(
                        e["work"], None,
                        (res.matched_pairs, res.always_bits),
                        res.events, results, live_rows=live,
                    )
                    self.pipelined_fused_chunks += 1
            finally:
                self.effector_replay_s += time.perf_counter() - t0
        except Exception as exc:
            # resolve, collect and the fallback settle what they took up;
            # a chunk that died before its resolve is settled here
            fw.abandon(pend)
            raise _ChunkFailed(e) from exc
        finally:
            self.stats.note_xfer(pend.h2d_bytes, pend.d2h_bytes)

    def probe(self, now_unix: Optional[float] = None) -> bool:
        """Synthetic device probe (ROADMAP matcher-staleness item): one
        canned line through the pure match path — no window updates, no
        Banner effects — so a wedged device trips the breaker/health while
        the tailer is idle, not at the next traffic burst.  Returns False
        when the probe failed or the breaker refused it."""
        if not self.breaker.allow():
            return False
        now = time.time() if now_unix is None else now_unix
        line = (
            f"{now:.6f} 203.0.113.1 GET banjax-probe.invalid "
            "GET /__banjax_probe HTTP/1.1 probe -"
        )
        t0 = time.perf_counter()
        try:
            lw = ListWork()
            lw.append((0, parse_line(line, now, OLD_LINE_CUTOFF_SECONDS)))
            self._match_bits(lw, None)
        except Exception:  # noqa: BLE001 — a probe failure is the signal, not a crash
            log.exception("matcher device probe failed")
            self.note_device_outcome(time.perf_counter() - t0, ok=False)
            return False
        self.note_device_outcome(time.perf_counter() - t0, ok=True)
        return self.breaker.state == CLOSED

    def _slots_for_work(self, work) -> Optional[tuple]:
        """(window-slot ids, address hashes) for a work batch's rows: one
        LRU decision + one pin per DISTINCT ip (the unique tables the
        gate already built), then a gather back to row order; the hashes
        are what the traffic sketch folds the rows under (None without
        one).  None when placement refused.  Pin/release semantics are
        unchanged — release_pins deduplicates slot ids either way."""
        uips, uinv = self._distinct_addresses(work)
        res = self.device_windows.resolve_addresses(
            uips, sketch=self.traffic_sketch
        )
        if res.slots is None:
            return None
        return res.slots[uinv], self._sketch_row_hashes(
            uips, res, uinv, trace.lap())

    # ---- cold-tier slot admission (mega-state tiering) ----

    @staticmethod
    def _split_rows(work, pre_encoded, row_mask):
        """Row-disjoint takes of a batch by the gate's per-row verdict:
        (work_admitted, pre_admitted, work_refused, pre_refused, the
        admitted rows' indices)."""
        adm = np.flatnonzero(row_mask)
        ref = np.flatnonzero(~row_mask)
        pre_a = pre_r = None
        if pre_encoded is not None:
            pre_a = _rows_of(pre_encoded, adm)
            pre_r = _rows_of(pre_encoded, ref)
        return work.take(adm), pre_a, work.take(ref), pre_r, adm

    def _consume_refused(self, work, pre_encoded, results) -> None:
        """Classic per-line path for slot-REFUSED rows: device-STATELESS
        match (no slot claimed, no device window state touched), then the
        window transitions applied host-side in the canonical
        (line, rule_id) order — apply_host_events replicates _window_step
        exactly and homes the state in the warm tier, so a refused IP
        that matched anything is admitted next batch.  Effects replay
        through the same _replay_window_events as every other path
        (Banner, provenance, rule pressure — full parity)."""
        if not len(work):
            return
        bits = self._match_bits(work, pre_encoded)
        events_in = []
        row_any = bits.any(axis=1)
        for row in np.flatnonzero(row_any):
            row = int(row)
            _, p = work[row]
            pos = self._rule_pos(p.host)
            # applicable rule ids ascending == per-site-then-global
            # (per-site ids precede global ids in self._entries)
            for idx in sorted(
                x for x in np.nonzero(bits[row])[0].tolist() if x in pos
            ):
                _, rule = self._entries[idx]
                if rule.hosts_to_skip.get(p.host):
                    continue  # no window event — active_table parity
                events_in.append((row, idx, p.ip, p.timestamp_ns))
        events = self.device_windows.apply_host_events(events_in)
        self._replay_window_events(work, bits, None, events, results)

    def _native_gate(self, nb, lines, now, results, use_scratch=True):
        """Step 1 over a native ParsedBatch: ONE call into C (native.gate:
        the candidate rows, the first-appearance tables of their
        addresses and hosts as spans of the blob, the per-row columns),
        then only what is rare — a deferred row's Python parse patched
        in, an error or old row's mark, the allowlist per DISTINCT (host,
        ip) with a snapshot-keyed cache where the lists have any allow
        entry — and a columnar NativeWork.  No string is made of an
        address here (workset.SpanStrings makes one when asked).
        Semantics identical to the per-line reference loop."""
        from banjax_tpu import native

        n = nb.n
        g = native.gate(nb, self._dedup_scratch if use_scratch else None)
        if g is None:  # an empty batch
            return ListWork(), None
        flags = nb.flags
        defer_map: Dict[int, ParsedLine] = {}
        span_buf = nb.blob
        ips_u = SpanStrings(
            span_buf, g.ip_off, g.ip_len, self._address_strings
        )
        hosts_u = decode_spans(span_buf, g.host_off, g.host_len)
        err_rows = old_rows = ()
        if g.n_defer:
            defer_map, err_rows, old_rows, ips_u, span_buf = \
                self._patch_deferred(nb, g, lines, now, ips_u, hosts_u)
        else:
            if g.n_err:
                err_rows = np.flatnonzero(flags & native.FLAG_ERROR).tolist()
            if g.n_old:
                old_rows = np.flatnonzero(flags & native.FLAG_OLD).tolist()
        for r in err_rows:
            log.warning("could not parse log line: %r", lines[r])
            results[r].error = True
        for r in old_rows:
            results[r].old_line = True

        cand = g.rows
        if cand.size == 0:
            return ListWork(), None
        ip_inv, host_inv, ts = g.ip_inv, g.host_inv, g.ts
        host_eval, long_len = g.host_eval, g.long_len

        # allowlist per distinct (host, ip) pair, cached across batches
        # until the static-lists generation bumps (hot reload) — the CIDR
        # filters parse the ip string per check, which at per-line rates
        # costs more than the device match. A decision-lists object
        # WITHOUT the public counter never caches (fail safe, not stale).
        gen = getattr(self.decision_lists, "generation", None)
        if gen is None:
            self._allow_cache = {}
            self._allow_cache_snap = None
        elif gen != self._allow_cache_snap or \
                len(self._allow_cache) > 500_000:
            self._allow_cache = {}
            self._allow_cache_snap = gen
        has_allow = getattr(
            self.decision_lists, "has_any_allow_entries", lambda: True
        )()
        rows = cand
        if has_allow:
            ips_u = list(ips_u)  # the check takes strings
            n_ip = max(1, len(ips_u))
            pair = host_inv * n_ip + ip_inv
            upair, upair_inv = np.unique(pair, return_inverse=True)
            allowed_u = np.empty(upair.size, dtype=bool)
            cache = self._allow_cache
            check = self.decision_lists.check_is_allowed
            for j, pr in enumerate(upair.tolist()):
                h = hosts_u[pr // n_ip]
                ip = ips_u[pr % n_ip]
                v = cache.get((h, ip))
                if v is None:
                    v = check(h, ip)
                    cache[(h, ip)] = v
                allowed_u[j] = v
            allowed = allowed_u[upair_inv]
            if allowed.any():
                for k in np.flatnonzero(allowed):
                    results[int(cand[k])].exempted = True
                keep = ~allowed
                rows = cand[keep]
                if rows.size == 0:
                    return ListWork(), None
                ip_inv, host_inv, ts = ip_inv[keep], host_inv[keep], ts[keep]
                host_eval, long_len = host_eval[keep], long_len[keep]
        work = NativeWork(
            nb, rows, ips_u, ip_inv, hosts_u, host_inv, ts, defer_map,
            (np.frombuffer(span_buf, dtype=np.uint8), g.ip_off, g.ip_len),
        )

        if rows.size == n:
            # nothing filtered (the common clean-traffic batch): views,
            # not 33 MB gather copies of the class matrix
            cls_ids = nb.cls_ids[:n]
            lens = nb.lens[:n]
        else:
            cls_ids = nb.cls_ids[rows]
            lens = nb.lens[rows]
        if not g.n_host_eval or (rows is not cand and not host_eval.any()):
            # as longrows.long_lens has it: None where the dense matrix
            # holds every row
            long_len = None
        if defer_map:
            deferred = (flags[rows] & native.FLAG_DEFER) != 0
            if deferred.any():
                # deferred rows were Python-parsed: encode them the Python
                # way into the same arrays
                d_idx = np.flatnonzero(deferred)
                d_cls, d_lens, d_he, d_long = self._encode_work(
                    [work[int(k)] for k in d_idx]
                )
                cls_ids[d_idx] = d_cls
                lens[d_idx] = d_lens
                host_eval[d_idx] = d_he
                if d_long is not None:
                    if long_len is None:
                        long_len = np.zeros(len(lens), dtype=np.int32)
                    long_len[d_idx] = d_long
        return work, (cls_ids, lens, host_eval, long_len)

    def _patch_deferred(self, nb, g, lines, now, ips_u, hosts_u):
        """The rows the C parse deferred (FLAG_DEFER: a timestamp whose
        text Python's float() may read differently), parsed the Python
        way and patched into the gate's columns `g` where they are
        candidates: they take their places among the rows in LINE order
        and append to the distinct tables in line order (the first-
        appearance contract) — `hosts_u` in place, the addresses to a
        list made of `ips_u`.  → (defer_map, error rows, old rows, the
        address table, the buffer its spans lie in).  Rare by
        construction; what it costs is the strings of the batch's
        distinct addresses, made here."""
        from banjax_tpu import native

        flags = nb.flags
        err = (flags & native.FLAG_ERROR) != 0
        old = (flags & native.FLAG_OLD) != 0
        defer_map: Dict[int, ParsedLine] = {}
        live: List[int] = []  # deferred rows that are candidates
        for r in np.flatnonzero(flags & native.FLAG_DEFER).tolist():
            p = parse_line(lines[r], now, OLD_LINE_CUTOFF_SECONDS)
            defer_map[r] = p
            err[r] = p.error
            old[r] = p.old_line
            if not (p.error or p.old_line):
                live.append(r)
        err_rows = np.flatnonzero(err).tolist()
        old_rows = np.flatnonzero(old & ~err).tolist()
        span_buf = nb.blob
        if not live:
            return defer_map, err_rows, old_rows, ips_u, span_buf

        cand = np.flatnonzero(~err & ~old)
        vmask = ~np.isin(cand, np.asarray(live, dtype=np.int64))
        at = np.flatnonzero(~vmask).tolist()  # where the live rows go

        def widened(col):
            out = np.zeros(cand.size, dtype=col.dtype)
            out[vmask] = col
            return out

        ts, ip_inv, host_inv = (
            widened(g.ts), widened(g.ip_inv), widened(g.host_inv)
        )
        ips_u = list(ips_u)
        iidx = {s: j for j, s in enumerate(ips_u)}
        hidx = {s: j for j, s in enumerate(hosts_u)}
        patched: List[bytes] = []  # a Python-parsed address's bytes
        for k, r in zip(at, live):
            p = defer_map[r]
            # Python float()*1e9 can exceed int64 (the columnar array
            # feeding the device windows); clamp HERE only — replay and
            # the host window path read the exact Python int from the
            # deferred ParsedLine itself
            ts[k] = min(max(p.timestamp_ns, -(2**63)), 2**63 - 1)
            j = iidx.get(p.ip)
            if j is None:
                j = iidx[p.ip] = len(ips_u)
                ips_u.append(p.ip)
                patched.append(p.ip.encode("utf-8", "surrogatepass"))
            ip_inv[k] = j
            j = hidx.get(p.host)
            if j is None:
                j = hidx[p.host] = len(hosts_u)
                hosts_u.append(p.host)
            host_inv[k] = j
        g.host_eval, g.long_len = widened(g.host_eval), widened(g.long_len)
        g.rows, g.ts, g.ip_inv, g.host_inv = cand, ts, ip_inv, host_inv
        if patched:
            # ... lie behind the blob in a copy of it
            lens_p = np.fromiter(map(len, patched), np.int64, len(patched))
            offs_p = len(span_buf) + np.cumsum(lens_p) - lens_p
            span_buf = b"".join([span_buf, *patched])
            g.ip_off = np.concatenate([g.ip_off, offs_p])
            g.ip_len = np.concatenate([g.ip_len, lens_p])
        return defer_map, err_rows, old_rows, ips_u, span_buf

    def _with_window_slots(self, work, split, apply_fn, results) -> None:
        """Shared scaffolding for every device-windows consume path: slot
        allocation with recursive batch split when it refuses, per-line
        ts/host prep, and the pin-lifecycle contract. `apply_fn(work,
        slots, row_hashes, ts_s, ts_ns, host_idx, results)` OWNS the pins
        from the moment it is entered and must release them exactly once
        on every path; any failure before that hand-off releases them
        here.  `row_hashes`: the rows' address hashes, for the traffic
        sketch's fold (None without a sketch).
        `split(lo, hi)` returns the work-aligned payload slices for a
        recursive half-batch."""
        from banjax_tpu.matcher.windows import split_ns

        dw = self.device_windows
        placed = self._slots_for_work(work)
        if placed is None:
            if len(work) <= 1:
                log.error(
                    "device-windows slot allocation failed for a single "
                    "line (capacity=%d, all slots pinned); dropping line",
                    dw.capacity,
                )
                for i, _ in work:
                    results[i].error = True
                return
            mid = max(1, len(work) // 2)
            self._with_window_slots(work[:mid], *split(0, mid), results)
            self._with_window_slots(
                work[mid:], *split(mid, len(work)), results
            )
            return
        slots, row_hashes = placed
        handed_off = False
        try:
            ts_s, ts_ns = split_ns(work.ts_array())
            host_idx = work.host_idx(self._host_row)
            handed_off = True
            apply_fn(work, slots, row_hashes, ts_s, ts_ns, host_idx, results)
        except Exception:
            if not handed_off:
                dw.release_pins(slots)
            raise

    def _long_rows_of(self, work, long_len):
        """A chunk's long rows as FusedWindowsPipeline.submit takes them:
        (rows, lens, their class ids back to back) — gathered from the
        parse blob here, the one place the host touches their bytes after
        the parse; None for a chunk without one.  The chunk is one of
        _fused_chunks', so its long operand has room for them."""
        if long_len is None:
            return None
        ks = np.flatnonzero(long_len > 0)
        if not ks.size:
            return None
        with trace.span("long-rows", args={"rows": int(ks.size)}):
            flat, lens = work.rest_bytes(ks)
            return ks.astype(np.int32), lens, self._class_of_byte[flat]

    def _log_hottest_bucket(self) -> None:
        """Beside a candidates overflow: which factor bucket hit most
        rows of the batch and the rules behind it, every 10 s at most."""
        now = time.monotonic()
        if now - self._overflow_logged_at < 10.0:
            return
        pf = self._prefilter
        hot = selectivity.hottest_bucket(pf.plan, pf.last_bucket_hits)
        if hot is None:
            return
        self._overflow_logged_at = now
        bucket, share, rules = hot
        names = [self._rule_names[i] for i in rules]
        log.info(
            "candidates overflow: factor bucket %d hit %.1f %% of the last "
            "batch's rows, the compaction holds %.1f %% (rules %s%s); the "
            "chunk replays single-stage",
            bucket, 100.0 * share, 100.0 * pf.cand_frac,
            ", ".join(repr(r) for r in names[:4]),
            f" and {len(names) - 4} more" if len(names) > 4 else "",
        )

    def _pipeline_fallback_entry(self, e, ov, results, live=None) -> None:
        """Classic replay of one overflowing chunk (shared by the sync and
        overlapped paths; caller guarantees all earlier chunks applied).
        `live` (bool [n] or None) masks drain-stale rows out of both the
        window apply and the replay — the streaming pipeline's staleness
        drop carried through the fallback."""
        dw = self.device_windows
        pend = e["pend"]
        n = len(e["work"])
        try:
            if ov.candidate_overflow:
                self._log_hottest_bucket()
                # stage 2 never saw the excess lines: recompute full-NFA
                # (the chunk's long rows, empty in the short matrix, by
                # the host's `re`: the classic way, exact and slow)
                cls_ids, lens, host_eval, _ = e["pre"]
                bits = self._single_stage_bits(
                    n, cls_ids, lens, host_eval, np.flatnonzero(~host_eval),
                )
                work = e["work"]
                self._host_eval_bits(
                    bits, np.flatnonzero(host_eval),
                    lambda row: work[row][1].rest,
                )
                if live is not None:
                    bits = bits * live[:, None].astype(np.uint8)
                apply_bits = bits
            else:
                # bitmap is complete: keep it DEVICE-resident for the
                # apply (re-uploading ~16 MB is the transfer this module
                # exists to avoid); replay uses the sparse rows decoded at
                # resolve when they fit, else one pull
                apply_bits = pend.bits_dev[:n]
                if live is not None:
                    apply_bits = apply_bits * jnp.asarray(
                        live.astype(np.uint8)
                    )[:, None]
                bits = None
        except Exception:
            dw.release_pins(e["slots"])
            self._fw_pipeline.fallback_done(pend)
            raise
        try:
            events = dw.apply_bitmap(  # releases the pins itself
                apply_bits, e["slots"], e["ts_s"], e["ts_ns"],
                self._active_table, e["host_idx"],
            )
        finally:
            self._fw_pipeline.fallback_done(pend)
        if bits is None and pend.matched_pairs is not None:
            sparse = (pend.matched_pairs, pend.always_bits)
            self._replay_window_events(
                e["work"], None, sparse, events, results, live_rows=live
            )
            return
        if bits is None:
            bits = np.asarray(pend.bits_dev)[:n]
            if live is not None:
                bits = bits * live[:, None].astype(np.uint8)
        self._replay_window_events(e["work"], bits, None, events, results)

    def _matched_pairs(self, n, bits, sparse):
        """(row, rule id) of every regex match of a chunk, as two int
        arrays — from the pipeline's sparse result ((row, rule) pairs:
        caller_row * R8 + packed stage-2 bit column, plus the packed
        always-column bits) or from a dense [n, n_rules] bitmap."""
        if sparse is None:
            rows, rids = np.nonzero(bits[:n])
            return rows.astype(np.int64), rids.astype(np.int64)
        matched_pairs, always_bits = sparse
        pf = self._prefilter
        plan = pf.plan
        rows_l, rids_l = [], []
        if matched_pairs is not None and len(matched_pairs):
            R8 = pf._nf8 * 8
            rows_idx, cols = matched_pairs // R8, matched_pairs % R8
            ok = cols < pf._n_filt
            rows_l.append(rows_idx[ok].astype(np.int64))
            rids_l.append(plan.f_idx[cols[ok]])
        if always_bits is not None and plan.n_always:
            ab = np.unpackbits(
                always_bits[:n], axis=1, count=plan.n_always
            )
            rows, cols = np.nonzero(ab)
            rows_l.append(rows.astype(np.int64))
            rids_l.append(plan.a_idx[cols])
        if not rows_l:
            z = np.zeros(0, dtype=np.int64)
            return z, z
        return np.concatenate(rows_l), np.concatenate(rids_l)

    def _replay_window_events(
        self, work, bits, sparse, events, results, live_rows=None
    ) -> None:
        """Replay one applied chunk — shared by the classic bitmap path
        and the fused pipeline.  Two parts, and only the first costs per
        event:

          * effects, one record per EXCEEDED event in reference order
            ((line, rule id) ascending = per-site-then-global), and the
            chunk's records to the banner as one batch
            (`apply_regex_bans`): ban + ban-log line + provenance, every
            line written and flushed before this returns.  With the
            default rules every log line is a window event and about
            three in a thousand of those exceed (the window restarts at
            0 on an exceed); the others have no effect to replay.
          * per-line ConsumeLineResults: owed to `results` as a deferred
            fill over the chunk's arrays (LazyResults.defer), built when
            a caller reads a line's `rule_results` — the sync entry
            point's callers and the tests do, the streaming drain does
            not.  The fill keeps arrays only, never `work`: the sync
            path's parse buffers are reused by the next batch.

        `live_rows` (bool [n]) skips rows the staleness check dropped:
        their bits were masked out of the window apply, so no event
        exists for them and no result is owed."""
        if self.traffic_sketch is not None and len(events):
            # per-rule match pressure, counted where every fired window
            # event already lands (fused commit, overflow fallback and
            # classic apply all replay through here) — exact even when a
            # chunk's device bitmap overflowed
            try:
                self.traffic_sketch.note_rule_events(events.rule)
            except Exception:  # noqa: BLE001 — sketch is passive
                log.exception("traffic sketch rule-pressure update failed")
        exc = np.flatnonzero(events.exceeded)
        if exc.size:
            # a line is built only for the rows that exceeded
            lines = work.lines_at(events.line[exc])
            entries = self._entries
            records = []
            for (_, p), idx in zip(lines, events.rule[exc].tolist()):
                rule = entries[idx][1]
                # fixed-window semantics: the ban fires the hit after the
                # threshold
                records.append(RegexBan(
                    p.ip, p.host, rule.decision, rule.rule, idx,
                    rule.hits_per_interval + 1, p.timestamp_ns / 1e9, p.rest,
                ))
            try:
                failed = self.banner.apply_regex_bans(self.config, records)
            except Exception as e:  # noqa: BLE001 — the chunk's effects are not tried again: a ban may not be inserted or logged twice
                failed = [(k, e) for k in range(len(records))]
            for k, e in failed:
                log.error(
                    "error applying rules to log line", exc_info=e
                )
                results[lines[k][0]].error = True

        results.defer(_LineResultsFill(
            self, len(work), bits, sparse, events, live_rows,
            orig=work.orig_rows(),
            hrow=work.host_idx(self._host_row) if self._host_row else None,
        ))

    def _apply_device_windows(self, work, bits, results) -> None:
        """Classic device window path: apply_bitmap per batch, then replay
        (shared scaffolding handles slot allocation/split/pin lifecycle)."""

        def make(bits_c):
            def apply_fn(work_c, slots, row_hashes, ts_s, ts_ns, host_idx,
                         results_c):
                # the dense-bitmap re-upload the fused path exists to
                # eliminate: count it so the win is measurable
                if isinstance(bits_c, np.ndarray):
                    self.stats.note_xfer(h2d_bytes=bits_c.nbytes)
                if self.traffic_sketch is not None:
                    # fold the chunk into the count-min/HLL sketches, as
                    # a program of its own (a fused dispatch carries its
                    # chunk's fold itself)
                    try:
                        self.traffic_sketch.update(row_hashes, len(work_c))
                    except Exception:  # noqa: BLE001 — sketch is passive
                        log.exception("traffic sketch update failed")
                events = self.device_windows.apply_bitmap(
                    bits_c, slots, ts_s, ts_ns, self._active_table, host_idx
                )
                self._replay_window_events(
                    work_c, bits_c, None, events, results_c
                )

            def split(lo, hi):
                return make(bits_c[lo:hi])

            return split, apply_fn

        self._with_window_slots(work, *make(bits), results)

    # ---- internals ----

    def _match_bits(self, work, pre_encoded=None) -> np.ndarray:
        """[N, n_rules] uint8 — exact regex-match bitmap for each line of
        a work batch ((index, line) sequence).

        `pre_encoded` = (cls_ids, lens, host_eval) from the native parse
        pass; when given, the Python re-encode is skipped AND line rests
        materialize only for host-fallback rows. The fused prefilter
        consumes it directly — its plan is built against THIS matcher's
        byte classes (build_plan byte_classes=...), so the one encode
        feeds stage 1, stage 2, and the single-stage fallback.

        Split into submit (device dispatch, no host sync) and collect
        (force device→host + host fallbacks) so the streaming pipeline
        scheduler can hide batch N's pull behind batch N+1's compute."""
        return self._match_bits_collect(
            self._match_bits_submit(work, pre_encoded)
        )

    def _match_bits_submit(self, work, pre_encoded=None) -> dict:
        """Dispatch the device match for a work batch without forcing any
        device→host transfer; `_match_bits_collect` completes it."""
        failpoints.check("matcher.device")
        n = len(work)
        rests = (
            None if pre_encoded is not None
            else [p.rest for _, p in work]
        )
        cls_ids, lens, host_eval = (pre_encoded or encode_for_match(
            self.compiled, rests, self._max_len
        ))[:3]
        device_rows = np.flatnonzero(~host_eval)
        pend = {
            "n": n, "work": work, "rests": rests, "cls": cls_ids,
            "lens": lens, "host_eval": host_eval, "device_rows": device_rows,
        }
        if self._prefilter is not None:
            # host_eval rows are decided by host `re` in collect; zeroing
            # their length keeps them out of the device bitmap w/o a gather
            dev_lens = np.where(host_eval, 0, lens)
            # submit every chunk before collecting any: each chunk's
            # device→host pull (a fixed round trip) overlaps
            # the next chunk's compute
            pend["kind"] = "prefilter"
            pend["chunks"] = [
                (sl, self._prefilter.submit(cls_ids[sl], dev_lens[sl]))
                for sl in (
                    slice(s, min(n, s + self._max_batch))
                    for s in range(0, n, self._max_batch)
                )
            ]
        elif self._mesh_matcher is not None:
            # sharded submit: dispatch the mesh device step per chunk
            # without forcing any device→host pull — collect merges the
            # per-shard results back into line order, so the pipeline
            # overlaps a sharded batch exactly like a single-device one
            pend["kind"] = "mesh"
            pend["chunks"] = [
                (rows, self._mesh_matcher.submit(cls_ids[rows], lens[rows]))
                for rows in (
                    device_rows[s : s + self._max_batch]
                    for s in range(0, len(device_rows), self._max_batch)
                )
            ]
        else:
            pend["kind"] = "single"
            pend["chunks"] = self._single_stage_submit(
                cls_ids, lens, device_rows
            )
        return pend

    def _match_bits_collect(self, pend: dict) -> np.ndarray:
        """Force the submitted match to a host [N, n_rules] bitmap and run
        the host fallback passes (lines the dense matrix does not hold;
        unlowerable rules)."""
        n = pend["n"]
        work, rests = pend["work"], pend["rests"]
        cls_ids, lens = pend["cls"], pend["lens"]
        host_eval, device_rows = pend["host_eval"], pend["device_rows"]

        def rest_of(row: int) -> str:
            return work[row][1].rest if rests is None else rests[row]

        if pend["kind"] == "prefilter":
            from banjax_tpu.matcher.prefilter import PrefilterOverflow

            try:
                bits = np.zeros((n, self.compiled.n_rules), dtype=np.uint8)
                for sl, p in pend["chunks"]:
                    bits[sl] = self._prefilter.collect(p)
                    self.stats.note_xfer(
                        getattr(p, "h2d_bytes", 0), getattr(p, "d2h_bytes", 0)
                    )
                # a zero-length row must contribute NO device bits (the
                # empty_only always-rule reconstruction keys on lens == 0,
                # which is also how host_eval rows were masked out)
                bits[host_eval] = 0
            except PrefilterOverflow as e:
                # adversarial all-matching traffic: rerun single-stage (the
                # full-NFA path has no candidate capacity to overflow)
                log.info("prefilter overflow (%s); batch reruns single-stage", e)
                self._log_hottest_bucket()
                bits = self._single_stage_bits(
                    n, cls_ids, lens, host_eval, device_rows
                )
        elif pend["kind"] == "mesh":
            bits = np.zeros((n, self.compiled.n_rules), dtype=np.uint8)
            for rows, p in pend["chunks"]:
                bits[rows] = self._mesh_matcher.collect(p)
                self.stats.note_xfer(
                    p.get("h2d_bytes", 0), p.get("d2h_bytes", 0)
                )
        else:
            bits = self._single_stage_collect(n, pend["chunks"])

        # host fallback: whole lines the device can't decide
        self._host_eval_bits(bits, np.flatnonzero(host_eval), rest_of)
        # host fallback: rules the compiler couldn't lower
        for idx in self._host_rule_idx:
            rule = self._entries[idx][1]
            for row in device_rows:
                if rule.regex.search(rest_of(int(row))) is not None:
                    bits[row, idx] = 1
        return bits

    def _host_eval_bits(self, bits, rows, rest_of) -> None:
        """Decide `rows` of a batch with the host's `re`, every rule over
        the whole request string, into `bits`."""
        for row in rows.tolist():
            rest = rest_of(row)
            for idx, (_, rule) in enumerate(self._entries):
                if rule.regex.search(rest) is not None:
                    bits[row, idx] = 1

    def _single_stage_submit(self, cls_ids, lens, device_rows) -> list:
        """Dispatch the full-NFA match per max_batch chunk; the returned
        device arrays are NOT forced — collect does that, so a caller can
        overlap this batch's pull with the next batch's compute."""
        chunks = []
        for start in range(0, len(device_rows), self._max_batch):
            rows = device_rows[start : start + self._max_batch]
            b = _bucket(len(rows), self._max_batch)
            pad_cls = np.zeros((b, self._max_len), dtype=np.int32)
            pad_len = np.zeros(b, dtype=np.int32)
            pad_cls[: len(rows)] = cls_ids[rows]
            pad_len[: len(rows)] = lens[rows]
            self.stats.note_xfer(h2d_bytes=pad_cls.nbytes + pad_len.nbytes)
            if self._pallas_prep is not None:
                packed = pallas_nfa.match_batch_pallas(
                    self._pallas_prep, pad_cls, pad_len,
                    interpret=self._pallas_interpret, packed=True,
                )
            else:
                packed = nfa_jax.match_batch_packed(
                    self._params, pad_cls, pad_len, self.compiled.n_rules
                )
            trace.runtime_calls()
            chunks.append((rows, packed))
        return chunks

    def _single_stage_collect(self, n: int, chunks: list) -> np.ndarray:
        bits = np.zeros((n, self.compiled.n_rules), dtype=np.uint8)
        for rows, packed in chunks:
            packed_np = np.asarray(packed)
            self.stats.note_xfer(d2h_bytes=packed_np.nbytes)
            out = np.unpackbits(
                packed_np, axis=1, count=self.compiled.n_rules
            )
            bits[rows] = out[: len(rows)]
        return bits

    def _single_stage_bits(
        self, n: int, cls_ids, lens, host_eval, device_rows
    ) -> np.ndarray:
        """Full-NFA match bitmap for the single-device path (also the
        prefilter's overflow fallback — it has no capacity to exceed)."""
        return self._single_stage_collect(
            n, self._single_stage_submit(cls_ids, lens, device_rows)
        )

    def _rule_pos(self, host: str) -> Dict[int, int]:
        """{rule id -> its position in the host's per-site-then-global
        order (regex_rate_limiter.go:175-211)} — O(matched-ids) per row.

        Hosts with no per-site rules share one global dict — the host
        field comes from attacker-controlled log lines, so caching per
        unknown host would be an unbounded-memory hole; the per-site cache
        is bounded by the config's site list."""
        if host not in self._per_site_idx:
            return self._global_pos
        d = self._rule_pos_cache.get(host)
        if d is None:
            d = {
                int(x): k
                for k, x in enumerate(self._per_site_idx[host] + self._global_idx)
            }
            self._rule_pos_cache[host] = d
        return d

    def _apply_matched_rule(self, rule: RegexWithRate, p: ParsedLine) -> RuleResult:
        """applyRegexToLog after a confirmed regex match
        (regex_rate_limiter.go:240-269) — identical to cpu_ref."""
        result = RuleResult(rule_name=rule.rule, regex_match=True)
        if rule.hosts_to_skip.get(p.host):
            result.skip_host = True
            return result
        result.skip_host = False
        seen_ip, rate_limit_result = self.rate_limit_states.apply(
            p.ip, rule, p.timestamp_ns
        )
        result.seen_ip = seen_ip
        result.rate_limit_result = rate_limit_result
        if rate_limit_result.exceeded:
            # a batch of one; what it raises is the caller's, as before
            for _, e in self.banner.apply_regex_bans(self.config, [RegexBan(
                p.ip, p.host, rule.decision, rule.rule, -1,
                rule.hits_per_interval + 1, p.timestamp_ns / 1e9, p.rest,
            )]):
                raise e
        return result


_MATCH_TYPES = tuple(RateLimitMatchType)


class _ChunkFailed(Exception):
    """A fused chunk's settle failed (the cause is chained); `chunk` is
    its entry.  See TpuMatcher._finish_batch."""

    def __init__(self, chunk: dict):
        super().__init__("fused chunk failed at its settle")
        self.chunk = chunk


class _LineResultsFill:
    """The per-line results one replayed chunk owes (see
    TpuMatcher._replay_window_events): built from the chunk's arrays when
    a result is first read.  A line's results are its matched rules that
    apply to its host, rule ids ascending (= per-site-then-global order);
    one that is not skipped for the host fired exactly one window event."""

    __slots__ = ("m", "n", "bits", "sparse", "ev", "live", "orig", "hrow")

    def __init__(self, m, n, bits, sparse, ev, live, orig, hrow):
        self.m, self.n, self.bits, self.sparse = m, n, bits, sparse
        self.ev, self.live, self.orig, self.hrow = ev, live, orig, hrow

    def __call__(self, results) -> None:
        m, ev = self.m, self.ev
        m_row, m_rid = m._matched_pairs(self.n, self.bits, self.sparse)
        if self.live is not None:
            keep = np.asarray(self.live, dtype=bool)[m_row]
            m_row, m_rid = m_row[keep], m_rid[keep]
        hrow = (
            np.zeros(len(m_row), dtype=np.int64) if self.hrow is None
            else self.hrow[m_row]
        )
        keep = m._applies[hrow, m_rid]
        m_row, m_rid, hrow = m_row[keep], m_rid[keep], hrow[keep]
        order = np.lexsort((m_rid, m_row))
        m_row, m_rid, hrow = m_row[order], m_rid[order], hrow[order]
        n_ent = m._applies.shape[1]
        ev_at = np.searchsorted(
            ev.line.astype(np.int64) * n_ent + ev.rule, m_row * n_ent + m_rid
        )
        names = m._rule_names
        mtype = ev.match_type.tolist()
        exceeded = ev.exceeded.tolist()
        seen = ev.seen_ip.tolist()
        for i, idx, skip, k in zip(
            np.asarray(self.orig)[m_row].tolist(), m_rid.tolist(),
            m._skips[hrow, m_rid].tolist(), ev_at.tolist(),
        ):
            if skip:
                rr = RuleResult(
                    rule_name=names[idx], regex_match=True, skip_host=True
                )
            else:
                rr = RuleResult(
                    rule_name=names[idx], regex_match=True, skip_host=False,
                    seen_ip=seen[k],
                    rate_limit_result=RateLimitResult(
                        match_type=_MATCH_TYPES[mtype[k]],
                        exceeded=exceeded[k],
                    ),
                )
            results.owed(i).append(rr)


def _mark_old(work, stale, results) -> None:
    """Rows `stale` (bool [n]) of a work batch aged out in the pipeline:
    old_line, and nothing else owed."""
    for i in np.asarray(work.orig_rows())[stale].tolist():
        r = results[i]
        r.old_line = True
        r.rule_results = []


def _bucket(n: int, cap: int) -> int:
    """Pad batch sizes to powers of two to bound jit recompiles."""
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return min(b, max(cap, _MIN_BUCKET))


def _rows_of(pre_encoded: tuple, idx) -> tuple:
    """Rows `idx` (a slice or an index array) of an encoded batch
    (TpuMatcher._encode_work's tuple)."""
    return tuple(None if a is None else a[idx] for a in pre_encoded)


def _long_len_of_shards(pres) -> Optional[np.ndarray]:
    """The fourth array of a batch encoded in shards: None where no shard
    has a row over the short width."""
    if all(p[3] is None for p in pres):
        return None
    return np.concatenate([
        np.zeros(len(p[1]), dtype=np.int32) if p[3] is None else p[3]
        for p in pres
    ])
