"""Entry point / process supervisor.

Reference behavior: /root/reference/banjax.go:66-275 — parse the three CLI
flags, build all shared state, wire and launch the long-lived workers (HTTP
server, log tailer, Kafka reader/writer, metrics reporter, Kafka status
heartbeat), install the SIGHUP hot-reload handler, and wait for
SIGINT/SIGTERM.

The supervisor is an object (BanjaxApp) so integration tests can run the real
process in-process, the way the reference's standalone-testing tests run the
real main() in a goroutine (banjax_base_test.go:32-81).

Run:  python -m banjax_tpu.cli -config-file <path> [-standalone-testing] [-debug]
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal
import sys
import threading
import time
from typing import Optional

from banjax_tpu.config.holder import ConfigHolder
from banjax_tpu.decisions.dynamic_lists import DynamicDecisionLists
from banjax_tpu.decisions.protected_paths import PasswordProtectedPaths
from banjax_tpu.decisions.rate_limit import (
    FailedChallengeRateLimitStates,
    RegexRateLimitStates,
)
from banjax_tpu.decisions.static_lists import StaticDecisionLists
from banjax_tpu.effectors.banner import Banner
from banjax_tpu.effectors.ipset import init_ipset
from banjax_tpu.httpapi.server import ServerDeps, run_http_server
from banjax_tpu.ingest.kafka_io import KafkaReader, KafkaWriter
from banjax_tpu.ingest.reports import report_status_message
from banjax_tpu.ingest.tailer import LogTailer
from banjax_tpu.matcher.cpu_ref import CpuMatcher
from banjax_tpu.obs import fleet as fleet_mod
from banjax_tpu.obs import flightrec as flightrec_mod
from banjax_tpu.obs import provenance, trace
from banjax_tpu.obs.metrics import MetricsReporter
from banjax_tpu.resilience import failpoints
from banjax_tpu.resilience.health import HealthRegistry

log = logging.getLogger(__name__)

KAFKA_STATUS_INTERVAL_SECONDS = 19  # banjax.go:204


def place_compile_cache() -> str:
    """Place JAX's persistent compile cache before the first jit: where
    JAX_COMPILATION_CACHE_DIR says when it is set (JAX reads it itself;
    nothing is set in code), else at <checkout>/.jax_cache — a fixed path,
    because the path is part of the cache key and every (rows, L_p)
    bucket is tens of seconds of Mosaic.  Returns the directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_matcher(config, banner, static_lists, regex_states, health=None):
    """The Matcher seam flag (BASELINE.json): cpu (default) or tpu."""
    if config.matcher == "tpu":
        from banjax_tpu.matcher.runner import TpuMatcher

        place_compile_cache()
        return TpuMatcher(config, banner, static_lists, regex_states,
                          health=health)
    if health is not None:
        # the CPU matcher has no device to fail; register it so /healthz
        # still lists the component
        health.register("matcher")
    return CpuMatcher(config, banner, static_lists, regex_states)


class RegexStatesView:
    """Introspection facade: when the TPU matcher runs device-resident
    windows (matcher/windows.py), /rate_limit_states and the metrics
    reporter must read those counters, not the bypassed host dict."""

    def __init__(self, app: "BanjaxApp"):
        self._app = app

    def _target(self):
        dw = getattr(self._app._matcher, "device_windows", None)
        return dw if dw is not None else self._app.regex_states

    def format_states(self) -> str:
        return self._target().format_states()

    def get(self, ip):
        return self._target().get(ip)

    def __len__(self) -> int:
        return len(self._target())


class BanjaxApp:
    """Builds all state and owns the worker lifecycle (banjax.go main)."""

    def __init__(self, config_file: str, standalone_testing: bool = False,
                 debug: bool = False):
        log.info("INIT: config file: %s", config_file)
        self.config_holder = ConfigHolder(config_file, standalone_testing, debug)
        config = self.config_holder.get()

        # component health registry (resilience/health.py): every long-
        # lived loop below registers itself; /healthz and the metrics line
        # read the aggregate.  Per-app (not global) so in-process tests
        # don't cross-contaminate.
        self.health = HealthRegistry()
        self._failpoints_spec = getattr(config, "failpoints", "")
        if self._failpoints_spec:
            failpoints.arm_from_spec(self._failpoints_spec)

        # pipeline span tracing (obs/trace.py): off by default — the
        # disabled tracer is a no-op fast path; /debug/trace dumps the
        # ring as Perfetto-loadable Chrome trace JSON when enabled
        trace.configure(
            enabled=getattr(config, "trace_enabled", False),
            ring_size=getattr(config, "trace_ring_size", 4096),
            jax_annotations=getattr(config, "trace_jax_annotations", False),
        )

        # decision provenance ledger (obs/provenance.py): on by default —
        # records fire per decision event, not per log line, and
        # /decisions/explain answers "why is this IP banned?"
        provenance.configure(
            enabled=getattr(config, "provenance_enabled", True),
            ring_size=getattr(config, "provenance_ring_size", 2048),
        )

        self.regex_states = RegexRateLimitStates()
        self._supervisor = None  # multi-worker serving (httpapi/workers.py)
        n_http_workers = config.http_workers
        if n_http_workers == -1:  # auto: one worker per extra core
            n_http_workers = max(0, (os.cpu_count() or 1) - 1)
        elif n_http_workers < -1:
            log.warning(
                "http_workers=%d is out of range (only -1 means auto); "
                "serving single-process", n_http_workers,
            )
            n_http_workers = 0
        if n_http_workers > 0:
            from banjax_tpu.native import shm as native_shm

            if native_shm.available():
                self.failed_challenge_states = native_shm.ShmFailedChallengeStates()
            else:
                log.warning(
                    "http_workers=%d but native shmstate is unavailable "
                    "(no C compiler?); serving single-process", n_http_workers
                )
                n_http_workers = 0
        self._n_http_workers = n_http_workers
        if n_http_workers == 0:
            # bounded when challenge_failure_state_max is set (the shm
            # variant above carries its own fixed-slot bound + dropped
            # counter, so the python LRU/spill tiering is single-process)
            from banjax_tpu.challenge.failures import (
                make_failed_challenge_states,
            )

            self.failed_challenge_states = make_failed_challenge_states(
                config
            )
        # device-batched PoW verification (challenge/verifier.py):
        # None = pure-CPU reference path, decisions identical either way
        from banjax_tpu.challenge import verifier as challenge_verifier_mod

        self.challenge_verifier = challenge_verifier_mod.from_config(config)
        self.protected_paths = PasswordProtectedPaths(config)
        self.static_lists = StaticDecisionLists(config)
        if n_http_workers > 0:
            from banjax_tpu.httpapi.workers import ReplicatedDynamicLists

            self.dynamic_lists = ReplicatedDynamicLists()
        else:
            self.dynamic_lists = DynamicDecisionLists()

        # compiled serving fast path (httpapi/fastpath.py): the dynamic
        # lists mirror every insert/expiry into this table; fastserve
        # consults it before the chain.  Worker mode needs the shm-backed
        # native table (workers attach by name); without the native
        # toolchain workers just serve via the chain — never a Py table
        # only the primary could see.
        self.decision_table = None
        if getattr(config, "serve_fastpath_enabled", True):
            from banjax_tpu.native import decisiontable

            cap = getattr(config, "serve_decision_table_capacity", 65536)
            try:
                if n_http_workers > 0:
                    if decisiontable.available():
                        self.decision_table = decisiontable.ShmDecisionTable(
                            capacity=cap
                        )
                else:
                    self.decision_table = decisiontable.create_decision_table(
                        capacity=cap
                    )
            except Exception:  # noqa: BLE001 — fast path off, chain serves
                log.exception("decision table unavailable; serving via chain")
                self.decision_table = None
            if self.decision_table is not None:
                self.dynamic_lists.set_mirror(self.decision_table)

        # ban log files (banjax.go:124-138)
        self._banning_log_file = open(config.banning_log_file, "a", encoding="utf-8")
        temp_path = config.banning_log_file_temp or f"{config.banning_log_file}.tmp"
        self._banning_log_file_temp = open(temp_path, "a", encoding="utf-8")

        ipset_instance = init_ipset(
            config.iptables_ban_seconds, config.standalone_testing
        )
        # netlink-batched kernel edge (effectors/ipset_netlink.py): bans
        # coalesce into batched AF_NETLINK sends; the subprocess shim
        # stays as the in-writer fallback and the admin read path
        self.ipset_writer = None
        if ipset_instance is not None and getattr(
            config, "ipset_netlink_enabled", True
        ):
            from banjax_tpu.effectors.ipset_netlink import IpsetBatchWriter

            self.ipset_writer = IpsetBatchWriter(ipset_instance)
        self.banner = Banner(
            decision_lists=self.dynamic_lists,
            ban_log_file=self._banning_log_file,
            ban_log_file_temp=self._banning_log_file_temp,
            ipset_instance=ipset_instance,
            netlink_writer=self.ipset_writer,
        )

        self._matcher = None
        self._matcher_generation = -1
        # streaming pipeline scheduler (banjax_tpu/pipeline/): sits between
        # the tailer and the matcher when enabled — overlapped stages,
        # adaptive batch sizing, bounded backpressure, drain-time staleness.
        # Disabled: _consume_lines calls matcher.consume_lines, which runs
        # the same four matcher stages in turn on the tailer's thread.
        self.pipeline = None
        if getattr(config, "pipeline_enabled", False):
            from banjax_tpu.pipeline import PipelineScheduler

            self.pipeline = PipelineScheduler.from_config(
                matcher_getter=lambda: self._current_matcher()[1],
                config=config,
                health=self.health.register("pipeline"),
            )
        self.tailer = LogTailer(
            config.server_log_file, self._consume_lines,
            health=self.health.register("tailer", stale_after=60.0),
        )

        # multi-host decision fabric (banjax_tpu/fabric/): shard the IP
        # keyspace across N banjax processes — this process keeps only
        # its hash range, forwards the rest over peer sockets, and
        # replicates every decision through the Kafka command path.
        # The banner wrap must happen BEFORE the first matcher build so
        # device decisions fan out from day one.
        self.fabric = None
        if getattr(config, "fabric_enabled", False):
            from banjax_tpu.fabric.service import FabricService
            from banjax_tpu.ingest.kafka_io import handle_command

            self.fabric = FabricService(
                config,
                local_submit=self._fabric_local_submit,
                apply_command=lambda cmd: handle_command(
                    self.config_holder.get(), cmd, self.dynamic_lists
                ),
                health=self.health,
                # fleet observability seams (obs/fleet.py): peers pull
                # this node's metrics over T_STATS, ask it to explain
                # over T_EXPLAIN, and capture it over T_FLIGHTREC
                metrics_text_fn=self._render_metrics_text,
                explain_fn=self._explain_local,
                health_bits_fn=lambda: fleet_mod.compute_health_bits(
                    slo=getattr(self, "slo", None),
                    matcher=getattr(self, "_matcher", None),
                ),
            )
            self.banner = self.fabric.wrap_banner(self.banner)
            # forwarded-line bans resolve (origin_node, origin_trace_id)
            # at record time: the origin index is fed by the owner-side
            # drain of every forwarded chunk
            provenance.set_origin_resolver(
                fleet_mod.get_origin_index().resolve
            )

        # federated /metrics?fleet=1 (obs/fleet.py FleetScraper): one
        # merged exposition across every ALIVE member, instance-labeled —
        # needs the fabric (its peer wire carries the T_STATS pulls)
        self.fleet_scraper = None
        if self.fabric is not None and getattr(
            config, "fleet_metrics_enabled", False
        ):
            from banjax_tpu.obs.fleet import FleetScraper

            self.fleet_scraper = FleetScraper(
                self.fabric.node_id,
                local_text_fn=self._render_metrics_text,
                peers_fn=self.fabric.fleet_pull_peers,
                timeout_s=getattr(
                    config, "fleet_scrape_timeout_ms", 750.0
                ) / 1000.0,
            )

        # incident flight recorder (obs/flightrec.py): armed only with a
        # flightrec_dir; installed as the module-level trigger target so
        # the breaker/scheduler/SLO hooks stay one None-check when off
        self.flightrec = None
        if getattr(config, "flightrec_dir", ""):
            from banjax_tpu.obs.flightrec import FlightRecorder

            self.flightrec = FlightRecorder(
                config.flightrec_dir,
                min_interval_s=getattr(
                    config, "flightrec_min_interval_s", 60.0
                ),
                keep=getattr(config, "flightrec_keep", 16),
                provenance_tail=getattr(
                    config, "flightrec_provenance_records", 256
                ),
                metrics_text_fn=self._render_metrics_text,
                config_hash_fn=self._config_hash,
                health=self.health,
                slo_getter=lambda: self.slo,
                traffic_fn=self._traffic_snapshot,
                fabric_fn=(
                    self._fabric_snapshot if self.fabric is not None
                    else None
                ),
                # cluster incident capture: fan T_FLIGHTREC to every
                # ALIVE peer; each contributes a peers/<node_id>/ tree
                fleet_capture_fn=(
                    (lambda incident: fleet_mod.capture_fleet(
                        incident, self.fabric.fleet_capture_peers
                    ))
                    if self.fabric is not None and getattr(
                        config, "flightrec_fleet_capture", False
                    )
                    else None
                ),
            )
            flightrec_mod.install(self.flightrec)

        # SLO burn-rate engine (obs/slo.py): evaluates 5 m / 1 h burn
        # from non-destructive peeks; a breach transition captures an
        # incident bundle (when the recorder is armed)
        self.slo = None
        if getattr(config, "slo_enabled", True):
            from banjax_tpu.obs.slo import SloEngine

            self.slo = SloEngine.from_config(
                config,
                matcher_getter=lambda: self._matcher,
                pipeline_getter=lambda: self.pipeline,
                on_breach=lambda name, burn: flightrec_mod.notify(
                    f"slo-{name}", f"burn rates {burn}"
                ),
            )

        # fleet-mode SLO: a second engine burning the CLUSTER-wide
        # admitted/shed/stale streams summed across the last federated
        # scrape (obs/fleet.py fleet_collect) — same window mechanics,
        # merged denominators
        self.fleet_slo = None
        if self.fleet_scraper is not None and getattr(
            config, "slo_enabled", True
        ):
            from banjax_tpu.obs.slo import SloEngine

            self.fleet_slo = SloEngine(
                collect_fn=self.fleet_scraper.fleet_collect,
                on_breach=lambda name, burn: flightrec_mod.notify(
                    f"fleet-slo-{name}", f"fleet burn rates {burn}"
                ),
            )

        self.kafka_reader: Optional[KafkaReader] = None
        self.kafka_writer: Optional[KafkaWriter] = None

        metrics_path = (
            "list-metrics.log" if config.standalone_testing else config.metrics_log_file
        )
        self.metrics = MetricsReporter(
            metrics_path, self.dynamic_lists, RegexStatesView(self),
            self.failed_challenge_states,
            matcher_getter=lambda: self._matcher,
            supervisor_getter=lambda: self._supervisor,
            health=self.health,
            pipeline_getter=lambda: self.pipeline,
            fabric_getter=lambda: (
                self.fabric.stats if self.fabric is not None else None
            ),
        )

        gin_log_name = "gin.log" if config.standalone_testing else config.gin_log_file
        self._gin_log_file = None
        if gin_log_name and gin_log_name != "-":
            # truncate on start (the reference's os.Create), then APPEND:
            # in multi-worker mode the workers append to the same file, and
            # a mode-"w" primary would overwrite their lines at its private
            # offset
            open(gin_log_name, "w", encoding="utf-8").close()
            self._gin_log_file = open(gin_log_name, "a", encoding="utf-8")

        self._server_log_file = None
        if config.standalone_testing:
            self._server_log_file = open(config.server_log_file, "a", encoding="utf-8")

        self._stop_event = threading.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._async_stop: Optional[asyncio.Event] = None
        self._server_thread: Optional[threading.Thread] = None
        self._started = threading.Event()

    # --- the SIGHUP body (banjax.go:101-117) ---
    def reload(self) -> None:
        log.info("HOT-RELOAD: reloading config")
        try:
            self.config_holder.reload()
        except Exception as e:  # noqa: BLE001 — keep serving on a bad reload
            log.error("failed to reload config: %s", e)
            return
        new_config = self.config_holder.get()
        self.static_lists.update_from_config(new_config)
        self.dynamic_lists.clear()
        self.protected_paths.update_from_config(new_config)
        # re-apply the fault-injection spec only when it CHANGED: a
        # reload for unrelated keys must not clobber points armed at
        # runtime via /debug/failpoints
        new_spec = getattr(new_config, "failpoints", "")
        if new_spec != self._failpoints_spec:
            failpoints.disarm()
            if new_spec:
                failpoints.arm_from_spec(new_spec)
            self._failpoints_spec = new_spec
        if self._supervisor is not None:
            self._supervisor.broadcast_reload()

    def _render_metrics_text(self) -> str:
        """Full /metrics text for incident bundles — the same render the
        route serves, from the same non-destructive views."""
        from banjax_tpu.obs.exposition import render_prometheus

        return render_prometheus(
            self.dynamic_lists, RegexStatesView(self),
            self.failed_challenge_states, matcher=self._matcher,
            pipeline=self.pipeline, health=self.health,
            supervisor=self._supervisor, slo=self.slo,
            flightrec=self.flightrec,
            fabric=self.fabric.stats if self.fabric is not None else None,
        )

    def _fabric_snapshot(self):
        """fabric.json for incident bundles: peer table, hash-range
        ownership, last takeover — a shard-failure capture is
        self-describing without asking the survivors."""
        if self.fabric is None:
            return {"enabled": False}
        return self.fabric.describe()

    def _explain_local(self, ip: str) -> dict:
        """This node's /decisions/explain payload — served locally AND
        over the peer wire (T_EXPLAIN) when another shard proxies an
        explain for an IP this shard owns."""
        ledger = provenance.get_ledger()
        active = None
        peek = getattr(self.dynamic_lists, "peek", None)
        if peek is not None:
            ed = peek(ip)
            if ed is not None:
                active = {
                    "decision": str(ed.decision),
                    "expires": ed.expires,
                    "domain": ed.domain,
                    "from_baskerville": ed.from_baskerville,
                }
        return {
            "ip": ip,
            "ledger_enabled": ledger.enabled,
            "records": ledger.explain(ip),
            "active_decision": active,
        }

    def _fabric_local_submit(self, lines, t_read=None, hop="local") -> int:
        """The single-process consume path — what the fabric router
        calls for lines THIS shard owns (and what every line takes when
        the fabric is off).  `t_read`/`hop` thread the tailer-read stamp
        through to the e2e latency histogram (local vs fabric hop)."""
        if self.pipeline is not None:
            # asynchronous: results surface through the pipeline's drain
            # stage; submit() applies bounded backpressure to the tailer
            self.pipeline.submit(lines, t_read=t_read, hop=hop)
            return len(lines)
        cfg, matcher = self._current_matcher()
        results = matcher.consume_lines(lines)
        if cfg.debug:
            for result in results:
                log.debug("consumeLine: %s", result)
        return len(lines)

    def _traffic_snapshot(self):
        """traffic.json for incident bundles (obs/sketch.py): a forced
        sketch pull so the bundle shows the flood as of the incident."""
        sketch = getattr(self._matcher, "traffic_sketch", None)
        if sketch is None:
            return {"enabled": False}
        return sketch.incident_snapshot()

    def _config_hash(self) -> str:
        """sha256 of the on-disk config file — ties an incident bundle
        to the exact rules/limits that were live."""
        import hashlib

        try:
            with open(self.config_holder.path, "rb") as f:
                return hashlib.sha256(f.read()).hexdigest()
        except OSError:
            return ""

    def _current_matcher(self):
        # rebuilt on config change so rules hot-reload (regex_rate_limiter.go:59)
        cfg = self.config_holder.get()
        if self._matcher_generation != self.config_holder.generation:
            if self._matcher is not None:
                self._matcher.close()
            self._matcher = build_matcher(
                cfg, self.banner, self.static_lists, self.regex_states,
                health=self.health,
            )
            self._matcher_generation = self.config_holder.generation
        return cfg, self._matcher

    def _consume_lines(self, lines):
        # tailer-read stamp: the e2e latency histogram measures from
        # HERE to effector commit, per hop (local vs fabric)
        t_read = time.monotonic()
        if self.fabric is not None:
            # keyspace-sharded: owned lines go down the local pipeline,
            # the rest ride peer sockets to their owning shard
            self.fabric.submit(lines, t_read=t_read)
            return None
        if self.pipeline is not None:
            # asynchronous: results surface through the pipeline's drain
            # stage; submit() applies bounded backpressure to the tailer
            self.pipeline.submit(lines, t_read=t_read)
            return None
        cfg, matcher = self._current_matcher()
        results = matcher.consume_lines(lines)
        if cfg.debug:
            for result in results:
                log.debug("consumeLine: %s", result)
        return results  # the tailer ignores this; fault tests assert on it

    def start_workers(self) -> None:
        """Launch tailer, Kafka, metrics, heartbeat (not the HTTP server)."""
        config = self.config_holder.get()
        if self.fabric is not None:
            # listen before the tailer feeds: peers may already be
            # forwarding this shard's range
            self.fabric.start()
        if self.pipeline is not None:
            self.pipeline.start()
        if self.slo is not None:
            self.slo.start(getattr(config, "slo_sample_seconds", 15.0))
        if self.fleet_slo is not None:
            self.fleet_slo.start(getattr(config, "slo_sample_seconds", 15.0))
        self.tailer.start()

        # kafka→pipeline routing: command messages share the pipeline's
        # admission buffer (bounded-block/oldest-first shed, drained in
        # admission order) when the scheduler runs — ROADMAP PR 2 item
        kafka_pipeline = (
            self.pipeline
            if getattr(config, "pipeline_kafka", True) else None
        )
        if config.disable_kafka:
            log.info("INIT: not running Kafka reader/writer due to disable_kafka")
        elif config.disable_kafka_writer:
            log.info("INIT: starting Kafka reader only due to disable_kafka_writer")
            self.kafka_reader = KafkaReader(
                self.config_holder, self.dynamic_lists,
                health=self.health.register("kafka-reader"),
                pipeline=kafka_pipeline,
            )
            self.kafka_reader.start()
        else:
            log.info("INIT: starting Kafka reader/writer")
            self.kafka_reader = KafkaReader(
                self.config_holder, self.dynamic_lists,
                health=self.health.register("kafka-reader"),
                pipeline=kafka_pipeline,
            )
            self.kafka_reader.start()
            self.kafka_writer = KafkaWriter(
                self.config_holder,
                health=self.health.register("kafka-writer"),
            )
            self.kafka_writer.start()

        if self.fabric is not None and self.kafka_reader is not None:
            # fabric dedup in front of command dispatch: own-origin
            # echoes and already-seen (origin, seq) pairs are suppressed
            self.kafka_reader.dispatch_raw = self.fabric.dispatch_raw

        self.metrics.start()

        if not config.disable_kafka:
            def heartbeat():
                while not self._stop_event.wait(KAFKA_STATUS_INTERVAL_SECONDS):
                    cfg = self.config_holder.get()
                    if not cfg.disable_kafka:
                        report_status_message(cfg)

            threading.Thread(target=heartbeat, name="kafka-status", daemon=True).start()

    def server_deps(self) -> ServerDeps:
        return ServerDeps(
            config_holder=self.config_holder,
            static_lists=self.static_lists,
            dynamic_lists=self.dynamic_lists,
            protected_paths=self.protected_paths,
            regex_states=RegexStatesView(self),
            failed_challenge_states=self.failed_challenge_states,
            banner=self.banner,
            gin_log_file=self._gin_log_file,
            server_log_file=self._server_log_file,
            health=self.health,
            # /metrics exposition sources (non-destructive peek() reads —
            # the 29 s line's interval windows are never stolen)
            matcher_getter=lambda: self._matcher,
            pipeline_getter=lambda: self.pipeline,
            supervisor_getter=lambda: self._supervisor,
            slo_getter=lambda: self.slo,
            flightrec_getter=lambda: self.flightrec,
            fabric_getter=lambda: (
                self.fabric.stats if self.fabric is not None else None
            ),
            fleet_getter=lambda: self.fleet_scraper,
            fabric_service_getter=lambda: self.fabric,
            challenge_verifier=self.challenge_verifier,
            decision_table=self.decision_table,
        )

    async def _serve(self, install_signal_handlers: bool) -> None:
        if self._n_http_workers > 0:
            import tempfile

            from banjax_tpu.httpapi.workers import PrimarySupervisor

            ctrl_dir = tempfile.mkdtemp(prefix="banjax-ctrl-")
            self._supervisor = PrimarySupervisor(
                self, ctrl_dir, self._n_http_workers,
                health=self.health.register("worker-supervisor"),
            )
            self.dynamic_lists.set_broadcast(self._supervisor.control.broadcast)
            runner = await run_http_server(
                self.server_deps(), reuse_port=True,
                unix_path=self._supervisor.primary_http_sock(),
            )
            self._supervisor.spawn_workers()
        else:
            runner = await run_http_server(self.server_deps())
        self._async_stop = asyncio.Event()
        if install_signal_handlers:
            loop = asyncio.get_running_loop()
            for sig in (signal.SIGINT, signal.SIGTERM):
                loop.add_signal_handler(sig, self._async_stop.set)
        self._started.set()
        await self._async_stop.wait()
        await runner.cleanup()

    def run_forever(self) -> None:
        """Blocking run for the CLI (main thread; installs signal handlers)."""
        signal.signal(signal.SIGHUP, lambda s, f: self.reload())
        self.start_workers()
        try:
            asyncio.run(self._serve(install_signal_handlers=True))
        finally:
            self.shutdown()

    def start_background(self, timeout: float = 10.0) -> None:
        """Non-blocking run for tests; waits until the server is listening."""
        self.start_workers()

        def run():
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)
            self._loop.run_until_complete(self._serve(install_signal_handlers=False))

        self._server_thread = threading.Thread(target=run, name="http-server", daemon=True)
        self._server_thread.start()
        if not self._started.wait(timeout):
            raise RuntimeError("http server did not start in time")

    def stop_background(self) -> None:
        if self._loop is not None and self._async_stop is not None:
            self._loop.call_soon_threadsafe(self._async_stop.set)
        if self._server_thread is not None:
            self._server_thread.join(timeout=5)
        self.shutdown()

    def shutdown(self) -> None:
        self._stop_event.set()
        if self._supervisor is not None:
            self._supervisor.stop()
            self._supervisor = None
        self.tailer.stop()
        if self.fabric is not None:
            # after the tailer (no new routes), before the pipeline
            # drain: peers get connection-refused and fail over
            self.fabric.stop()
        if self.pipeline is not None:
            # tailer first (no new admissions), then drain what's in flight
            self.pipeline.stop()
        if self.slo is not None:
            self.slo.stop()
        if self.fleet_slo is not None:
            self.fleet_slo.stop()
        # uninstall the module-level origin resolver so a later app in
        # the same process (in-process tests) starts clean
        if self.fabric is not None:
            provenance.set_origin_resolver(None)
        if self.flightrec is not None:
            # uninstall the module-level trigger target so a later app in
            # the same process (in-process tests) starts clean
            flightrec_mod.install(None)
        self.metrics.stop()
        # release the shm table only AFTER the metrics loop is stopped —
        # a late tick calling len(failed_challenge_states) on a released
        # mapping would segfault in fc_count
        fc = self.failed_challenge_states
        if hasattr(fc, "unlink"):
            fc.close()
            fc.unlink()
        # same ordering rule for the serving decision table: the metrics
        # loop and /metrics scrapes sample it (serve_stats), so it closes
        # only after metrics.stop(); close() NULL-guards later reads
        dt = self.decision_table
        if dt is not None:
            self.decision_table = None
            try:
                dt.close()
                if hasattr(dt, "unlink"):
                    dt.unlink()
            except Exception:  # noqa: BLE001
                pass
        if self.kafka_reader:
            self.kafka_reader.stop()
        if self.kafka_writer:
            self.kafka_writer.stop()
        if self._matcher is not None:
            self._matcher.close()
        if self.ipset_writer is not None:
            # final queue drain happens inside close(); errors there are
            # counted + logged, never raised
            self.ipset_writer.close()
        self.dynamic_lists.close()
        for f in (self._banning_log_file, self._banning_log_file_temp,
                  self._gin_log_file, self._server_log_file):
            if f is not None:
                try:
                    f.close()
                except OSError:
                    pass


def main(argv: Optional[list] = None) -> int:
    # Go-style single-dash long flags (banjax.go:67-69)
    parser = argparse.ArgumentParser(prog="banjax-tpu", prefix_chars="-")
    parser.add_argument("-standalone-testing", dest="standalone_testing",
                        action="store_true", help="makes it easy to test standalone")
    parser.add_argument("-config-file", dest="config_file",
                        default="/etc/banjax/banjax-config.yaml", help="config file")
    parser.add_argument("-debug", dest="debug", action="store_true",
                        help="debug mode with verbose logging")
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=logging.DEBUG if args.debug else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )

    app = BanjaxApp(args.config_file, args.standalone_testing, args.debug)
    app.run_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
