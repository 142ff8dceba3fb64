"""Config schema: the single YAML file every subsystem reads.

Reference behavior: /root/reference/internal/config.go:17-131 — a ~60-key YAML
schema whose custom unmarshal step compiles each `regexes_with_rates` entry's
regex and parses its decision string at load time, so a bad rule fails the
whole config load (fail fast, before any traffic is touched).

This port keeps the exact YAML key names. The rule-compile step additionally
feeds the TPU rule compiler (banjax_tpu/matcher/rulec.py) when the TPU matcher
is enabled; unsupported patterns are reported at load time and fall back
per-rule to the CPU path.

Extra keys beyond the reference (all optional, default to reference behavior):
  matcher:              "cpu" (default, Go-semantics reference path) or "tpu"
  matcher_batch_lines:  device batch size for the TPU matcher
  matcher_max_line_len: the TPU matcher's SHORT width: columns of the
                        host's class matrix and of the fused program's
                        first operand; a longer line (up to 8,192 bytes)
                        travels in one of two wider ones
                        (matcher/longrows.py)
"""

from __future__ import annotations

import dataclasses
import re
import socket
import time
from typing import Any, Dict, List

import yaml

from banjax_tpu.decisions.model import Decision, parse_decision
from banjax_tpu.matcher.re2check import check_re2_compatible

NANOS_PER_SECOND = 1_000_000_000


@dataclasses.dataclass
class RegexWithRate:
    """One rate-limit rule (config.go:87-131).

    `interval_ns` mirrors Go's time.Duration (int64 nanoseconds) so the
    fixed-window comparison `ts - start > interval` is bit-identical.
    """

    rule: str
    regex_string: str
    regex: "re.Pattern[str]"
    interval_ns: int
    hits_per_interval: int
    decision: Decision
    hosts_to_skip: Dict[str, bool] = dataclasses.field(default_factory=dict)

    @classmethod
    def from_yaml_dict(cls, d: Dict[str, Any]) -> "RegexWithRate":
        regex_string = d.get("regex", "")
        check_re2_compatible(regex_string)  # reject Python-only constructs RE2 refuses
        try:
            regex = re.compile(regex_string)
        except re.error as e:
            raise ValueError(f"bad regex {regex_string!r}: {e}") from None
        # Go: time.Duration(interval_seconds_float * 1e9) — truncation, not round.
        interval_ns = int(float(d.get("interval", 0)) * NANOS_PER_SECOND)
        return cls(
            rule=d.get("rule", ""),
            regex_string=regex_string,
            regex=regex,
            interval_ns=interval_ns,
            hits_per_interval=int(d.get("hits_per_interval", 0)),
            decision=parse_decision(d.get("decision", "")),
            hosts_to_skip=dict(d.get("hosts_to_skip") or {}),
        )


@dataclasses.dataclass
class Config:
    """Full banjax config (config.go:17-85). YAML keys unchanged."""

    regexes_with_rates: List[RegexWithRate] = dataclasses.field(default_factory=list)
    per_site_regexes_with_rates: Dict[str, List[RegexWithRate]] = dataclasses.field(default_factory=dict)
    server_log_file: str = ""
    banning_log_file: str = ""
    iptables_ban_seconds: int = 0
    iptables_unbanner_seconds: int = 0
    kafka_brokers: List[str] = dataclasses.field(default_factory=list)
    kafka_security_protocol: str = ""
    kafka_ssl_ca: str = ""
    kafka_ssl_cert: str = ""
    kafka_ssl_key: str = ""
    kafka_ssl_key_password: str = ""
    kafka_command_topic: str = ""
    kafka_report_topic: str = ""
    kafka_min_bytes: int = 0
    kafka_max_bytes: int = 0
    kafka_max_wait_ms: int = 0
    kafka_dialer_timeout_seconds: int = 0
    kafka_dialer_keep_alive_seconds: int = 0
    per_site_decision_lists: Dict[str, Dict[str, List[str]]] = dataclasses.field(default_factory=dict)
    global_decision_lists: Dict[str, List[str]] = dataclasses.field(default_factory=dict)
    config_version: str = ""
    standalone_testing: bool = False
    challenger_bytes: bytes = b""
    password_page_bytes: bytes = b""
    password_hashes: Dict[str, str] = dataclasses.field(default_factory=dict)
    password_protected_paths: Dict[str, List[str]] = dataclasses.field(default_factory=dict)
    password_protected_path_exceptions: Dict[str, List[str]] = dataclasses.field(default_factory=dict)
    password_hash_roaming: Dict[str, str] = dataclasses.field(default_factory=dict)
    password_persite_cookie_ttl_seconds: Dict[str, int] = dataclasses.field(default_factory=dict)
    use_user_agent_in_cookie: Dict[str, bool] = dataclasses.field(default_factory=dict)
    expiring_decision_ttl_seconds: int = 0
    block_ip_ttl_seconds: int = 0
    block_session_ttl_seconds: int = 0
    sites_to_block_ip_ttl_seconds: Dict[str, int] = dataclasses.field(default_factory=dict)
    sites_to_block_session_ttl_seconds: Dict[str, int] = dataclasses.field(default_factory=dict)
    too_many_failed_challenges_interval_seconds: int = 0
    too_many_failed_challenges_threshold: int = 0
    password_cookie_ttl_seconds: int = 0
    sha_inv_cookie_ttl_seconds: int = 0
    sha_inv_expected_zero_bits: int = 0
    restart_time: int = 0
    reload_time: int = 0
    hostname: str = ""
    hmac_secret: str = ""
    gin_log_file: str = ""
    sitewide_sha_inv_list: Dict[str, str] = dataclasses.field(default_factory=dict)
    metrics_log_file: str = ""
    sha_inv_challenge_html: str = ""
    password_protected_path_html: str = ""
    debug: bool = False
    profile: bool = False
    disable_logging: Dict[str, bool] = dataclasses.field(default_factory=dict)
    banning_log_file_temp: str = ""
    disable_kafka: bool = False
    disable_kafka_writer: bool = False
    session_cookie_hmac_secret: str = ""
    session_cookie_ttl_seconds: int = 0
    session_cookie_not_verify: bool = False
    sites_to_disable_baskerville: Dict[str, bool] = dataclasses.field(default_factory=dict)
    sha_inv_path_exceptions: Dict[str, List[str]] = dataclasses.field(default_factory=dict)
    dnet: str = ""
    dnet_to_partition: Dict[str, int] = dataclasses.field(default_factory=dict)
    per_site_user_agent_decision_lists: Dict[str, Dict[str, List[str]]] = dataclasses.field(default_factory=dict)
    global_user_agent_decision_lists: Dict[str, List[str]] = dataclasses.field(default_factory=dict)

    # --- banjax-tpu extensions (absent from the reference) ---
    matcher: str = "cpu"  # "cpu" | "tpu" — the Matcher seam flag (BASELINE.json)
    matcher_batch_lines: int = 16384
    matcher_max_line_len: int = 256
    # device backend for the TPU matcher: "auto" picks the Pallas kernel on
    # TPU and the XLA scan elsewhere; "pallas-interpret" runs the kernel as
    # plain JAX for CI (SURVEY.md §4 carry-over (f))
    matcher_backend: str = "auto"  # "auto" | "xla" | "pallas" | "pallas-interpret"
    # device-resident fixed-window counters (matcher/windows.py): the batch
    # of match events folds into persistent [capacity, n_rules] arrays on
    # the TPU instead of the host dict. Counters reset on config reload
    # (rule ids reindex); the reference keeps them (keyed by rule name).
    matcher_device_windows: bool = False
    # IP slots for device windows. 0 (the default) = auto-size: start at
    # 16384 and double on observed distinct-IP pressure up to a ~2 GiB
    # device-memory ceiling, so the common case never evicts. A fixed
    # positive count pins the table; beyond it the LRU IP's counters spill
    # losslessly to the host shadow (restored on re-admission) at a
    # throughput cost. DeviceWindows.eviction_count / the metrics line's
    # DeviceWindowsEvictionsPerInterval surface the churn.
    matcher_window_capacity: int = 0  # IP slots; 0 = auto-size
    # two-stage literal prefilter (matcher/prefilter.py): bit-identical
    # output, auto-disabled for rulesets with too few filterable rules.
    # cand_frac sizes the candidate capacity, as a fraction of the batch,
    # for the rules that keep the candidate gate: a chunk whose stage-1
    # hit rate exceeds it replays through the single-stage matcher
    # (correct but slower; banjax_fused_overflows_total{cause="candidates"}
    # counts them, and the log line of one names the factor bucket that
    # hit most rows and its rules) — raise it for rules whose literal
    # factor fires often on benign traffic.  Anchored literals such as
    # `^GET` and rules behind a gate of four bytes or fewer (`GET .* /`)
    # take no candidate slot: the plan routes them as always-columns of
    # stage 1 by itself (prefilter._stage1_decides, selectivity.weak_gate),
    # so the shipped default rules and upstream's fixtures need nothing
    # set here
    matcher_prefilter: bool = True
    matcher_prefilter_cand_frac: float = 0.125
    # multi-device mesh (parallel/mesh.py): shard the line batch over `dp`
    # devices and the packed NFA word axis over `rp` devices (dp * rp =
    # matcher_mesh_devices). 0 = single-device. matcher_mesh_rp 0 = auto
    # (widest power of two ≤ min(4, devices) that divides the device count).
    matcher_mesh_devices: int = 0
    matcher_mesh_rp: int = 0
    # native C batch parse+encode for the tailer hot path (banjax_tpu/
    # native); auto-disables when no C compiler is present
    matcher_native_parse: bool = True
    # SO_REUSEPORT worker processes for the HTTP request API
    # (httpapi/workers.py). 0 = single process, the reference's layout;
    # N > 0 spawns N workers sharing 127.0.0.1:8081 with the primary,
    # with the failed-challenge limiter in native shared memory and
    # side effects forwarded to the primary; -1 = auto (cores - 1,
    # which is 0 on a single-core host). Needs a C compiler at first
    # start (native/shmstate.c); falls back to 0 without one.
    http_workers: int = 0
    # native asyncio-protocol server for the /auth_request hot path
    # (httpapi/fastserve.py): ~2-3x the aiohttp requests/sec, identical
    # wire contract (cold routes proxied to the aiohttp app over a unix
    # socket). false restores the pure-aiohttp layout.
    http_fast_path: bool = True
    # circuit breaker around the TPU matcher batch path (resilience/
    # breaker.py): this many consecutive device failures (or latency-
    # budget breaches) route batches to the CPU reference matcher until a
    # half-open probe succeeds after breaker_recovery_seconds
    breaker_failure_threshold: int = 3
    breaker_recovery_seconds: float = 30.0
    # per-batch latency budget for the matcher in milliseconds; a batch
    # slower than this counts as a breaker failure. 0 disables the check.
    matcher_latency_budget_ms: float = 0.0
    # optional rolling failure-rate window for the breaker: also trip when
    # breaker_failure_threshold failures land within the last
    # breaker_window_size outcomes even with successes interleaved (the
    # flapping-device mode the consecutive counter misses). 0 = off.
    breaker_window_size: int = 0
    # deterministic fault injection (resilience/failpoints.py): same spec
    # syntax as the BANJAX_FAILPOINTS env var, e.g.
    # "matcher.device=error:5;kafka.read=error" (an optional "@p" suffix
    # fires probabilistically). Empty = nothing armed. Re-applied on
    # SIGHUP when the spec changed, so fault drills need no restart.
    failpoints: str = ""
    # runtime fault-injection admin surface: GET/POST /debug/failpoints
    # lists/arms/disarms failpoints (admin_token-gated off-loopback like
    # the rest of the admin surface; the chaos soak and operators drive
    # failpoints through it without env restarts). false removes the
    # routes' function entirely — defense in depth for deployments that
    # never want runtime fault injection reachable.
    failpoints_admin_enabled: bool = True
    # --- streaming pipeline scheduler (banjax_tpu/pipeline/) ---
    # Overlapped tailer→device→effector batching with adaptive sizing and
    # backpressure; false = the reference-shaped synchronous per-batch
    # consume path.
    pipeline_enabled: bool = False
    # bounded ring of in-flight batches; the encode stage blocks (and the
    # admission buffer absorbs) when it is full
    pipeline_ring_size: int = 4
    # per-batch latency target the adaptive sizer steers toward (encode +
    # device + drain, queueing excluded)
    pipeline_latency_budget_ms: float = 250.0
    # admission buffer bound in lines; beyond it the tailer blocks for
    # pipeline_max_block_ms and then the OLDEST buffered lines are shed
    # (counted in PipelineShedLines — bounded memory, never silent loss)
    pipeline_buffer_lines: int = 131072
    pipeline_max_block_ms: float = 250.0
    # synthetic device probe through the idle pipeline every N seconds so
    # a wedged device trips the breaker before the next burst; 0 = off
    # (the default — standalone tests run without a probe thread)
    matcher_probe_seconds: float = 0.0
    # route KafkaReader command messages through the pipeline's admission
    # buffer (same bounded-block/oldest-first-shed accounting as tailer
    # lines); only meaningful when pipeline_enabled is true
    pipeline_kafka: bool = True
    # --- parallel host path ---
    # sharded encode workers for the pipeline's host stage: each
    # admission batch splits into contiguous row shards parsed/gated on
    # a thread pool (the native parse is GIL-free), then merged back in
    # strict line order — output is byte-identical to single-thread.
    # -1 = auto (min(4, cores); 0 on a single-core host), 0 = the
    # single-thread encode path.
    encode_workers: int = -1
    # native C slot manager for the device-windows ip->slot table
    # (native/slotmgr.c): the whole per-distinct-IP assignment loop runs
    # as one C call per batch, with exact Python-path parity.  Auto-falls
    # back to the Python dict path when no C compiler is present; false
    # forces the dict path (the differential oracle).
    slotmgr_native: bool = True
    # take-size bound for command batches in the pipeline's encode stage:
    # commands carry no device timing for the adaptive sizer, so a Kafka
    # command flood is chopped into batches of at most this many messages
    # instead of riding the (much larger) adaptive line bucket and
    # starving line batching.
    pipeline_command_take_max: int = 1024
    # --- observability (banjax_tpu/obs/trace.py, obs/exposition.py) ---
    # ring-buffered pipeline span recorder: each admission batch gets a
    # trace id carried through encode/submit/collect/drain; /debug/trace
    # dumps the ring as Chrome trace_event JSON (Perfetto-loadable).
    # Off by default — the disabled fast path is a single attribute
    # check per call site.
    trace_enabled: bool = False
    # span slots in the ring (oldest overwritten); ~120 bytes/slot
    trace_ring_size: int = 4096
    # also enter jax.profiler.TraceAnnotation per span (and a
    # StepTraceAnnotation per batch submit) so host spans line up with
    # the XLA/TPU device timeline when a profiler session is active
    trace_jax_annotations: bool = False
    # bearer token for the admin surface (/healthz, /metrics,
    # /debug/trace).  Enforced (constant-time compare) only when the
    # HTTP listener binds a non-loopback address; loopback stays open
    # by default like the reference's 127.0.0.1:8081 surface.
    admin_token: str = ""
    # listener bind address; empty = the reference's hard-coded
    # 127.0.0.1.  Binding non-loopback without admin_token logs a
    # warning (the whole admin surface would be open to the network).
    http_listen_host: str = ""
    # --- decision provenance / SLO engine / flight recorder (obs/) ---
    # provenance ledger (obs/provenance.py): every Decision insertion
    # (static/ua list hit, fired rate-limit ban, Kafka command,
    # challenge failure, dynamic-list expiry) lands in a per-source
    # ring, queryable via GET /decisions/explain?ip=…  On by default:
    # records fire only on decision events, not per log line.
    provenance_enabled: bool = True
    provenance_ring_size: int = 2048
    # SLO burn-rate engine (obs/slo.py): multi-window (5 m / 1 h)
    # error-budget burn from non-destructive counter/histogram peeks,
    # exposed as banjax_slo_burn_rate{slo,window} / banjax_slo_breached
    slo_enabled: bool = True
    slo_sample_seconds: float = 15.0  # 0 = no background sampling thread
    # fraction of matcher batches that must land inside
    # pipeline_latency_budget_ms
    slo_batch_latency_target: float = 0.99
    # max acceptable (shed + drain-error) lines per admitted line
    slo_shed_ratio_max: float = 0.001
    # max acceptable drain-staleness drops per processed line
    slo_stale_ratio_max: float = 0.001
    # max acceptable breaker-OPEN seconds per wall second
    slo_breaker_open_ratio_max: float = 0.01
    # max acceptable matcher latency-budget trips per batch
    slo_budget_trip_ratio_max: float = 0.01
    # incident flight recorder (obs/flightrec.py): on any SLO breach,
    # breaker trip, or shed burst, capture a tar-friendly bundle
    # (trace.json / metrics.prom / provenance.json / meta.json) into
    # this directory; empty = disabled.  GET /debug/incidents lists and
    # serves bundles.
    flightrec_dir: str = ""
    flightrec_min_interval_s: float = 60.0  # capture debounce
    flightrec_keep: int = 16  # newest bundles retained
    flightrec_provenance_records: int = 256  # ledger tail per bundle
    # --- traffic introspection plane (obs/sketch.py; /traffic/top) ---
    # device-resident streaming sketches updated in-stream per matcher
    # chunk: a count-min sketch over client-IP hashes (heavy hitters), a
    # HyperLogLog register array (distinct-source cardinality) and
    # per-rule match-pressure accumulators.  Requires
    # matcher_device_windows (the update keys on the window slot ids the
    # device already holds); read-only telemetry — sketch-on output is
    # differentially proven byte-identical to sketch-off.
    traffic_sketch_enabled: bool = True
    traffic_sketch_depth: int = 4       # count-min rows (1..8)
    traffic_sketch_width: int = 8192    # count-min buckets per row
    traffic_sketch_hll_p: int = 12      # HLL registers = 2^p (~1.6% err)
    # sampling interval for the compact device->host pull every consumer
    # (/traffic/top, /metrics, the 29 s line, incident bundles) shares;
    # the sketch is NEVER pulled per batch
    traffic_sketch_pull_seconds: float = 5.0
    traffic_sketch_topk: int = 32       # heavy-hitter heap size
    traffic_sketch_candidates: int = 8192  # host candidate-IP LRU bound
    # --- mega-state tiering (matcher/windows.py, native/shmstate.c) ---
    # sketch-gated slot admission: an IP with no hot/shadow/warm state
    # only claims a device window slot when the count-min estimate of
    # its cumulative request count (device sketch + an exact host-side
    # mirror of refused rows) says it is plausibly over the cheapest
    # rule threshold.  Refused rows still match and rate-limit through
    # the stateless host path — the gate changes WHERE state lives,
    # never the ban multiset; the sketch never undercounts, so gating
    # delays a ban by at most the admission threshold's worth of rows.
    # Requires traffic_sketch_enabled + matcher_device_windows.
    slot_admission_enabled: bool = False
    # minimum sketch estimate (estimate + current-batch rows) at which
    # an unseen IP is admitted.  <= 0 (default) derives it from the
    # loaded ruleset: min(hits_per_interval) + 1 — the smallest count
    # at which any rule could possibly fire.
    slot_admission_min_estimate: int = 0
    # warm tier: on device-slot eviction the victim's per-rule window
    # vector spills into a shared-memory host table (native/shmstate.c
    # wt_*) instead of living in the unbounded Python shadow dict, and
    # refills into a slot on re-admission.  A record is a chain of
    # 256-byte blocks (128 bytes + 24 per counter the address HOLDS), and
    # every position has an 8-byte tag in a dense index that the probes
    # walk, and an 8-byte head.  The segment is mapped at capacity x (16
    # + 256 x the blocks of a full record, at most 16) bytes, but only
    # the tags and heads (16 MiB at 2^20) and the blocks that were
    # written are ever resident: a lookup of an address the tier does not
    # hold reads tags only.
    warm_tier_enabled: bool = False
    warm_tier_capacity: int = 1 << 20   # entries (rounded up to 2^n)
    # --- multi-host decision fabric (banjax_tpu/fabric/) ---
    # shard the IP keyspace by consistent hash across N banjax processes
    # on real sockets; lines this process does not own forward to the
    # owning shard, decisions replicate to every peer over the Kafka
    # command path, and a dead shard's range is taken over by its ring
    # successors with journal replay (README "Multi-host decision
    # fabric").
    fabric_enabled: bool = False
    # this shard's stable identity on the ring (must appear in
    # fabric_peers); required when fabric_enabled
    fabric_node_id: str = ""
    # host:port this shard's fabric node listens on; required when
    # fabric_enabled (port 0 = ephemeral, harness use only)
    fabric_listen: str = ""
    # peer table: node id -> "host:port" (this node's own id included)
    fabric_peers: Dict[str, str] = dataclasses.field(default_factory=dict)
    # vnodes per node on the consistent-hash ring: more vnodes = smoother
    # range split + smaller takeover shards, at ring-build cost
    fabric_vnodes: int = 64
    # per-send socket timeout on peer links; a send that cannot complete
    # within it counts as a peer failure (retried on the shared backoff)
    fabric_send_timeout_ms: float = 2000.0
    # drain grace between declaring a peer dead and replaying its line
    # journal to the takeover successors
    fabric_takeover_grace_ms: float = 500.0
    # SWIM gossip membership (banjax_tpu/fabric/membership.py): probe
    # one member per interval; <= 0 disables gossip entirely and the
    # fabric falls back to PR 11's static topology (death discovered
    # only by a failed forward)
    fabric_gossip_interval_ms: float = 1000.0
    # how long a SUSPECT member has to produce liveness evidence (direct
    # or indirect ack, or a refutation digest) before it is confirmed
    # DEAD; must exceed the gossip interval when gossip is enabled
    fabric_suspect_timeout_ms: float = 3000.0
    # indirect ping-req relays fanned out when a direct probe fails
    # (0 = suspect immediately on direct-probe failure)
    fabric_indirect_probes: int = 2
    # budget for the planned-leave drain (stop owning, flush, announce
    # LEFT) before the process departs anyway
    fabric_graceful_leave_ms: float = 5000.0
    # --- fabric wire v2 transport (fabric/peer.py LinePipe) ---
    # frames outstanding per peer on the pipelined data path; 0 = the
    # PR 11 synchronous per-group JSON path (the differential oracle —
    # every forward blocks for its ack)
    fabric_inflight_frames: int = 8
    # binary T_LINES_V2 framing on the data path; false forces the JSON
    # fallback even against v2-capable peers (the version handshake
    # still negotiates down automatically against old peers)
    fabric_wire_v2: bool = True
    # send-side coalescing cap: routed groups pack into one data frame
    # up to this many bytes
    fabric_frame_max_bytes: int = 1 << 20
    # co-located shards (loopback/same-host peer address): exchange data
    # frames through a pair of SPSC shared-memory rings
    # (native/shmring.c) instead of loopback TCP
    fabric_shm_enabled: bool = False
    # per-direction ring capacity in bytes (power of two, and must
    # exceed fabric_frame_max_bytes — a frame is written atomically)
    fabric_shm_ring_bytes: int = 1 << 21
    # --- fleet observability plane (banjax_tpu/obs/fleet.py) ---
    # forwarded chunks carry (origin node id, origin trace id) on the
    # wire and owner-side drains open linked fabric.remote-drain spans +
    # feed the provenance origin resolver — the cross-host trace join.
    # Inert without a live tracer/fabric; adds bytes per data frame.
    fabric_trace_propagation: bool = False
    # /metrics?fleet=1 (admin-gated): fan a metrics pull out to every
    # ALIVE member and serve ONE merged exposition with instance labels
    fleet_metrics_enabled: bool = False
    # per-peer budget for one federated metrics pull; a peer that cannot
    # answer within it is served from its cached snapshot (flagged
    # stale) or flagged unreachable — the scrape itself never fails
    fleet_scrape_timeout_ms: float = 750.0
    # incident capture fan-out: an incident on THIS node also collects
    # trace/metrics/provenance/fabric snapshots from every ALIVE peer
    # into the bundle's peers/<node_id>/ tree
    flightrec_fleet_capture: bool = False
    # --- challenge plane (banjax_tpu/challenge/) ---
    # device-batched PoW verification (challenge/verifier.py + matcher/
    # kernels/pow_verify.py): route the sha-inv leading-zero check through
    # the batched sha256 kernel, with the pure-CPU reference verifier as
    # differential oracle and breaker fallback.  false = CPU-only (the
    # reference layout; expiry+hmac always stay on the CPU wire path).
    challenge_device_verify: bool = False
    # max candidate solutions per device dispatch — the bound on the
    # HTTP-path verification queue; a full queue verifies inline on the
    # CPU oracle instead of blocking the worker
    challenge_verify_batch_max: int = 256
    # per-client failed-challenge state bound (challenge/failures.py):
    # at most this many exact per-IP fixed-window entries are held, LRU
    # beyond it with sketch-gated spill/refill — 1M+ concurrent
    # challengers cannot exhaust the host.  0 = unbounded (the
    # reference's dict semantics, exactly).
    challenge_failure_state_max: int = 0
    # --- compiled serving path (httpapi/fastpath.py) ---
    # consult the native shm decision table before the Python decision
    # chain on /auth_request: a table hit serializes the response from
    # byte templates (differential-tested byte-identical); any miss or
    # table fault falls open to the unchanged chain.  false = every
    # request takes the chain (the reference layout).
    serve_fastpath_enabled: bool = True
    # decision-table slots (native/decisiontable.c); rounded up to a
    # power of two.  A full table refuses inserts (counted in
    # banjax_serve_fastpath_table_dropped_total) — refused IPs simply
    # stay chain-served; live decisions are never evicted.
    serve_decision_table_capacity: int = 65536
    # --- kernel-edge ban batching (effectors/ipset_netlink.py) ---
    # coalesce ipset adds into batched AF_NETLINK sends from a bounded
    # background queue, with the per-entry `ipset` subprocess shim as
    # fallback (netlink failure, non-IPv4 entries, open breaker) and as
    # the admin read path.  false = one subprocess fork per ban (the
    # reference layout).  No effect in standalone testing (no kernel).
    ipset_netlink_enabled: bool = True


# yaml key -> required type; mirrors Go yaml.v2 strictness — a wrong-typed
# value (e.g. a quoted "10" for an int field) fails the whole config load
# rather than crashing later at request time
_SCALAR_KEYS = {
    "server_log_file": str, "banning_log_file": str,
    "iptables_ban_seconds": int, "iptables_unbanner_seconds": int,
    "kafka_security_protocol": str, "kafka_ssl_ca": str,
    "kafka_ssl_cert": str, "kafka_ssl_key": str, "kafka_ssl_key_password": str,
    "kafka_command_topic": str, "kafka_report_topic": str,
    "kafka_min_bytes": int, "kafka_max_bytes": int, "kafka_max_wait_ms": int,
    "kafka_dialer_timeout_seconds": int, "kafka_dialer_keep_alive_seconds": int,
    "config_version": str,
    "expiring_decision_ttl_seconds": int, "block_ip_ttl_seconds": int,
    "block_session_ttl_seconds": int,
    "too_many_failed_challenges_interval_seconds": int,
    "too_many_failed_challenges_threshold": int,
    "password_cookie_ttl_seconds": int, "sha_inv_cookie_ttl_seconds": int,
    "sha_inv_expected_zero_bits": int, "hmac_secret": str,
    "gin_log_file": str, "metrics_log_file": str,
    "sha_inv_challenge_html": str, "password_protected_path_html": str,
    "debug": bool, "profile": bool,
    "banning_log_file_temp": str, "disable_kafka": bool,
    "disable_kafka_writer": bool,
    "session_cookie_hmac_secret": str, "session_cookie_ttl_seconds": int,
    "session_cookie_not_verify": bool, "dnet": str, "standalone_testing": bool,
    "matcher": str, "matcher_batch_lines": int, "matcher_max_line_len": int,
    "matcher_backend": str, "matcher_device_windows": bool,
    "matcher_window_capacity": int, "matcher_prefilter": bool,
    "matcher_prefilter_cand_frac": float,
    "matcher_mesh_devices": int, "matcher_mesh_rp": int,
    "matcher_native_parse": bool, "http_workers": int,
    "http_fast_path": bool,
    "breaker_failure_threshold": int, "breaker_recovery_seconds": float,
    "breaker_window_size": int,
    "matcher_latency_budget_ms": float, "failpoints": str,
    "failpoints_admin_enabled": bool,
    "pipeline_enabled": bool, "pipeline_ring_size": int,
    "pipeline_latency_budget_ms": float, "pipeline_buffer_lines": int,
    "pipeline_max_block_ms": float, "matcher_probe_seconds": float,
    "pipeline_kafka": bool,
    "encode_workers": int, "slotmgr_native": bool,
    "pipeline_command_take_max": int,
    "trace_enabled": bool, "trace_ring_size": int,
    "trace_jax_annotations": bool, "admin_token": str,
    "http_listen_host": str,
    "provenance_enabled": bool, "provenance_ring_size": int,
    "slo_enabled": bool, "slo_sample_seconds": float,
    "slo_batch_latency_target": float, "slo_shed_ratio_max": float,
    "slo_stale_ratio_max": float, "slo_breaker_open_ratio_max": float,
    "slo_budget_trip_ratio_max": float,
    "flightrec_dir": str, "flightrec_min_interval_s": float,
    "flightrec_keep": int, "flightrec_provenance_records": int,
    "traffic_sketch_enabled": bool, "traffic_sketch_depth": int,
    "traffic_sketch_width": int, "traffic_sketch_hll_p": int,
    "traffic_sketch_pull_seconds": float, "traffic_sketch_topk": int,
    "traffic_sketch_candidates": int,
    "slot_admission_enabled": bool, "slot_admission_min_estimate": int,
    "warm_tier_enabled": bool, "warm_tier_capacity": int,
    "fabric_enabled": bool, "fabric_node_id": str, "fabric_listen": str,
    "fabric_vnodes": int, "fabric_send_timeout_ms": float,
    "fabric_takeover_grace_ms": float,
    "fabric_gossip_interval_ms": float, "fabric_suspect_timeout_ms": float,
    "fabric_indirect_probes": int, "fabric_graceful_leave_ms": float,
    "fabric_inflight_frames": int, "fabric_wire_v2": bool,
    "fabric_frame_max_bytes": int, "fabric_shm_enabled": bool,
    "fabric_shm_ring_bytes": int,
    "fabric_trace_propagation": bool, "fleet_metrics_enabled": bool,
    "fleet_scrape_timeout_ms": float, "flightrec_fleet_capture": bool,
    "challenge_device_verify": bool, "challenge_verify_batch_max": int,
    "challenge_failure_state_max": int,
    "serve_fastpath_enabled": bool, "serve_decision_table_capacity": int,
    "ipset_netlink_enabled": bool,
}

_DICT_OR_LIST_KEYS = {
    "kafka_brokers", "per_site_decision_lists", "global_decision_lists",
    "password_hashes", "password_protected_paths",
    "password_protected_path_exceptions", "password_hash_roaming",
    "password_persite_cookie_ttl_seconds", "use_user_agent_in_cookie",
    "sites_to_block_ip_ttl_seconds", "sites_to_block_session_ttl_seconds",
    "sitewide_sha_inv_list", "disable_logging",
    "sites_to_disable_baskerville", "sha_inv_path_exceptions",
    "dnet_to_partition", "per_site_user_agent_decision_lists",
    "global_user_agent_decision_lists", "fabric_peers",
}


def config_from_yaml_text(text: str, standalone_testing_default: bool = False) -> Config:
    """Parse YAML text into a Config, compiling all rate-limit rules.

    Mirrors the yaml.Unmarshal step of config_holder.go:90 with
    RegexWithRate.UnmarshalYAML (config.go:96-131): any bad regex or bad
    decision string raises, failing the whole load.

    `standalone_testing_default` reproduces config_holder.go:89-90 ordering:
    the CLI flag seeds the field *before* unmarshal, so an explicit YAML
    `standalone_testing:` key wins over the flag.
    """
    raw = yaml.safe_load(text) or {}
    if not isinstance(raw, dict):
        raise ValueError("config root must be a mapping")

    cfg = Config()
    cfg.standalone_testing = standalone_testing_default

    for key, typ in _SCALAR_KEYS.items():
        if key in raw and raw[key] is not None:
            value = raw[key]
            if typ is int:
                if isinstance(value, bool) or not isinstance(value, int):
                    raise ValueError(f"config key {key}: expected int, got {value!r}")
            elif typ is bool:
                if not isinstance(value, bool):
                    raise ValueError(f"config key {key}: expected bool, got {value!r}")
            elif typ is float:
                # YAML parses `1` as int: accept and coerce (bools excluded)
                if isinstance(value, bool) or not isinstance(value, (int, float)):
                    raise ValueError(f"config key {key}: expected float, got {value!r}")
                value = float(value)
            elif not isinstance(value, typ):
                raise ValueError(f"config key {key}: expected {typ.__name__}, got {value!r}")
            setattr(cfg, key, value)
    for key in _DICT_OR_LIST_KEYS:
        if key in raw and raw[key] is not None:
            value = raw[key]
            expected = list if key == "kafka_brokers" else dict
            if not isinstance(value, expected):
                raise ValueError(
                    f"config key {key}: expected {expected.__name__}, got {value!r}"
                )
            setattr(cfg, key, value)

    for entry in raw.get("regexes_with_rates") or []:
        cfg.regexes_with_rates.append(RegexWithRate.from_yaml_dict(entry))
    for site, entries in (raw.get("per_site_regexes_with_rates") or {}).items():
        cfg.per_site_regexes_with_rates[site] = [
            RegexWithRate.from_yaml_dict(e) for e in (entries or [])
        ]

    if cfg.matcher not in ("cpu", "tpu"):
        raise ValueError(f"config key matcher: expected cpu|tpu, got {cfg.matcher!r}")
    if cfg.matcher_backend not in ("auto", "xla", "pallas", "pallas-interpret"):
        raise ValueError(
            "config key matcher_backend: expected "
            f"auto|xla|pallas|pallas-interpret, got {cfg.matcher_backend!r}"
        )
    if cfg.matcher_window_capacity < 0:
        raise ValueError(
            "config key matcher_window_capacity: expected 0 (auto-size) or "
            f"a positive slot count, got {cfg.matcher_window_capacity}"
        )
    if cfg.matcher_mesh_devices < 0 or cfg.matcher_mesh_rp < 0:
        raise ValueError(
            "config keys matcher_mesh_devices/matcher_mesh_rp: expected "
            f"non-negative, got {cfg.matcher_mesh_devices}/{cfg.matcher_mesh_rp}"
        )
    if (
        cfg.matcher_mesh_devices > 0
        and cfg.matcher_mesh_rp > 0
        and cfg.matcher_mesh_devices % cfg.matcher_mesh_rp != 0
    ):
        raise ValueError(
            f"config key matcher_mesh_rp: {cfg.matcher_mesh_rp} does not "
            f"divide matcher_mesh_devices {cfg.matcher_mesh_devices}"
        )
    if cfg.breaker_failure_threshold < 1:
        raise ValueError(
            "config key breaker_failure_threshold: expected >= 1, got "
            f"{cfg.breaker_failure_threshold}"
        )
    if cfg.breaker_recovery_seconds < 0 or cfg.matcher_latency_budget_ms < 0:
        raise ValueError(
            "config keys breaker_recovery_seconds/matcher_latency_budget_ms: "
            f"expected non-negative, got {cfg.breaker_recovery_seconds}/"
            f"{cfg.matcher_latency_budget_ms}"
        )
    if cfg.breaker_window_size != 0 and (
        cfg.breaker_window_size < cfg.breaker_failure_threshold
    ):
        raise ValueError(
            "config key breaker_window_size: expected 0 (off) or >= "
            f"breaker_failure_threshold ({cfg.breaker_failure_threshold}), "
            f"got {cfg.breaker_window_size}"
        )
    if cfg.pipeline_ring_size < 1:
        raise ValueError(
            "config key pipeline_ring_size: expected >= 1, got "
            f"{cfg.pipeline_ring_size}"
        )
    if cfg.pipeline_latency_budget_ms <= 0:
        raise ValueError(
            "config key pipeline_latency_budget_ms: expected positive, got "
            f"{cfg.pipeline_latency_budget_ms}"
        )
    if cfg.pipeline_buffer_lines < 1:
        raise ValueError(
            "config key pipeline_buffer_lines: expected >= 1, got "
            f"{cfg.pipeline_buffer_lines}"
        )
    if cfg.pipeline_max_block_ms < 0 or cfg.matcher_probe_seconds < 0:
        raise ValueError(
            "config keys pipeline_max_block_ms/matcher_probe_seconds: "
            f"expected non-negative, got {cfg.pipeline_max_block_ms}/"
            f"{cfg.matcher_probe_seconds}"
        )
    if cfg.encode_workers < -1:
        raise ValueError(
            "config key encode_workers: expected -1 (auto), 0 (single-"
            f"thread) or a positive worker count, got {cfg.encode_workers}"
        )
    if cfg.pipeline_command_take_max < 1:
        raise ValueError(
            "config key pipeline_command_take_max: expected >= 1, got "
            f"{cfg.pipeline_command_take_max}"
        )
    if cfg.trace_ring_size < 1:
        raise ValueError(
            "config key trace_ring_size: expected >= 1, got "
            f"{cfg.trace_ring_size}"
        )
    if cfg.provenance_ring_size < 1:
        raise ValueError(
            "config key provenance_ring_size: expected >= 1, got "
            f"{cfg.provenance_ring_size}"
        )
    if not 0.0 < cfg.slo_batch_latency_target < 1.0:
        raise ValueError(
            "config key slo_batch_latency_target: expected a fraction in "
            f"(0, 1), got {cfg.slo_batch_latency_target}"
        )
    for _k in ("slo_shed_ratio_max", "slo_stale_ratio_max",
               "slo_breaker_open_ratio_max", "slo_budget_trip_ratio_max"):
        if getattr(cfg, _k) <= 0:
            raise ValueError(
                f"config key {_k}: expected positive, got {getattr(cfg, _k)}"
            )
    if cfg.slo_sample_seconds < 0 or cfg.flightrec_min_interval_s < 0:
        raise ValueError(
            "config keys slo_sample_seconds/flightrec_min_interval_s: "
            f"expected non-negative, got {cfg.slo_sample_seconds}/"
            f"{cfg.flightrec_min_interval_s}"
        )
    if not 1 <= cfg.traffic_sketch_depth <= 8:
        raise ValueError(
            "config key traffic_sketch_depth: expected 1..8, got "
            f"{cfg.traffic_sketch_depth}"
        )
    if cfg.traffic_sketch_width < 16:
        raise ValueError(
            "config key traffic_sketch_width: expected >= 16, got "
            f"{cfg.traffic_sketch_width}"
        )
    if not 4 <= cfg.traffic_sketch_hll_p <= 16:
        raise ValueError(
            "config key traffic_sketch_hll_p: expected 4..16, got "
            f"{cfg.traffic_sketch_hll_p}"
        )
    if cfg.traffic_sketch_pull_seconds < 0:
        raise ValueError(
            "config key traffic_sketch_pull_seconds: expected "
            f"non-negative, got {cfg.traffic_sketch_pull_seconds}"
        )
    if cfg.traffic_sketch_topk < 1 or cfg.traffic_sketch_candidates < 1:
        raise ValueError(
            "config keys traffic_sketch_topk/traffic_sketch_candidates: "
            f"expected >= 1, got {cfg.traffic_sketch_topk}/"
            f"{cfg.traffic_sketch_candidates}"
        )
    if cfg.slot_admission_enabled and not (
        cfg.traffic_sketch_enabled and cfg.matcher_device_windows
    ):
        raise ValueError(
            "config key slot_admission_enabled: requires "
            "traffic_sketch_enabled and matcher_device_windows"
        )
    if cfg.warm_tier_enabled and not cfg.matcher_device_windows:
        raise ValueError(
            "config key warm_tier_enabled: requires matcher_device_windows"
        )
    if cfg.warm_tier_capacity < 1:
        raise ValueError(
            "config key warm_tier_capacity: expected >= 1, got "
            f"{cfg.warm_tier_capacity}"
        )
    if cfg.fabric_vnodes < 1:
        raise ValueError(
            f"config key fabric_vnodes: expected >= 1, got {cfg.fabric_vnodes}"
        )
    if cfg.fabric_send_timeout_ms <= 0 or cfg.fabric_takeover_grace_ms < 0:
        raise ValueError(
            "config keys fabric_send_timeout_ms/fabric_takeover_grace_ms: "
            f"expected positive/non-negative, got {cfg.fabric_send_timeout_ms}"
            f"/{cfg.fabric_takeover_grace_ms}"
        )
    if cfg.fabric_enabled:
        if not cfg.fabric_node_id or not cfg.fabric_listen:
            raise ValueError(
                "config key fabric_enabled: requires fabric_node_id and "
                "fabric_listen"
            )
        if cfg.fabric_peers and cfg.fabric_node_id not in cfg.fabric_peers:
            raise ValueError(
                f"config key fabric_peers: missing this node's own id "
                f"{cfg.fabric_node_id!r}"
            )
    if (
        cfg.fabric_gossip_interval_ms > 0
        and cfg.fabric_suspect_timeout_ms <= cfg.fabric_gossip_interval_ms
    ):
        raise ValueError(
            "config key fabric_suspect_timeout_ms: must exceed "
            f"fabric_gossip_interval_ms, got {cfg.fabric_suspect_timeout_ms}"
            f" <= {cfg.fabric_gossip_interval_ms}"
        )
    if cfg.fabric_indirect_probes < 0:
        raise ValueError(
            "config key fabric_indirect_probes: expected >= 0, got "
            f"{cfg.fabric_indirect_probes}"
        )
    if cfg.fabric_graceful_leave_ms < 0:
        raise ValueError(
            "config key fabric_graceful_leave_ms: expected >= 0, got "
            f"{cfg.fabric_graceful_leave_ms}"
        )
    if cfg.fabric_inflight_frames < 0:
        raise ValueError(
            "config key fabric_inflight_frames: expected >= 0 (0 = "
            f"synchronous JSON path), got {cfg.fabric_inflight_frames}"
        )
    if cfg.fabric_frame_max_bytes < 4096:
        raise ValueError(
            "config key fabric_frame_max_bytes: expected >= 4096, got "
            f"{cfg.fabric_frame_max_bytes}"
        )
    if cfg.fabric_shm_ring_bytes & (cfg.fabric_shm_ring_bytes - 1) or \
            cfg.fabric_shm_ring_bytes < 4096:
        raise ValueError(
            "config key fabric_shm_ring_bytes: expected a power of two "
            f">= 4096, got {cfg.fabric_shm_ring_bytes}"
        )
    if (
        cfg.fabric_shm_enabled
        and cfg.fabric_shm_ring_bytes <= cfg.fabric_frame_max_bytes
    ):
        raise ValueError(
            "config key fabric_shm_ring_bytes: must exceed "
            "fabric_frame_max_bytes (a frame is ring-written atomically), "
            f"got {cfg.fabric_shm_ring_bytes} <= {cfg.fabric_frame_max_bytes}"
        )
    if cfg.fleet_scrape_timeout_ms <= 0:
        raise ValueError(
            "config key fleet_scrape_timeout_ms: expected positive, got "
            f"{cfg.fleet_scrape_timeout_ms}"
        )
    if cfg.flightrec_keep < 1 or cfg.flightrec_provenance_records < 1:
        raise ValueError(
            "config keys flightrec_keep/flightrec_provenance_records: "
            f"expected >= 1, got {cfg.flightrec_keep}/"
            f"{cfg.flightrec_provenance_records}"
        )
    if cfg.challenge_verify_batch_max < 1:
        raise ValueError(
            "config key challenge_verify_batch_max: expected >= 1, got "
            f"{cfg.challenge_verify_batch_max}"
        )
    if cfg.challenge_failure_state_max < 0:
        raise ValueError(
            "config key challenge_failure_state_max: expected 0 (unbounded) "
            f"or a positive entry count, got {cfg.challenge_failure_state_max}"
        )
    if cfg.serve_decision_table_capacity < 1:
        raise ValueError(
            "config key serve_decision_table_capacity: expected >= 1 "
            "(rounded up to a power of two), got "
            f"{cfg.serve_decision_table_capacity}"
        )

    return cfg


def default_hostname() -> str:
    try:
        return socket.gethostname()
    except OSError:
        return "unknown-hostname"


def now_unix() -> int:
    return int(time.time())
