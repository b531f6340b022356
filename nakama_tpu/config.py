"""Configuration tree: dataclasses + YAML files + reflected CLI flags.

Capability parity with the reference config system (reference
server/config.go:35-1073 and flags/ reflection flag-maker): every config key
is a nested dataclass field, loadable from one or more YAML files (later
files win) and overridable by ``--dotted.flag`` command-line arguments
(flags win over files). ``check()`` returns a list of warnings the console
surfaces, mirroring the reference's CheckConfig.
"""

from __future__ import annotations

import dataclasses
import socket
from dataclasses import dataclass, field, fields, is_dataclass
from typing import Any

import yaml


@dataclass
class LoggerConfig:
    level: str = "info"
    format: str = "json"  # json | text | logfmt | stackdriver
    stdout: bool = True
    file: str = ""
    # File-sink rotation (reference server/config.go:627-646, lumberjack
    # semantics): size-triggered rotation with count/age retention.
    rotation: bool = False
    max_size: int = 100  # megabytes before the file rotates
    max_age: int = 0  # days to retain rotated files (0 = no age pruning)
    max_backups: int = 0  # rotated files to retain (0 = keep all)
    local_time: bool = False  # timestamp rotated names in local time
    compress: bool = False  # gzip rotated files


@dataclass
class MetricsConfig:
    reporting_freq_sec: int = 60
    namespace: str = ""
    # 0 = exposition disabled (reference semantics); >0 = dedicated
    # internal listener; -1 = ephemeral port (tests).
    prometheus_port: int = 0


@dataclass
class SessionConfig:
    encryption_key: str = "defaultencryptionkey"
    token_expiry_sec: int = 60
    refresh_encryption_key: str = "defaultrefreshencryptionkey"
    refresh_token_expiry_sec: int = 3600
    single_socket: bool = False
    single_match: bool = False
    single_party: bool = False
    single_session: bool = False


@dataclass
class SocketConfig:
    server_key: str = "defaultkey"
    port: int = 7350
    address: str = ""
    max_message_size_bytes: int = 4096
    max_request_size_bytes: int = 262_144
    read_buffer_size_bytes: int = 4096
    write_buffer_size_bytes: int = 4096
    idle_timeout_ms: int = 60_000
    ping_period_ms: int = 15_000
    pong_wait_ms: int = 25_000
    ping_backoff_threshold: int = 20
    outgoing_queue_size: int = 64
    # gRPC front door port (reference convention: gRPC on port-1 = 7349,
    # HTTP on 7350, console on 7351 — server/config.go). 0 = main port - 1
    # (ephemeral when port is 0); -1 disables the gRPC listener.
    grpc_port: int = 0


@dataclass
class DatabaseConfig:
    # ":memory:" = embedded non-durable default; point at a file path for
    # durability (reference default is a live Postgres DSN, config.go).
    address: list[str] = field(default_factory=lambda: [":memory:"])
    driver: str = "sqlite"  # sqlite today; asyncpg seam for postgres
    conn_max_lifetime_ms: int = 3_600_000
    max_open_conns: int = 100
    # Reader pool width (file-backed WAL engines only; capped by
    # max_open_conns at construction, server.py). 8 matches the
    # pre-knob hardcoded server pool so existing deployments keep
    # their read parallelism.
    read_pool_size: int = 8
    # Group-commit write pipeline (storage/db.py WriteBatcher):
    # concurrent auto-commit writes coalesce into shared commits.
    # group_commit=False keeps the legacy one-commit-per-write path.
    group_commit: bool = True
    write_batch_max: int = 256  # most units one drain may share a commit
    write_queue_depth: int = 4096  # queued units before submitters park
    # Bounded linger (ms) before a non-full drain commits; 0 = drain
    # immediately (commit latency already batches concurrent writers).
    write_drain_deadline_ms: int = 0
    # Storage self-healing (faults.py degradation ladder): a crashed
    # write-drain / read-coalescer loop fails its pending futures with
    # DatabaseError and restarts with backoff; after this many
    # consecutive crash-restarts the batcher fails fast (new submits
    # rejected) until a drain succeeds or the engine reconnects.
    db_drain_restart_max: int = 8


@dataclass
class MatchmakerConfig:
    """Reference defaults: server/config.go:971-989."""

    max_tickets: int = 3
    interval_sec: int = 15
    max_intervals: int = 2
    rev_precision: bool = False
    rev_threshold: int = 1
    # TPU-native knobs (no reference equivalent):
    backend: str = "auto"  # auto | cpu | tpu
    pool_capacity: int = 131_072
    max_constraints: int = 16  # query constraint slots compiled per ticket
    candidates_per_ticket: int = 64  # device top-K candidate width
    numeric_fields: int = 24
    string_fields: int = 16
    max_party_size: int = 8
    embedding_dims: int = 16  # learned skill-embedding width
    # Pools whose scanned column extent reaches this switch from the exact
    # blockwise top-K kernel to the two-stage MXU kernel (device2.py).
    big_pool_threshold: int = 32_768
    # Mesh-sharded matchmaking (parallel/mesh.py): shard the pool's
    # column (candidate) axis over this many devices (0 = single
    # device; -1 = all visible devices). Every device scores all active
    # rows against its shard and the per-shard top-K lists merge, exact,
    # over ICI collectives (SURVEY §2.8); capacity must split into
    # col_block-sized shards, and the single-device path stays the
    # fallback behind the mesh breaker. check() holds the bounds.
    mesh_devices: int = 0
    # Pipelined intervals — THE SHIPPED DEFAULT: process() dispatches the
    # current interval's device pass and collects completed earlier ones,
    # hiding device+transfer latency entirely (100k-pool Process p99 is
    # ~20 ms pipelined vs ~1.5 s synchronous). Ticket properties are
    # immutable so candidate eligibility cannot go stale; removed tickets
    # are filtered at collection. A matched cohort delivers the moment
    # its device pass + host assembly finish: the worker thread signals
    # the event-driven delivery stage (local.py _delivery_loop), and
    # every cohort carries a delivery deadline of one interval_sec
    # backed by a deadline-guard join and the reclaim path, so a cohort
    # is delivered before its own interval ends instead of slipping
    # behind gap work. Set False for the synchronous reference
    # semantics (same-interval delivery, device pass on the critical
    # path) — kept as the explicit fallback and correctness oracle.
    interval_pipelining: bool = True
    # Seconds before a pipelined cohort's delivery deadline at which the
    # delivery stage block-joins the cohort's assembly (yielding the
    # core to it, once per head). Bounds the worst-case delivery lag at
    # interval_sec + this guard's overrun allowance; join_head also
    # refuses to block past deadline + guard, so a wedged head costs
    # the guard at most one bounded join before the reclaim path
    # (inflight_reclaim_deadline_ms) takes it.
    pipeline_deadline_guard_sec: float = 2.0
    # Delivery-stage watchdog poll cadence (seconds): the timed drain
    # that runs even if a completion signal is lost or the backend has
    # no signal to offer. The stage itself is event-driven — a cohort's
    # worker thread wakes it the moment assembly finishes — so this
    # bounds recovery from a lost signal; it is NOT the delivery
    # latency.
    delivery_watchdog_sec: float = 1.0
    # Per-interval cap on host-only actives run through the CPU oracle
    # fallback (exotic queries the device kernel can't express). The
    # fallback is O(actives x pool) Python; without a cap a hostile or
    # misconfigured client drags every interval back to oracle speed.
    # Overflow defers to the next interval, oldest-first (the reference's
    # own time-budget pattern: server/matchmaker_process.go:33-46).
    host_budget_per_interval: int = 512
    # Degradation ladder (faults.py CircuitBreaker in the device
    # backend): after `breaker_threshold` consecutive transient device
    # failures (dispatch or collect; a fatal error trips immediately)
    # the breaker OPENS and intervals run the bounded host-oracle
    # fallback (host_budget_per_interval still caps it). After
    # `breaker_cooldown_ms` a half-open probe re-tries the device path;
    # success closes the breaker, failure re-opens it with the cooldown
    # doubled (capped at 16x).
    breaker_threshold: int = 3
    breaker_cooldown_ms: int = 30_000
    # Backstop reclamation sweep: a pipelined cohort still unfinished
    # this long PAST its delivery deadline is abandoned — its slots'
    # in-flight claims are released and the tickets re-activated so a
    # wedged fetch/assembly thread can never strand them un-matchable.
    inflight_reclaim_deadline_ms: int = 60_000


@dataclass
class MatchConfig:
    """Queue sizes mirror reference server/config.go:893-902."""

    input_queue_size: int = 128
    call_queue_size: int = 128
    signal_queue_size: int = 10
    join_attempt_queue_size: int = 128
    deferred_queue_size: int = 128
    join_marker_deadline_ms: int = 15_000
    max_empty_sec: int = 0
    label_update_interval_ms: int = 1000


@dataclass
class TrackerConfig:
    event_queue_size: int = 1024


@dataclass
class RuntimeConfig:
    path: str = ""
    env: dict[str, str] = field(default_factory=dict)
    http_key: str = "defaulthttpkey"
    event_queue_size: int = 65_536
    event_queue_workers: int = 8


@dataclass
class ConsoleConfig:
    port: int = 7351
    address: str = ""
    username: str = "admin"
    password: str = "password"
    signing_key: str = "defaultsigningkey"
    max_message_size_bytes: int = 4_194_304
    token_expiry_sec: int = 86_400


@dataclass
class LeaderboardConfig:
    blacklist_rank_cache: list[str] = field(default_factory=list)
    callback_queue_size: int = 65_536
    callback_queue_workers: int = 8
    # Device rank engine (leaderboard/device.py): boards at or past
    # device_min_board_size mirror onto the device for batched rank
    # reads; smaller boards stay host-only (the bisect oracle wins
    # there). Write staging flushes at the dirty threshold or the
    # interval, whichever trips first — that pair bounds read staleness.
    device_enabled: bool = True
    device_min_board_size: int = 4096
    device_flush_dirty_threshold: int = 1024
    device_flush_interval_sec: float = 2.0
    # Deadline short-circuit: a request with less budget than this
    # serves ranks from the host oracle instead of a device round-trip.
    device_read_budget_ms: float = 5.0
    device_breaker_threshold: int = 3
    device_breaker_cooldown_ms: int = 30_000


@dataclass
class IAPConfig:
    apple_shared_password: str = ""
    google_client_email: str = ""
    google_private_key: str = ""
    google_package_name: str = ""
    google_refund_poll_sec: int = 900
    huawei_client_id: str = ""
    huawei_client_secret: str = ""
    huawei_public_key: str = ""


@dataclass
class SatoriConfig:
    url: str = ""
    api_key_name: str = ""
    api_key: str = ""
    signing_key: str = ""


@dataclass
class OverloadConfig:
    """Overload-control plane (overload.py): admission control, deadline
    propagation, prioritized shedding. Defaults are the disarmed
    production posture — deadlines propagate and admission is bounded,
    but the bounds are wide enough that an unloaded server never queues
    (the bench's --overload mode measures the <=1% request-path
    budget)."""

    enabled: bool = True
    # Server-wide concurrent-request permits shared by all three
    # priority classes (realtime socket ops > authenticated RPC/storage
    # > anonymous list/read endpoints).
    admission_max_concurrent: int = 256
    # Bounded per-class wait queues; a full queue rejects with 429 +
    # Retry-After (gRPC RESOURCE_EXHAUSTED). WARN halves these and
    # stops queueing the list class; SHED rejects the list class
    # outright.
    admission_queue_realtime: int = 512
    admission_queue_rpc: int = 256
    admission_queue_list: int = 64
    retry_after_sec: int = 1
    # Per-class request deadline defaults (ms), used when the client
    # sent no grpc-timeout / X-Request-Timeout header; 0 falls back to
    # deadline_default_ms. Expired deadlines short-circuit with 504 /
    # DEADLINE_EXCEEDED before doing dead work, and the storage write
    # batcher drops queued units whose caller deadline passed.
    deadline_default_ms: int = 10_000
    deadline_realtime_ms: int = 5_000
    deadline_rpc_ms: int = 0
    deadline_list_ms: int = 0
    # Token-bucket per-key (ip+token) rate limiter generalizing the
    # LoginAttemptCache tiers; 0 rps = disabled (the default: the
    # admission queues are the primary bound).
    rate_limit_rps: float = 0.0
    rate_limit_burst: int = 32
    # Load-level ladder (OK→WARN→SHED): sampled every ladder_sample_ms;
    # escalation is immediate, de-escalation needs
    # ladder_recover_samples consecutive calmer samples.
    ladder_sample_ms: int = 250
    ladder_recover_samples: int = 3
    # db_write_queue_depth thresholds as fractions of
    # database.write_queue_depth.
    shed_queue_depth_warn: float = 0.5
    shed_queue_depth_shed: float = 0.9
    # Matchmaker interval-lag thresholds (seconds past the head
    # cohort's delivery deadline).
    interval_lag_warn_sec: float = 2.0
    interval_lag_shed_sec: float = 15.0


@dataclass
class TracingConfig:
    """Request-scoped tracing + SLO plane (tracing.py): W3C traceparent
    in/out at the front doors, span trees across admission → pipeline →
    matchmaker/storage, tail-based sampling into the bounded in-process
    trace store (`/v2/console/traces`), and the 5m/1h SLO burn-rate
    recorder. Defaults are the disarmed production posture: tracing on,
    1% p-sample, errors/slow traces kept 100%."""

    enabled: bool = True
    # Probability a non-error, non-slow trace is kept (deterministic by
    # trace id). Error/429/504/deadline-exceeded traces and traces
    # slower than slow_trace_ms are ALWAYS kept (tail-based sampling).
    # "Slow" is judged on the full span extent — a held add→matched
    # trace spans its cohort's delivery, so at a 15s interval cadence
    # matched-ticket traces typically exceed 1s and are slow-kept;
    # raise slow_trace_ms above interval_sec*1000 to p-sample them.
    sample_rate: float = 0.01
    slow_trace_ms: int = 1000
    # Bounded stores: kept traces, in-flight trace buffer, spans/trace.
    capacity: int = 256
    max_active_traces: int = 512
    max_spans_per_trace: int = 64
    # Optional JSONL export: one kept trace per line, appended.
    export_path: str = ""
    # Fleet-shared p-sampling salt (cluster deployments): with the same
    # salt on every node, a cross-node trace's fragments are kept or
    # dropped TOGETHER, so the fleet collector can stitch p-sampled
    # traces, not only error/slow-kept ones. Empty = per-boot random
    # salt (the single-node default; still client-unforgeable).
    sample_salt: str = ""
    # SLO plane: target good-fraction + per-SLI thresholds. Burn rate =
    # bad_fraction / (1 - target) over 5m and 1h windows, published as
    # slo_burn_rate{slo,window}.
    slo_target: float = 0.99
    slo_api_latency_ms: int = 200
    slo_interval_ms: int = 1000  # matchmaker process() wall time
    slo_publish_lag_ms: int = 5000  # cohort dispatch→published lag
    # Feed the 5m burn rate into the OverloadController ladder (WARN at
    # slo_burn_warn, SHED at slo_burn_shed). Off by default: first
    # intervals pay multi-second XLA compiles that would spike the burn
    # and tighten admission on a freshly-booted server.
    slo_overload_feedback: bool = False
    slo_burn_warn: float = 14.0
    slo_burn_shed: float = 100.0


@dataclass
class DevObsConfig:
    """Device telemetry plane (devobs.py): compile-watch, per-kernel
    wall clocks, the HBM ownership ledger, and the console's on-demand
    profiler capture. Defaults are the armed production posture — the
    plane is always-on (bench.py --device-obs proves it under 1% of
    the interval budget); `enabled=False` reduces every hook to one
    attribute read."""

    enabled: bool = True
    # Interval ticks before the compile warmup window closes: compiles
    # inside it are expected (first shapes, prewarm chains); after it,
    # a hot-path compile WARNs and ticks xla_recompiles_total{kernel}.
    warmup_intervals: int = 3
    # Bounded kernel-event timeline depth (console last-interval view;
    # delivery-ledger device phase chains slice it by wall window).
    timeline_depth: int = 256
    # Upper bound on one console-triggered jax.profiler capture; the
    # endpoint clamps requested durations here (output under data_dir).
    capture_max_ms: int = 10_000


@dataclass
class RecoveryConfig:
    """Crash-recovery plane (recovery.py): the durable ticket journal
    (append-only, LSN-ordered, drained through the group-commit write
    pipeline), periodic pool checkpoints that truncate it, and the
    warm-restart replay at boot. Defaults are the armed production
    posture — journaling on, checkpoints every 60s. Durability requires
    a file-backed database; on `:memory:` engines the plane runs but a
    process restart starts a fresh store (documented, not an error)."""

    enabled: bool = True
    # Journal ticket outcomes (add/remove/matched/publish-failed).
    # False keeps checkpoints only: replay granularity becomes the
    # checkpoint interval instead of the last durable journal drain.
    journal: bool = True
    # Pool snapshot cadence (interval idle gap). Bounds both replay
    # work at boot and the journal's disk footprint.
    checkpoint_interval_sec: int = 60
    # Buffered journal records per drain unit (one atomic execute_many
    # riding a shared group commit).
    journal_flush_max: int = 2048
    # Degraded-mode (storage down) in-memory buffer bound; overflow
    # drops oldest records — the pool still holds the tickets and the
    # next checkpoint covers them.
    journal_buffer_cap: int = 65536
    # Checkpoint/snapshot directory; empty = config.data_dir.
    recovery_dir: str = ""


@dataclass
class LoadgenConfig:
    """In-process soak/load engine (loadgen/): an open-loop,
    scenario-catalog session population driven against this node's own
    pipeline — the modeled tier of the two-tier soak model (real
    websocket clients are driven by the lab parent, bench.py --soak).
    Off by default; production nodes never run it."""

    enabled: bool = False
    # Target steady-state concurrent modeled sessions on this node.
    sessions: int = 100
    # Poisson arrival rate; 0 derives it from sessions / lifetime_mean
    # (Little's law), so the population hovers at the target.
    arrival_rate_per_s: float = 0.0
    # Lognormal session lifetimes (mean seconds + shape sigma).
    lifetime_mean_s: float = 20.0
    lifetime_sigma: float = 0.8
    # Arrival/lifetime/mix stream seed — one seed reproduces the whole
    # schedule bit-for-bit.
    seed: int = 1
    # Scenario mix as name=weight entries; empty = the default catalog
    # mix (loadgen/engine.py DEFAULT_MIX).
    mix: list[str] = field(default_factory=list)
    # Hard protective cap on concurrent modeled sessions; 0 = 2x the
    # target. Capped arrivals are COUNTED (loadgen_sessions{state=
    # "shed"}), never silently dropped — open-loop honesty.
    max_concurrent: int = 0


@dataclass
class SocialConfig:
    steam_app_id: int = 0
    steam_publisher_key: str = ""
    facebook_instant_app_secret: str = ""
    apple_bundle_id: str = ""


# The tunable health-rule thresholds cluster.obs_rules may override
# (one source of truth shared with cluster/obs.py DEFAULT_RULES —
# check() rejects unknown names so a typo cannot silently disable a
# rule).
OBS_RULE_KEYS = (
    "burn_1h_max",
    "replication_lag_max_s",
    "recompiles_max",
    "stale_after_ms",
    "scenario_burn_1h_max",
    # Reshard-planner triggers (cluster/reshard.py ReshardPlanner).
    # 0 = that trigger disabled (the planner still executes
    # operator-submitted plans).
    "reshard_skew_max",
    "reshard_hbm_max_bytes",
    "reshard_burn_1h_max",
)


@dataclass
class ReshardConfig:
    """Elastic shard topology (cluster/reshard.py): the planner on the
    fleet collector plus the per-owner live-migration state machine.
    Disabled by default — the static boot-time shard map is unchanged.

    Rule thresholds (pool-size skew, per-owner HBM ledger, SLO burn)
    ride ``cluster.obs_rules`` under the OBS_RULE_KEYS contract
    (reshard_skew_max, reshard_hbm_max_bytes, reshard_burn_1h_max)."""

    enabled: bool = False
    # A migration's tail phase hands over once the un-shipped journal
    # tail for the moving slice is below this many records (the
    # drained-below-threshold gate before the epoch+1 claim).
    drain_threshold_lsn: int = 16
    # One migration at a time is the rollback-friendly posture: a plan
    # with several moves executes them serially.
    max_concurrent_migrations: int = 1
    # Source-side abort deadline: if the new owner's epoch+1 claim has
    # not folded back within this budget the plan aborts and the
    # source keeps its lease (covers a dropped handover frame).
    handover_timeout_ms: int = 8000


@dataclass
class ClusterConfig:
    """Multi-process clustering (cluster/): the cross-node bus, sharded
    presence, and fan-in matchmaker ingest behind the `node` seam the
    reference threads through every presence/ticket/match ID (SURVEY
    §1). Disabled by default — the single-process build is unchanged.

    Topology is static config, not discovery: every node lists every
    peer as ``name=host:port`` (or ``name=unix:/path`` for UDS), and
    exactly ONE node runs with ``role: device_owner`` — it owns the
    device pool and the interval loop; ``frontend`` nodes terminate
    sockets and forward `MatchmakerAdd`/`Remove` over the bus."""

    enabled: bool = False
    # device_owner: runs a real matchmaker (device pool, interval
    # loop, journal/checkpoints) — one SHARD of the owner fleet.
    # frontend: terminates sessions and routes matchmaker ops by the
    # shard map. standby: shadows one owner (standby_of) via journal
    # replication and promotes on lease expiry.
    role: str = "device_owner"
    # This node's bus listener, `host:port` or `unix:/path`.
    bind: str = "127.0.0.1:7353"
    # Every OTHER node, as `name=host:port` / `name=unix:/path`.
    peers: list[str] = field(default_factory=list)
    # Node name of the device owner; required for frontends (the
    # fan-in target) when `shards` is empty. Defaults to this node's
    # own name on the owner.
    device_owner: str = ""
    # Owner scale-out (cluster/sharding.py): the owner-fleet node
    # names — each is one shard id; a ticket's pool/query-family key
    # rendezvous-hashes over them. Empty = the single-owner map above
    # (PR 10 behavior, same code path).
    shards: list[str] = field(default_factory=list)
    # For role=standby: the owner node (== shard id) this node
    # shadows. The standby announces itself over heartbeats; the owner
    # needs no matching knob.
    standby_of: str = ""
    # Shard-ownership lease: an owner renews on every heartbeat; a
    # lease silent past lease_ms is in grace, past lease_ms +
    # lease_grace_ms it is EXPIRED and the configured standby promotes
    # (epoch + 1 — frontends re-route within one membership round).
    # Both must be >= heartbeat_ms or a single delayed heartbeat
    # could flap ownership.
    lease_ms: int = 2000
    lease_grace_ms: int = 3000
    # Peer liveness: heartbeats every heartbeat_ms; a peer silent for
    # down_after_ms is DOWN — its presences are swept from survivors
    # (leave events fired) and, on the owner, its tickets leave the
    # pool.
    heartbeat_ms: int = 500
    down_after_ms: int = 2500
    # Per-peer bounded outbound queue; overflow drops oldest (the
    # degradation posture: a dead peer costs frames, never memory or a
    # wedged sender).
    send_queue_depth: int = 4096
    max_frame_bytes: int = 4_194_304
    # Per-peer connect/write breaker (faults.CircuitBreaker): open =
    # reconnect attempts decay instead of hammering a dead address.
    breaker_threshold: int = 3
    breaker_cooldown_ms: int = 1000
    # Frame codec: json (always available) | msgpack (when installed).
    codec: str = "json"
    # Fleet observability plane (cluster/obs.py): the collector node
    # assembling stitched cross-node traces, federated metrics/SLO
    # views and the health-rule engine. Empty = the device-owner /
    # first shard owner (the node every ticket already flows through).
    obs_collector: str = ""
    # Collector pull cadence (`obs.pull` BusRpc to every node) — also
    # the health-rule evaluation cadence. Off the hot path by design.
    obs_pull_ms: int = 2000
    # Node-side trace-fragment export: batch bound per `obs.frag`
    # frame (drop-oldest via the kept-ring cursor; losses counted).
    obs_frag_max: int = 64
    # Collector-side bounded stitched-trace store.
    obs_trace_capacity: int = 256
    # Health-rule threshold overrides as `name=value` entries (see
    # cluster/obs.py DEFAULT_RULES: burn_1h_max, replication_lag_max_s,
    # recompiles_max, stale_after_ms, ...). Unknown names are rejected
    # by check() — a typo must not silently disable a rule.
    obs_rules: list[str] = field(default_factory=list)
    # Elastic shard topology (cluster/reshard.py).
    reshard: ReshardConfig = field(default_factory=ReshardConfig)


@dataclass
class Config:
    name: str = "nakama-tpu"
    data_dir: str = "./data"
    # Graceful-stop budget: in-flight matchmaker cohorts get this long
    # to publish, queued storage writes this long to commit, before
    # close() starts rejecting. 0 was the old default — and it meant a
    # clean SIGTERM under load rejected queued writes (the PR 7
    # graceful-stop write-loss bug); a small nonzero grace is the
    # crash-only-software posture: fast, but never lossy by default.
    shutdown_grace_sec: int = 3
    logger: LoggerConfig = field(default_factory=LoggerConfig)
    metrics: MetricsConfig = field(default_factory=MetricsConfig)
    session: SessionConfig = field(default_factory=SessionConfig)
    socket: SocketConfig = field(default_factory=SocketConfig)
    database: DatabaseConfig = field(default_factory=DatabaseConfig)
    matchmaker: MatchmakerConfig = field(default_factory=MatchmakerConfig)
    match: MatchConfig = field(default_factory=MatchConfig)
    tracker: TrackerConfig = field(default_factory=TrackerConfig)
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)
    console: ConsoleConfig = field(default_factory=ConsoleConfig)
    leaderboard: LeaderboardConfig = field(default_factory=LeaderboardConfig)
    iap: IAPConfig = field(default_factory=IAPConfig)
    social: SocialConfig = field(default_factory=SocialConfig)
    satori: SatoriConfig = field(default_factory=SatoriConfig)
    overload: OverloadConfig = field(default_factory=OverloadConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    recovery: RecoveryConfig = field(default_factory=RecoveryConfig)
    devobs: DevObsConfig = field(default_factory=DevObsConfig)
    cluster: ClusterConfig = field(default_factory=ClusterConfig)
    loadgen: LoadgenConfig = field(default_factory=LoadgenConfig)

    @property
    def node(self) -> str:
        return self.name

    def check(self) -> list[str]:
        """Sanity-check the config; returns warnings (shown in console)."""
        import re

        warnings: list[str] = []
        # The node name is embedded in presence/ticket/match IDs with
        # "." as the separator (e.g. `<uuid>.<node>` rendezvous and
        # cluster ticket ids) and is parsed back out by rsplit — a name
        # containing the separator or other unvetted chars silently
        # corrupts ID parsing at the exact seam clustering routes on.
        if not re.fullmatch(r"[A-Za-z0-9_-]+", self.name or ""):
            raise ValueError(
                "name must be non-empty and contain only"
                " [A-Za-z0-9_-] (it is embedded in presence/ticket/"
                "match IDs with '.' as the separator)"
            )
        cl = self.cluster
        if cl.enabled:
            if cl.role not in ("device_owner", "frontend", "standby"):
                raise ValueError(
                    "cluster.role must be device_owner, frontend or"
                    " standby"
                )
            peer_names = []
            for spec in cl.peers:
                name, sep, addr = spec.partition("=")
                if not sep or not name or not addr:
                    raise ValueError(
                        f"cluster.peers entry {spec!r} must be"
                        " name=host:port or name=unix:/path"
                    )
                if not re.fullmatch(r"[A-Za-z0-9_-]+", name):
                    raise ValueError(
                        f"cluster.peers name {name!r} must match"
                        " [A-Za-z0-9_-]+"
                    )
                peer_names.append(name)
            if len(set(peer_names)) != len(peer_names):
                raise ValueError("cluster.peers names must be unique")
            if self.name in peer_names:
                raise ValueError(
                    "cluster.peers must not include this node itself"
                )
            shards = list(cl.shards)
            if len(set(shards)) != len(shards):
                raise ValueError(
                    "cluster.shards ids must be unique (duplicate"
                    " shard id)"
                )
            for s in shards:
                if not re.fullmatch(r"[A-Za-z0-9_-]+", s):
                    raise ValueError(
                        f"cluster.shards id {s!r} must match"
                        " [A-Za-z0-9_-]+"
                    )
                if s != self.name and s not in peer_names:
                    raise ValueError(
                        f"cluster.shards id {s!r} must be this node or"
                        " a configured peer (shard ids are the owner-"
                        "fleet node names)"
                    )
            if shards and cl.role == "device_owner" and (
                self.name not in shards
            ) and not cl.reshard.enabled:
                # With resharding enabled an owner outside the boot map
                # is a RESERVE owner: it owns nothing until a split or
                # move plan hands it a shard.
                raise ValueError(
                    "cluster.role is device_owner but this node is not"
                    " in cluster.shards (enable cluster.reshard to run"
                    " a reserve owner)"
                )
            if cl.standby_of:
                if cl.standby_of == self.name:
                    raise ValueError(
                        "cluster.standby_of must not name this node"
                        " itself (a standby cannot shadow itself)"
                    )
                if shards and cl.standby_of not in shards:
                    raise ValueError(
                        "cluster.standby_of must name a shard id from"
                        " cluster.shards"
                    )
                if cl.standby_of not in peer_names:
                    raise ValueError(
                        "cluster.standby_of must name a configured"
                        " peer"
                    )
            if cl.role == "standby" and not cl.standby_of:
                raise ValueError(
                    "cluster.role is standby but cluster.standby_of"
                    " is empty"
                )
            if cl.lease_grace_ms < cl.heartbeat_ms:
                raise ValueError(
                    "cluster.lease_grace_ms must be >="
                    " cluster.heartbeat_ms (a grace below the"
                    " heartbeat cadence promotes on one delayed"
                    " heartbeat)"
                )
            if cl.lease_ms < cl.heartbeat_ms:
                raise ValueError(
                    "cluster.lease_ms must be >= cluster.heartbeat_ms"
                )
            owner = cl.device_owner or (
                self.name if cl.role == "device_owner" else ""
            )
            if (
                not shards
                and cl.role == "frontend"
                and owner not in peer_names
            ):
                raise ValueError(
                    "cluster.device_owner must name a peer when"
                    " cluster.role is frontend (or configure"
                    " cluster.shards)"
                )
            if cl.role == "device_owner" and cl.device_owner not in (
                "", self.name
            ):
                raise ValueError(
                    "cluster.device_owner names another node but"
                    " cluster.role is device_owner"
                )
            if cl.heartbeat_ms < 10 or cl.down_after_ms <= cl.heartbeat_ms:
                raise ValueError(
                    "cluster.down_after_ms must exceed"
                    " cluster.heartbeat_ms (>= 10ms)"
                )
            if cl.codec not in ("json", "msgpack"):
                raise ValueError("cluster.codec must be json or msgpack")
            if cl.obs_collector and (
                cl.obs_collector != self.name
                and cl.obs_collector not in peer_names
            ):
                raise ValueError(
                    "cluster.obs_collector must name this node or a"
                    " configured peer"
                )
            if cl.obs_pull_ms < 100:
                raise ValueError(
                    "cluster.obs_pull_ms must be >= 100 (the collector"
                    " pull cadence is a fleet-wide fan-out)"
                )
            for spec in cl.obs_rules:
                key, sep, value = spec.partition("=")
                if not sep or key not in OBS_RULE_KEYS:
                    raise ValueError(
                        f"cluster.obs_rules entry {spec!r} must be"
                        f" name=value with name in {OBS_RULE_KEYS}"
                    )
                try:
                    float(value)
                except ValueError:
                    raise ValueError(
                        f"cluster.obs_rules value {value!r} for"
                        f" {key!r} must be numeric"
                    ) from None
            rs = cl.reshard
            if rs.enabled and not shards:
                raise ValueError(
                    "cluster.reshard.enabled requires cluster.shards"
                    " (the elastic map edits the owner-fleet keyspace)"
                )
            if rs.drain_threshold_lsn < 1:
                raise ValueError(
                    "cluster.reshard.drain_threshold_lsn must be >= 1"
                )
            if rs.max_concurrent_migrations != 1:
                raise ValueError(
                    "cluster.reshard.max_concurrent_migrations must be"
                    " 1 (serial migrations are the rollback posture)"
                )
            if rs.handover_timeout_ms < cl.heartbeat_ms:
                raise ValueError(
                    "cluster.reshard.handover_timeout_ms must be >="
                    " cluster.heartbeat_ms (the epoch+1 claim folds"
                    " back on the heartbeat path)"
                )
        if self.session.encryption_key == "defaultencryptionkey":
            warnings.append("session.encryption_key is the insecure default")
        if self.socket.server_key == "defaultkey":
            warnings.append("socket.server_key is the insecure default")
        if self.console.password == "password":
            warnings.append("console.password is the insecure default")
        if self.matchmaker.max_tickets < 1:
            raise ValueError("matchmaker.max_tickets must be >= 1")
        if self.matchmaker.interval_sec < 1:
            raise ValueError("matchmaker.interval_sec must be >= 1")
        if self.matchmaker.max_intervals < 1:
            raise ValueError("matchmaker.max_intervals must be >= 1")
        if self.socket.port == self.console.port:
            raise ValueError("socket.port and console.port must differ")
        if self.overload.admission_max_concurrent < 1:
            raise ValueError(
                "overload.admission_max_concurrent must be >= 1"
            )
        if not (
            0.0 < self.overload.shed_queue_depth_warn
            <= self.overload.shed_queue_depth_shed
        ):
            warnings.append(
                "overload.shed_queue_depth_warn should be in"
                " (0, shed_queue_depth_shed]"
            )
        if not (0.0 <= self.tracing.sample_rate <= 1.0):
            warnings.append(
                "tracing.sample_rate should be in [0, 1]"
            )
        if not (0.0 < self.tracing.slo_target < 1.0):
            warnings.append("tracing.slo_target should be in (0, 1)")
        if self.devobs.warmup_intervals < 0:
            raise ValueError("devobs.warmup_intervals must be >= 0")
        mm = self.matchmaker
        if mm.mesh_devices:
            if mm.mesh_devices < -1:
                raise ValueError(
                    "matchmaker.mesh_devices must be 0 (single device),"
                    " -1 (all visible) or a positive device count"
                )
            if not mm.interval_pipelining:
                raise ValueError(
                    "matchmaker.mesh_devices requires matchmaker.interval_"
                    "pipelining: the mesh path's gather/merge rides the"
                    " pipelined gap — synchronous intervals would put"
                    " the ICI collective on the critical path"
                )
            n_dev = mm.mesh_devices
            try:
                import jax as _jax

                visible = len(_jax.devices())
            except Exception:
                visible = None
                warnings.append(
                    "matchmaker.mesh_devices could not be validated"
                    " against visible devices (jax unavailable)"
                )
            if visible is not None:
                if n_dev > visible:
                    raise ValueError(
                        f"matchmaker.mesh_devices={n_dev} but only"
                        f" {visible} devices visible"
                    )
                n_dev = visible if n_dev < 0 else n_dev
            if n_dev > 0 and mm.pool_capacity % n_dev:
                # The backend checks the finer bound at boot, against
                # its column blocks; this one needs no device.
                raise ValueError(
                    f"matchmaker.pool_capacity {mm.pool_capacity} must"
                    f" split into equal column shards across {n_dev}"
                    " mesh devices"
                )
        lg = self.loadgen
        if lg.enabled:
            if lg.sessions < 1:
                raise ValueError("loadgen.sessions must be >= 1")
            if lg.lifetime_mean_s <= 0 or lg.lifetime_sigma <= 0:
                raise ValueError(
                    "loadgen.lifetime_mean_s and loadgen.lifetime_sigma"
                    " must be > 0"
                )
            if lg.arrival_rate_per_s < 0:
                raise ValueError(
                    "loadgen.arrival_rate_per_s must be >= 0"
                )
            for spec in lg.mix:
                name = str(spec).partition("=")[0].strip()
                from .loadgen.scenarios import CATALOG as _CATALOG

                if name not in _CATALOG:
                    raise ValueError(
                        f"loadgen.mix names unknown scenario {name!r}"
                        f" (catalog: {sorted(_CATALOG)})"
                    )
            warnings.append(
                "loadgen.enabled — this node generates synthetic load"
                " against itself (soak lab posture, not production)"
            )
        if self.devobs.capture_max_ms > 60_000:
            warnings.append(
                "devobs.capture_max_ms over 60s — a console-triggered"
                " profiler capture of that length can fill data_dir"
            )
        if self.recovery.checkpoint_interval_sec < 1:
            raise ValueError(
                "recovery.checkpoint_interval_sec must be >= 1"
            )
        if self.recovery.enabled and self.database.address == [":memory:"]:
            warnings.append(
                "recovery is enabled but database.address is :memory: —"
                " the ticket journal will not survive a restart"
            )
        return warnings


_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def _coerce(current: Any, value: Any, where: str) -> Any:
    """Coerce `value` (a flag string or a YAML scalar) to the type of the
    field's current/default value; reject mismatches loudly."""
    if isinstance(current, bool):
        if isinstance(value, bool):
            return value
        if isinstance(value, str):
            low = value.lower()
            if low in _TRUE:
                return True
            if low in _FALSE:
                return False
        raise ValueError(f"{where}: expected a boolean, got {value!r}")
    if isinstance(current, int) and not isinstance(current, bool):
        if isinstance(value, int) and not isinstance(value, bool):
            return value
        if isinstance(value, str):
            try:
                return int(value)
            except ValueError:
                pass
        raise ValueError(f"{where}: expected an integer, got {value!r}")
    if isinstance(current, float):
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            return float(value)
        if isinstance(value, str):
            try:
                return float(value)
            except ValueError:
                pass
        raise ValueError(f"{where}: expected a number, got {value!r}")
    if isinstance(current, list):
        if isinstance(value, list):
            return value
        if isinstance(value, str):
            return [x for x in value.split(",") if x]
        raise ValueError(f"{where}: expected a list, got {value!r}")
    if isinstance(current, dict):
        if isinstance(value, dict):
            return value
        if isinstance(value, str):
            return dict(
                kv.split("=", 1) for kv in value.split(",") if "=" in kv
            )
        raise ValueError(f"{where}: expected a mapping, got {value!r}")
    if isinstance(value, str):
        return value
    raise ValueError(f"{where}: expected a string, got {value!r}")


def _set_dotted(obj: Any, dotted: str, raw: str) -> None:
    parts = dotted.split(".")
    try:
        for p in parts[:-1]:
            obj = getattr(obj, p)
        leaf = parts[-1]
        current = getattr(obj, leaf)
        if leaf not in {f.name for f in fields(obj)}:
            raise AttributeError(leaf)  # property/method, not a config field
        setattr(obj, leaf, _coerce(current, raw, f"--{dotted}"))
    except AttributeError as e:
        raise ValueError(f"unknown config flag: --{dotted}") from e


def _merge_dict(cfg: Any, data: Any) -> None:
    if not isinstance(data, dict):
        raise ValueError(
            f"config document must be a mapping, got {type(data).__name__}"
        )
    for key, value in data.items():
        if not hasattr(cfg, key):
            raise ValueError(f"unknown config key: {key}")
        current = getattr(cfg, key)
        if is_dataclass(current):
            if value is None:
                continue  # empty yaml section ("logger:") keeps defaults
            if not isinstance(value, dict):
                raise ValueError(
                    f"config section {key!r} must be a mapping, got {type(value).__name__}"
                )
            _merge_dict(current, value)
        else:
            setattr(cfg, key, _coerce(current, value, key))


def load_config(
    yaml_paths: list[str] | None = None, argv: list[str] | None = None
) -> Config:
    """Build a Config from YAML file(s) then CLI flags (flags win).

    Flags are ``--section.key value`` or ``--section.key=value``, generated
    by reflection over the dataclass tree the way the reference's flags/
    package reflects over struct yaml tags.
    """
    cfg = Config()
    for path in yaml_paths or []:
        with open(path) as fh:
            data = yaml.safe_load(fh) or {}
        _merge_dict(cfg, data)

    argv = list(argv or [])
    i = 0
    while i < len(argv):
        arg = argv[i]
        if not arg.startswith("--"):
            raise ValueError(f"unexpected argument: {arg}")
        body = arg[2:]
        if "=" in body:
            dotted, raw = body.split("=", 1)
            i += 1
        else:
            dotted = body
            if i + 1 >= len(argv):
                raise ValueError(f"flag {arg} missing value")
            raw = argv[i + 1]
            i += 2
        _set_dotted(cfg, dotted, raw)
    return cfg


def parse_args(argv: list[str]) -> Config:
    """CLI entrypoint parsing: ``--config file.yml`` flags first, rest as overrides."""
    yaml_paths: list[str] = []
    rest: list[str] = []
    i = 0
    while i < len(argv):
        if argv[i] == "--config":
            if i + 1 >= len(argv):
                raise ValueError("flag --config missing value")
            yaml_paths.append(argv[i + 1])
            i += 2
        elif argv[i].startswith("--config="):
            yaml_paths.append(argv[i].split("=", 1)[1])
            i += 1
        else:
            rest.append(argv[i])
            i += 1
    cfg = load_config(yaml_paths, rest)
    if not cfg.name:
        # Hostnames may carry dots/invalid chars; the node name is an
        # ID component (check() enforces [A-Za-z0-9_-]) — sanitize the
        # fallback instead of failing the default boot.
        import re

        cfg.name = (
            re.sub(r"[^A-Za-z0-9_-]", "-", socket.gethostname())
            or "nakama"
        )
    return cfg


def config_to_dict(cfg: Any, redact: bool = False) -> dict:
    """Dump the config tree (console config view; redacts keys/passwords)."""
    out: dict[str, Any] = {}
    for f in fields(cfg):
        value = getattr(cfg, f.name)
        if is_dataclass(value):
            out[f.name] = config_to_dict(value, redact=redact)
        else:
            if redact and any(
                s in f.name for s in ("key", "password", "secret")
            ):
                value = "***" if value else ""
            out[f.name] = value
    return out


__all__ = [
    "Config",
    "LoggerConfig",
    "MetricsConfig",
    "SessionConfig",
    "SocketConfig",
    "DatabaseConfig",
    "MatchmakerConfig",
    "MatchConfig",
    "TrackerConfig",
    "RuntimeConfig",
    "ConsoleConfig",
    "LeaderboardConfig",
    "IAPConfig",
    "SocialConfig",
    "OverloadConfig",
    "TracingConfig",
    "RecoveryConfig",
    "DevObsConfig",
    "ClusterConfig",
    "load_config",
    "parse_args",
    "config_to_dict",
]
