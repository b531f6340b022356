"""Server assembly: component wiring in dependency order + lifecycle.

Parity with the reference main() (reference main.go:64-282): metrics →
session registry/caches → tracker → router → match registry → matchmaker →
party registry → pipeline → socket acceptor — and graceful shutdown in
reverse, draining authoritative matches first (main.go:209-240).
"""

from __future__ import annotations

import asyncio
import signal

from .api.matchmaker_events import make_matched_handler
from .api.pipeline import Components, Pipeline
from .api.socket import SocketAcceptor
from .config import Config, parse_args
from .logger import Logger, setup_logging
from .match import LocalMatchRegistry, LocalPartyRegistry
from .matchmaker import LocalMatchmaker
from .metrics import Metrics
from .realtime import (
    LocalLoginAttemptCache,
    LocalMessageRouter,
    LocalSessionCache,
    LocalSessionRegistry,
    LocalStatusRegistry,
    LocalStreamManager,
    LocalTracker,
    StreamMode,
)


class NakamaServer:
    def __init__(
        self,
        config: Config,
        logger: Logger | None = None,
        matchmaker_backend=None,
        database=None,
        runtime_modules: list | None = None,
    ):
        self.config = config
        self.logger = logger or setup_logging(config.logger)
        log = self.logger
        node = config.name
        # Fleet log attribution: every record this process emits
        # carries the node name next to its trace ids (logger.py) —
        # merged fleet log streams are otherwise unattributable.
        from .logger import set_node_name

        set_node_name(node)

        # Persistence (reference DbConnect, main.go:129-133): constructed
        # here, connected in start(). `database=None` builds the embedded
        # engine from config.
        from .storage import make_database

        self.db = database
        self._owns_db = database is None
        if self.db is None:
            self.db = make_database(
                config.database.address or [":memory:"],
                read_pool_size=min(
                    config.database.read_pool_size,
                    config.database.max_open_conns,
                ),
                group_commit=config.database.group_commit,
                write_batch_max=config.database.write_batch_max,
                write_queue_depth=config.database.write_queue_depth,
                write_drain_deadline_ms=(
                    config.database.write_drain_deadline_ms
                ),
                db_drain_restart_max=config.database.db_drain_restart_max,
            )
        self._db_connected = False
        self._runtime_modules = runtime_modules or []

        self.metrics = Metrics(config.metrics.namespace)
        # Fault plane observability: injections delivered by armed
        # points surface as `faults_injected` on this server's registry
        # (the plane is process-wide; points are armed only by
        # tests/bench/chaos, so production scrapes read zero).
        from . import faults

        faults.PLANE.bind_metrics(self.metrics)
        # Cluster plane (cluster/): when enabled, the realtime layer
        # swaps to the Cluster* wrappers (presence replication + routed
        # fan-out over the bus) and frontend nodes run the matchmaker
        # proxy instead of the pool. Handler code is untouched — the
        # wrappers implement the same surfaces.
        self.cluster = None
        if config.cluster.enabled:
            from .cluster import ClusterPlane

            self.cluster = ClusterPlane(config, log, self.metrics)
        bus = self.cluster.bus if self.cluster is not None else None
        self._rpc = None
        if bus is not None:
            from .cluster import (
                BusRpc,
                ClusterMessageRouter,
                ClusterSessionRegistry,
                ClusterStreamManager,
                ClusterTracker,
            )

            # Correlated request/response over the bus: cross-node
            # party/match operations (cluster/ops.py) ride it.
            self._rpc = BusRpc(bus, node, log, self.metrics)
            self.session_registry = ClusterSessionRegistry(
                log, self.metrics, bus=bus
            )
            self.tracker = ClusterTracker(
                log, node, self.metrics,
                config.tracker.event_queue_size, bus=bus,
            )
            self.router = ClusterMessageRouter(
                log, self.session_registry, self.tracker, self.metrics,
                bus=bus, node=node,
            )
        else:
            self.session_registry = LocalSessionRegistry(log, self.metrics)
            self.tracker = LocalTracker(
                log, node, self.metrics, config.tracker.event_queue_size
            )
            self.router = LocalMessageRouter(
                log, self.session_registry, self.tracker, self.metrics
            )
        self.session_cache = LocalSessionCache(
            config.session.token_expiry_sec,
            config.session.refresh_token_expiry_sec,
        )
        self.login_attempt_cache = LocalLoginAttemptCache()
        self.tracker.set_event_router(self.router.route_presence_event)
        self.status_registry = LocalStatusRegistry(log, self.session_registry)
        self.tracker.add_listener(
            StreamMode.STATUS, self.status_registry.status_listener()
        )
        if bus is not None:
            self.stream_manager = ClusterStreamManager(
                log, self.session_registry, self.tracker, bus=bus
            )
        else:
            self.stream_manager = LocalStreamManager(
                log, self.session_registry, self.tracker
            )
        if bus is not None:
            # Authoritative matches stay single-writer on the node that
            # created them; joins and data route to that authority over
            # the bus (cluster/ops.py).
            from .cluster import ClusterMatchRegistry

            self.match_registry = ClusterMatchRegistry(
                log, config.match, self.router, node, self.metrics,
                tracker=self.tracker, bus=bus, rpc=self._rpc,
            )
        else:
            self.match_registry = LocalMatchRegistry(
                log, config.match, self.router, node, self.metrics,
                tracker=self.tracker,
            )
        self.tracker.add_listener(
            StreamMode.MATCH_AUTHORITATIVE, self.match_registry.join_listener()
        )
        if self.cluster is not None and not self.cluster.runs_pool:
            # Frontend role: no pool, no device, no interval loop —
            # adds/removes route by the epoch-versioned shard map to
            # the owning shard's node over the bus, behind the same
            # LocalMatchmaker surface.
            from .cluster import ClusterMatchmakerClient

            self.matchmaker = ClusterMatchmakerClient(
                log,
                config.matchmaker,
                bus,
                self.cluster.membership,
                node,
                self.cluster.owner,
                metrics=self.metrics,
                directory=self.cluster.directory,
            )
        else:
            # Owner shard — or a warm standby, whose LocalMatchmaker is
            # the replication shadow pool: fully registered (device
            # rows, duplicate guards) but NOT ticking until promotion.
            self.matchmaker = LocalMatchmaker(
                log,
                config.matchmaker,
                self.metrics,
                node,
                backend=matchmaker_backend,
            )
        self._cluster_ingest = None
        if self.cluster is not None:
            if self.cluster.runs_pool:
                from .cluster import ClusterMatchmakerIngest

                self._cluster_ingest = ClusterMatchmakerIngest(
                    self.matchmaker, bus, log, self.metrics,
                    directory=self.cluster.directory, node=node,
                )
            self.cluster.wire_sweeps(
                self.tracker,
                self.matchmaker if self.cluster.runs_pool else None,
                ingest=self._cluster_ingest,
            )
        # Group-commit batch size / queue depth / commit counter + the
        # reader-pool high-water mark become scrapeable, and drain spans
        # (record_db_drain) land in the same Tracing ledger operators
        # already read interval breadcrumbs from — the matchmaker
        # owns that instance, hence binding after it exists.
        # (An injected engine gets the same binding: per-server.)
        if hasattr(self.db, "bind_observability"):
            self.db.bind_observability(
                metrics=self.metrics, tracing=self.matchmaker.tracing
            )
        # Crash-recovery plane (recovery.py): attaches the durable
        # ticket journal + idle-gap checkpointer to the matchmaker;
        # start() runs the warm restart once the engine is connected,
        # stop() drains to durable (journal flush + final checkpoint).
        self.recovery = None
        if config.recovery.enabled and (
            self.cluster is None or self.cluster.runs_pool
        ):
            from .recovery import RecoveryPlane

            self.recovery = RecoveryPlane(
                config,
                self.db,
                self.matchmaker,
                log,
                metrics=self.metrics,
                node=node,
            )
        if self.cluster is not None and self.cluster.runs_pool:
            # Owner scale-out plane: lease claims + journal-tail
            # shipping on owners, replication apply + failover monitor
            # on standbys. Needs the matchmaker and (for the shipper)
            # the recovery journal, hence bound here.
            self.cluster.wire_matchmaker(
                self.matchmaker,
                ingest=self._cluster_ingest,
                recovery=self.recovery,
            )
            if (
                self.cluster.migrator is not None
                and self._rpc is not None
            ):
                # Typed begin/refusal for reshard plans: the planner's
                # dispatch gets "busy"/"invalid" back instead of a
                # silently-ignored frame.
                self._rpc.register(
                    "reshard.begin", self.cluster.migrator.on_begin
                )
        # Overload-control plane (overload.py): built here so the API
        # server and pipeline can reference it; signals are registered
        # and the ladder sampler started in start() once the components
        # they read exist. `overload.enabled=False` leaves the front
        # doors completely unwired (self.overload None = no admission,
        # no deadlines — the pre-overload behavior).
        from . import overload as overload_mod
        from . import tracing as tracing_mod
        from .tracing import SloRecorder

        # Request-scoped tracing + SLO plane (tracing.py): configure
        # the process-wide trace store from config (tail sampling,
        # bounds, export) and build the burn-rate recorder. The store
        # is process-global (faults.PLANE precedent) — the last server
        # constructed owns its metrics sink.
        tc = config.tracing
        tracing_mod.TRACES.configure(
            enabled=tc.enabled,
            capacity=tc.capacity,
            sample_rate=tc.sample_rate,
            slow_ms=tc.slow_trace_ms,
            max_active=tc.max_active_traces,
            max_spans=tc.max_spans_per_trace,
            export_path=tc.export_path,
            sample_salt=tc.sample_salt,
            metrics=self.metrics,
        )
        # Device telemetry plane (devobs.py): process-global like the
        # trace store — configure from config.devobs and hand it this
        # server's metrics registry + logger so compile-watch WARNs and
        # the xla_*/device_* families land where operators look.
        from .devobs import DEVOBS

        dv = config.devobs
        DEVOBS.configure(
            enabled=dv.enabled,
            warmup_intervals=dv.warmup_intervals,
            timeline_depth=dv.timeline_depth,
            capture_max_ms=dv.capture_max_ms,
            metrics=self.metrics,
            logger=log.with_fields(subsystem="devobs"),
        )
        self.slo = None
        if tc.enabled:
            self.slo = SloRecorder(
                {
                    "api_latency": {
                        "target": tc.slo_target,
                        "threshold_ms": tc.slo_api_latency_ms,
                    },
                    "matchmaker_interval": {
                        "target": tc.slo_target,
                        "threshold_ms": tc.slo_interval_ms,
                    },
                    "delivery_publish": {
                        "target": tc.slo_target,
                        "threshold_ms": tc.slo_publish_lag_ms,
                    },
                },
                metrics=self.metrics,
            )
        self.matchmaker.slo = self.slo

        self.overload = None
        if config.overload.enabled:
            oc = config.overload
            admission = overload_mod.AdmissionController(
                oc.admission_max_concurrent,
                {
                    overload_mod.REALTIME: oc.admission_queue_realtime,
                    overload_mod.RPC: oc.admission_queue_rpc,
                    overload_mod.LIST: oc.admission_queue_list,
                },
                retry_after_sec=oc.retry_after_sec,
                metrics=self.metrics,
            )
            limiter = (
                overload_mod.RateLimiter(
                    oc.rate_limit_rps, oc.rate_limit_burst
                )
                if oc.rate_limit_rps > 0
                else None
            )
            self.overload = overload_mod.OverloadController(
                admission,
                limiter,
                recover_samples=oc.ladder_recover_samples,
                logger=log.with_fields(subsystem="overload"),
                metrics=self.metrics,
                tracing=self.matchmaker.tracing,
            )
        self.runtime = None
        self.matchmaker.on_matched = self._wrap_matched(
            make_matched_handler(
                log,
                self.router,
                node,
                config.session.encryption_key,
                runtime=None,
            )
        )
        if bus is not None:
            # Parties are owned by their creating node; every operation
            # routes to that authority (cluster/ops.py), membership
            # converges through replicated presence events.
            from .cluster import ClusterPartyRegistry

            self.party_registry = ClusterPartyRegistry(
                log, self.tracker, self.router, self.matchmaker, node,
                bus=bus, rpc=self._rpc,
                session_registry=self.session_registry, config=config,
            )
            # Peer death also sweeps its party members (covers the
            # pre-registered-member window no tracker leave reaches).
            self.cluster.membership.on_peer_down.append(
                self.party_registry.sweep_node
            )
        else:
            self.party_registry = LocalPartyRegistry(
                log, self.tracker, self.router, self.matchmaker, node
            )
        self.tracker.add_listener(
            StreamMode.PARTY, self.party_registry.join_listener()
        )
        from .core.channel import Channels
        from .core.friend import Friends
        from .core.group import Groups
        from .core.notification import Notifications
        from .core.wallet import Wallets

        self.channels = Channels(log, self.db, self.router)
        self.notifications = Notifications(log, self.db, self.router)
        self.wallets = Wallets(log, self.db)
        self.friends = Friends(log, self.db, self.notifications)
        self.groups = Groups(log, self.db)

        from .core.purchase import Purchases
        from .iap.refund import GoogleRefundScheduler

        self.purchases = Purchases(log, self.db, config)
        self.google_refund_scheduler = GoogleRefundScheduler(
            log,
            self.db,
            config,
            poll_interval_sec=config.iap.google_refund_poll_sec,
        )
        self.pipeline = Pipeline(
            log,
            Components(
                config=config,
                tracker=self.tracker,
                router=self.router,
                status_registry=self.status_registry,
                matchmaker=self.matchmaker,
                match_registry=self.match_registry,
                party_registry=self.party_registry,
                session_registry=self.session_registry,
                channels=self.channels,
                groups=self.groups,
                db=self.db,
                metrics=self.metrics,
                overload=self.overload,
            ),
        )
        self.acceptor = SocketAcceptor(
            config,
            log,
            self.session_registry,
            self.session_cache,
            self.tracker,
            self.status_registry,
            self.pipeline,
            self.metrics,
            matchmaker=self.matchmaker,
        )
        # Production social verifier (reference social.NewClient,
        # main.go:136); per-provider config rides each call. Tests may
        # substitute a StubSocialClient.
        from .social.client import HttpSocialClient

        self.social = HttpSocialClient()

        from .leaderboard import (
            LeaderboardScheduler,
            Leaderboards,
            Tournaments,
            rank_cache_from_config,
        )

        # The shared factory is the blacklist's single source of truth
        # (the workload driver builds through it too).
        lb_rank_cache = rank_cache_from_config(config.leaderboard)
        lb_device = None
        if config.leaderboard.device_enabled and (
            self.cluster is None or self.cluster.is_owner
        ):
            # Second TPU workload on the shared mesh: large boards
            # mirror onto the device for batched rank reads; the host
            # cache stays the oracle behind the engine's breaker.
            from .leaderboard import DeviceRankEngine

            lb_device = DeviceRankEngine(
                config.leaderboard,
                log,
                metrics=self.metrics,
                oracle=lb_rank_cache,
            )
        self.leaderboards = Leaderboards(
            log, self.db, lb_rank_cache, device_engine=lb_device
        )
        if self.recovery is not None and lb_device is not None:
            # Board columns ride the PR 7 checkpoint: staged keys (seq
            # included) snapshot with the pool and restore before
            # load()'s DB re-inserts, preserving tie-break order across
            # a warm restart.
            self.recovery.register_extra(
                "leaderboard_device",
                lb_device.snapshot_state,
                lb_device.restore_state,
            )
        self.tournaments = Tournaments(self.leaderboards)
        self.leaderboard_scheduler = LeaderboardScheduler(
            log, self.leaderboards, self.tournaments, runtime=None
        )
        self.leaderboards.on_change = self.leaderboard_scheduler.update

        # Soak plane (loadgen/): the in-process modeled-session tier of
        # the load rig — lab posture, off by default.
        self.soak_engine = None
        if config.loadgen.enabled:
            from .loadgen import SoakEngine

            self.soak_engine = SoakEngine(
                self, config.loadgen, log, self.metrics
            )

        # Fleet observability plane (cluster/obs.py): trace-fragment
        # export toward the collector on every node; the collector
        # node additionally runs the stitching store, the obs.pull
        # federation loop and the health-rule engine. The read-side
        # counterpart to the PR 10-12 write-side cluster planes.
        self.fleet_obs = None
        if self.cluster is not None and self._rpc is not None:
            from .cluster import FleetObsPlane

            self.fleet_obs = FleetObsPlane(self, self._rpc)

        from .api.http import ApiServer
        from .console import ConsoleServer

        self.api = ApiServer(self)
        self.console = ConsoleServer(self)
        self.grpc = None
        self.grpc_port: int | None = None

    def _wrap_matched(self, handler):
        """On a pool-hosting cluster node (owner shard or standby —
        promotion makes the standby publish), matched delivery routes
        back to each ticket's origin node and refuses (→ PR 7
        `unpublished` journal) while a target node is down."""
        if self.cluster is None or not self.cluster.runs_pool:
            return handler
        from .cluster import cluster_matched_handler

        return cluster_matched_handler(
            handler,
            self.cluster.bus,
            self.cluster.membership,
            self.config.name,
            self.logger,
            self.metrics,
            # The publish-back stage stamps each cohort's delivery
            # frames with its held ticket trace, so the delivery hop
            # joins the fleet trace the envelope started (obs.py
            # stitches admission → forward → pool → delivery off it).
            matchmaker=self.matchmaker,
        )

    def attach_runtime(self, runtime):
        """Wire the extensibility runtime into the pipeline, the matchmaker
        matched hook, the match registry (named match factories), and the
        session start/end events (reference NewRuntime wiring,
        main.go:155-160; session_ws.go Close path)."""
        self.runtime = runtime
        self.pipeline.c.runtime = runtime
        self.matchmaker.on_matched = self._wrap_matched(
            make_matched_handler(
                self.logger,
                self.router,
                self.config.name,
                self.config.session.encryption_key,
                runtime=runtime,
            )
        )
        override = getattr(runtime, "matchmaker_override", None)
        if override is not None and override() is not None:
            self.matchmaker.override_fn = override()
        match_names = getattr(runtime, "match_names", None)
        if match_names is not None:
            for name in match_names():
                self.match_registry.register(
                    name, runtime.match_factory(name)
                )
        fire_start = getattr(runtime, "fire_session_start", None)
        if fire_start is not None:
            self.acceptor.on_session_start = fire_start
            self.acceptor.on_session_end = runtime.fire_session_end
        self.leaderboard_scheduler.runtime = runtime

    # ------------------------------------------------------------ lifecycle

    async def start(self, port: int | None = None):
        # Match tasks always land on this loop, even when create_match is
        # driven from a guest-module worker thread.
        self.match_registry.loop = asyncio.get_running_loop()
        if self.cluster is not None:
            # Bus + membership FIRST: presence replication and the
            # matchmaker fan-in must be live before sessions land and
            # before the interval loop ticks.
            await self.cluster.start()
        if not self._db_connected:
            await self.db.connect()
            self._db_connected = True
        if self.recovery is not None:
            # Warm restart BEFORE the matchmaker starts ticking: rebuild
            # the host pool + device buffers from snapshot and replay
            # the journal tail, so tickets stranded by a crash are
            # matchable again from the first interval — and matches
            # formed-but-unpublished at crash time re-dispatch through
            # PR 4's delivery loop instead of being lost.
            recovered = await self.recovery.recover()
            rc = self.config.recovery
            # The recovery posture in one line (PR 5 convention).
            self.logger.info(
                "crash recovery enabled",
                journal=rc.journal,
                checkpoint_interval_sec=rc.checkpoint_interval_sec,
                checkpoint_path=self.recovery.path,
                recovered_tickets=recovered["tickets"],
                replayed_rows=recovered["replayed_rows"],
                recovery_ms=round(recovered["duration_s"] * 1000, 1),
            )
        if self.cluster is not None:
            # Standby failover watchdog AFTER the warm restart: a
            # replication snapshot must never interleave with the
            # store restore above.
            self.cluster.start_failover()
        if self.fleet_obs is not None:
            # Fragment export + (collector) federation cadence tasks —
            # entirely off the hot path; a peer that cannot be pulled
            # costs freshness (stale-marked view), never a wedge.
            self.fleet_obs.start()
        if self.runtime is None and (
            self._runtime_modules or self.config.runtime.path
        ):
            from .runtime import load_runtime

            runtime = load_runtime(
                self.logger,
                self.config,
                modules=self._runtime_modules,
                db=self.db,
                session_cache=self.session_cache,
                session_registry=self.session_registry,
                tracker=self.tracker,
                router=self.router,
                stream_manager=self.stream_manager,
                status_registry=self.status_registry,
                matchmaker=self.matchmaker,
                match_registry=self.match_registry,
                party_registry=self.party_registry,
                metrics=self.metrics,
                leaderboards=self.leaderboards,
                tournaments=self.tournaments,
                channels=self.channels,
                friends=self.friends,
                groups=self.groups,
                notifications=self.notifications,
                wallet=self.wallets,
                purchases=self.purchases,
                social=self.social,
            )
            self.attach_runtime(runtime)
        if self.runtime is not None:
            self.runtime.start_events()
        await self.leaderboards.load()
        self.leaderboard_scheduler.start()
        self.google_refund_scheduler.runtime = self.runtime
        self.google_refund_scheduler.start()
        self.tracker.start()
        if self.cluster is not None and self.cluster.is_standby:
            # Warm standby: the shadow pool applies the owner's journal
            # stream but must NOT tick — the failover monitor starts
            # the interval/delivery loops at promotion.
            self.logger.info(
                "standby shadow pool armed (not ticking)",
                standby_of=self.config.cluster.standby_of,
                lease_ms=self.config.cluster.lease_ms,
                lease_grace_ms=self.config.cluster.lease_grace_ms,
            )
        else:
            self.matchmaker.start()
        if self.overload is not None:
            # Ladder signals read components that now exist: storage
            # write-queue depth (PR 2's gauge, read directly), the
            # device backend's breaker (PR 3), and matchmaker delivery
            # lag (PR 4's cohort deadlines).
            from . import overload as overload_mod

            oc = self.config.overload
            batcher = getattr(self.db, "_batcher", None)
            if batcher is not None:
                self.overload.register_signal(
                    "db_write_queue_depth",
                    overload_mod.db_queue_signal(
                        lambda: batcher.depth,
                        self.config.database.write_queue_depth,
                        oc.shed_queue_depth_warn,
                        oc.shed_queue_depth_shed,
                    ),
                )
            if self.matchmaker.backend.breaker is not None:
                self.overload.register_signal(
                    "backend_breaker",
                    overload_mod.breaker_signal(
                        lambda: self.matchmaker.backend.breaker
                    ),
                )
            self.overload.register_signal(
                "matchmaker_interval_lag",
                overload_mod.interval_lag_signal(
                    self.matchmaker._next_cohort_deadline,
                    oc.interval_lag_warn_sec,
                    oc.interval_lag_shed_sec,
                ),
            )
            if self.cluster is not None:
                # A DOWN peer is the local-only degraded posture: WARN
                # the ladder (tighten admission) while survivors serve.
                from .cluster import cluster_peers_signal

                self.overload.register_signal(
                    "cluster_peers",
                    cluster_peers_signal(self.cluster.membership),
                )
            if self.slo is not None:
                # The SLO plane rides the ladder's sampling cadence:
                # each sample publishes slo_burn_rate{slo,window}; with
                # slo_overload_feedback on, a fast 5m burn escalates
                # admission policy like any other signal.
                tc = self.config.tracing
                self.overload.register_signal(
                    "slo_burn",
                    overload_mod.slo_burn_signal(
                        self.slo,
                        tc.slo_burn_warn,
                        tc.slo_burn_shed,
                        escalate=tc.slo_overload_feedback,
                    ),
                )
            self.overload.start(max(50, oc.ladder_sample_ms) / 1000.0)
            # The admission posture in one line, like PR 4's delivery
            # line: an operator diagnosing 429s/504s reads the
            # effective knobs off the boot log.
            self.logger.info(
                "overload control enabled",
                max_concurrent=oc.admission_max_concurrent,
                queues=dict(
                    realtime=oc.admission_queue_realtime,
                    rpc=oc.admission_queue_rpc,
                    list=oc.admission_queue_list,
                ),
                deadline_default_ms=oc.deadline_default_ms,
                deadline_realtime_ms=oc.deadline_realtime_ms,
                rate_limit_rps=oc.rate_limit_rps,
                rate_limit_burst=oc.rate_limit_burst,
                ladder_sample_ms=oc.ladder_sample_ms,
                ladder_recover_samples=oc.ladder_recover_samples,
            )
        tc = self.config.tracing
        if tc.enabled:
            # The tracing posture in one line (PR 5 convention): an
            # operator wondering why a trace is missing reads the
            # sampling knobs off the boot log.
            self.logger.info(
                "tracing enabled",
                sample_rate=tc.sample_rate,
                slow_trace_ms=tc.slow_trace_ms,
                capacity=tc.capacity,
                export_path=tc.export_path or None,
                slo_target=tc.slo_target,
                slo_overload_feedback=tc.slo_overload_feedback,
            )
        dv = self.config.devobs
        if dv.enabled:
            # The device-telemetry posture in one line (PR 5/6
            # convention): an operator chasing a compile spike or an
            # HBM number reads the knobs off the boot log.
            self.logger.info(
                "device telemetry enabled",
                warmup_intervals=dv.warmup_intervals,
                timeline_depth=dv.timeline_depth,
                capture_max_ms=dv.capture_max_ms,
            )
        mesh = self.matchmaker.backend.mesh
        if mesh is not None:
            # The mesh posture in one line (boot-log convention): an
            # operator asking "is the pool sharded, over how many
            # devices" reads it here.
            self.logger.info(
                "mesh-sharded matchmaking enabled",
                devices=mesh.size,
                configured=self.config.matchmaker.mesh_devices,
            )
        mm_cfg = self.config.matchmaker
        if mm_cfg.interval_pipelining:
            # The delivery posture in one line: cohorts ship on their
            # completion events; the watchdog bounds a lost signal.
            self.logger.info(
                "matchmaker delivery stage started",
                watchdog_sec=float(mm_cfg.delivery_watchdog_sec),
                deadline_guard_sec=float(
                    mm_cfg.pipeline_deadline_guard_sec
                ),
            )
        # One port serves the REST API and /ws (reference api.go: the
        # gateway HTTP listener owns both on the main port).
        self.port = await self.api.start(
            self.config.socket.address or "127.0.0.1",
            self.config.socket.port if port is None else port,
        )
        # Second listener for operators (reference StartConsoleServer,
        # console.go:167). Port 0 in tests; collides with the API port
        # guard only when explicitly equal.
        self.console_port = await self.console.start(
            self.config.console.address or "127.0.0.1",
            0 if self.config.socket.port == 0 else self.config.console.port,
        )
        # gRPC front door: the NakamaApi service transcoding onto the REST
        # listener (api/grpc_server.py; reference convention puts gRPC on
        # port-1 = 7349 next to HTTP 7350 — port 0 in tests).
        if self.config.socket.grpc_port >= 0:
            from .api.grpc_server import GrpcGateway

            # Loopback must target the address the REST listener actually
            # bound, not a hardcoded localhost.
            self.grpc = GrpcGateway(
                self.logger,
                self.config.socket.address or "127.0.0.1",
                self.port,
            )
            self.grpc_port = await self.grpc.start(
                self.config.socket.address or "127.0.0.1",
                0 if self.config.socket.port == 0
                else self.config.socket.grpc_port or self.port - 1,
            )
        if self.soak_engine is not None:
            # The load engine starts LAST: every surface it drives is
            # up, and its first arrivals land on a serving node.
            await self.soak_engine.start()
        self.logger.info(
            "server listening",
            port=self.port,
            console=self.console_port,
            grpc=self.grpc_port,
        )

    async def stop(self, grace_seconds: int | None = None):
        """Reverse-order shutdown draining matches first (main.go:209-240),
        then DRAIN-TO-DURABLE (recovery.py): the overload ladder walks
        to SHED so no new low-priority work is admitted, in-flight
        matchmaker cohorts get the grace window to publish, sessions
        close with a structured restart code + Retry-After hint, the
        ticket journal flushes and a final checkpoint lands, and the
        storage write queue COMMITS before close() — a clean SIGTERM
        under load loses neither tickets nor acknowledged writes."""
        grace = (
            self.config.shutdown_grace_sec
            if grace_seconds is None
            else grace_seconds
        )
        loop = asyncio.get_running_loop()
        deadline = loop.time() + max(0, grace)
        if self.soak_engine is not None:
            # Synthetic load stops before anything drains: the rig must
            # never hold a shutdown hostage.
            await self.soak_engine.stop()
        if self.overload is not None:
            # Drain posture FIRST: reject new queue-able work with
            # Retry-After while the front doors finish in-flight
            # requests — the crash-only-software front half.
            self.overload.enter_drain()
        if self.grpc is not None:
            await self.grpc.stop()
            self.grpc = None
        await self.console.stop()
        await self.api.stop()
        await self.match_registry.stop_all(grace)
        self.leaderboard_scheduler.stop()
        self.google_refund_scheduler.stop()
        # In-flight cohorts publish inside the grace window: the
        # delivery loop is still live, so poll the pipeline until it
        # empties or the deadline passes — a SIGTERM must not strand a
        # formed match that one more second would have shipped. (The
        # journal's unpublished-match records cover whatever remains.)
        depth = self.matchmaker.backend.pipeline_depth
        if grace:
            while depth() and loop.time() < deadline:
                await asyncio.sleep(0.05)
        self.matchmaker.stop()
        retry_after = max(1.0, float(grace))
        for session in self.session_registry.all():
            try:
                await session.close(
                    "server shutting down",
                    code=1012,  # Service Restart
                    kind="shutdown",
                    retry_after_sec=retry_after,
                )
            except TypeError:
                # Non-WS session implementations keep the plain close.
                await session.close("server shutting down")
        self.tracker.stop()
        if self.fleet_obs is not None:
            self.fleet_obs.stop()
        if self.cluster is not None:
            # After sessions closed (their untrack_all replications ride
            # the bus) and before the durable tail: peers detect this
            # node's silence and sweep within down_after_ms.
            await self.cluster.stop()
        if self.runtime is not None:
            await self.runtime.shutdown()
        if self.recovery is not None:
            # Drain-to-durable tail: flush the journal and write one
            # final checkpoint so the next boot replays nothing.
            await self.recovery.shutdown()
        if self._db_connected:
            # Commit the queued write units BEFORE close() — close
            # rejects whatever is still queued, which used to be the
            # "clean SIGTERM rejects queued writes" loss. Deadline-
            # bounded with a 1s floor so even grace=0 stops commit the
            # backlog of an idle queue.
            drain = getattr(self.db, "drain_writes", None)
            if drain is not None:
                budget = max(1.0, deadline - loop.time())
                if not await drain(budget):
                    self.logger.warn(
                        "write queue not fully drained within the"
                        " shutdown grace; remaining units will be"
                        " rejected",
                        budget_s=round(budget, 2),
                    )
            # Close only a database we constructed; an injected one
            # belongs to the caller (it may be shared or inspected
            # after stop).
            if self._owns_db:
                await self.db.close()
                self._db_connected = False
        self.logger.info("server stopped")

    def issue_session(self, user_id: str, username: str) -> str:
        """Create a session token + register it with the cache (the auth
        core's tail; exposed for tests and the console)."""
        from .api import session_token

        token, claims = session_token.generate(
            self.config.session.encryption_key,
            user_id,
            username,
            self.config.session.token_expiry_sec,
        )
        self.session_cache.add(user_id, claims.expires_at, claims.token_id)
        return token


async def _amain(config: Config):
    server = NakamaServer(config)
    await server.start()
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await server.stop()


def main(argv: list[str] | None = None):
    import sys

    from .jaxenv import enable_compile_cache

    enable_compile_cache()
    config = parse_args(argv if argv is not None else sys.argv[1:])
    for warning in config.check():
        print(f"config warning: {warning}")
    asyncio.run(_amain(config))


if __name__ == "__main__":
    main()
