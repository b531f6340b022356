"""LocalMatchmaker: ticket pool bookkeeping + interval processing.

Capability parity with the reference Matchmaker interface and LocalMatchmaker
(reference server/matchmaker.go:169-1068): add/remove/extract/insert with
per-session and per-party MaxTickets enforcement, pause/resume/stop, and a
per-interval `process()` that forms matches and reports them to a callback.

Host bookkeeping is slot-centric (store.py): ticket state lives in
numpy arrays + native hash maps indexed by pool slot, so the interval
path — interval bumping, expiry, matched-ticket unregistration, match
delivery — is O(batch) numpy/native calls, never per-entry Python (the
round-2 latency floor). Delivery hands `on_matched` a columnar
`MatchBatch`; consumers that need entry objects materialize them lazily.

The process backend is pluggable: the CPU oracle (`process.py`) or the TPU
batch backend (`tpu.py`). Custom (runtime-override) processing always runs
the host path since it enumerates combinatorial candidates for user code.

Async production use: `start()` spawns an asyncio interval task; tests call
`process()` directly with the ticker off, mirroring the reference's
NewLocalBenchMatchmaker (server/matchmaker_test.go:1697).
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import threading
import time
import uuid
from typing import Callable

import numpy as np

from .. import faults, overload
from .. import tracing as trace_api
from ..config import MatchmakerConfig
from ..logger import Logger
from ..metrics import Metrics
from ..tracing import Tracing
from .process import process_custom, process_default
from .query import QueryError, parse_query
from .store import SlotStore
from .types import (
    MatchBatch,
    MatchmakerEntry,
    MatchmakerExtract,
    MatchmakerPresence,
    MatchmakerTicket,
)


class MatchmakerError(Exception):
    pass


class ErrTooManyTickets(MatchmakerError):
    pass


class ErrQueryInvalid(MatchmakerError):
    pass


class ErrDuplicateSession(MatchmakerError):
    pass


class ErrNotAvailable(MatchmakerError):
    pass


class PartialPublish(Exception):
    """Raised by an `on_matched` handler that delivered SOME cohorts
    but had to hold others (cluster: a cohort's origin node is down).
    `failed_tickets` names every ticket of every HELD cohort — those
    journal `unpublished` (a restart re-pools them) while the delivered
    cohorts journal `matched` as usual. Holding must be all-or-nothing
    per cohort: a partially-listed cohort would re-pool some of its
    tickets after a restart while its other members already saw the
    match."""

    def __init__(self, failed_tickets, reason: str = ""):
        super().__init__(
            reason or f"{len(failed_tickets)} cohort ticket(s) held"
        )
        self.failed_tickets = frozenset(failed_tickets)


MatchedCallback = Callable[[MatchBatch], None]
OverrideFn = Callable[
    [list[list[MatchmakerEntry]]], list[list[MatchmakerEntry]]
]


class ProcessBackend:
    """The seam between the interval host (`LocalMatchmaker`, the server,
    the console) and what forms the matches. Every backend method and
    attribute they use is declared here, with the body it has for a
    backend that keeps no cohort queue and no device state: nothing is
    ever ready, due or backlogged, and it is idle at once. `CpuBackend`
    adds the oracle's `process_slots`; `TpuBackend` (tpu.py) overrides
    the lot."""

    store: SlotStore | None = None
    # The interval record (breadcrumbs, delivery ledger, add stages):
    # the matchmaker's own, bound by attach().
    tracing: Tracing | None = None
    # The device path's circuit breaker (faults.py) and the jax Mesh the
    # pool shards over, where there is one.
    breaker = None
    mesh = None
    # The pipelined cohorts (tpu.py `Cohort`) the last process_slots /
    # collect_ready call accepted, oldest first; replaced every call.
    accepted_cohorts = ()

    def attach(self, store: SlotStore, tracing: Tracing) -> None:
        """Bind the shared slot store and the interval record before
        any other call."""
        self.store = store
        self.tracing = tracing

    def describe(self) -> dict | None:
        """Where the kernels really run (console /v2/console/device);
        None = on no device."""
        return None

    def annotate(self, name: str):
        """A host span in a captured device profile, where the backend
        runs on JAX; the host-only backends never import it."""
        return contextlib.nullcontext()

    # ------------------------------------------------ pool notifications

    def on_add(self, ticket: MatchmakerTicket, slot: int) -> None:
        """Called after the ticket is slot-registered; may raise to reject
        it (the caller rolls the registration back)."""

    def on_remove_slots(self, slots: np.ndarray) -> None:
        """Called when tickets leave the pool, BEFORE the store clears
        their slots."""

    def in_flight(self, slot: int) -> bool:
        """Is the slot claimed by a dispatched cohort that may still
        match it?"""
        return False

    # ------------------------------------------------------ the interval

    def process_slots(
        self,
        active_slots: np.ndarray,
        last_interval: np.ndarray,
        *,
        max_intervals: int,
        rev_precision: bool,
    ) -> tuple[MatchBatch, np.ndarray, np.ndarray]:
        """Returns (batch, matched_slots, reactivate_slots).

        `reactivate_slots` covers tickets whose pipelined match was
        invalidated after they already went inactive — they get another
        active interval so churn can't strand them passively matchable
        forever."""
        raise NotImplementedError

    # -------------------------------------------------- the cohort queue

    def set_ready_callback(self, cb: Callable[[], None] | None) -> None:
        """Register the cohort-completion signal, called from a worker
        thread whenever a dispatched cohort finishes; None unregisters."""

    def collect_ready(self, *, rev_precision: bool, block_until=None):
        """Accept the queued cohorts that completed, outside
        process_slots: (batch, matched_slots, reactivate_slots), or None
        when nothing is ready."""
        return None

    def pipeline_depth(self) -> int:
        """Dispatched cohorts not yet accepted."""
        return 0

    def pipeline_backlogged(self) -> bool:
        """Does an unfinished cohort need the host now (the idle gap
        sheds its deferrable work)?"""
        return False

    def next_deadline(self) -> float | None:
        """Delivery deadline of the head cohort (perf_counter seconds);
        None when nothing is queued."""
        return None

    def guard_point(self) -> float | None:
        """When the head cohort is due its deadline guard: its delivery
        deadline less the guard margin; None when nothing is queued."""
        return None

    def claim_guard_join(self) -> bool:
        """Claim the head cohort's one guard join: True when it is
        unfinished and was not claimed before (the caller then runs
        `join_head` off the event loop)."""
        return False

    def join_head(self, until: float | None = None) -> bool:
        """Block until the head cohort finishes, `until` passes or its
        own interval is over; returns readiness."""
        return False

    def reclaim_stale(self) -> None:
        """Abandon cohorts wedged past their deadline and free their
        in-flight claims."""

    def wait_idle(self, timeout: float | None = None) -> None:
        """Block until no worker thread of the backend is running."""

    # ------------------------------------------------------ idle-gap work

    def count_cohorts(self) -> None:
        """Put the candidate-list counters on the delivered cohorts'
        ledger rows (before the store drains)."""

    def flush(self) -> None:
        """Push the ticket rows staged so far to the device."""

    # ------------------------------------------------ snapshot / restore

    def snapshot_state(self) -> dict | None:
        """The backend's derived per-ticket state for a checkpoint; None
        where there is none to keep."""
        return None

    def restore_state(self, snap: dict) -> None:
        """Load `snapshot_state`'s section onto a fresh backend whose
        store is already restored; raises where it does not fit."""


class CpuBackend(ProcessBackend):
    """The oracle backend — exact reference semantics on host objects."""

    def process_slots(
        self, active_slots, last_interval, *, max_intervals, rev_precision
    ):
        store = self.store
        actives, _, pool = store.oracle_view(active_slots)
        matched, _ = process_default(
            actives,
            pool,
            max_intervals=max_intervals,
            rev_precision=rev_precision,
            bump_intervals=False,
        )
        batch, slots = lists_to_batch(matched, store)
        return batch, slots, np.zeros(0, dtype=np.int32)


def lists_to_batch(
    matched: list[list[MatchmakerEntry]], store: SlotStore
) -> tuple[MatchBatch, np.ndarray]:
    """Wrap object-path match lists (oracle / override) as a MatchBatch +
    the flat matched slot array for bulk removal."""
    batch = MatchBatch.from_lists(matched)
    slot_parts: list[int] = []
    for entry_set in matched:
        for tid in dict.fromkeys(e.ticket for e in entry_set):
            slot = store.slot_by_id(tid)
            if slot is not None:
                slot_parts.append(slot)
    return batch, np.asarray(slot_parts, dtype=np.int32)


def _select_backend(config: MatchmakerConfig, logger, metrics):
    """config.backend: "cpu" → oracle; "tpu" → device backend, and an
    error where the default JAX device is not a TPU (an interpreting
    backend is never handed out in its place); "auto" → device backend
    when an accelerator is the default JAX device, the exact oracle on a
    CPU-only host. A failing `jax.devices()` propagates: a host whose
    accelerator cannot be reached must not come up quietly on the
    oracle. (SURVEY §7.5: the swappable-backends seam.)"""
    choice = config.backend
    if choice == "cpu":
        return CpuBackend()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    chosen = dict(
        configured=choice,
        platform=platform,
        kind=devices[0].device_kind,
        count=len(devices),
    )
    if choice == "tpu" and platform != "tpu":
        raise RuntimeError(
            f'matchmaker.backend is "tpu" but the default JAX device is'
            f" {platform} ({devices[0].device_kind})"
        )
    if platform == "cpu":
        logger.info("matchmaker backend selected", backend="cpu", **chosen)
        return CpuBackend()
    from .tpu import TpuBackend

    logger.info("matchmaker backend selected", backend="device", **chosen)
    return TpuBackend(config, logger, metrics)


def _young_collections() -> int:
    """Collections of generations 0 and 1 this process has run so far."""
    gen0, gen1, _ = gc.get_stats()
    return gen0["collections"] + gen1["collections"]


class _TicketsView:
    """Mapping-compat view of live tickets (tests/console); not used on
    the interval path."""

    def __init__(self, store: SlotStore):
        self._store = store

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, ticket_id: str) -> bool:
        return ticket_id in self._store

    def __getitem__(self, ticket_id: str) -> MatchmakerTicket:
        t = self._store.get(ticket_id)
        if t is None:
            raise KeyError(ticket_id)
        return t

    def get(self, ticket_id: str, default=None):
        t = self._store.get(ticket_id)
        return default if t is None else t

    def __iter__(self):
        for t in self._store.live_tickets():
            yield t.ticket

    def keys(self):
        return iter(self)

    def values(self):
        return self._store.live_tickets()

    def items(self):
        return [(t.ticket, t) for t in self._store.live_tickets()]


class _ActiveView:
    """Mapping-compat view of active tickets (tests/console)."""

    def __init__(self, store: SlotStore):
        self._store = store

    def __len__(self) -> int:
        return self._store.n_active

    def __contains__(self, ticket_id: str) -> bool:
        slot = self._store.slot_by_id(ticket_id)
        return slot is not None and bool(self._store.active[slot])

    def values(self):
        return list(self._store.ticket_at[self._store.active])

    def keys(self):
        return [t.ticket for t in self.values()]

    def __iter__(self):
        return iter(self.keys())


class LocalMatchmaker:
    def __init__(
        self,
        logger: Logger,
        config: MatchmakerConfig,
        metrics: Metrics | None = None,
        node: str = "local",
        backend: ProcessBackend | None = None,
        on_matched: MatchedCallback | None = None,
        tracing: Tracing | None = None,
    ):
        self.logger = logger.with_fields(subsystem="matchmaker")
        self.config = config
        self.metrics = metrics
        self.node = node
        self.store = SlotStore(config.pool_capacity, config.max_party_size)
        # The interval record: `add`, `_publish` and `Pipeline.process`
        # stamp on it here, the backend writes its crumbs and ledger
        # rows on the same one.
        self.tracing = tracing or Tracing()
        self.backend = backend or _select_backend(config, self.logger, metrics)
        self.backend.attach(self.store, self.tracing)
        self.on_matched = on_matched
        self.override_fn: OverrideFn | None = None

        self._paused = False
        self._stopped = False
        # Request-scoped tracing: tickets added inside an active trace
        # hold that trace open (tail sampling defers until the ticket
        # resolves) so the cohort's dispatch→ready→collected→published
        # stages land in the SAME trace as the socket envelope that
        # created the ticket. Values carry the ticket's SLOT so the
        # interval sweep is O(held tickets), never O(matched slots).
        # Bounded: oldest holds release at the cap; expiry releases on
        # deactivation (a later passive match is not appended).
        self._ticket_traces: dict[str, tuple[str, str, int]] = {}
        # SLO plane (tracing.SloRecorder, bound by the server): interval
        # wall time and publish lag observations feed the burn gauges.
        self.slo = None
        # Crash-recovery plane (recovery.py, bound by the server's
        # RecoveryPlane): the durable ticket journal — every add /
        # remove / matched outcome appended (lazy payloads, drained
        # through the group-commit write pipeline) — and the idle-gap
        # checkpointer. None = journaling off (tests/bench default).
        self.journal = None
        self.checkpointer = None
        self._task: asyncio.Task | None = None
        # Event-driven delivery stage (start() spawns it alongside the
        # interval task): cohort worker threads set this event via
        # call_soon_threadsafe the moment assembly finishes, and the
        # delivery task runs accept → finalize → publish immediately —
        # no gap poll between a cohort being ready and players seeing
        # the match.
        self._delivery_task: asyncio.Task | None = None
        self._delivery_wakeup: asyncio.Event | None = None

    # ------------------------------------------------------ compat views

    @property
    def tickets(self) -> _TicketsView:
        return _TicketsView(self.store)

    @property
    def active(self) -> _ActiveView:
        return _ActiveView(self.store)

    # ------------------------------------------------------------- lifecycle

    def pause(self):
        self._paused = True

    def resume(self):
        self._paused = False

    def stop(self):
        self._stopped = True
        if self._task is not None:
            self._task.cancel()
            self._task = None
        if self._delivery_task is not None:
            self._delivery_task.cancel()
            self._delivery_task = None
        # Unhook the wakeup before the loop closes: a cohort finishing
        # during shutdown must not signal a dead loop.
        self.backend.set_ready_callback(None)
        # No device fetch thread may outlive the server (XLA aborts if
        # a transfer is in flight at interpreter teardown).
        self.backend.wait_idle(timeout=5.0)

    def start(self):
        """Spawn the per-interval processing task (reference
        matchmaker.go:250-260) AND the event-driven delivery stage: the
        interval task owns dispatch + gap maintenance; the delivery
        task wakes on cohort-completion signals from the backend's
        worker threads and ships accept → finalize → publish the
        moment a cohort is ready (deadline-guard and watchdog timed
        fallbacks cover lost signals and wedged heads)."""

        async def _loop():
            # The gap pass below owns full collections; an AUTOMATIC
            # gen2 pass over this server's steady heap (~100k ticket
            # objects plus runtime state) measures 100-650ms and lands
            # mid-interval whenever allocation counters happen to cross
            # the default threshold there. Push the gen2 trigger out of
            # reach — every gap still runs an explicit full collect, so
            # cyclic garbage is bounded by one interval's churn.
            g0, g1, g2_saved = gc.get_threshold()
            gc.set_threshold(g0, g1, 1_000_000)
            try:
                await _loop_body()
            finally:
                # Process-global state: hand automatic gen2 collection
                # back when this matchmaker stops — without the gap
                # collector running, the rest of the process must not be
                # left with full collections effectively disabled.
                gc.set_threshold(g0, g1, g2_saved)

        async def _loop_body():
            shed_streak = 0
            while not self._stopped:
                t0 = time.perf_counter()
                interval_end = t0 + self.config.interval_sec
                # Split the configured interval (cadence stays exactly
                # interval_sec): a short head-gap after process() lets a
                # pipelined device pass + D2H clear, then the GC pass
                # collects the interval's object churn (~2 objects per
                # matched entry) at a chosen point in the idle gap instead
                # of a generational pass landing mid-interval (measured
                # 1-2s pauses at 100k churn). The store graveyard (matched
                # ticket objects parked at removal) drains here too, so
                # the refcount cascade of ~100k objects is idle-gap work.
                gap = min(2.0, self.config.interval_sec / 4)
                await asyncio.sleep(gap)
                if self._stopped:
                    break
                shed_streak = await self._gap_pass(t0 + gap, shed_streak)
                # Delivery is NOT this loop's job: the dedicated
                # delivery stage (spawned alongside, below) wakes on the
                # cohort-completion event the worker thread fires and
                # runs accept → finalize → publish the moment a cohort
                # is ready — the interval loop keeps only dispatch and
                # maintenance, so a cohort ready 80ms after dispatch no
                # longer waits out a gap poll schedule.
                # Same small epsilon the pre-event gap poll ended on:
                # process() fires just BEFORE the nominal boundary, so
                # callers pacing adds on whole intervals enqueue for the
                # NEXT dispatch instead of racing this one.
                await asyncio.sleep(
                    max(0.0, interval_end - 0.02 - time.perf_counter())
                )
                if self._stopped:
                    break
                if not self._paused:
                    try:
                        self.process()
                    except Exception as e:  # never kill the interval loop
                        self.logger.error("matchmaker process error", error=str(e))

        async def _delivery_loop():
            # The delivery stage: waits for a cohort-completion wakeup
            # (worker thread → call_soon_threadsafe), with two timed
            # fallbacks — the head cohort's deadline-guard point (ship a
            # near-deadline cohort via a bounded join even if its signal
            # was lost) and a slow watchdog poll (belt-and-braces drain
            # for lost wakeups / signal-less backends, NOT the delivery
            # latency). Runs on the event loop, so accept/finalize/
            # publish serialize with process() — the in-flight mask and
            # sel-scratch invariants need no new locking.
            watchdog = max(0.05, float(self.config.delivery_watchdog_sec))
            wakeup = self._delivery_wakeup
            backend = self.backend
            while not self._stopped:
                guard_at = backend.guard_point()
                now = time.perf_counter()
                if guard_at is None or guard_at <= now:
                    # Nothing due (or the head is already at/past its
                    # guard point and was handled below): event or
                    # watchdog.
                    timeout = watchdog
                else:
                    timeout = min(watchdog, guard_at - now)
                cause = "watchdog"
                try:
                    await asyncio.wait_for(wakeup.wait(), timeout)
                    cause = "event"
                except asyncio.TimeoutError:
                    if (
                        guard_at is not None
                        and time.perf_counter() >= guard_at
                    ):
                        cause = "deadline"
                wakeup.clear()
                if self._stopped:
                    break
                if self._paused:
                    continue
                try:
                    guard_at = backend.guard_point()
                    if (
                        guard_at is not None
                        and time.perf_counter() >= guard_at
                    ):
                        if backend.claim_guard_join():
                            # Bounded join in a worker thread (the event
                            # loop stays responsive; the cohort's
                            # assembly gets the core), once per head:
                            # the backend keeps that count and bounds
                            # the join by the head's own interval. A
                            # head that failed its one guard join is
                            # wedged — the reclaim below takes it once
                            # it is past its deadline.
                            await asyncio.to_thread(backend.join_head)
                        backend.reclaim_stale()
                    if self.metrics is not None:
                        self.metrics.mm_delivery_wakeups.labels(
                            cause=cause
                        ).inc()
                    self.collect_pipelined()
                except Exception as e:
                    self.logger.error(
                        "delivery stage error", error=str(e)
                    )

        loop = asyncio.get_running_loop()
        self._delivery_wakeup = asyncio.Event()
        wakeup = self._delivery_wakeup

        def _signal():
            # Worker thread → event loop: the only thread-safe way to
            # poke an asyncio.Event. A loop already closed (shutdown
            # race) just drops the signal — stop()'s wait_idle covers
            # the tail.
            try:
                loop.call_soon_threadsafe(wakeup.set)
            except RuntimeError:
                pass

        self.backend.set_ready_callback(_signal)
        self._task = loop.create_task(_loop())
        self._delivery_task = loop.create_task(_delivery_loop())

    async def _gap_pass(self, due: float, shed_streak: int) -> int:
        """One pass of the interval loop's idle-gap maintenance, due at
        perf_counter `due` (where the gap sleep should have ended):
        candidate-list counters onto the delivered cohorts' rows, the
        store's graveyard drained, one full collection, the staged rows
        flushed, the checkpoint when one is due. Returns the new shed
        streak. Every pass stores a `kind: "gap"` breadcrumb beside the
        interval crumbs (tracing.py): when the loop woke and how late,
        the cohorts in flight, whether the pass was shed, and otherwise
        its stages, each also `mm.gap.<stage>` in a captured profile."""
        backend = self.backend
        crumb = self.tracing.open_crumb(kind="gap")
        crumb["wake_late_s"] = crumb["_pc_start"] - due
        crumb["in_flight"] = backend.pipeline_depth()
        cpu_start = time.thread_time()

        def stage(name):
            return self.tracing.span(
                crumb, f"{name}_s", f"mm.gap.{name}", backend.annotate
            )

        try:
            # Backpressure: while an unfinished cohort needs the host
            # (slow D2H fetch, heap-contended assembly), the gap work
            # is SHED for this gap — GC/drain/flush are deferrable
            # optimizations, delivery is not, and on a small host
            # they queue the cohort's worker thread behind seconds of
            # main-thread work. The streak cap keeps a permanently
            # slow pipeline from starving heap maintenance forever.
            crumb["shed"] = backend.pipeline_backlogged() and shed_streak < 2
            if crumb["shed"]:
                if self.metrics is not None:
                    self.metrics.mm_gap_shed.inc()
                return shed_streak + 1
            # Delivered cohorts' candidate-list counters go on
            # their ledger rows here, before the drain: they
            # read the removed tickets' slots as matched.
            with stage("count"):
                try:
                    backend.count_cohorts()
                except Exception as e:
                    self.logger.error("cohort count error", error=str(e))
            # Preemptible: stop the teardown pass early rather
            # than queue a due cohort delivery behind it. The
            # budget is floored at 200ms forward — when the head
            # cohort is already past its guard point (chronically
            # slow pipeline, forced maintenance gap) the drain
            # must still make progress, or the graveyard grows
            # until the allocator pays the full teardown inline
            # on the add path.
            with stage("drain"):
                guard_at = backend.guard_point()
                self.store.drain(
                    None
                    if guard_at is None
                    else max(time.perf_counter() + 0.2, guard_at)
                )
            with stage("gc"):
                crumb["gc_collected"] = gc.collect()
            # Idle-gap flush: push ticket rows staged so far so
            # the interval's own flush handles only the adds that
            # arrive during the remaining sleep (eager 2048-row
            # chunking already streams the bulk as adds come in).
            with stage("flush"):
                try:
                    backend.flush()
                except Exception as e:
                    self.logger.error("gap flush error", error=str(e))
            if self.checkpointer is not None and self.checkpointer.due():
                # Crash-recovery checkpoint rides the same idle
                # gap as GC/drain/flush: pool snapshot + journal
                # truncation, bounded replay for the next boot.
                # Failure is survivable (WARNed inside) and must
                # never kill the interval loop.
                with stage("checkpoint"):
                    try:
                        await self.checkpointer.maybe_checkpoint(self)
                    except Exception as e:
                        self.logger.error("checkpoint error", error=str(e))
            return 0
        finally:
            crumb["cpu_s"] = time.thread_time() - cpu_start
            self.tracing.record(crumb)

    # ------------------------------------------------------------------ add

    def add(
        self,
        presences: list[MatchmakerPresence],
        session_id: str,
        party_id: str,
        query: str,
        min_count: int,
        max_count: int,
        count_multiple: int = 1,
        string_properties: dict[str, str] | None = None,
        numeric_properties: dict[str, float] | None = None,
        embedding=None,
        ticket_id: str | None = None,
        created_at: float | None = None,
    ) -> tuple[str, float]:
        """Submit a ticket. Returns (ticket id, created_at seconds).

        `ticket_id`/`created_at` are normally minted here; the cluster
        ingest (cluster/matchmaker.py) passes the origin frontend's
        pre-minted node-stamped id and wall clock so cross-node tickets
        keep their identity and age through the pool, journal and
        checkpoints.

        Reference Add: server/matchmaker.go:443-566."""
        if self._stopped:
            raise ErrNotAvailable("matchmaker stopped")
        # Five stamps an add (tracing.AddStages): validation, parse and
        # the ticket's making; store + staged device row; journal; trace.
        t0 = time.perf_counter()
        # Deadline propagation (overload.py): a caller whose deadline
        # already passed gets DEADLINE_EXCEEDED before the ticket is
        # registered — registering it would be dead work the client has
        # already given up on (their retry re-adds it).
        dl = overload.current_deadline()
        if dl is not None and dl.expired():
            if self.metrics is not None:
                self.metrics.request_deadline_exceeded.labels(
                    stage="matchmaker"
                ).inc()
            raise overload.DeadlineExceeded(
                "caller deadline expired before matchmaker add"
            )
        if not presences:
            raise MatchmakerError("at least one presence required")
        if count_multiple < 1:
            raise MatchmakerError("count_multiple must be >= 1")
        if min_count < 1 or max_count < min_count:
            raise MatchmakerError("invalid min/max counts")
        if len(presences) > max_count:
            raise MatchmakerError("more presences than max_count")
        try:
            parsed = parse_query(query)
        except QueryError as e:
            raise ErrQueryInvalid(str(e)) from e

        session_ids: set[str] = set()
        for p in presences:
            if p.session_id in session_ids:
                raise ErrDuplicateSession(p.session_id)
            session_ids.add(p.session_id)

        if ticket_id is None:
            ticket_id = str(uuid.uuid4())
        elif self.store.get(ticket_id) is not None:
            # Re-delivered cluster forward: the id is already live. The
            # duplicate check MUST precede the MaxTickets enforcement —
            # a ticket re-forwarded during an owner takeover (frontend
            # closing the replication-lag window) is already counted in
            # this pool's quota, and judging it over-quota here would
            # reject-back a live ticket instead of absorbing the
            # idempotent re-delivery.
            raise KeyError(ticket_id)

        max_tickets = self.config.max_tickets
        for p in presences:
            if self.store.session_ticket_count(p.session_id) >= max_tickets:
                raise ErrTooManyTickets(p.session_id)
        if (
            party_id
            and self.store.party_ticket_count(party_id) >= max_tickets
        ):
            raise ErrTooManyTickets(party_id)
        if created_at is None:
            created_at = time.time()
        string_properties = string_properties or {}
        numeric_properties = numeric_properties or {}
        entries = [
            MatchmakerEntry(
                ticket=ticket_id,
                presence=p,
                string_properties=string_properties,
                numeric_properties=numeric_properties,
                party_id=party_id,
                create_time=created_at,
            )
            for p in presences
        ]
        ticket = MatchmakerTicket(
            ticket=ticket_id,
            query=query,
            min_count=min_count,
            max_count=max_count,
            count_multiple=count_multiple,
            session_id=session_id,
            party_id=party_id,
            entries=entries,
            string_properties=string_properties,
            numeric_properties=numeric_properties,
            created_at=created_at,
            parsed_query=parsed,
            embedding=embedding,
        )
        t_parsed = time.perf_counter()
        self._register(ticket)
        t_registered = time.perf_counter()
        if self.journal is not None:
            self.journal.record_add(ticket)
        t_journaled = time.perf_counter()
        sp = trace_api.current_span()
        if sp is not None:
            slot = self.store.slot_by_id(ticket_id)
            # The add as a real span in the caller's trace, plus a hold
            # so the trace stays open until the ticket matches (or is
            # removed) — the add→matched story reads off one trace id.
            trace_api.emit_span(
                sp.trace_id, sp.span_id, "matchmaker.add",
                start_ts=created_at, end_ts=time.time(),
                ticket=ticket_id, query=query,
                min_count=min_count, max_count=max_count,
            )
            if slot is not None:
                self._hold_ticket_trace(ticket_id, sp, slot)
            self.logger.debug(
                "matchmaker ticket added", ticket=ticket_id
            )
        self.tracing.add_stages.add(
            t0, t_parsed, t_registered, t_journaled, time.perf_counter()
        )
        return ticket_id, created_at

    def _hold_ticket_trace(self, ticket_id: str, sp, slot: int) -> None:
        trace_api.TRACES.hold(sp.trace_id)
        self._ticket_traces[ticket_id] = (sp.trace_id, sp.span_id, slot)
        while len(self._ticket_traces) > 4096:
            # Bounded holds: a flood of traced adds that never resolve
            # must not pin traces forever — oldest release unfinished.
            old_id = next(iter(self._ticket_traces))
            old_trace = self._ticket_traces.pop(old_id)[0]
            trace_api.TRACES.release(old_trace)

    def _release_ticket_trace(self, ticket_id: str) -> None:
        ctx = self._ticket_traces.pop(ticket_id, None)
        if ctx is not None:
            trace_api.TRACES.release(ctx[0])

    def trace_context(self, ticket_id: str) -> tuple[str, str] | None:
        """(trace_id, span_id) of a held traced ticket, or None — the
        cluster publish-back stamps outbound route frames with it so
        the delivery hop joins the ticket's own trace."""
        ctx = self._ticket_traces.get(ticket_id)
        if ctx is None:
            return None
        return ctx[0], ctx[1]

    def _finish_ticket_traces(self, matched_slots, cohorts) -> None:
        """Resolve held ticket traces after an interval/collect pass:
        matched tickets get the cohort stage spans (attributed to THEIR
        cohort's ledger entry; `cohorts` are those the call accepted)
        and their hold released; tickets parked inactive with no cohort in flight
        (expired unmatched) release too — their trace completes with
        just the add, and a later PASSIVE match is not appended (the
        bounded store cannot hold traces for tickets that may linger
        pooled indefinitely). O(held tickets) python plus O(matched)
        numpy mask writes; O(1) when no traced tickets exist (the
        bench path pays one dict bool check)."""
        if not self._ticket_traces:
            return
        cap = len(self.store.ticket_at)
        matched_mask = np.zeros(cap, dtype=bool)
        if matched_slots is not None and len(matched_slots):
            matched_mask[matched_slots] = True
        # slot → accepted-cohort index (numpy fancy-assign, C speed):
        # when one collect accepted SEVERAL cohorts, each matched slot
        # maps to ITS cohort's ledger entry — a ticket must not wear
        # another cohort's stage chain.
        cohort_of = None
        if cohorts:
            cohort_of = np.full(cap, -1, dtype=np.int32)
            for i, cohort in enumerate(cohorts):
                cohort_of[cohort.matched_slots] = i
        default_entry = None
        if len(self.tracing.deliveries):
            default_entry = self.tracing.deliveries[-1]
        ticket_at = self.store.ticket_at
        active = self.store.active
        for tid, (trace_id, span_id, slot) in list(
            self._ticket_traces.items()
        ):
            t = ticket_at[slot]
            if t is None or t.ticket != tid:
                # Slot already drained/reassigned under this entry (a
                # path that bypassed the release hooks): close it out
                # rather than pin the trace forever.
                del self._ticket_traces[tid]
                trace_api.TRACES.release(trace_id)
                continue
            if matched_mask[slot]:
                del self._ticket_traces[tid]
                entry = default_entry
                if cohort_of is not None and cohort_of[slot] >= 0:
                    entry = cohorts[cohort_of[slot]].entry
                trace_api.emit_matched_spans((trace_id, span_id), entry)
            elif not active[slot] and not self.backend.in_flight(slot):
                # Deactivated (expired / min==max attempt spent) with
                # no dispatched cohort that could still match it: the
                # add→(not yet matched) trace finalizes now.
                del self._ticket_traces[tid]
                trace_api.TRACES.release(trace_id)

    def _register(self, ticket: MatchmakerTicket, active: bool = True):
        slot = self.store.add(ticket, active=active)
        try:
            self.backend.on_add(ticket, slot)
        except Exception:
            # A rejection (bad embedding, device row overflow) must leave
            # everything as it was.
            self.store.remove_slots(
                np.asarray([slot], dtype=np.int32), defer_free=False
            )
            raise
        self._update_gauges()

    # -------------------------------------------------------------- process

    def _next_cohort_deadline(self) -> float | None:
        """Earliest delivery deadline among the backend's queued cohorts
        (perf_counter seconds), or None: pipeline-less backends and an
        empty queue both report nothing due."""
        return self.backend.next_deadline()

    def collect_pipelined(self, block_until=None) -> MatchBatch | None:
        """Deliver any pipelined cohorts whose device pass + gap assembly
        already completed — called mid-gap by the interval loop so a
        match reaches players seconds after its dispatch instead of a
        full interval later. `block_until` (perf_counter seconds) bounds
        a blocking join of the head cohort for deadline-guard delivery.
        No-op (None) for backends without a pipeline or when nothing is
        ready."""
        t0 = time.perf_counter()
        try:
            out = self.backend.collect_ready(
                rev_precision=self.config.rev_precision,
                block_until=block_until,
            )
        except Exception as e:
            # Defense in depth: the backend reclaims + degrades its own
            # failures (tpu.py breaker path); anything that still leaks
            # here must cost ONE collection poll, never the interval
            # loop. Tickets stay pooled; the backstop reclamation sweep
            # frees any claim the failure left behind.
            self.logger.error("pipelined collect failed", error=str(e))
            return None
        if out is None:
            return None
        batch, matched_slots, reactivate = out
        objs = None
        with self.backend.annotate("mm.remove"):
            if len(matched_slots):
                self.backend.on_remove_slots(matched_slots)
                objs = self.store.remove_slots(matched_slots)
                if batch.offsets is not None:
                    batch.bind_tickets(objs)
            self.store.reactivate(reactivate)
        if self.metrics is not None:
            self.metrics.mm_matched.inc(batch.entry_count if batch else 0)
            self._update_gauges()
        head = self._deliver(
            batch, matched_slots, objs, self.backend.accepted_cohorts
        )
        if head is not None:
            head["delivery_held_s"] = time.perf_counter() - t0
        return batch

    def _deliver(self, batch, matched_slots, objs, cohorts):
        """The tail of a process() / collect_pipelined() call: publish
        the batch, close each shipped cohort's stage chain with its
        dispatch→published lag, journal the outcome and resolve held
        ticket traces. `cohorts` are the backend's records of the
        pipelined cohorts the call accepted, oldest first; the call's
        own stamps go, in place, on the oldest one's ledger row, which
        is returned for the caller to add `delivery_held_s` as its last
        act (None when the call shipped no such cohort)."""
        head = cohorts[0].entry if cohorts else None
        published_ok = True
        t_publish = time.perf_counter()
        if len(batch) and self.on_matched is not None:
            with self.backend.annotate("mm.publish"):
                published_ok = self._publish(batch, head)
            now = time.perf_counter()
            for cohort in cohorts:
                # Feeds the matchmaker_delivery_publish_lag histogram —
                # the end-to-end number the dispatched→ready→accepted→
                # published attribution hangs off.
                lag = cohort.published(now)
                if self.metrics is not None:
                    self.metrics.mm_delivery_publish_lag.observe(lag)
                if self.slo is not None:
                    self.slo.observe("delivery_publish", lag * 1000)
        with self.backend.annotate("mm.journal_matched"):
            self._journal_matched(matched_slots, objs, published_ok)
        self._finish_ticket_traces(matched_slots, cohorts)
        if head is not None:
            # All between the newest accept stamp and the publish:
            # batch finalisation, slot removal, reactivation, gauges.
            head["deliver_remove_s"] = t_publish - cohorts[-1].t_accept
        return head

    def _publish(self, batch: MatchBatch, row: dict | None = None) -> bool:
        """Deliver a matched batch to `on_matched`, bounded by the fault
        plane's `delivery.publish` point. The tickets are already
        removed from the pool by the time delivery runs (reference
        single-shot semantics), so a failed or dropped publish is
        counted and logged loudly — the session-facing retry belongs to
        the consumer — but it must never poison interval bookkeeping.
        Returns publish success: a False journals the whole batch as
        `unpublished` matches so a restart re-pools the tickets; a
        handler raising PartialPublish (cluster: some cohorts' origin
        nodes down) returns the held tickets' id set so ONLY those
        cohorts journal unpublished. Where the handler keeps publish
        stage sums (`stages`, api/matchmaker_events.py), they are moved
        onto `row`, the ledger row of the delivery call, beside
        `publish_gc_collections`, the young-generation collections that
        ran while the handler did, and the loop thread's CPU account of
        the handler's call (`tracing.cpu_split`: `publish_cpu_s`,
        `publish_offcpu_s`, `publish_other_cpu_s`,
        `publish_invol_switches`, `publish_minor_faults`)."""
        try:
            if faults.fire("delivery.publish"):
                # drop-mode chaos: delivery intentionally discarded.
                self.logger.warn(
                    "match delivery dropped (fault armed)",
                    matches=len(batch),
                )
                if self.metrics is not None:
                    self.metrics.mm_delivery_failed.inc()
                return False
            # No automatic collection inside the call: what the handler
            # builds survives it (the sessions hold the envelopes), so a
            # young-generation pass in there only walks survivors, some
            # 730 times a full-pool cohort. The first collection after
            # the call walks them once, after the last envelope has
            # left; the interval's gap pass still owns full collections.
            gc_was_enabled = gc.isenabled()
            gc.disable()
            collections = _young_collections()
            started = trace_api.cpu_stamp()
            try:
                self.on_matched(batch)
            finally:
                if row is not None:
                    # Running or waiting: the loop thread's own CPU
                    # beside the wall of the handler's call.
                    row.update(
                        trace_api.cpu_split(
                            "publish", started, trace_api.cpu_stamp()
                        )
                    )
                    row["publish_gc_collections"] = (
                        _young_collections() - collections
                    )
                stages = getattr(self.on_matched, "stages", None)
                if stages:
                    if row is not None:
                        row.update(stages)
                    stages.update(dict.fromkeys(stages, 0))
                # Last, so that the pass it sets off starts after the
                # caller's publish stamp and is not read as publishing.
                if gc_was_enabled:
                    gc.enable()
            return True
        except PartialPublish as e:
            self.logger.warn(
                "match delivery partially held",
                held_tickets=len(e.failed_tickets),
                matches=len(batch),
                reason=str(e),
            )
            if self.metrics is not None:
                self.metrics.mm_delivery_failed.inc()
            return e.failed_tickets
        except Exception as e:
            self.logger.error(
                "match delivery failed",
                error=str(e),
                matches=len(batch),
            )
            if self.metrics is not None:
                self.metrics.mm_delivery_failed.inc()
            return False

    def _journal_matched(self, matched_slots, objs, published_ok: bool):
        """Journal one interval/collect call's matched outcome: ids only
        when the cohort published (the tickets are consumed for good),
        full payloads when it did NOT (`unpublished` — a restart
        re-pools them for re-dispatch). `objs` is the store's removal
        snapshot — usually the LAZY resolver, passed through unresolved
        so serialization lands in the journal drain (idle gap), never
        here on the delivery path."""
        if (
            self.journal is None
            or matched_slots is None
            or not len(matched_slots)
        ):
            return
        if callable(objs):
            resolver = objs
        else:
            arr = objs
            resolver = lambda: (arr if arr is not None else ())  # noqa: E731
        if isinstance(published_ok, frozenset):
            # Partial publish (cluster: held cohorts): only the held
            # tickets journal unpublished — journaling the delivered
            # ones too would double-deliver their matches after a
            # restart's re-pool.
            held = published_ok
            self.journal.record_unpublished(
                lambda: [
                    t for t in resolver()
                    if t is not None and t.ticket in held
                ]
            )
            self.journal.record_matched(
                lambda: [
                    t for t in resolver()
                    if t is not None and t.ticket not in held
                ]
            )
        elif published_ok:
            self.journal.record_matched(resolver)
        else:
            self.journal.record_unpublished(resolver)

    def process(self) -> MatchBatch:
        """One matching interval (reference Process, matchmaker.go:282-441).

        Interval bookkeeping is vectorized over the active slot array; the
        backend returns matches columnar; unregistration is one bulk store
        call. Per-entry Python objects are only touched on the override /
        host-only object paths."""
        t0 = time.perf_counter()
        t_backend = t0  # re-stamped just before the backend call below
        backend_failed = False
        store = self.store
        meta = store.meta
        active_slots = store.active_slots()
        max_intervals = self.config.max_intervals

        if self.override_fn is not None:
            batch, matched_slots, expired_slots = self._process_override(
                active_slots
            )
            reactivate = np.zeros(0, dtype=np.int32)
        else:
            # Interval bump + expiry, vectorized (reference bumps
            # per-active in the loop; equivalent because matched actives
            # leave the pool anyway).
            meta["intervals"][active_slots] += 1
            iv = meta["intervals"][active_slots]
            last = (iv >= max_intervals) | (
                meta["min_count"][active_slots]
                == meta["max_count"][active_slots]
            )
            expired_slots = active_slots[last]
            t_backend = time.perf_counter()
            try:
                batch, matched_slots, reactivate = (
                    self.backend.process_slots(
                        active_slots,
                        last,
                        max_intervals=max_intervals,
                        rev_precision=self.config.rev_precision,
                    )
                )
            except Exception as e:
                # Defense in depth: the device backend classifies and
                # absorbs its own failures (tpu.py breaker/reclaim
                # paths); a backend that still leaks an exception must
                # cost one interval's matching, never the bookkeeping
                # around it. Tickets stay pooled; expired min==max
                # actives get their attempt back next interval.
                self.logger.error(
                    "backend process failed; interval degraded",
                    error=str(e),
                    backend=type(self.backend).__name__,
                )
                backend_failed = True
                batch = MatchBatch.from_lists([])
                matched_slots = np.zeros(0, dtype=np.int32)
                reactivate = expired_slots.astype(np.int32)

        t_rm = time.perf_counter()
        with self.backend.annotate("mm.remove"):
            store.deactivate(expired_slots)
            t_rm1 = time.perf_counter()
            if len(matched_slots):
                self.backend.on_remove_slots(matched_slots)
            t_rm2 = time.perf_counter()
            objs = None
            if len(matched_slots):
                objs = store.remove_slots(matched_slots)
                if batch.offsets is not None:
                    # Columnar batch: its slots ARE matched_slots in
                    # order — reuse the parked refs as the delivery
                    # snapshot.
                    batch.bind_tickets(objs)
            store.reactivate(reactivate)
        t_cb = time.perf_counter()

        if self.metrics is not None:
            self.metrics.mm_process_time.observe(time.perf_counter() - t0)
            self.metrics.mm_matched.inc(batch.entry_count if batch else 0)
            self._update_gauges()
        if self.slo is not None:
            self.slo.observe(
                "matchmaker_interval", (time.perf_counter() - t0) * 1000
            )

        # Override intervals never called process_slots, and a backend
        # that RAISED out of it accepted nothing: the cohorts it lists
        # are some earlier call's.
        own_interval = self.override_fn is None and not backend_failed
        head = self._deliver(
            batch, matched_slots, objs,
            self.backend.accepted_cohorts if own_interval else (),
        )
        # Attribute the post-backend tail (slot removal, delivery
        # callback) on the interval's breadcrumb: the p99 work that
        # isn't inside process_slots must still be visible to the bench
        # (VERDICT r4 #2: per-pool breadcrumbs to attribute spikes).
        # Override intervals never called process_slots, so the last
        # crumb is some earlier interval's — updating it would corrupt
        # that interval's attribution. Likewise a backend that RAISED
        # out of process_slots recorded no crumb for this interval.
        if own_interval and self.tracing.breadcrumbs:
            self.tracing.breadcrumbs[-1].update(
                remove_s=t_cb - t_rm,
                rm_backend_s=t_rm2 - t_rm1,
                rm_store_s=t_cb - t_rm2,
                callback_s=time.perf_counter() - t_cb,
                pre_backend_s=t_backend - t0,
                threads=threading.active_count(),
            )
        if head is not None:
            head["delivery_held_s"] = time.perf_counter() - t0
        return batch

    def _process_override(self, active_slots: np.ndarray):
        """Runtime-override interval: object semantics (the override fn
        consumes entry lists), small pools by design."""
        store = self.store
        actives, ordered, pool = store.oracle_view(active_slots)
        matched, expired_ids = process_custom(
            actives,
            pool,
            max_intervals=self.config.max_intervals,
            rev_precision=self.config.rev_precision,
            override_fn=self.override_fn,
        )
        # process_custom bumped object intervals; write back.
        store.meta["intervals"][ordered] = [t.intervals for t in actives]
        # An override fn may return overlapping or raced-out sets: first
        # set wins, later ones drop (old unregister-as-you-go behaviour).
        confirmed: list[list[MatchmakerEntry]] = []
        taken: set[str] = set()
        for entry_set in matched:
            tids = {e.ticket for e in entry_set}
            if all(t in store and t not in taken for t in tids):
                confirmed.append(entry_set)
                taken |= tids
        batch, matched_slots = lists_to_batch(confirmed, store)
        expired_slots = np.asarray(
            [
                s
                for tid in expired_ids
                if (s := store.slot_by_id(tid)) is not None
            ],
            dtype=np.int32,
        )
        return batch, matched_slots, expired_slots

    # -------------------------------------------------------------- removal

    def _remove_slots(self, slots: np.ndarray):
        if len(slots) == 0:
            return
        # API callers may pass duplicate ids; the store requires unique
        # slots (a duplicate would double-free into the allocator).
        slots = np.unique(np.asarray(slots, dtype=np.int32))
        removed_ids: list[str] = []
        if self.journal is not None:
            # Ids captured BEFORE the eager teardown clears ticket_at;
            # journaled only AFTER the removal really happened (a remove
            # record for a removal that raised would drop a live ticket
            # at replay). Cancel-path removals are small (client/session
            # scoped): the id walk is O(removed), not interval work.
            ticket_at = self.store.ticket_at
            removed_ids = [
                ticket_at[s].ticket
                for s in slots
                if ticket_at[s] is not None
            ]
        if self._ticket_traces:
            # Cancelled/removed tickets release their trace holds (no
            # matched spans — the trace finalizes with just the add).
            ticket_at = self.store.ticket_at
            for s in slots:
                t = ticket_at[s]
                if t is not None:
                    self._release_ticket_trace(t.ticket)
        self.backend.on_remove_slots(slots)
        # Eager teardown: API removals are small, and immediate slot free
        # keeps LIFO reuse (pool density). Only the interval's bulk
        # matched-removal defers to the idle-gap drain.
        self.store.remove_slots(slots, defer_free=False)
        if self.journal is not None and removed_ids:
            self.journal.record_remove(removed_ids)

    def _unregister(self, ticket_id: str):
        slot = self.store.slot_by_id(ticket_id)
        if slot is None:
            return
        self._remove_slots(np.asarray([slot], dtype=np.int32))

    def remove_session(self, session_id: str, ticket_id: str):
        """Ownership-checked removal (reference matchmaker.go:725)."""
        t = self.store.get(ticket_id)
        if t is None or session_id not in t.session_ids:
            raise MatchmakerError("ticket not found")
        self._unregister(ticket_id)
        self._update_gauges()

    def remove_session_all(self, session_id: str):
        slots = [
            self.store.slot_by_id(t.ticket)
            for t in self.store.session_tickets(session_id)
        ]
        self._remove_slots(
            np.asarray([s for s in slots if s is not None], dtype=np.int32)
        )
        self._update_gauges()

    def remove_party(self, party_id: str, ticket_id: str):
        t = self.store.get(ticket_id)
        if t is None or t.party_id != party_id:
            raise MatchmakerError("ticket not found")
        self._unregister(ticket_id)
        self._update_gauges()

    def remove_party_all(self, party_id: str):
        slots = [
            self.store.slot_by_id(t.ticket)
            for t in self.store.party_tickets(party_id)
        ]
        self._remove_slots(
            np.asarray([s for s in slots if s is not None], dtype=np.int32)
        )
        self._update_gauges()

    def remove_all(self, node: str):
        if node == self.node:
            self._remove_slots(self.store.live_slots())
        else:
            # Cluster sweep: tickets whose presences belong to a (dead)
            # foreign node. O(pool) object walk — peer death is rare
            # and off the interval path.
            ticket_at = self.store.ticket_at
            slots = [
                s
                for s in self.store.live_slots()
                if any(
                    e.presence.node == node
                    for e in ticket_at[s].entries
                )
            ]
            self._remove_slots(np.asarray(slots, dtype=np.int32))
        self._update_gauges()

    def remove(self, ticket_ids: list[str]):
        slots = [self.store.slot_by_id(tid) for tid in ticket_ids]
        self._remove_slots(
            np.asarray([s for s in slots if s is not None], dtype=np.int32)
        )
        self._update_gauges()

    # ------------------------------------------------------ extract / insert

    def extract(self) -> list[MatchmakerExtract]:
        """Export all tickets for node-drain handover (matchmaker.go:684)."""
        store = self.store
        iv = store.meta["intervals"]
        out = []
        for s in store.live_slots():
            t = store.ticket_at[s]
            out.append(
                MatchmakerExtract(
                    presences=[e.presence for e in t.entries],
                    session_id=t.session_id,
                    party_id=t.party_id,
                    query=t.query,
                    min_count=t.min_count,
                    max_count=t.max_count,
                    count_multiple=t.count_multiple,
                    string_properties=dict(t.string_properties),
                    numeric_properties=dict(t.numeric_properties),
                    ticket=t.ticket,
                    created_at=t.created_at,
                    intervals=int(iv[s]),
                    embedding=t.embedding,
                )
            )
        return out

    def insert(self, extracts: list[MatchmakerExtract]):
        """Bulk-import tickets from another node (matchmaker.go:567) or
        the crash-recovery replay. Query ASTs are parsed once per
        DISTINCT query across the batch — handover/replay batches
        repeat a small canonical query set, and the shared-AST
        discipline is already established by the checkpoint thaw
        path (types.thaw_ticket)."""
        parse_cache: dict[str, object] = {}
        for ex in extracts:
            parsed = parse_cache.get(ex.query)
            if parsed is None:
                try:
                    parsed = parse_cache[ex.query] = parse_query(ex.query)
                except QueryError:
                    self.logger.warn(
                        "insert: dropping bad query", ticket=ex.ticket
                    )
                    continue
            entries = [
                MatchmakerEntry(
                    ticket=ex.ticket,
                    presence=p,
                    string_properties=ex.string_properties,
                    numeric_properties=ex.numeric_properties,
                    party_id=ex.party_id,
                    create_time=ex.created_at,
                )
                for p in ex.presences
            ]
            ticket = MatchmakerTicket(
                ticket=ex.ticket,
                query=ex.query,
                min_count=ex.min_count,
                max_count=ex.max_count,
                count_multiple=ex.count_multiple,
                session_id=ex.session_id,
                party_id=ex.party_id,
                entries=entries,
                string_properties=dict(ex.string_properties),
                numeric_properties=dict(ex.numeric_properties),
                created_at=ex.created_at,
                intervals=ex.intervals,
                parsed_query=parsed,
                embedding=ex.embedding,
            )
            try:
                self._register(ticket)
            except KeyError:
                # Re-delivered handover batch: the id is already live.
                self.logger.warn(
                    "insert: duplicate ticket", ticket=ex.ticket
                )
                continue
            if self.journal is not None:
                # Handover inserts are adds for durability purposes;
                # recovery replay suspends the journal so replayed
                # tickets are not re-journaled.
                self.journal.record_add(ticket)

    # ------------------------------------------------- snapshot / restore

    def snapshot_state(self) -> dict:
        """Checkpoint view of the whole matchmaker (recovery.py): the
        slot store's columnar state + ticket objects, and — when the
        backend keeps derived device state — its compiled pool rows and
        mirrors, so a warm restart is bulk restores + one device_put,
        never ~pool_size re-registrations."""
        snap: dict = {
            "store": self.store.snapshot(),
            "tickets_total": len(self.store),
        }
        alive = self.store.alive
        snap["max_created_seq"] = (
            int(self.store.meta["created_seq"][alive].max())
            if alive.any()
            else 0
        )
        backend_snap = self.backend.snapshot_state()
        if backend_snap is not None:
            snap["backend"] = backend_snap
        return snap

    def restore_state(self, snap: dict) -> None:
        """Warm-restart restore onto a FRESH matchmaker built from the
        same config. Restores the store, then the backend's derived
        state — directly when the snapshot carries a matching backend
        section, else by re-registering each live ticket through
        `on_add` (cross-backend restore: correct, not bulk-fast)."""
        from .types import advance_created_seq

        self.store.restore(snap["store"])
        advance_created_seq(snap.get("max_created_seq", 0))
        backend_snap = snap.get("backend")
        if backend_snap is not None:
            try:
                self.backend.restore_state(backend_snap)
            except Exception as e:
                # Schema drift (config changed across the restart) or a
                # torn backend section: the store is already populated,
                # so bailing here would leave live tickets with no
                # device rows — permanently unmatchable zombies. Fall
                # back to re-deriving each ticket's rows through the
                # normal add path: slow, correct.
                self.logger.warn(
                    "backend snapshot restore failed; re-deriving"
                    " device rows per ticket",
                    error=str(e),
                )
                self._rederive_backend_rows()
        else:
            # Snapshot written by a state-less backend (CPU oracle):
            # whatever this backend derives, it derives per ticket.
            self._rederive_backend_rows()
        self._update_gauges()

    def _rederive_backend_rows(self) -> None:
        """Rebuild the backend's per-ticket derived state through
        `on_add` for every live slot (cross-backend/cross-schema
        restore). A ticket the CURRENT backend rejects (e.g. embedding
        width changed) is dropped from the pool — loudly — rather than
        left registered-but-unmatchable."""
        ticket_at = self.store.ticket_at
        rejected: list[int] = []
        for s in self.store.live_slots():
            try:
                self.backend.on_add(ticket_at[s], int(s))
            except Exception as e:
                rejected.append(int(s))
                self.logger.warn(
                    "restored ticket rejected by backend; dropping",
                    ticket=ticket_at[s].ticket,
                    error=str(e),
                )
        if rejected:
            self.store.remove_slots(
                np.asarray(rejected, dtype=np.int32), defer_free=False
            )

    # -------------------------------------------------------------- helpers

    def _update_gauges(self):
        if self.metrics is not None:
            self.metrics.mm_tickets.set(len(self.store))
            self.metrics.mm_active_tickets.set(self.store.n_active)

    def __len__(self) -> int:
        return len(self.store)
