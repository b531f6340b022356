"""TpuBackend: the production matchmaker process path.

Per interval (the reference's LocalMatchmaker.Process hot loop re-framed,
SURVEY.md §2.5):

1. flush the tail of the queued ticket updates (bulk updates stream to the
   device eagerly in chunks as tickets are added — the H2D transfer rides
   the gap between intervals, not the interval),
2. score actives against the pool on device:
   - small pools: the exact blockwise top-K kernel (device.py),
   - large pools (>= config.big_pool_threshold columns): the two-stage MXU
     kernel (device2.py) — bucket-mask matmul prefilter + exact re-rank,
3. while the candidate lists transfer back asynchronously, run the CPU
   oracle for host-only actives (regex/wildcard queries, field overflow),
4. hand the candidate lists to the native C++ greedy assembler for exact
   sequential combo formation,
5. validate every formed match on host against exact (f64 / 63-bit hash)
   query mirrors — vectorized over all pairs at once — guarding the f32
   rounding and 31-bit hash collisions the device tensors admit; fully
   mutual validation when rev_precision is on.

Host-side per-slot metadata (counts, intervals, session hashes, exact query
mirrors) lives in persistent numpy arrays updated on add/remove, so an
interval never loops over the whole pool in Python.
"""

from __future__ import annotations

import threading
import time
from collections import deque

import jax
import numpy as np

from ..config import MatchmakerConfig
from ..logger import Logger
from ..metrics import Metrics
from .. import faults, native
from .. import tracing as trace_api
from ..devobs import DEVOBS
from ..faults import CLOSED, HALF_OPEN, STATE_CODE, CircuitBreaker, classify_exception
from ..parallel.mesh import POOL_AXIS, make_mesh, mesh_merge_fn, mesh_score_fn
from .compile import (
    FULL_HI,
    FULL_LO,
    SOP_ALL,
    SOP_NUM_RANGE,
    SOP_STR_EQ,
    SOP_UNUSED,
    CLAMP,
    CompiledQuery,
    FieldRegistry,
    HostOnlyQuery,
    compile_features,
    compile_query,
    exact_features,
    hash_str,
)
from .device import (
    FLAG_HAS_MUST,
    FLAG_HAS_SHOULD,
    FLAG_NEVER,
    FLAG_VALID,
    PoolBuffer,
    pad_to,
    topk_candidates,
)
from .device2 import MAX_COLS, stage2_shape_of, topk_candidates_big
from .local import ProcessBackend
from .process import _mutual, process_default
from .types import MatchBatch, MatchmakerTicket


_CQ_MISS = object()  # cache-miss sentinel (None is a valid cached value)

# assembler.cpp mirrors these should-clause opcodes; a drift here would
# silently corrupt in-assembly validation.
assert (SOP_UNUSED, SOP_ALL, SOP_NUM_RANGE, SOP_STR_EQ) == (0, 1, 2, 3)


def _program_refused(exc: BaseException) -> bool:
    """Did the compiler or the allocator refuse the PROGRAM (out of
    VMEM/HBM, a Mosaic lowering error) rather than the device having
    bad weather? Such a failure repeats on every retry, so it is logged
    with the kernel and its full shapes instead of only being counted
    on a breaker."""
    text = f"{type(exc).__name__}: {exc}"
    return any(
        mark in text
        for mark in ("RESOURCE_EXHAUSTED", "Mosaic", "LoweringException")
    )


def _pow2_blocks(blocks: int) -> int:
    """Smallest power of two >= blocks (>=1)."""
    return 1 << max(0, blocks - 1).bit_length()


class Cohort:
    """One dispatched cohort, from `_dispatch` to publish: the work the
    worker thread and the accept step hand over, and the cohort record
    of tracing.py — ids and `perf_counter` stamps at the stage
    boundaries, from which `row()` makes the delivery-ledger row."""

    __slots__ = (
        # ids: `seq` is never reused (the guard join's claim names the
        # head by it); the interval record with this `seq` dispatched
        # the cohort.
        "seq", "interval_seq", "variant", "actives",
        # the work: dispatched slots, the store generations at dispatch,
        # the worker and what it leaves (`cand`: the fetched candidate
        # lists, or `pairs`: the device pairing's own counts; `walk`:
        # the native assembler's WALK_COUNTERS sums; each kept until
        # `list_counts` has read it)
        "slots", "gen", "thread", "asm", "err", "cand", "pairs", "walk",
        "pool",
        # stamps, perf_counter seconds (wall twins for trace spans)
        "t_dispatch", "t_dispatch_wall", "t_window_wall", "deadline",
        "t_device_done", "t_fetched", "t_ready", "t_collect", "t_accept",
        "t_publish",
        # the worker thread's own CPU from t_fetched to t_ready
        "assemble_cpu_s",
        # what came of it
        "d2h_bytes", "matches", "envelopes", "matched_slots", "slipped",
        "status", "error_stage", "probe", "trace", "entry",
    )

    def __init__(self, seq, variant, slots, interval_sec):
        self.seq = seq
        self.interval_seq = None
        self.variant = variant
        self.actives = len(slots)
        self.slots = slots
        self.gen = None
        self.thread = None
        self.asm = None
        self.err = None
        self.cand = None
        self.pairs = None
        self.walk = None
        self.pool = 0  # tickets in the pool at dispatch
        self.t_dispatch = time.perf_counter()
        # Wall-clock twin of t_dispatch: ledger consumers (bench slip
        # gate, trace spans) attribute cohorts to dispatch windows
        # without reconstructing it from lag arithmetic.
        self.t_dispatch_wall = time.time()
        self.t_window_wall = None
        # Delivery deadline: the cohort must reach players before its
        # OWN interval ends. collect_ready preempts gap work for a
        # cohort nearing this stamp (local.py deadline guard).
        self.deadline = self.t_dispatch + max(1.0, float(interval_sec))
        self.t_device_done = self.t_fetched = self.t_ready = None
        self.t_collect = self.t_accept = self.t_publish = None
        self.assemble_cpu_s = None
        self.d2h_bytes = 0
        self.matches = self.envelopes = 0
        self.matched_slots = None
        self.slipped = False
        self.status = "ok"
        self.error_stage = None
        self.probe = False
        self.trace = None  # (trace_id, span_id) while its trace is held
        self.entry = None  # the stored ledger row, once recorded

    def ready(self) -> bool:
        """Has this cohort's device compute + D2H + gap-side assembly
        completed? The ready stamp (written before the completion
        signal fires) is authoritative: a collector woken BY the signal
        must see a ready head even though the worker thread is still
        unwinding its last microseconds; thread liveness is only the
        fallback for paths with no stamp."""
        return self.t_ready is not None or not self.thread.is_alive()

    def mine(self, store_gen) -> np.ndarray:
        """The dispatched slots whose in-flight claim is still THIS
        cohort's: a slot freed, reused and re-dispatched by a later
        cohort (gen changed) is not."""
        return self.slots[self.gen[self.slots] == store_gen[self.slots]]

    def lag(self, stamp) -> float | None:
        return None if stamp is None else stamp - self.t_dispatch

    def row(self) -> dict:
        """The delivery-ledger row: every stage an unrounded lag since
        dispatch (None where the cohort never got there), under the
        names the console, chip_smoke.py and the benchmark read."""
        cpu, offcpu = self.assemble_cpu_s, None
        if cpu is not None:
            # the assembly's wall less the worker's own CPU: it waited
            # for the GIL, was descheduled or blocked
            offcpu = self.t_ready - self.t_fetched - cpu
        row = dict(
            seq=self.seq,
            interval_seq=self.interval_seq,
            status=self.status,
            device_done_lag_s=self.lag(self.t_device_done),
            fetch_lag_s=self.lag(self.t_fetched),
            ready_lag_s=self.lag(self.t_ready),
            assemble_cpu_s=cpu,
            assemble_offcpu_s=offcpu,
            collect_lag_s=self.lag(self.t_collect),
            accept_lag_s=self.lag(self.t_accept),
            slipped=self.slipped,
            dispatched_ts=self.t_dispatch_wall,
            _pc_dispatch=self.t_dispatch,
            actives=self.actives,
            d2h_bytes=self.d2h_bytes,
            matches=self.matches,
            envelopes=self.envelopes, **stage2_shape_of(self.variant),
        )
        if self.error_stage is not None:
            row["error_stage"] = self.error_stage
        if self.trace is not None:
            # The row names its cohort trace, so a ticket trace closed
            # off this row can link to it.
            row["trace_id"] = self.trace[0]
        return row

    def published(self, now: float) -> float:
        """Stamp dispatch→published on the record and its stored row;
        returns the lag."""
        self.t_publish = now
        self.entry["publish_lag_s"] = lag = now - self.t_dispatch
        return lag

    def list_counts(self, count, max_count) -> dict:
        """What the query filter left of this cohort's candidate lists
        and what the assembler made of them, as row keys; `count` and
        `max_count` are the store's per-slot columns, which a removed
        ticket's slot keeps until the store drains. Lets go of the
        lists: one read."""
        n, offsets, flat, _ = self.asm
        flat = flat[: offsets[n]]
        # One spare cell: -1, "no candidate", lands there.
        seen = np.zeros(len(count) + 1, dtype=bool)
        seen[flat] = True
        entries = np.concatenate(([0], np.cumsum(count[flat])))
        sizes = entries[offsets[1 : n + 1]] - entries[offsets[:n]]
        searcher = flat[offsets[1 : n + 1] - 1]  # last slot of its match
        out = dict(
            actives_unmatched=self.actives - int(seen[self.slots].sum()),
            matches_below_max=int((sizes < max_count[searcher]).sum()),
        )
        cand, self.cand = self.cand, None
        if cand is not None:
            seen[:] = False
            seen[cand.ravel()] = True
            out.update(
                candidates_valid=int(np.count_nonzero(cand >= 0)),
                candidates_distinct=int(seen[:-1].sum()),
                candidates_pool=self.pool,
            )
        walk, self.walk = self.walk, None
        if walk is not None:
            # What validation did to the assembler's walk (the two
            # refusal sums are 0 unless the cohort ran under `rev`).
            out.update(zip(native.WALK_COUNTERS, walk.tolist()))
        pairs, self.pairs = self.pairs, None
        if pairs is not None:
            # The lists stayed on the device: what `pair_partners`
            # counted there, and what the host's exact re-check left.
            formed, listed, ran, a_pad = pairs
            total = int(formed.sum())
            out.update(
                pairs_formed=total,
                pairs_rejected=total - n,
                pair_rounds_formed=formed.tolist(),
                pairs_formed_last_round=int(formed[-1]),
                pair_rounds=len(formed),
                # rows each round ran its [rows, k] work at, against
                # what rounds over every row would have
                pair_round_rows=ran.tolist(),
                pair_rows_gathered=int(ran.sum()),
                pair_rows_dense=a_pad * len(ran),
                candidates_valid=int(listed),
                candidates_pool=self.pool,
            )
        return out


class TpuBackend(ProcessBackend):
    """ProcessBackend implementation running on the JAX default device."""

    def __init__(
        self,
        config: MatchmakerConfig,
        logger: Logger,
        metrics: Metrics | None = None,
        row_block: int = 256,
        col_block: int = 2048,
        big_row_block: int = 1024,
        big_col_block: int = 1024,
    ):
        self.config = config
        self.logger = logger.with_fields(subsystem="matchmaker.tpu")
        self.metrics = metrics
        cap = config.pool_capacity
        self.fn = config.numeric_fields
        self.fs = config.string_fields
        self.s = config.max_constraints
        self.k = config.candidates_per_ticket
        self.row_block = row_block
        self.col_block = min(col_block, cap)
        self.big_row_block = big_row_block
        self.big_col_block = min(big_col_block, cap)
        if cap % self.col_block or cap % self.big_col_block:
            raise ValueError("pool_capacity must be a multiple of col blocks")
        if cap > MAX_COLS and config.big_pool_threshold <= cap:
            raise ValueError(
                f"pool_capacity {cap} exceeds the big-kernel column limit "
                f"{MAX_COLS}; shard the pool or raise big_pool_threshold "
                f"above the capacity to stay on the exact kernel"
            )

        self.d = config.embedding_dims
        self.registry = FieldRegistry(self.fn, self.fs)

        # Multi-device: shard the pool's slot axis over a mesh; dispatch
        # runs the blockwise kernel per shard and merges over ICI
        # (SURVEY §2.8; parallel/mesh.py). Opt-in via config.mesh_devices
        # (0 off, -1 every visible device, n).
        self.mesh = None
        mesh_n = config.mesh_devices
        if mesh_n:
            n_dev = len(jax.devices()) if mesh_n < 0 else mesh_n
            if len(jax.devices()) < n_dev:
                raise ValueError(
                    f"mesh_devices={n_dev} but only "
                    f"{len(jax.devices())} devices visible"
                )
            if cap % n_dev or (cap // n_dev) % self.col_block:
                raise ValueError(
                    "pool_capacity must split into col_block-sized shards "
                    f"across {n_dev} devices"
                )
            if (
                config.big_pool_threshold <= cap
                and (cap // n_dev) % self.big_col_block
            ):
                raise ValueError(
                    "pool_capacity must split into big_col_block-sized "
                    f"shards across {n_dev} devices for the sharded MXU "
                    "kernel (or raise big_pool_threshold above capacity)"
                )
            self.mesh = make_mesh(n_dev)

        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            sharding = NamedSharding(self.mesh, PartitionSpec(POOL_AXIS))
        self.pool = PoolBuffer(
            cap, self.fn, self.fs, self.s, self.d,
            on_flush=self._observe_chunk,
            sharding=sharding,
        )
        # Pallas kernels lower through Mosaic on a TPU and run in
        # interpret mode anywhere else (the CPU tests construct this
        # backend directly). Which of the two is live is logged here and
        # shown on /v2/console/device: an interpreting backend must never
        # pass for the chip. A config that ASKS for the chip
        # (backend="tpu") is refused off-TPU in local._select_backend.
        device0 = jax.devices()[0]
        self._interpret = device0.platform != "tpu"
        self._where = dict(
            platform=device0.platform,
            kind=device0.device_kind,
            devices=len(jax.devices()),
            pallas_interpret=self._interpret,
        )
        self.logger.info("matchmaker device backend", **self._where)
        self._gather_rows = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            replicated = NamedSharding(self.mesh, PartitionSpec())
            self._gather_rows = jax.jit(
                lambda pool, safe: {
                    key: v[safe] for key, v in pool.items()
                },
                out_shardings=replicated,
            )

        # Host-side per-slot metadata (SlotStore.meta) is bound at
        # attach(); the assembler and the collect re-sort read it there.
        self.store = None
        self.meta = None
        # Exact query/value mirrors for vectorized match validation.
        s = self.s
        self.exact = {
            "v_num": np.full((cap, self.fn), np.nan),
            "v_str": np.zeros((cap, self.fs), dtype=np.int64),
            "q_lo": np.full((cap, self.fn), -np.inf),
            "q_hi": np.full((cap, self.fn), np.inf),
            "q_flo": np.ones((cap, self.fn)),
            "q_fhi": np.full((cap, self.fn), -1.0),
            "q_req": np.zeros((cap, self.fs), dtype=np.int64),
            "q_forb": np.zeros((cap, self.fs), dtype=np.int64),
            "q_sh_op": np.zeros((cap, s), dtype=np.int32),
            "q_sh_fld": np.zeros((cap, s), dtype=np.int32),
            "q_sh_lo": np.zeros((cap, s)),
            "q_sh_hi": np.zeros((cap, s)),
            "q_sh_term": np.zeros((cap, s), dtype=np.int64),
            "q_has_must": np.zeros(cap, dtype=bool),
            "q_has_should": np.zeros(cap, dtype=bool),
            "q_exact_ok": np.zeros(cap, dtype=bool),
        }
        # Per-slot masks replace the round-2 id-keyed sets: interval-path
        # updates are O(batch) numpy instead of per-entry set churn.
        # host_only keeps a small id-set view for observability/tests —
        # host-only tickets are few by design (budgeted, config).
        self.host_only_mask = np.zeros(cap, dtype=bool)
        self.host_only: set[str] = set()
        self._should_mask = np.zeros(cap, dtype=bool)
        self._should_count = 0
        self._emb_mask = np.zeros(cap, dtype=bool)
        self._emb_count = 0
        # Pure-pairs pool tracking (the `_use_pairs` gate): a ticket is
        # "pair-shaped" iff solo 1v1 (min==max==2, one presence,
        # count_multiple 1|2). The synchronous interval path can then run
        # grouping on device (device2.pair_partners).
        self._nonpair_mask = np.zeros(cap, dtype=bool)
        self._nonpair_count = 0
        # Per-process scratch: slots already claimed by an accepted match
        # this interval (reset each process_slots call).
        self._sel_mask = np.zeros(cap, dtype=bool)
        # Monotone lower bound on live created_seq: keeps the kernel's
        # wait-time tie-break penalty small on long-lived servers.
        self._created_base = 0
        # Pipelined-interval state: dispatched-but-uncollected work, oldest
        # first. Collection drains only READY results (device + transfer
        # complete), so process() never blocks on the device; backpressure
        # caps outstanding cohorts. Covered slots must not be
        # re-dispatched meanwhile (mask cleared on collection and on slot
        # reuse by a new add).
        self._pipeline_queue: deque = deque()
        # Recorded cohorts whose candidate lists nobody has counted yet
        # (`count_cohorts`, the interval loop's idle gap). Bounded: where
        # no loop sweeps, the oldest fall out uncounted.
        self._uncounted: deque = deque(maxlen=8)
        self._in_flight_mask = np.zeros(cap, dtype=bool)
        # Row-bucket shapes already compiled (or prewarmed) this process.
        self._warmed_buckets: set[tuple] = set()
        # Live prewarm threads: joined at wait_idle/shutdown — a daemon
        # thread cancelled mid-XLA-compile at interpreter teardown
        # aborts the process ("FATAL: exception not rethrown").
        self._warm_threads: list[threading.Thread] = []
        self.prewarm_failures = 0
        # Insertion-ordered slot ring: adds append here, so the ring IS
        # the (created_at, created_seq) dispatch order — the per-dispatch
        # lexsort over ~100k actives measured 8.7ms/interval. Entries of
        # reused slots are invalidated on re-add; a non-monotone
        # created_at (clock step, cross-node insert()) flags the ring
        # unsorted and dispatch falls back to the exact lexsort until the
        # next compaction re-sorts it.
        self._ring = np.empty(2 * cap, dtype=np.int32)
        self._ring_valid = np.zeros(2 * cap, dtype=bool)
        self._ring_pos = np.full(cap, -1, dtype=np.int64)
        self._ring_n = 0
        self._ring_last_created = np.iinfo(np.int64).min
        self._ring_unsorted = False
        self._dev_mask_scratch = np.zeros(cap, dtype=bool)
        # query string -> CompiledQuery | None (None = host-only).
        self._cq_cache: dict[str, CompiledQuery | None] = {}
        # Observed numeric value range per field (bucket grid for the MXU
        # kernel); stale-wide ranges only cost precision, never correctness.
        self._grid_lo = np.full(self.fn, np.inf)
        self._grid_hi = np.full(self.fn, -np.inf)
        # Degradation ladder (faults.py): consecutive transient device
        # failures (dispatch or collect; fatal errors immediately) open
        # this breaker and intervals route every active through the
        # bounded host-oracle fallback until a half-open probe closes it.
        self.breaker = CircuitBreaker(
            threshold=config.breaker_threshold,
            cooldown_s=config.breaker_cooldown_ms / 1000.0,
            on_transition=self._on_breaker_transition,
        )
        # Mesh rung of the ladder: when the SHARDED dispatch fails, this
        # breaker routes intervals through the single-device body (the
        # oracle path — same kernels, no shard_map) instead of wedging;
        # the main breaker below it still guards device work as a whole,
        # so a dead device degrades mesh → single-device → host oracle.
        self.mesh_breaker = CircuitBreaker(
            threshold=config.breaker_threshold,
            cooldown_s=config.breaker_cooldown_ms / 1000.0,
            on_transition=self._on_mesh_breaker_transition,
        )
        # ICI gather accounting for the sharded merge (console + gauge).
        self.mesh_gather_bytes = 0  # last dispatch's gathered bytes
        self.mesh_gather_bytes_total = 0
        self.inflight_reclaimed = 0  # ledger total (tests/console)
        self._sweep_tick = 0  # gates the O(capacity) orphan scan
        # Cohort-completion signal (event-driven delivery): called from
        # the cohort's worker thread the moment its device pass + gap
        # assembly finish (success OR failure), so the delivery stage
        # wakes immediately instead of a gap poll discovering the result
        # seconds later. None = nobody listening (tests, sync mode).
        self._ready_cb = None
        # Monotonic per-dispatch sequence (`Cohort.seq`): the head's
        # identity. id() of the cohort is NOT usable — CPython reuses a
        # freed object's address for the next cohort's, which would
        # make a new head look already-guard-joined.
        self._dispatch_counter = 0
        # The deadline guard (local.py delivery stage): the margin
        # before a cohort's delivery deadline at which its assembly is
        # block-joined, and the `seq` of the head that already had its
        # one guard join — a head joined and found unfinished is
        # wedged, the reclaim path's, never re-joined into the next
        # cycle.
        self._guard = max(0.1, float(config.pipeline_deadline_guard_sec))
        self._guard_joined = 0
        # Kernel + full shapes of the dispatch being launched: copied
        # onto the cohort (→ the interval breadcrumb's `kernel`) and
        # named by the ERROR a refused program logs.
        self._dispatching: dict = {}
        # Pipelined cohorts accepted by the CURRENT process/collect
        # call, oldest first, each with its stored ledger row (`entry`)
        # and matched slots: local.py stamps their publish and the
        # delivery call on them, and the ticket-trace closer attributes
        # each matched ticket to ITS cohort's stage chain when one call
        # collects several. Transient — replaced every call, never
        # retained past it.
        self.accepted_cohorts: list[Cohort] = []
        # Device telemetry plane: the named jit entry points this
        # backend drives. Registration installs the process-wide
        # compile-watch listener (jax is imported by now), so every
        # XLA compile from here on is attributed and counted.
        kernels = [
            "matchmaker.scatter",
            "matchmaker.score",
            "matchmaker.assign",
            "matchmaker.fetch",
        ]
        if self.mesh is not None:
            # The sharded interval splits scoring into two named entry
            # points so compile-watch attributes per-shard scan vs
            # gather+merge separately.
            kernels += ["matchmaker.shard_score", "matchmaker.gather_merge"]
        for kernel in kernels:
            DEVOBS.register(kernel)
        if self.metrics is not None and self.mesh is not None:
            n_dev = self.mesh.shape[POOL_AXIS]
            self.metrics.mesh_devices.set(n_dev)
            for d in self.mesh.devices.flat:
                self.metrics.mesh_shard_slots.labels(
                    device=str(d.id)
                ).set(cap // n_dev)

    def describe(self) -> dict:
        """Where the kernels really run (console /v2/console/device)."""
        return dict(
            self._where,
            breaker=self.breaker.state,
            mesh_breaker=self.mesh_breaker.state,
        )

    def device_path_faults(self) -> list[str]:
        """Every way this backend has left the device path since it was
        built: breaker or mesh-breaker failures and transitions, and row
        buckets that failed to prewarm. The ladder keeps players matched
        through all of these, so a measurement or a bring-up must ask:
        `chip_smoke.py` and `bench.py` fail unless this is empty."""
        out = []
        for name, b in (
            ("breaker", self.breaker), ("mesh_breaker", self.mesh_breaker)
        ):
            if b.failures or b.opens or b.state != CLOSED:
                out.append(
                    f"{name}: state={b.state} failures={b.failures}"
                    f" opens={b.opens}"
                )
        if self.prewarm_failures:
            out.append(f"prewarm_failures={self.prewarm_failures}")
        return out

    def attach(self, store, tracing):
        """Bind the LocalMatchmaker's SlotStore — one slot space shared
        by host metadata, reverse maps, and device rows — and its
        interval record."""
        super().attach(store, tracing)
        self.meta = store.meta
        self.pool.store = store

    def annotate(self, name: str):
        return trace_api.annotate(name)

    # -------------------------------------------------- pool notifications

    def _observe_chunk(self, stacked: dict[str, np.ndarray]):
        valid = (stacked["flags"] & FLAG_VALID) != 0
        num = stacked["num"][valid]
        if not len(num):
            return
        real = num < CLAMP  # excludes the MISSING sentinel
        masked_lo = np.where(real, num, np.inf).min(axis=0)
        masked_hi = np.where(real, num, -np.inf).max(axis=0)
        np.minimum(self._grid_lo, masked_lo, out=self._grid_lo)
        np.maximum(self._grid_hi, masked_hi, out=self._grid_hi)

    def on_add(self, ticket: MatchmakerTicket, slot: int, pool_id: int = 0):
        # Validate and compile everything BEFORE mutating any backend state,
        # so a rejected add (bad embedding) leaves the backend exactly as it
        # was (the caller rolls back its SlotStore registration on raise).
        emb = np.zeros(self.d, dtype=np.float32)
        if ticket.embedding is not None:
            e = np.asarray(ticket.embedding, dtype=np.float32)
            if e.shape != (self.d,):
                raise ValueError(f"embedding shape {e.shape} != ({self.d},)")
            emb = e

        num, strs, overflow = compile_features(ticket, self.registry)
        host_only = overflow
        cq: CompiledQuery | None = None
        if not host_only:
            # Compiled queries are pure functions of (query string,
            # registry field assignments, constraint budget); the registry
            # only ever appends, so earlier compiles stay valid. Production
            # pools repeat a small set of canonical queries — one compile,
            # then dict hits. CompiledQuery arrays are treated read-only by
            # every consumer (row staging stacks copies; exact mirrors
            # assign by slice copy).
            hit = self._cq_cache.get(ticket.query, _CQ_MISS)
            if hit is not _CQ_MISS:
                cq = hit
                if cq is None:
                    host_only = True
            else:
                try:
                    cq = compile_query(ticket, self.registry, self.s)
                except HostOnlyQuery as e:
                    self.logger.debug(
                        "host-only query",
                        ticket=ticket.ticket,
                        reason=str(e),
                    )
                    cq = None
                if len(self._cq_cache) >= 8192:
                    self._cq_cache.clear()
                self._cq_cache[ticket.query] = cq
                if cq is None:
                    host_only = True

        flags = FLAG_VALID
        if cq is not None:
            if cq.has_must:
                flags |= FLAG_HAS_MUST
            if cq.has_should:
                flags |= FLAG_HAS_SHOULD
            if cq.never:
                flags |= FLAG_NEVER

        fn, fs, s = self.fn, self.fs, self.s
        row = {
            "emb": emb,
            "num": num,
            "str": strs,
            # Host-only queries store accept-all constraints so the reverse
            # (mutual) direction treats them as accepting; the host
            # post-validation applies their real query.
            "n_lo": cq.n_lo if cq else np.full(fn, FULL_LO, np.float32),
            "n_hi": cq.n_hi if cq else np.full(fn, FULL_HI, np.float32),
            "n_flo": cq.n_flo if cq else np.ones(fn, np.float32),
            "n_fhi": cq.n_fhi if cq else np.full(fn, -1.0, np.float32),
            "s_req": cq.s_req if cq else np.zeros(fs, np.int32),
            "s_forb": cq.s_forb if cq else np.zeros(fs, np.int32),
            "sh_op": cq.sh_op if cq else np.zeros(s, np.int32),
            "sh_fld": cq.sh_fld if cq else np.zeros(s, np.int32),
            "sh_lo": cq.sh_lo if cq else np.zeros(s, np.float32),
            "sh_hi": cq.sh_hi if cq else np.zeros(s, np.float32),
            "sh_term": cq.sh_term if cq else np.zeros(s, np.int32),
            "sh_boost": cq.sh_boost if cq else np.zeros(s, np.float32),
            "min_count": np.int32(ticket.min_count),
            "max_count": np.int32(ticket.max_count),
            "party": np.int32(
                hash_str(ticket.party_id) if ticket.party_id else 0
            ),
            "pool_id": np.int32(pool_id),
            "created": np.int32(ticket.created_seq),
            "flags": np.int32(flags),
        }
        self.pool.add(slot, row)
        if len(self.store) == 1:
            self._created_base = ticket.created_seq
        self._ring_append(slot)
        self._in_flight_mask[slot] = False  # slot reuse: new ticket
        self.host_only_mask[slot] = host_only
        if host_only:
            self.host_only.add(ticket.ticket)
            # The host fallback is O(actives x pool) Python — fine for a
            # handful of exotic queries, catastrophic if schema overflow
            # sends the whole pool here. Make that loud.
            n = len(self.host_only)
            if n in (100, 1000, 10_000):
                self.logger.warn(
                    "host-only matchmaker tickets piling up — check "
                    "numeric_fields/string_fields/max_constraints sizing "
                    "(3 numeric + 2 string slots are builtin)",
                    count=n,
                )
        has_should = cq is not None and cq.has_should
        self._should_mask[slot] = has_should
        self._should_count += has_should
        has_emb = ticket.embedding is not None
        self._emb_mask[slot] = has_emb
        self._emb_count += has_emb
        nonpair = not (
            ticket.min_count == 2
            and ticket.max_count == 2
            and ticket.count == 1
            and ticket.count_multiple in (1, 2)
        )
        self._nonpair_mask[slot] = nonpair
        self._nonpair_count += nonpair

        ex = self.exact
        num64, str64 = exact_features(ticket, self.registry)
        ex["v_num"][slot] = num64
        ex["v_str"][slot] = str64
        if cq is not None:
            # Pure query bounds only: count-range compatibility is a
            # candidate-search filter (one-directional) plus the assembler's
            # formed-size crosscheck, NOT part of mutual query acceptance.
            ex["q_lo"][slot] = cq.n_lo64
            ex["q_hi"][slot] = cq.n_hi64
            ex["q_flo"][slot] = cq.n_flo64
            ex["q_fhi"][slot] = cq.n_fhi64
            ex["q_req"][slot] = cq.s_req64
            ex["q_forb"][slot] = cq.s_forb64
            ex["q_sh_op"][slot] = cq.sh_op
            ex["q_sh_fld"][slot] = cq.sh_fld
            ex["q_sh_lo"][slot] = cq.sh_lo64
            ex["q_sh_hi"][slot] = cq.sh_hi64
            ex["q_sh_term"][slot] = cq.sh_term64
            ex["q_has_must"][slot] = cq.has_must
            ex["q_has_should"][slot] = cq.has_should
            ex["q_exact_ok"][slot] = True
        else:
            ex["q_exact_ok"][slot] = False

    def on_remove_slots(self, slots: np.ndarray):
        """Bulk removal by slot array — called by LocalMatchmaker BEFORE
        the SlotStore clears `ticket_at`, so id-set views can resolve.
        All mask maintenance is O(batch) numpy; the only per-item Python
        is over host-only slots (few by design)."""
        if len(slots) == 0:
            return
        slots = np.asarray(slots, dtype=np.int32)
        self.pool.remove_slots(slots)
        hm = self.host_only_mask[slots]
        if hm.any():
            ticket_at = self.store.ticket_at
            for s in slots[hm]:
                t = ticket_at[s]
                if t is not None:
                    self.host_only.discard(t.ticket)
            self.host_only_mask[slots] = False
        self._should_count -= int(self._should_mask[slots].sum())
        self._should_mask[slots] = False
        self._emb_count -= int(self._emb_mask[slots].sum())
        self._emb_mask[slots] = False
        self._nonpair_count -= int(self._nonpair_mask[slots].sum())
        self._nonpair_mask[slots] = False
        self._in_flight_mask[slots] = False

    def in_flight(self, slot: int) -> bool:
        return bool(self._in_flight_mask[slot])

    # ------------------------------------------------- degradation ladder

    def _on_breaker_transition(self, old: str, new: str, reason: str):
        if self.metrics is not None:
            self.metrics.mm_backend_state.set(STATE_CODE[new])
        self.tracing.record_breaker(
            kind="matchmaker_backend", old=old, new=new, reason=reason
        )
        log = self.logger.warn if new == "open" else self.logger.info
        log(
            "matchmaker backend breaker transition",
            old=old,
            new=new,
            reason=reason,
            cooldown_s=round(self.breaker.cooldown_s, 3),
        )

    def _note_backend_failure(
        self, stage: str, exc: Exception, crumb: dict, probe: bool = True,
        variant: dict | None = None,
    ):
        """Classify + record one device-path failure (dispatch or
        collect). Transient failures count toward the breaker threshold;
        a fatal one (programming error) opens it immediately — retrying
        a deterministic bug N more intervals can't succeed.

        `probe=False` marks a failure that is NOT the half-open probe's
        answer (a stale pre-outage cohort draining late): while a probe
        is being judged, such a failure is logged and counted but must
        not be booked as the probe failing — the probe's own outcome
        decides the breaker."""
        kind = classify_exception(exc)
        if probe or self.breaker.state != HALF_OPEN:
            self.breaker.record_failure(fatal=(kind == "fatal"))
        # The failure (and the breaker state it drove — read AFTER
        # record_failure so the transition-causing failure reports the
        # post-transition state, matching the log line) lands on the
        # active trace span too: an injected `device.dispatch` fault
        # yields a tail-kept error trace carrying its breaker event
        # inline, not just a metrics bump to correlate by timestamp.
        trace_api.add_event(
            "breaker",
            stage=stage,
            kind=kind,
            error=str(exc),
            state=self.breaker.state,
        )
        key = f"{stage}_failed"
        crumb[key] = crumb.get(key, 0) + 1
        if self.metrics is not None:
            self.metrics.mm_backend_failures.labels(
                stage=stage, kind=kind
            ).inc()
        log = self.logger.error if kind == "fatal" else self.logger.warn
        log(
            "device backend failure",
            stage=stage,
            kind=kind,
            error=str(exc),
            breaker=self.breaker.state,
        )
        self._log_if_refused(stage, exc, variant)

    def _log_if_refused(
        self, stage: str, exc: Exception, variant: dict | None = None
    ):
        """`variant` names the failed program where it is not the one
        being dispatched: a cohort collected late fails as ITS dispatch."""
        if _program_refused(exc):
            self.logger.error(
                "device program refused by the compiler or allocator",
                stage=stage,
                error=str(exc),
                **(self._dispatching if variant is None else variant),
            )

    def _on_mesh_breaker_transition(self, old: str, new: str, reason: str):
        self.tracing.record_breaker(
            kind="matchmaker_mesh", old=old, new=new, reason=reason
        )
        log = self.logger.warn if new == "open" else self.logger.info
        log(
            "matchmaker mesh breaker transition",
            old=old,
            new=new,
            reason=reason,
            cooldown_s=round(self.mesh_breaker.cooldown_s, 3),
        )

    def _note_mesh_failure(self, stage: str, exc: Exception):
        """One sharded-dispatch failure: count it on the MESH breaker
        only — the interval immediately retries on the single-device
        body, so the main breaker (whose open routes to the host
        oracle) judges that retry's outcome, not this one's."""
        kind = classify_exception(exc)
        self.mesh_breaker.record_failure(fatal=(kind == "fatal"))
        trace_api.add_event(
            "breaker",
            stage=f"mesh_{stage}",
            kind=kind,
            error=str(exc),
            state=self.mesh_breaker.state,
        )
        if self.metrics is not None:
            self.metrics.mm_backend_failures.labels(
                stage=f"mesh_{stage}", kind=kind
            ).inc()
        log = self.logger.error if kind == "fatal" else self.logger.warn
        log(
            "mesh dispatch failure, degrading to single-device",
            stage=stage,
            kind=kind,
            error=str(exc),
            breaker=self.mesh_breaker.state,
        )
        self._log_if_refused(f"mesh_{stage}", exc)

    def _reclaim_inflight(self, slots: np.ndarray, why: str) -> int:
        """Release in-flight claims for `slots` (still-current gen only
        is the caller's concern) and re-activate the live ones so they
        are matchable next interval. Returns the number reclaimed."""
        if not len(slots):
            return 0
        live = slots[self.store.alive[slots]].astype(np.int32)
        self.store.reactivate(live)
        n = len(live)
        if n:
            self.inflight_reclaimed += n
            if self.metrics is not None:
                self.metrics.mm_inflight_reclaimed.inc(n)
            self.tracing.record_breaker(
                kind="inflight_reclaim", slots=n, why=why
            )
        return n

    def _reclaim_stale(self):
        """Backstop sweep, run once per process_slots call: (1) abandon
        queued cohorts still unfinished `inflight_reclaim_deadline_ms`
        PAST their delivery deadline (a wedged fetch/assembly thread —
        its eventual results are dropped with the queue entry) and free
        their slots; (2) clear in-flight bits not covered by ANY queued
        cohort (the belt-and-braces orphan case no known code path
        produces). Either way no ticket is ever stranded un-matchable
        behind a claim nobody will release."""
        grace = self.config.inflight_reclaim_deadline_ms / 1000.0
        now = time.perf_counter()
        abandoned = False
        while self._pipeline_queue:
            head = self._pipeline_queue[0]
            dl = head.deadline
            if head.ready() or now <= dl + grace:
                break
            self._pipeline_queue.popleft()
            abandoned = True
            mine = head.mine(self.store.gen)
            self._in_flight_mask[mine] = False
            n = self._reclaim_inflight(mine, "wedged cohort abandoned")
            if head.probe:
                # The abandoned cohort WAS the half-open probe: book its
                # wedge as the probe's failure, or the breaker waits
                # half-open forever for an answer that can never come.
                self.breaker.record_failure()
            self._lose_cohort(
                head, "abandoned",
                f"wedged cohort abandoned {round(now - dl, 1)}s"
                " past deadline",
            )
            self.logger.warn(
                "abandoned wedged pipelined cohort",
                overdue_s=round(now - dl, 1),
                slots_reclaimed=n,
            )
        # The orphan scan costs O(capacity); in steady pipelined state
        # in-flight bits are always set, so gate it to the one event
        # that can orphan bits (a cohort abandoned above) plus a sparse
        # belt-and-braces cadence for the unknown-path case.
        self._sweep_tick += 1
        if not (abandoned or self._sweep_tick % 64 == 0):
            return
        if not self._in_flight_mask.any():
            return
        if self._pipeline_queue:
            covered = np.zeros(self.pool.capacity, dtype=bool)
            for w in self._pipeline_queue:
                covered[w.slots] = True
            orphan = self._in_flight_mask & ~covered
        else:
            orphan = self._in_flight_mask.copy()
        if orphan.any():
            slots = np.nonzero(orphan)[0].astype(np.int32)
            self._in_flight_mask[orphan] = False
            self._reclaim_inflight(slots, "orphaned in-flight claim")

    # -------------------------------------------------------------- process

    def process_slots(
        self,
        active_slots: np.ndarray,  # i32 [A], interval-bumped by the caller
        last_interval: np.ndarray,  # bool [A]
        *,
        max_intervals: int,
        rev_precision: bool,
    ) -> tuple[MatchBatch, np.ndarray, np.ndarray]:
        """One interval, fully columnar: returns (batch, matched_slots,
        reactivate_slots). The caller (LocalMatchmaker) owns interval
        bumping, expiry deactivation, and store removal of matched_slots.

        No step here is O(entries) Python — that per-entry host
        bookkeeping measured ~1.5s/interval at ~100k matched entries in
        round 2 and was the north-star latency floor."""
        pipelined = self.config.interval_pipelining
        # Device telemetry: one warmup tick per interval — after
        # config.devobs.warmup_intervals of these, a hot-path compile
        # is an unexpected recompile (WARN + counter + span event).
        DEVOBS.interval_tick()
        # Backstop reclamation first: wedged/orphaned in-flight claims
        # must release BEFORE this interval filters its dispatch by the
        # in-flight mask, or a stranded slot stays invisible forever.
        self._reclaim_stale()
        # Degradation ladder: an OPEN breaker routes EVERY active
        # through the bounded host-oracle fallback (the same path
        # host-only queries already take; host_budget_per_interval still
        # caps it, overflow defers oldest-first). A half-open probe lets
        # one dispatch through to test the device path.
        device_allowed = self.breaker.allow()
        probe_pending = device_allowed and self.breaker.state == HALF_OPEN
        # Per-interval observability breadcrumb (SURVEY §5: device timing
        # breadcrumbs; the round-1 perf hole was diagnosed blind without
        # these).
        if device_allowed:
            host_sel = self.host_only_mask[active_slots]
        else:
            host_sel = np.ones(len(active_slots), dtype=bool)
        n_host = int(host_sel.sum())
        crumb = self.tracing.open_crumb(
            actives=len(active_slots), host_actives=n_host, cohort_seq=None
        )
        if self.breaker.state != CLOSED:
            crumb["backend_state"] = self.breaker.state
        span = self.tracing.span
        deferred_slots = None
        if n_host:
            host_slots = active_slots[host_sel]
            device_slots = active_slots[~host_sel]
            device_last = last_interval[~host_sel]
            budget = self.config.host_budget_per_interval
            if budget > 0 and n_host > budget:
                # Cap the O(actives x pool) oracle fallback per interval:
                # oldest tickets go first, the rest wait for the next
                # interval (they stay active; only their matching is
                # deferred, never dropped).
                order = np.argsort(
                    self.meta["created"][host_slots], kind="stable"
                )
                deferred_slots = host_slots[order[budget:]]
                host_slots = host_slots[order[:budget]]
                deferred = n_host - budget
                crumb["host_deferred"] = deferred
                if self.metrics is not None:
                    self.metrics.counter_add(
                        "matchmaker_host_only_deferred", deferred
                    )
                self.logger.warn(
                    "host-only fallback over budget; deferring",
                    budget=budget,
                    deferred=deferred,
                )
        else:
            host_slots = None
            device_slots = active_slots
            device_last = last_interval
        # Only work queued BEFORE this call may be collected this call:
        # this interval's own dispatch always gets at least one interval
        # of overlap (and tests rely on the deterministic lag).
        collectable = len(self._pipeline_queue)

        if pipelined and self._pipeline_queue:
            # A slot already dispatched and awaiting collection must not
            # be dispatched again: its first result would mark it matched
            # and the duplicate's matches all drop as stale — pure wasted
            # device work that was measured doubling the interval time.
            ff = ~self._in_flight_mask[device_slots]
            device_slots = device_slots[ff]
            device_last = device_last[ff]

        # react_parts: slots whose assembled match was dropped after
        # they may already have gone inactive (pipelined collection lags
        # dispatch by one interval) get another active interval.
        # Budget-deferred host-only slots likewise — the caller's expiry
        # pass deactivates min==max actives after ONE processing
        # attempt, and a deferred slot hasn't had its attempt yet.
        # Failed dispatch/collect slots ride the same channel
        # (degradation ladder: no ticket strands).
        parts = self._open_batch()
        sel, flat_parts, size_parts, react_parts = parts
        if deferred_slots is not None and len(deferred_slots):
            react_parts.append(deferred_slots.astype(np.int32))

        work = None
        probe_used = False
        if len(device_slots):
            # Oldest-first fairness for the greedy assembler: primary
            # created_at ns, tie created_seq — normally free via the
            # insertion-ordered ring, exact lexsort as fallback.
            device_slots, device_last = self._order_dispatch(
                device_slots, device_last
            )
            pending = None
            # Device-timeline window opens BEFORE the flush: the
            # cohort's ledger entry slices the kernel-event timeline
            # from here, so its scatter phase reads off the record too.
            t_window_wall = time.time()
            # Each dispatched cohort gets its own trace: root span over
            # flush+dispatch, held open until accept/abandon closes it
            # with the stage spans. A dispatch failure makes it an
            # error trace (tail-kept) carrying the breaker event.
            with trace_api.root_span(
                "matchmaker.cohort", actives=int(len(device_slots))
            ) as troot:
                try:
                    with span(crumb, "flush_s", "mm.flush"):
                        self.pool.flush()
                    with span(crumb, "dispatch_s", "mm.dispatch"):
                        pending = self._dispatch(
                            device_slots, device_last, rev_precision
                        )
                except Exception as e:
                    # A dispatch that dies — whether before or after any
                    # partial bookkeeping — must strand nothing: no in-flight
                    # claim survives (none was taken yet: claims are only
                    # written below, after _dispatch returned), no cohort is
                    # queued, and the slots stay matchable next interval (the
                    # caller's expiry pass already deactivated min==max
                    # actives, so they re-activate via react_parts).
                    if troot is not None:
                        troot.set_status(
                            "error", f"{type(e).__name__}: {e}"
                        )
                    self._note_backend_failure("dispatch", e, crumb)
                    react_parts.append(device_slots.astype(np.int32))
                else:
                    crumb["kernel"] = pending.variant
                    crumb["cohort_seq"] = pending.seq
                    pending.interval_seq = crumb["seq"]
                    pending.t_window_wall = t_window_wall
                    if probe_pending:
                        # Tag the half-open probe cohort: only ITS successful
                        # collection may close the breaker (_accept_work) — a
                        # pre-outage cohort draining late must not.
                        pending.probe = True
                        probe_used = True
                    if troot is not None:
                        # Keep the cohort trace open for the stage spans
                        # the accept path appends (ready/collect/accept);
                        # released there, or by the reclaim path.
                        trace_api.TRACES.hold(troot.trace_id)
                        pending.trace = (troot.trace_id, troot.span_id)
                    pending.gen = (
                        self.store.gen.copy() if pipelined else self.store.gen
                    )
                    work = pending
                    if pipelined:
                        # Queue it; collection below drains only completed
                        # results, so the dispatch computes + transfers while
                        # the server does everything else (ticket properties
                        # are immutable, so its candidates cannot go stale —
                        # only dead slots, masked at collection).
                        self._in_flight_mask[device_slots] = True
                        self._pipeline_queue.append(work)
                        work = None
        if probe_pending and not probe_used:
            # The probe was granted but no dispatch launched (no device
            # slots, or the dispatch itself failed — the failure already
            # re-opened the breaker): hand the slot back so the next
            # interval can probe.
            self.breaker.release_probe()

        ready_works: list[Cohort] = []
        if work is not None:
            ready_works.append(work)
        if pipelined:
            # Oldest-first; stop at the first still-in-flight result to
            # keep collection ordered. Length > 2 forces a blocking drain
            # (backpressure) so a slow device can't grow the queue without
            # bound. An overdue-but-unfinished head is NOT force-popped
            # here: process() runs on the event loop, and _collect's
            # unbounded thread join would freeze the whole server behind
            # a wedged fetch — the interval loop's deadline guard
            # (bounded join_head in a worker thread, local.py) is the
            # delivery path for overdue heads.
            while collectable > 0 and (
                self._pipeline_queue[0].ready()
                or len(self._pipeline_queue) > 2
            ):
                ready_works.append(self._pipeline_queue.popleft())
                collectable -= 1
            if (
                collectable > 0
                and not ready_works
                and not len(device_slots)
                and host_slots is None
            ):
                # Every remaining active is in-flight and nothing came
                # back yet: this interval has NOTHING else to do, so
                # block-drain the head (collection joins its fetch).
                # Without this, back-to-back process() calls (tests, a
                # zero-gap cadence) can starve the fetch thread forever
                # while its slots stay in-flight — livelock. (With host
                # work this interval — including breaker-open degraded
                # intervals, where every active routes host-side — the
                # interval is NOT empty-handed, and a blocking join on a
                # possibly-wedged cohort thread would stall delivery;
                # mid-gap collection and the reclamation sweep own those
                # cohorts instead.)
                ready_works.append(self._pipeline_queue.popleft())

        if host_slots is not None:
            # Runs while the device computes and the candidate lists
            # stream back. Object path: sync ticket-object intervals from
            # the authoritative arrays first (the oracle's "let them wait"
            # rule reads hit.intervals) — O(pool), paid only when exotic
            # host-only queries exist.
            with span(crumb, "host_s", "mm.host"):
                host_actives, _, pool_view = self.store.oracle_view(
                    host_slots
                )
                host_matched, _ = process_default(
                    host_actives,
                    pool_view,
                    max_intervals=max_intervals,
                    rev_precision=rev_precision,
                    bump_intervals=False,
                )
                for entry_set in host_matched:
                    uniq = list(
                        dict.fromkeys(e.ticket for e in entry_set)
                    )
                    slots_m = np.asarray(
                        [self.store.slot_by_id(t) for t in uniq],
                        dtype=np.int32,
                    )
                    flat_parts.append(slots_m)
                    size_parts.append(
                        np.asarray([len(slots_m)], dtype=np.int64)
                    )
                    sel[slots_m] = True

        return self._accept(
            ready_works, crumb, parts, pipelined, interval=True
        )

    def _open_batch(self):
        """Start one call's accept scaffolding: the claimed-slot scratch
        cleared, no cohort accepted yet, and the parts a batch is made
        of — (sel, flat_parts, size_parts, react_parts)."""
        self.accepted_cohorts = []
        self._sel_mask[:] = False
        return self._sel_mask, [], [], []

    def _accept(self, works, crumb, parts, pipelined, interval=False):
        """Accept these cohorts, oldest first, into the batch `parts`
        holds so far, and close `crumb` over it: (batch, matched_slots,
        reactivate_slots)."""
        for work in works:
            self._accept_work(work, crumb, *parts, pipelined)
        out = self._finalize_batch(*parts)
        crumb["matched_entries"] = out[0].entry_count
        self.tracing.record(crumb, interval=interval)
        return out

    # ----------------------------------------------- pipeline state surface

    def set_ready_callback(self, cb):
        """Register the cohort-completion signal: `cb()` is invoked FROM
        THE COHORT'S WORKER THREAD whenever a dispatched cohort's device
        pass + gap-side assembly finish (including on failure — a failed
        cohort must also be collected promptly so its slots reclaim).
        The callback must be cheap and thread-safe; the delivery stage
        passes a `loop.call_soon_threadsafe` wakeup. None unregisters."""
        self._ready_cb = cb

    def head_ready(self) -> bool:
        """Is the head cohort's device pass + assembly complete (its
        collection would be free, no blocking join)?"""
        return bool(self._pipeline_queue) and self._pipeline_queue[0].ready()

    def guard_point(self) -> float | None:
        deadline = self.next_deadline()
        return None if deadline is None else deadline - self._guard

    def claim_guard_join(self) -> bool:
        """The delivery stage guard-joins each head at most once: a head
        it already joined and found unfinished is wedged, booked to the
        reclaim path instead of re-joined into the next cycle."""
        if not self._pipeline_queue:
            return False
        head = self._pipeline_queue[0]
        if head.ready() or head.seq == self._guard_joined:
            return False
        self._guard_joined = head.seq
        return True

    def reclaim_stale(self):
        """Public reclamation entry for the delivery stage, for a head
        past its delivery deadline: abandon cohorts wedged
        `inflight_reclaim_deadline_ms` past theirs and clear orphaned
        in-flight claims BETWEEN process() calls. Without this the
        backstop sweep only runs once per interval, so a wedged head
        discovered mid-gap would hold the queue until the next
        dispatch."""
        deadline = self.next_deadline()
        if deadline is not None and time.perf_counter() > deadline:
            self._reclaim_stale()

    def next_deadline(self) -> float | None:
        """Earliest delivery deadline among queued cohorts (perf_counter
        seconds), or None when nothing is in flight. The interval loop
        schedules its gap wakes around this."""
        if not self._pipeline_queue:
            return None
        return self._pipeline_queue[0].deadline

    def pipeline_depth(self) -> int:
        return len(self._pipeline_queue)

    def pipeline_backlogged(self) -> bool:
        """True under genuine pipeline pressure — an unfinished head
        cohort that either already has a newer cohort stacked behind it
        (it survived a whole interval) or is close to its delivery
        deadline. The interval loop sheds its idle-gap work (GC pass,
        store drain, flush) for that gap instead of making the cohort's
        fetch/assembly thread queue behind it on a contended core. A
        head merely in normal mid-gap flight (seconds old, deadline far)
        does NOT shed: that would starve maintenance most intervals and
        then dump the accumulated churn into one still-backlogged gap."""
        if not self._pipeline_queue or self._pipeline_queue[0].ready():
            return False
        if len(self._pipeline_queue) > 1:
            return True
        deadline = self._pipeline_queue[0].deadline
        return time.perf_counter() >= deadline - 2.0 * self._guard

    def join_head(self, until: float | None = None) -> bool:
        """Block (yielding the GIL — and with it the core — to the
        cohort's worker thread) until the head cohort's assembly
        finishes or `until` (perf_counter seconds) passes. Returns
        readiness. The deadline guard's last resort: on a contended host
        the join IS the preemption that lets the cohort finish.

        Bounded twice: by the caller's `until`, and — wedged-head
        protection — by the head's OWN interval: the join never blocks
        past `deadline + guard`, so a wedged fetch/assembly thread can
        at worst cost the guard one bounded join, never hold it into
        the next cycle. A head still unfinished past that point belongs
        to the reclaim path (`inflight_reclaim_deadline_ms` →
        reclaim_stale abandons it and frees its slots)."""
        try:
            # Runs in a worker thread (delivery stage's asyncio.to_thread)
            # while the event loop may pop the queue from process_slots:
            # the head can vanish between an emptiness check and the
            # subscript, so take it under IndexError instead.
            head = self._pipeline_queue[0]
        except IndexError:
            return False
        bound = head.deadline + self._guard
        until = bound if until is None else min(until, bound)
        head.thread.join(max(0.0, until - time.perf_counter()))
        return head.ready()

    def collect_ready(self, *, rev_precision: bool, block_until=None):
        """Drain completed pipelined cohorts OUTSIDE process(): the
        interval loop calls this mid-gap, so a cohort delivers as soon as
        its device pass + gap assembly finish (~seconds into the gap)
        instead of waiting for the NEXT interval — cutting a full
        interval_sec off add→matched latency at production cadence. Same
        accept path, no new dispatch. `block_until` (perf_counter
        seconds) bounds a blocking join of the head cohort — the
        deadline guard passes it so a cohort nearing its delivery
        deadline ships now instead of waiting out another poll. Returns
        (batch, matched_slots, reactivate) or None when nothing is
        ready."""
        if not self._pipeline_queue:
            return None
        if block_until is not None:
            self.join_head(block_until)
        ready_works: list[Cohort] = []
        while self._pipeline_queue and self._pipeline_queue[0].ready():
            ready_works.append(self._pipeline_queue.popleft())
        if not ready_works:
            return None
        crumb = self.tracing.open_crumb(midgap_collect=True)
        return self._accept(
            ready_works, crumb, self._open_batch(), pipelined=True
        )

    def _accept_work(
        self, work: Cohort, crumb, sel, flat_parts, size_parts,
        react_parts, pipelined,
    ):
        span = self.tracing.span
        w_slots, w_gen = work.slots, work.gen
        if pipelined:
            # Release only slots whose in-flight claim is still THIS
            # cohort's: a slot freed, reused, and re-dispatched by a
            # later still-queued cohort (gen changed) keeps its bit or
            # the next interval triple-dispatches it.
            self._in_flight_mask[work.mine(self.store.gen)] = False
        with span(crumb, "collect_s", "mm.collect"):
            # Fetch + exact-ordering + native assembly + host
            # validation all ran on the cohort's worker thread in the
            # interval gap (_bg_asm); a ready cohort hands back
            # finished matches and this join is free. Staleness from
            # gap-time assembly (a slot reused or removed while the
            # thread ran) is exactly the staleness the accept step
            # below already drops via gen/alive masks.
            try:
                n_matches, offsets, flat, ok = self._collect(work)
            except Exception as e:
                # Cohort lost (worker crash, device fetch error,
                # injected fault): its in-flight claims were released
                # above, so reclamation is just giving the surviving
                # tickets another active interval — they retry next
                # dispatch instead of stranding, and the breaker hears
                # about it.
                self._note_backend_failure(
                    "collect", e, crumb,
                    probe=work.probe, variant=work.variant,
                )
                n = self._reclaim_inflight(
                    work.mine(self.store.gen), "cohort collect failed"
                )
                crumb["collect_reclaimed"] = (
                    crumb.get("collect_reclaimed", 0) + n
                )
                work.t_collect = time.perf_counter()
                self._lose_cohort(
                    work, "collect", f"collect failed: {e}",
                    ledger=pipelined,
                )
                return
        # The cohort's full device→host round trip succeeded: reset the
        # breaker's failure streak; a half-open PROBE cohort closes it.
        if self.breaker.state == CLOSED or work.probe:
            self.breaker.record_success()
        # Cohort delivery attribution (VERDICT r4 #3): when each cohort
        # became ready (device pass + gap assembly done) and when it was
        # actually collected, measured AFTER the join above so a
        # not-yet-ready cohort popped by backpressure (or the
        # non-pipelined path) charges its real blocking wait to the
        # collect stamp instead of under-reporting.
        work.t_collect = now = time.perf_counter()
        work.slipped = bool(pipelined and now > work.deadline)
        if work.slipped:
            crumb["cohort_slipped"] = crumb.get("cohort_slipped", 0) + 1
            # A cohort past its deadline missed every mid-gap collection
            # point — log it loudly instead of letting the cadence
            # metric average it away. Attribution in the message
            # itself: a long fetch lag names the D2H transfer;
            # ready≈fetch with a long collect names gap-poll gating.
            self.logger.warn(
                "cohort delivered past its interval deadline",
                ready_lag_s=round(work.lag(work.t_ready), 2),
                fetch_lag_s=round(work.lag(work.t_fetched), 2),
                collect_lag_s=round(work.lag(now), 2),
                interval_sec=self.config.interval_sec,
            )
        with span(crumb, "accept_s", "mm.accept"):
            total = int(offsets[n_matches])
            flat_t = flat[:total]
            sizes = (
                offsets[1 : n_matches + 1] - offsets[:n_matches]
            ).astype(np.int64)
            mid = np.repeat(np.arange(n_matches), sizes)
            # stale: a slot was reused between dispatch and collection
            # (pipelined interval) — its properties/query no longer
            # match what the kernel scored; dead: removed meanwhile;
            # sel: claimed by an earlier accepted match this interval.
            sel_conflict_n = int(sel[flat_t].sum())
            bad_e = (
                (w_gen[flat_t] != self.store.gen[flat_t])
                | ~self.store.alive[flat_t]
                | sel[flat_t]
            )
            bad = ~ok
            if bad_e.any():
                # bincount over the bad entries' match ids: ~10x the
                # buffered np.logical_or.at at 100k entries.
                bad |= (
                    np.bincount(mid[bad_e], minlength=n_matches) > 0
                )
            if pipelined and bad.any():
                # Only the pipeline lag can strand an inactive ticket;
                # non-pipelined drops keep reference single-shot
                # semantics.
                dropped = flat_t[bad[mid]]
                dropped = dropped[
                    self.store.alive[dropped] & ~sel[dropped]
                ]
                react_parts.append(dropped)
            if bad.any():
                # Attribution for reactivation-tail latency (VERDICT r4
                # #3): WHY matches dropped at accept — validation (~ok),
                # staleness (gen), death, or same-interval sel conflict.
                crumb["dropped_matches"] = crumb.get(
                    "dropped_matches", 0
                ) + int(bad.sum())
                crumb["dropped_invalid"] = crumb.get(
                    "dropped_invalid", 0
                ) + int((~ok).sum())
                crumb["dropped_stale_gen"] = crumb.get(
                    "dropped_stale_gen", 0
                ) + int((w_gen[flat_t] != self.store.gen[flat_t]).sum())
                crumb["dropped_dead"] = crumb.get(
                    "dropped_dead", 0
                ) + int((~self.store.alive[flat_t]).sum())
                crumb["dropped_sel"] = crumb.get("dropped_sel", 0) + int(
                    sel_conflict_n
                )
            good = ~bad
            good_flat = flat_t[good[mid]]
            sel[good_flat] = True
            flat_parts.append(good_flat)
            size_parts.append(sizes[good])
        work.matched_slots = good_flat
        work.matches = int(good.sum())
        work.envelopes = int(self.meta["count"][good_flat].sum())
        work.t_accept = time.perf_counter()
        if pipelined:
            # Per-cohort dispatch→delivered ledger: slips are read off
            # the console/metrics, not inferred from bench WARN lines.
            # Pipelined cohorts only — the synchronous fallback's
            # blocking same-interval collects would otherwise pollute
            # the delivery-lag histogram and evict real pipelined
            # entries from the ledger window slip_count() reads.
            if self.metrics is not None:
                self.metrics.mm_delivery_lag.observe(work.lag(now))
                if work.slipped:
                    self.metrics.mm_cohort_slipped.inc()
            self._record_cohort(work)
            self.accepted_cohorts.append(work)
        self._close_cohort_trace(work)

    def _record_cohort(self, work: Cohort) -> None:
        """Store the cohort's ledger row, with the device phases on the
        same record as the host stage chain: kernel events between the
        cohort's flush and now (shared-mesh neighbors — leaderboard
        flushes — land here too, which is the point: contention reads
        off one record), and `gap_in_flight_s`: how long the interval
        loop's gap passes ran between the cohort's dispatch and now."""
        row = work.row()
        # Maintenance that ran on the loop while these players waited.
        row["gap_in_flight_s"] = self.tracing.gap_seconds_since(
            work.t_dispatch
        )
        row["device_timeline"] = DEVOBS.timeline_between(
            work.t_window_wall or work.t_dispatch_wall, time.time()
        )
        work.entry = self.tracing.record_delivery(**row)
        if work.asm is not None:
            self._uncounted.append(work)

    def count_cohorts(self) -> None:
        """Idle-gap sweep (the interval loop calls it before the store
        drains): put on each recorded cohort's ledger row what filtering
        did to its candidate lists — `candidates_valid` (list cells that
        hold a ticket after stage 2), `candidates_distinct` (tickets in
        at least one list) of `candidates_pool` (tickets in the pool at
        dispatch) — and what the assembler made of them:
        `actives_unmatched` (searchers in no match), `matches_below_max`
        (matches smaller than their searcher's max_count), and the
        four sums the native assembler's call returned for what
        validation did to its walk (native.WALK_COUNTERS, described
        there): `hits_walked`, `hits_rev_refused`,
        `hits_combo_conflicts`, `matches_needing_host`; the middle two
        are 0 unless the cohort ran under `rev`. O(actives x
        k) numpy, which is why no stage between dispatch and publish
        pays for it. A pairs cohort never walks (none of the four) and
        its lists never leave the device: of the rest it gets the last
        two, `candidates_valid` and `candidates_pool` as
        `pair_partners` counted them there (no `candidates_distinct`),
        and the pairing's own: `pairs_formed` on the device,
        `pair_rounds_formed` round by round (`pair_rounds` of them,
        `pairs_formed_last_round` the last), `pairs_rejected` by the
        host's exact re-check or for sharing a session, and
        `pair_round_rows`, the rows each round gathered availability
        for (`pair_rows_gathered` their sum, of `pair_rows_dense` had
        every round run over every row)."""
        meta = self.meta
        while self._uncounted:
            work = self._uncounted.popleft()
            work.entry.update(
                work.list_counts(meta["count"], meta["max_count"])
            )

    def _lose_cohort(
        self, work: Cohort, stage: str, message: str, ledger: bool = True
    ) -> None:
        """A cohort that will deliver nothing (its collect raised, or it
        wedged and was abandoned): `status: error` with the stage on its
        record, one whole ledger row that claims no stage it never
        reached, and its trace closed as an error trace."""
        work.status, work.error_stage = "error", stage
        if ledger:
            self._record_cohort(work)
        self._close_cohort_trace(work, status="error", message=message)

    def _close_cohort_trace(
        self, work: Cohort, status: str = "ok", message: str = ""
    ) -> None:
        """Append the cohort's stage spans (fetched/ready, from the
        record's perf stamps) to its trace and release the
        hold taken at dispatch. Clears the ctx so the reclaim path can
        never double-release."""
        tctx, work.trace = work.trace, None
        if tctx is None:
            return
        trace_id, parent = tctx
        base = work.t_dispatch_wall
        for name, stamp in (
            ("cohort.fetched", work.t_fetched),
            ("cohort.ready", work.t_ready),
        ):
            if stamp is not None:
                trace_api.emit_span(
                    trace_id, parent, name,
                    start_ts=base, end_ts=base + work.lag(stamp),
                )
        trace_api.emit_span(
            trace_id, parent, "cohort.collected",
            start_ts=base,
            end_ts=base + work.lag(time.perf_counter()),
            status=status, message=message,
            breaker=self.breaker.state,
        )
        trace_api.TRACES.release(trace_id)

    def _finalize_batch(self, sel, flat_parts, size_parts, react_parts):
        if flat_parts:
            matched_slots = np.concatenate(flat_parts).astype(
                np.int32, copy=False
            )
            all_sizes = np.concatenate(size_parts)
            offsets_out = np.zeros(len(all_sizes) + 1, dtype=np.int64)
            np.cumsum(all_sizes, out=offsets_out[1:])
        else:
            matched_slots = np.zeros(0, dtype=np.int32)
            offsets_out = np.zeros(1, dtype=np.int64)
        # Ticket snapshot deferred: LocalMatchmaker binds the removal
        # path's parked object array (same slots, same order).
        batch = MatchBatch(
            offsets_out, matched_slots, counts=self.meta["count"]
        )
        if react_parts:
            reactivate = np.unique(np.concatenate(react_parts))
            reactivate = reactivate[~sel[reactivate]].astype(np.int32)
        else:
            reactivate = np.zeros(0, dtype=np.int32)
        return batch, matched_slots, reactivate

    def flush(self):
        self.pool.flush()

    def wait_idle(self, timeout: float | None = None):
        """Block until every dispatched cohort's compute + D2H + gap-side
        assembly completed (the results stay queued for the next process()
        to collect). Used between intervals by the bench to model the
        production interval gap, and at shutdown so no worker thread
        outlives the runtime (incl. prewarm compiles: XLA aborts the
        process if a compile thread dies at teardown)."""
        deadline = (
            None if timeout is None else time.monotonic() + timeout
        )

        def _left():
            if deadline is None:
                return None
            return max(0.0, deadline - time.monotonic())

        for work in list(self._pipeline_queue):
            work.thread.join(_left())
        # Warm threads join WITHOUT the deadline: they are pure XLA
        # compiles (bounded, ~seconds) and a daemon compile thread left
        # alive at interpreter teardown aborts the whole process — a
        # slightly slower stop() beats 'FATAL: exception not rethrown'.
        for t in self._warm_threads:
            if t.is_alive():
                t.join()
        self._warm_threads = []
        self.pool.join_prewarm()

    # ----------------------------------------------- snapshot / restore

    def snapshot_state(self) -> dict:
        """Checkpoint view of the backend (recovery.py): the compiled
        device pool rows (one D2H fetch), exact query/value mirrors, and
        the per-slot classification masks — everything on_add derives,
        so a warm restart is bulk array restores + ONE device_put
        instead of ~pool_size per-ticket recompiles. Sliced to the
        high-water mark so the blob scales with occupancy."""
        self.pool.flush()
        hw = self.pool.high_water
        return {
            "backend": "tpu",
            "schema": (
                self.pool.capacity, self.fn, self.fs, self.s, self.d,
            ),
            "pool": self.pool.snapshot(),
            "exact": {k: v[:hw].copy() for k, v in self.exact.items()},
            "host_only_mask": self.host_only_mask[:hw].copy(),
            "should_mask": self._should_mask[:hw].copy(),
            "emb_mask": self._emb_mask[:hw].copy(),
            "nonpair_mask": self._nonpair_mask[:hw].copy(),
            "created_base": int(self._created_base),
            "grid_lo": self._grid_lo.copy(),
            "grid_hi": self._grid_hi.copy(),
        }

    def restore_state(self, snap: dict) -> None:
        """Warm-restart restore onto a FRESH backend whose SlotStore was
        already restored (the masks below cross-reference live ticket
        objects). Pipeline state starts empty — no cohort survives a
        process, which is exactly what the journal's unpublished-match
        re-pooling covers."""
        schema = (
            self.pool.capacity, self.fn, self.fs, self.s, self.d,
        )
        if tuple(snap["schema"]) != schema:
            raise ValueError(
                f"snapshot schema {tuple(snap['schema'])} != backend"
                f" schema {schema} (restore requires the same"
                " matchmaker config)"
            )
        self.pool.load(snap["pool"])
        hw = self.pool.high_water
        for k, v in snap["exact"].items():
            if k in self.exact:
                self.exact[k][:hw] = v
        self.host_only_mask[:hw] = snap["host_only_mask"]
        self._should_mask[:hw] = snap["should_mask"]
        self._should_count = int(self._should_mask.sum())
        self._emb_mask[:hw] = snap["emb_mask"]
        self._emb_count = int(self._emb_mask.sum())
        self._nonpair_mask[:hw] = snap["nonpair_mask"]
        self._nonpair_count = int(self._nonpair_mask.sum())
        self._created_base = int(snap["created_base"])
        self._grid_lo = np.asarray(snap["grid_lo"]).copy()
        self._grid_hi = np.asarray(snap["grid_hi"]).copy()
        # The id-keyed host-only view rebuilds from the mask + the
        # restored ticket objects (few by design — budgeted fallback).
        self.host_only = set()
        ticket_at = self.store.ticket_at
        for s in np.nonzero(self.host_only_mask)[0]:
            t = ticket_at[s]
            if t is not None:
                self.host_only.add(t.ticket)
        self._rebuild_ring()

    def _rebuild_ring(self) -> None:
        """Reseed the insertion-ordered dispatch ring from the restored
        store: live slots in exact (created_at, created_seq) order."""
        meta = self.meta
        live = self.store.live_slots()
        order = np.lexsort(
            (meta["created_seq"][live], meta["created"][live])
        )
        live = live[order]
        n = len(live)
        self._ring[:n] = live
        self._ring_valid[:n] = True
        self._ring_valid[n:] = False
        self._ring_pos[:] = -1
        self._ring_pos[live] = np.arange(n, dtype=np.int64)
        self._ring_n = n
        self._ring_last_created = (
            int(meta["created"][live[-1]])
            if n
            else np.iinfo(np.int64).min
        )
        self._ring_unsorted = False

    # ----------------------------------------------------- dispatch order

    def _ring_append(self, slot: int):
        if self._ring_n == len(self._ring):
            self._ring_compact()
        old = self._ring_pos[slot]
        if old >= 0:
            self._ring_valid[old] = False  # slot reuse: void the old entry
        pos = self._ring_n
        self._ring[pos] = slot
        self._ring_valid[pos] = True
        self._ring_pos[slot] = pos
        self._ring_n = pos + 1
        created = self.meta["created"][slot]
        if created < self._ring_last_created:
            self._ring_unsorted = True
        else:
            self._ring_last_created = created

    def _ring_compact(self):
        """Drop invalidated/dead entries (and re-sort if flagged): runs
        when the ring fills, amortized O(1) per add."""
        n = self._ring_n
        ring = self._ring[:n]
        keep = self._ring_valid[:n] & self.store.alive[ring]
        # Dropped entries must release their slots' back-pointers: a
        # reused slot with a stale _ring_pos would invalidate whatever
        # entry now occupies that position (a live slot's), permanently
        # forcing the lexsort fallback.
        self._ring_pos[ring[~keep]] = -1
        live = ring[keep]
        if self._ring_unsorted:
            meta = self.meta
            order = np.lexsort(
                (meta["created_seq"][live], meta["created"][live])
            )
            live = live[order]
            self._ring_unsorted = False
        m = len(live)
        if m == len(self._ring):  # live <= capacity < ring size, always
            raise RuntimeError("slot ring compaction found no free space")
        self._ring[:m] = live
        self._ring_valid[:m] = True
        self._ring_valid[m:] = False
        self._ring_pos[live] = np.arange(m, dtype=np.int64)
        self._ring_n = m
        self._ring_last_created = (
            self.meta["created"][live[-1]]
            if m
            else np.iinfo(np.int64).min
        )

    def _order_dispatch(self, device_slots, device_last):
        """Order (device_slots, device_last) oldest-first by (created_at,
        created_seq). Fast path reads the insertion ring; the lexsort
        fallback covers unsorted rings and any ring/membership drift."""
        ordered = None
        if not self._ring_unsorted:
            dm = self._dev_mask_scratch
            dm[device_slots] = True
            ring = self._ring[: self._ring_n]
            keep = self._ring_valid[: self._ring_n] & dm[ring]
            ordered = np.ascontiguousarray(ring[keep])
            dm[device_slots] = False
            if len(ordered) != len(device_slots):
                ordered = None  # drift: resolve exactly
        if ordered is None:
            meta = self.meta
            order = np.lexsort(
                (
                    meta["created_seq"][device_slots],
                    meta["created"][device_slots],
                )
            )
            ordered = np.ascontiguousarray(device_slots[order])
            last = np.ascontiguousarray(device_last[order], dtype=np.uint8)
            return ordered, last
        # device_last is aligned to device_slots; realign to ring order
        # via the last-interval recomputation the caller already encoded:
        # map slot -> last flag, then gather in ring order.
        lm = self._dev_mask_scratch
        lm[device_slots] = device_last.astype(bool)
        last = np.ascontiguousarray(lm[ordered], dtype=np.uint8)
        lm[device_slots] = False
        return ordered, last

    # ------------------------------------------------------------- dispatch

    def _dispatch(self, slots: np.ndarray, last: np.ndarray, rev: bool):
        """Launch the device top-K for the given active slots; returns an
        opaque pending handle whose transfer AND downstream host assembly
        are already in flight on a worker thread."""
        hw = self.pool.high_water
        with_should = self._should_count > 0
        with_embedding = self._emb_count > 0
        if self.mesh is not None and self.mesh_breaker.allow():
            try:
                # chaos: raise/stall the dispatch (mesh rung first)
                faults.fire("device.dispatch")
                handle = self._dispatch_sharded(
                    slots, last, rev, with_should, with_embedding
                )
                self.mesh_breaker.record_success()
                return handle
            except Exception as exc:
                # Degrade, never wedge: the mesh rung failing books on
                # ITS breaker and the same interval falls through to the
                # single-device body below (whose own failure is what
                # the main breaker → host-oracle ladder judges).
                self._note_mesh_failure("dispatch", exc)
        faults.fire("device.dispatch")  # chaos: raise/stall the dispatch
        big = hw >= self.config.big_pool_threshold

        if big:
            bm, bn = self.big_row_block, self.big_col_block

            def bucket(blocks: int) -> int:
                # pow2 up to 16 blocks, then multiples of 16: bounded
                # compile-shape count with <= 1.15x padding waste at scale.
                if blocks <= 16:
                    return _pow2_blocks(blocks)
                return -(-blocks // 16) * 16

            n_cols = min(self.pool.capacity, bucket(-(-hw // bn)) * bn)
            # Rows pad pow2-ONLY: active counts swing every interval and
            # each distinct shape is a multi-second XLA compile that lands
            # straight in the p99 (measured 3.7-10s spikes from
            # 48/112-style buckets). The <=2x padded rows are pipelined
            # MXU time nobody waits on.
            a_pad = _pow2_blocks(-(-len(slots) // bm)) * bm
            use_pairs = self._use_pairs()
            self._dispatching = self._variant(
                "topk_candidates_big" + ("+pair_partners" * use_pairs),
                a_pad, n_cols, bm, bn, rev, with_should, with_embedding,
            )
            self._prewarm_row_bucket(
                a_pad, n_cols, rev, with_should, with_embedding, bm, bn,
                order_exact=not use_pairs,
            )

            grid_lo, grid_inv = self._grid_params()
            with DEVOBS.device_call("matchmaker.score"):
                cand_dev = topk_candidates_big(
                    self.pool.device,
                    pad_to(slots, a_pad, -1),
                    grid_lo,
                    grid_inv,
                    fn=self.fn,
                    fs=self.fs,
                    n_cols=n_cols,
                    # Pairs keep the full candidate width: coverage is
                    # set by list DIVERSITY, not handshake rounds —
                    # capping k to 16 measured ~5% unmatched leftovers
                    # (overlapping lists exhaust under contention;
                    # rounds can't recover).
                    k=self.k,
                    rev=rev,
                    with_should=with_should,
                    with_embedding=with_embedding,
                    bm=bm,
                    bn=bn,
                    interpret=self._interpret,
                    # The handshake needs eligible candidates, not the
                    # exact (-score, created) order: skip stage 2's
                    # second sort.
                    order_exact=not use_pairs,
                )
            if use_pairs:
                return self._pairs_dispatch(cand_dev, slots, a_pad, last, rev)
            return self._bg_asm("big", (cand_dev,), slots, last, rev)

        # Small-pool exact path (unchanged round-1 kernel).
        n_blocks = -(-len(slots) // self.row_block)
        a_pad = self.row_block * _pow2_blocks(n_blocks)
        col_blocks = -(-hw // self.col_block)
        n_cols = min(
            self.col_block * _pow2_blocks(col_blocks),
            self.pool.capacity,
        )
        self._dispatching = self._variant(
            "topk_candidates", a_pad, n_cols, self.row_block,
            self.col_block, rev, with_should, with_embedding,
        )
        with DEVOBS.device_call("matchmaker.score"):
            scores, cand = topk_candidates(
                self.pool.device,
                pad_to(slots, a_pad, -1),
                k=min(self.k, n_cols),
                br=self.row_block,
                bc=self.col_block,
                rev=rev,
                n_cols=n_cols,
                with_should=with_should,
                with_embedding=with_embedding,
                created_base=np.int32(self._created_base),
            )
        return self._bg_asm("small", (scores, cand), slots, last, rev)

    def _variant(
        self, kernel, a_pad, n_cols, bm, bn, rev, with_should,
        with_embedding, n_local=None,
    ) -> dict:
        """The dispatched program, as breadcrumbs and refusal logs name
        it. For the two-stage kernels `row_block` is the tile stage 1
        really runs (it halves the configured one to fit VMEM), and
        `winners_per_block` what each column block keeps."""
        big = {}
        if kernel.startswith("topk_candidates_big"):
            from .device2 import stage1_plan
            m, out_w, bm = stage1_plan(
                n=n_cols, n_local=n_local or n_cols, k=self.k, bm=bm,
                bn=bn, fn=self.fn, fs=self.fs,
                de=self.d if with_embedding else 8, rev=rev,
            )
            big = _big_variant(
                self, m, a_pad, n_cols, n_local, out_w, rev, with_should)
        return dict(
            kernel=kernel, a_pad=int(a_pad), n_cols=int(n_cols),
            row_block=bm, col_block=bn, **big, fn=self.fn, fs=self.fs,
            constraints=self.s, k=self.k, emb_dims=self.d, rev=bool(rev),
            with_should=with_should, with_embedding=with_embedding,
            interpret=self._interpret,
        )

    def _use_pairs(self) -> bool:
        """Device-side 1v1 grouping is taken when the whole pool is pure
        1v1 — one predicate, on what the backend observes, for the
        single-chip and mesh dispatch paths. Synchronous intervals shed
        the candidate matrix D2H (their latency floor); pipelined intervals shed the
        gap-side host work (16MB fetch + native assembly) that contends
        with the server on small hosts — the cohort-slip tail. Staleness
        semantics are identical either way: pairs flow through the same
        gen/alive/sel accept masks as assembler matches."""
        return self._nonpair_count == 0

    def _pairs_dispatch(self, cand_dev, slots, a_pad, last, rev):
        """Propose-accept handshake over the (one chip's or merged)
        candidate lists; only the partner vector and the handshake's
        three counters (a row each) cross D2H — the candidate matrix
        (~16MB at 100k) stays on device."""
        import jax.numpy as jnp

        from .device2 import pair_partners

        with DEVOBS.device_call("matchmaker.assign"):
            paired = pair_partners(
                cand_dev,
                jnp.asarray(pad_to(slots, a_pad, -1)),
                cap=self.pool.capacity,
            )
        return self._bg_asm("pairs", paired, slots, last, rev)

    def _grid_params(self):
        """Bucket-grid (lo, 1/width) per numeric field for the big kernel."""
        width = self._grid_hi - self._grid_lo
        ok = np.isfinite(width) & (width >= 0)
        grid_lo = np.where(ok, self._grid_lo, 0.0).astype(np.float32)
        grid_inv = (
            1.0 / np.maximum(np.where(ok, width, 1.0), 1e-30)
        ).astype(np.float32)
        return grid_lo, grid_inv

    def _bg_asm(self, kind, dev_arrays, slots, last, rev):
        """Run the whole post-kernel tail on a worker thread: D2H fetch
        (forced C-contiguous — this runtime hands back strided views whose
        lazy gather measured 10-300ms), the exact candidate re-ordering
        (small path), the native greedy assembly, and the host validation
        of flagged matches. All of it rides the gap to the next interval;
        collection picks up finished matches. ctypes drops the GIL for
        the C assembly, and the numpy/C work here reads only per-slot
        arrays whose staleness the accept step masks by gen/alive.
        copy_to_host_async alone proved unreliable here — issued before
        the computation commits, some plugins drop it and the collect-side
        np.asarray pays the full transfer."""
        self._dispatch_counter += 1
        out = Cohort(
            self._dispatch_counter, self._dispatching, slots,
            self.config.interval_sec,
        )
        out.pool = self.store.n_live
        n_rows = len(slots)
        # HBM ledger: the dispatch ring — candidate/partner tensors
        # alive on device between kernel launch and their D2H fetch
        # (transient, but at 100k actives it is tens of MB of HBM the
        # pool columns don't explain). Released when the fetch lands.
        dispatch_bytes = sum(
            int(getattr(a, "nbytes", 0)) for a in dev_arrays
        )
        DEVOBS.mem_add("matchmaker.dispatch", dispatch_bytes)
        annotate = trace_api.annotate

        def _fetch(arr):
            # The D2H read of a finished array: the `matchmaker.fetch`
            # clock holds the copy alone, the record the wait before it.
            with DEVOBS.device_call("matchmaker.fetch"):
                host = np.ascontiguousarray(np.asarray(arr))
            DEVOBS.transfer("cohort.fetch", "d2h", int(host.nbytes))
            out.d2h_bytes += int(host.nbytes)
            return host

        def _run():
            try:
                # Chaos: stall delays this cohort's readiness (a slow
                # D2H/assembly); raise surfaces at collect and walks the
                # reclamation + breaker path.
                faults.fire("device.collect")
                # The wait for the program, apart from the copy: on the
                # host clock these two were one number that read as
                # "D2H" while it was device compute.
                with annotate("cohort.device"):
                    jax.block_until_ready(dev_arrays)
                out.t_device_done = time.perf_counter()
                with annotate("cohort.d2h"):
                    fetched = [_fetch(a)[:n_rows] for a in dev_arrays]
                out.t_fetched = time.perf_counter()
                cpu_fetched = time.thread_time()
                with annotate("cohort.assemble"):
                    if kind == "pairs":
                        partner, formed, listed, ran = fetched
                        out.pairs = (
                            formed[0], listed[0], ran[0],
                            dev_arrays[0].shape[0],
                        )
                        out.asm = self._assemble_pairs(slots, partner, rev)
                    else:
                        if kind == "big":
                            # Already exactly ordered by (-score,
                            # created) on device; a row slice of the
                            # contiguous fetch stays C-contiguous.
                            (out.cand,) = fetched
                        else:
                            out.cand = self._order_small(*fetched)
                        out.asm, out.walk = self._assemble(
                            slots, last, out.cand, rev
                        )
                out.assemble_cpu_s = time.thread_time() - cpu_fetched
            except Exception as e:  # surfaced at collect
                out.err = e
            finally:
                DEVOBS.mem_add("matchmaker.dispatch", -dispatch_bytes)
                out.t_ready = time.perf_counter()
                # Completion signal LAST (after the ready stamp, so a
                # woken collector always sees a finished cohort). A
                # failing callback must never kill the worker before
                # its results are parked.
                cb = self._ready_cb
                if cb is not None:
                    try:
                        cb()
                    except Exception:
                        pass

        out.thread = threading.Thread(target=_run, daemon=True)
        out.thread.start()
        return out

    def _assemble(self, slots, last, cand_np, rev):
        """Native greedy assembly + host validation of flagged matches.
        Exact query validation runs INSIDE the assembler (f64 mirrors):
        an imprecision-admitted candidate is skipped there and assembly
        continues with the next hit — matching the reference, whose index
        search never returns non-matching hits. Only matches flagged
        needs_host (host-only member under mutual validation) fall back
        to the AST check. Returns the (n_matches, offsets, flat, ok)
        that accept reads and, beside it, the walk's counters
        (native.WALK_COUNTERS) for the cohort's ledger row."""
        meta = self.meta
        n_matches, offsets, flat, needs_host, walk = native.assemble_arrays(
            slots,
            last,
            cand_np,
            min_count=meta["min_count"],
            max_count=meta["max_count"],
            count_multiple=meta["count_multiple"],
            count=meta["count"],
            intervals=meta["intervals"],
            created=meta["created"],
            session_hashes=meta["session_hashes"],
            session_counts=meta["session_counts"],
            exact=self.exact,
            rev=rev,
        )
        ok = self._validate_flagged(n_matches, offsets, flat, needs_host, rev)
        return (n_matches, offsets, flat, ok), walk

    def _assemble_pairs(self, slots, partner, rev):
        """Host tail of the device-pairing path: exact (f64) validation of
        the device-formed pairs, vectorized over all pairs at once, then
        the shared (n_matches, offsets, flat, ok) shape. Mirrors the
        assembler's per-pair checks for the 1v1 case: forward query
        acceptance (both directions under rev — reference validateMatch,
        server/matchmaker.go:1042), session-overlap rejection. A pair
        failing here is dropped (its members retry next interval) rather
        than re-assembled — the f32/bucket false-positive rate this guards
        is per-mille, and reference semantics permit unmatched leftovers."""
        idx = np.nonzero(partner >= 0)[0]
        i_slots = slots[idx]
        j_slots = partner[idx].astype(np.int32)
        ok = self._exact_accepts_vec(i_slots, j_slots)
        needs_host = np.zeros(len(idx), dtype=np.uint8)
        if rev:
            j_ok = self.exact["q_exact_ok"][j_slots]
            back = self._exact_accepts_vec(j_slots, i_slots)
            ok &= np.where(j_ok, back, True)
            # Host-only passive member: its real query needs the AST check.
            needs_host = (~j_ok).astype(np.uint8)
        ok &= (
            self.meta["session_hashes"][i_slots, 0]
            != self.meta["session_hashes"][j_slots, 0]
        )
        i_slots, j_slots = i_slots[ok], j_slots[ok]
        needs_host = needs_host[ok]
        n = len(i_slots)
        offsets = np.arange(0, 2 * n + 2, 2, dtype=np.int32)
        flat = np.empty(2 * n, dtype=np.int32)
        flat[0::2] = i_slots
        flat[1::2] = j_slots
        okv = self._validate_flagged(n, offsets, flat, needs_host, rev)
        return n, offsets, flat, okv

    def _exact_accepts_vec(self, q, v):
        """Vectorized mirror of the assembler's Exact::accepts (f64
        mirrors, 63-bit hashes): does q's query accept v's values, for
        slot arrays q, v elementwise."""
        ex = self.exact
        lo, hi = ex["q_lo"][q], ex["q_hi"][q]
        x = ex["v_num"][v]
        unconstrained = np.isinf(lo) & (lo < 0) & np.isinf(hi) & (hi > 0)
        ok = np.all(unconstrained | ((x >= lo) & (x <= hi)), axis=1)
        ok &= ~np.any(
            (x >= ex["q_flo"][q]) & (x <= ex["q_fhi"][q]), axis=1
        )
        req, forb = ex["q_req"][q], ex["q_forb"][q]
        sv = ex["v_str"][v]
        ok &= np.all(
            ((req == 0) | (sv == req)) & ((forb == 0) | (sv != forb)),
            axis=1,
        )
        pure_should = ~ex["q_has_must"][q] & ex["q_has_should"][q]
        if pure_should.any():
            op, fld = ex["q_sh_op"][q], ex["q_sh_fld"][q]
            fn = ex["v_num"].shape[1]
            fs = ex["v_str"].shape[1]
            r = np.arange(len(q))[:, None]
            xv = x[r, np.minimum(fld, fn - 1)]
            sv2 = sv[r, np.minimum(fld, fs - 1)]
            term = ex["q_sh_term"][q]
            hit = (
                (
                    (op == SOP_NUM_RANGE)
                    & (xv >= ex["q_sh_lo"][q])
                    & (xv <= ex["q_sh_hi"][q])
                )
                | ((op == SOP_STR_EQ) & (term != 0) & (sv2 == term))
                | (op == SOP_ALL)
            )
            ok &= ~pure_should | np.any(hit, axis=1)
        # Missing exact mirror (host-only query): not decidable here.
        ok &= ex["q_exact_ok"][q]
        return ok

    def _order_small(self, scores_np, cand_np):
        """Exact re-sort of each candidate list by (-score, created): the
        small kernel's wait-time epsilon only biased the top-K cutoff."""
        created_of = self.meta["created"][np.maximum(cand_np, 0)]
        created_of = np.where(
            cand_np < 0, np.iinfo(np.int64).max, created_of
        )
        by_created = np.argsort(created_of, axis=1, kind="stable")
        s2 = np.take_along_axis(scores_np, by_created, axis=1)
        by_score = np.argsort(-s2, axis=1, kind="stable")
        order = np.take_along_axis(by_created, by_score, axis=1)
        return np.ascontiguousarray(
            np.take_along_axis(cand_np, order, axis=1)
        )

    def _dispatch_sharded(
        self, slots: np.ndarray, last: np.ndarray, rev: bool,
        with_should: bool, with_embedding: bool,
    ):
        """Multi-device interval (SURVEY §2.8; parallel/mesh.py +
        device2.topk_candidates_big_sharded): every device scores all
        active rows against ITS column shard of the pool, partial
        winners merge over ICI. Large pools take the sharded two-stage
        MXU kernel (VERDICT r2 #2); small pools keep the exact
        blockwise scan. Returns the shared pending shapes so
        collection/assembly are common."""
        import jax.numpy as jnp

        axis = POOL_AXIS
        n_dev = self.mesh.shape[axis]
        if self.pool.high_water >= self.config.big_pool_threshold:
            from .device2 import stage1_plan, topk_candidates_big_sharded

            bm, bn = self.big_row_block, self.big_col_block
            a_pad = _pow2_blocks(-(-len(slots) // bm)) * bm
            grid_lo, grid_inv = self._grid_params()
            cap = self.pool.capacity
            # The packed-winner all_gather rides inside the fused call;
            # its stripe width is the per-shard stage-1 output.
            _, out_w, _ = stage1_plan(
                n=cap, n_local=cap // n_dev, k=self.k, bm=bm, bn=bn,
                fn=self.fn, fs=self.fs,
                de=self.d if with_embedding else 8, rev=rev,
            )
            self._account_gather(n_dev * a_pad * out_w * 4)
            self._dispatching = self._variant(
                f"topk_candidates_big_sharded/{n_dev}"
                + ("+pair_partners" * self._use_pairs()),
                a_pad, cap, bm, bn, rev, with_should, with_embedding,
                n_local=cap // n_dev,
            )
            faults.fire("mesh.gather")  # chaos: fail the ICI merge
            with DEVOBS.device_call("matchmaker.shard_score"):
                cand_dev = topk_candidates_big_sharded(
                    self.pool.device,
                    pad_to(slots, a_pad, -1),
                    grid_lo,
                    grid_inv,
                    mesh=self.mesh,
                    axis=axis,
                    fn=self.fn,
                    fs=self.fs,
                    k=self.k,
                    rev=rev,
                    with_should=with_should,
                    with_embedding=with_embedding,
                    bm=bm,
                    bn=bn,
                    interpret=self._interpret,
                )
            if self._use_pairs():
                # Works on the ICI-merged candidate lists exactly as on
                # one chip (VERDICT r4 #8).
                return self._pairs_dispatch(cand_dev, slots, a_pad, last, rev)
            return self._bg_asm("big", (cand_dev,), slots, last, rev)

        br = self.row_block
        n_blocks = -(-len(slots) // br)
        a_pad = br * _pow2_blocks(n_blocks)
        pad_slots = pad_to(slots, a_pad, -1)
        safe = jnp.asarray(np.maximum(pad_slots, 0))
        rows = dict(self._gather_rows(self.pool.device, safe))
        rows["_valid"] = jnp.asarray((pad_slots >= 0).astype(np.int32))
        rows["_slot"] = jnp.asarray(pad_slots.astype(np.int32))
        # Every shard hands its full top-k to the merge: exact.
        k = min(self.k, self.pool.capacity)
        self._dispatching = self._variant(
            f"mesh_score+mesh_merge/{n_dev}", a_pad, self.pool.capacity,
            br, self.col_block, rev, with_should, with_embedding,
        )
        self._prewarm_mesh_bucket(
            a_pad, k, rev, with_should, with_embedding,
            {rk: (rv.shape, rv.dtype) for rk, rv in rows.items()},
        )
        score = mesh_score_fn(
            self.mesh, axis, k, br, self.col_block, rev,
            with_should, with_embedding, self.pool.capacity,
        )
        with DEVOBS.device_call("matchmaker.shard_score"):
            s_all, i_all = score(
                self.pool.device, rows, jnp.int32(self._created_base)
            )
        self._account_gather(n_dev * a_pad * k * 8)
        faults.fire("mesh.gather")  # chaos: fail the ICI merge
        with DEVOBS.device_call("matchmaker.gather_merge"):
            scores, cand = mesh_merge_fn(n_dev, k)(s_all, i_all)
        return self._bg_asm("small", (scores, cand), slots, last, rev)

    def _account_gather(self, nbytes: int):
        """Book one sharded merge's cross-device traffic (cost model:
        per-shard stripes x devices; the merge IS the all_gather)."""
        self.mesh_gather_bytes = int(nbytes)
        self.mesh_gather_bytes_total += int(nbytes)
        if self.metrics is not None:
            self.metrics.mesh_gather_bytes.set(nbytes)

    def _prewarm_mesh_bucket(
        self, a_pad, k_top, rev, with_should, with_embedding, row_shapes
    ):
        """Mesh twin of _prewarm_row_bucket: whenever a row bucket is
        dispatched on the sharded path, compile every smaller bucket
        down to one block on a background thread, so an active-count
        collapse never eats a multi-second shard_map compile inside a
        timed interval. The pool scratch carries the pool's REAL
        NamedSharding — jit keys on shardings as well as shapes, so an
        unsharded clone would warm a different cache entry than the
        live dispatch hits."""
        key0 = ("mesh", a_pad, k_top, rev, with_should, with_embedding)
        self._warmed_buckets.add(key0)
        sizes = []
        half = a_pad // 2
        while half >= self.row_block:
            key = ("mesh", half, k_top, rev, with_should, with_embedding)
            if key not in self._warmed_buckets:
                self._warmed_buckets.add(key)
                sizes.append(half)
            half //= 2
        if not sizes:
            return
        pool_shapes = {
            k: (v.shape, v.dtype) for k, v in self.pool.device.items()
        }
        sharding = self.pool.sharding
        mesh, axis = self.mesh, POOL_AXIS
        n_dev = mesh.shape[axis]

        def _warm():
            import jax
            import jax.numpy as jnp

            try:
                with DEVOBS.device_call(
                    "matchmaker.shard_score", expect_compile=True
                ):
                    scratch = {
                        k: jax.device_put(jnp.zeros(shp, dt), sharding)
                        for k, (shp, dt) in pool_shapes.items()
                    }
                score = mesh_score_fn(
                    mesh, axis, k_top, self.row_block, self.col_block, rev,
                    with_should, with_embedding, self.pool.capacity,
                )
                merge = mesh_merge_fn(n_dev, k_top)
                for size in sizes:
                    # Fully-masked pass: zero _valid rows score nothing,
                    # but the compile against this row bucket is real.
                    rows = {
                        rk: jnp.zeros((size,) + tuple(shp[1:]), dt)
                        for rk, (shp, dt) in row_shapes.items()
                    }
                    with DEVOBS.device_call(
                        "matchmaker.shard_score", expect_compile=True
                    ):
                        s_all, i_all = score(scratch, rows, jnp.int32(0))
                        jax.block_until_ready((s_all, i_all))
                    with DEVOBS.device_call(
                        "matchmaker.gather_merge", expect_compile=True
                    ):
                        jax.block_until_ready(merge(s_all, i_all))
            except Exception as e:  # best-effort: never break dispatch
                for size in sizes:
                    self._warmed_buckets.discard(
                        ("mesh", size, k_top, rev, with_should, with_embedding)
                    )
                self._note_prewarm_failure(
                    e, self._variant(
                        f"mesh_score+mesh_merge/{n_dev}", sizes[0],
                        self.pool.capacity, self.row_block,
                        self.col_block, rev, with_should, with_embedding,
                    ),
                )

        t = threading.Thread(target=_warm, daemon=True)
        self._warm_threads.append(t)
        t.start()

    def _note_prewarm_failure(self, exc: Exception, variant: dict):
        """A row bucket that failed to compile ahead of time will fail
        again when an interval dispatches it: count it and say which."""
        self.prewarm_failures += 1
        log = (
            self.logger.error if _program_refused(exc)
            else self.logger.warn
        )
        log("bucket prewarm failed", error=str(exc), **variant)

    def _prewarm_row_bucket(
        self, a_pad, n_cols, rev, with_should, with_embedding, bm, bn,
        order_exact=True,
    ):
        """Whenever a row bucket is dispatched, compile EVERY smaller
        bucket down to one block on a background thread: active counts
        both decay gradually and COLLAPSE suddenly (a big cohort matches
        wholesale and the next dispatch is a fraction of the size —
        cfg4-style pools), and any bucket first seen inside a timed
        interval eats its multi-second XLA compile right in the p99
        (measured 3.7-10s). jit compilation is synchronous on its calling
        thread but the cache is process-wide, so one daemon thread
        compiling the chain during the first interval's gap covers all
        later shrinkage; each dummy execution is a fully-masked pass."""
        self._warmed_buckets.add((a_pad, n_cols, rev, with_should,
                                  with_embedding, order_exact))
        sizes = []
        half = a_pad // 2
        while half >= bm:
            key = (half, n_cols, rev, with_should, with_embedding,
                   order_exact)
            if key not in self._warmed_buckets:
                self._warmed_buckets.add(key)
                sizes.append(half)
            half //= 2
        if not sizes:
            return
        grid_lo = np.zeros(self.fn, np.float32)
        grid_inv = np.ones(self.fn, np.float32)
        # Shapes only, never the live buffers: every flush DONATES
        # pool.device, so a captured reference dies the moment the next
        # interval flushes and the whole chain would silently fail (and
        # re-spawn, every dispatch). The jit cache keys on abstract
        # shapes, so compiling against a scratch clone warms the real
        # path; the scratch is transient device memory released when the
        # thread exits.
        shapes = {k: (v.shape, v.dtype) for k, v in self.pool.device.items()}

        def _warm():
            import jax.numpy as jnp

            # Scratch fills compile tiny programs of their own: keep
            # the whole prewarm body inside an expected-compile context.
            with DEVOBS.device_call(
                "matchmaker.score", expect_compile=True
            ):
                scratch = {
                    k: jnp.zeros(shp, dt)
                    for k, (shp, dt) in shapes.items()
                }
            for size in sizes:
                try:
                    with DEVOBS.device_call(
                        "matchmaker.score", expect_compile=True
                    ):
                        warm_cand = topk_candidates_big(
                            scratch,
                            np.full(size, -1, np.int32),
                            grid_lo,
                            grid_inv,
                            fn=self.fn,
                            fs=self.fs,
                            n_cols=n_cols,
                            k=self.k,
                            rev=rev,
                            with_should=with_should,
                            with_embedding=with_embedding,
                            bm=bm,
                            bn=bn,
                            interpret=self._interpret,
                            order_exact=order_exact,
                        )
                    if not order_exact:
                        # Pairs mode: the handshake compiles per row
                        # bucket too.
                        from .device2 import pair_partners

                        with DEVOBS.device_call(
                            "matchmaker.assign", expect_compile=True
                        ):
                            pair_partners(
                                warm_cand,
                                jnp.asarray(
                                    np.full(size, -1, np.int32)
                                ),
                                cap=self.pool.capacity,
                            )
                except Exception as e:  # best-effort: never break dispatch
                    self._warmed_buckets.discard(
                        (size, n_cols, rev, with_should, with_embedding,
                         order_exact)
                    )
                    self._note_prewarm_failure(
                        e, dict(self._variant(
                            "topk_candidates_big", size, n_cols, bm, bn,
                            rev, with_should, with_embedding,
                        ), order_exact=order_exact),
                    )

        t = threading.Thread(target=_warm, daemon=True)
        self._warm_threads.append(t)
        t.start()

    def _collect(self, work: Cohort):
        """Pick up the worker thread's finished (n_matches, offsets, flat,
        ok) — free when the cohort was ready, a blocking join otherwise
        (non-pipelined mode, or the block-drain fallback)."""
        work.thread.join()
        if work.err is not None:
            raise work.err
        return work.asm

    # ----------------------------------------------------------- validation

    def _validate_flagged(
        self,
        n_matches: int,
        offsets: np.ndarray,
        flat: np.ndarray,
        needs_host: np.ndarray,
        rev: bool,
    ) -> np.ndarray:
        """AST-validate only the matches the assembler could not check
        exactly (a member without an exact query mirror under mutual
        validation — host-only queries; reference validateMatch,
        server/matchmaker.go:1042-1068). Everything else was validated
        in-assembly."""
        ok = np.ones(n_matches, dtype=bool)
        idx = np.nonzero(needs_host[:n_matches])[0]
        if not len(idx):
            return ok
        ticket_at = self.store.ticket_at
        for i in idx:
            tickets = [
                ticket_at[s] for s in flat[offsets[i] : offsets[i + 1]]
            ]
            if any(t is None for t in tickets):
                ok[i] = False
                continue
            if rev:
                ok[i] = all(
                    _mutual(a, b)
                    for a in tickets
                    for b in tickets
                    if a is not b
                )
            else:
                searcher = tickets[-1]
                ok[i] = all(_mutual(searcher, m) for m in tickets[:-1])
        return ok


def _big_variant(backend, m, a_pad, n_cols, n_local, out_w, rev, with_should):
    """What `_variant` adds for a two-stage program: the winners a column
    block keeps and stage 2's static shape (`device2.stage2_shape`; on
    the mesh the rows' winners are the shards' side by side). Down here
    because a Pallas kernel's compile-cache key carries the line of every
    frame that called it (`_dispatch`, the warm-up threads, the mesh
    dispatch): lines added above them cost every two-stage cell a cold
    compile."""
    from .device2 import stage2_shape

    winners = n_cols // (n_local or n_cols) * out_w
    return dict(
        winners_per_block=m,
        **stage2_shape(
            backend.pool.device, int(a_pad), winners, backend.k, rev,
            with_should,
        ),
    )
