"""Device-resident ticket pool + the pairwise-eligibility top-K kernel.

The TPU re-design of the reference's per-interval Bluge index walk
(reference server/matchmaker_process.go:27-334): instead of one TopN inverted
-index search per active ticket, ALL active tickets score ALL pool tickets in
one blockwise device pass — flash-attention-style streaming over column
blocks with a running top-K per row, so the full N×N matrix never
materializes. Mutual-match ("reverse precision") is the same computation
transposed, evaluated in the same block — the reference's revCache memo
(server/matchmaker.go:1042-1068) becomes unnecessary.

Eligibility is evaluated in per-field form (see compile.py): a gather-free
broadcast compare-and-reduce over [col_block, row_block, F] that runs at
full VPU rate. The optional should-clause scoring path uses small slot
gathers and is compiled in only when the pool contains should queries.

PoolBuffer keeps the ticket tensors device-resident and applies queued
add/remove updates as one scatter per interval, so `Add` streams vectors in
instead of re-uploading the pool (BASELINE.md host↔device budget note).
Update counts, active counts, and the scanned column extent are padded to
power-of-two buckets so XLA compiles a handful of program shapes, not one
per interval.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..devobs import DEVOBS
from .compile import SOP_ALL, SOP_NUM_RANGE, SOP_STR_EQ, SOP_UNUSED

NEG_INF = np.float32(-np.inf)

# Flag bits in the "flags" column.
FLAG_VALID = 1
FLAG_HAS_MUST = 2
FLAG_HAS_SHOULD = 4
FLAG_NEVER = 8

# Tie-break: equal-score candidates prefer longer-waiting tickets. The host
# re-sorts each surviving candidate list exactly by (-score, created) before
# assembly (tpu.py), so this epsilon only biases WHICH candidates survive the
# top-K cutoff. It must stay below the smallest meaningful score gap; boosts
# are user-supplied, so that cutoff bias is a documented resolution limit of
# the device path. The kernel subtracts the pool's minimum live created_seq
# before scaling, keeping the penalty small on long-lived servers.
CREATED_EPS = np.float32(2.0**-24)


def pool_schema(
    capacity: int, fn: int, fs: int, s: int, d: int = 16
) -> dict[str, np.ndarray]:
    """Allocate host templates of the device pool arrays."""
    return {
        "emb": np.zeros((capacity, d), dtype=np.float32),
        "num": np.zeros((capacity, fn), dtype=np.float32),
        "str": np.zeros((capacity, fs), dtype=np.int32),
        "n_lo": np.zeros((capacity, fn), dtype=np.float32),
        "n_hi": np.zeros((capacity, fn), dtype=np.float32),
        "n_flo": np.ones((capacity, fn), dtype=np.float32),
        "n_fhi": np.full((capacity, fn), -1.0, dtype=np.float32),
        "s_req": np.zeros((capacity, fs), dtype=np.int32),
        "s_forb": np.zeros((capacity, fs), dtype=np.int32),
        "sh_op": np.zeros((capacity, s), dtype=np.int32),
        "sh_fld": np.zeros((capacity, s), dtype=np.int32),
        "sh_lo": np.zeros((capacity, s), dtype=np.float32),
        "sh_hi": np.zeros((capacity, s), dtype=np.float32),
        "sh_term": np.zeros((capacity, s), dtype=np.int32),
        "sh_boost": np.zeros((capacity, s), dtype=np.float32),
        "min_count": np.zeros(capacity, dtype=np.int32),
        "max_count": np.zeros(capacity, dtype=np.int32),
        "party": np.zeros(capacity, dtype=np.int32),
        "pool_id": np.zeros(capacity, dtype=np.int32),
        "created": np.zeros(capacity, dtype=np.int32),  # monotone seq
        "flags": np.zeros(capacity, dtype=np.int32),
    }


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter(pool: dict, idx: jnp.ndarray, rows: dict) -> dict:
    return {k: pool[k].at[idx].set(rows[k]) for k in pool}


@functools.partial(jax.jit, donate_argnums=(0,))
def _invalidate(flags: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Clear slots by flags alone — a removal needs no row data, so the
    H2D payload is 4 bytes/slot instead of a full ~600-byte empty row
    (matched-ticket churn at the 100k bench is ~50k removals/interval).
    Takes the flags column only, so prewarming one removal bucket costs
    a scratch column, not a scratch pool."""
    return flags.at[idx].set(0)


class _SlotOfView:
    """Read-only mapping view of ticket id -> slot (PoolBuffer compat)."""

    def __init__(self, store):
        self._store = store

    def __getitem__(self, ticket_id: str) -> int:
        slot = self._store.slot_by_id(ticket_id)
        if slot is None:
            raise KeyError(ticket_id)
        return slot

    def get(self, ticket_id: str, default=None):
        slot = self._store.slot_by_id(ticket_id)
        return default if slot is None else slot

    def __contains__(self, ticket_id: str) -> bool:
        return self._store.slot_by_id(ticket_id) is not None

    def __len__(self) -> int:
        return len(self._store)


class PoolBuffer:
    """Slot-allocated, device-resident ticket pool with queued updates.

    Updates flush eagerly in chunks as tickets stream in (`flush_chunk`),
    so the H2D transfer rides the gaps between intervals instead of the
    interval critical path; `flush()` at interval start only pushes the
    partial tail. `on_flush(stacked_rows)` lets the backend observe value
    distributions (bucket-grid maintenance for the MXU kernel) off the
    critical path too."""

    def __init__(
        self,
        capacity: int,
        fn: int,
        fs: int,
        s: int,
        d: int = 16,
        flush_chunk: int = 2048,
        on_flush=None,
        sharding=None,
    ):
        self.capacity = capacity
        self.fn, self.fs, self.s, self.d = fn, fs, s, d
        self.flush_chunk = flush_chunk
        self.on_flush = on_flush
        self.sharding = sharding
        host = pool_schema(capacity, fn, fs, s, d)
        if sharding is not None:
            # Slot axis sharded over the mesh; scatters preserve placement
            # via jit out_shardings below.
            self.device = {
                k: jax.device_put(v, sharding) for k, v in host.items()
            }
            self._scatter = jax.jit(
                lambda pool, idx, rows: {
                    k: pool[k].at[idx].set(rows[k]) for k in pool
                },
                donate_argnums=(0,),
                out_shardings=sharding,
            )
            self._invalidate = jax.jit(
                _invalidate.__wrapped__,
                donate_argnums=(0,),
                out_shardings=sharding,
            )
        else:
            self.device = jax.tree.map(jnp.asarray, host)
            self._scatter = _scatter
            self._invalidate = _invalidate
        # HBM ledger: the pool columns are the process's largest
        # device-resident allocation — one owner row (plus a per-device
        # row each when sharded over a mesh), refreshed on load()
        # (capacity is fixed, so alloc time is the whole story).
        self._ledger_pool_bytes()
        # Slot allocation lives in the caller's SlotStore (store.py) so
        # host metadata, reverse maps, and device rows share one slot
        # space; this buffer only stages device-row updates by slot.
        self.high_water = 0
        # Adds stage COLUMNAR into preallocated [chunk, ...] buffers at
        # add() time — re-stacking a chunk of per-ticket row dicts at
        # flush measured ~20-25ms/interval of pure np.stack. Removals
        # batch as raw slot arrays (the matched-churn path hands us ~100k
        # slots/interval). A removal of a just-staged add voids its
        # staging position (slot -1, compressed out at flush); adds after
        # removal of the same slot are resolved by flush order
        # (invalidate first, then scatter).
        self._stage = {
            k: np.empty((flush_chunk,) + v.shape[1:], v.dtype)
            for k, v in host.items()
        }
        self._stage_slots = np.full(flush_chunk, -1, dtype=np.int32)
        self._stage_n = 0
        self._stage_pos: dict[int, int] = {}  # slot -> staging row
        self._pending_add_mask = np.zeros(capacity, dtype=bool)
        self._pending_rm: list[np.ndarray] = []
        self._pending_rm_n = 0
        self.store = None  # SlotStore, bound by the backend at attach

    def _ledger_pool_bytes(self):
        """Refresh the pool's HBM ledger rows: the process-wide total,
        and — when the slot axis shards over a mesh — one row per mesh
        device so "which chip holds how much pool" is a ledger read."""
        total = sum(int(v.nbytes) for v in self.device.values())
        DEVOBS.mem_set("matchmaker.pool", total)
        if self.sharding is None:
            return
        try:
            devs = list(self.sharding.mesh.devices.flat)
        except Exception:
            return
        for d in devs:
            DEVOBS.mem_set(
                f"matchmaker.pool.dev{d.id}", total // len(devs)
            )

    def __len__(self) -> int:
        return len(self.store) if self.store is not None else 0

    @property
    def slot_of(self):
        """Compat mapping view: ticket id -> slot via the SlotStore."""
        return _SlotOfView(self.store)

    def add(self, slot: int, row: dict[str, np.ndarray]):
        if self._stage_n >= self.flush_chunk:
            self.flush()
        self.high_water = max(self.high_water, slot + 1)
        old = self._stage_pos.get(slot)
        if old is not None:  # re-staged before flush: void the old row
            self._stage_slots[old] = -1
        pos = self._stage_n
        for k, v in row.items():
            self._stage[k][pos] = v
        self._stage_slots[pos] = slot
        self._stage_pos[slot] = pos
        self._stage_n = pos + 1
        self._pending_add_mask[slot] = True

    def remove_slots(self, slots: np.ndarray):
        """Bulk removal by slot array — O(1) Python ops per call."""
        if len(slots) == 0:
            return
        slots = np.asarray(slots, dtype=np.int32)
        staged = slots[self._pending_add_mask[slots]]
        for s in staged:  # rare: removed before its add ever flushed
            pos = self._stage_pos.pop(int(s), None)
            if pos is not None:
                self._stage_slots[pos] = -1
        if len(staged):
            self._pending_add_mask[staged] = False
        self._pending_rm.append(slots)
        self._pending_rm_n += len(slots)
        # No flush trigger: staged removals are index arrays (tiny), and
        # deferring the invalidate scatter to the idle-gap/next-dispatch
        # flush keeps the ~25ms device round-trip off the interval's
        # matched-removal tail. Correctness needs rm applied before the
        # next kernel pass, and every dispatch flushes first.

    def snapshot(self) -> dict:
        """Checkpoint view of the device pool (recovery.py): ONE D2H
        fetch per column, sliced to the high-water mark so the blob
        scales with occupancy, not capacity. The caller must flush()
        first so staged adds are included; staged removals are already
        reflected in the caller's liveness masks, which gate restore-
        side validity (a dead row's stale contents are never scored —
        FLAG_VALID aside, the store's alive mask rules dispatch)."""
        hw = self.high_water
        columns = {
            k: np.ascontiguousarray(np.asarray(v)[:hw])
            for k, v in self.device.items()
        }
        DEVOBS.transfer(
            "pool.snapshot", "d2h",
            sum(int(v.nbytes) for v in columns.values()),
        )
        return {"high_water": hw, "columns": columns}

    def load(self, snap: dict) -> None:
        """Warm-restart restore: rebuild the device-resident pool from a
        snapshot with one host template fill + one device_put per
        column (sharded placement preserved) — the bulk `re-device_put`
        path, instead of ~pool_size re-staged scatter rows."""
        hw = int(snap["high_water"])
        if hw > self.capacity:
            raise ValueError(
                f"snapshot high_water {hw} > capacity {self.capacity}"
            )
        host = pool_schema(self.capacity, self.fn, self.fs, self.s, self.d)
        for k, v in snap["columns"].items():
            host[k][:hw] = v
        if self.sharding is not None:
            self.device = {
                k: jax.device_put(v, self.sharding)
                for k, v in host.items()
            }
        else:
            self.device = jax.tree.map(jnp.asarray, host)
        total = sum(int(v.nbytes) for v in self.device.values())
        DEVOBS.transfer("pool.load", "h2d", total)
        self._ledger_pool_bytes()
        self.high_water = hw
        # Staging state resets with the buffers it described.
        self._stage_slots[:] = -1
        self._stage_n = 0
        self._stage_pos.clear()
        self._pending_add_mask[:] = False
        self._pending_rm = []
        self._pending_rm_n = 0

    def prewarm(self):
        """Compile both add-scatter pad shapes (small tail + full chunk)
        and every removal pad bucket on a daemon thread: the first
        naturally-occurring small tail otherwise pays its multi-second
        XLA compile inside a timed interval, and a removal count that
        first falls in a new power-of-two bucket did the same whenever
        it came (on the chip: interval 11 of a run whose other programs
        came from the compile cache, PR 21). The jit cache is
        process-wide; the dummy scatters touch scratch arrays only."""
        if getattr(self, "_prewarmed", False) or self.sharding is not None:
            # Sharded pools: a scratch clone would donate unsharded
            # buffers into the sharded scatter (warning + no reuse);
            # the mesh path tolerates the one-off compile instead.
            return
        self._prewarmed = True
        import threading

        scatter, invalidate = self._scatter, self._invalidate
        shapes = {k: (v.shape, v.dtype) for k, v in self.device.items()}

        def _warm():
            try:
                # Compile-watch: the whole prewarm body (the scratch
                # jnp.zeros fills compile tiny programs too) attributes
                # as EXPECTED compiles — prewarming is the cure for
                # hot-path recompiles, never flagged as one.
                with DEVOBS.device_call(
                    "matchmaker.scatter", expect_compile=True
                ):
                    for u_pad in (max(256, self.flush_chunk // 4),
                                  self.flush_chunk):
                        # Scratch pool of identical shapes: the jit
                        # cache keys on abstract signatures, so the
                        # compile carries over to the real pool while
                        # self.device (donated by real flushes) is
                        # never touched off-thread.
                        scratch = {
                            k: jnp.zeros(shp, dt)
                            for k, (shp, dt) in shapes.items()
                        }
                        idx = jnp.zeros(u_pad, dtype=jnp.int32)
                        rows = {
                            k: jnp.zeros((u_pad,) + shp[1:], dt)
                            for k, (shp, dt) in shapes.items()
                        }
                        out = scatter(scratch, idx, rows)
                        jax.block_until_ready(out)
                    shp, dt = shapes["flags"]
                    for u_pad in self._removal_buckets():
                        jax.block_until_ready(invalidate(
                            jnp.zeros(shp, dt),
                            jnp.zeros(u_pad, dtype=jnp.int32),
                        ))
            except Exception as e:
                # One-shot: a persistent failure (device OOM on the
                # scratch clone) must not silently re-spawn an allocating
                # thread every flush. The real flush then just pays its
                # own compile.
                import logging

                logging.getLogger("nakama_tpu.matchmaker").warning(
                    "pool scatter prewarm failed: %s", e
                )

        self._prewarm_thread = threading.Thread(target=_warm, daemon=True)
        self._prewarm_thread.start()

    def _removal_pad(self, u: int) -> int:
        """Everything at or under one chunk pads to exactly the chunk
        size: ONE compiled scatter shape covers the steady state (pow2
        buckets above that). Distinct pow2 tails were costing a ~1.3s XLA
        compile on scattered intervals, dominating the bench p99."""
        if u <= self.flush_chunk:
            return self.flush_chunk
        return 1 << (u - 1).bit_length()

    def _removal_buckets(self) -> list[int]:
        """Every size `_removal_pad` can return for this pool."""
        top = self._removal_pad(self.capacity)
        out = [self.flush_chunk]
        b = 1 << self.flush_chunk.bit_length()  # first pow2 above a chunk
        while b <= top:
            out.append(b)
            b *= 2
        return out

    def join_prewarm(self, timeout=None):
        t = getattr(self, "_prewarm_thread", None)
        if t is not None and t.is_alive():
            t.join(timeout)

    def flush(self):
        """Apply queued updates: one flags-invalidate scatter for removals
        (4B/slot) + one row scatter for adds, removals first so a freed
        slot re-added in the same window ends up live.

        Counts are padded to a power of two (repeating the last entry — an
        idempotent duplicate write) so XLA compiles one scatter per size
        bucket instead of one per distinct update count."""
        if self._stage_n == 0 and not self._pending_rm:
            return
        if not getattr(self, "_prewarmed", False):
            self.prewarm()
        rm_parts = self._pending_rm
        self._pending_rm = []
        self._pending_rm_n = 0

        if rm_parts:
            rm = np.concatenate(rm_parts).astype(np.int32, copy=False)
            u = len(rm)
            u_pad = self._removal_pad(u)
            idx = np.empty(u_pad, dtype=np.int32)
            idx[:u] = rm
            idx[u:] = rm[-1]
            with DEVOBS.device_call("matchmaker.scatter"):
                self.device = dict(
                    self.device,
                    flags=self._invalidate(
                        self.device["flags"], jnp.asarray(idx)
                    ),
                )
            DEVOBS.transfer("pool.flush", "h2d", int(idx.nbytes))

        n = self._stage_n
        if n:
            valid = self._stage_slots[:n] >= 0
            idx_v = self._stage_slots[:n][valid]
            u = len(idx_v)
            self._stage_n = 0
            self._stage_pos = {}
            if u:
                self._pending_add_mask[idx_v] = False
                # Small tail bucket: the interval-start tail flush is
                # usually a few hundred rows; padding those to the full
                # chunk measured ~2/3 of the flush span. Two compiled
                # scatter shapes total (small, chunk).
                small = max(256, self.flush_chunk // 4)
                u_pad = small if u <= small else self.flush_chunk
                idx = np.empty(u_pad, dtype=np.int32)
                idx[:u] = idx_v
                idx[u:] = idx_v[-1]
                stacked = {}
                for k, buf in self._stage.items():
                    arr = buf[:n][valid]
                    padded = np.empty(
                        (u_pad,) + arr.shape[1:], dtype=arr.dtype
                    )
                    padded[:u] = arr
                    padded[u:] = arr[-1]
                    stacked[k] = padded
                with DEVOBS.device_call("matchmaker.scatter"):
                    self.device = self._scatter(
                        self.device,
                        jnp.asarray(idx),
                        jax.tree.map(jnp.asarray, stacked),
                    )
                DEVOBS.transfer(
                    "pool.flush", "h2d",
                    int(idx.nbytes)
                    + sum(int(v.nbytes) for v in stacked.values()),
                )
                if self.on_flush is not None:
                    self.on_flush(stacked)


def _accepts(qrow: dict, fcol: dict, with_should: bool):
    """Does each q-side ticket's query accept each f-side ticket's
    properties? Returns (ok [Bc, Br], score [Bc, Br] or 0.0).

    qrow arrays are [Br, ...], fcol arrays are [Bc, ...]; outputs orient
    feature-axis first."""
    num = fcol["num"][:, None, :]  # [Bc, 1, Fn]
    ok_num = jnp.all(
        (num >= qrow["n_lo"][None])
        & (num <= qrow["n_hi"][None])
        & ~((num >= qrow["n_flo"][None]) & (num <= qrow["n_fhi"][None])),
        axis=-1,
    )  # [Bc, Br]
    sv = fcol["str"][:, None, :]  # [Bc, 1, Fs]
    req = qrow["s_req"][None]
    forb = qrow["s_forb"][None]
    ok_str = jnp.all(
        ((req == 0) | (sv == req)) & ((forb == 0) | (sv != forb)), axis=-1
    )
    flags = qrow["flags"][None]  # [1, Br]
    ok = ok_num & ok_str & ((flags & FLAG_NEVER) == 0)

    if not with_should:
        return ok, jnp.float32(0.0)

    # Should slots: gather candidate values per slot — only compiled in when
    # the pool actually contains should queries.
    op = qrow["sh_op"][None]  # [1, Br, S]
    numvals = jnp.take(fcol["num"], qrow["sh_fld"], axis=1)  # [Bc, Br, S]
    strvals = jnp.take(fcol["str"], qrow["sh_fld"], axis=1)
    sat = jnp.where(
        op == SOP_NUM_RANGE,
        (numvals >= qrow["sh_lo"][None]) & (numvals <= qrow["sh_hi"][None]),
        jnp.where(
            op == SOP_STR_EQ,
            (strvals == qrow["sh_term"][None]) & (qrow["sh_term"][None] != 0),
            op == SOP_ALL,
        ),
    )
    used = op != SOP_UNUSED
    should_any = jnp.any(used & sat, axis=-1)
    score = jnp.sum(qrow["sh_boost"][None] * jnp.where(sat & used, 1.0, 0.0), axis=-1)
    has_must = (flags & FLAG_HAS_MUST) != 0
    has_should = (flags & FLAG_HAS_SHOULD) != 0
    ok = ok & (has_must | ~has_should | should_any)
    return ok, score


def _block_eval(
    row, col, row_slot, col_base, rev: bool, with_should: bool,
    with_embedding: bool, created_base=0,
):
    """Score one (row-block, column-block) pair → scores [Br, Bc]
    (−inf = ineligible)."""
    bc = col["num"].shape[0]

    ok, score = _accepts(row, col, with_should)  # [Bc, Br]
    if rev:
        rev_ok, _ = _accepts(col, row, with_should)  # [Br, Bc]
        ok = ok & rev_ok.T
    if with_embedding:
        # Skill-similarity scoring on the MXU (BASELINE.md config 3): higher
        # dot product = better-matched candidates.
        score = score + jnp.einsum(
            "cd,rd->cr", col["emb"], row["emb"]
        )

    # Count-range compatibility + party/self/validity (reference
    # matchmaker_process.go:65-85) + shared-batch pool masking.
    col_valid = (col["flags"] & FLAG_VALID) != 0  # [Bc]
    minmax_ok = (col["min_count"][:, None] >= row["min_count"][None]) & (
        col["max_count"][:, None] <= row["max_count"][None]
    )
    party_ok = (row["party"][None] == 0) | (
        col["party"][:, None] != row["party"][None]
    )
    pool_ok = col["pool_id"][:, None] == row["pool_id"][None]
    col_idx = col_base + jnp.arange(bc, dtype=jnp.int32)
    not_self = col_idx[:, None] != row_slot[None]

    eligible = (
        ok & col_valid[:, None] & minmax_ok & party_ok & pool_ok & not_self
    )
    age = (col["created"][:, None] - created_base).astype(jnp.float32)
    score = score - age * CREATED_EPS
    return jnp.where(eligible, score, NEG_INF).T  # [Br, Bc]


def scan_columns(
    pool_view: dict,
    row: dict,
    row_slots,
    row_valid,
    *,
    k: int,
    br: int,
    bc: int,
    n_col_blocks: int,
    col_base0,
    rev: bool,
    with_should: bool,
    with_embedding: bool,
    varying_axis: str | None = None,
    created_base=0,
):
    """Stream column blocks of `pool_view` against one row block, carrying a
    running top-k. Shared by the single-device kernel and the mesh-sharded
    path (which passes its shard offset as col_base0 and names its mesh axis
    so the carry is marked device-varying for shard_map)."""

    def col_step(state, cb):
        best_s, best_i = state
        col = {
            key: jax.lax.dynamic_slice_in_dim(v, cb * bc, bc, axis=0)
            for key, v in pool_view.items()
        }
        s = _block_eval(
            row, col, row_slots, col_base0 + cb * bc, rev, with_should,
            with_embedding, created_base,
        )
        s = jnp.where(row_valid[:, None], s, NEG_INF)
        idx = col_base0 + cb * bc + jnp.arange(bc, dtype=jnp.int32)
        cat_s = jnp.concatenate([best_s, s], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(idx, (br, bc))], axis=1
        )
        new_s, sel = jax.lax.top_k(cat_s, k)
        new_i = jnp.take_along_axis(cat_i, sel, axis=1)
        return (new_s, new_i), None

    init = (
        jnp.full((br, k), NEG_INF),
        jnp.full((br, k), -1, dtype=jnp.int32),
    )
    if varying_axis is not None:
        init = jax.lax.pcast(init, (varying_axis,), to="varying")
    (best_s, best_i), _ = jax.lax.scan(
        col_step, init, jnp.arange(n_col_blocks)
    )
    return best_s, best_i


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "br", "bc", "rev", "n_cols", "with_should", "with_embedding",
    ),
)
def topk_candidates(
    pool: dict,
    active_slots: jnp.ndarray,  # i32 [A_pad], padded with -1
    *,
    k: int,
    br: int,
    bc: int,
    rev: bool,
    n_cols: int,
    with_should: bool,
    with_embedding: bool = False,
    created_base: jnp.ndarray | int = 0,
):
    """For each active ticket, the top-k eligible candidates by
    (score desc, created asc): returns (scores [A_pad, k], slots [A_pad, k]
    with -1 for empty). Only the first n_cols pool slots are scanned (the
    bucketed high-water mark)."""
    pool = {key: v[:n_cols] for key, v in pool.items()}
    a_pad = active_slots.shape[0]
    n_row_blocks = a_pad // br
    n_col_blocks = n_cols // bc

    def row_block(rb):
        slots = jax.lax.dynamic_slice_in_dim(active_slots, rb * br, br)
        safe = jnp.maximum(slots, 0)
        row = {k_: v[safe] for k_, v in pool.items()}
        best_s, best_i = scan_columns(
            pool,
            row,
            safe,
            slots >= 0,
            k=k,
            br=br,
            bc=bc,
            n_col_blocks=n_col_blocks,
            col_base0=0,
            rev=rev,
            with_should=with_should,
            with_embedding=with_embedding,
            created_base=created_base,
        )
        best_i = jnp.where(best_s > NEG_INF, best_i, -1)
        return best_s, best_i

    scores, idxs = jax.lax.map(row_block, jnp.arange(n_row_blocks))
    return scores.reshape(a_pad, k), idxs.reshape(a_pad, k)


def pad_to(x: np.ndarray, size: int, fill) -> np.ndarray:
    if x.shape[0] == size:
        return x
    out = np.full((size, *x.shape[1:]), fill, dtype=x.dtype)
    out[: x.shape[0]] = x
    return out
