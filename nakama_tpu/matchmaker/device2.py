"""Two-stage MXU matchmaker kernel for large pools.

The round-1 kernel (device.py) evaluates eligibility with per-field VPU
compares and carries a running top-K through a per-block sort — profiling on
the real chip showed the sort alone is >50% of device time and the whole
pass is VPU-bound. This module re-frames the scan the way TPU retrieval
systems do (VERDICT round 1 weak #2):

Stage 1 (Pallas, MXU): eligibility as a matmul. Every ticket's properties
are encoded on device into a bucketed 0/1 vector v (one-hot value buckets
per numeric field from a per-field grid, hashed buckets per string field,
pool-id plane); every query into an allowed-bucket mask u (conservative:
any bucket intersecting the allowed interval is set). Then
``dot(u_i, v_j) == F`` (F = number of field planes) is a *necessary*
condition for ticket j passing query i — the O(A·N·D) work runs on the
systolic array in bfloat16 instead of the VPU. A fused epilogue packs
(priority << 18 | column) into one int32 and keeps only the per-column-block
argmax per row, so the N×N score matrix never leaves VMEM and no sort runs
at all. Per-pair jitter decorrelates equal-priority candidates across rows
— without it every row's top-K collapses onto the same oldest tickets and
the greedy assembler starves (round-1: only ~3k of 100k eligible entries
matched per interval).

Stage 2 (XLA): the per-block winners (n_col_blocks per row, ~64-128 at
bench size) are gathered and re-checked *exactly* — full interval/term/
forbidden compares, count-range, party/self/pool/validity, mutual (rev)
when on, exact should-boost and embedding scores — then lexicographically
sorted by (-score, created) on device. Stage-1 false positives die here.
A candidate is gathered as rows only: its `num`, `str` and `emb` rows
from the pool's row tables, its six scalar columns (RECORD_KEYS) as one
row of a record built from the pool once per dispatch, and under rev its
query mirrors on that row and one f32 row beside it. On a v5e a gather
costs by the index, not by the word: a word from a 1-D column 7.45 ns, a
row of 6 to 128 words 1.8 ns (PR 24's, PR 25's and PR 34's traces). Six
one-word gathers for each of 16.8 million candidates were 0.735 s of a
1.14 s pass; the record row is 0.030 s (PERF.md, PR 25).
Stage 1's eligibility test is a superset filter, so it never rejects a
true candidate; its per-block argmax can still drop one, when false
positives of the same block outrank it (a required string term whose
hash shares bucket 0 with tickets that lack the property makes the whole
pool look eligible). Such a row waits for a later interval.

The candidate lists feed the same native greedy assembler as the small-pool
path. Reference hot loop replaced: server/matchmaker_process.go:27-334.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .device import FLAG_NEVER, FLAG_VALID
from .device import _accepts  # exact per-field predicate (block form)

NUM_BUCKETS = 16  # per numeric field
STR_BUCKETS = 8  # per string field
POOL_BUCKETS = 8  # pool-id plane
COL_BITS = 18  # column index bits in the packed winner word
MAX_COLS = 1 << COL_BITS
PRIO_MAX = 8191  # 13-bit priority
JITTER_AMP = 256  # selection-jitter range (stays below 1 emb-score unit)
EMB_SCORE_SCALE = 256.0  # stage-1 embedding-score quantisation
PACKED_NONE = -(2**31)  # plain int: pallas kernels must not capture arrays
# Mosaic's scoped-VMEM limit for one kernel on a v5e (the compiler's
# default; a kernel over it is refused at compile time, not at run time).
VMEM_LIMIT_BYTES = 16 << 20
MIN_ROW_TILE = 128
# Stage 2 re-ranks the active rows in stripes sized so one stripe's
# candidate gather (per kept winner: its record row and a row of every
# table in _stage2_tables) stays under this many bytes: its temporaries
# then do not grow with the pool (un-striped, a 131072-row dispatch
# needs 9 GB of HBM without mutual matching and 19 GB with it).
STAGE2_GATHER_BYTES = 256 << 20

# Every pool field the row (query) side of the kernels reads.
ROWQ_KEYS = (
    "n_lo", "n_hi", "n_flo", "n_fhi", "s_req", "s_forb",
    "min_count", "max_count", "pool_id", "flags", "party",
    "num", "str", "emb", "created",
    "sh_op", "sh_fld", "sh_lo", "sh_hi", "sh_term", "sh_boost",
)


def encoding_dims(fn: int, fs: int) -> int:
    return fn * NUM_BUCKETS + fs * STR_BUCKETS + POOL_BUCKETS


# --------------------------------------------------------------- stage 1


def _bucket_of(x, grid_lo, grid_inv):
    """Bucket index of `x` per numeric field → i32, clipped to [0, NB-1].

    The ONE bucketing expression used for both value encoding and query
    mask bounds: it is monotone non-decreasing in x (f32 sub/mul by a
    positive constant and trunc are all monotone), so computing the query's
    allowed range as [bucket_of(lo), bucket_of(hi)] is guaranteed to cover
    the bucket of every value in [lo, hi] — the stage-1 superset property
    holds bit-for-bit, with no separately-rounded edge reconstruction."""
    t = (x - grid_lo[None]) * grid_inv[None] * NUM_BUCKETS
    # f32->i32 conversion of out-of-range values (±FULL bounds can overflow
    # to inf after the multiply) is implementation-defined in XLA; clamp in
    # float first. Applied identically on both sides, so monotone
    # consistency is preserved.
    t = jnp.clip(t, -2.0**30, 2.0**30)
    return jnp.clip(t.astype(jnp.int32), 0, NUM_BUCKETS - 1)


def _value_vectors(pool, n, fn, fs, grid_lo, grid_inv):
    """Bucket one-hot encodings of candidate values → [n, D] bf16."""
    num = pool["num"][:n]  # [n, fn]
    b = _bucket_of(num, grid_lo, grid_inv)
    oh_num = (
        b[:, :, None] == jnp.arange(NUM_BUCKETS, dtype=jnp.int32)[None, None]
    )
    sb = pool["str"][:n] & (STR_BUCKETS - 1)
    oh_str = (
        sb[:, :, None] == jnp.arange(STR_BUCKETS, dtype=jnp.int32)[None, None]
    )
    pb = pool["pool_id"][:n] & (POOL_BUCKETS - 1)
    oh_pool = pb[:, None] == jnp.arange(POOL_BUCKETS, dtype=jnp.int32)[None]
    valid = ((pool["flags"][:n] & FLAG_VALID) != 0)[:, None]
    v = jnp.concatenate(
        [
            oh_num.reshape(n, fn * NUM_BUCKETS),
            oh_str.reshape(n, fs * STR_BUCKETS),
            oh_pool,
        ],
        axis=1,
    )
    return (v & valid).astype(jnp.bfloat16)


def _query_vectors(q, fn, fs, grid_lo, grid_inv, with_counts=True):
    """Allowed-bucket masks of queries → [rows, D] bf16. `q` carries n_lo,
    n_hi, n_flo, n_fhi, s_req, min_count, max_count, pool_id, flags; any
    bucket that *could* contain an accepted value is set (conservative).

    `with_counts=False` for the reverse (mutual) direction: count-range
    compatibility is a forward candidate-search filter only, NOT part of
    mutual query acceptance (oracle _mutual checks queries alone)."""
    rows = q["n_lo"].shape[0]
    n_lo, n_hi = q["n_lo"], q["n_hi"]
    if with_counts:
        # Count-range compatibility as builtin-column bounds (reference
        # appends min_count/max_count clauses to every search,
        # server/matchmaker_process.go:65-85): candidate.min_count >= mine
        # and candidate.max_count <= mine. Builtin columns 0 and 1
        # (compile.py BUILTIN_NUMERIC order).
        n_lo = n_lo.at[:, 0].max(q["min_count"].astype(jnp.float32))
        n_hi = n_hi.at[:, 1].min(q["max_count"].astype(jnp.float32))

    bt = jnp.arange(NUM_BUCKETS, dtype=jnp.int32)[None, None]
    b_lo = _bucket_of(n_lo, grid_lo, grid_inv)[:, :, None]
    b_hi = _bucket_of(n_hi, grid_lo, grid_inv)[:, :, None]
    allowed = (bt >= b_lo) & (bt <= b_hi)  # [rows, fn, NB]
    # Buckets strictly between the forbidden bounds' buckets hold only
    # forbidden values (monotonicity of _bucket_of); the boundary buckets
    # themselves may straddle, so they stay allowed (conservative). Empty
    # intervals (flo > fhi) cut nothing since b(flo) >= b(fhi).
    bf_lo = _bucket_of(q["n_flo"], grid_lo, grid_inv)[:, :, None]
    bf_hi = _bucket_of(q["n_fhi"], grid_lo, grid_inv)[:, :, None]
    allowed = allowed & ~((bt > bf_lo) & (bt < bf_hi))

    req = q["s_req"]  # [rows, fs]; 0 = unconstrained
    oh_req = (req & (STR_BUCKETS - 1))[:, :, None] == jnp.arange(
        STR_BUCKETS, dtype=jnp.int32
    )[None, None]
    str_allowed = jnp.where(req[:, :, None] == 0, True, oh_req)

    pool_allowed = (q["pool_id"] & (POOL_BUCKETS - 1))[:, None] == jnp.arange(
        POOL_BUCKETS, dtype=jnp.int32
    )[None]

    u = jnp.concatenate(
        [
            allowed.reshape(rows, fn * NUM_BUCKETS),
            str_allowed.reshape(rows, fs * STR_BUCKETS),
            pool_allowed,
        ],
        axis=1,
    )
    live = (q["flags"] & FLAG_NEVER) == 0
    return (u & live[:, None]).astype(jnp.bfloat16)


def _mix(x):
    x = x * jnp.int32(-1640531527)  # Knuth multiplicative hash
    return x ^ (x >> 13)


def _stage1_kernel(
    uq_ref,
    vv_ref,
    col_mix_ref,
    col_gidx_ref,
    row_mix_ref,
    row_slot_ref,
    ue_ref,
    ve_ref,
    uv_ref,
    vq_ref,
    out_ref,
    *,
    f_tot: float,
    bn: int,
    m: int,
    out_w: int,
    with_embedding: bool,
    rev: bool,
):
    s = jax.lax.dot_general(
        uq_ref[:],
        vv_ref[:],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bm, bn]
    elig = s > (f_tot - 0.5)
    if rev:
        s2 = jax.lax.dot_general(
            uv_ref[:],
            vq_ref[:],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        elig = elig & (s2 > (f_tot - 0.5))

    # Pure per-(row, col) jitter priority: candidate selection must be
    # row-decorrelated or every row's winners collapse onto the same
    # tickets and the greedy assembler starves (the reference avoids this
    # by deleting matched tickets mid-iteration — impossible in one batch).
    # Wait-time fairness is preserved elsewhere: the assembler processes
    # actives oldest-first and stage 2 orders each row's candidates by
    # exact (-score, created).
    jit = (row_mix_ref[:] ^ col_mix_ref[:]) & (JITTER_AMP - 1)  # [bm, bn]
    prio = 4096 - jit
    if with_embedding:
        # Exact-scored pools: similarity dominates the jitter.
        score = jax.lax.dot_general(
            ue_ref[:],
            ve_ref[:],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        bump = jnp.clip(
            score * EMB_SCORE_SCALE, -4095.0, 4095.0
        ).astype(jnp.int32)
        prio = jnp.clip(prio + bump, 0, PRIO_MAX)

    j = pl.program_id(1)
    # GLOBAL column ids come in as data (not derived from the grid
    # position): under the mesh each device's grid walks only its column
    # shard, but the packed winner words and the self-exclusion compare
    # must use pool-global slot ids so the cross-device merge and stage-2
    # gather see one coherent index space.
    col = col_gidx_ref[:]  # [1, bn] -> broadcasts against [bm, bn]
    not_self = col != row_slot_ref[:]
    win = jnp.where(
        elig & not_self, (prio << COL_BITS) | col, jnp.int32(PACKED_NONE)
    )
    # Top-m winners per column block via iterated masked max (m is 1 for
    # big pools where the block count itself provides candidate width, and
    # grows for low-block-count pools). Packed words are unique per column,
    # so equality removes exactly the previous winner.
    #
    # The output block is one full-width [bm, out_w] row stripe revisited
    # across all column blocks (index map ignores j) — Mosaic requires the
    # lane dim of a block to be 128-divisible or array-width, so a narrow
    # per-block (bm, m) output is not lowerable. Each j deposits its m
    # winners into lanes [j*m, (j+1)*m) with a masked lane-select.
    @pl.when(j == 0)
    def _init():
        out_ref[:] = jnp.full_like(out_ref[:], PACKED_NONE)

    lane = jax.lax.broadcasted_iota(jnp.int32, (win.shape[0], out_w), 1)
    acc = out_ref[:]
    for t in range(m):
        cur = jnp.max(win, axis=1, keepdims=True)  # [bm, 1]
        if t + 1 < m:
            win = jnp.where(win == cur, jnp.int32(PACKED_NONE), win)
        acc = jnp.where(lane == j * m + t, cur, acc)
    out_ref[:] = acc


def stage1_row_tile(bm: int, bn: int, d: int, de: int, dq: int,
                    out_w: int) -> int:
    """Largest row tile <= `bm` (halving) whose stage-1 footprint fits
    the scoped-VMEM limit: every operand and output block double-buffered
    at its lane-padded size, plus the [bm, bn] 32-bit epilogue tiles the
    compiler keeps live (score, packed word, and at large grids most of
    a third — 1.75 tiles bounds every shape the v5e compiler was asked
    about, tests/test_chip_compile.py holds the shipped ones). Only the
    ROW tile moves: rows are independent and the jitter keys on the row
    index, so the winner set is the same at any `bm`; the column tile
    decides which per-block winners survive and stays as configured."""

    def lanes(w: int) -> int:
        return -(-w // 128) * 128

    def footprint(rows: int) -> int:
        blocks = (
            (rows + bn) * 2 * (lanes(d) + lanes(de) + lanes(dq))  # bf16
            + 2 * 8 * bn * 4  # col_mix, col_gidx: [1, bn] i32, 8 sublanes
            + 2 * rows * 128 * 4  # row_mix, row_slot: [rows, 1] i32
            + rows * lanes(out_w) * 4
        )
        return 2 * blocks + 7 * rows * bn

    tile = bm
    while tile > MIN_ROW_TILE and footprint(tile) > VMEM_LIMIT_BYTES:
        tile //= 2
    if footprint(tile) > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"stage-1 tile [{tile}, {bn}] at encoding width {d} (+{de}"
            f" embedding, +{dq} mutual) needs {footprint(tile)} bytes of"
            f" VMEM, over the {VMEM_LIMIT_BYTES} the kernel may use:"
            " lower numeric_fields/string_fields or the column block"
        )
    return tile


def stage1_plan(
    *, n: int, n_local: int, k: int, bm: int, bn: int, fn: int, fs: int,
    de: int, rev: bool,
) -> tuple[int, int, int]:
    """What one stage-1 launch does at these shapes: (winners kept per
    column block, lane width of its packed-winner output, row tile).
    `n` is the column extent the dispatch scores, `n_local` the part of
    it one launch sees (== `n` off the mesh), `de` the embedding operand
    width. Derived here only: the kernels, the mesh gather accounting
    and the dispatch breadcrumb all read it."""
    d = encoding_dims(fn, fs)
    # Enough total candidate width even when the pool spans few blocks.
    m = max(1, -(-2 * k // (n // bn)))
    out_w = -(-(n_local // bn * m) // 128) * 128  # lane dim: 128-aligned
    return m, out_w, stage1_row_tile(bm, bn, d, de, d if rev else 8, out_w)


def _stage1_call(
    uq, vv, col_mix, col_gidx, row_mix, row_slot, ue, ve, uv, vq,
    *,
    fn: int,
    fs: int,
    m: int,
    out_w: int,
    bm: int,
    bn: int,
    with_embedding: bool,
    rev: bool,
    interpret: bool,
    vma=None,
):
    """One pallas stage-1 launch over the column range held in `vv`
    (the whole pool unsharded; one device's shard under the mesh —
    `vma` names the mesh axes the output varies over in that case).
    `m`, `out_w` and the row tile `bm` come from `stage1_plan`. Returns
    packed per-block winners [a_pad, out_w]."""
    a_pad = uq.shape[0]
    n = vv.shape[0]
    d = encoding_dims(fn, fs)
    n_blocks = n // bn
    de = ue.shape[1]
    dq = uv.shape[1]
    kernel = functools.partial(
        _stage1_kernel,
        f_tot=float(fn + fs + 1),
        bn=bn,
        m=m,
        out_w=out_w,
        with_embedding=with_embedding,
        rev=rev,
    )
    return pl.pallas_call(
        kernel,
        grid=(a_pad // bm, n_blocks),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, de), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, de), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, dq), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, dq), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (bm, out_w), lambda i, j: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((a_pad, out_w), jnp.int32, vma=vma),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * a_pad * n * (d + (de if with_embedding else 0)),
            bytes_accessed=(a_pad + n) * d * 2 + a_pad * n_blocks * 4,
            transcendentals=0,
        ),
    )(uq, vv, col_mix, col_gidx, row_mix, row_slot, ue, ve, uv, vq)


@functools.partial(
    jax.jit,
    static_argnames=(
        "fn", "fs", "n_cols", "k", "rev", "with_should", "with_embedding",
        "bm", "bn", "interpret", "order_exact",
    ),
)
def topk_candidates_big(
    pool: dict,
    active_slots: jnp.ndarray,  # i32 [A_pad] padded with -1
    grid_lo: jnp.ndarray,  # f32 [fn]
    grid_inv: jnp.ndarray,  # f32 [fn]
    *,
    fn: int,
    fs: int,
    n_cols: int,
    k: int,
    rev: bool,
    with_should: bool,
    with_embedding: bool,
    bm: int = 1024,
    bn: int = 1024,
    interpret: bool = False,
    order_exact: bool = True,
):
    """Two-stage top-k: returns slots i32 [A_pad, k] ordered by exact
    (-score, created), -1 padded. Drop-in contract of
    device.topk_candidates minus the score output (the order already
    encodes it)."""
    assert n_cols <= MAX_COLS
    a_pad = active_slots.shape[0]
    n = n_cols

    pool_n = {key: v[:n] for key, v in pool.items()}
    safe = jnp.maximum(active_slots, 0)
    rowq = {key: pool_n[key][safe] for key in ROWQ_KEYS}

    vv = _value_vectors(pool_n, n, fn, fs, grid_lo, grid_inv)
    uq = _query_vectors(rowq, fn, fs, grid_lo, grid_inv)
    uq = uq * (active_slots >= 0).astype(jnp.bfloat16)[:, None]

    col_idx = jnp.arange(n, dtype=jnp.int32)
    col_gidx = col_idx[None]
    col_mix = _mix(col_idx + 1)[None]
    row_mix = _mix(jnp.arange(a_pad, dtype=jnp.int32) * 7919 + 13)[:, None]
    row_slot = safe[:, None]

    if with_embedding:
        ue = rowq["emb"].astype(jnp.bfloat16)
        ve = pool_n["emb"].astype(jnp.bfloat16)
    else:
        ue = jnp.zeros((a_pad, 8), jnp.bfloat16)
        ve = jnp.zeros((n, 8), jnp.bfloat16)
    if rev:
        uv = vv[safe]
        vq = _query_vectors(
            pool_n, fn, fs, grid_lo, grid_inv, with_counts=False
        )
    else:
        uv = jnp.zeros((a_pad, 8), jnp.bfloat16)
        vq = jnp.zeros((n, 8), jnp.bfloat16)

    m, out_w, tile = stage1_plan(
        n=n, n_local=n, k=k, bm=bm, bn=bn, fn=fn, fs=fs, de=ue.shape[1],
        rev=rev,
    )
    winners = _stage1_call(
        uq, vv, col_mix, col_gidx, row_mix, row_slot, ue, ve, uv, vq,
        fn=fn,
        fs=fs,
        m=m,
        out_w=out_w,
        bm=tile,
        bn=bn,
        with_embedding=with_embedding,
        rev=rev,
        interpret=interpret,
    )

    return _stage2(
        pool_n,
        rowq,
        active_slots,
        winners,
        k=k,
        rev=rev,
        with_should=with_should,
        with_embedding=with_embedding,
        order_exact=order_exact,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "axis", "fn", "fs", "k", "rev", "with_should",
        "with_embedding", "bm", "bn", "interpret",
    ),
)
def topk_candidates_big_sharded(
    pool: dict,  # [N, ...] arrays sharded along their slot axis
    active_slots: jnp.ndarray,  # i32 [A_pad] padded with -1
    grid_lo: jnp.ndarray,  # f32 [fn]
    grid_inv: jnp.ndarray,  # f32 [fn]
    *,
    mesh,
    axis: str = "pool",
    fn: int,
    fs: int,
    k: int,
    rev: bool,
    with_should: bool,
    with_embedding: bool,
    bm: int = 1024,
    bn: int = 1024,
    interpret: bool = False,
):
    """Mesh-sharded two-stage top-k (VERDICT r2 #2): stage 1 runs the MXU
    pallas kernel per device over ITS column shard of the pool, the packed
    per-block winners concatenate across devices (GSPMD inserts the ICI
    all_gather — winners are A_pad x out_w i32, orders of magnitude
    smaller than the score matrix), and ONE exact stage-2 re-rank runs on
    the merged set. Because the per-block winner count `m` derives from
    the GLOBAL block count and the packed words carry pool-global column
    ids, the merged winner SET is identical to the unsharded kernel's
    over the same column extent — sharding changes where the matmuls
    run, not what they select. The extent here is always the whole
    pool capacity; the single-device dispatch trims its own to the
    high-water bucket (tpu.py), so a part-filled pool has fewer blocks
    there and may keep more winners per block.

    Reference seam this replaces: the `node` string threaded through
    server/matchmaker.go:169-183 (cross-node matching absent in OSS)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = pool["num"].shape[0]
    n_dev = mesh.shape[axis]
    n_local = n // n_dev
    assert n_local % bn == 0, (n_local, bn)
    assert n <= MAX_COLS
    a_pad = active_slots.shape[0]

    # Row (query) side: gathered across shards by GSPMD, then replicated —
    # every device scores ALL active rows against its column shard.
    safe = jnp.maximum(active_slots, 0)
    rowq = {key: pool[key][safe] for key in ROWQ_KEYS}
    rep = NamedSharding(mesh, P())
    rowq = {
        key: jax.lax.with_sharding_constraint(v, rep)
        for key, v in rowq.items()
    }
    uq = _query_vectors(rowq, fn, fs, grid_lo, grid_inv)
    uq = uq * (active_slots >= 0).astype(jnp.bfloat16)[:, None]
    row_mix = _mix(jnp.arange(a_pad, dtype=jnp.int32) * 7919 + 13)[:, None]
    row_slot = safe[:, None]
    if with_embedding:
        ue = rowq["emb"].astype(jnp.bfloat16)
    else:
        ue = jnp.zeros((a_pad, 8), jnp.bfloat16)
    if rev:
        # Value vectors of the active rows == vv[safe] computed locally
        # from the gathered row data (same expression, no pool gather).
        uv = _value_vectors(rowq, a_pad, fn, fs, grid_lo, grid_inv)
    else:
        uv = jnp.zeros((a_pad, 8), jnp.bfloat16)
    m, out_w, tile = stage1_plan(
        n=n, n_local=n_local, k=k, bm=bm, bn=bn, fn=fn, fs=fs,
        de=ue.shape[1], rev=rev,
    )

    # Column side: per-shard constants carrying GLOBAL column ids.
    col_idx = jnp.arange(n, dtype=jnp.int32)
    col_gidx = col_idx[None]
    col_mix = _mix(col_idx + 1)[None]

    col_keys = ("num", "str", "pool_id", "flags") + (
        ("n_lo", "n_hi", "n_flo", "n_fhi", "s_req", "min_count",
         "max_count") if rev else ()
    )
    pool_cols = {key: pool[key] for key in sorted(set(col_keys))}

    def varying(x):
        return jax.lax.pcast(x, (axis,), to="varying")

    def per_device(pool_local, col_mix_l, col_gidx_l, uq, row_mix,
                   row_slot, ue, uv, grid_lo, grid_inv):
        # Replicated row-side inputs meet device-varying column data in
        # the kernel: mark them varying explicitly (vma typing).
        (uq, row_mix, row_slot, ue, uv, grid_lo, grid_inv) = varying(
            (uq, row_mix, row_slot, ue, uv, grid_lo, grid_inv)
        )
        nloc = pool_local["num"].shape[0]
        vv_l = _value_vectors(pool_local, nloc, fn, fs, grid_lo, grid_inv)
        if rev:
            vq_l = _query_vectors(
                pool_local, fn, fs, grid_lo, grid_inv, with_counts=False
            )
        else:
            vq_l = varying(jnp.zeros((nloc, 8), jnp.bfloat16))
        if with_embedding:
            ve_l = pool_local["emb"].astype(jnp.bfloat16)
        else:
            ve_l = varying(jnp.zeros((nloc, 8), jnp.bfloat16))
        win = _stage1_call(
            uq, vv_l, col_mix_l, col_gidx_l, row_mix, row_slot, ue,
            ve_l, uv, vq_l,
            fn=fn,
            fs=fs,
            m=m,
            out_w=out_w,
            bm=tile,
            bn=bn,
            with_embedding=with_embedding,
            rev=rev,
            interpret=interpret,
            vma=frozenset({axis}),
        )
        # Leading shard axis for the caller-side concat (same pattern as
        # parallel/mesh.py sharded_topk_rows).
        return win[None]

    if with_embedding:
        pool_cols["emb"] = pool["emb"]
    winners = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(axis), P(None, axis), P(None, axis), P(), P(), P(), P(),
            P(), P(), P(),
        ),
        out_specs=P(axis),
        # Pallas interpret mode (CPU tests) lifts kernel-body scalar
        # constants with empty vma and the checker rejects the mix — the
        # error text itself prescribes disabling the check as the
        # workaround. Real Mosaic lowering (TPU) keeps the check on.
        check_vma=not interpret,
    )(
        pool_cols, col_mix, col_gidx, uq, row_mix, row_slot, ue, uv,
        grid_lo, grid_inv,
    )  # [D, a_pad, out_w_local], sharded on dim 0
    # The merge: concat per-shard winner stripes along the lane axis.
    # GSPMD inserts the all_gather over ICI here; stage 2's top_k then
    # operates on the identical winner SET the unsharded kernel produces.
    winners = jnp.moveaxis(winners, 0, 1).reshape(a_pad, -1)

    pool_n = {key: v for key, v in pool.items()}
    return _stage2(
        pool_n,
        rowq,
        active_slots,
        winners,
        k=k,
        rev=rev,
        with_should=with_should,
        with_embedding=with_embedding,
    )


# --------------------------------------------------------------- stage 2


def _stage2(
    pool_n, rowq, active_slots, winners, *, k, rev, with_should,
    with_embedding, order_exact=True,
):
    """Exact re-rank of the per-block winners: [A_pad, B] packed → slots
    [A_pad, k] ordered by (-score, created). Rows are independent, so the
    pass runs stripe by stripe (lax.map) with identical output and
    temporaries bounded by the stripe, not by A_pad."""
    a_pad = active_slots.shape[0]
    keep = stage2_keep(winners.shape[1], k)
    stripe = stage2_stripe(
        a_pad, keep, stage2_words(pool_n, rev, with_should)
    )
    # Built once, before the stripes: the loop body only reads them.
    record = _stage2_record(pool_n, rev, with_should)
    mirror = _stage2_mirror(pool_n, with_should) if rev else None

    def rerank(args):
        rq, act, win = args
        return _stage2_rows(
            pool_n, record, mirror, rq, act, win, k=k, keep=keep,
            with_should=with_should, with_embedding=with_embedding,
            order_exact=order_exact,
        )

    if stripe == a_pad:
        return rerank((rowq, active_slots, winners))

    def striped(x):
        return x.reshape((a_pad // stripe, stripe) + x.shape[1:])

    out = jax.lax.map(
        rerank,
        (
            {key: striped(v) for key, v in rowq.items()},
            striped(active_slots),
            striped(winners),
        ),
    )
    return out.reshape(a_pad, k)


# A candidate's scalar columns, in the order its record row holds them
# (no padding: rows of 6, 8 and 16 words gather in the same time).
RECORD_KEYS = (
    "min_count", "max_count", "party", "pool_id", "flags", "created",
)
# A candidate's QUERY as `_accepts` reads it back against the searcher's
# values under rev (mutual): the int32 tables ride the record row after
# the scalars, the f32 tables are a row of their own (_stage2_mirror);
# the should slots join only when the pool holds should queries.
MIRROR_KEYS = ("n_lo", "n_hi", "n_flo", "n_fhi", "s_req", "s_forb")
SHOULD_KEYS = ("sh_op", "sh_fld", "sh_lo", "sh_hi", "sh_term", "sh_boost")


def _mirror_keys(with_should: bool) -> tuple[str, ...]:
    return MIRROR_KEYS + (SHOULD_KEYS if with_should else ())


def _stage2_tables(rev: bool, with_should: bool) -> list[str]:
    """Pool row tables ([n, w]) whose rows the exact checks read per
    candidate beside its record — the candidate's VALUES always, each a
    gather of its own; its QUERY mirrors only under rev (mutual), as
    spans of the record row and of the `_stage2_mirror` row."""
    return ["num", "str", "emb"] + (
        list(_mirror_keys(with_should)) if rev else []
    )


def stage2_keep(out_w: int, k: int) -> int:
    """Winners a row keeps of stage 1's `out_w` for the exact checks."""
    return min(out_w, max(2 * k, 8))  # see _stage2_rows


def stage2_words(pool, rev: bool, with_should: bool) -> int:
    """32-bit words stage 2 gathers per kept winner: its record row and
    a row of every table in `_stage2_tables`, whatever row carries it
    (`pool`: anything that maps a key to its [n, w] table)."""
    return len(RECORD_KEYS) + sum(
        pool[key].shape[1] for key in _stage2_tables(rev, with_should)
    )


def stage2_stripe(a_pad: int, keep: int, words: int) -> int:
    """Rows of one stage-2 stripe: `a_pad` halved until one stripe's
    candidate gather fits STAGE2_GATHER_BYTES."""
    stripe = a_pad
    while (
        stripe % 2 == 0
        and stripe * keep * words * 4 > STAGE2_GATHER_BYTES
    ):
        stripe //= 2
    return stripe


def _mirror_split(pool_n, with_should):
    """The mirror keys by the record that carries them: (int32, f32)."""
    keys = _mirror_keys(with_should)
    ints = [key for key in keys if pool_n[key].dtype == jnp.int32]
    return ints, [key for key in keys if key not in ints]


def stage2_shape(pool, a_pad, out_w, k, rev, with_should) -> dict:
    """The static shape `_stage2` runs at for a dispatch of `a_pad` rows
    with `out_w` stage-1 winners a row, under the names the dispatch
    record carries it by (tpu.py: the crumb's `kernel`, the cohort's
    ledger row): by the same functions, so it cannot drift from the
    program."""
    words = stage2_words(pool, rev, with_should)
    return dict(
        stage2_words=words,
        stage2_stripe_rows=stage2_stripe(a_pad, stage2_keep(out_w, k), words),
    )


def stage2_shape_of(variant: dict) -> dict:
    """`stage2_shape`'s keys of a dispatch record that has them."""
    return {
        key: variant[key]
        for key in ("stage2_words", "stage2_stripe_rows")
        if key in variant
    }


def _stage2_record(pool_n, rev=False, with_should=False):
    """int32 [n, W]: row j holds slot j's scalars (RECORD_KEYS) and,
    under rev, the int32 tables of its query mirror after them."""
    record = jnp.stack([pool_n[key] for key in RECORD_KEYS], axis=1)
    if not rev:
        return record
    ints, _ = _mirror_split(pool_n, with_should)
    return jnp.concatenate([record] + [pool_n[key] for key in ints], axis=1)


def _stage2_mirror(pool_n, with_should):
    """f32 [n, W]: row j holds the f32 tables of slot j's query mirror,
    key after key."""
    _, floats = _mirror_split(pool_n, with_should)
    return jnp.concatenate([pool_n[key] for key in floats], axis=1)


def _stage2_gather(pool_n, record, mirror, cand, with_should):
    """Everything the exact checks read of the candidates `cand`
    [R, B], by pool key → [R, B, ...]: one row gather per table, the
    scalars as columns of the gathered record block, the query mirrors
    (`mirror` is None without rev) as spans of the two record blocks
    and, under "mirror", the two blocks as they were gathered."""
    col = {key: pool_n[key][cand] for key in _stage2_tables(False, False)}
    block = record[cand]  # [R, B, W]
    n_rec = len(RECORD_KEYS)
    # Scalars come out of the gathered block [R, B, 6] along its second
    # axis: a slice of the last one leaves six [R, B, 1] blocks, each
    # tiled out to the size of the whole record block.
    rec = jnp.swapaxes(block[:, :, :n_rec], 1, 2)  # [R, 6, B]
    col.update({key: rec[:, i] for i, key in enumerate(RECORD_KEYS)})
    if mirror is not None:
        col["mirror"] = blocks = (block, mirror[cand])
        spans = zip(_mirror_split(pool_n, with_should), blocks, (n_rec, 0))
        for keys, rows, at in spans:
            for key in keys:
                w = pool_n[key].shape[1]
                col[key] = rows[:, :, at:at + w]
                at += w
    return col


def _stage2_rev_ok(col, rowq, with_should):
    """Does each candidate's own query accept its row's ticket (mutual
    matching)? [R, B]: `_accepts` with the two sides exchanged."""
    if not with_should:
        return _mirror_accepts(*col["mirror"], rowq, col["flags"])

    def one_row_rev(colrow, qrow):
        vals = {key: v[None] for key, v in qrow.items()}
        ok_r, _ = _accepts(colrow, vals, with_should)  # [1, B]
        return ok_r[0]

    return jax.vmap(one_row_rev)(col, rowq)


def _mirror_accepts(ints, floats, rowq, flags):
    """`_accepts(candidates' queries, rows' values)` for queries without
    should slots, read off the gathered record blocks as they lie:
    `ints` [R, B, 6 + 2 fs] holds scalars | s_req | s_forb, `floats`
    [R, B, 4 fn] n_lo | n_hi | n_flo | n_fhi, and the row's own `str`
    and `num` are laid out against them lane for lane. The same
    comparisons on the same words as `_accepts` makes, AND-reduced over
    whole blocks: a slice of a block's last axis costs a relayout of the
    block, six of them cost 0.09 s of a 0.34 s pass (PERF.md, PR 34)."""
    num, sv = rowq["num"], rowq["str"]  # [R, fn], [R, fs]
    fn, fs, n_rec = num.shape[1], sv.shape[1], len(RECORD_KEYS)
    lane = jnp.arange(ints.shape[2], dtype=jnp.int32)
    own = jnp.concatenate(
        [jnp.zeros((sv.shape[0], n_rec), sv.dtype), sv, sv], axis=1
    )[:, None]
    # (req == 0) | (sv == req) on s_req's lanes, (forb == 0) | (sv != forb)
    # on s_forb's; the scalars' lanes pass.
    met = jnp.where(lane >= n_rec + fs, own != ints, own == ints)
    ok_str = jnp.all((lane < n_rec) | (ints == 0) | met, axis=-1)
    lane = jnp.arange(4 * fn, dtype=jnp.int32)
    own = jnp.concatenate([num] * 4, axis=1)[:, None]
    # num >= n_lo | num <= n_hi | num >= n_flo | num <= n_fhi
    met = jnp.where((lane // fn) % 2 == 0, own >= floats, own <= floats)
    ok_num = jnp.all(met | (lane >= 2 * fn), axis=-1) & ~jnp.any(
        met[:, :, 2 * fn:3 * fn] & met[:, :, 3 * fn:], axis=-1
    )
    return ok_str & ok_num & ((flags & FLAG_NEVER) == 0)


def _stage2_rows(
    pool_n, record, mirror, rowq, active_slots, winners, *, k, keep,
    with_should, with_embedding, order_exact,
):
    """One stripe of `_stage2`: rows [R] against their winners [R, B],
    pre-trimmed to the `keep` best by stage-1 priority."""
    # Pre-trim the block winners to ~k by packed stage-1 priority BEFORE
    # any gather: at an 8-pool 160k bench the [A, 256, F] gather of every
    # pool field was a ~28 GB allocation (OOM on a 16 GB chip). The packed
    # word sorts by (priority << COL_BITS | col), so top_k keeps the
    # best-prioritised candidates; the exact re-rank below then orders the
    # survivors precisely. Keep 2x headroom over k so bucket-granular
    # false positives rarely crowd out true candidates.
    if winners.shape[1] > keep:
        winners, _ = jax.lax.top_k(winners, keep)
    cand = winners & (MAX_COLS - 1)  # [A, B]
    alive = winners != PACKED_NONE

    # Gather only what the exact checks read.
    col = _stage2_gather(
        pool_n, record, mirror, cand, with_should
    )  # [A, B, ...]

    # Exact per-field predicate, reusing the small-kernel form: _accepts
    # wants fcol [Bc,...] vs qrow [Br,...]; vmap over rows gives
    # fcol=[B,...] per row vs that row's query broadcast as Br=1.
    def one_row(colrow, qrow):
        q1 = {key: v[None] for key, v in qrow.items()}
        ok, score = _accepts(q1, colrow, with_should)  # [B, 1]
        return ok[:, 0], (score[:, 0] if with_should else jnp.zeros(()))

    ok, score = jax.vmap(one_row)(col, rowq)
    if not with_should:
        score = jnp.zeros(ok.shape, jnp.float32)
    if mirror is not None:  # rev
        ok = ok & _stage2_rev_ok(col, rowq, with_should)

    minmax_ok = (col["min_count"] >= rowq["min_count"][:, None]) & (
        col["max_count"] <= rowq["max_count"][:, None]
    )
    party_ok = (rowq["party"][:, None] == 0) | (
        col["party"] != rowq["party"][:, None]
    )
    pool_ok = col["pool_id"] == rowq["pool_id"][:, None]
    col_valid = (col["flags"] & FLAG_VALID) != 0
    not_self = cand != jnp.maximum(active_slots, 0)[:, None]
    row_live = (active_slots >= 0)[:, None]

    eligible = (
        ok & alive & minmax_ok & party_ok & pool_ok & col_valid & not_self
        & row_live
    )
    if with_embedding:
        score = score + jnp.einsum(
            "abd,ad->ab",
            col["emb"].astype(jnp.bfloat16),
            rowq["emb"].astype(jnp.bfloat16),
        ).astype(jnp.float32)

    # Truncate K' -> k by the stage-1 selection priority (jitter/score),
    # NOT by age: truncating oldest-first would re-concentrate every row's
    # list onto the same old tickets and resurrect assembler starvation.
    neg_prio = jnp.where(eligible, -winners, jnp.int32(2**31 - 1))
    neg_score = jnp.where(eligible, -score, jnp.inf)
    created = jnp.where(eligible, col["created"], jnp.int32(2**31 - 1))
    slot = jnp.where(eligible, cand, jnp.int32(2**31 - 1))
    _, s_k, c_k, slot_k = jax.lax.sort(
        (neg_prio, neg_score, created, slot), dimension=1, num_keys=1
    )
    s_k, c_k, slot_k = s_k[:, :k], c_k[:, :k], slot_k[:, :k]
    if not order_exact:
        # Pairs path: the handshake (pair_partners) needs eligible,
        # compacted candidate lists, not the exact (-score, created)
        # order — skip the second [A, k] multi-key sort.
        return jnp.where(slot_k == 2**31 - 1, -1, slot_k)
    # Final exact order within the survivors: (-score, created).
    _, _, ordered = jax.lax.sort((s_k, c_k, slot_k), dimension=1, num_keys=3)
    return jnp.where(ordered == 2**31 - 1, -1, ordered)


# -------------------------------------------------------- device pairing


# Rows of one step of a pairing round's walk over the open rows: the
# ladder of `pair_ladder` rises by it.
PAIR_CHUNK = 8192


def pair_ladder(a: int) -> tuple[int, ...]:
    """The row counts a pairing round over `a` rows can run its [rows, k]
    work at: every multiple of PAIR_CHUNK up to the first that holds `a`
    (`a` alone where it is no larger than one chunk). A function of the
    shape, nothing else; a round takes the smallest that holds its open
    rows, and none where no row is open."""
    step = min(PAIR_CHUNK, a)
    return tuple(range(step, a + step, step))


@functools.partial(jax.jit, static_argnames=("cap", "rounds"))
def pair_partners(
    cand: jnp.ndarray,  # i32 [A, k] candidate slots, -1 pad
    active_slots: jnp.ndarray,  # i32 [A] row slots, oldest-first, -1 pad
    *,
    cap: int,
    rounds: int = 8,
):
    """Greedy 1v1 assignment entirely on device: parallel propose-accept
    rounds over the candidate lists, oldest-first priority. The lists
    are eligible and compacted but not exactly ranked (the pairs path
    asks `topk_candidates_big` for `order_exact=False`): their order is
    stage 1's selection priority.

    Replaces the candidate-matrix D2H ([A,k] i32 is ~16MB at a 100k
    pool) with a partner vector (~0.5MB) and removes the native greedy
    assembly from the host entirely. Synchronous intervals shed their
    latency floor this way; pipelined intervals (the shipped default)
    shed the gap-side host work that contends with the server on small
    hosts — the cohort-slip tail. Under pipelining the formed pairs flow
    through the same queued-collect staleness masks (gen/alive/sel) as
    assembler matches; a pair invalidated by churn drops and its members
    reactivate. Semantics per round:

    - every open row proposes to a still-available candidate — its
      first-listed one in round 0, pseudo-randomly diffused afterwards
      (equal-score pools give every row the SAME candidate order, and
      un-diffused proposals serialize to one pair per round);
    - every proposed-to slot accepts its oldest proposer (min row index —
      rows arrive sorted by (created_at, created_seq), the reference's
      greedy iteration order, server/matchmaker_process.go:27);
    - a won proposal forms a pair unless its target is a row whose own
      proposal also won elsewhere (the target keeps its own win; the
      proposer retries next round). Mutual top-choices tie-break to the
      older row. Passive pool slots (inactive but matchable tickets) can
      accept but never propose.

    What a round costs is its gathers, which the chip charges by the
    index (a word of a 1-D column ~8ns): `avail_slot[cand]` over every
    cell of [A, k] was 87% of the program while it ran for every row.
    Only open rows use it and few stay open, so each round compacts the
    open rows' indices (a cumsum and one scatter) and walks them in
    steps of PAIR_CHUNK rows, as many steps as hold the round's open
    rows: the lists' row gather, the availability gather, the count, the
    cumsum and the pick of the j-th available candidate all run at
    [PAIR_CHUNK, k], each step scatters its proposals back to its rows,
    and a closed row keeps the -1 the dense form computed for it (bit
    for bit the same partners). The rows a round ran at are the smallest
    of `pair_ladder(A)` that holds its open rows; the count is the
    device's own, so one executable serves every history of one shape.
    Acceptance (per-slot
    min proposer) is one scatter-min and one gather over [A] (a sort +
    neighbor-compare + un-sort was tried and measured slower), and
    availability updates batch into ONE fused scatter per round.

    Returns (partner i32 [A] — formed-pair target slot on the PROPOSER
    row, -1 elsewhere (each pair reports exactly once); formed i32
    [1, rounds] — pairs each round formed; listed i32 [1] — cells of
    `cand` that hold a ticket; ran i32 [1, rounds] — rows each round
    ran its [rows, k] work at). The three counters are one row each, so
    that a caller that cuts what it fetches to its rows (tpu._bg_asm)
    keeps them whole; they cross D2H with the partner vector, end on
    the cohort's ledger row (tpu.Cohort.list_counts) and change nothing
    of the rounds.
    """
    a = cand.shape[0]
    i32 = jnp.int32
    rows = jnp.arange(a, dtype=i32)
    big = jnp.int32(2**31 - 1)
    ladder = pair_ladder(a)
    step, top = ladder[0], ladder[-1]
    valid_row = active_slots >= 0
    slot_of_row = jnp.maximum(active_slots, 0)
    # Pad rows (active_slots == -1) must not scatter: an index of
    # slot_of_row=0 would clobber slot 0's real owner and let the same
    # pair report from both sides (duplicate slots downstream).
    row_of_slot = (
        jnp.full((cap,), -1, i32)
        .at[jnp.where(valid_row, slot_of_row, cap)]
        .set(rows, mode="drop")
    )

    def row_mix(ids):
        # 2654435761 (Knuth) wrapped to int32 — jnp int32 math must not
        # see a Python int above 2^31.
        return (
            _mix(ids * jnp.int32(-1640531527) + 97) & 0x7FFFFFFF
        ).astype(i32)

    def round_fn(state, r):
        avail_slot, partner = state
        # A row is open while it neither formed a pair (partner set) nor
        # had its own slot taken by an accepted proposal.
        row_open = valid_row & (partner < 0) & avail_slot[slot_of_row]
        # The open rows' indices, in row order, at the head of `open_rows`.
        pos = jnp.cumsum(row_open.astype(i32)) - 1
        open_rows = (
            jnp.full((top,), a, i32)
            .at[jnp.where(row_open, pos, top)]
            .set(rows, mode="drop")
        )
        steps = (pos[-1] + step) // step  # ceil(open / step)

        def propose(i, prop):
            # past the last open row the walk holds `a`: no row
            to = jax.lax.dynamic_slice(open_rows, (i * step,), (step,))
            live = to < a
            ids = jnp.minimum(to, a - 1)
            c = cand[ids]
            cand_ok = (c >= 0) & avail_slot[jnp.maximum(c, 0)] & live[:, None]
            navail = jnp.sum(cand_ok, axis=1).astype(i32)
            has = navail > 0
            j = jnp.where(
                has & (r > 0), (row_mix(ids) * r) % jnp.maximum(navail, 1), 0
            )
            csum = jnp.cumsum(cand_ok, axis=1)
            first = jnp.argmax(csum == (j + 1)[:, None], axis=1)
            p = jnp.where(has, jnp.take_along_axis(
                c, first[:, None], axis=1)[:, 0], -1)
            return prop.at[to].set(p, mode="drop")

        # A closed row proposes nothing: the -1 the dense rounds
        # computed for it from a list with nothing available.
        prop = jax.lax.fori_loop(
            0, steps, propose, jnp.full((a,), -1, i32)
        )
        prop_safe = jnp.maximum(prop, 0)

        # Acceptance: oldest proposer (min row index) per slot, one
        # scatter-min + one gather. (A sort-based formulation was tried
        # and measured SLOWER: two [A] lax.sorts cost more than one
        # scatter on this chip.)
        win = (
            jnp.full((cap,), big, i32)
            .at[jnp.where(prop >= 0, prop, cap + 1)]
            .min(rows, mode="drop")
        )
        pwin = (prop >= 0) & (win[prop_safe] == rows)

        trow = jnp.where(prop >= 0, row_of_slot[prop_safe], -1)
        t_is_row = trow >= 0
        t_safe = jnp.maximum(trow, 0)
        t_pwin = pwin[t_safe] & t_is_row
        t_prop = jnp.where(t_is_row, prop[t_safe], -1)
        mutual = t_is_row & (t_prop == slot_of_row)
        ok_t = (~t_is_row) | (~t_pwin) | (mutual & (rows < trow))
        form = pwin & ok_t

        partner = jnp.where(form, prop, partner)
        # ONE fused availability scatter: both sides of every formed pair.
        taken = jnp.concatenate(
            [
                jnp.where(form, slot_of_row, cap + 1),
                jnp.where(form, prop_safe, cap + 1),
            ]
        )
        avail_slot = avail_slot.at[taken].set(False, mode="drop")
        return (avail_slot, partner), (jnp.sum(form, dtype=i32), steps * step)

    init = (
        jnp.ones((cap,), dtype=bool),
        jnp.full((a,), -1, i32),
    )
    (_, partner), (formed, ran) = jax.lax.scan(
        round_fn, init, jnp.arange(rounds, dtype=i32)
    )
    listed = jnp.count_nonzero(cand >= 0).astype(i32)
    return partner, formed[None], listed[None], ran[None]
