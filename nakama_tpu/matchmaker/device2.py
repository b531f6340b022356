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
A candidate is gathered as ONE row of a table built from the pool once
per dispatch (its scalars, values and embedding: `_stage2_tables`), two
under rev (its query mirrors beside them), and checked on whole rows. On
a v5e a gather costs by the index, not by the word: a word from a 1-D
column 7.45 ns, a row of 6 to 128 words 1.8 ns (the traces in PERF.md).
Six one-word gathers for each of 16.8 million candidates were 0.735 s of
a 1.14 s pass; one row of 6 to 128 words is 0.030 s.
Stage 1's eligibility test is a superset filter, so it never rejects a
true candidate; its per-block argmax can still drop one, when false
positives of the same block outrank it. Required string words are no
such source: 8 buckets a field hold many words (400 queue names fall
about 50 to a bucket), so stage 1 also compares every required word on
its full 31-bit code (`exact_words`) in the columns queries have
required (PR 38); a ticket of another queue never takes a winner's place. A
numeric window's false positives remain; such a row waits for a later
interval.

The candidate lists feed the same native greedy assembler as the small-pool
path. Reference hot loop replaced: server/matchmaker_process.go:27-334.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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
# candidate gather (per kept winner: a row of every table of
# _stage2_tables) stays under this many bytes: its temporaries
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
    *refs,
    f_tot: float,
    bn: int,
    m: int,
    out_w: int,
    with_embedding: bool,
    rev: bool,
    n_exact: int,
):
    out_ref = refs[-1]
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
    if n_exact:
        # The required words on their full 31-bit codes (`exact_words`):
        # a bucket of the planes above holds many words, so they pass
        # other queues' tickets that only these compares refuse before
        # the block's winners are picked.
        xr_ref, xc_ref = refs[0], refs[1]
        for f in range(n_exact):
            req = xr_ref[:, f:f + 1]  # [bm, 1]: the row's required word
            elig = elig & ((req == 0) | (req == xc_ref[f:f + 1, :]))
            if rev:  # the column's required word against the row's
                req = xc_ref[n_exact + f:n_exact + f + 1, :]  # [1, bn]
                held = xr_ref[:, n_exact + f:n_exact + f + 1]
                elig = elig & ((req == 0) | (req == held))

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
                    out_w: int, dx: int = 0) -> int:
    """Largest row tile <= `bm` (halving) whose stage-1 footprint fits
    the scoped-VMEM limit: every operand and output block double-buffered
    at its lane-padded size, plus the [bm, bn] 32-bit epilogue tiles the
    compiler keeps live (score, packed word, and at large grids most of
    a third — 1.75 tiles bounds every shape the v5e compiler was asked
    about, tests/test_chip_compile.py holds the shipped ones), and the
    `dx` int32 words of `exact_words` a row and a column carry. Only the
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
        if dx:
            blocks += 2 * rows * lanes(dx) * 4 + 2 * -(-dx // 8) * 8 * bn * 4
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
    de: int, rev: bool, n_exact: int = 0,
) -> tuple[int, int, int]:
    """What one stage-1 launch does at these shapes: (winners kept per
    column block, lane width of its packed-winner output, row tile).
    `n` is the column extent the dispatch scores, `n_local` the part of
    it one launch sees (== `n` off the mesh), `de` the embedding operand
    width, `n_exact` the string columns tested exactly. Derived here
    only: the kernels, the mesh gather accounting and the dispatch
    breadcrumb all read it."""
    d = encoding_dims(fn, fs)
    # Enough total candidate width even when the pool spans few blocks.
    m = max(1, -(-2 * k // (n // bn)))
    out_w = -(-(n_local // bn * m) // 128) * 128  # lane dim: 128-aligned
    dx = n_exact * (2 if rev else 1)
    return m, out_w, stage1_row_tile(
        bm, bn, d, de, d if rev else 8, out_w, dx)


def exact_words(rowq, cols, str_cols, rev):
    """Stage 1's operands for the exact string test: per row [A, W] and
    per column [W, N] int32, W = len(str_cols) (twice under rev). A row
    carries the code its query requires in each tested column (0 none)
    and, under rev, the code it holds; a column the code it holds and,
    under rev, the one its query requires. None when no column is
    tested: the kernel then takes no such operands."""
    if not str_cols:
        return None

    def take(table):
        return jnp.stack([table[:, c] for c in str_cols], axis=1)

    xr, xc = [take(rowq["s_req"])], [take(cols["str"]).T]
    if rev:
        xr.append(take(rowq["str"]))
        xc.append(take(cols["s_req"]).T)
    return jnp.concatenate(xr, axis=1), jnp.concatenate(xc, axis=0)


def _stage1_call(
    uq, vv, col_mix, col_gidx, row_mix, row_slot, ue, ve, uv, vq, exact,
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
    `m`, `out_w` and the row tile `bm` come from `stage1_plan`; `exact`
    is `exact_words`' pair or None. Returns packed per-block winners
    [a_pad, out_w]."""
    a_pad = uq.shape[0]
    n = vv.shape[0]
    d = encoding_dims(fn, fs)
    n_blocks = n // bn
    de = ue.shape[1]
    dq = uv.shape[1]
    extra, extra_specs, n_exact = (), [], 0
    if exact is not None:
        xr, xc = extra = exact
        n_exact = xr.shape[1] // (2 if rev else 1)
        extra_specs = [
            pl.BlockSpec((bm, xr.shape[1]), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((xc.shape[0], bn), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ]
    kernel = functools.partial(
        _stage1_kernel,
        f_tot=float(fn + fs + 1),
        bn=bn,
        m=m,
        out_w=out_w,
        with_embedding=with_embedding,
        rev=rev,
        n_exact=n_exact,
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
            *extra_specs,
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
    )(uq, vv, col_mix, col_gidx, row_mix, row_slot, ue, ve, uv, vq, *extra)


@functools.partial(
    jax.jit,
    static_argnames=(
        "fn", "fs", "n_cols", "k", "rev", "with_should", "with_embedding",
        "bm", "bn", "interpret", "order_exact", "str_cols",
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
    str_cols: tuple[int, ...] = (),
):
    """Two-stage top-k: returns slots i32 [A_pad, k] ordered by exact
    (-score, created), -1 padded. Drop-in contract of
    device.topk_candidates minus the score output (the order already
    encodes it). Stage 1 tests the string columns `str_cols` exactly
    (`exact_words`); the caller names those that queries have required."""
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
        rev=rev, n_exact=len(str_cols),
    )
    winners = _stage1_call(
        uq, vv, col_mix, col_gidx, row_mix, row_slot, ue, ve, uv, vq,
        exact_words(rowq, pool_n, str_cols, rev),
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
        "with_embedding", "bm", "bn", "interpret", "str_cols",
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
    str_cols: tuple[int, ...] = (),
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
        de=ue.shape[1], rev=rev, n_exact=len(str_cols),
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
                   row_slot, ue, uv, grid_lo, grid_inv, rowq_x):
        # Replicated row-side inputs meet device-varying column data in
        # the kernel: mark them varying explicitly (vma typing).
        (uq, row_mix, row_slot, ue, uv, grid_lo, grid_inv, rowq_x) = varying(
            (uq, row_mix, row_slot, ue, uv, grid_lo, grid_inv, rowq_x)
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
            ve_l, uv, vq_l, exact_words(rowq_x, pool_local, str_cols, rev),
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
            P(), P(), P(), P(),
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
        {key: rowq[key] for key in ("s_req", "str")} if str_cols else {},
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
        a_pad, keep, stage2_words(pool_n, rev, with_should, with_embedding)
    )
    # Built once, before the stripes: the loop body only reads them.
    layout = _stage2_tables(pool_n, rev, with_should, with_embedding)
    tables = _stage2_record(pool_n, layout)

    def rerank(args):
        rq, act, win = args
        return _stage2_rows(
            tables, layout, rq, act, win, k=k, keep=keep, rev=rev,
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


# A candidate's scalar columns, in the order its rows hold them.
RECORD_KEYS = (
    "min_count", "max_count", "party", "pool_id", "flags", "created",
)
# A candidate's QUERY as `_accepts` reads it back against the searcher's
# values under rev (mutual); the should slots join only when the pool
# holds should queries.
MIRROR_KEYS = ("n_lo", "n_hi", "n_flo", "n_fhi", "s_req", "s_forb")
SHOULD_KEYS = ("sh_op", "sh_fld", "sh_lo", "sh_hi", "sh_term", "sh_boost")


def _stage2_tables(pool, rev, with_should, with_embedding):
    """The rows stage 2 gathers a kept winner by, each as the pool keys
    it holds in lane order (`pool`: anything that maps a key to its
    table). ONE row on the forward programs: the embedding, the scalars
    and the values (62 words at shipped widths, 46 without the
    embedding). TWO under rev: the scalars, the int32 query mirrors and
    the values (78); the embedding and the f32 mirrors (112). On a v5e a
    row gather costs by the index, not the word, from 6 to 128 words, the
    widest measured. The embedding leads its row: its score reads its
    lanes by a slice at lane 0, which moves no word. Pools
    with should queries keep a row per table beside a record of the
    scalars and, under rev, of the query mirrors split by dtype:
    `_accepts` takes their should slots by field from `num` and `str`."""
    emb = ("emb",) if with_embedding else ()
    if not with_should:
        if not rev:
            return (emb + RECORD_KEYS + ("num", "str"),)
        return (
            RECORD_KEYS + ("s_req", "s_forb", "num", "str"),
            emb + ("n_lo", "n_hi", "n_flo", "n_fhi"),
        )
    mirror = MIRROR_KEYS + SHOULD_KEYS if rev else ()
    ints = tuple(key for key in mirror if pool[key].dtype == jnp.int32)
    floats = tuple(key for key in mirror if key not in ints)
    rows = (RECORD_KEYS + ints, floats, ("num",), ("str",), emb)
    return tuple(row for row in rows if row)


def _width(pool, key) -> int:
    """Words a row of `pool[key]` holds: 1 for a scalar column."""
    shape = pool[key].shape
    return shape[1] if len(shape) > 1 else 1


def stage2_keep(out_w: int, k: int) -> int:
    """Winners a row keeps of stage 1's `out_w` for the exact checks."""
    return min(out_w, max(2 * k, 8))  # see _stage2_rows


def stage2_words(pool, rev, with_should, with_embedding) -> int:
    """32-bit words stage 2 gathers per kept winner: every row of
    `_stage2_tables`, whatever its dtype."""
    return sum(
        _width(pool, key)
        for row in _stage2_tables(pool, rev, with_should, with_embedding)
        for key in row
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


def stage2_shape(
    pool, a_pad, out_w, k, rev, with_should, with_embedding
) -> dict:
    """The static shape `_stage2` runs at for a dispatch of `a_pad` rows
    with `out_w` stage-1 winners a row, under the names the dispatch
    record carries it by (tpu.py: the crumb's `kernel`, the cohort's
    ledger row): by the same functions, so it cannot drift from the
    program. `stage2_gathers` is the row gathers a kept winner costs."""
    words = stage2_words(pool, rev, with_should, with_embedding)
    return dict(
        stage2_gathers=len(
            _stage2_tables(pool, rev, with_should, with_embedding)
        ),
        stage2_words=words,
        stage2_stripe_rows=stage2_stripe(a_pad, stage2_keep(out_w, k), words),
    )


def stage2_shape_of(variant: dict) -> dict:
    """`stage2_shape`'s keys of a dispatch record that has them."""
    return {
        key: variant[key]
        for key in ("stage2_gathers", "stage2_words", "stage2_stripe_rows")
        if key in variant
    }


def _stage2_record(pool_n, layout):
    """The tables of `layout`, built once per dispatch, [n, W] each: a
    row of one key is the pool's own table; a row of several is f32
    where every key is f32, else int32 with the f32 keys bitcast (a
    bitcast round trip keeps every word: NaN payloads, -0.0, denormals)."""
    tables = []
    for keys in layout:
        cols = [pool_n[key].reshape(pool_n[key].shape[0], -1) for key in keys]
        if len(cols) == 1:
            tables.append(cols[0])
            continue
        same = all(col.dtype == cols[0].dtype for col in cols)
        dtype = cols[0].dtype if same else jnp.int32
        tables.append(jnp.concatenate(
            [jax.lax.bitcast_convert_type(col, dtype) for col in cols],
            axis=1,
        ))
    return tables


def _stage2_gather(layout, blocks, like):
    """The gathered rows `blocks` ([R, B, W], in `layout`'s order) by
    pool key → [R, B, ...] in the key's dtype (`like` maps a key to an
    array of its rank and dtype): the column form `_accepts` reads on
    pools with should queries. The scalars come out along a block's
    second axis: a slice of the last one leaves [R, B, 1] blocks, each
    tiled out to the size of the whole block."""
    col = {}
    for keys, block in zip(layout, blocks):
        at = np.cumsum([0] + [_width(like, key) for key in keys])
        scalars = [i for i, key in enumerate(keys) if like[key].ndim == 1]
        if scalars:
            lo, hi = at[scalars[0]], at[scalars[-1]] + 1
            rec = jnp.swapaxes(block[:, :, lo:hi], 1, 2)  # [R, n, B]
        for i, key in enumerate(keys):
            if i in scalars:
                part = rec[:, at[i] - lo]
            elif len(keys) == 1:
                part = block
            else:
                part = block[:, :, at[i]:at[i + 1]]
            col[key] = jax.lax.bitcast_convert_type(part, like[key].dtype)
    return col


def _stage2_by_column(col, rowq, rev, with_should, with_embedding):
    """(ok, score, created) [R, B] of candidates in column form: the
    small kernel's `_accepts` (vmapped over rows: fcol = [B, ...] per
    row against that row's query broadcast as Br = 1), under rev also
    with the two sides exchanged, and the scalars' tests."""

    def one_row(colrow, qrow):
        q1 = {key: v[None] for key, v in qrow.items()}
        ok, score = _accepts(q1, colrow, with_should)  # [B, 1]
        return ok[:, 0], (score[:, 0] if with_should else jnp.zeros(()))

    ok, score = jax.vmap(one_row)(col, rowq)
    if not with_should:
        score = jnp.zeros(ok.shape, jnp.float32)
    if rev:
        ok = ok & _stage2_rev_ok(col, rowq, with_should)
    minmax_ok = (col["min_count"] >= rowq["min_count"][:, None]) & (
        col["max_count"] <= rowq["max_count"][:, None]
    )
    party_ok = (rowq["party"][:, None] == 0) | (
        col["party"] != rowq["party"][:, None]
    )
    pool_ok = col["pool_id"] == rowq["pool_id"][:, None]
    col_valid = (col["flags"] & FLAG_VALID) != 0
    if with_embedding:
        score = score + jnp.einsum(
            "abd,ad->ab",
            col["emb"].astype(jnp.bfloat16),
            rowq["emb"].astype(jnp.bfloat16),
        ).astype(jnp.float32)
    ok = ok & minmax_ok & party_ok & pool_ok & col_valid
    return ok, score, col["created"]


def _stage2_rev_ok(col, rowq, with_should):
    """Does each candidate's own query accept its row's ticket (mutual
    matching)? [R, B]: `_accepts` with the two sides exchanged."""

    def one_row_rev(colrow, qrow):
        vals = {key: v[None] for key, v in qrow.items()}
        ok_r, _ = _accepts(colrow, vals, with_should)  # [1, B]
        return ok_r[0]

    return jax.vmap(one_row_rev)(col, rowq)


def _stage2_on_rows(layout, blocks, rowq, rev):
    """`_stage2_by_column`'s (ok, score, created) for pools without
    should queries, read off the gathered rows as they lie. The row's
    own words are laid out lane for lane against each block ([R, 1, W],
    0 on the lanes a test does not read); each test is the comparison
    `_accepts` or the scalars' tests make, on the same words, kept to
    its lanes by a mask; the block is AND-reduced whole, and `created`
    is a sum masked to its lane. No slice of a gathered block's last axis
    but the embedding's, at lane 0: one elsewhere costs a relayout of the
    block (0.10 + 0.05 s of a 0.34 s pass on a v5e; PERF.md)."""
    r = rowq["flags"].shape[0]
    ok = ((rowq["flags"] & FLAG_NEVER) == 0)[:, None]
    score = jnp.zeros(blocks[0].shape[:2], jnp.float32)
    flag_mask = FLAG_VALID | (FLAG_NEVER if rev else 0)
    for keys, block in zip(layout, blocks):
        widths = [_width(rowq, key) for key in keys]
        lane = np.repeat(np.array(keys), widths)  # each lane's key

        def on(*names):
            return np.isin(lane, names)

        def laid(own, dtype):
            return jnp.concatenate([
                own[key].reshape(r, w).astype(dtype) if key in own
                else jnp.zeros((r, w), dtype)
                for key, w in zip(keys, widths)
            ], axis=1)[:, None]

        bits = jax.lax.bitcast_convert_type(block, jnp.int32)
        flt = jax.lax.bitcast_convert_type(block, jnp.float32)
        own = laid(dict(
            {key: rowq[key] for key in RECORD_KEYS[:4]},
            str=rowq["s_req"], s_req=rowq["str"], s_forb=rowq["str"],
        ), jnp.int32)
        forb = laid({"str": rowq["s_forb"]}, jnp.int32)

        def num_ok():  # the candidate's value against the row's query
            lo, hi, flo, fhi = (
                laid({"num": rowq[key]}, jnp.float32)
                for key in MIRROR_KEYS[:4]
            )
            return (flt >= lo) & (flt <= hi) & ~((flt >= flo) & (flt <= fhi))

        if "n_lo" in keys:  # the row's value against the candidate's
            # query: num >= n_lo | num <= n_hi | num >= n_flo | num <= n_fhi
            mine = laid(dict.fromkeys(MIRROR_KEYS[:4], rowq["num"]),
                        jnp.float32)
            met = jnp.where(on("n_lo", "n_flo"), mine >= flt, mine <= flt)

        tests = (
            (("min_count",), lambda: bits >= own),
            (("max_count",), lambda: bits <= own),
            (("party",), lambda: (own == 0) | (bits != own)),
            (("pool_id",), lambda: bits == own),
            (("flags",), lambda: (bits & flag_mask) == FLAG_VALID),
            (("num",), num_ok),
            (("str",), lambda: ((own == 0) | (bits == own))
             & ((forb == 0) | (bits != forb))),
            (("s_req",), lambda: (bits == 0) | (bits == own)),
            (("s_forb",), lambda: (bits == 0) | (bits != own)),
            (("n_lo", "n_hi"), lambda: met),
        )
        lane_ok = True
        for names, test in tests:
            if set(names) & set(keys):
                lane_ok = jnp.where(on(*names), test(), lane_ok)
        ok = ok & jnp.all(lane_ok, axis=-1)
        if "n_flo" in keys:  # ~(num >= n_flo & num <= n_fhi), by field
            fn = _width(rowq, "num")
            flo, fhi = (
                sum(widths[:keys.index(key)]) for key in ("n_flo", "n_fhi")
            )
            ok = ok & ~jnp.any(
                met[:, :, flo:flo + fn] & met[:, :, fhi:fhi + fn], axis=-1
            )
        if "created" in keys:
            created = jnp.sum(jnp.where(on("created"), bits, 0), axis=-1)
        if "emb" in keys:
            # `_stage2_by_column`'s einsum on the same words, summed as
            # the chip sums it there: over a bf16 block of the embedding
            # alone, held apart by the barrier (fused into a pass over the
            # whole row, a v5e adds the products in another order and the
            # candidates' order moves). The embedding leads its row.
            at = sum(widths[:keys.index("emb")])
            emb = block[:, :, at:at + _width(rowq, "emb")]
            emb = jax.lax.optimization_barrier(
                jax.lax.bitcast_convert_type(emb, jnp.float32).astype(
                    jnp.bfloat16))
            score = jnp.einsum(
                "abd,ad->ab", emb, rowq["emb"].astype(jnp.bfloat16),
            ).astype(jnp.float32)
    return ok, score, created


def _stage2_rows(
    tables, layout, rowq, active_slots, winners, *, k, keep, rev,
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

    # One gather a row of the layout: only what the exact checks read.
    blocks = [table[cand] for table in tables]  # [A, B, W] each
    if with_should:
        ok, score, created = _stage2_by_column(
            _stage2_gather(layout, blocks, rowq), rowq, rev, with_should,
            with_embedding,
        )
    else:
        ok, score, created = _stage2_on_rows(layout, blocks, rowq, rev)
    not_self = cand != jnp.maximum(active_slots, 0)[:, None]
    row_live = (active_slots >= 0)[:, None]
    eligible = ok & alive & not_self & row_live

    # Truncate K' -> k by the stage-1 selection priority (jitter/score),
    # NOT by age: truncating oldest-first would re-concentrate every row's
    # list onto the same old tickets and resurrect assembler starvation.
    neg_prio = jnp.where(eligible, -winners, jnp.int32(2**31 - 1))
    neg_score = jnp.where(eligible, -score, jnp.inf)
    created = jnp.where(eligible, created, jnp.int32(2**31 - 1))
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
