"""The main path's kernels, compiled for a described v5e at shipped widths.

No chip is attached: the TPU compiler installed here compiles for a
topology that is only described, and raises what the chip's compiler
would raise — a kernel over its VMEM, a program over the chip's HBM, a
Pallas kernel that does not lower. Nothing runs, so these say nothing of
results or times; `chip_smoke.py` does that on the chip.

The topology is described inside a fixture (never at import or
collection: only one process may hold the TPU library, and every xdist
worker imports this file), compiles happen in this process, and all of
them live in this one file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from nakama_tpu.config import MatchmakerConfig
from nakama_tpu.leaderboard import tpu as lb
from nakama_tpu.matchmaker import device, device2

HBM_BYTES = 15.75e9  # one v5e chip
CFG = MatchmakerConfig()  # the shipped default widths
CAP = CFG.pool_capacity


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _pool(sharding, capacity=CAP):
    host = device.pool_schema(
        8, CFG.numeric_fields, CFG.string_fields, CFG.max_constraints,
        CFG.embedding_dims,
    )
    return {
        k: jax.ShapeDtypeStruct(
            (capacity,) + v.shape[1:], v.dtype, sharding=sharding
        )
        for k, v in host.items()
    }


def _fits(compiled, pallas: bool):
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    assert ("tpu_custom_call" in compiled.as_text()) == pallas
    return mem


def _compile_big(
    one_chip, rows, n_cols, emb, rev, capacity=CAP, order_exact=True,
    str_cols=(),
):
    """The two-stage kernel for `rows` actives against `n_cols` pool
    columns at shipped widths; its temporaries must stay far under the
    chip."""
    a = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    grid = jax.ShapeDtypeStruct(
        (CFG.numeric_fields,), jnp.float32, sharding=one_chip
    )
    compiled = device2.topk_candidates_big.lower(
        _pool(one_chip, capacity), a, grid, grid,
        fn=CFG.numeric_fields, fs=CFG.string_fields, n_cols=n_cols,
        k=CFG.candidates_per_ticket, rev=rev, with_should=False,
        with_embedding=emb, interpret=False, order_exact=order_exact,
        str_cols=str_cols,
    ).compile()
    assert _fits(compiled, pallas=True).temp_size_in_bytes < 4e9
    return compiled


@pytest.mark.parametrize("cell", [
    "playlists100k", "multiqueue8x20k", "squad50k", "mutual100k"])
def test_big_kernel_with_exact_words(one_chip, cell):
    """The four string-filtered cells' first dispatch with stage 1's
    exact word compares (PR 38): the Pallas kernel lowers with the two
    int32 operands, and its row tile is the one it had without them."""
    rows, n_cols, emb, rev, cap, exact, cols = {
        "playlists100k": (CAP, 112 * 1024, True, False, CAP, True, (2,)),
        "multiqueue8x20k": (2 * CAP, 160 * 1024, False, False,
                            device2.MAX_COLS, False, (2,)),
        "squad50k": (1 << 16, 1 << 16, False, False, CAP, True, (2, 3)),
        "mutual100k": (CAP, 112 * 1024, True, True, CAP, True, (2,)),
    }[cell]
    _compile_big(one_chip, rows, n_cols, emb, rev, capacity=cap,
                 order_exact=exact, str_cols=cols)
    plan = dict(n=n_cols, n_local=n_cols, k=CFG.candidates_per_ticket,
                bm=1024, bn=1024, fn=CFG.numeric_fields,
                fs=CFG.string_fields, de=16 if emb else 8, rev=rev)
    assert device2.stage1_plan(**plan, n_exact=len(cols)) == (
        device2.stage1_plan(**plan))


@pytest.mark.parametrize("emb_rev", [(False, False), (True, True)])
def test_big_kernel_full_pool_dispatch(one_chip, emb_rev):
    """A fresh 100k pool dispatches all actives in one pass: 131072 rows
    against 131072 columns. With mutual matching this was refused twice
    over (stage-1 VMEM, stage-2 HBM) before the row tile was derived and
    stage 2 striped; temporaries must stay far under the chip."""
    emb, rev = emb_rev
    compiled = _compile_big(one_chip, CAP, CAP, emb, rev)
    # Stage 2 gathers rows, never single words: a one-word gather from a
    # 1-D pool column costs four times a 16-word row on the chip, and the
    # re-rank loop issued six of them for every candidate.
    one_word = [
        line.strip()[:200] for line in compiled.as_text().splitlines()
        if " gather(" in line and "while/body" in line
        and "slice_sizes={1}" in line
    ]
    assert not one_word, one_word


@pytest.mark.parametrize("cell", ["ranked100k", "mutual100k"])
def test_stage2_gathers_a_candidate_as_rows(one_chip, cell):
    """Stage 2 at `ranked100k.burst`'s shape (131,072 rows against
    114,688 columns, the embedding) and under rev at `mutual100k.burst`'s
    (with stage 1's exact word test): it fits and lowers, its stripe loop
    gathers one candidate row forward and two under rev, as
    `stage2_gathers` says, and copies no gathered block to another
    layout (a slice of a block's last axis compiles to such a copy)."""
    rev = cell == "mutual100k"
    n_cols = 112 * 1024
    compiled = _compile_big(
        one_chip, CAP, n_cols, True, rev, str_cols=(2,) if rev else ())
    loop = [
        line.strip() for line in compiled.as_text().splitlines()
        if "while/body" in line
    ]
    rows = [line for line in loop
            if " gather(" in line and "offset_dims={2}" in line]
    shape = device2.stage2_shape(
        _pool(one_chip), CAP, 256, CFG.candidates_per_ticket, rev, False,
        True)
    assert len(rows) == shape["stage2_gathers"] == (2 if rev else 1)
    stripe, keep = shape["stage2_stripe_rows"], 128
    assert stripe == (2048 if rev else 8192)
    block = f"[{stripe},{keep},"
    relayouts = [line[:160] for line in loop
                 if " copy(" in line and block in line.split("=")[1][:40]]
    assert not relayouts, relayouts
    # The embedding score is the column form's einsum on a bf16 block of
    # the embedding alone, as the chip sums it there: fed the gathered
    # row itself, the sum ran in another order and the candidates' order
    # moved.
    text = compiled.as_text()
    emb = f"bf16[{stripe},{keep},{CFG.embedding_dims}]"
    sums = [
        body for body in re.split(r"\n}\n", text)
        if re.search(rf"= bf16\[{stripe},{keep}\]\S* reduce\(", body)
    ]
    assert len(sums) == 1, len(sums)
    params = re.findall(r"= (\w+\[[\d,]+\])\S* parameter\(", sums[0])
    assert emb in params, params


@pytest.mark.parametrize("rows", [1 << 16, 2048])
def test_big_kernel_squad_pool_dispatch(one_chip, rows):
    """`squad50k.burst`'s two dispatches: 50,000 tickets without an
    embedding pad to 65,536 rows against 65,536 columns (64 column
    blocks, two winners a block), and the fill tick sends the leftovers,
    one or two row blocks, against the same columns."""
    n_cols = 1 << 16
    _compile_big(one_chip, rows, n_cols, emb=False, rev=False)
    assert device2.stage1_plan(
        n=n_cols, n_local=n_cols, k=CFG.candidates_per_ticket, bm=1024,
        bn=1024, fn=CFG.numeric_fields, fs=CFG.string_fields, de=8,
        rev=False,
    )[0] == 2


@pytest.mark.parametrize("rows", [1 << 16, 2048])
def test_big_kernel_party_pool_dispatch(one_chip, rows):
    """`party50k.burst`'s two dispatches: 50,000 tickets of one to five
    players, each with an embedding, pad to 65,536 rows against 65,536
    columns; the fill tick sends the leftovers against the same
    columns."""
    _compile_big(one_chip, rows, 1 << 16, emb=True, rev=False)


@pytest.mark.parametrize("str_cols", [(), (2,)])
def test_big_kernel_sharded_mutual(topo, str_cols):
    """The 4-device mesh variant with embeddings and mutual matching,
    without and with stage 1's exact word compares."""
    mesh = Mesh(np.asarray(topo.devices), ("pool",))
    rep = NamedSharding(mesh, P())
    a = jax.ShapeDtypeStruct((CAP,), jnp.int32, sharding=rep)
    grid = jax.ShapeDtypeStruct(
        (CFG.numeric_fields,), jnp.float32, sharding=rep
    )
    compiled = device2.topk_candidates_big_sharded.lower(
        _pool(NamedSharding(mesh, P("pool"))), a, grid, grid,
        mesh=mesh, axis="pool", fn=CFG.numeric_fields,
        fs=CFG.string_fields, k=CFG.candidates_per_ticket, rev=True,
        with_should=False, with_embedding=True, interpret=False,
        str_cols=str_cols,
    ).compile()
    assert _fits(compiled, pallas=True).temp_size_in_bytes < 4e9


def test_big_kernel_multiqueue_pool_dispatch(one_chip):
    """`multiqueue8x20k.burst`'s dispatch, at the big kernel's edge: a
    pool of capacity `MAX_COLS` (the last column the packed winner word
    can name), 160,000 duel tickets padded to 262,144 rows against
    163,840 columns (160 column blocks: one winner a block, 256 lanes
    of winners, the row tile halved to 512), no embedding, and stage 2
    without its second sort, as the pairs path asks for it."""
    cap = device2.MAX_COLS
    assert cap == 2 * CAP == 262144
    rows, n_cols = cap, 160 * 1024
    _compile_big(
        one_chip, rows, n_cols, emb=False, rev=False, capacity=cap,
        order_exact=False,
    )
    assert device2.stage1_plan(
        n=n_cols, n_local=n_cols, k=CFG.candidates_per_ticket, bm=1024,
        bn=1024, fn=CFG.numeric_fields, fs=CFG.string_fields, de=8,
        rev=False,
    ) == (1, 256, 512)


@pytest.mark.parametrize("cap", [CAP, device2.MAX_COLS])
def test_pair_partners(one_chip, cap):
    """The device pairing over a full pool's lists, at the shipped
    capacity and at `multiqueue8x20k`'s (A = cap = 262,144): eight
    rounds in one scan, each walking its open rows in steps of
    `PAIR_CHUNK` inside a device loop, three small outputs, temporaries
    under the dense rounds' few hundred MB."""
    cand = jax.ShapeDtypeStruct(
        (cap, CFG.candidates_per_ticket), jnp.int32, sharding=one_chip
    )
    a = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=one_chip)
    compiled = device2.pair_partners.lower(cand, a, cap=cap).compile()
    assert _fits(compiled, False).temp_size_in_bytes < 3e8
    partner, formed, listed, ran = compiled.out_info
    assert (partner.shape, formed.shape, listed.shape, ran.shape) == (
        (cap,), (1, 8), (1,), (1, 8))
    assert cap // device2.PAIR_CHUNK == len(device2.pair_ladder(cap)) > 1


def test_small_exact_kernel(one_chip):
    """A pool under big_pool_threshold, should-clauses, embeddings and
    mutual matching on, at TpuBackend's default blocks."""
    n = CFG.big_pool_threshold // 2
    a = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    base = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = device.topk_candidates.lower(
        _pool(one_chip), a, k=CFG.candidates_per_ticket, br=256, bc=2048,
        rev=True, n_cols=n, with_should=True, with_embedding=True,
        created_base=base,
    ).compile()
    _fits(compiled, pallas=False)


def test_leaderboard_rank_lookup(one_chip):
    """`lex_ranks` at a 65,536-row board. (`sort_boards` takes about a
    minute to compile at any board size — its 3-key sort — and stays
    out of tier-1; chip_smoke.py runs it.)"""
    c = 1 << 16
    keys = jax.ShapeDtypeStruct((c, 3), jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((1024, 3), jnp.int32, sharding=one_chip)
    compiled = lb.lex_ranks.lower(
        keys, q, n_iters=lb.n_search_iters(c)
    ).compile()
    _fits(compiled, pallas=False)
