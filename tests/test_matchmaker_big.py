"""Two-stage MXU kernel path (device2.py): validity + quality parity vs the
CPU oracle, running the Pallas stage-1 in interpreter mode on the virtual
CPU device. Mirrors the small-kernel parity tier (test_matchmaker_tpu.py)
at a pool size that exercises the bucket-mask prefilter + exact re-rank."""

import numpy as np
import pytest

from nakama_tpu.config import MatchmakerConfig
from nakama_tpu.logger import test_logger as quiet_logger
from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
from nakama_tpu.matchmaker.tpu import TpuBackend

from test_matchmaker_tpu import (  # reuse fixtures/validators
    _random_pool,
    _run,
    _validate_matches,
)


def make_big_mm(**kw):
    # Matching-semantics tests pin the synchronous path (one
    # process() == one delivered interval); the pipelined shipped
    # default is covered by test_matchmaker_cadence.py.
    kw.setdefault("interval_pipelining", False)
    cfg = MatchmakerConfig(
        pool_capacity=2048,
        candidates_per_ticket=32,
        numeric_fields=8,
        string_fields=8,
        max_constraints=8,
        big_pool_threshold=64,  # force the two-stage path
        **kw,
    )
    collected = []
    backend = TpuBackend(
        cfg,
        quiet_logger(),
        row_block=8,
        col_block=64,
        big_row_block=64,
        big_col_block=64,
    )
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend, on_matched=collected.append
    )
    return mm, collected


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("rev", [False, True])
def test_big_path_parity_random_pools(seed, rev):
    rng = np.random.default_rng(seed)
    specs = _random_pool(rng, 64, party_frac=0.25, multiple=True)

    cfg = MatchmakerConfig(max_intervals=2, rev_precision=rev)
    cpu_mm = LocalMatchmaker(quiet_logger(), cfg)
    cpu_matches = _run(cpu_mm, specs)

    mm, _ = make_big_mm(max_intervals=2, rev_precision=rev)
    assert mm.backend.config.big_pool_threshold == 64
    tpu_matches = _run(mm, specs)

    cpu_count = _validate_matches(cpu_matches, specs, mutual=rev)
    tpu_count = _validate_matches(tpu_matches, specs, mutual=rev)
    # Every big-path match must be valid (checked above). Quality: at this
    # deliberately tiny pool (64 tickets forced through the big path) the
    # jittered selection can land a greedy outcome a few entries either
    # side of the oracle's; allow that variance here — the at-scale quality
    # bar (where the big path exists) is test_big_path_1v1_diversity, and
    # the oracle-exact small path covers exact parity.
    assert tpu_count >= cpu_count - 6


def test_big_path_1v1_diversity():
    """The jittered per-block winners must avoid the candidate-concentration
    starvation: nearly the whole pool pairs up in one interval."""
    mm, got = make_big_mm(max_intervals=2)
    n = 512
    rng = np.random.default_rng(3)
    for i in range(n):
        rank = float(rng.integers(0, 100))
        p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
        mm.add(
            [p],
            p.session_id,
            "",
            f"+properties.rank:>={max(0.0, rank - 30)}"
            f" +properties.rank:<={rank + 30}",
            2,
            2,
            1,
            {},
            {"rank": rank},
        )
    mm.process()
    matched_entries = sum(len(s) for batch in got for s in batch)
    assert matched_entries >= int(0.8 * n), matched_entries
    # Formed pairs must truly satisfy both rank windows one-directionally
    # (searcher side) — validated inside the backend; spot-check sizes.
    for batch in got:
        for entry_set in batch:
            assert len(entry_set) == 2


def test_big_path_embedding_scoring():
    """Embedding similarity steers candidate choice on the big path."""
    mm, got = make_big_mm(max_intervals=1)
    # Enough pool occupancy to push high_water past big_pool_threshold=64,
    # so the two-stage kernel (stage-1 emb priority bump + stage-2 einsum
    # re-score) actually runs — 3 tickets alone stay on the small kernel.
    for i in range(64):
        p = MatchmakerPresence(user_id=f"nu{i}", session_id=f"ns{i}")
        mm.add(
            [p], p.session_id, "", "+properties.grp:noise", 2, 2, 1,
            {"grp": "noise"}, {},
        )
    e = np.zeros(16, np.float32)
    e[0] = 1.0
    f = np.zeros(16, np.float32)
    f[0] = -1.0
    for i, emb in enumerate([e, e, f]):
        p = MatchmakerPresence(user_id=f"eu{i}", session_id=f"es{i}")
        mm.add(
            [p], p.session_id, "", "+properties.grp:emb", 2, 2, 1,
            {"grp": "emb"}, {}, embedding=emb,
        )
    assert mm.backend.pool.high_water >= mm.backend.config.big_pool_threshold
    mm.process()
    # The two aligned embeddings must pair; the anti-aligned one stays.
    emb_matches = [
        sorted(x.presence.user_id for x in entry_set)
        for batch in got
        for entry_set in batch
        if any(x.presence.user_id.startswith("eu") for x in entry_set)
    ]
    assert emb_matches == [["eu0", "eu1"]]


@pytest.mark.parametrize("rev", [False, True])
def test_big_path_stress_at_scale(rev):
    """Larger randomized stress of the two-stage path: parties, count
    multiples, squads, several intervals with churn, pipelining ON (the
    production posture), and the stage-2 priority pre-trim engaged. Every
    formed match must satisfy every member's query/count constraints; the
    pool must drain meaningfully (no assembler starvation)."""
    rng = np.random.default_rng(7)
    specs = _random_pool(rng, 384, party_frac=0.2, multiple=True)

    mm, _ = make_big_mm(
        max_intervals=3, rev_precision=rev, interval_pipelining=True
    )
    matches = []
    _run(mm, specs, intervals=0)  # adds only
    mm.on_matched = matches.append
    for _ in range(6):
        mm.process()
        # Model the production interval gap (the bench does the same):
        # collection only drains COMPLETED device passes.
        mm.backend.wait_idle()
    count = _validate_matches(matches, specs, mutual=rev)
    # With 384 tickets across 3 modes and generous windows, a healthy
    # matcher forms matches covering a large share of the pool.
    assert count >= 150, f"only {count} entries matched"

    # The pipelined backend must be drainable (no stuck fetch threads).
    mm.stop()


# ------------------------------------------- shapes chosen for the chip


def _kernel_inputs(rev):
    """A loaded device pool + padded active rows, as _dispatch hands them
    to the kernel."""
    from nakama_tpu.matchmaker.device import pad_to

    mm, _ = make_big_mm(rev_precision=rev)
    specs = _random_pool(np.random.default_rng(5), 300, party_frac=0.2)
    _run(mm, specs, intervals=0)
    rng = np.random.default_rng(6)
    backend = mm.backend
    backend.pool.flush()
    grid_lo, grid_inv = backend._grid_params()
    slots = pad_to(mm.store.active_slots(), 512, -1)
    emb = rng.normal(size=backend.pool.device["emb"].shape).astype(np.float32)
    pool = dict(backend.pool.device, emb=emb)
    kw = dict(
        fn=8, fs=8, n_cols=512, k=32, rev=rev, with_should=False,
        with_embedding=True, bn=64, interpret=True,
    )
    return pool, slots, grid_lo, grid_inv, kw


@pytest.mark.parametrize("rev", [False, True])
@pytest.mark.parametrize("change", ["row_tile", "stage2_stripes"])
def test_big_kernel_output_independent_of_chip_shapes(
    rev, change, monkeypatch
):
    """What was moved to make the kernel fit the chip moves no result: a
    smaller stage-1 row tile (rows are independent, the jitter keys on
    the row index) and stage 2 run in row stripes both give the same
    candidate lists, slot for slot."""
    from nakama_tpu.matchmaker import device2

    pool, slots, grid_lo, grid_inv, kw = _kernel_inputs(rev)
    topk = device2.topk_candidates_big
    base = np.asarray(topk(pool, slots, grid_lo, grid_inv, bm=64, **kw))
    assert (base >= 0).sum() > 1000  # a real comparison, not all padding
    if change == "row_tile":
        got = topk(pool, slots, grid_lo, grid_inv, bm=16, **kw)
    else:
        # 512 rows x 64 kept winners x 4 B x words -> 8 stripes of 64 rows
        words = len(device2.RECORD_KEYS) + sum(
            pool[c].shape[1] for c in device2._stage2_tables(rev)
        )
        monkeypatch.setattr(
            device2, "STAGE2_GATHER_BYTES", 64 * 64 * 4 * words
        )
        topk.clear_cache()
        got = topk(pool, slots, grid_lo, grid_inv, bm=64, **kw)
        topk.clear_cache()
    assert np.array_equal(base, np.asarray(got))


@pytest.mark.parametrize("order_exact", [True, False])
@pytest.mark.parametrize("with_should", [False, True])
@pytest.mark.parametrize("rev", [False, True])
def test_stage2_record_round_trips(rev, with_should, order_exact, monkeypatch):
    """A candidate's scalars travel as one record row: what stage 2
    unpacks for a candidate block is, key by key and bit for bit, what a
    gather of each pool column gives, and the kernel's output is what it
    is with those column gathers (the parent's) in the record's place."""
    from nakama_tpu.matchmaker import device2

    pool, slots, grid_lo, grid_inv, kw = _kernel_inputs(rev)
    kw.update(with_should=with_should, order_exact=order_exact)
    n = kw["n_cols"]
    rng = np.random.default_rng(8)
    pool = {key: np.array(v[:n]) for key, v in pool.items()}
    # Words a lossy packing would bend: negative values, the high bit.
    odd = rng.choice(n, size=3 * 40, replace=False).reshape(3, 40)
    pool["created"][odd[0]] = -1 - rng.integers(0, 2**31, 40)
    pool["party"][odd[1]] = -1 - rng.integers(0, 2**31, 40)
    pool["flags"][odd[2]] |= np.int32(-(2**31))

    def by_column(pool_n, record, cand, rev):
        keys = device2._stage2_tables(rev) + list(device2.RECORD_KEYS)
        return {key: pool_n[key][cand] for key in keys}

    cand = rng.integers(0, n, size=(96, 64)).astype(np.int32)
    cand[0, :40], cand[1, :40], cand[2, :40] = odd
    got = device2._stage2_gather(
        pool, device2._stage2_record(pool), cand, rev
    )
    want = by_column(pool, None, cand, rev)
    assert set(device2.RECORD_KEYS) < set(got) == set(want)
    for key, block in want.items():
        assert got[key].dtype == block.dtype, key
        assert np.asarray(got[key]).tobytes() == block.tobytes(), key

    topk = device2.topk_candidates_big
    topk.clear_cache()
    out = np.asarray(topk(pool, slots, grid_lo, grid_inv, bm=64, **kw))
    monkeypatch.setattr(device2, "_stage2_gather", by_column)
    topk.clear_cache()
    ref = np.asarray(topk(pool, slots, grid_lo, grid_inv, bm=64, **kw))
    topk.clear_cache()
    assert (ref >= 0).sum() > 1000
    assert np.array_equal(out, ref)


@pytest.mark.parametrize(
    "widths,want",
    [
        # (d, de, dq) -> row tile from a configured 1024
        ((200, 16, 8), 1024),  # the old bench widths, no mutual matching
        ((520, 16, 8), 512),  # shipped default widths
        ((520, 16, 520), 512),  # ... with mutual matching (was refused)
        ((1032, 16, 1032), 256),  # 48 numeric + 32 string fields, mutual
    ],
)
def test_stage1_row_tile_fits_vmem(widths, want):
    from nakama_tpu.matchmaker.device2 import stage1_row_tile

    d, de, dq = widths
    assert stage1_row_tile(1024, 1024, d, de, dq, 128) == want
    # Small tiles (the CPU tests') are left alone; the column tile never
    # moves, so a width nothing fits is an error, not a silent change.
    assert stage1_row_tile(64, 64, d, de, dq, 128) == 64
    with pytest.raises(ValueError, match="VMEM"):
        stage1_row_tile(1024, 1024, 16 * 1024, de, dq, 128)
