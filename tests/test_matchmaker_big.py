"""Two-stage MXU kernel path (device2.py): validity + quality parity vs the
CPU oracle, running the Pallas stage-1 in interpreter mode on the virtual
CPU device. Mirrors the small-kernel parity tier (test_matchmaker_tpu.py)
at a pool size that exercises the bucket-mask prefilter + exact re-rank."""

import jax
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
        words = device2.stage2_words(pool, rev, kw["with_should"])
        assert device2.stage2_stripe(512, 64, words) == 512
        monkeypatch.setattr(
            device2, "STAGE2_GATHER_BYTES", 64 * 64 * 4 * words
        )
        assert device2.stage2_stripe(512, 64, words) == 64
        topk.clear_cache()
        got = topk(pool, slots, grid_lo, grid_inv, bm=64, **kw)
        topk.clear_cache()
    assert np.array_equal(base, np.asarray(got))


@pytest.mark.parametrize("order_exact", [True, False])
@pytest.mark.parametrize("with_should", [False, True])
@pytest.mark.parametrize("rev", [False, True])
def test_stage2_record_round_trips(rev, with_should, order_exact, monkeypatch):
    """A candidate's scalars travel as one record row and, under rev, its
    query mirrors as one more: what stage 2 unpacks for a candidate block
    is, key by key and bit for bit, what a gather of each pool column
    gives, and the kernel's output is what it is with those column
    gathers (the parent's) in the records' place."""
    from nakama_tpu.matchmaker import device2

    pool, slots, grid_lo, grid_inv, kw = _kernel_inputs(rev)
    kw.update(with_should=with_should, order_exact=order_exact)
    n = kw["n_cols"]
    rng = np.random.default_rng(8)
    pool = {key: np.array(v[:n]) for key, v in pool.items()}
    # Words a lossy packing would bend: negative values, the high bit,
    # and in the f32 mirrors what a float round trip would not keep.
    odd = rng.choice(n, size=5 * 40, replace=False).reshape(5, 40)
    pool["created"][odd[0]] = -1 - rng.integers(0, 2**31, 40)
    pool["party"][odd[1]] = -1 - rng.integers(0, 2**31, 40)
    pool["flags"][odd[2]] |= np.int32(-(2**31))
    bent = np.array(
        [-0.0, np.inf, -np.inf, np.nan, 1e-45, -1e-40, 1.1754942e-38],
        np.float32,
    )
    for key in ("n_lo", "n_hi", "n_flo", "n_fhi"):
        pool[key][odd[3]] = rng.choice(bent, size=pool[key][odd[3]].shape)
    # a NaN with a payload of its own
    pool["n_lo"][odd[3][0], 0] = np.array([0x7FC12345], np.int32).view(
        np.float32
    )[0]
    for key in ("s_req", "s_forb"):
        words = pool[key][odd[4]].shape
        pool[key][odd[4]] = -1 - rng.integers(0, 2**31, words)
        pool[key][odd[4][:20]] |= np.int32(-(2**31))

    def by_column(pool_n, record, mirror, cand, with_should):
        keys = device2._stage2_tables(mirror is not None, with_should)
        return {
            key: pool_n[key][cand]
            for key in keys + list(device2.RECORD_KEYS)
        }

    def rev_ok_by_accepts(col, rowq, with_should):
        # the parent's: `_accepts` over the column gathers, sides exchanged
        def one_row_rev(colrow, qrow):
            vals = {key: v[None] for key, v in qrow.items()}
            return device2._accepts(colrow, vals, with_should)[0][0]

        return jax.vmap(one_row_rev)(col, rowq)

    cand = rng.integers(0, n, size=(96, 64)).astype(np.int32)
    for i, rows in enumerate(odd):
        cand[i, :40] = rows
    mirror = device2._stage2_mirror(pool, with_should) if rev else None
    record = device2._stage2_record(pool, rev, with_should)
    # every word the mirrors add rides one of the two rows
    assert record.shape[1] + (mirror.shape[1] if rev else 0) + sum(
        pool[key].shape[1] for key in ("num", "str", "emb")
    ) == device2.stage2_words(pool, rev, with_should)
    got = device2._stage2_gather(pool, record, mirror, cand, with_should)
    want = by_column(pool, None, mirror, cand, with_should)
    if rev:  # the two gathered blocks, as `_mirror_accepts` reads them
        ints, floats = got.pop("mirror")
        assert np.array_equal(ints, np.asarray(record)[cand])
        assert np.asarray(floats).tobytes() == (
            np.asarray(mirror)[cand].tobytes()
        )
    assert set(device2.RECORD_KEYS) < set(got) == set(want)
    assert (set(device2.MIRROR_KEYS) <= set(got)) == rev
    assert (set(device2.SHOULD_KEYS) <= set(got)) == (rev and with_should)
    for key, block in want.items():
        assert got[key].dtype == block.dtype, key
        assert np.asarray(got[key]).tobytes() == block.tobytes(), key

    topk = device2.topk_candidates_big
    topk.clear_cache()
    out = np.asarray(topk(pool, slots, grid_lo, grid_inv, bm=64, **kw))
    monkeypatch.setattr(device2, "_stage2_gather", by_column)
    monkeypatch.setattr(device2, "_stage2_rev_ok", rev_ok_by_accepts)
    topk.clear_cache()
    ref = np.asarray(topk(pool, slots, grid_lo, grid_inv, bm=64, **kw))
    topk.clear_cache()
    assert (ref >= 0).sum() > 1000
    assert np.array_equal(out, ref)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("widths", [(24, 16), (8, 8), (3, 5)])
def test_mirror_accepts_is_accepts_on_whole_blocks(widths, seed):
    """The reverse check reads the gathered record blocks lane for lane:
    on every pair it says what `_accepts` says of the same words —
    bounds met on the edge and missed by one float, empty and full
    forbidden intervals, NaN and infinities on either side, required and
    forbidden terms that are 0, the row's own, another's, negative."""
    from nakama_tpu.matchmaker import device, device2

    fn, fs = widths
    rng = np.random.default_rng(seed)
    r, b = 24, 40
    edge = np.array(
        [-np.inf, -3.4e38, -1.0, -0.0, 0.0, 1e-45, 1.0, np.nextafter(
            np.float32(1.0), np.float32(2.0)), 2.0, 3.4e38, np.inf, np.nan],
        np.float32,
    )
    num = rng.choice(edge, size=(r, fn))
    words = np.array([0, 1, 2, 7, -5, -(2**31), 2**31 - 1], np.int32)
    sv = rng.choice(words, size=(r, fs))
    q = dict(
        n_lo=rng.choice(edge, size=(r, b, fn)),
        n_hi=rng.choice(edge, size=(r, b, fn)),
        n_flo=rng.choice(edge, size=(r, b, fn)),
        n_fhi=rng.choice(edge, size=(r, b, fn)),
        s_req=rng.choice(words, size=(r, b, fs)),
        s_forb=rng.choice(words, size=(r, b, fs)),
    )
    # most fields unconstrained, so that whole queries pass too
    free = rng.random((r, b, fn)) < 1 - 1.0 / fn
    q["n_lo"][free], q["n_hi"][free] = -np.inf, np.inf
    free = rng.random((r, b, fn)) < 1 - 1.0 / fn
    q["n_flo"][free], q["n_fhi"][free] = 1.0, -1.0
    num[np.isnan(num) & (rng.random(num.shape) < 0.95)] = 0.5
    for key in ("s_req", "s_forb"):
        q[key][rng.random((r, b, fs)) < 1 - 0.7 / fs] = 0
    flags = rng.choice(
        np.array([1, 3, 1 | device.FLAG_NEVER], np.int32), size=(r, b),
        p=[0.6, 0.3, 0.1],
    )
    scalars = rng.integers(-9, 9, size=(r, b, len(device2.RECORD_KEYS)))
    ints = np.concatenate(
        [scalars.astype(np.int32), q["s_req"], q["s_forb"]], axis=2
    )
    floats = np.concatenate(
        [q[key] for key in ("n_lo", "n_hi", "n_flo", "n_fhi")], axis=2
    )
    rowq = dict(num=num, str=sv)
    got = np.asarray(device2._mirror_accepts(ints, floats, rowq, flags))

    def one_row(colrow, qrow):
        vals = {key: v[None] for key, v in qrow.items()}
        return device2._accepts(colrow, vals, False)[0][0]

    want = np.asarray(jax.vmap(one_row)(dict(q, flags=flags), rowq))
    assert got.dtype == want.dtype == bool and got.shape == (r, b)
    assert 0.02 < want.mean() < 0.9, want.mean()  # both answers are there
    assert np.array_equal(got, want)


@pytest.mark.parametrize(
    "a_pad,words,want",
    [
        (131072, 62, 8192),  # the forward program, `ranked100k.burst`
        (262144, 62, 8192),  # ... at `multiqueue8x20k.burst`'s rows
        (65536, 62, 8192),  # ... at `squad50k.burst`'s
        (131072, 190, 2048),  # under rev, `mutual100k.burst`
        (131072, 286, 1024),  # under rev with should queries
        (4096, 62, 4096),  # a dispatch smaller than one stripe: itself
        (1024, 286, 1024),
    ],
)
def test_stage2_stripe_at_shipped_widths(a_pad, words, want):
    """The stripe rule counts the words the program gathers per kept
    winner (keep = 128 at k = 64) and no others."""
    from nakama_tpu.matchmaker import device, device2

    cfg = MatchmakerConfig()
    keep = device2.stage2_keep(256, cfg.candidates_per_ticket)
    assert keep == 128
    assert device2.stage2_stripe(a_pad, keep, words) == want
    pool = device.pool_schema(
        8, cfg.numeric_fields, cfg.string_fields, cfg.max_constraints,
        cfg.embedding_dims,
    )
    assert [
        device2.stage2_words(pool, rev, should)
        for rev, should in ((False, False), (True, False), (True, True))
    ] == [62, 190, 286]
    # without rev the should slots never travel, queries or not
    assert device2.stage2_words(pool, False, True) == 62


def test_stage2_tables_hold_what_the_program_reads():
    from nakama_tpu.matchmaker import device2

    assert device2._stage2_tables(False, False) == ["num", "str", "emb"]
    assert device2._stage2_tables(False, True) == ["num", "str", "emb"]
    lone = device2._stage2_tables(True, False)
    assert set(device2.MIRROR_KEYS) < set(lone)
    assert not [key for key in lone if key.startswith("sh_")]
    both = device2._stage2_tables(True, True)
    assert both[: len(lone)] == lone
    assert set(both) - set(lone) == set(device2.SHOULD_KEYS)
    assert len(device2.SHOULD_KEYS) == 6
    assert all(key in device2.ROWQ_KEYS for key in both)


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
