"""Multi-device tier (the tier the reference lacks, SURVEY.md §4): the
pool-sharded top-K must agree with the single-device kernel on the virtual
8-device CPU mesh, and the skill model must train under dp/tp shardings."""

from functools import partial

import numpy as np
import pytest


def _build_pool(n=256, fn=8, fs=8, s=8, d=16, seed=0):
    import jax.numpy as jnp

    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend
    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger as quiet_logger

    cfg = MatchmakerConfig(
        pool_capacity=n, candidates_per_ticket=16,
        numeric_fields=fn, string_fields=fs, max_constraints=s,
        embedding_dims=d,
    )
    backend = TpuBackend(cfg, quiet_logger(), row_block=8, col_block=n // 8)
    mm = LocalMatchmaker(quiet_logger(), cfg, backend=backend)
    rng = np.random.default_rng(seed)
    n_tickets = n // 2
    for i in range(n_tickets):
        p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
        m, r = rng.integers(0, 4), rng.integers(0, 100)
        mm.add(
            [p], p.session_id, "",
            f"+properties.mode:m{m} +properties.rank:>={max(0, r-20)} +properties.rank:<={r+20}",
            2, 2, 1, {"mode": f"m{m}"}, {"rank": float(r)},
        )
    backend.pool.flush()
    slots = np.asarray(
        [backend.pool.slot_of[t] for t in mm.tickets], dtype=np.int32
    )
    return backend, slots


def test_sharded_topk_matches_single_device():
    import jax

    from nakama_tpu.matchmaker.device import pad_to, topk_candidates
    from nakama_tpu.parallel import (
        build_row_data,
        make_mesh,
        shard_pool,
        sharded_topk_rows,
    )

    assert len(jax.devices()) == 8, "conftest must provide the virtual mesh"
    backend, slots = _build_pool(n=256)
    a_pad = 128
    padded = pad_to(slots, a_pad, -1)

    kw = dict(k=16, br=8, bc=32, rev=False, with_should=False,
              with_embedding=False)
    s1, i1 = topk_candidates(
        backend.pool.device, padded, n_cols=256, **kw
    )

    mesh = make_mesh(8)
    pool_sharded = shard_pool(backend.pool.device, mesh)
    rows = build_row_data(backend.pool.device, padded)
    s2, i2 = sharded_topk_rows(mesh, pool_sharded, rows, **kw)

    s1, i1, s2, i2 = map(np.asarray, (s1, i1, s2, i2))
    # Same candidate sets with same scores (ordering ties may differ at
    # equal score+created only if duplicated — created_seq is unique, so
    # expect exact equality).
    np.testing.assert_allclose(s1, s2, rtol=1e-6)
    assert (i1 == i2).all()


def test_skill_model_trains_and_separates():
    import jax
    import jax.numpy as jnp

    from nakama_tpu.models import SkillModel, create_train_state, train_step

    model = SkillModel(embed_dim=8, hidden_dim=32, stat_dim=6)
    state, tx = create_train_state(model, jax.random.key(0), 3e-3)
    step = jax.jit(partial(train_step, model, tx))

    # Synthetic truth: player skill = sum of stats; team with higher total
    # skill wins.
    rng = np.random.default_rng(0)

    def batch(n=64, t=3):
        a = rng.normal(size=(n, t, 6)).astype(np.float32)
        b = rng.normal(size=(n, t, 6)).astype(np.float32)
        won = (a.sum((1, 2)) > b.sum((1, 2))).astype(np.float32)
        return {"team_a": jnp.asarray(a), "team_b": jnp.asarray(b),
                "a_won": jnp.asarray(won)}

    first_loss = None
    for i in range(60):
        state, loss = step(state, batch())
        if first_loss is None:
            first_loss = float(loss)
    assert float(loss) < first_loss * 0.7, (first_loss, float(loss))
    assert int(state.step) == 60


def test_skill_model_sharded_training():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from nakama_tpu.models import SkillModel, create_train_state, train_step

    devices = np.asarray(jax.devices()).reshape(4, 2)
    mesh = Mesh(devices, ("dp", "tp"))
    model = SkillModel(embed_dim=8, hidden_dim=64, stat_dim=6)
    state, tx = create_train_state(model, jax.random.key(0))

    # dp over batch; tp over the hidden dim of the MLP kernels.
    def shard_params(path, x):
        name = "/".join(str(p.key) for p in path if hasattr(p, "key"))
        if x.ndim == 2 and "in_proj" in name:
            return jax.device_put(x, NamedSharding(mesh, P(None, "tp")))
        if x.ndim == 2 and "mid_proj" in name:
            return jax.device_put(x, NamedSharding(mesh, P("tp", None)))
        return jax.device_put(x, NamedSharding(mesh, P()))

    state = jax.tree_util.tree_map_with_path(
        shard_params, state, is_leaf=lambda x: hasattr(x, "ndim")
    )
    batch_sharding = NamedSharding(mesh, P("dp"))
    rng = np.random.default_rng(1)
    a = rng.normal(size=(32, 3, 6)).astype(np.float32)
    b = rng.normal(size=(32, 3, 6)).astype(np.float32)
    batch = {
        "team_a": jax.device_put(jnp.asarray(a), batch_sharding),
        "team_b": jax.device_put(jnp.asarray(b), batch_sharding),
        "a_won": jax.device_put(
            jnp.asarray((a.sum((1, 2)) > b.sum((1, 2))).astype(np.float32)),
            batch_sharding,
        ),
    }
    from functools import partial

    step = jax.jit(partial(train_step, model, tx))
    state2, loss = step(state, batch)
    assert np.isfinite(float(loss))


def test_embedding_scoring_prefers_similar():
    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger as quiet_logger
    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    cfg = MatchmakerConfig(
        pool_capacity=64, candidates_per_ticket=64, numeric_fields=8,
        string_fields=8, max_constraints=8, embedding_dims=4,
        # Synchronous oracle path: one process() == one delivery.
        interval_pipelining=False,
    )
    backend = TpuBackend(cfg, quiet_logger(), row_block=8, col_block=8)
    got = []
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend, on_matched=got.extend
    )

    def player(name, emb):
        p = MatchmakerPresence(user_id=name, session_id="sess-" + name)
        return mm.add(
            [p], p.session_id, "", "*", 2, 2, 1, {}, {},
            embedding=np.asarray(emb, np.float32),
        )[0]

    searcher = player("searcher", [1, 0, 0, 0])
    far = player("far", [-1, 0, 0, 0])
    near = player("near", [0.9, 0.1, 0, 0])
    mm.process()
    assert got
    for entry_set in got:
        names = {e.presence.user_id for e in entry_set}
        if "searcher" in names:
            assert "near" in names


def test_full_process_on_mesh_matches_single_device():
    """The PRODUCTION path on an 8-device mesh: LocalMatchmaker.process()
    with config.mesh_devices=8 must form the same matches as the
    single-device backend (VERDICT r1 #1 done-criterion)."""
    import jax

    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger as quiet_logger
    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    assert len(jax.devices()) >= 8, "conftest provides the 8-CPU mesh"

    def build(mesh_devices):
        cfg = MatchmakerConfig(
            pool_capacity=512,
            candidates_per_ticket=16,
            numeric_fields=8,
            string_fields=8,
            max_constraints=8,
            mesh_devices=mesh_devices,
        )
        backend = TpuBackend(
            cfg, quiet_logger(), row_block=16, col_block=64
        )
        matched = []
        mm = LocalMatchmaker(
            quiet_logger(), cfg, backend=backend,
            on_matched=lambda sets: matched.extend(sets),
        )
        rng = np.random.default_rng(7)
        for i in range(300):
            p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
            m, r = rng.integers(0, 4), rng.integers(0, 100)
            mm.add(
                [p], p.session_id, "",
                f"+properties.mode:m{m}"
                f" +properties.rank:>={max(0, r - 20)}"
                f" +properties.rank:<={r + 20}",
                2, 2, 1, {"mode": f"m{m}"}, {"rank": float(r)},
            )
        return mm, matched

    mm_single, matched_single = build(0)
    mm_mesh, matched_mesh = build(8)
    assert mm_mesh.backend.mesh is not None
    for _ in range(2):
        mm_single.process()
        mm_mesh.process()

    def pairs(matched):
        return sorted(
            tuple(sorted(e.presence.user_id for e in s)) for s in matched
        )

    assert pairs(matched_mesh) == pairs(matched_single)
    assert len(matched_mesh) > 20  # the pool genuinely matched


def test_full_process_on_mesh_big_kernel_matches_single_device():
    """VERDICT r2 #2 done-criterion: above big_pool_threshold the mesh
    path must run the sharded two-stage MXU kernel
    (device2.topk_candidates_big_sharded) and form the SAME matches as
    the unsharded big kernel — the per-block winner set is provably
    identical (global `m`, global column ids), so parity is exact."""
    import jax

    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger as quiet_logger
    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    assert len(jax.devices()) >= 8, "conftest provides the 8-CPU mesh"

    def build(mesh_devices):
        cfg = MatchmakerConfig(
            pool_capacity=512,
            candidates_per_ticket=16,
            numeric_fields=8,
            string_fields=8,
            max_constraints=8,
            mesh_devices=mesh_devices,
            big_pool_threshold=64,  # force the MXU path at test scale
        )
        backend = TpuBackend(
            cfg, quiet_logger(), row_block=16, col_block=64,
            big_row_block=16, big_col_block=32,
        )
        matched = []
        mm = LocalMatchmaker(
            quiet_logger(), cfg, backend=backend,
            on_matched=lambda sets: matched.extend(sets),
        )
        rng = np.random.default_rng(11)
        for i in range(300):
            p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
            m, r = rng.integers(0, 4), rng.integers(0, 100)
            mm.add(
                [p], p.session_id, "",
                f"+properties.mode:m{m}"
                f" +properties.rank:>={max(0, r - 20)}"
                f" +properties.rank:<={r + 20}",
                2, 2, 1, {"mode": f"m{m}"}, {"rank": float(r)},
            )
        # Exact assembler parity is what this test proves: one ticket
        # that is no pair (a trio in a mode of its own, it never
        # matches) keeps the pool off the device-pairing handshake a
        # pure-1v1 pool takes (its own tests: test_matchmaker_tpu.py).
        p = MatchmakerPresence(user_id="trio", session_id="trio")
        mm.add(
            [p], p.session_id, "", "+properties.mode:trio", 3, 3, 1,
            {"mode": "trio"}, {},
        )
        return mm, matched

    mm_single, matched_single = build(0)
    mm_mesh, matched_mesh = build(8)
    assert mm_mesh.backend.mesh is not None
    # Prove the big path actually dispatched (not a silent small-path
    # fallback): capture the pending tag.
    tags = []
    orig = mm_mesh.backend._dispatch_sharded

    def spy(*a, **kw):
        pending = orig(*a, **kw)
        tags.append(pending.variant["kernel"])
        return pending

    mm_mesh.backend._dispatch_sharded = spy
    for _ in range(2):
        mm_single.process()
        mm_mesh.process()

    assert any(
        t.startswith("topk_candidates_big_sharded/") for t in tags
    ), "mesh path did not take the sharded MXU kernel"
    assert not any("pair_partners" in t for t in tags)

    def pairs(matched):
        return sorted(
            tuple(sorted(e.presence.user_id for e in s)) for s in matched
        )

    assert pairs(matched_mesh) == pairs(matched_single)
    assert len(matched_mesh) > 20  # the pool genuinely matched


def _build_paired_mm(mesh_devices):
    """A pool whose ONLY valid matches are designed pairs: each episode
    i has a unique `mk` property value shared by exactly two players,
    added 128 slots apart so under the 8-way mesh (512-slot pool, 64
    slots/shard) every pair spans two shards — cross-shard pairings are
    pinned, not incidental."""
    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger as quiet_logger
    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    cfg = MatchmakerConfig(
        pool_capacity=512,
        candidates_per_ticket=16,
        numeric_fields=8,
        string_fields=8,
        max_constraints=8,
        mesh_devices=mesh_devices,
    )
    backend = TpuBackend(cfg, quiet_logger(), row_block=16, col_block=64)
    matched = []
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend,
        on_matched=lambda sets: matched.extend(sets),
    )
    n_pairs = 128
    for half in range(2):
        for i in range(n_pairs):
            uid = f"p{i}h{half}"
            p = MatchmakerPresence(user_id=uid, session_id=uid)
            mm.add(
                [p], p.session_id, "", f"+properties.mk:v{i}",
                2, 2, 1, {"mk": f"v{i}"}, {},
            )
    return mm, matched


def test_mesh_parity_cross_shard_pairs_1_2_8_way():
    """Seeded host-oracle parity for the sharded path at every mesh
    width: 1-, 2-, and 8-way meshes must form the IDENTICAL matched
    cohorts as the single-device backend on a pool whose episodes pin
    cross-shard pairings (each unique `mk` value's two holders sit 128
    slots — two 8-way shards — apart). Greedy assignment stays global,
    so a pairing spanning shards is first-class, not a merge artifact."""
    import jax

    assert len(jax.devices()) >= 8, "conftest provides the 8-CPU mesh"

    def cohorts(mesh_devices):
        mm, matched = _build_paired_mm(mesh_devices)
        if mesh_devices:
            assert mm.backend.mesh is not None
        for _ in range(2):
            mm.process()
        return sorted(
            tuple(sorted(e.presence.user_id for e in s)) for s in matched
        )

    expect = sorted(
        (f"p{i}h0", f"p{i}h1") for i in range(128)
    )
    oracle = cohorts(0)
    assert oracle == expect, "single-device oracle missed designed pairs"
    for n_dev in (1, 2, 8):
        assert cohorts(n_dev) == expect, f"{n_dev}-way mesh diverged"


def test_mesh_cross_shard_pairs_span_shards():
    """The pinning premise itself: under the 8-way mesh the designed
    pairs' slots land on DIFFERENT column shards (64 slots each), so
    the parity above genuinely exercises cross-shard matching."""
    import jax

    assert len(jax.devices()) >= 8
    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger as quiet_logger
    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    cfg = MatchmakerConfig(
        pool_capacity=512, candidates_per_ticket=16, numeric_fields=8,
        string_fields=8, max_constraints=8, mesh_devices=8,
    )
    backend = TpuBackend(cfg, quiet_logger(), row_block=16, col_block=64)
    mm = LocalMatchmaker(quiet_logger(), cfg, backend=backend)
    tickets = {}

    def add_half(half):
        for i in range(16):
            uid = f"x{i}h{half}"
            p = MatchmakerPresence(user_id=uid, session_id=uid)
            tickets[uid] = mm.add(
                [p], p.session_id, "", f"+properties.mk:v{i}",
                2, 2, 1, {"mk": f"v{i}"}, {},
            )[0]

    add_half(0)
    # Occupy the gap so the halves sit a full shard apart in slot space.
    for j in range(100):
        uid = f"fill{j}"
        p = MatchmakerPresence(user_id=uid, session_id=uid)
        mm.add([p], p.session_id, "", "+properties.mk:zz", 2, 2, 1,
               {"mk": f"w{j}"}, {})
    add_half(1)
    backend.pool.flush()
    shard = 512 // 8
    crossing = 0
    for i in range(16):
        s0 = backend.pool.slot_of[tickets[f"x{i}h0"]]
        s1 = backend.pool.slot_of[tickets[f"x{i}h1"]]
        if s0 // shard != s1 // shard:
            crossing += 1
    assert crossing == 16, f"only {crossing}/16 designed pairs cross shards"


def test_mesh_recompile_budget_pool_churn():
    """Compile-watch gate, mesh leg: after warmup, pow2 active-count
    churn on the SHARDED path (shard_score + gather_merge) must compile
    nothing — the lru-cached shard_map builders (parallel/mesh.py) keep
    jit identity stable across dispatches, and this pins that as an
    enforced invariant rather than a docstring."""
    import jax

    from nakama_tpu.devobs import DEVOBS

    assert len(jax.devices()) >= 8
    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger as quiet_logger
    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    DEVOBS.reset()
    try:
        cfg = MatchmakerConfig(
            pool_capacity=512, candidates_per_ticket=8, numeric_fields=4,
            string_fields=4, max_constraints=4, max_intervals=50,
            mesh_devices=8, interval_pipelining=False,
        )
        backend = TpuBackend(
            cfg, quiet_logger(), row_block=8, col_block=64
        )
        mm = LocalMatchmaker(quiet_logger(), cfg, backend=backend)

        def interval(n, prefix):
            for i in range(n):
                sid = f"{prefix}-{i}"
                p = MatchmakerPresence(user_id=sid, session_id=sid)
                mm.add([p], sid, "", "*", 2, 2, 1, {}, {})
            mm.process()
            backend.wait_idle()
            mm.store.drain()

        warm_sizes = [3, 9, 17]  # row pads 8/16/32
        steady_sizes = [2, 12, 6, 24, 4]  # same pads, different counts
        DEVOBS.configure(warmup_intervals=len(warm_sizes) + 1)
        for it, n in enumerate(warm_sizes):
            interval(n, f"w{it}")
        interval(0, "wdrain")
        assert DEVOBS.warmed
        compiles_at_warm = DEVOBS.compiles_total
        for it, n in enumerate(steady_sizes):
            interval(n, f"s{it}")
        interval(0, "sdrain")
        assert DEVOBS.recompiles_total == 0, (
            "mesh-path churn recompiled: "
            f"{[k for k in DEVOBS.kernel_stats() if k['recompiles']]}"
        )
        assert DEVOBS.compiles_total == compiles_at_warm, (
            f"mesh steady phase compiled: {DEVOBS.compiles_total} vs"
            f" {compiles_at_warm} at warmup close"
        )
        mm.stop()
    finally:
        DEVOBS.reset()


def test_describe_mesh_reports_shard_occupancy_and_gather_bytes():
    """The console satellite: given the live (sharded) pool arrays,
    describe_mesh reports per-device slot counts, FLAG_VALID occupancy
    and resident HBM bytes, plus the last merge's gather cost."""
    import jax

    assert len(jax.devices()) >= 8
    from nakama_tpu.parallel.mesh import describe_mesh

    backend, slots = _build_pool(n=256)
    from nakama_tpu.parallel import make_mesh, shard_pool

    mesh = make_mesh(8)
    pool_sharded = shard_pool(backend.pool.device, mesh)
    out = describe_mesh(
        mesh, pool_capacity=256, pool=pool_sharded, gather_bytes=4096
    )
    m = out["mesh"]
    assert m["slots_per_device"] == 32
    assert m["gather_bytes"] == 4096
    shards = m["shards"]
    assert len(shards) == 8
    assert all(s["slots"] == 32 for s in shards)
    assert all(s["hbm_bytes"] > 0 for s in shards)
    assert sum(s["occupied"] for s in shards) == len(slots)
    # Hermetic on a jax-less view too: no mesh -> devices only.
    assert describe_mesh(None)["mesh"] is None


def test_device_pairing_runs_on_mesh():
    """Round-4 device-side 1v1 pairing under the 8-device mesh
    (VERDICT r4 #8): a synchronous pure-1v1 pool over the sharded big
    kernel takes the pair_partners handshake on the ICI-merged candidate
    lists, and its matches respect the pool-separating required terms."""
    import jax

    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.logger import test_logger as quiet_logger
    from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
    from nakama_tpu.matchmaker.tpu import TpuBackend

    assert len(jax.devices()) >= 8

    cfg = MatchmakerConfig(
        pool_capacity=128 * 8,
        candidates_per_ticket=8,
        numeric_fields=8,
        string_fields=8,
        max_constraints=8,
        mesh_devices=8,
        big_pool_threshold=16,
        interval_pipelining=False,
    )
    backend = TpuBackend(
        cfg, quiet_logger(), row_block=16, col_block=128,
        big_row_block=16, big_col_block=128,
    )
    matched: list = []
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend,
        on_matched=lambda sets: matched.extend(sets),
    )
    rng = np.random.default_rng(11)
    for i in range(64):
        p = MatchmakerPresence(user_id=f"dpu{i}", session_id=f"dps{i}")
        mode = int(rng.integers(0, 4))
        mm.add(
            [p], p.session_id, "", f"+properties.mode:m{mode}",
            2, 2, 1, {"mode": f"m{mode}"}, {},
        )
    mm.process()
    assert matched, "pairing on the mesh formed no matches"
    for entry_set in matched:
        assert len(entry_set) == 2
        modes = {e.string_properties["mode"] for e in entry_set}
        assert len(modes) == 1, f"pairing crossed pools: {modes}"


def test_mesh_shard_regression_gate():
    """The bench's mesh gate is a named pure function so tier 1 can
    pin its tripwires (the cadence_regression convention): parity
    drift, post-warmup recompiles, and a p99 blowout each produce a
    named reason and regression=True; a clean run produces neither."""
    import bench

    gate = bench.mesh_shard_regression
    reasons, bad = gate(0, 0, 100.0, 20.9, 25.0)
    assert not bad and reasons == []
    reasons, bad = gate(2, 0, 100.0, 20.9, 25.0)
    assert bad and "mesh_parity_diff=2" in reasons[0]
    reasons, bad = gate(0, 1, 100.0, 20.9, 25.0)
    assert bad and "recompiles_after_warmup=1" in reasons[0]
    reasons, bad = gate(0, 0, 20.9 * 25.0 + 1, 20.9, 25.0)
    assert bad and "p99" in reasons[0]
    # All three at once: every reason present, still one verdict.
    reasons, bad = gate(1, 1, 10_000.0, 20.9, 25.0)
    assert bad and len(reasons) == 3
    # The shipped default ratio exists and is sane.
    assert bench.MESH_P99_RATIO_MAX > 1
