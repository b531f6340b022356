"""Device rank engine (ISSUE 8): host-vs-device parity under randomized
workloads (both sort orders, deletes, identical resubmits, expiry
rollover), the tournament lifecycle sweep (create -> join -> writes ->
scheduler reset -> reward sweep) asserted identical between the host
oracle and the DeviceRankEngine, the degradation ladder (breaker
fallback, half-open probe, deadline short-circuit, armed flush/rank
faults), PR 6 spans, PR 7 snapshot/restore, and the bench's named
`leaderboard_rank_regression` gate contract."""

import random
import time

from fixtures import quiet_logger

from nakama_tpu import faults
from nakama_tpu import tracing as trace_api
from nakama_tpu.config import LeaderboardConfig
from nakama_tpu.leaderboard import (
    DeviceRankEngine,
    LeaderboardRankCache,
    LeaderboardScheduler,
    Leaderboards,
    Tournaments,
)
from nakama_tpu.overload import Deadline, deadline_scope
from nakama_tpu.storage.db import Database


def eng_cfg(**overrides):
    kw = dict(
        device_min_board_size=0,
        device_flush_dirty_threshold=64,
        device_flush_interval_sec=0.05,
        device_breaker_threshold=2,
        device_breaker_cooldown_ms=40,
    )
    kw.update(overrides)
    return LeaderboardConfig(**kw)


def make_engine(oracle=None, **overrides):
    oracle = oracle or LeaderboardRankCache()
    engine = DeviceRankEngine(
        eng_cfg(**overrides), quiet_logger(), oracle=oracle
    )
    return oracle, engine


def mirror_insert(oracle, engine, board, expiry, sort_order, owner,
                  score, sub=0):
    oracle.insert(board, expiry, sort_order, owner, score, sub)
    engine.record_upsert(board, expiry, sort_order, owner)


# ---------------------------------------------------------------- parity


def test_device_rank_parity_randomized():
    """Hypothesis-style seeded sweep: random board sizes, both sort
    orders, upserts + deletes + identical resubmits; after a flush the
    device answers (ranks, windows, sweeps) must equal the oracle's."""
    for seed in range(6):
        rng = random.Random(1000 + seed)
        sort_order = seed % 2
        n = rng.randrange(40, 400)
        oracle, engine = make_engine()
        owners = [f"u{i}" for i in range(n)]
        for o in owners:
            mirror_insert(oracle, engine, "b", 0.0, sort_order, o,
                          rng.randrange(30), rng.randrange(4))
        for o in rng.sample(owners, n // 4):
            oracle.delete("b", 0.0, o)
            engine.record_delete("b", 0.0, o)
        for o in rng.sample(owners, n // 3):
            mirror_insert(oracle, engine, "b", 0.0, sort_order, o,
                          rng.randrange(30), rng.randrange(4))
        assert engine.flush_all()
        q = owners + ["missing"]
        assert engine.get_many("b", 0.0, q) == oracle.get_many(
            "b", 0.0, q
        )
        for start in (0, 3, max(0, oracle.count("b", 0.0) - 2)):
            assert engine.rank_window(
                "b", 0.0, start, 17
            ) == oracle.rank_window("b", 0.0, start, 17)
        swept = engine.sweep_many([("b", 0.0)])
        assert swept[("b", 0.0)] == oracle.standings("b", 0.0)


def test_device_expiry_rollover_and_trim():
    oracle, engine = make_engine()
    for bucket in (100.0, 200.0):
        for i in range(20):
            mirror_insert(oracle, engine, "d", bucket, 1, f"u{i}", i)
    assert engine.flush_all()
    assert engine.get_many("d", 100.0, ["u3"]) == oracle.get_many(
        "d", 100.0, ["u3"]
    )
    oracle.trim_expired(150.0)
    assert engine.trim_expired(150.0) == 1
    # The trimmed bucket falls back (board gone); the live one serves.
    assert engine.get_many("d", 100.0, ["u3"]) is None
    assert engine.get_many("d", 200.0, ["u3"]) == oracle.get_many(
        "d", 200.0, ["u3"]
    )


def test_min_board_size_gates_adoption():
    """Small boards stay host-only (the bisect oracle wins there);
    crossing the threshold adopts the whole board from the oracle."""
    oracle, engine = make_engine(device_min_board_size=10)
    for i in range(9):
        mirror_insert(oracle, engine, "s", 0.0, 1, f"u{i}", i)
    assert engine.get_many("s", 0.0, ["u1"]) is None  # not adopted
    mirror_insert(oracle, engine, "s", 0.0, 1, "u9", 9)
    assert engine.get_many("s", 0.0, [f"u{i}" for i in range(10)]) == (
        oracle.get_many("s", 0.0, [f"u{i}" for i in range(10)])
    )


def test_percentile_from_rank_and_count():
    oracle, engine = make_engine()
    for i in range(10):
        mirror_insert(oracle, engine, "pct", 0.0, 1, f"u{i}", i)
    assert engine.flush_all()
    assert engine.percentile("pct", 0.0, "u9") == (0, 10, 0.1)  # best
    assert engine.percentile("pct", 0.0, "u0") == (9, 10, 1.0)  # worst
    assert engine.percentile("pct", 0.0, "missing") == (-1, 10, 1.0)
    assert engine.percentile("pct", 123.0, "u9") is None  # host serves


def test_out_of_range_scores_stay_host_only():
    oracle, engine = make_engine()
    mirror_insert(oracle, engine, "big", 0.0, 1, "a", 1)
    mirror_insert(oracle, engine, "big", 0.0, 1, "b", 2**40)
    assert engine.get_many("big", 0.0, ["a", "b"]) is None  # host serves
    assert oracle.get_many("big", 0.0, ["a", "b"]) == [1, 0]


# ----------------------------------------------------- lifecycle + sweep


async def test_tournament_lifecycle_sweep_parity():
    """create -> join -> writes -> scheduler reset -> reward sweep: the
    standings handed to the reset/end hooks match the host oracle
    exactly, across randomized sizes, both sort orders, and an expiry
    rollover driven through the real scheduler fire path."""
    from nakama_tpu.config import Config
    from nakama_tpu.runtime import Initializer, Runtime

    for seed, sort_order in ((1, "desc"), (2, "asc")):
        rng = random.Random(seed)
        db = Database(":memory:")
        await db.connect()
        oracle, engine = make_engine()
        lb = Leaderboards(quiet_logger(), db, oracle,
                          device_engine=engine)
        await lb.load()
        t = Tournaments(lb)
        fired = []
        runtime = Runtime(quiet_logger(), Config())
        init = Initializer(runtime)
        init.register_tournament_end(
            lambda ctx, b, when: fired.append(("end", b))
        )
        init.register_tournament_reset(
            lambda ctx, b, when: fired.append(("reset", b))
        )
        sched = LeaderboardScheduler(quiet_logger(), lb, t, runtime)
        now = time.time()
        # Nothing here races the wall clock: the hourly reset lies half
        # an hour from now whatever the time of day, so no bucket
        # boundary falls inside the test, and the tournament ends well
        # after its last write — the scheduler below is fired at a time
        # of its own, past that end.
        minute = (time.gmtime(now).tm_min + 30) % 60
        end_time = now + 60.0
        await t.create(
            "cup", duration=3600, sort_order=sort_order,
            reset_schedule=f"{minute} * * * *", start_time=now - 7200,
            end_time=end_time, operator="best",
        )
        n = rng.randrange(15, 60)
        for i in range(n):
            await t.join("cup", f"p{i}")
            await t.record_write("cup", f"p{i}",
                                 score=rng.randrange(40))
        # A few rewrites (best semantics) + identical resubmits.
        for i in rng.sample(range(n), n // 3):
            await t.record_write("cup", f"p{i}",
                                 score=rng.randrange(40))
        expiry = lb.get("cup").expiry_at(now)
        host_standings = oracle.standings("cup", expiry)
        # Device sweep parity BEFORE the scheduler consumes it.
        assert t.reward_sweep("cup", expiry_override=expiry) == (
            host_standings
        )
        assert engine.sweeps >= 1  # it really was the device path
        # Scheduler end fire: the hook payload carries the final sweep.
        assert t.is_active(lb.get("cup"))  # every write was in time
        await sched._fire(end_time + 0.8)
        ends = [b for kind, b in fired if kind == "end"]
        assert ends and ends[0]["standings"] == host_standings
        # Expiry rollover: writes after the bucket boundary land in a
        # fresh bucket on both structures.
        await db.close()


async def test_leaderboards_routed_reads_match_host():
    """records_list / records_haystack through the full core path give
    identical results with and without the device engine."""
    db = Database(":memory:")
    await db.connect()
    oracle, engine = make_engine()
    lb = Leaderboards(quiet_logger(), db, oracle, device_engine=engine)
    await lb.load()
    await lb.create("arena")
    for i in range(40):
        await lb.record_write("arena", f"u{i}", score=i * 3 % 17,
                              subscore=i % 5)
    assert engine.flush_all()
    page = await lb.records_list("arena", limit=10)
    hay = await lb.records_haystack("arena", "u20", limit=7)
    # Replay against a host-only Leaderboards over the same db.
    lb_host = Leaderboards(quiet_logger(), db)
    await lb_host.load()
    page_h = await lb_host.records_list("arena", limit=10)
    hay_h = await lb_host.records_haystack("arena", "u20", limit=7)
    assert [
        (r["owner_id"], r["rank"]) for r in page["records"]
    ] == [(r["owner_id"], r["rank"]) for r in page_h["records"]]
    assert [
        (r["owner_id"], r["rank"]) for r in hay["records"]
    ] == [(r["owner_id"], r["rank"]) for r in hay_h["records"]]
    assert engine.device_reads > 0
    await db.close()


# ------------------------------------------------------ degradation ladder


def test_breaker_fallback_and_half_open_probe():
    oracle, engine = make_engine()
    for i in range(30):
        mirror_insert(oracle, engine, "f", 0.0, 1, f"u{i}", i)
    assert engine.flush_all()
    owners = [f"u{i}" for i in range(30)]
    try:
        faults.arm("leaderboard.rank", "raise")
        # Threshold (2) failures open the breaker; every call degrades
        # to None (host serves) and nothing escapes.
        for _ in range(4):
            assert engine.get_many("f", 0.0, owners) is None
        assert engine.breaker.state == "open"
    finally:
        faults.disarm()
    time.sleep(engine.breaker.cooldown_s + 0.02)
    # Half-open probe heals and serves device again.
    assert engine.get_many("f", 0.0, owners) == oracle.get_many(
        "f", 0.0, owners
    )
    assert engine.breaker.state == "closed"


def test_flush_fault_degrades_then_heals():
    oracle, engine = make_engine()
    for i in range(20):
        mirror_insert(oracle, engine, "g", 0.0, 1, f"u{i}", i)
    try:
        faults.arm("leaderboard.flush", "raise")
        # First read must flush -> injected failure -> host fallback.
        assert engine.get_many("g", 0.0, ["u1"]) is None
        assert engine.breaker.failures >= 1
    finally:
        faults.disarm()
    time.sleep(engine.breaker.cooldown_s + 0.02)
    assert engine.get_many("g", 0.0, ["u1"]) == oracle.get_many(
        "g", 0.0, ["u1"]
    )


def test_deadline_short_circuits_device_reads():
    oracle, engine = make_engine()
    for i in range(10):
        mirror_insert(oracle, engine, "dl", 0.0, 1, f"u{i}", i)
    assert engine.flush_all()
    with deadline_scope(Deadline(0.0, explicit=True)):
        assert engine.get_many("dl", 0.0, ["u1"]) is None
    # Budget below the device floor also short-circuits.
    with deadline_scope(Deadline(0.0005, explicit=True)):
        assert engine.get_many("dl", 0.0, ["u1"]) is None
    with deadline_scope(Deadline(5.0, explicit=True)):
        assert engine.get_many("dl", 0.0, ["u1"]) == oracle.get_many(
            "dl", 0.0, ["u1"]
        )
    # The short-circuit never feeds the breaker.
    assert engine.breaker.state == "closed"


def test_device_reads_emit_spans():
    """PR 6 integration: a device read inside an active trace records
    leaderboard.rank / leaderboard.flush spans."""
    trace_api.TRACES.reset()
    trace_api.TRACES.configure(sample_rate=1.0)
    try:
        oracle, engine = make_engine()
        for i in range(10):
            mirror_insert(oracle, engine, "tr", 0.0, 1, f"u{i}", i)
        with trace_api.root_span("test leaderboard read") as root:
            assert engine.get_many("tr", 0.0, ["u1"]) is not None
        trace = trace_api.TRACES.get(root.trace_id)
        names = {
            sp["name"]
            for sp in trace["resourceSpans"][0]["scopeSpans"][0]["spans"]
        }
        assert "leaderboard.rank" in names
        assert "leaderboard.flush" in names  # first read flushed
    finally:
        trace_api.TRACES.reset()


# ------------------------------------------------------ snapshot / restore


def test_snapshot_restore_preserves_tie_order():
    """PR 7 integration: board columns snapshot with their seqs and
    restore into a fresh engine + oracle; a post-restore identical-score
    re-insert pass (what load() replays from the DB) keeps the restored
    tie-break order thanks to the seq-preservation rule."""
    oracle, engine = make_engine()
    # a and b tie on score; a wrote first and must stay ahead.
    mirror_insert(oracle, engine, "snap", 0.0, 1, "a", 50)
    mirror_insert(oracle, engine, "snap", 0.0, 1, "b", 50)
    mirror_insert(oracle, engine, "snap", 0.0, 1, "c", 10)
    snap = engine.snapshot_state()

    oracle2, engine2 = make_engine()
    assert engine2.restore_state(snap) == 1
    # The restorer repopulated the oracle with original seqs.
    assert oracle2.get("snap", 0.0, "a") == 0
    assert oracle2.get("snap", 0.0, "b") == 1
    # load()-style replay: identical scores re-inserted in DB order.
    for owner, score in (("b", 50), ("a", 50), ("c", 10)):
        oracle2.insert("snap", 0.0, 1, owner, score, 0)
        engine2.record_upsert("snap", 0.0, 1, owner)
    assert oracle2.get("snap", 0.0, "a") == 0  # order survived
    assert engine2.get_many("snap", 0.0, ["a", "b", "c"]) == [0, 1, 2]
    # Corrupt / missing sections degrade to lazy adoption, never raise.
    assert engine2.restore_state(None) == 0
    assert engine2.restore_state({"version": 99}) == 0


# ------------------------------------------------------------- bench gate


def test_leaderboard_rank_regression_gate():
    """bench.leaderboard_rank_regression: the named tier-1 contract —
    device must beat host, zero parity/fault errors, degraded reads
    bounded, post-fault convergence required."""
    from bench import LB_DEGRADED_BUDGET_US, leaderboard_rank_regression

    ok = leaderboard_rank_regression(4.0, 9.0, 0, 0, 50.0, True)
    assert ok == ([], False)
    reasons, reg = leaderboard_rank_regression(9.0, 4.0, 0, 0, 50.0, True)
    assert reg and "device_rank_p99" in reasons[0]
    reasons, reg = leaderboard_rank_regression(4.0, 9.0, 2, 0, 50.0, True)
    assert reg and "parity_failures=2" in reasons
    reasons, reg = leaderboard_rank_regression(4.0, 9.0, 0, 1, 50.0, True)
    assert reg and "fault_errors=1" in reasons
    reasons, reg = leaderboard_rank_regression(
        4.0, 9.0, 0, 0, LB_DEGRADED_BUDGET_US, True
    )
    assert reg and "degraded_rank_p99" in reasons[0]
    reasons, reg = leaderboard_rank_regression(4.0, 9.0, 0, 0, 50.0, False)
    assert reg and "post_fault_convergence_failed" in reasons
