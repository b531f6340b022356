"""ISSUE 9: the device telemetry plane (devobs.py).

Covers the plane's own semantics (kernel clocks, compile-watch
attribution + the warmup window, the HBM ownership ledger, transfer
counters, the bounded timeline), the RECOMPILE-BUDGET invariant — a
steady-state interval sequence through pow2 scatter-bucket churn and
leaderboard flush-size churn must produce ZERO unexpected recompiles
after warmup, pinning the compile-shape design in matchmaker/device.py
as an enforced invariant instead of a code comment — the bench gate
units, and a subprocess-isolated console smoke (`/v2/console/device` +
the bounded profiler capture) per the test_trace_smoke convention (the
plane is process-global; a fresh interpreter keeps warmup posture and
compile caches from leaking either way).
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from nakama_tpu.devobs import DEVOBS


@pytest.fixture(autouse=True)
def _reset_plane():
    DEVOBS.reset()
    yield
    DEVOBS.reset()


def _quiet_logger():
    import io

    from nakama_tpu.logger import Logger

    return Logger(level=logging.CRITICAL, fmt="json", streams=[io.StringIO()])


# ------------------------------------------------------------ plane units


def test_kernel_clock_records_calls_and_percentiles():
    DEVOBS.register("t.kernel")
    for _ in range(10):
        with DEVOBS.device_call("t.kernel"):
            # An empty block can take under the half microsecond that
            # `ema_ms` (three decimals of a ms) rounds to zero.
            time.sleep(0.0002)
    stats = {k["kernel"]: k for k in DEVOBS.kernel_stats()}
    k = stats["t.kernel"]
    assert k["calls"] == 10
    assert k["p50_ms"] >= 0 and k["p99_ms"] >= k["p50_ms"]
    assert k["ema_ms"] > 0
    # Every call landed on the timeline with its wall stamp.
    assert len(DEVOBS.recent_timeline()) == 10
    assert all(e["kernel"] == "t.kernel" for e in DEVOBS.recent_timeline())


def test_disarmed_plane_records_nothing():
    DEVOBS.configure(enabled=False)
    with DEVOBS.device_call("t.kernel"):
        pass
    DEVOBS.mem_set("t.owner", 1024)
    DEVOBS.transfer("t.site", "h2d", 64)
    assert DEVOBS.kernel_stats() == []
    assert DEVOBS.memory_by_owner() == {}
    assert DEVOBS.stats()["transfers"] == []


def test_timeline_bounded_and_sliced():
    DEVOBS.configure(timeline_depth=16)
    for i in range(40):
        with DEVOBS.device_call(f"k{i % 3}"):
            pass
    assert len(DEVOBS.recent_timeline(100)) == 16
    t0 = time.time()
    with DEVOBS.device_call("window.kernel"):
        pass
    events = DEVOBS.timeline_between(t0, time.time())
    assert any(e["kernel"] == "window.kernel" for e in events)
    assert DEVOBS.timeline_between(t0 + 3600, t0 + 7200) == []


def test_memory_ledger_and_high_water():
    DEVOBS.mem_set("a", 1000)
    DEVOBS.mem_set("b", 500)
    assert DEVOBS.memory_by_owner() == {"a": 1000, "b": 500}
    assert DEVOBS.memory_high_water == 1500
    DEVOBS.mem_add("a", 250)
    assert DEVOBS.memory_by_owner()["a"] == 1250
    assert DEVOBS.memory_high_water == 1750
    DEVOBS.mem_set("a", 0)  # free
    assert "a" not in DEVOBS.memory_by_owner()
    assert DEVOBS.memory_high_water == 1750  # high water survives frees
    mem = DEVOBS.stats()["memory"]
    assert mem["total_bytes"] == 500
    assert mem["high_water_bytes"] == 1750


def test_transfer_counters_by_site_and_direction():
    DEVOBS.transfer("pool.flush", "h2d", 100)
    DEVOBS.transfer("pool.flush", "h2d", 50)
    DEVOBS.transfer("cohort.fetch", "d2h", 75)
    transfers = {
        (t["site"], t["direction"]): t for t in DEVOBS.stats()["transfers"]
    }
    assert transfers[("pool.flush", "h2d")]["count"] == 2
    assert transfers[("pool.flush", "h2d")]["bytes"] == 150
    assert transfers[("cohort.fetch", "d2h")]["bytes"] == 75


def test_metrics_binding_publishes_gauges_and_counters():
    from nakama_tpu.metrics import Metrics

    m = Metrics()
    # Rows written BEFORE binding republish at configure (the pool
    # allocates at backend construction, the server binds after).
    DEVOBS.mem_set("early.owner", 4096)
    DEVOBS.configure(metrics=m)
    snap = m.snapshot()
    assert snap.get("nakama_device_memory_bytes{owner=early.owner}") == 4096
    with DEVOBS.device_call("m.kernel"):
        pass
    DEVOBS.transfer("m.site", "d2h", 32)
    snap = m.snapshot()
    assert (
        snap.get("nakama_device_kernel_time_sec_count{kernel=m.kernel}")
        == 1.0
    )
    assert (
        snap.get(
            "nakama_device_transfer_bytes_total"
            "{direction=d2h,site=m.site}"
        )
        == 32.0
    )


def test_interval_tick_closes_warmup_window():
    DEVOBS.configure(warmup_intervals=2)
    assert not DEVOBS.warmed
    DEVOBS.interval_tick()
    assert not DEVOBS.warmed
    DEVOBS.interval_tick()
    assert DEVOBS.warmed
    # Re-configuring a larger window after the fact re-opens it.
    DEVOBS.configure(warmup_intervals=5)
    assert not DEVOBS.warmed


# --------------------------------------------------------- compile-watch


def _fresh_jit(shape):
    """A jit callable guaranteed to compile (unique closure constant per
    call site) executed at `shape`."""
    import jax
    import jax.numpy as jnp

    salt = time.perf_counter()  # unique constant → fresh cache entry

    @jax.jit
    def f(x):
        return x * 2.0 + jnp.float32(salt)

    return f(np.zeros(shape, dtype=np.float32))


def test_compile_attribution_and_unexpected_recompile():
    import nakama_tpu.tracing as trace_api

    DEVOBS.register("cw.kernel")  # installs the monitoring listener
    DEVOBS.configure(warmup_intervals=1)
    # Warmup-window compile: attributed, counted, NOT unexpected.
    with DEVOBS.device_call("cw.kernel"):
        _fresh_jit((8,))
    stats = {k["kernel"]: k for k in DEVOBS.kernel_stats()}
    assert stats["cw.kernel"]["compiles"] >= 1
    assert stats["cw.kernel"]["recompiles"] == 0
    assert stats["cw.kernel"]["compile_total_s"] > 0

    DEVOBS.interval_tick()  # closes the warmup window
    assert DEVOBS.warmed
    # A compile outside any device_call: unattributed, never judged.
    _fresh_jit((8,))
    # An EXPECTED compile (prewarm thread posture): never judged.
    with DEVOBS.device_call("cw.kernel", expect_compile=True):
        _fresh_jit((8,))
    assert DEVOBS.recompiles_total == 0

    # A hot-path compile after warmup: the unexpected-recompile alarm —
    # counter + span event on the active trace.
    trace_api.TRACES.reset()
    with trace_api.root_span("t.interval") as root:
        with DEVOBS.device_call("cw.kernel"):
            _fresh_jit((8,))
        events = [e["name"] for e in root.events]
    trace_api.TRACES.reset()
    stats = {k["kernel"]: k for k in DEVOBS.kernel_stats()}
    assert stats["cw.kernel"]["recompiles"] == 1
    assert DEVOBS.recompiles_total == 1
    assert "xla.recompile" in events


def test_unexpected_recompile_warns_and_ticks_metric():
    import io

    from nakama_tpu.logger import Logger
    from nakama_tpu.metrics import Metrics

    buf = io.StringIO()
    log = Logger(level=logging.INFO, fmt="json", streams=[buf])
    m = Metrics()
    DEVOBS.register("warn.kernel")
    DEVOBS.configure(warmup_intervals=0, metrics=m, logger=log)
    assert DEVOBS.warmed
    with DEVOBS.device_call("warn.kernel"):
        _fresh_jit((16,))
    lines = [json.loads(x) for x in buf.getvalue().splitlines()]
    assert any(
        "unexpected XLA recompile" in ln["msg"]
        and ln["kernel"] == "warn.kernel"
        for ln in lines
    )
    snap = m.snapshot()
    assert (
        snap.get("nakama_xla_recompiles_total{kernel=warn.kernel}")
        >= 1.0
    )
    assert (
        snap.get("nakama_xla_compiles_total{kernel=warn.kernel}") >= 1.0
    )


# ------------------------------------------------------- recompile budget


def _mk_small_backend(**overrides):
    from nakama_tpu.config import MatchmakerConfig
    from nakama_tpu.matchmaker import LocalMatchmaker
    from nakama_tpu.matchmaker.tpu import TpuBackend

    defaults = dict(
        pool_capacity=256,
        candidates_per_ticket=8,
        numeric_fields=4,
        string_fields=4,
        max_constraints=4,
        max_intervals=50,
        interval_pipelining=True,
    )
    defaults.update(overrides)
    cfg = MatchmakerConfig(**defaults)
    backend = TpuBackend(cfg, _quiet_logger(), row_block=8, col_block=64)
    mm = LocalMatchmaker(_quiet_logger(), cfg, backend=backend)
    return mm, backend


def _add_tickets(mm, n, prefix):
    from nakama_tpu.matchmaker.types import MatchmakerPresence

    for i in range(n):
        sid = f"{prefix}-{i}"
        mm.add(
            [
                MatchmakerPresence(
                    user_id=sid, session_id=sid, username=sid, node="n"
                )
            ],
            sid,
            "",
            "*",
            2,
            2,
        )


def test_recompile_budget_matchmaker_bucket_churn():
    """The enforced invariant behind matchmaker/device.py's pow2
    padding comments: active-count churn that stays inside the
    already-seen row/scatter buckets must compile NOTHING after the
    warmup window — a recompile here is exactly the ~1.3s surprise the
    ISSUE motivates, and now it fails tier-1 instead of spiking a p99.
    Warmup intervals walk the bucket range (row pads 8/16/32); the
    steady phase re-enters every bucket at different sizes.

    Synchronous intervals (the correctness-oracle fallback) keep the
    dispatch sizes deterministic: every process matches all pairable
    actives in place, so the leftover between intervals is at most a
    couple of odd tickets and the steady sizes below stay inside the
    warmed row buckets. The 65-ticket burst FIRST pushes the pool
    high-water past one 64-slot column block, pinning the scanned
    column bucket (n_cols) at 128 for the whole test — a pool GROWING
    across a pow2 column bucket legitimately compiles once, and that
    is not the churn this test outlaws."""
    mm, backend = _mk_small_backend(interval_pipelining=False)
    warm_sizes = [65, 3, 9, 17]  # col bucket 128; row pads 128/8/16/32
    steady_sizes = [2, 24, 12, 6, 20]  # same pads, different counts
    DEVOBS.configure(warmup_intervals=len(warm_sizes) + 1)

    def interval(n, prefix):
        _add_tickets(mm, n, prefix)
        mm.process()
        backend.wait_idle()
        # The production interval gap: graveyard drain recycles the
        # matched slots, so the pool high-water (and with it the
        # scanned column bucket) stays put instead of ratcheting.
        mm.store.drain()

    for it, n in enumerate(warm_sizes):
        interval(n, f"w{it}")
    interval(0, "wdrain")  # settle inside the warmup window
    assert DEVOBS.warmed
    compiles_at_warm = DEVOBS.compiles_total
    for it, n in enumerate(steady_sizes):
        interval(n, f"s{it}")
    interval(0, "sdrain")
    assert backend.pool.high_water <= 128, (
        "test invariant broke: the pool crossed the pinned column"
        f" bucket (hw {backend.pool.high_water})"
    )
    assert DEVOBS.recompiles_total == 0, (
        "steady-state bucket churn recompiled: "
        f"{[k for k in DEVOBS.kernel_stats() if k['recompiles']]}"
    )
    # Stronger: the matchmaker kernels compiled nothing at all in the
    # steady phase (attributed or not, the jit caches held).
    steady_compiles = {
        k["kernel"]: k["compiles"]
        for k in DEVOBS.kernel_stats()
        if k["kernel"].startswith("matchmaker.")
    }
    assert DEVOBS.compiles_total == compiles_at_warm, (
        f"steady phase compiled: total {DEVOBS.compiles_total} vs"
        f" {compiles_at_warm} at warmup close; per-kernel"
        f" {steady_compiles}"
    )
    mm.stop()


def test_recompile_budget_removal_buckets_are_prewarmed():
    """A removal count that first falls in a new power-of-two pad bucket
    must not compile on the hot path: `PoolBuffer.prewarm` compiles every
    bucket `flush()` can pad to. (On the chip this was the one compile
    left after warm-up once the score kernels came from the persistent
    cache and warm-up got short: interval 11, 165 ms, PR 21.)"""
    from nakama_tpu.matchmaker.device import PoolBuffer

    pool = PoolBuffer(2048, 4, 4, 4, d=4, flush_chunk=64)
    assert pool._removal_buckets() == [64, 128, 256, 512, 1024, 2048]
    DEVOBS.configure(warmup_intervals=1000)
    pool.prewarm()
    pool.join_prewarm()
    DEVOBS.mark_warm()
    compiles_at_warm = DEVOBS.compiles_total
    for n in (1, 64, 65, 200, 500, 1000, 2048):  # every bucket, once
        pool.remove_slots(np.arange(n, dtype=np.int32))
        pool.flush()
    assert DEVOBS.recompiles_total == 0
    assert DEVOBS.compiles_total == compiles_at_warm, (
        "a removal flush compiled after prewarm: "
        f"{[k for k in DEVOBS.kernel_stats() if k['compiles']]}"
    )


def test_recompile_budget_leaderboard_flush_churn():
    """Leaderboard twin: flush-size churn (dirty counts padded pow2)
    and rank-batch churn inside seen buckets must not recompile after
    warmup."""
    from nakama_tpu.leaderboard.rank_cache import LeaderboardRankCache

    from bench import _lb_engine

    oracle = LeaderboardRankCache()
    for i in range(600):
        oracle.insert("b", 0.0, 1, f"u{i}", i * 3 % 997, i)
    engine = _lb_engine(oracle)
    assert engine.adopt_board("b", 0.0, 1)
    # Hold the warmup window open through the warm phase; mark_warm()
    # closes it explicitly (no matchmaker interval ticks here).
    DEVOBS.configure(warmup_intervals=1000)
    # Warmup phase: first full-upload flush + one dirty-scatter bucket
    # + one rank-batch bucket.
    assert engine.flush_all()
    for i in range(5):
        oracle.insert("b", 0.0, 1, f"u{i}", 5000 + i, i)
        engine.record_upsert("b", 0.0, 1, f"u{i}")
    assert engine.flush_all()  # dirty 5 → pad 8
    assert engine.get_many("b", 0.0, [f"u{i}" for i in range(10)])
    DEVOBS.mark_warm()
    compiles_at_warm = DEVOBS.compiles_total
    # Steady churn: different dirty counts in the same pow2 bucket,
    # different batch size in the same query pad.
    for i in range(7):
        oracle.insert("b", 0.0, 1, f"u{100 + i}", 7000 + i, i)
        engine.record_upsert("b", 0.0, 1, f"u{100 + i}")
    assert engine.flush_all()  # dirty 7 → pad 8 (seen)
    assert engine.get_many("b", 0.0, [f"u{i}" for i in range(13)])
    assert DEVOBS.recompiles_total == 0
    assert DEVOBS.compiles_total == compiles_at_warm, (
        "leaderboard steady flush/rank churn compiled: "
        f"{[k for k in DEVOBS.kernel_stats() if k['calls']]}"
    )


# ------------------------------------------------------- ledger timeline


def test_delivery_ledger_carries_device_timeline():
    mm, backend = _mk_small_backend()
    _add_tickets(mm, 6, "tl")
    mm.process()
    backend.wait_idle()
    mm.process()  # collects the pipelined cohort → ledger entry
    backend.wait_idle()
    entries = [
        d
        for d in backend.tracing.recent_deliveries(8)
        if "device_timeline" in d
    ]
    assert entries, "no delivery-ledger entry carried a device timeline"
    kernels = {e["kernel"] for d in entries for e in d["device_timeline"]}
    # The cohort's own window must at least show its score kernel
    # (flush may precede the wall window on coarse clocks).
    assert any(k.startswith("matchmaker.") for k in kernels)
    mm.stop()


def test_pool_memory_and_transfer_accounting():
    mm, backend = _mk_small_backend()
    mem = DEVOBS.memory_by_owner()
    expected = sum(
        int(v.nbytes) for v in backend.pool.device.values()
    )
    assert mem.get("matchmaker.pool") == expected
    _add_tickets(mm, 4, "mv")
    mm.process()
    backend.wait_idle()
    sites = {
        (t["site"], t["direction"]) for t in DEVOBS.stats()["transfers"]
    }
    assert ("pool.flush", "h2d") in sites
    mm.stop()


# ------------------------------------------------------------- bench gate


def test_device_telemetry_gate_units():
    from bench import device_telemetry_overhead_regression as gate

    reasons, reg = gate(0.3, kernels_n=5, compiles_total=10,
                        memory_owners=2)
    assert not reg and reasons == []
    reasons, reg = gate(1.5, kernels_n=5, compiles_total=10,
                        memory_owners=2)
    assert reg and any(">= 1%" in r for r in reasons)
    # Cheap-because-dead is also a regression.
    reasons, reg = gate(0.1, kernels_n=0, compiles_total=0,
                        memory_owners=0)
    assert reg and len(reasons) == 3


# ------------------------------------------------------- console smoke


_SMOKE = r"""
import asyncio, base64, json, os, sys, tempfile

def main():
    from nakama_tpu.config import Config
    from nakama_tpu.server import NakamaServer

    cfg = Config()
    cfg.data_dir = tempfile.mkdtemp(prefix="devobs-smoke-")
    cfg.socket.port = 0
    cfg.socket.grpc_port = -1
    cfg.logger.stdout = False
    mc = cfg.matchmaker
    mc.pool_capacity = 64
    mc.candidates_per_ticket = 16
    mc.numeric_fields = 4
    mc.string_fields = 4
    mc.max_constraints = 4
    mc.interval_sec = 1
    mc.max_intervals = 50
    cfg.leaderboard.device_min_board_size = 0
    out = {}

    async def run():
        import aiohttp

        from nakama_tpu.matchmaker.types import MatchmakerPresence

        # backend="tpu" in config is refused off-TPU; the CPU rig hands
        # the server its own (interpreting) device backend instead.
        from nakama_tpu.logger import setup_logging
        from nakama_tpu.matchmaker.tpu import TpuBackend

        log = setup_logging(cfg.logger)
        server = NakamaServer(
            cfg, log, matchmaker_backend=TpuBackend(cfg.matchmaker, log)
        )
        await server.start()
        console = f"http://127.0.0.1:{server.console_port}"
        try:
            # One matchmaker interval with live tickets...
            for i in range(2):
                server.matchmaker.add(
                    [MatchmakerPresence(
                        user_id=f"u{i}", session_id=f"s{i}",
                        username=f"u{i}", node="n")],
                    f"s{i}", "", "*", 2, 2,
                )
            server.matchmaker.process()
            backend = server.matchmaker.backend
            backend.wait_idle()
            server.matchmaker.process()
            backend.wait_idle()
            # ...and one leaderboard flush on the SAME process.
            engine = server.leaderboards.device
            for i in range(32):
                engine.oracle.insert(
                    "smoke", 0.0, 1, f"o{i}", i * 7, i
                )
            assert engine.adopt_board("smoke", 0.0, 1)
            assert engine.flush_all()
            engine.get_many("smoke", 0.0, ["o1", "o2"])

            async with aiohttp.ClientSession() as http:
                async with http.post(
                    f"{console}/v2/console/authenticate",
                    json={"username": "admin", "password": "password"},
                ) as resp:
                    token = (await resp.json())["token"]
                hdrs = {"Authorization": f"Bearer {token}"}
                async with http.get(
                    f"{console}/v2/console/device", headers=hdrs
                ) as resp:
                    out["status"] = resp.status
                    d = await resp.json()
                out["kernels"] = sorted(
                    k["kernel"] for k in d["kernels"] if k["calls"]
                )
                out["compiles_total"] = d["compiles"]["total"]
                out["memory_owners"] = sorted(d["memory"]["by_owner"])
                out["mesh_devices"] = len(d["mesh"]["devices"])
                out["backend"] = d["backend"]
                out["timeline_n"] = len(d["timeline"])
                out["unauth"] = (
                    await http.get(f"{console}/v2/console/device")
                ).status
                async with http.post(
                    f"{console}/v2/console/device/capture",
                    headers=hdrs,
                    json={"duration_ms": 200},
                ) as resp:
                    out["capture_status"] = resp.status
                    cap = await resp.json()
                out["capture_under_data_dir"] = cap.get(
                    "path", ""
                ).startswith(cfg.data_dir)
                out["capture_exists"] = os.path.isdir(
                    cap.get("path", "")
                )
        finally:
            await server.stop()

    asyncio.run(run())
    print("RESULT " + json.dumps(out))

main()
"""


def test_console_device_endpoint_smoke():
    """Acceptance leg: /v2/console/device returns non-empty kernels /
    compiles / memory-by-owner after one matchmaker interval + one
    leaderboard flush on the same process, the endpoint requires
    console auth, and the on-demand profiler capture writes a bounded
    trace under data_dir."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _SMOKE],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, (
        f"smoke failed\nstdout: {proc.stdout[-2000:]}\n"
        f"stderr: {proc.stderr[-2000:]}"
    )
    line = [
        ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")
    ]
    assert line, proc.stdout[-2000:]
    out = json.loads(line[-1][len("RESULT "):])
    assert out["status"] == 200
    assert out["unauth"] == 401
    assert any(k.startswith("matchmaker.") for k in out["kernels"])
    assert "leaderboard.flush" in out["kernels"]
    assert out["compiles_total"] > 0
    assert "matchmaker.pool" in out["memory_owners"]
    assert "leaderboard.boards" in out["memory_owners"]
    assert out["mesh_devices"] >= 1
    # Where the kernels really run is on the page: this rig interprets.
    assert out["backend"]["platform"] == "cpu"
    assert out["backend"]["pallas_interpret"] is True
    assert out["backend"]["breaker"] == "closed"
    assert out["timeline_n"] > 0
    assert out["capture_status"] == 200
    assert out["capture_under_data_dir"] and out["capture_exists"]


# ---------------------------------------------------- the device report


def test_shared_device_report_lines():
    """The plain-text report of the telemetry plane
    (`DEVOBS.report_lines()`): kernels, owners and transfer sites."""
    DEVOBS.register("r.kernel")
    with DEVOBS.device_call("r.kernel"):
        pass
    DEVOBS.mem_set("r.owner", 2048)
    DEVOBS.transfer("r.site", "d2h", 128)
    text = "\n".join(DEVOBS.report_lines())
    assert "device telemetry:" in text
    assert "r.kernel" in text
    assert "r.owner" in text
    assert "r.site" in text


def test_device_report_after_a_real_interval():
    """One real interval (tiny pool) through the shipped code paths,
    then the report: a drift in the backend's telemetry surface breaks
    THIS test, not a perf session. The cohort worker's copies sit on
    the `matchmaker.fetch` clock (its wait for the program is the cohort
    record's `device_done_lag_s`)."""
    mm, backend = _mk_small_backend()
    _add_tickets(mm, 6, "rp")
    mm.process()
    backend.wait_idle()
    mm.process()
    text = "\n".join(DEVOBS.report_lines())
    assert "device telemetry:" in text
    assert "matchmaker.score" in text
    assert "matchmaker.pool" in text
    assert "cohort.fetch" in text
    fetch = [
        k for k in DEVOBS.kernel_stats() if k["kernel"] == "matchmaker.fetch"
    ]
    assert fetch and fetch[0]["calls"] >= 2
    mm.stop()
