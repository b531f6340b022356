"""The host's account of a cohort's flight (tracing.py): the interval
loop's gap pass as a `kind: "gap"` breadcrumb, the delivery call's and
the assembly's CPU beside their wall time on the cohort's row, and the
gap passes that ran while the cohort was in flight.

What the benchmark's `gap` reader and the new `ledger` metric files
read is pinned here, on a small pool on the interpreting backend.
"""

import asyncio
import os
import subprocess
import sys
import threading
import time

import pytest

from nakama_tpu import tracing as trace_api
from nakama_tpu.tracing import Tracing

from test_matchmaker_spans import PUBLISH_STAGES, Rig, _cycle, _metric_files

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GAP_STAGES = ("count_s", "drain_s", "gc_s", "flush_s")
GAP_KEYS = {
    "kind", "seq", "ts", "_pc_start", "_pc_end", "wake_late_s", "shed",
    "in_flight", "gc_collected", "cpu_s", *GAP_STAGES,
}
PUBLISH_CPU = (
    "publish_cpu_s", "publish_offcpu_s", "publish_other_cpu_s",
    "publish_invol_switches", "publish_minor_faults",
)


def gaps(tracing):
    return [c for c in tracing.recent(256) if c.get("kind") == "gap"]


# ------------------------------------------------------- the gap record


def test_a_started_loop_stores_a_gap_crumb_every_pass():
    rig = Rig(interval_sec=0.4)  # the gap sleep is a quarter of it

    async def drive():
        rig.mm.start()
        try:
            await asyncio.sleep(1.0)
        finally:
            rig.mm.stop()

    t0 = time.perf_counter()
    asyncio.run(drive())
    t1 = time.perf_counter()
    crumbs = gaps(rig.tracing)
    assert len(crumbs) >= 2, rig.tracing.recent(16)
    for c in crumbs:
        assert set(c) == GAP_KEYS, c  # no checkpointer: no checkpoint_s
        assert c["shed"] is False and c["in_flight"] == 0
        assert t0 <= c["_pc_start"] <= c["_pc_end"] <= t1
        # the loop woke at or after the sleep was due
        assert 0.0 <= c["wake_late_s"] < 0.5
        stages = [c[k] for k in GAP_STAGES]
        assert all(v >= 0.0 for v in stages)
        assert sum(stages) <= c["_pc_end"] - c["_pc_start"]
        assert isinstance(c["gc_collected"], int)
        assert 0.0 <= c["cpu_s"]
    # among the interval crumbs, in one sequence; `run.py`'s filter and
    # the `crumb` reader (crumbs with "actives") never see one
    assert not any("actives" in c for c in crumbs)
    seqs = [c["seq"] for c in rig.tracing.recent(256)]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    first, second = crumbs[:2]
    assert second["_pc_start"] - first["_pc_start"] == pytest.approx(
        0.4, abs=0.2)


async def test_a_shed_pass_says_so_and_has_no_stage():
    rig = Rig()
    rig.backend.pipeline_backlogged = lambda: True
    due = time.perf_counter() - 0.25  # the loop was held a quarter second
    assert await rig.mm._gap_pass(due, 0) == 1
    assert await rig.mm._gap_pass(due, 1) == 2
    # the streak's cap: the third backlogged gap runs its work
    assert await rig.mm._gap_pass(due, 2) == 0
    shed1, shed2, ran = gaps(rig.tracing)
    for c in (shed1, shed2):
        assert c["shed"] is True
        assert set(c) == GAP_KEYS - set(GAP_STAGES) - {"gc_collected"}
        assert c["_pc_start"] <= c["_pc_end"]
        assert 0.25 <= c["wake_late_s"] < 5.0
    assert ran["shed"] is False and set(ran) == GAP_KEYS
    # a shed pass held the loop for nothing: it is in no cohort's flight
    assert rig.tracing.gap_seconds_since(0.0) == pytest.approx(
        ran["_pc_end"] - ran["_pc_start"])


class _DueCheckpointer:
    def __init__(self):
        self.took = 0

    def due(self):
        return True

    async def maybe_checkpoint(self, mm):
        self.took += 1
        await asyncio.sleep(0.01)


async def test_a_due_checkpoint_is_the_passes_fifth_stage():
    rig = Rig()
    rig.mm.checkpointer = _DueCheckpointer()
    assert await rig.mm._gap_pass(time.perf_counter(), 0) == 0
    (c,) = gaps(rig.tracing)
    assert rig.mm.checkpointer.took == 1
    assert set(c) == GAP_KEYS | {"checkpoint_s"}
    assert c["checkpoint_s"] >= 0.01
    assert sum(c[k] for k in GAP_STAGES) + c["checkpoint_s"] <= (
        c["_pc_end"] - c["_pc_start"])


async def test_a_stage_that_raises_still_leaves_the_crumb():
    """`store.drain` and the collection are not guarded (an error there
    ends the loop, as it always did): the crumb is stored all the same,
    with the stages it reached."""
    rig = Rig()

    def boom(deadline=None):
        raise RuntimeError("drain")

    rig.mm.store.drain = boom
    with pytest.raises(RuntimeError, match="drain"):
        await rig.mm._gap_pass(time.perf_counter(), 0)
    (c,) = gaps(rig.tracing)
    assert "count_s" in c and "drain_s" in c and "cpu_s" in c
    assert "gc_s" not in c and "flush_s" not in c


def test_a_host_only_backend_records_the_pass_without_jax():
    code = """
import asyncio, sys, time
from nakama_tpu.config import MatchmakerConfig
from nakama_tpu.logger import test_logger
from nakama_tpu.matchmaker.local import CpuBackend, LocalMatchmaker
mm = LocalMatchmaker(test_logger(), MatchmakerConfig(backend="cpu"),
                     backend=CpuBackend())
assert asyncio.run(mm._gap_pass(time.perf_counter(), 0)) == 0
(c,) = [c for c in mm.tracing.recent(8) if c.get("kind") == "gap"]
assert c["shed"] is False and c["in_flight"] == 0, c
assert all(k in c for k in ("count_s", "drain_s", "gc_s", "flush_s")), c
assert "jax" not in sys.modules, "a host-only backend imported JAX"
print("ok")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# ---------------------------------------------- CPU beside wall, by thread


def _burn(cpu_s):
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        pass


def test_cpu_split_tells_running_from_waiting():
    a = trace_api.cpu_stamp()
    _burn(0.03)
    b = trace_api.cpu_stamp()
    time.sleep(0.05)
    c = trace_api.cpu_stamp()
    ran = trace_api.cpu_split("x", a, b)
    assert set(ran) == {"x_" + k.split("_", 1)[1] for k in PUBLISH_CPU}
    assert ran["x_cpu_s"] >= 0.03
    assert ran["x_cpu_s"] + ran["x_offcpu_s"] == pytest.approx(b[0] - a[0])
    waited = trace_api.cpu_split("x", b, c)
    assert waited["x_cpu_s"] < 0.02 and waited["x_offcpu_s"] >= 0.03
    assert waited["x_minor_faults"] >= 0
    assert waited["x_invol_switches"] >= 0


def test_cpu_split_books_another_threads_cpu_apart():
    other = threading.Thread(target=_burn, args=(0.05,))
    a = trace_api.cpu_stamp()
    other.start()
    other.join()
    split = trace_api.cpu_split("x", a, trace_api.cpu_stamp())
    assert split["x_other_cpu_s"] >= 0.04
    assert split["x_cpu_s"] < split["x_other_cpu_s"]


def test_cpu_split_without_rusage_thread_leaves_the_counts_out(monkeypatch):
    monkeypatch.setattr(trace_api, "RUSAGE_THREAD", None)
    split = trace_api.cpu_split(
        "x", trace_api.cpu_stamp(), trace_api.cpu_stamp())
    assert set(split) == {"x_cpu_s", "x_offcpu_s", "x_other_cpu_s"}


@pytest.mark.parametrize("via", ["collect", "process"])
async def test_cohort_row_carries_cpu_beside_wall(via):
    rig = Rig()
    await _cycle(rig, 8, via)
    (row,) = rig.tracing.recent_deliveries(1)
    for key in PUBLISH_CPU:
        # the clocks are read one after another: a microsecond's slack
        assert row[key] >= -1e-4, (key, row)
    # the handler's call: its CPU and its waiting are its wall, which
    # holds the five publish stages and ends before the publish stamp
    wall = row["publish_cpu_s"] + row["publish_offcpu_s"]
    assert sum(row[k] for k in PUBLISH_STAGES) <= wall
    assert wall <= row["publish_lag_s"] - row["accept_lag_s"]
    assert row["publish_cpu_s"] > 0.0
    # the worker's assembly, between its fetched and ready stamps
    assert row["assemble_cpu_s"] >= 0.0
    assert row["assemble_cpu_s"] + row["assemble_offcpu_s"] == (
        pytest.approx(row["ready_lag_s"] - row["fetch_lag_s"], abs=1e-9))
    # no gap pass ran while it was in flight
    assert row["gap_in_flight_s"] == 0.0


async def test_a_lost_cohort_claims_no_assembly():
    from nakama_tpu import faults

    rig = Rig()
    rig.add_direct("a")
    rig.add_direct("a")
    faults.arm("device.collect", "raise", count=1)
    try:
        rig.dispatch_and_wait()
        rig.mm.collect_pipelined()
    finally:
        faults.disarm()
    (row,) = rig.tracing.recent_deliveries(1)
    assert row["status"] == "error"
    assert row["assemble_cpu_s"] is None and row["assemble_offcpu_s"] is None
    assert row["gap_in_flight_s"] == 0.0
    assert not any(k in row for k in PUBLISH_CPU)


# ---------------------------------------- the gap pass on the cohort's row


async def test_a_gap_pass_inside_the_flight_is_on_the_row():
    rig = Rig()
    await rig.mm._gap_pass(time.perf_counter(), 0)  # before the dispatch
    rig.add_direct("g")
    rig.add_direct("g")
    rig.dispatch_and_wait()
    await rig.mm._gap_pass(time.perf_counter(), 0)  # inside the flight
    assert len(rig.mm.collect_pipelined()) == 1
    (row,) = rig.tracing.recent_deliveries(1)
    before, inside = gaps(rig.tracing)
    assert before["_pc_end"] <= row["_pc_dispatch"] <= inside["_pc_start"]
    assert inside["in_flight"] == 1 and before["in_flight"] == 0
    assert row["gap_in_flight_s"] == pytest.approx(
        inside["_pc_end"] - inside["_pc_start"], abs=1e-9)
    assert row["gap_in_flight_s"] > 0.0


def test_gap_seconds_since_counts_the_overlap_alone():
    t = Tracing()

    def crumb(start, end, **kw):
        c = dict(_pc_start=start, _pc_end=end, **kw)
        t.breadcrumbs.append(c)
        return c

    crumb(1.0, 2.0, kind="gap", shed=False)
    crumb(2.5, 2.6, actives=4)  # an interval crumb
    crumb(3.0, 3.5, kind="gap", shed=False)
    crumb(4.0, 4.001, kind="gap", shed=True)
    crumb(5.0, 5.25, kind="gap", shed=False)
    assert t.gap_seconds_since(6.0) == 0.0
    assert t.gap_seconds_since(5.1) == pytest.approx(0.15)
    assert t.gap_seconds_since(3.25) == pytest.approx(0.25 + 0.25)
    assert t.gap_seconds_since(0.0) == pytest.approx(1.0 + 0.5 + 0.25)
    assert Tracing().gap_seconds_since(0.0) == 0.0


# ---------------------------- the keys the `gap` reader's metric files name


@pytest.mark.parametrize("spec", _metric_files(("gap",)))
async def test_gap_metric_file_names_a_key_the_pass_writes(spec):
    rig = Rig()
    await rig.mm._gap_pass(time.perf_counter(), 0)
    (c,) = gaps(rig.tracing)
    key = spec["args"]["key"]
    assert key in ("pass", "start") or isinstance(c[key], float), key
    assert spec["args"]["pick"] in ("first", "median")
