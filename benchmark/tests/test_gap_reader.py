"""The `gap` reader on hand-made crumbs and rows, and the metric files
of PR 35's records (the gap pass, CPU beside wall on the cohort's row):
each loads, names a reader that is there and only what it takes."""

import json
import os
import types

import pytest

from lib import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
gap = harness.load_module("readers", "gap")
ledger = harness.load_module("readers", "ledger")
ledger_ratio = harness.load_module("readers", "ledger_ratio")

BURSTS = ["ranked100k.burst", "multiqueue8x20k.burst", "mutual100k.burst"]
GAP_METRICS = {
    "gap_pass_ms.burst": ("pass", "first"),
    "gap_pass_ms.steady": ("pass", "median"),
    "gap_gc_ms.burst": ("gc_s", "first"),
    "gap_gc_ms.steady": ("gc_s", "median"),
    "gap_drain_ms.burst": ("drain_s", "first"),
    "gap_count_ms.burst": ("count_s", "first"),
    "gap_start_ms.burst": ("start", "first"),
    "gap_wake_late_ms.burst": ("wake_late_s", "first"),
}
ROW_METRICS = {
    "publish_begin_ms.burst": "collect_lag_s",
    "publish_offcpu_ms.burst": "publish_offcpu_s",
    "publish_offcpu_ms.steady": "publish_offcpu_s",
    "publish_other_cpu_ms.burst": "publish_other_cpu_s",
    "assemble_offcpu_ms.burst": "assemble_offcpu_s",
    "gap_in_flight_ms.burst": "gap_in_flight_s",
    "gap_in_flight_ms.steady": "gap_in_flight_s",
}


def crumb(start, length, **kw):
    return dict(kind="gap", shed=False, _pc_start=start,
                _pc_end=start + length, **kw)


def ctx_of(crumbs, rows, t0=100.0, t1=120.0):
    tracing = types.SimpleNamespace(recent=lambda n: crumbs[-n:])
    return types.SimpleNamespace(
        backend=types.SimpleNamespace(tracing=tracing),
        window_rows=rows, t0=t0, t1=t1,
    )


CRUMBS = [
    crumb(97.0, 0.5, gc_s=0.4, drain_s=0.05, wake_late_s=0.0),  # warm-up
    {"actives": 100000, "_pc_start": 100.0, "_pc_end": 100.01},  # the tick
    crumb(101.25, 0.7, gc_s=0.5, drain_s=0.1, count_s=0.05,
          wake_late_s=0.24),
    {"kind": "gap", "shed": True, "_pc_start": 105.0, "_pc_end": 105.0,
     "wake_late_s": 0.0},
    crumb(109.0, 0.3, gc_s=0.25, drain_s=0.0, count_s=0.0, wake_late_s=0.001),
    crumb(113.0, 0.1, gc_s=0.05, drain_s=0.0, count_s=0.0, wake_late_s=0.0),
    crumb(125.0, 0.2, gc_s=0.15, drain_s=0.0, count_s=0.0, wake_late_s=0.0),
]
ROWS = [{"_pc_dispatch": 100.005, "collect_lag_s": 0.57},
        {"_pc_dispatch": 104.0}]


def test_first_is_the_first_pass_after_the_first_cohorts_dispatch():
    ctx = ctx_of(CRUMBS, ROWS)
    read = lambda key: gap.read(ctx, {"key": key, "pick": "first"})
    assert read("pass") == pytest.approx(700.0)
    assert read("gc_s") == pytest.approx(500.0)
    assert read("drain_s") == pytest.approx(100.0)
    assert read("count_s") == pytest.approx(50.0)
    assert read("wake_late_s") == pytest.approx(240.0)
    # against the row's `collect_lag_s` (570 ms): the pass fell behind
    # the delivery call's beginning
    assert read("start") == pytest.approx(1245.0)


def test_median_is_over_the_unshed_passes_that_start_in_the_window():
    ctx = ctx_of(CRUMBS, ROWS)
    read = lambda key: gap.read(ctx, {"key": key, "pick": "median"})
    assert read("pass") == pytest.approx(300.0)  # of 700, 300, 100
    assert read("gc_s") == pytest.approx(250.0)
    assert read("wake_late_s") == pytest.approx(1.0)
    # each pass's start against the first cohort's dispatch
    assert read("start") == pytest.approx(8995.0)
    # a window with no cohort still has its passes
    assert gap.read(ctx_of(CRUMBS, []),
                    {"key": "pass", "pick": "median"}) == pytest.approx(300.0)


def test_nothing_to_read_is_none():
    first = {"key": "pass", "pick": "first"}
    median = {"key": "gc_s", "pick": "median"}
    # the parent of the PR that added the crumbs: interval crumbs alone
    old = [{"actives": 1000, "_pc_start": 100.0, "_pc_end": 100.01}]
    for args in (first, median, {"key": "start", "pick": "first"}):
        assert gap.read(ctx_of(old, ROWS), args) is None
        assert gap.read(ctx_of([], ROWS), args) is None
    # no cohort in the window: nothing to be first after
    assert gap.read(ctx_of(CRUMBS, []), first) is None
    assert gap.read(ctx_of(CRUMBS, []),
                    {"key": "start", "pick": "median"}) is None
    # every pass before the dispatch, or shed
    assert gap.read(ctx_of(CRUMBS[:2] + CRUMBS[3:4], ROWS), first) is None
    # a crumb that lacks the key (a pass that raised before the stage)
    half = [crumb(101.0, 0.1, count_s=0.05)]
    assert gap.read(ctx_of(half, ROWS), {"key": "gc_s", "pick": "first"}) is None
    assert gap.read(ctx_of(half, ROWS), first) == pytest.approx(100.0)


def _entry(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}[name]


def _check_entry(name, layer):
    entry = _entry(name)
    assert entry["layer"] == layer
    assert set(
        BURSTS if name.endswith(".burst") else ["duel1k.steady"]
    ) <= set(entry["workloads"])
    assert entry["moves"] == (
        "tick_to_matched_p95_ms" if name.endswith(".burst")
        else "add_to_matched_p95_ms")
    return entry


@pytest.mark.parametrize("name", sorted(GAP_METRICS))
def test_gap_metric_file_loads_and_names_what_the_reader_takes(name):
    spec = harness.load_json("layer_metrics", f"{name}.json")
    assert spec["reader"] == "gap"
    harness.load_module("readers", spec["reader"])
    assert spec["args"] == dict(zip(("key", "pick"), GAP_METRICS[name]))
    assert spec["args"]["pick"] == (
        "median" if name.endswith(".steady") else "first")
    entry = _check_entry(name, "interval host")
    assert (entry["unit"], entry["source"]) == ("ms", "program_span")
    # it reads: a value off the hand-made record
    assert gap.read(ctx_of(CRUMBS, ROWS), spec["args"]) is not None


@pytest.mark.parametrize("name", sorted(ROW_METRICS))
def test_row_metric_file_loads_and_names_what_the_reader_takes(name):
    spec = harness.load_json("layer_metrics", f"{name}.json")
    assert spec["reader"] == "ledger"
    harness.load_module("readers", spec["reader"])
    assert spec["args"] == {
        "plus": [ROW_METRICS[name]],
        "pick": "median" if name.endswith(".steady") else "first",
    }
    layer = {"assemble": "assign", "gap": "interval host"}.get(
        name.split("_")[0], "accept/publish")
    entry = _check_entry(name, layer)
    assert (entry["unit"], entry["source"]) == ("ms", "program_span")
    rows = [{ROW_METRICS[name]: 0.25}, {"ready_lag_s": 0.1},
            {ROW_METRICS[name]: 0.75}, {ROW_METRICS[name]: 0.5}]
    ctx = types.SimpleNamespace(window_rows=rows)
    assert ledger.read(ctx, spec["args"]) == pytest.approx(
        500.0 if name.endswith(".steady") else 250.0)
    # the parent: no such key on any row
    old = types.SimpleNamespace(window_rows=[{"ready_lag_s": 0.1}])
    assert ledger.read(old, spec["args"]) is None or (
        ROW_METRICS[name] == "collect_lag_s")


def test_minor_faults_are_summed_over_the_windows_rows_as_a_count():
    name = "publish_minor_faults.burst"
    spec = harness.load_json("layer_metrics", f"{name}.json")
    assert spec == {"reader": "ledger_ratio", "args": {
        "sum": ["publish_minor_faults"], "scale": 1}}
    entry = _check_entry(name, "accept/publish")
    assert (entry["unit"], entry["source"]) == ("faults", "program_counter")
    rows = [{"publish_minor_faults": 1200}, {"ready_lag_s": 0.1},
            {"publish_minor_faults": 34}]
    ctx = types.SimpleNamespace(window_rows=rows)
    assert ledger_ratio.read(ctx, spec["args"]) == 1234
    old = types.SimpleNamespace(window_rows=[{"ready_lag_s": 0.1}])
    assert ledger_ratio.read(old, spec["args"]) is None
