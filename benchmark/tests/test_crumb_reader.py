"""The `crumb` and `ledger_ratio` readers on hand-made records, and the
metric files of the program's own spans: each loads, and names only
what its reader takes."""

import json
import os
import types

import pytest

from lib import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
crumb = harness.load_module("readers", "crumb")
ledger_ratio = harness.load_module("readers", "ledger_ratio")

PROGRAM_SPANS = [
    "device_wait_ms.burst", "d2h_ms.burst", "collect_wait_ms.burst",
    "collect_wait_ms.steady", "deliver_remove_ms.burst",
    "publish_materialise_ms.burst", "publish_token_ms.burst",
    "publish_envelope_ms.burst", "publish_route_ms.burst",
    "delivery_held_ms.burst", "delivery_held_ms.steady",
    "ingest.pipeline_us", "ingest.parse_us", "ingest.register_us",
    "ingest.journal_us", "ingest.trace_us", "ingest.adds_counted",
    "publish_hook_ms.burst", "publish_us_per_match.burst",
    "publish_us_per_envelope.burst",
]


def ctx_of(crumbs):
    return types.SimpleNamespace(window_crumbs=crumbs)


def test_ratio_of_sums_over_the_window():
    crumbs = [
        {"adds": 3, "adds_enveloped": 2, "add_parse_s": 3e-5,
         "add_register_s": 6e-5},
        {"adds": 1, "adds_enveloped": 0, "add_parse_s": 1e-5,
         "add_register_s": 2e-5},
    ]
    args = {"sum": ["add_parse_s"], "per": "adds", "scale": 1e6}
    assert crumb.read(ctx_of(crumbs), args) == pytest.approx(10.0)
    both = {"sum": ["add_parse_s", "add_register_s"], "per": "adds",
            "scale": 1e6}
    assert crumb.read(ctx_of(crumbs), both) == pytest.approx(30.0)
    enveloped = {"sum": ["add_parse_s"], "per": "adds_enveloped",
                 "scale": 1e3}
    assert crumb.read(ctx_of(crumbs), enveloped) == pytest.approx(0.02)


def test_a_count_is_a_sum_with_no_divisor():
    crumbs = [{"adds": 3}, {"actives": 9}, {"adds": 4}]
    args = {"sum": ["adds"], "scale": 1}
    assert crumb.read(ctx_of(crumbs), args) == 7
    assert crumb.read(ctx_of([{"actives": 9}]), args) is None
    assert crumb.read(ctx_of([]), args) is None


def test_ledger_ratio_reads_the_rows_of_calls_that_published():
    rows = [
        {"publish_token_s": 0.5, "publish_hook_s": 0.1,
         "publish_matches": 100, "publish_envelopes": 1000},
        {"ready_lag_s": 0.1},  # a cohort that published nothing
        {"publish_token_s": 0.3, "publish_hook_s": 0.1,
         "publish_matches": 100, "publish_envelopes": 200},
    ]
    ctx = types.SimpleNamespace(window_rows=rows)
    args = {"sum": ["publish_token_s", "publish_hook_s"],
            "per": "publish_matches", "scale": 1e6}
    assert ledger_ratio.read(ctx, args) == pytest.approx(5000.0)
    # the parent of the PR that added the stages: no such key on a row
    old = types.SimpleNamespace(window_rows=[{"ready_lag_s": 0.1}])
    assert ledger_ratio.read(old, args) is None
    assert ledger_ratio.read(types.SimpleNamespace(window_rows=[]), args) is None


def test_nothing_to_read_is_none_not_zero():
    args = {"sum": ["add_parse_s"], "per": "adds", "scale": 1e6}
    assert crumb.read(ctx_of([]), args) is None  # an empty window
    # no add in the window: the divisor is 0
    quiet = [{"adds": 0, "add_parse_s": 0.0}]
    assert crumb.read(ctx_of(quiet), args) is None
    # a program that keeps no such sums (the parent of the PR that added
    # them): the key is on no crumb
    old = [{"actives": 1000, "flush_s": 0.001}]
    assert crumb.read(ctx_of(old), args) is None
    half = [{"adds": 2}]
    assert crumb.read(ctx_of(half), args) is None
    # a key on some crumbs only is read where it is
    mixed = [{"actives": 5}, {"adds": 2, "add_parse_s": 4e-6}]
    assert crumb.read(ctx_of(mixed), args) == pytest.approx(2.0)


@pytest.mark.parametrize("name", PROGRAM_SPANS)
def test_metric_file_loads_and_names_what_its_reader_takes(name):
    spec = harness.load_json("layer_metrics", f"{name}.json")
    args = spec["args"]
    if spec["reader"] == "ledger":
        assert set(args) <= {"plus", "minus", "pick"}
        assert args["plus"] and args["pick"] in ("first", "median")
        assert args["pick"] == ("median" if name.endswith(".steady")
                                else "first")
        keys = args["plus"] + args.get("minus", [])
    elif name == "ingest.adds_counted":
        assert spec["reader"] == "crumb"
        assert args == {"sum": ["adds"], "scale": 1}
        keys = args["sum"]
    else:
        assert spec["reader"] == (
            "ledger_ratio" if name.endswith(".burst") else "crumb")
        assert set(args) == {"sum", "per", "scale"}
        assert args["scale"] == 1e6 and "us" in name.split("_")
        keys = args["sum"] + [args["per"]]
    assert all(isinstance(k, str) and k for k in keys)
    harness.load_module("readers", spec["reader"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert entries[name]["source"] == (
        "program_counter" if name == "ingest.adds_counted"
        else "program_span")
    assert entries[name]["workloads"] == [
        "ranked100k.burst" if name.endswith(".burst") else "duel1k.steady"
    ]
