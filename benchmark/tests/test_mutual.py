"""The `mutual100k` cell's own files: the recipe draws the shares it
states from a seed and stays inside the reference's grammar; the cell's
two new metrics resolve by name through `run.py`'s lookup, and their
readers give hand-worked numbers on hand-made records."""

import json
import os
import types

import numpy as np
import pytest

import run
from lib import harness, reference
from lib.peaks import peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "mutual100k.burst"


@pytest.fixture(scope="module")
def config():
    return harness.load_json("configs", "mutual100k.json")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_recipe_draws_the_stated_shares_from_the_seed(config):
    recipe = harness.load_module("recipes", config["recipe"])
    params = config["recipe_params"]
    n = config["tickets"]
    specs = recipe.specs([3300000041, 0], n, params)
    assert len(specs) == n == 100_000
    region = np.array([params["regions"].index(s["strs"]["region"])
                       for s in specs])
    share = np.bincount(region, minlength=4) / n
    assert np.abs(share - np.array([0.4, 0.3, 0.2, 0.1])).max() < 0.01
    strict = np.array([s["query"] != "*" for s in specs])
    assert abs(strict.mean() - 0.7) < 0.01
    # strictness is drawn independently of the region
    for r in range(4):
        assert abs(strict[region == r].mean() - 0.7) < 0.02
    for s in specs[:2000]:
        assert s["min_count"] == s["max_count"] == 10 and s["nums"] == {}
        assert list(s["strs"]) == ["region"]
        assert s["query"] in ("*", f"+properties.region:{s['strs']['region']}")
        assert abs(float(np.linalg.norm(s["emb"])) - 1.0) < 1e-5
        assert s["emb"].shape == (16,) and s["emb"].dtype == np.float32
    # the same seed gives the same tickets, another seed others
    again = recipe.specs([3300000041, 0], n, params)
    assert all(a["query"] == b["query"] and a["strs"] == b["strs"]
               and np.array_equal(a["emb"], b["emb"])
               for a, b in zip(specs[:256], again[:256]))
    other = recipe.specs([3300000041, 1], 256, params)
    assert any(not np.array_equal(a["emb"], b["emb"])
               for a, b in zip(specs, other))


def test_recipe_stays_inside_the_reference_grammar(config):
    recipe = harness.load_module("recipes", config["recipe"])
    specs = recipe.specs([3300000042, 0], 4096, config["recipe_params"])
    enc = reference.encode(specs)  # parses every query
    assert enc["s_val"].shape == (4096, 1) and (enc["s_val"] >= 0).all()
    assert set(np.unique(enc["s_req"])) <= {-1, 0, 1, 2, 3}
    assert enc["emb"].shape == (4096, 16)
    assert set(enc["min_c"]) == set(enc["max_c"]) == {10}
    eu_strict = reference.parse("+properties.region:eu")
    assert reference.accepts(eu_strict, {"region": "eu"}, {})
    assert not reference.accepts(eu_strict, {"region": "sa"}, {})
    # a strict ticket and an any-region ticket of another region: valid
    # for the searcher alone, refused under rev
    pair = [dict(session=0, query="+properties.region:eu", min_count=2,
                 max_count=2, strs={"region": "eu"}, nums={}),
            dict(session=1, query="*", min_count=2, max_count=2,
                 strs={"region": "sa"}, nums={})]
    assert reference.match_fault(pair, rev=False) is None
    assert reference.match_fault(pair, rev=True) is not None


def test_the_configuration_states_what_the_issue_gives(config, bench):
    assert config["overrides"]["matchmaker.rev_precision"] is True
    assert config["reduced"] == [] and config["tickets"] == 100_000
    assert config["recipe_params"] == {
        "embedding_dims": 16, "match_size": 10,
        "regions": ["eu", "us", "ap", "sa"],
        "region_shares": [0.4, 0.3, 0.2, 0.1], "strict_share": 0.7}
    ranked = harness.load_json("configs", "ranked100k.json")
    assert config["expect"] == ranked["expect"]
    assert config["rehearse"] == ranked["rehearse"]
    (entry,) = [c for c in bench["configs"] if c["name"] == "mutual100k"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and entry["file"].endswith("mutual100k.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mutual100k", "burst", 1)


def test_the_cells_metrics_resolve_by_name(bench):
    """As `run.py main` finds them: the entry's name gives the file, the
    file's `reader` the module."""
    cell = run.find_cell(bench, CELL)
    traced = {m["name"]: m for m in run.metrics_for(bench, cell["name"], True)}
    assert {"stage1_roofline.rev", "mutual_refused_pct.burst",
            "score_device_ms.burst", "fetch_ms.burst", "assign_ms.burst",
            "publish_ms.burst", "device_idle_pct.burst",
            "process_host_ms.burst", "candidates_valid_per_active.burst",
            "candidates_distinct_pct.burst",
            "unmatched_actives_pct.burst"} <= set(traced)
    assert "stage1_roofline" not in traced  # 536 planes is not its work
    for name, reader in (("stage1_roofline.rev", "roofline"),
                         ("mutual_refused_pct.burst", "ledger_ratio")):
        spec = harness.load_json("layer_metrics", f"{name}.json")
        assert spec["reader"] == reader
        assert callable(harness.load_module("readers", reader).read)
        assert traced[name]["workloads"] == [CELL]
    end = {m["name"] for m in run.metrics_for(bench, cell["name"], False)}
    assert {"matched_per_s", "setup_s"} <= end


def test_mutual_refused_pct_on_hand_made_rows():
    spec = harness.load_json("layer_metrics", "mutual_refused_pct.burst.json")
    reader = harness.load_module("readers", spec["reader"])
    rows = [dict(hits_walked=1000, hits_rev_refused=10,
                 hits_combo_conflicts=90), dict(matches=3)]
    ctx = types.SimpleNamespace(window_rows=rows)
    assert reader.read(ctx, spec["args"]) == pytest.approx(10.0)
    # a program that keeps no such counter: nothing, not 0
    ctx = types.SimpleNamespace(window_rows=[dict(matches=3)])
    assert reader.read(ctx, spec["args"]) is None


def test_stage1_roofline_rev_on_a_hand_made_trace():
    spec = harness.load_json("layer_metrics", "stage1_roofline.rev.json")
    reader = harness.load_module("readers", spec["reader"])
    assert spec["args"]["dims"] == 520 + 520 + 16
    kernel = dict(a_pad=131072, n_cols=114688, col_block=1024)
    trace = dict(
        programs=[("jit_topk_candidates_big", 1.0, 2.1)],
        ops=[("tpu_custom_call.1", 1.0, 1.5), ("while.5", 1.5, 2.1),
             ("tpu_custom_call.1", 9.0, 9.5)],
    )
    ctx = types.SimpleNamespace(
        trace=trace, window_crumbs=[dict(kernel=kernel)],
        device=dict(kind="TPU v5 lite"), notes={})
    least = 2 * 131072 * 114688 * 1056 / peaks("TPU v5 lite")["bf16_flops"]
    assert least == pytest.approx(0.16116, rel=1e-3)
    value = reader.read(ctx, spec["args"])
    assert value == pytest.approx(100.0 * least / 0.5)
    assert ctx.notes["roofline"]["tpu_custom_call"]["roof"] == "compute"
