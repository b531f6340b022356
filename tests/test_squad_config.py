"""The `squad50k` deployment (BASELINE config 2) at a small size: its
recipe against the benchmark's plain reference, two ticks of squad fill
on the two-stage kernel (interpreting backend, small blocks, shipped
widths, pipelined as shipped), the cohort row's candidate-list counters,
and the cell's own rehearsal run.

The benchmark's reference (`benchmark/lib/reference.py`) imports nothing
of the program; here it judges what the program formed.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from nakama_tpu.config import MatchmakerConfig
from nakama_tpu.logger import test_logger as quiet_logger
from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
from nakama_tpu.matchmaker.tpu import TpuBackend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import harness, reference  # noqa: E402

N = 1500
SEED = [2600000026, 0]
COUNTERS = (
    "candidates_valid", "candidates_distinct", "candidates_pool",
    "actives_unmatched", "matches_below_max",
)
# The program's two-tick yield against the unbounded walk's, as a share
# of the pool. Both are greedy and oldest first, and at this size a
# ticket's true candidates (about 22) fit its list of 64 uncut, so the
# two agree to the ticket on the seeds tried; the room is a few squads,
# for an order of equals that differs.
YIELD_TOLERANCE = 0.01


@pytest.fixture(scope="module")
def config():
    return harness.load_json("configs", "squad50k.json")


@pytest.fixture(scope="module")
def specs(config):
    recipe = harness.load_module("recipes", config["recipe"])
    return recipe.specs(SEED, N, config["recipe_params"])


@pytest.fixture(scope="module")
def two_ticks(config, specs):
    """Both intervals of a burst of `N` squad tickets: per tick the
    matches as lists of spec indices (searcher last) and the cohort's
    ledger row once the idle-gap sweep has counted its lists."""
    cfg = MatchmakerConfig(
        pool_capacity=2048, big_pool_threshold=256,
        max_intervals=config["max_intervals"],
    )
    assert cfg.candidates_per_ticket == config["candidates_per_ticket"]
    backend = TpuBackend(
        cfg, quiet_logger(), big_row_block=128, big_col_block=128
    )
    formed = []
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend,
        # Entries are read here: a batch's tickets are gone once the
        # store drains.
        on_matched=lambda batch: formed.extend(
            [int(e.presence.session_id[1:]) for e in match]
            for match in batch
        ),
    )
    for i, s in enumerate(specs):
        p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
        mm.add([p], p.session_id, "", s["query"], s["min_count"],
               s["max_count"], 1, s["strs"], s["nums"])
    ready = threading.Event()
    backend.set_ready_callback(ready.set)
    ticks = []
    for _ in range(2):
        ready.clear()
        mm.process()
        assert ready.wait(120), "the cohort never became ready"
        mm.collect_pipelined()
        backend.count_cohorts()  # the interval loop's idle-gap sweep
        matches, formed[:] = list(formed), []
        ticks.append((matches, backend.tracing.recent_deliveries(1)[0]))
    kernel = [c["kernel"] for c in backend.tracing.recent(8) if "kernel" in c]
    mm.stop()
    return ticks, kernel


def test_recipe_stays_inside_the_reference_grammar(specs):
    """`encode` parses every query and refuses a number that is not
    exact in float32; the mix is config 2's: 8 numeric and 4 string
    properties, a four-term query, squads of 3 to 4."""
    enc = reference.encode(specs)
    assert enc["n_val"].shape == (N, 8) and enc["s_val"].shape == (N, 4)
    assert not np.isnan(enc["n_val"]).any() and (enc["s_val"] >= 0).all()
    assert (enc["n_con"].sum(axis=1) == 1).all()  # rank alone is asked for
    assert ((enc["s_req"] >= 0).sum(axis=1) == 2).all()  # mode and region
    assert set(enc["min_c"]) == {3} and set(enc["max_c"]) == {4}
    for s in specs[:50]:
        assert len(reference.parse(s["query"])) == 4
        assert not reference.accepts(reference.parse(s["query"]), {}, {})


def test_recipe_is_the_seed_and_bench_cfg2_mix(config, specs):
    recipe = harness.load_module("recipes", config["recipe"])
    again = recipe.specs(SEED, N, config["recipe_params"])
    assert again == specs
    assert recipe.specs([SEED[0], 1], 8, config["recipe_params"]) != specs[:8]
    ranks = np.array([s["nums"]["rank"] for s in specs])
    assert 0 <= ranks.min() and ranks.max() < 2000
    assert {s["strs"]["region"] for s in specs} == {"eu", "us", "ap", "sa"}
    assert {s["strs"]["mode"] for s in specs} == {"m0", "m1", "m2", "m3"}


def test_both_ticks_ran_the_two_stage_kernel_without_embedding(two_ticks):
    _, kernels = two_ticks
    assert len(kernels) == 2
    for k in kernels:
        assert k["kernel"] == "topk_candidates_big"
        assert k["with_embedding"] is False and k["interpret"] is True
        assert (k["fn"], k["fs"], k["k"]) == (24, 16, 64)
    assert kernels[0]["a_pad"] > kernels[1]["a_pad"]  # tick 2: the leftovers


@pytest.mark.parametrize("tick", [0, 1])
def test_every_match_is_valid_by_the_reference(two_ticks, specs, tick):
    matches, _ = two_ticks[0][tick]
    assert matches
    for m in matches:
        members = [dict(session=i, **{
            k: specs[i][k] for k in
            ("query", "min_count", "max_count", "strs", "nums")
        }) for i in m]
        assert reference.match_fault(members, rev=False) is None, m
        # the searcher is the last member: its query accepts the others
        terms = reference.parse(specs[m[-1]]["query"])
        assert all(reference.accepts(
            terms, specs[i]["strs"], specs[i]["nums"]) for i in m[:-1])


def test_first_tick_forms_fours_only_and_the_second_fills_threes(two_ticks):
    (first, _), (second, _) = two_ticks[0]
    assert {len(m) for m in first} == {4}
    assert {len(m) for m in second} <= {3, 4}
    assert 3 in {len(m) for m in second}
    assert len(first) > 10 * len(second)


def test_no_ticket_in_two_matches(two_ticks):
    members = [i for matches, _ in two_ticks[0] for m in matches for i in m]
    assert len(members) == len(set(members))


def test_two_tick_yield_is_the_unbounded_walks(two_ticks, specs):
    """The reference server walks every hit (SURVEY 2.5); `replay` with
    `k` = the pool size is that walk. A plain matcher whose lists are
    cut oldest first, with no jitter, starves: here at `k` = 8, at
    50,000 tickets at the judge's 64 (PERF.md section 2)."""
    ack = np.arange(N) * 1e-6
    walk = reference.replay(specs, ack, [1.0, 2.0], N, False, 2)
    walked = sum(len(g) for g in walk)
    got = sum(len(m) for matches, _ in two_ticks[0] for m in matches)
    assert abs(got - walked) <= YIELD_TOLERANCE * N, (got, walked)
    assert got >= 0.9 * N
    cut = reference.replay(specs, ack, [1.0, 2.0], 8, False, 2)
    assert sum(len(g) for g in cut) < got


@pytest.mark.parametrize("tick", [0, 1])
def test_row_counters_are_present_and_add_up(two_ticks, tick):
    matches, row = two_ticks[0][tick]
    for key in COUNTERS:
        assert isinstance(row[key], int), (key, row)
    k = 64
    assert 0 < row["candidates_valid"] <= row["actives"] * k
    assert 0 < row["candidates_distinct"] <= row["candidates_pool"]
    assert row["candidates_distinct"] <= row["candidates_valid"]
    matched = sum(len(m) for m in matches)
    assert row["envelopes"] == matched
    # every ticket of a burst searches, so an unmatched one is a searcher
    assert row["actives_unmatched"] == row["actives"] - matched
    assert row["matches_below_max"] == sum(1 for m in matches if len(m) < 4)
    if tick == 0:
        assert row["candidates_pool"] == row["actives"] == N
        assert row["matches_below_max"] == 0
    else:
        first = two_ticks[0][0][1]
        assert row["candidates_pool"] == first["actives_unmatched"]
        assert row["matches_below_max"] > 0


def test_counting_lets_go_of_the_lists_and_runs_once():
    """A swept cohort keeps no candidate array, and a second sweep finds
    nothing to do."""
    cfg = MatchmakerConfig(pool_capacity=256, max_intervals=2)
    backend = TpuBackend(cfg, quiet_logger(), row_block=8, col_block=64)
    mm = LocalMatchmaker(quiet_logger(), cfg, backend=backend,
                         on_matched=lambda batch: None)
    for i in range(4):
        p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
        mm.add([p], p.session_id, "", "*", 2, 2, 1, {}, {})
    ready = threading.Event()
    backend.set_ready_callback(ready.set)
    mm.process()
    assert ready.wait(60)
    mm.collect_pipelined()
    (work,) = backend._uncounted
    assert work.cand is not None and "candidates_valid" not in work.entry
    backend.count_cohorts()
    assert work.cand is None and not backend._uncounted
    # the exact kernel's lists count too: 4 wildcard tickets, 3 others each
    assert work.entry["candidates_valid"] == 12
    assert work.entry["candidates_distinct"] == 4
    assert work.entry["actives_unmatched"] == 0
    backend.count_cohorts()
    mm.stop()


def test_rehearsal_run_of_the_cell_is_correct_and_prints_the_new_metrics():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "squad50k.burst", "--seed", "2600000031", "--seconds", "9",
         "--trace", "1", "--rehearse", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["device"]["platform"] == "cpu"
    metrics = result["metrics"]
    # The cell's per-layer metrics are the counters: it carries no
    # `tick_to_matched_p95_ms` (PERF.md section 2), so none of the
    # metrics that move it.
    assert sorted(metrics) == [
        "candidates_distinct_pct.burst", "candidates_valid_per_active.burst",
        "entries_per_match.burst", "unmatched_actives_pct.burst",
    ]
    assert 3.0 <= metrics["entries_per_match.burst"]["value"] <= 4.0
    assert 0.0 < metrics["candidates_valid_per_active.burst"]["value"] <= 64.0
    assert 90.0 < metrics["candidates_distinct_pct.burst"]["value"] <= 100.0
    assert 0.0 < metrics["unmatched_actives_pct.burst"]["value"] < 50.0
    (ticks,) = [ln for ln in lines if ln.get("line") == "ticks"]
    assert ticks["pool"][0] == 1200 and 0 < ticks["pool"][1] < 240
    (dispatched,) = [ln for ln in lines if ln.get("line") == "dispatched"]
    assert [k["kernel"] for k in dispatched["kernels"]] == [
        "topk_candidates_big"] * 2
    assert not dispatched["wrong"] and not dispatched["off_device"]
