"""The `party50k` deployment (solo and party tickets of one to five
players, six to ten a match in a multiple of two) at a small size: the
recipe, the program over two ticks against the plain reference, the
assembler's counters for the counted fill and the trim on hand-built
pools, and the cell's own rehearsal run and controls.

Judged by the benchmark's plain reference (`benchmark/lib/reference.py`,
which imports nothing of the program).
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import threading
from collections import Counter

import numpy as np
import pytest

from nakama_tpu import native
from nakama_tpu.config import MatchmakerConfig
from nakama_tpu.logger import test_logger as quiet_logger
from nakama_tpu.matchmaker import LocalMatchmaker, MatchmakerPresence
from nakama_tpu.matchmaker.tpu import TpuBackend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import harness, reference  # noqa: E402

SEED = 4200000042
WALK = native.WALK_COUNTERS
FILL = ("hits_overflow", "trims", "trims_failed", "matches_trimmed")


@pytest.fixture(scope="module")
def config():
    return harness.load_json("configs", "party50k.json")


@pytest.fixture(scope="module")
def recipe():
    return harness.load_module("recipes", "party")


# ------------------------------------------------------------ the recipe


def test_the_recipe_is_the_seed(config, recipe):
    params = config["recipe_params"]
    n = config["tickets"]
    specs = recipe.specs([SEED, 0], n, params)
    again = recipe.specs([SEED, 0], n, params)
    assert [s["count"] for s in again] == [s["count"] for s in specs]
    assert all((a["emb"] == b["emb"]).all() for a, b in zip(again, specs))
    other = recipe.specs([SEED, 1], n, params)
    assert [s["count"] for s in other] != [s["count"] for s in specs]
    # the shares within sampling error (four standard deviations)
    got = Counter(s["count"] for s in specs)
    for size, share in zip(params["sizes"], params["shares"]):
        sd = (share * (1 - share) / n) ** 0.5
        assert abs(got[size] / n - share) < 4 * sd, (size, got[size])
    players = sum(got[c] * c for c in got)
    assert abs(players / n - 2.3) < 0.02  # about 115,000 players
    for s in specs:
        assert (s["query"], s["min_count"], s["max_count"],
                s["count_multiple"]) == ("*", 6, 10, 2)
        assert s["strs"] == {} and s["nums"] == {}
    norms = np.linalg.norm(np.stack([s["emb"] for s in specs]), axis=1)
    assert np.abs(norms - 1).max() < 1e-5
    assert reference.counts(specs)[0] == [s["count"] for s in specs]
    assert config["reduced"] == [] and n == 50000


def test_the_recipe_is_the_one_the_party_judge_rehearsed(config, recipe):
    """The same specs as the recipe the judge's tests build a party cell
    from (`benchmark/tests/test_party_judge.py`)."""
    path = os.path.join(BENCH, "tests", "test_party_judge.py")
    spec = importlib.util.spec_from_file_location("party_judge_tests", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rehearsed = {}
    exec(mod.PARTY_RECIPE, rehearsed)
    params = config["recipe_params"]
    ours = recipe.specs([SEED, 3], 3000, params)
    theirs = rehearsed["specs"]([SEED, 3], 3000, params)
    for a, b in zip(ours, theirs):
        assert (a["emb"] == b["emb"]).all()
        assert {k: v for k, v in a.items() if k != "emb"} == \
            {k: v for k, v in b.items() if k != "emb"}


# ------------------------------------------- the program over two ticks


def _two_ticks(config, specs):
    """Two pipelined intervals of the two-stage kernel (interpreting) at
    the configuration's rehearsal sizes: each tick's matches as lists of
    (ticket index, session) and each cohort's ledger row once the
    idle-gap sweep has counted it."""
    rehearse = config["rehearse"]
    cfg = MatchmakerConfig(
        pool_capacity=rehearse["matchmaker.pool_capacity"],
        big_pool_threshold=rehearse["matchmaker.big_pool_threshold"],
        max_intervals=config["max_intervals"],
        max_party_size=config["max_party_size"],
    )
    backend = TpuBackend(cfg, quiet_logger(),
                         big_row_block=rehearse["big_row_block"],
                         big_col_block=rehearse["big_col_block"])
    formed = []
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend,
        on_matched=lambda batch: formed[-1].extend(
            [(int(e.presence.user_id.split(".")[0][1:]),
              e.presence.session_id) for e in match] for match in batch),
    )
    for i, s in enumerate(specs):
        party = [MatchmakerPresence(user_id=f"u{i}.{j}",
                                    session_id=f"s{i}.{j}")
                 for j in range(s["count"])]
        solo = s["count"] == 1
        mm.add(party, party[0].session_id if solo else "",
               "" if solo else f"p{i}", s["query"], s["min_count"],
               s["max_count"], s["count_multiple"], s["strs"], s["nums"],
               embedding=s["emb"])
    rows, kernels = [], []
    for _ in range(2):
        formed.append([])
        ready = threading.Event()
        backend.set_ready_callback(ready.set)
        mm.process()
        assert ready.wait(300), "the cohort never became ready"
        mm.collect_pipelined()
        backend.count_cohorts()  # the interval loop's idle-gap sweep
        rows.append(backend.tracing.recent_deliveries(1)[0])
        kernels += [c["kernel"]["kernel"] for c in backend.tracing.recent(8)
                    if "kernel" in c]
    mm.stop()
    return formed, rows, kernels


@pytest.mark.parametrize("seed", [4200000101, 4200000102, 4200000103])
def test_two_ticks_against_the_plain_reference(config, recipe, seed):
    n = config["rehearse"]["tickets"]
    specs = recipe.specs([seed, 0], n, config["recipe_params"])
    formed, rows, kernels = _two_ticks(config, specs)
    assert set(kernels) == {"topk_candidates_big"}
    seen_tickets, seen_sessions = set(), set()
    for tick, matches in enumerate(formed):
        for match in matches:
            members = [dict(
                session=sess, ticket=i, query=specs[i]["query"],
                min_count=6, max_count=10, count=specs[i]["count"],
                count_multiple=specs[i]["count_multiple"], strs={}, nums={},
            ) for i, sess in match]
            assert reference.match_fault(members, rev=False) is None, match
            if tick == 0:
                assert len(match) == 10  # a first interval forms only tens
            tickets = {i for i, _ in match}
            assert not tickets & seen_tickets  # no ticket in two matches
            seen_tickets |= tickets
            seen_sessions |= {sess for _, sess in match}
    assert formed[0], "the first tick formed nothing"
    # Players matched, against the plain matcher's over the same ticks.
    plain = reference.replay(specs, np.arange(n) * 1e-6, [1.0, 2.0],
                             config["candidates_per_ticket"], False,
                             config["max_intervals"])
    players = sum(s["count"] for s in specs)
    plain_players = sum(specs[i]["count"] for g in plain for i in g)
    limit = harness.load_json("traffic", "burst_party.json")[
        "rehearse"]["limits"]["yield_shortfall"]
    assert (plain_players - len(seen_sessions)) / players <= limit
    # the counters are on every assembled row; the fill turned hits away
    for row in rows:
        for key in WALK:
            assert isinstance(row[key], int) and row[key] >= 0, (key, row)
        assert row["trims_failed"] <= row["trims"]
        assert row["matches_trimmed"] <= min(row["trims"], row["matches"])
    assert rows[0]["trims"] == 0  # a full ten is even: nothing to trim
    assert rows[0]["hits_overflow"] > 0


# ----------------------------------------- the counters, on hand-built pools


@pytest.fixture()
def hand_built():
    """A matchmaker whose tickets are added by hand, and the native
    assembler called straight on one searcher's candidate list."""
    cfg = MatchmakerConfig(pool_capacity=256, max_intervals=2)
    backend = TpuBackend(cfg, quiet_logger(), row_block=8, col_block=64)
    mm = LocalMatchmaker(quiet_logger(), cfg, backend=backend,
                         on_matched=lambda batch: None)
    made = []

    def add(count, lo=6, hi=10, mult=2):
        i = len(made)
        party = [MatchmakerPresence(user_id=f"u{i}.{j}",
                                    session_id=f"s{i}.{j}")
                 for j in range(count)]
        ticket, _ = mm.add(party, party[0].session_id if count == 1 else "",
                           "" if count == 1 else f"p{i}", "*", lo, hi, mult,
                           {}, {})
        made.append(backend.store.slot_by_id(ticket))
        return made[-1]

    def walk(searcher, hits, last=True):
        cand = np.array([hits], dtype=np.int32)
        (n, offsets, flat, _), sums = backend._assemble(
            np.array([searcher], np.int32), np.array([int(last)], np.uint8),
            cand, False)
        matches = [flat[offsets[m]:offsets[m + 1]].tolist()
                   for m in range(n)]
        return matches, dict(zip(WALK, sums.tolist()))

    yield add, walk
    mm.stop()


def test_a_party_that_would_pass_max_count_is_turned_away(hand_built):
    add, walk = hand_built
    a, b, c = add(3), add(3), add(5)
    # a + b hold six players; c's five would make eleven: c opens a
    # combo of its own, which the last interval accepts as eight
    matches, sums = walk(a, [b, c])
    assert sums["hits_overflow"] == 1 and sums["hits_walked"] == 2
    assert matches == [[c, a]]
    assert (sums["trims"], sums["matches_trimmed"]) == (0, 0)
    # on a first interval a fill of eight is no match
    assert walk(a, [b, c], last=False) == ([], sums)


def test_a_fill_of_nine_is_trimmed_to_eight_by_its_solo(hand_built):
    add, walk = hand_built
    a, b, c, d = add(2), add(1), add(3), add(3)
    matches, sums = walk(a, [b, c, d])
    assert matches == [[c, d, a]]
    assert (sums["trims"], sums["trims_failed"], sums["matches_trimmed"]) \
        == (1, 0, 1)
    assert sums["hits_overflow"] == 0


def test_a_fill_of_nine_with_no_solo_forms_nothing(hand_built):
    add, walk = hand_built
    a, b, c, d = add(3), add(2), add(2), add(2)
    matches, sums = walk(a, [b, c, d])
    assert matches == []
    assert (sums["trims"], sums["trims_failed"], sums["matches_trimmed"]) \
        == (1, 1, 0)


@pytest.mark.parametrize("lo,hi,hits,last", [
    (2, 4, 3, False),  # a full four
    (6, 10, 6, True),  # a last interval's seven
    (10, 10, 12, False),  # a full ten, the walk cut short
])
def test_a_solo_pool_of_multiple_one_reads_zero(hand_built, lo, hi, hits,
                                                last):
    add, walk = hand_built
    slots = [add(1, lo, hi, 1) for _ in range(hits + 1)]
    matches, sums = walk(slots[0], slots[1:], last)
    assert len(matches) == 1
    assert sums["hits_walked"] == min(hits, hi - 1)
    assert {k: sums[k] for k in FILL} == dict.fromkeys(FILL, 0)


# ----------------------------------------------- the cell, from its files


def _copy(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)


def test_rehearsal_run_of_the_cell(tmp_path):
    """The cell's own run at its rehearsal size: `correct`, every match
    valid, and the three new metrics printed, each a share."""
    env = _copy(tmp_path)
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "party50k.burst", "--seed", "4200000003",
         "--seconds", "8", "--trace", "1", "--rehearse", "1"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0, result
    checks = {k: v["value"] for k, v in result["checks"].items()}
    for name in ("invalid_matches", "in_two_matches", "envelope_errors",
                 "ingest_mismatch", "acked_not_durable", "wrong_program",
                 "off_device_path"):
        assert checks[name] == 0, (name, checks)
    judged = next(ln for ln in lines if ln.get("line") == "judge")
    assert judged["matched_entries"] > 2 * judged["matches"] * 3
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    for name in ("fill_overflow_pct.burst", "trimmed_matches_pct.burst",
                 "trim_failed_pct.burst"):
        assert 0 <= metrics[name] <= 100, (name, metrics)
    assert metrics["fill_overflow_pct.burst"] > 0
    assert metrics["trimmed_matches_pct.burst"] > 0
    assert 9 < metrics["entries_per_match.burst"] <= 10
    assert "stage1_roofline" not in metrics  # never off a CPU run


def test_the_controls_fail_and_the_plain_matcher_passes(tmp_path):
    """`scripts/control_party.py` at the rehearsal size: the plain
    matcher passes; a party split into solo tickets and a size off the
    multiple fail by `invalid_matches`, each with its note; the
    embedding ignored fails the yield and the similarity."""
    env = _copy(tmp_path)
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "scripts"
                             / "control_party.py"),
         "party50k.burst", "4200000001", "--rehearse", "1"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    ways = {d["way"]: d for d in map(json.loads, out.stdout.splitlines())}
    assert ways["reference"]["correct"] is True
    for way, note in (("split", "split"), ("nomult", "not a multiple of 2")):
        assert ways[way]["correct"] is False
        assert ways[way]["checks"]["invalid_matches"][0] >= 1
        assert any(note in n for n in ways[way]["notes"]), ways[way]
    noemb = ways["noemb"]["checks"]
    for name in ("yield_shortfall", "similarity_shortfall"):
        assert noemb[name][0] > noemb[name][1], noemb
