"""The `mutual100k` deployment (the 100k embedding pool under upstream's
`matchmaker.rev_precision=true`) at a small size: its recipe's tickets
through `TpuBackend` (interpreting, the two-stage kernel at the
configuration's rehearsal sizes) with the setting on and off, judged by
the benchmark's plain reference; the four counters the native
assembler's walk leaves on the cohort's ledger row; and the cell's own
rehearsal run, sound and with the reverse check planted off.

The benchmark's reference (`benchmark/lib/reference.py`) imports nothing
of the program; here it judges what the program formed.
"""

import json
import os
import shutil
import subprocess
import sys
import threading

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

SEED = [3300000033, 0]
WALK = native.WALK_COUNTERS


@pytest.fixture(scope="module")
def config():
    return harness.load_json("configs", "mutual100k.json")


@pytest.fixture(scope="module")
def specs(config):
    recipe = harness.load_module("recipes", config["recipe"])
    return recipe.specs(
        SEED, config["rehearse"]["tickets"], config["recipe_params"])


def _one_cohort(cfg, tickets, **blocks):
    """One pipelined interval over `tickets` (recipe specs): the matches
    as lists of ticket indices (searcher last), the cohort's ledger row
    once the idle-gap sweep has counted it, the dispatched kernels."""
    backend = TpuBackend(cfg, quiet_logger(), **blocks)
    formed = []
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend,
        on_matched=lambda batch: formed.extend(
            [int(e.presence.user_id[1:]) for e in match] for match in batch
        ),
    )
    for i, t in enumerate(tickets):
        p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
        mm.add([p], p.session_id, "", t["query"], t["min_count"],
               t["max_count"], 1, t["strs"], t["nums"],
               embedding=t.get("emb"))
    ready = threading.Event()
    backend.set_ready_callback(ready.set)
    mm.process()
    assert ready.wait(180), "the cohort never became ready"
    mm.collect_pipelined()
    backend.count_cohorts()  # the interval loop's idle-gap sweep
    row = backend.tracing.recent_deliveries(1)[0]
    kernels = [c["kernel"] for c in backend.tracing.recent(8)
               if "kernel" in c]
    mm.stop()
    return formed, row, kernels


@pytest.fixture(scope="module", params=[True, False], ids=["rev", "norev"])
def one_tick(request, config, specs):
    """The one interval of a burst of the recipe's tickets (min = max: a
    ticket searches once) at the configuration's rehearsal sizes, with
    `rev_precision` as the parameter says."""
    rehearse = config["rehearse"]
    cfg = MatchmakerConfig(
        pool_capacity=rehearse["matchmaker.pool_capacity"],
        big_pool_threshold=rehearse["matchmaker.big_pool_threshold"],
        max_intervals=config["max_intervals"],
        rev_precision=request.param,
    )
    assert cfg.candidates_per_ticket == config["candidates_per_ticket"]
    return request.param, _one_cohort(
        cfg, specs, big_row_block=rehearse["big_row_block"],
        big_col_block=rehearse["big_col_block"],
    )


def _faults(matches, specs, rev):
    out = []
    for m in matches:
        members = [dict(session=i, **{
            k: specs[i][k] for k in
            ("query", "min_count", "max_count", "strs", "nums")
        }) for i in m]
        fault = reference.match_fault(members, rev=rev)
        if fault is not None:
            out.append((m, fault))
    return out


def test_the_four_regions_fall_in_four_string_buckets(config):
    """Stage 1 separates the regions completely only if their hashes
    land in different buckets of the string plane, none in bucket 0
    (which tickets without the property share); stage 2 is exact either
    way."""
    from nakama_tpu.matchmaker.compile import hash_str
    from nakama_tpu.matchmaker.device2 import STR_BUCKETS

    buckets = [hash_str(w) & (STR_BUCKETS - 1)
               for w in config["recipe_params"]["regions"]]
    assert len(set(buckets)) == 4 and 0 not in buckets, buckets


def test_the_big_path_ran_under_the_setting(one_tick):
    rev, (matches, row, kernels) = one_tick
    (k,) = kernels
    assert k["kernel"] == "topk_candidates_big" and k["interpret"] is True
    assert k["rev"] is rev and k["with_embedding"] is True
    assert (k["fn"], k["fs"], k["k"], k["emb_dims"]) == (24, 16, 64, 16)
    assert matches and {len(m) for m in matches} == {10}
    assert row["matches"] == len(matches)
    members = [i for m in matches for i in m]
    assert len(members) == len(set(members))


def test_the_setting_decides_validity_on_this_mix(one_tick, specs):
    """Under `rev` every match passes the reference's mutual rule: every
    strict member's region is every other member's. Without it the same
    pool forms matches that rule refuses: an any-region searcher takes
    strict tickets of other regions than its fellows'."""
    rev, (matches, _, _) = one_tick
    assert not _faults(matches, specs, rev=False)  # searcher-centred
    mutual_faults = _faults(matches, specs, rev=True)
    if rev:
        assert not mutual_faults
        for m in matches:
            strict = {specs[i]["strs"]["region"] for i in m
                      if specs[i]["query"] != "*"}
            assert len(strict) <= 1
            if strict:
                assert {specs[i]["strs"]["region"] for i in m} == strict
    else:
        assert mutual_faults


def test_the_walks_counters_are_on_the_row(one_tick):
    rev, (matches, row, _) = one_tick
    for key in WALK:
        assert isinstance(row[key], int) and row[key] >= 0, (key, row)
    # every member but the searcher was a hit the walk reached
    assert row["hits_walked"] >= 9 * len(matches)
    assert row["hits_walked"] <= row["candidates_valid"]
    assert row["matches_needing_host"] == 0  # every query has its mirror
    refused = row["hits_rev_refused"] + row["hits_combo_conflicts"]
    if rev:
        assert refused > 0
        # stage 2 applies the mirrors exactly: the lists hold no ticket
        # whose query refuses the searcher, so all of it is the combos'
        assert row["hits_rev_refused"] == 0
    else:
        assert refused == 0
    json.dumps(row)  # the console's matchmaker view sends the row as it is


def test_stage2s_shape_is_on_the_row(one_tick, config):
    """Every two-stage cohort's row names the stripe its stage 2 ran in,
    the row gathers a kept winner cost and the words they brought, from
    the functions the program itself calls: under `rev` two rows, the
    scalars, values and int32 query mirrors on one, the embedding and
    the f32 mirrors on the other (no should slot: the pool holds no
    should query); without it one row of the scalars, the values and the
    embedding."""
    from nakama_tpu.matchmaker import device, device2

    rev, (_, row, (k,)) = one_tick
    assert row["stage2_gathers"] == k["stage2_gathers"] == (2 if rev else 1)
    assert row["stage2_words"] == k["stage2_words"] == (190 if rev else 62)
    pool = device.pool_schema(1, 24, 16, 16, 16)
    assert device2.stage2_words(pool, rev, False, True) == row["stage2_words"]
    assert device2.stage2_shape(pool, 1, 256, 64, rev, False, True)[
        "stage2_gathers"] == row["stage2_gathers"]
    # the rehearsal's dispatch is smaller than one stripe at either width
    assert row["stage2_stripe_rows"] == k["stage2_stripe_rows"] == k["a_pad"]
    keep = device2.stage2_keep(256, config["candidates_per_ticket"])
    assert device2.stage2_stripe(131072, keep, row["stage2_words"]) == (
        2048 if rev else 8192
    )


def test_a_pairs_cohort_carries_no_walk_counter():
    """A pool that is all solo 1v1 over `big_pool_threshold` is paired
    on the device: no walk, none of its counters."""
    tickets = [dict(query="*", min_count=2, max_count=2, strs={}, nums={})
               for _ in range(300)]
    cfg = MatchmakerConfig(pool_capacity=1024, big_pool_threshold=256,
                           max_intervals=2, rev_precision=True)
    matches, row, (k,) = _one_cohort(
        cfg, tickets, big_row_block=128, big_col_block=128)
    assert k["kernel"] == "topk_candidates_big+pair_partners" and k["rev"]
    assert matches and row["pairs_formed"] >= len(matches)
    # two rows under `rev`; the tickets carry no embedding, so its 16
    # words stay off them
    assert (row["stage2_gathers"], row["stage2_words"],
            row["stage2_stripe_rows"]) == (2, 174, 512)
    assert not set(WALK) & set(row)


def test_assemble_arrays_returns_the_four_sums():
    """Straight at the native call: three tickets, one list each. `a`
    (any-region, eu) lists `b` (strict us, which refuses it) and `c`
    (strict eu): under `rev` the walk reaches both and refuses `b`."""
    cfg = MatchmakerConfig(pool_capacity=256, max_intervals=2,
                           rev_precision=True)
    backend = TpuBackend(cfg, quiet_logger(), row_block=8, col_block=64)
    mm = LocalMatchmaker(quiet_logger(), cfg, backend=backend,
                         on_matched=lambda batch: None)
    ids = []
    for i, (query, region) in enumerate((
        ("*", "eu"), ("+properties.region:us", "us"),
        ("+properties.region:eu", "eu"),
    )):
        p = MatchmakerPresence(user_id=f"u{i}", session_id=f"s{i}")
        ticket, _ = mm.add([p], p.session_id, "", query, 2, 2, 1,
                           {"region": region}, {})
        ids.append(ticket)
    a, b, c = (backend.store.slot_by_id(t) for t in ids)
    cand = np.array([[b, c]], dtype=np.int32)
    (n, offsets, flat, ok), walk = backend._assemble(
        np.array([a], np.int32), np.array([1], np.uint8), cand, True)
    assert dict(zip(WALK, walk.tolist())) == dict(
        hits_walked=2, hits_rev_refused=1, hits_combo_conflicts=0,
        matches_needing_host=0, hits_overflow=0, trims=0, trims_failed=0,
        matches_trimmed=0)
    assert n == 1 and flat[:2].tolist() == [c, a] and ok.all()
    (n, _, flat, _), walk = backend._assemble(
        np.array([a], np.int32), np.array([1], np.uint8), cand, False)
    assert walk.tolist() == [1] + [0] * 7 and flat[:2].tolist() == [b, a]
    mm.stop()


_FORWARD_ONLY = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location("bench_run", sys.argv[1])
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)


def forward_only(ctx):
    # the reverse check planted off: the backend is told `rev` is off on
    # every interval while the configuration (and the judge) keep it on
    inner = ctx.backend.process_slots

    def process_slots(*a, **kw):
        kw["rev_precision"] = False
        return inner(*a, **kw)

    ctx.backend.process_slots = process_slots


sys.exit(run.main(sys.argv[3:],
                  sabotage=forward_only if sys.argv[2] == "1" else None))
"""


@pytest.fixture(scope="module")
def bench_copy(tmp_path_factory):
    """The benchmark in a directory of its own: `run.py` keeps a run's
    data directory beside `benchmark/`, and the suite's workers run
    other cells' rehearsals in the checkout at the same time."""
    root = tmp_path_factory.mktemp("mutual_bench")
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


@pytest.mark.parametrize("planted", [0, 1], ids=["sound", "forward_only"])
def test_rehearsal_run_of_the_cell(bench_copy, planted):
    """The cell's own run at its rehearsal size: `correct`, the new
    metrics printed; with the reverse check planted off not `correct`,
    by `invalid_matches`."""
    out = subprocess.run(
        [sys.executable, "-c", _FORWARD_ONLY,
         str(bench_copy / "benchmark" / "run.py"), str(planted),
         "--workload", "mutual100k.burst", "--seed", "3300000034",
         "--seconds", "8", "--trace", "1", "--rehearse", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        cwd=bench_copy, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    result = lines[-1]
    checks = {k: v["value"] for k, v in result["checks"].items()}
    (server,) = [ln for ln in lines if ln.get("line") == "server"]
    assert server["overrides"]["matchmaker.rev_precision"] is True
    if planted:
        assert result["correct"] is False
        assert checks["invalid_matches"] >= 1, checks
        assert checks["wrong_program"] >= 1  # the crumb says rev was off
        return
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["device"]["platform"] == "cpu"
    assert checks["invalid_matches"] == 0 and checks["wrong_program"] == 0
    metrics = result["metrics"]
    # the cell's per-layer metrics that need no device trace
    assert sorted(metrics) == [
        "assemble_offcpu_ms.burst",
        "assign_ms.burst", "candidates_distinct_pct.burst",
        "candidates_valid_per_active.burst", "fetch_ms.burst",
        # PR 35: the gap pass's crumb, read by `readers/gap.py`
        "gap_count_ms.burst", "gap_drain_ms.burst", "gap_gc_ms.burst",
        "gap_in_flight_ms.burst", "gap_pass_ms.burst", "gap_start_ms.burst",
        "gap_wake_late_ms.burst",
        "mutual_refused_pct.burst", "process_host_ms.burst",
        # PR 35: when the delivery call began, and its CPU beside its wall
        "publish_begin_ms.burst",
        # PR 36: the matches served as slices of the batch's flat list
        "publish_bulk_pct.burst", "publish_minor_faults.burst",
        "publish_ms.burst", "publish_offcpu_ms.burst",
        "publish_other_cpu_ms.burst", "unmatched_actives_pct.burst",
    ]
    assert metrics["publish_bulk_pct.burst"]["value"] == 100.0
    assert metrics["gap_in_flight_ms.burst"]["value"] == 0.0
    assert metrics["gap_start_ms.burst"]["value"] > (
        metrics["publish_begin_ms.burst"]["value"])  # the pass came after
    assert 0.0 < metrics["mutual_refused_pct.burst"]["value"] < 100.0
    assert 0.0 < metrics["candidates_valid_per_active.burst"]["value"] <= 64.0
    (dispatched,) = [ln for ln in lines if ln.get("line") == "dispatched"]
    assert [k["kernel"] for k in dispatched["kernels"]] == [
        "topk_candidates_big"]
    assert not dispatched["wrong"] and not dispatched["off_device"]
