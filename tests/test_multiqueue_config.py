"""The `multiqueue8x20k` deployment (BASELINE config 5) at a small size:
its recipe against the benchmark's plain reference, one tick of eight
1v1 queues on the two-stage kernel with the pairs assigned on the device
(interpreting backend, small blocks, shipped widths, pipelined as
shipped), the cohort row's pairing counters, and the cell's own
rehearsal run.

The benchmark's reference (`benchmark/lib/reference.py`) imports nothing
of the program; here it judges what the program formed.
"""

import hashlib
import json
import os
import shutil
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

POOLS = 8
N = POOLS * 200
SEED = [3000000030, 0]
PAIR_COUNTERS = (
    "pairs_formed", "pairs_rejected", "pairs_formed_last_round",
    "pair_rounds", "pair_rows_gathered", "pair_rows_dense",
    "candidates_valid", "candidates_pool", "actives_unmatched",
    "matches_below_max",
)
# The program's one-tick yield against the unbounded walk's, as a share
# of the pool. The walk pairs a searcher with the oldest ticket it
# accepts; the program pairs from lists of 64 over eight rounds. At this
# size a ticket's true candidates (about 38) fit its list uncut, so
# both leave only tickets whose every candidate was taken first: on
# three seeds the program matched 1,566 / 1,578 / 1,574 of 1,600 where
# the walk matched 1,580 / 1,574 / 1,580; the room is for an order that
# differs.
YIELD_TOLERANCE = 0.02


@pytest.fixture(scope="module")
def config():
    return harness.load_json("configs", "multiqueue8x20k.json")


@pytest.fixture(scope="module")
def specs(config):
    recipe = harness.load_module("recipes", config["recipe"])
    return recipe.specs(SEED, N, config["recipe_params"])


def _one_cohort(cfg, tickets, **blocks):
    """One pipelined interval over `tickets` (dicts of `mm.add`'s query,
    counts and property maps; `session`, where two tickets share one):
    the matches as lists of ticket indices, the cohort's ledger row
    before and after the idle-gap sweep, the dispatched kernels and the
    cohort."""
    backend = TpuBackend(cfg, quiet_logger(), **blocks)
    formed = []
    mm = LocalMatchmaker(
        quiet_logger(), cfg, backend=backend,
        on_matched=lambda batch: formed.extend(
            [int(e.presence.user_id[1:]) for e in match] for match in batch
        ),
    )
    for i, t in enumerate(tickets):
        sid = t.get("session", f"s{i}")
        p = MatchmakerPresence(user_id=f"u{i}", session_id=sid)
        mm.add([p], sid, "", t["query"], t["min_count"], t["max_count"], 1,
               t["strs"], t["nums"])
    ready = threading.Event()
    backend.set_ready_callback(ready.set)
    mm.process()
    assert ready.wait(120), "the cohort never became ready"
    mm.collect_pipelined()
    (work,) = backend._uncounted
    before = dict(work.entry)
    backend.count_cohorts()  # the interval loop's idle-gap sweep
    row = backend.tracing.recent_deliveries(1)[0]
    kernels = [c["kernel"] for c in backend.tracing.recent(8)
               if "kernel" in c]
    mm.stop()
    return formed, row, kernels, before, work


def _queue_tickets(n, session=None):
    """`n` duel tickets, two to a queue `q<i>`; `session(i)` names a
    ticket's session where the default (its own) will not do."""
    return [dict(query=f"+properties.q:q{i // 2}", min_count=2, max_count=2,
                 strs={"q": f"q{i // 2}"}, nums={},
                 **({"session": session(i)} if session else {}))
            for i in range(n)]


@pytest.fixture(scope="module")
def one_tick(config, specs):
    """The one interval of a burst of `N` duel tickets (min = max: a
    ticket searches once), at the configuration's rehearsal sizes."""
    rehearse = config["rehearse"]
    cfg = MatchmakerConfig(
        pool_capacity=rehearse["matchmaker.pool_capacity"],
        big_pool_threshold=rehearse["matchmaker.big_pool_threshold"],
        max_intervals=config["max_intervals"],
    )
    assert cfg.candidates_per_ticket == config["candidates_per_ticket"]
    return _one_cohort(
        cfg, specs, big_row_block=rehearse["big_row_block"],
        big_col_block=rehearse["big_col_block"],
    )


def test_recipe_stays_inside_the_reference_grammar(specs):
    """`encode` parses every query and refuses a number that is not
    exact in float32; the mix is config 5's: one string and one numeric
    property, a three-term query, two to a match."""
    enc = reference.encode(specs)
    assert enc["n_val"].shape == (N, 1) and enc["s_val"].shape == (N, 1)
    assert not np.isnan(enc["n_val"]).any() and (enc["s_val"] >= 0).all()
    assert (enc["n_con"].sum(axis=1) == 1).all()  # the rank window
    assert ((enc["s_req"] >= 0).sum(axis=1) == 1).all()  # the pool
    assert set(enc["min_c"]) == {2} and set(enc["max_c"]) == {2}
    for s in specs[:50]:
        assert len(reference.parse(s["query"])) == 3
        assert not reference.accepts(reference.parse(s["query"]), {}, {})
        assert "emb" not in s


def test_recipe_is_the_seed_and_every_pool_holds_exactly_its_share(
        config, specs):
    recipe = harness.load_module("recipes", config["recipe"])
    params = config["recipe_params"]
    assert recipe.specs(SEED, N, params) == specs
    other = recipe.specs([SEED[0], 1], N, params)
    assert [s["strs"] for s in other] != [s["strs"] for s in specs]
    pools = [s["strs"]["pool"] for s in specs]
    assert {p: pools.count(p) for p in set(pools)} == {
        f"p{i}": N // POOLS for i in range(POOLS)}
    # drawn from the seed, not laid out pool after pool
    assert pools[:N // POOLS] != ["p0"] * (N // POOLS)
    ranks = np.array([s["nums"]["rank"] for s in specs])
    assert 0 <= ranks.min() and ranks.max() < 1000
    assert (ranks == ranks.astype(np.float32)).all()
    assert config["tickets"] == POOLS * 20000 and config["reduced"] == []
    with pytest.raises(ValueError):
        recipe.specs(SEED, N + 1, params)


def test_the_pool_words_fall_in_eight_buckets_of_the_string_plane(config):
    """Per-pool masking as the source says it: under the device's crc32
    the eight pool names fill stage 1's eight string buckets, so no
    ticket of another pool passes the matmul."""
    from nakama_tpu.matchmaker.compile import hash_str
    from nakama_tpu.matchmaker.device2 import STR_BUCKETS

    buckets = {hash_str(p) & (STR_BUCKETS - 1)
               for p in config["recipe_params"]["pools"]}
    assert len(buckets) == POOLS == STR_BUCKETS


def test_the_tick_pairs_on_the_device(one_tick, config):
    _, _, kernels, _, _ = one_tick
    (k,) = kernels
    assert k["kernel"] == config["expect"]["kernel"] == (
        "topk_candidates_big+pair_partners")
    assert k["with_embedding"] is False and k["interpret"] is True
    assert {w: k[w] for w in config["expect"]["widths"]} == (
        config["expect"]["widths"])


def test_every_pair_is_valid_by_the_reference(one_tick, specs):
    matches = one_tick[0]
    assert matches and {len(m) for m in matches} == {2}
    for m in matches:
        members = [dict(session=i, **{
            k: specs[i][k] for k in
            ("query", "min_count", "max_count", "strs", "nums")
        }) for i in m]
        assert reference.match_fault(members, rev=False) is None, m
        assert specs[m[0]]["strs"] == specs[m[1]]["strs"], m  # one pool


def test_no_ticket_in_two_matches(one_tick):
    members = [i for m in one_tick[0] for i in m]
    assert len(members) == len(set(members))


def test_one_tick_yield_is_the_unbounded_walks(one_tick, specs):
    """The reference server walks every hit (SURVEY 2.5); `replay` with
    `k` = the pool size is that walk."""
    ack = np.arange(N) * 1e-6
    walk = reference.replay(specs, ack, [1.0], N, False, 2)
    walked = sum(len(g) for g in walk)
    got = 2 * len(one_tick[0])
    assert abs(got - walked) <= YIELD_TOLERANCE * N, (got, walked)
    assert got >= 0.95 * N


def test_pair_counters_are_on_the_row_and_add_up(one_tick, config):
    matches, row, _, before, work = one_tick
    # written by the idle-gap sweep, never between dispatch and publish
    assert not (set(PAIR_COUNTERS) | {"pair_round_rows"}) & set(before)
    assert before["publish_lag_s"] is not None
    assert work.pairs is None  # the sweep let go of what it read
    for key in PAIR_COUNTERS:
        assert isinstance(row[key], int), (key, row)
    assert "candidates_distinct" not in row
    rounds = row["pair_rounds_formed"]
    assert len(rounds) == row["pair_rounds"] == 8
    assert all(isinstance(n, int) and n >= 0 for n in rounds)
    assert sum(rounds) == row["pairs_formed"]
    assert rounds[-1] == row["pairs_formed_last_round"]
    assert rounds[0] > rounds[-1]
    _assert_round_rows_hold_the_open_rows(row, a_pad=2048)
    # whole-number ranks compare exactly on the device: nothing for the
    # host's f64 re-check to throw away
    assert row["pairs_rejected"] == 0
    assert row["pairs_formed"] - row["pairs_rejected"] == row["matches"]
    assert row["matches"] == len(matches) and row["envelopes"] == 2 * len(
        matches)
    k = config["candidates_per_ticket"]
    assert 0 < row["candidates_valid"] <= row["actives"] * k
    assert row["candidates_pool"] == row["actives"] == N
    assert row["actives_unmatched"] == N - 2 * len(matches)
    assert row["matches_below_max"] == 0
    assert row["d2h_bytes"] < 5 * 4 * 2048  # a partner vector, no lists
    json.dumps(row)  # the console's matchmaker view sends the row as it is


def _assert_round_rows_hold_the_open_rows(row, a_pad):
    """`pair_round_rows`: the rows each round gathered availability for,
    a size of the shape's ladder that holds the rows open at the round's
    start (every ticket here is a row, so a pair closes two), or none
    once no row is open; its sum and the dense rounds' rows beside it."""
    from nakama_tpu.matchmaker.device2 import pair_ladder

    ran = row["pair_round_rows"]
    assert len(ran) == row["pair_rounds"]
    assert all(isinstance(n, int) for n in ran)
    opened = row["actives"]
    for rows, formed in zip(ran, row["pair_rounds_formed"]):
        assert rows in pair_ladder(a_pad) if opened else rows == 0
        assert rows >= opened
        opened -= 2 * formed
    assert row["pair_rows_gathered"] == sum(ran)
    assert row["pair_rows_dense"] == a_pad * row["pair_rounds"]
    assert 0 < row["pair_rows_gathered"] <= row["pair_rows_dense"]


def test_a_pair_the_exact_recheck_refuses_is_counted_rejected():
    """`_assemble_pairs` drops a device-formed pair whose members share a
    session; the row says so."""
    cfg = MatchmakerConfig(pool_capacity=512, big_pool_threshold=8,
                           max_intervals=2)
    # tickets 0 and 1 are one session's: they may not meet
    tickets = _queue_tickets(16, lambda i: "s0" if i < 2 else f"s{i}")
    got, row, _, _, _ = _one_cohort(
        cfg, tickets, big_row_block=128, big_col_block=128)
    assert row["pairs_formed"] == 8 and row["pairs_rejected"] == 1
    assert row["matches"] == len(got) == 7
    assert row["actives_unmatched"] == 2


def test_a_cohort_of_fewer_rows_than_rounds_keeps_every_round():
    """The worker cuts what it fetches to the cohort's rows; four
    actives may not cut eight rounds' counts to four."""
    cfg = MatchmakerConfig(pool_capacity=512, big_pool_threshold=2,
                           max_intervals=2)
    got, row, _, _, _ = _one_cohort(
        cfg, _queue_tickets(4), big_row_block=128, big_col_block=128)
    assert row["actives"] == 4 and row["pair_rounds"] == 8
    assert len(row["pair_rounds_formed"]) == 8
    assert sum(row["pair_rounds_formed"]) == row["pairs_formed"] == 2
    assert row["pairs_rejected"] == 0 and row["matches"] == len(got) == 2
    assert row["candidates_valid"] == 4  # each lists its one partner
    # both pairs form in round 0: one step of rows, then none open
    assert row["pair_round_rows"] == [128] + [0] * 7
    _assert_round_rows_hold_the_open_rows(row, a_pad=128)


# A cohort row's keys at the parent (commit 3bdfc14) for a pool under
# `big_pool_threshold`: exact kernel, native assembler, pipelined; and
# the assembler's walk counters, on every assembled row (validation's,
# then the counted fill's and the trim's);
# since PR 35 the worker's and the delivery call's CPU beside their wall
# and the gap passes that ran during the flight.
SMALL_PATH_ROW_KEYS = {
    "assemble_cpu_s", "assemble_offcpu_s", "gap_in_flight_s",
    "publish_cpu_s", "publish_offcpu_s", "publish_other_cpu_s",
    "publish_invol_switches", "publish_minor_faults",
    "_pc_dispatch", "accept_lag_s", "actives", "actives_unmatched",
    "candidates_distinct", "candidates_pool", "candidates_valid",
    "collect_lag_s", "d2h_bytes", "deliver_remove_s", "delivery_held_s",
    "device_done_lag_s", "device_timeline", "dispatched_ts", "envelopes",
    "fetch_lag_s", "hits_combo_conflicts", "hits_rev_refused",
    "hits_walked", "interval_seq", "matches", "matches_below_max",
    "matches_needing_host", "publish_gc_collections", "publish_lag_s", "ready_lag_s", "seq",
    "hits_overflow", "trims", "trims_failed", "matches_trimmed",
    "slipped", "status", "trace_id", "ts",
}


def test_a_small_path_cohorts_row_is_the_parents():
    """A `duel1k`-shaped pool (region and a rank window required, 1v1,
    under `big_pool_threshold`) runs the exact kernel and the native
    assembler: its row carries the parent's keys and none of the
    pairing's."""
    ranks = [(i * 37) % 100 for i in range(40)]
    tickets = [dict(
        query=(f"+properties.region:eu +properties.rank:>={max(0, r - 10)}"
               f" +properties.rank:<={r + 10}"),
        min_count=2, max_count=2, strs={"region": "eu"},
        nums={"rank": float(r)},
    ) for r in ranks]
    got, row, (k,), _, work = _one_cohort(
        MatchmakerConfig(pool_capacity=2048, max_intervals=2), tickets)
    assert k["kernel"] == "topk_candidates" and got
    assert work.pairs is None and work.cand is None
    assert set(row) == SMALL_PATH_ROW_KEYS
    assert not {key for key in row if key.startswith("pair")}


def _seeded_lists(seed, a=512, k=16, cap=1024, pad=37):
    """Candidate lists from `seed`: `a - pad` active rows on a
    permutation of slots, each listing up to `k` distinct other slots
    (rows and passive ones), -1 padded."""
    rng = np.random.default_rng(seed)
    slots = rng.permutation(cap)[: a - pad].astype(np.int32)
    active = np.concatenate([slots, np.full(pad, -1, np.int32)])
    cand = np.full((a, k), -1, np.int32)
    for i in range(a - pad):
        n = int(rng.integers(0, k + 1))
        c = rng.choice(cap, size=n, replace=False).astype(np.int32)
        c = c[c != slots[i]]
        cand[i, : len(c)] = c
    return cand, active


@pytest.mark.parametrize("seed,digest,pairs", [
    (30, "10c11b2d2ec231f2c4ea6df9c53dea3c363e1b2782ea8e150638b0a39c24ade8",
     380),
    (31, "7450b800cd2f2bca804c8f8cf125602e985b06be6407b899d088cb489a5a1057",
     384),
])
def test_partner_vector_is_bit_for_bit_the_parents(seed, digest, pairs):
    """The counters are by-products of the scan, not a change of the
    rounds: the digests are of the partner vector the parent's
    `pair_partners` (commit 3bdfc14) gave on the same lists."""
    import jax.numpy as jnp

    from nakama_tpu.matchmaker.device2 import pair_partners

    cand, active = _seeded_lists(seed)
    partner, formed, listed, _ = pair_partners(
        jnp.asarray(cand), jnp.asarray(active), cap=1024)
    partner = np.asarray(partner)
    assert hashlib.sha256(partner.tobytes()).hexdigest() == digest
    formed, listed = np.asarray(formed), np.asarray(listed)
    # one row each: a caller's row slice keeps them whole
    assert formed.shape == (1, 8) and listed.shape == (1,)
    assert int(formed.sum()) == int((partner >= 0).sum()) == pairs
    assert int(listed[0]) == int((cand >= 0).sum())


def test_rehearsal_run_of_the_cell_is_correct_and_prints_the_counters(
        tmp_path):
    # In a copy of the benchmark: a run keeps its server log, data and
    # profile under its checkout's root, and `test_squad_config.py`'s
    # rehearsal may be running in the repo's own at this moment.
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "multiqueue8x20k.burst", "--seed", "3000000031",
         "--seconds", "6", "--trace", "1", "--rehearse", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["device"]["platform"] == "cpu"
    metrics = result["metrics"]
    for name in ("candidates_valid_per_active.burst",
                 "unmatched_actives_pct.burst", "pairs_rejected_pct.burst",
                 "pairs_last_round_pct.burst",
                 "pair_rows_gathered_pct.burst"):
        assert name in metrics, sorted(metrics)
    # a CPU run reports no device metric
    assert not {"pair_device_ms.burst", "pair_roofline",
                "stage1_roofline.noemb"} & set(metrics)
    assert 0.0 < metrics["candidates_valid_per_active.burst"]["value"] <= 64.0
    assert 0.0 <= metrics["unmatched_actives_pct.burst"]["value"] < 10.0
    assert metrics["pairs_rejected_pct.burst"]["value"] == 0.0
    assert 0.0 <= metrics["pairs_last_round_pct.burst"]["value"] < 10.0
    assert 0.0 < metrics["pair_rows_gathered_pct.burst"]["value"] <= 100.0
    (ticks,) = [ln for ln in lines if ln.get("line") == "ticks"]
    assert ticks["pool"][0] == 1600
    (dispatched,) = [ln for ln in lines if ln.get("line") == "dispatched"]
    assert [k["kernel"] for k in dispatched["kernels"]] == [
        "topk_candidates_big+pair_partners"]
    assert not dispatched["wrong"] and not dispatched["off_device"]
