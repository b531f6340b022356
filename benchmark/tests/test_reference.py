"""The plain reference against itself computed the slow way, and the
CONTROLS: the plain matcher with a shortcut taken must come out as not
correct by the judge, on three seeds, in both configurations' shapes."""

import importlib.util
import os

import numpy as np
import pytest

from lib import reference
from lib.judge import delivered, judge

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def recipe(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "recipes", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DUEL = dict(rank_mean_std=[1500, 300], rank_max=3000, rank_window=100,
            regions=[[1, 0.5], [2, 0.3], [3, 0.2]])
TEAMS = dict(embedding_dims=16, match_size=10)
LIMITS = dict(yield_shortfall=0.02, similarity_shortfall=0.5)


def test_grammar():
    q = "+properties.mode:ranked +properties.rank:>=1400 +properties.rank:<=1600"
    terms = reference.parse(q)
    assert terms == [("mode", "str", "ranked"), ("rank", ">=", 1400.0),
                     ("rank", "<=", 1600.0)]
    assert reference.accepts(terms, {"mode": "ranked"}, {"rank": 1600.0})
    assert not reference.accepts(terms, {"mode": "ranked"}, {"rank": 1601.0})
    assert not reference.accepts(terms, {"mode": "casual"}, {"rank": 1500.0})
    assert not reference.accepts(terms, {"mode": "ranked"}, {})
    eq = reference.parse("+properties.region:2")
    assert eq == [("region", "==", 2.0)]
    assert reference.accepts(eq, {}, {"region": 2.0})
    assert not reference.accepts(eq, {"region": "2"}, {})
    assert reference.parse("*") == [] and reference.accepts([], {}, {})
    for bad in ("* +properties.a:1", "properties.mode:x",
                "+properties.rank:>1", ""):
        with pytest.raises(ValueError):
            reference.parse(bad)


def member(i, query, lo, hi, strs, nums):
    return dict(session=f"s{i}", query=query, min_count=lo, max_count=hi,
                strs=strs, nums=nums)


def test_match_fault():
    a = member(1, "+properties.rank:>=10 +properties.rank:<=20", 2, 2, {},
               {"rank": 15.0})
    b = member(2, "+properties.rank:>=100", 2, 2, {}, {"rank": 18.0})
    assert reference.match_fault([a, b], rev=False) is None  # a searched
    assert "no member" in reference.match_fault([a, b], rev=True)
    c = member(3, "+properties.rank:>=0", 2, 4, {}, {"rank": 500.0})
    assert "size 3" in reference.match_fault([a, b, c], rev=False)
    assert "twice" in reference.match_fault([a, dict(a)], rev=False)
    assert "no member" in reference.match_fault(
        [a, member(4, "+properties.rank:>=100", 2, 2, {}, {"rank": 21.0})],
        rev=False)
    team = [member(i, "*", 10, 10, {}, {}) for i in range(10)]
    assert reference.match_fault(team, rev=False) is None
    assert "size 9" in reference.match_fault(team[:9], rev=False)


@pytest.mark.parametrize("rev", [False, True])
def test_candidates_equal_the_slow_way(rev):
    specs = recipe("duel").specs(3, 300, DUEL)
    specs += recipe("duel").unmatchable(4, 5, DUEL)
    for s, e in zip(specs, np.random.default_rng(5).normal(size=(305, 4))):
        s["emb"] = e.astype(np.float32)
    in_pool = np.random.default_rng(6).random(305) < 0.8
    rows = np.arange(0, 305, 3, dtype=np.int32)
    got = reference.Candidates(reference.encode(specs), 4, rev).top(
        rows, in_pool)
    terms = [reference.parse(s["query"]) for s in specs]

    def ok(i, j):
        return reference.accepts(terms[i], specs[j]["strs"], specs[j]["nums"])

    for i, row in zip(rows, got):
        want = [
            j for j in range(305) if j != i and in_pool[j] and ok(i, j)
            and (not rev or ok(j, i))
        ]
        want.sort(key=lambda j: (-float(np.dot(
            specs[i]["emb"].astype(np.float64), specs[j]["emb"])), j))
        assert [j for j in row if j >= 0] == want[:4], i


def test_replay_by_hand():
    """Three ticks of a 1v1 queue any two tickets of which match: the
    tickets acknowledged before a tick pair off oldest first, and an odd
    one out waits, passive, for the next tick's searchers."""
    spec = dict(query="+properties.rank:>=0", min_count=2, max_count=2,
                strs={}, nums={"rank": 1.0})
    ack = [0.1, 0.2, 0.3, 1.1, 1.2, 2.5]
    groups = reference.replay([dict(spec) for _ in ack], ack, [1.0, 2.0, 3.0],
                              k=8, rev=False)
    assert groups == [(1, 0), (2, 3), (4, 5)]  # 2 and 4 waited, passive
    teams = [dict(query="*", min_count=3, max_count=3, strs={}, nums={},
                  emb=np.float32([1, 0]) if i % 2 else np.float32([0, 1]))
             for i in range(7)]
    groups = reference.replay(teams, np.arange(7) * 0.01, [1.0], k=8,
                              rev=False)
    assert groups == [(2, 4, 0), (3, 5, 1)]  # like with like; 6 is left
    assert reference.mean_pair_similarity(teams, groups) == 1.0


def verdict(specs, groups, ack, ticks, k, eligible=None):
    sessions = delivered(specs, groups, ack)
    v = judge(sessions, False, eligible or sessions, LIMITS, ticks, k)
    return {c["name"]: c for c in v["checks"]}


@pytest.mark.parametrize("seed", [1, 2, 4000000007])
def test_duel_plain_matcher_passes_and_controls_fail(seed):
    specs = recipe("duel").specs(seed, 1500, DUEL)
    ack = np.sort(np.random.default_rng(seed).random(1500) * 3.0)
    ticks = [1.0, 2.0, 3.0, 4.0]

    def way(**kw):
        groups = reference.replay(specs, ack, ticks, kw.pop("k", 64), False,
                                  **kw)
        return verdict(specs, groups, ack, ticks, 64)

    good = way()
    assert all(c["ok"] for c in good.values()), good
    assert good["yield_shortfall"]["value"] == 0
    bf16 = way(precision="bfloat16")
    assert bf16["invalid_matches"]["value"] > 0
    k1 = way(k=1)
    assert k1["yield_shortfall"]["value"] > 3 * LIMITS["yield_shortfall"]
    assert k1["invalid_matches"]["ok"]


@pytest.mark.parametrize("seed", [1, 2, 4000000007])
def test_teams_plain_matcher_passes_and_controls_fail(seed):
    specs = recipe("ranked").specs(seed, 3000, TEAMS)
    ack, ticks = np.arange(3000) * 1e-6, [1.0]

    def way(**kw):
        groups = reference.replay(specs, ack, ticks, kw.pop("k", 64), False,
                                  **kw)
        return verdict(specs, groups, ack, ticks, 64)

    good = way()
    assert all(c["ok"] for c in good.values()), good
    noemb = way(use_emb=False)
    assert noemb["similarity_shortfall"]["value"] > 0.9
    assert not noemb["similarity_shortfall"]["ok"]
    kcut = way(k=16)
    assert kcut["yield_shortfall"]["value"] > 3 * LIMITS["yield_shortfall"]


def test_judge_counts_each_kind_of_fault():
    specs = recipe("duel").specs(9, 400, DUEL)
    ack, ticks = np.arange(400) * 1e-3, [1.0]
    pairs = reference.replay(specs, ack, ticks, 64, False)
    # half of the matches left out: the yield falls short
    v = verdict(specs, pairs[: len(pairs) // 2], ack, ticks, 64)
    assert not v["yield_shortfall"]["ok"]
    assert v["invalid_matches"]["ok"] and v["envelope_errors"]["ok"]
    # a ticket in two matches
    (a, b), (c, d) = pairs[0], pairs[1]
    v = verdict(specs, pairs + [(a, c)], ack, ticks, 64)
    assert v["in_two_matches"]["value"] == 2
    # one member's envelope lost; another's delivered twice
    sessions = delivered(specs, pairs, ack)
    sessions[a].matched.clear()
    sessions[c].matched.append(sessions[c].matched[0])
    lim = dict(LIMITS, yield_shortfall=1.0)
    v = {x["name"]: x
         for x in judge(sessions, False, sessions, lim, ticks, 64)["checks"]}
    assert v["envelope_errors"]["value"] == 2
    # properties altered on the way in
    sessions = delivered(specs, pairs, ack)
    sessions[b].spec = dict(sessions[b].spec, nums={"rank": -1.0})
    v = {x["name"]: x
         for x in judge(sessions, False, sessions, lim, ticks, 64)["checks"]}
    assert v["ingest_mismatch"]["value"] == 1
