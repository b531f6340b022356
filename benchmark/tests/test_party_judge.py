"""Party tickets in the plain reference and the judge.

A spec may carry `count` (players on the ticket) and `count_multiple`;
without them it is one player and multiple 1, as every recipe of
`recipes/` writes it. Here: the plain matcher's fill and trim by hand,
each party fault `match_fault` refuses with its note, the solo answers
frozen as the reference gave them before it knew parties, and a party
cell built from files alone in a temporary copy, sound and with a
party's envelope or member lost.
"""

import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from lib import reference
from lib.judge import delivered, judge

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
LIMITS = dict(yield_shortfall=0.02, similarity_shortfall=0.5)


def load_recipe(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "recipes", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------ solo invariance

# `solo_digest` of each configuration, `rev` off and on, as the
# reference and the judge computed it before they knew parties.
SOLO_DIGESTS = {
    "duel1k:0":
        "ac32a3e64044c2f7dfe477d1bd3092319f9ceaa8dba6141ce8061d2c1e36f53e",
    "duel1k:1":
        "ac32a3e64044c2f7dfe477d1bd3092319f9ceaa8dba6141ce8061d2c1e36f53e",
    "multiqueue8x20k:0":
        "b49044530c26e2038e44d4fc19b672068718b326895bf04840088593794b9fc5",
    "multiqueue8x20k:1":
        "b49044530c26e2038e44d4fc19b672068718b326895bf04840088593794b9fc5",
    "mutual100k:0":
        "1ba7955d393c083ec9e2fcaa006b348e7dbcb91d4c1951b6e729eaab90299685",
    "mutual100k:1":
        "34550153e88edff5256963ce74b23ebada880f289ff92bafb914c3952ca3b61c",
    "playlists100k:0":
        "cbc5c17ae928d2bd60602123ad52eb2d2b54d4b3c9ba68fd1189147af0e0978e",
    "playlists100k:1":
        "cbc5c17ae928d2bd60602123ad52eb2d2b54d4b3c9ba68fd1189147af0e0978e",
    "ranked100k:0":
        "272ef1118c306d0a8051f7fc937f3054a150a4a967ee773a97b9ec8b54b48c4b",
    "ranked100k:1":
        "272ef1118c306d0a8051f7fc937f3054a150a4a967ee773a97b9ec8b54b48c4b",
    "squad50k:0":
        "f8544f56d20ce85fe749070023d8da54065b826f1cfb52f1ef285f3125a2c541",
    "squad50k:1":
        "f8544f56d20ce85fe749070023d8da54065b826f1cfb52f1ef285f3125a2c541",
}


def solo_digest(name: str, rev: bool) -> str:
    """Everything the reference and the judge answer on one recipe's
    tickets at its configuration's rehearsal size: the plain matcher's
    matches and their similarity, and the judge's whole verdict on them,
    on the forward matcher's (invalid under `rev` where queries are
    one-sided) and on those with every fifth match cut by a member."""
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        config = json.load(f)
    recipe = load_recipe(config["recipe"])
    n = config["rehearse"]["tickets"]
    specs = recipe.specs([41, 0], n, config["recipe_params"])
    ack = np.sort(np.random.default_rng([41, 1]).random(n) * 3.0)
    ticks = [1.0, 2.0, 3.0, 4.0]
    k, mi = config["candidates_per_ticket"], config["max_intervals"]
    groups = reference.replay(specs, ack, ticks, k, rev, mi)
    fwd = reference.replay(specs, ack, ticks, k, False, mi) if rev else groups
    cut = [g[:-1] if i % 5 == 0 else g for i, g in enumerate(fwd)]
    out = [groups, reference.mean_pair_similarity(specs, groups)]
    for gs in (fwd, cut):
        sessions = delivered(specs, gs, ack)
        out.append(judge(sessions, rev, sessions[: n // 2], LIMITS, ticks,
                         k, mi))
    text = json.dumps(out, default=lambda o: o.item())
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("case", sorted(SOLO_DIGESTS))
def test_solo_answers_are_the_parents(case):
    name, rev = case.split(":")
    assert solo_digest(name, rev == "1") == SOLO_DIGESTS[case]


# ------------------------------------------------------ the plain matcher


def ticket(count=1, lo=2, hi=4, mult=1, query="*", strs=None):
    return dict(query=query, min_count=lo, max_count=hi, strs=strs or {},
                nums={}, count=count, count_multiple=mult)


def replay(specs, ticks=(1.0,), rev=False, max_intervals=2):
    """No embedding: every candidate list is oldest first."""
    return reference.replay(specs, np.arange(len(specs)) * 1e-3, ticks,
                            k=8, rev=rev, max_intervals=max_intervals)


def test_a_party_fits_or_is_skipped():
    specs = [ticket(3, 4, 4), ticket(2, 4, 4), ticket(1, 4, 4),
             ticket(2, 4, 4)]
    # 0 (three players) skips the pair 1 (five > 4) and takes 2; then 1
    # takes the pair 3.
    assert replay(specs) == [(2, 0), (3, 1)]


def test_a_trim_removes_the_oldest_exact_group():
    specs = [ticket(1, 2, 9, 3), ticket(2, 2, 9), ticket(1, 2, 9),
             ticket(1, 2, 9), ticket(3, 2, 9)]
    # 0 walks to 1 + 2 + 1 + 1 + 3 = 8 players on its last interval; 8 %
    # 3 leaves 2: the groups of exactly two are {1} (mean 1) and {2, 3}
    # (mean 2.5), so the party 1 goes and six players stay.
    assert replay(specs, max_intervals=1) == [(2, 3, 4, 0)]
    assert reference.oldest_group([1, 2, 3, 4], [1, 2, 1, 1, 3], 2) == {1}
    # among equal means the first listed wins, as groupIndexes lists them
    assert reference.oldest_group([1, 2, 3], [1, 1, 2, 1], 2) == {1, 3}


def test_an_impossible_trim_forms_no_match():
    specs = [ticket(1, 2, 6, 2), ticket(2, 2, 6, 2), ticket(2, 2, 6, 2)]
    # 0 reaches five players and no taken ticket is of one: no match;
    # then 1 reaches 2 + 1 + 2 = 5, drops the solo 0 and forms a four.
    assert replay(specs, max_intervals=1) == [(2, 1)]


def test_a_last_interval_fills_to_min_count():
    pairs = [ticket(2, 4, 6), ticket(2, 4, 6)]
    # four players: short of six on the first interval, enough on the
    # last (the second tick)
    assert replay(pairs, ticks=(1.0,)) == []
    assert replay(pairs, ticks=(1.0, 2.0)) == [(1, 0)]
    assert replay([ticket(2, 5, 6), ticket(2, 5, 6)],
                  ticks=(1.0, 2.0)) == []


def test_rev_both_ways():
    region = "+properties.region:"
    specs = [ticket(2, 3, 3, strs={"region": "eu"}),
             ticket(1, 3, 3, query=region + "us", strs={"region": "us"}),
             ticket(1, 3, 3, query=region + "eu", strs={"region": "eu"})]
    assert replay(specs) == [(1, 0)]  # 0's wildcard takes the oldest
    assert replay(specs, rev=True) == [(2, 0)]  # 1 refuses 0 back


def test_solo_specs_mean_what_they_meant():
    solo = dict(query="*", min_count=2, max_count=2, strs={}, nums={})
    spelt = dict(solo, count=1, count_multiple=1)
    assert replay([solo] * 5) == replay([spelt] * 5) == [(1, 0), (3, 2)]
    with pytest.raises(ValueError):
        replay([dict(solo, count=3)] * 2)  # more players than max_count


# ---------------------------------------------------------- match_fault


def players(t, spec, n=None, strs=None):
    """`n` of the players of ticket `t` (all by default)."""
    n = spec["count"] if n is None else n
    return [dict(session=f"{t}.{i}", ticket=t, query=spec["query"],
                 min_count=spec["min_count"], max_count=spec["max_count"],
                 count=spec["count"], count_multiple=spec["count_multiple"],
                 strs=strs or spec["strs"], nums={}) for i in range(n)]


@pytest.mark.parametrize("members,note", [
    (players("a", ticket(3, 2, 6)) + players("b", ticket(3, 2, 6), 2),
     "ticket b split: 2 of its 3 players"),
    (players("a", ticket(2, 2, 6, 2)) + players("b", ticket(2, 2, 6, 2))
     + players("c", ticket(1, 2, 6, 2)), "size 5 not a multiple of 2"),
    (players("a", ticket(2, 2, 6)) + players("b", ticket(3, 2, 4)),
     "size 5 outside [2, 4]"),
    (players("a", ticket(2, 4, 4, query="+properties.region:eu",
                         strs={"region": "eu"}))
     + players("b", ticket(2, 4, 4, query="+properties.region:us",
                           strs={"region": "us"})),
     "no member's query accepts the others"),
])
def test_each_party_fault_reads_invalid(members, note):
    assert note in reference.match_fault(members, rev=False)


def test_a_party_is_not_held_to_its_own_query():
    # a's query refuses a's own players and accepts b's; b refuses a, so
    # only a can have searched: valid forward, and under rev only where
    # b accepts a back
    a = ticket(2, 3, 3, query="+properties.mode:ranked",
               strs={"mode": "casual"})
    b = ticket(1, 3, 3, query="+properties.mode:ranked",
               strs={"mode": "ranked"})
    members = players("a", a) + players("b", b)
    assert reference.match_fault(members, rev=False) is None
    assert "no member" in reference.match_fault(members, rev=True)
    anyone = players("a", a) + players("b", dict(b, query="*"))
    assert reference.match_fault(anyone, rev=True) is None


# ------------------------------------------------------------- the judge


def party_pool(seed, n):
    """Parties of one to five, count_multiple 2, six to ten players, a
    unit embedding of four normals."""
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n, 4)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    return [dict(ticket(int(c), 6, 10, 2), emb=e)
            for c, e in zip(rng.integers(1, 6, n), emb)]


def checks(sessions, specs, ticks, rev=False):
    v = judge(sessions, rev, sessions, LIMITS, ticks, 64)
    return {c["name"]: c["value"] for c in v["checks"]}, v


@pytest.mark.parametrize("seed", [1, 2, 4000000007])
def test_plain_party_matches_pass_and_faults_fail(seed):
    specs = party_pool(seed, 300)
    ack, ticks = np.arange(300) * 1e-3, [1.0, 2.0]
    groups = reference.replay(specs, ack, ticks, 64, False)
    assert groups
    sessions = delivered(specs, groups, ack)
    assert len(sessions) == sum(s["count"] for s in specs)
    got, v = checks(sessions, specs, ticks)
    assert got["invalid_matches"] == got["envelope_errors"] == 0, v
    assert got["yield_shortfall"] == 0
    assert abs(got["similarity_shortfall"]) < 1e-12  # the sum's order
    assert v["eligible"] == len(sessions)  # players, not tickets
    assert v["plain_matches"] == len(groups)
    # the plain matcher's every match holds whole parties, a multiple of 2
    for g in groups:
        size = sum(specs[i]["count"] for i in g)
        assert 6 <= size <= 10 and size % 2 == 0
    party = next(g for g in groups if specs[g[0]]["count"] > 1)

    # one party player's envelope lost
    lost = delivered(specs, groups, ack)
    member = next(s for s in lost if s.ticket == f"t{party[0]}")
    member.matched.clear()
    got, _ = checks(lost, specs, ticks)
    assert got["envelope_errors"] == 1

    # a party split: one of its players neither listed nor sent
    cut = delivered(specs, groups, ack)
    member = next(s for s in cut if s.ticket == f"t{party[0]}")
    token = member.matched[0]["token"]
    for s in cut:
        for body in s.matched:
            if body["token"] == token:
                body["users"] = [u for u in body["users"]
                                 if u["presence"]["session_id"] != member.id]
    member.matched.clear()
    got, v = checks(cut, specs, ticks)
    assert got["invalid_matches"] >= 1 and "split" in " ".join(v["notes"])

    # a party matched alone has no pair of players of different tickets
    assert reference.mean_pair_similarity(specs, [(party[0],)]) is None


# ------------------------------------------- a party cell from files alone

PARTY_RECIPE = '''"""Parties of one to five players, count_multiple 2."""

import numpy as np


def specs(seed, n, params):
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n, params["embedding_dims"]))
    emb = emb.astype(np.float32)
    emb /= np.maximum(1e-6, np.linalg.norm(emb, axis=1, keepdims=True))
    count = rng.choice(params["sizes"], size=n, p=params["shares"])
    return [dict(query="*", min_count=params["min_count"],
                 max_count=params["max_count"], count=int(count[i]),
                 count_multiple=params["count_multiple"], strs={}, nums={},
                 emb=emb[i]) for i in range(n)]
'''

# Runs the copy's run.py with one fault planted in the window.
PARTY_SABOTAGE = '''import sys

sys.path.insert(0, sys.argv[1])
import run  # noqa: E402


def in_window(ctx, broken, sound):
    def call(*a, **kw):
        return (broken if ctx.t0 is not None else sound)(*a, **kw)
    return call


def dropped(ctx):
    """One party player's envelope lost on the way to its session."""
    router = ctx.server.router
    send = router.send_envelopes
    done = []

    def drop(recipients):
        tickets = [env["matchmaker_matched"]["ticket"]
                   for _, _, env in recipients]
        party = [i for i, t in enumerate(tickets) if tickets.count(t) > 1]
        if party and not done:
            done.append(recipients[party[-1]][1])
            recipients = [r for i, r in enumerate(recipients)
                          if i != party[-1]]
        send(recipients)

    router.send_envelopes = in_window(ctx, drop, send)


def split(ctx):
    """One party player left out of its match where it is formed."""
    publish = ctx.mm.on_matched

    def cut(batch):
        batch = [list(entries) for entries in batch]
        for entries in batch:
            tickets = [e.ticket for e in entries]
            party = [i for i, t in enumerate(tickets) if tickets.count(t) > 1]
            if party:
                del entries[party[-1]]
                break
        publish(batch)

    ctx.mm.on_matched = in_window(ctx, cut, publish)


sys.exit(run.main(sys.argv[3:], sabotage=globals()[sys.argv[2]]))
'''


def party_copy(tmp_path):
    """The benchmark copied, with a party configuration, its recipe and
    its burst cell added as files and entries."""
    bench_dir = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    config = json.loads((bench_dir / "configs" / "ranked100k.json").read_text())
    config.update(name="party_test", recipe="party_test", recipe_params=dict(
        embedding_dims=16, sizes=[1, 2, 3, 4, 5],
        shares=[0.4, 0.2, 0.2, 0.1, 0.1], min_count=6, max_count=10,
        count_multiple=2))
    (bench_dir / "configs" / "party_test.json").write_text(json.dumps(config))
    (bench_dir / "recipes" / "party_test.py").write_text(PARTY_RECIPE)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "party_test.burst"
    bench["configs"].append({
        "name": "party_test", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/party_test.json"})
    bench["workloads"].append({
        "name": cell, "config": "party_test", "traffic": "burst",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "ranked100k.burst" in m.get("workloads", [cell]):
            m.setdefault("workloads", []).append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "sabotage.py").write_text(PARTY_SABOTAGE)
    return cell


def test_party_cell_from_files_alone(tmp_path):
    cell = party_copy(tmp_path)
    argv = ["--workload", cell, "--seed", "4000000041", "--seconds", "6",
            "--trace", "0", "--rehearse", "1"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    lines = {}
    for fault in ("sound", "dropped", "split"):
        cmd = [sys.executable, str(tmp_path / "benchmark" / "run.py")] \
            if fault == "sound" else [
                sys.executable, str(tmp_path / "sabotage.py"),
                str(tmp_path / "benchmark"), fault]
        out = subprocess.run(cmd + argv, env=env, cwd=tmp_path,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        lines[fault] = json.loads(out.stdout.strip().splitlines()[-1])
        judged = [json.loads(x) for x in out.stdout.splitlines()
                  if x.startswith('{"line": "judge"')]
        lines[fault]["judge"] = judged[0]
    sound = lines["sound"]
    assert sound["correct"] is True, sound
    assert sound["judge"]["matched_entries"] > sound["judge"]["matches"] * 5
    assert lines["dropped"]["correct"] is False
    assert lines["dropped"]["checks"]["envelope_errors"]["value"] == 1
    assert lines["split"]["correct"] is False
    assert lines["split"]["checks"]["invalid_matches"]["value"] >= 1
    assert any("split" in n for n in lines["split"]["judge"]["notes"])
