#!/usr/bin/env python3
"""The party controls, on the chip, at a cell's own size:

    python3 benchmark/scripts/control_party.py <cell> <seed> [<seed> ...]
        [--rehearse 1]

For a cell whose tickets are parties (specs with `count` and
`count_multiple`, `recipes/party.py`), each seed's window tickets are
drawn as the cell's burst draws them (`[seed, 0]`, every one
acknowledged before the first tick, a tick every `interval_sec` over
`run_seconds`), matched by the plain matcher with a shortcut put in the
program's place, and judged as the cell's runs are, against the cell's
limits:

  reference  the plain matcher: has to pass
  bf16       embeddings rounded to bfloat16 (`control.py`'s; the
             configuration states no precision for the similarity)
  split      each party's players fed as solo tickets, then handed back
             to the party's ticket: has to fail `invalid_matches` with
             "split" notes
  nomult     `count_multiple` taken as 1: has to fail `invalid_matches`
             on sizes off the multiple
  kcut       the candidate search cut to a quarter of
             candidates_per_ticket (`control.py`'s): the yield
  noemb      the embedding ignored (`control.py`'s): the yield and the
             similarity

`--rehearse 1` draws the configuration's rehearsal size and judges
against the traffic's rehearsal limits (a CPU check of the script). One
JSON line per seed and way, in `control.py`'s form. The benchmark's own
runs never run this.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

from lib import harness, reference  # noqa: E402
from lib.judge import delivered, judge  # noqa: E402


def window_tickets(bench, config, rehearse: bool, seed: int):
    """(specs, ack_t, ticks) of one burst window: the pool waits, the
    interval loop ticks from 1 s on."""
    n = config["rehearse"]["tickets"] if rehearse else config["tickets"]
    specs = harness.load_module("recipes", config["recipe"]).specs(
        [seed, 0], n, config["recipe_params"])
    interval = config["overrides"]["matchmaker.interval_sec"]
    ticks = list(np.arange(1.0, bench["run_seconds"], interval))
    return specs, np.arange(n) * 1e-6, ticks


def split(specs, ack, ticks, k, rev, mi):
    """Every player a solo ticket of its party's spec; the sessions then
    hold the party's ticket, as the program's clients would."""
    owner = [i for i, s in enumerate(specs) for _ in range(s["count"])]
    solo = [dict(specs[i], count=1) for i in owner]
    groups = reference.replay(solo, ack[owner], ticks, k, rev, mi)
    sessions = delivered(solo, groups, ack[owner])
    for s, i in zip(sessions, owner):
        s.spec, s.ticket = specs[i], f"t{i}"
        for body in s.matched:
            body["ticket"] = s.ticket
    return sessions, groups


def main(argv) -> int:
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[args.cell]
    config = harness.load_json("configs", f"{cell['config']}.json")
    traffic = harness.load_json("traffic", f"{cell['traffic']}.json")
    limits = (traffic["rehearse"] if args.rehearse else traffic)["limits"]
    rev = bool(config["overrides"]["matchmaker.rev_precision"])
    k, mi = config["candidates_per_ticket"], config["max_intervals"]
    d = jax.devices()[0]
    for seed in args.seeds:
        specs, ack, ticks = window_tickets(bench, config, args.rehearse,
                                           seed)
        if all(s.get("count", 1) == 1 for s in specs):
            raise SystemExit(f"{args.cell} has no party tickets")
        for way in ("reference", "bf16", "split", "nomult", "kcut",
                    "noemb"):
            t = time.perf_counter()
            if way == "split":
                sessions, groups = split(specs, ack, ticks, k, rev, mi)
            else:
                fed = [dict(s, count_multiple=1) for s in specs] \
                    if way == "nomult" else specs
                groups = reference.replay(
                    fed, ack, ticks, k // 4 if way == "kcut" else k, rev, mi,
                    precision="bfloat16" if way == "bf16" else "float32",
                    use_emb=way != "noemb")
                sessions = delivered(specs, groups, ack)
            v = judge(sessions, rev, sessions, limits, ticks, k, mi)
            print(json.dumps({
                "cell": args.cell, "seed": seed, "way": way,
                "tickets": len(specs), "players": len(sessions),
                "platform": d.platform, "kind": d.device_kind,
                "correct": all(c["ok"] for c in v["checks"]),
                "checks": {c["name"]: [c["value"], c["limit"]]
                           for c in v["checks"]},
                "matches": len(groups), "notes": v["notes"][:3],
                "seconds": round(time.perf_counter() - t, 1),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
