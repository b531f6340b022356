#!/usr/bin/env python3
"""The controls, on the chip, at a cell's own size:

    python3 benchmark/scripts/control.py <cell> <seed> [<seed> ...]

For each seed the window's own tickets and arrival times (the traffic
kind's seed derivation) are matched by the plain matcher put in the
program's place (`reference.replay`), once as it is and once for each
control, and each result goes through the judge:

  reference  the plain matcher: has to pass
  bf16       numeric comparisons and embeddings rounded to bfloat16
  kcut       the candidate search cut to a quarter of the
             configuration's candidates_per_ticket (1v1: to one)
  noemb      the embedding ignored (cells whose tickets carry one)

One JSON line per seed and way. The benchmark's own runs never run this.
"""

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
from lib.judge import delivered, judge, players  # noqa: E402


def window_tickets(bench, config, traffic, recipe, seed):
    """(specs, ack_t, ticks, eligible indices) of one window, as
    traffic_kinds/<kind>.py draws them."""
    params = config["recipe_params"]
    interval = config["overrides"]["matchmaker.interval_sec"]
    seconds = bench["run_seconds"]
    if traffic["kind"] == "burst":
        n = config["tickets"]
        specs = recipe.specs([seed, 0], n, params)
        return specs, np.arange(n) * 1e-6, [1.0], range(n)
    kind = harness.load_module("traffic_kinds", traffic["kind"])
    ack = kind.schedule(seed, 0, traffic["adds_per_s"], seconds)
    specs = recipe.specs([seed, 4], len(ack), params)
    ticks = np.arange(kind.PHASE * interval, seconds, interval)
    grace = traffic["grace_intervals"] * interval
    return specs, ack, list(ticks), np.flatnonzero(ack <= seconds - grace)


def main(argv) -> int:
    import jax

    cell_name, seeds = argv[0], [int(s) for s in argv[1:]]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[cell_name]
    config = harness.load_json("configs", f"{cell['config']}.json")
    traffic = harness.load_json("traffic", f"{cell['traffic']}.json")
    recipe = harness.load_module("recipes", config["recipe"])
    rev = bool(config["overrides"]["matchmaker.rev_precision"])
    k, mi = config["candidates_per_ticket"], config["max_intervals"]
    d = jax.devices()[0]
    for seed in seeds:
        specs, ack, ticks, eligible = window_tickets(
            bench, config, traffic, recipe, seed)
        pairs_only = all(s["max_count"] == 2 for s in specs)
        ways = [("reference", {}), ("bf16", {"precision": "bfloat16"}),
                ("kcut", {"k": 1 if pairs_only else k // 4})]
        if reference.has_embeddings(specs):
            ways.append(("noemb", {"use_emb": False}))
        for way, kw in ways:
            t = time.perf_counter()
            groups = reference.replay(
                specs, ack, ticks, kw.pop("k", k), rev, mi, **kw)
            sessions = delivered(specs, groups, ack)
            v = judge(sessions, rev, players(sessions, eligible),
                      traffic["limits"], ticks, k, mi)
            print(json.dumps({
                "cell": cell_name, "seed": seed, "way": way,
                "tickets": len(specs), "platform": d.platform,
                "kind": d.device_kind,
                "correct": all(c["ok"] for c in v["checks"]),
                "checks": {c["name"]: [c["value"], c["limit"]]
                           for c in v["checks"]},
                "matches": len(groups),
                "seconds": round(time.perf_counter() - t, 1),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
