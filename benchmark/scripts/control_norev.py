#!/usr/bin/env python3
"""The forward-only control, on the chip, at a cell's own size:

    python3 benchmark/scripts/control_norev.py <cell> <seed> [<seed> ...]

For a cell whose configuration sets `matchmaker.rev_precision`: the
window's own tickets (`control.py`'s derivation, imported, not copied)
are matched by the plain matcher with the reverse check left out
(`reference.replay(..., rev=False)`), put in the program's place, and
judged as the cell's runs are, with `rev` on. The shortcut a later
change would be tempted by (one matmul a tile less, no query mirrors in
stage 2, no pair checks in the walk) has to come out as not correct, by
`invalid_matches`. One JSON line per seed, in `control.py`'s form. The
benchmark's own runs never run this.
"""

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

from lib import harness, reference  # noqa: E402
from lib.judge import delivered, judge, players  # noqa: E402


def main(argv) -> int:
    import jax

    control = harness.load_module("scripts", "control")
    cell_name, seeds = argv[0], [int(s) for s in argv[1:]]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[cell_name]
    config = harness.load_json("configs", f"{cell['config']}.json")
    traffic = harness.load_json("traffic", f"{cell['traffic']}.json")
    recipe = harness.load_module("recipes", config["recipe"])
    if not config["overrides"]["matchmaker.rev_precision"]:
        raise SystemExit(f"{cell_name} does not set rev_precision")
    k, mi = config["candidates_per_ticket"], config["max_intervals"]
    d = jax.devices()[0]
    for seed in seeds:
        specs, ack, ticks, eligible = control.window_tickets(
            bench, config, traffic, recipe, seed)
        t = time.perf_counter()
        groups = reference.replay(specs, ack, ticks, k, False, mi)
        sessions = delivered(specs, groups, ack)
        v = judge(sessions, True, players(sessions, eligible),
                  traffic["limits"], ticks, k, mi)
        print(json.dumps({
            "cell": cell_name, "seed": seed, "way": "norev",
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
