#!/usr/bin/env python3
"""The yield a burst cell's tickets could have: the reference server's
unbounded walk, on the host, by hand:

    python3 benchmark/scripts/walk_control.py <cell> <seed> [<seed> ...]

The reference server hands a searcher EVERY ticket its query accepts,
oldest first (SURVEY 2.5: TopN over the whole pool), where the plain
matcher the judge runs (`reference.replay`) cuts each list to the
configuration's `candidates_per_ticket` before it walks. On tickets
with an embedding the cut keeps the most similar and costs little; on
tickets without one every searcher of a partition lists the same oldest
tickets, and the plain matcher starves. This walks the cell's own
tickets (the burst's seed derivation) with no cut, over as many ticks
as a ticket searches, and prints per seed the tickets matched on each
tick beside the plain matcher's at the configuration's `k`.

numpy for the walk, whatever JAX has for `replay`. The benchmark's own
runs never run this.
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

_BLOCK = 1024


def walk(specs, n_ticks: int, max_intervals: int) -> list[int]:
    """Tickets matched on each of `n_ticks` ticks, all `specs` (oldest
    first) in the pool from the first: `replay`'s loop, a searcher's
    list being every free ticket it accepts."""
    enc = reference.encode(specs)
    n = len(specs)
    min_c, max_c = enc["min_c"], enc["max_c"]
    free = np.ones(n, bool)
    intervals = np.zeros(n, np.int32)
    searching = np.arange(n)
    matched = []
    for _ in range(n_ticks):
        before = int(free.sum())
        actives = searching[free[searching]]
        again = []
        for lo in range(0, len(actives), _BLOCK):
            rows = actives[lo:lo + _BLOCK]
            ok = reference._accept(
                {key: v[rows] for key, v in enc.items()}, enc, np.float32)
            ok &= min_c[None, :] >= min_c[rows][:, None]
            ok &= max_c[None, :] <= max_c[rows][:, None]
            for a, ok_a in zip(rows.tolist(), ok):
                if not free[a]:
                    continue
                intervals[a] += 1
                last = intervals[a] >= max_intervals or min_c[a] == max_c[a]
                if not last:
                    again.append(a)
                ok_a[a] = False
                got = np.flatnonzero(ok_a & free)[:max_c[a] - 1]
                size = len(got) + 1
                if size < 2 or not (
                    size == max_c[a] or (last and size >= min_c[a])
                ) or np.any((min_c[got] > size) | (max_c[got] < size)):
                    continue
                free[got] = False
                free[a] = False
        searching = np.asarray(again, np.int64)
        matched.append(before - int(free.sum()))
    return matched


def main(argv) -> int:
    cell_name, seeds = argv[0], [int(s) for s in argv[1:]]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[cell_name]
    config = harness.load_json("configs", f"{cell['config']}.json")
    traffic = harness.load_json("traffic", f"{cell['traffic']}.json")
    if traffic["kind"] != "burst":
        raise SystemExit("walk_control.py reads burst cells")
    recipe = harness.load_module("recipes", config["recipe"])
    rev = bool(config["overrides"]["matchmaker.rev_precision"])
    if rev:
        raise SystemExit("the walk restates rev_precision off only")
    k, mi = config["candidates_per_ticket"], config["max_intervals"]
    n = config["tickets"]
    ack = np.arange(n) * 1e-6
    for seed in seeds:
        specs = recipe.specs([seed, 0], n, config["recipe_params"])
        t = time.perf_counter()
        walked = walk(specs, mi, mi)
        walk_s = time.perf_counter() - t
        t = time.perf_counter()
        plain, so_far = [], 0
        for ticks in range(1, mi + 1):
            groups = reference.replay(
                specs, ack, [float(i + 1) for i in range(ticks)], k, rev, mi)
            total = sum(len(g) for g in groups)
            plain.append(total - so_far)
            so_far = total
        print(json.dumps({
            "cell": cell_name, "seed": seed, "tickets": n,
            "walk_matched_by_tick": walked, "walk_matched": sum(walked),
            f"plain_k{k}_matched_by_tick": plain,
            f"plain_k{k}_matched": sum(plain),
            "walk_seconds": round(walk_s, 1),
            "plain_seconds": round(time.perf_counter() - t, 1),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
