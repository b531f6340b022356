"""Tickets of the `duel1k` deployment: BASELINE config 1, the reference
harness's 1v1 queue (`server/matchmaker_test.go:2444`): two numeric
properties, rank and region, min = max = 2. The query form is
`bench.py ticket_cfg1`'s, copied.

`unmatchable` tickets require a property no ticket carries: the
reference harness keeps 100 of them in the pool beside its 1,000.
"""

from __future__ import annotations

import numpy as np


def specs(seed, n: int, params: dict) -> list[dict]:
    """`n` tickets from `seed` (an int or a sequence of ints)."""
    rng = np.random.default_rng(seed)
    mean, std = params["rank_mean_std"]
    rank = np.clip(rng.normal(mean, std, size=n), 0, params["rank_max"])
    rank = rank.astype(int)
    regions = params["regions"]
    region = rng.choice(
        [r for r, _ in regions], size=n, p=[p for _, p in regions]
    )
    w = params["rank_window"]
    return [
        dict(
            query=(
                f"+properties.region:{region[i]}"
                f" +properties.rank:>={max(0, rank[i] - w)}"
                f" +properties.rank:<={rank[i] + w}"
            ),
            min_count=2,
            max_count=2,
            strs={},
            nums={"rank": float(rank[i]), "region": float(region[i])},
        )
        for i in range(n)
    ]


def unmatchable(seed, n: int, params: dict) -> list[dict]:
    out = specs(seed, n, params)
    for s in out:
        s["query"] += " +properties.never:1"
    return out
