"""Tickets of the `squad50k` deployment: driver BASELINE.json config 2,
"50k tickets, 8 numeric + 4 string props, min=3 max=4 (squad fill)", as
the repo's own `bench.py ticket_cfg2` reads it (copied, not imported):
game mode and region required as strings, rank inside a window around
the ticket's own, and eight more properties that no query asks for
(level, six further numerics, platform, input device) but every envelope
carries. Every number is a small whole number, exact in float32.
"""

from __future__ import annotations

import numpy as np


def specs(seed, n: int, params: dict) -> list[dict]:
    """`n` tickets from `seed` (an int or a sequence of ints)."""
    rng = np.random.default_rng(seed)
    modes, regions = params["modes"], params["regions"]
    platforms, inputs = params["platforms"], params["inputs"]
    mode = rng.integers(0, len(modes), size=n)
    region = rng.integers(0, len(regions), size=n)
    platform = rng.integers(0, len(platforms), size=n)
    device = rng.integers(0, len(inputs), size=n)
    rank = rng.integers(0, params["rank_max"], size=n)
    level = rng.integers(*params["level_range"], size=n)
    extra = rng.integers(0, params["extra_max"],
                         size=(n, params["extra_numerics"]))
    w = params["rank_window"]
    lo, hi = np.maximum(0, rank - w), rank + w
    return [
        dict(
            query=(
                f"+properties.mode:{modes[mode[i]]}"
                f" +properties.region:{regions[region[i]]}"
                f" +properties.rank:>={lo[i]}"
                f" +properties.rank:<={hi[i]}"
            ),
            min_count=params["min_count"],
            max_count=params["max_count"],
            strs={
                "mode": modes[mode[i]],
                "region": regions[region[i]],
                "platform": platforms[platform[i]],
                "input": inputs[device[i]],
            },
            nums={
                **{f"n{j}": float(extra[i, j])
                   for j in range(extra.shape[1])},
                "rank": float(rank[i]),
                "level": float(level[i]),
            },
        )
        for i in range(n)
    ]
