"""Tickets of the `multiqueue8x20k` deployment: driver BASELINE.json
config 5, "multi-queue: 8 concurrent game-mode pools x 20k tickets,
shared TPU batch with per-pool masking", as the repo's own
`bench.py ticket_cfg5` reads it (copied, not imported): the pool
required as a string property (the reference server has one index and
separates queues by query terms), rank inside a window around the
ticket's own, two to a match. Where `ticket_cfg5` draws each ticket's
pool uniformly, every pool here holds exactly its share, in an order
drawn from the seed: the source fixes the sizes. Every number is a
small whole number, exact in float32.
"""

from __future__ import annotations

import numpy as np


def specs(seed, n: int, params: dict) -> list[dict]:
    """`n` tickets from `seed` (an int or a sequence of ints), `n` a
    multiple of the number of pools."""
    rng = np.random.default_rng(seed)
    pools = params["pools"]
    share, rest = divmod(n, len(pools))
    if rest:
        raise ValueError(f"{n} tickets do not split into {len(pools)} pools")
    pool = rng.permutation(np.repeat(np.arange(len(pools)), share))
    rank = rng.integers(0, params["rank_max"], size=n)
    w = params["rank_window"]
    lo, hi = np.maximum(0, rank - w), rank + w
    return [
        dict(
            query=(
                f"+properties.pool:{pools[pool[i]]}"
                f" +properties.rank:>={lo[i]}"
                f" +properties.rank:<={hi[i]}"
            ),
            min_count=params["min_count"],
            max_count=params["max_count"],
            strs={"pool": pools[pool[i]]},
            nums={"rank": float(rank[i])},
        )
        for i in range(n)
    ]
