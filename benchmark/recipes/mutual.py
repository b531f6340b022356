"""Tickets of the `mutual100k` deployment: the pool of driver
BASELINE.json config 3 ("100k tickets, 16-dim learned skill embedding,
min=max=10") in a queue whose operator has set upstream's
`matchmaker.rev_precision` (server/config.go:971-989): clients write
their own queries, some strict ("my region only"), the others not
("anyone"), and a match holds only members that accept each other both
ways. The sources name no property, so the whole mix is assumed (the
configuration file says so): every ticket carries the string property
`region`, drawn by `region_shares`; a `strict_share` of them, drawn
independently of the region, send `+properties.region:<their own>`, the
others `*`; a unit embedding of independent normals as `recipes/ranked.py`
draws it; ten to a match; no numeric property.
"""

from __future__ import annotations

import numpy as np


def specs(seed, n: int, params: dict) -> list[dict]:
    """`n` tickets from `seed` (an int or a sequence of ints)."""
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n, params["embedding_dims"])).astype(np.float32)
    emb /= np.maximum(1e-6, np.linalg.norm(emb, axis=1, keepdims=True))
    regions = params["regions"]
    region = rng.choice(len(regions), size=n, p=params["region_shares"])
    strict = rng.random(n) < params["strict_share"]
    size = params["match_size"]
    return [
        dict(
            query=f"+properties.region:{regions[region[i]]}"
            if strict[i] else "*",
            min_count=size, max_count=size,
            strs={"region": regions[region[i]]}, nums={}, emb=emb[i],
        )
        for i in range(n)
    ]
