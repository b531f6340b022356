"""Tickets of the `ranked100k` deployment: driver BASELINE.json config 3,
"100k tickets, 16-dim learned skill embedding, min=max=10 (5v5 team
balance)", as the repo's own `bench.py ticket_cfg3` reads it (copied,
not imported): the wildcard query, so that every ticket is eligible for
every other and the skill embedding alone orders the candidates; no
properties; a unit embedding of independent normals; ten to a match.
"""

from __future__ import annotations

import numpy as np


def specs(seed, n: int, params: dict) -> list[dict]:
    """`n` tickets from `seed` (an int or a sequence of ints)."""
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n, params["embedding_dims"])).astype(np.float32)
    emb /= np.maximum(1e-6, np.linalg.norm(emb, axis=1, keepdims=True))
    size = params["match_size"]
    return [
        dict(query="*", min_count=size, max_count=size, strs={}, nums={},
             emb=emb[i])
        for i in range(n)
    ]
