"""Tickets of the `party50k` deployment: BASELINE.json config 4,
"party-aware: 50k mixed solo/party tickets (party_handler.go),
count_multiple constraint". As upstream's `party_handler.go`
`MatchmakerAdd` makes it, a party's leader adds ONE ticket that carries
every member's presence: a spec's `count` is its players (1 is a solo
ticket), drawn from `sizes` at `shares`; every ticket asks for
`min_count` to `max_count` players in a multiple of `count_multiple`
(teams kept even). The wildcard query, no properties, one unit embedding
of independent normals a ticket (the party's skill, as `ranked.py`'s),
which alone orders the candidates.
"""

from __future__ import annotations

import numpy as np


def specs(seed, n: int, params: dict) -> list[dict]:
    """`n` tickets from `seed` (an int or a sequence of ints)."""
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n, params["embedding_dims"]))
    emb = emb.astype(np.float32)
    emb /= np.maximum(1e-6, np.linalg.norm(emb, axis=1, keepdims=True))
    count = rng.choice(params["sizes"], size=n, p=params["shares"])
    return [
        dict(query="*", min_count=params["min_count"],
             max_count=params["max_count"], count=int(count[i]),
             count_multiple=params["count_multiple"], strs={}, nums={},
             emb=emb[i])
        for i in range(n)
    ]
