"""Bytes of assigning 1v1 partners from candidate lists, from the
dispatched shapes.

The work counted is the ALGORITHM's, not the rounds of one program:
every one of `a_pad` rows reads its `k` candidates (int32 slots) once
and writes one int32 partner. However many propose-accept rounds, sorts,
scatters or gathers an implementation spends on it, the lists have to be
read and the partner vector written; nothing else has to cross HBM, and
no arithmetic worth a roof is done. So the roof is the memory one, and
the share reads the same whatever implements the rounds.
"""

from __future__ import annotations


def pair_ops_bytes(a_pad: int, k: int) -> tuple[float, float]:
    """(operations, bytes): the lists read once, the partners written
    once, in int32; one compare a candidate."""
    return 1.0 * a_pad * k, 4.0 * a_pad * k + 4.0 * a_pad
