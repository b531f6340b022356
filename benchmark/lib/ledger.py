"""Ledger rows -> per-layer numbers."""

from __future__ import annotations

from .stats import median


def stage_ms(rows, plus, minus, pick: str):
    """ms of one delivery stage: sum(row[plus]) - sum(row[minus]) of the
    first row or the median over rows. Rows that lack a stamp (a cohort
    that published nothing) are left out; none left: None."""
    xs = []
    for r in rows:
        if all(r.get(k) is not None for k in list(plus) + list(minus)):
            xs.append(
                (sum(r[k] for k in plus) - sum(r[k] for k in minus)) * 1e3
            )
    if not xs:
        return None
    return xs[0] if pick == "first" else median(xs)
