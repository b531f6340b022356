"""Operations and bytes of scoring a pool pass, from the cell's shapes.

The work counted is the ALGORITHM's, not a kernel's tiles: every one of
`a_pad` active rows is scored against every one of `n_cols` pool columns
by a dot product over `dims` bf16 planes (the bucket encoding of the
query against the value, plus the skill embedding), and keeps one packed
32-bit word per row and per `col_block` columns (the per-block winners
stage 2 re-ranks). It reads the same whatever implements it.
"""

from __future__ import annotations


def score_ops_bytes(a_pad: int, n_cols: int, dims: int,
                    col_block: int = 1024) -> tuple[float, float]:
    """(operations, bytes): one multiply and one add per pair and plane;
    each operand read once and the winners written once, in bf16/int32."""
    ops = 2.0 * a_pad * n_cols * dims
    nbytes = 2.0 * (a_pad + n_cols) * dims + 4.0 * a_pad * (n_cols / col_block)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which roof sets it."""
    t_ops = ops / peak["bf16_flops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
