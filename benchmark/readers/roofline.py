"""A share of the chip's roofline, %, for the score pass of the
window's first (full-pool) dispatch.

The least time the chip could take for the ALGORITHM's work at the
dispatched shape (`lib/roofline.py`; peaks from `lib/peaks.py` by
`device_kind`), over the device time the trace gives:

args: dims     bf16 planes per pair (bucket encoding + embedding)
      program  substring of the score program's name
      op       substring of the kernel's operation name; the time is
               that of those operations inside the first program run
"""

from lib.peaks import peaks
from lib.roofline import least_seconds, score_ops_bytes
from lib.trace import program_runs


def read(ctx, args):
    tr = ctx.trace
    if not tr or not ctx.window_crumbs:
        return None
    kernel = ctx.window_crumbs[0].get("kernel")
    runs = program_runs(tr, args["program"])
    if not kernel or not runs:
        return None
    lo, hi = runs[0]
    peak = peaks(ctx.device["kind"])
    ops, nbytes = score_ops_bytes(
        kernel["a_pad"], kernel["n_cols"], args["dims"], kernel["col_block"]
    )
    seconds = sum(b - a for n, a, b in tr["ops"]
                  if args["op"] in n and a >= lo and b <= hi)
    least, roof = least_seconds(ops, nbytes, peak)
    if seconds <= 0:
        return None
    ctx.notes.setdefault("roofline", {})[args["op"]] = dict(
        a_pad=kernel["a_pad"], n_cols=kernel["n_cols"], dims=args["dims"],
        ops=ops, bytes=nbytes, least_s=least, roof=roof, device_s=seconds,
    )
    return 100.0 * least / seconds
