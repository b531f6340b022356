"""A share of the chip's roofline, %, for the device-side 1v1
assignment of the window's first (full-pool) dispatch.

The least time the chip could take for the ALGORITHM's work at the
dispatched shape (`lib/roofline_pairs.py`: one read of the `[a_pad, k]`
candidate lists, one write of the `[a_pad]` partner vector; peaks from
`lib/peaks.py` by `device_kind`), over the device time the trace gives
the first run of the pairing program. None where the dispatch paired
nothing on the device (another kernel variant) or the trace holds no
such program.

args: program  substring of the pairing program's name
"""

from lib.peaks import peaks
from lib.roofline import least_seconds
from lib.roofline_pairs import pair_ops_bytes
from lib.trace import program_runs


def read(ctx, args):
    tr = ctx.trace
    if not tr or not ctx.window_crumbs:
        return None
    kernel = ctx.window_crumbs[0].get("kernel")
    runs = program_runs(tr, args["program"])
    if not kernel or args["program"] not in kernel["kernel"] or not runs:
        return None
    seconds = runs[0][1] - runs[0][0]
    if seconds <= 0:
        return None
    ops, nbytes = pair_ops_bytes(kernel["a_pad"], kernel["k"])
    least, roof = least_seconds(ops, nbytes, peaks(ctx.device["kind"]))
    ctx.notes.setdefault("roofline", {})[args["program"]] = dict(
        a_pad=kernel["a_pad"], k=kernel["k"], ops=ops, bytes=nbytes,
        least_s=least, roof=roof, device_s=seconds,
    )
    return 100.0 * least / seconds
