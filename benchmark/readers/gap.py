"""A number of the interval loop's gap pass from the program's gap
breadcrumbs (`tracing.recent`, `kind` "gap", one per pass of the loop's
idle-gap maintenance: counters onto the delivered cohorts' rows, the
store's drain, one full collection, the flush of staged rows), shed
passes left out: they did no work.

args: key   a crumb key in seconds (`gc_s`, `drain_s`, `count_s`,
            `flush_s`, `wake_late_s`, `cpu_s`), or
            "pass"   the whole pass, `_pc_end - _pc_start`, or
            "start"  the pass's `_pc_start` less the `_pc_dispatch` of
                     the window's first cohort: read against the
                     cohort's `collect_lag_s` it says on which side of
                     the delivery call the pass fell
      pick  "first"   the first pass that starts after the dispatch of
                      the window's first cohort (the burst's full-pool
                      pass)
            "median"  over the passes that start inside the window

The value is in ms. None where there is no such crumb (a program that
keeps none, the parent of the PR that added them; no cohort in the
window) or the crumb lacks the key.
"""

from lib.stats import median


def value_s(crumb, key, dispatch):
    if key == "pass":
        return crumb["_pc_end"] - crumb["_pc_start"]
    if key == "start":
        return None if dispatch is None else crumb["_pc_start"] - dispatch
    return crumb.get(key)


def read(ctx, args):
    tracing = ctx.backend.tracing
    gaps = [c for c in tracing.recent(4096)
            if c.get("kind") == "gap" and not c.get("shed")]
    rows = ctx.window_rows
    dispatch = rows[0]["_pc_dispatch"] if rows else None
    if args["pick"] == "first":
        if dispatch is None:
            return None
        gaps = [c for c in gaps if c["_pc_start"] >= dispatch][:1]
    else:
        gaps = [c for c in gaps if ctx.t0 <= c["_pc_start"] <= ctx.t1]
    xs = [value_s(c, args["key"], dispatch) for c in gaps]
    xs = [x * 1e3 for x in xs if x is not None]
    return median(xs) if xs else None
