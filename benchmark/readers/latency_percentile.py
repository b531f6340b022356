"""A percentile of a per-ticket latency, in ms, over ALL tickets of the
window that were due `ctx.grace_s` before its close or earlier (the
burst: all of them) and have both stamps. An envelope that arrives
after the close counts with the wait it took: the run waits for the
cohorts in flight, a minute at the most.

args: from  "tick"  the start of the first process() call after the
                    ticket's add was acknowledged
            "due"   when the add was due (open loop) or made
      to    "matched" | "ack"
      q     the percentile
"""

from lib.stats import percentile


def read(ctx, args):
    out = []
    for s in ctx.sessions:
        if not s.in_window or s.due_t is None:
            continue
        end = s.matched_t if args["to"] == "matched" else s.ack_t
        if end is None:
            continue
        if args["to"] == "matched" and s.due_t > ctx.t1 - ctx.grace_s:
            continue
        if args["from"] == "tick":
            if s.ack_t is None:
                continue
            start = ctx.first_tick_after(max(s.ack_t, ctx.t0))
        else:
            start = s.due_t
        if start is None:
            continue
        out.append((end - start) * 1e3)
    return percentile(out, args["q"]) if out else None
