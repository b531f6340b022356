"""A statistic over the benchmark's own host spans of the window.

args: span   "add"      around each Pipeline.process(matchmaker_add)
             "process"  around each mm.process() call
      stat   "mean" | "median" | "first"
      scale  seconds -> the metric's unit (1e3 for ms, 1e6 for us)
"""

from lib.stats import median


def read(ctx, args):
    spans = ctx.add_spans if args["span"] == "add" else ctx.ticks
    xs = [d for t, d in spans if ctx.t0 <= t <= ctx.t1]
    if not xs:
        return None
    value = {"mean": sum(xs) / len(xs), "median": median(xs),
             "first": xs[0]}[args["stat"]]
    return value * args["scale"]
