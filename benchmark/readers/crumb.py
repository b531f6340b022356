"""A ratio of sums over the program's interval breadcrumbs of the
window (`tracing.recent`, one per `process_slots` call): the stage sums
the program folds into each, over a count it folds in beside them.

args: sum    crumb keys; the value's numerator is their sum over the
             window's crumbs
      per    crumb key of the count the numerator is divided by; left
             out, the value is the numerator itself (a count)
      scale  seconds -> the metric's unit (1e6 for us; 1 for a count)

Which adds these are. A crumb holds the adds since the crumb before it,
and the window's crumbs are those of every tick from its opening on
(`run.py window_rows`; a tick of the drain too, where it makes one). So
the first holds what arrived since the last tick BEFORE the window
(warm-up arrivals, which `ingest.add_us` leaves out), and what arrives
after the last tick is on no crumb: the population is the window's,
moved earlier by the share of an interval at which the window opens
(`open_loop`: half of one, 500 adds of 10,000 at each end of
`duel1k.steady`). The same traffic on both sides, not the same adds as
`ingest.add_us` times, which is why the count is a metric of its own
(`ingest.adds_counted`). Only adds that returned a ticket are in any
sum (`tracing.AddStages`).

None where a key is on no crumb of the window (a program that does not
keep it) or the count is 0.
"""


def ratio(records, args):
    """`scale` x sum of `sum` keys / sum of the `per` key, over dicts."""
    per = args.get("per")
    keys = list(args["sum"]) + ([per] if per else [])
    if any(all(r.get(k) is None for r in records) for k in keys):
        return None
    total = sum(r.get(k) or 0.0 for r in records for k in args["sum"])
    if not per:
        return args["scale"] * total
    count = sum(r.get(per) or 0 for r in records)
    return args["scale"] * total / count if count else None


def read(ctx, args):
    return ratio(ctx.window_crumbs, args)
