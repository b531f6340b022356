"""Tickets whose `matchmaker_matched` reached their session inside the
window, over `--seconds`: all of the window's deliveries, the steady
pool's older tickets among them. The window is `--seconds` from its
opening, whenever the harness got to close it."""

from lib.stats import rate


def read(ctx, args):
    seconds = ctx.args.seconds
    n = sum(1 for s in ctx.sessions if s.matched_t is not None
            and ctx.t0 <= s.matched_t <= ctx.t0 + seconds)
    return rate(n, seconds) if n else None
