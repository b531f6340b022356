"""The device's idle share of the traced window, %: 1 - the union of
the intervals in which an operation ran, over the window, averaged over
the chips used."""


def read(ctx, args):
    tr = ctx.trace
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
