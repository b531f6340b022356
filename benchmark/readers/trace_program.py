"""Device time, in ms, of the window's first run of a program, from the
trace's `XLA Modules` line (the burst's full-pool dispatch).

args: match  substring of the program's name
"""

from lib.trace import program_runs


def read(ctx, args):
    found = program_runs(ctx.trace, args["match"])
    if not found:
        return None
    return (found[0][1] - found[0][0]) * 1e3
