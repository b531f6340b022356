"""Process start to window start, seconds: imports, native build where
missing, server boot, loads, warm-up and, in a run that compiles,
compilation."""


def read(ctx, args):
    return ctx.t0 - ctx.t_process
