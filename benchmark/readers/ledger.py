"""A stage of cohort delivery from the program's delivery ledger
(`tracing.recent_deliveries`): host-clock stamps at the real stage
boundaries, each a lag since dispatch.

args: plus / minus  ledger keys; the value is sum(plus) - sum(minus)
      pick          "first"   the window's first cohort (the burst's
                              full-pool pass)
                    "median"  over the window's cohorts
"""

from lib.ledger import stage_ms


def read(ctx, args):
    return stage_ms(ctx.window_rows, args["plus"], args.get("minus", []),
                    args["pick"])
