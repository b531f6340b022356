"""A ratio of sums over the window's delivery-ledger rows: what a
delivery call's publish stages (`publish_*_s`, on the row of the oldest
cohort the call shipped) cost by the match or by the envelope, with the
counts the publish callback keeps beside them (`publish_matches`,
`publish_envelopes`). Rows of calls that published nothing carry
neither and add nothing.

args: sum, per, scale  as the `crumb` reader's, over `ctx.window_rows`
"""

from readers.crumb import ratio


def read(ctx, args):
    return ratio(ctx.window_rows, args)
