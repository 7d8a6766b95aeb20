"""Median time to first token, in ms, over every request due in the window,
from its due time (host clock).  A window of the chat cell holds 28
requests, too few for a 95th percentile: the median is the highest
percentile that its sample supports."""
from servebench.window import percentile, ttfts


def read(ctx, name):
    values = ttfts(ctx.run)
    return percentile(values, 50) * 1e3 if values else None
