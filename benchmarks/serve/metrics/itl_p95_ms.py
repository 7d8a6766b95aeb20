"""95th percentile of the gaps between consecutive tokens of a request, in
ms, over every gap that ends in the window (host clock)."""
from servebench.window import inter_token_gaps, percentile


def read(ctx, name):
    gaps = inter_token_gaps(ctx.run)
    return percentile(gaps, 95) * 1e3 if gaps else None
