"""95th percentile over every record the window's polls applied, per
replica, of the time from the start of the train step whose update the
record carries to the return of the poll that applied it (and so
invalidated the serve cache)."""

import numpy as np


def read(ctx):
    s = ctx.stats.get("staleness_s") if ctx.train else None
    if s is None or not len(s):
        return None
    return float(np.percentile(s, 95)) * 1e3
