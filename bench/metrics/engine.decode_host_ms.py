"""Median milliseconds of host work per decode call: each
``engine.decode`` span of the window less its ``engine.decode.sync``
child (the wait for the device's tokens), so page growth, dispatch and
token bookkeeping remain."""

import statistics

from bench import spans


def read(ctx):
    log = spans.window(ctx)
    if log is None:
        return None
    host = [spans.seconds(d) - sum(spans.seconds(c)
                                   for c in spans.children(log, d)
                                   if c.name == "engine.decode.sync")
            for d in log if d.name == "engine.decode"]
    if not host:
        return None
    return 1e3 * statistics.median(host)
