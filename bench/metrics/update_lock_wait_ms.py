"""Graph lock: the median time an ``/update`` burst waited for the graph
lock (``probesim:lock.update`` spans wholly inside the traced window),
which a query dispatch in flight holds."""
import statistics

import spanreduce


def read(ctx):
    if ctx.trace is None or ctx.window is None:
        return None
    ev = spanreduce.span_events(ctx.trace, spanreduce.LOCK_UPDATE,
                                 *ctx.window)
    return statistics.median(d for _, _, d in ev) / 1e6 if ev else None
