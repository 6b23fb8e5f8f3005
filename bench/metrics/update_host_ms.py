"""Update apply: the median host time of one ``/update`` burst under the
graph lock (``probesim:update`` spans wholly inside the traced window):
splitting and padding the ops, both apply launches and the syncs that
read back what was applied."""
import statistics

import spanreduce


def read(ctx):
    if ctx.trace is None or ctx.window is None:
        return None
    ev = spanreduce.span_events(ctx.trace, spanreduce.UPDATE, *ctx.window)
    return statistics.median(d for _, _, d in ev) / 1e6 if ev else None
