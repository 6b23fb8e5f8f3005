"""Device: the share of the traced window in which the chip was idle while
work waited, ``100 * (idle not covered by a collector.idle span) /
window``.  Idle is the first device's time without an ``XLA Ops`` event;
``probesim:collector.idle`` spans the service's wait with nothing pending,
so what is left is host time between dispatches (batching window,
answers, lock waits, launches).  Nothing is read from a program without
the span."""
import spanreduce
import tracereduce


def read(ctx):
    if ctx.trace is None or ctx.window is None:
        return None
    lo, hi = ctx.window
    waiting = spanreduce.span_events(ctx.trace, spanreduce.COLLECTOR_IDLE)
    if hi <= lo or not waiting or not tracereduce.device_planes(ctx.trace):
        return None
    idle = spanreduce.device_idle(ctx.trace, lo, hi)
    return 100.0 * spanreduce.uncovered_ns(
        idle, tracereduce.clip(waiting, lo, hi)) / (hi - lo)
