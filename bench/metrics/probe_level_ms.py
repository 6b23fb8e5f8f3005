"""Probe step: device time per probe level, the device time of the fused
serve executions (``jit_fused_serve_impl``) that lie wholly inside the
traced window over the sum of the ``probe_levels`` of the window's
dispatches.  It holds sampling and the epilogue too, spread over the
levels.  Nothing is read where the two counts of dispatches differ."""
import spanreduce
import tracereduce


def read(ctx):
    if ctx.trace is None or ctx.window is None:
        return None
    levels = spanreduce.dispatch_levels(ctx.answers)
    d = tracereduce.module_durations_ns(ctx.trace, spanreduce.SERVE_MODULE,
                                        *ctx.window)
    if not levels or len(d) != len(levels):
        return None
    return sum(d) / 1e6 / sum(levels.values())
