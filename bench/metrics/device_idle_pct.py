"""Device: the share of the traced window in which no operation ran on
the chip, ``100 * (1 - busy / window)``, with busy the union of the
``XLA Ops`` intervals (averaged over the chips).  The traced window is the
benchmark's own span and holds several whole serve dispatches."""
import tracereduce


def read(ctx):
    if ctx.trace is None or ctx.window is None:
        return None
    if not tracereduce.device_planes(ctx.trace):
        return None
    lo, hi = ctx.window
    if hi <= lo:
        return None
    return 100.0 * (1.0 - tracereduce.busy_ns(ctx.trace, lo, hi) / (hi - lo))
