"""Probe step: the median device time of one execution of the fused serve
program (``core/multisource.py``: pooled walk sampling, the lane-probe
levels, the epilogue and top-k), read from the executions that lie wholly
inside the traced window.

The jitted function is ``fused_serve_impl``; the TPU trace names its
program's executions ``jit_fused_serve_impl(<id>)`` on the device plane's
``XLA Modules`` line."""
import statistics

import tracereduce

MODULE = "jit_fused_serve_impl"


def read(ctx):
    if ctx.trace is None or ctx.window is None:
        return None
    d = tracereduce.module_durations_ns(ctx.trace, MODULE, *ctx.window)
    return statistics.median(d) / 1e6 if d else None
