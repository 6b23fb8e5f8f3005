"""Probe step: the median number of probe levels one fused serve dispatch
ran, the program's counter (``probe_levels`` in each answer: the lane
loop's trips plus its peeled first level), one reading per dispatch."""
import statistics

import spanreduce


def read(ctx):
    levels = spanreduce.dispatch_levels(ctx.answers)
    return float(statistics.median(levels.values())) if levels else None
