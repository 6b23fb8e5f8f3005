"""Update apply: the device time one ``/update`` burst costs, read from the
apply executions that lie wholly inside the traced window.

A burst goes through ``session.update`` to ``graph/dynamic.py``
``apply_update_batch_jit`` once for its inserts and once for its deletes.
``has_deletes`` is a static argument, so the two are separate programs:
the trace names their executions ``jit_apply_update_batch(<id>)`` on the
device plane's ``XLA Modules`` line, with one id for each program.  The
metric is the sum over the programs of the median time of one execution:
the device time of a burst that launches each once."""
import statistics

import tracereduce

MODULE = "jit_apply_update_batch"


def read(ctx):
    if ctx.trace is None or ctx.window is None:
        return None
    progs = tracereduce.program_durations_ns(ctx.trace, MODULE, *ctx.window)
    if not progs:
        return None
    return sum(statistics.median(d) for d in progs.values()) / 1e6
