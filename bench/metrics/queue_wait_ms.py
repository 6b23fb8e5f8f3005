"""Service front end: the median time an answered query waited in the
collector's queue and micro-batch window (``queue_delay_s`` of each answer,
which the service measures from enqueue to the start of its dispatch)."""
import statistics


def read(ctx):
    waits = [a["queue_delay_s"] for a in ctx.answers
             if a.get("queue_delay_s") is not None]
    return 1e3 * statistics.median(waits) if waits else None
