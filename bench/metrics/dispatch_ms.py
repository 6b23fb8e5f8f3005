"""Session and backend dispatch: the median host time of one fused serve
dispatch, ``serve_batch`` up to the copy of its answers to the host.  Every
answer of one dispatch carries the same ``latency_s``, so answers are
grouped by it (and by graph version) to count each dispatch once."""
import statistics


def read(ctx):
    per_dispatch = {(a.get("version"), a["latency_s"]) for a in ctx.answers
                    if a.get("latency_s") is not None}
    vals = [lat for _, lat in per_dispatch]
    return 1e3 * statistics.median(vals) if vals else None
