"""Named host spans on the served path, on the device trace's clock.

Each span is a ``jax.profiler.TraceAnnotation`` (a TraceMe): with no
profiler trace active it costs about a microsecond, and under
``jax.profiler.start_trace`` it lands on the ``/host:CPU`` plane of the
same trace as the device's operations, so host spans and device events
share one clock.  A span entered before the trace starts, or still open
when it stops, is not recorded, so long waits are spanned piecewise.

Names are fixed strings (no ids or counts in them): a trace reduction
finds a span by its name alone.  No span is opened inside a jitted
function; the device's own work is read from the device plane.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

COLLECTOR_IDLE = "probesim:collector.idle"  # nothing pending: no traffic
COLLECTOR_WINDOW = "probesim:collector.window"  # the micro-batch window
RESPOND = "probesim:respond"  # answers of a served batch to the wire
LOCK_QUERY = "probesim:lock.query"  # a query batch waits on the graph lock
LOCK_UPDATE = "probesim:lock.update"  # an update waits on the graph lock
UPDATE = "probesim:update"  # the host side of one update burst
DISPATCH = "probesim:dispatch"  # one fused serve dispatch, answers on host
DISPATCH_FETCH = "probesim:dispatch.fetch"  # device wait + copy to host

NAMES = (
    COLLECTOR_IDLE, COLLECTOR_WINDOW, RESPOND, LOCK_QUERY, LOCK_UPDATE,
    UPDATE, DISPATCH, DISPATCH_FETCH,
)


class span:
    """``with span(name) as s:`` records ``name`` in an active profiler
    trace and leaves the block's ``time.perf_counter`` duration in
    ``s.seconds``."""

    __slots__ = ("name", "seconds", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)


@contextmanager
def locked(lock, name: str):
    """Hold ``lock`` for the block; the acquisition is spanned as ``name``
    and the wait, in seconds, is the value of the ``with``."""
    with span(name) as s:
        lock.acquire()
    try:
        yield s.seconds
    finally:
        lock.release()
