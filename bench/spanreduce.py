"""Reduction of the program's own spans and counter to benchmark numbers.

The program names its host spans ``probesim:<what>``
(``src/repro/utils/spans.py``) and records them as ``TraceAnnotation``s on
the host plane of the trace that :mod:`tracereduce` reads; its probe-level
counter rides in each answer envelope as ``probe_levels``.  A program
without them leaves these readings empty, so a reader built on them
returns ``None`` there.
"""
from __future__ import annotations

import math

import tracereduce

SERVE_MODULE = "jit_fused_serve_impl"
COLLECTOR_IDLE = "probesim:collector.idle"
LOCK_UPDATE = "probesim:lock.update"
UPDATE = "probesim:update"


def span_events(tr: dict, name: str, lo: float = -math.inf,
                hi: float = math.inf) -> list:
    """``[[name, start_ns, duration_ns], ...]``: the host events named
    ``name``, on any host line, that lie wholly inside ``[lo, hi]``."""
    return [e for line in tracereduce.host_lines(tr) for e in line["events"]
            if e[0] == name and e[1] >= lo and e[1] + e[2] <= hi]


def device_idle(tr: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """The intervals of ``[lo, hi]`` in which the first device ran no
    operation (the gaps :func:`tracereduce.idle_gaps` names)."""
    planes = tracereduce.device_planes(tr)
    if not planes:
        return []
    gaps, t = [], lo
    for a, b in tracereduce.union(tracereduce.clip(
            tracereduce.line_events(planes[0], tracereduce.OPS_LINE), lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def uncovered_ns(intervals, cover) -> float:
    """Total length of ``intervals`` (sorted, disjoint) outside the union
    of the ``cover`` intervals."""
    cov = tracereduce.union(cover)
    total, j = 0.0, 0
    for a, b in intervals:
        total += b - a
        while j < len(cov) and cov[j][1] <= a:
            j += 1
        k = j
        while k < len(cov) and cov[k][0] < b:
            total -= min(b, cov[k][1]) - max(a, cov[k][0])
            k += 1
    return total


def dispatch_levels(answers) -> dict:
    """``{(version, latency_s): probe_levels}``, one entry per fused serve
    dispatch: every answer of one dispatch carries the same ``latency_s``
    and ``probe_levels`` (the program's counter in the answer envelope),
    so answers are grouped as ``dispatch_ms`` groups them."""
    return {(a.get("version"), a["latency_s"]): a["probe_levels"]
            for a in answers if a.get("probe_levels") is not None
            and a.get("latency_s") is not None}
