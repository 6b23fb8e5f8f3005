"""Reduction from a profiler trace to the numbers the benchmark reports.

A trace is kept in a neutral form, a dict::

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

:func:`load_xplane` builds it from the ``.xplane.pb`` that
``jax.profiler.stop_trace`` writes (read with ``jax.profiler.ProfileData``);
``tests/data/trace_small.json.gz`` is a recorded one in this form.

What the reduction relies on, as the TPU profiler names it:

* device planes are named ``/device:TPU:<i>``;
* on a device plane, line ``XLA Ops`` holds one event per executed
  operation, and line ``XLA Modules`` one event per program execution,
  named ``<module>(<id>)``, where a jitted function ``f`` gives the module
  ``jit_f``;
* the host plane is ``/host:CPU``: one line per thread, with the runtime's
  own TraceMe events (launches, transfers, waits) and the benchmark's
  ``TraceAnnotation`` spans;
* all planes share one clock, so host and device events can be compared.
"""
from __future__ import annotations

import glob
import math
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench:traced_window"


def load_xplane(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir``, in the neutral form."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(tr: dict) -> list[dict]:
    return [p for p in tr["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_lines(tr: dict) -> list[dict]:
    for p in tr["planes"]:
        if p["name"] == HOST_PLANE:
            return p["lines"]
    return []


def window(tr: dict) -> tuple[float, float] | None:
    """``(start_ns, end_ns)`` of the benchmark's traced-window span."""
    for line in host_lines(tr):
        for name, t, d in line["events"]:
            if name == WINDOW_SPAN:
                return t, t + d
    return None


def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Event intervals cut to ``[lo, hi]``; empty ones dropped."""
    out = []
    for _, t, d in events:
        a, b = max(t, lo), min(t + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(tr: dict, lo: float, hi: float) -> float:
    """Device-busy time in ``[lo, hi]``, averaged over the device planes:
    the union of the operation intervals of each device."""
    planes = device_planes(tr)
    if not planes:
        return 0.0
    total = 0.0
    for p in planes:
        total += sum(b - a for a, b in union(clip(line_events(p, OPS_LINE), lo, hi)))
    return total / len(planes)


def module_durations_ns(tr: dict, module: str, lo: float = -math.inf,
                        hi: float = math.inf) -> list[float]:
    """Device durations of the executions of ``module`` (all devices) that
    lie wholly inside ``[lo, hi]``; one cut by the trace's edges is left
    out, as its event holds only the traced part."""
    pat = re.compile(rf"^{re.escape(module)}(\(\d+\))?$")
    return [d for p in device_planes(tr)
            for name, t, d in line_events(p, MODULES_LINE)
            if pat.match(name) and t >= lo and t + d <= hi]


def program_durations_ns(tr: dict, module: str, lo: float = -math.inf,
                         hi: float = math.inf) -> dict[str, list[float]]:
    """:func:`module_durations_ns` kept apart by program: one list per
    compiled program of ``module``, keyed by the full execution name
    ``<module>(<id>)`` (programs of one function with other static
    arguments or shapes have other ids)."""
    pat = re.compile(rf"^{re.escape(module)}(\(\d+\))?$")
    out: dict[str, list[float]] = {}
    for p in device_planes(tr):
        for name, t, d in line_events(p, MODULES_LINE):
            if pat.match(name) and t >= lo and t + d <= hi:
                out.setdefault(name, []).append(d)
    return out


LAYOUT = re.compile(r"\{[^{}]*\}")
CONTROL_OPS = {"while", "conditional", "call"}  # they enclose other ops


def op_label(name: str) -> str:
    """``"fusion.177 f32[34547,256] fusion"`` from the profiler's full HLO
    text of an operation (``%<op> = <type> <kind>(<operands>), ...``);
    names it cannot parse are kept as they are."""
    op, sep, rest = name.partition(" = ")
    if not sep:
        return name
    rest = LAYOUT.sub("", rest)
    if rest.startswith("("):  # a tuple type: up to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        typ, rest = rest[: i + 1], rest[i + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
    kind = rest.partition("(")[0]
    return f"{op.lstrip('%')} {typ} {kind}" if kind else name


def top_ops(tr: dict, lo: float, hi: float, count: int = 10) -> list:
    """``[[label, seconds], ...]``: the operations that took most device
    time in ``[lo, hi]``, summed by label over the devices (control flow
    that only encloses other operations is left out)."""
    tot: dict[str, float] = {}
    for p in device_planes(tr):
        for name, t, d in line_events(p, OPS_LINE):
            label = op_label(name)
            if label.rsplit(" ", 1)[-1] in CONTROL_OPS:
                continue
            a, b = max(t, lo), min(t + d, hi)
            if b > a:
                tot[label] = tot.get(label, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:count]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(tr: dict, lo: float, hi: float, count: int = 10) -> list:
    """``[[host activity, seconds], ...]``: the longest gaps in ``[lo, hi]``
    in which the first device ran nothing, each named by the host event
    that overlaps it most (``"idle: no host event"`` when none does)."""
    planes = device_planes(tr)
    if not planes:
        return []
    busy = union(clip(line_events(planes[0], OPS_LINE), lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:count]
    host = [e for line in host_lines(tr) for e in line["events"]
            if e[0] != WINDOW_SPAN]
    out = []
    for a, b in gaps:
        best, over = "idle: no host event", 0.0
        for name, t0, d in host:
            ov = min(t0 + d, b) - max(t0, a)
            if ov > over:
                best, over = name, ov
        out.append([best, (b - a) / 1e9])
    return out
