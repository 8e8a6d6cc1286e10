"""Reduce a profiler trace of one window to device metrics.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into a flat event
list: every event on a device plane, and the benchmark's own host spans
(``TraceAnnotation``, on the same clock).  ``reduce`` turns that list into

* ``busy_s`` / ``window_s``: the union of device-op intervals inside the
  window, and the window's length (the ``window`` span);
* ``<group>_s`` / ``<group>_events``: device seconds and count of the
  events on one device line (ops, or whole jitted modules) whose names
  match a group's patterns;
* ``device_ops``: the ten op names that took the most device time;
* ``idle_gaps``: device idle time inside the window, summed by the
  innermost benchmark span of the service's worker thread that covered
  the gap's midpoint (``"outside spans"`` where none did: the worker
  waited for the client), the ten largest.

Events are ``(plane, line, name, start_ns, dur_ns)`` tuples, so a small
recorded trace can be kept as JSON and reduced without the profiler.
"""

from __future__ import annotations

import glob
import os

WINDOW = "window"
OPS_LINES = ("XLA Ops",)


def load(trace_dir: str, host_names: set[str]) -> list[tuple]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            device = plane.name.startswith("/device:")
            for line in plane.lines:
                for ev in line.events:
                    if device or ev.name in host_names:
                        out.append((plane.name, line.name, ev.name,
                                    float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def device_ops(events: list[tuple]) -> list[tuple]:
    """Op events of the device planes (the ``XLA Ops`` lines, else every
    device line)."""
    dev = [e for e in events if e[0].startswith("/device:")]
    ops = [e for e in dev if e[1] in OPS_LINES]
    return ops or dev


def reduce(events: list[tuple], groups: dict | None = None,
           host_spans: list[tuple] | None = None) -> dict:
    """``groups``: name -> (device line, op-name substrings); each group's
    device seconds and event count come back as ``<name>_s`` and
    ``<name>_events``.  ``host_spans``: ``(name, start_ns, dur_ns)`` of
    the service's worker thread on the trace's clock (the trace names
    every Python thread alike), for the idle-gap attribution."""
    groups = groups or {}
    win = [e for e in events if e[2] == WINDOW and not e[0].startswith("/device:")]
    if not win:
        raise ValueError("trace holds no window span")
    w0 = min(e[3] for e in win)
    w1 = max(e[3] + e[4] for e in win)
    inside = [e for e in events if e[0].startswith("/device:")
              and e[3] + e[4] > w0 and e[3] < w1]
    ops = [e for e in device_ops(events) if e[3] + e[4] > w0 and e[3] < w1]
    planes = sorted({e[0] for e in ops})
    nplanes = max(1, len(planes))
    busy = 0.0
    for p in planes:
        iv = _union([(max(e[3], w0), min(e[3] + e[4], w1)) for e in ops if e[0] == p])
        busy += sum(b - a for a, b in iv)
    by_name: dict[str, float] = {}
    for e in ops:
        by_name[e[2]] = by_name.get(e[2], 0.0) + e[4]
    grouped = {}
    for g, (line, pats) in groups.items():
        hit = [e for e in inside if e[1] == line and any(p in e[2] for p in pats)]
        grouped[f"{g}_s"] = sum(e[4] for e in hit) / nplanes * 1e-9
        grouped[f"{g}_events"] = len(hit)
    # idle gaps of the (first) device, attributed to the worker's spans
    busy0 = _union([(max(e[3], w0), min(e[3] + e[4], w1))
                    for e in ops if planes and e[0] == planes[0]])
    gaps, t = [], w0
    for a, b in busy0:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    spans = [("", "", n, a, d) for n, a, d in (host_spans or [])]
    idle: dict[str, float] = {}
    mids = sorted((0.5 * (a + b), b - a) for a, b in gaps)
    for (_, width), name in zip(mids, _innermost(spans, [m for m, _ in mids])):
        name = name or "outside spans"
        idle[name] = idle.get(name, 0.0) + width
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / nplanes * 1e-9,
        **grouped,
        "device_ops": [[_short(n), s * 1e-9] for n, s in top],
        "idle_gaps": [[n, s * 1e-9] for n, s in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
        "devices": len(planes),
    }


def _short(name: str) -> str:
    """An HLO op's name without its operands: ``%fusion.3 = f32[..] ...``
    -> ``fusion.3 (f32[..])``."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    return f"{lhs.lstrip('%')} ({rhs.split(' ', 1)[0]})"


def _innermost(spans: list[tuple], points: list[float]) -> list[str | None]:
    """Name of the innermost span of one thread (spans nest) containing
    each of the sorted ``points``."""
    spans = sorted(spans, key=lambda e: (e[3], -e[4]))
    out: list[str | None] = []
    stack: list[tuple] = []
    i = 0
    for t in points:
        while i < len(spans) and spans[i][3] <= t:
            while stack and stack[-1][3] + stack[-1][4] < spans[i][3]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][3] + stack[-1][4] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


__all__ = ["load", "reduce", "device_ops", "WINDOW"]
