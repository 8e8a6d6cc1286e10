"""The program's own spans (``repro.spans``) in the measured window, for
the per-layer readers that split a layer's time from inside the program.

A traced run keeps the profiler on through the window, so the program
records its spans into its in-memory buffer.  :func:`window` picks the
records that lie inside the window, bounded by the benchmark's own spans
of the service's worker thread in ``run["spans"]``.  It returns ``None``
where there is nothing to read: a program without ``repro.spans``, or no
record inside the window.  Readers then return ``None`` too.
"""

from __future__ import annotations

import importlib

WORKER = "serve-worker"

# host work of a kernel launch before its results are waited for
LAUNCH_HOST = ("launch/prep", "launch/put", "launch/dispatch", "accept/dispatch")
FETCH = ("launch/fetch", "accept/fetch")
UPLOADS = ("launch/put", "accept/dispatch")
# what follows a launch/dispatch: the argmin's fetch, or the accept scan
AFTER_DISPATCH = ("launch/fetch", "accept/dispatch")


def window(run) -> list | None:
    """The program's records that start and end inside the window."""
    try:
        spans = importlib.import_module("repro.spans")
    except ImportError:
        return None
    worker = [s for s in run["spans"] if s.thread == WORKER]
    if not worker:
        return None
    lo = min(s.t0 for s in worker) * 1e9
    hi = max(s.t1 for s in worker) * 1e9
    recs = [r for r in spans.records() if lo <= r.t0_ns and r.t1_ns <= hi]
    return recs or None


def launched(recs) -> bool:
    """Whether any kernel launch recorded its spans."""
    return any(r.name in LAUNCH_HOST for r in recs)


def wall_ms(recs, names, thread: str = WORKER) -> float:
    """Wall milliseconds of the records ``names`` of one thread."""
    return sum(r.t1_ns - r.t0_ns for r in recs
               if r.thread == thread and r.name in names) / 1e6


def cpu_ms(recs, name: str, thread: str | None = WORKER) -> float:
    """Thread CPU milliseconds of the records ``name`` of one thread (all
    threads for ``thread=None``)."""
    return sum(r.cpu_ns for r in recs
               if r.name == name and (thread is None or r.thread == thread)) / 1e6


def attr_sum(recs, name: str, attr: str, thread: str | None = WORKER) -> float:
    """Sum of one attribute over the records ``name`` of one thread (all
    threads for ``thread=None``)."""
    return sum(r.attrs.get(attr, 0) for r in recs
               if r.name == name and (thread is None or r.thread == thread))


def device_wait_ms(recs, thread: str = WORKER) -> float:
    """Milliseconds ``thread`` waited for launch results: its fetch spans,
    plus the time from each ``launch/dispatch`` end to the launch span
    that follows it.  Only a result read in between fills that gap, and
    a traced run's kernel probe reads each launch's results there, so
    the launch's wait moves from its fetch span into the gap."""
    mine = sorted((r for r in recs if r.thread == thread
                   and (r.name in LAUNCH_HOST or r.name in FETCH)),
                  key=lambda r: r.t0_ns)
    ns = sum(r.t1_ns - r.t0_ns for r in mine if r.name in FETCH)
    for a, b in zip(mine, mine[1:]):
        if a.name == "launch/dispatch" and b.name in AFTER_DISPATCH:
            ns += b.t0_ns - a.t1_ns
    return ns / 1e6
