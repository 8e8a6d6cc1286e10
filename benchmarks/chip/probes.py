"""Benchmark-side probes around the calls into each layer of the program.

Nothing here edits the program: the probes wrap the objects the served
path already calls (the scheduler handed to ``SchedulerService``, the
pipeline's stage objects, the fluid engine's ``advance`` /
``configure_incremental``, ``plugin.find_rotations_batched`` and the
kernel entry points) and record

* the decision latency of every ``scheduler.schedule(state)`` call the
  service makes (always on: it is the end-to-end metric);
* the inputs and outputs the correctness check compares (always on:
  references only, no copies on the timed path except the fluid state
  of the running jobs after each advance);
* with tracing on, host spans per layer (also emitted as
  ``jax.profiler.TraceAnnotation`` so they share the device trace's
  clock), ``BatchStats`` summed over calls, and per-launch kernel shapes
  for the roofline.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from dataclasses import dataclass, field


@dataclass
class Span:
    name: str
    thread: str
    t0: float
    t1: float


@dataclass
class Recorder:
    """Everything the probes collect during one run."""

    tracing: bool = False
    in_window: bool = False
    decisions: list = field(default_factory=list)       # (t0, t1, in_window)
    spans: list = field(default_factory=list)
    scored: list = field(default_factory=list)          # (decision index, now, Score output)
    fluid: list = field(default_factory=list)           # the watched tenants' fluid log
    batch: object = None                                # summed BatchStats
    solves: int = 0
    launches: list = field(default_factory=list)        # per kernel launch
    lock: threading.Lock = field(default_factory=threading.Lock)

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.tracing:
            yield
            return
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                t1 = time.perf_counter()
                if self.in_window:
                    with self.lock:
                        self.spans.append(Span(
                            name, threading.current_thread().name, t0, t1))


class DecisionProxy:
    """The scheduler the service sees: times every decision and forwards
    ``pipeline``, ``host`` and ``module`` so the prefetch, fallback and
    telemetry paths find them."""

    def __init__(self, inner, rec: Recorder) -> None:
        self._inner = inner
        self._rec = rec
        self.name = inner.name
        self.pipeline = inner.pipeline
        self.host = inner.host
        self.module = inner.module
        self.outputs: list = []   # (decision index, Decision) in the window

    def allocate_workers(self, state):
        return self._inner.allocate_workers(state)

    def propose(self, state, workers, k):
        return self._inner.propose(state, workers, k)

    def schedule(self, state):
        rec = self._rec
        with rec.span("decision"):
            t0 = time.perf_counter()
            decision = self._inner.schedule(state)
            t1 = time.perf_counter()
        rec.decisions.append((t0, t1, rec.in_window))
        if rec.in_window:
            self.outputs.append((len(rec.decisions) - 1, decision))
        return decision


def wrap_stages(pipeline, rec: Recorder) -> None:
    """Spans on every stage object; the Score stage also keeps its input
    and output of decisions made on the service's worker thread."""
    for stage in pipeline.stages:
        run = stage.run
        name = stage.name

        def probe(state, inp, _run=run, _name=name):
            with rec.span(_name):
                out = _run(state, inp)
            if (_name == "score" and rec.in_window
                    and threading.current_thread().name == "serve-worker"):
                rec.scored.append((len(rec.decisions), state.now_ms, out))
            return out

        stage.run = probe


def wrap_solver(rec: Recorder):
    """Sum ``BatchStats`` over every batched link solve (the module keeps
    only the last call's).  Returns an undo callable."""
    import repro.core.plugin as plugin
    from repro.core.compat import BatchStats

    solve = plugin.find_rotations_batched
    rec.batch = BatchStats()

    def probe(batch, **kw):
        with rec.span("score.solve"):
            out = solve(batch, **kw)
        if rec.in_window:
            with rec.lock:
                rec.solves += 1
                for f in dataclasses.fields(rec.batch):
                    setattr(rec.batch, f.name, getattr(rec.batch, f.name)
                            + getattr(kw["stats"], f.name))
        return out

    plugin.find_rotations_batched = probe

    def undo():
        plugin.find_rotations_batched = solve

    return undo


def wrap_kernels(rec: Recorder):
    """Record the shape of every ragged kernel launch (rows, per-row angle
    counts, and the shifts each row needed: up to its first zero-excess
    shift, where the kernel may stop, else all admissible ones) for the
    roofline.  Tracing only: reading the launch's per-row result adds one
    device-to-host copy.  Returns an undo callable."""
    import numpy as np
    from repro.kernels.circle_score import ops

    launch = ops._ragged_device

    def probe(base, cand, capacity, valid, num_angles, variant, **kw):
        with rec.span("kernel." + variant):
            idx, val, rows = launch(
                base, cand, capacity, valid, num_angles, variant, **kw)
        if rec.in_window:
            i = np.asarray(idx)[:rows].astype(np.int64)
            v = np.asarray(val)[:rows]
            ok = np.broadcast_to(np.asarray(valid), (rows,)).astype(np.int64)
            na = np.broadcast_to(np.asarray(num_angles), (rows,))
            with rec.lock:
                rec.launches.append({
                    "variant": variant, "rows": int(rows),
                    "num_angles": na.astype(np.int64).tolist(),
                    "needed": np.minimum(np.where(v == 0.0, i + 1, ok),
                                         ok).tolist(),
                })
        return idx, val, rows

    ops._ragged_device = probe

    def undo():
        ops._ragged_device = launch

    return undo


def wrap_fluid(svc, rec: Recorder) -> None:
    """Spans on the fluid engine, and the log the reference replays, in
    the window: for each decision being applied, what it asks of each job
    it places (time-shift, pacing and its period, or nothing), where it
    places it, and which of them are new to the fluid engine with no
    iteration done; after every ``advance``, each running job's state and
    placement.  The check picks the jobs it watches from this log.

    This runs inside the timed window, on every job and not only on those
    the check will watch, since which ones it watches on an open layout
    is known only from later states: about 30 us a snapshot of 51 jobs
    (CPU), against hundreds of milliseconds a decision."""
    net = svc.net
    advance = net.advance
    configure = net.configure_incremental

    def probe_advance(until_ms, **kw):
        with rec.span("fluid.advance"):
            finished = advance(until_ms, **kw)
        if rec.in_window:
            execs = net._execs
            rec.fluid.append(("snap", net.now_ms, {
                jid: (ex.job.iters_done, ex.seg_idx, ex.remaining, ex.delay_ms,
                      ex.ideal_next_ms, ex.consec_adjust, ex.applied_shift_ms,
                      ex.iter_start_ms, ex.paced_iter_ms)
                for jid, ex in execs.items()},
                {jid: ex.job.placement for jid, ex in execs.items()}))
        return finished

    def probe_configure(jobs):
        if rec.in_window:
            plan = svc.decisions[-1][1].plan
            shifts = plan.time_shifts_ms if plan is not None else {}
            rec.fluid.append(("configure", net.now_ms, {
                j.job_id: (None, False, None) if j.job_id not in shifts else
                (float(shifts[j.job_id]), plan.align_ok(j.job_id),
                 plan.paced_periods_ms.get(j.job_id))
                for j in jobs},
                {j.job_id: j.placement for j in jobs},
                frozenset(j.job_id for j in jobs
                          if j.job_id not in net._execs and j.iters_done == 0)))
        with rec.span("fluid.configure"):
            return configure(jobs)

    net.advance = probe_advance
    net.configure_incremental = probe_configure


def wrap_handler(svc, rec: Recorder) -> None:
    handle = svc._handle

    def probe(event):
        with rec.span("serve.handle"):
            return handle(event)

    svc._handle = probe


class CompileCounter:
    """Backend compiles, persistent-cache hits and their seconds, from
    JAX's monitoring events (registered before the first compile)."""

    def __init__(self, jax) -> None:
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                self.cache_hits += 1
                self.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def total(self) -> int:
        return self.compiles + self.cache_hits


def span_ms(spans, names, thread: str = "serve-worker") -> float:
    """Milliseconds the named spans took on one thread."""
    return 1e3 * sum(s.t1 - s.t0 for s in spans
                     if s.thread == thread and s.name in names)
