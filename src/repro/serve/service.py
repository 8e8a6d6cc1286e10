"""`SchedulerService`: the scheduling pipeline as a long-running service.

One worker thread owns all scheduling state and consumes a bounded request
queue (FIFO — processing order equals submission order, so results are
deterministic regardless of thread timing).  The embedded event loop is the
batch :class:`~repro.cluster.ClusterSimulator` loop, run *incrementally*
against a stream watermark:

  - events carry simulated time and must arrive in non-decreasing order;
  - an event at time ``T`` first *pumps* the loop — executing every
    arrival-admission / epoch-expiry / finish-departure action whose time
    is strictly before ``T`` — then buffers (arrival) or applies
    (departure/query) itself;
  - arrivals sharing one timestamp therefore accumulate in the buffer and
    are admitted as ONE batch with one scheduling decision when the
    watermark moves past them, exactly like the batch simulator;
  - :meth:`drain` runs the remaining buffered work to a horizon with the
    batch loop verbatim and returns batch-identical :class:`Metrics`.

State updates go through :meth:`FluidNetworkSim.configure_incremental`
(slot deltas + retained water-filling cache; bit-exact vs rebuild), and an
optional prefetch thread warms the CASSINI link cache for the predicted
next epoch while the fluid engine advances — speculation only ever *adds*
pure cache entries, so the authoritative scoring path stays bit-identical
with prefetch on or off.
"""

from __future__ import annotations

import math
import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field, fields

from repro import spans
from repro.cluster.job import Job, JobState
from repro.cluster.network import FluidNetworkSim
from repro.cluster.simulator import Metrics
from repro.cluster.topology import Topology
from repro.sched.base import ClusterState, Decision, Scheduler
from repro.serve.events import (
    JobArrival,
    JobDeparture,
    PlacementView,
    QueryPlacement,
    ServeEvent,
)
from repro.serve.metrics import LatencyRecorder

__all__ = ["SchedulerService", "QueueFullError"]

_EPS = 1e-9


class QueueFullError(RuntimeError):
    """The bounded request queue rejected a submission (backpressure)."""


@dataclass
class _Request:
    event: ServeEvent
    future: Future = field(default_factory=Future)
    t_submit: float = field(default_factory=time.perf_counter)


_SHUTDOWN = object()


class SchedulerService:
    """Long-running scheduling service over the fluid cluster model.

    Construction mirrors :class:`~repro.cluster.ClusterSimulator` (same
    topology / scheduler / epoch semantics) so a served arrival replay is
    decision-for-decision identical to the batch run — the golden
    equivalence pinned by tests/test_serve.py.
    """

    def __init__(
        self,
        topology: Topology,
        scheduler: Scheduler,
        *,
        epoch_ms: float = 600_000.0,
        compute_jitter: float = 0.0,
        migration_pause_ms: float = 1000.0,
        congested_efficiency: float = 0.88,
        vectorized: bool = True,
        incremental: bool = False,
        sharded: bool = False,
        seed: int = 0,
        queue_size: int = 1024,
        submit_timeout_s: float | None = None,
        prefetch: bool = True,
        start: bool = True,
        fault_schedule=None,
        fallback: bool = True,
        realign_timeout_ms: float | None = None,
    ) -> None:
        self.topo = topology
        self.scheduler = scheduler
        self.epoch_ms = epoch_ms
        self.net = FluidNetworkSim(
            topology,
            compute_jitter=compute_jitter,
            migration_pause_ms=migration_pause_ms,
            congested_efficiency=congested_efficiency,
            vectorized=vectorized,
            incremental=incremental,
            sharded=sharded,
            seed=seed,
        )
        # optional repro.chaos.FaultSchedule replayed against the embedded
        # loop at exactly the batch simulator's injection point
        self.fault_schedule = fault_schedule
        self._chaos = None
        if fault_schedule is not None and not fault_schedule.empty:
            from repro.chaos.inject import FaultInjector

            self._chaos = FaultInjector(self.net, fault_schedule)
        # graceful degradation: on pipeline exception or a decision that
        # exceeds realign_timeout_ms, fall back to the host scheduler's
        # placement (counted as degraded_decisions) instead of killing the
        # worker; the next trigger retries the full pipeline, so one bad
        # epoch degrades one decision, not the service
        self.fallback = bool(fallback)
        self.realign_timeout_ms = realign_timeout_ms
        self._host = getattr(scheduler, "host", None)
        self.decisions: list[tuple[float, Decision]] = []
        self.metrics = LatencyRecorder()
        self.submit_timeout_s = submit_timeout_s
        # scheduling state (owned by the worker thread once started)
        self._arrivals: list[Job] = []      # buffered, not yet admitted
        self._running: list[Job] = []
        self._done: list[Job] = []
        self._next_epoch = 0.0
        self._watermark = 0.0               # highest event time seen
        # epoch-prefetch: warms the CASSINI link cache on a side thread
        # while the worker advances the fluid engine (pipeline-bearing
        # schedulers only — plain hosts have nothing device-side to warm)
        self._pipeline = getattr(scheduler, "pipeline", None)
        self.prefetch = bool(prefetch and self._pipeline is not None)
        self._prefetch_pool = (
            ThreadPoolExecutor(max_workers=1, thread_name_prefix="serve-prefetch")
            if self.prefetch
            else None
        )
        self._prefetch_future: Future | None = None
        # bounded request queue + worker
        self._queue: queue.Queue[_Request | object] = queue.Queue(
            maxsize=queue_size
        )
        self._worker: threading.Thread | None = None
        # exception that escaped the worker loop itself (not a per-request
        # handler error): stored here and re-raised to the next caller, so
        # a crashed worker fails fast instead of leaving requests queued
        # forever against a silently dead service
        self._worker_exc: BaseException | None = None
        self._closed = False
        if start:
            self.start()

    # ---------------------- lifecycle ----------------------------- #
    def start(self) -> None:
        if self._worker is not None:
            return
        self._worker = threading.Thread(
            target=self._worker_loop, name="serve-worker", daemon=True
        )
        self._worker.start()

    def close(self, timeout_s: float = 10.0) -> None:
        """Stop the worker after the queued requests finish.

        Joins with a timeout so a wedged (or already-crashed) worker can
        never hang shutdown, and is idempotent — including after a worker
        crash, where the queue may be full and the thread already dead.
        """
        if self._closed:
            return
        self._closed = True
        worker = self._worker
        if worker is not None:
            if worker.is_alive():
                try:
                    # a crashed worker stops consuming: don't block forever
                    # trying to hand it the shutdown sentinel
                    self._queue.put(_SHUTDOWN, timeout=timeout_s)
                except queue.Full:
                    pass
            worker.join(timeout=timeout_s)
            if worker.is_alive():
                raise RuntimeError(
                    f"serve worker did not stop within {timeout_s}s"
                )
            self._worker = None
        self._join_prefetch()
        if self._prefetch_pool is not None:
            self._prefetch_pool.shutdown(wait=True)

    def __enter__(self) -> "SchedulerService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---------------------- client API ---------------------------- #
    def submit(self, event: ServeEvent) -> Future:
        """Enqueue one event; returns a Future with the handler's result.

        Raises :class:`QueueFullError` when the bounded queue stays full
        past ``submit_timeout_s`` (no timeout → immediate rejection).
        """
        if self._closed:
            raise RuntimeError("service is closed")
        self._check_worker()
        req = _Request(event=event)
        try:
            if self.submit_timeout_s is None:
                self._queue.put_nowait(req)
            else:
                self._queue.put(req, timeout=self.submit_timeout_s)
        except queue.Full:
            self.metrics.count("queue_rejected")
            raise QueueFullError(
                f"request queue full ({self._queue.maxsize} pending)"
            ) from None
        self.metrics.gauge("queue_depth", self._queue.qsize())
        return req.future

    def query(
        self, job_id: str | None = None, at_ms: float | None = None
    ) -> PlacementView:
        """Synchronous :class:`QueryPlacement` (submit + wait)."""
        return self.submit(QueryPlacement(job_id=job_id, at_ms=at_ms)).result()

    def drain(self, horizon_ms: float) -> Metrics:
        """Process queued events, then run everything to ``horizon_ms``
        with batch-loop semantics; returns batch-identical Metrics."""
        self._check_worker()
        fut: Future = Future()
        req = _Request(event=("__drain__", horizon_ms))  # type: ignore[arg-type]
        req.future = fut
        self._queue.put(req)
        return fut.result()

    def _check_worker(self) -> None:
        """Fail fast once the worker loop has died (vs hanging forever on
        a Future no thread will ever resolve)."""
        if self._worker_exc is not None:
            raise RuntimeError(
                "serve worker crashed; service is dead"
            ) from self._worker_exc

    def telemetry(self) -> dict[str, float]:
        """Latency percentiles + counters + cache telemetry, one flat dict.

        Never raises: this is what an operator polls *during* an incident,
        so a half-broken scheduler/module must degrade to fewer keys, not
        to a stack trace (the core snapshot itself is total — see
        ``LatencyRecorder.snapshot``).
        """
        out = self.metrics.snapshot()
        try:
            out["alloc_cache_solves"] = float(self.net.alloc_solves)
            out["alloc_cache_hits"] = float(self.net.alloc_hits)
        except Exception:  # pragma: no cover - defensive
            pass
        try:
            module = getattr(self.scheduler, "module", None)
            if module is not None:
                out["link_cache_hits"] = float(module.cache_hits)
                out["link_cache_misses"] = float(module.cache_misses)
                totals = module.batch_totals
                for f in fields(totals):
                    out[f"batch_{f.name}"] = float(getattr(totals, f.name))
        except Exception:  # pragma: no cover - defensive
            pass
        out["decisions"] = float(len(self.decisions))
        # always present, even before the first fallback, so dashboards
        # and the never-dies acceptance test can key on it unconditionally
        out.setdefault("degraded_decisions", 0.0)
        if self._chaos is not None:
            out["faults_applied"] = float(self._chaos.applied_count)
            out["faults_skipped"] = float(self._chaos.skipped)
        return out

    # ---------------------- worker -------------------------------- #
    def _worker_loop(self) -> None:
        try:
            while True:
                item = self._queue.get()
                if item is _SHUTDOWN:
                    break
                req: _Request = item  # type: ignore[assignment]
                kind = (
                    req.event[0].strip("_")
                    if isinstance(req.event, tuple)
                    else type(req.event).__name__
                )
                try:
                    result = self._handle(req.event)
                except BaseException as exc:  # propagate to the caller
                    req.future.set_exception(exc)
                    self.metrics.count(f"{kind}_errors")
                else:
                    req.future.set_result(result)
                    self.metrics.observe(
                        kind, (time.perf_counter() - req.t_submit) * 1e3
                    )
        except BaseException as exc:
            # anything escaping the loop body itself (result delivery,
            # telemetry, queue internals) kills the worker: record it so
            # submit/drain re-raise instead of enqueueing into a void, and
            # fail whatever is already queued so no caller blocks forever
            self._worker_exc = exc
            self.metrics.count("worker_crashed")
            while True:
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    break
                if isinstance(item, _Request) and not item.future.done():
                    item.future.set_exception(
                        RuntimeError("serve worker crashed")
                    )

    def _handle(self, event):
        if isinstance(event, tuple) and event[0] == "__drain__":
            return self._drain(event[1])
        if isinstance(event, JobArrival):
            return self._handle_arrival(event)
        if isinstance(event, JobDeparture):
            return self._handle_departure(event)
        if isinstance(event, QueryPlacement):
            return self._handle_query(event)
        raise TypeError(f"unknown serve event {type(event).__name__}")

    # ---------------------- event handlers ------------------------ #
    def _check_watermark(self, at_ms: float) -> None:
        if at_ms < self._watermark - _EPS:
            raise ValueError(
                f"event at t={at_ms} ms behind the stream watermark "
                f"({self._watermark} ms); events must arrive in "
                "non-decreasing time order"
            )
        self._watermark = max(self._watermark, at_ms)

    def _handle_arrival(self, ev: JobArrival) -> None:
        self._check_watermark(ev.at_ms)
        # everything strictly before this arrival is now decidable
        self._pump(ev.at_ms)
        self._arrivals.append(ev.job)

    def _handle_departure(self, ev: JobDeparture) -> None:
        self._check_watermark(ev.at_ms)
        self._pump(ev.at_ms)
        for i, job in enumerate(self._arrivals):
            if job.job_id == ev.job_id:  # cancelled before admission
                self._arrivals.pop(i)
                self._done.append(job)
                return
        for job in self._running:
            if job.job_id == ev.job_id:
                self._running.remove(job)
                # stopped without finishing: same lifecycle terminal the
                # batch horizon cutoff uses (finish_ms/jct stay None)
                job.state = JobState.CUTOFF
                self._done.append(job)
                # departure-triggered re-placement, like a finish
                self._reschedule(self.net.now_ms, "departure")
                return
        raise KeyError(f"job {ev.job_id!r} is not queued or running")

    def _handle_query(self, ev: QueryPlacement) -> PlacementView:
        if ev.at_ms is not None:
            self._check_watermark(ev.at_ms)
            self._pump(ev.at_ms)
        jobs = self._running if ev.job_id is None else [
            j for j in self._running + self._arrivals + self._done
            if j.job_id == ev.job_id
        ]
        if ev.job_id is not None and not jobs:
            raise KeyError(f"unknown job {ev.job_id!r}")
        return PlacementView(
            placements={j.job_id: tuple(j.placement) for j in jobs},
            shifts_ms={j.job_id: j.alignment.shift_ms for j in jobs},
            states={j.job_id: j.state for j in jobs},
            as_of_ms=self.net.now_ms,
        )

    # ---------------------- embedded event loop ------------------- #
    # This is ClusterSimulator.run's loop body.  _pump runs it with a
    # *deferral bound*: an action at or beyond the bound (within the batch
    # loop's 1e-9 tie window) is left for a later pump, so same-timestamp
    # arrival batches stay whole and the fluid clock advances in exactly
    # the steps the batch run takes (two-phase advances would change float
    # accumulation).  _drain runs it verbatim to a horizon.
    def _loop(self, bound_ms: float, *, defer: bool) -> None:
        net = self.net
        chaos = self._chaos
        while (self._arrivals or self._running) and net.now_ms < bound_ms:
            now = net.now_ms
            t_arrival = (
                self._arrivals[0].arrival_ms if self._arrivals else math.inf
            )
            t_fault = chaos.next_ms if chaos is not None else math.inf
            if defer and (
                min(t_arrival, self._next_epoch, t_fault) >= bound_ms - _EPS
            ):
                break
            t_event = min(t_arrival, self._next_epoch, t_fault, bound_ms)

            if t_event > now:
                finished = net.advance(t_event)
                if finished:
                    for job in finished:
                        self._running.remove(job)
                        self._done.append(job)
                    self._reschedule(net.now_ms, "departure")
                    continue
            now = net.now_ms
            if chaos is not None and now >= chaos.next_ms - _EPS:
                # same injection point (and same same-instant arrival
                # suppression) as ClusterSimulator.run — replay parity
                if chaos.apply_due(now, self._running) and not (
                    self._arrivals
                    and self._arrivals[0].arrival_ms <= now + _EPS
                ):
                    self._reschedule(now, "fault")
            if self._arrivals and now >= self._arrivals[0].arrival_ms - _EPS:
                while (
                    self._arrivals
                    and self._arrivals[0].arrival_ms <= now + _EPS
                ):
                    self._running.append(self._arrivals.pop(0))
                self._reschedule(now, "arrival")
            if now >= self._next_epoch - _EPS:
                self._next_epoch = now + self.epoch_ms
                if not (
                    self._arrivals
                    and self._arrivals[0].arrival_ms <= now + _EPS
                ):
                    self._reschedule(now, "epoch")

    def _pump(self, watermark_ms: float) -> None:
        self._loop(watermark_ms, defer=True)

    def _drain(self, horizon_ms: float) -> Metrics:
        self._loop(horizon_ms, defer=False)
        self._join_prefetch()
        for job in self._running:  # cut off like the batch horizon does
            if job.state == JobState.RUNNING:
                job.state = JobState.CUTOFF
        return Metrics(jobs=self._done + self._running)

    # ---------------------- scheduling ---------------------------- #
    def _reschedule(self, now: float, trigger: str) -> None:
        # the root span of one decision: every span under it, the
        # prefetch it launches included, carries this decision's index
        with spans.span("serve/reschedule", decision=len(self.decisions),
                        trigger=trigger):
            self._join_prefetch()  # the pipeline/module is single-consumer
            state = ClusterState(
                topology=self.topo, now_ms=now, running=list(self._running),
                pending=[],
            )
            t0 = time.perf_counter()
            decision = self._decide(state)
            self.metrics.observe("schedule", (time.perf_counter() - t0) * 1e3)
            self.metrics.count(f"reschedule_{trigger}")
            self.decisions.append((now, decision))
            placed: list[Job] = []
            for job in self._running:
                servers = decision.placements.get(job.job_id, ())
                if servers:
                    job.placement = tuple(servers)
                    job.state = JobState.RUNNING
                    directive = (
                        decision.plan.directive_for(job.job_id)
                        if decision.plan is not None
                        else None
                    )
                    if directive is not None:
                        job.apply_directive(directive)
                    else:
                        job.clear_directive()
                    placed.append(job)
                else:
                    job.placement = ()
                    job.state = JobState.PENDING  # queued: no GPUs this epoch
            mode = self.net.configure_incremental(placed)
            self.metrics.count(f"configure_{mode}")
            self._maybe_prefetch()

    def _decide(self, state: ClusterState) -> Decision:
        """One scheduling decision, degrading gracefully when allowed.

        The fallback state machine is stateless by design: HEALTHY on
        every call; a pipeline exception or a decision slower than
        ``realign_timeout_ms`` degrades *this* decision to the host
        scheduler's placement (or, with no host, to freezing the current
        placements) and the very next trigger retries the full CASSINI
        pipeline — recovery needs no operator action and no reset, just
        one healthy epoch.
        """
        if not self.fallback:
            return self.scheduler.schedule(state)
        t0 = time.perf_counter()
        decision: Decision | None
        try:
            decision = self.scheduler.schedule(state)
        except Exception:
            self.metrics.count("pipeline_errors")
            decision = None
        if (
            decision is not None
            and self.realign_timeout_ms is not None
            and (time.perf_counter() - t0) * 1e3 > self.realign_timeout_ms
        ):
            # the decision arrived, but after the re-alignment budget: a
            # real deployment has already had to act, so act like it did —
            # discard the stale plan and take the host placement now
            self.metrics.count("realign_timeouts")
            decision = None
        if decision is None:
            decision = self._fallback_decision(state)
        return decision

    def _fallback_decision(self, state: ClusterState) -> Decision:
        """Degraded-mode decision: host scheduler, else freeze in place."""
        self.metrics.count("degraded_decisions")
        if self._host is not None:
            try:
                return self._host.schedule(state)
            except Exception:
                self.metrics.count("fallback_errors")
        # last resort (host also failing, or no host to fall back to):
        # keep every placed job exactly where it is, no new directives
        return Decision(
            placements={
                j.job_id: tuple(j.placement)
                for j in state.running
                if j.placement
            }
        )

    # ---------------------- epoch prefetch ------------------------ #
    def _maybe_prefetch(self) -> None:
        """Speculatively score the predicted next-epoch candidate grids.

        Runs Allocate → Propose → Score for the state the next epoch-expiry
        reschedule would see (same running set, ``now = next epoch``) on a
        side thread, so the ragged ``circle_score`` launches execute on
        device while the worker advances the fluid engine / applies the
        current alignment.  The value is the *link cache* it fills: the
        authoritative reschedule always re-runs Score itself and simply
        hits the warmed entries (CompatResults are pure functions of the
        link problem), so a wrong prediction — membership changed, an
        arrival preempted the epoch — costs only wasted device work and
        can never alter a decision.
        """
        if not self.prefetch:
            return
        pipeline = self._pipeline
        pred_now = self._next_epoch
        pred_running = list(self._running)
        launcher = spans.current()

        def warm():
            with spans.span("prefetch/warm", parent=launcher):
                st = ClusterState(
                    topology=self.topo, now_ms=pred_now, running=pred_running,
                    pending=[],
                )
                out = None
                for stage in pipeline.stages[:-1]:  # Allocate, Propose, Score
                    out = stage.run(st, out)
                return out

        self._prefetch_future = self._prefetch_pool.submit(warm)
        self.metrics.count("prefetch_launched")

    def _join_prefetch(self) -> None:
        fut = self._prefetch_future
        if fut is None:
            return
        self._prefetch_future = None
        try:
            fut.result()
        except Exception:
            # speculation is best-effort; the real pass recomputes anyway
            self.metrics.count("prefetch_errors")
