"""Event-driven fluid model of the cluster fabric.

Each running job executes a cyclic sequence of *segments* derived from its
:class:`~repro.core.circle.CommPattern`:

  - **compute** segments advance in wall-clock time unconditionally,
  - **comm** segments carry a fixed number of Gbits at a demand cap
    (the phase's Gbps); their *achieved* rate is the job's max-min-fair
    share across every link it traverses.

Between events (segment completions / scheduler epochs) all rates are
constant, so the simulator jumps directly to the next completion — an exact
fluid solution, not a time-stepped approximation.  Congestion therefore
manifests exactly as in the paper: jobs whose Up phases collide on a link
get a fraction of the link and their iterations stretch; CASSINI's
time-shifts (applied as one-shot delays before the next iteration) move the
phases apart and restore full-rate communication.

ECN marking model: whenever aggregate *demand* on a link exceeds capacity,
marks accrue at ``ecn_marks_per_gbit`` × excess-bits, attributed to the
jobs on the link in proportion to their demand — the macroscopic behaviour
of DCQCN/WRED marking in the paper's testbed (§5.1).

Two engines share these semantics bit for bit:

  - the **scalar oracle** (``vectorized=False``): the original pure-Python
    dict-of-dicts progressive-filling loop, re-run at every event — kept
    as the reference the vectorized engine is equivalence-tested against;
  - the **vectorized engine** (``vectorized=True``, the default): job and
    link state lives in numpy arrays keyed by the job×link incidence the
    topology precomputes at ``configure`` (never per event); the max-min
    allocation + ECN marking are solved with vectorized water-filling once
    per *distinct comm-competing set* and cached (segment transitions of
    compute-only jobs hit the cache), and ``advance`` steps every job's
    delay/remaining/marks with batched array updates.  Every float is
    produced by the same IEEE operation in the same order as the scalar
    loop, so rates, event sequences and ``Metrics.summary()`` are
    *identical* — not merely close (tests/test_fluid_vectorized.py).
"""

from __future__ import annotations

import math
import random
import time
from dataclasses import dataclass

import numpy as np

from repro import spans
from repro.cluster.errors import UnknownJobError
from repro.cluster.job import Job, JobState
from repro.cluster.shard import MIN_COMPONENTS as _SHARD_MIN_COMPONENTS
from repro.cluster.shard import ShardStats, batched_fill
from repro.cluster.topology import Link, LinkIncidence, Topology
from repro.core.circle import CommPattern

__all__ = ["Segment", "segments_from_pattern", "FluidNetworkSim"]

# Distinct comm-competing sets cached between two ``configure`` calls are
# bounded in practice (jobs cycle through few segments); this cap only
# guards pathological drift from unbounded memory growth.
_ALLOC_CACHE_MAX = 4096

# Delta solves between from-scratch rebuilds of the incremental solver's
# per-link demand accumulators: bounds float drift from repeated ± deltas
# (each rebuild resets demand to one exact left-to-right bincount sum).
_WF_REFRESH = 64

_EPS = 1e-9


@dataclass
class Segment:
    """One piecewise-constant piece of a job's iteration cycle."""

    kind: str          # "compute" | "comm"
    duration_ms: float # compute: wall time; comm: duration at full demand
    gbps: float = 0.0  # comm demand cap

    @property
    def gbits(self) -> float:
        return self.gbps * self.duration_ms * 1e-3


def segments_from_pattern(pattern: CommPattern) -> list[Segment]:
    """Convert a (possibly overlapping-phase) pattern into alternating
    compute/comm segments with piecewise-constant demand.

    The segments **exactly tile** ``[0, iter_time_ms)``: every cut interval
    contributes its full width to some segment.  Sub-``_EPS`` sliver
    intervals (nearly-coincident cut points from wrapped/overlapping
    phases) are folded into the neighbouring segment's duration instead of
    being dropped — the conservation error of billing a sliver at its
    neighbour's demand level is at most ``gbps × _EPS`` Gbit, while
    dropping it used to leave a tiling gap that desynchronized iteration
    boundaries from ``iter_time_ms`` (tests/test_segments.py pins both the
    tiling and the Gbit-conservation invariants).
    """
    t = pattern.iter_time_ms
    points = {0.0, t}
    for ph in pattern.phases:
        points.add(ph.start_ms % t)
        points.add(min((ph.start_ms % t) + ph.duration_ms, t))
        if (ph.start_ms % t) + ph.duration_ms > t:  # wrapped phase
            points.add(((ph.start_ms % t) + ph.duration_ms) % t)
    cuts = sorted(points)
    segs: list[Segment] = []
    carry = 0.0  # sliver width owed to the next emitted segment
    for a, b in zip(cuts, cuts[1:]):
        if b - a < _EPS:
            # sliver: fold its width into a neighbour, never drop it
            if segs:
                segs[-1].duration_ms += b - a
            else:
                carry += b - a
            continue
        mid = 0.5 * (a + b)
        level = float(pattern.demand_at(mid))
        kind = "comm" if level > _EPS else "compute"
        gbps = level if kind == "comm" else 0.0
        width = (b - a) + carry
        carry = 0.0
        if segs and segs[-1].kind == kind and (segs[-1].gbps - gbps) == 0.0:
            segs[-1].duration_ms += width
        elif kind == "comm":
            segs.append(Segment("comm", width, gbps))
        else:
            segs.append(Segment("compute", width))
    if carry:
        if segs:
            segs[-1].duration_ms += carry
        else:
            segs.append(Segment("compute", carry))
    if not segs:
        segs.append(Segment("compute", t))
    return segs


# ---------------------------------------------------------------------- #
@dataclass
class _JobExec:
    """Mutable execution state of one running job."""

    job: Job
    segments: list[Segment]
    links: list[Link]
    seg_idx: int = 0
    remaining: float = 0.0        # compute: ms left; comm: Gbit left
    delay_ms: float = 0.0         # one-shot delay before next segment runs
    iter_start_ms: float = 0.0
    marks: float = 0.0            # ECN marks accumulated this iteration
    # CASSINI drift-adjustment agent (paper §4.2 step 3, §5.7):
    solo_iter_ms: float = 0.0
    paced_iter_ms: float = 0.0          # isochronous grid period (≥ solo)
    ideal_next_ms: float | None = None  # armed only for aligned jobs
    applied_shift_ms: float = 0.0       # shift already realized by delays
    consec_adjust: int = 0              # disarm guard
    skip_record: bool = False           # one-shot setup delay in this iter

    def reset_segment(self) -> None:
        seg = self.segments[self.seg_idx]
        self.remaining = (
            seg.duration_ms if self.kind == "compute" or not self.links
            else seg.gbits
        )

    @property
    def kind(self) -> str:
        return self.segments[self.seg_idx].kind

    @property
    def cap_gbps(self) -> float:
        return self.segments[self.seg_idx].gbps


class FluidNetworkSim:
    """Exact event-driven fluid simulation of jobs sharing the fabric."""

    def __init__(
        self,
        topology: Topology,
        *,
        ecn_marks_per_gbit: float = 1000.0,
        compute_jitter: float = 0.0,
        migration_pause_ms: float = 1000.0,
        drift_tolerance: float = 0.05,
        congested_efficiency: float = 0.88,
        vectorized: bool = True,
        incremental: bool = False,
        sharded: bool = False,
        seed: int = 0,
    ) -> None:
        # DCQCN under congestion does not achieve the full link rate: the
        # paper's own Fig. 2(b) measures two competing jobs at ~22 Gbps each
        # on a 50 Gbps link (~88 %).  When aggregate demand exceeds capacity
        # the contended link delivers capacity × this factor.
        self.congested_efficiency = congested_efficiency
        self.topo = topology
        self.drift_tolerance = drift_tolerance
        self.ecn_marks_per_gbit = ecn_marks_per_gbit
        self.compute_jitter = compute_jitter
        self.migration_pause_ms = migration_pause_ms
        self._rng = random.Random(seed)
        self.now_ms: float = 0.0
        self._execs: dict[str, _JobExec] = {}
        self.vectorized = vectorized
        # incremental water-filling re-solve (256+-rack fabrics): cache
        # misses delta-update per-link demand/live state from the previous
        # solve and fill only the links that can actually saturate.  Rates
        # then match the scalar oracle within documented tolerance bands
        # rather than bit-exactly; the default (False) keeps the bit-exact
        # from-scratch solve.  Meaningful only on the vectorized engine.
        self.incremental = bool(incremental and vectorized)
        # device-sharded component fills (repro.cluster.shard): dirty
        # components batch into bucketed vmap fills split across
        # jax.devices() with shard_map instead of one fused host fill.
        # Rides on the incremental path's component decomposition, so it
        # is meaningful only with incremental=True; results stay inside
        # the same documented tolerance band.
        self.sharded = bool(sharded and self.incremental)
        # test hook: force the device count seen by the sharded fill
        # (None → len(jax.devices())); the device-count-invariance tests
        # pin that decisions do not depend on this value
        self._shard_devices: int | None = None
        self.shard_stats = ShardStats()
        # telemetry: how many allocations were actually *solved* (cache
        # misses) on the vectorized path — the invalidation tests pin that
        # compute-only segment churn does not grow this — and how many
        # were answered from the cache (serve-mode telemetry)
        self.alloc_solves: int = 0
        self.alloc_hits: int = 0
        # optional psim-style per-link load telemetry (repro.cluster
        # .linkload): None costs nothing; attach_link_recorder wires one
        # into the vectorized event loop
        self.link_recorder = None
        # telemetry: solves answered by the delta path (vs from-scratch
        # state rebuilds within the incremental solver)
        self.alloc_delta_solves: int = 0
        # incremental link-state (see _solve_alloc_incremental)
        self._wf: dict | None = None
        # link ids whose capacity changed since the last incremental solve
        # (fault injection): fed into _wf_delta as extra dirty links so the
        # affected components re-fill against the new capacities
        self._wf_cap_dirty: set[int] = set()
        # array-resident engine state, rebuilt by _build_arrays on configure
        self._slots: list[_JobExec] = []
        self._slot_of: dict[str, int] = {}
        self._inc: LinkIncidence | None = None
        self._alloc_cache: dict[bytes, tuple[np.ndarray, np.ndarray]] = {}
        # while a fluid/advance span is open: host ns spent solving its
        # alloc-cache misses (None otherwise, so the miss path stays as is)
        self._solve_ns: int | None = None
        # event steps of the last advance call
        self._last_events = 0
        self._rem = np.zeros(0)
        self._dly = np.zeros(0)
        self._mk = np.zeros(0)
        self._cap_now = np.zeros(0)
        self._segi = np.zeros(0, dtype=np.int32)
        self._is_comm = np.zeros(0, dtype=bool)
        self._alive = np.zeros(0, dtype=bool)

    # -------------------------------------------------------------- #
    def _exec_for(self, job: Job) -> _JobExec:
        """Build job's execution state for this epoch (reading the *current*
        ``_execs`` for its previous state).  Shared verbatim by the
        rebuild (:meth:`configure`) and delta (:meth:`add_job` /
        :meth:`update_job`) paths, so both produce identical execs."""
        pattern = job.pattern()
        segs = segments_from_pattern(pattern)
        links = self.topo.job_links(job.placement)
        prev = self._execs.get(job.job_id)
        align = job.alignment
        ex = _JobExec(
            job=job, segments=segs, links=links,
            solo_iter_ms=pattern.iter_time_ms,
            paced_iter_ms=align.paced_period_ms or pattern.iter_time_ms,
        )
        # a changed segment structure (elastic resize: same placement, new
        # worker count → new pattern) is a remesh: checkpoint-restore like
        # a migration, restarting the cycle at segment 0 — stale seg_idx /
        # remaining from the old segment list would be meaningless
        migrated = prev is not None and (
            prev.links != links or prev.segments != segs
        )
        if prev is None or migrated:
            ex.delay_ms = (self.migration_pause_ms if migrated else 0.0)
            ex.delay_ms += align.shift_ms
            ex.applied_shift_ms = align.shift_ms
            ex.iter_start_ms = self.now_ms
            ex.seg_idx = 0
            ex.reset_segment()
            # the migration pause / initial shift is a one-shot setup
            # cost, not an iteration time: exclude it from the CDF
            ex.skip_record = ex.delay_ms > _EPS
            if align.hold:
                ex.ideal_next_ms = self.now_ms + ex.delay_ms + ex.paced_iter_ms
        else:
            # same placement: keep mid-iteration progress.  A shift from
            # this epoch's decision is applied as the *delta* against the
            # shift this worker has already realized (re-sending the same
            # shift must be a no-op).
            ex.seg_idx = prev.seg_idx
            ex.remaining = prev.remaining
            ex.iter_start_ms = prev.iter_start_ms
            ex.marks = prev.marks
            ex.delay_ms = prev.delay_ms
            ex.applied_shift_ms = prev.applied_shift_ms
            ex.ideal_next_ms = prev.ideal_next_ms
            ex.consec_adjust = prev.consec_adjust
            ex.skip_record = prev.skip_record
            if job.shift_pending:
                delta = (align.shift_ms - prev.applied_shift_ms) % ex.solo_iter_ms
                if delta > _EPS and (ex.solo_iter_ms - delta) > _EPS:
                    ex.delay_ms += delta
                    ex.skip_record = True
                    if ex.ideal_next_ms is not None:
                        ex.ideal_next_ms += delta
                ex.applied_shift_ms = align.shift_ms
            # (re)arm / disarm the alignment agent (§5.7)
            if align.hold and ex.ideal_next_ms is None:
                ex.ideal_next_ms = ex.iter_start_ms + ex.delay_ms + ex.paced_iter_ms
                ex.consec_adjust = 0
            elif not align.hold:
                ex.ideal_next_ms = None
        return ex

    @staticmethod
    def _admit(job: Job, now_ms: float) -> None:
        """Per-job bookkeeping every (re)configuration path performs."""
        job.shift_pending = False
        if job.start_ms is None:
            job.start_ms = now_ms

    def configure(self, jobs: list[Job]) -> None:
        """(Re)configure the running set after a scheduling decision.

        Jobs keep their identity across epochs; a job whose placement
        changed pays ``migration_pause_ms`` (checkpoint-restore) and every
        job (re)starts its cycle at its (new) time-shift delay.  All CASSINI
        inputs come off the job's typed ``alignment`` directive
        (:class:`repro.engine.plan.JobAlignment`): the cumulative shift
        target, whether the pacing agent holds the isochronous grid, and
        the grid period.

        This is the *rebuild* path: array state and the water-filling
        cache are reconstructed from scratch.  Serve mode goes through
        :meth:`configure_incremental`, which applies the same per-job
        logic as slot-level deltas whenever the membership diff allows.
        """
        new: dict[str, _JobExec] = {}
        for job in jobs:
            ex = self._exec_for(job)
            self._admit(job, self.now_ms)
            new[job.job_id] = ex
        self._execs = new
        if self.vectorized:
            self._build_arrays()

    # ---------------------- delta configuration ------------------- #
    # Serve-mode arrivals/departures touch one job while the other
    # n-1 keep running; rebuilding every array (and discarding the
    # water-filling cache) per event is what makes batch reconfiguration
    # O(cluster) per arrival.  The delta ops below touch only the affected
    # slot and *keep* the allocation cache, which stays sound because a
    # cache key is (comm-membership bytes, per-member segment bytes) over
    # the current slot axis:
    #
    #   * ``remove_job`` only clears the slot's alive bit — keys where the
    #     slot was a comm member can never be produced again, keys where
    #     it was not remain exactly as valid;
    #   * ``add_job`` appends a slot, so every new key's membership mask is
    #     one byte longer — old entries become unreachable (never wrong),
    #     since a (mask, int32-segments) encoding can never collide with
    #     one whose mask length differs by 1 (4·k' − 4·k = 1 is unsolvable);
    #   * ``update_job`` with an unchanged placement alters only
    #     delay/alignment state, which enters the solve through the
    #     membership mask itself; a changed placement (in-place migration)
    #     rewrites the slot's link columns, which ARE invisible to the key —
    #     that one case clears the cache.
    #
    # Dead slots accumulated by departures are compacted (full rebuild)
    # once they outnumber the live ones, bounding memory.
    def add_job(self, job: Job) -> None:
        """Admit one arriving job without rebuilding the running set.

        Bit-exact against ``configure(previous jobs + [job])``
        (tests/test_serve_incremental.py pins state and trace parity).
        """
        if job.job_id in self._execs:
            raise ValueError(f"job {job.job_id!r} already configured")
        ex = self._exec_for(job)
        self._admit(job, self.now_ms)
        self._execs[job.job_id] = ex
        if not self.vectorized:
            return
        live = int(np.count_nonzero(self._alive))
        if self._inc is None or len(self._slots) - live >= max(8, live):
            self._build_arrays()  # first build / compact dead slots
            return
        i = len(self._slots)
        self._slots.append(ex)
        self._slot_of[job.job_id] = i
        cols = self.topo.job_link_ids(job.placement)
        self._inc = self._inc.with_row(cols)
        self._rem = np.append(self._rem, ex.remaining)
        self._dly = np.append(self._dly, ex.delay_ms)
        self._mk = np.append(self._mk, ex.marks)
        self._cap_now = np.append(self._cap_now, 0.0)
        self._segi = np.append(self._segi, np.int32(0))
        self._is_comm = np.append(self._is_comm, False)
        self._alive = np.append(self._alive, True)
        # the incremental solver's link-state is per-slot: the new slot
        # axis invalidates it (rebuilt from scratch at the next solve)
        self._wf = None
        self._sync_seg(i, ex)

    def remove_job(self, job_id: str) -> Job:
        """Retire one departing job without rebuilding the running set."""
        try:
            ex = self._execs.pop(job_id)
        except KeyError:
            raise UnknownJobError(job_id, self._execs) from None
        if self.vectorized:
            self._alive[self._slot_of.pop(job_id)] = False
        return ex.job

    def update_job(self, job: Job) -> None:
        """Re-apply one running job's epoch decision (directive / placement)
        in place — the per-job logic of :meth:`configure` on a single slot."""
        old = self._execs.get(job.job_id)
        if old is None:
            raise UnknownJobError(job.job_id, self._execs)
        ex = self._exec_for(job)
        migrated = ex.links != old.links
        # elastic resize with an unchanged placement: the link columns keep
        # the cache keys valid, but the new segment list changes the demand
        # the same (mask, segment-index) key now encodes
        resized = ex.segments != old.segments
        self._admit(job, self.now_ms)
        self._execs[job.job_id] = ex  # overwrite keeps dict position
        if not self.vectorized:
            return
        i = self._slot_of[job.job_id]
        self._slots[i] = ex
        self._rem[i] = ex.remaining
        self._dly[i] = ex.delay_ms
        self._mk[i] = ex.marks
        self._sync_seg(i, ex)
        if migrated:
            # the slot's link columns change under the cache keys' feet
            cols = self.topo.job_link_ids(job.placement)
            self._inc = self._inc.replace_row(i, cols)
        if migrated or resized:
            # either way the cached rates no longer describe this slot:
            # drop the cache (and the incremental solver's per-link
            # demand/live state with it)
            self._alloc_cache.clear()
            self._wf = None

    def configure_incremental(self, jobs: list[Job]) -> str:
        """Apply an epoch decision as slot deltas when the membership diff
        allows, falling back to the full rebuild otherwise.

        The delta form requires the new running order to be reachable by
        departures + in-place updates + appended arrivals (surviving jobs
        in their current relative order, new jobs at the end) — exactly
        what arrival/departure-triggered decisions produce.  A decision
        that *reorders* survivors (e.g. re-admitting a previously starved
        job mid-list) rebuilds, because slot order defines the float
        accumulation order the scalar oracle is matched against.

        Returns ``"delta"`` or ``"rebuild"`` (serve-mode telemetry).
        """
        new_ids = [j.job_id for j in jobs]
        live = list(self._execs)
        new_set = set(new_ids)
        if len(new_set) != len(new_ids):
            raise ValueError("duplicate job ids in decision")
        survivors = [jid for jid in live if jid in new_set]
        expected = survivors + [jid for jid in new_ids if jid not in set(live)]
        if new_ids != expected:
            self.configure(jobs)
            return "rebuild"
        for jid in live:
            if jid not in new_set:
                self.remove_job(jid)
        for job in jobs:
            if job.job_id in self._execs:
                self.update_job(job)
            else:
                self.add_job(job)
        return "delta"

    # ---------------------- fault injection ----------------------- #
    def set_link_capacity(self, name: str, gbps: float) -> float:
        """Mutate one link's capacity mid-simulation; returns the old value.

        The primitive behind ``LinkDown`` (0.0) / ``LinkDegrade`` /
        ``LinkRecover``.  Capacities are deliberately not part of the
        allocation-cache key (they never changed mid-run before faults
        existed), so the cache is dropped; the solvers read capacities
        live, so the next solve — scalar, vectorized, or incremental —
        sees the new value.  The incremental water-filling state is kept:
        the link id is marked dirty and the next delta solve re-fills
        exactly the components the change touches.
        """
        old = self.topo.set_link_capacity(name, gbps)
        self._alloc_cache.clear()
        if self.incremental:
            self._wf_cap_dirty.add(self.topo.link_ids[name])
        return old

    def perturb_job(self, job_id: str, delta_ms: float) -> float:
        """Shift one job's pending segment delay by ``delta_ms``
        (``PhaseJitter``): per-iteration timing perturbation à la psim's
        measured ``deltas``, pushing the job's phase off its aligned slot
        without touching alignment state — the drift-adjustment agent
        (§5.7) sees it exactly like real compute jitter.  Negative deltas
        pull the phase earlier, floored at zero delay.  Returns the new
        delay.  Both engines apply the identical float operation (the
        vectorized mirror and the exec field agree between advances), so
        replays stay bit-identical.
        """
        ex = self._execs.get(job_id)
        if ex is None:
            raise UnknownJobError(job_id, self._execs)
        new = max(0.0, ex.delay_ms + delta_ms)
        ex.delay_ms = new
        if self.vectorized and self._inc is not None:
            self._dly[self._slot_of[job_id]] = new
        return new

    # -------------------------------------------------------------- #
    def _comm_jobs(self) -> dict[str, _JobExec]:
        """Jobs currently competing for link bandwidth: in a comm segment,
        not delayed, and not horizon-expired — a ``JobState.CUTOFF`` job has
        stopped training and must not consume link share or attract marks."""
        return {
            jid: ex
            for jid, ex in self._execs.items()
            if ex.kind == "comm" and ex.delay_ms <= _EPS and ex.links
            and ex.job.state is not JobState.CUTOFF
        }

    def _allocate(self) -> dict[str, float]:
        """Max-min-fair rates (Gbps) for jobs currently in a comm segment,
        respecting per-segment demand caps (progressive filling).

        Dispatches to the cached vectorized solve or the scalar oracle;
        both return the same dict, bit for bit."""
        if self.vectorized:
            comm_mask = self._comm_mask(self._cutoff_mask())
            rates, _, _ = self._cached_solve(comm_mask)
            return {
                self._slots[i].job.job_id: float(rates[i])
                for i in np.nonzero(comm_mask)[0]
            }
        return self._allocate_scalar()

    def _mark_rates(self) -> dict[str, float]:
        """ECN marks per ms for each job (demand-over-capacity model)."""
        if self.vectorized:
            comm_mask = self._comm_mask(self._cutoff_mask())
            _, marks, _ = self._cached_solve(comm_mask)
            return {
                self._slots[i].job.job_id: float(marks[i])
                for i in np.nonzero(comm_mask)[0]
            }
        return self._mark_rates_scalar()

    # ---------------------- scalar oracle ------------------------- #
    def _allocate_scalar(self) -> dict[str, float]:
        """The original per-event progressive-filling loop (the oracle the
        vectorized water-filling is equivalence-tested against)."""
        comm = self._comm_jobs()
        rates = {jid: 0.0 for jid in comm}
        if not comm:
            return rates
        remaining = {}
        users: dict[str, list[str]] = {}
        demand: dict[str, float] = {}
        caps: dict[str, float] = {}
        for jid, ex in comm.items():
            for l in ex.links:
                users.setdefault(l.name, []).append(jid)
                demand[l.name] = demand.get(l.name, 0.0) + ex.cap_gbps
                caps[l.name] = l.capacity_gbps
        for lname, cap in caps.items():
            eff = self.congested_efficiency if demand[lname] > cap + _EPS else 1.0
            remaining[lname] = cap * eff
        unfrozen = set(comm)
        while unfrozen:
            # next increment: smallest of (per-link equal share, cap slack)
            inc = math.inf
            for lname, js in users.items():
                live = [j for j in js if j in unfrozen]
                if live:
                    inc = min(inc, remaining[lname] / len(live))
            for j in unfrozen:
                inc = min(inc, comm[j].cap_gbps - rates[j])
            if inc is math.inf or inc < 0:
                break
            for j in unfrozen:
                rates[j] += inc
            for lname, js in users.items():
                live = sum(1 for j in js if j in unfrozen)
                remaining[lname] -= inc * live
            newly_frozen = {
                j for j in unfrozen if comm[j].cap_gbps - rates[j] <= _EPS
            }
            for lname, js in users.items():
                if remaining[lname] <= _EPS:
                    newly_frozen |= {j for j in js if j in unfrozen}
            if not newly_frozen:
                break
            unfrozen -= newly_frozen
        return rates

    def _mark_rates_scalar(self) -> dict[str, float]:
        """ECN marks per ms for each job (demand-over-capacity model)."""
        comm = self._comm_jobs()
        demand: dict[str, float] = {}
        users: dict[str, list[str]] = {}
        caps: dict[str, float] = {}
        for jid, ex in comm.items():
            for l in ex.links:
                demand[l.name] = demand.get(l.name, 0.0) + ex.cap_gbps
                users.setdefault(l.name, []).append(jid)
                caps[l.name] = l.capacity_gbps
        marks = {jid: 0.0 for jid in comm}
        for lname, d in demand.items():
            excess = d - caps[lname]
            if excess <= 0:
                continue
            for jid in users[lname]:
                share = comm[jid].cap_gbps / d
                # Gbit/ms of excess attributed to this job × marks/Gbit
                marks[jid] += excess * share * 1e-3 * self.ecn_marks_per_gbit
        return marks

    # ---------------------- vectorized engine --------------------- #
    def _build_arrays(self) -> None:
        """Rebuild the array-resident execution state after ``configure``.

        The job×link incidence comes precomputed from the topology (global
        link ids, cached ring walks); everything else is a dense per-slot
        vector.  Slots follow ``_execs`` insertion order — the same order
        every scalar dict iterates — which is what lets the vectorized
        reductions reproduce the oracle's float accumulation exactly.
        """
        self._slots = list(self._execs.values())
        self._slot_of = {
            ex.job.job_id: i for i, ex in enumerate(self._slots)
        }
        n = len(self._slots)
        self._inc = self.topo.incidence(
            [ex.job.placement for ex in self._slots]
        )
        self._rem = np.array([ex.remaining for ex in self._slots], dtype=np.float64)
        self._dly = np.array([ex.delay_ms for ex in self._slots], dtype=np.float64)
        self._mk = np.array([ex.marks for ex in self._slots], dtype=np.float64)
        self._cap_now = np.zeros(n, dtype=np.float64)
        self._segi = np.zeros(n, dtype=np.int32)
        self._is_comm = np.zeros(n, dtype=bool)
        self._alive = np.ones(n, dtype=bool)
        for i, ex in enumerate(self._slots):
            self._sync_seg(i, ex)
        self._alloc_cache.clear()
        self._wf = None

    def _sync_seg(self, i: int, ex: _JobExec) -> None:
        """Refresh slot ``i``'s segment-derived columns (on transition)."""
        seg = ex.segments[ex.seg_idx]
        self._segi[i] = ex.seg_idx
        self._is_comm[i] = seg.kind == "comm" and bool(ex.links)
        self._cap_now[i] = seg.gbps

    def _sync_execs(self) -> None:
        """Write the array state back into the exec objects so callers
        between ``advance`` calls (configure, tests, probes) see current
        values."""
        for i in np.nonzero(self._alive)[0]:
            ex = self._slots[i]
            ex.remaining = float(self._rem[i])
            ex.delay_ms = float(self._dly[i])
            ex.marks = float(self._mk[i])

    def _cutoff_mask(self) -> np.ndarray:
        return np.fromiter(
            (ex.job.state is JobState.CUTOFF for ex in self._slots),
            dtype=bool, count=len(self._slots),
        )

    def _comm_mask(self, cutoff: np.ndarray) -> np.ndarray:
        """Array form of :meth:`_comm_jobs`'s membership rule."""
        return self._alive & self._is_comm & (self._dly <= _EPS) & ~cutoff

    def _cached_solve(
        self, comm_mask: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(rates, mark rates, rate>0 mask) for the comm-competing set.

        Keyed on (membership, per-member segment): the allocation is a
        pure function of *which jobs communicate with which demand cap*,
        so anything else — compute-only jobs advancing through their own
        segments, delays draining, time passing — hits the cache and the
        per-event cost collapses to one dict lookup.
        """
        key = comm_mask.tobytes() + self._segi[comm_mask].tobytes()
        hit = self._alloc_cache.get(key)
        if hit is not None:
            self.alloc_hits += 1
            # LRU touch: re-insertion moves the key to the dict's tail, so
            # eviction below always removes the least-recently-used entry
            self._alloc_cache[key] = self._alloc_cache.pop(key)
        else:
            while len(self._alloc_cache) >= _ALLOC_CACHE_MAX:
                # evict only the LRU entry — a cold scan of fresh comm-sets
                # (256+-rack churn) must not wipe the hot working set
                del self._alloc_cache[next(iter(self._alloc_cache))]
            timed = self._solve_ns is not None
            if timed:
                t0 = time.perf_counter_ns()
            if self.incremental:
                rates, marks = self._solve_alloc_incremental(comm_mask)
            else:
                rates, marks = self._solve_alloc(comm_mask)
            if timed:
                self._solve_ns += time.perf_counter_ns() - t0
            hit = (rates, marks, rates > _EPS)
            self._alloc_cache[key] = hit
            self.alloc_solves += 1
        return hit

    def _solve_alloc(self, comm_mask: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized water-filling + ECN marking over (jobs, links) arrays.

        Produces exactly the scalar oracle's floats: per-link demand
        accumulates through ``np.bincount`` over the job-major flat
        incidence (sequential in input order == the scalar dicts'
        insertion order), every filling round performs the same
        divisions/additions the scalar loop does as whole-array
        operations, and per-membership mark contributions on congested
        links are summed per job in the oracle's demand-dict order (a
        (job, first-seen-rank) lexsort when any job has ≥ 3 congested
        links; ≤ 2-term sums are commutative) — so even multi-link float
        accumulations agree bit for bit.
        """
        n = len(self._slots)
        rates = np.zeros(n, dtype=np.float64)
        marks = np.zeros(n, dtype=np.float64)
        idx = np.nonzero(comm_mask)[0]
        k = idx.size
        if k == 0:
            return rates, marks
        caps_j = self._cap_now[idx]
        # flat (job-major) view of the comm subset's incidence — the CSR
        # gather returns columns in exactly the job-major order the scalar
        # dicts iterate, so the bincount sums below stay bit-exact
        counts = self._inc.counts[idx]
        cols_sub = self._inc.flat_cols(idx)
        job_rep = np.repeat(np.arange(k), counts)
        caps_rep = np.repeat(caps_j, counts)
        nl = self._inc.num_links
        cap_l = self._inc.capacities
        # np.bincount accumulates its weights sequentially in input (job-
        # major) order — the scalar dicts' per-link insertion order — so
        # demand is the oracle's float sum bit for bit
        demand = np.bincount(cols_sub, weights=caps_rep, minlength=nl)
        # progressive filling: one vector op per filling round (links with
        # no comm users keep demand 0 < capacity, so they never bound inc,
        # never saturate and never mark — the global link axis is free).
        # Every unfrozen job has received every increment so far, so all
        # unfrozen rates equal ONE scalar accumulator ``r_cur`` (the same
        # float-add sequence the oracle applies per job), the cap-slack min
        # is (smallest unfrozen cap) − r_cur via a sorted-cap pointer, and
        # jobs freeze at caps_j − r_cur ≤ ε exactly like the oracle's
        # per-job test — the per-job array work drops out of the loop.
        eff = np.where(demand > cap_l + _EPS, self.congested_efficiency, 1.0)
        remaining = cap_l * eff
        r = np.zeros(k, dtype=np.float64)
        unfrozen = np.ones(k, dtype=bool)
        n_unfrozen = k
        r_cur = 0.0
        cap_order = np.argsort(caps_j, kind="stable").tolist()
        caps_list = caps_j.tolist()
        ptr = 0
        # live user counts per link, maintained incrementally as jobs freeze
        # (exact integers — identical to recounting every round)
        live = np.bincount(cols_sub, minlength=nl)
        has = live > 0
        linkbuf = np.empty(nl, dtype=np.float64)
        inf = math.inf
        while n_unfrozen:
            linkbuf.fill(inf)
            np.divide(remaining, live, out=linkbuf, where=has)
            inc = float(linkbuf.min()) if nl else inf
            while ptr < k and not unfrozen[cap_order[ptr]]:
                ptr += 1
            if ptr < k:
                inc = min(inc, caps_list[cap_order[ptr]] - r_cur)
            if inc == inf or inc < 0:
                break
            r_cur += inc
            remaining -= inc * live
            newly = np.zeros(k, dtype=bool)
            any_newly = False
            while ptr < k and caps_list[cap_order[ptr]] - r_cur <= _EPS:
                j = cap_order[ptr]
                if unfrozen[j]:
                    newly[j] = True
                    any_newly = True
                ptr += 1
            sat = remaining <= _EPS
            if sat.any():
                sat_jobs = np.zeros(k, dtype=bool)
                sat_jobs[job_rep[sat[cols_sub]]] = True
                newly |= unfrozen & sat_jobs
                any_newly = any_newly or bool(newly.any())
            if not any_newly:
                break
            r[newly] = r_cur
            unfrozen &= ~newly
            n_unfrozen = int(np.count_nonzero(unfrozen))
            live -= np.bincount(cols_sub[newly[job_rep]], minlength=nl)
            has = live > 0
        r[unfrozen] = r_cur
        rates[idx] = r
        # ECN marking: per-membership contributions on congested links,
        # accumulated per job in the oracle's order — jobs with ≤ 2
        # congested links sum commutatively (any order is exact), ≥ 3
        # require the subset's first-seen link order (the oracle iterates
        # its demand dict), restored by a (job, first-seen-rank) lexsort
        exc = demand - cap_l
        cong_flat = exc[cols_sub] > 0
        if cong_flat.any():
            jm = job_rep[cong_flat]
            lm = cols_sub[cong_flat]
            cm = caps_rep[cong_flat]
            if np.bincount(jm, minlength=k).max() > 2:
                uniq, first_idx = np.unique(cols_sub, return_index=True)
                rank = np.zeros(nl, dtype=np.int64)
                rank[uniq[np.argsort(first_idx, kind="stable")]] = np.arange(
                    uniq.size
                )
                order = np.lexsort((rank[lm], jm))
                jm, lm, cm = jm[order], lm[order], cm[order]
            contrib = exc[lm] * (cm / demand[lm]) * 1e-3 * self.ecn_marks_per_gbit
            marks[idx] = np.bincount(jm, weights=contrib, minlength=k)
        return rates, marks

    # ------------------ incremental water-filling ----------------- #
    def _solve_alloc_incremental(
        self, comm_mask: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Water-filling via delta-maintained state and dirty-component
        refills.

        At 256+ racks adjacent comm-competing sets differ by one or two
        jobs, yet the from-scratch solve re-accumulates demand and live
        counts over *every* member and re-runs the filling cascade over
        *every* contended link.  This path keeps the full solution between
        solves — per-link demand / live counts / mark ratios, per-slot
        rates and per-job mark totals — and applies the member diff as
        batched ``np.bincount`` deltas over only the changed slots' link
        columns, O(changed nnz) instead of O(comm nnz).

        Rates exploit that water-filling decomposes exactly across
        connected components of the (member job × binding link) graph —
        the same loosely-connected affinity-graph structure the paper's
        scheduler partitions (§4): components share no links and no jobs,
        so each one's cascade is independent of the rest.  A delta dirties
        only the components touching a changed slot or a demand-changed
        binding link; a seed-driven BFS walks exactly those components
        (output-sensitive — clean components are never visited) and ONE
        batched fill re-solves their union (independent sub-problems solve
        jointly without interacting), while every clean component keeps
        its previous rates verbatim.  Mark totals are maintained the same
        way: per-link ``max(excess,0)/demand`` ratios are patched on the
        changed links and scattered into per-job totals through the
        link-major CSR.

        Equivalence is by tolerance band, not bit-exactness (see
        docs/architecture.md "Incremental re-solve"): demand/mark sums
        float-drift under ± deltas (bounded by a from-scratch refresh
        every ``_WF_REFRESH`` delta solves) and component-local fills
        reorder float accumulation.  ``incremental=False`` (the default)
        never enters this path and stays bit-exact against the scalar
        oracle.
        """
        n = len(self._slots)
        caps_now = np.where(comm_mask, self._cap_now, 0.0)
        st = self._wf
        if st is None or st["caps"].shape[0] != n or st["age"] >= _WF_REFRESH:
            st = self._wf_rebuild(comm_mask, caps_now)
            self._wf_cap_dirty.clear()  # rebuilt from live capacities
        else:
            changed = np.nonzero(
                (st["mask"] != comm_mask) | (st["caps"] != caps_now)
            )[0]
            extra = None
            if self._wf_cap_dirty:
                # link capacities mutated by fault injection since the
                # last solve: treat them as demand-changed links so their
                # ratios/binding flips recompute and their components
                # re-fill against the new capacity
                extra = np.fromiter(
                    sorted(self._wf_cap_dirty), dtype=np.int64,
                    count=len(self._wf_cap_dirty),
                )
                self._wf_cap_dirty.clear()
            if changed.size or extra is not None:
                self._wf_delta(
                    st, comm_mask, caps_now, changed, extra_links=extra
                )
            st["age"] += 1
            self.alloc_delta_solves += 1
        # T accumulates ± ratio deltas between refreshes — clamp the tiny
        # negative float residue so mark rates stay ≥ 0 like the oracle's
        marks = caps_now * np.maximum(st["T"], 0.0)
        marks *= 1e-3 * self.ecn_marks_per_gbit
        return st["rates"].copy(), marks

    def _wf_rebuild(self, comm_mask: np.ndarray, caps_now: np.ndarray) -> dict:
        """From-scratch build of the incremental solver state."""
        inc = self._inc
        n = len(self._slots)
        nl = inc.num_links
        cap_l = inc.capacities
        idx = np.nonzero(comm_mask)[0]
        cols = inc.flat_cols(idx)
        w = np.repeat(caps_now[idx], inc.counts[idx])
        # bincount returns int64 for *empty* weights — pin float64
        demand = np.bincount(cols, weights=w, minlength=nl).astype(np.float64)
        live = np.bincount(cols, minlength=nl).astype(np.int64)
        exc = demand - cap_l
        with np.errstate(divide="ignore", invalid="ignore"):
            lratio = np.where(exc > 0, exc / demand, 0.0)
        rows_all, cols_all = inc.flat_pairs
        T = np.bincount(
            rows_all, weights=lratio[cols_all], minlength=n
        ).astype(np.float64)
        eff = np.where(demand > cap_l + _EPS, self.congested_efficiency, 1.0)
        binding = (live > 0) & (demand >= cap_l * eff - _EPS)
        rates = np.zeros(n, dtype=np.float64)
        rates[idx] = caps_now[idx]
        if binding.any():
            bpair = binding[cols_all] & comm_mask[rows_all]
            JR = np.unique(rows_all[bpair])
            if JR.size:
                self._wf_fill_dispatch(rates, JR, binding, demand, live)
        self._wf = st = {
            "mask": comm_mask.copy(),
            "caps": caps_now,
            "demand": demand,
            "live": live,
            "lratio": lratio,
            "T": T,
            "binding": binding,
            "rates": rates,
            "age": 0,
        }
        return st

    def _wf_delta(
        self,
        st: dict,
        comm_mask: np.ndarray,
        caps_now: np.ndarray,
        changed: np.ndarray,
        extra_links: np.ndarray | None = None,
    ) -> None:
        """Apply a member diff to the state and refill dirty components.

        ``extra_links`` names link ids whose *capacity* changed with no
        member diff of their own (fault injection): they join the changed-
        link set so mark ratios, binding flips and component refills all
        re-evaluate against the mutated ``inc.capacities``."""
        inc = self._inc
        nl = inc.num_links
        cap_l = inc.capacities
        ccols = inc.flat_cols(changed)
        reps = inc.counts[changed]
        dcap = np.repeat(caps_now[changed] - st["caps"][changed], reps)
        demand = st["demand"]
        demand += np.bincount(ccols, weights=dcap, minlength=nl)
        dmem = (
            comm_mask[changed].astype(np.int64)
            - st["mask"][changed].astype(np.int64)
        )
        if dmem.any():
            # sums of ±1 in float64 are exact — astype is lossless
            st["live"] += np.bincount(
                ccols, weights=np.repeat(dmem, reps), minlength=nl
            ).astype(np.int64)
        live = st["live"]
        st["mask"] = comm_mask.copy()
        st["caps"] = caps_now
        # mark ratios move only where demand (or capacity) moved; scatter
        # the per-link delta into the per-job totals through the link-major
        # CSR
        if extra_links is not None and extra_links.size:
            cl = np.unique(np.concatenate((ccols, extra_links)))
        else:
            cl = np.unique(ccols)
        exc = demand[cl] - cap_l[cl]
        with np.errstate(divide="ignore", invalid="ignore"):
            new_r = np.where(exc > 0, exc / demand[cl], 0.0)
        dr = new_r - st["lratio"][cl]
        if dr.any():
            st["T"] += np.bincount(
                inc.link_users(cl),
                weights=np.repeat(dr, inc.link_csr[1][cl]),
                minlength=st["T"].size,
            )
            st["lratio"][cl] = new_r
        # binding flips can only happen on the demand-changed links
        binding = st["binding"]
        b_old = binding[cl]
        eff = np.where(demand[cl] > cap_l[cl] + _EPS, self.congested_efficiency, 1.0)
        b_new = (live[cl] > 0) & (demand[cl] >= cap_l[cl] * eff - _EPS)
        binding[cl] = b_new
        # dirty slots: the changed members themselves, plus every user of a
        # changed link that is (or just stopped being) contended — slots in
        # clean components are untouched and keep their previous rates
        dlinks = cl[b_old | b_new]
        dirty = np.concatenate((changed, inc.link_users(dlinks)))
        rates = st["rates"]
        # members default to their demand caps (exact for every slot with
        # no binding link — sub-binding links can never saturate), then the
        # component refill overwrites the contended ones
        rates[dirty] = caps_now[dirty]
        # seed-driven BFS over the (member × binding-link) graph: visits
        # exactly the dirty components, never the clean ones
        rows_l, link_rows = inc.adjacency
        seenL: set[int] = set()
        stack: list[int] = []
        for lnk in dlinks.tolist():
            if binding[lnk] and lnk not in seenL:
                seenL.add(lnk)
                stack.append(lnk)
        for s in dirty.tolist():
            if comm_mask[s]:
                for g in rows_l[s]:
                    if g not in seenL and binding[g]:
                        seenL.add(g)
                        stack.append(g)
        if not stack:
            return  # no contended component touched
        JRs: set[int] = set()
        while stack:
            lnk = stack.pop()
            for u in link_rows[lnk]:
                if u not in JRs and comm_mask[u]:
                    JRs.add(u)
                    for g in rows_l[u]:
                        if g not in seenL and binding[g]:
                            seenL.add(g)
                            stack.append(g)
        if not JRs:
            return
        sub_binding = np.zeros(nl, dtype=bool)
        sub_binding[sorted(seenL)] = True
        JR = np.fromiter(sorted(JRs), dtype=np.int64, count=len(JRs))
        self._wf_fill_dispatch(rates, JR, sub_binding, demand, live)

    def _wf_components(
        self, JR: np.ndarray, binding: np.ndarray
    ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Partition the closed member set ``JR`` into its connected
        components of the (member x binding-link) graph.

        ``JR`` is closed under the BFS that built it: every comm user of
        every binding link reachable from a member of ``JR`` is itself in
        ``JR`` (``_wf_rebuild`` takes all bound comm users; ``_wf_delta``
        closes over the dirty seeds).  That closure is what makes each
        returned ``(members, links)`` pair a self-contained water-filling
        sub-problem: global live counts on a component's links equal its
        in-component user counts, so the batched fill can recompute them
        from the component's own sub-incidence.
        """
        rows_l, link_rows = self._inc.adjacency
        jr = set(JR.tolist())
        seen: set[int] = set()
        comps: list[tuple[np.ndarray, np.ndarray]] = []
        for j0 in JR.tolist():
            if j0 in seen:
                continue
            seen.add(j0)
            members = [j0]
            links: list[int] = []
            seenL: set[int] = set()
            stack = [j0]
            while stack:
                u = stack.pop()
                for g in rows_l[u]:
                    if binding[g] and g not in seenL:
                        seenL.add(g)
                        links.append(g)
                        for v in link_rows[g]:
                            if v in jr and v not in seen:
                                seen.add(v)
                                members.append(v)
                                stack.append(v)
            members.sort()
            links.sort()
            comps.append((
                np.array(members, dtype=np.int64),
                np.array(links, dtype=np.int64),
            ))
        return comps

    def _wf_fill_dispatch(
        self,
        rates: np.ndarray,
        JR: np.ndarray,
        binding: np.ndarray,
        demand: np.ndarray,
        live: np.ndarray,
    ) -> None:
        """Route a dirty-union refill to the fused or device-sharded fill.

        The sharded path (``sharded=True``) re-partitions the union into
        components and solves them as rows of bucketed vmap batches split
        across devices (repro.cluster.shard).  Below ``MIN_COMPONENTS``
        the batch cannot amortise a device round-trip, so small unions —
        including every typical delta, which dirties one or two
        components — keep the fused host fill.  Both paths write the same
        slots of ``rates``; equivalence is tolerance-band (component
        fills reorder float accumulation vs the union fill)."""
        if self.sharded:
            comps = self._wf_components(JR, binding)
            if len(comps) >= _SHARD_MIN_COMPONENTS:
                cap_l = self._inc.capacities
                rows = []
                for mem, lnks in comps:
                    eff = np.where(
                        demand[lnks] > cap_l[lnks] + _EPS,
                        self.congested_efficiency,
                        1.0,
                    )
                    rows.append((
                        self._cap_now[mem],
                        self._inc.sub_incidence(mem, lnks),
                        cap_l[lnks] * eff,
                    ))
                filled, stats = batched_fill(rows, ndev=self._shard_devices)
                for (mem, _), vec in zip(comps, filled):
                    rates[mem] = vec
                self.shard_stats.merge(stats)
                return
            self.shard_stats.fused_fills += 1
        rates[JR] = self._wf_fill_core(JR, binding, demand, live)

    def _wf_fill_core(
        self,
        idx: np.ndarray,
        binding: np.ndarray,
        demand: np.ndarray,
        live: np.ndarray,
    ) -> np.ndarray:
        """Progressive filling over only the links that can saturate.

        A link with ``demand < capacity·eff − ε`` can never bound a filling
        increment: its remaining/live ratio strictly exceeds the smallest
        cap slack among its users at every round (each user's rate is
        capped by its demand contribution, so the link retains headroom
        until every user freezes at cap).  Dropping those links — and every
        job incident to *no* surviving link, which simply freezes at its
        demand cap — shrinks the filling loop's axes from (comm jobs, all
        links) to (contended jobs, contended links), typically a small
        constant at 256+ racks.  Frozen-at-cap rates agree with the oracle
        to ≤ ε (the oracle freezes at cap-slack ≤ ε); everything else is
        the same progressive-filling recurrence on fewer axes.

        ``idx`` is the candidate slot set (the comm members on a rebuild, a
        dirty-component union on a delta); ``binding`` restricts the link
        axis the same way.  Returns the rates for ``idx`` in order.
        """
        n = len(self._slots)
        k = idx.size
        caps_j = self._cap_now[idx]
        r = caps_j.copy()
        nl = self._inc.num_links
        cap_l = self._inc.capacities
        counts = self._inc.counts[idx]
        cols_sub = self._inc.flat_cols(idx)
        job_rep = np.repeat(np.arange(k), counts)
        bsel = binding[cols_sub]
        jb = job_rep[bsel]
        B = np.nonzero(binding)[0]
        bound = np.zeros(k, dtype=bool)
        bound[jb] = True
        J = np.nonzero(bound)[0]
        m = J.size
        L = B.size
        if m == 0 or L == 0:
            return r
        slotJ = idx[J]
        # Freeze events are scalar-sparse (each job freezes once, touching
        # a handful of links), so the loop keeps vector state only for the
        # per-round ratio min and does freeze bookkeeping through python
        # adjacency lists.  Dead links never leave the arrays: a saturated
        # link gets remaining=inf, live=BIG so its ratio pins at inf and
        # stray decrements stay harmless — no per-round masking at all.
        rows_l, link_rows = self._inc.adjacency
        BIG = 1e300
        lpos = np.full(nl, L, dtype=np.int64)  # sentinel L → dummy tail
        lpos[B] = np.arange(L)
        # Per-link *absolute* saturation level: with Rem_l = limit_l minus
        # the rates of its frozen users, a link saturates when the shared
        # water level reaches Rem_l / lv_l.  The level is invariant under
        # rounds that do not freeze one of the link's users, so each round
        # costs one reduction over the level array plus O(affected) updates
        # — no full rem/live rewrite.  The dummy tail slot absorbs
        # decrements for links outside the binding set (lpos sentinel).
        db = demand[B]
        clb = cap_l[B]
        eff_b = np.where(db > clb + _EPS, self.congested_efficiency, 1.0)
        Rem = np.empty(L + 1, dtype=np.float64)
        Rem[:L] = clb * eff_b
        Rem[L] = math.inf
        lv = np.empty(L + 1, dtype=np.float64)
        lv[:L] = live[B]
        lv[L] = BIG
        level = np.empty(L + 1, dtype=np.float64)
        np.divide(Rem, lv, out=level)
        B_list = B.tolist()
        slotJ_list = slotJ.tolist()
        unfrozen_slot = bytearray(n)
        for s in slotJ_list:
            unfrozen_slot[s] = 1
        order = np.argsort(caps_j[J], kind="stable")
        caps_sorted = caps_j[J][order].tolist()
        slot_order = slotJ[order].tolist()
        frozen_slots: list[int] = []
        frozen_vals: list[float] = []
        dec_gids: list[int] = []
        dec_vals: list[float] = []  # per frozen job: rate, fan-out
        dec_lens: list[int] = []
        n_unfrozen = m
        r_cur = 0.0
        ptr = 0
        inf = math.inf
        fmin_reduce = np.fmin.reduce
        with np.errstate(divide="ignore", invalid="ignore"):
            while n_unfrozen:
                S = float(fmin_reduce(level))
                while ptr < m and not unfrozen_slot[slot_order[ptr]]:
                    ptr += 1
                if ptr < m and caps_sorted[ptr] <= S + _EPS:
                    # batched cap freezes: every unfrozen cap ≤ S takes its
                    # final rate now — freezing a user below a link's level
                    # only raises that level ((level−c)/(lv−1) ≥ 0), so no
                    # link can saturate before the water reaches S
                    while ptr < m and caps_sorted[ptr] <= S + _EPS:
                        s = slot_order[ptr]
                        if unfrozen_slot[s]:
                            unfrozen_slot[s] = 0
                            n_unfrozen -= 1
                            c = caps_sorted[ptr]
                            if c > r_cur:
                                r_cur = c
                            frozen_slots.append(s)
                            frozen_vals.append(c)
                            row = rows_l[s]
                            dec_gids.extend(row)
                            dec_vals.append(c)
                            dec_lens.append(len(row))
                        ptr += 1
                else:
                    if S == inf:
                        break
                    r_cur = S
                    for p in np.nonzero(level == S)[0].tolist():
                        for s in link_rows[B_list[p]]:
                            if unfrozen_slot[s]:
                                unfrozen_slot[s] = 0
                                n_unfrozen -= 1
                                frozen_slots.append(s)
                                frozen_vals.append(S)
                                row = rows_l[s]
                                dec_gids.extend(row)
                                dec_vals.append(S)
                                dec_lens.append(len(row))
                    if not dec_gids:
                        break  # defensive: argmin link had no live users
                pos = lpos[np.array(dec_gids, dtype=np.int64)]
                w = np.repeat(dec_vals, dec_lens)
                Rem -= np.bincount(pos, weights=w, minlength=L + 1)
                lv -= np.bincount(pos, minlength=L + 1)
                # drained links (lv → 0) pin at +inf; the 1e-300 floor keeps
                # float drift in Rem from producing -inf/NaN levels
                np.divide(np.maximum(Rem, 1e-300), lv, out=level)
                dec_gids.clear()
                dec_vals.clear()
                dec_lens.clear()
        if n_unfrozen:
            for s in slotJ_list:
                if unfrozen_slot[s]:
                    frozen_slots.append(s)
                    frozen_vals.append(r_cur)
        if frozen_slots:
            # frozen bookkeeping runs on global slot ids — map back to
            # positions within idx for the (len idx) result
            loc = np.zeros(n, dtype=np.int64)
            loc[idx] = np.arange(k)
            r[loc[np.array(frozen_slots, dtype=np.int64)]] = frozen_vals
        return r

    # -------------------------------------------------------------- #
    def attach_link_recorder(self, recorder) -> "FluidNetworkSim":
        """Wire a :class:`repro.cluster.linkload.LinkLoadRecorder` into
        the vectorized event loop (per-link utilization / ECN-mark
        timelines).  Raises on the scalar engine — the oracle loop has no
        recording hook, and silently recording nothing would be worse."""
        recorder._bind(self)
        self.link_recorder = recorder
        return self

    def advance(self, until_ms: float, *, max_events: int = 2_000_000) -> list[Job]:
        """Advance the fluid simulation to ``until_ms`` (exact events).

        Returns as soon as one or more jobs finish their last iteration (so
        the cluster simulator can react to the departure immediately); the
        finished jobs are returned with ``finish_ms`` / ``state`` set.

        While spans are on (:mod:`repro.spans`), each call records one
        ``fluid/advance`` span: ``events`` (loop steps), ``solves``
        (alloc-cache misses) and ``solve_ns`` (host ns solving them).
        """
        if not spans.enabled():
            return self._advance(until_ms, max_events)
        solves0 = self.alloc_solves
        with spans.span("fluid/advance") as sp:
            self._solve_ns = 0
            try:
                return self._advance(until_ms, max_events)
            finally:
                sp.set(events=self._last_events,
                       solves=self.alloc_solves - solves0,
                       solve_ns=self._solve_ns)
                self._solve_ns = None

    def _advance(self, until_ms: float, max_events: int) -> list[Job]:
        self._last_events = 0
        if not self._execs:
            # empty cluster (every job queued or between arrivals — elastic
            # churn can grow a lone job past the fabric): the fluid state
            # is trivially constant, so jump the clock instead of stalling
            # the caller's event loop at a fixed ``now``
            self.now_ms = max(self.now_ms, until_ms)
            return []
        if self.vectorized:
            return self._advance_vectorized(until_ms, max_events=max_events)
        return self._advance_scalar(until_ms, max_events=max_events)

    def _advance_vectorized(
        self, until_ms: float, *, max_events: int
    ) -> list[Job]:
        """Batched event stepping over the cached rates.

        Per event: one cache lookup for (rates, mark rates), one batched
        min for the next event time, and whole-array updates for
        delay/remaining/marks — no per-job Python in the hot loop.  Segment
        completions (the rare part) drop back to the shared scalar
        ``_complete_segment`` in slot order, so jitter draws and the
        alignment agent behave exactly like the oracle.
        """
        finished: list[Job] = []
        events = 0
        # job states only change outside advance (scheduler epochs, tests),
        # and a finish breaks the loop — the active view is loop-invariant
        act = self._alive & ~self._cutoff_mask()
        divbuf = np.empty(len(self._slots), dtype=np.float64)
        divbuf.fill(np.inf)
        try:
            while self.now_ms < until_ms - _EPS and self._execs:
                events += 1
                if events > max_events:
                    raise RuntimeError("fluid sim exceeded max_events")
                not_delayed = self._dly <= _EPS
                comm = act & self._is_comm & not_delayed
                rates, markr, pos = self._cached_solve(comm)
                delayed = act & ~not_delayed
                compute_like = act & not_delayed & ~self._is_comm
                dt = until_ms - self.now_ms
                dt = min(dt, float(np.where(delayed, self._dly, np.inf).min()))
                dt = min(
                    dt, float(np.where(compute_like, self._rem, np.inf).min())
                )
                # pos ⊆ comm: the cached solve's comm set IS this event's
                # (same key), so rate>_EPS slots are exactly the comm slots
                # that bound dt
                divbuf.fill(np.inf)
                np.divide(self._rem, rates, out=divbuf, where=pos)
                tmin = float(divbuf.min())
                if tmin < np.inf:
                    dt = min(dt, tmin * 1e3)
                dt = max(dt, 1e-6)
                self.now_ms += dt
                if self.link_recorder is not None:
                    # rates are constant over [now-dt, now) by construction
                    self.link_recorder.record(
                        self.now_ms - dt, self.now_ms, comm, rates
                    )
                # progress everyone by dt (rates constant over the interval)
                np.subtract(self._dly, dt, out=self._dly, where=delayed)
                np.maximum(self._dly, 0.0, out=self._dly, where=delayed)
                np.subtract(self._rem, dt, out=self._rem, where=compute_like)
                drained = rates * dt
                drained *= 1e-3
                np.subtract(self._rem, drained, out=self._rem, where=comm)
                np.add(self._mk, markr * dt, out=self._mk, where=comm)
                prog = act & not_delayed
                done = prog & (self._rem <= _EPS)
                if done.any():
                    for i in np.nonzero(done)[0]:
                        ex = self._slots[i]
                        ex.remaining = float(self._rem[i])
                        ex.delay_ms = float(self._dly[i])
                        ex.marks = float(self._mk[i])
                        self._complete_segment(ex)
                        self._rem[i] = ex.remaining
                        self._dly[i] = ex.delay_ms
                        self._mk[i] = ex.marks
                        self._sync_seg(i, ex)
                        if ex.job.remaining_iters() == 0:
                            ex.job.finish_ms = self.now_ms
                            ex.job.state = JobState.DONE
                            del self._execs[ex.job.job_id]
                            self._slot_of.pop(ex.job.job_id, None)
                            self._alive[i] = False
                            finished.append(ex.job)
                if finished:
                    break
        finally:
            self._last_events = events
            self._sync_execs()
        return finished

    def _advance_scalar(
        self, until_ms: float, *, max_events: int
    ) -> list[Job]:
        """The original per-event Python loop (oracle for the vectorized
        engine's event stepping)."""
        finished: list[Job] = []
        events = 0
        while self.now_ms < until_ms - _EPS and self._execs:
            events += 1
            if events > max_events:
                raise RuntimeError("fluid sim exceeded max_events")
            rates = self._allocate()
            marks = self._mark_rates()
            # time to next event for every job; CUTOFF jobs are frozen —
            # they neither bound dt nor make progress (a cutoff job must
            # not finish iterations, flip to DONE, or consume link share)
            dt = until_ms - self.now_ms
            for jid, ex in self._execs.items():
                if ex.job.state is JobState.CUTOFF:
                    continue
                if ex.delay_ms > _EPS:
                    dt = min(dt, ex.delay_ms)
                elif ex.kind == "compute" or not ex.links:
                    dt = min(dt, ex.remaining)
                else:
                    r = rates.get(jid, 0.0)
                    if r > _EPS:
                        dt = min(dt, ex.remaining / r * 1e3)
            dt = max(dt, 1e-6)
            self.now_ms += dt
            # progress everyone by dt (rates constant over the interval)
            for jid, ex in list(self._execs.items()):
                if ex.job.state is JobState.CUTOFF:
                    continue
                if ex.delay_ms > _EPS:
                    ex.delay_ms = max(0.0, ex.delay_ms - dt)
                    continue
                if ex.kind == "compute" or not ex.links:
                    ex.remaining -= dt
                else:
                    ex.remaining -= rates.get(jid, 0.0) * dt * 1e-3
                    ex.marks += marks.get(jid, 0.0) * dt
                if ex.remaining <= _EPS:
                    self._complete_segment(ex)
                    if ex.job.remaining_iters() == 0:
                        ex.job.finish_ms = self.now_ms
                        ex.job.state = JobState.DONE
                        del self._execs[jid]
                        finished.append(ex.job)
            if finished:
                break
        self._last_events = events
        return finished

    # -------------------------------------------------------------- #
    def _complete_segment(self, ex: _JobExec) -> None:
        ex.seg_idx += 1
        if ex.seg_idx >= len(ex.segments):
            # iteration boundary
            job = ex.job
            end = self.now_ms  # dt already chosen to land on the boundary
            if ex.skip_record:
                ex.skip_record = False
            else:
                job.iter_times_ms.append(end - ex.iter_start_ms)
                job.ecn_marks.append(ex.marks)
            job.iters_done += 1
            ex.marks = 0.0
            ex.iter_start_ms = end
            ex.seg_idx = 0
            # CASSINI alignment agent (§4.2 step 3, §5.7).  Aligned jobs run
            # *isochronously* on a grid with the optimizer's (quantized)
            # period: finishing early waits for the next slot (pacing — this
            # is what makes interleaving stable when real iteration times
            # differ slightly from the quantized ones the optimizer saw);
            # drifting late by more than 5 % triggers a re-alignment delay
            # onto the next slot.  Systematically-late jobs (3 consecutive
            # adjustments) disarm — their placement is not interleavable and
            # holding the grid would only burn time.
            if ex.ideal_next_ms is not None:
                drift = end - ex.ideal_next_ms
                if drift <= 0.0:
                    ex.delay_ms += -drift          # pace to the slot
                    ex.consec_adjust = 0
                    ex.ideal_next_ms += ex.paced_iter_ms
                elif drift > self.drift_tolerance * ex.paced_iter_ms:
                    extra = (-drift) % ex.paced_iter_ms
                    ex.delay_ms += extra
                    job.drift_adjustments += 1
                    ex.consec_adjust += 1
                    ex.ideal_next_ms = end + extra + ex.paced_iter_ms
                    if ex.consec_adjust >= 3:
                        ex.ideal_next_ms = None    # disarm
                else:
                    ex.consec_adjust = 0
                    ex.ideal_next_ms += ex.paced_iter_ms
        seg = ex.segments[ex.seg_idx]
        if seg.kind == "compute" or not ex.links:
            jitter = (
                1.0 + self._rng.gauss(0.0, self.compute_jitter)
                if self.compute_jitter > 0
                else 1.0
            )
            ex.remaining = seg.duration_ms * max(0.1, jitter)
        else:
            ex.remaining = seg.gbits
