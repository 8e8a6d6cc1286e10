"""CASSINI's pluggable scheduler module (paper §4.2, Algorithm 2).

Host schedulers (Themis, Pollux, …) are modified to emit up to ``N``
*candidate placements* instead of one; this module

  1. builds the affinity graph of every candidate (jobs ↔ contended links),
  2. discards candidates whose affinity graph has a loop (Theorem 1
     precondition),
  3. solves the Table-1 optimization on every contended link to obtain the
     link's compatibility score and per-job link-level time-shifts,
  4. ranks candidates by the mean link score (tail/other aggregations are
     supported, cf. paper footnote 1),
  5. runs Algorithm 1 on the winner to produce unique per-job time-shifts.

The module is deliberately independent of any concrete cluster model: a
candidate is fully described by ``job → links traversed``, per-link
capacities and per-job communication patterns.

Scoring (steps 1–4) and alignment (step 5) are exposed separately —
:meth:`CassiniModule.score_candidates` / ``score_candidates_batched`` and
:meth:`CassiniModule.align` — so :class:`repro.engine.SchedulingPipeline`
can run them as independent stages; :meth:`CassiniModule.decide` composes
them (Algorithm 2 end-to-end).
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Mapping, Sequence

import numpy as np

from .affinity import AffinityGraph, JobId, LinkId
from .circle import CommPattern, DEFAULT_PRECISION_DEG, DEFAULT_QUANTUM_MS
from .compat import BatchStats, CompatResult, find_rotations, find_rotations_batched

__all__ = ["PlacementCandidate", "CassiniDecision", "CassiniModule"]

# (candidate, affinity graph or None when loop-discarded, per-link results)
Evaluated = tuple[
    "PlacementCandidate", AffinityGraph | None, dict[LinkId, CompatResult]
]


@dataclass
class PlacementCandidate:
    """One candidate placement returned by the host scheduler.

    ``job_links`` maps every placed job to the network links its traffic
    traverses (as computed by the host's topology/routing); ``meta`` carries
    the host scheduler's own payload (e.g. the concrete server assignment)
    through CASSINI untouched.
    """

    job_links: Mapping[JobId, Sequence[LinkId]]
    meta: object = None
    # filled in by CassiniModule:
    score: float = float("nan")
    link_scores: dict[LinkId, float] = field(default_factory=dict)
    discarded_loop: bool = False


@dataclass
class CassiniDecision:
    """Output of Algorithm 2."""

    top_placement: PlacementCandidate
    time_shifts_ms: dict[JobId, float]
    link_results: dict[LinkId, CompatResult]
    candidates: list[PlacementCandidate]  # all, with scores filled in
    # per-job isochronous pacing period (max across the job's links):
    paced_periods_ms: dict[JobId, float] = field(default_factory=dict)
    # per-job minimum compatibility score across its contended links --
    # pacing is only worth holding when interleaving can actually succeed
    job_min_score: dict[JobId, float] = field(default_factory=dict)

    @property
    def score(self) -> float:
        return self.top_placement.score


class CassiniModule:
    """Algorithm 2, reusable across host schedulers."""

    def __init__(
        self,
        *,
        precision_deg: float = DEFAULT_PRECISION_DEG,
        quantum_ms: float = DEFAULT_QUANTUM_MS,
        aggregate: Callable[[Sequence[float]], float] | None = None,
        max_workers: int | None = None,
        seed: int = 0,
        device_reduce: bool = True,
        ragged: bool = True,
        tuned: bool = True,
    ) -> None:
        self.precision_deg = precision_deg
        self.quantum_ms = quantum_ms
        self.aggregate = aggregate or (lambda xs: float(np.mean(xs)))
        self.max_workers = max_workers
        self.seed = seed
        # Batched solves keep the rotation-search argmin/acceptance on the
        # device for kernel-eligible shapes (fused circle_score reduction);
        # False forces the full-matrix + host-reduction path everywhere.
        self.device_reduce = device_reduce
        # Ragged single-launch batching: all kernel-eligible link problems
        # of an epoch ship as ONE kernel launch per grid-chunk/descent
        # step, whatever mix of unified-circle angle counts they carry;
        # False restores the per-angle-count launch grouping (comparison
        # path — results are bit-identical either way).
        self.ragged = ragged
        # Per-bucket tuned launch schedules from the committed tuning
        # table (repro.kernels.tune); False pins the untuned kernel
        # defaults — a comparison/debug switch, bit-identical either way.
        self.tuned = tuned
        # Candidates at one epoch mostly share link job-sets: memoize the
        # per-link optimization across candidates (and epochs).  All reads
        # and writes go through ``_cache_lock`` so the ThreadPoolExecutor
        # path (``max_workers``) and the batched path stay race-free; the
        # cached CompatResults themselves are frozen dataclasses.
        self._link_cache: dict[tuple, CompatResult] = {}
        self._cache_lock = threading.Lock()
        # serve-mode telemetry: cache_hits counts successful link-cache
        # lookups (what the speculative epoch-prefetch buys), cache_misses
        # counts link problems actually *solved* (scalar or batched)
        self.cache_hits: int = 0
        self.cache_misses: int = 0
        # Telemetry of the most recent score_candidates_batched call (None
        # until one runs, or when every link problem was already cached):
        # benches and tests use it to prove no silent scalar fallback.
        self.last_batch_stats: BatchStats | None = None
        # the same counters summed over every batched solve, under
        # ``_cache_lock`` (the prefetch thread solves too)
        self.batch_totals = BatchStats()

    # -------------------------------------------------------------- #
    def contended_links(
        self, cand: PlacementCandidate
    ) -> dict[LinkId, list[JobId]]:
        """Links carrying more than one job (the V vertex set)."""
        by_link: dict[LinkId, list[JobId]] = {}
        for job, links in cand.job_links.items():
            for l in links:
                by_link.setdefault(l, []).append(job)
        return {l: js for l, js in by_link.items() if len(js) > 1}

    @staticmethod
    def merge_equivalent_links(
        shared: Mapping[LinkId, Sequence[JobId]],
        capacities: Mapping[LinkId, float],
    ) -> tuple[dict[LinkId, list[JobId]], dict[LinkId, float]]:
        """Collapse parallel links that carry an *identical* job set.

        Two links with the same job set impose the same interleaving
        constraint and would produce identical per-job time-shifts; keeping
        both as affinity-graph vertices creates a spurious 2-cycle that
        Algorithm 2 would needlessly discard (e.g. a job pair spanning the
        same two racks shares both racks' uplinks).  We keep one merged
        vertex per job set, with the group's *minimum* capacity (the most
        constrained member governs).  True loops — cycles through links
        with different job sets — are still detected and discarded.
        """
        groups: dict[tuple, list[LinkId]] = {}
        for l, js in shared.items():
            key = tuple(sorted(js, key=repr))
            groups.setdefault(key, []).append(l)
        merged_links: dict[LinkId, list[JobId]] = {}
        merged_caps: dict[LinkId, float] = {}
        for key, ls in groups.items():
            rep = min(ls, key=repr)
            merged_links[rep] = list(key)
            merged_caps[rep] = min(capacities[l] for l in ls)
        return merged_links, merged_caps

    # -------------------------------------------------------------- #
    def _link_key(
        self, js: Sequence[JobId], patterns: Mapping[JobId, CommPattern], cap: float
    ) -> tuple:
        return (
            tuple(
                (patterns[j].name, patterns[j].iter_time_ms, patterns[j].phases)
                for j in js
            ),
            cap,
        )

    def _cached(self, key: tuple) -> CompatResult | None:
        with self._cache_lock:
            res = self._link_cache.get(key)
            if res is not None:
                self.cache_hits += 1
            return res

    def _cache_put(self, key: tuple, res: CompatResult) -> None:
        with self._cache_lock:
            self._link_cache[key] = res

    # ------------------------- delta updates ---------------------- #
    def add_job(self, pattern: CommPattern) -> None:
        """Job arrival: nothing to precompute — entries fill lazily on the
        first solve involving the new pattern.  Kept as the explicit
        counterpart of :meth:`remove_job` so serve-mode churn drives both
        sides of the cache's lifecycle through one API."""

    def remove_job(self, pattern: CommPattern | str) -> int:
        """Job departure: evict every cached link solve involving the
        departed pattern (matched by pattern name — a cache key embeds the
        ``(name, iter_time, phases)`` triple of each participant).

        A long-running service would otherwise accumulate solves for jobs
        that can never communicate again.  Evicting by name is safe even
        when another running job shares the pattern: the next epoch's solve
        misses and recomputes the identical frozen ``CompatResult``, so
        delta-evicted and rebuilt-from-scratch caches stay interchangeable
        (tests/test_serve_incremental.py pins the parity).

        Returns the number of evicted entries.
        """
        name = pattern if isinstance(pattern, str) else pattern.name
        with self._cache_lock:
            doomed = [
                key
                for key in self._link_cache
                if any(entry[0] == name for entry in key[0])
            ]
            for key in doomed:
                del self._link_cache[key]
        return len(doomed)

    def _prepare_candidate(
        self,
        cand: PlacementCandidate,
        patterns: Mapping[JobId, CommPattern],
        capacities: Mapping[LinkId, float],
    ) -> tuple[dict[LinkId, list[JobId]], dict[LinkId, float], AffinityGraph] | None:
        """Lines 3–13 of Algorithm 2: contention map + loop check.

        Returns None (and marks the candidate discarded) when the affinity
        graph has a loop — the Theorem 1 precondition fails.
        """
        shared, caps = self.merge_equivalent_links(
            self.contended_links(cand), capacities
        )
        graph = AffinityGraph()
        # Build graph edges with weight 0 first (Alg. 2 line 11) so the loop
        # check runs before paying for any optimization.
        for l, js in shared.items():
            for j in sorted(js, key=repr):
                graph.add_edge(j, l, 0.0, patterns[j].iter_time_ms)
        if graph.has_loop():
            cand.discarded_loop = True
            cand.score = -float("inf")
            return None
        return shared, caps, graph

    def _fill_candidate(
        self,
        cand: PlacementCandidate,
        shared: Mapping[LinkId, list[JobId]],
        caps: Mapping[LinkId, float],
        graph: AffinityGraph,
        patterns: Mapping[JobId, CommPattern],
    ) -> Evaluated:
        """Lines 14–23 of Algorithm 2: per-link optimization + aggregation.

        Link results are pulled from the cache; misses are solved scalar
        (the batched path pre-populates the cache, so it only pays for
        genuinely new link job-sets).
        """
        link_results: dict[LinkId, CompatResult] = {}
        scores: list[float] = []
        for l, js in sorted(shared.items(), key=lambda kv: repr(kv[0])):
            js = sorted(js, key=repr)
            key = self._link_key(js, patterns, caps[l])
            res = self._cached(key)
            if res is None:
                self.cache_misses += 1
                res = find_rotations(
                    [patterns[j] for j in js],
                    caps[l],
                    precision_deg=self.precision_deg,
                    quantum_ms=self.quantum_ms,
                    seed=self.seed,
                )
                self._cache_put(key, res)
            link_results[l] = res
            scores.append(res.score)
            cand.link_scores[l] = res.score
            graph.perimeter_ms[l] = res.circle.perimeter_ms
            for j, t_ms in zip(js, res.shifts_ms):
                # edge weight = link-level time-shift t_j^l (§4.1)
                graph.add_edge(j, l, t_ms, patterns[j].iter_time_ms)

        cand.score = self.aggregate(scores) if scores else 1.0
        return cand, graph, link_results

    def _evaluate_candidate(
        self,
        cand: PlacementCandidate,
        patterns: Mapping[JobId, CommPattern],
        capacities: Mapping[LinkId, float],
    ) -> Evaluated:
        """Lines 3–23 of Algorithm 2 for one candidate (scalar path)."""
        prep = self._prepare_candidate(cand, patterns, capacities)
        if prep is None:
            return cand, None, {}
        return self._fill_candidate(cand, *prep, patterns)

    # -------------------------------------------------------------- #
    def score_candidates(
        self,
        candidates: Sequence[PlacementCandidate],
        patterns: Mapping[JobId, CommPattern],
        capacities: Mapping[LinkId, float],
    ) -> list[Evaluated]:
        """Score every candidate with per-link scalar optimizations."""
        if self.max_workers and len(candidates) > 1:
            with ThreadPoolExecutor(max_workers=self.max_workers) as pool:
                return list(
                    pool.map(
                        lambda c: self._evaluate_candidate(c, patterns, capacities),
                        candidates,
                    )
                )
        return [
            self._evaluate_candidate(c, patterns, capacities) for c in candidates
        ]

    def score_candidates_batched(
        self,
        candidates: Sequence[PlacementCandidate],
        patterns: Mapping[JobId, CommPattern],
        capacities: Mapping[LinkId, float],
    ) -> list[Evaluated]:
        """Score every candidate, solving all uncached link problems at once.

        Candidates at one epoch share most of their contended-link job-sets;
        instead of optimizing link-by-link inside a per-candidate loop, this
        path collects every *distinct uncached* (job-set, capacity) problem
        across all candidates and hands them to
        :func:`repro.core.compat.find_rotations_batched`, which packs every
        k-job link's shift product grid into batched ``circle_score``
        evaluations (Pallas kernel / vectorized numpy) and lockstep-batches
        the coordinate-descent sweeps above the exact-grid cutoff — no link
        shape drops to the scalar path.  With ``device_reduce`` (the
        default) the kernel-eligible evaluations use the *fused* reduction:
        argmin and grid acceptance run inside the kernel and only
        per-problem scalars return to the host, never the ``(B, A)`` excess
        matrix (``last_batch_stats.device_reduced`` / ``bytes_returned``
        prove it).  Results land in the shared link
        cache, so the final per-candidate assembly is pure cache hits and
        the scalar and batched paths produce identical Evaluated tuples;
        ``self.last_batch_stats`` records which batched path each problem
        took.
        """
        prepared = [
            self._prepare_candidate(c, patterns, capacities) for c in candidates
        ]
        todo: dict[tuple, tuple[list[CommPattern], float]] = {}
        for prep in prepared:
            if prep is None:
                continue
            shared, caps, _ = prep
            for l, js in shared.items():
                js = sorted(js, key=repr)
                key = self._link_key(js, patterns, caps[l])
                if key not in todo and self._cached(key) is None:
                    todo[key] = ([patterns[j] for j in js], caps[l])
        # reset first so a fully-cached epoch reads None, not stale counts
        self.last_batch_stats = None
        if todo:
            keys = list(todo)
            self.cache_misses += len(keys)
            stats = BatchStats()
            solved = find_rotations_batched(
                [todo[k] for k in keys],
                precision_deg=self.precision_deg,
                quantum_ms=self.quantum_ms,
                seed=self.seed,
                stats=stats,
                device_reduce=self.device_reduce,
                ragged=self.ragged,
                tuned=self.tuned,
            )
            self.last_batch_stats = stats
            with self._cache_lock:
                self.batch_totals.add(stats)
            for key, res in zip(keys, solved):
                self._cache_put(key, res)
        out: list[Evaluated] = []
        for cand, prep in zip(candidates, prepared):
            if prep is None:
                out.append((cand, None, {}))
            else:
                out.append(self._fill_candidate(cand, *prep, patterns))
        return out

    # -------------------------------------------------------------- #
    def align(self, evaluated: Sequence[Evaluated]) -> CassiniDecision:
        """Rank scored candidates and run Algorithm 1 on the winner."""
        if not evaluated:
            raise ValueError("need at least one scored candidate")
        # Sort decreasing by compatibility score; stable on input order.
        order = sorted(
            range(len(evaluated)), key=lambda i: evaluated[i][0].score, reverse=True
        )
        top_cand, top_graph, top_links = evaluated[order[0]]

        if top_graph is None:
            # every candidate had a loop: fall back to the first candidate
            # with no time-shifts (plain host-scheduler behaviour).
            return CassiniDecision(
                top_placement=evaluated[0][0],
                time_shifts_ms={},
                link_results={},
                candidates=[e[0] for e in evaluated],
            )

        shifts = top_graph.bfs_time_shifts(seed=self.seed)
        paced: dict[JobId, float] = {}
        min_score: dict[JobId, float] = {}
        for l, res in top_links.items():
            for j, pp in zip(
                sorted(top_graph.link_jobs.get(l, []), key=repr),
                res.paced_periods_ms,
            ):
                paced[j] = max(paced.get(j, 0.0), pp)
                min_score[j] = min(min_score.get(j, 1.0), res.score)
        return CassiniDecision(
            top_placement=top_cand,
            time_shifts_ms=shifts,
            link_results=top_links,
            candidates=[e[0] for e in evaluated],
            paced_periods_ms=paced,
            job_min_score=min_score,
        )

    def decide(
        self,
        candidates: Sequence[PlacementCandidate],
        patterns: Mapping[JobId, CommPattern],
        capacities: Mapping[LinkId, float],
        *,
        batched: bool = False,
    ) -> CassiniDecision:
        """Algorithm 2 end-to-end (score + align)."""
        if not candidates:
            raise ValueError("need at least one placement candidate")
        score = self.score_candidates_batched if batched else self.score_candidates
        return self.align(score(candidates, patterns, capacities))
