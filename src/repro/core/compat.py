"""CASSINI compatibility optimization (paper §3, Table 1).

Given the unified circle of jobs ``J^l`` sharing link ``l`` with capacity
``C^l``, find per-job rotation angles that maximize

    score = 1 − Σ_α Excess(demand_α) / (|A| · C)          (Table 1, Eq. 2)
    Excess(d) = max(0, d − C)                             (Eq. 1)

subject to Δ_j ∈ [0, 2π / r_j)                            (Eq. 4)

The paper solves this with an off-the-shelf optimizer; because the angle
grid is discrete (5° default) and each job only has ``|A| / r_j`` distinct
rotations, the search space is small and we solve it *exactly* for ≤ 3 jobs
(full product grid) and with seeded coordinate descent above that.  The
inner scoring loop — "score every rotation of one job against a base
demand" — is the compute hot-spot and is implemented three ways:

  * numpy (always available, used for tiny inputs),
  * the full-matrix Pallas TPU kernel :mod:`repro.kernels.circle_score`
    (batched tiles; also the numpy paths' reference), and
  * the *fused-reduction* kernels (``circle_score_argmin`` /
    ``circle_score_segmin``): the per-row argmin (a chunked
    tournament-tree reduction) and the product-grid acceptance scan run
    inside the kernel, so the batched search returns O(problems) scalars
    instead of round-tripping the ``(B, A)`` excess matrix through the
    host (``device_reduce=True``, the default on the kernel-eligible
    paths).  The fused paths are *ragged* by default (``ragged=True``):
    rows from link problems with **different** unified-circle angle
    counts ship as ONE kernel launch per grid chunk / descent step, each
    row masked to its own ``num_angles``/``valid`` window — a
    heterogeneous fabric no longer pays one dispatch per angle-count
    group (``BatchStats.launches``/``ragged_rows``/``pad_fraction``).
"""

from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass, fields
from typing import Sequence

import numpy as np

from .circle import (
    DEFAULT_PRECISION_DEG,
    DEFAULT_QUANTUM_MS,
    CommPattern,
    UnifiedCircle,
)

__all__ = [
    "CompatResult",
    "BatchStats",
    "excess",
    "score_for_shifts",
    "score_all_shifts",
    "find_rotations",
    "find_rotations_batched",
    "compatibility_score",
]

# Above this many jobs on one link, fall back from the exact product grid to
# coordinate descent (the paper's links carry 2–4 jobs in practice).
MAX_EXACT_JOBS = 3
EXACT_SEARCH_MAX_JOBS = MAX_EXACT_JOBS  # back-compat alias
# The exact product grid is only affordable while the number of admissible
# shift combinations of jobs 1..k−1 stays below this.
EXACT_GRID_LIMIT = 20_000
# Batched grid evaluation materializes base-demand rows in chunks of at most
# this many rows, so a full 20k-combination grid never holds more than
# chunk × A floats at once.
GRID_CHUNK_ROWS = 4096
# The vectorized numpy excess evaluation builds an (Lc, A, A) intermediate
# per row slice; keep it around this many elements so the temporaries stay
# cache-resident — evaluating a full 20k-row batch in one numpy expression
# is 5-6x *slower* (measured) because every pass streams from DRAM.
_NUMPY_CHUNK_ELEMS = 1_000_000
_COORD_DESCENT_SWEEPS = 4
_COORD_DESCENT_SEEDS = 3
# Strict-improvement slack of every acceptance predicate in the rotation
# search: a candidate only displaces the incumbent when its excess is lower
# by more than this.  The device-side accept scan
# (repro.kernels.circle_score.ops) imports this SAME constant and evaluates
# the predicate in float64 — host and device acceptance must never drift.
ACCEPT_SLACK = 1e-12


@dataclass
class BatchStats:
    """Telemetry of one :func:`find_rotations_batched` call.

    Every problem is counted exactly once: single-job problems are
    ``trivial``, problems solved on the batched exact product grid are
    ``grid_problems`` and problems solved by the lockstep-batched coordinate
    descent are ``descent_problems`` — so ``scalar_fallbacks`` is zero by
    construction, and benchmarks/CI assert it stays that way.

    The transfer counters prove the ``(B, A)`` round-trip is gone on the
    fused-reduction paths: ``device_reduced`` counts batched evaluations
    whose argmin/acceptance ran inside the kernel, ``bytes_returned`` the
    bytes that actually crossed the evaluator→search boundary, and
    ``bytes_matrix`` what the full excess matrices would have moved — on
    kernel-eligible shapes ``device_reduced == batched_calls`` and the
    ratio ``bytes_matrix / bytes_returned`` is ~A/2 or better (asserted
    ≥ 100x in the CI bench for large grids).

    The launch counters prove the per-angle-count dispatch fan-out is
    gone on the ragged path: ``launches`` counts kernel dispatches (the
    grouped comparison path pays one per angle-count group per step;
    ragged pays exactly one per grid chunk / descent step —
    ``launches == batched_calls``, asserted in the CI bench),
    ``ragged_rows`` the rows that shipped through ragged single-launch
    batches, and ``pad_fraction`` how much of the ragged launches' lane
    footprint was padding (``ragged_real_elems`` / ``ragged_pad_elems``
    are the raw element counts behind it).
    """

    problems: int = 0
    trivial: int = 0            # single-job links (no search needed)
    grid_problems: int = 0      # solved on the batched exact product grid
    grid_rows: int = 0          # product-grid rows evaluated batched
    descent_problems: int = 0   # solved by batched coordinate descent
    descent_rows: int = 0       # rows evaluated across all descent steps
    batched_calls: int = 0      # number of batched evaluator invocations
    device_reduced: int = 0     # calls whose argmin/accept ran on device
    bytes_returned: int = 0     # bytes returned by batched evaluations
    bytes_matrix: int = 0       # bytes the full (B, A) matrices would move
    launches: int = 0           # kernel dispatches (ragged: one per step)
    ragged_rows: int = 0        # rows shipped via ragged single launches
    ragged_real_elems: int = 0  # real (unpadded) elements in those launches
    ragged_pad_elems: int = 0   # lane-padded elements those launches shipped

    def add(self, other: "BatchStats") -> None:
        """Add ``other``'s counts to this one's."""
        for f in fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))

    @property
    def scalar_fallbacks(self) -> int:
        """Problems that did not take a batched (or trivial) path."""
        return self.problems - self.trivial - self.grid_problems - self.descent_problems

    @property
    def reduction_ratio(self) -> float:
        """How many times smaller the returned results are than the full
        ``(B, A)`` matrices (1.0 when every call returned the matrix)."""
        if self.bytes_returned == 0:
            return float("inf") if self.bytes_matrix else 1.0
        return self.bytes_matrix / self.bytes_returned

    @property
    def pad_fraction(self) -> float:
        """Fraction of the ragged launches' lane footprint that was padding
        (0.0 when no ragged launch ran)."""
        if self.ragged_pad_elems == 0:
            return 0.0
        return 1.0 - self.ragged_real_elems / self.ragged_pad_elems


@dataclass(frozen=True)
class CompatResult:
    """Output of the link-level optimization (Table 1 output block)."""

    score: float                    # compatibility score (≤ 1, may be negative)
    shifts_steps: tuple[int, ...]   # per-job rotation, in discrete angle steps
    shifts_ms: tuple[float, ...]    # per-job time-shift (Eq. 5), milliseconds
    deltas_rad: tuple[float, ...]   # per-job rotation angle Δ_j in radians
    circle: UnifiedCircle
    capacity_gbps: float
    # The optimization treats job j as exactly periodic with period
    # perimeter / r_j (its *quantized* iteration time).  Workers must pace
    # their iterations at this period for the interleaving to hold — real
    # periods that differ from it precess and collide.
    paced_periods_ms: tuple[float, ...] = ()

    @property
    def fully_compatible(self) -> bool:
        return self.score >= 1.0 - 1e-9


# ---------------------------------------------------------------------- #
# scoring primitives
# ---------------------------------------------------------------------- #
def excess(demand: np.ndarray, capacity: float) -> np.ndarray:
    """Eq. 1."""
    return np.maximum(demand - capacity, 0.0)


def score_from_demand(total_demand: np.ndarray, capacity: float) -> float:
    """Eq. 2 given the summed demand per angle."""
    if capacity <= 0:
        raise ValueError("link capacity must be positive")
    return float(1.0 - excess(total_demand, capacity).mean() / capacity)


def score_for_shifts(
    circle: UnifiedCircle, shifts: Sequence[int], capacity: float
) -> float:
    """Compatibility score for a concrete rotation assignment."""
    return score_from_demand(circle.total_demand(shifts), capacity)


def score_all_shifts(
    base: np.ndarray, cand: np.ndarray, capacity: float, *, backend: str = "auto"
) -> np.ndarray:
    """Score every rotation of one candidate-job demand against a base demand.

    Args:
      base: (A,) summed demand of already-placed jobs at each angle.
      cand: (A,) candidate job demand at each angle.
      capacity: link capacity (Gbps).

    Returns:
      (A,) array: ``out[s] = Σ_α max(0, base[α] + cand[(α − s) mod A] − C)``
      — the *excess sum* for delaying the candidate by ``s`` steps (lower is
      better; the score follows as ``1 − out[s] / (A·C)``).
    """
    base = np.asarray(base, dtype=np.float32)
    cand = np.asarray(cand, dtype=np.float32)
    return _batched_excess(base[None, :], cand[None, :], capacity, backend=backend)[0]


# ---------------------------------------------------------------------- #
# optimization (Table 1)
# ---------------------------------------------------------------------- #
def find_rotations(
    patterns: Sequence[CommPattern],
    capacity_gbps: float,
    *,
    precision_deg: float = DEFAULT_PRECISION_DEG,
    quantum_ms: float = DEFAULT_QUANTUM_MS,
    backend: str = "auto",
    seed: int = 0,
    dilate_steps: int = 1,
) -> CompatResult:
    """Solve Table 1 for the jobs in ``patterns`` sharing one link.

    Returns the best rotation assignment found (exact for ≤ 3 jobs on the
    discrete grid; coordinate descent with multiple seeds above that) and
    the corresponding compatibility score and per-job time-shifts.

    ``dilate_steps`` widens every job's demand arcs by that many discrete
    angles (max-pool) before scoring.  The optimization is discretized, so a
    zero-excess solution *at the sample points* can still overlap by up to
    one angle step in continuous time; scoring on dilated arcs makes
    ``score == 1`` mean true zero overlap (with margin), which is what the
    per-worker alignment agents need to hold the shift without systematic
    drift.
    """
    circle = _build_circle(
        patterns, precision_deg=precision_deg, quantum_ms=quantum_ms,
        dilate_steps=dilate_steps,
    )
    shifts = _search(circle, capacity_gbps, backend=backend, seed=seed)
    return _finalize(circle, shifts, capacity_gbps)


def find_rotations_batched(
    problems: Sequence[tuple[Sequence[CommPattern], float]],
    *,
    precision_deg: float = DEFAULT_PRECISION_DEG,
    quantum_ms: float = DEFAULT_QUANTUM_MS,
    backend: str = "auto",
    seed: int = 0,
    dilate_steps: int = 1,
    stats: BatchStats | None = None,
    device_reduce: bool = True,
    ragged: bool = True,
    tuned: bool = True,
) -> list[CompatResult]:
    """Solve many independent link-level Table-1 problems in one pass.

    ``problems`` is a sequence of ``(patterns, capacity_gbps)`` pairs — one
    per contended link (across *all* placement candidates of a scheduling
    epoch).  Every problem takes a batched path:

      * ``k ≤ MAX_EXACT_JOBS`` jobs whose admissible shift combinations fit
        :data:`EXACT_GRID_LIMIT` — the scalar path's exact-search regime —
        enumerate the (k−1)-dimensional shift product grid as rows of a
        base-demand array (jobs 1..k−2 baked into each row, the last job
        scored for all its rotations at once), chunked to
        :data:`GRID_CHUNK_ROWS`.  On the default *ragged* kernel path all
        kernel-eligible rows of a chunk — **whatever mix of angle counts**
        — ship as ONE launch (:func:`_batched_segmin_ragged`: per-row
        ``num_angles`` masking, tournament-tree argmin and the
        product-grid acceptance scan all inside the kernel, O(problems)
        scalars back).  Non-eligible (small-angle) rows keep the
        vectorized-numpy full-matrix evaluation, grouped by angle count.

      * everything above the exact-grid cutoff runs the same seeded
        coordinate descent as the scalar path, but *lockstep-batched*: at
        each (trial, sweep, job) step the "score every rotation of the job
        being optimized" rows of all still-active problems are packed into
        one batched call — one ragged launch per step on the kernel path
        (:func:`_batched_argmin_ragged`), so each step returns one
        accepted shift per problem instead of the per-problem rotation
        rows.

    ``ragged=False`` restores the per-angle-count grouping (one launch per
    angle-count group per chunk/step — the pre-ragged behaviour, kept as
    the benchmark comparison path); ``device_reduce=False`` forces the
    full-matrix evaluation + host reduction everywhere (the pre-fusion
    behaviour, which is always grouped).  ``tuned=False`` pins every
    kernel launch to the untuned module-default schedule instead of the
    per-bucket tuning table (:mod:`repro.kernels.tune`) — schedule
    parameters are bit-inert for this family, so tuned on/off changes
    wall time only, never a shift (tests assert it).  Results are bit-identical on
    every path — tests assert it; the fold-sum padding invariance of the
    kernel family is what makes the ragged launch exact.  Pass a
    :class:`BatchStats` to observe which path each problem took
    (benchmarks assert ``scalar_fallbacks == 0``, ``device_reduced`` /
    ``bytes_returned`` prove the ``(B, A)`` round-trip is gone, and
    ``launches == batched_calls`` proves one kernel launch per
    grid-chunk/descent step on the ragged path).

    Returns one :class:`CompatResult` per problem, in input order,
    bit-identical to what per-problem ``find_rotations`` calls would produce
    (same circle construction, same argmin tie-breaking and improvement
    slack, same normalization).
    """
    stats = stats if stats is not None else BatchStats()
    stats.problems += len(problems)
    results: list[CompatResult | None] = [None] * len(problems)
    grid_probs: list[_GridProblem] = []
    descent_probs: list[_DescentState] = []
    for i, (patterns, capacity) in enumerate(problems):
        circle = _build_circle(
            patterns, precision_deg=precision_deg, quantum_ms=quantum_ms,
            dilate_steps=dilate_steps,
        )
        n = len(circle.patterns)
        grids = [circle.shift_grid(j) for j in range(n)]
        # Route exactly as the scalar _search does, so both paths stay
        # result-identical at any precision / job count.
        if n == 1:
            stats.trivial += 1
            results[i] = _finalize(circle, (0,), capacity)
        elif n <= MAX_EXACT_JOBS and int(np.prod(grids[1:])) <= EXACT_GRID_LIMIT:
            grid_probs.append(_GridProblem(i, circle, grids, float(capacity)))
        else:
            descent_probs.append(
                _DescentState(i, circle, grids, float(capacity), seed)
            )

    if grid_probs:
        _solve_grids_batched(
            grid_probs, backend, stats, device_reduce, ragged, tuned
        )
        stats.grid_problems += len(grid_probs)
        for gp in grid_probs:
            results[gp.index] = _finalize(gp.circle, gp.best, gp.capacity)
    if descent_probs:
        _solve_descent_batched(
            descent_probs, backend, stats, device_reduce, ragged, tuned
        )
        stats.descent_problems += len(descent_probs)
        for dp in descent_probs:
            results[dp.index] = _finalize(dp.circle, dp.best, dp.capacity)
    return [r for r in results if r is not None]


def _build_circle(
    patterns: Sequence[CommPattern],
    *,
    precision_deg: float,
    quantum_ms: float,
    dilate_steps: int,
) -> UnifiedCircle:
    """Unified circle with optional arc dilation (see find_rotations)."""
    import dataclasses

    circle = UnifiedCircle.build(
        patterns, precision_deg=precision_deg, quantum_ms=quantum_ms
    )
    if dilate_steps > 0:
        bw = circle.bw
        dilated = bw.copy()
        for s in range(1, dilate_steps + 1):
            dilated = np.maximum(dilated, np.roll(bw, s, axis=1))
            dilated = np.maximum(dilated, np.roll(bw, -s, axis=1))
        circle = dataclasses.replace(circle, bw=dilated)
    return circle


def _search(
    circle: UnifiedCircle, capacity_gbps: float, *, backend: str, seed: int
) -> tuple[int, ...]:
    """Pick the search strategy for one circle (Table 1 solve)."""
    n = len(circle.patterns)
    grids = [circle.shift_grid(j) for j in range(n)]
    if n == 1:
        return (0,)
    if n <= MAX_EXACT_JOBS and int(np.prod([g for g in grids[1:]])) <= EXACT_GRID_LIMIT:
        return _exact_search(circle, grids, capacity_gbps, backend)
    return _coordinate_descent(circle, grids, capacity_gbps, backend, seed)


def _finalize(
    circle: UnifiedCircle, shifts: Sequence[int], capacity_gbps: float
) -> CompatResult:
    """Score + normalize a rotation assignment into a CompatResult."""
    n = len(circle.patterns)
    score = score_for_shifts(circle, shifts, capacity_gbps)
    # normalize so the first job's shift is zero: only *relative* rotations
    # matter (global rotation leaves the score unchanged), and a zero shift
    # for the reference job makes time-shifts minimal / reproducible.
    shifts = _normalize_shifts(circle, shifts)
    shifts_ms = tuple(circle.shift_steps_to_ms(j, s) for j, s in enumerate(shifts))
    deltas = tuple(2.0 * np.pi * s / circle.num_angles for s in shifts)
    paced = tuple(circle.perimeter_ms / circle.wraps[j] for j in range(n))
    return CompatResult(
        score=score,
        shifts_steps=tuple(shifts),
        shifts_ms=shifts_ms,
        deltas_rad=deltas,
        circle=circle,
        capacity_gbps=capacity_gbps,
        paced_periods_ms=paced,
    )


def _kernel_eligible(backend: str, num_angles: int) -> bool:
    """Shapes the Pallas kernel family handles (mirrors ``_batched_excess``'s
    routing so the fused and full-matrix paths always agree on backends)."""
    return backend == "pallas" or (backend == "auto" and num_angles >= 512)


def _batched_excess(
    base: np.ndarray,
    cand: np.ndarray,
    capacity: float | np.ndarray,
    *,
    backend: str = "auto",
    stats: BatchStats | None = None,
    tuned: bool = True,
) -> np.ndarray:
    """Excess sums for every rotation of ``L`` independent rows at once.

    ``out[l, s] = Σ_α max(0, base[l, α] + cand[l, (α − s) mod A] − C_l)``.

    ``capacity`` is a scalar shared by every row or an ``(L,)`` array of
    per-row capacities — per-row capacities are what let rows from links
    with *different* capacities share one batched call (only the angle
    count must match).

    ``backend="auto"`` routes large angle grids to the Pallas
    ``circle_score`` kernel (one batched call over all rows — the TPU
    target's hot path) and everything else to a vectorized numpy evaluation;
    ``"pallas"`` / ``"numpy"`` force a path.  Both produce float32 sums like
    the scalar :func:`score_all_shifts`.

    This is the *full-matrix* evaluator: the whole ``(L, A)`` result crosses
    back to the caller (``stats`` records it), and the argmin/acceptance
    happens host-side.  The fused :func:`_batched_argmin` /
    :func:`_batched_segmin` replace it on the kernel-eligible hot paths.
    """
    base = np.asarray(base, dtype=np.float32)
    cand = np.asarray(cand, dtype=np.float32)
    l, a = base.shape
    cap = np.asarray(capacity, dtype=np.float32)
    if stats is not None:
        stats.bytes_returned += l * a * 4
        stats.bytes_matrix += l * a * 4
    if _kernel_eligible(backend, a):
        from repro.kernels.circle_score import ops as _cs_ops

        out = np.asarray(_cs_ops.circle_score(base, cand, cap, tuned=tuned))
        if stats is not None:
            stats.launches += 1
        return out
    idx = _roll_index(a)                                       # (S, A)
    cap_rows = np.broadcast_to(cap.reshape(-1, 1, 1), (l, 1, 1))
    out = np.empty((l, a), dtype=np.float32)
    # chunk rows so the (Lc, A, A) rolled/total temporaries stay cache-sized
    # regardless of batch size (see _NUMPY_CHUNK_ELEMS)
    step = max(1, _NUMPY_CHUNK_ELEMS // (a * a))
    for i in range(0, l, step):
        rolled = cand[i:i + step][:, idx]                      # (Lc, S, A)
        total = base[i:i + step, None, :] + rolled
        out[i:i + step] = np.maximum(total - cap_rows[i:i + step], 0.0).sum(axis=-1)
    return out


def _batched_argmin(
    base: np.ndarray,
    cand: np.ndarray,
    capacity: np.ndarray,
    valid: np.ndarray,
    *,
    backend: str,
    stats: BatchStats | None = None,
    tuned: bool = True,
) -> tuple[np.ndarray, np.ndarray] | None:
    """Fused per-row rotation search: ``(best_shift, best_excess)`` per row.

    Device path only — returns ``None`` when the shape is not
    kernel-eligible, so the caller takes the full-matrix evaluation + host
    ``np.argmin``; a kernel that fails raises.  Only O(L) scalars leave
    the device: ``stats.device_reduced`` counts the call and
    ``bytes_returned`` grows by the reduced result size instead of the
    ``(L, A)`` matrix.
    """
    l, a = np.asarray(base).shape
    if not _kernel_eligible(backend, a):
        return None
    from repro.kernels.circle_score import ops as _cs_ops

    idx, val = _cs_ops.circle_score_argmin(
        base, cand, capacity, valid, tuned=tuned
    )
    if stats is not None:
        stats.device_reduced += 1
        stats.launches += 1
        stats.bytes_returned += idx.nbytes + val.nbytes
        stats.bytes_matrix += l * a * 4
    return idx, val


def _batched_argmin_ragged(
    base: np.ndarray,
    cand: np.ndarray,
    capacity: np.ndarray,
    valid: np.ndarray,
    num_angles: np.ndarray,
    *,
    stats: BatchStats | None = None,
    tuned: bool = True,
) -> tuple[np.ndarray, np.ndarray]:
    """Ragged fused rotation search: mixed angle counts, ONE launch.

    ``base`` / ``cand`` are packed ``(L, W)`` rows (row ``l`` real in
    ``[:num_angles[l]]``, zero above).  The caller has already partitioned
    rows by kernel eligibility; a kernel that fails (or rejects its
    inputs with ``ValueError``) raises.
    """
    from repro.kernels.circle_score import ops as _cs_ops

    idx, val = _cs_ops.circle_score_ragged_argmin(
        base, cand, capacity, valid, num_angles, tuned=tuned
    )
    if stats is not None:
        _account_ragged(stats, base.shape, num_angles)
        stats.bytes_returned += idx.nbytes + val.nbytes
    return idx, val


def _batched_segmin(
    base: np.ndarray,
    cand: np.ndarray,
    capacity: np.ndarray,
    valid: np.ndarray,
    seg_ids: np.ndarray,
    init_best: np.ndarray,
    *,
    backend: str,
    stats: BatchStats | None = None,
    tuned: bool = True,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray] | None:
    """Fused per-row search + segmented acceptance scan, fully on device.

    One segment = the contiguous product-grid rows of one link problem
    within the chunk; ``init_best`` carries each problem's incumbent best
    excess across chunk boundaries, so the device scan replays the host
    acceptance rule (strict 1e-12 improvement, rows in product order)
    exactly.  Returns ``(accepted, row, shift, best)`` per segment — four
    O(segments) vectors instead of the ``(B, A)`` matrix — or ``None``
    when not kernel-eligible.
    """
    l, a = np.asarray(base).shape
    if not _kernel_eligible(backend, a):
        return None
    from repro.kernels.circle_score import ops as _cs_ops

    acc, row, shift, best = _cs_ops.circle_score_segmin(
        base, cand, capacity, valid, seg_ids, init_best, tuned=tuned
    )
    if stats is not None:
        stats.device_reduced += 1
        stats.launches += 1
        stats.bytes_returned += acc.nbytes + row.nbytes + shift.nbytes + best.nbytes
        stats.bytes_matrix += l * a * 4
    return acc, row, shift, best


def _batched_segmin_ragged(
    base: np.ndarray,
    cand: np.ndarray,
    capacity: np.ndarray,
    valid: np.ndarray,
    num_angles: np.ndarray,
    seg_ids: np.ndarray,
    init_best: np.ndarray,
    *,
    stats: BatchStats | None = None,
    tuned: bool = True,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Ragged fused search + segmented acceptance scan: ONE launch per
    chunk, whatever mix of angle counts the chunk's problems carry (see
    :func:`_batched_segmin` for the segment semantics).  A kernel that
    fails raises."""
    from repro.kernels.circle_score import ops as _cs_ops

    acc, row, shift, best = _cs_ops.circle_score_ragged_segmin(
        base, cand, capacity, valid, num_angles, seg_ids, init_best,
        tuned=tuned,
    )
    if stats is not None:
        _account_ragged(stats, base.shape, num_angles)
        stats.bytes_returned += acc.nbytes + row.nbytes + shift.nbytes + best.nbytes
    return acc, row, shift, best


def _account_ragged(
    stats: BatchStats, shape: tuple[int, int], num_angles: np.ndarray
) -> None:
    """Launch/row/padding telemetry shared by the ragged evaluators.

    ``bytes_matrix`` grows by each row's *real* width (Σ A_l · 4), exactly
    what the grouped full-matrix path would account for the same rows, so
    ragged-on/off byte comparisons stay apples-to-apples.  The padded
    footprint uses the launch's *bucketed* width (the wrapper rounds the
    packed width up to a power-of-two multiple of the lane size), so
    ``pad_fraction`` reports what actually shipped.
    """
    from repro.kernels.circle_score.ops import bucket_width

    l, w = shape
    wl = bucket_width(w)
    stats.device_reduced += 1
    stats.launches += 1
    stats.ragged_rows += l
    stats.ragged_real_elems += int(np.sum(num_angles))
    stats.ragged_pad_elems += l * wl
    stats.bytes_matrix += int(np.sum(num_angles)) * 4


@functools.lru_cache(maxsize=16)
def _roll_index(a: int) -> np.ndarray:
    """``idx[s, α] = (α − s) mod A`` — the gather realizing all A rolls."""
    return (np.arange(a)[None, :] - np.arange(a)[:, None]) % a


def compatibility_score(
    patterns: Sequence[CommPattern], capacity_gbps: float, **kw
) -> float:
    """Convenience: just the score (paper's compatibility *rank* input)."""
    return find_rotations(patterns, capacity_gbps, **kw).score


# ---------------------------------------------------------------------- #
# search strategies
# ---------------------------------------------------------------------- #
def _exact_search(
    circle: UnifiedCircle,
    grids: Sequence[int],
    capacity: float,
    backend: str,
) -> tuple[int, ...]:
    """Full product grid over jobs 1..n−1 (job 0 pinned at 0 by rotation
    invariance); the innermost job is scored for *all* its rotations at once
    via :func:`score_all_shifts`."""
    n = len(grids)
    if n == 1:
        return (0,)
    last = n - 1
    best_excess = np.inf
    best: tuple[int, ...] = (0,) * n
    outer_grids = [range(g) for g in grids[1:last]]  # jobs 1..n−2
    base0 = circle.bw[0]
    for mid in itertools.product(*outer_grids):
        base = base0.copy()
        for j, s in enumerate(mid, start=1):
            base += circle.rotated(j, s)
        ex = score_all_shifts(base, circle.bw[last], capacity, backend=backend)
        ex = ex[: grids[last]]  # Eq. 4 bound: distinct rotations only
        s_last = int(np.argmin(ex))
        if ex[s_last] < best_excess - ACCEPT_SLACK:
            best_excess = float(ex[s_last])
            best = (0, *mid, s_last)
        if best_excess == 0.0:
            break  # fully compatible; nothing can beat zero excess
    return best


def _coordinate_descent(
    circle: UnifiedCircle,
    grids: Sequence[int],
    capacity: float,
    backend: str,
    seed: int,
) -> tuple[int, ...]:
    """Seeded coordinate descent: repeatedly re-place each job against the sum
    of all the others, scoring every rotation at once."""
    rng = np.random.default_rng(seed)
    n = len(grids)
    best: tuple[int, ...] = (0,) * n
    best_excess = np.inf
    for trial in range(_COORD_DESCENT_SEEDS):
        if trial == 0:
            shifts = np.zeros(n, dtype=np.int64)
        else:
            shifts = np.array([rng.integers(0, g) for g in grids], dtype=np.int64)
        rotated = np.stack([circle.rotated(j, int(shifts[j])) for j in range(n)])
        total = rotated.sum(axis=0)
        for _ in range(_COORD_DESCENT_SWEEPS):
            changed = False
            for j in range(n):
                base = total - rotated[j]
                ex = score_all_shifts(base, circle.bw[j], capacity, backend=backend)
                ex = ex[: grids[j]]
                s_new = int(np.argmin(ex))
                if s_new != shifts[j]:
                    shifts[j] = s_new
                    new_rot = circle.rotated(j, s_new)
                    total = base + new_rot
                    rotated[j] = new_rot
                    changed = True
            if not changed:
                break
        ex_now = float(np.maximum(total - capacity, 0.0).sum())
        if ex_now < best_excess - ACCEPT_SLACK:
            best_excess = ex_now
            best = tuple(int(s) for s in shifts)
        if best_excess == 0.0:
            break
    return best


# ---------------------------------------------------------------------- #
# batched search (k-job product grids + lockstep coordinate descent)
# ---------------------------------------------------------------------- #
class _GridProblem:
    """One ≤ MAX_EXACT_JOBS link problem destined for the batched exact grid.

    Mirrors :func:`_exact_search` exactly: job 0 is pinned at shift 0, jobs
    1..k−2 span the outer product grid (one base-demand row per
    combination), and the last job is scored for *all* its admissible
    rotations within each row.  ``update`` replays the scalar loop's
    acceptance rule (strict improvement with 1e-12 slack, rows visited in
    ``itertools.product`` order), so the arg-result is bit-identical.
    """

    __slots__ = ("index", "circle", "grids", "capacity", "last",
                 "best", "best_excess")

    def __init__(
        self, index: int, circle: UnifiedCircle, grids: Sequence[int], capacity: float
    ) -> None:
        self.index = index
        self.circle = circle
        self.grids = list(grids)
        self.capacity = capacity
        self.last = len(grids) - 1
        self.best: tuple[int, ...] = (0,) * len(grids)
        self.best_excess = float(np.inf)

    def iter_rows(self):
        """Yield ``(mid_shifts, base_row)`` in scalar product order.

        ``base_row`` is accumulated in float64 in the same job order as the
        scalar search (bw[0] + rotated(1) + …) so the float32 cast inside
        :func:`_batched_excess` sees identical inputs.
        """
        base0 = self.circle.bw[0]
        outer = [range(g) for g in self.grids[1:self.last]]
        for mid in itertools.product(*outer):
            if self.best_excess == 0.0:
                return  # fully compatible; nothing can beat zero excess
            base = base0.copy()
            for j, s in enumerate(mid, start=1):
                base += self.circle.rotated(j, s)
            yield mid, base

    def update(self, mid: tuple[int, ...], row: np.ndarray) -> None:
        ex = row[: self.grids[self.last]]  # Eq. 4 bound
        s_last = int(np.argmin(ex))
        if float(ex[s_last]) < self.best_excess - ACCEPT_SLACK:
            self.best_excess = float(ex[s_last])
            self.best = (0, *mid, s_last)


def _solve_grids_batched(
    probs: Sequence[_GridProblem],
    backend: str,
    stats: BatchStats,
    device_reduce: bool = True,
    ragged: bool = True,
    tuned: bool = True,
) -> None:
    """Evaluate every problem's product grid through chunked batched calls.

    On the default ragged kernel path every kernel-eligible problem —
    whatever its angle count — feeds ONE shared pending-row stream,
    flushed every :data:`GRID_CHUNK_ROWS` rows as a single ragged launch
    (:func:`_solve_grids_ragged`).  Non-eligible problems (and the
    ``ragged=False`` / ``device_reduce=False`` comparison modes) keep the
    per-angle-count grouping (:func:`_solve_grids_grouped`).  All paths
    replay the scalar loop's tie-breaking exactly; flushing between
    chunks also lets ``iter_rows`` early-out the moment a problem reaches
    zero excess, exactly like the scalar break.
    """
    if ragged and device_reduce:
        kernel_probs = [
            p for p in probs if _kernel_eligible(backend, p.circle.num_angles)
        ]
        if kernel_probs:
            _solve_grids_ragged(kernel_probs, stats, tuned)
        probs = [
            p for p in probs if not _kernel_eligible(backend, p.circle.num_angles)
        ]
    if probs:
        _solve_grids_grouped(probs, backend, stats, device_reduce, tuned)


def _grid_segments(
    pending: Sequence[tuple["_GridProblem", tuple[int, ...], np.ndarray]],
) -> tuple[list["_GridProblem"], np.ndarray, np.ndarray]:
    """Contiguous per-problem segments of a pending-row chunk (rows were
    appended problem-by-problem in product order): ``(segs, seg_ids,
    init)`` where ``init`` carries each problem's incumbent best excess
    into the device acceptance scan."""
    segs: list[_GridProblem] = []
    seg_ids = np.empty(len(pending), dtype=np.int32)
    for r, (p, _, _) in enumerate(pending):
        if not segs or segs[-1] is not p:
            segs.append(p)
        seg_ids[r] = len(segs) - 1
    init = np.array([p.best_excess for p in segs], dtype=np.float64)
    return segs, seg_ids, init


def _apply_segmin(
    segs: Sequence["_GridProblem"],
    pending: Sequence[tuple["_GridProblem", tuple[int, ...], np.ndarray]],
    reduced: tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray],
) -> None:
    """Write the device acceptance scan's per-segment results back into
    the problems (shared by the ragged and grouped flushes — the two
    paths must stay bit-identical)."""
    acc, row, shift, best = reduced
    for s, p in enumerate(segs):
        if acc[s]:
            p.best_excess = float(best[s])
            p.best = (0, *pending[row[s]][1], int(shift[s]))


def _solve_grids_ragged(
    probs: Sequence[_GridProblem],
    stats: BatchStats,
    tuned: bool = True,
) -> None:
    """One ragged launch per grid chunk: rows from *all* problems, mixed
    angle counts, packed to the chunk's max width with per-row
    ``num_angles`` riding into the kernel.  Segments stay contiguous
    (rows are appended problem-by-problem in product order) and each
    problem's incumbent best rides in as its segment's init, so the
    device acceptance scan replays the host rule exactly — results are
    bit-identical to the per-group launches by the fold-sum padding
    invariance."""
    pending: list[tuple[_GridProblem, tuple[int, ...], np.ndarray]] = []

    def flush() -> None:
        if not pending:
            return
        stats.batched_calls += 1
        stats.grid_rows += len(pending)
        widths = np.array(
            [p.circle.num_angles for p, _, _ in pending], dtype=np.int32
        )
        w = int(widths.max())
        base = np.zeros((len(pending), w))
        cand = np.zeros((len(pending), w))
        for r, (p, _, row) in enumerate(pending):
            base[r, : row.shape[0]] = row
            cand[r, : row.shape[0]] = p.circle.bw[p.last]
        caps = np.array([p.capacity for p, _, _ in pending], dtype=np.float32)
        valid = np.array([p.grids[p.last] for p, _, _ in pending], dtype=np.int32)
        segs, seg_ids, init = _grid_segments(pending)
        reduced = _batched_segmin_ragged(
            base, cand, caps, valid, widths, seg_ids, init,
            stats=stats, tuned=tuned,
        )
        _apply_segmin(segs, pending, reduced)
        pending.clear()

    for p in probs:
        for mid, base_row in p.iter_rows():
            pending.append((p, mid, base_row))
            if len(pending) >= GRID_CHUNK_ROWS:
                flush()
    flush()


def _solve_grids_grouped(
    probs: Sequence[_GridProblem],
    backend: str,
    stats: BatchStats,
    device_reduce: bool = True,
    tuned: bool = True,
) -> None:
    """Per-angle-count grouping (the pre-ragged layout, kept for the
    vectorized-numpy rows and as the ragged comparison path): rows are
    grouped by angle count — per-row capacities let links with different
    capacities share a call — and flushed every :data:`GRID_CHUNK_ROWS`
    rows, one launch per group per chunk.

    On kernel-eligible shapes (``device_reduce=True``) each chunk goes
    through :func:`_batched_segmin`: one segment per problem (rows stay in
    product order, the problem's incumbent best rides in as the segment's
    init), and the per-row argmin *and* the acceptance scan run on device —
    only per-problem ``(accepted, row, shift, best)`` scalars come back.
    Otherwise the full ``(B, A)`` matrix is evaluated and the sequential
    ``update`` scan runs host-side.
    """
    by_angles: dict[int, list[_GridProblem]] = {}
    for p in probs:
        by_angles.setdefault(p.circle.num_angles, []).append(p)

    for num_angles, group in by_angles.items():
        pending: list[tuple[_GridProblem, tuple[int, ...], np.ndarray]] = []
        # hoisted: on the numpy path (small grids) the per-chunk segment
        # bookkeeping below would be pure overhead
        try_device = device_reduce and _kernel_eligible(backend, num_angles)

        def flush() -> None:
            if not pending:
                return
            base = np.stack([row for _, _, row in pending])
            cand = np.stack([p.circle.bw[p.last] for p, _, _ in pending])
            caps = np.array([p.capacity for p, _, _ in pending], dtype=np.float32)
            stats.batched_calls += 1
            stats.grid_rows += len(pending)
            reduced = None
            if try_device:
                segs, seg_ids, init = _grid_segments(pending)
                valid = np.array(
                    [p.grids[p.last] for p, _, _ in pending], dtype=np.int32
                )
                reduced = _batched_segmin(
                    base, cand, caps, valid, seg_ids, init,
                    backend=backend, stats=stats, tuned=tuned,
                )
            if reduced is not None:
                _apply_segmin(segs, pending, reduced)
            else:
                ex = _batched_excess(
                    base, cand, caps, backend=backend, stats=stats, tuned=tuned
                )
                for (p, mid, _), row_ex in zip(pending, ex):
                    p.update(mid, row_ex)
            pending.clear()

        for p in group:
            for mid, base_row in p.iter_rows():
                pending.append((p, mid, base_row))
                if len(pending) >= GRID_CHUNK_ROWS:
                    flush()
        flush()


class _DescentState:
    """Per-problem state of the lockstep-batched coordinate descent.

    Replays :func:`_coordinate_descent` step for step — same zero/random
    trial seeds drawn from a per-problem ``default_rng(seed)`` in the same
    order, same sweep convergence break, same end-of-trial acceptance and
    zero-excess early exit — with only the "score every rotation of job j"
    evaluation delegated to a shared batched call.
    """

    __slots__ = ("index", "circle", "grids", "capacity", "n", "rng",
                 "best", "best_excess", "done", "in_sweep", "changed",
                 "shifts", "rotated", "total")

    def __init__(
        self,
        index: int,
        circle: UnifiedCircle,
        grids: Sequence[int],
        capacity: float,
        seed: int,
    ) -> None:
        self.index = index
        self.circle = circle
        self.grids = list(grids)
        self.capacity = capacity
        self.n = len(grids)
        self.rng = np.random.default_rng(seed)
        self.best: tuple[int, ...] = (0,) * self.n
        self.best_excess = float(np.inf)
        self.done = False
        self.in_sweep = False
        self.changed = False
        self.shifts: np.ndarray | None = None
        self.rotated: np.ndarray | None = None
        self.total: np.ndarray | None = None

    def start_trial(self, trial: int) -> None:
        if trial == 0:
            self.shifts = np.zeros(self.n, dtype=np.int64)
        else:
            self.shifts = np.array(
                [self.rng.integers(0, g) for g in self.grids], dtype=np.int64
            )
        self.rotated = np.stack(
            [self.circle.rotated(j, int(self.shifts[j])) for j in range(self.n)]
        )
        self.total = self.rotated.sum(axis=0)
        self.in_sweep = True

    def job_row(self, j: int) -> tuple[np.ndarray, np.ndarray]:
        """(base, cand) for re-placing job ``j`` against all the others."""
        return self.total - self.rotated[j], self.circle.bw[j]

    def apply(self, j: int, base: np.ndarray, row: np.ndarray) -> None:
        """Host-side acceptance: argmin over job ``j``'s admissible shifts."""
        ex = row[: self.grids[j]]
        self.apply_shift(j, base, int(np.argmin(ex)))

    def apply_shift(self, j: int, base: np.ndarray, s_new: int) -> None:
        """Accept the (host- or device-computed) best shift for job ``j``."""
        if s_new != self.shifts[j]:
            self.shifts[j] = s_new
            new_rot = self.circle.rotated(j, s_new)
            self.total = base + new_rot
            self.rotated[j] = new_rot
            self.changed = True

    def end_trial(self) -> None:
        ex_now = float(np.maximum(self.total - self.capacity, 0.0).sum())
        if ex_now < self.best_excess - ACCEPT_SLACK:
            self.best_excess = ex_now
            self.best = tuple(int(s) for s in self.shifts)
        if self.best_excess == 0.0:
            self.done = True


def _solve_descent_batched(
    states: Sequence[_DescentState],
    backend: str,
    stats: BatchStats,
    device_reduce: bool = True,
    ragged: bool = True,
    tuned: bool = True,
) -> None:
    """Run all coordinate descents in lockstep, batching each step's rows.

    At step (trial, sweep, job j) the base-vs-candidate rows of every
    problem still active at that step are scored in one batched call —
    one row per problem, every candidate shift of job ``j`` covered by
    the call's rotation axis.  On the default ragged kernel path *all*
    kernel-eligible rows ship as ONE launch per step whatever mix of
    angle counts they carry (:func:`_batched_argmin_ragged` — the
    padding/masking invariants make the result bit-identical to the
    per-group launches); ``ragged=False`` restores the per-angle-count
    grouping, and non-eligible rows always take the grouped full-matrix
    evaluation plus host ``np.argmin``.  Per-problem updates between
    steps keep the exact scalar semantics (sequential-within-sweep,
    convergence breaks, seeded restarts) — accepted-shift sequences are
    identical on every path.
    """
    def step_grouped(group_states: list[_DescentState], j: int) -> None:
        by_angles: dict[int, list[_DescentState]] = {}
        for s in group_states:
            by_angles.setdefault(s.circle.num_angles, []).append(s)
        for num_angles, group in by_angles.items():
            rows = [s.job_row(j) for s in group]
            base = np.stack([b for b, _ in rows])
            cand = np.stack([c for _, c in rows])
            caps = np.array([s.capacity for s in group], dtype=np.float32)
            stats.batched_calls += 1
            stats.descent_rows += len(group)
            reduced = None
            if device_reduce and _kernel_eligible(backend, num_angles):
                valid = np.array([s.grids[j] for s in group], dtype=np.int32)
                reduced = _batched_argmin(
                    base, cand, caps, valid,
                    backend=backend, stats=stats, tuned=tuned,
                )
            if reduced is not None:
                s_new, _ = reduced
                for s, (b, _), sn in zip(group, rows, s_new):
                    s.apply_shift(j, b, int(sn))
            else:
                ex = _batched_excess(
                    base, cand, caps, backend=backend, stats=stats, tuned=tuned
                )
                for s, (b, _), row in zip(group, rows, ex):
                    s.apply(j, b, row)

    def step_ragged(group: list[_DescentState], j: int) -> None:
        """One ragged launch for the step's kernel-eligible rows."""
        rows = [s.job_row(j) for s in group]
        widths = np.array([s.circle.num_angles for s in group], dtype=np.int32)
        w = int(widths.max())
        base = np.zeros((len(group), w))
        cand = np.zeros((len(group), w))
        for r, (b, c) in enumerate(rows):
            base[r, : b.shape[0]] = b
            cand[r, : c.shape[0]] = c
        caps = np.array([s.capacity for s in group], dtype=np.float32)
        valid = np.array([s.grids[j] for s in group], dtype=np.int32)
        s_new, _ = _batched_argmin_ragged(
            base, cand, caps, valid, widths, stats=stats, tuned=tuned
        )
        stats.batched_calls += 1
        stats.descent_rows += len(group)
        for s, (b, _), sn in zip(group, rows, s_new):
            s.apply_shift(j, b, int(sn))

    for trial in range(_COORD_DESCENT_SEEDS):
        live = [s for s in states if not s.done]
        if not live:
            break
        for s in live:
            s.start_trial(trial)
        for _ in range(_COORD_DESCENT_SWEEPS):
            sweeping = [s for s in live if s.in_sweep]
            if not sweeping:
                break
            for s in sweeping:
                s.changed = False
            for j in range(max(s.n for s in sweeping)):
                stepping = [s for s in sweeping if j < s.n]
                grouped = stepping
                if ragged and device_reduce:
                    eligible = [
                        s for s in stepping
                        if _kernel_eligible(backend, s.circle.num_angles)
                    ]
                    grouped = [
                        s for s in stepping
                        if not _kernel_eligible(backend, s.circle.num_angles)
                    ]
                    if eligible:
                        step_ragged(eligible, j)
                if grouped:
                    step_grouped(grouped, j)
            for s in sweeping:
                s.in_sweep = s.changed
        for s in live:
            s.end_trial()


def _normalize_shifts(
    circle: UnifiedCircle, shifts: Sequence[int]
) -> tuple[int, ...]:
    """Rotate all jobs together so job 0's shift becomes 0, then reduce each
    job's shift modulo its own distinct-rotation count (identity rotations)."""
    s0 = shifts[0]
    out = []
    for j, s in enumerate(shifts):
        g = circle.shift_grid(j)
        out.append(int((s - s0) % circle.num_angles) % g)
    return tuple(out)
