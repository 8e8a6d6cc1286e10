"""Jitted public entry points for the circle_score kernel family.

``circle_score(base, cand, capacity)`` dispatches to the full-matrix
Pallas kernel (interpret mode on CPU — the TPU target compiles the same
kernel with ``interpret=False``) and is what :mod:`repro.core.compat`
calls for its numpy-free fallback paths and what the tests oracle against.

``circle_score_argmin`` is the fused reduction: per-row
``(best_shift, best_excess)`` computed inside the kernel (chunked
tournament-tree argmin), so only O(L) scalars cross the device→host
boundary instead of the O(L·A) excess matrix.

``circle_score_ragged_argmin`` is the same kernel with per-row angle
counts: rows built on *different* unified circles (mixed ``A_l``) ship
as ONE launch, each row masked to its own ``num_angles[l]`` angles and
``valid[l]`` admissible shifts.  The fold-sum row reduction is
padding-invariant, so ragged results are bit-identical to per-group
launches of the uniform entry point (tests assert it).

``circle_score_segmin`` / ``circle_score_ragged_segmin`` layer the
segmented accept-scan on top: rows belong to contiguous *segments* (one
segment = one link problem's product-grid rows within a chunk) and the
scan replays the host coordinate-search acceptance rule — visit rows in
order, accept a row's best shift iff it beats the segment's incumbent by
more than the 1e-12 slack — entirely on device, returning four
O(num_segments) vectors.  The scan runs in float64 (via
:func:`jax.enable_x64`) so the ``excess < best − 1e-12``
predicate is evaluated in exactly the arithmetic the host search uses
(python floats), keeping accepted-shift sequences bit-identical even for
sub-ulp float32 excess differences.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans

from .kernel import (
    LANE_MULTIPLE,
    _next_pow2,
    circle_score_argmin_pallas,
    circle_score_pallas,
)
from .ref import circle_score_argmin_ref, circle_score_ref

__all__ = [
    "circle_score",
    "circle_score_argmin",
    "circle_score_ragged_argmin",
    "circle_score_segmin",
    "circle_score_ragged_segmin",
    "circle_score_ref",
    "circle_score_argmin_ref",
    "bucket_width",
    "ACCEPT_SLACK",
]


def bucket_width(w: int) -> int:
    """Bucketed ragged launch width: the smallest power-of-two multiple of
    :data:`LANE_MULTIPLE` ≥ ``w`` (128, 256, 512, 1024, …).

    Ragged batches ship at their chunk's max angle count, and a long-tailed
    mix of unified-circle sizes would otherwise present the jit cache with
    one distinct lane width — hence one Mosaic recompile — per chunk.
    Rounding the packed width up to a small fixed set of buckets caps the
    compile count at O(log max_width) for any angle-count distribution;
    the fold-sum padding invariance makes the wider launch bit-exact
    (tests assert both the cache bound and the parity).
    """
    if w < 1:
        raise ValueError(f"width must be positive, got {w}")
    b = LANE_MULTIPLE
    while b < w:
        b *= 2
    return b


def row_bucket(l: int, block_l: int) -> int:
    """Bucketed launch height: the smallest power-of-two multiple of
    ``block_l`` ≥ ``l``.

    The jit key includes the row count, so every distinct chunk height
    would be one more Mosaic compile — and descent steps and grid chunks
    change height all the time.  The fused entry points pad rows on the
    host to this bucket (pad rows are internal: ``valid = 0``, sliced
    off), capping compiles at O(log max_rows) per width bucket.
    """
    return block_l * _next_pow2(-(-l // block_l))


def _interpret() -> bool:
    """Pallas interpret mode everywhere but the TPU, decided at each call:
    a run on the chip always compiles the kernels with Mosaic."""
    return jax.default_backend() != "tpu"

# The host rotation search's strict-improvement slack — ONE source of truth,
# owned by repro.core.compat (numpy-only, no import cycle: compat only loads
# this module lazily inside functions).  Re-exported here because the device
# accept scan below evaluates the same predicate.
from repro.core.compat import ACCEPT_SLACK  # noqa: E402


def _schedule(variant: str, width: int, tuned: bool, **explicit) -> dict:
    """Resolve a launch's schedule parameters (block_l, shift_chunk, …).

    Explicit non-``None`` kwargs always win; otherwise ``tuned=True``
    consults the per-bucket tuning table (:mod:`repro.kernels.tune` —
    every loader failure mode already falls back to defaults inside
    ``lookup``) and ``tuned=False`` pins the kernels' module defaults
    (the untuned comparison path the autotuner and benches measure
    against).  Schedule parameters are bit-inert for this family, so
    this choice can only ever move wall time.
    """
    from repro.kernels import tune

    params = (
        tune.lookup(variant, width) if tuned else dict(tune.DEFAULTS[variant])
    )
    params.update({k: v for k, v in explicit.items() if v is not None})
    return params


def circle_score(base, cand, capacity, *, tuned=True, block_l=None) -> jax.Array:
    """``capacity`` may be a scalar (shared by all rows) or an ``(L,)`` /
    ``(L, 1)`` array of per-row link capacities.  ``tuned`` / ``block_l``
    select the launch schedule (see :func:`_schedule`); outputs are
    bit-identical for every choice."""
    base = jnp.atleast_2d(jnp.asarray(base, jnp.float32))
    cand = jnp.atleast_2d(jnp.asarray(cand, jnp.float32))
    cap = jnp.asarray(capacity, jnp.float32)
    sched = _schedule("circle_score", base.shape[1], tuned, block_l=block_l)
    return circle_score_pallas(base, cand, cap, interpret=_interpret(), **sched)


def _pad_rows(base, cand, capacity, valid, num_angles, sched):
    """Pad rows to :func:`row_bucket` on the host: the five launch
    operands, pad rows last."""
    l = base.shape[0]
    lb = row_bucket(l, sched["block_l"])
    cap = np.broadcast_to(np.asarray(capacity, np.float32).reshape(-1), (l,))
    rows = (0, lb - l)
    return (
        np.pad(base, (rows, (0, 0))),
        np.pad(cand, (rows, (0, 0))),
        np.pad(cap, rows),
        np.pad(valid, rows),
        # pad rows: period 1, no admissible shift
        np.pad(num_angles, rows, constant_values=1),
    )


def _launch_argmin(operands, sched, interpret):
    """Upload the padded operands one by one and launch the fused kernel.
    Returns the bucketed ``(idx, val)`` device arrays."""
    with spans.span("launch/put", arrays=len(operands)) as sp:
        if spans.enabled():
            sp.set(bytes=sum(a.nbytes for a in operands))
        dev = [jnp.asarray(a) for a in operands]
    with spans.span("launch/dispatch"):
        return circle_score_argmin_pallas(*dev, interpret=interpret, **sched)


def _argmin_device(
    base, cand, capacity, valid, variant, *, tuned, block_l, shift_chunk,
):
    """Uniform launch: every row spans all ``A`` angles."""
    with spans.span("launch/prep"):
        base = np.atleast_2d(np.asarray(base, np.float32))
        cand = np.atleast_2d(np.asarray(cand, np.float32))
        l, a = base.shape
        if valid is None:
            valid = np.full((l,), a, np.int32)
        else:
            valid = np.broadcast_to(np.asarray(valid, np.int32).reshape(-1), (l,))
        sched = _schedule(
            variant, a, tuned, block_l=block_l, shift_chunk=shift_chunk
        )
        operands = _pad_rows(
            base, cand, capacity, valid, np.full((l,), a, np.int32), sched
        )
        interpret = _interpret()
    idx, val = _launch_argmin(operands, sched, interpret)
    return idx, val, l


def _ragged_device(
    base, cand, capacity, valid, num_angles, variant, *, pad_to,
    tuned, block_l, shift_chunk,
):
    """Ragged launch: validate, bucket the width, launch."""
    with spans.span("launch/prep"):
        base = np.atleast_2d(np.asarray(base, np.float32))
        cand = np.atleast_2d(np.asarray(cand, np.float32))
        l, w = base.shape
        na = np.broadcast_to(np.asarray(num_angles, np.int32), (l,))
        valid = np.broadcast_to(np.asarray(valid, np.int32), (l,))
        if np.any(na < 1) or np.any(na > w):
            raise ValueError(f"num_angles must lie in [1, {w}], got {na}")
        if np.any(valid < 1) or np.any(valid > na):
            # valid == 0 is the *internal* block-padding convention of the
            # kernel (rows the wrapper slices off); a caller-supplied row with
            # no admissible shift would come back as a fabricated perfect
            # (shift 0, excess 0) — reject it instead
            raise ValueError("valid shift counts must lie in [1, num_angles]")
        # bucket the packed width host-side (zero-pad the angle axis) so the
        # jit cache key only ever sees O(log max_width) distinct widths; rows
        # are masked to num_angles in-kernel, so padding is provably inert
        wb = bucket_width(max(w, pad_to or 0))
        if wb != w:
            base = np.pad(base, ((0, 0), (0, wb - w)))
            cand = np.pad(cand, ((0, 0), (0, wb - w)))
        # the table is keyed by exactly this bucketed launch width, so the
        # lookup and the jit cache see the same (variant, bucket) universe
        sched = _schedule(
            variant, wb, tuned, block_l=block_l, shift_chunk=shift_chunk
        )
        operands = _pad_rows(base, cand, capacity, valid, na, sched)
        interpret = _interpret()
    idx, val = _launch_argmin(operands, sched, interpret)
    return idx, val, l


def circle_score_argmin(
    base, cand, capacity, valid=None,
    *, tuned=True, block_l=None, shift_chunk=None,
):
    """Fused rotation search: ``(best_shift, best_excess)`` per row.

    ``valid`` bounds the admissible shifts per row (Eq. 4: job ``j`` only
    has ``A / r_j`` distinct rotations); ``None`` admits all ``A`` shifts.
    Bit-identical to ``np.argmin`` over ``circle_score(...)[l, :valid[l]]``
    (first-index tie-breaking) without ever materializing the matrix —
    for every launch schedule, tuned or not.  Returns host arrays.
    """
    idx, val, l = _argmin_device(
        base, cand, capacity, valid, "circle_score_argmin",
        tuned=tuned, block_l=block_l, shift_chunk=shift_chunk,
    )
    with spans.span("launch/fetch"):
        return np.asarray(idx)[:l], np.asarray(val)[:l]


def circle_score_ragged_argmin(
    base, cand, capacity, valid, num_angles, *, pad_to=None,
    tuned=True, block_l=None, shift_chunk=None,
):
    """Ragged fused rotation search: ONE launch over mixed angle counts.

    Args:
      base, cand: (L, W) float32, row ``l`` real in ``[:num_angles[l]]``
        and zero-padded above (W = the packed batch width ≥ max A_l).
      capacity: scalar or (L,) per-row link capacities.
      valid: (L,) int32 admissible shifts per row (1 ≤ valid ≤ A_l).
      num_angles: (L,) int32 per-row real angle counts (1 ≤ A_l ≤ W).
      pad_to: optionally force a wider launch width (tests); the actual
        launch width is always rounded up to a :func:`bucket_width`
        bucket and the row count to a :func:`row_bucket` — both bit-exact
        by the masking invariants — so long-tailed mixes of angle counts
        and chunk heights stop paying one jit recompile each.
      tuned, block_l, shift_chunk: launch schedule selection (see
        :func:`_schedule`) — the table lookup is keyed by the bucketed
        launch width; outputs are bit-identical for every schedule.

    Returns host ``(best_shift, best_excess)`` per row, bit-identical to
    invoking :func:`circle_score_argmin` once per angle-count group on
    the tightly-sliced rows.
    """
    idx, val, l = _ragged_device(
        base, cand, capacity, valid, num_angles, "circle_score_argmin",
        pad_to=pad_to, tuned=tuned, block_l=block_l, shift_chunk=shift_chunk,
    )
    with spans.span("launch/fetch"):
        return np.asarray(idx)[:l], np.asarray(val)[:l]


@jax.jit
def _accept_scan(val, idx, seg_ids, init_best):
    """Sequential accept fold over rows, segmented by ``seg_ids``.

    Path-dependent by design (the slack rule is not associative), hence a
    scan rather than a segmented min.  Must run under x64 so the predicate
    matches the host's float64 comparison exactly.
    """
    num_segs = init_best.shape[0]
    rows = jnp.arange(val.shape[0], dtype=jnp.int32)

    def step(state, xs):
        best, row, shift, acc = state
        v, i, sid, r = xs
        take = v < best[sid] - ACCEPT_SLACK
        best = best.at[sid].set(jnp.where(take, v, best[sid]))
        row = row.at[sid].set(jnp.where(take, r, row[sid]))
        shift = shift.at[sid].set(jnp.where(take, i, shift[sid]))
        acc = acc.at[sid].set(jnp.logical_or(acc[sid], take))
        return (best, row, shift, acc), None

    init = (
        init_best.astype(jnp.float64),
        jnp.zeros(num_segs, jnp.int32),
        jnp.zeros(num_segs, jnp.int32),
        jnp.zeros(num_segs, jnp.bool_),
    )
    (best, row, shift, acc), _ = jax.lax.scan(
        step, init, (val.astype(jnp.float64), idx, seg_ids, rows)
    )
    return acc, row, shift, best


def _segmin_from(idx, val, l, seg_ids, init_best):
    """Shared accept-scan tail of the (ragged) segmin entry points.

    ``idx`` / ``val`` stay on device at their bucketed height; their pad
    rows (past ``l``) go to one extra dummy segment, and the segment axis
    is padded to a power of two with ``+inf`` incumbents, so the scan's
    jit key sees bucketed shapes only.  Returns host arrays."""
    with spans.span("accept/dispatch") as handle:
        lb = idx.shape[0]
        num_segs = len(init_best)
        sp = _next_pow2(num_segs + 1)
        seg = np.full((lb,), num_segs, np.int32)
        seg[:l] = np.asarray(seg_ids, np.int32)
        init = np.full((sp,), np.inf, np.float64)
        init[:num_segs] = np.asarray(init_best, np.float64)
        if spans.enabled():
            handle.set(bytes=seg.nbytes + init.nbytes)
        with jax.enable_x64(True):
            out = _accept_scan(val, idx, jnp.asarray(seg), jnp.asarray(init))
    with spans.span("accept/fetch"):
        return tuple(np.asarray(x)[:num_segs] for x in out)


def circle_score_segmin(
    base, cand, capacity, valid, seg_ids, init_best,
    *, tuned=True, block_l=None, shift_chunk=None,
):
    """Fused rotation search + segmented acceptance, fully device-side.

    Args:
      base, cand, capacity, valid: as :func:`circle_score_argmin`.
      seg_ids: (L,) int — segment index of each row (rows of one segment
        must be contiguous and in host visit order).
      init_best: (S,) float64 — each segment's incumbent best excess from
        previous chunks (``inf`` for a fresh segment).
      tuned, block_l, shift_chunk: launch schedule, resolved against the
        ``circle_score_segmin`` table entries (the grid path's tall
        chunks tune differently from the descent path's short steps).

    Returns ``(accepted (S,) bool, row (S,) int32, shift (S,) int32,
    best (S,) float64)`` — ``row`` is the chunk-global index of the
    accepted row; entries with ``accepted == False`` carry their init
    state.  Only these four O(S) vectors leave the device.
    """
    idx, val, l = _argmin_device(
        base, cand, capacity, valid, "circle_score_segmin",
        tuned=tuned, block_l=block_l, shift_chunk=shift_chunk,
    )
    return _segmin_from(idx, val, l, seg_ids, init_best)


def circle_score_ragged_segmin(
    base, cand, capacity, valid, num_angles, seg_ids, init_best, *,
    pad_to=None, tuned=True, block_l=None, shift_chunk=None,
):
    """Ragged :func:`circle_score_segmin`: one launch over mixed angle
    counts (see :func:`circle_score_ragged_argmin`), then the same
    segmented device-side acceptance scan.  The schedule resolves against
    the ``circle_score_segmin`` table entries, keyed by the bucketed
    launch width."""
    idx, val, l = _ragged_device(
        base, cand, capacity, valid, num_angles, "circle_score_segmin",
        pad_to=pad_to, tuned=tuned, block_l=block_l, shift_chunk=shift_chunk,
    )
    return _segmin_from(idx, val, l, seg_ids, init_best)
