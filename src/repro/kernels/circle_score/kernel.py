"""Pallas TPU kernels for CASSINI compatibility scoring (paper Table 1).

For every link row ``l`` and candidate rotation ``s``:

    out[l, s] = Σ_α max(0, base[l, α] + cand[l, (α − s) mod A_l] − C_l)

This is the inner loop of the rotation search (:mod:`repro.core.compat`) —
a circular-shift correlation with a ReLU inside the reduction, evaluated
for *all* admissible rotations of a candidate job against the
already-placed demand ``base``.  The scheduler evaluates thousands of
(candidate × link) rows per epoch at 10 candidates × O(links)
(Algorithm 2), so the batched form is the hot-spot.

Two kernel variants share the same inner arithmetic:

  * :func:`circle_score_pallas` — the full ``(L, A)`` excess matrix
    (kept for the host-reduction fallback paths and for tests);
  * :func:`circle_score_argmin_pallas` — the fused *ragged* reduction:
    every row carries its own angle count ``num_angles[l]`` (``A_l``) and
    admissible-shift bound ``valid[l]``, so link problems built on
    *different* unified circles ship in ONE launch.  The argmin is a
    **chunked tournament tree**: each round evaluates
    :data:`SHIFT_CHUNK` independent shifts, reduces them with a
    log-depth pairwise ``(value, index)`` tournament and merges one
    champion into the ``(BL, 1)`` running best — the lexicographic
    compare (take the right operand iff ``(rv < lv) or (rv == lv and
    ri < li)``) preserves the strict-``<`` lowest-shift tie-break of
    host ``np.argmin`` for *any* tree shape, and the sequential depth
    drops by the chunk factor versus the old one-shift-per-iteration
    scan.  Only ``O(L)`` scalars ever leave the device.

Ragged row layout and masking invariants (see docs/architecture.md):

  * the angle axis is padded to the batch-wide lane width ``AP`` (a
    multiple of :data:`LANE_MULTIPLE`); ``base`` is zero beyond ``A_l``;
  * the kernel builds, once per row block, a ``(BL, 2·AP)`` buffer ``cc``
    with ``cc[l, AP + j] = cand[l, j]`` and ``cc[l, AP − A_l + j] =
    cand[l, j]`` for ``j < A_l`` — two periods of every row's candidate
    meeting at lane ``AP`` (:func:`_periodic_buffer`: a log-depth barrel
    of static lane rolls, no gathers, any mix of periods).  The roll by
    any shift ``s < A_l`` is then ``pltpu.roll(cc, s)[:, AP:]`` — one
    lane rotate by the row-independent ``s`` and one static, aligned
    slice — and it reads ``cc`` only inside that two-period window;
  * per-shift excess terms at angles ``α ≥ A_l`` are masked to exactly
    ``0.0`` before the row reduction, and shifts ``s ≥ valid[l]`` are
    masked to ``+inf`` before the tournament — padded angles and
    inadmissible shifts provably cannot win any reduction;
  * row sums use :func:`_fold_sum`: ascending sequential accumulation
    of 128-lane groups into one fixed-width partial plus one fixed-shape
    reduce.  Zero groups appended by wider padding are exact additive
    identities, so the fold at *any* padded width ``≥ A_l`` produces
    bit-identical float32 sums — this is what makes a ragged launch
    bit-identical to per-group launches (and to the full-matrix kernel
    the scalar search scores through), regardless of what other rows
    share the batch.

The tournament loop exits early once every row's running best has
reached zero — excess sums are non-negative and ties resolve to the
earlier shift, so nothing can displace a found zero, and each row's
evaluated prefix is guaranteed to contain its first zero shift, which
the tournament selects exactly like ``np.argmin`` over the full window.

TPU mapping: the circle rows live in VMEM (A ≤ ~2k angles ⇒ a (BL, AP)
f32 tile is ≤ 1 MiB); rolls are lane rotates of the (BL, 2·AP) buffer
(``pltpu.roll``, which Mosaic lowers for a traced shift — a dynamic
slice at the unaligned start ``AP − s`` it refuses), the chunk's shift
evaluations are independent (pipelineable; the only carried state is the
(BL, 1) champion pair) and both reductions (fold sum, tournament argmin)
are log-depth.  Every store is lane-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 8-row blocks amortize poorly; 32 measured ~1.5-2x faster for both kernel
# variants on large batches (and is still one VREG sublane tile on TPU).
DEFAULT_BLOCK_L = 32
# Mosaic wants the lane (minor) dimension a multiple of 128; the wrappers
# zero-pad the angle axis up to this multiple by default (masked in-kernel,
# exact — see module docstring).
LANE_MULTIPLE = 128
# Default shifts evaluated per tournament round of the fused argmin
# kernel: each loop iteration scores this many consecutive shifts
# (independent slices, unrolled — no carried dependence between them),
# reduces them with a log-depth tournament and merges one (value, index)
# champion pair into the (BL, 1) running best.  Cuts the loop's
# sequential depth by the chunk factor while keeping the carried state
# tiny — materializing the full per-shift value matrix instead (one
# store per iteration) measured ~4x slower because the loop then drags a
# (BL, AP) buffer through every iteration.  The chunk width is a
# traced-static kernel parameter (``shift_chunk``); this module constant
# is only the untuned default — per-bucket winners live in the
# repro.kernels.tune tables and flow in through the ops wrappers.
SHIFT_CHUNK = 8


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _fold_sum(x: jax.Array) -> jax.Array:
    """Padding-invariant row sums: ``(BL, W) → (BL, 1)``.

    Pads to a multiple of :data:`LANE_MULTIPLE` with zeros, accumulates
    the 128-lane groups **sequentially in ascending order** into one
    128-wide partial, then reduces that partial with one fused
    ``jnp.sum``.

    Invariance: if ``x[l, α] == 0`` for all ``α ≥ A_l``, lane ``i`` of
    the partial is ``(...(x[l,i] + x[l,i+128]) + x[l,i+256]) + ...`` —
    appending all-zero groups (any wider padding) only appends
    ``v + 0.0`` steps, which are exact in IEEE (all operands ``≥ +0.0``),
    so the partial is elementwise identical for every batch width
    ``≥ A_l``.  The closing reduce then always runs on the same static
    ``(·, 128)`` shape, so XLA emits one fixed reduction whose result is
    a function of the partial alone (batch-width, row-count and
    pallas-vs-host invariant — pinned by the parity tests).  Plain
    ``jnp.sum`` over the raw row does NOT have this property (XLA
    regroups partials per width, measured), which is why every
    kernel-family row sum goes through this fold.
    """
    bl, w = x.shape
    wp = -(-w // LANE_MULTIPLE) * LANE_MULTIPLE
    if wp != w:
        x = jnp.pad(x, ((0, 0), (0, wp - w)))
    acc = x[:, :LANE_MULTIPLE]
    for k in range(1, wp // LANE_MULTIPLE):
        acc = acc + x[:, k * LANE_MULTIPLE : (k + 1) * LANE_MULTIPLE]
    return jnp.sum(acc, axis=-1, keepdims=True)


def _tournament_min(
    lv: jax.Array, li: jax.Array, rv: jax.Array, ri: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """One tournament round: elementwise lexicographic ``(value, index)``
    min.  The right operand wins iff ``rv < lv or (rv == lv and ri < li)``
    — so ties always resolve to the lowest index no matter how a tree
    pairs elements: at every internal node the survivor is the
    lexicographic minimum of the leaves below it, hence the root is the
    global ``(min value, first index of it)`` — exactly ``np.argmin``
    (proof sketch in docs/architecture.md)."""
    take_r = jnp.logical_or(rv < lv, jnp.logical_and(rv == lv, ri < li))
    return jnp.where(take_r, rv, lv), jnp.where(take_r, ri, li)


def _tournament_argmin(
    vals: jax.Array, idx: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Tournament-tree argmin: ``(BL, S) → ((BL, 1) val, (BL, 1) idx)``.

    Log-depth pairwise halving over ``(value, index)`` pairs using
    :func:`_tournament_min`; the lexicographic compare makes the result
    independent of the tree shape.  Padding columns are ``+inf`` and can
    only win when a whole row is ``+inf`` (then the lowest index wins,
    like argmin over a constant row).
    """
    bl, s = vals.shape
    p = _next_pow2(s)
    if p != s:
        vals = jnp.pad(vals, ((0, 0), (0, p - s)), constant_values=jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, p - s)))
    while vals.shape[1] > 1:
        h = vals.shape[1] // 2
        vals, idx = _tournament_min(
            vals[:, :h], idx[:, :h], vals[:, h:], idx[:, h:]
        )
    return vals, idx


def _periodic_buffer(cand: jax.Array, na: jax.Array) -> jax.Array:
    """``(BL, AP)`` candidate rows → the ``(BL, 2·AP)`` roll buffer ``cc``.

    ``cc = [left | cand]`` where ``left`` is each row rotated right by its
    own ``AP − A_l``, so ``cc[l, AP − A_l + j] = cand[l, j]`` for ``j <
    A_l``: two periods of the row meet at lane ``AP``.  The row-dependent
    rotation is a barrel of static lane rolls (bit ``b`` of ``AP − A_l``
    selects a roll by ``2**b``), which Mosaic lowers without gathers.
    Outside the two-period window ``cc`` holds other candidate values;
    every read there is masked (see the module docstring).
    """
    bl, ap = cand.shape
    r = ap - na                                         # (BL, 1) in [0, AP)
    left = cand
    b = 1
    while b < ap:
        left = jnp.where((r & b) != 0, pltpu.roll(left, b, 1), left)
        b *= 2
    return jnp.concatenate([left, cand], axis=1)


def _circle_score_kernel(a: int, base_ref, cand_ref, cap_ref, out_ref):
    """Full-matrix variant: ``out[:, s]`` for every shift ``s < a``.

    ``a`` is the shared *real* (unpadded) angle count, closed over
    statically.  Rows use the same masked fold-sum as the ragged argmin
    kernel, so full-matrix values and fused values are bit-identical.
    Shifts are scored 128 at a time into a ``(BL, 128)`` tile, and each
    tile is stored at a lane-aligned offset (Mosaic refuses single-lane
    dynamic stores); shifts ``≥ a`` in the last tile are sliced off by
    the wrapper.
    """
    base = base_ref[...]                                # (BL, AP)
    cap = cap_ref[...]                                  # (BL, 1) per-row
    bl, ap = base.shape
    cc = _periodic_buffer(
        cand_ref[...], jnp.full((bl, 1), a, jnp.int32)
    )                                                   # (BL, 2*AP)
    # mask angles >= a to exactly 0 before the fold: the reduction then
    # sees the unpadded operands plus exact additive identities, so lane
    # padding provably cannot change a single output bit
    mask = jax.lax.broadcasted_iota(jnp.int32, (bl, ap), 1) < a
    lane = jax.lax.broadcasted_iota(jnp.int32, (bl, LANE_MULTIPLE), 1)

    def tile(c, _):
        def shift(i, acc):
            s = c * LANE_MULTIPLE + i
            # rolled[α] = cand[(α − s) mod a] == cc[AP − s + α] for α < a
            rolled = pltpu.roll(cc, s, 1)[:, ap:]
            excess = jnp.maximum(base + rolled - cap, 0.0)
            val = _fold_sum(jnp.where(mask, excess, 0.0))   # (BL, 1)
            return jnp.where(lane == i, val, acc)

        acc = jax.lax.fori_loop(
            0, LANE_MULTIPLE, shift, jnp.zeros((bl, LANE_MULTIPLE), jnp.float32)
        )
        start = pl.multiple_of(c * LANE_MULTIPLE, LANE_MULTIPLE)
        out_ref[:, pl.ds(start, LANE_MULTIPLE)] = acc
        return 0

    jax.lax.fori_loop(0, pl.cdiv(a, LANE_MULTIPLE), tile, 0)


def _circle_score_argmin_kernel(
    shift_chunk: int,
    base_ref, cand_ref, cap_ref, valid_ref, na_ref, idx_ref, val_ref,
):
    """Ragged fused variant: per-row angle counts, chunked tournament.

    Each loop round evaluates ``shift_chunk`` consecutive shifts —
    independent slices, unrolled, no carried dependence between them —
    masks shifts ``s ≥ valid[row]`` to ``+inf`` (Eq. 4 bound) and angles
    ``α ≥ num_angles[row]`` to exactly ``0.0`` before the fold (ragged
    masking invariant), reduces the chunk with a log-depth tournament
    and merges the champion into the ``(BL, 1)`` running ``(best_val,
    best_idx)`` pair with the same lexicographic compare.  Chunks are
    visited in ascending shift order, so the running pair always carries
    the lowest-index minimum — exactly ``np.argmin`` over each row's
    admissible window.

    The loop stops at the block's largest admissible shift count and
    exits early once every row's running best hit zero (excess sums are
    non-negative, ties resolve to the earlier shift — nothing can
    displace a found zero).  Each row's evaluated prefix therefore
    provably contains its own first-zero shift (or its whole admissible
    window), independent of which other rows share the block.
    """
    base = base_ref[...]                                # (BL, AP)
    cap = cap_ref[...]                                  # (BL, 1)
    valid = valid_ref[...]                              # (BL, 1) int32
    na = na_ref[...]                                    # (BL, 1) int32
    bl, ap = base.shape
    cc = _periodic_buffer(cand_ref[...], na)            # (BL, 2*AP)
    mask = jax.lax.broadcasted_iota(jnp.int32, (bl, ap), 1) < na
    nvalid = jnp.max(valid)

    def cond(carry):
        c, best_val, _ = carry
        return jnp.logical_and(c < nvalid, jnp.max(best_val) > 0.0)

    def body(carry):
        c, best_val, best_idx = carry
        cols_v, cols_i = [], []
        for i in range(shift_chunk):                    # unrolled: no deps
            s = c + i
            # rolled[α] = cand[(α − s) mod A] == cc[AP − s + α] for α < A
            # (shifts s ≥ A read outside the two-period window; they are
            # ≥ valid and masked to +inf below, so those values never
            # matter)
            rolled = pltpu.roll(cc, s, 1)[:, ap:]
            excess = jnp.maximum(base + rolled - cap, 0.0)
            val = _fold_sum(jnp.where(mask, excess, 0.0))   # (BL, 1)
            cols_v.append(jnp.where(s < valid, val, jnp.inf))
            cols_i.append(jnp.broadcast_to(jnp.reshape(s, (1, 1)), (bl, 1)))
        chunk_v, chunk_i = _tournament_argmin(
            jnp.concatenate(cols_v, axis=1), jnp.concatenate(cols_i, axis=1)
        )
        best_val, best_idx = _tournament_min(
            best_val, best_idx, chunk_v, chunk_i
        )
        return c + shift_chunk, best_val, best_idx

    # rows with valid == 0 (block padding) start "done" so they can never
    # hold the early-exit condition open
    init_val = jnp.where(valid > 0, jnp.inf, 0.0).astype(jnp.float32)
    init = (jnp.int32(0), init_val, jnp.zeros((bl, 1), jnp.int32))
    _, best_val, best_idx = jax.lax.while_loop(cond, body, init)
    idx_ref[...] = best_idx
    val_ref[...] = best_val


# ---------------------------------------------------------------------- #
def _prep_inputs(
    base, cand, capacity, block_l: int, lane_pad: bool,
    *, num_angles=None,
):
    """Row-pad to the block size and lane-pad the angle axis.

    Returns ``(base, cand, cap, na, l, a, ap)``: both operands zero-padded
    to ``(L_pad, AP)``, per-row capacities and angle counts as ``(L_pad,
    1)`` columns (a uniform batch, ``num_angles=None``, has ``A_r = a``
    on every row).  The kernels build their roll buffer from ``cand`` and
    ``na`` in VMEM (:func:`_periodic_buffer`), so nothing here gathers.
    """
    l, a = base.shape
    ap = -(-a // LANE_MULTIPLE) * LANE_MULTIPLE if lane_pad else a
    pad_rows = (-l) % block_l
    cap = jnp.asarray(capacity, jnp.float32)
    cap = jnp.broadcast_to(cap.reshape(-1, 1) if cap.ndim else cap, (l, 1))
    if num_angles is None:
        na = jnp.full((l, 1), a, jnp.int32)
    else:
        na = jnp.asarray(num_angles, jnp.int32).reshape(-1, 1)
    pad = ((0, pad_rows), (0, ap - a))
    base = jnp.pad(base.astype(jnp.float32), pad)
    cand = jnp.pad(cand.astype(jnp.float32), pad)
    cap = jnp.pad(cap, ((0, pad_rows), (0, 0)))
    # padding rows get A = 1 (their demand is all-zero anyway) so every
    # row's period stays in [1, AP]
    na = jnp.pad(na, ((0, pad_rows), (0, 0)), constant_values=1)
    return base, cand, cap, na, l, a, ap


@functools.partial(
    jax.jit, static_argnames=("block_l", "interpret", "lane_pad")
)
def circle_score_pallas(
    base: jax.Array,      # (L, A) float32
    cand: jax.Array,      # (L, A) float32
    capacity: jax.Array,  # scalar shared by all rows, or (L,)/(L, 1) per-row
    *,
    interpret: bool,
    block_l: int = DEFAULT_BLOCK_L,
    lane_pad: bool = True,
) -> jax.Array:
    """Batched scoring; returns (L, A) excess sums (lower = better).

    Per-row capacities let one launch cover links with different
    capacities; a scalar capacity is broadcast to every row.  Values are
    bit-identical to the fused ragged kernel (same masked fold-sum).
    ``interpret`` is explicit: the caller decides from the backend.
    """
    base, cand, cap, _na, l, a, ap = _prep_inputs(
        base, cand, capacity, block_l, lane_pad
    )
    lp = base.shape[0]
    # the output is written in whole 128-lane tiles, whatever the input
    # lane padding
    ow = pl.cdiv(a, LANE_MULTIPLE) * LANE_MULTIPLE

    out = pl.pallas_call(
        functools.partial(_circle_score_kernel, a),
        grid=(lp // block_l,),
        in_specs=[
            pl.BlockSpec((block_l, ap), lambda i: (i, 0)),
            pl.BlockSpec((block_l, ap), lambda i: (i, 0)),
            pl.BlockSpec((block_l, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_l, ow), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((lp, ow), jnp.float32),
        interpret=interpret,
    )(base, cand, cap)
    return out[:l, :a]


@functools.partial(
    jax.jit,
    static_argnames=("block_l", "interpret", "lane_pad", "shift_chunk"),
)
def circle_score_argmin_pallas(
    base: jax.Array,      # (L, A) float32 — zero-padded beyond num_angles[l]
    cand: jax.Array,      # (L, A) float32 — row l real in [:num_angles[l]]
    capacity: jax.Array,  # scalar, or (L,)/(L, 1) per-row
    valid: jax.Array,     # (L,) int32 admissible shifts per row (≤ num_angles)
    num_angles: jax.Array | None = None,  # (L,) int32 per-row angle counts
    *,
    interpret: bool,
    block_l: int = DEFAULT_BLOCK_L,
    lane_pad: bool = True,
    shift_chunk: int = SHIFT_CHUNK,
) -> tuple[jax.Array, jax.Array]:
    """Fused ragged reduction; one launch for any mix of angle counts.

    Returns ``(best_shift (L,) int32, best_excess (L,) float32)`` —
    bit-identical to ``np.argmin(full_matrix[l, :valid[l]])`` per row
    (same fold-sum excess values, first-index tie-breaking via the
    tournament tree) while returning O(L) scalars instead of the O(L·A)
    matrix.  ``num_angles=None`` treats the batch as uniform (every row
    spans all ``A`` angles); per-group launches are exactly this kernel
    invoked once per distinct angle count, so ragged-vs-grouped
    equivalence reduces to the fold's padding invariance.

    ``block_l`` and ``shift_chunk`` are pure schedule parameters: per-row
    fold sums and the tree-shape-independent tournament make the returned
    pair bit-identical for every (block_l, shift_chunk) combination —
    larger chunks only evaluate extra shifts past a found zero, and those
    can never displace a lower-index champion.  That invariance is what
    lets the autotuner (:mod:`repro.kernels.tune`) swap them per width
    bucket without a numerics audit; it is re-verified for every search
    candidate and pinned by the parity tests.
    """
    l, a = base.shape
    valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32).reshape(-1, 1), (l, 1))
    base, cand, cap, na, l, a, ap = _prep_inputs(
        base, cand, capacity, block_l, lane_pad,
        num_angles=num_angles,
    )
    lp = base.shape[0]
    valid = jnp.pad(valid, ((0, lp - l), (0, 0)))

    idx, val = pl.pallas_call(
        functools.partial(_circle_score_argmin_kernel, shift_chunk),
        grid=(lp // block_l,),
        in_specs=[
            pl.BlockSpec((block_l, ap), lambda i: (i, 0)),
            pl.BlockSpec((block_l, ap), lambda i: (i, 0)),
            pl.BlockSpec((block_l, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_l, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_l, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_l, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_l, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((lp, 1), jnp.int32),
            jax.ShapeDtypeStruct((lp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(base, cand, cap, valid, na)
    return idx[:l, 0], val[:l, 0]
