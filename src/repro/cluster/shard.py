"""Device-sharded batched water-filling for independent components.

The incremental re-solver (:mod:`repro.cluster.network`,
``_solve_alloc_incremental``) decomposes every dirty re-fill into
connected components of the (member job x binding link) graph —
components share no links and no jobs, so their progressive-filling
cascades are mutually independent.  The fused path solves their union in
one ``_wf_fill_core`` call on the host; this module instead solves the
components as *rows of a batch*:

- each component becomes one (caps, binding-matrix, link-limit) row,
- rows are grouped into fixed power-of-two **buckets** by padded
  (members, links) shape so the jit cache stays small and stable,
- every bucket dispatches as ONE ``vmap``-batched fill, and
- with more than one device the bucket's row axis is split across
  ``jax.devices()`` with ``jax.shard_map`` (on one device: the same
  jitted fill without the mesh).

Padding invariants (see docs/architecture.md "Device sharding"):

- padded members carry ``cap = +inf`` and ``valid = False`` — they start
  frozen, bind no links, and their output rate is discarded;
- padded links have an all-False binding column, so their live count is
  0 and their water level pins at ``+inf`` (never the round minimum);
- padded rows are entirely invalid and exit the fill loop immediately.

The per-row fill mirrors ``_wf_fill_core``'s absolute-water-level
recurrence (cap-batch freezes vs link-saturation freezes against the
same ``1e-300``-floored remaining/live ratio), recomputing per-link
used/live from the frozen mask each round instead of maintaining
decrements — algebraically the same quantities, so results agree with
the fused path inside the documented 1e-9 tolerance band.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache, partial

import numpy as np

_EPS = 1e-9

# Below this many dirty components a batch dispatch cannot amortise its
# device round-trip — callers should keep the fused host fill instead.
MIN_COMPONENTS = 4

# Floor bucket dims: merging tiny components into one shape avoids a
# recompile per distinct 2-member/3-link shape.
_MIN_MEMBERS = 8
_MIN_LINKS = 8


def device_count() -> int:
    """Host-visible device count."""
    import jax

    return len(jax.devices())


def _pow2ceil(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


@dataclass
class ShardStats:
    """Telemetry for one or more sharded fill dispatches."""

    dispatches: int = 0  # batched bucket launches
    components: int = 0  # component rows solved on device
    padded_rows: int = 0  # all-invalid rows added for the device split
    fused_fills: int = 0  # fills kept on the host (below MIN_COMPONENTS)
    devices: int = 1  # device count used by the last dispatch
    bucket_shapes: set = field(default_factory=set)  # distinct (M, L)

    def merge(self, other: "ShardStats") -> None:
        self.dispatches += other.dispatches
        self.components += other.components
        self.padded_rows += other.padded_rows
        self.fused_fills += other.fused_fills
        self.devices = other.devices
        self.bucket_shapes |= other.bucket_shapes


def _fill_row(caps, bmat, limit, valid, jnp, lax):
    """One component's progressive filling at fixed (M, L) shape.

    ``caps``    (M,)  member demand caps (+inf on padding)
    ``bmat``    (M,L) member-uses-link incidence as float64 0/1
                      (all-zero on padding rows/columns)
    ``limit``   (L,)  per-link capacity x congestion efficiency
    ``valid``   (M,)  real-member mask

    Returns (M,) rates; padding positions hold 0.

    Link remaining-capacity / live-count state is carried through the
    loop and decremented by one ``newly-frozen @ bmat`` matvec per round
    — the same ±decrement recurrence as the fused host fill, so float
    behaviour tracks it closely (both start from ``limit`` and subtract
    the identical per-member rates).
    """
    m = caps.shape[0]
    inf = jnp.inf

    def cond(state):
        rates, frozen, rem, lv, r_cur, done, rounds = state
        return (~done) & jnp.any(valid & ~frozen) & (rounds <= m + 1)

    def body(state):
        rates, frozen, rem, lv, r_cur, done, rounds = state
        # drained links (lv 0) pin at +inf; the 1e-300 floor keeps float
        # drift in rem from producing -inf/NaN levels
        level = jnp.where(lv > 0.5, jnp.maximum(rem, 1e-300) / lv, inf)
        s = jnp.min(level)
        cap_unf = jnp.where(valid & ~frozen, caps, inf)
        cap_first = jnp.min(cap_unf) <= s + _EPS
        # cap-batch freeze: every unfrozen cap <= S takes its final rate
        # now (freezing a user below a link's level only raises it)
        newly_cap = valid & ~frozen & (caps <= s + _EPS)
        # link-saturation freeze: unfrozen users of every argmin link
        sat = (level == s).astype(caps.dtype)
        newly_sat = valid & ~frozen & (bmat @ sat > 0.5)
        # stuck: no finite level and no cap to take (defensive — a finite
        # S always has a live user while rem/lv track the fused fill)
        stuck = (~cap_first) & (jnp.isinf(s) | ~jnp.any(newly_sat))
        newly = jnp.where(
            stuck, False, jnp.where(cap_first, newly_cap, newly_sat)
        )
        vals = jnp.where(cap_first, caps, s)
        r_new = jnp.where(
            cap_first,
            jnp.maximum(r_cur, jnp.max(jnp.where(newly_cap, caps, -inf))),
            s,
        )
        r_cur = jnp.where(stuck, r_cur, r_new)
        rates = jnp.where(newly, vals, rates)
        frozen = frozen | newly
        newf = newly.astype(caps.dtype)
        rem = rem - (newf * vals) @ bmat
        lv = lv - newf @ bmat
        return rates, frozen, rem, lv, r_cur, stuck, rounds + 1

    rates0 = jnp.zeros_like(caps)
    frozen0 = ~valid
    rem0 = limit
    lv0 = valid.astype(caps.dtype) @ bmat
    state = (
        rates0, frozen0, rem0, lv0,
        jnp.float64(0.0), jnp.bool_(False), jnp.int32(0),
    )
    rates, frozen, _, _, r_cur, _, _ = lax.while_loop(cond, body, state)
    # residual unfrozen members ride at the last water level
    rates = jnp.where(valid & ~frozen, r_cur, rates)
    return jnp.where(valid, rates, 0.0)


@lru_cache(maxsize=None)
def _bucket_fill(ndev: int):
    """Compiled batched fill for ``ndev`` devices (jit caches per shape).

    ``ndev == 1`` is a plain ``jit(vmap(fill))``; ``ndev > 1`` wraps the
    vmapped fill in ``shard_map`` over a 1-d device mesh, splitting the
    row axis.  Row counts must be a multiple of ``ndev`` (callers pad
    with all-invalid rows).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    fill = partial(_fill_row, jnp=jnp, lax=lax)
    batched = jax.vmap(fill)
    if ndev <= 1:
        return jax.jit(batched)

    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:ndev]), axis_names=("rows",))
    spec = P("rows")
    sharded = jax.shard_map(
        batched,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(sharded)


def batched_fill(rows, ndev: int | None = None):
    """Solve independent component rows as bucketed batched fills.

    ``rows`` is a sequence of ``(caps, bmat, limit)`` numpy triples, one
    per component: member demand caps ``(m,)``, boolean member x link
    incidence ``(m, l)``, and per-link fill limits ``(l,)``.  Returns
    ``(rates, stats)`` where ``rates[i]`` is the ``(m_i,)`` float64 rate
    vector for row ``i`` and ``stats`` is a :class:`ShardStats`.

    ``ndev`` overrides the device count (tests use 1 to pin the
    single-device path and assert device-count invariance).
    """
    import jax

    if ndev is None:
        ndev = device_count()
    ndev = max(1, int(ndev))

    stats = ShardStats(devices=ndev)
    buckets: dict[tuple[int, int], list[int]] = {}
    for i, (caps, bmat, limit) in enumerate(rows):
        key = (
            max(_MIN_MEMBERS, _pow2ceil(caps.shape[0])),
            max(_MIN_LINKS, _pow2ceil(limit.shape[0])),
        )
        buckets.setdefault(key, []).append(i)

    out: list[np.ndarray | None] = [None] * len(rows)
    with jax.enable_x64(True):
        for (mpad, lpad), members in sorted(buckets.items()):
            r = len(members)
            rpad = -(-r // ndev) * ndev if ndev > 1 else r
            caps_b = np.full((rpad, mpad), np.inf, dtype=np.float64)
            bmat_b = np.zeros((rpad, mpad, lpad), dtype=np.float64)
            lim_b = np.full((rpad, lpad), np.inf, dtype=np.float64)
            val_b = np.zeros((rpad, mpad), dtype=bool)
            for j, i in enumerate(members):
                caps, bmat, limit = rows[i]
                m, l = bmat.shape
                caps_b[j, :m] = caps
                bmat_b[j, :m, :l] = bmat
                lim_b[j, :l] = limit
                val_b[j, :m] = True
            filled = np.asarray(_bucket_fill(ndev)(caps_b, bmat_b, lim_b, val_b))
            for j, i in enumerate(members):
                m = rows[i][0].shape[0]
                out[i] = filled[j, :m]
            stats.dispatches += 1
            stats.components += r
            stats.padded_rows += rpad - r
            stats.bucket_shapes.add((mpad, lpad))
    return out, stats
