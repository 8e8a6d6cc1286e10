"""Parity harness for the fused device-resident rotation search.

``circle_score_argmin`` must match host ``np.argmin`` over the full
excess matrix *bit for bit* — same excess values, first-index (lowest
shift) tie-breaking — for every row shape the batched search can
produce: equal excess at multiple shifts, zero-capacity rows (every
shift ties), all-infeasible rows (no shift reaches zero excess) and
per-row admissible-shift bounds.  ``circle_score_segmin`` must replay
the product-grid acceptance scan (strict 1e-12 improvement, rows in
order, incumbent carried across chunks) exactly.  Lane padding — the
default that makes any angle count Mosaic-alignable — must not change
one output bit.

The hypothesis properties need the dev extra; seeded numpy sweeps cover
the same distributions where it is unavailable.
"""

import numpy as np
import pytest

from repro.core.compat import BatchStats, find_rotations, find_rotations_batched
from repro.core.circle import CommPattern, Phase
from repro.kernels.circle_score.kernel import (
    LANE_MULTIPLE,
    circle_score_argmin_pallas,
    circle_score_pallas,
)
from repro.kernels.circle_score.ops import (
    ACCEPT_SLACK,
    circle_score,
    circle_score_argmin,
    circle_score_argmin_ref,
    circle_score_ragged_argmin,
    circle_score_ragged_segmin,
    circle_score_segmin,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - dev extra absent
    HAVE_HYPOTHESIS = False


def _random_rows(rng, l, a, *, zero_cap_frac=0.25, infeasible_frac=0.25):
    base = (rng.random((l, a)) * 60).astype(np.float32)
    cand = (rng.random((l, a)) * 60).astype(np.float32)
    caps = rng.choice([25.0, 50.0, 100.0], l).astype(np.float32)
    k = int(l * zero_cap_frac)
    caps[:k] = 0.0                       # zero capacity: every shift ties
    m = int(l * infeasible_frac)
    base[k:k + m] += 200.0               # all-infeasible: excess everywhere
    valid = rng.integers(1, a + 1, l).astype(np.int32)
    return base, cand, caps, valid


def _assert_parity(base, cand, caps, valid):
    idx, val = circle_score_argmin(base, cand, caps, valid)
    idx, val = np.asarray(idx), np.asarray(val)
    ref_idx, ref_val = circle_score_argmin_ref(base, cand, caps, valid)
    np.testing.assert_array_equal(idx, ref_idx)
    np.testing.assert_array_equal(val, ref_val)
    # and against the kernel's own full matrix (the exact values the host
    # reduction would have seen)
    mat = np.asarray(circle_score(base, cand, caps))
    for i in range(len(idx)):
        assert idx[i] == np.argmin(mat[i, : valid[i]])
        assert val[i] == mat[i, idx[i]]


# ---------------------------------------------------------------------- #
# per-row argmin parity
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("seed,l,a", [(0, 6, 72), (1, 4, 257), (2, 9, 144),
                                      (3, 3, 720), (4, 33, 96)])
def test_argmin_parity_seeded(seed, l, a):
    rng = np.random.default_rng(seed)
    _assert_parity(*_random_rows(rng, l, a))


def test_argmin_ties_pick_lowest_shift():
    """Exactly periodic candidate: shifts s and s + A/2 produce identical
    excess — the fused reduction must return the lower one, like argmin."""
    a = 144
    base = np.zeros((2, a), np.float32)
    base[:, :12] = 80.0
    cand = np.zeros((2, a), np.float32)
    cand[:, 20:32] = 60.0
    cand[:, 20 + a // 2: 32 + a // 2] = 60.0   # period A/2 ⇒ full-circle ties
    idx, val = circle_score_argmin(base, cand, 50.0)
    mat = np.asarray(circle_score(base, cand, 50.0))
    for i in range(2):
        winners = np.flatnonzero(mat[i] == mat[i].min())
        assert len(winners) >= 2               # the tie actually happened
        assert int(np.asarray(idx)[i]) == winners[0]


def test_argmin_zero_capacity_rows():
    """C = 0 makes every rotation's excess the same total demand.  With
    integer demands the float32 sums are exact, so all A shifts tie
    *exactly* and the reduction must settle on shift 0 (lowest wins)."""
    rng = np.random.default_rng(5)
    base = rng.integers(0, 40, (3, 72)).astype(np.float32)
    cand = rng.integers(0, 40, (3, 72)).astype(np.float32)
    idx, val = circle_score_argmin(base, cand, 0.0)
    assert np.all(np.asarray(idx) == 0)
    np.testing.assert_array_equal(
        np.asarray(val), (base + cand).sum(axis=1, dtype=np.float32)
    )


def test_argmin_all_infeasible_rows():
    """No rotation reaches zero excess: the early-exit must not fire and the
    scan must still return the true minimum."""
    rng = np.random.default_rng(6)
    base = (rng.random((4, 96)) * 30 + 100).astype(np.float32)
    cand = (rng.random((4, 96)) * 30).astype(np.float32)
    idx, val = circle_score_argmin(base, cand, 50.0)
    assert np.all(np.asarray(val) > 0.0)
    _assert_parity(base, cand, np.full(4, 50.0, np.float32),
                   np.full(4, 96, np.int32))


# ---------------------------------------------------------------------- #
# segmented acceptance scan
# ---------------------------------------------------------------------- #
def _host_fold(mat, valid, seg_ids, init_best):
    """Reference: the scalar product-grid acceptance loop."""
    num_segs = len(init_best)
    best = [float(b) for b in init_best]
    row = [0] * num_segs
    shift = [0] * num_segs
    acc = [False] * num_segs
    for r in range(mat.shape[0]):
        sid = int(seg_ids[r])
        s = int(np.argmin(mat[r, : valid[r]]))
        if float(mat[r, s]) < best[sid] - ACCEPT_SLACK:
            best[sid] = float(mat[r, s])
            row[sid] = r
            shift[sid] = s
            acc[sid] = True
    return acc, row, shift, best


@pytest.mark.parametrize("seed", range(4))
def test_segmin_matches_host_acceptance_scan(seed):
    rng = np.random.default_rng(100 + seed)
    l, a = 24, 144
    base, cand, caps, valid = _random_rows(rng, l, a)
    seg_sizes = [5, 1, 8, 10]
    seg_ids = np.repeat(np.arange(4), seg_sizes).astype(np.int32)
    # mixed incumbents: fresh (inf), already-zero (0 — nothing can beat it),
    # and a finite best carried from a "previous chunk"
    init = np.array([np.inf, 0.0, np.inf, 300.0], np.float64)
    acc, row, shift, best = map(
        np.asarray, circle_score_segmin(base, cand, caps, valid, seg_ids, init)
    )
    mat = np.asarray(circle_score(base, cand, caps))
    h_acc, h_row, h_shift, h_best = _host_fold(mat, valid, seg_ids, init)
    np.testing.assert_array_equal(acc, h_acc)
    np.testing.assert_array_equal(best, h_best)
    for s in range(4):
        if acc[s]:
            assert row[s] == h_row[s] and shift[s] == h_shift[s]
    assert not acc[1]  # zero incumbent is unbeatable


def test_segmin_equal_row_does_not_displace_earlier():
    """Two identical rows in one segment: the strict-slack rule keeps the
    first accepted row (np.argmin-style earliest-wins across rows)."""
    rng = np.random.default_rng(9)
    one = (rng.random((1, 72)) * 80).astype(np.float32)
    base = np.repeat(one, 2, axis=0)
    cand = np.repeat((rng.random((1, 72)) * 80).astype(np.float32), 2, axis=0)
    caps = np.full(2, 50.0, np.float32)
    valid = np.full(2, 72, np.int32)
    seg = np.zeros(2, np.int32)
    acc, row, shift, best = map(
        np.asarray,
        circle_score_segmin(base, cand, caps, valid, seg, np.array([np.inf])),
    )
    assert acc[0] and row[0] == 0


# ---------------------------------------------------------------------- #
# lane padding (Mosaic alignment satellite)
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("l,a", [(3, 72), (5, 257), (2, 100), (4, 720)])
def test_lane_padding_changes_no_output_bit(l, a):
    """Padding the angle axis to a multiple of LANE_MULTIPLE (the default,
    satisfying the kernel's Mosaic lane requirement for any circle) must
    leave every score bit-identical — the kernels statically re-slice to
    the real width before each reduction."""
    rng = np.random.default_rng(a)
    base, cand, caps, valid = _random_rows(rng, l, a)
    on = np.asarray(
        circle_score_pallas(base, cand, caps, interpret=True, lane_pad=True)
    )
    off = np.asarray(
        circle_score_pallas(base, cand, caps, interpret=True, lane_pad=False)
    )
    np.testing.assert_array_equal(on, off)
    assert on.shape == (l, a)  # padding never leaks into the result

    assert a % LANE_MULTIPLE != 0  # every case exercises a padded width

    i_on, v_on = circle_score_argmin_pallas(
        base, cand, caps, valid, interpret=True, lane_pad=True
    )
    i_off, v_off = circle_score_argmin_pallas(
        base, cand, caps, valid, interpret=True, lane_pad=False
    )
    np.testing.assert_array_equal(np.asarray(i_on), np.asarray(i_off))
    np.testing.assert_array_equal(np.asarray(v_on), np.asarray(v_off))


# ---------------------------------------------------------------------- #
# ragged single-launch batches (mixed angle counts in ONE kernel launch)
# ---------------------------------------------------------------------- #
RAGGED_ANGLE_COUNTS = (512, 640, 1024)


def _ragged_rows(rng, nas, *, zero_cap_frac=0.25, infeasible_frac=0.25):
    """Pack rows with per-row angle counts ``nas`` into one (L, max) batch
    (row l real in [:nas[l]], zero-padded above), with the same zero-cap /
    infeasible row mix as the uniform harness."""
    nas = np.asarray(nas, np.int32)
    l, w = len(nas), int(nas.max())
    base = np.zeros((l, w), np.float32)
    cand = np.zeros((l, w), np.float32)
    for i, a in enumerate(nas):
        base[i, :a] = rng.random(a) * 60
        cand[i, :a] = rng.random(a) * 60
    caps = rng.choice([25.0, 50.0, 100.0], l).astype(np.float32)
    k = int(l * zero_cap_frac)
    caps[:k] = 0.0
    m = int(l * infeasible_frac)
    base[k:k + m] += np.where(
        np.arange(w)[None, :] < nas[k:k + m, None], 200.0, 0.0
    ).astype(np.float32)
    valid = np.array([rng.integers(1, a + 1) for a in nas], np.int32)
    return base, cand, caps, valid, nas


def _assert_ragged_parity(base, cand, caps, valid, nas, **kw):
    """Ragged single launch == per-group uniform launches == scalar oracle,
    bit for bit (shifts AND excess values)."""
    idx, val = map(
        np.asarray,
        circle_score_ragged_argmin(base, cand, caps, valid, nas, **kw),
    )
    # per-group launches: one uniform kernel call per distinct angle count,
    # rows tightly sliced to their own width
    for a in np.unique(nas):
        sel = nas == a
        g_idx, g_val = map(
            np.asarray,
            circle_score_argmin(
                base[sel][:, :a], cand[sel][:, :a], caps[sel], valid[sel]
            ),
        )
        np.testing.assert_array_equal(idx[sel], g_idx)
        np.testing.assert_array_equal(val[sel], g_val)
    # scalar oracle: per-row full matrix + np.argmin over admissible shifts
    r_idx, r_val = circle_score_argmin_ref(base, cand, caps, valid, nas)
    np.testing.assert_array_equal(idx, r_idx)
    np.testing.assert_array_equal(val, r_val)


@pytest.mark.parametrize("seed", range(3))
def test_ragged_mixed_angle_parity_seeded(seed):
    rng = np.random.default_rng(500 + seed)
    nas = rng.choice(RAGGED_ANGLE_COUNTS, 9)
    _assert_ragged_parity(*_ragged_rows(rng, nas))


def test_ragged_single_row_batch():
    """L = 1 (one link problem in the whole launch) for each angle count."""
    for a in RAGGED_ANGLE_COUNTS:
        rng = np.random.default_rng(a)
        _assert_ragged_parity(
            *_ragged_rows(rng, [a], zero_cap_frac=0.0, infeasible_frac=0.0)
        )


def test_ragged_all_rows_padded():
    """Every row narrower than the launch width (``pad_to`` forces the
    width no row reaches): the masking invariants alone must keep the
    results bit-identical to the tightly-padded launches."""
    rng = np.random.default_rng(7)
    nas = np.array([512, 512, 640, 640, 512], np.int32)
    base, cand, caps, valid, nas = _ragged_rows(rng, nas)
    _assert_ragged_parity(base, cand, caps, valid, nas, pad_to=1024)
    # and wider than any lane requirement, mid-block
    _assert_ragged_parity(base, cand, caps, valid, nas, pad_to=1920)


def test_ragged_ties_and_zero_capacity():
    """Zero capacity + integer demands: the float32 sums are exact, so all
    admissible shifts of a row tie *exactly* — the tournament must resolve
    every row of the mixed batch to shift 0 (np.argmin first-index)."""
    rng = np.random.default_rng(11)
    nas = np.array([512, 640, 1024, 640], np.int32)
    l, w = len(nas), int(nas.max())
    base = np.zeros((l, w), np.float32)
    cand = np.zeros((l, w), np.float32)
    for i, a in enumerate(nas):
        base[i, :a] = rng.integers(0, 40, a)
        cand[i, :a] = rng.integers(0, 40, a)
    caps = np.zeros(l, np.float32)
    valid = nas.copy()  # all shifts admissible
    idx, val = map(
        np.asarray, circle_score_ragged_argmin(base, cand, caps, valid, nas)
    )
    assert np.all(idx == 0)
    np.testing.assert_array_equal(
        val,
        np.array([
            (base[i, :a] + cand[i, :a]).sum(dtype=np.float64)
            for i, a in enumerate(nas)
        ]).astype(np.float32),
    )
    _assert_ragged_parity(base, cand, caps, valid, nas)


def test_ragged_segmin_matches_host_scan():
    """Segments spanning rows of different angle counts: the device accept
    scan must replay the host fold over each row's own-width matrix."""
    rng = np.random.default_rng(21)
    nas = np.array([512, 640, 1024, 512, 640, 1024, 512, 640], np.int32)
    base, cand, caps, valid, nas = _ragged_rows(rng, nas)
    seg_sizes = [3, 1, 4]
    seg_ids = np.repeat(np.arange(3), seg_sizes).astype(np.int32)
    init = np.array([np.inf, 0.0, 90000.0], np.float64)
    acc, row, shift, best = map(
        np.asarray,
        circle_score_ragged_segmin(base, cand, caps, valid, nas, seg_ids, init),
    )
    # host fold over per-row own-width matrices
    h_best = [float(b) for b in init]
    h_row, h_shift, h_acc = [0] * 3, [0] * 3, [False] * 3
    for r in range(len(nas)):
        a = int(nas[r])
        mat = np.asarray(
            circle_score(base[r : r + 1, :a], cand[r : r + 1, :a], caps[r])
        )[0]
        s = int(np.argmin(mat[: valid[r]]))
        sid = int(seg_ids[r])
        if float(mat[s]) < h_best[sid] - ACCEPT_SLACK:
            h_best[sid] = float(mat[s])
            h_row[sid], h_shift[sid], h_acc[sid] = r, s, True
    np.testing.assert_array_equal(acc, h_acc)
    np.testing.assert_array_equal(best, h_best)
    for s in range(3):
        if acc[s]:
            assert row[s] == h_row[s] and shift[s] == h_shift[s]
    assert not acc[1]  # zero incumbent is unbeatable


# ---------------------------------------------------------------------- #
# end-to-end ragged: one launch per step through find_rotations_batched
# ---------------------------------------------------------------------- #
def _mixed_angle_link_problems(rng, wraps=(7, 11, 13), per=2, k=2):
    """Link problems whose unified circles land on different angle counts:
    a slow job of period 100·w forces ``num_angles`` to the next multiple
    of w above the base grid, so each w yields its own angle count."""
    out = []
    for wi, w in enumerate(wraps):
        for i in range(per):
            pats = [
                CommPattern(
                    100.0 * w,
                    (Phase(float(rng.uniform(0, 50.0 * w)), 30.0 * w, 40.0),),
                    name=f"w{w}s{i}",
                )
            ]
            for j in range(k - 1):
                pats.append(
                    CommPattern(
                        100.0,
                        (Phase(float(rng.uniform(0, 60.0)), 35.0, 30.0),),
                        name=f"w{w}f{i}{j}",
                    )
                )
            out.append((pats, float(rng.choice((25.0, 50.0)))))
    return out


def test_grid_ragged_one_launch_bit_identical():
    """Mixed-angle grid problems: ragged=True must solve the whole epoch in
    ONE launch (launches == batched_calls == 1) with results bit-identical
    to the per-group launches (ragged=False) and the scalar search."""
    rng = np.random.default_rng(60)
    problems = _mixed_angle_link_problems(rng)
    deg = 0.5
    scalar = [find_rotations(p, c, precision_deg=deg) for p, c in problems]
    angle_counts = {s.circle.num_angles for s in scalar}
    assert len(angle_counts) >= 2  # the mix actually happened

    st_r, st_g = BatchStats(), BatchStats()
    ragged = find_rotations_batched(
        problems, precision_deg=deg, stats=st_r, ragged=True
    )
    grouped = find_rotations_batched(
        problems, precision_deg=deg, stats=st_g, ragged=False
    )
    for s, r, g in zip(scalar, ragged, grouped):
        assert r.shifts_steps == s.shifts_steps == g.shifts_steps
        assert r.score == s.score == g.score
        assert r.shifts_ms == s.shifts_ms == g.shifts_ms
    assert st_r.launches == st_r.batched_calls == 1
    assert st_r.ragged_rows == st_r.grid_rows > 0
    assert 0.0 <= st_r.pad_fraction < 1.0
    assert st_g.launches == len(angle_counts) > st_r.launches
    assert st_g.ragged_rows == 0
    # bytes_matrix accounts real row widths on both paths
    assert st_r.bytes_matrix == st_g.bytes_matrix


def test_descent_ragged_accepted_sequences_match_grouped():
    """Mixed-angle k=4 descents: the ragged per-step launch must walk the
    exact accepted-shift sequence of the per-group launches, with one
    launch per (trial, sweep, job) step."""
    from repro.core.compat import _DescentState

    rng = np.random.default_rng(61)
    problems = _mixed_angle_link_problems(rng, wraps=(7, 11), per=1, k=4)

    def record(ragged):
        accepted = []
        orig = _DescentState.apply_shift

        def recording(self, j, base, s_new):
            accepted.append((self.index, j, int(s_new)))
            return orig(self, j, base, s_new)

        stats = BatchStats()
        try:
            _DescentState.apply_shift = recording
            res = find_rotations_batched(
                problems, precision_deg=0.5, stats=stats, ragged=ragged
            )
        finally:
            _DescentState.apply_shift = orig
        return accepted, res, stats

    acc_r, res_r, st_r = record(True)
    acc_g, res_g, st_g = record(False)
    assert acc_r == acc_g and len(acc_r) > 0
    for r, g in zip(res_r, res_g):
        assert r.shifts_steps == g.shifts_steps and r.score == g.score
    assert st_r.descent_problems == 2
    assert st_r.launches == st_r.batched_calls  # one launch per step
    assert st_r.ragged_rows == st_r.descent_rows
    assert st_g.launches > st_r.launches  # grouped pays per angle count


def test_ragged_chunk_boundaries(monkeypatch):
    """A tiny GRID_CHUNK_ROWS splits the mixed-angle batch mid-problem: one
    launch per chunk, incumbents carried across, results unchanged."""
    from repro.core import compat

    rng = np.random.default_rng(62)
    problems = _mixed_angle_link_problems(rng, wraps=(7, 13), per=2, k=3)
    deg = 5.0  # k=3 grids at 5°: multi-row product grids, still mixed A
    scalar = [
        find_rotations(p, c, precision_deg=deg, backend="pallas")
        for p, c in problems
    ]
    monkeypatch.setattr(compat, "GRID_CHUNK_ROWS", 3)
    stats = BatchStats()
    batched = find_rotations_batched(
        problems, precision_deg=deg, backend="pallas", stats=stats, ragged=True
    )
    for s, b in zip(scalar, batched):
        assert b.shifts_steps == s.shifts_steps and b.score == s.score
    assert stats.launches == stats.batched_calls > 1
    assert stats.ragged_rows == stats.grid_rows


# ---------------------------------------------------------------------- #
# end-to-end: device-reduced search == scalar search
# ---------------------------------------------------------------------- #
def _link_problems(rng, n, k):
    periods = (160.0, 200.0, 240.0, 320.0, 400.0, 480.0)
    demands = (0.0, 4.0, 20.0, 40.0, 45.0, 60.0)
    out = []
    for i in range(n):
        pats = []
        for j in range(k):
            it = float(rng.choice(periods))
            phases = tuple(
                Phase(float(rng.uniform(0, it)), float(rng.uniform(0, 0.9 * it)),
                      float(rng.choice(demands)))
                for _ in range(int(rng.integers(1, 3)))
            )
            pats.append(CommPattern(it, phases, name=f"f{i}j{j}"))
        out.append((pats, float(rng.choice((25.0, 50.0, 100.0)))))
    return out


@pytest.mark.parametrize("seed,k", [(0, 2), (1, 3), (2, 3)])
def test_grid_device_reduce_bit_identical_forced_pallas(seed, k):
    """backend='pallas' makes even small circles kernel-eligible, so the
    fused grid path runs; results must equal the scalar search and the
    full-matrix batched path bit for bit, with every call device-reduced."""
    rng = np.random.default_rng(seed)
    problems = _link_problems(rng, 3, k)
    scalar = [find_rotations(p, c, backend="pallas") for p, c in problems]
    stats_on = BatchStats()
    on = find_rotations_batched(
        problems, backend="pallas", stats=stats_on, device_reduce=True
    )
    stats_off = BatchStats()
    off = find_rotations_batched(
        problems, backend="pallas", stats=stats_off, device_reduce=False
    )
    for s, b_on, b_off in zip(scalar, on, off):
        assert b_on.score == s.score == b_off.score
        assert b_on.shifts_steps == s.shifts_steps == b_off.shifts_steps
        assert b_on.shifts_ms == s.shifts_ms == b_off.shifts_ms
    assert stats_on.device_reduced == stats_on.batched_calls > 0
    assert stats_off.device_reduced == 0
    assert stats_on.bytes_returned < stats_off.bytes_returned
    assert stats_on.bytes_matrix == stats_off.bytes_matrix


def test_grid_device_reduce_across_chunks(monkeypatch):
    """A tiny GRID_CHUNK_ROWS splits problems mid-grid; the incumbent best
    must carry into the next chunk's device scan (init_best) so the result
    still equals the unchunked scalar search."""
    from repro.core import compat

    rng = np.random.default_rng(42)
    problems = _link_problems(rng, 3, 3)
    scalar = [find_rotations(p, c, backend="pallas") for p, c in problems]
    monkeypatch.setattr(compat, "GRID_CHUNK_ROWS", 5)
    stats = BatchStats()
    batched = find_rotations_batched(
        problems, backend="pallas", stats=stats, device_reduce=True
    )
    for s, b in zip(scalar, batched):
        assert b.score == s.score and b.shifts_steps == s.shifts_steps
    assert stats.device_reduced == stats.batched_calls > 1


# ---------------------------------------------------------------------- #
# hypothesis properties (dev extra)
# ---------------------------------------------------------------------- #
if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(st.data())
    def test_argmin_parity_property(data):
        seed = data.draw(st.integers(0, 2**31 - 1))
        l = data.draw(st.integers(1, 12))
        a = data.draw(st.sampled_from((72, 96, 144, 257)))
        rng = np.random.default_rng(seed)
        zero_frac = data.draw(st.sampled_from((0.0, 0.5, 1.0)))
        inf_frac = data.draw(st.sampled_from((0.0, 0.5)))
        _assert_parity(*_random_rows(
            rng, l, a, zero_cap_frac=zero_frac, infeasible_frac=inf_frac
        ))

    @settings(max_examples=12, deadline=None)
    @given(st.data())
    def test_ragged_mixed_angle_parity_property(data):
        """One ragged launch over rows mixing angle counts {512, 640, 1024}
        — any mix, any admissible-shift bounds, zero-capacity and
        infeasible rows included — must match the per-group launches and
        the scalar oracle bit for bit (all-same-width and single-row
        batches are drawn too)."""
        seed = data.draw(st.integers(0, 2**31 - 1))
        rng = np.random.default_rng(seed)
        l = data.draw(st.sampled_from((1, 3, 6, 9)))
        nas = np.array(
            [data.draw(st.sampled_from(RAGGED_ANGLE_COUNTS)) for _ in range(l)],
            np.int32,
        )
        zero_frac = data.draw(st.sampled_from((0.0, 0.5)))
        inf_frac = data.draw(st.sampled_from((0.0, 0.5)))
        pad_to = data.draw(st.sampled_from((None, 1024, 1664)))
        base, cand, caps, valid, nas = _ragged_rows(
            rng, nas, zero_cap_frac=zero_frac, infeasible_frac=inf_frac
        )
        _assert_ragged_parity(base, cand, caps, valid, nas, pad_to=pad_to)

    @settings(max_examples=20, deadline=None)
    @given(st.data())
    def test_segmin_matches_host_scan_property(data):
        seed = data.draw(st.integers(0, 2**31 - 1))
        rng = np.random.default_rng(seed)
        num_segs = data.draw(st.integers(1, 5))
        sizes = [data.draw(st.integers(1, 6)) for _ in range(num_segs)]
        a = data.draw(st.sampled_from((72, 144)))
        l = sum(sizes)
        base, cand, caps, valid = _random_rows(rng, l, a)
        seg_ids = np.repeat(np.arange(num_segs), sizes).astype(np.int32)
        init = np.array(
            [data.draw(st.sampled_from((np.inf, 0.0, 500.0)))
             for _ in range(num_segs)], np.float64,
        )
        acc, row, shift, best = map(
            np.asarray,
            circle_score_segmin(base, cand, caps, valid, seg_ids, init),
        )
        mat = np.asarray(circle_score(base, cand, caps))
        h_acc, h_row, h_shift, h_best = _host_fold(mat, valid, seg_ids, init)
        np.testing.assert_array_equal(acc, h_acc)
        np.testing.assert_array_equal(best, h_best)
        for s in range(num_segs):
            if acc[s]:
                assert row[s] == h_row[s] and shift[s] == h_shift[s]


# ---------------------------------------------------------------------- #
# ragged launch-width bucketing (jit recompile bound)
# ---------------------------------------------------------------------- #
def test_bucket_width_values():
    from repro.kernels.circle_score.ops import bucket_width

    assert bucket_width(1) == LANE_MULTIPLE
    assert bucket_width(128) == 128
    assert bucket_width(129) == 256
    assert bucket_width(512) == 512
    assert bucket_width(513) == 1024
    assert bucket_width(721) == 1024
    assert bucket_width(1024) == 1024
    assert bucket_width(1025) == 2048
    with pytest.raises(ValueError, match="positive"):
        bucket_width(0)


def test_ragged_width_bucketing_bounds_recompiles():
    """A long-tailed mix of packed widths inside one bucket must compile
    the fused kernel at most once: the ragged wrapper rounds the launch
    width up to a power-of-two multiple of 128 before the jit boundary,
    so the cache key sees the bucket, not the raw chunk width."""
    rng = np.random.default_rng(23)
    widths = (513, 600, 648, 700, 777, 900, 1000, 1024)  # all bucket to 1024
    l = 4
    baseline = circle_score_argmin_pallas._cache_size()
    results = []
    for w in widths:
        nas = np.full(l, w, np.int32)
        base, cand, caps, valid, nas = _ragged_rows(
            rng, nas, zero_cap_frac=0.0, infeasible_frac=0.0
        )
        results.append(
            tuple(
                map(np.ndarray.tolist, map(np.asarray, circle_score_ragged_argmin(
                    base, cand, caps, valid, nas
                )))
            )
        )
    grown = circle_score_argmin_pallas._cache_size() - baseline
    assert grown <= 1, (
        f"8 distinct packed widths in one bucket grew the jit cache by "
        f"{grown} entries (expected at most 1 — one compile per bucket)"
    )
    # and the bucketed launches stay correct: parity for the last width
    nas = np.full(l, widths[-1], np.int32)
    _assert_ragged_parity(
        *_ragged_rows(rng, nas, zero_cap_frac=0.0, infeasible_frac=0.0)
    )


def test_ragged_width_bucketing_distinct_buckets_compile_separately():
    """Widths in different buckets still get their own (correct) compile —
    bucketing caps recompiles, it does not merge genuinely different
    shapes."""
    from repro.kernels.circle_score.ops import bucket_width

    rng = np.random.default_rng(29)
    for w in (200, 520, 1100):
        nas = np.full(3, w, np.int32)
        base, cand, caps, valid, nas = _ragged_rows(
            rng, nas, zero_cap_frac=0.0, infeasible_frac=0.0
        )
        _assert_ragged_parity(base, cand, caps, valid, nas)
        assert bucket_width(w) in (256, 1024, 2048)


# ---------------------------------------------------------------------- #
# no silent host fallback: a kernel that fails raises
# ---------------------------------------------------------------------- #
KERNEL_ENTRY_POINTS = (
    "circle_score",
    "circle_score_argmin",
    "circle_score_ragged_argmin",
    "circle_score_segmin",
    "circle_score_ragged_segmin",
)


def _break_kernels(monkeypatch):
    from repro.kernels.circle_score import ops

    def failed(*args, **kwargs):
        raise RuntimeError("circle_score kernel failed")

    for name in KERNEL_ENTRY_POINTS:
        monkeypatch.setattr(ops, name, failed)


@pytest.mark.parametrize("k", [2, 4], ids=["grid", "descent"])
@pytest.mark.parametrize(
    "ragged,device_reduce",
    [(True, True), (False, True), (False, False)],
    ids=["ragged", "grouped", "full-matrix"],
)
def test_kernel_failure_raises_from_find_rotations_batched(
    monkeypatch, k, ragged, device_reduce
):
    """Every kernel path of the batched search propagates the kernel's
    error instead of returning a numpy-scored result."""
    rng = np.random.default_rng(70 + k)
    problems = _mixed_angle_link_problems(rng, wraps=(7, 11), per=1, k=k)
    _break_kernels(monkeypatch)
    with pytest.raises(RuntimeError, match="kernel failed"):
        find_rotations_batched(
            problems, precision_deg=0.5, ragged=ragged,
            device_reduce=device_reduce,
        )


def test_kernel_failure_raises_from_cassini_schedule(monkeypatch):
    """A fine-grid CASSINI epoch scores through the kernels; when they
    fail, ``schedule`` raises rather than deciding from host scores."""
    from repro.engine.scenarios import get_scenario
    from repro.sched import CassiniAugmented, ThemisScheduler
    from repro.sched.base import ClusterState

    spec = get_scenario("hetero-16rack")
    topo = spec.topology()
    state = ClusterState(
        topology=topo, now_ms=0.0, running=spec.trace(topo)[:10], pending=[]
    )
    _break_kernels(monkeypatch)
    with pytest.raises(RuntimeError, match="kernel failed"):
        CassiniAugmented(ThemisScheduler(), precision_deg=0.5).schedule(state)


@pytest.mark.parametrize(
    "num_angles,valid,match",
    [((512, 700), (10, 10), "num_angles"), ((512, 640), (0, 10), "valid")],
    ids=["angles-above-width", "no-admissible-shift"],
)
def test_ragged_input_validation_raises(num_angles, valid, match):
    """Bad ragged inputs raise ``ValueError`` through the batched search's
    evaluator — they are rejected, not scored."""
    from repro.core.compat import _batched_argmin_ragged

    base = np.zeros((2, 640), np.float32)
    with pytest.raises(ValueError, match=match):
        _batched_argmin_ragged(
            base, base, np.full(2, 50.0, np.float32),
            np.asarray(valid, np.int32), np.asarray(num_angles, np.int32),
        )
