"""Plain reference for what the served path decides.

Written from the CASSINI paper (arXiv:2308.00852: §3 Table 1, §4.1
Algorithm 1 and Theorem 1, §4.2 Algorithm 2, §5.1 profiles and fabric)
and the semantics the configuration file states.  It imports nothing of
the program and takes nothing it made: job communication patterns are
rebuilt from the configuration's profile table and each job's (model,
workers, batch), link sets from the configuration's fabric, and every
score, optimum and fluid trajectory is recomputed here in float64.

The fluid model takes its arithmetic type as an argument, so the control
(float32) runs through the same code.
"""

from __future__ import annotations

import hashlib
import itertools
import math
from functools import reduce

import numpy as np

EPS = 1e-9
# the pacing agent's response jumps by up to a paced period at a drift of
# exactly the re-alignment threshold or of whole periods: within this many
# ms of such a step, rounding alone can choose the branch
NEAR_MS = 1e-6


# ---------------------------------------------------------------------- #
# communication patterns (§5.1 analytic profiles)
# ---------------------------------------------------------------------- #
def pattern(profile: dict, workers: int, batch: int | None):
    """``(iter_ms, ((start_ms, dur_ms, gbps), ...))`` of one job."""
    b = batch or profile["ref_batch"]
    if profile["parallelism"] == "mp":
        it = profile["mp_iter_ms"] * (0.5 + 0.5 * b / profile["ref_batch"])
        return it, tuple((f0 * it, fd * it, g)
                         for f0, fd, g in profile["phases_frac"])
    n = max(2, workers)
    gbit = 2.0 * profile["param_mb"] * 8e-3 * (n - 1) / n
    comm = gbit / (profile["peak_gbps"] * profile["comm_efficiency"]) * 1e3
    compute = profile["compute_ms"] * (b / profile["ref_batch"])
    return compute + comm, ((compute, comm, profile["peak_gbps"]),)


def demand(iter_ms: float, phases, t: np.ndarray) -> np.ndarray:
    """Demand (Gbps) at times ``t``; overlapping phases add, a phase may
    wrap round the end of the iteration."""
    t = np.asarray(t, dtype=np.float64) % iter_ms
    out = np.zeros_like(t)
    for start, dur, gbps in phases:
        s = start % iter_ms
        e = s + dur
        on = (t >= s) & (t < e)
        if e > iter_ms:
            on |= t < (e - iter_ms)
        out = out + np.where(on, gbps, 0.0)
    return out


# ---------------------------------------------------------------------- #
# unified circle (§3)
# ---------------------------------------------------------------------- #
def _ticks(t_ms: float, quantum: float) -> int:
    return max(1, int(math.ceil(t_ms / quantum - 1e-9)))


def circle(pats, sem: dict) -> dict:
    """Unified circle of the jobs ``pats`` (list of ``(iter_ms, phases)``)
    on one link: perimeter = LCM of the quantized periods (quantum
    coarsened until the perimeter is at most ``max_perimeter_factor``
    longest periods), ``360/precision`` angles or one per quantum, at most
    ``max_angles``, then a multiple of the LCM of the wrap counts; demand
    arcs dilated by ``dilate_steps`` angles."""
    iters = [p[0] for p in pats]
    quantum = sem["quantum_ms"]

    def perimeter_of(q):
        return reduce(math.lcm, [_ticks(t, q) for t in iters], 1) * q

    perim = perimeter_of(quantum)
    while perim > sem["max_perimeter_factor"] * max(iters) and quantum < max(iters):
        quantum *= 2.0
        perim = perimeter_of(quantum)
    a = int(round(360.0 / sem["precision_deg"]))
    a = min(max(a, int(math.ceil(perim / quantum))), sem["max_angles"])
    q_iter = [_ticks(t, quantum) * quantum for t in iters]
    wraps = [int(round(perim / q)) for q in q_iter]
    lcm = reduce(math.lcm, wraps, 1)
    a = max(int(math.ceil(a / lcm)), 1) * lcm
    # job j repeats every quantized period q_j = a / wraps_j angles (the
    # paper's bw_circle_j, r_j copies of the job's pattern): sample
    # one period of its pattern stretched onto q_j, tile it wraps_j times
    rows = []
    for (it, phases), q, r in zip(pats, q_iter, wraps):
        s = q / it
        t = np.arange(a // r, dtype=np.float64) * (perim / a)
        one = demand(it * s, [(st * s, d * s, g) for st, d, g in phases], t)
        rows.append(np.tile(one, r))
    bw = np.stack(rows)
    d = sem["dilate_steps"]
    if d > 0:
        out = bw.copy()
        for k in range(1, d + 1):
            out = np.maximum(out, np.roll(bw, k, axis=1))
            out = np.maximum(out, np.roll(bw, -k, axis=1))
        bw = out
    return {"perimeter_ms": perim, "angles": a, "wraps": wraps, "bw": bw,
            "iters": iters, "grids": [max(1, a // w) for w in wraps]}


def link_score(c: dict, shifts, capacity: float) -> float:
    """Table 1, Eq. 2: ``1 - Σ_α Excess(total_α) / (|A| C)`` in float64."""
    total = sum(np.roll(c["bw"][j], int(s)) for j, s in enumerate(shifts))
    return float(1.0 - np.maximum(total - capacity, 0.0).mean() / capacity)


def _excess_rows(base, cand, cap, shifts: int) -> np.ndarray:
    """``out[r, s] = Σ_α max(0, base[r, α] + cand[(α - s) mod A] - C)`` for
    ``s < shifts``."""
    a = base.shape[-1]
    idx = (np.arange(a)[None, :] - np.arange(shifts)[:, None]) % a
    rolled = cand[idx]                                        # (S, A)
    out = np.empty((base.shape[0], shifts))
    step = max(1, 4_000_000 // (shifts * a))
    for i in range(0, base.shape[0], step):
        tot = base[i:i + step, None, :] + rolled[None]
        out[i:i + step] = np.maximum(tot - cap, 0.0).sum(axis=-1)
    return out


def optimum(c: dict, capacity: float, sem: dict):
    """Best rotation of Table 1 on one link: exact over the product grid of
    jobs 1..k-1 (job 0 pinned: a common rotation changes nothing) while
    ``k <= max_exact_jobs`` and the grid has at most ``exact_grid_limit``
    points; above, the seeded coordinate descent (restarts, sweeps and
    draws as the configuration states).  Returns the shifts."""
    bw, grids = c["bw"], c["grids"]
    k = len(grids)
    if k == 1:
        return (0,)
    if k <= sem["max_exact_jobs"] and int(np.prod(grids[1:])) <= sem["exact_grid_limit"]:
        mids = list(itertools.product(*[range(g) for g in grids[1:-1]]))
        base = np.stack([
            bw[0] + sum((np.roll(bw[j], s) for j, s in enumerate(m, start=1)),
                        np.zeros_like(bw[0]))
            for m in mids])
        ex = _excess_rows(base, bw[-1], capacity, grids[-1])
        r, s = np.unravel_index(int(np.argmin(ex)), ex.shape)
        return (0, *mids[r], int(s))
    rng = np.random.default_rng(sem["seed"])
    best, best_ex = (0,) * k, np.inf
    for trial in range(sem["descent_seeds"]):
        shifts = (np.zeros(k, dtype=np.int64) if trial == 0 else
                  np.array([rng.integers(0, g) for g in grids], dtype=np.int64))
        rot = np.stack([np.roll(bw[j], int(shifts[j])) for j in range(k)])
        total = rot.sum(axis=0)
        for _ in range(sem["descent_sweeps"]):
            changed = False
            for j in range(k):
                base = total - rot[j]
                ex = _excess_rows(base[None], bw[j], capacity, grids[j])[0]
                s = int(np.argmin(ex))
                if s != shifts[j]:
                    shifts[j] = s
                    rot[j] = np.roll(bw[j], s)
                    total = base + rot[j]
                    changed = True
            if not changed:
                break
        ex_now = float(np.maximum(total - capacity, 0.0).sum())
        if ex_now < best_ex - sem["accept_slack"]:
            best_ex, best = ex_now, tuple(int(s) for s in shifts)
        if best_ex == 0.0:
            break
    return best


# ---------------------------------------------------------------------- #
# fabric (§5.1): two-tier leaf-spine, ring collectives, hashed ECMP
# ---------------------------------------------------------------------- #
class Fabric:
    def __init__(self, topo: dict) -> None:
        self.spr = topo["servers_per_rack"]
        self.gps = topo.get("gpus_per_server", 1)
        self.nic = topo["rack_nic_gbps"]
        self.spines = max(1, round(self.spr / topo["oversubscription"]))
        self._memo: dict = {}

    def _uplink(self, rack: int, a: int, b: int) -> tuple[str, float]:
        key = f"{min(a, b)}/{max(a, b)}".encode()
        h = int.from_bytes(hashlib.blake2s(key, digest_size=8).digest(), "big")
        return f"up:r{rack}-sp{h % self.spines}", self.nic[rack]

    def _host(self, server: int) -> tuple[str, float]:
        r, s = divmod(server, self.spr)
        return f"host:r{r}s{s}", self.nic[r]

    def links(self, gpus) -> dict[str, float]:
        """Links of a ring collective over the job's GPUs in id order."""
        ws = tuple(sorted(set(gpus)))
        out = self._memo.get(ws)
        if out is None:
            out = {}
            if len(ws) >= 2:
                for a, b in zip(ws, ws[1:] + ws[:1]):
                    sa, sb = a // self.gps, b // self.gps
                    if sa == sb:
                        continue
                    ra, rb = sa // self.spr, sb // self.spr
                    path = [self._host(sa)]
                    if ra != rb:
                        path += [self._uplink(ra, ra, rb), self._uplink(rb, ra, rb)]
                    path.append(self._host(sb))
                    for name, cap in path:
                        out.setdefault(name, cap)
            self._memo[ws] = out
        return out


def contended(fabric: Fabric, placement: dict) -> dict[tuple, tuple]:
    """Contended links of a placement, links with the same job set merged
    (one constraint; the least capacity governs): job set -> (capacity,
    the group's first link name)."""
    users: dict[str, list] = {}
    caps: dict[str, float] = {}
    for jid, gpus in placement.items():
        for name, cap in fabric.links(gpus).items():
            users.setdefault(name, []).append(jid)
            caps[name] = cap
    out: dict[tuple, tuple] = {}
    for name, js in users.items():
        if len(js) > 1:
            key = tuple(sorted(js))
            cap, rep = out.get(key, (math.inf, name))
            out[key] = (min(cap, caps[name]), min(rep, name))
    return out


def has_loop(links: dict[tuple, float]) -> bool:
    """Theorem 1 precondition: the job-link affinity graph is a forest."""
    parent: dict = {}

    def find(x):
        while parent.setdefault(x, x) != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for js in links:
        for j in js:
            a, b = find(("job", j)), find(("link", js))
            if a == b:
                return True
            parent[a] = b
    return False


def aligned_links(links: dict[tuple, tuple]) -> dict[tuple, tuple] | None:
    """The loop rule: which contended links of a candidate (``contended``'s
    output) alignment covers, or None when the candidate is discarded.
    Algorithm 2 (§4.2): a candidate whose job-link affinity graph has a
    loop is discarded; otherwise alignment covers every contended link.

    This is the one function a configuration's own reference
    (``references/<configuration>.py``) may replace."""
    return None if has_loop(links) else links


def congruent(t: dict, w: dict, iters: dict, tol: float = 1e-6) -> bool:
    """Theorem 1 on one link: some δ has ``t_j - w_j ≡ δ (mod iter_j)`` for
    every job, ``t`` the decision's time-shifts and ``w`` the link-level
    shifts (Eq. 5).  Algorithm 1 enters a link through one of its jobs, so
    δ is ``t_a - w_a`` for one anchor job ``a``."""
    for a in t:
        delta = t[a] - w[a]
        if all(abs(_wrap((t[j] - w[j] - delta) % iters[j], iters[j])) <= tol
               for j in t):
            return True
    return False


def _wrap(x: float, m: float) -> float:
    return x - m if x > m / 2 else x


# ---------------------------------------------------------------------- #
# fluid fabric (§5.1 testbed behaviour): exact event-driven max-min model
# ---------------------------------------------------------------------- #
def segments(iter_ms: float, phases) -> list[tuple[str, float, float]]:
    """Piecewise-constant ``(kind, duration_ms, gbps)`` pieces that tile
    one iteration; pieces thinner than ``EPS`` fold into a neighbour."""
    pts = {0.0, iter_ms}
    for start, dur, _ in phases:
        s = start % iter_ms
        pts.add(s)
        pts.add(min(s + dur, iter_ms))
        if s + dur > iter_ms:
            pts.add((s + dur) % iter_ms)
    cuts = sorted(pts)
    segs: list[list] = []
    carry = 0.0
    for a, b in zip(cuts, cuts[1:]):
        if b - a < EPS:
            if segs:
                segs[-1][1] += b - a
            else:
                carry += b - a
            continue
        level = float(demand(iter_ms, phases, 0.5 * (a + b)))
        kind = "comm" if level > EPS else "compute"
        gbps = level if kind == "comm" else 0.0
        width = (b - a) + carry
        carry = 0.0
        if segs and segs[-1][0] == kind and segs[-1][2] - gbps == 0.0:
            segs[-1][1] += width
        else:
            segs.append([kind, width, gbps])
    if carry:
        if segs:
            segs[-1][1] += carry
        else:
            segs.append(["compute", carry, 0.0])
    if not segs:
        segs.append(["compute", iter_ms, 0.0])
    return [tuple(s) for s in segs]


def max_min(comm: dict, caps: dict, efficiency: float, f=float) -> dict:
    """Progressive filling with per-job demand caps; a link whose demand
    exceeds its capacity delivers ``capacity * efficiency`` (congestion
    control under contention).  ``comm``: job -> (cap_gbps, links)."""
    users: dict[str, list] = {}
    dem: dict[str, float] = {}
    for j, (cap, links) in comm.items():
        for name in links:
            users.setdefault(name, []).append(j)
            dem[name] = f(dem.get(name, f(0.0)) + cap)
    left = {n: f(caps[n] * (efficiency if dem[n] > caps[n] + EPS else 1.0))
            for n in users}
    rate = {j: f(0.0) for j in comm}
    live = set(comm)
    while live:
        inc = math.inf
        for n, js in users.items():
            k = sum(1 for j in js if j in live)
            if k:
                inc = min(inc, f(left[n] / k))
        for j in live:
            inc = min(inc, f(comm[j][0] - rate[j]))
        if inc is math.inf or inc < 0:
            break
        for j in live:
            rate[j] = f(rate[j] + inc)
        for n, js in users.items():
            left[n] = f(left[n] - inc * sum(1 for j in js if j in live))
        frozen = {j for j in live if comm[j][0] - rate[j] <= EPS}
        for n, js in users.items():
            if left[n] <= EPS:
                frozen |= {j for j in js if j in live}
        if not frozen:
            break
        live -= frozen
    return rate


class FluidRef:
    """The fluid fabric of a set of jobs, from a given state.

    ``configure`` applies one decision as a job receives it: a new job
    starts its first iteration after its time-shift; a running job on the
    same placement keeps its progress and delays by the change of its
    shift (modulo its solo iteration); the pacing agent is armed for a
    held job and disarmed otherwise.  ``advance`` runs the exact
    event-driven max-min model: compute pieces in wall time (no jitter),
    comm pieces drain Gbit at the max-min rate, a pending delay holds a job
    still, and a paced job waits for its grid slot at each iteration
    boundary or, late by more than the drift tolerance, re-aligns onto the
    next slot (disarming after three consecutive re-alignments).  A job
    that finishes its last iteration leaves.  ``near`` counts iteration
    ends at which the pacing agent's branch lay within ``NEAR_MS`` of a
    step, where rounding alone decides.
    """

    def __init__(self, cfg: dict, fabric: Fabric, f=float) -> None:
        self.cfg = cfg
        self.fabric = fabric
        self.f = f
        self.tol = cfg["semantics"]["drift_tolerance"]
        self.eff = cfg["service"]["congested_efficiency"]
        self.now = f(0.0)
        self.jobs: dict[str, dict] = {}
        # iteration ends whose drift lay within NEAR_MS of a step of the
        # pacing agent (the re-alignment threshold, or a whole number of
        # paced periods where the re-alignment delay wraps)
        self.near = 0

    def load(self, now_ms: float, states: dict, specs: dict) -> None:
        """Start from ``states``: job -> ``(iters_done, piece, remaining,
        delay_ms, ideal_next_ms, consecutive re-alignments, applied shift,
        iteration start, paced period)``."""
        f = self.f
        self.now = f(now_ms)
        self.jobs = {}
        for jid, (done, seg, rem, delay, ideal, consec, applied, start,
                  paced) in states.items():
            model, workers, batch, placement, iters = specs[jid]
            it, ph = pattern(self.cfg["models"][model], workers, batch)
            self.jobs[jid] = {
                "segs": segments(it, ph), "solo": it,
                "links": self.fabric.links(placement), "iters": iters,
                "iters_done": done, "seg": seg, "remaining": f(rem),
                "delay": f(delay), "applied": applied,
                "iter_start": f(start), "consec": consec, "paced": f(paced),
                "ideal_next": None if ideal is None else f(ideal),
            }

    def configure(self, directives: dict, specs: dict) -> None:
        """``directives``: job -> ``(shift_ms, hold, paced_ms)`` for every
        job of this set the decision keeps running, ``shift_ms`` None where
        the decision gives the job no directive (it keeps its shift, paced
        at its solo period and unheld); ``specs``: job -> ``(model,
        workers, batch, placement, iters)``."""
        f = self.f
        for jid in [j for j in self.jobs if j not in directives]:
            del self.jobs[jid]
        for jid, (shift, hold, paced) in directives.items():
            pending = shift is not None
            if not pending:
                hold, paced = False, None
            j = self.jobs.get(jid)
            if j is None:
                model, workers, batch, placement, iters = specs[jid]
                it, ph = pattern(self.cfg["models"][model], workers, batch)
                j = self.jobs[jid] = {
                    "segs": segments(it, ph), "solo": it,
                    "links": self.fabric.links(placement), "iters": iters,
                    "iters_done": 0, "seg": 0, "delay": f(shift or 0.0),
                    "applied": shift or 0.0, "iter_start": self.now, "consec": 0,
                    "ideal_next": None,
                }
                self._load(j)
                j["paced"] = f(paced or it)
                if hold:
                    j["ideal_next"] = f(self.now + j["delay"] + j["paced"])
                continue
            j["paced"] = f(paced or j["solo"])
            if pending:
                delta = (shift - j["applied"]) % j["solo"]
                if delta > EPS and j["solo"] - delta > EPS:
                    j["delay"] = f(j["delay"] + delta)
                    if j["ideal_next"] is not None:
                        j["ideal_next"] = f(j["ideal_next"] + delta)
                j["applied"] = shift
            if hold and j["ideal_next"] is None:
                j["ideal_next"] = f(j["iter_start"] + j["delay"] + j["paced"])
                j["consec"] = 0
            elif not hold:
                j["ideal_next"] = None

    def _load(self, j: dict) -> None:
        kind, dur, gbps = j["segs"][j["seg"]]
        j["remaining"] = self.f(dur if kind == "compute" or not j["links"]
                                else gbps * dur * 1e-3)

    def _complete(self, j: dict) -> None:
        f = self.f
        j["seg"] += 1
        if j["seg"] >= len(j["segs"]):
            end = self.now
            j["iters_done"] += 1
            j["iter_start"] = end
            j["seg"] = 0
            if j["ideal_next"] is not None:
                drift = f(end - j["ideal_next"])
                paced = j["paced"]
                if (abs(drift - self.tol * paced) < NEAR_MS
                        or (drift > self.tol * paced
                            and min(drift % paced, paced - drift % paced) < NEAR_MS)):
                    self.near += 1
                if drift <= 0.0:
                    j["delay"] = f(j["delay"] - drift)
                    j["consec"] = 0
                    j["ideal_next"] = f(j["ideal_next"] + paced)
                elif drift > self.tol * paced:
                    extra = f((-drift) % paced)
                    j["delay"] = f(j["delay"] + extra)
                    j["consec"] += 1
                    j["ideal_next"] = f(end + extra + paced)
                    if j["consec"] >= 3:
                        j["ideal_next"] = None
                else:
                    j["consec"] = 0
                    j["ideal_next"] = f(j["ideal_next"] + paced)
        self._load(j)

    def advance(self, until_ms: float) -> None:
        f = self.f
        jobs = self.jobs
        caps: dict[str, float] = {}
        for j in jobs.values():
            caps.update(j["links"])
        while self.now < until_ms - EPS and jobs:
            comm = {k: (j["segs"][j["seg"]][2], list(j["links"]))
                    for k, j in jobs.items()
                    if j["segs"][j["seg"]][0] == "comm" and j["delay"] <= EPS
                    and j["links"]}
            rates = max_min(comm, caps, self.eff, f)
            dt = f(until_ms - self.now)
            for k, j in jobs.items():
                if j["delay"] > EPS:
                    dt = min(dt, j["delay"])
                elif j["segs"][j["seg"]][0] == "compute" or not j["links"]:
                    dt = min(dt, j["remaining"])
                elif rates.get(k, 0.0) > EPS:
                    dt = min(dt, f(j["remaining"] / rates[k] * 1e3))
            dt = max(dt, f(1e-6))
            self.now = f(self.now + dt)
            for k, j in list(jobs.items()):
                if j["delay"] > EPS:
                    j["delay"] = max(f(0.0), f(j["delay"] - dt))
                    continue
                if j["segs"][j["seg"]][0] == "compute" or not j["links"]:
                    j["remaining"] = f(j["remaining"] - dt)
                else:
                    j["remaining"] = f(j["remaining"] - rates.get(k, 0.0) * dt * 1e-3)
                if j["remaining"] <= EPS:
                    self._complete(j)
                    if j["iters_done"] >= j["iters"]:
                        del jobs[k]
        self.now = f(max(self.now, until_ms))


def move(sim: FluidRef, jid: str, spec: tuple, shift: float | None,
         pause_ms: float) -> bool:
    """A decision moves or resizes the running job ``jid`` of ``sim`` onto
    ``spec`` (``(model, workers, batch, placement, iters)``): a
    checkpoint-restore.  Its finished iterations are kept, the iteration in
    progress is lost, and it starts a new one on the new placement after
    ``pause_ms`` plus its time-shift target (``shift``, or the shift it has
    applied when the decision gives it none); pacing is re-armed by the
    decision (``FluidRef.configure``).  A job whose links and pattern stay
    as they were is not moved.  Returns whether it was."""
    f = sim.f
    old = sim.jobs[jid]
    model, workers, batch, placement, _ = spec
    it, ph = pattern(sim.cfg["models"][model], workers, batch)
    segs = segments(it, ph)
    links = sim.fabric.links(placement)
    if links == old["links"] and segs == old["segs"]:
        return False
    target = old["applied"] if shift is None else shift
    j = sim.jobs[jid] = dict(
        old, segs=segs, solo=it, links=links, seg=0,
        delay=f(pause_ms + target), applied=target, iter_start=sim.now,
        consec=0, ideal_next=None)
    sim._load(j)
    return True


def position(j: dict, segs) -> float:
    """Iterations done plus the share of the current iteration completed,
    continuous across piece boundaries (a finished piece with nothing
    left equals the next one untouched)."""
    full = sum(d for _, d, _ in segs)
    before = sum(d for _, d, _ in segs[:j["seg"]])
    kind, dur, gbps = segs[j["seg"]]
    work = dur if kind == "compute" or not j["links"] else gbps * dur * 1e-3
    done = dur * (1.0 - float(j["remaining"]) / work) if work > 0 else dur
    return j["iters_done"] + (before + done) / full
