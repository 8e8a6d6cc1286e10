"""The comparison that decides ``correct``.

Three numbers, each compared with a limit the configuration states:

``rotation_gap``
    Over the distinct link problems of the sampled decisions (every
    contended link of every candidate): the mean of how far the link
    score of the rotation the program returned lies below the
    reference's best (§3, Table 1), both scored by the reference in
    float64 on the exactly periodic unified circle.
``align_gap``
    Over the sampled decisions: the mean of how far the chosen
    candidate's reference score lies below the best candidate's
    (Algorithm 2's ranking) plus the mean rotation shortfall on the
    chosen candidate's links; 1 for a decision whose placement is not
    the pinned layout (fixed host) or none of the candidates, or whose
    per-job time-shifts do not realize, on some contended link of the
    chosen placement, the link-level shifts found there (Theorem 1).
    Which candidates are discarded and which contended links alignment
    covers is the reference's loop rule, ``aligned_links``.
``fluid_gap``
    A watched set of jobs is replayed by the reference fluid model over
    the window in back-to-back chains of a fixed span of cluster time.
    At a chain's first state the watched set is the jobs with a server on
    a few seeded racks, closed under link sharing: every job that shares
    a link with a watched job is watched too, until nothing is added (on
    a ``hub_leaf`` layout, the tenants of the seeded hubs).  A chain
    starts from the program's state and applies every decision itself
    (time-shift deltas, pacing armed or not, and its period), with each
    job's placement, and its worker count, as the decision the service
    acted on gave them.  A job new to the cluster that a decision places
    on a seeded rack or on a watched job's links joins the set; a watched
    job that a decision moves or resizes restarts its iteration there
    after the configuration's migration pause (``reference.move``).  A
    decision that links to the set a job that ran before ends the chain
    at the next state, uncompared: the reference has no state of it.
    Over every later state the program reached: the largest gap in job
    progress (iterations, continuous across pieces) and in pending delay
    (in iterations), a job that has finished standing at its last
    iteration with no delay.  Chains are short because the fluid model
    is chaotic: a rounding difference grows e-fold every few seconds of
    cluster time.  A chain in which the pacing agent met one of its steps
    within rounding (``NEAR_MS``) ends at the next state uncompared: there
    either branch is sound.  Every paced period a decision delivers is
    held, on each contended link alignment covers in the chosen placement,
    to the reference circle's quantized period of the job there, the
    largest over its links (the program's rule).

Everything the check compares with is ``reference.py``'s, which imports
nothing of the program.  The one exception is the loop rule: the
``aligned_links`` of ``references/<configuration>.py`` where the
configuration has that file (``Judge``'s ``rule``; nothing else of the
file is used), else ``reference.aligned_links`` (Algorithm 2).

The check runs after the window closes and is not timed.  It may take no
longer than the window: with set-up and the window it must end within a
run's 360 s.  Its seconds are printed as ``reference_s`` on an earlier
line.  Most of them go to the fluid replay, whose cost grows with the
watched set and with the cluster time the window covers (about 0.6 s a
cluster second with some 50 jobs watched, on a TPU v5 lite host).

The control puts the reference in the program's place with one stated
guarantee broken: rotations searched on a unified circle of half the
configured angles (the step that halves the kernel work), and the fluid
model in float32, over the same chains.  Lowering the scoring precision is no control here:
every demand is a whole number of Gbps, so excess sums are exact in
bfloat16 as in float32 and the precision control reads exactly what the
program reads.
"""

from __future__ import annotations

import math
import random

import numpy as np

from . import reference as ref

NUMBERS = ("rotation_gap", "align_gap", "fluid_gap")


def semantics(cfg: dict) -> dict:
    return {**cfg["semantics"], **{k: cfg["cassini"][k] for k in
                                   ("precision_deg", "quantum_ms", "seed")}}


def closure(seeds, placements: dict, fabric) -> set:
    """``seeds`` and every job of ``placements`` (job -> servers) that
    shares a link with one of them, until nothing is added."""
    users: dict[str, list] = {}
    for jid, servers in placements.items():
        for name in fabric.links(servers):
            users.setdefault(name, []).append(jid)
    out = set(seeds)
    todo = list(out)
    while todo:
        for name in fabric.links(placements[todo.pop()]):
            for other in users[name]:
                if other not in out:
                    out.add(other)
                    todo.append(other)
    return out


class Judge:
    def __init__(self, cfg: dict, specs: dict, rule=ref.aligned_links) -> None:
        self.cfg = cfg
        self.sem = semantics(cfg)
        self.specs = specs
        self.rule = rule            # the loop rule (``reference.aligned_links``)
        self.fabric = ref.Fabric(cfg["topology"])
        self._circles: dict = {}
        self._opt: dict = {}
        # (gap, link, jobs, capacity, shifts, optimum, best, got, reported)
        self.worst: tuple = (0.0,)
        # rotation shortfall of each distinct link problem compared
        self.shortfall: dict = {}

    # -------------------------------------------------------------- #
    def _jobs(self, js, placement) -> tuple:
        return tuple((self.specs[j].model, len(placement[j]), self.specs[j].batch)
                     for j in js)

    def circle(self, jobs: tuple) -> dict:
        c = self._circles.get(jobs)
        if c is None:
            pats = [ref.pattern(self.cfg["models"][m], w, b) for m, w, b in jobs]
            c = self._circles[jobs] = ref.circle(pats, self.sem)
        return c

    def optimum(self, jobs: tuple, cap: float) -> tuple:
        key = (jobs, cap)
        s = self._opt.get(key)
        if s is None:
            s = self._opt[key] = ref.optimum(self.circle(jobs), cap, self.sem)
        return s

    def coarse(self, jobs: tuple, cap: float) -> tuple:
        """The control's answer: the best rotation on a circle of half the
        angles, mapped onto the configured circle."""
        key = (jobs, cap, "coarse")
        s = self._opt.get(key)
        if s is None:
            sem = dict(self.sem, precision_deg=2 * self.sem["precision_deg"],
                       max_angles=self.sem["max_angles"] // 2)
            pats = [ref.pattern(self.cfg["models"][m], w, b) for m, w, b in jobs]
            c2 = ref.circle(pats, sem)
            fine = self.circle(jobs)
            ratio = fine["angles"] / c2["angles"]
            s = self._opt[key] = tuple(
                int(round(x * ratio)) % g
                for x, g in zip(ref.optimum(c2, cap, sem), fine["grids"]))
        return s

    # -------------------------------------------------------------- #
    def decision(self, scored, decision, control: bool = False) -> dict:
        """Readings of one decision (``scored``: the Score stage's output,
        ``decision``: what the service acted on)."""
        cands = scored.placements
        faults = []
        cand_scores, cand_links = [], []
        for i, pl in enumerate(cands):
            links = self.rule(ref.contended(self.fabric, pl))
            if links is None:
                cand_scores.append(-math.inf)
                cand_links.append({})
                continue
            prog = scored.evaluated[i][2]
            scores, gaps = [], {}
            for js, (cap, rep) in links.items():
                jobs = self._jobs(js, pl)
                c = self.circle(jobs)
                best = ref.link_score(c, self.optimum(jobs, cap), cap)
                if control:
                    steps = self.coarse(jobs, cap)
                else:
                    res = prog.get(rep)
                    if res is None or not _valid(res.shifts_steps, c):
                        faults.append(f"candidate {i} link {rep}: no valid result")
                        continue
                    steps = res.shifts_steps
                got = ref.link_score(c, steps, cap)
                gaps[js] = (max(0.0, best - got), steps, rep, jobs)
                self.shortfall[(jobs, cap)] = max(0.0, best - got)
                if best - got > self.worst[0]:
                    self.worst = (best - got, rep, jobs, cap, tuple(steps),
                                  self.optimum(jobs, cap), best, got,
                                  None if control else res.score)
                scores.append(got if control else best)
            cand_scores.append(float(np.mean(scores)) if scores else 1.0)
            cand_links.append(gaps)
        if control:  # the control ranks by its own scores
            chosen = int(np.argmax(cand_scores)) if cands else None
            ref_scores = [self._ref_score(pl) for pl in cands]
        else:
            ref_scores = cand_scores
            chosen = next((i for i, pl in enumerate(cands)
                           if pl == decision.placements), None)
            if chosen is None:
                faults.append("placement is none of the candidates")
            pinned = self.cfg["host"]["kind"] == "fixed"
            if pinned and any(tuple(self.specs[j].placement) != tuple(s)
                              for j, s in decision.placements.items()):
                faults.append("placement is not the pinned layout")
        align = 0.0
        if cands and max(ref_scores) == -math.inf:
            # every candidate has a loop: the host's first placement stands,
            # with no time-shift
            if chosen not in (0, None) or (not control and decision.time_shifts_ms):
                faults.append("no loop-free candidate, yet not the host's first")
        elif chosen is not None and cands:
            if ref_scores[chosen] == -math.inf:
                faults.append("chose a candidate whose affinity graph has a loop")
            chosen_gaps = [g for g, *_ in cand_links[chosen].values()]
            align = max(ref_scores) - ref_scores[chosen]
            if chosen_gaps:
                align += float(np.mean(chosen_gaps))
            for js, (gap, steps, rep, jobs) in cand_links[chosen].items():
                if control:
                    continue
                res = scored.evaluated[chosen][2][rep]
                iters = {j: ref.pattern(self.cfg["models"][m], w, b)[0]
                         for j, (m, w, b) in zip(js, jobs)}
                t = {j: decision.time_shifts_ms.get(j, math.nan) for j in js}
                w = dict(zip(js, res.shifts_ms))
                if not ref.congruent(t, w, iters):
                    faults.append(f"time-shifts do not realize link {rep}")
        if faults:
            align = 1.0
        return {"align_gap": align, "faults": faults}

    def _ref_score(self, pl) -> float:
        links = self.rule(ref.contended(self.fabric, pl))
        if links is None:
            return -math.inf
        s = [ref.link_score(self.circle(self._jobs(js, pl)),
                            self.optimum(self._jobs(js, pl), cap), cap)
             for js, (cap, _) in links.items()]
        return float(np.mean(s)) if s else 1.0

    # -------------------------------------------------------------- #
    def fluid(self, log: list, horizon_ms: float, racks: set,
              control: bool = False) -> tuple[float, list]:
        """Replay a watched set of jobs through the fluid log in chains of
        ``horizon_ms`` of cluster time: each chain starts from the
        program's state at its first snapshot, watching the jobs with a
        server on ``racks`` and every job linked to them, and carries its
        own state through every later decision and advance, where it is
        compared.  Every paced period a decision delivers is held to the
        reference circle's."""
        f = np.float32 if control else float
        spr = self.cfg["topology"]["servers_per_rack"]
        pause = self.cfg["service"]["migration_pause_ms"]

        def on_racks(placements: dict) -> set:
            return {j for j, servers in placements.items()
                    if any(s // spr in racks for s in servers)}

        sim, t0 = None, -math.inf
        watched: dict = {}          # job -> placement, in the current chain
        gap, faults = 0.0, []
        self.fluid_chains = self.fluid_near = self.fluid_cut = self.fluid_jobs = 0
        for kind, t, data, placements, *fresh in log:
            if kind == "configure":
                if not control:
                    faults += self._paced(data, placements)
                if sim is None:
                    continue
                live = {j for j in watched if j in placements}
                fresh = fresh[0]
                grown = closure(live | (on_racks(placements) & fresh),
                                placements, self.fabric)
                if not grown - live <= fresh:
                    # a job that ran before joins the set: the reference
                    # has no state of it to carry on
                    self.fluid_cut += 1
                    sim = None
                    continue
                moved = [j for j in live if placements[j] != watched[j]]
                watched = {j: placements[j] for j in grown}
                self.fluid_jobs = max(self.fluid_jobs, len(watched))
                specs = self._fluid_specs(watched)
                sim.advance(t)
                for j in moved:
                    if j in sim.jobs:
                        ref.move(sim, j, specs[j], data[j][0], pause)
                sim.configure({j: data[j] for j in grown}, specs)
                continue
            if sim is not None:
                sim.advance(t)
            if sim is None or sim.near or t >= t0 + horizon_ms:
                # a new chain; one whose pacing agent met a step within
                # rounding is not compared, since either branch is sound
                self.fluid_chains += 1
                self.fluid_near += bool(sim is not None and sim.near)
                watched = {j: placements[j] for j in
                           closure(on_racks(placements), placements, self.fabric)}
                self.fluid_jobs = max(self.fluid_jobs, len(watched))
                sim, t0 = ref.FluidRef(self.cfg, self.fabric, f=f), t
                sim.load(t, {j: data[j] for j in watched},
                         self._fluid_specs(watched))
                continue
            for jid in watched:
                j, got = sim.jobs.get(jid), data.get(jid)
                if j is None and got is None:
                    continue            # finished on both sides
                if j is None or got is None:
                    # a job one side has finished stands at its last
                    # iteration there, with no delay: a completion within
                    # rounding of this state reads as a gap within rounding
                    end = self._finished(jid, watched[jid])
                    j = j or end
                if got is not None and not 0 <= got[1] < len(j["segs"]):
                    return 1.0, faults + [f"fluid: {jid} in no piece"]
                prog = end if got is None else dict(
                    j, iters_done=got[0], seg=got[1], remaining=got[2],
                    delay=got[3])
                gap = max(gap,
                          abs(ref.position(prog, j["segs"])
                              - ref.position(j, j["segs"])),
                          abs(float(prog["delay"]) - float(j["delay"])) / j["solo"])
        return gap, faults

    def _finished(self, jid: str, placement) -> dict:
        """Job ``jid`` at its end, as ``FluidRef`` keeps a job: all its
        iterations done, nothing of the next begun, no delay."""
        model, workers, batch, _, iters = self._fluid_specs({jid: placement})[jid]
        it, ph = ref.pattern(self.cfg["models"][model], workers, batch)
        segs = ref.segments(it, ph)
        links = self.fabric.links(placement)
        kind, dur, gbps = segs[0]
        work = dur if kind == "compute" or not links else gbps * dur * 1e-3
        return {"segs": segs, "solo": it, "links": links, "iters_done": iters,
                "seg": 0, "remaining": work, "delay": 0.0}

    def _fluid_specs(self, placements: dict) -> dict:
        """job -> ``(model, workers, batch, placement, iters)`` as
        ``FluidRef`` takes them, the workers those the placement holds."""
        return {j: (self.specs[j].model, len(pl), self.specs[j].batch, pl,
                    self.specs[j].iters) for j, pl in placements.items()}

    def _paced(self, directives: dict, placements: dict) -> list:
        """Each paced period is the job's quantized period on the unified
        circle of a contended link that alignment covers in the placement
        the decision chose (perimeter over its wraps), the largest over
        its links."""
        links = self.rule(ref.contended(self.fabric, placements))
        want: dict = {}
        for js in links or ():
            c = self.circle(self._jobs(js, placements))
            for i, jid in enumerate(js):
                want[jid] = max(want.get(jid, 0.0),
                                c["perimeter_ms"] / c["wraps"][i])
        faults = []
        for jid, w in want.items():
            paced = directives[jid][2]
            if paced is not None and abs(paced - w) > 1e-9 * w:
                faults.append(f"paced period of {jid}: {paced!r}, "
                              f"circle's {w!r}")
        return faults


def _valid(steps, c: dict) -> bool:
    return (len(steps) == len(c["grids"])
            and all(0 <= int(s) < g for s, g in zip(steps, c["grids"])))


def sample_decisions(scored: list, n: int, seed: int) -> list:
    """A seeded sample of ``n`` in-window decisions, always holding the one
    with the most contended links over its candidates (the longest)."""
    if not scored:
        return []
    longest = max(range(len(scored)), key=lambda i: sum(
        len(e[2]) for e in scored[i][2].evaluated))
    rest = [i for i in range(len(scored)) if i != longest]
    pick = random.Random(seed ^ 0xC0FFEE).sample(rest, min(n - 1, len(rest)))
    return sorted([longest] + pick)


def readings(judge: Judge, scored: list, outputs: dict, fluid: list,
             chk: dict, seed: int, racks: set, control: bool = False) -> dict:
    """The three numbers over the seeded sample, and what was compared."""
    faults: list[str] = []
    align = []
    judge.shortfall = {}
    # a decision that raised (degraded to the host's) has no output here;
    # ``degraded_decisions`` counts it
    scored = [s for s in scored if s[0] in outputs]
    picked = sample_decisions(scored, chk["decisions"], seed)
    for i in picked:
        idx, _, sp = scored[i]
        r = judge.decision(sp, outputs[idx], control=control)
        align.append(r["align_gap"])
        faults += r["faults"]
    short = list(judge.shortfall.values())
    fluid_gap, fluid_faults = judge.fluid(
        fluid, chk["fluid_chain_ms"], racks, control=control)
    cands = [pl for _, _, sp in scored for pl in sp.placements]
    out = {
        "rotation_gap": float(np.mean(short)) if short else 0.0,
        "align_gap": float(np.mean(align)) if align else 0.0,
        "fluid_gap": fluid_gap,
    }
    faults += fluid_faults
    return {"numbers": out, "faults": faults[:5], "decisions": len(picked),
            "links": len(short),
            "fluid_states": sum(1 for e in fluid if e[0] == "snap"),
            "fluid_chains": judge.fluid_chains, "fluid_near": judge.fluid_near,
            "fluid_cut": judge.fluid_cut, "fluid_jobs": judge.fluid_jobs,
            "candidates": len(cands),
            "discarded": sum(judge.rule(
                ref.contended(judge.fabric, pl)) is None for pl in cands)}


def verdict(got: dict, degraded: int, limits: dict) -> bool:
    """``correct``: no degraded decision, no fault, every number within
    its limit."""
    return (degraded == 0 and not got["faults"]
            and all(got["numbers"][k] <= limits[k] for k in NUMBERS))
