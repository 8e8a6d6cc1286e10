"""Seeded, endless event streams for the benchmark cells.

One generator reads every traffic mix (``mixes/<name>.json``); the mix's
``process`` picks the arrival process:

``"slots"`` (or no ``process``)
    A full cluster of pinned tenant slots (the configuration's
    ``hub_leaf`` layout).  Every slot holds one tenant from t = 0.  A
    tenant trains for its drawn number of iterations and leaves when that
    many iterations take at its own solo iteration time; a new tenant
    takes the slot at the same instant.  Each slot's first tenant has a
    uniform share of its iterations left, as in a cluster that has run for
    a while, so departures start at once.
``"poisson"``
    Open arrivals with exponential gaps, no placement (the host places)
    and no departures (jobs complete inside the fluid engine).  The gap
    after a job is ``Exp(1) * workers * solo_ms / (load * cluster GPUs)``,
    where ``solo_ms`` is its iterations at its solo iteration time on the
    workers it asks for: offered load counts solo run time, as
    ``repro.cluster.traces.iter_arrival_trace`` does, so the expected
    number of busy GPUs is ``load`` times the cluster's.  Contention, the
    host's shrinking and queueing make the real occupancy differ.
``"burst"``
    The same, in groups of ``burst`` arrivals at one instant; the gap
    between groups is the sum of its members' gaps, so the mean load is
    the same.

Tenants come from a deck that the mix fixes (``deck`` cards drawn from
``deck_seed``): one of the configuration's models, a worker count in
``min_workers``..``max_workers``, a per-GPU batch ``ref_batch * U(lo,
hi)`` (``batch_scale``) and an iteration count in
``min_iters``..``max_iters`` (the CASSINI paper's §5.1 population).

The work is the same for every seed: the order in which the deck is dealt,
the first tenants' remaining iterations and the gaps' exponential draws
all come from the mix's ``deal_seed``.  The run's seed only moves that
work: with ``slots`` it picks which hub group, and which slot in it,
runs each sequence of tenants, so every seed has the same tenants at the
same instants sharing the same hubs, on other racks; with ``poisson`` and
``burst`` it orders the arrivals within each block of ``block`` (each
job keeping its own gap draw), so every block offers the same load.

Events are plain tuples, in non-decreasing time order:
``("arrival", t_ms, JobSpec)`` and ``("departure", t_ms, job_id)``.
"""

from __future__ import annotations

import heapq
import itertools
import random
from dataclasses import dataclass
from typing import Iterator

__all__ = ["JobSpec", "deck", "events", "iter_time_ms", "slot_layout"]


@dataclass(frozen=True)
class JobSpec:
    job_id: str
    model: str
    workers: int
    iters: int
    batch: int | None = None
    placement: tuple[int, ...] | None = None  # pinned servers; None: the host places


def iter_time_ms(profile: dict, workers: int, batch: int | None) -> float:
    """Solo iteration time of one job from the configuration's profile
    table (the paper's §5.1 analytic profiles)."""
    b = batch or profile["ref_batch"]
    if profile["parallelism"] == "mp":
        return profile["mp_iter_ms"] * (0.5 + 0.5 * b / profile["ref_batch"])
    n = max(2, workers)
    gbit = 2.0 * profile["param_mb"] * 8e-3 * (n - 1) / n
    comm = gbit / (profile["peak_gbps"] * profile["comm_efficiency"]) * 1e3
    return profile["compute_ms"] * (b / profile["ref_batch"]) + comm


def slot_layout(config: dict) -> list[tuple[int, ...]]:
    """Server lists of the pinned tenant slots of a ``hub_leaf`` layout.

    A group is one hub rack and ``k`` leaf racks; slot ``i`` of the group
    is hub server ``i`` plus the servers of leaf rack ``i``, so each hub
    uplink carries ``k`` tenants and each leaf uplink one: the affinity
    graph is a forest of stars (the Theorem 1 precondition holds).
    """
    layout = config["layout"]
    if layout["kind"] != "hub_leaf":
        raise ValueError(f"unknown layout {layout['kind']!r}")
    spr = config["topology"]["servers_per_rack"]
    slots: list[tuple[int, ...]] = []
    rack = 0
    for count, leaves in layout["groups"]:
        for _ in range(count):
            hub = rack
            for i in range(leaves):
                leaf = hub + 1 + i
                slots.append(
                    (hub * spr + i,)
                    + tuple(leaf * spr + s for s in range(spr))
                )
            rack += 1 + leaves
    if rack != config["topology"]["racks"]:
        raise ValueError(f"layout covers {rack} racks, topology has "
                         f"{config['topology']['racks']}")
    return slots


def deck(config: dict, mix: dict) -> list[tuple[str, int, int | None, int]]:
    """The mix's fixed tenant cards ``(model, workers, batch, iters)``."""
    rng = random.Random(mix["deck_seed"])
    profiles = config["models"]
    models = sorted(profiles)
    lo_b, hi_b = mix["batch_scale"]
    cards = []
    for _ in range(mix["deck"]):
        model = rng.choice(models)
        workers = rng.randint(mix["min_workers"], mix["max_workers"])
        batch = max(1, int(round(profiles[model]["ref_batch"]
                                 * rng.uniform(lo_b, hi_b))))
        cards.append((model, workers, batch,
                      rng.randint(mix["min_iters"], mix["max_iters"])))
    return cards


def events(config: dict, mix: dict, seed: int) -> Iterator[tuple]:
    """The endless event stream of one cell, a function of the seed."""
    process = mix.get("process", "slots")
    if process == "slots":
        return _slots(config, mix, seed)
    if process in ("poisson", "burst"):
        return _open(config, mix, seed,
                     mix["burst"] if process == "burst" else 1)
    raise ValueError(f"unknown arrival process {process!r}; one of slots, "
                     "poisson, burst")


def _places(config: dict, seed: int) -> list[int]:
    """The slot of each sequence of tenants: the seed moves whole groups
    among the layout's groups of one kind, and the slots within each
    group, so the tenants that share a hub are the same for every seed."""
    rng = random.Random(seed)
    where: list[int] = []
    first = 0
    for count, leaves in config["layout"]["groups"]:
        groups = [range(first + g * leaves, first + (g + 1) * leaves)
                  for g in range(count)]
        for group in rng.sample(groups, count):
            where.extend(rng.sample(group, leaves))
        first += count * leaves
    return where


def _slots(config: dict, mix: dict, seed: int) -> Iterator[tuple]:
    rng = random.Random(mix["deal_seed"])
    profiles = config["models"]
    slots = slot_layout(config)
    where = _places(config, seed)
    cards = deck(config, mix)
    if any(w > min(len(s) for s in slots) for _, w, _, _ in cards):
        raise ValueError("a card asks for more workers than a slot holds")
    order: list = []
    gen = itertools.count()

    def deal(k: int, t: float, first: bool) -> tuple[JobSpec, float]:
        if not order:
            order.extend(rng.sample(cards, len(cards)))
        model, workers, batch, iters = order.pop()
        if first:
            iters = rng.randint(1, iters)
        s = where[k]
        spec = JobSpec(f"s{s:03d}g{next(gen):06d}-{model}", model, workers,
                       iters, batch, slots[s][:workers])
        return spec, t + iters * iter_time_ms(profiles[model], workers, batch)

    heap: list[tuple[float, int, str]] = []
    for k in range(len(slots)):
        spec, end = deal(k, 0.0, True)
        yield ("arrival", 0.0, spec)
        heapq.heappush(heap, (end, k, spec.job_id))
    while True:
        t, k, old = heapq.heappop(heap)
        spec, end = deal(k, t, False)
        yield ("departure", t, old)
        yield ("arrival", t, spec)
        heapq.heappush(heap, (end, k, spec.job_id))


def _open(config: dict, mix: dict, seed: int, burst: int) -> Iterator[tuple]:
    rng = random.Random(mix["deal_seed"])
    shuffle = random.Random(seed)
    profiles = config["models"]
    topo = config["topology"]
    gpus = topo["racks"] * topo["servers_per_rack"] * topo.get("gpus_per_server", 1)
    cards = deck(config, mix)
    block = mix["block"]
    if burst < 1 or block % burst or any(w > gpus for _, w, _, _ in cards):
        raise ValueError("bursts need one arrival or more and a block a whole "
                         "number of bursts, and a card no more workers than "
                         "the cluster holds")
    order: list = []
    t = gap = 0.0
    n = 0
    while True:
        work = []
        for _ in range(block):
            if not order:
                order.extend(rng.sample(cards, len(cards)))
            work.append((order.pop(), rng.expovariate(1.0)))
        for (model, workers, batch, iters), draw in shuffle.sample(work, block):
            yield ("arrival", t, JobSpec(f"a{n:07d}-{model}", model, workers,
                                         iters, batch))
            solo_ms = iters * iter_time_ms(profiles[model], workers, batch)
            gap += draw * workers * solo_ms / (mix["load"] * gpus)
            n += 1
            if n % burst == 0:
                t, gap = t + gap, 0.0
