"""Seeded, endless tenant streams for the benchmark cells.

One generator reads every traffic mix (``mixes/<name>.json``): a full
cluster of pinned tenant slots (the configuration's layout).  Every slot
holds one tenant from t = 0.  A tenant trains for its drawn number of
iterations, uniform in ``min_iters``..``max_iters`` (the CASSINI paper's
§5.1 population), and leaves when that many iterations take at its own
solo iteration time; a new tenant takes the slot at the same instant.

Tenants come from a deck that the mix fixes (``deck`` cards drawn from
``deck_seed``): a model, a worker count within the slot, a per-GPU batch
``ref_batch * U(lo, hi)`` and an iteration count.  The run's seed only
shuffles the deck and deals it to the slots, so every seed runs the same
tenants in another order.  Each slot's first tenant has a uniform share
of its iterations left, as in a cluster that has run for a while, so
departures start at once.

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
    placement: tuple[int, ...] | None = None  # pinned servers


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
    rng = random.Random(seed)
    profiles = config["models"]
    slots = slot_layout(config)
    cards = deck(config, mix)
    if any(w > min(len(s) for s in slots) for _, w, _, _ in cards):
        raise ValueError("a card asks for more workers than a slot holds")
    order: list = []
    gen = itertools.count()

    def deal(s: int, t: float, first: bool) -> tuple[JobSpec, float]:
        if not order:
            order.extend(rng.sample(cards, len(cards)))
        model, workers, batch, iters = order.pop()
        if first:
            iters = rng.randint(1, iters)
        spec = JobSpec(f"s{s:03d}g{next(gen):06d}-{model}", model, workers,
                       iters, batch, slots[s][:workers])
        return spec, t + iters * iter_time_ms(profiles[model], workers, batch)

    heap: list[tuple[float, int, str]] = []
    for s in range(len(slots)):
        spec, end = deal(s, 0.0, True)
        yield ("arrival", 0.0, spec)
        heapq.heappush(heap, (end, s, spec.job_id))
    while True:
        t, s, old = heapq.heappop(heap)
        spec, end = deal(s, t, False)
        yield ("departure", t, old)
        yield ("arrival", t, spec)
        heapq.heappush(heap, (end, s, spec.job_id))
