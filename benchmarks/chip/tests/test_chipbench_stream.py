"""The benchmark's traffic generator: seeded, endless, at its load."""

import collections
import hashlib
import itertools
import json
import statistics

import chipbench_support as sup
import pytest

from benchmarks.chip import stream


def _cfg(name):
    return json.loads((sup.ROOT / "benchmarks/chip/configs" / f"{name}.json").read_text())


def _mix(name):
    return json.loads((sup.ROOT / "benchmarks/chip/mixes" / f"{name}.json").read_text())


@pytest.mark.parametrize("cfg,mix", [("dense64-fine", "churn")])
def test_same_seed_same_stream(cfg, mix):
    c, m = _cfg(cfg), _mix(mix)
    seed = 2**31 + 12345  # seeds may exceed 32 signed bits
    a = list(itertools.islice(stream.events(c, m, seed), 300))
    b = list(itertools.islice(stream.events(c, m, seed), 300))
    other = list(itertools.islice(stream.events(c, m, seed + 1), 300))
    assert a == b
    assert a != other
    times = [e[1] for e in a]
    assert times == sorted(times)


def _dealt(c, m, seed, n):
    ev = itertools.islice(stream.events(c, m, seed), 2 * n + 51)
    return [e[2] for e in ev if e[0] == "arrival"][:n]


def test_every_seed_deals_the_same_deck():
    c, m = _cfg("dense64-fine"), _mix("churn")
    cards = collections.Counter((w, b, it) for w, b, it in
                                ((x[1], x[2], x[3]) for x in stream.deck(c, m)))
    for seed in (3, 2**33 + 1):
        # one whole deck: the first tenants' iterations are cut short
        got = _dealt(c, m, seed, m["deck"])
        assert collections.Counter(s.model for s in got) == collections.Counter(
            x[0] for x in stream.deck(c, m))
        later = collections.Counter((s.workers, s.batch, s.iters) for s in got[51:])
        assert not later - cards


def test_lifetimes_are_the_iterations_at_solo_time():
    c, m = _cfg("dense64-fine"), _mix("churn")
    start = {}
    lives = []
    for kind, t, what in itertools.islice(stream.events(c, m, 11), 2000):
        if kind == "arrival":
            start[what.job_id] = (t, what)
            assert m["min_iters"] <= what.iters <= m["max_iters"] or t == 0.0
            continue
        t0, spec = start.pop(what)
        solo = stream.iter_time_ms(c["models"][spec.model], spec.workers, spec.batch)
        assert t - t0 == pytest.approx(spec.iters * solo, rel=1e-12)
        if t0 > 0:
            lives.append(spec.iters)
    # the iteration counts of CASSINI section 5.1: uniform in [200, 1000]
    assert statistics.mean(lives) == pytest.approx(600, rel=0.1)


def test_churn_keeps_every_slot_full():
    c, m = _cfg("dense64-fine"), _mix("churn")
    slots = stream.slot_layout(c)
    assert len(slots) == 51
    servers = [s for slot in slots for s in slot]
    assert len(servers) == len(set(servers))  # no server in two slots
    live = {}
    for kind, t, what in itertools.islice(stream.events(c, m, 5), 2000):
        if kind == "arrival":
            assert what.placement == slots[int(what.job_id[1:4])][:what.workers]
            assert m["min_workers"] <= what.workers == len(what.placement)
            live[what.job_id] = what
        else:
            del live[what]
        if kind == "arrival" and t > 0:
            assert len(live) == len(slots)
    # departures come at the rate the deck's mean life sets
    mean_life = statistics.mean(
        it * stream.iter_time_ms(c["models"][mo], w, b)
        for mo, w, b, it in stream.deck(c, m))
    ev = list(itertools.islice(stream.events(c, m, 5), 40000))
    later = [e for e in ev if e[1] > 2 * mean_life]
    departures = sum(1 for e in later if e[0] == "departure")
    span = later[-1][1] - later[0][1]
    assert departures / span == pytest.approx(len(slots) / mean_life, rel=0.1)


# sha256 of the first 2,000 churn events of each seed, as the cell's bounds
# were measured on them: a change to the stream changes what the bounds hold
CHURN_SHA256 = {
    1: "c29cbd1f5c6e338917a6604d41defbb422f0a714b01a47315d972af5184a8595",
    2**31 + 12345: "0112693b0a3066b1def5b39a80ee21afd02149ed1469738d1ce6de6b9ceee922",
    4052739537: "4aa32d0116cd2e464523ad73a85be3273ed115cd5021af46c577de91f200368a",
}


def _digest(events) -> str:
    flat = [(k, t, (w.job_id, w.model, w.workers, w.iters, w.batch, w.placement)
             if k == "arrival" else w) for k, t, w in events]
    return hashlib.sha256(repr(flat).encode()).hexdigest()


@pytest.mark.parametrize("process", [None, "slots"], ids=["absent", "slots"])
@pytest.mark.parametrize("seed", sorted(CHURN_SHA256))
def test_churn_stream_is_unchanged(process, seed):
    c, m = _cfg("dense64-fine"), _mix("churn")
    if process is not None:
        m["process"] = process
    events = list(itertools.islice(stream.events(c, m, seed), 2000))
    assert _digest(events) == CHURN_SHA256[seed]


def test_every_seed_runs_the_same_tenants_elsewhere():
    c, m = _cfg("dense64-fine"), _mix("churn")

    def run(seed):
        ev = list(itertools.islice(stream.events(c, m, seed), 2000))
        work = [(k, t, (w.model, w.workers, w.batch, w.iters) if k == "arrival"
                 else None) for k, t, w in ev]
        return work, [(w.job_id[:4], w.placement) for k, _, w in ev if k == "arrival"]

    (work_a, where_a), (work_b, where_b) = run(3), run(2**33 + 1)
    assert work_a == work_b
    assert where_a != where_b
    # each seed starts one tenant on every slot
    assert len({slot for slot, _ in where_a[:51]}) == 51
    assert len({slot for slot, _ in where_b[:51]}) == 51
    # and the same sequences of tenants share a hub rack
    slots = stream.slot_layout(c)
    spr = c["topology"]["servers_per_rack"]

    def sharing(where):
        racks = collections.defaultdict(set)
        for k, s in enumerate(where):
            racks[slots[s][0] // spr].add(k)
        return sorted(map(sorted, racks.values()))

    assert sharing(stream._places(c, 3)) == sharing(stream._places(c, 2**33 + 1))


def _open_mix(process, **kw):
    m = _mix("churn")
    m.update(process=process, load=0.9, min_workers=1, max_workers=12, deck=256,
             block=8, **kw)
    return m


@pytest.mark.parametrize("process,kw", [("poisson", {}), ("burst", {"burst": 4})])
def test_every_seed_offers_the_same_blocks(process, kw):
    c, m = _cfg("dense64-fine"), _open_mix(process, **kw)
    n = 400 * m["block"]
    a = list(itertools.islice(stream.events(c, m, 7), n))
    b = list(itertools.islice(stream.events(c, m, 2**33 + 1), n))
    assert a != b

    def cards(events):
        return collections.Counter((s.model, s.workers, s.batch, s.iters)
                                   for _, _, s in events)

    for i in range(0, n, m["block"]):
        assert cards(a[i:i + m["block"]]) == cards(b[i:i + m["block"]])
        assert a[i][1] == pytest.approx(b[i][1], rel=1e-9)


def _offered_load(c, events) -> float:
    """Solo GPU-milliseconds of the arrivals over the cluster's GPU time
    up to the last of them."""
    topo = c["topology"]
    gpus = topo["racks"] * topo["servers_per_rack"] * topo["gpus_per_server"]
    busy = sum(s.workers * s.iters
               * stream.iter_time_ms(c["models"][s.model], s.workers, s.batch)
               for _, _, s in events)
    return busy / (gpus * events[-1][1])


@pytest.mark.parametrize("seed", [7, 2**33 + 1])
def test_poisson_stream_is_seeded_at_its_load(seed):
    c, m = _cfg("dense64-fine"), _open_mix("poisson")
    events = list(itertools.islice(stream.events(c, m, seed), 20000))
    assert events[:300] == list(itertools.islice(stream.events(c, m, seed), 300))
    assert events[:300] != list(itertools.islice(stream.events(c, m, seed + 1), 300))
    assert all(k == "arrival" and s.placement is None for k, _, s in events)
    times = [t for _, t, _ in events]
    assert times == sorted(times) and len(set(times)) == len(times)
    assert _offered_load(c, events) == pytest.approx(m["load"], rel=0.03)


def test_burst_stream_lands_in_bursts_at_the_same_load():
    c, m = _cfg("dense64-fine"), _open_mix("burst", burst=4)
    events = list(itertools.islice(stream.events(c, m, 2**31 + 3), 20000))
    per_instant = collections.Counter(t for _, t, _ in events)
    assert set(per_instant.values()) == {4}
    assert all(k == "arrival" and s.placement is None for k, _, s in events)
    assert _offered_load(c, events) == pytest.approx(m["load"], rel=0.03)


def test_unknown_process_raises():
    with pytest.raises(ValueError):
        stream.events(_cfg("dense64-fine"), _open_mix("diurnal"), 1)
