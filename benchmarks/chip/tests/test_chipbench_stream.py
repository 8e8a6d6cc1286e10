"""The benchmark's traffic generator: seeded, endless, at its load."""

import collections
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
