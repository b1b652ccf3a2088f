"""The request generator (bench/traffic_gen.py)."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic_gen as tg

TRAFFIC = Path(__file__).resolve().parent / "traffic"


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["bursty", "decode-heavy"])
def test_same_seed_same_schedule(name):
    a = tg.schedule(mix(name), 30.0, 2**31 + 17, 1000, n_fill=4)
    b = tg.schedule(mix(name), 30.0, 2**31 + 17, 1000, n_fill=4)
    assert [(x.due, x.max_new_tokens) for x in a] == \
        [(x.due, x.max_new_tokens) for x in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


@pytest.mark.parametrize("name", ["bursty", "decode-heavy"])
def test_seeds_share_phase_shape_and_sizes(name):
    t = mix(name)
    runs = [tg.schedule(t, 30.0, s, 1000) for s in (1, 2, 3 * 10**9)]
    edges, period = [], 0.0
    for ph in t["arrivals"]["phases"]:
        period += ph["seconds"]
        edges.append(period)

    def per_phase(reqs):
        out = Counter()
        for r in reqs:
            k, q = divmod(r.due, period)
            out[(int(k), int(np.searchsorted(edges, q, side="right")))] += 1
        return out

    shapes = [per_phase(r) for r in runs]
    assert shapes[0] == shapes[1] == shapes[2]
    sizes = [sorted((len(x.prompt), x.max_new_tokens) for x in r)
             for r in runs]
    assert sorted(len(x.prompt) for x in runs[0]) == \
        sorted(len(x.prompt) for x in runs[1])
    assert sum(s[1] for s in sizes[0]) == sum(s[1] for s in sizes[1])
    assert [x.due for x in runs[0]] != [x.due for x in runs[1]]


def test_order_seed_fixes_the_order_of_sizes():
    t = mix("bursty")
    assert "order_seed" in t
    a, b = (tg.schedule(t, 30.0, s, 1000) for s in (1, 2))
    assert [(len(x.prompt), x.max_new_tokens) for x in a] == \
        [(len(x.prompt), x.max_new_tokens) for x in b]
    assert not all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    t.pop("order_seed")
    a, b = (tg.schedule(t, 30.0, s, 1000) for s in (1, 2))
    assert [x.max_new_tokens for x in a] != [x.max_new_tokens for x in b]


def test_bursty_phase_counts():
    t = mix("bursty")
    rate = t["arrivals"]["mean_rate"]
    reqs = tg.schedule(t, 10.0, 5, 1000)
    calm = [r for r in reqs if r.due < 6.0]
    assert len(calm) == round(rate * 0.5 * 6)
    assert len(reqs) - len(calm) == round(rate * 1.75 * 4)
    assert tg.mean_rate_of(t["arrivals"]) == pytest.approx(rate)


@pytest.mark.parametrize("name", ["bursty", "decode-heavy"])
def test_lengths_stay_in_their_laws(name):
    t = mix(name)
    reqs = tg.schedule(t, 30.0, 9, 777, n_fill=3)
    assert all(r.due == 0.0 for r in reqs[:3])
    for r in reqs:
        assert t["prompt"]["min"] <= len(r.prompt) <= t["prompt"]["max"]
        assert 1 <= r.max_new_tokens <= t["output"]["max"]
        assert len(r.prompt) + r.max_new_tokens <= t["max_total"]
        assert 0 <= r.prompt.min() and r.prompt.max() < 777


def test_quantiles_follow_the_law():
    q = tg.lognormal_quantiles(1001, {"median": 512, "sigma": 0.8,
                                      "min": 1, "max": 10**6})
    assert q[500] == 512
    assert (np.diff(q) >= 0).all()


@pytest.mark.parametrize("seed", [0, 2**31 + 5, -3, 2**40])
def test_any_whole_seed(seed):
    assert 0 <= tg.seed_key_int(seed) < 2**31
    tg.schedule(mix("bursty"), 1.0, seed, 10)
