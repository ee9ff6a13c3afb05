"""Traffic generators: seeded, inside their clips, at their rate, and the
same work for every seed."""

import json
import os

import numpy as np
import pytest

from benchlib import cells, traffic

CHAT = cells.load_traffic("nemo-chat-poisson")
OFFLINE = cells.load_traffic("nemo-reason-offline")
BIG_SEED = 2**31 + 12345


def gen(spec, seed, seconds=30.0, vocab=131072):
    return cells.traffic_kind(spec["kind"]).generate(spec, seed, seconds,
                                                     vocab)


@pytest.mark.parametrize("spec", [CHAT, OFFLINE], ids=["chat", "offline"])
def test_same_seed_same_trace(spec):
    a, b = gen(spec, BIG_SEED), gen(spec, BIG_SEED)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.t == y.t and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("spec", [CHAT, OFFLINE], ids=["chat", "offline"])
def test_seeds_share_sizes_not_order(spec):
    spec = {k: v for k, v in spec.items() if k != "schedule_seed"}
    a, b = gen(spec, 1, seconds=300.0), gen(spec, 2, seconds=300.0)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    warm = spec.get("warm_in_s", 0.0)
    for size in (lambda x: len(x.prompt), lambda x: x.max_new):
        window = lambda arr: sorted(size(x) for x in arr if x.t >= warm)
        assert window(a) == window(b)


@pytest.mark.parametrize("spec", [CHAT, OFFLINE], ids=["chat", "offline"])
def test_schedule_seed_fixes_the_order_not_the_tokens(spec):
    fixed = dict(spec, schedule_seed=99)
    a, b = gen(fixed, 1), gen(fixed, 2)
    assert [(x.t, len(x.prompt), x.max_new) for x in a] == \
        [(x.t, len(x.prompt), x.max_new) for x in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("spec", [CHAT, OFFLINE], ids=["chat", "offline"])
def test_lengths_inside_clips(spec):
    arr = gen(spec, BIG_SEED)
    p, o = spec["prompt"], spec["output"]
    assert all(p["min"] <= len(x.prompt) <= p["max"] for x in arr)
    assert all(o["min"] <= x.max_new <= o["max"] for x in arr)
    assert all(x.prompt.dtype == np.int32 for x in arr)


def test_lognormal_median():
    n = traffic.lengths(CHAT["prompt"], 1001)
    assert abs(np.median(n) - CHAT["prompt"]["median"]) <= 1


@pytest.mark.parametrize("spec", [CHAT], ids=["chat"])
def test_poisson_window_holds_its_rate(spec):
    seconds = 40.0
    arr = gen(spec, 7, seconds=seconds)
    rate, warm = spec["rate_per_s"], spec["warm_in_s"]
    ts = np.array([x.t for x in arr])
    assert np.all(np.diff(ts[ts < warm]) > 0)
    assert np.all(np.diff(ts[ts >= warm]) > 0)
    assert np.all(ts > 0) and np.all(ts < warm + seconds)
    # Each part holds rate x its length of arrivals, inside it.
    assert (ts < warm).sum() == traffic.count_for(rate, warm)
    assert (ts >= warm).sum() == traffic.count_for(rate, seconds)


def test_poisson_times_fill_the_span():
    rng = np.random.default_rng(0)
    t = traffic.poisson_times(rng, 2.0, 80, 40.0)
    assert len(t) == 80 and 0 < t[0] and t[-1] < 40.0
    # The 81 gaps, to the span's end included, are Exp(2)'s stratified
    # quantiles scaled to fill the span: near their own mean of 1/2 s.
    gaps = np.diff(np.concatenate([[0.0], t, [40.0]]))
    want = traffic.exponential_gaps(2.0, 81)
    np.testing.assert_allclose(np.sort(gaps), want * 40.0 / want.sum())
    assert 40.0 / want.sum() == pytest.approx(1.0, rel=0.05)


def test_offline_all_due_at_zero():
    arr = gen(OFFLINE, 3)
    assert len(arr) == OFFLINE["requests"]
    assert all(x.t == 0.0 for x in arr)


def test_uniform_lengths_cover_range():
    n = traffic.lengths({"dist": "uniform", "min": 16, "max": 128}, 113)
    assert sorted(set(n.tolist())) == list(range(16, 129))


def test_unknown_distribution_refused():
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "pareto", "min": 1, "max": 2}, 4)


def test_traffic_kind_added_as_a_file(checkout):
    root, chip = checkout
    with open(os.path.join(chip, "traffic_kinds", "burst.py"), "w") as f:
        f.write("from benchlib import traffic\n\n"
                "def generate(spec, seed, seconds, vocab):\n"
                "    p, o = traffic.sizes(traffic.order_rng(spec, seed),\n"
                "                         spec['n'], spec['prompt'],\n"
                "                         spec['output'])\n"
                "    return traffic.arrivals(seed, [0.0] * spec['n'], p, o,\n"
                "                            vocab)\n")
    with open(os.path.join(chip, "traffic", "burst-cell.json"), "w") as f:
        json.dump({"kind": "burst", "n": 5, "prompt": CHAT["prompt"],
                   "output": CHAT["output"]}, f)
    spec = cells.load_traffic("burst-cell", chip)
    arr = cells.traffic_kind(spec["kind"], chip).generate(spec, 1, 10.0, 100)
    assert len(arr) == 5
