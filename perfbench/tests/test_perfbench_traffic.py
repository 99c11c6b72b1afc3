"""The traffic generator on the CPU: what a seed draws, how evenly, and
the structure each mix promises."""

import collections

import numpy as np
import pytest
from scipy.special import ndtri

from perfbench import model, traffic

VOCAB = 151936
MIXES = ("prefix", "decode", "unshared")
SEED = 2 ** 31 + 12345


def sched(mix, seed=SEED, seconds=51.0):
    return traffic.make_schedule(traffic.load_mix(mix), seed, seconds, VOCAB)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    a, b = sched(mix), sched(mix)
    assert [(s.prompt, s.max_new, s.due_s) for s in a.specs] == \
        [(s.prompt, s.max_new, s.due_s) for s in b.specs]


@pytest.mark.parametrize("mix", MIXES)
def test_count_fixed_and_schedule_drawn_from_the_seed(mix):
    m = traffic.load_mix(mix)
    a, b = sched(mix, 1), sched(mix, SEED)
    n = traffic.request_count(m, 51.0)
    assert len(a.specs) == len(b.specs) == n
    sizes = [[(len(s.prompt), s.max_new) for s in x.specs] for x in (a, b)]
    assert sizes[0] != sizes[1]
    assert [s.prompt for s in a.specs] != [s.prompt for s in b.specs]
    if m["kind"] == "open":
        assert [s.due_s for s in a.specs] != [s.due_s for s in b.specs]
    if "prefixes" not in m:
        # one draw per stratum: two seeds offer nearly the same work
        work = [sum(p + o for p, o in x) for x in sizes]
        assert abs(work[0] - work[1]) < 0.05 * work[0]


@pytest.mark.parametrize("dist", [
    {"dist": "lognormal", "median": 2048, "sigma": 0.4, "min": 1024,
     "max": 3968},
    {"dist": "uniform", "min": 512, "max": 1024}])
def test_lengths_one_per_stratum(dist):
    n = 40
    for seed in (1, SEED):
        x = traffic._lengths(np.random.default_rng(seed), dist, n)
        q = np.arange(n + 1) / n
        if dist["dist"] == "lognormal":
            with np.errstate(divide="ignore"):
                edges = dist["median"] * np.exp(dist["sigma"] * ndtri(q))
        else:
            edges = dist["min"] + q * (dist["max"] + 1 - dist["min"])
        edges = np.clip(np.floor(edges), dist["min"], dist["max"])
        xs = np.sort(x)
        assert np.all(edges[:-1] <= xs) and np.all(xs <= edges[1:])
        assert not np.array_equal(x, xs)        # in an order of their own


def test_arrivals_are_spaced_as_poisson_conditioned_on_count():
    n, seconds = 2000, 51.0
    due = np.asarray(traffic._arrivals(np.random.default_rng(SEED), n,
                                       seconds))
    assert len(due) == n and np.all(np.diff(due) > 0)
    assert 0 < due[0] and due[-1] < seconds
    gaps = np.diff(np.concatenate([[0.0], due, [seconds]]))
    mean = seconds / (n + 1)
    # exponential spacings: the mean, and as many gaps over the mean as an
    # exponential law leaves there (e**-1)
    assert abs(gaps.mean() - mean) < 1e-9
    assert abs((gaps > mean).mean() - np.exp(-1)) < 0.02


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_in_range(mix):
    m = traffic.load_mix(mix)
    for s in sched(mix).specs:
        assert m["answer"]["min"] <= s.max_new <= m["answer"]["max"]
        assert all(1 <= t < VOCAB for t in s.prompt)
        if "prefixes" not in m:
            assert m["prompt"]["min"] <= len(s.prompt) <= m["prompt"]["max"]


@pytest.mark.parametrize("mix,config", [("prefix", "qwen3-8b-l12"),
                                        ("decode", "qwen3-8b-l12"),
                                        ("unshared", "qwen3-32b-l4")])
def test_requests_fit_the_served_context(mix, config):
    limit = model.load_config(config)["serving"]["max_seq_len"]
    for seed in (1, SEED):
        for s in sched(mix, seed).specs:
            assert len(s.prompt) + s.max_new <= limit


def test_open_arrivals_inside_window():
    for mix in ("prefix", "unshared"):
        due = [s.due_s for s in sched(mix, seconds=20.0).specs]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 20.0


def test_prefix_sessions():
    m = traffic.load_mix("prefix")
    sc = sched("prefix")
    plen = m["prefixes"]["tokens"]
    assert len(sc.prefixes) == m["prefixes"]["count"]
    by_session = collections.defaultdict(list)
    for s in sc.specs:
        assert s.prompt[:plen] == sc.prefixes[s.prefix]
        by_session[s.session].append(s)
    lo, hi = m["turns"]
    sizes = sorted(len(v) for v in by_session.values())
    assert all(lo <= n <= hi for n in sizes[1:])    # the last may be cut
    for turns in by_session.values():
        turns.sort(key=lambda s: s.turn)
        assert [s.due_s for s in turns] == sorted(s.due_s for s in turns)
        for a, b in zip(turns, turns[1:]):
            # each turn extends the history: its prompt, its answer, a turn
            assert b.prompt[:len(a.prompt)] == a.prompt
            grown = len(b.prompt) - len(a.prompt)
            assert m["answer"]["min"] + m["prompt"]["min"] <= grown <= \
                m["answer"]["max"] + m["prompt"]["max"]


def test_prefix_popularity_is_zipf():
    m = traffic.load_mix("prefix")
    shape = np.random.default_rng(0)
    picks = traffic._zipf_choice(shape, m["prefixes"]["count"],
                                 m["prefixes"]["zipf_s"], 20000)
    freq = np.bincount(picks, minlength=m["prefixes"]["count"]) / 20000
    w = 1 / np.arange(1, m["prefixes"]["count"] + 1) ** m["prefixes"]["zipf_s"]
    np.testing.assert_allclose(freq, w / w.sum(), atol=0.01)
    # in the schedule, the most used prefix carries more sessions than the
    # least used one
    sessions = {(s.session, s.prefix) for s in sched("prefix").specs}
    counts = collections.Counter(p for _, p in sessions)
    assert max(counts.values()) > 2 * min(counts.values())


def test_unshared_has_no_shared_prefix():
    page = 16
    heads = [tuple(s.prompt[:page]) for s in sched("unshared").specs]
    assert len(set(heads)) == len(heads)
    assert not sched("unshared").prefixes


def test_closed_mix():
    sc = sched("decode")
    assert sc.kind == "closed" and sc.clients == 16
    assert all(s.due_s is None for s in sc.specs)
