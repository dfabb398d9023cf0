"""Traffic generators: deterministic per seed, and the lengths, rates
and sharing they draw match their files' parameters."""

from __future__ import annotations

import numpy as np
import pytest

from bench import spec
from bench.traffic import _draw

VOCAB = 1000


def make(mix_name, load, seed, horizon=60.0, max_len=8192):
    mix = spec.traffic(mix_name)
    cls = spec.load_module("traffic", mix["kind"]).Traffic
    return cls(mix, load, vocab=VOCAB, seed=seed, horizon_s=horizon,
               max_len=max_len), mix


def drain(gen, until=1e9):
    return gen.due(until)


@pytest.mark.parametrize("mix_name,load", [
    ("chat", {"rate_per_s": 4.0}),
    ("longdoc", {"clients": 8}),
    ("sessions", {"session_rate_per_s": 1.0}),
])
def test_same_seed_same_inputs(mix_name, load):
    big = 2 ** 31 + 12345
    a = drain(make(mix_name, load, big)[0])
    b = drain(make(mix_name, load, big)[0])
    c = drain(make(mix_name, load, big + 1)[0])
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.rid == y.rid and x.max_new == y.max_new and x.due == y.due
        assert np.array_equal(x.prompt, y.prompt)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_stratified_quantiles_match_spec():
    spec_ = {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16,
             "max": 768}
    q = _draw.quantiles(spec_, 32)
    assert q.min() >= 16 and q.max() <= 768
    assert np.median(q) == pytest.approx(192, rel=0.05)
    # the sigma of the log lengths, away from the clipped tails
    mid = np.log(_draw.quantiles(dict(spec_, min=0, max=1e9), 1000))
    assert np.std(mid) == pytest.approx(0.8, rel=0.03)
    u = _draw.quantiles({"dist": "uniform", "min": 2, "max": 5}, 16)
    assert sorted(set(u)) == [2, 3, 4, 5] and list(u).count(2) == 4
    e = _draw.quantiles({"dist": "exponential", "mean": 3.0}, 4000)
    assert e.mean() == pytest.approx(3.0, rel=0.02)


def test_every_seed_gets_the_same_work():
    """Blocks hold the same multiset of sizes under every seed."""
    lens = [sorted(a.max_new for a in drain(make(
        "chat", {"rate_per_s": 4.0}, s)[0])) for s in (1, 2)]
    assert lens[0] == lens[1]
    due = [sorted(a.due for a in drain(make(
        "chat", {"rate_per_s": 4.0}, s)[0]))[-1] for s in (1, 2)]
    assert due[0] == pytest.approx(due[1])


def test_poisson_rate_and_lengths():
    gen, mix = make("chat", {"rate_per_s": 4.0}, 3, horizon=100.0)
    arr = drain(gen)
    due = np.array([a.due for a in arr])
    assert due[0] > 0.0 and np.all(np.diff(due) >= 0)
    n_100 = int(np.sum(due < 100.0))
    assert 380 <= n_100 <= 420               # 4/s over 100 s
    plen = np.array([len(a.prompt) for a in arr])
    assert plen.min() >= 16 and plen.max() <= 768
    assert np.median(plen) == pytest.approx(192, rel=0.1)
    assert np.median([a.max_new for a in arr]) == pytest.approx(128, rel=0.1)
    assert gen.next_due() is None


def test_stratified_arrivals_steady_the_window():
    """Stratified gaps offer the mean rate, hold the same multiset per
    block under every seed and none beyond ln(2 x block) mean gaps; the
    number due in a 40 s window varies far less than under independent
    exponential gaps at the same rate."""
    mix = spec.traffic("chat")
    block = mix["block"]
    cls = spec.load_module("traffic", mix["kind"]).Traffic
    counts, iid, gaps = [], [], []
    for seed in range(2 ** 31, 2 ** 31 + 40):
        gen = cls(mix, {"rate_per_s": 1.4}, vocab=VOCAB, seed=seed,
                  horizon_s=400.0, max_len=1024)
        due = np.array([a.due for a in gen.due(1e9)])
        counts.append(int(np.sum((due >= 30.0) & (due < 70.0))))
        gaps.append(np.diff(np.concatenate([[0.0], due])))
        free = np.cumsum(np.random.default_rng(seed).exponential(
            1 / 1.4, len(due)))
        iid.append(int(np.sum((free >= 30.0) & (free < 70.0))))
    g = np.concatenate(gaps)
    assert g.mean() == pytest.approx(1 / 1.4, rel=0.05)
    assert g.max() <= np.log(2 * block) / 1.4 + 1e-9
    assert sorted(gaps[0][:block]) == pytest.approx(sorted(gaps[1][:block]))
    assert np.mean(counts) == pytest.approx(56, rel=0.05)
    assert np.std(counts) < 4.5 < np.std(iid)


def test_closed_loop_sends_on_finish():
    gen, mix = make("longdoc", {"clients": 8}, 5)
    first = gen.due(0.0)
    assert len(first) == 8 and gen.due(10.0) == []
    for a in first:
        assert 1024 + 16 <= len(a.prompt) <= 7168 + 64
        assert 64 <= a.max_new <= 512
    gen.finished(first[0].rid, [1, 2], 2.5)
    assert gen.next_due() == 2.5
    nxt = gen.due(3.0)
    assert len(nxt) == 1 and nxt[0].due == 2.5


def test_sessions_share_system_prompts_and_history():
    gen, mix = make("sessions", {"session_rate_per_s": 2.0}, 7,
                    horizon=30.0, max_len=2048)
    systems = [s.tobytes() for s in gen._system]
    first = gen.due(30.0)
    assert first and all(len(a.prompt) > mix["system_len"] for a in first)
    heads = [a.prompt[:mix["system_len"]].tobytes() for a in first]
    assert set(heads) <= set(systems)
    counts = [heads.count(s) for s in systems]
    assert counts[0] > counts[-1]            # Zipf: the first is most popular
    a = first[0]
    answer = np.arange(a.max_new, dtype=np.int32)
    gen.finished(a.rid, answer, 31.0)
    follow = [b for b in gen.due(1e9) if len(b.prompt) > len(a.prompt)
              and np.array_equal(b.prompt[:len(a.prompt)], a.prompt)]
    assert len(follow) == 1
    b = follow[0]
    assert b.due > 31.0
    assert np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
    assert np.array_equal(b.prompt[len(a.prompt):len(a.prompt) + len(answer)],
                          answer)
    assert len(b.prompt) + b.max_new <= 2048
