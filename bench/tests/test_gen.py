"""The traffic generator: reproducible from the seed, within the mix's
stated ranges, the same multiset of sizes and the same arrival path for
every seed, and sizes spread evenly over the window."""
import json
import math
import pathlib

import numpy as np
import pytest

from bench import gen

MIXES = pathlib.Path(__file__).resolve().parents[1] / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _flat(sessions):
    return [a for s in sessions for a in s]


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_reproducible(name):
    mix = _mix(name)
    a = _flat(gen.generate(mix, 2 ** 31 + 7, 20, 1000))
    b = _flat(gen.generate(mix, 2 ** 31 + 7, 20, 1000))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.n_tokens, x.due_s, x.think_s) == (y.n_tokens, y.due_s, y.think_s)


def test_chat_ranges_and_rate():
    mix = _mix("chat")
    seconds = 30
    asks = _flat(gen.generate(mix, 12345, seconds, 1000))
    rate = mix["arrival"]["rate_per_s"]
    assert len(asks) == round(rate * seconds)
    p = np.array([len(a.prompt) for a in asks])
    o = np.array([a.n_tokens for a in asks])
    assert p.min() >= 128 and p.max() <= 1536
    assert o.min() >= 16 and o.max() <= 512
    assert abs(np.median(p) - 700) < 40 and abs(np.median(o) - 128) < 15
    due = np.array([a.due_s for a in asks])
    assert (np.diff(due) > 0).all() and 0 < due[0] and due[-1] < seconds
    assert all(a.temperature == 0.0 for a in asks)


def test_seeds_permute_one_multiset():
    mix = _mix("chat")
    a = _flat(gen.generate(mix, 1, 30, 1000))
    b = _flat(gen.generate(mix, 2, 30, 1000))
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.n_tokens for x in a) == sorted(x.n_tokens for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert not all(np.array_equal(x.prompt[:8], y.prompt[:8]) for x, y in zip(a, b))


def test_docqa_sessions_share_their_document():
    mix = _mix("docqa")
    s = mix["session"]
    for asks in gen.generate(mix, 99, 30, 1000):
        assert s["asks"]["min"] <= len(asks) <= s["asks"]["max"]
        for a in asks:
            assert 32 <= a.n_tokens <= 128 and 1.0 <= a.think_s <= 3.0
            assert (s["document_tokens"]["min"] + s["question_tokens"]["min"]
                    <= len(a.prompt)
                    <= s["document_tokens"]["max"] + s["question_tokens"]["max"])
        first = asks[0].prompt
        for a in asks[1:]:
            n = min(len(first), len(a.prompt))
            differ = np.flatnonzero(first[:n] != a.prompt[:n])
            shared = n if differ.size == 0 else differ[0]
            assert shared >= s["document_tokens"]["min"]
        assert asks[0].due_s is not None and all(a.due_s is None for a in asks[1:])


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_every_seed_shares_one_poisson_arrival_path(name):
    """The arrival times do not depend on the seed, and their gaps are the
    exponential quantiles, scaled to the window, in some order."""
    mix = _mix(name)
    seconds = 51
    runs = [gen.generate(mix, seed, seconds, 1000) for seed in (1, 2, 2 ** 31 + 7)]
    dues = [np.array([s[0].due_s for s in sessions]) for sessions in runs]
    for due in dues[1:]:
        assert np.array_equal(due, dues[0])
    n = len(dues[0])
    assert n == round(mix["arrival"]["rate_per_s"] * seconds)
    q = np.array([-math.log1p(-(i + 0.5) / (n + 1)) for i in range(n + 1)])
    gaps = np.diff(np.concatenate([[0.0], dues[0]]))
    expected = np.sort(q * seconds / q.sum())
    # n of the n + 1 gaps lie before the last arrival: each is one of the quantiles.
    assert np.isin(np.round(gaps, 9), np.round(expected, 9)).all()
    assert not (np.diff(gaps) > 0).all() and not (np.diff(gaps) < 0).all()


@pytest.mark.parametrize("n", [1, 7, 8, 61, 143])
def test_order_puts_one_rank_of_each_band_in_every_run(n):
    """``order`` is a permutation, and each run of ``BLOCK`` places holds
    ranks from as many bands: no seed puts a band's values together."""
    band = np.empty(n, int)
    for i, b in enumerate(np.array_split(np.arange(n), gen.BLOCK)):
        band[b] = i
    for seed in range(5):
        o = gen.order(n, np.random.default_rng(seed))
        assert sorted(o) == list(range(n))
        for start in range(0, n, gen.BLOCK):
            run = band[o[start:start + gen.BLOCK]]
            assert len(set(run)) == len(run)


def test_order_evens_out_the_work_between_stretches():
    """Output tokens offered in each quarter of a window of 143 chat
    requests vary between seeds far less than under a plain permutation
    (``block=1``)."""
    mix = _mix("chat")
    n = 143
    xs = np.array([gen.quantile(mix["output_tokens"], (i + 0.5) / n) for i in range(n)])

    def quarter_std(block):
        sums = [[q.sum() for q in np.array_split(
            xs[gen.order(n, np.random.default_rng(seed), block)], 4)] for seed in range(40)]
        return np.std(sums, axis=0).mean()

    assert quarter_std(gen.BLOCK) < 0.6 * quarter_std(1)
