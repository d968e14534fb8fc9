"""The traffic generator: reproducible from the seed, within the mix's
stated ranges, and the same multiset of sizes for every seed."""
import json
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
