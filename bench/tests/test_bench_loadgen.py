"""The traffic generator: deterministic from the seed, stratified, and the
same spread of lengths under every seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.loadgen import LoadGen

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def test_same_seed_same_requests():
    a, b = LoadGen(mix("chat"), 2**31 + 99, 32064), \
        LoadGen(mix("chat"), 2**31 + 99, 32064)
    for j in range(20):
        assert a.request(j) == b.request(j)


def test_another_seed_other_tokens_same_lengths():
    """The seed draws the data, not the amount of work."""
    a, c = LoadGen(mix("chat"), 2**31 + 99, 32064), \
        LoadGen(mix("chat"), 2**31 + 100, 32064)
    for j in range(20):
        assert a.lengths(j) == c.lengths(j)
        assert a.request(j).prompt != c.request(j).prompt


@pytest.mark.parametrize("n", [8, 16, 32, 64])
@pytest.mark.parametrize("dim", [0, 1])
def test_any_run_of_requests_is_stratified(n, dim):
    """The quantiles of any ``n`` consecutive requests leave no gap wider
    than 2/n on the circle: every stretch of the distribution is drawn
    about as often as it should be."""
    g = LoadGen(mix("chat"), 1, 32064)
    for start in (0, 5, 17, 300, 1001):
        u = np.sort([g.quantiles(j)[dim] for j in range(start, start + n)])
        assert np.diff(np.r_[u, u[0] + 1]).max() < 2 / n


def test_chat_lengths_follow_the_mix():
    g = LoadGen(mix("chat"), 7, 32064)
    p, o = np.array([g.lengths(j) for j in range(2000)]).T
    assert np.median(p) == pytest.approx(256, rel=0.03)
    assert np.median(o) == pytest.approx(128, rel=0.03)
    assert p.min() >= 16 and p.max() <= 768 and o.min() >= 8 \
        and o.max() <= 256
    # P(lognormal(256, 0.8) > 768) = P(Z > ln 3 / 0.8) = 8.5%
    assert np.mean(p == 768) == pytest.approx(0.085, abs=0.01)
    assert (p + o).max() <= 1024


def test_reasoning_outputs_fit_the_slot():
    g = LoadGen(mix("reasoning"), 7, 32064)
    p, o = np.array([g.lengths(j) for j in range(2000)]).T
    assert (p + o).max() <= 1024 and o.min() >= 256 and p.max() <= 128
    assert np.median(o) == pytest.approx(640, rel=0.03)


def test_token_ids_lie_in_the_vocabulary():
    r = LoadGen(mix("chat"), 3, 100).request(5)
    assert len(r.prompt) == LoadGen(mix("chat"), 3, 100).lengths(5)[0]
    assert 0 <= min(r.prompt) and max(r.prompt) < 100
