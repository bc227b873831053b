"""The traffic generator and the percentile: seeded, stratified, and
matching hand-worked fixtures."""
import collections
import math

import numpy as np
import pytest

import gen
import stats

OPEN = {"loop": "open", "strata": 16,
        "arrivals": {"kind": "poisson", "rate_rps": 4.0},
        "prompt": {"dist": "pareto", "alpha": 1.2, "lo": 128, "hi": 448},
        "output": {"dist": "lognormal", "median": 32, "sigma": 0.5,
                   "lo": 16, "hi": 64}}
CLOSED = {"loop": "closed", "strata": 16,
          "prompt": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                     "lo": 32, "hi": 512},
          "output": {"dist": "fixed", "lo": 7, "hi": 7}}


def _key(reqs):
    return [(r.due_s, tuple(r.prompt), r.max_tokens) for r in reqs]


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_same_seed_same_requests(mix):
    seed = 2**33 + 17
    assert _key(gen.generate(mix, seed, 48, 1000)) == \
        _key(gen.generate(mix, seed, 48, 1000))
    assert _key(gen.generate(mix, seed, 48, 1000)) != \
        _key(gen.generate(mix, seed + 1, 48, 1000))


def test_seeds_share_lengths_and_gaps():
    """Every seed sends the same multiset of sizes and gaps per block."""
    a = gen.generate(OPEN, 3, 32, 1000)
    b = gen.generate(OPEN, 2**40 + 5, 32, 1000)
    for field in ("max_tokens",):
        assert collections.Counter(getattr(r, field) for r in a) == \
            collections.Counter(getattr(r, field) for r in b)
    assert collections.Counter(len(r.prompt) for r in a) == \
        collections.Counter(len(r.prompt) for r in b)
    assert a[-1].due_s == pytest.approx(b[-1].due_s, rel=1e-12)
    assert [r.due_s for r in a] != [r.due_s for r in b]


def test_lengths_in_range_and_closed_loop_due_at_zero():
    reqs = gen.generate(CLOSED, 9, 64, 500)
    assert all(32 <= len(r.prompt) <= 512 for r in reqs)
    assert all(r.max_tokens == 7 and r.due_s == 0.0 for r in reqs)
    assert all(0 <= t < 500 for r in reqs for t in r.prompt)


def test_poisson_gaps_mean():
    """Stratified exponential gaps: 32 midpoints average to 1/rate
    within the midpoint rule's error."""
    reqs = gen.generate({**OPEN, "strata": 32}, 1, 32, 100)
    assert reqs[-1].due_s / 32 == pytest.approx(0.25, rel=0.03)


def test_length_inverse_cdfs():
    par = {"dist": "pareto", "alpha": 1.0, "lo": 100, "hi": 400}
    # bounded Pareto, alpha 1: x = lo / (1 - u (1 - lo/hi)); u=0.5 -> 160
    assert gen.length(par, 0.5) == 160
    assert gen.length(par, 0.0) == 100
    logn = {"dist": "lognormal", "median": 50, "sigma": 1.0, "lo": 1,
            "hi": 10**6}
    assert gen.length(logn, 0.5) == 50
    assert gen.length(logn, 0.975) == round(50 * math.exp(1.959963985))
    assert gen.length({"dist": "fixed", "lo": 5, "hi": 9}, 0.3) == 5


def test_norm_ppf():
    for u, z in [(0.5, 0.0), (0.975, 1.959963985), (0.01, -2.326347874),
                 (0.999, 3.090232306)]:
        assert gen._norm_ppf(u) == pytest.approx(z, abs=1e-7)


def test_stratified_blocks():
    u = gen.stratified(np.random.default_rng(0), 20, 8)
    assert sorted(u[:8]) == [(j + 0.5) / 8 for j in range(8)]
    assert sorted(u[8:16]) == [(j + 0.5) / 8 for j in range(8)]


@pytest.mark.parametrize("xs,q,want", [
    (list(range(1, 11)), 90, 9.0),
    (list(range(1, 11)), 50, 5.0),
    ([5.0, 1.0, 3.0], 50, 3.0),
    ([5.0, 1.0, 3.0], 100, 5.0),
    ([2.5], 90, 2.5),
    ([0.1, 0.4, 0.2, 0.3], 0, 0.1),
])
def test_percentile_fixtures(xs, q, want):
    assert stats.percentile(xs, q) == want


def test_percentile_refuses_empty():
    with pytest.raises(ValueError):
        stats.percentile([], 90)
