"""The benchmark's generator copies make the program's draws."""
import numpy as np
import pytest

from bench import data


@pytest.mark.parametrize("seed", [0, 3, 2**33 + 5])
def test_sent140_copy_draws_what_the_program_draws(seed):
    from repro.data import make_sent140_like
    want = make_sent140_like(num_clients=30, vocab=3000, seed=seed)
    got = data.sent140(30, 3000, 24, 30, 1.1, seed)
    for k, v in want.client_data.items():
        np.testing.assert_array_equal(got["client_data"][k], v)
    np.testing.assert_array_equal(got["sample_counts"], want.sample_counts)
    np.testing.assert_array_equal(got["heat"], want.heat.counts)
    for k, v in want.test_data.items():
        np.testing.assert_array_equal(got["test_data"][k], v)


@pytest.mark.parametrize("seed", [0, 3, 2**33 + 5])
def test_amazon_copy_draws_what_the_program_draws(seed):
    from repro.data import make_amazon_like
    want = make_amazon_like(num_clients=30, num_items=700, seed=seed)
    got = data.amazon(30, 700, 10, 40, 1.05, 8, seed)
    for k, v in want.client_data.items():
        np.testing.assert_array_equal(got["client_data"][k], v)
    np.testing.assert_array_equal(got["heat"], want.heat.counts)


def test_boosted_zipf_matches_a_full_cdf_search():
    rng = np.random.default_rng(1)
    pop = data.zipf_probs(5000, 1.1)
    topic = rng.integers(0, 5000, 20)
    boost = float(np.exp(0.6))
    w = np.ones(5000)
    w[np.unique(topic)] = boost
    p = pop * w
    cdf = (p / p.sum()).cumsum()
    cdf /= cdf[-1]
    u = rng.random(20000)
    draw = data._BoostedZipf(pop, pop.cumsum(), topic, boost)
    got, want = draw(u), cdf.searchsorted(u, side="right")
    assert (got == want).mean() > 0.999
    assert got.min() >= 0 and got.max() < 5000


def test_heat_counts_clients_not_occurrences():
    ids = [np.array([1, 1, 2, -1]), np.array([2, 3])]
    np.testing.assert_array_equal(data.heat_counts(ids, 5), [0, 1, 2, 1, 0])
