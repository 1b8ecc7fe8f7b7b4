"""Synthetic federated datasets: statistics + learnability invariants."""
import numpy as np
import pytest

from repro.data.synthetic import (make_alibaba_like, make_amazon_like,
                                  make_lm_federated, make_movielens_like,
                                  make_sent140_like)


@pytest.mark.parametrize("maker,task", [
    (make_movielens_like, "lr"),
    (make_sent140_like, "lstm"),
    (make_amazon_like, "din"),
    (make_alibaba_like, "din"),
    (make_lm_federated, "lm"),
])
def test_dataset_invariants(maker, task):
    ds = maker()
    assert ds.task == task
    assert ds.num_clients == len(ds.sample_counts)
    # heat counts never exceed the client count, dispersion > 1 (hot/cold split)
    assert ds.heat.counts.max() <= ds.num_clients
    assert ds.heat.dispersion() > 2.0
    key = ds.feature_key
    assert key in ds.client_data
    ids = ds.client_data[key]
    assert ids.max() < ds.num_features
    # padded leaves share the leading (clients, max_samples) shape
    shapes = {v.shape[:2] for v in ds.client_data.values()}
    assert len(shapes) == 1


def test_movielens_labels_learnable():
    """Pooled logistic regression on the planted model must beat chance."""
    ds = make_movielens_like(num_clients=100, num_items=60)
    import jax, jax.numpy as jnp
    from repro.models.recsys import lr_loss, lr_logits, make_lr_params
    params = make_lr_params(ds.num_features, rng=jax.random.PRNGKey(0))
    feats, labels = [], []
    for c in range(ds.num_clients):
        n = ds.sample_counts[c]
        feats.append(ds.client_data["features"][c][:n])
        labels.append(ds.client_data["label"][c][:n])
    feats = jnp.asarray(np.concatenate(feats))
    labels = jnp.asarray(np.concatenate(labels))
    batch = {"features": feats, "label": labels}

    @jax.jit
    def step(p):
        g = jax.grad(lr_loss)(p, batch)
        return jax.tree.map(lambda a, b: a - 1.0 * b, p, g)

    for _ in range(60):
        params = step(params)
    acc = float(((lr_logits(params, feats) > 0) == (labels > 0.5)).mean())
    assert acc > 0.65


def test_dispersion_grows_with_zipf_exponent():
    lo = make_movielens_like(num_clients=150, num_items=100, zipf_a=0.6, seed=3)
    hi = make_movielens_like(num_clients=150, num_items=100, zipf_a=1.8, seed=3)
    assert hi.heat.dispersion() >= lo.heat.dispersion()


def test_sent140_draws_match_per_sentence_choice():
    """The per-client CDF reproduces ``rng.choice(vocab, size, p=p)`` draw
    for draw: the generator's old per-sentence loop, kept here as the
    reference, yields the identical corpus."""
    num_clients, vocab, seq_len, mean_samples, zipf_a = 6, 700, 24, 30, 1.1
    rng = np.random.default_rng(5)
    p0 = 1.0 / np.arange(1, vocab + 1) ** zipf_a
    pop = p0 / p0.sum()
    rng.normal(0, 1.0, vocab)                  # the planted polarities
    toks = []
    for _ in range(num_clients):
        n = max(5, int(rng.poisson(mean_samples)))
        boost = np.zeros(vocab)
        boost[rng.choice(vocab, size=20, p=pop)] += 3.0
        p = pop * np.exp(boost * 0.2)
        p /= p.sum()
        lens = rng.integers(6, seq_len + 1, n)
        seqs = np.full((n, seq_len), -1, np.int32)
        for j in range(n):
            s = rng.choice(vocab, size=lens[j], p=p)
            seqs[j, : lens[j]] = s
            rng.normal(0, 0.3)                 # the label's noise draw
        toks.append(seqs[max(1, int(n * 0.2)):])
    ds = make_sent140_like(num_clients=num_clients, vocab=vocab, seed=5)
    for c, want in enumerate(toks):
        np.testing.assert_array_equal(ds.client_data["tokens"][c, :len(want)],
                                      want)
