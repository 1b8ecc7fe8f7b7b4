"""The plain dense FedSubAvg round, in float32 ``jax.numpy``.

Each sampled client copies the whole model, runs ``I`` steps of minibatch
SGD on its own ``(I, B)`` batches and returns the difference; the server
averages the K differences, multiplies every row of a feature table by
``N / n_m`` (0 where no client holds the feature) and adds ``server_lr``
times the result (Ding et al., arXiv:2109.07704, Algorithm 1). The round's
reported loss is the mean over clients of the loss on each client's first
batch at the round's starting parameters.

The cohorts are drawn as the trainer documents its stream: a NumPy
generator seeded with the run's seed draws K distinct clients, then for
each client in order ``(I, B)`` sample indices with replacement.

Nothing here imports the program. ``fault`` plants one of the faults the
benchmark must catch, for the readings that set its limits.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def cohort_stream(client_data: dict, sample_counts: np.ndarray, k: int,
                  iters: int, batch: int, seed: int | None = None, rng=None):
    """Yield each round's cohort, leaves ``(K, I, B, ...)`` plus a
    ``sample_mask``, drawn from ``rng`` (a NumPy generator seeded with
    ``seed`` when none is given)."""
    rng = np.random.default_rng(seed) if rng is None else rng
    n_clients = len(sample_counts)
    while True:
        ids = rng.choice(n_clients, size=k, replace=False)
        out = {key: [] for key in client_data}
        out["sample_mask"] = []
        for c in ids:
            n = int(sample_counts[c])
            idx = rng.integers(0, max(n, 1), size=(iters, batch))
            for key, arr in client_data.items():
                out[key].append(arr[c][idx])
            out["sample_mask"].append(
                np.ones((iters, batch), np.float32) * (n > 0))
        yield {key: np.stack(v) for key, v in out.items()}


def heat_counts(client_data: dict, sample_counts: np.ndarray,
                feature_keys, num_features: int) -> np.ndarray:
    """Number of clients whose training samples hold each feature id."""
    counts = np.zeros(num_features, np.float64)
    for c, n in enumerate(sample_counts):
        ids = np.concatenate([client_data[k][c][:n].reshape(-1)
                              for k in feature_keys])
        counts[np.unique(ids[ids >= 0])] += 1
    return counts


@jax.jit
def leaf_norms(a, b):
    """Per-leaf L2 norm of ``a - b``, in float32."""
    return [jnp.sqrt(jnp.sum(jnp.square((x - y).astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def make_round(loss, lr: float, server_lr: float, tables, factor,
               k: int, fault: str | None = None, shards: int = 1):
    """The jitted dense round ``(params, cohort) -> (params, loss)``.

    ``tables`` names the top-level leaves keyed by feature id; ``factor``
    is the ``(V,)`` vector ``N / n_m``.
    """
    def local(params, batches):
        def step(p, b):
            g = jax.grad(loss)(p, b)
            return jax.tree.map(lambda x, gx: x - lr * gx, p, g), None

        p, _ = lax.scan(step, params, batches)
        return jax.tree.map(jnp.subtract, p, params)

    def one_round(params, cohort):
        first = jax.tree.map(lambda x: x[:, 0], cohort)
        losses = jax.vmap(lambda b: loss(params, b))(first)
        used, denom = k, k
        if fault == "half_batch":
            used = denom = k // 2
            losses = losses[:used]
        elif fault == "no_exchange":
            used = k // shards
        cohort = jax.tree.map(lambda x: x[:used], cohort)

        def acc(total, client):
            d = local(params, client)
            return jax.tree.map(jnp.add, total, d), None

        total, _ = lax.scan(acc, jax.tree.map(jnp.zeros_like, params),
                            cohort)
        mean = jax.tree.map(lambda t: t / denom, total)
        mean = {key: (v * factor[:, None] if key in tables else v)
                for key, v in mean.items()}
        new = jax.tree.map(lambda p, u: p + server_lr * u, params, mean)
        return new, losses.mean()

    return jax.jit(one_round)


def alter_token(cohort: dict, key: str, num_features: int) -> dict:
    """The first token of client 0's first sample, moved to the next id."""
    arr = cohort[key].copy()
    idx = (0,) * arr.ndim
    arr[idx] = (arr[idx] + 1) % num_features
    return {**cohort, key: arr}
