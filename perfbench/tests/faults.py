"""Faults planted in the trainer's timed path, for the tests that see each
one turn ``correct`` false. Each takes a ``monkeypatch``."""
from __future__ import annotations

import numpy as np


def _wrap_step(monkeypatch, wrap):
    from repro.federated import server
    real = server.build_round_step

    def build(*a, **k):
        return wrap(real(*a, **k))

    monkeypatch.setattr(server, "build_round_step", build)


def unchanged_state(monkeypatch):
    """Every round returns the state it was given."""
    def wrap(step):
        def broken(state, batch, sub_ids=None):
            _, metrics = step(state, batch, sub_ids)
            return state, metrics
        return broken
    _wrap_step(monkeypatch, wrap)


def half_batch(monkeypatch):
    """Every round sees only the first half of its clients; the mean is
    taken over them."""
    def wrap(step):
        def broken(state, batch, sub_ids=None):
            h = sub_ids.shape[0] // 2
            return step(state, {k: v[:h] for k, v in batch.items()},
                        sub_ids[:h])
        return broken
    _wrap_step(monkeypatch, wrap)


def token_altered(monkeypatch):
    """The first token of the first client of every cohort moves to the
    next id where the cohort is produced."""
    from repro.federated import server
    real = server.sample_cohort_batch

    def altered(ds, ids, iters, batch, rng):
        out = real(ds, ids, iters, batch, rng)
        arr = np.array(out[ds.feature_key])
        idx = (0,) * arr.ndim
        arr[idx] = (arr[idx] + 1) % ds.num_features
        out[ds.feature_key] = arr
        return out

    monkeypatch.setattr(server, "sample_cohort_batch", altered)


def no_exchange(monkeypatch):
    """Each shard applies its own partial union: the cross-shard combine
    is left out."""
    from repro.federated import plan
    from repro.sparse.aggregate import correct_rowsparse

    def local(partial, axis_name, num_shards, heat, total, scale=1.0, **_):
        return correct_rowsparse(partial, heat, total, scale)

    monkeypatch.setattr(plan, "combine_rowsparse_partials", local)


ONE_CHIP = {"unchanged_state": unchanged_state, "half_batch": half_batch,
            "token_altered": token_altered}
