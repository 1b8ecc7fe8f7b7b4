"""The benchmark's own federated data generators, drawn from ``--seed``.

Copies of the program's Sent140-like and Amazon-like generators that make
the same draws from the same seed, with the per-client O(V) work taken
out:

- ``sent140``: a client's token distribution is the shared Zipf law with
  the client's (at most 20) topic tokens boosted. The copy searches the
  shared Zipf cdf with a per-segment offset for the boosted mass instead of
  building a length-V cdf per client.
- ``amazon``: each user's cdf is built once and searched for every history
  (``Generator.choice(p=...)`` rebuilds it on every call).

Both return plain numpy arrays: ``client_data`` leaves ``(N, max_samples,
...)``, per-client ``sample_counts``, the exact per-feature ``heat`` (the
number of clients whose training data hold the feature), the id space
size and which leaf carries feature ids.
"""
from __future__ import annotations

import numpy as np


def zipf_probs(n: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** a
    return p / p.sum()


def _cdf(p: np.ndarray) -> np.ndarray:
    """The cdf ``Generator.choice(p=p)`` searches."""
    cdf = p.cumsum()
    cdf /= cdf[-1]
    return cdf


def _pad_stack(rows, max_len, fill=0):
    out = np.full((len(rows), max_len) + rows[0].shape[1:], fill,
                  dtype=rows[0].dtype)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r[:max_len]
    return out


def heat_counts(per_client_ids, num_features: int) -> np.ndarray:
    """Clients involving each feature: ``n_m`` of FedSubAvg's ``N / n_m``."""
    counts = np.zeros(num_features, np.float64)
    for ids in per_client_ids:
        counts[np.unique(ids[ids >= 0])] += 1
    return counts


class _BoostedZipf:
    """Sampler of ``p ∝ pop * w`` with ``w = boost`` on a few tokens, 1 else.

    ``pop_cum`` is ``pop.cumsum()``. A uniform ``u`` maps to the first id
    whose cumulative mass exceeds ``u`` times the total, as a search of the
    length-V cdf would, found by locating the boosted segment first.
    """

    def __init__(self, pop: np.ndarray, pop_cum: np.ndarray,
                 topic: np.ndarray, boost: float):
        self.pop_cum = pop_cum
        self.starts = np.unique(topic)
        self.extra = np.cumsum(pop[self.starts] * (boost - 1.0))
        self.seg_cum = pop_cum[self.starts] + self.extra
        self.total = pop_cum[-1] + self.extra[-1]
        self.v = len(pop_cum)

    def __call__(self, u: np.ndarray) -> np.ndarray:
        target = u * self.total
        j = self.seg_cum.searchsorted(target, side="right")
        off = np.where(j > 0, self.extra[np.maximum(j - 1, 0)], 0.0)
        upper = np.where(j < len(self.starts),
                         self.starts[np.minimum(j, len(self.starts) - 1)],
                         self.v - 1)
        i = self.pop_cum.searchsorted(target - off, side="right")
        return np.minimum(i, upper).astype(np.int32)


def sent140(num_clients: int, vocab: int, seq_len: int, mean_samples: int,
            zipf_a: float, seed: int, test_frac: float = 0.2) -> dict:
    """Zipf token streams for the Sent140 LSTM; one topic slice per client."""
    rng = np.random.default_rng(seed)
    pop = zipf_probs(vocab, zipf_a)
    pop_cdf = _cdf(pop)
    pop_cum = pop.cumsum()
    sentiment = rng.normal(0, 1.0, vocab)           # planted word polarity
    boost = float(np.exp(3.0 * 0.2))

    toks, labels, counts, t_toks, t_labels = [], [], [], [], []
    for _ in range(num_clients):
        n = max(5, int(rng.poisson(mean_samples)))
        topic = pop_cdf.searchsorted(rng.random(20), side="right")
        draw = _BoostedZipf(pop, pop_cum, topic, boost)
        lens = rng.integers(6, seq_len + 1, n)
        seqs = np.full((n, seq_len), -1, np.int32)
        lab = np.zeros(n, np.int32)
        for j in range(n):
            s = draw(rng.random(lens[j]))
            seqs[j, : lens[j]] = s
            lab[j] = int(sentiment[s].mean() + rng.normal(0, 0.3) > 0)
        n_test = max(1, int(n * test_frac))
        t_toks.append(seqs[:n_test])
        t_labels.append(lab[:n_test])
        toks.append(seqs[n_test:])
        labels.append(lab[n_test:])
        counts.append(n - n_test)

    max_len = max(counts)
    return {
        "client_data": {"tokens": _pad_stack(toks, max_len, fill=-1),
                        "label": _pad_stack(labels, max_len, fill=0)},
        "sample_counts": np.array(counts),
        "heat": heat_counts([t.reshape(-1) for t in toks], vocab),
        "test_data": {"tokens": np.concatenate(t_toks),
                      "label": np.concatenate(t_labels)},
        "num_features": vocab,
        "feature_key": "tokens",
    }


def amazon(num_clients: int, num_items: int, hist_len: int,
           mean_samples: int, zipf_a: float, emb_rank: int, seed: int,
           test_frac: float = 0.2) -> dict:
    """Behaviour histories and targets for the DIN CTR model."""
    rng = np.random.default_rng(seed)
    pop = zipf_probs(num_items, zipf_a)
    item_vec = rng.normal(0, 1.0 / np.sqrt(emb_rank), (num_items, emb_rank))

    hists, targets, labels, counts = [], [], [], []
    t_h, t_t, t_l = [], [], []
    for _ in range(num_clients):
        u = rng.normal(0, 1.0, emb_rank)
        n = max(5, int(rng.poisson(mean_samples)))
        aff = item_vec @ u                            # the user's interests
        p = pop * np.exp(aff - aff.max())
        p = p / p.sum()
        p_cdf = _cdf(p)
        hist = np.full((n, hist_len), -1, np.int32)
        tgt = _cdf(0.5 * pop + 0.5 * p).searchsorted(rng.random(n),
                                                      side="right")
        lab = np.zeros(n, np.int32)
        for j in range(n):
            hl = rng.integers(3, hist_len + 1)
            h = p_cdf.searchsorted(rng.random(hl), side="right")
            hist[j, :hl] = h
            match = item_vec[h] @ item_vec[tgt[j]]
            lab[j] = int(u @ item_vec[tgt[j]] + match.mean()
                         + rng.normal(0, 0.4) > 0)
        n_test = max(1, int(n * test_frac))
        t_h.append(hist[:n_test])
        t_t.append(tgt[:n_test])
        t_l.append(lab[:n_test])
        hists.append(hist[n_test:])
        targets.append(tgt[n_test:].astype(np.int32))
        labels.append(lab[n_test:])
        counts.append(n - n_test)

    max_len = max(counts)
    ids = [np.concatenate([h.reshape(-1), t]) for h, t in zip(hists, targets)]
    return {
        "client_data": {"hist": _pad_stack(hists, max_len, fill=-1),
                        "target": _pad_stack(targets, max_len, fill=0),
                        "label": _pad_stack(labels, max_len, fill=0)},
        "sample_counts": np.array(counts),
        "heat": heat_counts(ids, num_items),
        "test_data": {"hist": np.concatenate(t_h),
                      "target": np.concatenate(t_t),
                      "label": np.concatenate(t_l)},
        "num_features": num_items,
        "feature_key": "hist",
    }


GENERATORS = {"sent140": sent140, "amazon": amazon}
