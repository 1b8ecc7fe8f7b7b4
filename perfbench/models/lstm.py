"""Plain float32 reference of the Sent140 two-layer LSTM classifier.

Embedding lookup (id -1 is padding and contributes zero), a stack of
standard LSTM layers scanned over the sequence (masked steps carry the
state; forget-gate bias +1), a linear head on the last hidden state, and
the mean binary cross-entropy over the samples whose ``sample_mask`` is 1.
Written from that description in ``jax.numpy``; it imports nothing of the
program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def init_params(key, cfg: dict, vocab: int):
    """Seeded weights in the program's tree layout: normal(0.02) embedding,
    1/sqrt(fan_in) matrices, zero biases."""
    emb_dim, hidden, layers = cfg["emb_dim"], cfg["hidden"], cfg["layers"]
    keys = iter(jax.random.split(key, 2 * layers + 2))

    def fan_in(shape):
        return jax.random.normal(next(keys), shape) / jnp.sqrt(shape[0])

    cells = []
    for i in range(layers):
        d_in = emb_dim if i == 0 else hidden
        cells.append({"wx": fan_in((d_in, 4 * hidden)),
                      "wh": fan_in((hidden, 4 * hidden)),
                      "b": jnp.zeros((4 * hidden,))})
    return {"embedding": 0.02 * jax.random.normal(next(keys),
                                                  (vocab, emb_dim)),
            "cells": tuple(cells),
            "head_w": fan_in((hidden, 1)),
            "head_b": jnp.zeros((1,))}


def _layer(cell, xs, mask, mm):
    b = xs.shape[0]
    hdim = cell["wh"].shape[0]

    def step(carry, inp):
        h, c = carry
        x_t, m_t = inp
        z = mm(x_t, cell["wx"]) + mm(h, cell["wh"]) + cell["b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c_new = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        keep = m_t[:, None]
        return ((h_new * keep + h * (1 - keep), c_new * keep + c * (1 - keep)),
                h_new)

    init = (jnp.zeros((b, hdim)), jnp.zeros((b, hdim)))
    (h, _), hs = lax.scan(step, init, (xs.transpose(1, 0, 2), mask.T))
    return h, hs.transpose(1, 0, 2)


def loss(params, batch, mm=jnp.matmul):
    """``mm`` is the matmul every layer uses (the control passes a lower
    precision one)."""
    tokens = batch["tokens"]
    mask = (tokens >= 0).astype(jnp.float32)
    x = params["embedding"][jnp.maximum(tokens, 0)] * mask[..., None]
    for cell in params["cells"]:
        h, x = _layer(cell, x, mask, mm)
    logit = mm(h, params["head_w"])[:, 0] + params["head_b"][0]
    y = batch["label"].astype(jnp.float32)
    per = (jnp.maximum(logit, 0) - logit * y
           + jnp.log1p(jnp.exp(-jnp.abs(logit))))
    m = batch["sample_mask"]
    return (per * m).sum() / jnp.maximum(m.sum(), 1.0)


def flops_per_sample(cfg: dict) -> float:
    """Forward and backward matmul FLOPs of one sample (3x the forward).

    Every one of the ``seq_len`` scanned positions runs each layer's input
    and recurrent matmuls, padded positions included; the head runs once.
    """
    emb_dim, hidden, layers = cfg["emb_dim"], cfg["hidden"], cfg["layers"]
    seq_len = cfg["seq_len"]
    fwd = 0
    for i in range(layers):
        d_in = emb_dim if i == 0 else hidden
        fwd += seq_len * 2 * (d_in + hidden) * 4 * hidden
    fwd += 2 * hidden
    return 3.0 * fwd
