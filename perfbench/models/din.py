"""Plain float32 reference of the Deep Interest Network CTR model.

Item embeddings of the behaviour history (id -1 is padding) and of the
target; an attention unit over ``[h, t, h*t, h-t]`` (one ReLU layer, then a
scalar score, padded positions masked out of the softmax); the
attention-pooled history, the target and their product through a ReLU MLP
to one logit; mean binary cross-entropy over the samples whose
``sample_mask`` is 1 (Zhou et al., arXiv:1706.06978, as the program
reproduces it). Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_params(key, cfg: dict, vocab: int):
    """Seeded weights in the program's tree layout: normal(0.02) item
    embedding, 1/sqrt(fan_in) matrices, zero biases."""
    emb_dim, hidden = cfg["emb_dim"], cfg["hidden"]
    keys = iter(jax.random.split(key, 5))

    def fan_in(shape):
        return jax.random.normal(next(keys), shape) / jnp.sqrt(shape[0])

    return {"item_emb": 0.02 * jax.random.normal(next(keys),
                                                 (vocab, emb_dim)),
            "att_w1": fan_in((4 * emb_dim, hidden)),
            "att_b1": jnp.zeros((hidden,)),
            "att_w2": fan_in((hidden, 1)),
            "mlp_w1": fan_in((3 * emb_dim, hidden)),
            "mlp_b1": jnp.zeros((hidden,)),
            "mlp_w2": fan_in((hidden, 1)),
            "mlp_b2": jnp.zeros((1,))}


def loss(params, batch, mm=jnp.matmul):
    """``mm`` is the matmul every layer uses (the control passes a lower
    precision one)."""
    hist, target = batch["hist"], batch["target"]
    emb = params["item_emb"]
    hmask = (hist >= 0).astype(jnp.float32)
    he = emb[jnp.maximum(hist, 0)] * hmask[..., None]
    te = emb[target]
    tb = jnp.broadcast_to(te[:, None], he.shape)
    att_in = jnp.concatenate([he, tb, he * tb, he - tb], axis=-1)
    a = mm(jax.nn.relu(mm(att_in, params["att_w1"]) + params["att_b1"]),
           params["att_w2"])[..., 0]
    a = a + (hmask - 1.0) * 1e9
    w = jax.nn.softmax(a, axis=-1) * (hmask.sum(-1, keepdims=True) > 0)
    pooled = jnp.einsum("bh,bhe->be", w, he)
    feat = jnp.concatenate([pooled, te, pooled * te], axis=-1)
    h = jax.nn.relu(mm(feat, params["mlp_w1"]) + params["mlp_b1"])
    logit = mm(h, params["mlp_w2"])[:, 0] + params["mlp_b2"][0]
    y = batch["label"].astype(jnp.float32)
    per = (jnp.maximum(logit, 0) - logit * y
           + jnp.log1p(jnp.exp(-jnp.abs(logit))))
    m = batch["sample_mask"]
    return (per * m).sum() / jnp.maximum(m.sum(), 1.0)


def flops_per_sample(cfg: dict) -> float:
    """Forward and backward matmul FLOPs of one sample (3x the forward):
    the attention unit at every history position, the pooling, the MLP."""
    emb_dim, hidden, hist_len = cfg["emb_dim"], cfg["hidden"], cfg["hist_len"]
    fwd = (hist_len * 2 * (4 * emb_dim * hidden + hidden)
           + 2 * hist_len * emb_dim
           + 2 * (3 * emb_dim * hidden + hidden))
    return 3.0 * fwd
