"""Plain reference for the ``bert-base-dag`` configuration.

The BERT encoder (Devlin et al. 2018) in straightforward float32
``jax.numpy``: token + position embeddings, LayerNorm, then post-LayerNorm
blocks (dense multi-head attention, erf-GELU feed-forward), the first
token's state into a linear classifier, softmax. One sequence batch at a
time, no bucketing, no bf16. It takes the served models' weights (random,
drawn from the seed) and the graph's semantics and nothing else: the input
transformer subtracts its ``means`` (0.0 here), the router names the branch
that answered, and that branch's model must give these probabilities.

Departures from the published model, because the served model
(``seldon_core_tpu/models/bert.py``) makes them: no token-type embeddings,
no tanh pooler before the classifier, LayerNorm epsilon 1e-6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _ln(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(p, x):
    return x @ p["w"] + p["b"]


def _forward(params, ids, n_head: int):
    b, s = ids.shape
    x = _ln(params["ln_emb"], params["tok_emb"][ids] + params["pos_emb"][:s][None])
    d = x.shape[-1]
    for p in params["layers"]:
        q, k, v = jnp.split(_dense(p["qkv"], x), 3, axis=-1)
        q, k, v = (t.reshape(b, s, n_head, d // n_head) for t in (q, k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d // n_head))
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
        x = _ln(p["ln1"], x + _dense(p["attn_out"], ctx.reshape(b, s, d)))
        x = _ln(p["ln2"], x + _dense(p["mlp_out"], jax.nn.gelu(_dense(p["mlp_in"], x), approximate=False)))
    return jax.nn.softmax(_dense(params["head"], x[:, 0, :]), axis=-1)


_jitted = jax.jit(_forward, static_argnames=("n_head",))


def probabilities(params, ids, *, n_head: int, means: float = 0.0):
    """ids [b, s] -> float32 class probabilities [b, classes]."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(ids, jnp.float32) - jnp.float32(means)  # the input transformer
        return _jitted(params, x.astype(jnp.int32), n_head)
