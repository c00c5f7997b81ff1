"""Plain reference for the ``gpt2-large`` configuration.

The GPT-2 forward pass (Radford et al. 2019) in straightforward float32
``jax.numpy``: learned token and position embeddings, pre-LayerNorm blocks
(multi-head causal attention, then a 4x feed-forward), a final LayerNorm
and the weight-tied output head. No cache, no batching tricks, no kernels:
every position attends over the whole prefix in one dense softmax. It takes
the served model's weights (random, drawn from the seed) and nothing else
from the program.

Departures from the published model, because the served decoder
(``seldon_core_tpu/models/decoder.py``) makes them and the comparison is of
the same mathematics: the exact erf GELU where GPT-2 uses the tanh
approximation (``gelu_new``).

On a TPU a float32 matmul runs as bf16 passes unless the precision is
raised, so the caller chooses: ``"highest"`` is the reference proper,
``"default"`` is the same forward at the chip's own rounding, and the
difference of the two is the rounding noise the tolerance is set from.
One block is jitted and called per layer: one small compile for 36 layers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _ln(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(p, x):
    return x @ p["w"] + p["b"]


@functools.partial(jax.jit, static_argnames=("n_head",))
def _block(p, x, *, n_head: int):
    b, s, d = x.shape
    q, k, v = jnp.split(_dense(p["qkv"], _ln(p["ln1"], x)), 3, axis=-1)
    q, k, v = (t.reshape(b, s, n_head, d // n_head) for t in (q, k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d // n_head))
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, d)
    x = x + _dense(p["attn_out"], ctx)
    hidden = jax.nn.gelu(_dense(p["mlp_in"], _ln(p["ln2"], x)), approximate=False)
    return x + _dense(p["mlp_out"], hidden)


@jax.jit
def _embed(tok_emb, pos_emb, ids):
    return tok_emb[ids] + pos_emb[: ids.shape[1]][None]


@jax.jit
def _head(ln_f, tok_emb, x):
    return _ln(ln_f, x) @ tok_emb.T


def logits(params, ids, first: int, *, n_head: int, precision: str):
    """ids [b, s] -> float32 logits [b, s - first, vocab]: row j is the
    distribution of the token AFTER position ``first + j``."""
    # the precision is part of jit's trace context: each value gets its own trace
    with jax.default_matmul_precision(precision):
        x = _embed(params["tok_emb"], params["pos_emb"], jnp.asarray(ids, jnp.int32))
        for p in params["layers"]:
            x = _block(p, x, n_head=n_head)
        return _head(params["ln_f"], params["tok_emb"], x[:, first:, :])
