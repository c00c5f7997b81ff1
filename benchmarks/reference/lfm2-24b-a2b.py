"""Plain reference for the ``lfm2-24b-a2b`` configuration.

The forward pass of an LFM2-MoE block (``model_type: lfm2_moe``), as ISSUE 41
wrote it down from the published ``config.json`` (configs/lfm2-24b-a2b.json,
whose ``assumed`` repeats the equations), in straightforward ``jax.numpy``:

    n(x) = x . rsqrt(mean(x^2) + eps) . w   in float32, cast back; no bias anywhere
    x0 = E[tok]
    h  = x + Mix_i(n_op(x))
         conv layer:  [B | C | X] = W_in u;  z_t = B_t * X_t
                      c_t = sum_{j<3} w[j] * z_{t-2+j}   (z before the sequence 0; no bias, no activation)
                      Mix = W_out (C_t * c_t)
         attention:   q = W_q u [32 x 64], k = W_k u, v = W_v u [8 x 64]
                      q_h <- n_q(q_h), k_h <- n_k(k_h)  over each head's 64 numbers, THEN
                      rotary on all 64 dims (theta^(-2i/64), rotate-half); query head j reads
                      K/V head j // 4; scores / 8; causal; Mix = W_o concat_h
    y  = h + FF_i(n_ffn(h))
         i < num_dense_layers:  W_2(silu(W_1 m) * W_3 m)
         later:  s = sigmoid(W_r m) in float32; pick = top4(s + b);
                 g_e = s_e / (sum_{pick} s + 1e-6) * routed_scaling_factor
                 FF = sum_{e in pick} g_e . W2_e(silu(W1_e m) * W3_e m)
    logits = E . n_out(x_L)            the tied head

No cache, no batching, no chunking: the convolution is three shifted
products over the whole sequence, every position attends over the whole
prefix under a mask, and every expert HELD computes every token, a dense
[tokens, held] gate (zero off a token's picks) selecting. It is given the same
share as the served model: the experts ``[first_expert, first_expert + held)``
(``held`` is the stored experts' count, ``first_expert`` the configuration's
``share``), a pick that lands on an absent expert adding nothing, the gates
normalised over all 4 picks. With all experts held (``first_expert`` 0) it is
the uncut layer. It takes the served model's weights and nothing else from the
program; sizes that weight shapes do not give come from the configuration's
file, or from ``config`` (the CPU tests' small size).

``precision="highest"`` is the reference proper: float32 activations, float32
matmuls. ``precision="default"`` is the same forward at the precision the
configuration states: bfloat16 activations (every matmul's result and every
residual rounded to bfloat16; norms, the convolution's products, softmax and
the router in float32) at the chip's default matmul. ``harness/correct.py``
takes its rounding delta from their difference.

One sequence at a time, layer by layer, attention in blocks of query rows, one
expert at a time: 2072 positions fit beside the served model on the chip.
"""

from __future__ import annotations

import functools
import json
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256
GATE_EPS = 1e-6


@functools.lru_cache(maxsize=1)
def published() -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def inv_freq(cfg: dict) -> np.ndarray:
    """[head_dim / 2] float32 plain rotary frequencies theta^(-2i/d)."""
    d = int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    return (theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)).astype(np.float32)


def _rms(w, x, eps, act):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(act) * w.astype(act)


def _mm(a, b, act):
    return jnp.matmul(a.astype(act), b.astype(act), preferred_element_type=jnp.float32).astype(act)


def _rope(x, freq):
    """x [s, h, d] at positions 0..s-1: rotate-half pairing."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(freq)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _normed_rotated(w, x, freq, eps, act):
    """A head's RMS norm (one ``w`` for all heads of x [s, h, d]), THEN the rotation."""
    return _rope(_rms(w, x, eps, act), freq)


def _keys(w, x, freq, eps, act):
    """The keys every query attends over (a cache would hold these): normed, then rotated."""
    return _normed_rotated(w, x, freq, eps, act)


def _short_conv(z, w):
    """z [s, d] float32, w [taps, d]: c_t = sum_j w[j] * z_{t - (taps - 1) + j}, z before the sequence zero."""
    taps, s = w.shape[0], z.shape[0]
    zp = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    return sum(w[j] * zp[j : j + s] for j in range(taps))


def _gated_conv(b, c, x, w):
    """The whole operator between its projections, float32: C gates what the filter made of B * X."""
    return c * _short_conv(b * x, w)


def _conv_mixer(p, x, *, eps, act):
    act = jnp.dtype(act)
    f32 = jnp.float32
    b, c, xs = jnp.split(_mm(_rms(p["ln1"], x, eps, act), p["conv_in"], act), 3, axis=-1)
    y = _gated_conv(b.astype(f32), c.astype(f32), xs.astype(f32), p["conv_w"].astype(f32)).astype(act)
    return x + _mm(y, p["conv_out"], act)


def _attention(p, x, freq, *, n_head, eps, act):
    """x [s, d] + Wo . Attn(...): one sequence. Query rows go in blocks of
    QUERY_BLOCK against all keys under the mask."""
    act = jnp.dtype(act)
    s = x.shape[0]
    d = p["q_norm"].shape[0]
    g = (p["attn_qkv"].shape[1] - n_head * d) // (2 * d)
    qkv = _mm(_rms(p["ln1"], x, eps, act), p["attn_qkv"], act)
    q, k, v = jnp.split(qkv, [n_head * d, (n_head + g) * d], axis=-1)
    q = _normed_rotated(p["q_norm"], q.reshape(s, n_head, d), freq, eps, act)
    k = _keys(p["k_norm"], k.reshape(s, g, d), freq, eps, act)
    k = jnp.repeat(k, n_head // g, axis=1)  # query head j reads K/V head j // (heads / groups)
    v = jnp.repeat(v.reshape(s, g, d), n_head // g, axis=1)
    blk = min(QUERY_BLOCK, s)
    nb = -(-s // blk)
    qp = jnp.pad(q, ((0, nb * blk - s), (0, 0), (0, 0))).reshape(nb, blk, n_head, d)
    k_pos = jnp.arange(s)

    def block(args):
        qb, start = args
        seen = k_pos[None, :] <= (start + jnp.arange(blk))[:, None]
        sc = jnp.einsum("qhd,khd->hqk", qb.astype(jnp.float32), k.astype(jnp.float32)) * d**-0.5
        pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        # a padded query row past the sequence sees every key: finite, unused
        return jnp.einsum("hqk,khd->qhd", pr, v.astype(jnp.float32)).astype(act)

    ctx = lax.map(block, (qp, jnp.arange(nb) * blk)).reshape(nb * blk, -1)[:s]
    return x + _mm(ctx, p["attn_o"], act)


def _pick_weights(s, b):
    """What a pick weighs: its UNBIASED score (the bias chooses and does not weigh)."""
    return s


def router(w, b, n2, *, top_k: int, scale: float):
    """n2 [T, d] -> the dense gate [T, E] float32 over ALL experts: a token's
    picks, the top k of score + bias, carry ``scale * s_e / (sum of the
    picks' s + 1e-6)``, every other expert 0."""
    s = jax.nn.sigmoid(n2.astype(jnp.float32) @ w.astype(jnp.float32))
    _, top_e = lax.top_k(s + b.astype(jnp.float32), top_k)
    picked = jnp.sum(jax.nn.one_hot(top_e, s.shape[1], dtype=jnp.float32), axis=1)  # [T, E] 0/1
    weigh = picked * _pick_weights(s, b.astype(jnp.float32))
    return scale * weigh / (jnp.sum(weigh, axis=-1, keepdims=True) + GATE_EPS)


def _gated(h, f: int):
    return jax.nn.silu(h[:, :f]) * h[:, f:]


def _dense(p, x, *, eps, act):
    act = jnp.dtype(act)
    m = p["mlp"]
    h = _mm(_rms(p["ln2"], x, eps, act), m["gate_up"], act)
    return x + _mm(_gated(h, m["down"].shape[0]), m["down"], act)


def expert_ffn(m, n2, *, first_expert, top_k, scale, act):
    """The expert layer's FFN over n2 [T, d] for the share that ``m`` holds:
    the routed experts ``[first_expert, first_expert + held)``, every one of
    them over every token, one at a time, selected by the dense gate's
    columns. With every expert held and ``first_expert`` 0: the uncut layer."""
    act = jnp.dtype(act)
    gate = router(m["router"], m["router_bias"], n2, top_k=top_k, scale=scale)
    held, f = m["gate_up"].shape[0], m["down"].shape[1]

    def one(acc, e):
        y = jnp.matmul(
            _gated(_mm(n2, m["gate_up"][e], act), f).astype(act), m["down"][e].astype(act),
            preferred_element_type=jnp.float32,
        )
        return acc + y * lax.dynamic_slice_in_dim(gate, first_expert + e, 1, axis=1), None

    y, _ = lax.scan(one, jnp.zeros(n2.shape, jnp.float32), jnp.arange(held))
    return y.astype(act)


def _experts(p, x, *, first_expert, top_k, scale, eps, act):
    n2 = _rms(p["ln2"], x, eps, jnp.dtype(act))
    return x + expert_ffn(p["moe"], n2, first_expert=first_expert, top_k=top_k, scale=scale, act=act)


def _head(ln_f, tok_emb, x, *, eps, act):
    act = jnp.dtype(act)
    return jnp.matmul(_rms(ln_f, x, eps, act), tok_emb.astype(act).T, preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted():
    """The layer functions under jit, made at the first ``logits`` call of
    this module object (a test that swaps one of the helpers above loads the
    module anew, and traces what it swapped)."""
    return {
        "conv": jax.jit(_conv_mixer, static_argnames=("eps", "act")),
        "attn": jax.jit(_attention, static_argnames=("n_head", "eps", "act")),
        "dense": jax.jit(_dense, static_argnames=("eps", "act")),
        "experts": jax.jit(_experts, static_argnames=("first_expert", "top_k", "scale", "eps", "act")),
        "head": jax.jit(_head, static_argnames=("eps", "act")),
    }


def logits(params, ids, first: int, *, n_head: int, precision: str, config: dict | None = None):
    """ids [b, s] -> float32 logits [b, s - first, vocab]: row j is the
    distribution of the token AFTER position ``first + j``. ``config``: a
    dict with the published keys and the ``share`` (default: the
    configuration's file)."""
    cfg = config or published()
    act = "float32" if precision == "highest" else "bfloat16"
    eps = float(cfg["norm_eps"])
    freq = inv_freq(cfg)
    moe = dict(first_expert=int(cfg["share"]["first_expert"]), top_k=int(cfg["num_experts_per_tok"]),
               scale=float(cfg["routed_scaling_factor"]), eps=eps, act=act)
    fn = _jitted()
    out = []
    # the precision is part of jit's trace context: each value gets its own trace
    with jax.default_matmul_precision(precision):
        for row in np.asarray(ids):
            x = jnp.asarray(params["tok_emb"])[jnp.asarray(row, jnp.int32)].astype(act)
            for i, (p, kind) in enumerate(zip(params["layers"], cfg["layer_types"])):
                if kind == "conv":
                    x = fn["conv"](p, x, eps=eps, act=act)
                else:
                    x = fn["attn"](p, x, freq, n_head=n_head, eps=eps, act=act)
                x = fn["dense"](p, x, eps=eps, act=act) if i < int(cfg["num_dense_layers"]) else fn["experts"](p, x, **moe)
            out.append(fn["head"](params["ln_f"], params["tok_emb"], x[first:], eps=eps, act=act))
        return jnp.stack(out)
