"""Plain reference for the ``laguna-s-2.1`` configuration.

The forward pass of a Laguna-S-2.1 block, as ISSUE 47 wrote it down from the
published ``config.json`` (configs/laguna-s-2.1.json, whose ``assumed``
repeats the equations and marks each reading the config does not spell out),
in straightforward ``jax.numpy``, one sequence at a time:

    h  = x + Wo_i . (g (.) Attn_i(n1))                          n1 = RMSNorm(x)
         H_i query heads (num_attention_heads_per_layer: 48 on a full layer,
         72 on a sliding one), 8 key/value heads of 128, query head j reads
         key/value head j // (H_i / 8); scores / sqrt(128); causal; a sliding
         layer also masks keys with q_pos - k_pos >= 512
         g = sigmoid(Wg_i . n1): one scalar a head and token (gating per-head)
         rotary: a sliding layer all 128 dimensions, theta 10000, plain; a
         full layer the first 64 (partial_rotary_factor 0.5), YaRN over them
    y  = h + FFN_i(n2)                                          n2 = RMSNorm(h)
         layer 0 (mlp_only_layers): SwiGLU of width 12288
         others: Shared(n2) + 2.5 . sum_{e in top10(p)} (p_e / sum_top10 p) . Expert_e(n2),
         p = softmax_256(Wr . n2) in float32; an expert this chip does not
         hold (``share``) adds nothing
    logits = Whead . RMSNorm(x_L), the vocabulary rows held

No cache, no kernels, no grouping, no page tables: every position attends
over the whole prefix under a mask, and every held expert computes every
token, a dense [tokens, experts] gate (zero off a token's top 10) selecting.
It takes the served model's weights (random, drawn from the seed;
``attn_qkv`` is the q, k and v projections side by side, ``gate_up`` the gate
and up ones) and nothing else from the program; the sizes that weight shapes
do not give come from the configuration's file, or from ``config`` (the CPU
tests' small size).

``precision="highest"`` is the reference proper: float32 activations, float32
matmuls. ``precision="default"`` is the same forward at the precision the
configuration states: bfloat16 activations (every matmul's result and every
residual rounded to bfloat16; norms, softmax, the gate's sigmoid and the
router in float32) at the chip's default matmul. ``harness/correct.py`` takes
its rounding delta from their difference.

Query rows go in blocks of QUERY_BLOCK against all keys, by key/value group
(no head is repeated), one expert at a time: a block of scores over 7,424
keys at 72 heads is 0.55 GB beside the served model.
"""

from __future__ import annotations

import functools
import json
import math
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256


@functools.lru_cache(maxsize=1)
def published() -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "laguna-s-2.1.json")) as f:
        return json.load(f)


def inv_freq(rope: dict, head_dim: int) -> tuple[np.ndarray, float]:
    """(frequencies [rot / 2] float32 over the rot = partial_rotary_factor x
    head_dim dimensions that rotate, the factor on cos and sin) of one
    ``rope_parameters`` entry: ``default`` or ``yarn``."""
    rot = int(head_dim * float(rope.get("partial_rotary_factor", 1)))
    i = np.arange(rot // 2, dtype=np.float64)
    theta = float(rope["rope_theta"])
    plain = theta ** (-2.0 * i / rot)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    orig, factor = float(rope["original_max_position_embeddings"]), float(rope["factor"])

    def c(n: float) -> float:
        return rot * math.log(orig / (2.0 * math.pi * n)) / (2.0 * math.log(theta))

    lo = min(max(math.floor(c(float(rope["beta_fast"]))), 0), rot - 1)
    hi = min(max(math.ceil(c(float(rope["beta_slow"]))), 0), rot - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    freq = (1.0 - ramp) * plain + ramp * plain / factor
    att = float(rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0)
    return freq.astype(np.float32), att


def _rms(w, x, eps, act):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(act) * w.astype(act)


def _mm(a, b, act):
    return jnp.matmul(a.astype(act), b.astype(act), preferred_element_type=jnp.float32).astype(act)


def _rope(x, freq, att):
    """x [s, h, d] at positions 0..s-1: rotate-half pairing over the first
    2 * len(freq) dimensions, the others pass through."""
    rot = 2 * len(freq)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(freq)[None, :]
    cos, sin = (jnp.cos(ang) * att)[:, None, :], (jnp.sin(ang) * att)[:, None, :]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "head_dim", "window", "gated", "eps", "act"))
def _attention(p, x, freq, att, *, n_head, n_kv, head_dim, window, gated, eps, act):
    """x [s, d] + Wo . (g (.) Attn(...)): one sequence. ``window`` 0 = a full
    layer. Query rows go in blocks of QUERY_BLOCK against all keys."""
    act = jnp.dtype(act)
    s = x.shape[0]
    qw, kw, group = n_head * head_dim, n_kv * head_dim, n_head // n_kv
    n1 = _rms(p["ln1"], x, eps, act)
    qkv = _mm(n1, p["attn_qkv"], act)
    q = _rope(qkv[:, :qw].reshape(s, n_head, head_dim), freq, att)
    k = _rope(qkv[:, qw : qw + kw].reshape(s, n_kv, head_dim), freq, att).astype(jnp.float32)
    v = qkv[:, qw + kw :].reshape(s, n_kv, head_dim).astype(jnp.float32)
    blk = min(QUERY_BLOCK, s)
    nb = -(-s // blk)
    # query head j = group * g + r reads key/value head g
    qp = jnp.pad(q, ((0, nb * blk - s), (0, 0), (0, 0))).reshape(nb, blk, n_kv, group, head_dim)
    k_pos = jnp.arange(s)

    def block(args):
        qb, start = args
        q_pos = start + jnp.arange(blk)
        seen = k_pos[None, :] <= q_pos[:, None]
        if window:
            seen &= q_pos[:, None] - k_pos[None, :] < window
        sc = jnp.einsum("qgrd,kgd->grqk", qb.astype(jnp.float32), k) / math.sqrt(head_dim)
        pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
        # a padded query row past the sequence sees every key: finite, unused
        return jnp.einsum("grqk,kgd->qgrd", pr, v).astype(act)

    ctx = lax.map(block, (qp, jnp.arange(nb) * blk)).reshape(nb * blk, n_head, head_dim)[:s]
    if gated:
        g = jax.nn.sigmoid(_mm(n1, p["attn_gate"], act).astype(jnp.float32))  # [s, n_head]
        ctx = ctx * g[:, :, None].astype(act)
    return x + _mm(ctx.reshape(s, qw), p["attn_o"], act)


def _gated(h, f: int):
    return jax.nn.silu(h[:, :f]) * h[:, f:]


@functools.partial(jax.jit, static_argnames=("eps", "act"))
def _dense(p, x, *, eps, act):
    act = jnp.dtype(act)
    m = p["mlp"]
    h = _mm(_rms(p["ln2"], x, eps, act), m["gate_up"], act)
    return x + _mm(_gated(h, m["down"].shape[0]), m["down"], act)


def router(w, n2, *, top_k: int, scale: float):
    """n2 [T, d] -> the dense gate [T, E] float32 over ALL experts: softmax
    over them all, then the top ``top_k``, renormalised, times ``scale``;
    every other expert 0."""
    probs = jax.nn.softmax(n2.astype(jnp.float32) @ w.astype(jnp.float32), axis=-1)
    top_p, top_e = lax.top_k(probs, top_k)
    top_p = scale * top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(top_e, probs.shape[-1], dtype=jnp.float32) * top_p[..., None], axis=1)


def expert_ffn(m, n2, *, first_expert, top_k, scale, act, shared=True):
    """The expert layer's FFN over n2 [T, d] for the share that ``m`` holds:
    the shared expert (``shared``) + the routed experts ``[first_expert,
    first_expert + held)``, every one of them over every token, one at a
    time, selected by the dense gate's columns."""
    act = jnp.dtype(act)
    gate = router(m["router"], n2, top_k=top_k, scale=scale)
    held, f = m["gate_up"].shape[0], m["down"].shape[1]

    def one(acc, e):
        y = jnp.matmul(
            _gated(_mm(n2, m["gate_up"][e], act), f).astype(act), m["down"][e].astype(act),
            preferred_element_type=jnp.float32,
        )
        return acc + y * lax.dynamic_slice_in_dim(gate, first_expert + e, 1, axis=1), None

    y, _ = lax.scan(one, jnp.zeros(n2.shape, jnp.float32), jnp.arange(held))
    y = y.astype(act)
    if shared:
        y = y + _mm(_gated(_mm(n2, m["shared_gate_up"], act), f), m["shared_down"], act)
    return y


@functools.partial(jax.jit, static_argnames=("first_expert", "top_k", "scale", "eps", "act"))
def _experts(p, x, *, first_expert, top_k, scale, eps, act):
    n2 = _rms(p["ln2"], x, eps, jnp.dtype(act))
    return x + expert_ffn(p["moe"], n2, first_expert=first_expert, top_k=top_k, scale=scale, act=act)


@functools.partial(jax.jit, static_argnames=("eps", "act"))
def _head(ln_f, lm_head, x, *, eps, act):
    act = jnp.dtype(act)
    return jnp.matmul(_rms(ln_f, x, eps, act), lm_head.astype(act), preferred_element_type=jnp.float32)


def logits(params, ids, first: int, *, n_head: int, precision: str, config: dict | None = None):
    """ids [b, s] -> float32 logits [b, s - first, vocab held]: row j is the
    distribution of the token AFTER position ``first + j``. ``config``: a
    dict with the published keys and the ``share`` (default: the
    configuration's file). ``n_head`` is the full layers' count; each layer's
    own comes from ``num_attention_heads_per_layer``."""
    cfg = config or published()
    act = "float32" if precision == "highest" else "bfloat16"
    eps, head_dim = float(cfg["rms_norm_eps"]), int(cfg["head_dim"])
    kinds = {
        "full_attention": (0, *inv_freq(cfg["rope_parameters"]["full_attention"], head_dim)),
        "sliding_attention": (
            int(cfg["sliding_window"]), *inv_freq(cfg["rope_parameters"]["sliding_attention"], head_dim)),
    }
    attn = dict(n_kv=int(cfg["num_key_value_heads"]), head_dim=head_dim, gated=cfg["gating"] == "per-head",
                eps=eps, act=act)
    moe = dict(first_expert=int(cfg["share"]["first_expert"]), top_k=int(cfg["num_experts_per_tok"]),
               scale=float(cfg["moe_routed_scaling_factor"]), eps=eps, act=act)
    dense = set(cfg["mlp_only_layers"])
    assert int(cfg["num_attention_heads_per_layer"][0]) == n_head
    out = []
    # the precision is part of jit's trace context: each value gets its own trace
    with jax.default_matmul_precision(precision):
        for row in np.asarray(ids):
            x = jnp.asarray(params["tok_emb"])[jnp.asarray(row, jnp.int32)].astype(act)
            for i, p in enumerate(params["layers"]):
                window, freq, att = kinds[cfg["layer_types"][i]]
                x = _attention(p, x, freq, att, n_head=int(cfg["num_attention_heads_per_layer"][i]),
                               window=window, **attn)
                x = _dense(p, x, eps=eps, act=act) if i in dense else _experts(p, x, **moe)
            out.append(_head(params["ln_f"], params["lm_head"], x[first:], eps=eps, act=act))
        return jnp.stack(out)
