"""Plain reference for the ``a.x-k1`` configuration.

The forward pass of an A.X-K1 block (``model_type: axk1``: the DeepSeek-V3
family's keys), as ISSUE 37 wrote it down from the published ``config.json``
(configs/a.x-k1.json, whose ``assumed`` repeats the equations), in
straightforward ``jax.numpy``:

    x0 = E[tok]
    h  = x + Wo . concat_h( sum_s softmax_s(score_h)(t, s) v_h(s) )         n = RMSNorm(x)
         c_q = RMSNorm(Wdq n);  [q_nope_h | q_rope_h] = Wuq c_q;  q_rope_h = RoPE(q_rope_h, pos)
         [c_kv | k_r] = Wdkv n;  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r, pos)   ONE k_r for all heads
         [k_nope_h | v_h] = Wukv c_kv
         score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_r(s)) * scale, causal
         scale = (nope + rope)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
    y  = h + FFN_i(RMSNorm(h))
         FFN_i = Wdown(silu(Wgate n) * Wup n) of width intermediate_size     i < first_k_dense_replace
         FFN_i = Shared(n) + sum_{e in top8(n)} g_e(n) . Expert_e(n)          the later layers
         s = sigmoid(Wr n) in float32; n_group groups of consecutive experts, a group's
         score its largest s; the topk_group best groups stay; top8 = the 8 largest s
         inside them; g_e = routed_scaling_factor * s_e / sum_top8 s
    logits = Whead . RMSNorm(x_L)

NOT absorbed, no cache, no batching: every position's per-head keys and
values are expanded from its latent, every position attends over the whole
prefix under a mask, and every expert HELD computes every token, a dense
[tokens, held] gate (zero off a token's picks) selecting. It is given the
same share as the served model: the experts ``[first_expert, first_expert +
held)`` (``held`` is the stored experts' count, ``first_expert`` the
configuration's ``share``), a pick that lands on an absent expert adding
nothing, the gates normalised over all 8 picks; and the slice of the
vocabulary the weights hold. With all experts held (``first_expert`` 0) it
is the uncut layer. It takes the served model's weights and nothing else
from the program; sizes that weight shapes do not give come from the
configuration's file, or from ``config`` (the CPU tests' small size).

``precision="highest"`` is the reference proper: float32 activations,
float32 matmuls. ``precision="default"`` is the same forward at the precision
the configuration states: bfloat16 activations (every matmul's result and
every residual rounded to bfloat16; norms, softmax and the router in
float32) at the chip's default matmul. ``harness/correct.py`` takes its
rounding delta from their difference.

One sequence at a time, layer by layer, in blocks of query rows, one expert
at a time: 8280 positions of 64 heads fit beside the served model on the chip
(all scores of one sequence at once would be 17.5 GB).
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
    with open(os.path.join(here, "..", "configs", "a.x-k1.json")) as f:
        return json.load(f)


def inv_freq(cfg: dict) -> np.ndarray:
    """[rope / 2] float32 YaRN frequencies over the rope dimensions: fast
    dimensions keep theta^(-2i/d), slow ones are divided by ``factor``, a
    linear ramp between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over the original context."""
    d, theta, rs = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"]), cfg["rope_scaling"]
    i = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / d)
    orig, factor = float(rs["original_max_position_embeddings"]), float(rs["factor"])

    def c(n: float) -> float:
        return d * math.log(orig / (2.0 * math.pi * n)) / (2.0 * math.log(theta))

    lo = min(max(math.floor(c(float(rs["beta_fast"]))), 0), d - 1)
    hi = min(max(math.ceil(c(float(rs["beta_slow"]))), 0), d - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((1.0 - ramp) * plain + ramp * plain / factor).astype(np.float32)


def score_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = 0.1 * float(rs["mscale_all_dim"]) * math.log(float(rs["factor"])) + 1.0
    return (int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])) ** -0.5 * m * m


def _rms(w, x, eps, act):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(act) * w.astype(act)


def _mm(a, b, act):
    return jnp.matmul(a.astype(act), b.astype(act), preferred_element_type=jnp.float32).astype(act)


def _rope(x, freq):
    """x [s, h, d] at positions 0..s-1: rotate-half pairing, no factor on
    cos and sin (mscale == mscale_all_dim)."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(freq)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("n_head", "nope", "rope", "rank", "scale", "eps", "act"))
def _attention(p, x, freq, *, n_head, nope, rope, rank, scale, eps, act):
    """x [s, d] + Wo . Attn(...): one sequence. Query rows go in blocks of
    QUERY_BLOCK against all keys under the mask."""
    act = jnp.dtype(act)
    s = x.shape[0]
    n = _rms(p["ln1"], x, eps, act)
    # q_b and kv_b are stored [rank, heads, columns of a head]: as plain matrices, [rank, heads * columns]
    q_b, kv_b = (p[k].reshape(p[k].shape[0], -1) for k in ("q_b", "kv_b"))
    q = _mm(_rms(p["q_norm"], _mm(n, p["q_a"], act), eps, act), q_b, act).reshape(s, n_head, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], freq)], axis=-1)
    kv = _mm(n, p["kv_a"], act)
    c_kv = _rms(p["kv_norm"], kv[:, :rank], eps, act)
    k_r = _rope(kv[:, None, rank:], freq)  # [s, 1, rope]: one key for all heads
    kvh = _mm(c_kv, kv_b, act).reshape(s, n_head, -1)  # [s, H, nope + v]
    k = jnp.concatenate([kvh[..., :nope], jnp.broadcast_to(k_r, (s, n_head, rope))], axis=-1)
    v = kvh[..., nope:]
    blk = min(QUERY_BLOCK, s)
    nb = -(-s // blk)
    qp = jnp.pad(q, ((0, nb * blk - s), (0, 0), (0, 0))).reshape(nb, blk, n_head, nope + rope)
    k_pos = jnp.arange(s)

    def block(args):
        qb, start = args
        seen = k_pos[None, :] <= (start + jnp.arange(blk))[:, None]
        sc = jnp.einsum("qhd,khd->hqk", qb.astype(jnp.float32), k.astype(jnp.float32)) * scale
        pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        # a padded query row past the sequence sees every key: finite, unused
        return jnp.einsum("hqk,khd->qhd", pr, v.astype(jnp.float32)).astype(act)

    ctx = lax.map(block, (qp, jnp.arange(nb) * blk)).reshape(nb * blk, -1)[:s]
    return x + _mm(ctx, p["attn_o"], act)


def router(w, n2, *, top_k: int, n_group: int, topk_group: int, scale: float):
    """n2 [T, d] -> the dense gate [T, E] float32 over ALL experts: a token's
    8 picks carry ``scale * s_e / sum of the 8``, every other expert 0."""
    s = jax.nn.sigmoid(n2.astype(jnp.float32) @ w.astype(jnp.float32))
    t, n_exp = s.shape
    group_best = jnp.max(s.reshape(t, n_group, n_exp // n_group), axis=-1)
    _, groups = lax.top_k(group_best, topk_group)
    keep = jnp.sum(jax.nn.one_hot(groups, n_group, dtype=jnp.float32), axis=1) > 0  # [T, G]
    inside = jnp.where(jnp.repeat(keep, n_exp // n_group, axis=1), s, -jnp.inf)
    _, top_e = lax.top_k(inside, top_k)
    picked = jnp.sum(jax.nn.one_hot(top_e, n_exp, dtype=jnp.float32), axis=1)  # [T, E] 0/1
    return scale * picked * s / jnp.sum(picked * s, axis=-1, keepdims=True)


def _gated(h, f: int):
    return jax.nn.silu(h[:, :f]) * h[:, f:]


@functools.partial(jax.jit, static_argnames=("eps", "act"))
def _dense(p, x, *, eps, act):
    act = jnp.dtype(act)
    m = p["mlp"]
    h = _mm(_rms(p["ln2"], x, eps, act), m["gate_up"], act)
    return x + _mm(_gated(h, m["down"].shape[0]), m["down"], act)


def expert_ffn(m, n2, *, first_expert, top_k, n_group, topk_group, scale, act, shared=True):
    """The expert layer's FFN over n2 [T, d] for the share that ``m`` holds:
    the shared expert (``shared``) + the routed experts ``[first_expert,
    first_expert + held)``, every one of them over every token, one at a
    time, selected by the dense gate's columns."""
    act = jnp.dtype(act)
    gate = router(m["router"], n2, top_k=top_k, n_group=n_group, topk_group=topk_group, scale=scale)
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


@functools.partial(jax.jit, static_argnames=("first_expert", "top_k", "n_group", "topk_group", "scale", "eps", "act"))
def _experts(p, x, *, first_expert, top_k, n_group, topk_group, scale, eps, act):
    n2 = _rms(p["ln2"], x, eps, jnp.dtype(act))
    return x + expert_ffn(p["moe"], n2, first_expert=first_expert, top_k=top_k, n_group=n_group,
                          topk_group=topk_group, scale=scale, act=act)


@functools.partial(jax.jit, static_argnames=("eps", "act"))
def _head(ln_f, lm_head, x, *, eps, act):
    act = jnp.dtype(act)
    return jnp.matmul(_rms(ln_f, x, eps, act), lm_head.astype(act), preferred_element_type=jnp.float32)


def logits(params, ids, first: int, *, n_head: int, precision: str, config: dict | None = None):
    """ids [b, s] -> float32 logits [b, s - first, vocab held]: row j is the
    distribution of the token AFTER position ``first + j``. ``config``: a
    dict with the published keys and the ``share`` (default: the
    configuration's file)."""
    cfg = config or published()
    act = "float32" if precision == "highest" else "bfloat16"
    eps = float(cfg["rms_norm_eps"])
    freq = inv_freq(cfg)
    attn = dict(n_head=n_head, nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
                rank=int(cfg["kv_lora_rank"]), scale=score_scale(cfg), eps=eps, act=act)
    moe = dict(first_expert=int(cfg["share"]["first_expert"]), top_k=int(cfg["num_experts_per_tok"]),
               n_group=int(cfg["n_group"]), topk_group=int(cfg["topk_group"]),
               scale=float(cfg["routed_scaling_factor"]), eps=eps, act=act)
    out = []
    # the precision is part of jit's trace context: each value gets its own trace
    with jax.default_matmul_precision(precision):
        for row in np.asarray(ids):
            x = jnp.asarray(params["tok_emb"])[jnp.asarray(row, jnp.int32)].astype(act)
            for i, p in enumerate(params["layers"]):
                x = _attention(p, x, freq, **attn)
                x = _dense(p, x, eps=eps, act=act) if i < int(cfg["first_k_dense_replace"]) else _experts(p, x, **moe)
            out.append(_head(params["ln_f"], params["lm_head"], x[first:], eps=eps, act=act))
        return jnp.stack(out)
