"""Plain reference for the ``xing4.0-29b-a4b`` configuration.

The forward pass of a Xing4.0 block (``model_type: xing4_0``: the DeepSeek-V3
family's keys + manifold-constrained hyper-connections, arXiv:2512.24880), as
ISSUE 43 wrote it down from the published ``config.json``
(configs/xing4.0-29b-a4b.json, whose ``assumed`` repeats the equations), in
straightforward ``jax.numpy``. A token's state is X[4, C]:

    X_0[j] = E[tok] for all four j
    a block F (attention or feed-forward) with its own maps, 2 a layer:
        v = vec(X) . rsqrt(mean(vec(X)^2) + 1e-6)                 over all 4C numbers, no weight
        H_pre  = sigmoid(a_pre  . (v phi_pre)  + b_pre)           [4]
        H_post = 2 sigmoid(a_post . (v phi_post) + b_post)        [4]
        H_res  = SK(clip(a_res . mat(v phi_res) + b_res, -30, 30))  [4, 4]
        SK: M = exp(.); 20 times: M <- M / (sum over rows + hc_eps), then
            M <- M / (sum over columns + hc_eps)                  the literal loop
        u = sum_j H_pre[j] X[j];  o = F(RMSNorm(u));  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] o
    attention: MLA as configs/a.x-k1.json writes it (32 heads, YaRN factor 64)
    feed-forward, i < first_k_dense_replace: Wdown(silu(Wgate n) * Wup n)
    feed-forward, later: Shared(n) + sum_{e in top4(s + b)} g_e . Expert_e(n),
        s = sigmoid(Wr n) in float32, g_e = scale . s_e / (sum of the picks' s + 1e-6):
        the bias b chooses and does not weigh; no groups
    logits = Whead . RMSNorm(sum_j X_L[j])

NOT absorbed, no cache, no batching, no kernels: every position's per-head
keys and values are expanded from its latent and every position attends over
the whole prefix under a mask; every expert HELD computes every token, a
dense [tokens, held] gate (zero off a token's picks) selecting. It is given
the same share as the served model: the experts ``[first_expert,
first_expert + held)`` (``held`` is the stored experts' count), a pick that
lands on an absent expert adding nothing, the gates normalised over all 4
picks; and the slice of the vocabulary the weights hold. It takes the served
model's weights and nothing else from the program; sizes that weight shapes
do not give come from the configuration's file, or from ``config`` (the CPU
tests' small size).

``precision="highest"`` is the reference proper: float32 state, float32
matmuls. ``precision="default"`` is the same forward at the precision the
configuration states: a bfloat16 state (every matmul's result and the state
rounded to bfloat16 where written; norms, softmax, the router and the stream
maps in float32) at the chip's default matmul. ``harness/correct.py`` takes
its rounding delta from their difference.

One sequence at a time, layer by layer, query rows in blocks, one expert at
a time (the small helpers are the ``a.x-k1`` reference's, loaded by path).
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HERE = os.path.dirname(os.path.abspath(__file__))


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location("bench_reference_" + "".join(c if c.isalnum() else "_" for c in name),
                                                  os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_k1 = _sibling("a.x-k1")  # inv_freq, score_scale, _rms, _mm, _rope, _gated: the family's small helpers
inv_freq, score_scale, _rms, _mm, _rope, _gated = (
    _k1.inv_freq, _k1.score_scale, _k1._rms, _k1._mm, _k1._rope, _k1._gated
)
QUERY_BLOCK = 256


@functools.lru_cache(maxsize=1)
def published() -> dict:
    with open(os.path.join(HERE, "..", "configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the stream maps


def sinkhorn_knopp(logits, iters: int, eps: float):
    """logits [s, n, n] float32 -> exp, then ``iters`` times: every column
    over its sum + eps (the sum runs over rows), every row over its sum + eps."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
    return m


def stream_maps(m, x, *, iters, hc_eps, clamp, eps):
    """x [s, n, C] -> (H_pre [s, n], H_post [s, n], H_res [s, n, n]) float32."""
    s, n, c = x.shape
    v = x.reshape(s, n * c).astype(jnp.float32)
    v = v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    z = v @ m["phi"].astype(jnp.float32)  # [s, n + n + n * n]: pre | post | res row-major
    a, b = m["alpha"].astype(jnp.float32), m["bias"].astype(jnp.float32)
    h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n : 2 * n] + b[n : 2 * n])
    r = jnp.clip(a[2] * z[:, 2 * n :] + b[2 * n :], -clamp, clamp).reshape(s, n, n)
    return h_pre, h_post, sinkhorn_knopp(r, iters, hc_eps)


def through_streams(m, x, block, *, iters, hc_eps, clamp, eps, act):
    """X' of one block: the maps from X, ``u`` into ``block`` (which takes its
    own pre-norm), its output back through H_post beside H_res X."""
    h_pre, h_post, h_res = stream_maps(m, x, iters=iters, hc_eps=hc_eps, clamp=clamp, eps=eps)
    xf = x.astype(jnp.float32)
    u = jnp.sum(h_pre[:, :, None] * xf, axis=1).astype(act)  # [s, C]
    o = block(u).astype(jnp.float32)
    new = jnp.sum(h_res[:, :, :, None] * xf[:, None, :, :], axis=2) + h_post[:, :, None] * o[:, None, :]
    return new.astype(act)


# ------------------------------------------------------------ the two blocks


def attention(p, u, freq, *, n_head, nope, rope, rank, scale, eps, act):
    """Wo . Attn(RMSNorm(u)) of one sequence u [s, d]: the block's OUTPUT, no
    residual. Query rows go in blocks of QUERY_BLOCK against all keys."""
    s = u.shape[0]
    n = _rms(p["ln1"], u, eps, act)
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
        return jnp.einsum("hqk,khd->qhd", pr, v.astype(jnp.float32)).astype(act)

    ctx = lax.map(block, (qp, jnp.arange(nb) * blk)).reshape(nb * blk, -1)[:s]
    return _mm(ctx, p["attn_o"], act)


def router(w, b, n2, *, top_k: int, scale: float):
    """n2 [T, d] -> the dense gate [T, E] float32 over ALL experts: a token's
    picks, the top k of score + bias, carry ``scale * s_e / (sum of the
    picks' s + 1e-6)``, every other expert 0."""
    s = jax.nn.sigmoid(n2.astype(jnp.float32) @ w.astype(jnp.float32))
    _, top_e = lax.top_k(s + b.astype(jnp.float32), top_k)
    picked = jnp.sum(jax.nn.one_hot(top_e, s.shape[1], dtype=jnp.float32), axis=1)  # [T, E] 0/1
    return scale * picked * s / (jnp.sum(picked * s, axis=-1, keepdims=True) + 1e-6)


def dense_ffn(p, u, *, eps, act):
    m = p["mlp"]
    h = _mm(_rms(p["ln2"], u, eps, act), m["gate_up"], act)
    return _mm(_gated(h, m["down"].shape[0]), m["down"], act)


def expert_ffn(p, u, *, first_expert, top_k, scale, eps, act, shared=True):
    """The expert layer's feed-forward OUTPUT over u [T, d] for the share the
    weights hold: the shared expert (``shared``) + the routed experts
    ``[first_expert, first_expert + held)``, every one over every token, one
    at a time, selected by the dense gate's columns."""
    m = p["moe"]
    n2 = _rms(p["ln2"], u, eps, act)
    gate = router(m["router"], m["router_bias"], n2, top_k=top_k, scale=scale)
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


# ------------------------------------------------------------ a layer, the model


@functools.partial(jax.jit, static_argnames=("attn", "ffn", "hc", "act"))
def _layer(p, x, freq, *, attn, ffn, hc, act):
    """x [s, n, C] -> the layer's new state: the attention block through its
    maps, then the feed-forward block (dense where the layer holds ``mlp``)
    through its own. ``attn`` / ``ffn`` / ``hc``: the blocks' static sizes as
    sorted item tuples."""
    act = jnp.dtype(act)
    attn, ffn, hc = dict(attn), dict(ffn), dict(hc)
    x = through_streams(p["hc_attn"], x, lambda u: attention(p, u, freq, act=act, **attn), act=act, **hc)
    if "mlp" in p:
        return through_streams(p["hc_mlp"], x, lambda u: dense_ffn(p, u, eps=attn["eps"], act=act), act=act, **hc)
    return through_streams(p["hc_mlp"], x, lambda u: expert_ffn(p, u, act=act, **ffn), act=act, **hc)


@functools.partial(jax.jit, static_argnames=("eps", "act"))
def _head(ln_f, lm_head, top, *, eps, act):
    """The final norm and the untied head over the summed streams."""
    return jnp.matmul(_rms(ln_f, top, eps, jnp.dtype(act)), lm_head.astype(act), preferred_element_type=jnp.float32)


def hidden(params, row, *, n_head: int, act: str, cfg: dict):
    """One sequence's token ids [s] -> [s, C]: the four streams of the last
    layer SUMMED (what the final norm reads, and the served programs'
    ``hidden``). Call under the precision's ``jax.default_matmul_precision``."""
    eps = float(cfg["rms_norm_eps"])
    freq = inv_freq(cfg)
    attn = tuple(sorted(dict(n_head=n_head, nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
                             rank=int(cfg["kv_lora_rank"]), scale=score_scale(cfg), eps=eps).items()))
    ffn = tuple(sorted(dict(first_expert=int(cfg["share"]["first_expert"]), top_k=int(cfg["num_experts_per_tok"]),
                            scale=float(cfg["routed_scaling_factor"]), eps=eps).items()))
    hc = tuple(sorted(dict(iters=int(cfg["hc_sinkhorn_iters"]), hc_eps=float(cfg["hc_eps"]),
                           clamp=float(cfg["mhc_h_res_clamp_max"]), eps=eps).items()))
    e = jnp.asarray(params["tok_emb"])[jnp.asarray(row, jnp.int32)].astype(act)
    x = jnp.broadcast_to(e[:, None, :], (e.shape[0], int(cfg["hc_mult"]), e.shape[1]))
    for p in params["layers"]:
        x = _layer(p, x, freq, attn=attn, ffn=ffn, hc=hc, act=act)
    return jnp.sum(x.astype(jnp.float32), axis=1).astype(act)


def logits(params, ids, first: int, *, n_head: int, precision: str, config: dict | None = None):
    """ids [b, s] -> float32 logits [b, s - first, vocab held]: row j is the
    distribution of the token AFTER position ``first + j``. ``config``: a
    dict with the published keys and the ``share`` (default: the
    configuration's file)."""
    cfg = config or published()
    act = "float32" if precision == "highest" else "bfloat16"
    out = []
    # the precision is part of jit's trace context: each value gets its own trace
    with jax.default_matmul_precision(precision):
        for row in np.asarray(ids):
            top = hidden(params, row, n_head=n_head, act=act, cfg=cfg)
            out.append(_head(params["ln_f"], params["lm_head"], top[first:], eps=float(cfg["rms_norm_eps"]), act=act))
        return jnp.stack(out)
