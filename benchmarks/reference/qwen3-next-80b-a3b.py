"""Plain reference for the ``qwen3-next-80b-a3b`` configuration.

The forward pass of a Qwen3-Next block (``model_type: qwen3_next``) as
ISSUE 57 wrote it down from the published ``config.json`` and implementation
(configs/qwen3-next-80b-a3b.json, whose ``assumed`` repeats the equations and
marks what the keys do not settle), in straightforward ``jax.numpy``. Every
norm but the delta rule's gated one is ZERO-CENTRED: ``n(x) = x .
rsqrt(mean(x^2) + 1e-6) . (1 + w)`` in float32. No bias anywhere.

    x0      = E[tok]
    layer i : x <- x + Mix_i(n1_i(x));  x <- x + Moe_i(n2_i(x))
              Mix_i gated attention where (i + 1) % full_attention_interval
              == 0, else the gated delta rule
    logits  = W_head . n_f(x_L)                               untied

    Delta rule: Hk key heads, Hv value heads of d_k = d_v; value head h reads
              key head h // (Hv / Hk)
              [q | k | v | z] = W_qkvz . n;  [b | a] = W_ba . n
              [q | k | v]_t <- silu(sum_j w_c[j] * [q | k | v]_{t-3+j})   4 taps, no bias
              q <- q / sqrt(sum q^2 + 1e-6) / sqrt(d_k);  k <- k / sqrt(sum k^2 + 1e-6)
              beta = sigmoid(b);  alpha = exp(-exp(A_log) . softplus(a + dt_bias))
              S' = alpha_t S_{t-1};  d_t = beta_t (v_t - S'^T k_t)
              S_t = S' + k_t (x) d_t;  o_t = S_t^T q_t        S [d_k, d_v] float32 a value head
              out = W_out . flatten_h(w_g * (o_h . rsqrt(mean(o_h^2) + 1e-6)) * silu(z_h))
              the norm first, then the gate; w_g a plain weight over d_v
    Attention: [q_h | gate_h] = W_q . n a head; k, v; q <- n_q(q), k <- n_k(k) a
              head (zero-centred); rotate-half rotary, theta, on the head's first
              partial_rotary_factor . head_dim dimensions of q and k; causal
              softmax of q . k / sqrt(head_dim); W_o . (ctx * sigmoid(gate))
    Experts : p = softmax(W_r . n) in float32 over all routed experts; the top
              k; g = p[picks] / sum p[picks]
              Expert_e(n) = W_down,e (silu(W_gate,e n) * W_up,e n)
              Moe(n) = sigmoid(w_sg . n) . Shared(n) + sum_e g_e Expert_e(n)
              On one chip's share the sum runs over the picks that are held;
              the gates stay normalised over all k.

No cache, no blocked form, no pages, no kernels, nothing imported from the
system's models or ops: the delta rule is a token-by-token ``lax.scan`` from a
zero state over the whole sequence, the convolution an explicit 4-tap sum over
a left-padded sequence, attention every position over the whole prefix under a
causal mask, in blocks of query rows. It takes the served model's weights
(random, drawn from the seed; ``gdn_in`` is the q, k, v and z projections
side by side and ``gdn_ba`` b then a, NOT interleaved by key head as the
published checkpoint stores them, which a random draw does not see;
``attn_qkv`` is each head's query and gate side by side, then k, then v;
``moe`` the router and the HELD experts' ``gate_up`` / ``down``, gate and up
side by side; ``shared`` the shared expert's, ``shared_gate`` its gate's
vector; ``lm_head`` [hidden, vocab]) and nothing else from the program; the
sizes that weight shapes do not give come from the configuration's file, or
from ``config`` (the CPU tests' small size).

``precision="highest"`` is the reference proper: float32 activations,
float32 matmuls. ``precision="default"`` is the same forward at the precision
the configuration states, as reference/granite-4.0-h-micro.py defines it:
bfloat16 activations (every matmul's result and every residual rounded to
bfloat16; norms, softmax, the router, the decay, beta, the l2 norms, the
state and the gated norm in float32) at the chip's default matmul.
``harness/correct.py`` takes its rounding delta from their difference.

It computes layer by layer, a sequence at a time, so that one layer's upcast
weights and one sequence of scan outputs fit beside the served model on the
chip; an expert layer one held expert at a time.
"""

from __future__ import annotations

import functools
import json
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 512  # query rows a block of the attention's scores
L2_EPS = 1e-6


@functools.lru_cache(maxsize=1)
def published() -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "qwen3-next-80b-a3b.json")) as f:
        return json.load(f)


def _norm(w, x, eps, act):
    """The zero-centred RMS norm: all of it in float32, then the cast."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(act)


def _mm(a, b, act):
    return jnp.matmul(a.astype(act), b.astype(act), preferred_element_type=jnp.float32).astype(act)


# --------------------------------------------------------------- delta rule


def _gated_norm(o, z, w, eps):
    """o, z [s, Hv, d_v] float32: the RMS norm over each head's d_v and its
    plain weight FIRST, then the silu(z) gate."""
    return w * (o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)) * jax.nn.silu(z)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _delta_rule(p, x, *, key_heads, value_heads, eps, act):
    """x [s, d] -> x + the mixer's output: one sequence."""
    act = jnp.dtype(act)
    f32 = jnp.float32
    s = x.shape[0]
    taps, width = p["conv_w"].shape
    dv = p["gdn_out"].shape[0] // value_heads
    dk = (width - value_heads * dv) // (2 * key_heads)
    n1 = _norm(p["ln1"], x, eps, act)
    qkvz, ba = _mm(n1, p["gdn_in"], act), _mm(n1, p["gdn_ba"], act)
    qkv, z = qkvz[:, :width], qkvz[:, width:]
    b, a = ba[:, :value_heads], ba[:, value_heads:]
    # the depthwise causal convolution: tap j reads the input taps - 1 - j steps back; no bias
    padded = jnp.pad(qkv.astype(f32), ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_w"][j].astype(f32) * padded[j : j + s] for j in range(taps)))
    q = _l2(qkv[:, : key_heads * dk].reshape(s, key_heads, dk)) * dk**-0.5
    k = _l2(qkv[:, key_heads * dk : 2 * key_heads * dk].reshape(s, key_heads, dk))
    v = qkv[:, 2 * key_heads * dk :].reshape(s, value_heads, dv)
    of = jnp.arange(value_heads) // (value_heads // key_heads)  # the key head a value head reads
    beta = jax.nn.sigmoid(b.astype(f32))  # [s, Hv]
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(a.astype(f32) + p["dt_bias"].astype(f32)))

    def token(st, t):
        q_t, k_t, v_t, alpha_t, beta_t = t  # [Hk, dk], [Hk, dk], [Hv, dv], [Hv], [Hv]
        st = alpha_t[:, None, None] * st
        d = beta_t[:, None] * (v_t - jnp.sum(st * k_t[of][:, :, None], axis=1))
        st = st + k_t[of][:, :, None] * d[:, None, :]
        return st, jnp.sum(st * q_t[of][:, :, None], axis=1)

    _, o = lax.scan(token, jnp.zeros((value_heads, dk, dv), f32), (q, k, v, alpha, beta))
    g = _gated_norm(o, z.astype(f32).reshape(s, value_heads, dv), p["gdn_norm"].astype(f32), eps)
    return x + _mm(g.reshape(s, value_heads * dv).astype(act), p["gdn_out"], act)


# ---------------------------------------------------------------- attention


def _rotary_dims(head_dim: int, factor: float) -> int:
    return int(head_dim * factor)


def _rope(x, theta: float, rot: int):
    """Rotate-half rotary on the first ``rot`` dimensions of x [s, h, d] at positions 0..s-1."""
    inv = theta ** (-2.0 * np.arange(rot // 2, dtype=np.float64) / rot)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)  # [s, rot / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2].astype(jnp.float32), x[..., rot // 2 : rot].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _attention(p, x, *, n_head, n_kv, theta, rotary, eps, act):
    """x [s, d] -> x + Wo . (Attn(...) * sigmoid(gate)): one sequence. Query
    rows go in blocks of QUERY_BLOCK against all keys under the mask."""
    act = jnp.dtype(act)
    s = x.shape[0]
    d = p["attn_o"].shape[0] // n_head
    qkv = _mm(_norm(p["ln1"], x, eps, act), p["attn_qkv"], act)
    qg, k, v = jnp.split(qkv, [2 * n_head * d, (2 * n_head + n_kv) * d], axis=-1)
    qg = qg.reshape(s, n_head, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]  # [q_h | gate_h] a head
    rot = _rotary_dims(d, rotary)
    q = _rope(_norm(p["q_norm"], q, eps, act), theta, rot)
    k = _rope(_norm(p["k_norm"], k.reshape(s, n_kv, d), eps, act), theta, rot)
    k = jnp.repeat(k, n_head // n_kv, axis=1)  # query head j reads K/V head j // (heads / kv heads)
    v = jnp.repeat(v.reshape(s, n_kv, d), n_head // n_kv, axis=1)
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

    ctx = lax.map(block, (qp, jnp.arange(nb) * blk)).reshape(nb * blk, n_head, d)[:s]
    ctx = (ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(act)).reshape(s, n_head * d)
    return x + _mm(ctx, p["attn_o"], act)


# ------------------------------------------------------------------ experts


def router(w, n2, *, top_k: int):
    """n2 [T, d] -> the dense gate [T, E] float32 over ALL routed experts:
    softmax, a token's top k carry ``p_e / sum of the picks' p``, every other
    expert 0."""
    p = jax.nn.softmax(n2.astype(jnp.float32) @ w.astype(jnp.float32), axis=-1)
    _, top_e = lax.top_k(p, top_k)
    picked = jnp.sum(jax.nn.one_hot(top_e, p.shape[1], dtype=jnp.float32), axis=1) * p
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def _expert(gate_up, down, n2, act):
    """One gated-SiLU expert over n2 [T, d]: float32 [T, d] before any rounding of the sum."""
    gu = _mm(n2, gate_up, act)
    f = gu.shape[-1] // 2
    return jnp.matmul((jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(act), down.astype(act), preferred_element_type=jnp.float32)


def _shared_gate(w, n2):
    """[T, 1] float32: the shared expert's gate, one scalar a token."""
    return jax.nn.sigmoid(jnp.sum(n2.astype(jnp.float32) * w.astype(jnp.float32), axis=-1, keepdims=True))


def routed_ffn(m, n2, *, first_expert, top_k, act):
    """The ROUTED part of the expert layer over n2 [T, d] for the share that
    ``m`` holds: the experts ``[first_expert, first_expert + held)``, every
    one of them over every token, one at a time, selected by the dense gate's
    columns. With every expert held and ``first_expert`` 0: the uncut layer's."""
    act = jnp.dtype(act)
    gate = router(m["router"], n2, top_k=top_k)

    def one(acc, e):
        y = _expert(m["gate_up"][e], m["down"][e], n2, act)
        return acc + y * lax.dynamic_slice_in_dim(gate, first_expert + e, 1, axis=1), None

    y, _ = lax.scan(one, jnp.zeros(n2.shape, jnp.float32), jnp.arange(m["down"].shape[0]))
    return y


def _experts(p, x, *, first_expert, top_k, eps, act):
    """x [s, d] -> x + Moe(n2(x))."""
    n2 = _norm(p["ln2"], x, eps, jnp.dtype(act))
    routed = routed_ffn(p["moe"], n2, first_expert=first_expert, top_k=top_k, act=act)
    shared = _expert(p["shared"]["gate_up"], p["shared"]["down"], n2, jnp.dtype(act)) * _shared_gate(p["shared_gate"], n2)
    return x + (shared + routed).astype(act)


def _head(ln_f, params, x, *, eps, act):
    """The untied head: ``lm_head`` [hidden, vocab]."""
    act = jnp.dtype(act)
    return jnp.matmul(_norm(ln_f, x, eps, act), params["lm_head"].astype(act), preferred_element_type=jnp.float32)


def _kinds(cfg: dict) -> str:
    """A character a layer held here: ``G`` gated attention where (i + 1) %
    full_attention_interval == 0, else ``D`` the delta rule."""
    every = int(cfg["full_attention_interval"])
    return "".join("G" if (i + 1) % every == 0 else "D" for i in range(int(cfg["num_hidden_layers"])))


@functools.lru_cache(maxsize=None)
def _jitted():
    """The layer functions under jit, made at the first ``logits`` call of
    this module object (a test that swaps one of the helpers above loads the
    module anew, and traces what it swapped)."""
    return {
        "D": jax.jit(_delta_rule, static_argnames=("key_heads", "value_heads", "eps", "act")),
        "G": jax.jit(_attention, static_argnames=("n_head", "n_kv", "theta", "rotary", "eps", "act")),
        "E": jax.jit(_experts, static_argnames=("first_expert", "top_k", "eps", "act")),
        "head": jax.jit(_head, static_argnames=("eps", "act")),
    }


def logits(params, ids, first: int, *, n_head: int, precision: str, config: dict | None = None):
    """ids [b, s] -> float32 logits [b, s - first, vocab]: row j is the
    distribution of the token AFTER position ``first + j``. ``config``: a
    dict with the published keys and the ``share`` (default: the
    configuration's file)."""
    cfg = config or published()
    act = "float32" if precision == "highest" else "bfloat16"
    eps = float(cfg["rms_norm_eps"])
    kw = {
        "D": dict(key_heads=int(cfg["linear_num_key_heads"]), value_heads=int(cfg["linear_num_value_heads"]), eps=eps, act=act),
        "G": dict(n_head=n_head, n_kv=int(cfg["num_key_value_heads"]), theta=float(cfg["rope_theta"]),
                  rotary=float(cfg["partial_rotary_factor"]), eps=eps, act=act),
        "E": dict(first_expert=int(cfg["share"]["first_expert"]), top_k=int(cfg["num_experts_per_tok"]), eps=eps, act=act),
    }
    fn = _jitted()
    out = []
    # the precision is part of jit's trace context: each value gets its own trace
    with jax.default_matmul_precision(precision):
        for row in np.asarray(ids):
            x = jnp.asarray(params["tok_emb"])[jnp.asarray(row, jnp.int32)].astype(act)
            for p, kind in zip(params["layers"], _kinds(cfg)):
                x = fn["E"](p, fn[kind](p, x, **kw[kind]), **kw["E"])
            out.append(fn["head"](params["ln_f"], params, x[first:], eps=eps, act=act))
        return jnp.stack(out)
