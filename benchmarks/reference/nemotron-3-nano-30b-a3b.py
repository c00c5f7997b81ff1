"""Plain reference for the ``nemotron-3-nano-30b-a3b`` configuration.

The forward pass of a Nemotron-H block (``model_type: nemotron_h``) as
ISSUE 51 wrote it down from the published ``config.json``
(configs/nemotron-3-nano-30b-a3b.json, whose ``assumed`` repeats the
equations and marks what the keys do not settle), in straightforward
``jax.numpy``. ``n = RMSNorm(x)``, eps 1e-5, float32 inside; no bias but the
convolution's:

    x0      = E[tok]                                          no multiplier
    layer i : x <- x + Mix_i(n_i(x))            ONE sublayer a layer, by
              hybrid_override_pattern[i]: M Mamba-2, * attention, E experts
    logits  = W_head . n_f(x_L)                               untied

    Mamba-2 : H heads of P, state N, G groups of B and C, head h reads group
              h // (H / G); d_inner = H . P
              [z | xBC | dt] = W_in . n         widths d_inner | d_inner + 2GN | H
              xBC_t = silu(b_c + sum_k w_c[k] * xBC_{t-3+k})     4 taps, bias
              [xs | B | C] = xBC_t,  xs [H, P],  B, C [G, N]
              dt = softplus(dt + dt_bias); a = exp(dt . A), A = -exp(A_log)
              S_t[h] = a_h S_{t-1}[h] + (dt_h xs_h) (x) B_g(h)
              y_h = S_t[h] . C_g(h) + D_h xs_h
              out = W_out (w_n * RMSNorm_group(y * silu(z)))   gate first, then
              the norm over EACH group's d_inner / G channels
    Attention: query head j reads key/value head j // (heads / kv heads);
              scores / sqrt(head); causal; NO position term
    Experts : s = sigmoid(W_r . n) in float32 over all routed experts
              picks = top k of s + b            the bias selects, does not weigh
              g = s[picks] / (sum s[picks] + 1e-20) . routed_scaling_factor
              Expert_e(n) = W_down,e . relu(W_up,e . n)^2        no gate projection
              Mix = Shared(n) + sum_e g_e Expert_e(n)   Shared: the same form,
              wider, ungated. On one chip's share the sum runs over the picks
              that are held; the gates stay normalised over all k.

No cache, no chunked form, no pages, no kernels: the recurrence is a
token-by-token ``lax.scan`` from a zero state over the whole sequence (its
result does not depend on the published ``chunk_size``, which only the
chunked form has), the convolution an explicit 4-tap sum over a left-padded
sequence, attention every position over the whole prefix under a causal
mask, in blocks of query rows. It takes the served model's weights (random,
drawn from the seed; ``ssm_in`` is the z, xBC and dt projections side by
side, ``conv_w`` [tap, channel], ``moe`` the router, its bias and the HELD
experts' ``up`` / ``down``, ``shared`` the shared expert's, ``lm_head``
[hidden, vocab]) and nothing else from the program; the sizes that weight
shapes do not give come from the configuration's file, or from ``config``
(the CPU tests' small size). A weight stored wider than its published width
with zeros (ISSUE 51 allows the experts' 1856 padded to whole lane tiles) is
the same mathematics: ``relu(0)^2 = 0`` against zero rows of ``down``.

``precision="highest"`` is the reference proper: float32 activations,
float32 matmuls. ``precision="default"`` is the same forward at the
precision the configuration states, as reference/granite-4.0-h-micro.py
defines it: bfloat16 activations (every matmul's result and every residual
rounded to bfloat16; norms, softmax, the router, the time step, the decay,
the state and the gated norm in float32) at the chip's default matmul.
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
GATE_EPS = 1e-20


@functools.lru_cache(maxsize=1)
def published() -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


def _rms(w, x, eps, act):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(act) * w.astype(act)


def _mm(a, b, act):
    return jnp.matmul(a.astype(act), b.astype(act), preferred_element_type=jnp.float32).astype(act)


# ------------------------------------------------------------------ Mamba-2


def _group_of(heads: int, groups: int):
    """[heads] int: the B/C group a head reads."""
    return jnp.arange(heads) // (heads // groups)


def _gated_norm(y, z, w, groups: int, eps):
    """y, z [s, d_inner] float32: the gate first, then the RMS norm over each
    group's d_inner / groups channels, then the weight."""
    g = (y * jax.nn.silu(z)).reshape(y.shape[0], groups, -1)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(y.shape) * w


def _mamba(p, x, *, heads, state, groups, eps, act):
    """x [s, d] -> x + the mixer's output: one sequence."""
    act = jnp.dtype(act)
    f32 = jnp.float32
    s = x.shape[0]
    d_inner = p["ssm_out"].shape[0]
    hd = d_inner // heads
    taps, width = p["conv_w"].shape
    zxd = _mm(_rms(p["ln1"], x, eps, act), p["ssm_in"], act)
    z, xbc, dt = zxd[:, :d_inner], zxd[:, d_inner : d_inner + width], zxd[:, d_inner + width :]
    # the depthwise causal convolution: tap k reads the input taps - 1 - k steps back
    padded = jnp.pad(xbc.astype(f32), ((taps - 1, 0), (0, 0)))
    conv = p["conv_b"].astype(f32)
    for k in range(taps):
        conv = conv + p["conv_w"][k].astype(f32) * padded[k : k + s]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(s, heads, hd)
    bm = xbc[:, d_inner : d_inner + groups * state].reshape(s, groups, state)
    cm = xbc[:, d_inner + groups * state :].reshape(s, groups, state)
    of = _group_of(heads, groups)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))  # [s, h]
    a = jnp.exp(dt * -jnp.exp(p["A_log"].astype(f32)))

    def token(st, t):
        a_t, dt_t, xs_t, b_t, c_t = t  # [h], [h], [h, p], [g, N], [g, N]
        st = a_t[:, None, None] * st + (dt_t[:, None] * xs_t)[..., None] * b_t[of][:, None, :]
        return st, jnp.sum(st * c_t[of][:, None, :], axis=-1)

    _, y = lax.scan(token, jnp.zeros((heads, hd, state), f32), (a, dt, xs, bm, cm))
    y = y + p["D"].astype(f32)[:, None] * xs
    g = _gated_norm(y.reshape(s, d_inner), z.astype(f32), p["ssm_norm"].astype(f32), groups, eps)
    return x + _mm(g.astype(act), p["ssm_out"], act)


# ---------------------------------------------------------------- attention


def _attention(p, x, *, n_head, n_kv, eps, act):
    """x [s, d] -> x + Wo . Attn(...): one sequence, no position term. Query
    rows go in blocks of QUERY_BLOCK against all keys under the mask."""
    act = jnp.dtype(act)
    s = x.shape[0]
    d = p["attn_o"].shape[0] // n_head
    qkv = _mm(_rms(p["ln1"], x, eps, act), p["attn_qkv"], act)
    q, k, v = jnp.split(qkv, [n_head * d, (n_head + n_kv) * d], axis=-1)
    q = q.reshape(s, n_head, d)
    k = jnp.repeat(k.reshape(s, n_kv, d), n_head // n_kv, axis=1)  # query head j reads K/V head j // (heads / kv heads)
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

    ctx = lax.map(block, (qp, jnp.arange(nb) * blk)).reshape(nb * blk, -1)[:s]
    return x + _mm(ctx, p["attn_o"], act)


# ------------------------------------------------------------------ experts


def _pick_weights(s, b):
    """What a pick weighs: its UNBIASED score (the bias chooses and does not weigh)."""
    return s


def router(w, b, n2, *, top_k: int, scale: float):
    """n2 [T, d] -> the dense gate [T, E] float32 over ALL routed experts: a
    token's picks, the top k of score + bias, carry ``scale * s_e / (sum of
    the picks' s + 1e-20)``, every other expert 0."""
    s = jax.nn.sigmoid(n2.astype(jnp.float32) @ w.astype(jnp.float32))
    _, top_e = lax.top_k(s + b.astype(jnp.float32), top_k)
    picked = jnp.sum(jax.nn.one_hot(top_e, s.shape[1], dtype=jnp.float32), axis=1)  # [T, E] 0/1
    weigh = picked * _pick_weights(s, b.astype(jnp.float32))
    return scale * weigh / (jnp.sum(weigh, axis=-1, keepdims=True) + GATE_EPS)


def _expert_act(h):
    """mlp_hidden_act relu2: the ReLU, squared; no gate projection."""
    return jnp.square(jax.nn.relu(h))


def _expert(up, down, n2, act):
    """One expert over n2 [T, d]: float32 [T, d] before any rounding of the sum."""
    return jnp.matmul(
        _expert_act(_mm(n2, up, act)).astype(act), down.astype(act), preferred_element_type=jnp.float32
    )


def _shared(m, n2, act):
    """The shared expert: every token, ungated."""
    return _expert(m["up"], m["down"], n2, act)


def routed_ffn(m, n2, *, first_expert, top_k, scale, act):
    """The ROUTED part of the expert layer over n2 [T, d] for the share that
    ``m`` holds: the experts ``[first_expert, first_expert + held)``, every
    one of them over every token, one at a time, selected by the dense gate's
    columns. With every expert held and ``first_expert`` 0: the uncut layer's."""
    act = jnp.dtype(act)
    gate = router(m["router"], m["router_bias"], n2, top_k=top_k, scale=scale)

    def one(acc, e):
        y = _expert(m["up"][e], m["down"][e], n2, act)
        return acc + y * lax.dynamic_slice_in_dim(gate, first_expert + e, 1, axis=1), None

    y, _ = lax.scan(one, jnp.zeros(n2.shape, jnp.float32), jnp.arange(m["down"].shape[0]))
    return y


def _experts(p, x, *, first_expert, top_k, scale, eps, act):
    n2 = _rms(p["ln1"], x, eps, jnp.dtype(act))
    routed = routed_ffn(p["moe"], n2, first_expert=first_expert, top_k=top_k, scale=scale, act=act)
    return x + (_shared(p["shared"], n2, jnp.dtype(act)) + routed).astype(act)


def _head(ln_f, params, x, *, eps, act):
    """The untied head: ``lm_head`` [hidden, vocab]."""
    act = jnp.dtype(act)
    return jnp.matmul(_rms(ln_f, x, eps, act), params["lm_head"].astype(act), preferred_element_type=jnp.float32)


def _kinds(cfg: dict) -> str:
    """A character a layer held here: the pattern's first ``num_hidden_layers``."""
    return cfg["hybrid_override_pattern"][: int(cfg["num_hidden_layers"])]


@functools.lru_cache(maxsize=None)
def _jitted():
    """The layer functions under jit, made at the first ``logits`` call of
    this module object (a test that swaps one of the helpers above loads the
    module anew, and traces what it swapped)."""
    return {
        "M": jax.jit(_mamba, static_argnames=("heads", "state", "groups", "eps", "act")),
        "*": jax.jit(_attention, static_argnames=("n_head", "n_kv", "eps", "act")),
        "E": jax.jit(_experts, static_argnames=("first_expert", "top_k", "scale", "eps", "act")),
        "head": jax.jit(_head, static_argnames=("eps", "act")),
    }


def logits(params, ids, first: int, *, n_head: int, precision: str, config: dict | None = None):
    """ids [b, s] -> float32 logits [b, s - first, vocab]: row j is the
    distribution of the token AFTER position ``first + j``. ``config``: a
    dict with the published keys and the ``share`` (default: the
    configuration's file)."""
    cfg = config or published()
    act = "float32" if precision == "highest" else "bfloat16"
    eps = float(cfg["layer_norm_epsilon"])
    kw = {
        "M": dict(heads=int(cfg["mamba_num_heads"]), state=int(cfg["ssm_state_size"]), groups=int(cfg["n_groups"]),
                  eps=eps, act=act),
        "*": dict(n_head=n_head, n_kv=int(cfg["num_key_value_heads"]), eps=eps, act=act),
        "E": dict(first_expert=int(cfg["share"]["first_expert"]), top_k=int(cfg["num_experts_per_tok"]),
                  scale=float(cfg["routed_scaling_factor"]), eps=eps, act=act),
    }
    fn = _jitted()
    out = []
    # the precision is part of jit's trace context: each value gets its own trace
    with jax.default_matmul_precision(precision):
        for row in np.asarray(ids):
            x = jnp.asarray(params["tok_emb"])[jnp.asarray(row, jnp.int32)].astype(act)
            for p, kind in zip(params["layers"], _kinds(cfg)):
                x = fn[kind](p, x, **kw[kind])
            out.append(fn["head"](params["ln_f"], params, x[first:], eps=eps, act=act))
        return jnp.stack(out)
