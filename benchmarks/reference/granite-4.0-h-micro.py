"""Plain reference for the ``granite-4.0-h-micro`` configuration.

The forward pass of a Granite-4.0-H block, as ISSUE 34 wrote it down from
the published ``config.json`` (configs/granite-4.0-h-micro.json, whose
``assumed`` repeats the equations), in straightforward ``jax.numpy``:

    x0      = 12 . E[tok]
    layer i : h = x + 0.22 . Mix_i(RMSNorm(x))
              y = h + 0.22 . Wout(silu(g) * u),  [g, u] = Win . RMSNorm(h)
    Mix_i   = Attention where layer_types[i] == "attention", else Mamba2
    logits  = (E . RMSNorm(x_last)) / 8                       the tied head

    Attention: 32 query heads, head j reads key/value head j // 4; no
               position term; scores * attention_multiplier; causal
    Mamba2   : [z | xBC | dt] = Win . n
               xBC_t = silu(b_c + sum_k w_c[k] * xBC_{t-3+k})      4 taps
               [xs | B | C] = xBC_t
               dt = softplus(dt + dt_bias); a = exp(dt * A), A = -exp(A_log)
               S_t = a . S_{t-1} + (dt . xs) (x) B
               y  = S_t . C + D . xs
               out = Wout(RMSNorm_4096(y * silu(z)) * w_n)

No cache, no chunked form, no pages: the recurrence is a token-by-token
``lax.scan`` from a zero state over the whole sequence, the convolution an
explicit 4-tap sum over a left-padded sequence, attention every position
over the whole prefix under a causal mask. It takes the served model's
weights (random, drawn from the seed; ``ssm_in`` is the z, xBC and dt
projections side by side, ``mlp_in`` the gate and up ones, ``conv_w``
[tap, channel]) and nothing else from the program; the sizes that weight
shapes do not give come from the configuration's file, or from ``config``
(the CPU tests' small size).

Departures from the published model, which the served decoder makes too:
none in the equations; the state and the convolution's inputs are float32
at either precision (the configuration's ``assumed``).

``precision="highest"`` is the reference proper: float32 activations,
float32 matmuls. ``precision="default"`` is the same forward at the
precision the configuration states, as reference/mellum2-12b-a2.5b.py
defines it: bfloat16 activations (every matmul's result and every residual
rounded to bfloat16; norms, softmax, the time step, the decay, the state
and the gated norm in float32) at the chip's default matmul.
``harness/correct.py`` takes its rounding delta from their difference.

It computes layer by layer, so that one layer's upcast weights and one
sequence of scan outputs fit beside the served model on the chip.
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
from jax import lax


@functools.lru_cache(maxsize=1)
def published() -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def _rms(w, x, eps, act):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(act) * w.astype(act)


def _mm(a, b, act):
    return jnp.matmul(a.astype(act), b.astype(act), preferred_element_type=jnp.float32).astype(act)


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "scale", "eps", "act"))
def _attention(p, x, *, n_head, n_kv, scale, eps, act):
    act = jnp.dtype(act)
    b, s, _ = x.shape
    qkv = _mm(_rms(p["ln1"], x, eps, act), p["attn_qkv"], act)
    hd = p["attn_o"].shape[0] // n_head
    qw, kw = n_head * hd, n_kv * hd
    q = qkv[..., :qw].reshape(b, s, n_head, hd)
    k = jnp.repeat(qkv[..., qw : qw + kw].reshape(b, s, n_kv, hd), n_head // n_kv, axis=2)
    v = jnp.repeat(qkv[..., qw + kw :].reshape(b, s, n_kv, hd), n_head // n_kv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", pr, v.astype(jnp.float32)).astype(act).reshape(b, s, qw)
    return _mm(ctx, p["attn_o"], act)


@functools.partial(jax.jit, static_argnames=("heads", "state", "eps", "act"))
def _mamba(p, x, *, heads, state, eps, act):
    act = jnp.dtype(act)
    f32 = jnp.float32
    b, s, _ = x.shape
    d_inner = p["ssm_out"].shape[0]
    hd = d_inner // heads
    taps, width = p["conv_w"].shape
    zxd = _mm(_rms(p["ln1"], x, eps, act), p["ssm_in"], act)
    z, xbc, dt = zxd[..., :d_inner], zxd[..., d_inner : d_inner + width], zxd[..., d_inner + width :]
    # the depthwise causal convolution: tap k reads the input taps - 1 - k steps back
    padded = jnp.pad(xbc.astype(f32), ((0, 0), (taps - 1, 0), (0, 0)))
    conv = p["conv_b"].astype(f32)
    for k in range(taps):
        conv = conv + p["conv_w"][k].astype(f32) * padded[:, k : k + s]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :d_inner].reshape(b, s, heads, hd)
    bm, cm = xbc[..., d_inner : d_inner + state], xbc[..., d_inner + state :]
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))  # [b, s, h]
    a = jnp.exp(dt * -jnp.exp(p["A_log"].astype(f32)))

    def token(st, t):
        a_t, dt_t, xs_t, b_t, c_t = t
        st = a_t[:, :, None, None] * st + (dt_t[:, :, None] * xs_t)[..., None] * b_t[:, None, None, :]
        return st, jnp.sum(st * c_t[:, None, None, :], axis=-1)

    swap = lambda t: jnp.swapaxes(t, 0, 1)  # noqa: E731
    _, y = lax.scan(
        token, jnp.zeros((b, heads, hd, state), f32), tuple(swap(t) for t in (a, dt, xs, bm, cm))
    )
    y = swap(y) + p["D"].astype(f32)[:, None] * xs
    g = y.reshape(b, s, d_inner) * jax.nn.silu(z.astype(f32))
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return _mm(g.astype(act) * p["ssm_norm"].astype(act), p["ssm_out"], act)


@functools.partial(jax.jit, static_argnames=("eps", "act"))
def _mlp(p, x, *, eps, act):
    act = jnp.dtype(act)
    gu = _mm(_rms(p["ln2"], x, eps, act), p["mlp_in"], act)
    f = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], p["mlp_out"], act)


@functools.partial(jax.jit, static_argnames=("eps", "act", "scaling"))
def _head(ln_f, emb, x, *, eps, act, scaling):
    act = jnp.dtype(act)
    out = jnp.einsum("bsd,vd->bsv", _rms(ln_f, x, eps, act), emb.astype(act), preferred_element_type=jnp.float32)
    return out / scaling


def logits(params, ids, first: int, *, n_head: int, precision: str, config: dict | None = None):
    """ids [b, s] -> float32 logits [b, s - first, vocab]: row j is the
    distribution of the token AFTER position ``first + j``. ``config``: a
    dict with the published keys (default: the configuration's file)."""
    cfg = config or published()
    act = "float32" if precision == "highest" else "bfloat16"
    eps, res = float(cfg["rms_norm_eps"]), float(cfg["residual_multiplier"])
    # the precision is part of jit's trace context: each value gets its own trace
    with jax.default_matmul_precision(precision):
        emb = jnp.asarray(params["tok_emb"])
        x = (emb[jnp.asarray(ids, jnp.int32)].astype(jnp.float32) * float(cfg["embedding_multiplier"])).astype(act)
        for p, kind in zip(params["layers"], cfg["layer_types"]):
            if kind == "attention":
                mix = _attention(
                    p, x, n_head=n_head, n_kv=int(cfg["num_key_value_heads"]),
                    scale=float(cfg["attention_multiplier"]), eps=eps, act=act,
                )
            else:
                mix = _mamba(p, x, heads=int(cfg["mamba_n_heads"]), state=int(cfg["mamba_d_state"]), eps=eps, act=act)
            x = (x + mix * jnp.asarray(res, x.dtype)).astype(act)
            x = (x + _mlp(p, x, eps=eps, act=act) * jnp.asarray(res, x.dtype)).astype(act)
        return _head(params["ln_f"], emb, x[:, first:, :], eps=eps, act=act, scaling=float(cfg["logits_scaling"]))
