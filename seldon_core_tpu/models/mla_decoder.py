"""Latent-attention, shared-expert causal decoder — the generative tier's
fourth family (the DeepSeek-V3 block, as ``model_type: axk1`` publishes it).

What the block has, beside the three families before it:

- multi-head latent attention (MLA): queries through a low-rank pair
  (``q_a`` -> RMSNorm -> ``q_b``), each head's query a ``nope`` part and a
  rotated ``rope`` part; keys and values through ONE ``kv_rank``-wide
  normalised latent a token (``kv_a`` -> RMSNorm) and ONE rotated ``rope``
  key all heads share. The cache row of a token is ``[latent | rotated
  key]`` (576 numbers at the published sizes), stored in whole 128-lane
  tiles (``row_width``: 640, the tail zero), written before attention at the
  row's own position: the LATENT page kind (``decoder.kv_pool_zeros`` with
  ``kv_planes`` 1: one plane, no V plane, no per-head rows), copied, pinned
  and shared like any page. The
  attention itself, absorbed or expanded by the program's static chunk
  length, is ops/mla.py;
- YaRN on the rope dimensions (``moe_decoder.rope_inv_freq``'s ramp) with
  the family's score scale ``(nope + rope)^-0.5 * mscale^2``;
- ``dense_layers`` leading layers with a dense gated MLP, then layers of one
  SHARED expert every token takes plus routed experts under the sigmoid,
  group-limited, scaled gate (ops/moe.py ``route_sigmoid_grouped``: the
  family routes, ``moe_held_ffn`` takes the picks);
- an expert layer that holds ONE CHIP'S SHARE: the router keeps its
  ``experts`` outputs, the parameters hold ``experts_held`` of them from
  ``first_expert``, and a pick that lands elsewhere adds nothing
  (``moe_held_ffn``): the layer runs without its exchange.

Two more keys of the same block, as ``model_type: xing4_0`` publishes it:

- ``hc_mult`` > 1: the residual path is that many STREAMS wide
  (manifold-constrained hyper-connections, ops/mhc.py). The state between
  blocks is ``[hc_mult, n, m, hidden]`` (streams-major) from the embedding
  (replicated) to the final norm (summed); around each attention and feed-forward block a
  per-token map decides which mixture of the streams the block reads, how
  its output is written back to each and how the streams mix (a
  Sinkhorn-normalised ``hc_mult x hc_mult``). The block itself, its pre-norm
  and the cache row are unchanged: the streams are activations, not state.
  With ``hc_mult`` 1 there is no stream axis, no map and no sum: the
  programs are ``x + F(norm(x))``'s, text for text;
- ``gate_bias``: the router's per-expert bias selects and does not weigh
  (``topk_method: noaux_tc`` without groups; ops/moe.py
  ``route_sigmoid_biased``) where no groups are declared; the grouped gate
  where they are.

RMSNorm, the rotary helper, the expert forms, the sampler and the step /
chunk wrappers are the other families' (imported, not re-typed).

Served beside the plain rounds: a step and prefill chunks that read the latent
plane in place (ops/mla.py ``mla_decode_attention`` and
``mla_chunk_attention``, where ``decode_programs._step_attn_kernel`` chooses
them). Not served: speculation, a decode mesh, the
int8 pool, the KV tiers and prefix export (each refuses by name,
``decoder.require_served``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import types

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.decoder import (
    SCOPE_ATTN,
    SCOPE_ATTN_OUT,
    SCOPE_EMBED,
    SCOPE_LM_HEAD,
    SCOPE_MLP,
    SCOPE_QKV,
    FamilyNotServed,
    _paged_write_latent,
    counted_programs,
    kv_pool_zeros,
    paged_greedy_generate,
)
from seldon_core_tpu.models.moe_decoder import SCOPE_ROPE, _rms, _rope, rope_inv_freq
from seldon_core_tpu.ops import mhc
from seldon_core_tpu.ops.mla import (
    SCOPE_MLA_CORE,
    absorb_short,
    expand_cheaper,
    kernel_runs,
    kernel_takes,
    mla_paged_attention,
    pages_fetched,
)
from seldon_core_tpu.ops.moe import (
    HELD_COUNTERS,
    N_HELD_COUNTERS,
    SCOPE_DENSE_MLP,
    SCOPE_MOE_COMBINE,
    SCOPE_SHARED_EXPERT,
    gated_mlp,
    moe_held_ffn,
    route_sigmoid_biased,
    route_sigmoid_grouped,
)

# device scopes this family adds, each nested under a decoder.PAGED_SCOPES
# name so readers of those still see the time: ``qkv/mla_q``, ``qkv/mla_kv``,
# ``qkv/rope``, ``attn/mla_absorb|mla_core|mla_expand`` (ops/mla.py),
# ``mlp/moe_*``, ``mlp/shared_expert``, ``mlp/dense`` (ops/moe.py)
SCOPE_MLA_Q = "mla_q"  # q_a, its norm, q_b
SCOPE_MLA_KV = "mla_kv"  # kv_a and the latent's norm
# with ``hc_mult`` > 1 also ``qkv/mhc_map``, ``qkv/mhc_pre`` and
# ``attn_out/mhc_post`` round the attention block, ``mlp/mhc_map``,
# ``mlp/mhc_pre`` and ``mlp/mhc_post`` round the feed-forward one (ops/mhc.py)

# the stream maps' random parameters (``init_mla_decoder``; ops/mhc.py
# ``init_maps`` sets ``alpha`` from them): the std of the DYNAMIC part of each
# map's logits, whatever the width, and the diagonal of ``b_res``. H_pre and
# H_post move over most of their range from token to token; H_res's logits
# move by 0.24 round a diagonal of 1.5 (mean H_res[i, i] 0.59). Sinkhorn's
# rate is the square of its limit's second singular value: over 4 M seeded
# maps 20 iterations leave 2 ppm at the worst with these two numbers, 53 ppm
# with a diagonal of 2.0 and 1,100 with 2.0 and a std of 0.4 (numpy,
# float32; PERF.md section 6, PR 43)
HC_LOGIT_STD = (1.2, 1.2, 0.24)  # pre, post, res
HC_RES_DIAG = 1.5
# the selection bias's std, as the short-convolution family draws its gate's
# (models/conv_decoder.py EXPERT_BIAS_STD): about three gaps between
# neighbouring sorted scores of 64
GATE_BIAS_STD = 0.05


@dataclasses.dataclass(frozen=True)
class MLADecoderConfig:
    """The published keys of a latent-attention decoder (zoo://mla_decoder)."""

    vocab: int = 512
    hidden: int = 64
    layers: int = 3
    heads: int = 4
    q_rank: int = 24  # q_lora_rank
    kv_rank: int = 16  # kv_lora_rank: the latent
    nope_dim: int = 8  # qk_nope_head_dim
    rope_dim: int = 4  # qk_rope_head_dim
    v_dim: int = 8  # v_head_dim
    dense_layers: int = 1  # first_k_dense_replace
    dense_ffn: int = 96  # intermediate_size
    ffn: int = 32  # ONE expert's width (routed or shared)
    experts: int = 16  # the router's width: every expert of the deployment
    experts_held: int = 16  # how many of them this chip's parameters hold ...
    first_expert: int = 0  # ... from this one
    experts_per_tok: int = 4
    n_group: int = 4
    topk_group: int = 2
    routed_scale: float = 2.5
    rope_theta: float = 10000.0
    yarn_factor: float = 32.0
    yarn_original: int = 16
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    max_len: int = 131072
    gate_bias: bool = False  # topk_method noaux_tc: a per-expert bias selects; no groups then (n_group 0)
    hc_mult: int = 1  # residual streams; 1: x + F(norm(x))
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0  # mhc_h_res_clamp_max = -mhc_h_res_clamp_min

    def __post_init__(self):
        if self.rope_dim % 2:
            raise ValueError(f"rope_dim={self.rope_dim} must be even (rotary pairs)")
        if self.gate_bias:  # the biased gate has no group step: no groups are declared
            if self.n_group or self.topk_group or not 1 <= self.experts_per_tok <= self.experts:
                raise ValueError(f"gate_bias with n_group={self.n_group}, topk_group={self.topk_group} (0 and 0: no "
                                 f"groups), experts_per_tok={self.experts_per_tok} of {self.experts}")
        elif self.experts % self.n_group or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(f"{self.experts} experts in n_group={self.n_group}, topk_group={self.topk_group}")
        elif not 1 <= self.experts_per_tok <= self.topk_group * (self.experts // self.n_group):
            raise ValueError(f"experts_per_tok={self.experts_per_tok} of {self.topk_group} groups kept")
        if not 0 <= self.first_expert <= self.experts - self.experts_held or self.experts_held < 1:
            raise ValueError(f"experts [{self.first_expert}, +{self.experts_held}) of {self.experts}")
        if not 0 <= self.dense_layers <= self.layers:
            raise ValueError(f"dense_layers={self.dense_layers} of layers={self.layers}")
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 1:
            raise ValueError(f"hc_mult={self.hc_mult}, hc_sinkhorn_iters={self.hc_sinkhorn_iters}")

    @property
    def row_width(self) -> int:
        """A token's cache row as the plane stores it: ``kv_rank + rope_dim``
        numbers in whole 128-lane tiles. The chip's (8, 128) tiling pads a
        576-wide row to 640 lanes in memory either way; asked for 576, its
        compiler avoids the padding by laying the plane out PAGES-minor, and
        a step then copies the whole plane twice (in, and back out in the
        other layout: compiled for a described v5e, PERF.md section 6, PR 37)."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    @property
    def score_scale(self) -> float:
        """(nope + rope)^-0.5 * mscale^2, mscale = 0.1 * mscale_all_dim *
        ln(factor) + 1: YaRN's attention factor lands on the scores, since
        ``mscale == mscale_all_dim`` leaves cos and sin themselves alone."""
        m = 0.1 * self.mscale_all_dim * math.log(self.yarn_factor) + 1.0 if self.yarn_factor > 1 else 1.0
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m

    @functools.cached_property
    def inv_freq(self):
        """YaRN's frequencies over the rope dimensions: the sparse-expert
        family's ramp (``rope_inv_freq``) with this family's numbers."""
        return rope_inv_freq(
            types.SimpleNamespace(
                head_dim=self.rope_dim, rope_theta=self.rope_theta, yarn_factor=self.yarn_factor,
                yarn_original=self.yarn_original, yarn_beta_fast=self.yarn_beta_fast, yarn_beta_slow=self.yarn_beta_slow,
            ),
            full=True,
        )


def init_mla_decoder(cfg: MLADecoderConfig, seed: int = 0, dtype=jnp.bfloat16) -> dict:
    """Random weights drawn ON THE DEVICE in ``dtype``, layer by layer, as
    ``init_moe_decoder`` draws them and for its reasons: std 0.02, norms 1,
    embedding rows std 1 (a token's identity, not the shared context's
    average, decides its routing), the chip's own bit generator, layer i's key
    fold_in(seed, i). The router is drawn like the rest: over a normalised
    input its logits have std 0.02 * sqrt(hidden) (1.7 at 7168), so the
    sigmoid scores spread over (0, 1) and are not all one half. The routed
    experts drawn are the ``experts_held`` this chip holds. With
    ``gate_bias`` the selection bias is normal(0, ``GATE_BIAS_STD``), float32;
    with ``hc_mult`` > 1 each layer gets its two blocks' stream maps
    (``hc_attn``, ``hc_mlp``: ops/mhc.py ``init_maps`` with ``HC_LOGIT_STD`` and
    ``HC_RES_DIAG``). Both from keys folded out of the layer's, so the other
    weights are the draws they were."""
    root = jax.random.key(int(seed), impl="rbg")
    c = cfg

    def draw(key, shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def attention(ks):
        return {
            "ln1": jnp.ones((c.hidden,), dtype),
            "q_a": draw(ks[0], (c.hidden, c.q_rank)),
            "q_norm": jnp.ones((c.q_rank,), dtype),
            "q_b": draw(ks[1], (c.q_rank, c.heads, c.nope_dim + c.rope_dim)),  # a head's columns: [nope | rope]
            "kv_a": draw(ks[2], (c.hidden, c.kv_rank + c.rope_dim)),
            "kv_norm": jnp.ones((c.kv_rank,), dtype),
            "kv_b": draw(ks[3], (c.kv_rank, c.heads, c.nope_dim + c.v_dim)),  # a head's columns: [Wuk | Wuv]
            "attn_o": draw(ks[4], (c.heads * c.v_dim, c.hidden)),
            "ln2": jnp.ones((c.hidden,), dtype),
        }

    def streams(key):
        if c.hc_mult == 1:
            return {}
        return {
            name: mhc.init_maps(jax.random.fold_in(key, 1000 + b), c.hc_mult, c.hidden, dtype,
                                logit_std=HC_LOGIT_STD, res_diag=HC_RES_DIAG)
            for b, name in enumerate(("hc_attn", "hc_mlp"))
        }

    @jax.jit
    def dense_layer(key):
        ks = jax.random.split(key, 7)
        mlp = {"gate_up": draw(ks[5], (c.hidden, 2 * c.dense_ffn)), "down": draw(ks[6], (c.dense_ffn, c.hidden))}
        return {**attention(ks), "mlp": mlp, **streams(key)}

    @jax.jit
    def expert_layer(key):
        ks = jax.random.split(key, 10)
        moe = {
            "router": draw(ks[5], (c.hidden, c.experts)),
            "gate_up": draw(ks[6], (c.experts_held, c.hidden, 2 * c.ffn)),
            "down": draw(ks[7], (c.experts_held, c.ffn, c.hidden)),
            "shared_gate_up": draw(ks[8], (c.hidden, 2 * c.ffn)),
            "shared_down": draw(ks[9], (c.ffn, c.hidden)),
        }
        if c.gate_bias:
            moe["router_bias"] = jax.random.normal(jax.random.fold_in(key, 999), (c.experts,), jnp.float32) * GATE_BIAS_STD
        return {**attention(ks), "moe": moe, **streams(key)}

    @jax.jit
    def ends(key):
        k_emb, k_head = jax.random.split(key)
        return {
            "tok_emb": draw(k_emb, (c.vocab, c.hidden), 1.0),
            "ln_f": jnp.ones((c.hidden,), dtype),
            "lm_head": draw(k_head, (c.hidden, c.vocab)),
        }

    params = ends(jax.random.fold_in(root, 1 << 20))
    params["layers"] = [
        (dense_layer if i < c.dense_layers else expert_layer)(jax.random.fold_in(root, i)) for i in range(c.layers)
    ]
    return params


def _route(cfg: MLADecoderConfig, moe: dict, h):
    """The configuration's gate: the bias-selected one where a bias is
    declared, the group-limited one where groups are."""
    if cfg.gate_bias:
        return route_sigmoid_biased(moe["router"], moe["router_bias"], h, cfg.experts_per_tok, cfg.routed_scale)
    return route_sigmoid_grouped(moe["router"], h, cfg.experts_per_tok, cfg.n_group, cfg.topk_group, cfg.routed_scale)


def _streams_in(cfg: MLADecoderConfig, maps: dict, x, valid):
    """What a block reads of the ``hc_mult``-stream state x[S, n, m, d]: (u[n,
    m, d], (H_post, H_res) for ``mhc.post_mix``, the maps' ``mhc_resid_ppm``)."""
    h_pre, h_post, h_res = mhc.stream_maps(
        maps, x, iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps, clamp=cfg.hc_res_clamp, rms_eps=cfg.rms_eps
    )
    with jax.named_scope(mhc.SCOPE_MHC_MAP):
        resid = mhc.doubly_stochastic_residual(h_res, valid)
    return mhc.pre_mix(x, h_pre), (h_post, h_res), resid


def _layer(cfg: MLADecoderConfig, li: int, p, x, pool, bt, positions, counts, valid, n_keys, read, runs, interpret):
    """One layer over the latent plane: x[n, m, d] with row i's query j at
    positions[i] + j (x[S, n, m, d] with ``hc_mult`` S > 1: each block then
    reads ``u``, the maps' mixture of the streams, and writes its output
    back through them, ops/mhc.py). The new rows ``[latent | rotated key]``
    scatter through the block tables first, attention reads them back with
    the cached ones
    (write-then-read, as in every family): through ops/mla.py's kernels where
    ``_forward`` found the program set to have chosen them (``runs``, with
    ``read`` [n] the leading queries of a row somebody reads; ``interpret``:
    under the Pallas interpreter), else the walk. Returns (x, pool,
    counters[6]: zeros for a dense layer, the two blocks' larger
    ``mhc_resid_ppm`` or None)."""
    c = cfg
    hc = c.hc_mult > 1
    n, m = x.shape[-3:-1]
    q_pos = positions[:, None] + jnp.arange(m, dtype=positions.dtype)[None, :]  # [n, m]
    resid = None
    with jax.named_scope(SCOPE_QKV):
        u = x
        if hc:
            u, mix, resid = _streams_in(c, p["hc_attn"], x, valid)
        h = _rms(p["ln1"], u, c.rms_eps)
        with jax.named_scope(SCOPE_MLA_Q):
            q = _rms(p["q_norm"], h @ p["q_a"].astype(x.dtype), c.rms_eps)
            q = jnp.einsum("nmq,qhd->nmhd", q, p["q_b"].astype(x.dtype))  # [n, m, H, nope + rope]
        with jax.named_scope(SCOPE_MLA_KV):
            kv = h @ p["kv_a"].astype(x.dtype)  # [n, m, rank + rope]
            latent = _rms(p["kv_norm"], kv[..., : c.kv_rank], c.rms_eps)
        with jax.named_scope(SCOPE_ROPE):
            q_rope = _rope(q[..., c.nope_dim :], q_pos, c.inv_freq, 1.0)
            k_rope = _rope(kv[..., None, c.kv_rank :], q_pos, c.inv_freq, 1.0)[:, :, 0]  # ONE key for all heads
    tail = jnp.zeros((n, m, c.row_width - c.kv_rank - c.rope_dim), x.dtype)
    pool = _paged_write_latent(pool, li, jnp.concatenate([latent, k_rope, tail], axis=-1), bt, positions, counts)
    with jax.named_scope(SCOPE_ATTN):
        sizes = dict(rank=c.kv_rank, nope=c.nope_dim, rope=c.rope_dim, v_dim=c.v_dim)
        ctx = mla_paged_attention(
            q[..., : c.nope_dim], q_rope, pool[0], li, bt, q_pos, n_keys, p["kv_b"], scale=c.score_scale,
            expand=expand_cheaper(m, **sizes), short=absorb_short(**sizes),
            live=None if counts is None else jnp.max(counts), runs=runs, counts=read, interpret=interpret,
        )
    with jax.named_scope(SCOPE_ATTN_OUT):
        o = ctx @ p["attn_o"].astype(x.dtype)
        x = mhc.post_mix(x, o, *mix) if hc else x + o
    with jax.named_scope(SCOPE_MLP):
        u = x
        if hc:
            u, mix, r2 = _streams_in(c, p["hc_mlp"], x, valid)
            with jax.named_scope(mhc.SCOPE_MHC_MAP):
                resid = jnp.maximum(resid, r2)
        h = _rms(p["ln2"], u, c.rms_eps).reshape(n * m, -1)
        if "mlp" in p:
            with jax.named_scope(SCOPE_DENSE_MLP):
                y, cnt = gated_mlp(p["mlp"]["gate_up"], p["mlp"]["down"], h), jnp.zeros((N_HELD_COUNTERS,), jnp.int32)
        else:
            real = valid.reshape(-1)
            gates, experts = _route(c, p["moe"], h)
            y, cnt = moe_held_ffn(p["moe"], h, gates, experts, c.first_expert, real)
            with jax.named_scope(SCOPE_SHARED_EXPERT):
                y = y + gated_mlp(p["moe"]["shared_gate_up"], p["moe"]["shared_down"], h)
        y = y.reshape(u.shape)
        x = mhc.post_mix(x, y, *mix) if hc else x + y
    return x, pool, cnt, resid


def _forward(cfg, params, pool, bt, tokens, positions, counts=None, rows=None, pick=None, attn_kernel=""):
    """Shared body of the paged programs, with ``moe_decoder._forward``'s
    arguments: tokens[n, m], row i's query j at positions[i] + j; ``counts``
    (chunk rounds), ``rows`` (the step's generating slots), ``pick`` (the
    head's one query a row). ``attn_kernel`` (static; "" | "mosaic" |
    "interpret": ``decode_programs._step_attn_kernel``'s answer) lets a
    dispatch read the plane through ops/mla.py's kernels, the step's for ONE
    query a row and the chunk's for more (``kernel_takes``); "" walks. With ``hc_mult`` > 1 the state between the
    embedding and the final norm is [hc_mult, n, m, d]; ``hidden`` is the
    streams' sum. Returns (logits[n, m or 1, vocab] float32, hidden[n, m, d],
    pool, counters[9 or 10] int32: ``MLADecoder.frame_counters``)."""
    n, m = tokens.shape
    valid = jnp.ones((n, m), bool)
    last = jnp.full((n,), m, positions.dtype)  # queries a row really has
    if counts is not None:
        valid &= jnp.arange(m)[None, :] < counts[:, None]
        last = counts.astype(positions.dtype)
    if rows is not None:
        valid &= rows[:, None]
        last = jnp.where(rows, last, 0)
    # the keys a row's last real query sees; a row nobody reads walks one block
    n_keys = jnp.where(last > 0, positions + last, 1)
    with jax.named_scope(SCOPE_ATTN), jax.named_scope(SCOPE_MLA_CORE):
        ps = pool[0].shape[2]
        runs = kernel_runs(attn_kernel, m, cfg.kv_rank, cfg.heads, bt, n_keys, ps)  # every layer's kernel walks the same tables
        if runs is None:
            fetched = jnp.zeros((2,), jnp.int32)  # the walk fetches nothing through the kernel
        else:
            fetched = pages_fetched(n_keys, runs, last > 0, ps, bt.shape[1])
    with jax.named_scope(SCOPE_EMBED):
        x = jnp.asarray(params["tok_emb"])[tokens]  # [n, m, d]
        if cfg.hc_mult > 1:
            x = mhc.spread(x, cfg.hc_mult)
    cnt = jnp.zeros((N_HELD_COUNTERS,), jnp.int32)
    resid = []
    for li, lp in enumerate(params["layers"]):
        x, pool, c, r = _layer(cfg, li, lp, x, pool, bt, positions, counts, valid, n_keys, last, runs, attn_kernel == "interpret")
        with jax.named_scope(SCOPE_MLP), jax.named_scope(SCOPE_MOE_COMBINE):
            cnt = cnt + c
        if r is not None:
            resid.append(r)
    with jax.named_scope(SCOPE_LM_HEAD):
        if cfg.hc_mult > 1:  # the streams summed, then the final norm
            x = mhc.merged(x)
        top = x if pick is None else jnp.take_along_axis(x, pick[:, None, None], axis=1)
        logits = jnp.matmul(
            _rms(params["ln_f"], top, cfg.rms_eps), params["lm_head"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        # rows are every layer's own count: reported once, not summed; the
        # latent rows attended over are one layer's (every layer reads as many)
        cnt = cnt.at[0].set(jnp.sum(valid, dtype=jnp.int32))
        ctx_rows = jnp.sum(jnp.where(last > 0, n_keys, 0), dtype=jnp.int32)
        counted = [cnt, ctx_rows[None], fetched] + ([jnp.max(jnp.stack(resid))[None]] if resid else [])
    return logits, x, pool, jnp.concatenate(counted)


@dataclasses.dataclass(frozen=True)
class MLADecoder:
    """The family object of one configuration: what the decode scheduler
    asks of a family (``decoder.GPT2Decoder``'s docstring has the list), with
    the configuration's static sizes bound. Hashable: equal configurations
    share compiled programs."""

    cfg: MLADecoderConfig

    name = "mla"
    # what the programs' readback carries after the tokens (FlightFrame
    # fields): the routing over the experts HELD, the picks of real rows that
    # landed on one, the layer calls that ran the grouped form and ran it
    # compact, and the latent rows the dispatch's live rows attended
    # over (each row's keys, summed; one layer's), and where a kernel ran (the
    # step's or the chunk's) the pages it fetched for them and those that came
    # in run DMAs;
    # with ``hc_mult`` > 1 also the stream maps' canary, ``mhc_resid_ppm``: the
    # largest |row or column sum - 1| of any H_res of the dispatch's real rows,
    # x 1e6 (the scheduler SUMS a round's dispatches: a round of one dispatch,
    # as a steady step round is, carries that dispatch's own)
    @property
    def frame_counters(self) -> tuple:
        base = ("moe_rows", "moe_experts_hit", "moe_load_max", *HELD_COUNTERS, "mla_ctx_rows",
                "mla_pages_read", "mla_run_pages")
        return base + (("mhc_resid_ppm",) if self.cfg.hc_mult > 1 else ())

    # beside the plain rounds: a step and chunks that read the plane in place (ops/mla.py's kernels)
    serves = frozenset({"attn_kernel"})
    state_init = None  # no recurrent state: latent pages only

    def decoder_dims(self, params: dict) -> dict:
        if "lm_head" not in params or "kv_b" not in params["layers"][0]:
            raise FamilyNotServed("not a latent-attention decoder's parameters (models/mla_decoder.py layout)")
        c = self.cfg
        moe = params["layers"][-1].get("moe")
        if (c.hc_mult > 1) != ("hc_attn" in params["layers"][0]) or (moe and c.gate_bias != ("router_bias" in moe)):
            raise FamilyNotServed(f"parameters and configuration disagree on hc_mult={c.hc_mult} / gate_bias={c.gate_bias}")
        return {
            "layers": len(params["layers"]), "kv_layers": len(params["layers"]), "heads": c.heads,
            # the latent page kind: ONE plane, one row of latent + rotated key a token, in whole lane tiles
            "kv_planes": 1, "kv_heads": 1, "head_dim": c.row_width,
            "hidden": c.hidden, "q_width": c.heads * (c.nope_dim + c.rope_dim),
            "vocab": params["tok_emb"].shape[0], "max_len": c.max_len,
        }

    def paged_kv_init(self, params, n_pages, page_size, dtype=jnp.float32, kv_dtype=""):
        return kv_pool_zeros(self.decoder_dims(params), n_pages, page_size, dtype, kv_dtype)

    def paged_forward(self, params, pool, bt, tokens, positions, counts=None, rows=None, pick=None, attn_kernel=""):
        return _forward(self.cfg, params, pool, bt, tokens, positions, counts, rows, pick, attn_kernel)

    @functools.lru_cache(maxsize=None)
    def fused_programs(self, attn_kernel: str = ""):
        """This family's step and chunk bodies (``decoder.counted_programs``);
        with ``attn_kernel`` the step takes ops/mla.py's step kernel and a
        chunk, whatever its length, the chunk's (``chunk_attn``). Cached:
        equal configurations share compiled programs."""
        return counted_programs(functools.partial(self.paged_forward, attn_kernel=attn_kernel))

    def chunk_attn(self, attn_kernel: str, c: int) -> str:
        """How the chunk program of ``c`` tokens a row reads the plane under
        ``attn_kernel``: "kernel" (``mla_chunk_attention``) or "walk". Static
        a program: what its dispatches' annotation and frames say."""
        return "kernel" if kernel_takes(attn_kernel, c, self.cfg.kv_rank, self.cfg.heads) else "walk"

    def generate(self, params, ids, max_new_tokens: int):
        """The fused fallback apply (``decoder.paged_greedy_generate``) over a
        private latent plane."""
        return paged_greedy_generate(
            functools.partial(self.paged_forward, params),
            lambda n_pages, ps: self.paged_kv_init(params, n_pages, ps, params["tok_emb"].dtype), ids, max_new_tokens,
        )


@functools.lru_cache(maxsize=None)
def mla_family(cfg: MLADecoderConfig) -> MLADecoder:
    return MLADecoder(cfg)
