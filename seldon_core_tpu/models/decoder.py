"""GPT-style causal decoder with KV-cache generation — the generative
serving tier.

Greenfield vs the reference (SURVEY §2: classifiers/regressors only); the
TPU-native pieces are exactly the ones a naive port gets wrong:

- ONE compiled program per (batch bucket, prompt length): prefill computes
  every prompt position's K/V in one causal-attention pass (the same
  length-adaptive policy BERT serving uses — naive < 1024, blockwise, the
  Pallas causal kernel on TPU at long prompts), writes them into a
  [b, h, max_ctx, d] cache, then a ``lax.scan`` runs ``max_new_tokens``
  greedy steps — static shapes throughout, no Python loop, no recompiles.
- per-step attention is one [b, h, 1, d] query against the cache with a
  position mask (cache slots beyond the current length contribute zero
  mass), K/V written in place via ``lax.dynamic_update_slice``.
- outputs are int32 token ids (the serving wire keeps integer dtypes
  exact; float32 readback holds every id < 2^24).

Serving contract: apply(params, ids[b, s]) -> [b, s + max_new_tokens]
(prompt echoed, generated ids appended) — max_new_tokens is a DEPLOYMENT
parameter (static at trace time), the zoo entry is ``tiny_gpt``.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from seldon_core_tpu.ops.gqa_decode import (
    chunk_reads,
    gqa_chunk_attention,
    gqa_decode_attention,
    pages_fetched,
    step_reads,
)
from seldon_core_tpu.ops.paged_attention import paged_attention_decode, slot_lengths


def _dense(rng: np.random.Generator, n_in: int, n_out: int) -> dict:
    scale = (2.0 / (n_in + n_out)) ** 0.5
    return {
        "w": (rng.standard_normal((n_in, n_out)) * scale).astype(np.float32),
        "b": np.zeros((n_out,), np.float32),
    }


def _ln_init(d: int) -> dict:
    return {"scale": np.ones((d,), np.float32), "bias": np.zeros((d,), np.float32)}


def _ln(p: dict, x: jax.Array) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + jnp.asarray(1e-5, x.dtype))
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def init_decoder(
    seed: int = 0,
    vocab: int = 512,
    hidden: int = 128,
    layers: int = 2,
    ffn: int = 256,
    max_len: int = 128,
    resid_scale: float = 1.0,
) -> dict:
    """``resid_scale`` scales the residual-branch output projections
    (attn_out, mlp_out) after drawing them — GPT-2/µP-style depth-scaled
    init. At 1.0 (default) the params are bit-identical to earlier builds.
    Scaling happens AFTER the rng draws, so two builds that differ only in
    ``layers`` share their embedding + leading-layer weights verbatim (the
    generator stream is positional): a fewer-layers build IS the deeper
    build's prefix — what makes a seed-shared truncated draft model a
    faithful early-exit approximation of its target for speculative
    decoding (serving/decode_scheduler.py)."""
    heads = _heads_for(hidden)
    if hidden % heads:
        raise ValueError(
            f"hidden={hidden} not divisible by its derived head count "
            f"{heads} (GPT-2's head_dim-64 convention, _heads_for) — a "
            "cryptic reshape error at first trace otherwise"
        )
    rng = np.random.default_rng(seed)

    def _resid(p: dict) -> dict:
        if resid_scale != 1.0:
            p["w"] = (p["w"] * np.float32(resid_scale)).astype(np.float32)
        return p

    return {
        "tok_emb": (rng.standard_normal((vocab, hidden)) * 0.02).astype(np.float32),
        "pos_emb": (rng.standard_normal((max_len, hidden)) * 0.02).astype(np.float32),
        "layers": [
            {
                "ln1": _ln_init(hidden),
                "qkv": _dense(rng, hidden, 3 * hidden),
                "attn_out": _resid(_dense(rng, hidden, hidden)),
                "ln2": _ln_init(hidden),
                "mlp_in": _dense(rng, hidden, ffn),
                "mlp_out": _resid(_dense(rng, ffn, hidden)),
            }
            for _ in range(layers)
        ],
        "ln_f": _ln_init(hidden),
        # lm head reuses tok_emb^T (weight tying, the standard decoder move)
    }


def _heads_for(hidden: int) -> int:
    """GPT-2's convention, and this family's only: heads of 64 (gpt2 768/12,
    gpt2-large 1280/20), two heads below 64. A family whose head size is
    its own states it (models/moe_decoder.py ``MoEDecoderConfig``)."""
    return max(1, hidden // 64) if hidden >= 64 else 2


def _heads(params: dict) -> int:
    return _heads_for(params["layers"][0]["qkv"]["w"].shape[0])


def _split_heads(t: jax.Array, h: int) -> jax.Array:
    b, s, d = t.shape
    return t.reshape(b, s, h, d // h).transpose(0, 2, 1, 3)


def _merge_heads(t: jax.Array) -> jax.Array:
    b, h, s, hd = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


def _causal_attention(q, k, v):
    """Prefill attention: the shared backend-adaptive causal policy
    (ops/attention.causal_attention_auto — Pallas kernel on TPU at long
    prompts, pure JAX elsewhere)."""
    from seldon_core_tpu.ops.attention import causal_attention_auto

    return causal_attention_auto(q, k, v)


def _layer_prefill(p, x, h):
    """Returns (x_out, k[b,h,s,hd], v[b,h,s,hd]) for the cache."""
    normed = _ln(p["ln1"], x)
    qkv = normed @ p["qkv"]["w"].astype(x.dtype) + p["qkv"]["b"].astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q, k, v = _split_heads(q, h), _split_heads(k, h), _split_heads(v, h)
    ctx = _merge_heads(_causal_attention(q, k, v))
    x = x + ctx @ p["attn_out"]["w"].astype(x.dtype) + p["attn_out"]["b"].astype(x.dtype)
    normed2 = _ln(p["ln2"], x)
    hdn = jax.nn.gelu(
        normed2 @ p["mlp_in"]["w"].astype(x.dtype) + p["mlp_in"]["b"].astype(x.dtype),
        approximate=False,
    )
    x = x + hdn @ p["mlp_out"]["w"].astype(x.dtype) + p["mlp_out"]["b"].astype(x.dtype)
    return x, k, v


def _layer_step(p, x, cache_k, cache_v, pos, h):
    """One token through one layer against the cache. x: [b, 1, d]; cache
    [b, h, max_ctx, hd]; pos: scalar current position (tokens < pos are
    valid). Returns (x_out, cache_k, cache_v) with the new K/V written at
    ``pos``."""
    normed = _ln(p["ln1"], x)
    qkv = normed @ p["qkv"]["w"].astype(x.dtype) + p["qkv"]["b"].astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = _split_heads(q, h)  # [b, h, 1, hd]
    k = _split_heads(k, h)
    v = _split_heads(v, h)
    cache_k = lax.dynamic_update_slice(cache_k, k, (0, 0, pos, 0))
    cache_v = lax.dynamic_update_slice(cache_v, v, (0, 0, pos, 0))
    # masked dot attention over the whole (static) cache: slots > pos get
    # -inf, so their mass is exactly zero — no dynamic shapes
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), cache_k.astype(jnp.float32)) * scale
    valid = jnp.arange(cache_k.shape[2]) <= pos  # [max_ctx]
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    p_attn = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", p_attn, cache_v.astype(jnp.float32))
    ctx = _merge_heads(ctx.astype(x.dtype))
    x = x + ctx @ p["attn_out"]["w"].astype(x.dtype) + p["attn_out"]["b"].astype(x.dtype)
    normed2 = _ln(p["ln2"], x)
    hdn = jax.nn.gelu(
        normed2 @ p["mlp_in"]["w"].astype(x.dtype) + p["mlp_in"]["b"].astype(x.dtype),
        approximate=False,
    )
    x = x + hdn @ p["mlp_out"]["w"].astype(x.dtype) + p["mlp_out"]["b"].astype(x.dtype)
    return x, cache_k, cache_v


def _embed(params, ids, pos_offset: int = 0):
    # jnp.asarray: params may be host numpy on the direct (un-device_put)
    # call path, and numpy arrays cannot be indexed by tracers
    h = jnp.asarray(params["tok_emb"])[ids]
    return h + jnp.asarray(params["pos_emb"])[
        pos_offset : pos_offset + ids.shape[1]
    ][None, :, :]


def _logits(params, x):
    x = _ln(params["ln_f"], x)
    return x @ jnp.asarray(params["tok_emb"]).T.astype(x.dtype)  # weight-tied head


def generate(params: dict, ids: jax.Array, max_new_tokens: int) -> jax.Array:
    """Greedy decode: ids[b, s] int -> [b, s + max_new_tokens] int32.

    Prefill fills the KV caches in one causal pass; a lax.scan then runs
    ``max_new_tokens`` single-token steps. max_ctx = s + max_new_tokens is
    static, so one XLA program serves every request of this bucket."""
    ids = ids.astype(jnp.int32)
    b, s = ids.shape
    heads = _heads(params)
    max_ctx = s + max_new_tokens
    max_len = params["pos_emb"].shape[0]
    if max_ctx > max_len:
        raise ValueError(
            f"prompt {s} + max_new_tokens {max_new_tokens} exceeds the "
            f"position table ({max_len}) — raise max_len"
        )

    # ---- prefill
    x = _embed(params, ids)
    caches = []
    hd = x.shape[-1] // heads
    for lp in params["layers"]:
        x, k, v = _layer_prefill(lp, x, heads)
        ck = jnp.zeros((b, heads, max_ctx, hd), x.dtype)
        cv = jnp.zeros((b, heads, max_ctx, hd), x.dtype)
        ck = lax.dynamic_update_slice(ck, k, (0, 0, 0, 0))
        cv = lax.dynamic_update_slice(cv, v, (0, 0, 0, 0))
        caches.append((ck, cv))
    first_tok = jnp.argmax(_logits(params, x[:, -1:, :]), axis=-1)  # [b, 1]

    # ---- decode scan: carry = (token, pos, caches)
    cache_k = jnp.stack([c[0] for c in caches])  # [L, b, h, max_ctx, hd]
    cache_v = jnp.stack([c[1] for c in caches])

    def step(carry, _):
        tok, pos, ck_all, cv_all = carry
        x = _embed_one(params, tok, pos)
        new_k, new_v = [], []
        for li, lp in enumerate(params["layers"]):
            x, ck, cv = _layer_step(lp, x, ck_all[li], cv_all[li], pos, heads)
            new_k.append(ck)
            new_v.append(cv)
        nxt = jnp.argmax(_logits(params, x), axis=-1)  # [b, 1]
        return (nxt, pos + 1, jnp.stack(new_k), jnp.stack(new_v)), tok

    # max_new - 1 steps: each step consumes one already-chosen token and
    # chooses the next, and first_tok came from prefill — a full step for
    # the token after the last would be paid-for-then-discarded compute
    (last, _, _, _), toks = lax.scan(
        step, (first_tok, jnp.int32(s), cache_k, cache_v), None,
        length=max_new_tokens - 1,
    )
    # toks: the token CONSUMED by each step (first_tok first); `last` is
    # the final chosen token — together exactly max_new generated ids
    gen = jnp.concatenate(
        [toks[:, :, 0].T.reshape(b, -1), last], axis=1
    )
    return jnp.concatenate([ids, gen.astype(jnp.int32)], axis=1)


def _embed_one(params, tok: jax.Array, pos) -> jax.Array:
    """tok: [b, 1] -> [b, 1, d] with the position-``pos`` embedding."""
    h = jnp.asarray(params["tok_emb"])[tok]
    return h + lax.dynamic_slice_in_dim(
        jnp.asarray(params["pos_emb"]), pos, 1, axis=0
    )[None, :, :]


# --------------------------------------------------------------------------
# Continuous-batching building blocks (serving/decode_scheduler.py).
#
# The fused ``generate`` above runs one whole batch to completion inside a
# single lax.scan — the correctness oracle. The functions below split that
# program into the three pieces iteration-level scheduling needs:
#   prefill()      one causal pass over a prompt -> per-sequence K/V + the
#                  last-position logits (the first generated token's logits)
#   init_slot_cache  a STATIC [L, n_slots, h, max_ctx, hd] cache (the
#                  draft's; the target's K/V lives in pages, below)
#   decode_step()  one token for EVERY slot at per-slot positions — batch
#                  composition changes between steps without shape changes
#   sample_tokens  per-slot temperature/top-k sampling, greedy at temp<=0
#   draft_propose / speculative_accept
#                  draft-model speculation: k proposed tokens per slot and
#                  the acceptance rule of their one-dispatch verification
#                  (paged_verify_step)
# All shapes are static in (n_slots, max_ctx), so one XLA program per
# function serves every batch composition (zero recompiles after warmup).


class FamilyNotServed(ValueError):
    """A decode mechanism was asked of a decoder family that does not serve
    it, or parameters were handed to a family they do not belong to."""


def decoder_dims(params: dict) -> dict:
    """Static geometry the scheduler sizes its cache from. Every family
    says the same keys (models/moe_decoder.py ``MoEDecoder.decoder_dims``):
    the pool's token row is ``kv_heads * head_dim`` wide and the query
    ``q_width``; here, multi-head attention, both are the hidden size."""
    try:
        hidden = params["layers"][0]["qkv"]["w"].shape[0]
        max_len = params["pos_emb"].shape[0]
    except (KeyError, IndexError, TypeError) as e:
        raise FamilyNotServed(
            f"not a GPT-2 family decoder's parameters (models/decoder.py layout): no {e}"
        ) from None
    heads = _heads(params)
    return {
        "layers": len(params["layers"]),
        "kv_layers": len(params["layers"]),  # the layers that hold K/V pages: all of them here
        "heads": heads,
        "kv_heads": heads,
        "hidden": hidden,
        "head_dim": hidden // heads,
        "q_width": hidden,
        "ffn": params["layers"][0]["mlp_in"]["w"].shape[1],
        "vocab": params["tok_emb"].shape[0],
        "max_len": max_len,
    }


def prefill(params: dict, ids: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One causal pass over prompts ids[b, s] -> (logits[b, vocab],
    k[L, b, h, s, hd], v[L, b, h, s, hd]).

    Same math as the fused generate's prefill phase (shared _layer_prefill /
    causal-attention policy), but the K/V comes back to the caller to be
    scattered into slots instead of being written into a private cache."""
    ids = ids.astype(jnp.int32)
    heads = _heads(params)
    x = _embed(params, ids)
    ks, vs = [], []
    for lp in params["layers"]:
        x, k, v = _layer_prefill(lp, x, heads)
        ks.append(k)
        vs.append(v)
    logits = _logits(params, x[:, -1:, :])[:, 0, :]
    return logits, jnp.stack(ks), jnp.stack(vs)


def init_slot_cache(
    params: dict, n_slots: int, max_ctx: int, dtype=jnp.float32
) -> tuple[jax.Array, jax.Array]:
    """Zeroed slot KV cache pair, each [L, n_slots, heads, max_ctx, hd]."""
    d = decoder_dims(params)
    shape = (d["layers"], n_slots, d["heads"], max_ctx, d["head_dim"])
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _layer_step_slots(p, x, cache_k, cache_v, positions, h, counts=None, starts=None):
    """_layer_step generalized to PER-SLOT positions and m queries per
    slot. x: [n, m, d]; cache [n, h, max_ctx, hd]; positions: [n] — slot
    i's query j sits at positions[i] + j, writes its K/V there, and
    attends to cache entries <= positions[i] + j (the in-block causal
    mask: speculative query j sees the keys queries 0..j-1 of the same
    dispatch just wrote). The serving decode step is the m=1 case.

    ``counts`` (optional, [n]): per-slot WRITE masks for chunked prefill —
    slot i persists only its first counts[i] K/V entries and leaves the
    rest of its cache byte-identical (a select against the current block,
    so a counts-0 slot riding the static-shape dispatch mutates nothing).
    None keeps the unconditional m-wide write (decode/verify paths, where
    junk beyond a slot's limit lands ahead of its cursor by design).

    ``starts`` (optional, [n]): per-slot attention LOWER bound — cache
    entries before starts[i] are masked out. The feature draft uses this
    on warm (prefix-reuse) admissions: positions the target mapped from
    the prefix pool have no draft-side K/V (the draft cache is populated
    by the chunk rounds, which only compute the uncovered suffix), so the
    draft's window opens at the suffix instead of attending to zeroed
    rows. None keeps the full [0, pos] window (target paths — the pool
    is always complete there)."""
    normed = _ln(p["ln1"], x)
    qkv = normed @ p["qkv"]["w"].astype(x.dtype) + p["qkv"]["b"].astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = _split_heads(q, h)  # [n, h, m, hd]
    k = _split_heads(k, h)
    v = _split_heads(v, h)
    # per-slot scatter: vmap over the slot axis turns the per-sequence
    # dynamic_update_slice into one batched scatter — no host loop, no
    # per-slot programs; the m-wide K/V block lands at positions[i]..+m-1
    if counts is None:
        write = jax.vmap(lambda c, kk, pos: lax.dynamic_update_slice(c, kk, (0, pos, 0)))
        cache_k = write(cache_k, k, positions)
        cache_v = write(cache_v, v, positions)
    else:
        m_w = k.shape[2]

        def _masked(c, kk, pos, cnt):
            cur = lax.dynamic_slice(c, (0, pos, 0), kk.shape)
            blk = jnp.where((jnp.arange(m_w) < cnt)[None, :, None], kk, cur)
            return lax.dynamic_update_slice(c, blk, (0, pos, 0))

        write = jax.vmap(_masked)
        cache_k = write(cache_k, k, positions, counts)
        cache_v = write(cache_v, v, positions, counts)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum(
        "nhqd,nhkd->nhqk", q.astype(jnp.float32), cache_k.astype(jnp.float32)
    ) * scale
    m = x.shape[1]
    q_pos = positions[:, None] + jnp.arange(m)[None, :]  # [n, m]
    valid = jnp.arange(cache_k.shape[2])[None, None, :] <= q_pos[:, :, None]
    if starts is not None:
        valid = valid & (
            jnp.arange(cache_k.shape[2])[None, None, :] >= starts[:, None, None]
        )
    s = jnp.where(valid[:, None, :, :], s, -1e30)
    p_attn = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("nhqk,nhkd->nhqd", p_attn, cache_v.astype(jnp.float32))
    ctx = _merge_heads(ctx.astype(x.dtype))
    x = x + ctx @ p["attn_out"]["w"].astype(x.dtype) + p["attn_out"]["b"].astype(x.dtype)
    normed2 = _ln(p["ln2"], x)
    hdn = jax.nn.gelu(
        normed2 @ p["mlp_in"]["w"].astype(x.dtype) + p["mlp_in"]["b"].astype(x.dtype),
        approximate=False,
    )
    x = x + hdn @ p["mlp_out"]["w"].astype(x.dtype) + p["mlp_out"]["b"].astype(x.dtype)
    return x, cache_k, cache_v


def decode_step(
    params: dict,
    cache_k: jax.Array,
    cache_v: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for every slot: consume tokens[n] sitting at
    positions[n], return (logits[n, vocab], cache_k, cache_v) with each
    slot's K/V written at its own position.

    Free slots step too (their compute is the price of static shapes); the
    scheduler passes position 0 for them and their garbage K/V is
    overwritten by the next admission's prefill scatter."""
    heads = _heads(params)
    x = jnp.asarray(params["tok_emb"])[tokens][:, None, :]
    x = x + jnp.asarray(params["pos_emb"])[positions][:, None, :]
    new_k, new_v = [], []
    for li, lp in enumerate(params["layers"]):
        x, ck, cv = _layer_step_slots(lp, x, cache_k[li], cache_v[li], positions, heads)
        new_k.append(ck)
        new_v.append(cv)
    logits = _logits(params, x)[:, 0, :]
    return logits, jnp.stack(new_k), jnp.stack(new_v)


def _kth_largest(logits: jax.Array, k: jax.Array) -> jax.Array:
    """Each row's k-th largest logit, [..., 1] (``k`` [...] int32 in
    1..vocab, data): bit for bit ``flip(sort(row))[k - 1]``, ties, ``-inf``
    and NaN (largest, as ``lax.sort`` has it) included, without ordering the
    row. A float32's bits, the magnitude flipped where the sign is set, are
    an int32 whose order is the floats'; the answer is the largest ``t``
    that ``k`` entries reach, found a bit at a time from the top: 32
    compare-and-count passes over the row. Float32 or narrower (the
    upcast is exact, and the answer is one of the row's entries)."""
    x = logits.astype(jnp.float32)
    bits = lax.bitcast_convert_type(x, jnp.int32)
    image = jnp.where(jnp.isnan(x), jnp.int32(0x7FFFFFFF), bits ^ ((bits >> 31) & 0x7FFFFFFF))
    k = k[..., None]

    def narrow(i, t):
        # bit 31 first: it takes t from the least int32 to 0, every later
        # bit is clear in t so far; either way the candidate lies above t
        cand = t ^ (jnp.int32(1) << (31 - i))
        reach = jnp.sum(image >= cand, axis=-1, keepdims=True, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, t)

    t = lax.fori_loop(0, 32, narrow, jnp.full(k.shape, -(2**31), jnp.int32))
    return lax.bitcast_convert_type(t ^ ((t >> 31) & 0x7FFFFFFF), jnp.float32).astype(logits.dtype)


def _transform_logits(logits: jax.Array, temperature, top_k) -> jax.Array:
    """The per-row sampling transform shared by ``sample_tokens`` and the
    speculative acceptance rule (both MUST agree, or the draft's proposal
    distribution q would differ from the one acceptance corrects against):
    top_k restriction (<= 0 = full vocabulary) then temperature scaling.
    ``temperature``/``top_k`` broadcast against logits' leading axes;
    top_k is data, not shape — the cutoff is the row's k-th largest logit,
    selected (``_kth_largest``), so one compiled program serves every
    per-request k; a dispatch in which no row asks for top_k looks for no
    cutoff (the predicate is computed on the device from ``top_k``)."""
    vocab = logits.shape[-1]
    temperature = jnp.broadcast_to(temperature, logits.shape[:-1])
    top_k = jnp.broadcast_to(top_k, logits.shape[:-1])

    def cutoffs():
        kth = _kth_largest(logits, jnp.clip(top_k, 1, vocab))
        return jnp.where(top_k[..., None] > 0, kth, -jnp.inf)  # nothing lies under -inf

    thresh = lax.cond(
        jnp.any(top_k > 0), cutoffs, lambda: jnp.full((*logits.shape[:-1], 1), -jnp.inf, logits.dtype)
    )
    masked = jnp.where(logits < thresh, -jnp.inf, logits)
    return masked / jnp.maximum(temperature, 1e-6)[..., None].astype(logits.dtype)


def sample_tokens(
    logits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    key: jax.Array,
) -> jax.Array:
    """Per-row sampling: greedy argmax where temperature <= 0 (the serving
    default — what the fused oracle computes), else temperature-scaled
    categorical restricted to the top_k logits (top_k <= 0 means the full
    vocabulary). The cost follows what the dispatch's rows ask for: all
    rows greedy, the argmax and nothing else; a sampling row, the draw over
    [rows, vocab]; a sampling row with top_k, its cutoff besides. Both
    predicates are computed on the device; nothing is read back."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampling = temperature > 0

    def draw():
        # a greedy row's top_k buys nothing
        scaled = _transform_logits(logits, temperature, jnp.where(sampling, top_k, 0))
        sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
        return jnp.where(sampling, sampled, greedy)

    return lax.cond(jnp.any(sampling), draw, lambda: greedy)


# ------------------------------------------------------------- paged KV
# Block-table KV memory (serving/kv_pool.py owns the allocator): instead of
# one contiguous [L, n_slots, h, max_ctx, hd] row per slot, K/V lives in a
# shared page pool of TOKEN ROWS [L, n_pages, page_size, h*hd] and each slot
# carries a static-shape block table [max_pages] of physical page ids. The
# attention building blocks below mirror the flat decode_step exactly —
# same masks, same einsums, same f32 accumulation, one query a slot or many —
# but read the cache through a pool gather and write through a per-token
# (layer, page, row) scatter, so two slots sharing a system prompt REFERENCE
# the same pages (vLLM's PagedAttention memory model) instead of each
# holding a copy.
#
# Why token rows: every paged program takes the WHOLE pool, donated, and
# updates it in place — one scatter per layer at (li, page, row) into the
# [L, ...] array, one gather pool[li, bt] out of it; no per-layer slice, no
# restack. The TPU's scatter wants the scattered dimensions major and the
# written row minor; with heads between page and row ([L, P, h, ps, hd]) the
# compiler converts the pool to token rows and back around every write (a
# whole-pool copy per layer: half of a fused step on the chip, ~7 GiB of
# temporaries; PERF.md section 6, PR 27). A token's K (or V) for all heads
# is one contiguous h*hd row — what the qkv projection emits, no head
# transpose — and h*hd is whole lane tiles where head_dim 64 alone pads to
# 128. The head split happens on the gathered rows, not in the pool.
#
# Conventions the scheduler relies on:
# - physical page 0 is a reserved junk sink: free slots' block tables are
#   all-zero and masked-off writes (beyond a slot's chunk count, past the
#   virtual length) are redirected there, so a static-shape dispatch can
#   never corrupt a live page;
# - the gathered virtual cache is [max_pages * page_size] long; positions
#   beyond a query's own position contribute exactly zero attention mass
#   (the same -1e30 masking the flat path uses), so greedy output stays
#   bit-identical to the contiguous layout and the scan oracle;
# - pool state is a flat tuple pytree: (k, v) in fp mode, or
#   (k_q, k_scale, k_zp, v_q, v_scale, v_zp) with int8 payloads and ONE
#   (scale, zero-point) pair per page row (= per cached token, shared
#   across heads) stored page-resident [L, n_pages, page_size] beside the
#   payload — copy-on-write and sharing move the scales with their page,
#   and dequantization fuses into the attention gather. Pages stay at axis
#   1 of every component, so everything that deals in page indices
#   (paged_copy, allocator, prefix cache, replica seeding, host tier)
#   never sees the row layout.


# Device scopes of the paged path: ``jax.named_scope`` names that every op of
# the fused paged programs carries in its HLO ``op_name`` (metadata only:
# the compiled program is the same instruction for instruction), so a
# profiler trace reads device time by WHAT the op does, not by a shape that
# changes with the page count. The benchmark's per-layer metrics and
# docs/observability.md "Reading a device trace" key on these strings.
SCOPE_EMBED = "embed"  # token + position embedding lookup
SCOPE_QKV = "qkv"  # ln1, the fused q/k/v projection, head split
SCOPE_KV_WRITE = "kv_write"  # the in-place scatter of new K/V rows through the block tables
SCOPE_KV_GATHER = "kv_gather"  # page gather into the virtual contiguous cache (kernel path: the slots' lengths)
SCOPE_ATTN = "attn"  # scores, mask, softmax, context (kernel path: the paged-attention kernel, fetches included)
SCOPE_ATTN_OUT = "attn_out"  # output projection + residual
SCOPE_MLP = "mlp"  # ln2, mlp_in, gelu, mlp_out + residual
SCOPE_LM_HEAD = "lm_head"  # ln_f + vocabulary projection
SCOPE_SAMPLE = "sample"  # key derivation, last-position pick, sampling (the fused programs)
PAGED_SCOPES = (
    SCOPE_EMBED, SCOPE_QKV, SCOPE_KV_WRITE, SCOPE_KV_GATHER, SCOPE_ATTN,
    SCOPE_ATTN_OUT, SCOPE_MLP, SCOPE_LM_HEAD, SCOPE_SAMPLE,
)


def paged_kv_init(
    params: dict, n_pages: int, page_size: int, dtype=jnp.float32, kv_dtype: str = ""
) -> tuple:
    """Zeroed page pool state tuple (see module comment for the layout)."""
    return kv_pool_zeros(decoder_dims(params), n_pages, page_size, dtype, kv_dtype)


def kv_pool_zeros(
    d: dict, n_pages: int, page_size: int, dtype=jnp.float32, kv_dtype: str = ""
) -> tuple:
    """The pool of ANY family from its ``decoder_dims``: a token row holds
    one token's K (or V) for all ``kv_heads``, in each of the ``kv_layers``
    layers that attend (a hybrid family's recurrent layers hold no pages).
    ``kv_planes`` 1 (a latent-attention family, models/mla_decoder.py) is the
    LATENT page kind: ONE plane whose token row is the token's compressed
    key/value of ``kv_heads * head_dim`` = 1 x (latent + rotated shared key),
    no V plane, no per-head rows; a float plane only.
    ``kv_window_layers`` > 0 (models/moe_decoder.py: that many of the
    ``kv_layers`` are sliding-window layers) gives TWO PAGE KINDS in one
    state tuple: the full layers' planes, then the window layers' planes of
    the same form, each kind with its own page axis, ``n_pages`` = (full,
    window) or one count for both. A layer addresses its kind's planes by
    its index among that kind's layers; serving/kv_pool.py holds a block
    table and an allocator a kind."""
    if d.get("kv_window_layers", 0):
        n_full, n_win = n_pages if isinstance(n_pages, (tuple, list)) else (n_pages, n_pages)
        one = {**d, "kv_window_layers": 0}
        return kv_pool_zeros(
            {**one, "kv_layers": d["kv_layers"] - d["kv_window_layers"]}, n_full, page_size, dtype, kv_dtype
        ) + kv_pool_zeros({**one, "kv_layers": d["kv_window_layers"]}, n_win, page_size, dtype, kv_dtype)
    shape = (d["kv_layers"], n_pages, page_size, d["kv_heads"] * d["head_dim"])
    if d.get("kv_planes", 2) == 1:
        if kv_dtype:
            raise ValueError(f"kv_dtype {kv_dtype!r} on a one-plane (latent) pool: a float plane only")
        return (jnp.zeros(shape, dtype),)
    if kv_dtype == "int8":
        sshape = (d["kv_layers"], n_pages, page_size)
        # scale 1 / zp 0: dequantized junk pages read back as exact zeros,
        # matching the fp pool's init
        return (
            jnp.zeros(shape, jnp.int8),
            jnp.ones(sshape, jnp.float32),
            jnp.zeros(sshape, jnp.float32),
            jnp.zeros(shape, jnp.int8),
            jnp.ones(sshape, jnp.float32),
            jnp.zeros(sshape, jnp.float32),
        )
    if kv_dtype:
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r} (want '' or 'int8')")
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def paged_copy(pool: tuple, src: jax.Array, dst: jax.Array) -> tuple:
    """Copy pool pages src[i] -> dst[i] across every state component (the
    copy-on-write primitive). Padding entries use src=dst=0: page 0 is the
    junk sink, so rewriting it with its own bytes is a no-op by design."""
    return tuple(a.at[:, dst].set(jnp.take(a, src, axis=1)) for a in pool)


def _quant_rows(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-row asymmetric int8: x[N, w] -> (q[N, w] int8, scale[N], zp[N])
    with q = round((x - zp) / scale) in [-127, 127]; a row is one token's
    K (or V) across all heads."""
    lo = jnp.min(x, axis=1)
    hi = jnp.max(x, axis=1)
    zp = (hi + lo) * 0.5
    scale = jnp.maximum((hi - lo) / 254.0, 1e-8)
    q = jnp.clip(jnp.round((x - zp[:, None]) / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale, zp


def _write_index(ps: int, m: int, bt, positions, counts):
    """(physical page, row in it), each [n * m], of a dispatch's new token
    rows: slot i's entry j at positions[i] + j through its block-table row;
    an invalid entry (beyond counts[i], past the virtual length) at junk
    page 0."""
    n_log = bt.shape[1]
    gp = positions[:, None] + jnp.arange(m)[None, :]  # [n, m] global positions
    lp = jnp.clip(gp // ps, 0, n_log - 1)
    phys = jnp.take_along_axis(bt, lp, axis=1)  # [n, m] physical pages
    ok = (gp >= 0) & (gp < n_log * ps)
    if counts is not None:
        ok = ok & (jnp.arange(m)[None, :] < counts[:, None])
    phys = jnp.where(ok, phys, 0)
    return phys.reshape(-1), (gp % ps).reshape(-1)


def _write_rows(pool: tuple, li: int, parts: tuple, bt, positions, counts) -> tuple:
    """The ROW form of the pool write: one scatter index a token row,
    n * m slices of one row each at (li, page, row)."""
    n, m = parts[0].shape[:2]
    pf, of = _write_index(pool[0].shape[2], m, bt, positions, counts)
    flat = [x.reshape((n * m,) + x.shape[2:]) for x in parts]  # per-token rows
    return tuple(plane.at[li, pf, of].set(x) for plane, x in zip(pool, flat))


@functools.partial(jax.jit, inline=True)
def _write_pages(pool: tuple, li, parts: tuple, bt, positions, counts) -> tuple:
    """The PAGE form of the pool write: slot i's m consecutive positions
    from positions[i] cover at most ``pw`` logical pages of its table row,
    so the new rows are laid out by page (shifted by positions[i] % ps) and
    n * pw slices of one whole page each go to (li, page). A page's rows
    that the dispatch does not write (before the start, at or past
    counts[i]) keep what the pool holds: the rows written are consecutive,
    so only the first and the last page written can hold such rows, and
    those two are read, merged by row mask and written back. A page with
    no row to write (past counts[i], past the virtual length, a padding
    row's) goes to junk page 0, where an invalid row goes in the row form;
    everywhere else the pool ends bit for bit as ``_write_rows`` leaves it
    (tests/test_kv_pool.py holds the two forms equal).

    Why pages: a scatter costs the chip its indices, not its bytes. The 72
    writes of a (2, 256) dispatch into the gpt2-large pool (float32 rows of
    1280), timed alone on a v5e (PERF.md section 6, PR 40): 6.49 ms by
    rows (0.18 us an index of 5 KB), 1.54 this way (the scatter 0.89,
    0.36 us a page of 80 KB; the shift 0.28; the ends' read 0.09), 2.05
    with every page read back, 1.50 with the ends in a scatter of their own
    (but 0.67 against 0.57 at (2, 64)), 5.98 as a ``fori_loop`` of
    ``dynamic_update_slice`` a page; 0.74 where the positions are known to
    be page-aligned and whole (no shift, no merge). (layer, page) is ONE
    index: the compiler's own flattening of a scatter of 1,024 indices or
    more leaves its fusion without an op name, so without a scope.

    Jitted on its own and inlined: a program's writes (72 in gpt2-large)
    are then traced once and not a layer and a plane; traced in line, the
    five chunk programs took 4.6 s more to trace and lower than the
    parent's 7.2, at every boot, compile cache or not (setup_s +5 s)."""
    n, m = parts[0].shape[:2]
    n_log, ps = bt.shape[1], pool[0].shape[2]
    pw = (m + 2 * ps - 2) // ps  # pages that m rows from any row of a page can touch
    first = positions // ps  # [n] logical page of the first new row
    off = positions - first * ps  # its row in that page
    t = jnp.arange(pw * ps)[None, :]
    j = t - off[:, None]  # [n, pw * ps] the new row that lands in each row of the tile
    gp = first[:, None] * ps + t
    ok = (j >= 0) & (j < m) & (gp >= 0) & (gp < n_log * ps)
    if counts is not None:
        ok = ok & (j < counts[:, None])
    ok = ok.reshape(n, pw, ps)
    some = ok.any(axis=2)  # [n, pw] pages with a row to write
    lp = jnp.clip(first[:, None] + jnp.arange(pw)[None, :], 0, n_log - 1)
    phys = jnp.where(some, jnp.take_along_axis(bt, lp, axis=1), 0)
    last = pw - 1 - jnp.argmax(some[:, ::-1], axis=1)  # [n] the last page written, the first beside it
    ends = jnp.take_along_axis(phys, jnp.stack([jnp.argmax(some, axis=1), last], axis=1), axis=1).reshape(-1)
    is_last = jnp.arange(pw)[None, :] == last[:, None]

    def tiles(x):
        """x[n, m, ...] -> [n, pw, ps, ...], row j of slot i at tile row off[i] + j."""
        pad = [(0, 0), (ps, pw * ps - m)] + [(0, 0)] * (x.ndim - 2)
        cut = jax.vmap(lambda a, s: jax.lax.dynamic_slice_in_dim(a, s, pw * ps, axis=0))
        return cut(jnp.pad(x, pad), ps - off).reshape((n, pw, ps) + x.shape[2:])

    out = []
    for plane, x in zip(pool, parts):
        wide = (1,) * (x.ndim - 2)  # a row's own dims: (w,) of a payload plane, () of a scale plane
        flat = plane.reshape((-1,) + plane.shape[2:])  # (layer, page) as one index
        base = li * plane.shape[1]
        held = flat[base + ends].reshape((n, 2, 1, ps) + x.shape[2:])
        kept = jnp.where(is_last.reshape((n, pw, 1) + wide), held[:, 1], held[:, 0])
        merged = jnp.where(ok.reshape((n, pw, ps) + wide), tiles(x), kept)
        flat = flat.at[base + phys.reshape(-1)].set(merged.reshape((n * pw, ps) + x.shape[2:]))
        out.append(flat.reshape(plane.shape))
    return tuple(out)


def write_form(m: int, page_size: int) -> str:
    """The granule of a dispatch's pool write, "page" | "row", from its
    static shape: ``m`` consecutive positions a slot against the page size.
    A prefill chunk (a page's worth of rows or more) writes whole pages; a
    step, a verify or a tree commit (fewer rows than a page holds) its
    rows, which for one row a slot is already the least bytes."""
    return "page" if m >= page_size else "row"


def _write_parts(pool: tuple, li: int, parts: tuple, bt, positions, counts) -> tuple:
    """Write a dispatch's new rows (``parts[c]``: [n, m, ...] in component
    c's dtype, slot i's entry j at positions[i] + j) into layer ``li`` of
    every pool component in place, through the block tables, in the form
    ``write_form`` names."""
    write = _write_pages if write_form(parts[0].shape[1], pool[0].shape[2]) == "page" else _write_rows
    return write(pool, li, parts, bt, positions, counts)


@jax.named_scope(SCOPE_KV_WRITE)
def _paged_write_latent(pool: tuple, li: int, rows, bt, positions, counts):
    """``_paged_write`` for the one-plane latent pool: the dispatch's new
    rows [n, m, w] into layer ``li`` of the plane, in place."""
    return _write_parts(pool, li, (rows.astype(pool[0].dtype),), bt, positions, counts)


@jax.named_scope(SCOPE_KV_WRITE)
def _paged_write(pool: tuple, li: int, k, v, bt, positions, counts):
    """Write the dispatch's new K/V rows (k, v: [n, m, h*hd], slot i's
    entry j at positions[i] + j) into layer ``li`` of the WHOLE pool
    through the block tables — one in-place update per component, by rows
    or by pages (``_write_parts``). Invalid entries — beyond counts[i], or
    past the virtual length — are redirected to junk page 0 instead of
    masked in place, which is what lets free/prefilling slots ride
    static-shape dispatches without owning writable pages."""
    if len(pool) == 2:
        parts = (k.astype(pool[0].dtype), v.astype(pool[1].dtype))
    else:
        n, m, w = k.shape
        parts = tuple(
            a.reshape((n, m) + a.shape[1:])
            for x in (k, v)
            for a in _quant_rows(x.reshape(n * m, w).astype(jnp.float32))
        )
    return _write_parts(pool, li, parts, bt, positions, counts)


@jax.named_scope(SCOPE_KV_GATHER)
def _paged_gather(pool: tuple, li: int, bt, h: int) -> tuple[jax.Array, jax.Array]:
    """Gather each slot's pages of layer ``li`` out of the whole pool into
    a virtual contiguous cache [n, h, max_pages * page_size, hd] in f32
    (the flat path's attention accumulation dtype): whole token rows come
    out, and the head split is a reshape + transpose of the GATHERED rows
    (a layout the TPU compiler assigns to the scores' operand, not a copy;
    the CPU backend keeps the flat path's reduction order, hence its
    bits). int8 mode fuses the per-page-row dequant here."""
    if len(pool) == 2:
        k = pool[0][li, bt].astype(jnp.float32)  # [n, P, ps, h*hd]
        v = pool[1][li, bt].astype(jnp.float32)
    else:
        kq, sk, zk, vq, sv, zv = pool
        k = kq[li, bt].astype(jnp.float32) * sk[li, bt][..., None] + zk[li, bt][..., None]
        v = vq[li, bt].astype(jnp.float32) * sv[li, bt][..., None] + zv[li, bt][..., None]
    n, p, ps, w = k.shape
    return _split_heads(k.reshape(n, p * ps, w), h), _split_heads(v.reshape(n, p * ps, w), h)


def _paged_step_reads(attn_kernel: str, queries: int, pool: tuple, bt, positions, rows, counts=None):
    """What a grouped-query family's program hands ops/gqa_decode.py's
    kernels, once for all its attention layers (they walk the same tables):
    (the kernel's vectors, the pages of one layer's K that a STEP's kernel
    fetches in run DMAs as int32[1]) where the program set chose a kernel
    (``attn_kernel``) AND the dispatch is one the kernels take against the
    two-plane float pool: one query a slot (the step: ``gqa_decode.step_reads``'
    lengths and run flags) or a prefill chunk whose ``counts`` the family
    hands over where its ``chunk_attn`` says "kernel" (``chunk_reads``'
    five); else (None, zero): the gather. What is left of the gather there, a
    few integers a slot, stays under the ``kv_gather`` scope."""
    if not attn_kernel or len(pool) != 2 or (queries != 1 and counts is None):
        return None, jnp.zeros((1,), jnp.int32)
    with jax.named_scope(SCOPE_KV_GATHER):
        page_size = pool[0].shape[2]
        if queries != 1:
            return chunk_reads(bt, positions, counts, page_size), jnp.zeros((1,), jnp.int32)
        reads = step_reads(bt, positions, rows, page_size)
        return reads, pages_fetched(*reads, page_size, bt.shape[1])[1:]


def paged_gqa_attention(q, pool: tuple, li, bt, reads, *, scale: float, interpret: bool, window: int = 0):
    """A grouped-query family's attention through ops/gqa_decode.py's
    kernels, q[n, m, H, d] over layer ``li`` of a two-plane float pool read
    where it lies, with the vectors ``_paged_step_reads`` made for ``bt``:
    the step's kernel for one query a slot, the chunk's for more (``window``:
    ``bt`` is a sliding layer's windowed sub-table; the step's vectors carry
    it as ``first``). Returns [n, m, H * d]."""
    if q.shape[1] == 1:
        return gqa_decode_attention(q[:, 0], pool[0], pool[1], li, bt, *reads, scale=scale, interpret=interpret)[:, None]
    return gqa_chunk_attention(q, pool[0], pool[1], li, bt, *reads, scale=scale, window=window, interpret=interpret)


def _layer_step_paged(p, x, pool, li, bt, positions, h, counts=None, attn_kernel=""):
    """_layer_step_slots reworked onto the page pool: same math, but the
    new K/V rows scatter through the block tables into layer ``li`` of the
    pool first and attention reads them back (so in-dispatch queries see
    the keys earlier queries of the same dispatch just wrote, exactly like
    the flat path's write-then-read). Two read sides:

    - a page gather into a float32 virtual cache and the flat path's
      attention over it — every dispatch but the fused decode step on a
      TPU, and the oracle that is bit-identical to the flat path;
    - where ``attn_kernel`` (static; "" | "mosaic" | "interpret") asks for
      it AND the dispatch has one query a slot against the two-component
      float pool: the Pallas decode kernel (ops/paged_attention.py), which
      takes the WHOLE pool and ``li`` — no per-layer slice — and fetches
      only the pages each slot's length covers. What is left of the gather
      there, the lengths (one fusion for all layers once the compiler has
      merged them), stays under the ``kv_gather`` scope.

    Returns (x_out, new pool)."""
    kernel = bool(attn_kernel) and x.shape[1] == 1 and len(pool) == 2
    with jax.named_scope(SCOPE_QKV):
        normed = _ln(p["ln1"], x)
        qkv = normed @ p["qkv"]["w"].astype(x.dtype) + p["qkv"]["b"].astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)  # k, v stay token rows [n, m, h*hd]
        if not kernel:
            q = _split_heads(q, h)  # [n, h, m, hd]
    pool = _paged_write(pool, li, k, v, bt, positions, counts)
    if kernel:
        with jax.named_scope(SCOPE_KV_GATHER):
            lengths = slot_lengths(positions, pool[0].shape[2], bt.shape[1])
        with jax.named_scope(SCOPE_ATTN):
            scale = 1.0 / ((q.shape[-1] // h) ** 0.5)
            ctx = paged_attention_decode(
                q[:, 0, :].astype(jnp.float32) * scale, pool[0], pool[1], li, bt,
                lengths, heads=h, interpret=attn_kernel == "interpret",
            )[:, None, :].astype(x.dtype)
    else:
        cache_k, cache_v = _paged_gather(pool, li, bt, h)  # f32 virtual caches
        with jax.named_scope(SCOPE_ATTN):
            scale = 1.0 / (q.shape[-1] ** 0.5)
            s = jnp.einsum("nhqd,nhkd->nhqk", q.astype(jnp.float32), cache_k) * scale
            m = x.shape[1]
            q_pos = positions[:, None] + jnp.arange(m)[None, :]  # [n, m]
            valid = jnp.arange(cache_k.shape[2])[None, None, :] <= q_pos[:, :, None]
            s = jnp.where(valid[:, None, :, :], s, -1e30)
            p_attn = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("nhqk,nhkd->nhqd", p_attn, cache_v)
            ctx = _merge_heads(ctx.astype(x.dtype))
    with jax.named_scope(SCOPE_ATTN_OUT):
        x = x + ctx @ p["attn_out"]["w"].astype(x.dtype) + p["attn_out"]["b"].astype(x.dtype)
    with jax.named_scope(SCOPE_MLP):
        normed2 = _ln(p["ln2"], x)
        hdn = jax.nn.gelu(
            normed2 @ p["mlp_in"]["w"].astype(x.dtype) + p["mlp_in"]["b"].astype(x.dtype),
            approximate=False,
        )
        x = x + hdn @ p["mlp_out"]["w"].astype(x.dtype) + p["mlp_out"]["b"].astype(x.dtype)
    return x, pool


def _paged_forward(params, pool, bt, tokens, positions, counts=None, attn_kernel=""):
    """Shared body of the paged decode/verify/chunk programs: tokens[n, m]
    with slot i's query j at positions[i] + j; returns (logits[n, m, vocab],
    hidden[n, m, d], new pool state) — ``hidden`` is the final layer's
    residual-stream output (pre-``ln_f``), the per-position FEATURE an
    EAGLE-style draft head conditions on (data-only: same static shapes,
    and XLA dead-code-eliminates the extra output inside fused programs
    that drop it). The pool tuple threads through the layers whole: each
    layer's write is a scatter into it, so a caller that donates the pool
    gets it updated in place. Junk queries clip the position table like
    the flat verify/chunk paths — their logits are never read and their
    writes are junk-redirected.

    ``attn_kernel`` asks for the Pallas decode kernel on the layers' read
    side (``_layer_step_paged``); a dispatch it does not fit keeps the
    gather whatever was asked."""
    heads = _heads(params)
    m = tokens.shape[1]
    max_len = params["pos_emb"].shape[0]
    with jax.named_scope(SCOPE_EMBED):
        x = jnp.asarray(params["tok_emb"])[tokens]  # [n, m, d]
        pidx = jnp.clip(positions[:, None] + jnp.arange(m)[None, :], 0, max_len - 1)
        x = x + jnp.asarray(params["pos_emb"])[pidx]
    for li, lp in enumerate(params["layers"]):
        x, pool = _layer_step_paged(lp, x, pool, li, bt, positions, heads, counts, attn_kernel)
    with jax.named_scope(SCOPE_LM_HEAD):
        logits = _logits(params, x)  # [n, m, vocab]
    return logits, x, pool


def paged_decode_step(params, pool, bt, tokens, positions, attn_kernel=""):
    """decode_step over the page pool: consume tokens[n] at positions[n],
    return (logits[n, vocab], hidden[n, d], pool) — K/V written through
    block tables; ``hidden`` is the consumed position's final-layer
    feature (what a feature-level draft conditions the next round on).
    ``attn_kernel``: see ``_paged_forward``."""
    logits, hidden, pool = _paged_forward(
        params, pool, bt, tokens[:, None], positions, attn_kernel=attn_kernel
    )
    return logits[:, 0, :], hidden[:, 0, :], pool


def paged_verify_step(params, pool, bt, tokens, positions):
    """The widened speculative verify: consume tokens[n, m] (the last
    emitted token + the m-1 draft proposals) with slot i's query j at
    positions[i] + j; logits[i, j] is the target's next-token distribution
    AFTER consuming query j — what j sequential paged_decode_step calls
    give for the same prefix, which is what makes greedy acceptance exact.
    Returns (logits, hidden[n, m, d], pool)."""
    return _paged_forward(params, pool, bt, tokens, positions)


def paged_chunk_prefill(params, pool, bt, tokens, positions, counts):
    """One prefill CHUNK a row: row i is one slot's chunk (token j at
    positions[i] + j), ``bt[i]`` that slot's block-table row; persist only
    the first counts[i] K/V entries per row (a counts-0 row — padding — has
    its writes junk-redirected, touching no live page). A prompt prefilled
    in ANY chunk partition yields the same K/V as one pass. Returns (logits,
    hidden[rows, c, d], pool); logits[i, counts[i] - 1] is the next-token
    distribution after row i's last consumed token."""
    return _paged_forward(params, pool, bt, tokens, positions, counts)


def _fused_step(params, pool, bt, tokens, positions, temps, topks, seed, tick, *, attn_kernel=""):
    """One device program per scheduler step: paged decode_step + sampling
    + key derivation fused into a single dispatch. Per-step host->device
    traffic is the block tables plus four tiny vectors, and the readback
    one [n_slots] int32 — the per-step floor is ONE dispatch, not three.
    ``tick`` is a traced scalar, so the per-step RNG key needs
    no host-side split and the program never recompiles. ``attn_kernel``
    (bound by ``GPT2Decoder.fused_programs``, never traced) is the layers'
    read side: serving/decode_programs.py ``_step_attn_kernel``'s answer."""
    logits, _hidden, pool = paged_decode_step(params, pool, bt, tokens, positions, attn_kernel)
    with jax.named_scope(SCOPE_SAMPLE):
        key = jax.random.fold_in(jax.random.key(seed), tick)
        return sample_tokens(logits, temps, topks, key), pool


def _fused_chunk(params, pool, bt, ids, positions, counts, temps, topks, seed, tick):
    """One device program per prefill chunk round: ``paged_chunk_prefill``
    over the slots that prefill + next-token sampling from each row's last
    consumed position, one dispatch. ``ids`` is a [rows, c] entry of the
    scheduler's chunk ladder and ``bt`` [rows, n_log] the block-table rows
    of those slots, whichever they are (a padding row has counts 0 and
    writes junk page 0); a generating or free slot is not in the batch.
    Only the sampled token of a row whose prompt COMPLETED this round is
    consumed by the host (it is the first generated token). With the
    monolithic admit path gone, this IS admission's prompt compute — a
    wave prefills four slots a round at the ladder's widest entry, and
    each prompt over rounds when chunking is on."""
    logits, _hidden, pool = paged_chunk_prefill(params, pool, bt, ids, positions, counts)
    with jax.named_scope(SCOPE_SAMPLE):
        c = ids.shape[1]
        idx = jnp.clip(counts - 1, 0, c - 1)
        last = logits[jnp.arange(ids.shape[0]), idx]  # [n, vocab]
        key = jax.random.fold_in(jax.random.key(seed), tick)
        return sample_tokens(last, temps, topks, key), pool


def _sample_and_count(logits, counted, temps, topks, seed, tick):
    """A counting family's readback: the dispatch's sampled tokens, then its counts."""
    with jax.named_scope(SCOPE_SAMPLE):
        key = jax.random.fold_in(jax.random.key(seed), tick)
        toks = sample_tokens(logits[:, 0, :], temps, topks, key)
        return jnp.concatenate([toks, counted])


def counted_programs(paged_forward):
    """The step and chunk bodies of a family whose ``paged_forward(params,
    pool, bt, tokens, positions, counts=, rows=, pick=)`` gives (logits,
    hidden, pool, counted), under the GPT-2 family's names (a device trace
    calls every family's programs ``jit__fused_step``), with two
    differences: the step takes ``rows`` (which slots generate, so junk rows
    stay out of the counts), and the counts ride the token readback,
    appended to it: one [rows + len(frame_counters)] int32 array, one
    transfer (the step's rows are the slots, the chunk's the slots that
    prefill: ``_fused_chunk``). The chunk's head runs on each row's last
    real position only."""

    def step(params, pool, bt, tokens, positions, temps, topks, seed, tick, rows):
        logits, _hidden, pool, counted = paged_forward(
            params, pool, bt, tokens[:, None], positions, rows=rows
        )
        return _sample_and_count(logits, counted, temps, topks, seed, tick), pool

    def chunk(params, pool, bt, ids, positions, counts, temps, topks, seed, tick):
        idx = jnp.clip(counts - 1, 0, ids.shape[1] - 1)
        logits, _hidden, pool, counted = paged_forward(
            params, pool, bt, ids, positions, counts=counts, pick=idx
        )
        return _sample_and_count(logits, counted, temps, topks, seed, tick), pool

    # jit names a program after its function: the trace's name for every family
    step.__name__ = step.__qualname__ = "_fused_step"
    chunk.__name__ = chunk.__qualname__ = "_fused_chunk"
    return step, chunk


def paged_greedy_generate(forward, make_pool, ids, max_new_tokens: int, page_size: int = 16):
    """Greedy whole-batch decode ids[b, s] -> [b, s + max_new_tokens]: the
    fused fallback apply of a deployment without ``tpu.decode_slots``, for a
    family whose ``forward(pool, bt, tokens, positions, counts=, pick=)``
    gives (logits, hidden, pool, counted). The SAME paged forward over a
    private pool, ``make_pool(n_pages, page_size)`` of the junk page + every
    sequence's own pages, with identity block tables: one prefill over the
    whole prompt, then a scan of single-token steps."""
    ids = ids.astype(jnp.int32)
    b, s = ids.shape
    pages = -(-(s + max_new_tokens) // page_size)
    pool = make_pool(1 + b * pages, page_size)
    bt = 1 + jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    zero = jnp.zeros((b,), jnp.int32)
    logits, _, pool, _ = forward(pool, bt, ids, zero, counts=zero + s, pick=zero + (s - 1))
    first = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)

    def step(carry, _):
        tok, pos, pool = carry
        logits, _, pool, _ = forward(pool, bt, tok[:, None], pos)
        return (jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32), pos + 1, pool), tok

    (last, _, _), toks = lax.scan(step, (first, zero + s, pool), None, length=max_new_tokens - 1)
    return jnp.concatenate([ids, toks.T.reshape(b, -1), last[:, None]], axis=1)


def counted_state_programs(paged_forward):
    """``counted_programs`` for a family with a state cache beside the pages
    (``state_init``): ``paged_forward(params, pool, rec, bt, tokens,
    positions, counts=, rows=, pick=, state_rows=)`` gives (logits, pool,
    rec, counted). Both bodies take the state cache ``rec`` after the pool
    (donated with it) and give it back after it; the step takes ``rows``
    (the slots that generate: the others' state stands), the chunk
    ``state_rows`` [3, rows]; the counts ride the token readback."""

    def step(params, pool, rec, bt, tokens, positions, temps, topks, seed, tick, rows):
        logits, pool, rec, counted = paged_forward(params, pool, rec, bt, tokens[:, None], positions, rows=rows)
        return _sample_and_count(logits, counted, temps, topks, seed, tick), pool, rec

    def chunk(params, pool, rec, bt, ids, positions, counts, temps, topks, seed, tick, state_rows):
        idx = jnp.clip(counts - 1, 0, ids.shape[1] - 1)
        logits, pool, rec, counted = paged_forward(
            params, pool, rec, bt, ids, positions, counts=counts, pick=idx, state_rows=state_rows
        )
        return _sample_and_count(logits, counted, temps, topks, seed, tick), pool, rec

    step.__name__ = step.__qualname__ = "_fused_step"
    chunk.__name__ = chunk.__qualname__ = "_fused_chunk"
    return step, chunk


def paged_state_greedy_generate(forward, make_pool, make_state, ids, max_new_tokens: int, chunk: int = 256):
    """``paged_greedy_generate`` for a family with a state cache:
    ``forward(pool, rec, bt, tokens, positions, counts=, pick=,
    state_rows=)`` gives (logits, pool, rec, counted), over a private pool
    and private state rows (``make_state(rows)``): the prompt in chunks of
    ``chunk``, then a scan of single-token steps."""
    ids = ids.astype(jnp.int32)
    b, s = ids.shape
    ps = 16
    pages = -(-(s + max_new_tokens) // ps)
    pool = make_pool(1 + b * pages, ps)
    rec = make_state(b)
    bt = 1 + jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    zero = jnp.zeros((b,), jnp.int32)
    own = jnp.arange(b, dtype=jnp.int32)
    rows3 = jnp.stack([own, own, own + b])  # read and write the row's own; no snapshot
    for at in range(0, s, chunk):
        c = min(chunk, s - at)
        logits, pool, rec, _ = forward(
            pool, rec, bt, ids[:, at : at + c], zero + at, counts=zero + c, pick=zero + (c - 1), state_rows=rows3
        )
    first = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)

    def step(carry, _):
        tok, pos, pool, rec = carry
        logits, pool, rec, _ = forward(pool, rec, bt, tok[:, None], pos)
        return (jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32), pos + 1, pool, rec), tok

    (last, _, _, _), toks = lax.scan(step, (first, zero + s, pool, rec), None, length=max_new_tokens - 1)
    return jnp.concatenate([ids, toks.T.reshape(b, -1), last[:, None]], axis=1)


_MECHANISMS = {
    "speculation": "speculative decoding (draft, tree, feature head)",
    "decode_mesh": "tensor-parallel decode (parallel/tp.py)",
    "kv_int8": "the int8 KV pool (decode_kv_dtype)",
    "host_tier": "the host and store KV tiers (decode_kv_host_bytes, decode_kv_store_tier)",
    "prefix_export": "prefix-cache export and pre-seeding (export_prefix_state, preseed_prefix_state)",
}


def require_served(family, mechanism: str) -> None:
    """Ask a decoder family whether it serves a decode mechanism (a key of
    ``_MECHANISMS``): ``FamilyNotServed`` by name where it does not."""
    if mechanism not in family.serves:
        raise FamilyNotServed(
            f"{_MECHANISMS[mechanism]} is not served for the {family.name!r} decoder family"
        )


class GPT2Decoder:
    """The GPT-2 family as the object the decode scheduler asks — what a
    decoder family answers, in one list (the others: models/moe_decoder.py
    ``MoEDecoder``, models/hybrid_decoder.py ``HybridDecoder``,
    models/mla_decoder.py ``MLADecoder``): ``name``; ``decoder_dims(params)``
    (raises ``FamilyNotServed`` for another family's parameters; with
    ``kv_planes`` 1 the family's pages are the one-plane latent kind,
    ``kv_pool_zeros``);
    ``paged_kv_init`` (the zeroed pool, of ``decoder_dims``' ``kv_layers``
    layers); ``frame_counters`` (FlightFrame fields its programs' readback
    carries after the tokens, none here);
    ``serves`` (of "speculation", "decode_mesh", "attn_kernel", "kv_int8",
    "host_tier", "prefix_export": what beside the plain rounds it can be
    asked for — ``require_served``);
    ``fused_programs(attn_kernel)`` (its step and chunk bodies, both named
    ``_fused_step`` / ``_fused_chunk`` whatever the family);
    ``state_init`` — None, or for a family whose layers carry a recurrent
    state (models/hybrid_decoder.py) ``state_init(params, rows)``: the
    zeroed state cache, a tuple of arrays with the ROW at axis 0, which the
    pool holds beside the pages (serving/kv_pool.py ``recurrent``). Such a
    family's programs take that tuple after the pool, donated with it, and
    give it back after it; its step takes ``rows`` and advances no other
    slot's state; its chunk takes ``state_rows`` [3, rows] last (the row
    each batch row reads, writes and snapshots)."""

    name = "gpt2"
    frame_counters = ()
    serves = frozenset(
        {"speculation", "decode_mesh", "attn_kernel", "kv_int8", "host_tier", "prefix_export"}
    )
    state_init = None
    decoder_dims = staticmethod(decoder_dims)
    paged_kv_init = staticmethod(paged_kv_init)

    @functools.lru_cache(maxsize=None)
    def fused_programs(self, attn_kernel: str = ""):
        """(``_fused_step``, ``_fused_chunk``); with ``attn_kernel`` the
        step has the layers' read side bound to it, under the same name.
        Cached: every scheduler shares the compiled programs."""
        if not attn_kernel:
            return _fused_step, _fused_chunk
        step = functools.partial(_fused_step, attn_kernel=attn_kernel)
        step.__name__ = step.__qualname__ = _fused_step.__name__
        return step, _fused_chunk


gpt2_family = GPT2Decoder()


def decoder_family(family=None):
    """The family a model's spec names (``ModelSpec.generative["family"]``);
    the GPT-2 family where it names none — THE default."""
    return family if family is not None else gpt2_family


# ----------------------------------------------------- speculative decoding
# Draft-model speculation (Leviathan et al.; Chen et al.): a cheap draft
# decoder proposes k tokens per slot in ONE dispatch, the target model
# scores all k+1 queries against its pages in ONE widened dispatch
# (paged_verify_step), and the longest valid prefix is accepted — amortizing
# the per-dispatch cost over several emitted tokens. Speculative cache
# writes need no rollback copy: positions only advance by the ACCEPTED length, so
# rejected entries sit beyond every later attention mask until the next
# consumed token overwrites them.


def draft_propose(
    params: dict,
    cache_k: jax.Array,
    cache_v: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    key: jax.Array,
    k: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """k autoregressive draft steps in ONE program: starting from the last
    emitted token of every slot, propose (draft_tokens[n, k],
    draft_logits[n, k, vocab], cache_k, cache_v). ``k`` is static (the
    deployment's decode_spec_k), so the loop unrolls at trace time and the
    whole proposal chain costs one dispatch. Greedy rows (temperature <=
    0) propose argmax; sampled rows propose from the same transformed
    distribution sample_tokens serves — the q the acceptance rule corrects
    against."""
    toks = tokens
    drafts, logit_steps = [], []
    for j in range(k):
        logits, cache_k, cache_v = decode_step(
            params, cache_k, cache_v, toks, positions + j
        )
        toks = sample_tokens(logits, temperature, top_k, jax.random.fold_in(key, j))
        drafts.append(toks)
        logit_steps.append(logits)
    # one extra cache-fill step consuming the LAST proposal at pos+k
    # (logits discarded): a fully-accepted round advances the slot past
    # pos+k without ever consuming d_k here, and without this write the
    # draft cache keeps a permanent zero/stale hole inside every later
    # attention mask — accept rate silently decays. On partial accepts
    # the entry is junk-then-overwritten like every speculative write.
    _, cache_k, cache_v = decode_step(params, cache_k, cache_v, toks, positions + k)
    return (
        jnp.stack(drafts, axis=1),
        jnp.stack(logit_steps, axis=1),
        cache_k,
        cache_v,
    )


def speculative_accept(
    target_logits: jax.Array,
    draft_tokens: jax.Array,
    draft_logits: jax.Array,
    limits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    key: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """The acceptance rule: given the widened target logits [n, k+1, V]
    (position j scored AFTER consuming query j), the draft's proposals
    [n, k] and raw logits [n, k, V], and per-slot accept limits [n]
    (0..k — the tighten-only spec_k override and the remaining token
    budget), return (out_tokens [n, k+1], n_accepted [n]): slot i emits
    out_tokens[i, :n_accepted[i] + 1].

    Greedy rows (temperature <= 0) accept the longest draft prefix that
    matches the target's own argmax chain and emit the target argmax at
    the first mismatch — bit-identical to sequential greedy decoding by
    induction (query 0 consumed the true last token, so a match at j
    makes query j+1's context exact too). Sampled rows use standard
    speculative sampling: accept d_j with probability min(1, p(d_j) /
    q(d_j)) and resample a TRUE rejection from the residual
    max(p - q, 0) — the emitted distribution is exactly the target's
    (Leviathan et al. Thm 1). A limit clamp is NOT a rejection (nothing
    was proposed there): its bonus token samples p directly."""
    n, kp1, vocab = target_logits.shape
    k = kp1 - 1
    rows = jnp.arange(n)
    greedy_t = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)  # [n, k+1]
    p = jax.nn.softmax(
        _transform_logits(target_logits, temperature[:, None], top_k[:, None]), axis=-1
    )
    greedy_ok = draft_tokens == greedy_t[:, :k]  # [n, k]
    q = jax.nn.softmax(
        _transform_logits(draft_logits, temperature[:, None], top_k[:, None]), axis=-1
    )
    p_d = jnp.take_along_axis(p[:, :k], draft_tokens[..., None], axis=-1)[..., 0]
    q_d = jnp.take_along_axis(q, draft_tokens[..., None], axis=-1)[..., 0]
    key_u, key_b = jax.random.split(key)
    u = jax.random.uniform(key_u, (n, k))
    sampled_ok = u * q_d < p_d  # u < p/q without the division
    ok = jnp.where(temperature[:, None] > 0, sampled_ok, greedy_ok)
    ok = ok & (jnp.arange(k)[None, :] < limits[:, None])
    n_acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    # bonus token at index n_acc
    p_a = p[rows, n_acc]  # [n, vocab]
    q_a = jnp.where(
        (n_acc < k)[:, None], q[rows, jnp.minimum(n_acc, k - 1)], jnp.float32(0.0)
    )
    true_reject = n_acc < limits  # a draft existed here and lost
    residual = jnp.maximum(p_a - q_a, 0.0)
    rsum = jnp.sum(residual, axis=-1, keepdims=True)
    residual = jnp.where(rsum > 1e-9, residual / jnp.maximum(rsum, 1e-9), p_a)
    dist = jnp.where(true_reject[:, None], residual, p_a)
    bonus_sampled = jax.random.categorical(
        key_b, jnp.log(dist + 1e-38), axis=-1
    ).astype(jnp.int32)
    bonus = jnp.where(temperature > 0, bonus_sampled, greedy_t[rows, n_acc])
    out = jnp.concatenate([draft_tokens, jnp.zeros((n, 1), jnp.int32)], axis=1)
    out = out.at[rows, n_acc].set(bonus)
    return out, n_acc.astype(jnp.int32)


# ------------------------------------------------------- tree speculation
# Multi-candidate (tree) speculation (SpecInfer; Medusa; EAGLE): instead of
# one k-token chain, the draft proposes a token TREE — ``branching[d]``
# candidates per depth under every surviving branch (models/spec_tree.py
# owns the static layout) — and the target scores the whole flattened tree
# in ONE widened dispatch. Acceptance walks the longest valid PATH, so
# accepted-tokens-per-dispatch rises at the same 2-dispatch round cost:
# where a chain dies at the first mismatch, a tree usually has a sibling
# candidate covering the target's actual choice.
#
# Cache discipline differs from the chain on purpose: sibling nodes at one
# depth would collide on the same (page, offset), so the tree forward
# NEVER writes speculative K/V — in-dispatch queries read their ancestors
# through the ancestor mask (the in-block causal mask generalized), and
# only the ACCEPTED path is committed afterwards, every other column
# junk-redirected. The pool never holds speculative garbage.


def sequence_hidden(params: dict, ids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Teacher-forced (logits, hidden) at every position: ids[b, s] ->
    ([b, s, vocab], [b, s, d]). ``hidden`` is the final layer's
    residual-stream output (pre-``ln_f``) — the same FEATURE definition
    the paged serving programs thread out, so the feature-conditioned
    distillation recipe (training/distill_draft.py) trains on exactly
    what the serving draft head will be fed."""
    ids = ids.astype(jnp.int32)
    heads = _heads(params)
    x = _embed(params, ids)
    for lp in params["layers"]:
        x, _, _ = _layer_prefill(lp, x, heads)
    return _logits(params, x), x


def sequence_logits(params: dict, ids: jax.Array) -> jax.Array:
    """Teacher-forced logits at every position: ids[b, s] -> [b, s, vocab]
    (position j's row is the next-token distribution after consuming
    tokens 0..j). One causal pass — the signal both sides of the draft
    KL-distillation recipe (training/distill_draft.py) train on."""
    return sequence_hidden(params, ids)[0]


def _layer_tree_flat(p, x, cache_k, cache_v, positions, h, ek, ev, sub_mask, starts=None):
    """One layer of a draft tree-expansion step over the FLAT draft cache:
    x [n, c, d] carries one depth's nodes; attention reads the cache at
    entries <= positions[i] (prompt + committed tokens + the root's fresh
    write) PLUS the in-register K/V of every node proposed so far this
    round (``ek``/``ev`` [n, h, E, hd], grown per depth — speculative
    draft K/V is never written to the cache; the verify dispatch commits
    the accepted path). ``sub_mask`` [c, E + c] is the ancestor-or-self
    mask over those in-flight nodes. Returns (x_out, ek', ev') with this
    depth's K/V appended."""
    normed = _ln(p["ln1"], x)
    qkv = normed @ p["qkv"]["w"].astype(x.dtype) + p["qkv"]["b"].astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = _split_heads(q, h)  # [n, h, c, hd]
    k = _split_heads(k, h)
    v = _split_heads(v, h)
    ek = k if ek is None else jnp.concatenate([ek, k], axis=2)
    ev = v if ev is None else jnp.concatenate([ev, v], axis=2)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    qf = q.astype(jnp.float32)
    s_cache = jnp.einsum("nhqd,nhkd->nhqk", qf, cache_k.astype(jnp.float32)) * scale
    valid = jnp.arange(cache_k.shape[2])[None, None, None, :] <= positions[:, None, None, None]
    if starts is not None:
        # per-slot attention lower bound (see _layer_step_slots): the
        # feature draft's warm-admit window opens at the computed suffix
        valid = valid & (
            jnp.arange(cache_k.shape[2])[None, None, None, :]
            >= starts[:, None, None, None]
        )
    s_cache = jnp.where(valid, s_cache, -1e30)
    s_ext = jnp.einsum("nhqd,nhkd->nhqk", qf, ek.astype(jnp.float32)) * scale
    s_ext = jnp.where(sub_mask[None, None, :, :], s_ext, -1e30)
    p_attn = jax.nn.softmax(jnp.concatenate([s_cache, s_ext], axis=-1), axis=-1)
    c_len = cache_k.shape[2]
    ctx = jnp.einsum(
        "nhqk,nhkd->nhqd", p_attn[..., :c_len], cache_v.astype(jnp.float32)
    ) + jnp.einsum("nhqk,nhkd->nhqd", p_attn[..., c_len:], ev.astype(jnp.float32))
    ctx = _merge_heads(ctx.astype(x.dtype))
    x = x + ctx @ p["attn_out"]["w"].astype(x.dtype) + p["attn_out"]["b"].astype(x.dtype)
    normed2 = _ln(p["ln2"], x)
    hdn = jax.nn.gelu(
        normed2 @ p["mlp_in"]["w"].astype(x.dtype) + p["mlp_in"]["b"].astype(x.dtype),
        approximate=False,
    )
    x = x + hdn @ p["mlp_out"]["w"].astype(x.dtype) + p["mlp_out"]["b"].astype(x.dtype)
    return x, ek, ev


def _tree_candidates(parent_logits, temperature, top_k, key, d: int, b: int):
    """One depth's candidate tokens [n, c_prev * b] in parent-major block
    order, from the parents' logits [n, c_prev, V] — THE candidate rule
    both tree drafts share (token-level ``draft_propose_tree`` and the
    feature head ``draft_propose_features``; extracting it is what keeps
    their RNG streams and block layouts identical by construction).
    Greedy rows take the top-b DISTINCT tokens (branch 0 is the chain's
    argmax proposal); sampled rows draw b i.i.d. tokens from the
    transformed distribution ``sample_tokens`` serves — i.i.d. candidates
    are what make the per-depth recursive rejection resampling in
    ``speculative_accept_tree`` exact."""
    n, c_prev, _ = parent_logits.shape
    _, top_idx = lax.top_k(parent_logits, b)  # [n, c_prev, b]
    flat_parent = parent_logits.reshape(n * c_prev, -1)
    scaled = _transform_logits(
        flat_parent, jnp.repeat(temperature, c_prev), jnp.repeat(top_k, c_prev)
    )
    samp = [
        jax.random.categorical(
            jax.random.fold_in(jax.random.fold_in(key, d), bi), scaled, axis=-1
        ).astype(jnp.int32)
        for bi in range(b)
    ]
    sampled = jnp.stack(samp, axis=-1).reshape(n, c_prev, b)
    cand = jnp.where(
        (temperature > 0)[:, None, None], sampled, top_idx.astype(jnp.int32)
    )
    return cand.reshape(n, c_prev * b)  # parent-major: the block layout


def draft_propose_tree(
    params: dict,
    cache_k: jax.Array,
    cache_v: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    key: jax.Array,
    tree,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Grow the whole proposal tree in ONE program: a root decode step
    (consume the last emitted token at ``pos``, write its K/V — always
    consumed, so the write is never speculative), then ``tree.depth``
    unrolled widened expansions, each proposing ``branching[d]`` children
    per surviving node. Greedy rows take the top-b distinct tokens of the
    parent's raw logits (branch 0 IS the chain's argmax proposal); sampled
    rows draw b i.i.d. tokens from the transformed distribution
    ``sample_tokens`` serves — i.i.d. candidates are what make the
    per-depth recursive rejection resampling in ``speculative_accept_tree``
    exact.

    Returns (node_tokens [n, n_tree], block_logits [n, width, V],
    node_k [L, n, h, n_tree, hd], node_v, cache_k, cache_v): block j's
    logits are the draft's next-token distribution AFTER consuming block
    j's token along its path (block 0 = the root) — the q each node's
    children are corrected against. Speculative node K/V comes back
    in-register for the verify dispatch to commit (``draft_tree_commit``);
    the cache itself only gains the root's entry."""
    heads = _heads(params)
    max_len = params["pos_emb"].shape[0]
    n = tokens.shape[0]
    logits0, cache_k, cache_v = decode_step(params, cache_k, cache_v, tokens, positions)
    block_logits = [logits0[:, None, :]]
    node_tokens = []
    ek: list = [None] * len(params["layers"])
    ev: list = [None] * len(params["layers"])
    parent_logits = logits0[:, None, :]  # [n, 1, V]
    mask_np = tree.ancestor_mask
    for d in range(1, tree.depth + 1):
        b = tree.branching[d - 1]
        c_d = tree.level_counts[d - 1]
        toks_d = _tree_candidates(parent_logits, temperature, top_k, key, d, b)
        node_tokens.append(toks_d)
        x = jnp.asarray(params["tok_emb"])[toks_d]
        pidx = jnp.clip(positions + d, 0, max_len - 1)
        x = x + jnp.asarray(params["pos_emb"])[pidx][:, None, :]
        start = tree.level_starts[d - 1]
        sub_mask = jnp.asarray(mask_np[start : start + c_d, 1 : start + c_d])
        for li, lp in enumerate(params["layers"]):
            x, ek[li], ev[li] = _layer_tree_flat(
                lp, x, cache_k[li], cache_v[li], positions, heads,
                ek[li], ev[li], sub_mask,
            )
        depth_logits = _logits(params, x)  # [n, c_d, V]
        block_logits.append(depth_logits)
        parent_logits = depth_logits
    return (
        jnp.concatenate(node_tokens, axis=1),
        jnp.concatenate(block_logits, axis=1),
        jnp.stack(ek),
        jnp.stack(ev),
        cache_k,
        cache_v,
    )


def _layer_tree_paged(p, x, pool, li, bt, positions, h, mask):
    """One layer of the widened TARGET tree verify over the page pool:
    all ``width`` blocks at once, attention over the gathered cache
    (entries strictly before ``pos`` — nothing speculative lives there)
    plus the dispatch's own fresh K/V under the ancestor mask. The pool
    is NOT written (``paged_tree_commit`` writes the accepted path after
    acceptance). int8 pools round-trip the fresh K/V through the same
    per-page-row quantizer the commit will apply, so every value a query
    reads is bit-identical to what the sequential plain path would have
    read back from the pool. Returns (x_out, k, v) with the RAW fresh
    K/V for the commit."""
    normed = _ln(p["ln1"], x)
    qkv = normed @ p["qkv"]["w"].astype(x.dtype) + p["qkv"]["b"].astype(x.dtype)
    q, k_rows, v_rows = jnp.split(qkv, 3, axis=-1)  # [n, m, h*hd] each
    q = _split_heads(q, h)  # [n, h, m, hd]
    k = _split_heads(k_rows, h)
    v = _split_heads(v_rows, h)
    if len(pool) == 6:
        # int8 pool: quantize-dequantize the in-block K/V per token row —
        # the exact transform _paged_write/_paged_gather would apply
        def _rt(rows):
            qr, sc, zp = _quant_rows(rows.reshape(-1, rows.shape[-1]).astype(jnp.float32))
            deq = qr.astype(jnp.float32) * sc[:, None] + zp[:, None]
            return _split_heads(deq.reshape(rows.shape), h)

        k_att, v_att = _rt(k_rows), _rt(v_rows)
    else:
        # fp pool: round-trip through the pool dtype (no-op at float32)
        k_att = k.astype(pool[0].dtype).astype(jnp.float32)
        v_att = v.astype(pool[0].dtype).astype(jnp.float32)
    cache_k, cache_v = _paged_gather(pool, li, bt, h)  # f32 virtual caches
    scale = 1.0 / (q.shape[-1] ** 0.5)
    qf = q.astype(jnp.float32)
    s_cache = jnp.einsum("nhqd,nhkd->nhqk", qf, cache_k) * scale
    c_len = cache_k.shape[2]
    valid = jnp.arange(c_len)[None, None, None, :] < positions[:, None, None, None]
    s_cache = jnp.where(valid, s_cache, -1e30)
    s_blk = jnp.einsum("nhqd,nhkd->nhqk", qf, k_att) * scale
    s_blk = jnp.where(jnp.asarray(mask)[None, None, :, :], s_blk, -1e30)
    p_attn = jax.nn.softmax(jnp.concatenate([s_cache, s_blk], axis=-1), axis=-1)
    ctx = jnp.einsum("nhqk,nhkd->nhqd", p_attn[..., :c_len], cache_v) + jnp.einsum(
        "nhqk,nhkd->nhqd", p_attn[..., c_len:], v_att
    )
    ctx = _merge_heads(ctx.astype(x.dtype))
    x = x + ctx @ p["attn_out"]["w"].astype(x.dtype) + p["attn_out"]["b"].astype(x.dtype)
    normed2 = _ln(p["ln2"], x)
    hdn = jax.nn.gelu(
        normed2 @ p["mlp_in"]["w"].astype(x.dtype) + p["mlp_in"]["b"].astype(x.dtype),
        approximate=False,
    )
    x = x + hdn @ p["mlp_out"]["w"].astype(x.dtype) + p["mlp_out"]["b"].astype(x.dtype)
    return x, k, v


def paged_tree_verify(
    params: dict, pool: tuple, bt: jax.Array, tokens: jax.Array,
    positions: jax.Array, tree,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Score the flattened tree in ONE widened dispatch: tokens [n, width]
    (block 0 = the last emitted token, blocks 1.. = tree nodes), block j
    at position ``pos + depth(j)``. logits[i, j] is the target's
    next-token distribution AFTER consuming block j's token along its
    path — exactly what sequential decoding down that path would produce,
    which is what keeps greedy path acceptance bit-exact. Returns
    (logits [n, width, V], hidden [n, width, d], new_k
    [L, n, h, width, hd], new_v); ``hidden`` is each block's final-layer
    feature — the accepted path's last entry seeds the NEXT round's
    feature-draft root. The pool is untouched — ``paged_tree_commit``
    writes the accepted path."""
    heads = _heads(params)
    max_len = params["pos_emb"].shape[0]
    x = jnp.asarray(params["tok_emb"])[tokens]  # [n, width, d]
    pidx = jnp.clip(
        positions[:, None] + jnp.asarray(tree.block_depth)[None, :], 0, max_len - 1
    )
    x = x + jnp.asarray(params["pos_emb"])[pidx]
    mask = tree.ancestor_mask
    nk, nv = [], []
    for li, lp in enumerate(params["layers"]):
        x, k, v = _layer_tree_paged(lp, x, pool, li, bt, positions, heads, mask)
        nk.append(k)
        nv.append(v)
    logits = _logits(params, x)  # [n, width, V]
    return logits, x, jnp.stack(nk), jnp.stack(nv)


def paged_tree_commit(
    pool: tuple, bt: jax.Array, new_k: jax.Array, new_v: jax.Array,
    path_idx: jax.Array, positions: jax.Array, n_acc: jax.Array,
) -> tuple:
    """Write the ACCEPTED path's K/V — the root block plus the chosen
    node at depths 1..n_acc — through the block tables at
    ``pos..pos+n_acc``; every column beyond ``n_acc + 1`` is
    junk-redirected by the counts mask, so the pool holds exactly what
    sequential decoding would have written and no speculative garbage."""
    L = new_k.shape[0]
    idx = jnp.broadcast_to(
        path_idx[None, :, None, :, None],
        new_k.shape[:3] + (path_idx.shape[1], new_k.shape[4]),
    )
    k_sel = jnp.take_along_axis(new_k, idx, axis=3)  # [L, n, h, D+1, hd]
    v_sel = jnp.take_along_axis(new_v, idx, axis=3)
    counts = n_acc + 1
    for li in range(L):
        pool = _paged_write(
            pool, li, _merge_heads(k_sel[li]), _merge_heads(v_sel[li]),
            bt, positions, counts,
        )
    return pool


def draft_tree_commit(
    cache_k: jax.Array, cache_v: jax.Array, node_k: jax.Array, node_v: jax.Array,
    path_idx: jax.Array, positions: jax.Array, n_acc: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """The draft-side twin of ``paged_tree_commit``: write the accepted
    path's draft K/V into the FLAT draft cache at ``pos+1..pos+n_acc``
    (the root's entry at ``pos`` was written by the draft dispatch
    itself). node_k/node_v [L, n, h, n_tree, hd] are in block order, so
    ``path_idx[:, 1:] - 1`` selects the chosen node per depth; columns
    beyond ``n_acc`` keep the cache's current bytes (a masked select, so
    a zero-accept slot mutates nothing)."""
    D = path_idx.shape[1] - 1
    nidx = jnp.maximum(path_idx[:, 1:] - 1, 0)  # [n, D] node indices
    idx = jnp.broadcast_to(
        nidx[None, :, None, :, None], node_k.shape[:3] + (D, node_k.shape[4])
    )
    k_sel = jnp.take_along_axis(node_k, idx, axis=3)  # [L, n, h, D, hd]
    v_sel = jnp.take_along_axis(node_v, idx, axis=3)

    def upd(c, r, pos, cnt):  # c [h, ctx, hd]; r [h, D, hd]
        cur = lax.dynamic_slice(c, (0, pos, 0), r.shape)
        blk = jnp.where((jnp.arange(D) < cnt)[None, :, None], r, cur)
        return lax.dynamic_update_slice(c, blk, (0, pos, 0))

    write = jax.vmap(jax.vmap(upd), in_axes=(0, 0, None, None))
    cache_k = write(cache_k, k_sel.astype(cache_k.dtype), positions + 1, n_acc)
    cache_v = write(cache_v, v_sel.astype(cache_v.dtype), positions + 1, n_acc)
    return cache_k, cache_v


def speculative_accept_tree(
    target_logits: jax.Array,
    block_tokens: jax.Array,
    draft_logits: jax.Array,
    width_limits: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    key: jax.Array,
    tree,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Longest-accepted-PATH walk over the scored tree. Per depth, the
    current node's children (in branch order, gated by ``width_limits
    [n, depth]`` — the per-slot tighten/adapt mask; width 0 at a depth
    ends that slot's walk as a limit clamp, not a rejection) are tried:

    - greedy rows (temperature <= 0) accept the child matching the
      target's own argmax at the current node — bit-identical to
      sequential greedy decoding by induction, for ANY draft, since a
      match at depth d makes depth d+1's scored context exact too;
    - sampled rows run recursive rejection resampling (SpecInfer): each
      candidate c_i (i.i.d. from the draft's q) accepts with probability
      min(1, r(c_i)/q(c_i)) against the running residual r (r starts at
      the target's p; every rejection folds q out: r <- norm(max(r - q,
      0))), so the emitted marginal at every position is exactly the
      target's.

    The bonus token at the final node samples the target's p directly —
    or, after a TRUE rejection (candidates existed and all lost), the
    final residual, which is what preserves the distribution. Returns
    (out_tokens [n, depth+1] — slot i emits out[:n_acc[i]+1], n_acc [n],
    path_idx [n, depth+1] block indices, path_idx[:, 0] = 0)."""
    n, width, vocab = target_logits.shape
    D = tree.depth
    rows = jnp.arange(n)
    greedy_t = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)  # [n, width]
    p_all = jax.nn.softmax(
        _transform_logits(target_logits, temperature[:, None], top_k[:, None]), axis=-1
    )
    q_all = jax.nn.softmax(
        _transform_logits(draft_logits, temperature[:, None], top_k[:, None]), axis=-1
    )
    child_tab = jnp.asarray(tree.child_table)  # [width, max_b]
    sampled_row = temperature > 0
    cur = jnp.zeros(n, jnp.int32)
    alive = jnp.ones(n, bool)
    n_acc = jnp.zeros(n, jnp.int32)
    rejected = jnp.zeros(n, bool)
    rej_dist = jnp.zeros((n, vocab), jnp.float32)
    path_blocks = []
    for d in range(1, D + 1):
        b = tree.branching[d - 1]
        kd = jax.random.fold_in(key, d)
        ch = child_tab[cur][:, :b]  # [n, b] candidate block indices
        ch_tok = jnp.take_along_axis(block_tokens, ch, axis=1)  # [n, b]
        p_cur = p_all[rows, cur]  # [n, V]
        q_cur = q_all[rows, cur]
        gt = greedy_t[rows, cur]  # [n]
        wl = width_limits[:, d - 1]
        step_ok = alive & (wl > 0)
        in_w = jnp.arange(b)[None, :] < wl[:, None]
        # greedy arm: at most one candidate can match (top-b is distinct)
        g_match = (ch_tok == gt[:, None]) & in_w
        g_any = jnp.any(g_match, axis=1)
        g_sel = jnp.argmax(g_match, axis=1).astype(jnp.int32)
        # sampled arm: recursive rejection over the i.i.d. candidates
        r = p_cur
        s_acc = jnp.zeros(n, bool)
        s_sel = jnp.zeros(n, jnp.int32)
        for bi in range(b):
            c_tok = ch_tok[:, bi]
            r_c = jnp.take_along_axis(r, c_tok[:, None], axis=1)[:, 0]
            q_c = jnp.take_along_axis(q_cur, c_tok[:, None], axis=1)[:, 0]
            u = jax.random.uniform(jax.random.fold_in(kd, bi), (n,))
            considered = in_w[:, bi] & ~s_acc
            ok_bi = considered & (u * q_c < r_c)  # u < r/q without dividing
            s_sel = jnp.where(ok_bi, bi, s_sel)
            s_acc = s_acc | ok_bi
            # a rejected candidate folds its proposal out of the residual
            upd = considered & ~ok_bi
            r_new = jnp.maximum(r - q_cur, 0.0)
            rs = jnp.sum(r_new, axis=-1, keepdims=True)
            r_new = jnp.where(rs > 1e-9, r_new / jnp.maximum(rs, 1e-9), p_cur)
            r = jnp.where(upd[:, None], r_new, r)
        acc_d = jnp.where(sampled_row, s_acc, g_any) & step_ok
        sel = jnp.where(sampled_row, s_sel, g_sel)
        new_cur = ch[rows, sel]
        # a TRUE rejection (candidates existed, all lost) pins the final
        # residual as this slot's bonus distribution; a limit clamp does
        # not (nothing was proposed there — bonus samples p directly)
        rej_now = step_ok & ~acc_d
        rej_dist = jnp.where((rej_now & ~rejected)[:, None], r, rej_dist)
        rejected = rejected | rej_now
        cur = jnp.where(acc_d, new_cur, cur)
        n_acc = n_acc + acc_d.astype(jnp.int32)
        alive = alive & acc_d
        path_blocks.append(cur)
    p_fin = p_all[rows, cur]
    dist = jnp.where(rejected[:, None], rej_dist, p_fin)
    bonus_sampled = jax.random.categorical(
        jax.random.fold_in(key, 0), jnp.log(dist + 1e-38), axis=-1
    ).astype(jnp.int32)
    bonus = jnp.where(sampled_row, bonus_sampled, greedy_t[rows, cur])
    path_idx = jnp.concatenate(
        [jnp.zeros((n, 1), jnp.int32), jnp.stack(path_blocks, axis=1)], axis=1
    )
    out = jnp.take_along_axis(block_tokens, path_idx[:, 1:], axis=1)  # [n, D]
    out = jnp.concatenate([out, jnp.zeros((n, 1), jnp.int32)], axis=1)
    out = out.at[rows, n_acc].set(bonus)
    return out, n_acc.astype(jnp.int32), path_idx


# ------------------------------------------------------ feature-level draft
# EAGLE-style feature drafting (Li et al., EAGLE): instead of a truncated-
# layer decoder re-embedding TOKENS, the draft head conditions on the
# TARGET's last hidden state — the final layer's residual-stream output,
# which the paged programs above already compute per committed position
# and thread out as ``hidden``. The head is ONE transformer layer plus a
# weight-tied LM head; its input at position j is
# ``fc([target_feature_{j-1} ; tok_emb(token_j)])`` (position 0 pads the
# feature with zeros), and during tree expansion the head autoregresses in
# FEATURE space: a depth-d node's input feature is its parent node's own
# output hidden (the draft's approximation of the target feature the
# target would have produced there). The target feature summarizes the
# whole prefix through the target's own stack, so acceptance beats any
# token-only draft of the same depth — the accept-rate headroom PR 8
# noted.
#
# Cache discipline is the tree draft's, unchanged: the head keeps a flat
# per-slot K/V cache ([1, n_slots, h, ctx, hd] — ``init_slot_cache`` on
# the head's one layer), the root step's write is never speculative,
# expansion K/V stays in-register, and only the accepted path commits
# (``draft_tree_commit`` with L=1). On warm (prefix-reuse) admissions the
# reused span has no draft K/V; ``starts`` opens the head's attention
# window at the computed suffix instead of reading zeroed rows.


def is_feature_draft(params) -> bool:
    """Whether a draft param tree is the feature-head layout (the ``fc``
    feature+embedding fuse marks it — a truncated-layer decoder has none)."""
    return isinstance(params, dict) and "fc" in params


def init_feature_draft(
    seed: int = 0, vocab: int = 512, hidden: int = 128, ffn: int = 256,
    max_len: int = 128,
) -> dict:
    """Feature-draft head params: the ``fc`` [2*hidden -> hidden] fuse, one
    decoder layer (same block structure as the target's, so every slot/tree
    building block above applies verbatim with L=1), own position table and
    a weight-tied LM head. ``hidden`` MUST equal the target's — the fuse
    consumes the target's feature vector directly.

    The rng draws follow ``init_decoder``'s positional order (tok_emb,
    pos_emb, the layer's qkv/attn_out/mlp_in/mlp_out; ``fc`` drawn LAST):
    built with the target's seed/vocab/hidden/ffn the head starts with
    the target's embeddings, weight-tied LM head, AND leading layer
    verbatim — the same stream-sharing trick the truncation draft rides,
    so distillation only has to learn the feature path, not re-derive the
    output geometry from scratch."""
    heads = _heads_for(hidden)
    if hidden % heads:
        raise ValueError(
            f"hidden={hidden} not divisible by its derived head count {heads}"
        )
    rng = np.random.default_rng(seed)
    return {
        "tok_emb": (rng.standard_normal((vocab, hidden)) * 0.02).astype(np.float32),
        "pos_emb": (rng.standard_normal((max_len, hidden)) * 0.02).astype(np.float32),
        "layers": [
            {
                "ln1": _ln_init(hidden),
                "qkv": _dense(rng, hidden, 3 * hidden),
                "attn_out": _dense(rng, hidden, hidden),
                "ln2": _ln_init(hidden),
                "mlp_in": _dense(rng, hidden, ffn),
                "mlp_out": _dense(rng, ffn, hidden),
            }
        ],
        "ln_f": _ln_init(hidden),
        "fc": _dense(rng, 2 * hidden, hidden),
    }


def _feature_fuse(params: dict, feats, tokens, pidx) -> jax.Array:
    """The head's input embedding: ``fc([feature ; tok_emb(token)])`` plus
    the position embedding. feats [n, m, d] aligned with tokens [n, m];
    pidx broadcastable position indices (already clipped)."""
    emb = jnp.asarray(params["tok_emb"])[tokens]  # [n, m, d]
    z = jnp.concatenate([feats.astype(emb.dtype), emb], axis=-1)
    x = z @ params["fc"]["w"].astype(emb.dtype) + params["fc"]["b"].astype(emb.dtype)
    return x + jnp.asarray(params["pos_emb"])[pidx]


def feature_sequence_logits(
    params: dict, ids: jax.Array, feats: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Teacher-forced head forward for distillation: ids[b, s] with the
    TARGET's aligned features feats[b, s, d] (``sequence_hidden``'s second
    output) -> (logits[b, s, vocab], head_hidden[b, s, d]). Input at
    position j fuses feature j-1 with token j (feature -1 = zeros), so
    logits[j] predicts token j+1 and head_hidden[j] is the head's
    approximation of feature j — the KL and feature-regression targets of
    the distillation recipe, and exactly the serving root step's
    conditioning (the root consumes the TRUE previous feature)."""
    ids = ids.astype(jnp.int32)
    heads = _heads(params)
    s = ids.shape[1]
    fin = jnp.concatenate(
        [jnp.zeros_like(feats[:, :1]), feats[:, :-1]], axis=1
    )
    x = _feature_fuse(params, fin, ids, jnp.arange(s)[None, :])
    for lp in params["layers"]:
        x, _, _ = _layer_prefill(lp, x, heads)
    return _logits(params, x), x


def feature_chunk_prefill(
    params: dict, cache_k, cache_v, tokens, target_hidden, prev_feat,
    positions, counts, starts,
) -> tuple[jax.Array, jax.Array]:
    """Teacher-forced head-side chunk prefill, fused into the target's
    chunk round: tokens[n, c] (the chunk's prompt ids), the target's fresh
    per-position hidden for the SAME chunk, and ``prev_feat[n, d]`` — the
    carried feature at position positions[i]-1 (the previous chunk's last
    hidden; zeroed when positions == starts, i.e. the slot's first chunk,
    matching the recipe's zero pad at position 0). Writes the head's K/V
    under the same ``counts`` mask the target chunk uses (counts-0 slots
    mutate nothing) with the ``starts`` attention window."""
    m = tokens.shape[1]
    heads = _heads(params)
    max_len = params["pos_emb"].shape[0]
    fin = jnp.concatenate([prev_feat[:, None, :], target_hidden[:, :-1, :]], axis=1)
    first = positions == starts
    fin = fin.at[:, 0, :].set(
        jnp.where(first[:, None], jnp.zeros_like(prev_feat), fin[:, 0, :])
    )
    pidx = jnp.clip(positions[:, None] + jnp.arange(m)[None, :], 0, max_len - 1)
    x = _feature_fuse(params, fin, tokens, pidx)
    new_k, new_v = [], []
    for li, lp in enumerate(params["layers"]):
        x, ck, cv = _layer_step_slots(
            lp, x, cache_k[li], cache_v[li], positions, heads,
            counts=counts, starts=starts,
        )
        new_k.append(ck)
        new_v.append(cv)
    return jnp.stack(new_k), jnp.stack(new_v)


def draft_propose_features(
    params: dict,
    cache_k: jax.Array,
    cache_v: jax.Array,
    feats: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    starts: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    key: jax.Array,
    tree,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """``draft_propose_tree`` with the feature head: the root step fuses
    the slot's carried TARGET feature (``feats[n, d]`` — position
    ``pos - 1``'s final-layer hidden, threaded out of the previous verify
    / plain step / chunk round) with the last emitted token; each
    expansion depth fuses the PARENT NODE's own head hidden with the
    candidate token — autoregression in feature space, per EAGLE. Same
    candidate rule, RNG stream, block layout, in-register node K/V, and
    return shape as the token tree draft, so the scheduler's verify /
    accept / commit round half is shared unchanged."""
    heads = _heads(params)
    max_len = params["pos_emb"].shape[0]
    n = tokens.shape[0]
    # root step: consume the last emitted token at ``pos`` (its write is
    # never speculative) conditioned on the carried target feature
    pidx0 = jnp.clip(positions, 0, max_len - 1)[:, None]
    x = _feature_fuse(params, feats[:, None, :], tokens[:, None], pidx0)
    new_k, new_v = [], []
    for li, lp in enumerate(params["layers"]):
        x, ck, cv = _layer_step_slots(
            lp, x, cache_k[li], cache_v[li], positions, heads, starts=starts
        )
        new_k.append(ck)
        new_v.append(cv)
    cache_k, cache_v = jnp.stack(new_k), jnp.stack(new_v)
    logits0 = _logits(params, x)[:, 0, :]
    block_logits = [logits0[:, None, :]]
    node_tokens = []
    ek: list = [None] * len(params["layers"])
    ev: list = [None] * len(params["layers"])
    parent_logits = logits0[:, None, :]  # [n, 1, V]
    parent_feats = x  # [n, 1, d] — the head's own hidden, root block
    mask_np = tree.ancestor_mask
    for d in range(1, tree.depth + 1):
        b = tree.branching[d - 1]
        c_d = tree.level_counts[d - 1]
        toks_d = _tree_candidates(parent_logits, temperature, top_k, key, d, b)
        pf = jnp.repeat(parent_feats, b, axis=1)  # [n, c_d, d] parent-major
        pidx = jnp.clip(positions + d, 0, max_len - 1)[:, None]
        x = _feature_fuse(params, pf, toks_d, pidx)
        node_tokens.append(toks_d)
        start = tree.level_starts[d - 1]
        sub_mask = jnp.asarray(mask_np[start : start + c_d, 1 : start + c_d])
        for li, lp in enumerate(params["layers"]):
            x, ek[li], ev[li] = _layer_tree_flat(
                lp, x, cache_k[li], cache_v[li], positions, heads,
                ek[li], ev[li], sub_mask, starts=starts,
            )
        depth_logits = _logits(params, x)  # [n, c_d, V]
        block_logits.append(depth_logits)
        parent_logits = depth_logits
        parent_feats = x
    return (
        jnp.concatenate(node_tokens, axis=1),
        jnp.concatenate(block_logits, axis=1),
        jnp.stack(ek),
        jnp.stack(ev),
        cache_k,
        cache_v,
    )


def reference_generate(params: dict, ids: np.ndarray, max_new_tokens: int) -> np.ndarray:
    """Cache-less reference: full forward per step (the slow obvious
    implementation the scan version must match token-for-token)."""
    ids = np.asarray(ids, dtype=np.int32)
    heads = _heads(params)
    for _ in range(max_new_tokens):
        x = _embed(params, jnp.asarray(ids))
        for lp in params["layers"]:
            x, _, _ = _layer_prefill(lp, x, heads)
        nxt = np.asarray(jnp.argmax(_logits(params, x[:, -1:, :]), axis=-1))
        ids = np.concatenate([ids, nxt.astype(np.int32)], axis=1)
    return ids
