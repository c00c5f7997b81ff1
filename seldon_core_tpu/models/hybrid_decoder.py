"""Hybrid state-space / attention causal decoder — the generative tier's
third family (Granite 4.0-H: ``model_type: granitemoehybrid``).

What the block has, beside the two families before it:

- most layers mix tokens with a Mamba-2 recurrence instead of attention: a
  ``[ssm_heads, ssm_head_dim, ssm_state]`` float32 state per layer and
  sequence, advanced one token at a time in the step and as ONE chunked
  scan in a prefill chunk (the published ``mamba_chunk_size`` is the tier's
  chunk cap, so a chunk dispatch is at most one scan chunk), behind a
  depthwise causal convolution whose cache is the last ``ssm_conv - 1``
  inputs;
- the layers named in ``attn_layers`` are grouped-query attention WITHOUT
  positions (``position_embedding_type: nope``), scores times
  ``attention_multiplier``; only they hold K/V pages (``decoder_dims``
  ``kv_layers``), written and gathered by the GPT-2 family's
  ``_paged_write`` / ``_paged_gather``;
- Granite's multipliers: the embedding times ``embedding_multiplier``, every
  residual branch times ``residual_multiplier``, the tied head's logits
  over ``logits_scaling``; a dense gated-SiLU MLP in every layer.

The recurrent state is the family's second cache, beside the pages
(``state_init``; serving/kv_pool.py holds it as ``pool.recurrent``): ROWS of
state ``[rows, heads, head_dim, state]`` and of conv inputs ``[rows,
(ssm_conv - 1) * conv_width]`` (time-major and flat: a last axis of 3 would
pad to a whole lane tile on the chip), float32, ONE ARRAY A MAMBA LAYER of
each (``state_zeros``): as one ``[ssm_layers, rows, ...]`` array the step's 36
in-place updates chained through a single 4.9 GB value, the chip's compiler
rematerialised one of them beside a read of the next layer's rows, and a
step over 64 slots answered 5-7 times the noise of a step over one (my chip
runs, PR 34). Row r is slot r's; the rows after the slots hold cached
prefixes' snapshots; one row stays zero. The step advances the slots' own rows in place, the rows
that generate (``rows``) and no other: a slot between two prefill chunks
rides the step as junk and must keep its state. A chunk's batch row names
the row it reads, the row it writes and the snapshot row it also writes
(``state_rows`` [3, n]; an index past the last row drops the write).

On one TPU the STEP's attention layers read the pool's pages where they lie
(ops/gqa_decode.py ``gqa_decode_attention``, where ``decode_programs.
_step_attn_kernel`` chooses it; chunks and the CPU keep the gather). Not
served: speculation, a decode mesh, the int8 pool, the host tier, prefix
export (each refuses by name, ``decoder.require_served``).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from seldon_core_tpu.models.decoder import (
    SCOPE_ATTN,
    SCOPE_ATTN_OUT,
    SCOPE_EMBED,
    SCOPE_LM_HEAD,
    SCOPE_MLP,
    SCOPE_QKV,
    FamilyNotServed,
    _paged_gather,
    _paged_step_reads,
    _paged_write,
    counted_state_programs,
    kv_pool_zeros,
    paged_state_greedy_generate,
)
from seldon_core_tpu.models.moe_decoder import _SCORES_BATCH_BYTES, _attend, _rms
from seldon_core_tpu.ops.gqa_decode import gqa_decode_attention

# device scopes this family adds, each nested under a decoder.PAGED_SCOPES
# name so readers of those still see whole steps: ``qkv/ssm_in``,
# ``attn/ssm_conv``, ``attn/ssm_scan`` (the recurrence or the chunked scan,
# with the state rows' read and write), ``attn_out/ssm_norm``,
# ``attn_out/ssm_out``
SCOPE_SSM_IN = "ssm_in"
SCOPE_SSM_CONV = "ssm_conv"
SCOPE_SSM_SCAN = "ssm_scan"
SCOPE_SSM_NORM = "ssm_norm"
SCOPE_SSM_OUT = "ssm_out"

# a scan chunk's decay matrix [rows, heads, c, c] in float32 above this goes
# in blocks of rows (lax.map): the (64, 256) chunk program would hold 1.07 GB
# of it a layer beside 12 GB of weights and state
_SCAN_BLOCK_BYTES = 128 << 20
# the scan's matrix products take float32 operands whole: at the chip's
# default precision they are rounded to bfloat16 first, and the state and the
# decay are the float32 part of the model
_SCAN_PRECISION = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class HybridDecoderConfig:
    """The published keys of a Granite-4.0-H decoder (zoo://hybrid_decoder)."""

    vocab: int = 512
    hidden: int = 64
    layers: int = 4
    attn_layers: tuple = (1,)  # the layers that are attention; every other is Mamba-2
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    ffn: int = 128  # shared_intermediate_size: the gated MLP's width
    ssm_heads: int = 8
    ssm_head_dim: int = 16
    ssm_state: int = 16
    ssm_conv: int = 4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0625
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    max_len: int = 131072

    def __post_init__(self):
        if self.heads % self.kv_heads:
            raise ValueError(f"heads={self.heads} not a multiple of kv_heads={self.kv_heads}")
        if any(not 0 <= i < self.layers for i in self.attn_layers):
            raise ValueError(f"attn_layers={self.attn_layers} outside 0..{self.layers - 1}")
        if self.ssm_conv < 2:
            raise ValueError("ssm_conv must be >= 2")

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:  # xs | B | C, one group
        return self.d_inner + 2 * self.ssm_state

    @property
    def ssm_layers(self) -> int:
        return self.layers - len(set(self.attn_layers))

    def cache_index(self, layer: int) -> int:
        """A layer's index in ITS cache: the attention layers count through
        the KV pool's layers, the Mamba layers through the state's."""
        attn = layer in self.attn_layers
        return sum((i in self.attn_layers) == attn for i in range(layer))


# ----------------------------------------------------------------- weights


def init_hybrid_decoder(cfg: HybridDecoderConfig, seed: int = 0, dtype=jnp.bfloat16) -> dict:
    """Random weights drawn ON THE DEVICE in ``dtype``, layer by layer (layer
    i's key is fold_in(seed, i)). Projections normal(0, 0.02), norms 1.

    The recurrence's own parameters as the published initialiser draws them,
    so that random weights neither freeze nor erase the state: ``A`` uniform
    in [1, 16] (``A_log`` its log), the time step log-uniform in [1e-3, 1e-1]
    (``dt_bias`` its inverse softplus), ``D`` 1; the convolution uniform in
    +-1/sqrt(ssm_conv), a depthwise Conv1d's default.

    The embedding at std 0.004: the head is the embedding (tied) and the
    stream starts at 12 times a row of it, so a token's own row scores
    E_t . E_t, a coherent sum sqrt(hidden) = 45 times the spread of every
    other row's score, unless what the layers add outweighs 12 E_t about
    fifteen to one. At std 1 (as models/moe_decoder.py draws an untied
    embedding) every position's best token is its own input, whatever the
    state holds, and the comparison with the reference sees nothing; at
    0.01 two positions in five of a 20-layer build at the published widths
    still predict their own input, at 0.004 one in a hundred of the
    40-layer one (float32 on the CPU, PR 34)."""
    root = jax.random.key(int(seed), impl="rbg")
    h, n, w = cfg.ssm_heads, cfg.ssm_state, cfg.conv_width

    def draw(key, shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def mlp(k1, k2):
        return {
            "ln2": jnp.ones((cfg.hidden,), dtype),
            "mlp_in": draw(k1, (cfg.hidden, 2 * cfg.ffn)),
            "mlp_out": draw(k2, (cfg.ffn, cfg.hidden)),
        }

    @jax.jit
    def attn_layer(key):
        ks = jax.random.split(key, 4)
        return {
            "ln1": jnp.ones((cfg.hidden,), dtype),
            "attn_qkv": draw(ks[0], (cfg.hidden, cfg.q_width + 2 * cfg.kv_width)),
            "attn_o": draw(ks[1], (cfg.q_width, cfg.hidden)),
            **mlp(ks[2], ks[3]),
        }

    @jax.jit
    def ssm_layer(key):
        ks = jax.random.split(key, 7)
        dt = jnp.exp(jax.random.uniform(ks[3], (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        bound = 1.0 / math.sqrt(cfg.ssm_conv)
        return {
            "ln1": jnp.ones((cfg.hidden,), dtype),
            "ssm_in": draw(ks[0], (cfg.hidden, 2 * cfg.d_inner + 2 * n + h)),  # z | xBC | dt
            "conv_w": jax.random.uniform(ks[1], (cfg.ssm_conv, w), jnp.float32, -bound, bound).astype(dtype),
            "conv_b": jax.random.uniform(ks[2], (w,), jnp.float32, -bound, bound).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(ks[4], (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
            "D": jnp.ones((h,), dtype),
            "ssm_norm": jnp.ones((cfg.d_inner,), dtype),
            "ssm_out": draw(ks[5], (cfg.d_inner, cfg.hidden)),
            **mlp(*jax.random.split(ks[6])),
        }

    params = {
        "tok_emb": jax.jit(lambda k: draw(k, (cfg.vocab, cfg.hidden), 0.004))(
            jax.random.fold_in(root, 1 << 20)
        ),
        "ln_f": jnp.ones((cfg.hidden,), dtype),
    }
    params["layers"] = [
        (attn_layer if i in cfg.attn_layers else ssm_layer)(jax.random.fold_in(root, i))
        for i in range(cfg.layers)
    ]
    return params


def state_zeros(cfg: HybridDecoderConfig, rows: int) -> tuple:
    """The zeroed state cache, float32, the row at axis 0: one state array
    [rows, heads, head_dim, state] a Mamba layer, then one conv array [rows,
    (ssm_conv - 1) * conv_width] a Mamba layer (``2 * ssm_layers`` arrays:
    layer i's are ``rec[i]`` and ``rec[ssm_layers + i]``)."""
    n = cfg.ssm_layers
    state = (rows, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    conv = (rows, (cfg.ssm_conv - 1) * cfg.conv_width)
    return tuple(jnp.zeros(state if i < n else conv, jnp.float32) for i in range(2 * n))


# ----------------------------------------------------------------- forward


def _scan_chunk(dt, a_neg, xs, b, c, s_in):
    """One chunk of the Mamba-2 recurrence in its chunked form. dt [n, m, h]
    (0 on a row past the slot's count: decay 1, no input, the state stands),
    a_neg [h] = -exp(A_log), xs [n, m, h, p], b / c [n, m, N], s_in
    [n, h, p, N]; all float32. Returns (y [n, m, h, p], s_out)."""
    m = dt.shape[1]
    cs = jnp.cumsum(dt * a_neg, axis=1)  # [n, m, h], <= 0 and falling
    dtx = dt[..., None] * xs
    causal = jnp.tril(jnp.ones((m, m), bool))
    diff = cs.transpose(0, 2, 1)[:, :, :, None] - cs.transpose(0, 2, 1)[:, :, None, :]  # [n, h, t, s]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    dot = functools.partial(jnp.einsum, precision=_SCAN_PRECISION)
    cb = dot("ntk,nsk->nts", c, b)
    y = dot("nhts,nshp->nthp", decay * cb[:, None], dtx)
    y = y + jnp.exp(cs)[..., None] * dot("ntk,nhpk->nthp", c, s_in)
    tail = jnp.exp(cs[:, -1:, :] - cs)  # [n, m, h]: what is left of step s at the chunk's end
    s_out = jnp.exp(cs[:, -1, :])[:, :, None, None] * s_in + dot(
        "nshp,nsk->nhpk", tail[..., None] * dtx, b
    )
    return y, s_out


def _scan_blocked(dt, a_neg, xs, b, c, s_in):
    """``_scan_chunk`` with the rows in blocks where the decay matrix of all
    of them would pass ``_SCAN_BLOCK_BYTES``."""
    n, m, h = dt.shape
    blk = n
    while blk > 1 and 4 * blk * h * m * m > _SCAN_BLOCK_BYTES and blk % 2 == 0:
        blk //= 2
    if blk == n:
        return _scan_chunk(dt, a_neg, xs, b, c, s_in)
    split = lambda t: t.reshape(n // blk, blk, *t.shape[1:])  # noqa: E731
    y, s_out = lax.map(
        lambda a: _scan_chunk(a[0], a_neg, *a[1:]), tuple(split(t) for t in (dt, xs, b, c, s_in))
    )
    return y.reshape(n, *y.shape[2:]), s_out.reshape(n, *s_out.shape[2:])


def _mamba(cfg: HybridDecoderConfig, si: int, p, x, rec, counts, rows, state_rows):
    """The Mamba-2 mixer over x[n, m, d], Mamba layer ``si``'s state and conv
    arrays of ``rec`` (``state_zeros``). The step (``state_rows`` None; m = 1): batch row r is
    state row r, advanced where ``rows[r]``. A chunk: row r reads
    ``state_rows[0, r]``, writes ``state_rows[1, r]`` and
    ``state_rows[2, r]`` (an index past the rows is dropped); positions past
    ``counts[r]`` leave state and conv cache as they were. Returns (the
    mixer's output [n, m, d], rec)."""
    state, conv = rec[si], rec[cfg.ssm_layers + si]
    if state_rows is not None:
        # a layer's rows are gathered when its input exists, not before: the
        # gathers depend on the program's arguments alone, and the chip's
        # compiler otherwise schedules all 36 layers' ahead of the first
        # layer (4.8 GB of gathered state at 64 rows)
        x, state, conv = lax.optimization_barrier((x, state, conv))
    n, m, _ = x.shape
    h, hd, ns, w, k = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.conv_width, cfg.ssm_conv
    f32 = jnp.float32
    with jax.named_scope(SCOPE_QKV), jax.named_scope(SCOPE_SSM_IN):
        zxd = _rms(p["ln1"], x, cfg.rms_eps) @ p["ssm_in"].astype(x.dtype)
        z, xbc, dt = jnp.split(zxd, [cfg.d_inner, cfg.d_inner + w], axis=-1)
    valid = jnp.ones((n, m), bool)
    if counts is not None:
        valid &= jnp.arange(m)[None, :] < counts[:, None]
    if rows is not None:
        valid &= rows[:, None]
    with jax.named_scope(SCOPE_ATTN):
        with jax.named_scope(SCOPE_SSM_CONV):
            conv_in = conv[:n] if state_rows is None else conv[state_rows[0]]
            # the last k - 1 inputs, then the dispatch's own: [n, k - 1 + m, w]
            seq = jnp.concatenate([conv_in.reshape(n, k - 1, w), xbc.astype(f32)], axis=1)
            cw = p["conv_w"].astype(f32)
            act = p["conv_b"].astype(f32) + sum(cw[j] * seq[:, j : j + m] for j in range(k))
            xbc = jax.nn.silu(act)
            # the cache after the dispatch: the k - 1 inputs that end at the
            # row's last real one (all of the old cache where it has none)
            last = jnp.sum(valid, axis=1, dtype=jnp.int32)
            conv_out = jax.vmap(lambda s, at: lax.dynamic_slice_in_dim(s, at, k - 1))(seq, last)
            conv_out = conv_out.reshape(n, (k - 1) * w)
        with jax.named_scope(SCOPE_SSM_SCAN):
            xs, b, c = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + ns], axis=-1)
            xs = xs.reshape(n, m, h, hd)
            dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
            dt = jnp.where(valid[..., None], dt, 0.0)
            a_neg = -jnp.exp(p["A_log"].astype(f32))
            if state_rows is None:
                s_in = state[:n]
                decay = jnp.exp(dt[:, 0] * a_neg)  # [n, h]; 1 where the row stands
                s_out = decay[:, :, None, None] * s_in + (
                    (dt[:, 0, :, None] * xs[:, 0])[..., None] * b[:, 0, None, None, :]
                )
                # a product and a sum over the state as it is written, not a
                # matrix product that would read it again rounded to bfloat16
                y = jnp.sum(s_out * c[:, 0, None, None, :], axis=-1)[:, None]
                state = state.at[:n].set(s_out)
                conv = conv.at[:n].set(conv_out)
            else:
                y, s_out = _scan_blocked(dt, a_neg, xs, b, c, state[state_rows[0]])
                for to in (state_rows[1], state_rows[2]):
                    state = state.at[to].set(s_out, mode="drop")
                    conv = conv.at[to].set(conv_out, mode="drop")
            y = y + p["D"].astype(f32)[:, None] * xs
    with jax.named_scope(SCOPE_ATTN_OUT):
        with jax.named_scope(SCOPE_SSM_NORM):
            g = y.reshape(n, m, cfg.d_inner) * jax.nn.silu(z.astype(f32))
            g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_eps)
            g = g.astype(x.dtype) * p["ssm_norm"].astype(x.dtype)
        with jax.named_scope(SCOPE_SSM_OUT):
            out = g @ p["ssm_out"].astype(x.dtype)
    rec = tuple(state if i == si else conv if i == cfg.ssm_layers + si else a for i, a in enumerate(rec))
    return out, rec


def _attention(cfg: HybridDecoderConfig, ki: int, p, x, pool, bt, positions, counts, reads=None, interpret=False):
    """Grouped-query attention without positions over pool layer ``ki``:
    K and V scatter through the block tables and attention reads them back,
    like the other families' write-then-read: through the gather, or, where
    the step was given ``reads`` (``decoder._paged_step_reads``), through
    ops/gqa_decode.py's kernel, which reads the pages where they lie.
    Returns (the mixer's output [n, m, d], pool)."""
    n, m, _ = x.shape
    with jax.named_scope(SCOPE_QKV):
        qkv = _rms(p["ln1"], x, cfg.rms_eps) @ p["attn_qkv"].astype(x.dtype)
        q, k, v = jnp.split(qkv, [cfg.q_width, cfg.q_width + cfg.kv_width], axis=-1)
        q = q.reshape(n, m, cfg.heads, cfg.head_dim)
    pool = _paged_write(pool, ki, k, v, bt, positions, counts)
    scale = cfg.attention_multiplier
    if reads is not None:
        with jax.named_scope(SCOPE_ATTN):
            ctx = gqa_decode_attention(q[:, 0], pool[0], pool[1], ki, bt, *reads, scale=scale, interpret=interpret)[:, None]
    else:
        ck, cv = _paged_gather(pool, ki, bt, cfg.kv_heads)  # [n, g, K, d] float32
        with jax.named_scope(SCOPE_ATTN):
            q_pos = positions[:, None] + jnp.arange(m, dtype=positions.dtype)[None, :]
            visible = jnp.arange(ck.shape[2], dtype=positions.dtype)[None, None, :] <= q_pos[:, :, None]
            if 4 * n * cfg.heads * m * ck.shape[2] > _SCORES_BATCH_BYTES:
                ctx = lax.map(
                    lambda a: _attend(*(t[None] for t in a), scale=scale)[0], (q, ck, cv, visible)
                )
            else:
                ctx = _attend(q, ck, cv, visible, scale=scale)
    with jax.named_scope(SCOPE_ATTN_OUT):
        return ctx @ p["attn_o"].astype(x.dtype), pool


def _forward(
    cfg, params, pool, rec, bt, tokens, positions, counts=None, rows=None, pick=None, state_rows=None, attn_kernel=""
):
    """Shared body of the paged programs: tokens[n, m], slot i's query j at
    positions[i] + j. ``counts`` [n] (chunk rounds): the first counts[i]
    rows of slot i are real. ``rows`` [n] bool (the step): the slots that
    generate. ``pick`` [n]: the head runs on that one query of each row.
    ``state_rows`` [3, n] int32: ``_mamba``. ``attn_kernel`` (static; "" |
    "mosaic" | "interpret": ``decode_programs._step_attn_kernel``'s answer)
    lets a dispatch of ONE query a slot read the pool through
    ops/gqa_decode.py's kernel; every other shape gathers. Returns (logits
    [n, m or 1, vocab] float32, pool, rec, counters[2] int32:
    ``HybridDecoder.frame_counters``)."""
    n, m = tokens.shape
    res = cfg.residual_multiplier
    reads, run_pages = _paged_step_reads(attn_kernel, m, pool, bt, positions, rows)
    with jax.named_scope(SCOPE_EMBED):
        x = jnp.asarray(params["tok_emb"])[tokens] * jnp.asarray(cfg.embedding_multiplier, params["tok_emb"].dtype)
    for li, p in enumerate(params["layers"]):
        ci = cfg.cache_index(li)
        if li in cfg.attn_layers:
            mix, pool = _attention(cfg, ci, p, x, pool, bt, positions, counts, reads, attn_kernel == "interpret")
        else:
            mix, rec = _mamba(cfg, ci, p, x, rec, counts, rows, state_rows)
        with jax.named_scope(SCOPE_ATTN_OUT):
            x = x + mix * jnp.asarray(res, x.dtype)
        with jax.named_scope(SCOPE_MLP):
            gu = _rms(p["ln2"], x, cfg.rms_eps) @ p["mlp_in"].astype(x.dtype)
            g, u = jnp.split(gu, 2, axis=-1)
            x = x + ((jax.nn.silu(g) * u) @ p["mlp_out"].astype(x.dtype)) * jnp.asarray(res, x.dtype)
    with jax.named_scope(SCOPE_LM_HEAD):
        last = x if pick is None else jnp.take_along_axis(x, pick[:, None, None], axis=1)
        logits = jnp.einsum(  # the tied head: the embedding's rows again
            "nmd,vd->nmv", _rms(params["ln_f"], last, cfg.rms_eps), jnp.asarray(params["tok_emb"]).astype(x.dtype),
            preferred_element_type=jnp.float32,
        ) / cfg.logits_scaling
        live = jnp.ones((n,), bool) if counts is None else counts > 0
        if rows is not None:
            live &= rows
        advanced = jnp.sum(live, dtype=jnp.int32)[None]
    return logits, pool, rec, jnp.concatenate([advanced, run_pages])


def _generate(cfg, params, ids, max_new_tokens: int):
    """The fused fallback apply of a deployment without ``tpu.decode_slots``
    (``decoder.paged_state_greedy_generate``) over a private pool and
    private state rows."""
    dims = {"kv_layers": len(cfg.attn_layers), "kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim}
    return paged_state_greedy_generate(
        functools.partial(_forward, cfg, params),
        lambda n_pages, ps: kv_pool_zeros(dims, n_pages, ps, params["tok_emb"].dtype),
        functools.partial(state_zeros, cfg), ids, max_new_tokens,
    )


# ------------------------------------------------------------------ family


@dataclasses.dataclass(frozen=True)
class HybridDecoder:
    """The family object of one configuration: what the decode scheduler
    asks of a family (``decoder.GPT2Decoder``'s docstring has the list),
    with the configuration's static sizes bound. Hashable: equal
    configurations share compiled programs."""

    cfg: HybridDecoderConfig

    name = "hybrid"
    # what the programs' readback carries after the tokens (FlightFrame
    # fields): the rows whose state advanced, and where the step's kernel ran
    # the pages it fetched in run DMAs (one layer's K)
    frame_counters = ("ssm_rows", "attn_run_pages")
    # beside the plain rounds: a step that reads the pool in place (ops/gqa_decode.py's kernel)
    serves = frozenset({"attn_kernel"})

    def decoder_dims(self, params: dict) -> dict:
        if "lm_head" in params or not any("ssm_in" in p for p in params["layers"]):
            raise FamilyNotServed("not a hybrid decoder's parameters (models/hybrid_decoder.py layout)")
        c = self.cfg
        return {
            "layers": len(params["layers"]), "kv_layers": len(c.attn_layers), "heads": c.heads,
            "kv_heads": c.kv_heads, "hidden": c.hidden, "head_dim": c.head_dim, "q_width": c.q_width,
            "vocab": params["tok_emb"].shape[0], "max_len": c.max_len,
        }

    def paged_kv_init(self, params, n_pages, page_size, dtype=jnp.float32, kv_dtype=""):
        return kv_pool_zeros(self.decoder_dims(params), n_pages, page_size, dtype, kv_dtype)

    def state_init(self, params, rows: int) -> tuple:
        """The zeroed state cache of ``rows`` rows (``state_zeros``): float32
        whatever the serving dtype."""
        return state_zeros(self.cfg, rows)

    def paged_forward(
        self, params, pool, rec, bt, tokens, positions, counts=None, rows=None, pick=None, state_rows=None,
        attn_kernel="",
    ):
        return _forward(
            self.cfg, params, pool, rec, bt, tokens, positions, counts, rows, pick, state_rows, attn_kernel
        )

    @functools.lru_cache(maxsize=None)
    def fused_programs(self, attn_kernel: str = ""):
        """This family's step and chunk bodies (``decoder.
        counted_state_programs``: both carry the state cache beside the
        pool, the step takes ``rows``, the chunk ``state_rows``); with
        ``attn_kernel`` the one whose dispatch is one query a slot, the step,
        reads the pool through the kernel. Cached: equal configurations
        share compiled programs."""
        return counted_state_programs(functools.partial(self.paged_forward, attn_kernel=attn_kernel))

    def generate(self, params, ids, max_new_tokens: int):
        return _generate(self.cfg, params, ids, max_new_tokens)


@functools.lru_cache(maxsize=None)
def hybrid_family(cfg: HybridDecoderConfig) -> HybridDecoder:
    return HybridDecoder(cfg)
