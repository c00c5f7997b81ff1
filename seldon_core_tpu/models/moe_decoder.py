"""Sparse-expert causal decoder — the generative tier's second family.

What today's open mixture-of-experts decoders have in their block, beside
the GPT-2 block of ``models/decoder.py``:

- RMSNorm, no biases, an untied output head;
- grouped-query attention: ``heads`` query heads of ``head_dim`` (the query
  width ``heads * head_dim`` need not be the hidden size), ``kv_heads``
  key/value heads, query head j reading key/value head ``j // (heads //
  kv_heads)``;
- rotary positions, applied to q and k BEFORE the cache write at the row's
  own position, so pages hold rotated keys and a prefix hit needs nothing
  new: plain frequencies on the sliding layers, YaRN on the full ones (over
  the first ``rotary_full`` share of a head's dimensions; the rest pass);
- a layer pattern of period ``period``: ``period - 1`` sliding-window layers
  (a query sees the ``window`` newest keys) and one full-attention layer,
  last of its period or (``full_first``) first;
- every feed-forward a routed expert layer: softmax over ``experts`` in
  float32, top ``experts_per_tok``, gates renormalised, gated-SiLU experts
  of width ``ffn`` (``ops/moe.py`` ``moe_topk_ffn``).

What a configuration may add to that block (each off by default):
``heads_window`` (another query head count on the sliding layers: weights of
two shapes in one model), ``attn_gate`` (a per-head sigmoid gate on the
attention output, from the layer's normed input: arXiv:2505.06708),
``dense_layers`` leading layers with a dense gated MLP of width
``dense_ffn``, ``shared_expert`` (one more expert every token takes,
ungated), ``routed_scale`` on the renormalised gates, and ONE CHIP'S SHARE of
an expert-parallel layer: ``experts_held`` experts from ``first_expert`` (the
router keeps ``experts`` outputs, a pick that lands on an absent expert adds
nothing: ``ops/moe.py`` ``moe_held_ffn``).

It serves through the SAME paged machinery as the GPT-2 family: token-row
planes ``[L, pages, page_size, kv_heads * head_dim]`` written by
``decoder._paged_write``, read by ``decoder._paged_gather`` (on a TPU the
STEP and the prefill CHUNKS read them where they lie instead: ops/gqa_decode.py
``gqa_decode_attention`` and ``gqa_chunk_attention``, where
``decode_programs._step_attn_kernel`` chooses a kernel and, for a chunk,
``_chunk_takes`` holds; a verify and the CPU keep the gather), copied by
``decoder.paged_copy``. A configuration with sliding layers has TWO PAGE
KINDS (``decoder.kv_pool_zeros``, serving/kv_pool.py): the full layers'
planes hold every position, the sliding layers' planes have pages of their
own under a block table of their own, and a page wholly older than the
window is given back while the sequence runs. A sliding layer gathers
through a WINDOWED block table: the pages that cover its queries' windows,
taken from the slot's window-kind table by position (a page given back
reads as junk page 0); the mask is by absolute key position. The kernels
walk the same sub-table: the step's with the in-table position of each
slot's oldest visible key beside it (``gqa_decode.step_reads``' ``first``),
the chunk's with the window itself, a lower bound a query.

A family is what ``serving/decode_scheduler.py`` takes from the model's
spec (``ModelSpec.generative["family"]``) and asks (the list is
``decoder.GPT2Decoder``'s docstring): ``name``, ``decoder_dims``,
``paged_kv_init``, ``frame_counters``, ``serves``, ``fused_programs``.
``MoEDecoder`` counts its routing (``frame_counters``) through
``paged_forward``'s extra output, real rows only; the scheduler lands the
counts in the round's FlightFrame.

Not served yet: speculation (a draft, a tree, a feature head) and
tensor-parallel decode refuse this family at build (``FamilyNotServed``).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from seldon_core_tpu.models.decoder import (
    SCOPE_ATTN,
    SCOPE_ATTN_OUT,
    SCOPE_EMBED,
    SCOPE_KV_GATHER,
    SCOPE_LM_HEAD,
    SCOPE_MLP,
    SCOPE_QKV,
    FamilyNotServed,
    _paged_gather,
    _paged_write,
    counted_programs,
    kv_pool_zeros,
    paged_gqa_attention,
    paged_greedy_generate,
)
from seldon_core_tpu.ops.gqa_decode import chunk_reads, gqa_chunk_tiles, pages_fetched, step_reads
from seldon_core_tpu.ops.moe import (
    HELD_COUNTERS,
    N_HELD_COUNTERS,
    SCOPE_DENSE_MLP,
    SCOPE_MOE_COMBINE,
    SCOPE_SHARED_EXPERT,
    gated_mlp,
    moe_held_ffn,
    moe_topk_ffn,
    route_topk,
)
from seldon_core_tpu.ops.paged_attention import window_first_page, window_pages

# device scopes this family adds, each nested under a decoder.PAGED_SCOPES
# name so readers of those still see the time: ``qkv/rope``,
# ``win|full/kv_gather``, ``win|full/attn``, ``attn_out/gate``, ``mlp/moe_*``,
# ``mlp/shared_expert``, ``mlp/dense`` (ops/moe.py)
SCOPE_ROPE = "rope"
SCOPE_ATTN_GATE = "gate"  # the per-head sigmoid gate on the attention output
SCOPE_WIN = "win"  # a sliding-window layer's gather and attention
SCOPE_FULL = "full"  # a full-attention layer's

# a dispatch's scores [slots, heads, queries, keys] in float32 above this go
# slot by slot (lax.map) instead of as one batch: the 256-token chunk round
# at 3264 keys would hold 1.7 GB of scores beside 11 GB of weights
_SCORES_BATCH_BYTES = 512 << 20


@dataclasses.dataclass(frozen=True)
class MoEDecoderConfig:
    """The published keys of a sparse-expert decoder (zoo://moe_decoder)."""

    vocab: int = 512
    hidden: int = 64
    layers: int = 4
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    ffn: int = 32  # ONE expert's width
    experts: int = 8
    experts_per_tok: int = 2
    window: int = 8
    period: int = 4  # layer i is full attention where i % period == period - 1 (0 with full_first)
    rope_theta: float = 10000.0
    yarn_factor: float = 4.0
    yarn_original: int = 16
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 0.0  # 0 = 0.1 * ln(yarn_factor) + 1
    rms_eps: float = 1e-6
    max_len: int = 131072
    # what a configuration may add to the block (module docstring), each off by default
    heads_window: int = 0  # query heads of a sliding layer; 0 = ``heads``
    attn_gate: bool = False
    rotary_full: float = 1.0  # the share of a head's dimensions a full layer rotates
    rope_theta_window: float = 0.0  # a sliding layer's theta; 0 = ``rope_theta``
    full_first: bool = False
    dense_layers: int = 0
    dense_ffn: int = 0
    shared_expert: bool = False
    experts_held: int = 0  # 0 = all of them
    first_expert: int = 0
    routed_scale: float = 1.0

    def __post_init__(self):
        for h in (self.heads, self.heads_window or self.heads):
            if h % self.kv_heads:
                raise ValueError(f"heads={h} not a multiple of kv_heads={self.kv_heads}")
        rot = self.head_dim * self.rotary_full
        if self.head_dim % 2 or rot != int(rot) or int(rot) % 2 or not 0 < rot <= self.head_dim:
            raise ValueError(f"head_dim={self.head_dim} x rotary_full={self.rotary_full} must be even (rotary pairs)")
        if not 1 <= self.experts_per_tok <= self.experts:
            raise ValueError(f"experts_per_tok={self.experts_per_tok} of experts={self.experts}")
        if self.layers < 1 or self.period < 1 or self.window < 1:
            raise ValueError("layers, period and window must be >= 1")
        if not 0 <= self.dense_layers <= self.layers or (self.dense_layers and self.dense_ffn < 1):
            raise ValueError(f"dense_layers={self.dense_layers} of layers={self.layers}, dense_ffn={self.dense_ffn}")
        if not 0 <= self.first_expert <= self.experts - self.held:
            raise ValueError(f"experts [{self.first_expert}, +{self.held}) of {self.experts}")

    @property
    def q_width(self) -> int:
        """A full layer's query width (a sliding layer's: ``heads_of``)."""
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def held(self) -> int:
        return self.experts_held or self.experts

    @property
    def n_counters(self) -> int:
        """What an expert layer counts: rows, experts hit, the fullest expert's
        rows, and over a share of the experts the picks that landed on it and
        the layer calls that ran the grouped form, and ran it compact."""
        return N_HELD_COUNTERS if self.experts_held else 3

    def is_full(self, layer: int) -> bool:
        return layer % self.period == (0 if self.full_first else self.period - 1)

    def heads_of(self, layer: int) -> int:
        return self.heads if self.is_full(layer) else self.heads_window or self.heads

    @property
    def window_layers(self) -> int:
        return sum(not self.is_full(i) for i in range(self.layers))

    @property
    def two_kinds(self) -> bool:
        """Whether the pool holds two page kinds: there are layers of both
        kinds (a model of sliding layers alone keeps one table)."""
        return 0 < self.window_layers < self.layers

    @property
    def kind_layers(self) -> int:
        """``decoder_dims``' ``kv_window_layers``: the layers whose pages are the window kind."""
        return self.window_layers if self.two_kinds else 0

    def plane_layer(self, layer: int) -> int:
        """The layer's index inside its page kind's planes."""
        if not self.two_kinds:
            return layer
        return sum(self.is_full(i) == self.is_full(layer) for i in range(layer))

    @property
    def attention_factor(self) -> float:
        return self.yarn_attention_factor or 0.1 * math.log(self.yarn_factor) + 1.0


# ------------------------------------------------------------------ rotary


def rope_inv_freq(cfg, full: bool) -> np.ndarray:
    """[d / 2] float32 rotary frequencies of a layer kind over the ``d``
    dimensions it rotates. Sliding layers: theta^(-2i/d), d the whole head.
    Full layers (YaRN; d = ``rotary_full`` of the head): fast dimensions keep
    their frequency, slow ones are divided by ``yarn_factor``, with a linear
    ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times over the original context."""
    if not full:
        d = cfg.head_dim
        theta = getattr(cfg, "rope_theta_window", 0.0) or cfg.rope_theta
        return (theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)).astype(np.float32)
    d = int(cfg.head_dim * getattr(cfg, "rotary_full", 1.0))
    i = np.arange(d // 2, dtype=np.float64)
    plain = cfg.rope_theta ** (-2.0 * i / d)

    def turns_dim(n: float) -> float:
        return d * math.log(cfg.yarn_original / (2.0 * math.pi * n)) / (2.0 * math.log(cfg.rope_theta))

    lo = max(math.floor(turns_dim(cfg.yarn_beta_fast)), 0)
    hi = min(math.ceil(turns_dim(cfg.yarn_beta_slow)), d - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((1.0 - ramp) * plain + ramp * plain / cfg.yarn_factor).astype(np.float32)


def _rope(x, pos, inv_freq: np.ndarray, factor: float):
    """Rotate-half rotary embedding of x[n, m, h, d] at pos[n, m] over its
    first ``2 * len(inv_freq)`` dimensions (the rest pass through), cos and
    sin scaled by ``factor`` (YaRN's attention factor; 1 on plain layers)."""
    ang = pos[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)  # [n, m, rot/2]
    cos = (jnp.cos(ang) * factor)[:, :, None, :]
    sin = (jnp.sin(ang) * factor)[:, :, None, :]
    rot = 2 * len(inv_freq)
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return turned if rot == x.shape[-1] else jnp.concatenate([turned, x[..., rot:]], axis=-1)


# ----------------------------------------------------------------- weights


def init_moe_decoder(cfg: MoEDecoderConfig, seed: int = 0, dtype=jnp.bfloat16) -> dict:
    """Random weights (std 0.02, norms 1; embedding rows std 1) drawn ON THE
    DEVICE in ``dtype``, layer by layer: 5 B parameters never exist as
    float32 on the host. Layer i's key is fold_in(seed, i), so a build of
    fewer layers is the deeper build's prefix.

    Why the embedding is drawn at std 1: random projections make attention
    near-uniform, so what a layer adds to the residual stream is an average
    over the context — the SAME vector for every slot that shares a prefix,
    and one that feeds itself (the next layer's value rows all carry it).
    With the embedding at 0.02 too, that common part outgrows a token's own
    row after one layer and every row of a step picks the same 8 experts
    (12.5 of 64 hit a layer; my chip run, PR 28), which no trained,
    load-balanced router does. At std 1 it outgrows a token's row only in
    the deeper layers (16 rows over one context hit 57 experts in layer 1,
    22 in layer 12). Other draws were tried on the chip (PR 28, PERF.md
    section 6): a larger attention output projection collapses routing by
    layer 4; anything that keeps routing even in every layer (sharper
    attention, or a smaller output projection) leaves the bfloat16 logits
    0.07-0.69 of their std off the float32 ones against this draw's 0.015,
    because top-8 flips then cascade through the layers."""
    # the RBG generator: threefry takes ~26 s for 5.5 B normals on a v5e
    # (my chip run, PR 28), the chip's own bit generator a tenth of it
    root = jax.random.key(int(seed), impl="rbg")

    def draw(key, shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def layer(heads: int, dense: bool, key):
        ks = jax.random.split(key, 5)
        q_width = heads * cfg.head_dim
        p = {
            "ln1": jnp.ones((cfg.hidden,), dtype),
            "attn_qkv": draw(ks[0], (cfg.hidden, q_width + 2 * cfg.kv_width)),
            "attn_o": draw(ks[1], (q_width, cfg.hidden)),
            "ln2": jnp.ones((cfg.hidden,), dtype),
        }
        # what a configuration adds draws from keys of its own: the block
        # above is the same draw with or without it
        extra = [jax.random.fold_in(key, 100 + j) for j in range(3)]
        if cfg.attn_gate:
            p["attn_gate"] = draw(extra[0], (cfg.hidden, heads))
        if dense:
            p["mlp"] = {
                "gate_up": draw(ks[3], (cfg.hidden, 2 * cfg.dense_ffn)),
                "down": draw(ks[4], (cfg.dense_ffn, cfg.hidden)),
            }
            return p
        p["moe"] = {
            "router": draw(ks[2], (cfg.hidden, cfg.experts)),
            "gate_up": draw(ks[3], (cfg.held, cfg.hidden, 2 * cfg.ffn)),
            "down": draw(ks[4], (cfg.held, cfg.ffn, cfg.hidden)),
        }
        if cfg.shared_expert:
            p["moe"]["shared_gate_up"] = draw(extra[1], (cfg.hidden, 2 * cfg.ffn))
            p["moe"]["shared_down"] = draw(extra[2], (cfg.ffn, cfg.hidden))
        return p

    @jax.jit
    def ends(key):
        k_emb, k_head = jax.random.split(key)
        return {
            "tok_emb": draw(k_emb, (cfg.vocab, cfg.hidden), 1.0),
            "ln_f": jnp.ones((cfg.hidden,), dtype),
            "lm_head": draw(k_head, (cfg.hidden, cfg.vocab)),
        }

    params = ends(jax.random.fold_in(root, 1 << 20))
    params["layers"] = [
        layer(cfg.heads_of(i), i < cfg.dense_layers, jax.random.fold_in(root, i)) for i in range(cfg.layers)
    ]
    return params


# ----------------------------------------------------------------- forward


def _rms(w, x, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w.astype(x.dtype)


def _window_table(bt, positions, m: int, page_size: int, window: int):
    """The windowed block table of a sliding layer: (bt_w[n, pw], k0[n]) —
    the ``pw`` = ceil((window + m) / page_size) + 1 pages that cover the
    windows of the m queries at positions[i].., taken from each slot's
    table by position, and the absolute position of their first key. A
    table no longer than that is returned whole."""
    n_log = bt.shape[1]
    pw = window_pages(window, m, page_size)
    if pw >= n_log:
        return bt, jnp.zeros_like(positions)
    p0 = window_first_page(positions, window, page_size, n_log, pw)
    cols = p0[:, None] + jnp.arange(pw, dtype=p0.dtype)[None, :]
    return jnp.take_along_axis(bt, cols, axis=1), p0 * page_size


def _attend(q, ck, cv, visible, scale=None):
    """q[n, m, h, d] against the gathered float32 cache ck/cv[n, g, K, d]
    (g key/value heads; query head j reads head j // (h / g)) under
    visible[n, m, K] -> context [n, m, h * d] in q's dtype. Same masking
    and float32 softmax as the GPT-2 family's paged attention. ``scale``
    multiplies the scores: 1 / sqrt(d) unless the family states another."""
    n, m, h, d = q.shape
    g = ck.shape[1]
    r = h // g
    qg = q.reshape(n, m, g, r, d).transpose(0, 2, 3, 1, 4).reshape(n, g, r * m, d)
    s = jnp.einsum("ngqd,ngkd->ngqk", qg.astype(jnp.float32), ck) * (1.0 / d**0.5 if scale is None else scale)
    s = jnp.where(visible[:, None, None, :, :], s.reshape(n, g, r, m, -1), -1e30)
    p = jax.nn.softmax(s, axis=-1).reshape(n, g, r * m, -1)
    ctx = jnp.einsum("ngqk,ngkd->ngqd", p, cv)
    ctx = ctx.reshape(n, g, r, m, d).transpose(0, 3, 1, 2, 4).reshape(n, m, h * d)
    return ctx.astype(q.dtype)


def _kind_pool(cfg: MoEDecoderConfig, full: bool, pool: tuple, bt):
    """(the planes, the block table) of a layer kind: the whole pool and its
    one table where the configuration has one page kind, else the kind's half
    of the state tuple (full planes first) and its table of ``bt``: ``[2, n,
    pages]`` (full, window) as the pool hands it (``PagedKVPool.block_tables``)
    or a pair; ONE ``[n, pages]`` table serves both kinds (a test, the fused
    fallback's identity tables)."""
    if not cfg.two_kinds:
        return pool, bt
    half = len(pool) // 2
    tables = bt if isinstance(bt, (tuple, list)) or bt.ndim == 3 else (bt, bt)
    return (pool[:half], tables[0]) if full else (pool[half:], tables[1])


def _ffn(cfg: MoEDecoderConfig, p, h, valid):
    """A layer's feed-forward over h[T, d] (normed): the dense MLP of a
    leading layer, else the routed experts (all of them, or the share held)
    and the shared one. Returns (y[T, d], counters[3 or 6])."""
    if "mlp" in p:
        with jax.named_scope(SCOPE_DENSE_MLP):
            return gated_mlp(p["mlp"]["gate_up"], p["mlp"]["down"], h), jnp.zeros((cfg.n_counters,), jnp.int32)
    if cfg.experts_held:
        gates, experts = route_topk(p["moe"]["router"], h, cfg.experts_per_tok)
        y, cnt = moe_held_ffn(p["moe"], h, gates * cfg.routed_scale, experts, cfg.first_expert, valid)
    else:
        y, cnt = moe_topk_ffn(p["moe"], h, cfg.experts_per_tok, valid)
        if cfg.routed_scale != 1.0:
            y = y * cfg.routed_scale
    if cfg.shared_expert:
        with jax.named_scope(SCOPE_SHARED_EXPERT):
            y = y + gated_mlp(p["moe"]["shared_gate_up"], p["moe"]["shared_down"], h)
    return y, cnt


def _chunk_takes(cfg: MoEDecoderConfig, attn_kernel: str, queries: int) -> bool:
    """Whether a chunk of ``queries`` a row reads the pool through
    ops/gqa_decode.py's chunk kernel under ``_step_attn_kernel``'s answer:
    ``gqa_chunk_tiles`` for the head count of every layer kind. Static: what
    the program (``_step_reads``) and the scheduler's count of its dispatches
    (``MoEDecoder.chunk_attn``) both ask."""
    return all(
        gqa_chunk_tiles(attn_kernel, queries, heads, cfg.kv_heads, cfg.head_dim)
        for heads in sorted({cfg.heads_of(i) for i in range(cfg.layers)})
    )


def _step_reads(cfg: MoEDecoderConfig, attn_kernel: str, queries: int, pool: tuple, bt, positions, rows, counts=None):
    """What this family's program hands ops/gqa_decode.py's kernels, ONCE a
    layer kind for all the kind's layers (they walk the same table): ({full:
    (table, *vectors)}, the pages of one layer a kind that a STEP's kernel
    fetches in run DMAs as int32[1]) where the program set chose a kernel
    (``attn_kernel``), the planes are float (two a kind) AND the dispatch is
    one the kernels take: one query a slot (the step: ``step_reads``'
    lengths, runs[, first]) or a prefill chunk (``counts`` and
    ``_chunk_takes``: ``chunk_reads``' five); else (None, zero): the gather
    (a verify or tree program, the int8 pool). A full layer's table is its
    kind's whole; a sliding layer's the windowed sub-table, with the first
    key a slot. What is left of the gather there, a few integers a slot,
    stays under the kind's ``kv_gather`` scope."""
    step = queries == 1
    takes = step or (counts is not None and _chunk_takes(cfg, attn_kernel, queries))
    if not attn_kernel or not takes or len(pool) != (4 if cfg.two_kinds else 2):
        return None, jnp.zeros((1,), jnp.int32)
    reads, in_runs = {}, jnp.zeros((), jnp.int32)
    for full in sorted({cfg.is_full(i) for i in range(cfg.layers)}):
        kind, table = _kind_pool(cfg, full, pool, bt)
        ps = kind[0].shape[2]
        with jax.named_scope(SCOPE_FULL if full else SCOPE_WIN), jax.named_scope(SCOPE_KV_GATHER):
            k0 = None
            if not full:
                table, k0 = _window_table(table, positions, queries, ps, cfg.window)
            if step:
                r = step_reads(table, positions, rows, ps, k0, cfg.window)
                in_runs = in_runs + pages_fetched(r[0], r[1], ps, table.shape[1])[1]
            else:
                r = chunk_reads(table, positions, counts, ps, k0)
            reads[full] = (table, *r)
    return reads, in_runs[None]


def _gathered_attention(cfg: MoEDecoderConfig, full: bool, q, kind, pl: int, bt_k, positions, q_pos):
    """A layer's attention through the gather: q[n, m, h, d] against layer
    ``pl`` of its kind's planes, the whole table (a full layer) or the
    windowed sub-table, masked by absolute key position."""
    n, m, heads, _ = q.shape
    if full:
        bt_l, k0 = bt_k, jnp.zeros_like(positions)
    else:
        with jax.named_scope(SCOPE_KV_GATHER):
            bt_l, k0 = _window_table(bt_k, positions, m, kind[0].shape[2], cfg.window)
    ck, cv = _paged_gather(kind, pl, bt_l, cfg.kv_heads)  # [n, g, K, d] float32
    with jax.named_scope(SCOPE_ATTN):
        k_pos = k0[:, None] + jnp.arange(ck.shape[2], dtype=k0.dtype)[None, :]  # [n, K]
        visible = k_pos[:, None, :] <= q_pos[:, :, None]
        if not full:
            visible &= q_pos[:, :, None] - k_pos[:, None, :] < cfg.window
        if 4 * n * heads * m * ck.shape[2] > _SCORES_BATCH_BYTES:
            return lax.map(lambda a: _attend(*(t[None] for t in a))[0], (q, ck, cv, visible))
        return _attend(q, ck, cv, visible)


def _layer(cfg: MoEDecoderConfig, li: int, p, x, pool, bt, positions, counts, valid, reads=None, interpret=False):
    """One layer over the page pool: x[n, m, d] with slot i's query j at
    positions[i] + j. Rotated K and V scatter through the layer kind's block
    table first, attention reads them back through the (windowed) gather,
    like the GPT-2 family's write-then-read, or, where the step was given
    ``reads`` (``_step_reads``), through ops/gqa_decode.py's kernel, which
    reads the kind's pages where they lie. Returns (x, pool, counters)."""
    n, m, _ = x.shape
    full = cfg.is_full(li)
    heads, pl = cfg.heads_of(li), cfg.plane_layer(li)
    q_width = heads * cfg.head_dim
    q_pos = positions[:, None] + jnp.arange(m, dtype=positions.dtype)[None, :]  # [n, m]
    with jax.named_scope(SCOPE_QKV):
        n1 = _rms(p["ln1"], x, cfg.rms_eps)
        qkv = n1 @ p["attn_qkv"].astype(x.dtype)
        q, k, v = jnp.split(qkv, [q_width, q_width + cfg.kv_width], axis=-1)
        with jax.named_scope(SCOPE_ROPE):
            inv_freq = rope_inv_freq(cfg, full)
            factor = cfg.attention_factor if full else 1.0
            q = _rope(q.reshape(n, m, heads, cfg.head_dim), q_pos, inv_freq, factor)
            k = _rope(k.reshape(n, m, cfg.kv_heads, cfg.head_dim), q_pos, inv_freq, factor)
            k = k.reshape(n, m, cfg.kv_width)  # token rows, rotated
    kind, bt_k = _kind_pool(cfg, full, pool, bt)
    kind = _paged_write(kind, pl, k, v, bt_k, positions, counts)
    if cfg.two_kinds:
        pool = kind + pool[len(kind):] if full else pool[: len(kind)] + kind
    else:
        pool = kind
    with jax.named_scope(SCOPE_FULL if full else SCOPE_WIN):
        if reads is not None:
            with jax.named_scope(SCOPE_ATTN):
                ctx = paged_gqa_attention(
                    q, kind, pl, reads[full][0], reads[full][1:],
                    scale=cfg.head_dim**-0.5, interpret=interpret, window=0 if full else cfg.window,
                )
        else:
            ctx = _gathered_attention(cfg, full, q, kind, pl, bt_k, positions, q_pos)
    with jax.named_scope(SCOPE_ATTN_OUT):
        if cfg.attn_gate:
            with jax.named_scope(SCOPE_ATTN_GATE):
                g = jax.nn.sigmoid((n1 @ p["attn_gate"].astype(x.dtype)).astype(jnp.float32))  # [n, m, heads]
                ctx = (ctx.reshape(n, m, heads, cfg.head_dim) * g[..., None].astype(ctx.dtype)).reshape(n, m, q_width)
        x = x + ctx @ p["attn_o"].astype(x.dtype)
    with jax.named_scope(SCOPE_MLP):
        y, cnt = _ffn(cfg, p, _rms(p["ln2"], x, cfg.rms_eps).reshape(n * m, -1), valid.reshape(-1))
        x = x + y.reshape(x.shape)
    return x, pool, cnt


def _forward(cfg, params, pool, bt, tokens, positions, counts=None, rows=None, pick=None, attn_kernel=""):
    """Shared body of the paged programs: tokens[n, m], slot i's query j
    at positions[i] + j; ``bt`` the slots' block-table rows, ``[2, n, pages]``
    (full kind, window kind) where the configuration has two page kinds. ``counts`` [n]
    (chunk rounds): only the first counts[i] rows of slot i are real.
    ``rows`` [n] bool (the step): the slots that generate. ``pick`` [n]:
    the head runs on that one query of each slot (a chunk round needs only
    the last real one; 256 positions of a 98k vocabulary are 1.6 GB of
    logits). ``attn_kernel`` (static; "" | "mosaic" | "interpret":
    ``decode_programs._step_attn_kernel``'s answer) lets a dispatch of ONE
    query a slot, and a prefill chunk (``counts``; ``_chunk_takes``), read both
    page kinds through ops/gqa_decode.py's kernels; every other shape gathers.
    Returns (logits[n, m or 1, vocab] float32,
    hidden[n, m, d], pool, counters int32: ``MoEDecoder.frame_counters``)."""
    n, m = tokens.shape
    valid = jnp.ones((n, m), bool)
    if counts is not None:
        valid &= jnp.arange(m)[None, :] < counts[:, None]
    if rows is not None:
        valid &= rows[:, None]
    with jax.named_scope(SCOPE_EMBED):
        x = jnp.asarray(params["tok_emb"])[tokens]  # [n, m, d]
    reads, run_pages = _step_reads(cfg, attn_kernel, m, pool, bt, positions, rows, counts)
    cnt = jnp.zeros((cfg.n_counters,), jnp.int32)
    for li, lp in enumerate(params["layers"]):
        x, pool, c = _layer(cfg, li, lp, x, pool, bt, positions, counts, valid, reads, attn_kernel == "interpret")
        with jax.named_scope(SCOPE_MLP), jax.named_scope(SCOPE_MOE_COMBINE):
            cnt = cnt + c
    with jax.named_scope(SCOPE_LM_HEAD):
        last = x if pick is None else jnp.take_along_axis(x, pick[:, None, None], axis=1)
        logits = jnp.matmul(
            _rms(params["ln_f"], last, cfg.rms_eps), params["lm_head"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        # rows is every layer's own count: report it once, not summed
        cnt = cnt.at[0].set(jnp.sum(valid, dtype=jnp.int32))
    return logits, x, pool, jnp.concatenate([cnt, run_pages])


def _generate(cfg, params, ids, max_new_tokens: int):
    """The fused fallback apply of a deployment without ``tpu.decode_slots``
    (``decoder.paged_greedy_generate``) over a private pool: every sequence
    keeps all its pages in both kinds, under one identity table."""
    dims = {
        "kv_layers": cfg.layers, "kv_window_layers": cfg.kind_layers, "kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
    }
    return paged_greedy_generate(
        functools.partial(_forward, cfg, params),
        lambda n_pages, ps: kv_pool_zeros(dims, n_pages, ps, params["tok_emb"].dtype), ids, max_new_tokens,
    )


# ------------------------------------------------------------------ family


@dataclasses.dataclass(frozen=True)
class MoEDecoder:
    """The family object of one configuration: what the decode scheduler
    asks of a family, with the configuration's static sizes bound
    (window, period, frequencies and head counts are not readable from
    weight shapes). Hashable: equal configurations share compiled programs."""

    cfg: MoEDecoderConfig

    name = "moe"
    state_init = None  # no recurrent state: pages only

    @property
    def frame_counters(self) -> tuple:
        """What paged_forward's extra output counts, in order (FlightFrame
        fields); over a share of the experts also the picks that landed on
        it and the layer calls that ran the grouped form, and ran it compact
        (``moe_held_ffn``'s six); last, the pages of one layer a page kind
        that the step's kernel fetched in run DMAs (0 where it gathers)."""
        base = ("moe_rows", "moe_experts_hit", "moe_load_max")
        return base + (HELD_COUNTERS if self.cfg.experts_held else ()) + ("attn_run_pages",)

    @property
    def serves(self) -> frozenset:
        """Beside the plain rounds (``decoder.require_served``): a step that
        reads the pool in place (ops/gqa_decode.py's kernel, both page kinds);
        the int8 pool on either layout (its step gathers); the KV tiers and
        prefix export only where the pool has ONE page kind (they move a
        prefix as one list of pages). Not served: speculation, a decode
        mesh."""
        one_kind = () if self.cfg.two_kinds else ("host_tier", "prefix_export")
        return frozenset({"attn_kernel", "kv_int8", *one_kind})

    def decoder_dims(self, params: dict) -> dict:
        layers = params.get("layers") or [{}]
        if "lm_head" not in params or not ("moe" in layers[-1] and "attn_qkv" in layers[0]):
            raise FamilyNotServed("not a sparse-expert decoder's parameters (models/moe_decoder.py layout)")
        c = self.cfg
        if len(layers) != c.layers or c.attn_gate != ("attn_gate" in layers[0]) or (
            layers[0]["attn_qkv"].shape[1] != c.heads_of(0) * c.head_dim + 2 * c.kv_width
        ):
            raise FamilyNotServed("parameters and configuration disagree on layers, attn_gate or the head counts")
        return {
            "layers": c.layers, "kv_layers": c.layers, "heads": c.heads,
            "heads_window": c.heads_window or c.heads,  # a sliding layer's query heads
            "kv_heads": c.kv_heads, "hidden": c.hidden, "head_dim": c.head_dim, "q_width": c.q_width,
            "vocab": params["tok_emb"].shape[0], "max_len": c.max_len,
            # the second page kind (decoder.kv_pool_zeros, serving/kv_pool.py):
            # how many of kv_layers are sliding layers, and their window
            "kv_window_layers": c.kind_layers, "kv_window": c.window if c.two_kinds else 0,
        }

    def paged_kv_init(self, params, n_pages, page_size, dtype=jnp.float32, kv_dtype=""):
        return kv_pool_zeros(self.decoder_dims(params), n_pages, page_size, dtype, kv_dtype)

    def paged_forward(self, params, pool, bt, tokens, positions, counts=None, rows=None, pick=None, attn_kernel=""):
        return _forward(self.cfg, params, pool, bt, tokens, positions, counts, rows, pick, attn_kernel)

    @functools.lru_cache(maxsize=None)
    def fused_programs(self, attn_kernel: str = ""):
        """This family's step and chunk bodies (``decoder.counted_programs``:
        the step takes ``rows``, the counts ride the token readback); with
        ``attn_kernel`` the step reads the pool through ops/gqa_decode.py's step
        kernel and a chunk through its chunk kernel (``chunk_attn``). Cached:
        equal configurations share compiled programs."""
        return counted_programs(functools.partial(self.paged_forward, attn_kernel=attn_kernel))

    def chunk_attn(self, attn_kernel: str, c: int) -> str:
        """How the chunk program of ``c`` tokens a row reads the pool under
        ``attn_kernel``: "kernel" (``gqa_chunk_attention``, both page kinds)
        or "gather". Static (``_chunk_takes``)."""
        return "kernel" if _chunk_takes(self.cfg, attn_kernel, c) else "gather"

    def paged_decode_step(self, params, pool, bt, tokens, positions):
        logits, hidden, pool, _ = _forward(self.cfg, params, pool, bt, tokens[:, None], positions)
        return logits[:, 0, :], hidden[:, 0, :], pool

    def paged_verify_step(self, params, pool, bt, tokens, positions):
        return _forward(self.cfg, params, pool, bt, tokens, positions)[:3]

    def paged_chunk_prefill(self, params, pool, bt, tokens, positions, counts):
        return _forward(self.cfg, params, pool, bt, tokens, positions, counts)[:3]

    def generate(self, params, ids, max_new_tokens: int):
        return _generate(self.cfg, params, ids, max_new_tokens)


@functools.lru_cache(maxsize=None)
def moe_family(cfg: MoEDecoderConfig) -> MoEDecoder:
    return MoEDecoder(cfg)
