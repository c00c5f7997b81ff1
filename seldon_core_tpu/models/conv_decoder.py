"""Short-convolution / attention causal decoder with held experts — the
generative tier's fifth family (the LFM2 block, ``model_type: lfm2_moe``).

What the block has, beside the four families before it:

- most layers mix tokens with a GATED SHORT CONVOLUTION and nothing else:
  ``[B | C | X] = W_in n(x)``; ``z = B * X``; a depthwise causal filter of
  ``conv_taps`` taps over z, no bias, no activation; ``C`` gates the result;
  ``W_out``. Its cache is the last ``conv_taps - 1`` values of z: two rows
  of ``hidden`` numbers a layer and sequence (hybrid_decoder's convolution
  is a biased, SiLU'd pre-filter of a state-space scan; this one is the
  whole mixer);
- the layers named in ``attn_layers`` are the sparse-expert family's
  grouped-query attention (``_attend``, ``_rope``, plain frequencies on all
  of ``head_dim``) with an RMS norm over each head's q and k BEFORE the
  rotation, one learned ``head_dim``-vector for all query heads and one for
  all key heads; the page holds the normed, rotated key. Only they hold K/V
  pages (``decoder_dims`` ``kv_layers``);
- ``dense_layers`` leading layers with a dense gated MLP (``gated_mlp``),
  then layers of routed experts alone under a sigmoid gate whose per-expert
  bias selects and does not weigh (ops/moe.py ``route_sigmoid_biased``),
  over ONE CHIP'S SHARE of the experts (``moe_held_ffn``, as the latent
  family holds its share);
- a tied head behind the final norm.

The conv inputs are the family's second cache, beside the pages
(``state_init``; serving/kv_pool.py holds it as ``pool.recurrent``): ONE
ARRAY A CONV LAYER of rows ``[rows, (conv_taps - 1) * hidden]`` (time-major
and flat, oldest first, as ``hybrid_decoder.state_zeros`` lays out Mamba's
conv inputs), float32: z is the product of two bfloat16 projections, formed
and filtered in float32 in a chunk's registers, and a row that kept it
rounded would make a position's output depend on whether a chunk boundary
fell just before it (the rows are 34 MB at 69 rows x 30 layers x 4096).
Row r is slot r's; the rows after the slots hold cached prefixes'
snapshots; one row stays zero. The step advances the rows that generate
(``rows``) and no other; a chunk's batch row names the row it reads, the
row it writes and the snapshot row it also writes (``state_rows`` [3, n];
an index past the last row drops the write), and positions past
``counts[r]`` leave the cache as it was: the hybrid family's contract, with
nothing of Mamba's in it.

On one TPU the attention layers read the pool's pages where they lie: the
STEP through ops/gqa_decode.py ``gqa_decode_attention``, a prefill CHUNK
through ``gqa_chunk_attention`` (where ``decode_programs._step_attn_kernel``
chooses a kernel and ``gqa_chunk_tiles`` holds for the chunk: ``chunk_attn``;
the CPU keeps the gather, the oracle of both). Not
served: speculation, a decode mesh, the int8 pool, the host tier, prefix
export (each refuses by name, ``decoder.require_served``).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

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
    paged_gqa_attention,
    paged_state_greedy_generate,
)
from seldon_core_tpu.models.moe_decoder import _SCORES_BATCH_BYTES, SCOPE_ROPE, _attend, _rms, _rope
from seldon_core_tpu.ops.gqa_decode import gqa_chunk_tiles
from seldon_core_tpu.ops.moe import (
    HELD_COUNTERS,
    N_HELD_COUNTERS,
    SCOPE_DENSE_MLP,
    SCOPE_MOE_COMBINE,
    gated_mlp,
    moe_held_ffn,
    route_sigmoid_biased,
)

# device scopes this family adds, each nested under a decoder.PAGED_SCOPES
# name so readers of those still see whole steps: ``qkv/conv_in``,
# ``attn/conv_mix`` (the two gates, the taps, the state rows' read and
# write), ``attn_out/conv_out``; ``qkv/qk_norm`` and ``qkv/rope`` in the
# attention layers; ``mlp/dense``, ``mlp/moe_*`` (ops/moe.py)
SCOPE_CONV_IN = "conv_in"
SCOPE_CONV_MIX = "conv_mix"
SCOPE_CONV_OUT = "conv_out"
SCOPE_QK_NORM = "qk_norm"


@dataclasses.dataclass(frozen=True)
class ConvDecoderConfig:
    """The published keys of a short-convolution decoder (zoo://conv_decoder)."""

    vocab: int = 512
    hidden: int = 64
    layers: int = 8
    attn_layers: tuple = (2, 6)  # ``layer_types``: the layers that are full attention; every other is a conv
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    conv_taps: int = 3  # conv_L_cache
    dense_layers: int = 2  # num_dense_layers
    dense_ffn: int = 128  # intermediate_size
    ffn: int = 32  # ONE expert's width
    experts: int = 16  # the router's width: every expert of the deployment
    experts_held: int = 16  # how many of them this chip's parameters hold ...
    first_expert: int = 0  # ... from this one
    experts_per_tok: int = 4
    routed_scale: float = 1.0
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    max_len: int = 128000

    def __post_init__(self):
        if self.heads % self.kv_heads:
            raise ValueError(f"heads={self.heads} not a multiple of kv_heads={self.kv_heads}")
        if self.head_dim % 2:
            raise ValueError(f"head_dim={self.head_dim} must be even (rotary pairs)")
        if any(not 0 <= i < self.layers for i in self.attn_layers):
            raise ValueError(f"attn_layers={self.attn_layers} outside 0..{self.layers - 1}")
        if self.conv_taps < 2:
            raise ValueError("conv_taps must be >= 2")
        if not 1 <= self.experts_per_tok <= self.experts:
            raise ValueError(f"experts_per_tok={self.experts_per_tok} of experts={self.experts}")
        if not 0 <= self.first_expert <= self.experts - self.experts_held or self.experts_held < 1:
            raise ValueError(f"experts [{self.first_expert}, +{self.experts_held}) of {self.experts}")
        if not 0 <= self.dense_layers <= self.layers:
            raise ValueError(f"dense_layers={self.dense_layers} of layers={self.layers}")

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def conv_layers(self) -> int:
        return self.layers - len(set(self.attn_layers))

    @functools.cached_property
    def inv_freq(self):
        """Plain rotary frequencies theta^(-2i/d) over all of ``head_dim``."""
        i = np.arange(self.head_dim // 2, dtype=np.float64)
        return (self.rope_theta ** (-2.0 * i / self.head_dim)).astype(np.float32)

    def cache_index(self, layer: int) -> int:
        """A layer's index in ITS cache: the attention layers count through
        the KV pool's layers, the conv layers through the state's arrays."""
        attn = layer in self.attn_layers
        return sum((i in self.attn_layers) == attn for i in range(layer))


# ----------------------------------------------------------------- weights

# the selection bias's std: about three gaps between neighbouring sorted
# scores (64 sigmoid scores over (0, 1) lie 1/64 apart on average), so that
# some of a token's picks differ from top_k(scores) and no expert is always
# or never picked
EXPERT_BIAS_STD = 0.05


def init_conv_decoder(cfg: ConvDecoderConfig, seed: int = 0, dtype=jnp.bfloat16) -> dict:
    """Random weights drawn ON THE DEVICE in ``dtype``, layer by layer, as
    ``init_moe_decoder`` draws them: projections and the embedding
    normal(0, 0.02) (the head is the embedding, tied: at std 1 every
    position's best token would be its own input, hybrid_decoder's finding),
    norms 1, the chip's own bit generator, layer i's key fold_in(seed, i).
    The taps uniform in +-1/sqrt(conv_taps), a depthwise Conv1d's default:
    at 0.02 the filter's output would be a fortieth of a dense layer's and
    the operator, which is the model, would not show in the logits. The
    router like the rest (logits of std 0.02 * sqrt(hidden) over a normalised
    input), its selection bias normal(0, ``EXPERT_BIAS_STD``): it is trained
    by load balancing and published as a buffer, and zeros would leave the
    selection path untested. The routed experts drawn are the
    ``experts_held`` this chip holds."""
    root = jax.random.key(int(seed), impl="rbg")
    c = cfg

    def draw(key, shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def mixer(ks, attn: bool):
        if attn:
            return {
                "ln1": jnp.ones((c.hidden,), dtype),
                "attn_qkv": draw(ks[0], (c.hidden, c.q_width + 2 * c.kv_width)),
                "q_norm": jnp.ones((c.head_dim,), dtype),
                "k_norm": jnp.ones((c.head_dim,), dtype),
                "attn_o": draw(ks[1], (c.q_width, c.hidden)),
            }
        bound = c.conv_taps**-0.5
        return {
            "ln1": jnp.ones((c.hidden,), dtype),
            "conv_in": draw(ks[0], (c.hidden, 3 * c.hidden)),  # B | C | X
            "conv_w": jax.random.uniform(ks[2], (c.conv_taps, c.hidden), jnp.float32, -bound, bound).astype(dtype),
            "conv_out": draw(ks[1], (c.hidden, c.hidden)),
        }

    def feed_forward(ks, dense: bool):
        if dense:
            return {"mlp": {"gate_up": draw(ks[3], (c.hidden, 2 * c.dense_ffn)), "down": draw(ks[4], (c.dense_ffn, c.hidden))}}
        return {"moe": {
            "router": draw(ks[3], (c.hidden, c.experts)),
            "router_bias": jax.random.normal(ks[6], (c.experts,), jnp.float32) * EXPERT_BIAS_STD,
            "gate_up": draw(ks[4], (c.experts_held, c.hidden, 2 * c.ffn)),
            "down": draw(ks[5], (c.experts_held, c.ffn, c.hidden)),
        }}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def layer(key, attn: bool, dense: bool):
        ks = jax.random.split(key, 7)
        return {**mixer(ks, attn), "ln2": jnp.ones((c.hidden,), dtype), **feed_forward(ks, dense)}

    params = {
        "tok_emb": jax.jit(lambda k: draw(k, (c.vocab, c.hidden)))(jax.random.fold_in(root, 1 << 20)),
        "ln_f": jnp.ones((c.hidden,), dtype),
    }
    params["layers"] = [
        layer(jax.random.fold_in(root, i), i in c.attn_layers, i < c.dense_layers) for i in range(c.layers)
    ]
    return params


def state_zeros(cfg: ConvDecoderConfig, rows: int) -> tuple:
    """The zeroed conv cache, float32, the row at axis 0: one array [rows,
    (conv_taps - 1) * hidden] a conv layer (conv layer i's is ``rec[i]``)."""
    return tuple(
        jnp.zeros((rows, (cfg.conv_taps - 1) * cfg.hidden), jnp.float32) for _ in range(cfg.conv_layers)
    )


# ----------------------------------------------------------------- forward


def _conv(cfg: ConvDecoderConfig, si: int, p, x, rec, valid, state_rows):
    """The gated short convolution over x[n, m, d], conv layer ``si``'s rows
    of ``rec`` (``state_zeros``). The step (``state_rows`` None; m = 1):
    batch row r is state row r, advanced where ``valid[r]``. A chunk: row r
    reads ``state_rows[0, r]``, writes ``state_rows[1, r]`` and
    ``state_rows[2, r]`` (an index past the rows is dropped); positions where
    ``valid`` [n, m] is False leave the cache as it was. Returns (the
    mixer's output [n, m, d], rec)."""
    state = rec[si]
    n, m, d = x.shape
    k = cfg.conv_taps
    f32 = jnp.float32
    with jax.named_scope(SCOPE_QKV), jax.named_scope(SCOPE_CONV_IN):
        b, c, xs = jnp.split(_rms(p["ln1"], x, cfg.rms_eps) @ p["conv_in"].astype(x.dtype), 3, axis=-1)
    with jax.named_scope(SCOPE_ATTN), jax.named_scope(SCOPE_CONV_MIX):
        z_in = state[:n] if state_rows is None else state[state_rows[0]]
        # the last k - 1 values of z, then the dispatch's own: [n, k - 1 + m, d]
        seq = jnp.concatenate([z_in.reshape(n, k - 1, d), b.astype(f32) * xs.astype(f32)], axis=1)
        w = p["conv_w"].astype(f32)
        y = (c.astype(f32) * sum(w[j] * seq[:, j : j + m] for j in range(k))).astype(x.dtype)
        # the cache after the dispatch: the k - 1 values that end at the
        # row's last real one (all of the old cache where it has none)
        last = jnp.sum(valid, axis=1, dtype=jnp.int32)
        z_out = jax.vmap(lambda s, at: lax.dynamic_slice_in_dim(s, at, k - 1))(seq, last).reshape(n, (k - 1) * d)
        if state_rows is None:
            state = state.at[:n].set(z_out)
        else:
            for to in (state_rows[1], state_rows[2]):
                state = state.at[to].set(z_out, mode="drop")
    with jax.named_scope(SCOPE_ATTN_OUT), jax.named_scope(SCOPE_CONV_OUT):
        out = y @ p["conv_out"].astype(x.dtype)
    return out, tuple(state if i == si else a for i, a in enumerate(rec))


def _attention(cfg: ConvDecoderConfig, ki: int, p, x, pool, bt, positions, counts, reads=None, interpret=False):
    """Grouped-query attention over pool layer ``ki``: q and k normed a
    head, then rotated, at the row's own position; K and V scatter through
    the block tables and attention reads them back (the families'
    write-then-read): through the gather, or, where the step was given
    ``reads`` (``decoder._paged_step_reads``: one query a slot and the
    program set chose the kernel), through ops/gqa_decode.py's kernel, which
    reads the pages where they lie. Returns (the mixer's output [n, m, d],
    pool)."""
    n, m, _ = x.shape
    q_pos = positions[:, None] + jnp.arange(m, dtype=positions.dtype)[None, :]  # [n, m]
    with jax.named_scope(SCOPE_QKV):
        qkv = _rms(p["ln1"], x, cfg.rms_eps) @ p["attn_qkv"].astype(x.dtype)
        q, k, v = jnp.split(qkv, [cfg.q_width, cfg.q_width + cfg.kv_width], axis=-1)
        with jax.named_scope(SCOPE_QK_NORM):
            q = _rms(p["q_norm"], q.reshape(n, m, cfg.heads, cfg.head_dim), cfg.rms_eps)
            k = _rms(p["k_norm"], k.reshape(n, m, cfg.kv_heads, cfg.head_dim), cfg.rms_eps)
        with jax.named_scope(SCOPE_ROPE):
            q = _rope(q, q_pos, cfg.inv_freq, 1.0)
            k = _rope(k, q_pos, cfg.inv_freq, 1.0).reshape(n, m, cfg.kv_width)  # token rows, normed and rotated
    pool = _paged_write(pool, ki, k, v, bt, positions, counts)
    if reads is not None:
        with jax.named_scope(SCOPE_ATTN):
            ctx = paged_gqa_attention(q, pool, ki, bt, reads, scale=cfg.head_dim**-0.5, interpret=interpret)
    else:
        ck, cv = _paged_gather(pool, ki, bt, cfg.kv_heads)  # [n, g, K, d] float32
        with jax.named_scope(SCOPE_ATTN):
            visible = jnp.arange(ck.shape[2], dtype=positions.dtype)[None, None, :] <= q_pos[:, :, None]
            if 4 * n * cfg.heads * m * ck.shape[2] > _SCORES_BATCH_BYTES:
                ctx = lax.map(lambda a: _attend(*(t[None] for t in a))[0], (q, ck, cv, visible))
            else:
                ctx = _attend(q, ck, cv, visible)
    with jax.named_scope(SCOPE_ATTN_OUT):
        return ctx @ p["attn_o"].astype(x.dtype), pool


def _feed_forward(cfg: ConvDecoderConfig, p, h, valid):
    """A layer's feed-forward over h[T, d]: the dense MLP, or the routed
    experts held here under this family's gate. Returns (y[T, d],
    counters[6]: zeros for a dense layer)."""
    if "mlp" in p:
        with jax.named_scope(SCOPE_DENSE_MLP):
            return gated_mlp(p["mlp"]["gate_up"], p["mlp"]["down"], h), jnp.zeros((N_HELD_COUNTERS,), jnp.int32)
    gates, experts = route_sigmoid_biased(
        p["moe"]["router"], p["moe"]["router_bias"], h, cfg.experts_per_tok, cfg.routed_scale
    )
    return moe_held_ffn(p["moe"], h, gates, experts, cfg.first_expert, valid)


def _forward(
    cfg, params, pool, rec, bt, tokens, positions, counts=None, rows=None, pick=None, state_rows=None, attn_kernel=""
):
    """Shared body of the paged programs, with ``hybrid_decoder._forward``'s
    arguments: tokens[n, m], slot i's query j at positions[i] + j;
    ``counts`` [n] (chunk rounds), ``rows`` [n] bool (the step's generating
    slots), ``pick`` [n] (the head's one query a row), ``state_rows`` [3, n]
    (``_conv``); ``attn_kernel`` (static; "" | "mosaic" | "interpret":
    ``decode_programs._step_attn_kernel``'s answer) lets a dispatch of ONE
    query a slot, and a prefill chunk (``counts``; ``gqa_chunk_tiles``), read
    the pool through ops/gqa_decode.py's kernels; every other shape gathers.
    Returns (logits [n, m or 1, vocab] float32, pool,
    rec, counters[8] int32: ``ConvDecoder.frame_counters``)."""
    n, m = tokens.shape
    valid = jnp.ones((n, m), bool)
    if counts is not None:
        valid &= jnp.arange(m)[None, :] < counts[:, None]
    if rows is not None:
        valid &= rows[:, None]
    chunk = gqa_chunk_tiles(attn_kernel, m, cfg.heads, cfg.kv_heads, cfg.head_dim)
    reads, run_pages = _paged_step_reads(attn_kernel, m, pool, bt, positions, rows, counts if chunk else None)
    with jax.named_scope(SCOPE_EMBED):
        x = jnp.asarray(params["tok_emb"])[tokens]  # [n, m, d]
    cnt = jnp.zeros((N_HELD_COUNTERS,), jnp.int32)
    for li, p in enumerate(params["layers"]):
        ci = cfg.cache_index(li)
        if li in cfg.attn_layers:
            mix, pool = _attention(cfg, ci, p, x, pool, bt, positions, counts, reads, attn_kernel == "interpret")
        else:
            mix, rec = _conv(cfg, ci, p, x, rec, valid, state_rows)
        with jax.named_scope(SCOPE_ATTN_OUT):
            x = x + mix
        with jax.named_scope(SCOPE_MLP):
            y, c = _feed_forward(cfg, p, _rms(p["ln2"], x, cfg.rms_eps).reshape(n * m, -1), valid.reshape(-1))
            x = x + y.reshape(x.shape)
            with jax.named_scope(SCOPE_MOE_COMBINE):
                cnt = cnt + c
    with jax.named_scope(SCOPE_LM_HEAD):
        top = x if pick is None else jnp.take_along_axis(x, pick[:, None, None], axis=1)
        logits = jnp.einsum(  # the tied head: the embedding's rows again
            "nmd,vd->nmv", _rms(params["ln_f"], top, cfg.rms_eps), jnp.asarray(params["tok_emb"]).astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        # rows are every layer's own count: reported once, not summed; the
        # rows whose conv state the dispatch advanced are one layer's too
        cnt = cnt.at[0].set(jnp.sum(valid, dtype=jnp.int32))
        advanced = jnp.sum(jnp.any(valid, axis=1), dtype=jnp.int32)
    return logits, pool, rec, jnp.concatenate([cnt, advanced[None], run_pages])


# ------------------------------------------------------------------ family


@dataclasses.dataclass(frozen=True)
class ConvDecoder:
    """The family object of one configuration: what the decode scheduler
    asks of a family (``decoder.GPT2Decoder``'s docstring has the list), with
    the configuration's static sizes bound. Hashable: equal configurations
    share compiled programs."""

    cfg: ConvDecoderConfig

    name = "conv"
    # what the programs' readback carries after the tokens (FlightFrame
    # fields): the routing over the experts HELD and the picks of real rows
    # that landed on one and the layer calls that ran the grouped form and ran
    # it compact (the latent family's six), and the batch rows
    # whose conv state the dispatch advanced, and where the step's kernel ran
    # the pages it fetched in run DMAs (one layer's K)
    frame_counters = (
        "moe_rows", "moe_experts_hit", "moe_load_max", *HELD_COUNTERS, "conv_rows", "attn_run_pages",
    )
    # beside the plain rounds: a step that reads the pool in place (ops/gqa_decode.py's kernel)
    serves = frozenset({"attn_kernel"})

    def decoder_dims(self, params: dict) -> dict:
        if "lm_head" in params or not any("conv_in" in p for p in params["layers"]):
            raise FamilyNotServed("not a short-convolution decoder's parameters (models/conv_decoder.py layout)")
        c = self.cfg
        return {
            "layers": len(params["layers"]), "kv_layers": len(c.attn_layers), "heads": c.heads,
            "kv_heads": c.kv_heads, "hidden": c.hidden, "head_dim": c.head_dim, "q_width": c.q_width,
            "vocab": params["tok_emb"].shape[0], "max_len": c.max_len,
        }

    def paged_kv_init(self, params, n_pages, page_size, dtype=jnp.float32, kv_dtype=""):
        return kv_pool_zeros(self.decoder_dims(params), n_pages, page_size, dtype, kv_dtype)

    def state_init(self, params, rows: int) -> tuple:
        """The zeroed conv cache of ``rows`` rows (``state_zeros``): float32
        whatever the serving dtype."""
        return state_zeros(self.cfg, rows)

    def paged_forward(
        self, params, pool, rec, bt, tokens, positions, counts=None, rows=None, pick=None, state_rows=None,
        attn_kernel="",
    ):
        return _forward(
            self.cfg, params, pool, rec, bt, tokens, positions, counts, rows, pick, state_rows, attn_kernel
        )

    def chunk_attn(self, attn_kernel: str, c: int) -> str:
        """How the chunk program of ``c`` tokens a row reads the pool under
        ``attn_kernel``: "kernel" (ops/gqa_decode.py ``gqa_chunk_attention``)
        or "gather". Static (``gqa_chunk_tiles``: what ``_forward`` asks)."""
        takes = gqa_chunk_tiles(attn_kernel, c, self.cfg.heads, self.cfg.kv_heads, self.cfg.head_dim)
        return "kernel" if takes else "gather"

    @functools.lru_cache(maxsize=None)
    def fused_programs(self, attn_kernel: str = ""):
        """This family's step and chunk bodies (``decoder.
        counted_state_programs``); with ``attn_kernel`` the step reads the
        pool through ops/gqa_decode.py's step kernel and a chunk through its
        chunk kernel (``chunk_attn``). Cached: equal configurations share
        compiled programs."""
        return counted_state_programs(functools.partial(self.paged_forward, attn_kernel=attn_kernel))

    def generate(self, params, ids, max_new_tokens: int):
        """The fused fallback apply (``decoder.paged_state_greedy_generate``)
        over a private pool and private state rows."""
        return paged_state_greedy_generate(
            functools.partial(self.paged_forward, params),
            lambda n_pages, ps: self.paged_kv_init(params, n_pages, ps, params["tok_emb"].dtype),
            functools.partial(state_zeros, self.cfg), ids, max_new_tokens,
        )


@functools.lru_cache(maxsize=None)
def conv_family(cfg: ConvDecoderConfig) -> ConvDecoder:
    return ConvDecoder(cfg)
