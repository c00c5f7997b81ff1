"""Hybrid recurrent / attention causal decoder — the generative tier's
third family (Granite 4.0-H: ``model_type: granitemoehybrid``; Nemotron-H:
``model_type: nemotron_h``; Qwen3-Next: ``model_type: qwen3_next``).

What the block has, beside the two families before it:

- most mixers are a Mamba-2 recurrence instead of attention: a
  ``[ssm_heads, ssm_head_dim, ssm_state]`` float32 state per layer and
  sequence, advanced one token at a time in the step and as ONE chunked
  scan in a prefill chunk whatever scan chunk the configuration publishes
  (the result does not depend on it: granite's ``mamba_chunk_size`` 256 is
  the tier's chunk cap; Nemotron-H publishes ``chunk_size`` 128 under the
  same 256-token dispatch, see ``_scan_chunk``), behind a depthwise causal
  convolution whose cache is the last ``ssm_conv - 1`` inputs. B and C come
  in ``ssm_groups`` groups (``n_groups``): head h reads group
  ``h // (ssm_heads // ssm_groups)``, the conv cache is ``d_inner + 2 *
  ssm_groups * ssm_state`` wide and the gated norm runs over each group's
  ``d_inner / ssm_groups`` channels; one group is granite's;
- the attention layers are grouped-query attention WITHOUT positions
  (``position_embedding_type: nope``), scores times
  ``attention_multiplier``; only they hold K/V pages (``decoder_dims``
  ``kv_layers``), written and gathered by the GPT-2 family's
  ``_paged_write`` / ``_paged_gather``;
- a layer's kind is the configuration's. Without a ``pattern`` (granite)
  the layers named in ``attn_layers`` attend, every other is Mamba-2, and a
  dense gated-SiLU MLP pairs with EVERY mixer. With one
  (``hybrid_override_pattern``: a character a layer, ``M`` Mamba-2, ``*``
  attention, ``E`` an expert layer) a layer is ONE sublayer, ``x + Mix(
  RMSNorm(x))`` and nothing else; an expert layer is a shared expert every
  token takes plus one chip's share of the routed ones under the
  bias-selected sigmoid gate (ops/moe.py ``route_sigmoid_biased``,
  ``moe_held_ffn``), each ``down(relu(up x)^2)`` with no gate projection;
- the THIRD SHAPE (``qwen3_next``; pattern characters ``D`` and ``G``):
  every layer is a mixer AND an expert layer, ``x <- x + Mix(n1(x))`` then
  ``x <- x + Moe(n2(x))``. ``D`` is a gated delta-rule (linear-attention)
  mixer (``_gdn``; ops/gated_delta.py): q, k, v behind ONE depthwise causal
  convolution without bias, q and k l2-normalised a head, a float32 MATRIX
  state ``[value heads, d_k, d_v]`` a layer and sequence that is read before
  it is written, the step in place and a prefill chunk in the blocked form,
  then a norm over each head's ``d_v`` and only then the ``silu(z)`` gate
  (the reverse of Mamba-2's order). ``G`` is gated attention (``_attention``):
  a doubled query projection whose second half is an element-wise sigmoid
  gate on the context, a zero-centred RMS norm on q and k a head, rotary on
  the first ``rotary`` of the head. The expert layer (``_experts_softmax``):
  softmax over all routed experts then the top k renormalised (ops/moe.py
  ``route_topk``), gated-SiLU experts, one chip's share of them
  (``moe_held_ffn``), plus a shared expert times ``sigmoid(w . x)``, one
  scalar a token. Every norm of this shape is ZERO-CENTRED: ``x .
  rsqrt(mean(x^2) + eps) . (1 + w)`` in float32 (``_norm``);
- Granite's multipliers: the embedding times ``embedding_multiplier``, every
  residual branch times ``residual_multiplier``, the head's logits over
  ``logits_scaling`` (a configuration without them passes ones); the head is
  the embedding (tied) unless ``untied`` (``lm_head`` [hidden, vocab]).

The recurrent state is the family's second cache, beside the pages
(``state_init``; serving/kv_pool.py holds it as ``pool.recurrent``): ROWS of
state ``[rows, heads, head_dim, state]`` (a delta-rule layer's ``[rows, value
heads, d_k, d_v]``) and of conv inputs ``[rows,
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
import math

import jax
import jax.numpy as jnp
import numpy as np
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
from seldon_core_tpu.models.moe_decoder import SCOPE_ROPE, _SCORES_BATCH_BYTES, _attend, _rms, _rope
from seldon_core_tpu.ops.gated_delta import gdn_chunk, gdn_chunk_rows, gdn_step, gdn_step_rows
from seldon_core_tpu.ops.gated_delta import kernel_mode as gdn_kernel_mode
from seldon_core_tpu.ops.gqa_decode import gqa_chunk_tiles
from seldon_core_tpu.ops.moe import (
    HELD_COUNTERS,
    N_HELD_COUNTERS,
    SCOPE_MOE_COMBINE,
    SCOPE_SHARED_EXPERT,
    expert_mlp,
    lane_tiles,
    moe_held_ffn,
    route_sigmoid_biased,
    route_topk,
)

# device scopes this family adds, each nested under a decoder.PAGED_SCOPES
# name so readers of those still see whole steps: ``qkv/ssm_in``,
# ``attn/ssm_conv``, ``attn/ssm_scan`` (the recurrence or the chunked scan,
# with the state rows' read and write), ``attn_out/ssm_norm``,
# ``attn_out/ssm_out``; an expert layer's are ops/moe.py's, under ``mlp``
# (``mlp/moe_*``, ``mlp/shared_expert``)
SCOPE_SSM_IN = "ssm_in"
SCOPE_SSM_CONV = "ssm_conv"
SCOPE_SSM_SCAN = "ssm_scan"
SCOPE_SSM_NORM = "ssm_norm"
SCOPE_SSM_OUT = "ssm_out"
# a delta-rule layer's, nested the same way: ``qkv/gdn_in``, ``attn/gdn_conv``,
# ``attn/gdn_scan`` (the step's update or the blocked form, with the state rows'
# read and write), ``attn_out/gdn_norm``, ``attn_out/gdn_out``; the gated
# attention's ``qkv/rope`` and ``attn_out/attn_gate``
SCOPE_GDN_IN = "gdn_in"
SCOPE_GDN_CONV = "gdn_conv"
SCOPE_GDN_SCAN = "gdn_scan"
SCOPE_GDN_NORM = "gdn_norm"
SCOPE_GDN_OUT = "gdn_out"
SCOPE_ATTN_GATE = "attn_gate"

# a scan chunk's decay matrix [rows, heads, c, c] in float32 above this goes
# in blocks of rows (lax.map): the (64, 256) chunk program would hold 1.07 GB
# of it a layer beside 12 GB of weights and state
_SCAN_BLOCK_BYTES = 128 << 20
# the scan's matrix products take float32 operands whole: at the chip's
# default precision they are rounded to bfloat16 first, and the state and the
# decay are the float32 part of the model
_SCAN_PRECISION = lax.Precision.HIGHEST
# a layer's kind, as ``hybrid_override_pattern`` spells it
KIND_SSM, KIND_ATTN, KIND_EXPERT = "M", "*", "E"
# the third shape's two (no published pattern spells them: ``qwen3_next`` gives ``full_attention_interval``): a
# gated delta-rule mixer, gated attention; an expert layer follows either in the SAME layer
KIND_GDN, KIND_GATED = "D", "G"
# what the l2 norm of a delta-rule layer's q and k adds under the root (the published kernels' constant)
_L2_EPS = 1e-6
# the gate's denominator adds this to the picks' scores (``nemotron_h``)
_GATE_EPS = 1e-20
# the selection bias's std (models/conv_decoder.py ``EXPERT_BIAS_STD``'s
# reasoning at 128 scores: about six gaps between neighbouring sorted ones)
EXPERT_BIAS_STD = 0.05


@dataclasses.dataclass(frozen=True)
class HybridDecoderConfig:
    """The published keys of a Granite-4.0-H, a Nemotron-H or a Qwen3-Next
    decoder (zoo://hybrid_decoder)."""

    vocab: int = 512
    hidden: int = 64
    layers: int = 4
    attn_layers: tuple = (1,)  # the layers that are attention (read from ``pattern`` where there is one)
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    ffn: int = 128  # the paired MLP's width (shared_intermediate_size); with a pattern ONE routed expert's
    ssm_heads: int = 8
    ssm_head_dim: int = 16
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_groups: int = 1  # n_groups: the B/C groups the heads share
    pattern: str = ""  # hybrid_override_pattern: one sublayer a layer; "": a mixer and a dense MLP in every layer
    untied: bool = False  # tie_word_embeddings false: the head is ``lm_head``
    experts: int = 0  # n_routed_experts: the router's width
    experts_held: int = 0  # the routed experts this chip holds, from ``first_expert``
    first_expert: int = 0
    experts_per_tok: int = 0
    shared_ffn: int = 0  # moe_shared_expert_intermediate_size
    routed_scale: float = 1.0
    gdn_key_heads: int = 0  # linear_num_key_heads: a delta-rule layer's q / k heads
    gdn_value_heads: int = 0  # linear_num_value_heads: its v heads, each with a [gdn_key_dim, gdn_value_dim] state
    gdn_key_dim: int = 0  # linear_key_head_dim
    gdn_value_dim: int = 0  # linear_value_head_dim
    rope_theta: float = 0.0  # the gated attention's rotary base
    rotary: float = 1.0  # partial_rotary_factor: the share of a head it rotates
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0625
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    max_len: int = 131072

    def __post_init__(self):
        if self.heads % self.kv_heads:
            raise ValueError(f"heads={self.heads} not a multiple of kv_heads={self.kv_heads}")
        if self.pattern:
            single, double = {KIND_SSM, KIND_ATTN, KIND_EXPERT}, {KIND_GDN, KIND_GATED}
            if len(self.pattern) != self.layers or not (set(self.pattern) <= single or set(self.pattern) <= double):
                raise ValueError(
                    f"pattern={self.pattern!r}: {self.layers} characters of {KIND_SSM!r} (Mamba-2), "
                    f"{KIND_ATTN!r} (attention) and {KIND_EXPERT!r} (an expert layer), or of {KIND_GDN!r} (a gated "
                    f"delta-rule mixer) and {KIND_GATED!r} (gated attention), each with an expert layer"
                )
            object.__setattr__(
                self, "attn_layers", tuple(i for i, k in enumerate(self.pattern) if k in (KIND_ATTN, KIND_GATED))
            )
        if any(not 0 <= i < self.layers for i in self.attn_layers):
            raise ValueError(f"attn_layers={self.attn_layers} outside 0..{self.layers - 1}")
        if self.ssm_conv < 2:
            raise ValueError("ssm_conv must be >= 2")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(f"ssm_heads={self.ssm_heads} not a multiple of ssm_groups={self.ssm_groups}")
        if self.expert_layers and not (
            0 < self.experts_per_tok <= self.experts
            and 0 < self.experts_held <= self.experts - self.first_expert
            and self.shared_ffn > 0
        ):
            raise ValueError(
                "an expert layer needs experts, experts_per_tok, shared_ffn and the share held "
                f"(experts_held from first_expert): {self.experts}, {self.experts_per_tok}, {self.shared_ffn}, "
                f"{self.experts_held} from {self.first_expert}"
            )
        if KIND_GDN in self.pattern and not (
            self.gdn_key_heads > 0 and self.gdn_key_dim > 0 and self.gdn_value_dim > 0
            and self.gdn_value_heads > 0 and self.gdn_value_heads % self.gdn_key_heads == 0
        ):
            raise ValueError(
                "a delta-rule layer needs gdn_key_heads, gdn_key_dim, gdn_value_dim and gdn_value_heads in whole "
                f"groups of the key heads: {self.gdn_key_heads}, {self.gdn_key_dim}, {self.gdn_value_dim}, "
                f"{self.gdn_value_heads}"
            )
        if KIND_GATED in self.pattern and not (self.rope_theta > 0 and 0 < self.rotary <= 1):
            raise ValueError(f"gated attention needs rope_theta and rotary in (0, 1]: {self.rope_theta}, {self.rotary}")

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
    def conv_width(self) -> int:  # xs | B | C, a B and a C a group
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def gdn_key_width(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def gdn_value_width(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def gdn_conv_width(self) -> int:  # q | k | v behind one convolution
        return 2 * self.gdn_key_width + self.gdn_value_width

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.rotary)

    @property
    def kinds(self) -> str:
        """A character a layer (``KIND_*``)."""
        return self.pattern or "".join(KIND_ATTN if i in self.attn_layers else KIND_SSM for i in range(self.layers))

    @property
    def paired(self) -> bool:
        """Whether a dense MLP follows every mixer (no pattern: granite)."""
        return not self.pattern

    @property
    def paired_experts(self) -> bool:
        """Whether an expert layer follows every mixer in the same layer,
        under zero-centred norms (a pattern of ``D`` and ``G``: the third
        shape)."""
        return KIND_GDN in self.pattern or KIND_GATED in self.pattern

    @property
    def ssm_layers(self) -> int:
        return self.kinds.count(KIND_SSM)

    @property
    def gdn_layers(self) -> int:
        return self.kinds.count(KIND_GDN)

    @property
    def rec_layers(self) -> int:
        """The layers with state rows: Mamba-2's or the delta rule's (a pattern has one of the two)."""
        return self.ssm_layers + self.gdn_layers

    @property
    def expert_layers(self) -> int:
        return self.layers if self.paired_experts else self.kinds.count(KIND_EXPERT)

    def cache_index(self, layer: int) -> int:
        """A layer's index in ITS cache: the attention layers count through
        the KV pool's layers, the recurrent layers through the state's."""
        return self.kinds[:layer].count(self.kinds[layer])


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
    40-layer one (float32 on the CPU, PR 34). An UNTIED head has no such
    coherent sum: the embedding at std 1 (as models/moe_decoder.py draws one)
    and ``lm_head`` like a projection.

    An expert layer as models/conv_decoder.py draws one: the router like the
    rest, its selection bias normal(0, ``EXPERT_BIAS_STD``) in float32 (it is
    trained by load balancing and published as a buffer; zeros would leave
    the selection path untested), the routed experts drawn the
    ``experts_held`` this chip holds, ``up`` and ``down`` and no gate. An
    expert's hidden width is STORED in whole lane tiles (ops/moe.py
    ``lane_tiles``: 1856 as 1920, zeros in ``up``'s last columns and
    ``down``'s last rows, so the grouped products run on the megablox kernel;
    the mathematics is the width's: ``relu(0)^2 = 0``).

    The third shape as ``qwen3_next``'s initialiser draws it: a delta-rule
    layer's ``A`` uniform in (0, 16] (``A_log`` its log), ``dt_bias`` ones,
    its convolution uniform like Mamba's and without bias, its gated norm's
    weight ones; every zero-centred norm's weight zeros (it weighs by ``1 +
    w``); the rest as above, the experts' ``gate_up`` with gate and up side
    by side (512 is four whole lane tiles: nothing is padded)."""
    root = jax.random.key(int(seed), impl="rbg")
    h, n, w = cfg.ssm_heads, cfg.ssm_state, cfg.conv_width
    # a zero-centred norm weighs by 1 + w: zeros where the others hold ones
    unit = (jnp.zeros if cfg.paired_experts else jnp.ones)((cfg.hidden,), dtype)

    def draw(key, shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def mlp(k1, k2):
        if not cfg.paired:
            return {}
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
            "ssm_in": draw(ks[0], (cfg.hidden, cfg.d_inner + w + h)),  # z | xBC | dt
            "conv_w": jax.random.uniform(ks[1], (cfg.ssm_conv, w), jnp.float32, -bound, bound).astype(dtype),
            "conv_b": jax.random.uniform(ks[2], (w,), jnp.float32, -bound, bound).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(ks[4], (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
            "D": jnp.ones((h,), dtype),
            "ssm_norm": jnp.ones((cfg.d_inner,), dtype),
            "ssm_out": draw(ks[5], (cfg.d_inner, cfg.hidden)),
            **mlp(*jax.random.split(ks[6])),
        }

    def expert(k_up, k_down, lead, width):
        pad = lane_tiles(width) - width
        nil = ((0, 0),) * len(lead)
        return {"up": jnp.pad(draw(k_up, (*lead, cfg.hidden, width)), (*nil, (0, 0), (0, pad))),
                "down": jnp.pad(draw(k_down, (*lead, width, cfg.hidden)), (*nil, (0, pad), (0, 0)))}

    @jax.jit
    def expert_layer(key):
        ks = jax.random.split(key, 6)
        return {
            "ln1": jnp.ones((cfg.hidden,), dtype),
            "moe": {
                "router": draw(ks[0], (cfg.hidden, cfg.experts)),
                "router_bias": jax.random.normal(ks[1], (cfg.experts,), jnp.float32) * EXPERT_BIAS_STD,
                **expert(ks[2], ks[3], (cfg.experts_held,), cfg.ffn),
            },
            "shared": expert(ks[4], ks[5], (), cfg.shared_ffn),
        }

    def softmax_experts(ks):
        """The third shape's expert layer: gated-SiLU experts (``gate_up``:
        gate and up side by side), the held share of them, a shared one and
        its one-scalar gate."""
        gated = lambda k1, k2, lead, width: {  # noqa: E731
            "gate_up": draw(k1, (*lead, cfg.hidden, 2 * width)), "down": draw(k2, (*lead, width, cfg.hidden))}
        return {
            "ln2": unit,
            "moe": {"router": draw(ks[0], (cfg.hidden, cfg.experts)), **gated(ks[1], ks[2], (cfg.experts_held,), cfg.ffn)},
            "shared": gated(ks[3], ks[4], (), cfg.shared_ffn),
            "shared_gate": draw(ks[5], (cfg.hidden,)),
        }

    @jax.jit
    def gdn_layer(key):
        ks = jax.random.split(key, 11)
        bound = 1.0 / math.sqrt(cfg.ssm_conv)
        return {
            "ln1": unit,
            "gdn_in": draw(ks[0], (cfg.hidden, cfg.gdn_conv_width + cfg.gdn_value_width)),  # q | k | v | z
            "gdn_ba": draw(ks[1], (cfg.hidden, 2 * cfg.gdn_value_heads)),  # b | a
            "conv_w": jax.random.uniform(ks[2], (cfg.ssm_conv, cfg.gdn_conv_width), jnp.float32, -bound, bound).astype(dtype),
            "dt_bias": jnp.ones((cfg.gdn_value_heads,), dtype),
            # A uniform in (0, 16]
            "A_log": jnp.log(16.0 * (1.0 - jax.random.uniform(ks[3], (cfg.gdn_value_heads,), jnp.float32))).astype(dtype),
            "gdn_norm": jnp.ones((cfg.gdn_value_dim,), dtype),
            "gdn_out": draw(ks[4], (cfg.gdn_value_width, cfg.hidden)),
            **softmax_experts(ks[5:]),
        }

    @jax.jit
    def gated_attn_layer(key):
        ks = jax.random.split(key, 8)
        return {
            "ln1": unit,
            # a head's query and its gate side by side ([q_h | gate_h] a head), then k, then v
            "attn_qkv": draw(ks[0], (cfg.hidden, 2 * cfg.q_width + 2 * cfg.kv_width)),
            "q_norm": jnp.zeros((cfg.head_dim,), dtype),
            "k_norm": jnp.zeros((cfg.head_dim,), dtype),
            "attn_o": draw(ks[1], (cfg.q_width, cfg.hidden)),
            **softmax_experts(ks[2:]),
        }

    params = {
        "tok_emb": jax.jit(lambda k: draw(k, (cfg.vocab, cfg.hidden), 1.0 if cfg.untied else 0.004))(
            jax.random.fold_in(root, 1 << 20)
        ),
        "ln_f": unit,
    }
    if cfg.untied:
        params["lm_head"] = jax.jit(lambda k: draw(k, (cfg.hidden, cfg.vocab)))(jax.random.fold_in(root, (1 << 20) + 1))
    layer = {KIND_ATTN: attn_layer, KIND_SSM: ssm_layer, KIND_EXPERT: expert_layer, KIND_GDN: gdn_layer,
             KIND_GATED: gated_attn_layer}
    params["layers"] = [layer[k](jax.random.fold_in(root, i)) for i, k in enumerate(cfg.kinds)]
    return params


def state_zeros(cfg: HybridDecoderConfig, rows: int) -> tuple:
    """The zeroed state cache, float32, the row at axis 0: one state array
    [rows, heads, head_dim, state] a Mamba layer ([rows, value heads, d_k,
    d_v] a delta-rule layer), then one conv array [rows, (ssm_conv - 1) *
    conv_width] a layer (``2 * rec_layers`` arrays: recurrent layer i's are
    ``rec[i]`` and ``rec[rec_layers + i]``)."""
    n = cfg.rec_layers
    if cfg.gdn_layers:  # the delta rule's matrix a value head, and its q | k | v conv inputs
        state = (rows, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim)
        conv = (rows, (cfg.ssm_conv - 1) * cfg.gdn_conv_width)
    else:
        state = (rows, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
        conv = (rows, (cfg.ssm_conv - 1) * cfg.conv_width)
    return tuple(jnp.zeros(state if i < n else conv, jnp.float32) for i in range(2 * n))


# ----------------------------------------------------------------- forward


def _scan_chunk(dt, a_neg, xs, b, c, s_in):
    """One chunk of the Mamba-2 recurrence in its chunked form. dt [n, m, h]
    (0 on a row past the slot's count: decay 1, no input, the state stands),
    a_neg [h] = -exp(A_log), xs [n, m, h, p], b / c [n, m, N], s_in
    [n, h, p, N]; all float32. Returns (y [n, m, h, p], s_out). With B/C
    groups the head axis ``h`` is two, (group, head of the group), everywhere
    it stands and b / c are [n, m, g, N]: a head reads its group's B and C
    where they lie, none is repeated a head.

    A dispatch is ONE scan chunk whatever ``chunk_size`` a configuration
    publishes (the result does not depend on it). At Nemotron-H's widths (64
    heads of 64 in 8 groups, state 128, ``chunk_size`` 128) the scans of a
    (4, 256) dispatch, twelve layers chained, read 0.401 ms a layer as one
    chunk of 256, 0.731 as two of 128 with the state carried and 0.645 as
    four of 64 (my chip run, PR 51): the second chunk waits for the first
    one's state, and the halved decay matrix does not pay for it."""
    g = "g" if b.ndim == 4 else ""  # the group axis, where there is one; "h" then counts a group's heads
    m = dt.shape[1]
    cs = jnp.cumsum(dt * a_neg, axis=1)  # [n, m, h], <= 0 and falling
    dtx = dt[..., None] * xs
    causal = jnp.tril(jnp.ones((m, m), bool))
    diff = jnp.moveaxis(cs, 1, -1)[..., :, None] - jnp.moveaxis(cs, 1, -1)[..., None, :]  # [n, h, t, s]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    dot = functools.partial(jnp.einsum, precision=_SCAN_PRECISION)
    cb = dot(f"nt{g}k,ns{g}k->n{g}ts", c, b)
    y = dot(f"n{g}hts,ns{g}hp->nt{g}hp", decay * jnp.expand_dims(cb, -3), dtx)
    y = y + jnp.exp(cs)[..., None] * dot(f"nt{g}k,n{g}hpk->nt{g}hp", c, s_in)
    tail = jnp.exp(cs[:, -1:] - cs)  # [n, m, h]: what is left of step s at the chunk's end
    s_out = jnp.exp(cs[:, -1])[..., None, None] * s_in + dot(
        f"ns{g}hp,ns{g}k->n{g}hpk", tail[..., None] * dtx, b
    )
    return y, s_out


def _scan_blocked(dt, a_neg, xs, b, c, s_in):
    """``_scan_chunk`` with the rows in blocks where the decay matrix of all
    of them would pass ``_SCAN_BLOCK_BYTES``."""
    n, m = dt.shape[:2]
    h = math.prod(dt.shape[2:])
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


def _valid(n: int, m: int, counts, rows):
    """[n, m] bool: the dispatch's real rows: the first ``counts[r]`` of a
    chunk's row r, the step's rows that generate."""
    valid = jnp.ones((n, m), bool)
    if counts is not None:
        valid &= jnp.arange(m)[None, :] < counts[:, None]
    if rows is not None:
        valid &= rows[:, None]
    return valid


def _norm(cfg: HybridDecoderConfig, w, x):
    """The configuration's RMS norm over x's last axis: ``_rms``, or for the
    third shape the zero-centred one, ``x . rsqrt(mean(x^2) + eps) . (1 +
    w)``, all of it in float32 before the cast back."""
    if not cfg.paired_experts:
        return _rms(w, x, cfg.rms_eps)
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.rms_eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _causal_conv(conv_in, xin, w, bias, valid):
    """The depthwise causal convolution of a recurrent mixer over the
    dispatch's inputs xin [n, m, width] behind the cached last k - 1 ones
    (conv_in [n, (k - 1) * width], time-major and flat), w [k, width], an
    optional bias. Returns (silu of the convolution [n, m, width] float32,
    the cache after the dispatch: the k - 1 inputs that end at the row's
    last real one, all of the old cache where ``valid`` [n, m] has none)."""
    f32 = jnp.float32
    n, m, width = xin.shape
    k = w.shape[0]
    # the last k - 1 inputs, then the dispatch's own: [n, k - 1 + m, w]
    seq = jnp.concatenate([conv_in.reshape(n, k - 1, width), xin.astype(f32)], axis=1)
    cw = w.astype(f32)
    # (the bias is cast before the taps are summed: the order granite's hashed lowered text has)
    taps = lambda: sum(cw[j] * seq[:, j : j + m] for j in range(k))  # noqa: E731
    act = jax.nn.silu(taps() if bias is None else bias.astype(f32) + taps())
    last = jnp.sum(valid, axis=1, dtype=jnp.int32)
    conv_out = jax.vmap(lambda s, at: lax.dynamic_slice_in_dim(s, at, k - 1))(seq, last)
    return act, conv_out.reshape(n, (k - 1) * width)


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
    h, hd, ns, w = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.conv_width
    # the head axis: (heads,) under one B/C group, (groups, heads of a group) under more (head i reads group
    # i // heads of a group), and B and C [.., groups, N] beside it; one group reshapes nothing
    grp = (cfg.ssm_groups,) if cfg.ssm_groups > 1 else ()
    hs = (*grp, h // cfg.ssm_groups)
    f32 = jnp.float32
    with jax.named_scope(SCOPE_QKV), jax.named_scope(SCOPE_SSM_IN):
        zxd = _rms(p["ln1"], x, cfg.rms_eps) @ p["ssm_in"].astype(x.dtype)
        z, xbc, dt = jnp.split(zxd, [cfg.d_inner, cfg.d_inner + w], axis=-1)
    valid = _valid(n, m, counts, rows)
    with jax.named_scope(SCOPE_ATTN):
        with jax.named_scope(SCOPE_SSM_CONV):
            conv_in = conv[:n] if state_rows is None else conv[state_rows[0]]
            xbc, conv_out = _causal_conv(conv_in, xbc, p["conv_w"], p["conv_b"], valid)
        with jax.named_scope(SCOPE_SSM_SCAN):
            xs, b, c = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + (w - cfg.d_inner) // 2], axis=-1)
            xs = xs.reshape(n, m, *hs, hd)
            b, c = b.reshape(n, m, *grp, ns), c.reshape(n, m, *grp, ns)
            dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
            dt = jnp.where(valid[..., None], dt, 0.0).reshape(n, m, *hs)
            a_neg = -jnp.exp(p["A_log"].astype(f32)).reshape(hs)
            if state_rows is None:
                s_in = state[:n].reshape(n, *hs, hd, ns)
                own = (slice(None), 0, *[slice(None)] * len(grp), None, None, slice(None))  # [n, (g,) 1, 1, N]: beside a head's [p, N]
                decay = jnp.exp(dt[:, 0] * a_neg)  # [n, h]; 1 where the row stands
                s_out = decay[..., None, None] * s_in + (dt[:, 0, ..., None] * xs[:, 0])[..., None] * b[own]
                # a product and a sum over the state as it is written, not a
                # matrix product that would read it again rounded to bfloat16
                y = jnp.sum(s_out * c[own], axis=-1)[:, None]
                state = state.at[:n].set(s_out.reshape(n, h, hd, ns))
                conv = conv.at[:n].set(conv_out)
            else:
                y, s_out = _scan_blocked(dt, a_neg, xs, b, c, state[state_rows[0]].reshape(n, *hs, hd, ns))
                for to in (state_rows[1], state_rows[2]):
                    state = state.at[to].set(s_out.reshape(n, h, hd, ns), mode="drop")
                    conv = conv.at[to].set(conv_out, mode="drop")
            y = y + p["D"].astype(f32).reshape(hs)[..., None] * xs
    with jax.named_scope(SCOPE_ATTN_OUT):
        with jax.named_scope(SCOPE_SSM_NORM):
            # the gate first, then the norm over each B/C group's channels (all of them under one group)
            g = (y.reshape(n, m, cfg.d_inner) * jax.nn.silu(z.astype(f32))).reshape(n, m, *grp, -1)
            g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_eps)
            g = g.reshape(n, m, cfg.d_inner).astype(x.dtype) * p["ssm_norm"].astype(x.dtype)
        with jax.named_scope(SCOPE_SSM_OUT):
            out = g @ p["ssm_out"].astype(x.dtype)
    rec = tuple(state if i == si else conv if i == cfg.ssm_layers + si else a for i, a in enumerate(rec))
    return out, rec


# (configuration, "step" | "chunk") -> {delta-rule layer: whether ``_gdn``'s newest trace of that layer in that kind of
# program took ops/gated_delta.py's kernel}: written where a program is traced, read by ``HybridDecoder.gdn_passes``
_GDN_TRACED: dict = {}


def _gdn(cfg: HybridDecoderConfig, si: int, p, x, rec, counts, rows, state_rows):
    """The gated delta-rule mixer over x[n, m, d], delta-rule layer ``si``'s
    state and conv arrays of ``rec``; the rows it reads and writes as
    ``_mamba`` has them (the step in place where ``rows``; a chunk by
    ``state_rows``, positions past ``counts`` leaving state and conv cache as
    they were: decay 1 and beta 0). The state, the decay, beta, both l2 norms
    and the gated norm are float32. Returns (the mixer's output [n, m, d],
    rec)."""
    state, conv = rec[si], rec[cfg.rec_layers + si]
    if state_rows is not None:
        x, state, conv = lax.optimization_barrier((x, state, conv))  # ``_mamba``: a layer's rows when its input exists
    n, m, _ = x.shape
    hk, hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    r, kw = hv // hk, cfg.gdn_key_width
    f32 = jnp.float32
    with jax.named_scope(SCOPE_QKV), jax.named_scope(SCOPE_GDN_IN):
        h = _norm(cfg, p["ln1"], x)
        qkv, z = jnp.split(h @ p["gdn_in"].astype(x.dtype), [cfg.gdn_conv_width], axis=-1)
        b, a = jnp.split(h @ p["gdn_ba"].astype(x.dtype), 2, axis=-1)
    valid = _valid(n, m, counts, rows)
    with jax.named_scope(SCOPE_ATTN):
        with jax.named_scope(SCOPE_GDN_CONV):
            conv_in = conv[:n] if state_rows is None else conv[state_rows[0]]
            qkv, conv_out = _causal_conv(conv_in, qkv, p["conv_w"], None, valid)
        with jax.named_scope(SCOPE_GDN_SCAN):
            q, k, v = jnp.split(qkv, [kw, 2 * kw], axis=-1)
            q, k = q.reshape(n, m, hk, dk), k.reshape(n, m, hk, dk)
            q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + _L2_EPS) * dk**-0.5
            k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + _L2_EPS)
            v = v.reshape(n, m, hk, r, dv)
            # log alpha = -exp(A_log) softplus(a + dt_bias) <= 0; 0 (and beta 0) where the row stands
            log_alpha = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(a.astype(f32) + p["dt_bias"].astype(f32))
            log_alpha = jnp.where(valid[..., None], log_alpha, 0.0).reshape(n, m, hk, r)
            beta = jnp.where(valid[..., None], jax.nn.sigmoid(b.astype(f32)), 0.0).reshape(n, m, hk, r)
            kernel = gdn_kernel_mode(dk, dv, state.dtype)  # static: the platform, the head's tiles, the rows' type
            # what this trace of the layer runs, for ``HybridDecoder.gdn_passes`` (the frames' count of what ran)
            _GDN_TRACED.setdefault((cfg, "step" if state_rows is None else "chunk"), {})[si] = bool(kernel)
            if kernel:
                # a head's matrix passes through VMEM once, read from and written to the rows where they lie
                if state_rows is None:
                    y, state = gdn_step_rows(
                        state, *(t[:, 0] for t in (q, k, v, log_alpha, beta)), interpret=kernel == "interpret"
                    )
                    y = y[:, None]
                    conv = conv.at[:n].set(conv_out)
                else:
                    y, state = gdn_chunk_rows(
                        state, state_rows, q, k, v, log_alpha, beta, interpret=kernel == "interpret"
                    )
                    for to in (state_rows[1], state_rows[2]):
                        conv = conv.at[to].set(conv_out, mode="drop")
            elif state_rows is None:
                # the plain form, over EVERY row of the state array, the rows past the slots (snapshots, the
                # zero row) with k 0, decay 1 and beta 0, which leave them as they were to the bit: the state is
                # read by two fusions (the sums, the update), and a ``state[:n]`` both read was materialised
                # first, 0.42 of a layer's 1.06 ms at 64 slots (my chip run, PR 57); whole, the update aliases
                # the donated array
                total = state.shape[0]
                whole = lambda t: jnp.pad(t[:, 0], ((0, total - n), *[(0, 0)] * (t.ndim - 2)))  # noqa: E731
                y, s_out = gdn_step(
                    state.astype(f32).reshape(total, hk, r, dk, dv), *(whole(t) for t in (q, k, v, log_alpha, beta))
                )
                y = y[:n, None]
                state = s_out.reshape(total, hv, dk, dv).astype(state.dtype)
                conv = conv.at[:n].set(conv_out)
            else:
                s_in = state[state_rows[0]].astype(f32).reshape(n, hk, r, dk, dv)
                y, s_out = gdn_chunk(s_in, q, k, v, log_alpha, beta)
                for to in (state_rows[1], state_rows[2]):
                    state = state.at[to].set(s_out.reshape(n, hv, dk, dv), mode="drop")
                    conv = conv.at[to].set(conv_out, mode="drop")
    with jax.named_scope(SCOPE_ATTN_OUT):
        with jax.named_scope(SCOPE_GDN_NORM):
            # the norm over each head's d_v first (a plain weight, not zero-centred), then the gate
            y = y.reshape(n, m, hv, dv)
            y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps) * p["gdn_norm"].astype(f32)
            g = (y.reshape(n, m, hv * dv) * jax.nn.silu(z.astype(f32))).astype(x.dtype)
        with jax.named_scope(SCOPE_GDN_OUT):
            out = g @ p["gdn_out"].astype(x.dtype)
    rec = tuple(state if i == si else conv if i == cfg.rec_layers + si else a for i, a in enumerate(rec))
    return out, rec


def _experts_softmax(cfg: HybridDecoderConfig, p, x, valid):
    """The third shape's expert layer over x[n, m, d] (after its own norm
    ``ln2``): the shared expert times ``sigmoid(shared_gate . n)``, one scalar
    a token, plus the routed experts held here under the softmax gate: softmax
    over ALL ``experts`` in float32, the top ``experts_per_tok``, renormalised
    over the picks (ops/moe.py ``route_topk``); a pick on an expert another
    chip holds adds nothing. Returns (the layer's output [n, m, d],
    counters[6]: ``moe_held_ffn``)."""
    n, m, d = x.shape
    h = _norm(cfg, p["ln2"], x).reshape(n * m, d)
    with jax.named_scope(SCOPE_SHARED_EXPERT):
        gate = jax.nn.sigmoid(jnp.sum(h.astype(jnp.float32) * p["shared_gate"].astype(jnp.float32), axis=-1, keepdims=True))
        shared = expert_mlp(p["shared"], h) * gate.astype(x.dtype)
    gates, experts = route_topk(p["moe"]["router"], h, cfg.experts_per_tok)
    y, cnt = moe_held_ffn(p["moe"], h, gates, experts, cfg.first_expert, valid.reshape(-1))
    return (shared + y).reshape(x.shape), cnt


def _experts(cfg: HybridDecoderConfig, p, x, valid):
    """An expert layer over x[n, m, d]: the shared expert, which every token
    takes ungated, plus the routed experts held here. The router scores ALL
    ``experts`` (sigmoid, float32), its bias selects the top
    ``experts_per_tok`` and does not weigh, the gates are the picks' scores
    over their sum times ``routed_scale``; a pick on an expert another chip
    holds adds nothing. Returns (the layer's output [n, m, d], counters[6]:
    ops/moe.py ``moe_held_ffn``)."""
    n, m, d = x.shape
    h = _rms(p["ln1"], x, cfg.rms_eps).reshape(n * m, d)
    with jax.named_scope(SCOPE_SHARED_EXPERT):
        shared = expert_mlp(p["shared"], h)
    gates, experts = route_sigmoid_biased(
        p["moe"]["router"], p["moe"]["router_bias"], h, cfg.experts_per_tok, cfg.routed_scale, _GATE_EPS
    )
    y, cnt = moe_held_ffn(p["moe"], h, gates, experts, cfg.first_expert, valid.reshape(-1))
    return (shared + y).reshape(x.shape), cnt


def _attention(cfg: HybridDecoderConfig, ki: int, p, x, pool, bt, positions, counts, reads=None, interpret=False):
    """Grouped-query attention over pool layer ``ki``, without positions, or
    the third shape's gated one (a layer with ``q_norm``: a sigmoid gate
    beside each head's query, q and k normed a head, rotary on the head's first
    ``rotary_dim`` dimensions before the cache write):
    K and V scatter through the block tables and attention reads them back,
    like the other families' write-then-read: through the gather, or, where
    the step was given ``reads`` (``decoder._paged_step_reads``), through
    ops/gqa_decode.py's kernel, which reads the pages where they lie.
    Returns (the mixer's output [n, m, d], pool)."""
    n, m, _ = x.shape
    gated = "q_norm" in p  # the third shape's: [q_h | gate_h] a head, q and k normed a head, partial rotary
    with jax.named_scope(SCOPE_QKV):
        qkv = _norm(cfg, p["ln1"], x) @ p["attn_qkv"].astype(x.dtype)
        q_width = cfg.q_width * (2 if gated else 1)
        q, k, v = jnp.split(qkv, [q_width, q_width + cfg.kv_width], axis=-1)
        q = q.reshape(n, m, cfg.heads, -1)
        if gated:
            q, gate = jnp.split(q, 2, axis=-1)
            q = _norm(cfg, p["q_norm"], q)
            k = _norm(cfg, p["k_norm"], k.reshape(n, m, cfg.kv_heads, cfg.head_dim))
            with jax.named_scope(SCOPE_ROPE):
                rot = cfg.rotary_dim
                inv_freq = (cfg.rope_theta ** (-2.0 * np.arange(rot // 2, dtype=np.float64) / rot)).astype(np.float32)
                q_pos = positions[:, None] + jnp.arange(m, dtype=positions.dtype)[None, :]
                q, k = _rope(q, q_pos, inv_freq, 1.0), _rope(k, q_pos, inv_freq, 1.0).reshape(n, m, cfg.kv_width)
    pool = _paged_write(pool, ki, k, v, bt, positions, counts)
    scale = cfg.attention_multiplier
    if reads is not None:
        with jax.named_scope(SCOPE_ATTN):
            ctx = paged_gqa_attention(q, pool, ki, bt, reads, scale=scale, interpret=interpret)
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
        if gated:
            with jax.named_scope(SCOPE_ATTN_GATE):
                ctx = ctx * jax.nn.sigmoid(gate.reshape(n, m, cfg.q_width).astype(jnp.float32)).astype(ctx.dtype)
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
    lets a dispatch of ONE query a slot, and a prefill chunk (``counts``;
    ``gqa_chunk_tiles``), read the pool through ops/gqa_decode.py's kernels;
    every other shape gathers. Returns (logits
    [n, m or 1, vocab] float32, pool, rec, counters[2, or 8 with expert
    layers] int32: ``HybridDecoder.frame_counters``)."""
    n, m = tokens.shape
    res = cfg.residual_multiplier
    chunk = gqa_chunk_tiles(attn_kernel, m, cfg.heads, cfg.kv_heads, cfg.head_dim)
    reads, run_pages = _paged_step_reads(attn_kernel, m, pool, bt, positions, rows, counts if chunk else None)
    with jax.named_scope(SCOPE_EMBED):
        x = jnp.asarray(params["tok_emb"])[tokens] * jnp.asarray(cfg.embedding_multiplier, params["tok_emb"].dtype)
    counted = []  # a configuration with expert layers: their six counts, before the two every configuration has
    if cfg.expert_layers:
        real = _valid(n, m, counts, rows)  # the rows an expert layer routes and counts
        cnt = jnp.zeros((N_HELD_COUNTERS,), jnp.int32)
    for li, (kind, p) in enumerate(zip(cfg.kinds, params["layers"])):
        ci = cfg.cache_index(li)
        if kind == KIND_EXPERT:
            with jax.named_scope(SCOPE_MLP):
                y, c = _experts(cfg, p, x, real)
                x = x + y * jnp.asarray(res, x.dtype)
                with jax.named_scope(SCOPE_MOE_COMBINE):
                    cnt = cnt + c
            continue
        if kind in (KIND_ATTN, KIND_GATED):
            mix, pool = _attention(cfg, ci, p, x, pool, bt, positions, counts, reads, attn_kernel == "interpret")
        elif kind == KIND_GDN:
            mix, rec = _gdn(cfg, ci, p, x, rec, counts, rows, state_rows)
        else:
            mix, rec = _mamba(cfg, ci, p, x, rec, counts, rows, state_rows)
        with jax.named_scope(SCOPE_ATTN_OUT):
            x = x + mix * jnp.asarray(res, x.dtype)
        if cfg.paired_experts:
            with jax.named_scope(SCOPE_MLP):
                y, c = _experts_softmax(cfg, p, x, real)
                x = x + y * jnp.asarray(res, x.dtype)
                with jax.named_scope(SCOPE_MOE_COMBINE):
                    cnt = cnt + c
        if cfg.paired:
            with jax.named_scope(SCOPE_MLP):
                gu = _rms(p["ln2"], x, cfg.rms_eps) @ p["mlp_in"].astype(x.dtype)
                g, u = jnp.split(gu, 2, axis=-1)
                x = x + ((jax.nn.silu(g) * u) @ p["mlp_out"].astype(x.dtype)) * jnp.asarray(res, x.dtype)
    with jax.named_scope(SCOPE_LM_HEAD):
        last = _norm(cfg, params["ln_f"], x if pick is None else jnp.take_along_axis(x, pick[:, None, None], axis=1))
        if cfg.untied:
            logits = jnp.matmul(last, params["lm_head"].astype(x.dtype), preferred_element_type=jnp.float32)
        else:  # the tied head: the embedding's rows again
            logits = jnp.einsum(
                "nmd,vd->nmv", last, jnp.asarray(params["tok_emb"]).astype(x.dtype), preferred_element_type=jnp.float32
            )
        logits = logits / cfg.logits_scaling
        live = jnp.ones((n,), bool) if counts is None else counts > 0
        if rows is not None:
            live &= rows
        advanced = jnp.sum(live, dtype=jnp.int32)[None]
        if cfg.expert_layers:  # rows are every expert layer's own count: reported once, not summed
            counted = [cnt.at[0].set(jnp.sum(real, dtype=jnp.int32))]
    return logits, pool, rec, jnp.concatenate([*counted, advanced, run_pages])


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
    # beside the plain rounds: a step that reads the pool in place (ops/gqa_decode.py's kernel)
    serves = frozenset({"attn_kernel"})

    @property
    def frame_counters(self) -> tuple:
        """What the programs' readback carries after the tokens (FlightFrame
        fields): the rows whose state advanced, and where the step's kernel
        ran the pages it fetched in run DMAs (one layer's K); before them,
        from a configuration with expert layers and no other, the routing
        over the experts HELD (ops/moe.py ``moe_held_ffn``'s six)."""
        held = ("moe_rows", "moe_experts_hit", "moe_load_max", *HELD_COUNTERS) if self.cfg.expert_layers else ()
        return (*held, "ssm_rows", "attn_run_pages")

    def gdn_passes(self, kind: str) -> tuple:
        """(the delta-rule layer passes of one ``kind`` dispatch, "step" or
        "chunk"; those among them that run in ops/gated_delta.py's kernels),
        the second as ``_gdn`` decided it layer by layer where the program
        was traced (``_GDN_TRACED``: from the state array it was handed, not
        asked again here), so 0 before the first trace; (0, 0) for a
        configuration without such layers. FlightFrame ``gdn_passes`` /
        ``gdn_kernel_passes``."""
        return self.cfg.gdn_layers, sum(_GDN_TRACED.get((self.cfg, kind), {}).values())

    def decoder_dims(self, params: dict) -> dict:
        if ("lm_head" in params) != self.cfg.untied or not any("ssm_in" in p or "gdn_in" in p for p in params["layers"]):
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

    def chunk_attn(self, attn_kernel: str, c: int) -> str:
        """How the chunk program of ``c`` tokens a row reads the pool under
        ``attn_kernel``: "kernel" (ops/gqa_decode.py ``gqa_chunk_attention``)
        or "gather". Static (``gqa_chunk_tiles``: what ``_forward`` asks)."""
        takes = gqa_chunk_tiles(attn_kernel, c, self.cfg.heads, self.cfg.kv_heads, self.cfg.head_dim)
        return "kernel" if takes else "gather"

    @functools.lru_cache(maxsize=None)
    def fused_programs(self, attn_kernel: str = ""):
        """This family's step and chunk bodies (``decoder.
        counted_state_programs``: both carry the state cache beside the
        pool, the step takes ``rows``, the chunk ``state_rows``); with
        ``attn_kernel`` the step reads the pool through ops/gqa_decode.py's step
        kernel and a chunk through its chunk kernel (``chunk_attn``). Cached:
        equal configurations share compiled programs."""
        return counted_state_programs(functools.partial(self.paged_forward, attn_kernel=attn_kernel))

    def generate(self, params, ids, max_new_tokens: int):
        return _generate(self.cfg, params, ids, max_new_tokens)


@functools.lru_cache(maxsize=None)
def hybrid_family(cfg: HybridDecoderConfig) -> HybridDecoder:
    return HybridDecoder(cfg)
