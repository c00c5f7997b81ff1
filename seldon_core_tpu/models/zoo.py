"""Model zoo: named model builders -> (apply_fn, params, metadata).

Parity role: the reference's examples/models/* (sklearn_iris, deep_mnist,
keras_mnist, mean_classifier, ...) are user containers; here the equivalents
are JAX builders that the JAX_MODEL graph unit loads straight into HBM.
``model_uri`` schemes understood by unit_from_container:
    zoo://<name>[?k=v...]   build from this registry (fresh deterministic init)
    file://<path>           orbax checkpoint dir (params restored to device)
    hf-bert://<path>[?seq=N]  local HF BertForSequenceClassification dir
                            (save_pretrained), mapped via models/hf_import
"""

from __future__ import annotations

import inspect
import threading
import urllib.parse
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from seldon_core_tpu.graph.spec import ContainerSpec, PredictiveUnit
from seldon_core_tpu.models.base import JaxModelUnit, ModelRuntime


@dataclass
class ModelSpec:
    """What a builder returns: everything needed to instantiate a runtime."""

    apply_fn: Callable[[Any, jax.Array], jax.Array]
    params: Any
    feature_shape: tuple[int, ...]
    class_names: tuple[str, ...] = ()
    param_pspecs: Any | None = None  # PartitionSpec pytree for tensor parallelism
    # optional mesh-aware apply: called with the predictor's Mesh to build a
    # sharded apply (e.g. ring attention over the "seq" axis); apply_fn
    # remains the single-device/no-mesh path
    apply_factory: Callable[[Any], Callable] | None = None
    # integer-payload semantics: "cast" = integers are values (images,
    # tabular) and normalize to the model dtype; "ids" = integers are token
    # ids and stay exact int32 (ModelRuntime wire-dtype policy)
    int_inputs: str = "cast"
    # generative decoders advertise their decode geometry here ({"seq":
    # prompt bucket, "max_new_tokens": cap}) so the serving layer can offer
    # the continuous-batching decode scheduler (tpu.decode_slots) as an
    # alternative to the fused whole-batch apply; "family" names the decoder
    # family whose paged programs the scheduler runs (absent: the GPT-2
    # family of models/decoder.py)
    generative: dict | None = None


Builder = Callable[..., ModelSpec]
_REGISTRY: dict[str, Builder] = {}


def register_model(name: str):
    def deco(fn: Builder) -> Builder:
        _REGISTRY[name] = fn
        return fn

    return deco


# Heavy builds are memoized per (name, builder-relevant kwargs): same-seed
# builds are deterministic, params are treated as immutable downstream
# (ModelRuntime casts/quantizes into NEW arrays; online fine-tuning rebinds
# runtime.params, never writes through), so sharing the pytree is safe — and
# re-initializing a ResNet50/BERT for every deployment of the same spec
# costs tens of seconds of device time (e.g. an ensemble CR + its bench
# rerun). Bounded LRU: the admission estimator also builds via get_model,
# and an unbounded cache would retain every rejected spec's params forever.
_HEAVY_CACHE: OrderedDict[tuple, ModelSpec] = OrderedDict()
_HEAVY_CACHE_MAX = 4
_CACHEABLE = frozenset({"resnet50", "bert_base"})
# the admission estimator and operator reconcile both build via get_model
# from different threads: the lock serializes the OrderedDict check/insert/
# evict (a concurrent popitem interleaving could KeyError), and the
# in-flight table de-dups concurrent FIRST builds of the same key —
# a duplicated resnet50/bert build costs tens of seconds of device time and
# 2x peak params memory. Builds themselves run OUTSIDE the lock.
_HEAVY_CACHE_LOCK = threading.Lock()
_HEAVY_BUILDING: dict[tuple, threading.Event] = {}


def _heavy_cache_key(name: str, kwargs: dict) -> tuple | None:
    """(name, kwargs restricted to the builder's own parameters) — callers
    forward EVERY unit parameter (finetune_lr etc.) as builder kwargs and
    the builders swallow unknowns via **_, so keying on the full dict would
    duplicate bit-identical builds. None when any relevant value is
    unhashable (build uncached)."""
    sig = inspect.signature(_REGISTRY[name])
    relevant = {
        k: v
        for k, v in kwargs.items()
        if k in sig.parameters
        and sig.parameters[k].kind is not inspect.Parameter.VAR_KEYWORD
    }
    # normalize defaults so zoo://resnet50?space_to_depth=1 and
    # zoo://resnet50?seed=0&space_to_depth=1 (bit-identical builds) share a
    # key instead of occupying two LRU slots
    bound = sig.bind_partial(**relevant)
    bound.apply_defaults()
    args = {
        k: v
        for k, v in bound.arguments.items()
        if sig.parameters[k].kind is not inspect.Parameter.VAR_KEYWORD
    }
    key = (name, tuple(sorted(args.items())))
    try:
        hash(key)
    except TypeError:
        return None
    return key


def get_model(name: str, **kwargs) -> ModelSpec:
    if name not in _REGISTRY:
        _register_heavy_models()
    if name not in _REGISTRY:
        raise KeyError(f"unknown model '{name}'; known: {sorted(_REGISTRY)}")
    if name in _CACHEABLE:
        key = _heavy_cache_key(name, kwargs)
        if key is None:
            return _REGISTRY[name](**kwargs)
        with _HEAVY_CACHE_LOCK:
            if key in _HEAVY_CACHE:
                _HEAVY_CACHE.move_to_end(key)
                return _HEAVY_CACHE[key]
            in_flight = _HEAVY_BUILDING.get(key)
            if in_flight is None:
                in_flight = threading.Event()
                _HEAVY_BUILDING[key] = in_flight
                am_builder = True
            else:
                am_builder = False
        if not am_builder:
            in_flight.wait()
            with _HEAVY_CACHE_LOCK:
                if key in _HEAVY_CACHE:
                    _HEAVY_CACHE.move_to_end(key)
                    return _HEAVY_CACHE[key]
            # the builder raised — build for ourselves (uncached; a broken
            # spec must not poison the cache for later callers)
            return _REGISTRY[name](**kwargs)
        try:
            spec = _REGISTRY[name](**kwargs)
            with _HEAVY_CACHE_LOCK:
                _HEAVY_CACHE[key] = spec
                while len(_HEAVY_CACHE) > _HEAVY_CACHE_MAX:
                    _HEAVY_CACHE.popitem(last=False)
            return spec
        finally:
            with _HEAVY_CACHE_LOCK:
                _HEAVY_BUILDING.pop(key, None)
            in_flight.set()
    return _REGISTRY[name](**kwargs)


def list_models() -> list[str]:
    return sorted(_REGISTRY)


# ------------------------------------------------------------------ builders


def _dense_init(key, n_in: int, n_out: int):
    wkey, _ = jax.random.split(key)
    scale = (2.0 / n_in) ** 0.5
    return {
        "w": jax.random.normal(wkey, (n_in, n_out), dtype=jnp.float32) * scale,
        "b": jnp.zeros((n_out,), dtype=jnp.float32),
    }


# apply fns are MODULE-LEVEL (not per-build closures) so two builds of the
# same architecture share function identity — that is what lets the fused
# ensemble compiler (engine/fused.py) stack their params and vmap once.


def _apply_logistic(p, x):
    return jax.nn.softmax(x @ p["w"] + p["b"], axis=-1)


def _apply_mlp2(p, x):
    h = jax.nn.relu(x @ p["l1"]["w"] + p["l1"]["b"])
    return jax.nn.softmax(h @ p["l2"]["w"] + p["l2"]["b"], axis=-1)


def _apply_mean_sigmoid(p, x):
    return jax.nn.sigmoid(jnp.mean(x, axis=-1, keepdims=True))


def _apply_mlp3_flat(p, x):
    x = x.reshape((x.shape[0], -1))
    h = jax.nn.relu(x @ p["l1"]["w"] + p["l1"]["b"])
    h = jax.nn.relu(h @ p["l2"]["w"] + p["l2"]["b"])
    return jax.nn.softmax(h @ p["l3"]["w"] + p["l3"]["b"], axis=-1)


@register_model("iris_logistic")
def build_iris_logistic(seed: int = 0, **_) -> ModelSpec:
    """Logistic head, 4 features -> 3 classes — the sklearn-iris-equivalent
    (reference examples/models/sklearn_iris/IrisClassifier.py)."""
    params = _dense_init(jax.random.key(seed), 4, 3)
    return ModelSpec(_apply_logistic, params, (4,), ("setosa", "versicolor", "virginica"))


@register_model("iris_mlp")
def build_iris_mlp(seed: int = 0, hidden: int = 32, **_) -> ModelSpec:
    k1, k2 = jax.random.split(jax.random.key(seed))
    params = {"l1": _dense_init(k1, 4, hidden), "l2": _dense_init(k2, hidden, 3)}
    return ModelSpec(_apply_mlp2, params, (4,), ("setosa", "versicolor", "virginica"))


@register_model("mean_classifier")
def build_mean_classifier(**_) -> ModelSpec:
    """Parity with reference examples/models/mean_classifier/MeanClassifier.py:
    sigmoid of the feature mean -> single score."""
    return ModelSpec(_apply_mean_sigmoid, {}, (4,), ("proba",))


@register_model("mnist_mlp")
def build_mnist_mlp(seed: int = 0, hidden: int = 512, **_) -> ModelSpec:
    """Deep-MNIST-equivalent (reference examples/models/deep_mnist): flat 784
    input -> 10 softmax. MLP keeps the matmuls MXU-shaped."""
    keys = jax.random.split(jax.random.key(seed), 3)
    params = {
        "l1": _dense_init(keys[0], 784, hidden),
        "l2": _dense_init(keys[1], hidden, hidden),
        "l3": _dense_init(keys[2], hidden, 10),
    }
    return ModelSpec(_apply_mlp3_flat, params, (784,), tuple(str(i) for i in range(10)))


def _pipe_stage_fn(p, h):
    """One pipeline stage: residual tanh block, [mb, d] -> [mb, d] (the
    uniform signature pipeline_apply requires)."""
    return h + jnp.tanh(h @ p["w"] + p["b"])


def _apply_pipe_tower_seq(p, x):
    """Single-device reference path: stages run sequentially via scan over
    the stacked [S, ...] stage params — bitwise the same math the pipelined
    path computes, so serving equivalence is testable."""
    from jax import lax

    h = x @ p["embed"]["w"] + p["embed"]["b"]

    def body(h, stage_p):
        return _pipe_stage_fn(stage_p, h), None

    h, _ = lax.scan(body, h, p["stages"])
    return jax.nn.softmax(h @ p["head"]["w"] + p["head"]["b"], axis=-1)


@register_model("pipe_mlp")
def build_pipe_mlp(
    seed: int = 0, n_in: int = 16, d: int = 64, stages: int = 4, classes: int = 3, **_
) -> ModelSpec:
    """Pipeline-parallel SERVING model (VERDICT r2 item 6): a residual MLP
    tower whose stages shard one-per-device over a "pipe" mesh axis.

    With ``tpu.mesh: {"pipe": S}`` the apply_factory wraps
    parallel/pipeline.pipeline_apply — each device holds ONE stage's
    params, activations flow stage-to-stage over ICI (ppermute), and the
    micro-batched GPipe schedule hides the per-stage latency. Without a
    pipe axis the same stacked params run as a sequential scan, so the
    deployment spec alone decides the execution strategy (the SURVEY §7
    inversion: the CR compiles onto the slice)."""
    keys = jax.random.split(jax.random.key(seed), 3)
    scale = (1.0 / d) ** 0.5
    params = {
        "embed": _dense_init(keys[0], n_in, d),
        "stages": {
            "w": jax.random.normal(keys[1], (stages, d, d), jnp.float32) * scale,
            "b": jnp.zeros((stages, d), jnp.float32),
        },
        "head": _dense_init(keys[2], d, classes),
    }
    from jax.sharding import PartitionSpec as P

    pspecs = {
        "embed": {"w": P(), "b": P()},
        # one stage per device along the pipe axis
        "stages": {"w": P("pipe"), "b": P("pipe")},
        "head": {"w": P(), "b": P()},
    }

    def apply_factory(mesh):
        if "pipe" not in mesh.axis_names:
            return _apply_pipe_tower_seq
        from seldon_core_tpu.parallel.pipeline import pipeline_apply

        n_stages = int(mesh.shape["pipe"])

        def apply_pipelined(p, x):
            h = x @ p["embed"]["w"] + p["embed"]["b"]
            batch = h.shape[0]
            # microbatch count: S microbatches fill the pipe (bubble
            # fraction (S-1)/(2S-1)); shapes are static per bucket so this
            # branch resolves at trace time, and power-of-two buckets are
            # always divisible by a power-of-two stage count
            m = n_stages if batch % n_stages == 0 else 1
            h_micro = h.reshape(m, batch // m, h.shape[-1])
            out = pipeline_apply(_pipe_stage_fn, p["stages"], h_micro, mesh)
            h2 = out.reshape(batch, h.shape[-1])
            return jax.nn.softmax(h2 @ p["head"]["w"] + p["head"]["b"], axis=-1)

        return apply_pipelined

    return ModelSpec(
        _apply_pipe_tower_seq,
        params,
        (n_in,),
        tuple(f"c{i}" for i in range(classes)),
        param_pspecs=pspecs,
        apply_factory=apply_factory,
    )


def _apply_moe_mlp(p, x):
    """[batch, features] -> class probabilities through a top-1 MoE FFN
    (ops/moe.py): embed -> residual MoE block (seq length 1) -> softmax
    head. Module-level for fused-ensemble apply-fn identity."""
    from seldon_core_tpu.ops.moe import moe_ffn

    h = x @ p["embed"]["w"] + p["embed"]["b"]
    h = h[:, None, :]  # [b, 1, d_model] — moe_ffn's token axis
    h = h + moe_ffn(p["moe"], h)
    h = h[:, 0, :]
    return jax.nn.softmax(h @ p["head"]["w"] + p["head"]["b"], axis=-1)


@register_model("moe_mlp")
def build_moe_mlp(
    seed: int = 0,
    n_in: int = 16,
    d_model: int = 64,
    d_ff: int = 128,
    n_experts: int = 8,
    classes: int = 3,
    **_,
) -> ModelSpec:
    """Expert-parallel SERVING model (VERDICT r4 Next #5): a mixture-of-
    experts classifier whose expert weights shard over the mesh "expert"
    axis (ops/moe.moe_pspecs) — with ``tpu.mesh: {"data": D, "expert": E}``
    each device computes only its local experts' slab and XLA inserts the
    one psum the gate-weighted reduction needs. Without a mesh the same
    params serve dense on one device, so the deployment spec alone decides
    the strategy (same inversion as pipe_mlp). No reference analogue
    (SURVEY §2: no expert parallelism exists there).
    """
    from jax.sharding import PartitionSpec as P

    from seldon_core_tpu.ops.moe import init_moe, moe_pspecs

    k1, k2 = jax.random.split(jax.random.key(seed))
    params = {
        "embed": _dense_init(k1, n_in, d_model),
        "moe": init_moe(seed, d_model=d_model, d_ff=d_ff, n_experts=n_experts),
        "head": _dense_init(k2, d_model, classes),
    }
    pspecs = {
        "embed": {"w": P(), "b": P()},
        "moe": moe_pspecs("expert"),
        "head": {"w": P(), "b": P()},
    }
    return ModelSpec(
        _apply_moe_mlp,
        params,
        (n_in,),
        tuple(f"c{i}" for i in range(classes)),
        param_pspecs=pspecs,
    )


@register_model("tiny_gpt")
def build_tiny_gpt(
    seed: int = 0,
    vocab: int = 512,
    hidden: int = 128,
    layers: int = 2,
    ffn: int = 256,
    max_len: int = 128,
    seq: int = 32,
    max_new_tokens: int = 16,
    resid_scale: float = 1.0,
    **_,
) -> ModelSpec:
    """Generative SERVING model (greenfield tier — the reference serves no
    autoregressive models): GPT-style causal decoder, greedy KV-cache
    decode inside one compiled program (models/decoder.py — prefill
    through the causal-attention policy incl. the Pallas kernel on TPU,
    then a lax.scan of single-token steps). ``max_new_tokens`` and the
    prompt bucket are deployment parameters, so every request of a bucket
    reuses one XLA program. Wire: int token ids in, ids out
    ([b, seq + max_new_tokens], exact int32 through the serving dtype
    policy)."""
    from functools import partial

    from seldon_core_tpu.models.decoder import init_decoder

    if seq + max_new_tokens > max_len:
        raise ValueError(
            f"seq={seq} + max_new_tokens={max_new_tokens} exceeds "
            f"max_len={max_len} — raise max_len"
        )
    params = init_decoder(
        seed, vocab=vocab, hidden=hidden, layers=layers, ffn=ffn, max_len=max_len,
        resid_scale=resid_scale,
    )
    return ModelSpec(
        partial(_apply_tiny_gpt, max_new_tokens=max_new_tokens),
        params,
        (seq,),
        (),
        int_inputs="ids",
        generative={"seq": seq, "max_new_tokens": max_new_tokens},
    )


@register_model("moe_decoder")
def build_moe_decoder(
    seed: int = 0,
    vocab: int = 512,
    hidden: int = 64,
    layers: int = 4,
    ffn: int = 32,
    heads: int | str = 4,
    kv_heads: int = 2,
    head_dim: int = 16,
    experts: int = 8,
    experts_per_tok: int = 2,
    window: int = 8,
    period: int = 4,
    rope_theta: float = 10000.0,
    yarn_factor: float = 4.0,
    yarn_original: int = 16,
    yarn_beta_fast: float = 32.0,
    yarn_beta_slow: float = 1.0,
    yarn_attention_factor: float = 0.0,
    rms_eps: float = 1e-6,
    max_len: int = 131072,
    attn_gate: bool = False,
    rotary_full: float = 1.0,
    rope_theta_window: float = 0.0,
    full_first: bool = False,
    dense_layers: int = 0,
    dense_ffn: int = 0,
    shared_expert: bool = False,
    experts_held: int = 0,
    first_expert: int = 0,
    routed_scale: float = 1.0,
    seq: int = 32,
    max_new_tokens: int = 16,
    param_dtype: str = "bfloat16",
    **unknown,
) -> ModelSpec:
    """The generative tier's second decoder family (models/moe_decoder.py):
    RMSNorm, grouped-query attention with rotary positions (YaRN on the
    full layers), ``period - 1`` sliding-window layers to one full layer,
    and a top-``experts_per_tok``-of-``experts`` gated-SiLU expert layer in
    every block. The parameters are a published config's keys; ``ffn`` is
    ONE expert's width. What a configuration may add: ``heads`` as
    ``"full,sliding"`` (two query head counts by layer kind), ``attn_gate``
    (a per-head sigmoid gate on the attention output), ``rotary_full`` (the
    share of a head's dimensions the full layers rotate),
    ``rope_theta_window`` (the sliding layers' theta), ``full_first`` (the
    full layer leads its period), ``dense_layers`` leading dense layers of
    width ``dense_ffn``, ``shared_expert``, ``routed_scale``, and one chip's
    share of an expert-parallel layer: ``experts_held`` (0: all) from
    ``first_expert``, the router keeping ``experts`` outputs. Weights are
    drawn on the device from ``seed`` in
    ``param_dtype`` (set it to the deployment's ``tpu.dtype``: the runtime
    casts what differs). It serves through ``tpu.decode_slots`` (the
    scheduler takes the family from ``generative["family"]``); without it
    the fused fallback decodes whole batches greedily through the same
    paged forward. Speculation and tensor-parallel decode are not served
    for it yet. A parameter it does not know is refused by name: a
    configuration written for a later tree fails with a sentence here and
    does not build another model."""
    import jax.numpy as jnp

    from seldon_core_tpu.graph.spec import bool_param
    from seldon_core_tpu.models.moe_decoder import (
        MoEDecoderConfig,
        init_moe_decoder,
        moe_family,
    )

    if unknown:
        raise ValueError(
            f"zoo://moe_decoder does not know the parameter(s) {sorted(unknown)}: "
            "it builds what it is told, not a model without them"
        )
    if seq + max_new_tokens > max_len:
        raise ValueError(
            f"seq={seq} + max_new_tokens={max_new_tokens} exceeds max_len={max_len}"
        )
    by_kind = [int(h) for h in str(heads).split(",")]
    if len(by_kind) not in (1, 2):
        raise ValueError(f"heads={heads!r}: one count, or 'full,sliding'")
    cfg = MoEDecoderConfig(
        vocab=int(vocab), hidden=int(hidden), layers=int(layers), heads=by_kind[0],
        kv_heads=int(kv_heads), head_dim=int(head_dim), ffn=int(ffn),
        experts=int(experts), experts_per_tok=int(experts_per_tok), window=int(window),
        period=int(period), rope_theta=float(rope_theta), yarn_factor=float(yarn_factor),
        yarn_original=int(yarn_original), yarn_beta_fast=float(yarn_beta_fast),
        yarn_beta_slow=float(yarn_beta_slow),
        yarn_attention_factor=float(yarn_attention_factor), rms_eps=float(rms_eps),
        max_len=int(max_len), heads_window=by_kind[-1] if len(by_kind) == 2 else 0,
        attn_gate=bool_param(attn_gate), rotary_full=float(rotary_full),
        rope_theta_window=float(rope_theta_window), full_first=bool_param(full_first),
        dense_layers=int(dense_layers), dense_ffn=int(dense_ffn),
        shared_expert=bool_param(shared_expert), experts_held=int(experts_held),
        first_expert=int(first_expert), routed_scale=float(routed_scale),
    )
    family = moe_family(cfg)
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[str(param_dtype)]
    max_new = int(max_new_tokens)
    return ModelSpec(
        lambda p, x: family.generate(p, x, max_new),
        init_moe_decoder(cfg, int(seed), dtype),
        (int(seq),),
        (),
        int_inputs="ids",
        generative={"seq": int(seq), "max_new_tokens": max_new, "family": family},
    )


@register_model("hybrid_decoder")
def build_hybrid_decoder(
    seed: int = 0,
    vocab: int = 512,
    hidden: int = 64,
    layers: int = 4,
    attn_layers: str = "1",
    ffn: int = 128,
    heads: int = 4,
    kv_heads: int = 2,
    head_dim: int = 16,
    ssm_heads: int = 8,
    ssm_head_dim: int = 16,
    ssm_state: int = 16,
    ssm_conv: int = 4,
    ssm_groups: int = 1,
    experts: int = 0,
    experts_held: int = 0,
    first_expert: int = 0,
    experts_per_tok: int = 0,
    shared_ffn: int = 0,
    routed_scale: float = 1.0,
    untied: bool = False,
    gdn_key_heads: int = 0,
    gdn_value_heads: int = 0,
    gdn_key_dim: int = 0,
    gdn_value_dim: int = 0,
    rope_theta: float = 0.0,
    rotary: float = 1.0,
    embedding_multiplier: float = 12.0,
    residual_multiplier: float = 0.22,
    attention_multiplier: float = 0.0625,
    logits_scaling: float = 8.0,
    rms_eps: float = 1e-5,
    max_len: int = 131072,
    seq: int = 32,
    max_new_tokens: int = 16,
    param_dtype: str = "bfloat16",
    **unknown,
) -> ModelSpec:
    """The generative tier's third decoder family (models/hybrid_decoder.py,
    Granite 4.0-H, Nemotron-H and Qwen3-Next): Mamba-2 layers with a recurrent state
    (``ssm_groups`` B/C groups), grouped-query attention without positions,
    Granite's four multipliers (a model without them passes ones), a tied
    head unless ``untied``. ``attn_layers`` says which layer is what, one of
    three ways: comma-separated indices (Granite: those layers attend, every
    other is Mamba-2, and a dense gated-SiLU MLP of width ``ffn`` pairs with
    every mixer), or the published ``hybrid_override_pattern`` (Nemotron-H: a
    character a layer, ``M`` Mamba-2, ``*`` attention, ``E`` an expert
    layer; a layer is that ONE sublayer), or a pattern of ``D`` and ``G``
    (the third shape, ``qwen3_next``: every layer a mixer AND an expert layer
    under zero-centred norms; ``D`` a gated delta-rule mixer of
    ``gdn_key_heads`` / ``gdn_value_heads`` heads of ``gdn_key_dim`` /
    ``gdn_value_dim`` behind a convolution of ``ssm_conv`` taps, whose state
    is a float32 matrix a value head; ``G`` attention with a sigmoid output
    gate, q and k normed a head and rotary (``rope_theta``) on the first
    ``rotary`` of the head; its expert layer the softmax top-k gate over
    gated-SiLU experts plus a sigmoid-gated shared expert; ``qwen3_next``
    publishes ``full_attention_interval`` n: every n-th character ``G``, the
    others ``D``). An ``E`` expert layer is a shared expert
    of width ``shared_ffn`` plus the top ``experts_per_tok`` of ``experts``
    routed ones of width ``ffn`` under the bias-selected sigmoid gate times
    ``routed_scale``, squared-ReLU and ungated; ``experts_held`` (0: all)
    from ``first_expert`` is one chip's share of an expert-parallel
    deployment: the router keeps ``experts`` outputs and a pick that lands
    on an absent expert adds nothing. The parameters are a published
    config's keys; ``vocab`` the rows of the vocabulary held. Weights are
    drawn on the device from ``seed`` in ``param_dtype``. It serves through
    ``tpu.decode_slots``: the recurrent state lives in state rows beside the
    KV pages, sized from ``decode_slots`` and ``decode_prefix_slots``;
    without it the fused fallback decodes whole batches greedily through the
    same forward. Speculation, tensor-parallel decode, the int8 pool, the KV
    tiers and prefix export are not served for it. A parameter it does not
    know is refused by name: a configuration written for a later tree fails
    with a sentence here and does not build another model."""
    import jax.numpy as jnp

    from seldon_core_tpu.graph.spec import bool_param
    from seldon_core_tpu.models.hybrid_decoder import (
        HybridDecoderConfig,
        hybrid_family,
        init_hybrid_decoder,
    )

    if unknown:
        raise ValueError(
            f"zoo://hybrid_decoder does not know the parameter(s) {sorted(unknown)}: "
            "it builds what it is told, not a model without them"
        )
    if seq + max_new_tokens > max_len:
        raise ValueError(
            f"seq={seq} + max_new_tokens={max_new_tokens} exceeds max_len={max_len}"
        )
    named = str(attn_layers).strip()
    by_index = all(i.strip().isdigit() for i in named.split(",") if i.strip())
    cfg = HybridDecoderConfig(
        vocab=int(vocab), hidden=int(hidden), layers=int(layers),
        attn_layers=tuple(int(i) for i in named.split(",") if i.strip()) if by_index else (),
        pattern="" if by_index else named,
        heads=int(heads), kv_heads=int(kv_heads), head_dim=int(head_dim), ffn=int(ffn),
        ssm_heads=int(ssm_heads), ssm_head_dim=int(ssm_head_dim), ssm_state=int(ssm_state),
        ssm_conv=int(ssm_conv), ssm_groups=int(ssm_groups), untied=bool_param(untied), experts=int(experts),
        experts_held=int(experts_held) or int(experts), first_expert=int(first_expert),
        experts_per_tok=int(experts_per_tok), shared_ffn=int(shared_ffn), routed_scale=float(routed_scale),
        gdn_key_heads=int(gdn_key_heads), gdn_value_heads=int(gdn_value_heads), gdn_key_dim=int(gdn_key_dim),
        gdn_value_dim=int(gdn_value_dim), rope_theta=float(rope_theta), rotary=float(rotary),
        embedding_multiplier=float(embedding_multiplier),
        residual_multiplier=float(residual_multiplier),
        attention_multiplier=float(attention_multiplier),
        logits_scaling=float(logits_scaling), rms_eps=float(rms_eps), max_len=int(max_len),
    )
    family = hybrid_family(cfg)
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[str(param_dtype)]
    max_new = int(max_new_tokens)
    return ModelSpec(
        lambda p, x: family.generate(p, x, max_new),
        init_hybrid_decoder(cfg, int(seed), dtype),
        (int(seq),),
        (),
        int_inputs="ids",
        generative={"seq": int(seq), "max_new_tokens": max_new, "family": family},
    )


@register_model("mla_decoder")
def build_mla_decoder(
    seed: int = 0,
    vocab: int = 512,
    hidden: int = 64,
    layers: int = 3,
    heads: int = 4,
    q_rank: int = 24,
    kv_rank: int = 16,
    nope_dim: int = 8,
    rope_dim: int = 4,
    v_dim: int = 8,
    dense_layers: int = 1,
    dense_ffn: int = 96,
    ffn: int = 32,
    experts: int = 16,
    experts_held: int = 0,
    first_expert: int = 0,
    experts_per_tok: int = 4,
    n_group: int = 4,
    topk_group: int = 2,
    routed_scale: float = 2.5,
    rope_theta: float = 10000.0,
    yarn_factor: float = 32.0,
    yarn_original: int = 16,
    yarn_beta_fast: float = 32.0,
    yarn_beta_slow: float = 1.0,
    mscale_all_dim: float = 1.0,
    rms_eps: float = 1e-6,
    max_len: int = 131072,
    gate_bias: bool = False,
    hc_mult: int = 1,
    hc_sinkhorn_iters: int = 20,
    hc_eps: float = 1e-6,
    hc_res_clamp: float = 30.0,
    seq: int = 32,
    max_new_tokens: int = 16,
    param_dtype: str = "bfloat16",
    **_,
) -> ModelSpec:
    """The generative tier's fourth decoder family (models/mla_decoder.py,
    the DeepSeek-V3 block): multi-head latent attention whose cache row is
    one ``kv_rank + rope_dim`` latent a token (the one-plane page kind),
    ``dense_layers`` leading dense layers, then a shared expert plus
    ``experts_per_tok`` of ``experts`` routed ones under the sigmoid,
    group-limited gate. The parameters are a published config's keys; ``ffn``
    is ONE expert's width, ``vocab`` the rows of the vocabulary held.
    ``experts_held`` (0: all) from ``first_expert`` is one chip's share of an
    expert-parallel deployment: the router keeps ``experts`` outputs and a
    pick that lands on an absent expert adds nothing. ``gate_bias`` (with
    ``n_group`` 0 and ``topk_group`` 0: no groups) is the gate whose
    per-expert bias selects and does not weigh (``topk_method: noaux_tc``).
    ``hc_mult`` > 1 makes the residual path that many streams wide
    (manifold-constrained hyper-connections, ops/mhc.py: ``hc_sinkhorn_iters``
    Sinkhorn-Knopp iterations with ``hc_eps`` in their denominators over
    logits clamped to +-``hc_res_clamp``); 1 is ``x + F(norm(x))``. It serves
    through ``tpu.decode_slots``; without it the fused fallback decodes whole
    batches greedily through the same paged forward. Speculation,
    tensor-parallel decode, the int8 pool, the KV tiers and prefix export are
    not served for it."""
    import jax.numpy as jnp

    from seldon_core_tpu.graph.spec import bool_param
    from seldon_core_tpu.models.mla_decoder import MLADecoderConfig, init_mla_decoder, mla_family

    if seq + max_new_tokens > max_len:
        raise ValueError(
            f"seq={seq} + max_new_tokens={max_new_tokens} exceeds max_len={max_len}"
        )
    cfg = MLADecoderConfig(
        vocab=int(vocab), hidden=int(hidden), layers=int(layers), heads=int(heads), q_rank=int(q_rank),
        kv_rank=int(kv_rank), nope_dim=int(nope_dim), rope_dim=int(rope_dim), v_dim=int(v_dim),
        dense_layers=int(dense_layers), dense_ffn=int(dense_ffn), ffn=int(ffn), experts=int(experts),
        experts_held=int(experts_held) or int(experts), first_expert=int(first_expert),
        experts_per_tok=int(experts_per_tok), n_group=int(n_group), topk_group=int(topk_group),
        routed_scale=float(routed_scale), rope_theta=float(rope_theta), yarn_factor=float(yarn_factor),
        yarn_original=int(yarn_original), yarn_beta_fast=float(yarn_beta_fast),
        yarn_beta_slow=float(yarn_beta_slow), mscale_all_dim=float(mscale_all_dim), rms_eps=float(rms_eps),
        max_len=int(max_len), gate_bias=bool_param(gate_bias), hc_mult=int(hc_mult),
        hc_sinkhorn_iters=int(hc_sinkhorn_iters), hc_eps=float(hc_eps), hc_res_clamp=float(hc_res_clamp),
    )
    family = mla_family(cfg)
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[str(param_dtype)]
    max_new = int(max_new_tokens)
    return ModelSpec(
        lambda p, x: family.generate(p, x, max_new),
        init_mla_decoder(cfg, int(seed), dtype),
        (int(seq),),
        (),
        int_inputs="ids",
        generative={"seq": int(seq), "max_new_tokens": max_new, "family": family},
    )


@register_model("conv_decoder")
def build_conv_decoder(
    seed: int = 0,
    vocab: int = 512,
    hidden: int = 64,
    layers: int = 8,
    attn_layers: str = "2,6",
    heads: int = 4,
    kv_heads: int = 2,
    head_dim: int = 16,
    conv_taps: int = 3,
    dense_layers: int = 2,
    dense_ffn: int = 128,
    ffn: int = 32,
    experts: int = 16,
    experts_held: int = 0,
    first_expert: int = 0,
    experts_per_tok: int = 4,
    routed_scale: float = 1.0,
    rope_theta: float = 1000000.0,
    rms_eps: float = 1e-5,
    max_len: int = 128000,
    seq: int = 32,
    max_new_tokens: int = 16,
    param_dtype: str = "bfloat16",
    **_,
) -> ModelSpec:
    """The generative tier's fifth decoder family (models/conv_decoder.py,
    the LFM2 block): gated short-convolution layers whose cache is
    ``conv_taps - 1`` rows a layer, rotary grouped-query attention with an
    RMS norm on each head's q and k in the layers ``attn_layers`` names
    (comma-separated indices), ``dense_layers`` leading dense MLPs, then
    ``experts_per_tok`` of ``experts`` routed experts under a sigmoid gate
    whose bias selects and does not weigh; a tied head. The parameters are
    a published config's keys; ``ffn`` is ONE expert's width.
    ``experts_held`` (0: all) from ``first_expert`` is one chip's share of an
    expert-parallel deployment: the router keeps ``experts`` outputs and a
    pick that lands on an absent expert adds nothing. It serves through
    ``tpu.decode_slots``: the conv cache lives in state rows beside the KV
    pages, sized from ``decode_slots`` and ``decode_prefix_slots``; without
    it the fused fallback decodes whole batches greedily through the same
    forward. Speculation, tensor-parallel decode, the step attention kernel,
    the int8 pool, the KV tiers and prefix export are not served for it."""
    import jax.numpy as jnp

    from seldon_core_tpu.models.conv_decoder import ConvDecoderConfig, conv_family, init_conv_decoder

    if seq + max_new_tokens > max_len:
        raise ValueError(
            f"seq={seq} + max_new_tokens={max_new_tokens} exceeds max_len={max_len}"
        )
    cfg = ConvDecoderConfig(
        vocab=int(vocab), hidden=int(hidden), layers=int(layers),
        attn_layers=tuple(int(i) for i in str(attn_layers).split(",") if i.strip()),
        heads=int(heads), kv_heads=int(kv_heads), head_dim=int(head_dim), conv_taps=int(conv_taps),
        dense_layers=int(dense_layers), dense_ffn=int(dense_ffn), ffn=int(ffn), experts=int(experts),
        experts_held=int(experts_held) or int(experts), first_expert=int(first_expert),
        experts_per_tok=int(experts_per_tok), routed_scale=float(routed_scale), rope_theta=float(rope_theta),
        rms_eps=float(rms_eps), max_len=int(max_len),
    )
    family = conv_family(cfg)
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[str(param_dtype)]
    max_new = int(max_new_tokens)
    return ModelSpec(
        lambda p, x: family.generate(p, x, max_new),
        init_conv_decoder(cfg, int(seed), dtype),
        (int(seq),),
        (),
        int_inputs="ids",
        generative={"seq": int(seq), "max_new_tokens": max_new, "family": family},
    )


@register_model("draft")
def build_draft(
    seed: int = 0,
    vocab: int = 512,
    hidden: int = 128,
    layers: int = 1,
    ffn: int = 256,
    max_len: int = 128,
    resid_scale: float = 1.0,
    seq: int = 32,
    max_new_tokens: int = 16,
    distilled: str = "",
    features: int = 0,
    **_,
) -> ModelSpec:
    """Draft decoder for speculative decoding (tpu.decode_draft_model):
    the same GPT-style architecture as tiny_gpt, defaulting to ONE layer.
    Because init_decoder draws weights positionally from a single seeded
    generator, a draft built with the target's seed/vocab/hidden/ffn/
    max_len (the decode scheduler injects vocab and max_len from the
    target automatically) IS the target's embeddings + leading layers
    verbatim — early-exit self-speculation, the untrained-weights
    analogue of a distilled draft. With the default depth-unscaled init
    the truncated layers dominate the logits and the accept rate is low;
    builds meant as drafts should set resid_scale (on BOTH target and
    draft) so the shared prefix carries the prediction — see
    docs/generative.md. Serves standalone like any other zoo entry —
    it IS tiny_gpt with a 1-layer default, so it delegates (any change to
    the target's ModelSpec wiring automatically carries to the draft,
    which the truncation property depends on).

    ``distilled=/path/to.npz`` refills the build's weights from a
    KL-distillation checkpoint (training/distill_draft.py) trained
    against the target — acceptance from LEARNING the target's
    conditionals instead of seed-shared layer truncation alone. The
    checkpoint must match this build's geometry exactly (the loader
    asserts every leaf's shape), so the URI still carries the full
    architecture and ``distilled`` only swaps the values.

    ``features=1`` builds the EAGLE-style feature-draft HEAD instead
    (models/decoder.init_feature_draft): one transformer layer whose
    input fuses the TARGET's last hidden state with the token embedding.
    ``hidden`` must equal the target's (the decode scheduler injects it
    from the target automatically); ``layers``/``resid_scale`` do not
    apply. A feature head is not a standalone decoder — it serves ONLY
    through ``tpu.decode_draft_model``, and its apply raises to say so.
    Distill it with ``python -m seldon_core_tpu.training.distill_draft
    --features`` and load via
    ``zoo://draft?features=1&distilled=/path.npz``."""
    if features:
        from seldon_core_tpu.models.decoder import init_feature_draft

        params = init_feature_draft(
            seed, vocab=vocab, hidden=hidden, ffn=ffn, max_len=max_len
        )
        if distilled:
            from seldon_core_tpu.training.distill_draft import load_draft_checkpoint

            params = load_draft_checkpoint(str(distilled), params)
        return ModelSpec(
            _feature_draft_apply,
            params,
            (seq,),
            (),
            int_inputs="ids",
        )
    ms = build_tiny_gpt(
        seed=seed, vocab=vocab, hidden=hidden, layers=layers, ffn=ffn,
        max_len=max_len, seq=seq, max_new_tokens=max_new_tokens,
        resid_scale=resid_scale,
    )
    if distilled:
        from seldon_core_tpu.training.distill_draft import load_draft_checkpoint

        ms.params = load_draft_checkpoint(str(distilled), ms.params)
    return ms


def _feature_draft_apply(p, x):
    raise ValueError(
        "a feature-draft head (zoo://draft?features=1) conditions on the "
        "target's hidden states and cannot serve standalone — point "
        "tpu.decode_draft_model at it instead"
    )


def _apply_tiny_gpt(p, x, *, max_new_tokens: int):
    from seldon_core_tpu.models.decoder import generate

    return generate(p, x, max_new_tokens)


def _register_heavy_models() -> None:
    """resnet50 / bert_base import lazily — they pull flax."""
    from seldon_core_tpu.models import resnet as _resnet  # noqa: F401
    from seldon_core_tpu.models import bert as _bert  # noqa: F401


# ------------------------------------------------------------- unit factory


def _runtime_from_modelspec(ms: ModelSpec, tpu_cfg, mesh=None) -> ModelRuntime:
    import jax.numpy as jnp

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[
        getattr(tpu_cfg, "dtype", "float32")
    ]
    apply_fn = ms.apply_fn
    if mesh is not None and ms.apply_factory is not None:
        apply_fn = ms.apply_factory(mesh)
    rt = ModelRuntime(
        apply_fn,
        ms.params,
        mesh=mesh,
        param_pspecs=ms.param_pspecs,
        buckets=tuple(getattr(tpu_cfg, "batch_buckets", ()) or ()),
        max_batch=getattr(tpu_cfg, "max_batch", 64),
        dtype=dtype,
        class_names=ms.class_names,
        donate=getattr(tpu_cfg, "donate_input", True),
        int_inputs=ms.int_inputs,
        weight_quant=getattr(tpu_cfg, "weight_quant", ""),
        offload_compute=getattr(tpu_cfg, "offload_compute", "auto"),
    )
    rt.feature_shape = ms.feature_shape
    rt.generative = ms.generative
    return rt


def _parse_zoo_uri(uri: str) -> tuple[str, dict]:
    parsed = urllib.parse.urlparse(uri)
    name = parsed.netloc or parsed.path.lstrip("/")
    kwargs: dict[str, Any] = {}
    for k, v in urllib.parse.parse_qsl(parsed.query):
        try:
            kwargs[k] = int(v)
        except ValueError:
            try:
                kwargs[k] = float(v)
            except ValueError:
                kwargs[k] = v
    return name, kwargs


def build_runtime_from_uri(uri: str, tpu_cfg, mesh=None, extra_params: dict | None = None) -> ModelRuntime:
    """``extra_params``: unit parameters beyond model/model_uri (typed by
    the CR) — merged as builder kwargs under the URI's own query string, so
    ``model_uri`` deployments get the same knobs (seq_parallel etc.) as the
    ``model`` shorthand."""
    extra_params = extra_params or {}
    if uri.startswith("zoo://"):
        name, kwargs = _parse_zoo_uri(uri)
        kwargs = {**extra_params, **kwargs}  # the uri's own query wins
        ms = get_model(name, **kwargs)  # lazy-registers heavy models itself
        return _runtime_from_modelspec(ms, tpu_cfg, mesh)
    if uri.startswith("file://"):
        if extra_params:
            import logging

            logging.getLogger(__name__).warning(
                "file:// checkpoints ignore extra unit parameters %s (the "
                "builder and its kwargs are baked into the checkpoint)",
                sorted(extra_params),
            )
        from seldon_core_tpu.persistence.checkpoint import restore_model

        ms = restore_model(uri[len("file://") :])
        return _runtime_from_modelspec(ms, tpu_cfg, mesh)
    if uri.startswith("hf-bert://"):
        # a LOCAL Hugging Face BertForSequenceClassification checkpoint dir
        # (from save_pretrained): trained torch weights map into the
        # jit-compiled BERT (models/hf_import.py) — torch leaves the loop
        import transformers

        from seldon_core_tpu.models.bert import (
            _apply_for_kernel,
            _bert_apply_factory,
            _infer_heads,
            bert_pspecs,
        )
        from seldon_core_tpu.models.hf_import import bert_params_from_hf

        rest = uri[len("hf-bert://") :]
        path, _, query = rest.partition("?")
        kwargs = {
            **{k: str(v) for k, v in extra_params.items()},
            **dict(urllib.parse.parse_qsl(query)),  # the uri's query wins
        }
        hf = transformers.BertForSequenceClassification.from_pretrained(path)
        params = bert_params_from_hf(hf.eval())
        id2label = getattr(hf.config, "id2label", None) or {}
        class_names = tuple(
            str(id2label[i]) for i in sorted(id2label)
        ) or tuple(f"class_{i}" for i in range(params["head"]["w"].shape[1]))
        seq = int(kwargs.get("seq", 128))
        max_len = int(params["pos_emb"].shape[0])
        if seq > max_len:
            raise ValueError(
                f"hf-bert seq={seq} exceeds the checkpoint's "
                f"max_position_embeddings={max_len} — failing fast instead "
                "of an opaque XLA broadcast error at warmup"
            )
        from functools import partial

        ms = ModelSpec(
            _apply_for_kernel(str(kwargs.get("attn_kernel", "auto"))),
            params,
            (seq,),
            class_names,
            param_pspecs=bert_pspecs(params),
            # same mesh-aware apply as zoo bert builders: a 'seq' mesh axis
            # turns on sequence parallelism for imported checkpoints too,
            # with the same ring|ulysses strategy knob (?seq_parallel=) and
            # attention-kernel knob (?attn_kernel=auto|pallas|blockwise)
            apply_factory=partial(
                _bert_apply_factory,
                seq_parallel=str(kwargs.get("seq_parallel", "ring")),
                num_heads=_infer_heads(params),
                attn_kernel=str(kwargs.get("attn_kernel", "auto")),
            ),
            int_inputs="ids",
        )
        return _runtime_from_modelspec(ms, tpu_cfg, mesh)
    raise ValueError(f"unsupported model_uri '{uri}'")


def make_jax_model_unit(spec: PredictiveUnit, context: dict) -> JaxModelUnit:
    """Factory for implementation=JAX_MODEL units: model name/uri comes from a
    unit parameter ``model_uri`` (or ``model`` shorthand)."""
    from seldon_core_tpu.graph.spec import parameters_dict

    params = parameters_dict(spec.parameters)
    uri = params.get("model_uri") or (
        f"zoo://{params['model']}" if "model" in params else None
    )
    # every OTHER unit parameter forwards as a builder kwarg, so CR
    # parameters like seq_parallel/num_classes reach the builder on every
    # URI scheme instead of being silently dropped
    extra = {
        k: v for k, v in params.items() if k not in ("model", "model_uri", "finetune")
    }
    if uri is None:
        container = (context.get("containers") or {}).get(spec.name)
        uri = getattr(container, "model_uri", "") or None
    if uri is None:
        raise ValueError(f"JAX_MODEL unit '{spec.name}' needs a model_uri parameter")
    from seldon_core_tpu.graph.spec import bool_param

    finetune = bool_param(params.get("finetune", False))
    # invalid config fails BEFORE any params are built or device_put —
    # admission-protected HBM must not be touched for a doomed deployment
    if finetune and getattr(context.get("tpu"), "weight_quant", "") == "int8":
        raise ValueError(
            f"unit '{spec.name}': finetune=true cannot combine with "
            "tpu.weight_quant='int8' — gradients over int8 weight payloads "
            "are undefined and updates would corrupt the frozen per-channel "
            "scales; serve the finetuning replica unquantized"
        )
    runtime = build_runtime_from_uri(
        uri, context.get("tpu"), context.get("mesh"), extra_params=extra
    )

    if finetune:
        from seldon_core_tpu.graph.spec import TYPE_METHODS, PredictiveUnitMethod
        from seldon_core_tpu.models.online import OnlineFinetuneModelUnit

        effective = tuple(spec.methods) or TYPE_METHODS.get(spec.type, ())
        if PredictiveUnitMethod.SEND_FEEDBACK not in effective:
            import logging

            logging.getLogger(__name__).warning(
                "finetune=true on unit '%s' but SEND_FEEDBACK is not in its "
                "methods — feedback will never reach it (run the spec "
                "through defaulting, or add the method explicitly)",
                spec.name,
            )
        return OnlineFinetuneModelUnit(spec, runtime)
    return JaxModelUnit(spec, runtime)


def unit_from_container(spec: PredictiveUnit, container: ContainerSpec, context: dict):
    runtime = build_runtime_from_uri(
        container.model_uri, context.get("tpu"), context.get("mesh")
    )
    return JaxModelUnit(spec, runtime)
