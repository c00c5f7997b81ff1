"""BERT-base encoder in pure JAX with tensor-parallel PartitionSpecs.

Parity role: BASELINE.json's "Full DAG: input Transformer -> epsilon-greedy
Router -> BERT-base models -> Combiner" config. The reference would run each
BERT as its own GPU container; here it is a params pytree whose attention/MLP
weights carry PartitionSpecs so ModelRuntime can shard them over the mesh
"model" axis (Megatron-style TP: qkv column-split, output row-split — the
all-reduce after the row-split matmul is inserted by XLA from the shardings,
never hand-written).

Serving contract: apply(params, x) where x is int token ids [batch, seq]
(arriving as the SeldonMessage float tensor; cast inside — TPU serving keeps
one input dtype at the edge). Output: [batch, num_classes] probabilities.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from seldon_core_tpu.models.zoo import ModelSpec, register_model


# Host-side numpy init (see models/resnet.py): one device_put instead of one
# compiled rng program per tensor.
import numpy as np


def _dense_init(rng: np.random.Generator, n_in, n_out):
    scale = (2.0 / (n_in + n_out)) ** 0.5
    return {
        "w": (rng.standard_normal((n_in, n_out)) * scale).astype(np.float32),
        "b": np.zeros((n_out,), np.float32),
    }


def _ln_init(d):
    return {"scale": np.ones((d,), np.float32), "bias": np.zeros((d,), np.float32)}


def _ln(p, x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype))
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _layer_init(rng, hidden, ffn):
    return {
        "qkv": _dense_init(rng, hidden, 3 * hidden),
        "attn_out": _dense_init(rng, hidden, hidden),
        "ln1": _ln_init(hidden),
        "mlp_in": _dense_init(rng, hidden, ffn),
        "mlp_out": _dense_init(rng, ffn, hidden),
        "ln2": _ln_init(hidden),
    }


def _default_attention(q, k, v):
    """seq-length-adaptive: dense einsum below FLASH_MIN_SEQ; above it
    blockwise, and from PALLAS_MIN_SEQ the COMPILED Pallas flash kernel
    (ops/pallas_flash — VMEM-streamed online softmax on the MXU) on every
    backend but the CPU, which has no Mosaic and keeps blockwise. The
    kernel needs the KV axis to divide its 128 block (no in-kernel
    masking); shapes are static at trace time so this resolves during
    compilation, never per request. The length policy constants live in
    ops/attention so seq-parallel local bodies can't drift from them."""
    from seldon_core_tpu.ops.attention import FLASH_MIN_SEQ, PALLAS_MIN_SEQ

    if q.shape[2] >= FLASH_MIN_SEQ:
        if (
            q.shape[2] >= PALLAS_MIN_SEQ
            and jax.default_backend() != "cpu"
            and k.shape[2] % 128 == 0
        ):
            from seldon_core_tpu.ops.pallas_flash import flash_attention

            return flash_attention(q, k, v)
        from seldon_core_tpu.ops.attention import blockwise_attention

        return blockwise_attention(q, k, v, block_size=512)
    from seldon_core_tpu.ops.attention import naive_attention

    return naive_attention(q, k, v)


def _pallas_attention(q, k, v):
    """Forced-Pallas impl (attn_kernel=pallas): compiled with Mosaic on an
    accelerator; on the CPU backend it asks for interpret mode, so a CI
    deployment on the CPU mesh exercises the same kernel code path. Falls
    back to blockwise only for a static KV length the kernel's block sizes
    can't tile, mirroring _default_attention. Short sequences (<= one KV
    block) tile trivially — _kv_block caps the block at the sequence."""
    from seldon_core_tpu.ops.pallas_flash import DEFAULT_BLOCK_K, flash_attention

    sk = k.shape[2]
    # sublane alignment (16 for bf16) + either the 128-lane tiling or a
    # single-KV-block fit (the kernel caps its block at the sequence)
    if sk % 16 == 0 and (sk % 128 == 0 or sk <= DEFAULT_BLOCK_K):
        return flash_attention(
            q, k, v, interpret=jax.default_backend() == "cpu"
        )
    from seldon_core_tpu.ops.attention import blockwise_attention

    return blockwise_attention(q, k, v, block_size=512)


def _blockwise_only_attention(q, k, v):
    """attn_kernel=blockwise: the pure-JAX path at any length — the control
    leg the bench compares the Pallas kernel against."""
    from seldon_core_tpu.ops.attention import blockwise_attention

    return blockwise_attention(q, k, v, block_size=512)


# attn_kernel knob -> attention impl for the NON-seq-parallel path. Values
# are module-level functions (not per-build closures) so two builds of the
# same config share apply-fn identity — what lets engine/fused.py stack a
# homogeneous ensemble and vmap once.
_KERNEL_IMPLS = {
    "auto": None,  # _default_attention policy
    "pallas": _pallas_attention,
    "blockwise": _blockwise_only_attention,
}


def make_ring_attention(mesh, seq_axis: str = "seq"):
    """Sequence-parallel attention impl for serving long contexts over a
    mesh: K/V shards rotate over ICI (ops/ring_attention.py) so each device
    holds O(seq/ring) of the sequence. Plug into build_bert_* via
    attn_impl."""

    def impl(q, k, v):
        ring = mesh.shape[seq_axis]
        if q.shape[2] % ring != 0:
            # shapes are static at trace time: lengths the ring can't split
            # evenly fall back to the length-adaptive single-device path
            # instead of erroring the request
            return _default_attention(q, k, v)
        from seldon_core_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, mesh, seq_axis=seq_axis)

    return impl


def make_ulysses_attention_impl(mesh, seq_axis: str = "seq"):
    """The all-to-all (Ulysses-style) seq-parallel twin of
    make_ring_attention: heads scatter / sequence gathers for the attention
    op, then reverses (ops/ulysses.py). Same graceful fallback to the
    single-device path when shapes don't divide the mesh axis."""

    def impl(q, k, v):
        n = mesh.shape[seq_axis]
        if q.shape[2] % n != 0 or q.shape[1] % n != 0:
            return _default_attention(q, k, v)
        from seldon_core_tpu.ops.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, mesh, seq_axis=seq_axis)

    return impl


def _attention(p, x, num_heads, attn_impl=None):
    b, s, d = x.shape
    head = d // num_heads
    qkv = x @ p["qkv"]["w"].astype(x.dtype) + p["qkv"]["b"].astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, num_heads, head).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    ctx = (attn_impl or _default_attention)(q, k, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, d)
    return ctx @ p["attn_out"]["w"].astype(x.dtype) + p["attn_out"]["b"].astype(x.dtype)


def _layer_apply(p, x, num_heads, attn_impl=None):
    x = _ln(p["ln1"], x + _attention(p, x, num_heads, attn_impl))
    # erf gelu, not the tanh approximation: BERT (paper and HF) uses the
    # exact form, so imported checkpoints reproduce their torch logits
    h = jax.nn.gelu(
        x @ p["mlp_in"]["w"].astype(x.dtype) + p["mlp_in"]["b"].astype(x.dtype),
        approximate=False,
    )
    h = h @ p["mlp_out"]["w"].astype(x.dtype) + p["mlp_out"]["b"].astype(x.dtype)
    return _ln(p["ln2"], x + h)


def init_bert(
    seed: int = 0,
    vocab: int = 30522,
    hidden: int = 768,
    layers: int = 12,
    ffn: int = 3072,
    max_len: int = 512,
    num_classes: int = 2,
) -> dict:
    """Head count is hidden//64 by convention (head_dim 64, BERT-base
    geometry) — see _infer_heads; it is derived from the params at apply
    time, never stored."""
    rng = np.random.default_rng(seed)
    params: dict[str, Any] = {
        "tok_emb": (rng.standard_normal((vocab, hidden)) * 0.02).astype(np.float32),
        "pos_emb": (rng.standard_normal((max_len, hidden)) * 0.02).astype(np.float32),
        "ln_emb": _ln_init(hidden),
        "layers": [_layer_init(rng, hidden, ffn) for _ in range(layers)],
        "head": _dense_init(rng, hidden, num_classes),
    }
    return params


def bert_pspecs(params: dict) -> dict:
    """Megatron-style TP over the mesh 'model' axis:
    qkv / mlp_in column-parallel, attn_out / mlp_out row-parallel;
    embeddings + layernorms + head replicated. XLA inserts the row-parallel
    all-reduce from these shardings."""

    def layer_spec(_):
        return {
            "qkv": {"w": P(None, "model"), "b": P("model")},
            "attn_out": {"w": P("model", None), "b": P()},
            "ln1": {"scale": P(), "bias": P()},
            "mlp_in": {"w": P(None, "model"), "b": P("model")},
            "mlp_out": {"w": P("model", None), "b": P()},
            "ln2": {"scale": P(), "bias": P()},
        }

    specs = {
        "tok_emb": P(),
        "pos_emb": P(),
        "ln_emb": {"scale": P(), "bias": P()},
        "layers": [layer_spec(l) for l in params["layers"]],
        "head": {"w": P(), "b": P()},
    }
    if "pooler" in params:  # imported checkpoints carry the HF tanh pooler
        specs["pooler"] = {"w": P(), "b": P()}
    return specs


def bert_logits(params: dict, x: jax.Array, attn_impl=None) -> jax.Array:
    """x: token ids [batch, seq] (any numeric dtype) -> logits [batch, classes]."""
    ids = x.astype(jnp.int32)
    num_heads = _infer_heads(params)
    compute_dtype = params["tok_emb"].dtype
    h = params["tok_emb"][ids] + params["pos_emb"][: ids.shape[1]][None, :, :]
    h = _ln(params["ln_emb"], h.astype(compute_dtype))
    for lp in params["layers"]:
        h = _layer_apply(lp, h, num_heads, attn_impl)
    cls = h[:, 0, :]  # [CLS] pooling
    pooler = params.get("pooler")
    if pooler is not None:
        # HF/original BERT classification head: tanh pooler before the
        # classifier (BertPooler) — present only on imported checkpoints,
        # init_bert's native head classifies [CLS] directly
        cls = jnp.tanh(
            cls @ pooler["w"].astype(cls.dtype) + pooler["b"].astype(cls.dtype)
        )
    return cls @ params["head"]["w"].astype(cls.dtype) + params["head"]["b"].astype(
        cls.dtype
    )


def apply_bert(params: dict, x: jax.Array) -> jax.Array:
    """Serving entrypoint: softmax probabilities."""
    return jax.nn.softmax(bert_logits(params, x), axis=-1)


def make_apply_bert(attn_impl):
    """apply_bert with a custom attention impl (e.g. make_ring_attention)."""

    def apply(params, x):
        return jax.nn.softmax(bert_logits(params, x, attn_impl), axis=-1)

    return apply


def _infer_heads(params: dict) -> int:
    hidden = params["layers"][0]["qkv"]["w"].shape[0]
    return max(1, hidden // 64)


# memoized per (mesh, strategy) / per kernel: fused.py detects homogeneous
# ensembles by apply-fn IDENTITY, so two builds of the same config must get
# the same function object
_RING_APPLY_CACHE: dict = {}
_KERNEL_APPLY_CACHE: dict = {}


def _apply_for_kernel(attn_kernel: str):
    """Single-device/no-seq-mesh apply for an attn_kernel knob value."""
    if attn_kernel not in _KERNEL_IMPLS:
        raise ValueError(
            f"attn_kernel must be one of {sorted(_KERNEL_IMPLS)}, got "
            f"{attn_kernel!r}"
        )
    if attn_kernel == "auto":
        return apply_bert
    fn = _KERNEL_APPLY_CACHE.get(attn_kernel)
    if fn is None:
        fn = make_apply_bert(_KERNEL_IMPLS[attn_kernel])
        _KERNEL_APPLY_CACHE[attn_kernel] = fn
    return fn


def _bert_apply_factory(
    mesh,
    seq_parallel: str = "ring",
    num_heads: int | None = None,
    attn_kernel: str = "auto",
):
    """Mesh-aware serving apply: a mesh with a "seq" axis turns on sequence
    parallelism automatically — ring attention by default, or the
    all-to-all (Ulysses) strategy when the deployment asks for it
    (``seq_parallel`` model parameter); otherwise the default
    length-adaptive attention runs under whatever data/TP sharding the mesh
    provides.

    ``num_heads`` (static model config, known at build time) lets ulysses
    fail the DEPLOYMENT when heads don't divide the seq axis — heads are
    the all-to-all resharding currency, and silently serving unsharded
    attention would defeat the knob exactly at the long contexts that
    motivated it. (Ring's seq-length fallback stays dynamic: request
    lengths vary per bucket and must not error.)"""
    if mesh is not None and "seq" in getattr(mesh, "shape", {}):
        if seq_parallel == "ulysses" and num_heads is not None:
            n = int(mesh.shape["seq"])
            if num_heads % n != 0:
                raise ValueError(
                    f"seq_parallel=ulysses needs attention heads divisible "
                    f"by the seq-axis size: {num_heads} heads vs seq={n} — "
                    "use a smaller seq axis or seq_parallel=ring"
                )
        key = (mesh, seq_parallel)
        fn = _RING_APPLY_CACHE.get(key)
        if fn is None:
            if seq_parallel == "ulysses":
                impl = make_ulysses_attention_impl(mesh)
            elif seq_parallel == "ring":
                impl = make_ring_attention(mesh)
            else:
                raise ValueError(
                    f"seq_parallel must be 'ring' or 'ulysses', got {seq_parallel!r}"
                )
            fn = make_apply_bert(impl)
            _RING_APPLY_CACHE[key] = fn
        return fn
    return _apply_for_kernel(attn_kernel)


@register_model("bert_base")
def build_bert_base(
    seed: int = 0,
    num_classes: int = 2,
    max_len: int = 512,
    seq: int = 128,
    seq_parallel: str = "ring",
    attn_kernel: str = "auto",
    **_,
) -> ModelSpec:
    from functools import partial

    if seq > max_len:
        raise ValueError(
            f"seq={seq} exceeds max_len={max_len} (position table size) — "
            "raise max_len for long-context deployments"
        )
    params = init_bert(seed, num_classes=num_classes, max_len=max_len)
    return ModelSpec(
        # attn_kernel is a deployment knob (auto|pallas|blockwise): auto
        # routes long sequences to the compiled Pallas flash kernel off
        # the CPU backend and blockwise on it; pallas forces the kernel
        # (interpret mode on the CPU backend) so CI serving configs reach it
        _apply_for_kernel(attn_kernel),
        params,
        (seq,),  # serving seq length (buckets handle the batch axis)
        tuple(f"class_{i}" for i in range(num_classes)),
        param_pspecs=bert_pspecs(params),
        # seq-parallel strategy is a deployment knob: a "seq" mesh axis plus
        # model parameter seq_parallel=ring|ulysses picks the collective;
        # num_heads lets ulysses reject undivisible meshes at BUILD time
        # (derived by the SAME rule attention itself uses)
        apply_factory=partial(
            _bert_apply_factory,
            seq_parallel=seq_parallel,
            num_heads=_infer_heads(params),
            attn_kernel=attn_kernel,
        ),
        int_inputs="ids",
    )


@register_model("bert_tiny")
def build_bert_tiny(
    seed: int = 0,
    vocab: int = 1024,
    hidden: int = 128,
    layers: int = 2,
    ffn: int = 256,
    max_len: int = 128,
    num_classes: int = 2,
    seq: int = 16,
    seq_parallel: str = "ring",
    attn_kernel: str = "auto",
    **_,
) -> ModelSpec:
    """Shrunk config for tests / virtual-mesh dryruns."""
    from functools import partial

    if seq > max_len:
        raise ValueError(f"seq={seq} exceeds max_len={max_len}")
    params = init_bert(
        seed,
        vocab=vocab,
        hidden=hidden,
        layers=layers,
        ffn=ffn,
        max_len=max_len,
        num_classes=num_classes,
    )
    return ModelSpec(
        _apply_for_kernel(attn_kernel),
        params,
        (seq,),
        tuple(f"class_{i}" for i in range(num_classes)),
        param_pspecs=bert_pspecs(params),
        apply_factory=partial(
            _bert_apply_factory,
            seq_parallel=seq_parallel,
            num_heads=_infer_heads(params),
            attn_kernel=attn_kernel,
        ),
        int_inputs="ids",
    )
