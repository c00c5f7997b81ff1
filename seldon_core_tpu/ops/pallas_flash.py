"""Pallas flash-attention forward kernel for TPU.

The hot op of the BERT/long-context serving path, hand-tiled for the MXU.
Grid (batch*heads, Q blocks, KV blocks) with the KV axis innermost: each
(bh, q) pair streams KV blocks through VMEM while online-softmax statistics
(running max, denominator, f32 accumulator) live in VMEM scratch carried
across the KV grid steps — TPU grids execute sequentially, which is what
makes the carry sound. KV never resides fully in VMEM, so sequence length is
bounded by HBM, not the 16 MB VMEM (the previous full-KV design OOMed at
seq 16k).

Dots run in the input dtype (bf16 on the serving path) with f32
accumulation — the MXU's native mode and ~2x the f32 rate; softmax stats
stay f32 for exactness. Stats are stored lane-replicated ([block_q, 128])
and re-collapsed with a max over lanes, the standard Mosaic-friendly layout.

The kernel compiles under Mosaic by default. Interpreter mode exists for
the CPU backend only, and only where a caller passes ``interpret=True``
(the tests, and the serving policies' explicit CPU branch): a kernel call
that reaches an accelerator is compiled or it raises.

Speed vs the pure-JAX blockwise path: not measured on the chip, and the
kernel is in no cell of the benchmark (PERF.md section 3). The block
defaults (block_q 512 / block_k 2048) and the PALLAS_MIN_SEQ routing
threshold in ops/attention.py are carried-over design choices with no
measurement behind them. models/bert.py routes long sequences here off the CPU backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # stats are stored lane-replicated at this width
# default KV block (flash_attention block_k); the bert routing policy
# reuses it as the single-block-fit bound for non-128-multiple sequences
DEFAULT_BLOCK_K = 2048


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, n_kv: int, scale: float, block_q: int, block_k: int, causal: bool,
):
    """One (bh, q-block, kv-block) program; scratch carries across kv.

    Causal mode: KV blocks strictly above the diagonal are skipped whole
    (pl.when on the block predicate — no dots issued), the straddling
    block masks entrywise. Init/finalize stay unconditional so the scratch
    lifecycle is identical in both modes."""
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _update():
        q = q_ref[0]  # [block_q, d] input dtype
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]
        # scale in f32 then return to the input dtype: bf16 dot at MXU
        # rate, f32 accumulation via preferred_element_type
        qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
        s = jnp.dot(qs, k.T, preferred_element_type=jnp.float32)  # [bq, bk]
        if causal:
            # entrywise mask for the diagonal-straddling block (cheap
            # enough to apply on every executed block; fully-below-diagonal
            # blocks mask nothing)
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(cols <= rows, s, NEG_INF)

        # lane-replicated stats -> collapse with a max (all lanes equal)
        m_prev = jnp.max(m_ref[...], axis=-1, keepdims=True)  # [bq, 1]
        l_prev = jnp.max(l_ref[...], axis=-1, keepdims=True)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [bq, bk] f32
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    if causal:
        # skip blocks with no col <= row entry: min col > max row
        block_live = j * block_k <= i * block_q + block_q - 1
        pl.when(block_live)(_update)
    else:
        _update()

    @pl.when(j == n_kv - 1)
    def _finalize():
        # causal rows with zero mass cannot occur (row r always sees col
        # <= r); padded q rows are sliced off by the wrapper, and their
        # l stays 0 only when EVERY kv block was skipped — guard the
        # divide so those garbage rows stay finite instead of inf/nan
        l_fin = jnp.max(l_ref[...], axis=-1, keepdims=True)
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _kv_block(sk: int, requested: int) -> int:
    """Largest power-of-two block <= requested that divides sk (any
    128-multiple sk admits 128)."""
    b = min(requested, sk)
    while b > 128 and sk % b:
        b //= 2
    if sk % b:
        raise ValueError(
            f"kv seq {sk} must be a multiple of 128 (pad inputs before "
            "calling, or use blockwise_attention)"
        )
    return b


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_q: int = 512,
    block_k: int = DEFAULT_BLOCK_K,
    causal: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """q,k,v: [batch, heads, seq, head_dim] -> same shape.

    ``causal=True`` applies the autoregressive mask with whole KV blocks
    above the diagonal skipped (no dots issued) — decoder-style scoring;
    seq-parallel causal long-context goes through ring_attention. The
    skip saves MXU work but the block pipeline still prefetches skipped
    KV blocks — a triangular grid would reclaim that DMA, a known upgrade.

    ``interpret=True`` runs the Pallas interpreter and is accepted on the
    CPU backend only; the default compiles the kernel (and so fails on
    the CPU backend, which has no Mosaic)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if interpret and jax.default_backend() != "cpu":
        raise ValueError(
            "flash_attention(interpret=True) is for the CPU backend; on "
            f"'{jax.default_backend()}' the kernel must run compiled"
        )

    # pad head_dim to the 128 lane width: zero-padded K dims add 0 to every
    # dot product and padded V dims are sliced off, so numerics are
    # unchanged (scale uses the original d). It keeps Mosaic's lanes full
    # at d=64, so the pad applies on the compiled path; interpret mode
    # skips it.
    orig_d = d
    if not interpret and d % _LANES:
        pad_d = _LANES - d % _LANES
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        d = q.shape[-1]

    block_q = min(block_q, sq)
    block_k = _kv_block(sk, block_k)
    # padded Q rows are harmless (sliced off after)
    pad_q = (-sq) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))

    qf = q.reshape(b * h, q.shape[2], d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    n_q = qf.shape[1] // block_q
    n_kv = sk // block_k

    kernel = functools.partial(
        _flash_kernel,
        n_kv=n_kv,
        scale=1.0 / (orig_d**0.5),
        block_q=block_q,
        block_k=block_k,
        causal=causal,
    )
    # scratch carries the online-softmax state across the (sequential) kv
    # grid dimension; interpret mode emulates VMEM scratch faithfully
    scratch_shapes = [
        pltpu.VMEM((block_q, _LANES), jnp.float32),  # m (lane-replicated)
        pltpu.VMEM((block_q, _LANES), jnp.float32),  # l (lane-replicated)
        pltpu.VMEM((block_q, d), jnp.float32),  # acc
    ]
    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, h, -1, d)
    if pad_q:
        out = out[:, :, :sq, :]
    if d != orig_d:
        out = out[..., :orig_d]
    return out
