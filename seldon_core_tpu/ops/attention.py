"""Blockwise (flash-style) attention in pure JAX.

No reference analogue (the reference serves tabular/image models over RPC —
SURVEY §5.7 'long-context: absent'); this is the greenfield long-context
tier: an online-softmax attention whose KV axis is processed in blocks with
running (max, denominator, numerator) statistics, so memory is O(block)
instead of O(seq^2), and whose math is the per-step building block of ring
attention (ops/ring_attention.py) where the "blocks" arrive over ICI.

All shapes [batch, heads, seq, head_dim]; lax.scan keeps the loop inside one
XLA program (no Python-unrolled graph bloat at long seq).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_stats(q, k, v, mask=None):
    """One KV block: returns (m, l, o) running stats for online softmax.
    m: rowwise max [.., sq], l: rowwise denom [.., sq], o: numerator
    [.., sq, d]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.asarray(
        q.shape[-1] ** 0.5, q.dtype
    )
    if mask is not None:
        s = jnp.where(mask, s, jnp.asarray(NEG_INF, s.dtype))
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m, l, o


def combine_stats(m1, l1, o1, m2, l2, o2):
    """Merge two online-softmax partials (associative — the reduction law
    that makes blockwise and ring attention exact, not approximate)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = a1 * l1 + a2 * l2
    o = a1[..., None] * o1 + a2[..., None] * o2
    return m, l, o


# seq length at which the dense O(s^2) score matrix gives way to the
# blockwise kernel — the ONE policy constant shared by the single-device
# default (models/bert.py) and the seq-parallel local bodies (ops/ulysses.py)
FLASH_MIN_SEQ = 1024

# seq length from which an accelerator backend routes to the hand-tiled
# Pallas kernel (ops/pallas_flash) instead of this pure-JAX blockwise path:
# blockwise's per-step [.., sq, block] score tensors go through HBM while
# the kernel keeps its working set in VMEM. The crossover is not measured
# on the chip; the kernel is in no cell of the benchmark (PERF.md section 3).
PALLAS_MIN_SEQ = 4096


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_size: int = 512,
    causal: bool = False,
    vary_axes: tuple = (),
) -> jax.Array:
    """Exact attention with KV processed in blocks of ``block_size``.

    q,k,v: [batch, heads, seq, head_dim] -> [batch, heads, seq, head_dim].

    ``vary_axes``: when called INSIDE shard_map, the scan carry is
    initialized from axis-invariant constants and must be marked varying
    over the manual mesh axes or the carry-in/carry-out types mismatch —
    pass the enclosing mesh axis names (same fix ring_attention applies).
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block = min(block_size, sk)
    if sk % block != 0:
        # pad KV to a block multiple; padded keys are masked out
        pad = block - sk % block
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_blocks = k.shape[2] // block

    q_pos = jnp.arange(sq)

    def body(carry, blk):
        m_acc, l_acc, o_acc = carry
        kb = lax.dynamic_slice_in_dim(k, blk * block, block, axis=2)
        vb = lax.dynamic_slice_in_dim(v, blk * block, block, axis=2)
        k_pos = blk * block + jnp.arange(block)
        valid = k_pos < sk
        mask = valid[None, None, None, :]
        if causal:
            mask = mask & (k_pos[None, None, None, :] <= q_pos[None, None, :, None])
        m, l, o = _block_stats(q, kb, vb, mask)
        return combine_stats(m_acc, l_acc, o_acc, m, l, o), None

    init = (
        jnp.full((b, h, sq), NEG_INF, q.dtype),
        jnp.zeros((b, h, sq), q.dtype),
        jnp.zeros((b, h, sq, d), q.dtype),
    )
    if vary_axes:
        init = tuple(lax.pcast(x, vary_axes, to="varying") for x in init)
    (m, l, o), _ = lax.scan(body, init, jnp.arange(n_blocks))
    return o / l[..., None]


def causal_attention_auto(q, k, v) -> jax.Array:
    """Backend-adaptive CAUSAL attention — the one policy shared by every
    causal consumer (decoder prefill today): dense below FLASH_MIN_SEQ,
    blockwise above it, the COMPILED Pallas causal kernel from
    PALLAS_MIN_SEQ when the KV axis tiles — on every backend but the CPU,
    which has no Mosaic. Mirrors models/bert.py's non-causal
    `_default_attention` thresholds so the two policies cannot drift
    apart in spirit."""
    s = q.shape[2]
    if s >= FLASH_MIN_SEQ:
        if s >= PALLAS_MIN_SEQ and jax.default_backend() != "cpu" and k.shape[2] % 128 == 0:
            from seldon_core_tpu.ops.pallas_flash import flash_attention

            return flash_attention(q, k, v, causal=True)
        return blockwise_attention(q, k, v, block_size=512, causal=True)
    return naive_attention(q, k, v, causal=True)


def naive_attention(q, k, v, *, causal: bool = False) -> jax.Array:
    """Reference O(seq^2) attention for testing."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.asarray(q.shape[-1] ** 0.5, q.dtype)
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None, None], s, jnp.asarray(NEG_INF, s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)
