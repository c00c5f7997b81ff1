"""Attention ops: blockwise == naive, ring == naive on the 8-device mesh,
pallas flash kernel == naive (interpret mode on CPU).

This is the multi-host-simulation test tier the reference lacks entirely
(SURVEY §4 implication) — collectives run on 8 virtual devices.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from seldon_core_tpu.ops import (
    blockwise_attention,
    flash_attention,
    naive_attention,
    ring_attention,
)


def _qkv(b=2, h=2, s=64, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    return mk(), mk(), mk()


def test_blockwise_matches_naive():
    q, k, v = _qkv()
    ref = naive_attention(q, k, v)
    got = blockwise_attention(q, k, v, block_size=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_blockwise_causal_matches_naive():
    q, k, v = _qkv(s=48)
    ref = naive_attention(q, k, v, causal=True)
    got = blockwise_attention(q, k, v, block_size=16, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_blockwise_ragged_block_padding():
    # seq 40 with block 16 -> padded KV blocks must not change the result
    q, k, v = _qkv(s=40)
    ref = naive_attention(q, k, v)
    got = blockwise_attention(q, k, v, block_size=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def _seq_mesh(n=4):
    devices = np.asarray(jax.devices()[:n])
    return Mesh(devices, ("seq",))


def test_ring_attention_matches_naive():
    q, k, v = _qkv(s=64)
    ref = naive_attention(q, k, v)
    mesh = _seq_mesh(4)
    got = ring_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_causal_matches_naive():
    q, k, v = _qkv(s=64)
    ref = naive_attention(q, k, v, causal=True)
    mesh = _seq_mesh(4)
    got = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_eight_devices():
    q, k, v = _qkv(s=64, b=1, h=1)
    ref = naive_attention(q, k, v)
    mesh = _seq_mesh(8)
    got = ring_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_rejects_ragged_seq():
    q, k, v = _qkv(s=30)
    with pytest.raises(ValueError):
        ring_attention(q, k, v, _seq_mesh(4))


def test_flash_attention_matches_naive():
    q, k, v = _qkv(s=64, d=16)
    ref = naive_attention(q, k, v)
    got = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_q_padding():
    # sq=40 not a multiple of block_q=16: wrapper pads and slices
    b, h, d = 1, 2, 16
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, h, 40, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, 64, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, 64, d)), jnp.float32)
    ref = naive_attention(q, k, v)
    got = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_rejects_ragged_kv():
    q, k, v = _qkv(s=40)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)


def test_flash_attention_causal_matches_naive():
    """Causal mode: whole KV blocks above the diagonal are skipped, the
    straddling block masks entrywise — numerics must equal the dense
    causal reference at shapes where skipping actually triggers (seq
    spans several blocks)."""
    q, k, v = _qkv(s=64, d=16)
    ref = naive_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, block_q=16, block_k=16, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # mismatched block sizes exercise the straddling-block mask
    got2 = flash_attention(q, k, v, block_q=32, block_k=16, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_causal_with_q_padding():
    # sq=40 pads to the 16-row q block; padded rows are sliced off and the
    # real rows' causal numerics are unchanged
    b, h, d = 1, 2, 16
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((b, h, 40, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, 64, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, 64, d)), jnp.float32)
    ref = naive_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, block_q=16, block_k=16, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
