"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context sequence parallelism (greenfield vs the reference — SURVEY
§5.7): the sequence axis is sharded over the mesh "seq" axis, each device
holding one Q/K/V shard. K/V shards rotate around the ring with
``lax.ppermute`` (XLA lowers it to ICI neighbor transfers) while each device
accumulates online-softmax partials of its local Q against every visiting
K/V shard — after |seq| steps every Q block has attended to the full
sequence exactly, with peak memory O(seq/|ring|) per device and communication
overlapped with the per-step attention compute by XLA's async collectives.

Causal masking works on global positions: each device knows its shard offset
from lax.axis_index.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seldon_core_tpu.ops.attention import NEG_INF, _block_stats, combine_stats


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool, seq_per_dev: int, vary_axes: tuple):
    """Per-device body (runs under shard_map). q,k,v: local shards
    [b, h, s_local, d]."""
    b, h, s, d = q.shape
    ring_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    q_pos = my_idx * seq_per_dev + jnp.arange(s)  # global positions of local Q

    def step(carry, i):
        m_acc, l_acc, o_acc, k_cur, v_cur = carry
        # the K/V block currently held arrived from device (my_idx + i) % ring
        src = (my_idx + i) % ring_size
        k_pos = src * seq_per_dev + jnp.arange(s)
        mask = None
        if causal:
            mask = k_pos[None, None, None, :] <= q_pos[None, None, :, None]
        m, l, o = _block_stats(q, k_cur, v_cur, mask)
        m_acc, l_acc, o_acc = combine_stats(m_acc, l_acc, o_acc, m, l, o)
        # rotate K/V around the ring (device p receives from p+1: after step
        # i every device holds the shard of (my_idx + i + 1) % ring)
        perm = [(j, (j - 1) % ring_size) for j in range(ring_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m_acc, l_acc, o_acc, k_nxt, v_nxt), None

    # constants created inside shard_map are axis-invariant; the carry must
    # be marked varying over EVERY manual axis the inputs vary over (on a
    # mixed data+seq mesh that includes the batch axis) to match the loop
    # outputs
    init = (
        lax.pcast(jnp.full((b, h, s), NEG_INF, q.dtype), vary_axes, to="varying"),
        lax.pcast(jnp.zeros((b, h, s), q.dtype), vary_axes, to="varying"),
        lax.pcast(jnp.zeros((b, h, s, d), q.dtype), vary_axes, to="varying"),
        k,
        v,
    )
    (m, l, o, _, _), _ = lax.scan(step, init, jnp.arange(ring_size))
    return o / l[..., None]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    data_axis: str = "data",
    causal: bool = False,
) -> jax.Array:
    """q,k,v: [batch, heads, seq, head_dim] GLOBAL arrays (or already
    sharded); returns attention output sharded the same way. seq must divide
    evenly by the mesh's seq-axis size. On a mixed data+seq mesh the batch
    dim shards over ``data_axis`` too — otherwise every device in the data
    group would recompute attention for the full batch."""
    seq = q.shape[2]
    ring = mesh.shape[seq_axis]
    if seq % ring != 0:
        raise ValueError(f"seq {seq} not divisible by ring size {ring}")
    seq_per_dev = seq // ring
    batch_entry = data_axis if data_axis in mesh.shape else None
    spec = P(batch_entry, None, seq_axis, None)

    fn = jax.shard_map(
        partial(
            _ring_attention_local,
            axis_name=seq_axis,
            causal=causal,
            seq_per_dev=seq_per_dev,
            vary_axes=tuple(mesh.axis_names),
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
