"""Ulysses-style all-to-all sequence parallelism: exact attention over a
sequence-sharded mesh axis, the alternative strategy to ring attention.

Where ring attention (ops/ring_attention.py) keeps the sequence sharded and
ROTATES K/V shards around the ring — |ring| ppermute steps, compute
overlapping communication — the all-to-all strategy RE-SHARDS for the
attention op itself (DeepSpeed-Ulysses pattern, arXiv:2309.14509):

    [b, h, s/N, d]  --all_to_all-->  [b, h/N, s, d]
        (sequence-sharded)            (head-sharded, FULL sequence local)

Each device then runs plain attention for its head subset over the whole
sequence, and a second all-to-all restores sequence sharding for the
(sequence-local) MLP and layernorms. Two collectives per attention instead
of |ring| permutes: on TPU both lower to ICI all-to-alls, and the better
choice is workload-dependent — ring wins when compute per step hides the
permute latency (very long sequences); all-to-all wins at moderate lengths
where the ring's |N|-step latency chain dominates. Both are exact, so the
framework exposes the choice as a deployment knob
(``parameters: [{"name": "seq_parallel", "value": "ulysses"}]`` on a BERT
unit) rather than hard-coding either.

Constraint: attention heads must divide by the seq-axis size (heads are the
resharding currency); ring attention has the complementary constraint on
sequence length only.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from seldon_core_tpu.ops.attention import (
    FLASH_MIN_SEQ,
    blockwise_attention,
    naive_attention,
)


def _local_attention(q, k, v, causal: bool, vary_axes: tuple):
    # same dense/blockwise policy boundary as the single-device default
    # (models/bert.py _default_attention): dense below FLASH_MIN_SEQ
    if q.shape[2] < FLASH_MIN_SEQ:
        return naive_attention(q, k, v, causal=causal)
    # vary_axes: the blockwise scan carry must be varying over the manual
    # mesh axes or shard_map rejects the scan (carry type mismatch)
    return blockwise_attention(q, k, v, causal=causal, vary_axes=vary_axes)


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, vary_axes: tuple):
    """Per-device body (runs under shard_map). q,k,v: sequence-sharded
    local blocks [b, h, s_local, d]."""
    # scatter heads / gather sequence: [b, h, s/N, d] -> [b, h/N, s, d]
    qh = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    o = _local_attention(qh, kh, vh, causal, vary_axes)
    # gather heads / scatter sequence back: [b, h/N, s, d] -> [b, h, s/N, d]
    return lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1, tiled=True)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    data_axis: str = "data",
    causal: bool = False,
) -> jax.Array:
    """q,k,v: [batch, heads, seq, head_dim] GLOBAL arrays; returns attention
    output with the same (sequence-sharded) layout as ring_attention, so the
    two strategies are drop-in interchangeable. heads AND seq must divide
    evenly by the mesh's seq-axis size."""
    heads, seq = q.shape[1], q.shape[2]
    n = mesh.shape[seq_axis]
    if heads % n != 0:
        raise ValueError(
            f"ulysses: {heads} heads not divisible by seq-axis size {n} "
            "(heads are the all-to-all resharding currency — use ring "
            "attention for head counts below the mesh axis)"
        )
    if seq % n != 0:
        raise ValueError(f"ulysses: seq {seq} not divisible by seq-axis size {n}")
    batch_entry = data_axis if data_axis in mesh.shape else None
    spec = P(batch_entry, None, seq_axis, None)
    fn = jax.shard_map(
        partial(
            _ulysses_local,
            axis_name=seq_axis,
            causal=causal,
            vary_axes=tuple(mesh.axis_names),
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
