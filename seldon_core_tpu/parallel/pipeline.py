"""Pipeline parallelism over a mesh axis via shard_map + ppermute.

Greenfield vs the reference (its only scaling axis is k8s replicas): a
GPipe-style microbatch pipeline where each device along the "pipe" mesh axis
owns one stage's parameters and activations flow stage-to-stage over ICI
with ``lax.ppermute``. The schedule is the classic (M + S - 1)-tick loop: at
tick t, stage 0 feeds microbatch t while stage s works on microbatch t - s;
bubbles are the usual (S-1)/(M+S-1) fraction.

Backward comes for free: JAX differentiates through the scan + ppermute
(the transpose of a permute is the inverse permute), so jax.grad of a loss
over pipeline outputs yields the reverse-schedule backward pipeline without
hand-writing it — train steps in training/steps.py compose directly.

Stage parameters are a pytree whose leaves are stacked on axis 0 with length
|pipe| and sharded P("pipe", ...) — device s holds slice s (its stage).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

StageFn = Callable[[Any, jax.Array], jax.Array]


def _pipeline_local(
    stage_params: Any,
    x_micro: jax.Array,
    stage_fn: StageFn,
    axis_name: str,
):
    """Per-device body. stage_params: this stage's params (leading stacked
    axis of size 1, squeezed). x_micro: [M, mb, ...] full microbatch stack
    (replicated; only stage 0 reads it). Returns [M, mb, ...] outputs valid
    on the LAST stage (zeros elsewhere)."""
    n_stages = lax.psum(1, axis_name)
    stage_id = lax.axis_index(axis_name)
    params = jax.tree.map(lambda a: a[0], stage_params)
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    mb_shape = x_micro.shape[1:]
    perm = [(i, i + 1) for i in range(n_stages - 1)]  # stage s -> s+1

    def tick(carry, t):
        recv, outs = carry
        feed_idx = jnp.clip(t, 0, n_micro - 1)
        inp = jnp.where(stage_id == 0, x_micro[feed_idx], recv)
        out = stage_fn(params, inp)
        # ship my output to the next stage (last stage's send is dropped)
        recv_next = lax.ppermute(out, axis_name, perm)
        # last stage stores microbatch t-(S-1) once the pipe is full
        store_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        is_valid = (t >= n_stages - 1) & (stage_id == n_stages - 1)
        outs = jnp.where(
            is_valid,
            outs.at[store_idx].set(out),
            outs,
        )
        return (recv_next, outs), None

    init_recv = lax.pcast(
        jnp.zeros(mb_shape, x_micro.dtype), (axis_name,), to="varying"
    )
    init_outs = lax.pcast(jnp.zeros_like(x_micro), (axis_name,), to="varying")
    (_, outs), _ = lax.scan(tick, (init_recv, init_outs), jnp.arange(ticks))
    # broadcast the last stage's buffer to every device so the caller gets a
    # replicated result (psum of zeros elsewhere)
    outs = jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs))
    return lax.psum(outs, axis_name)


def pipeline_apply(
    stage_fn: StageFn,
    stage_params: Any,
    x_micro: jax.Array,
    mesh: Mesh,
    *,
    pipe_axis: str = "pipe",
) -> jax.Array:
    """Run x_micro [M, mb, ...] through S pipeline stages.

    stage_params: pytree with leaves stacked [S, ...]; stage_fn(params, x)
    must map [mb, ...] -> [mb, ...] (uniform stage signature). Returns
    [M, mb, ...] outputs, replicated over the pipe axis.
    """
    n_stages = mesh.shape[pipe_axis]
    for leaf in jax.tree.leaves(stage_params):
        if leaf.shape[0] != n_stages:
            # a mismatch would silently run only each device's first local
            # stage slice (tree.map a[0]) and return wrong outputs
            raise ValueError(
                f"stage_params stacked axis is {leaf.shape[0]} but mesh "
                f"'{pipe_axis}' axis has {n_stages} devices — they must match"
            )
    param_specs = jax.tree.map(lambda _: P(pipe_axis), stage_params)
    fn = jax.shard_map(
        partial(_pipeline_local, stage_fn=stage_fn, axis_name=pipe_axis),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
    )
    return fn(stage_params, x_micro)
