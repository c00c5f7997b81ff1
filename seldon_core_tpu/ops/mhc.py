"""Manifold-constrained hyper-connections: a residual path ``n`` streams wide
(Xie et al., "mHC: Manifold-Constrained Hyper-Connections", arXiv:2512.24880;
``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_*`` of a
published config).

A token's state between blocks is ``X[n, C]``; a dispatch's is stored
STREAMS-MAJOR, ``[n, rows, queries, C]``. Around EACH block ``F``
(attention or feed-forward) three small maps are computed per token from the
whole state, ``v = rms(vec(X))`` over all ``n * C`` numbers, no learned weight:

    H_pre  = sigmoid(a_pre  * (v phi_pre)  + b_pre)            [n]: the mixture F reads
    H_post = 2 sigmoid(a_post * (v phi_post) + b_post)         [n]: how F's output is written back
    H_res  = SK(clip(a_res * mat(v phi_res) + b_res, -c, c))   [n, n]: how the streams mix
    u  = sum_j H_pre[j] X[j];   o = F(norm(u));   X'[i] = sum_j H_res[i, j] X[j] + H_post[i] o

``SK`` is Sinkhorn-Knopp on ``exp`` of the clamped logits: ``iters`` times,
every column divided by its sum + eps, then every row by its sum + eps, so
``H_res`` is doubly stochastic to the iteration's convergence and a new stream
is a convex combination of the old ones.

Three entry points (beside the two ends, ``spread`` and ``merged``), plain ``jax.numpy`` under device scopes (nested by the
caller under a ``decoder.PAGED_SCOPES`` name): ``stream_maps`` (``mhc_map``),
``pre_mix`` (``mhc_pre``), ``post_mix`` (``mhc_post``). The maps are float32
whatever the state's dtype; the state is rounded where it is written.

What the forms are for (measured on a v5e, PERF.md section 6, PR 43): the
state is stored streams-major, so a stream is a plain ``[rows, queries, C]``
array that the compiler tiles like any activation; stored ``[rows, queries,
n, C]`` the chip's tiling either pads four streams to sixteen sublanes or
lays the stream axis out major anyway and copies the state between the two
layouts round every product (17 passes over the state a block of a chunk
dispatch where the least is 2.5, 2.5 ms a block at 4,096 rows where this form
takes 1.1). The norm is applied AFTER the product, ``(X phi) * rsqrt(mean(X^2)
+ eps)``, so the normalised 14,336-wide vector is never written and the
product's operands are the state and ``phi`` as stored (exact in one bfloat16
pass where both are bfloat16), a stream at a time; the mixing sums are
written out over the stream axis, elementwise, so that no ``[rows, n, n, C]``
product and no batched 1 x 4 matrix product (which the chip would round to
bfloat16) exists.
"""

from __future__ import annotations

import functools
import operator

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

SCOPE_MHC_MAP = "mhc_map"  # sum of squares, the three products, sigmoid, exp, Sinkhorn
SCOPE_MHC_PRE = "mhc_pre"  # u = sum_j H_pre[j] X[j]
SCOPE_MHC_POST = "mhc_post"  # X' = H_res X + H_post o


def init_maps(key, streams: int, hidden: int, dtype, *, logit_std, res_diag: float) -> dict:
    """One block's map parameters: ``phi[n * C, n + n + n * n]`` (columns
    ``pre | post | res`` row-major) at std 0.02 in ``dtype``; ``alpha[3]``
    (pre, post, res) float32, set so that the dynamic logits have std
    ``logit_std[k]`` whatever the width (over a normalised n * C vector
    ``phi``'s products have std 0.02 * sqrt(n * C)); ``bias[2n + n * n]``
    float32, zero but for ``res_diag`` on the diagonal of ``b_res`` (the
    published initialiser starts ``H_res`` at the identity; a finite
    diagonal keeps Sinkhorn's rate, which is the square of the second
    singular value of its limit)."""
    n = streams
    phi = (jax.random.normal(key, (n * hidden, 2 * n + n * n), jnp.float32) * 0.02).astype(dtype)
    bias = jnp.concatenate([jnp.zeros((2 * n,), jnp.float32), (res_diag * jnp.eye(n, dtype=jnp.float32)).reshape(-1)])
    alpha = jnp.asarray(logit_std, jnp.float32) / (0.02 * (n * hidden) ** 0.5)
    return {"phi": phi, "alpha": alpha, "bias": bias}


def spread(e: jax.Array, streams: int) -> jax.Array:
    """The entry: every stream starts as the embedding, e[..., C] -> [n, ..., C]."""
    return jnp.broadcast_to(e[None], (streams, *e.shape))


def merged(x: jax.Array) -> jax.Array:
    """The exit: the streams SUMMED (float32, rounded once), x[n, ..., C] -> [..., C]."""
    return jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype)


def sinkhorn_plain(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    """logits[..., n, n] float32 (clamped) -> ``T_r(T_c(.))`` ``iters`` times
    over ``exp(logits)``: columns to sum one, then rows, ``eps`` in both
    denominators. A loop, not 2 * iters unrolled stages: a program of 40
    blocks stays small. The kernel's oracle, and the form of every backend
    but the TPU."""

    def one(_, m):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps)

    return lax.fori_loop(0, iters, one, jnp.exp(logits))


# the kernel's block: up to this many groups of 128 rows (a [n * n, 32, 128]
# float32 block is 256 KB at n = 4: in and out, double-buffered, 1 MB of VMEM)
SINKHORN_BLOCK_GROUPS = 32


def sinkhorn_tiles(n: int) -> bool:
    """Whether ``sinkhorn_kernel`` takes maps of ``n x n``: a matrix small
    enough to unroll (any row count: it pads to whole 128-lane groups, and to
    whole blocks above one)."""
    return 2 <= n <= 8


def _sinkhorn_body(l_ref, o_ref, *, n: int, iters: int, eps: float):
    """One block of maps, each entry (i, j) a [groups, 128] tile of rows: the
    whole iteration in registers, sums over i or j as adds of tiles."""

    def over_sum(m, picks):
        r = functools.reduce(operator.add, (m[k] for k in picks)) + eps
        for k in picks:
            m[k] = m[k] / r

    def one(_, m):
        m = list(m)
        for j in range(n):  # every column over its sum
            over_sum(m, range(j, n * n, n))
        for i in range(n):  # then every row over its sum
            over_sum(m, range(i * n, (i + 1) * n))
        return tuple(m)

    m = lax.fori_loop(0, iters, one, tuple(jnp.exp(l_ref[k]) for k in range(n * n)))
    for k in range(n * n):
        o_ref[k] = m[k]


def sinkhorn_kernel(logits: jax.Array, iters: int, eps: float, interpret: bool = False) -> jax.Array:
    """``sinkhorn_plain`` as ONE Pallas call: the plain form is three to five
    small dependent fusions an iteration whatever its layout (on a v5e the
    loop above was 100 launches a block, 4,000 and 1.5 ms a step of 40 blocks:
    PERF.md section 6, PR 43), this is one launch. The maps go rows-minor, ``[n * n, groups, 128]``: a tile holds
    the same entry of 128 rows, so the sums over a 4-wide axis are adds of
    tiles and nothing crosses lanes."""
    from jax.experimental import pallas as pl

    *lead, n, _ = logits.shape
    rows = int(np.prod(lead)) if lead else 1
    groups = -(-rows // 128)
    block = min(groups, SINKHORN_BLOCK_GROUPS)
    groups = -(-groups // block) * block
    flat = jnp.pad(logits.reshape(rows, n * n).T, ((0, 0), (0, groups * 128 - rows)))  # padding rows: exp(0), finite
    out = pl.pallas_call(
        functools.partial(_sinkhorn_body, n=n, iters=iters, eps=eps),
        out_shape=jax.ShapeDtypeStruct((n * n, groups, 128), jnp.float32),
        grid=(groups // block,),
        in_specs=[pl.BlockSpec((n * n, block, 128), lambda g: (0, g, 0))],
        out_specs=pl.BlockSpec((n * n, block, 128), lambda g: (0, g, 0)),
        interpret=interpret,
        name="mhc_sinkhorn",
    )(flat.reshape(n * n, groups, 128))
    return out.reshape(n * n, groups * 128)[:, :rows].T.reshape(*lead, n, n)


def _sinkhorn_mode() -> str:
    """"mosaic" on a TPU, "" elsewhere (tests answer "interpret" here: the
    CPU's way to run the same kernel)."""
    return "mosaic" if jax.default_backend() == "tpu" else ""


def sinkhorn(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    """The stream mix's normalisation: the kernel where the backend has one
    and ``sinkhorn_tiles`` holds, else the plain loop."""
    mode = _sinkhorn_mode()
    if mode and sinkhorn_tiles(logits.shape[-1]):
        return sinkhorn_kernel(logits, iters, eps, interpret=mode == "interpret")
    return sinkhorn_plain(logits, iters, eps)


def doubly_stochastic_residual(h_res: jax.Array, valid: jax.Array | None = None) -> jax.Array:
    """The largest ``|row or column sum - 1|`` of any ``h_res[..., n, n]``
    (of the rows ``valid`` marks), times 1e6, int32: the frames'
    ``mhc_resid_ppm``. Float32 Sinkhorn reads single digits (``eps`` itself
    is one), a bfloat16 one thousands."""
    off = jnp.maximum(jnp.max(jnp.abs(jnp.sum(h_res, axis=-1) - 1.0), axis=-1),
                      jnp.max(jnp.abs(jnp.sum(h_res, axis=-2) - 1.0), axis=-1))
    if valid is not None:
        off = jnp.where(valid, off, 0.0)
    return jnp.round(jnp.max(off) * 1e6).astype(jnp.int32)


def stream_maps(p: dict, x: jax.Array, *, iters: int, eps: float, clamp: float, rms_eps: float):
    """x[n, ..., C] -> (H_pre[..., n], H_post[..., n], H_res[..., n, n]), float32."""
    with jax.named_scope(SCOPE_MHC_MAP):
        n, c = x.shape[0], x.shape[-1]
        phi = p["phi"].astype(x.dtype).reshape(n, c, -1)  # vec(X)'s rows are the streams, one after another
        z = sum(jnp.matmul(x[j], phi[j], preferred_element_type=jnp.float32) for j in range(n))
        squares = sum(jnp.sum(jnp.square(x[j].astype(jnp.float32)), axis=-1, keepdims=True) for j in range(n))
        z = z * lax.rsqrt(squares / (n * c) + rms_eps)
        alpha = jnp.repeat(p["alpha"], np.array([n, n, n * n]), total_repeat_length=2 * n + n * n)
        z = z * alpha + p["bias"]
        h_pre = jax.nn.sigmoid(z[..., :n])
        h_post = 2.0 * jax.nn.sigmoid(z[..., n : 2 * n])
        logits = jnp.clip(z[..., 2 * n :], -clamp, clamp).reshape(*z.shape[:-1], n, n)
        return h_pre, h_post, sinkhorn(logits, iters, eps)


def pre_mix(x: jax.Array, h_pre: jax.Array) -> jax.Array:
    """u[..., C] = sum_j H_pre[..., j] x[j], in x's dtype."""
    with jax.named_scope(SCOPE_MHC_PRE):
        u = sum(h_pre[..., j, None] * x[j].astype(jnp.float32) for j in range(x.shape[0]))
        return u.astype(x.dtype)


def post_mix(x: jax.Array, o: jax.Array, h_post: jax.Array, h_res: jax.Array) -> jax.Array:
    """x'[i] = sum_j H_res[..., i, j] x[j] + H_post[..., i] o, in x's dtype."""
    with jax.named_scope(SCOPE_MHC_POST):
        n = x.shape[0]
        xf = [x[j].astype(jnp.float32) for j in range(n)]
        of = o.astype(jnp.float32)
        return jnp.stack([
            (h_post[..., i, None] * of + sum(h_res[..., i, j, None] * xf[j] for j in range(n))).astype(x.dtype)
            for i in range(n)
        ])
