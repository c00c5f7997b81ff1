"""The gated delta rule: the recurrence of a Gated DeltaNet (linear-attention)
layer (``model_type: qwen3_next``), in plain ``jax.numpy`` / ``lax``.

A value head keeps a MATRIX state ``S`` [d_k, d_v] in float32 that is READ
before it is written: with the decay ``alpha_t`` in (0, 1] and the write
strength ``beta_t`` in [0, 1],

    S'  = alpha_t S_{t-1}
    d_t = beta_t (v_t - S'^T k_t)        what the state already holds for this
    S_t = S' + k_t (x) d_t               key is taken off the value first
    o_t = S_t^T q_t

(``q`` and ``k`` l2-normalised, ``q`` also over sqrt(d_k), by the caller).
Mamba-2's state is written with a rank-one term that does not look at the
state (``S <- a S + dt x (x) B``): neither its step nor its chunked scan
(models/hybrid_decoder.py ``_scan_chunk``) computes this one. Value head h
reads key head ``h // (value heads / key heads)``; the head axis is (key
head, value head of the key head) throughout and no q or k is repeated.

THE STEP (``gdn_step``) reads the rows' state twice and writes it once: one
pass for ``S^T k`` and ``S^T q`` together (``o_t = alpha S^T q + (q . k)
d_t``, so the output needs no third look at the new state), one for ``alpha S
+ k (x) d``. Products and sums over the state as it lies, not matrix products
that would take it rounded to bfloat16.

THE CHUNK (``gdn_chunk``) is the blocked (WY / UT-transform) form. In a block
of C tokens with ``G_t = sum_{i<=t} log alpha_i`` and ``Gam[t, s] = exp(G_t -
G_s)`` for ``s <= t`` (masked BEFORE the exponential: no decay is ever
divided by),

    A[t, s] = beta_t (k_t . k_s) Gam[t, s]   for s < t, else 0
    (I + A) D = beta V - (beta exp(G) K) S_0  =>  D = U - W S_0,
        U = T (beta V),  W = T (beta exp(G) K),  T = (I + A)^-1
    o_t = exp(G_t) S_0^T q_t + sum_{s<=t} Gam[t, s] (q_t . k_s) D_s
    S_C = exp(G_C) S_0 + sum_s exp(G_C - G_s) k_s (x) D_s

A, T, U and W do not depend on the state: every block of the dispatch is
solved at once, and only the three products with ``S_0`` go block after
block. A position with ``log alpha`` 0 and ``beta`` 0 (a row past a slot's
count, the padding of the last block) leaves the state as it was.

``T`` is the inverse of a UNIT lower-triangular matrix
(``_unit_lower_inverse``): halved down to blocks of at most ``_SOLVE_BASE``
rows by the block formula ``[[T11, 0], [-T22 A21 T11, T22]]`` (matrix
products, both halves of a level in one batch), a base block by the finite
Neumann product ``(I - A)(I + A^2)(I + A^4)...`` (A is nilpotent). The
Neumann product over a WHOLE 64-row block is what must not be used: with
entries of 0.3 the powers of A reach 1e5 before they cancel and float32
keeps one digit; inside 16 rows they stay under 4 (6,435 in the worst case
of identical keys written at full strength without decay, which leaves three
digits more than the bfloat16 activations carry). A row-by-row forward
substitution (``lax.linalg.triangular_solve`` lowers to one on the chip: a
loop of 64 dependent steps over every block's matrix) reads the same to
rounding and takes its time from the loop, not from the products.

Everything here is float32 at ``Precision.HIGHEST`` (the hybrid family's
``_SCAN_PRECISION``): at the chip's default the operands are rounded to
bfloat16 first, and the state and the decay are the float32 part of the
model.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_PRECISION = lax.Precision.HIGHEST
# tokens of one block of the chunk form. The result does not depend on it. The
# published kernels take 64; a 256-token dispatch at Qwen3-Next's widths (16 /
# 32 heads of 128), six layers chained through the state, read 0.509 / 0.455 /
# 0.428 ms a layer over four rows and 0.249 / 0.227 / 0.207 over two in blocks
# of 64 / 128 / 256 (my chip run, PR 57; the same result to 2e-5): a state
# pass between blocks waits for the block before it, and the larger solve
# does not cost what that wait does (Mamba-2's scan found the same: PR 51)
GDN_BLOCK = 256
# rows of a unit lower-triangular block inverted by the Neumann product
_SOLVE_BASE = 16
# a dispatch whose [rows, blocks, heads, C, C] float32 decay matrix passes this
# goes in blocks of rows (hybrid_decoder._SCAN_BLOCK_BYTES' reasoning)
_BLOCK_BYTES = 128 << 20


def _dot(spec: str, *operands):
    return jnp.einsum(spec, *operands, precision=_PRECISION)


def gdn_step(state, q, k, v, log_alpha, beta):
    """One token a row. state [n, Hk, r, dk, dv] float32; q, k [n, Hk, dk];
    v [n, Hk, r, dv]; log_alpha, beta [n, Hk, r] (0 and 0: the row stands).
    Returns (o [n, Hk, r, dv], the new state)."""
    alpha = jnp.exp(log_alpha)[..., None]  # [n, Hk, r, 1]
    kq = jnp.stack([k, q], axis=2)[:, :, :, None, :, None]  # [n, Hk, 2, 1, dk, 1]
    # S^T k and S^T q in one pass over the state: [n, Hk, 2, r, dv]
    held = jnp.sum(state[:, :, None] * kq, axis=-2)
    d = beta[..., None] * (v - alpha * held[:, :, 0])
    new = alpha[..., None] * state + k[:, :, None, :, None] * d[..., None, :]
    o = alpha * held[:, :, 1] + jnp.sum(q * k, axis=-1)[..., None, None] * d
    return o, new


def _solver_rows(c: int) -> int:
    """The least c' >= c that halves evenly down to ``_SOLVE_BASE`` rows or fewer."""
    halvings = max(math.ceil(math.log2(c / _SOLVE_BASE)), 0) if c > _SOLVE_BASE else 0
    return -(-c // (1 << halvings)) * (1 << halvings)


def _unit_lower_inverse(a):
    """(I + a)^-1 of strictly lower-triangular a [..., c, c] float32, c as
    ``_solver_rows`` gives it (module docstring)."""
    c = a.shape[-1]
    if c <= _SOLVE_BASE:
        eye = jnp.eye(c, dtype=a.dtype)
        inv, power = eye - a, a
        for _ in range(max(math.ceil(math.log2(c)) - 1, 0)):  # (I - a)(I + a^2)...: every power of a below a^c = 0
            power = _dot("...ij,...jk->...ik", power, power)
            inv = _dot("...ij,...jk->...ik", inv, eye + power)
        return inv
    h = c // 2
    t11, t22 = _unit_lower_inverse(jnp.stack([a[..., :h, :h], a[..., h:, h:]]))
    t21 = -_dot("...ij,...jk,...kl->...il", t22, a[..., h:, :h], t11)
    return jnp.concatenate(
        [jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1), jnp.concatenate([t21, t22], axis=-1)], axis=-2
    )


def _chunk(state, q, k, v, log_alpha, beta, c: int):
    """``gdn_chunk`` over m = blocks * c tokens, every row at once."""
    n, m, hk, dk = q.shape
    r, dv = v.shape[3:]
    nb = m // c
    # blocks first: [nb, n, c, ...]
    blocked = lambda t: jnp.moveaxis(t.reshape(n, nb, c, *t.shape[2:]), 1, 0)  # noqa: E731
    q, k, v, log_alpha, beta = (blocked(t) for t in (q, k, v, log_alpha, beta))
    g = jnp.cumsum(log_alpha, axis=2)  # [nb, n, c, Hk, r], <= 0 and falling
    gt = jnp.moveaxis(g, 2, -1)  # [nb, n, Hk, r, c]
    lower = jnp.tril(jnp.ones((c, c), bool))
    gam = jnp.exp(jnp.where(lower, gt[..., :, None] - gt[..., None, :], -jnp.inf))  # [.., t, s]
    kk = _dot("bnthk,bnshk->bnhts", k, k)[:, :, :, None]  # [nb, n, Hk, 1, t, s]
    qk = _dot("bnthk,bnshk->bnhts", q, k)[:, :, :, None]
    bt_ = jnp.moveaxis(beta, 2, -1)[..., None]  # [nb, n, Hk, r, t, 1]
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), bt_ * kk * gam, 0.0)
    t_inv = _unit_lower_inverse(a)
    u = _dot("bnhrts,bnshrv->bnthrv", t_inv, beta[..., None] * v)
    w = _dot("bnhrts,bnshrk->bnthrk", t_inv, (beta * jnp.exp(g))[..., None] * k[:, :, :, :, None, :])
    p = qk * gam  # [nb, n, Hk, r, t, s], zero above the diagonal
    eg = jnp.exp(g)  # [nb, n, c, Hk, r]
    left = jnp.exp(g[:, :, -1:] - g)  # what is left of step s at the block's end

    def block(s0, at):
        q_b, k_b, u_b, w_b, p_b, eg_b, left_b = at
        d = u_b - _dot("nthrk,nhrkv->nthrv", w_b, s0)
        o = eg_b[..., None] * _dot("nthk,nhrkv->nthrv", q_b, s0) + _dot("nhrts,nshrv->nthrv", p_b, d)
        s1 = eg_b[:, -1][..., None, None] * s0 + _dot("nshrk,nshrv->nhrkv", left_b[..., None] * k_b[:, :, :, None, :], d)
        return s1, o

    if nb == 1:
        state, o = block(state, tuple(t[0] for t in (q, k, u, w, p, eg, left)))
        return o, state
    state, o = lax.scan(block, state, (q, k, u, w, p, eg, left))
    return jnp.moveaxis(o, 0, 1).reshape(n, m, hk, r, dv), state


def gdn_chunk(state, q, k, v, log_alpha, beta, block: int | None = None):
    """m tokens a row in the blocked form (module docstring). state [n, Hk,
    r, dk, dv] float32; q, k [n, m, Hk, dk]; v [n, m, Hk, r, dv]; log_alpha,
    beta [n, m, Hk, r], both 0 on a position that must leave the state as it
    was. ``block``: tokens a block (``GDN_BLOCK``; the result does not depend
    on it). Returns (o [n, m, Hk, r, dv], the state after the last token)."""
    n, m, hk, _ = q.shape
    r = v.shape[3]
    c = _solver_rows(min(block or GDN_BLOCK, m))
    pad = -m % c
    if pad:
        q, k, v, log_alpha, beta = (
            jnp.pad(t, ((0, 0), (0, pad), *[(0, 0)] * (t.ndim - 2))) for t in (q, k, v, log_alpha, beta)
        )
    rows = n
    while rows > 1 and 4 * rows * (m + pad) * hk * r * c > _BLOCK_BYTES and rows % 2 == 0:
        rows //= 2
    if rows == n:
        o, state = _chunk(state, q, k, v, log_alpha, beta, c)
    else:
        split = lambda t: t.reshape(n // rows, rows, *t.shape[1:])  # noqa: E731
        o, state = lax.map(lambda a: _chunk(*a, c), tuple(split(t) for t in (state, q, k, v, log_alpha, beta)))
        o, state = o.reshape(n, *o.shape[2:]), state.reshape(n, *state.shape[2:])
    return o[:, :m], state
