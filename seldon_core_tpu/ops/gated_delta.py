"""The gated delta rule: the recurrence of a Gated DeltaNet (linear-attention)
layer (``model_type: qwen3_next``): in plain ``jax.numpy`` / ``lax`` (``gdn_step``,
``gdn_chunk``), and in two Pallas kernels that hold a head's matrix state in
VMEM (``gdn_step_rows``, ``gdn_chunk_rows``; WHICH FORM RUNS WHERE, below).

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

WHICH FORM RUNS WHERE (``kernel_mode``; read from the input, not set). On a
TPU, where ``d_k`` and ``d_v`` are whole 128-lane tiles and the state rows are
float32, ``models/hybrid_decoder.py`` ``_gdn`` calls the kernels; everywhere
else (the CPU backend, the CPU tests' head of 8, ``--rehearse``, a state of
another type) the plain forms, which are also the kernels' reference. Both
kernels take a layer's WHOLE state array ``[rows, value heads, d_k, d_v]``,
aliased in and out, and touch the rows the dispatch names and no other: a
value head's ``[d_k, d_v]`` passes through VMEM once a dispatch.

- ``gdn_step_rows``: grid (slot row, ``STEP_HEADS`` value heads). A head's
  state is read once, ``S^T k`` and ``S^T q`` summed down its rows (k and q
  come in with the head's numbers down the sublanes, so nothing is
  transposed on chip), ``alpha S + k (x) d`` written once over the block it
  came from. The plain step reads a row's state twice; the rows past the
  slots, which it runs over to keep its update in place, the kernel never
  sees. 0.43 ms a layer at 64 slots of 32 heads of [128, 128] where the
  plain step takes 0.65 (my chip run, PR 58: 628 GB/s of one read and one
  write; 8 / 16 / 32 heads a program: 0.442 / 0.429 / 0.427).
- ``gdn_chunk_rows``: grid (row of the dispatch, value head; a key head's
  blocks stay in VMEM while its r value heads pass). The states the rows
  start from are gathered before the call (``state[rows[0]]``: 2 MB a row,
  0.55 against 0.58 ms a layer at two rows with the kernel's own DMA from
  the row), because EVERY row must be read before any is written: the
  scheduler lets a dispatch write a snapshot row that a warm admission
  riding it still starts from (``DecodeScheduler._snapshot_row``), the grid
  runs its rows one after the other over the aliased array, and the Pallas
  interpreter, which keeps the array it reads apart from the one it writes,
  cannot show the difference (on the chip: PERF.md section 6, PR 58). The
  kernel never reads the array it writes. A row's state goes by one DMA
  each to the rows ``rows[1]`` and ``rows[2]`` name (an index outside the
  array: nowhere): no scatter, no copy of the array. Between them the row's
  tokens in blocks of ``KERNEL_BLOCK`` = 128, everything between q, k, v,
  log alpha, beta and o in VMEM: the decay matrix (masked before the
  exponential), A, its inverse, U and W in one product, ``[W; Q] S``, ``[P;
  (left k)^T] D``, the update (k^T comes in beside k, so nothing is
  transposed on chip). The inverse (``_inverse_whole``): diagonal blocks of
  ``SOLVE_ROWS`` = 16 rows by FORWARD SUBSTITUTION down the rows on the
  vector unit (15 steps, every diagonal block's row i in one), the halvings
  above them by the block formula as masked whole-tile products. The
  whole-block Neumann product stays out for the reason above; the Neumann
  product of 16-row blocks read 0.60 ms a layer over two rows of 256 where
  the substitution read 0.49, and substituting 16, 32 or 64 rows the same to
  2%; blocks of 64 read 0.54 where 128 read 0.48 (a product's weights are
  loaded once for twice the rows) and 256 read 0.98 (all: my chip runs, PR
  58; six layers chained with every input of a layer hanging on the one
  before: a chain that shares k, v and beta between layers lets XLA solve
  once for all six and flatters the plain form, as PR 57's microbench did).
  What the kernel's SIZE costs decided its shape: with four heads a program
  and every block written out it read 0.48 ms a layer at two rows, and each
  of a program's 18 calls took Mosaic 4-8 s to compile (the cell's set-up
  551 s cold and 348 warm where the parent's are 260 and 63); one head a
  program and at most ``UNROLL_BLOCKS`` blocks written out compiles in 0.3 s
  a call. Both kernels are jitted: a program's 18 layers trace and lower one
  call.

Both are the same mathematics at the same precision: the state, the decay,
beta and every product float32 with float32 accumulation
(``Precision.HIGHEST`` inside the kernels too: six passes of the MXU a
product, none with an operand rounded to bfloat16 alone); on the chip at
Qwen3-Next's widths the kernels' worst difference from the token-by-token
recurrence is the plain forms' (1.6e-6 in o, 4.8e-6 against 5.8e-6 in the
state over 256 tokens; the step 7e-8 / 2.4e-7 both ways: my chip run, PR
58).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# ------------------------------------------------------------------------------
# The kernels: a head's state passes through VMEM once a dispatch

_LANES = 128
# tokens of one block of the chunk kernel, and the blocks of a row it writes out one after the other (a longer row's go
# round a loop: one block's code whatever the chunk's length)
KERNEL_BLOCK = 128
UNROLL_BLOCKS = 2
# rows of a diagonal block that the chunk kernel inverts by forward substitution (the halvings above it are products)
SOLVE_ROWS = _SOLVE_BASE
# value heads of one program of the step kernel: their states are one block, in and out
STEP_HEADS = 16
_VMEM_LIMIT = 32 << 20


def kernel_mode(dk: int, dv: int, dtype) -> str:
    """Which form a delta-rule layer of these widths runs, from what the
    program can see: "mosaic" (the kernels below) on a TPU where ``d_k`` and
    ``d_v`` are whole 128-lane tiles and the state rows are float32; ""
    (``gdn_step`` / ``gdn_chunk``) everywhere else: the CPU backend, a head
    off the tile, state rows of another type. Tests answer "interpret" here
    (the kernels under the Pallas interpreter, any widths)."""
    tiled = dk % _LANES == 0 and dv % _LANES == 0 and jnp.dtype(dtype) == jnp.float32
    return "mosaic" if tiled and jax.default_backend() == "tpu" else ""


def _mm(a, b):
    """a . b, a float32 product on the MXU at ``_PRECISION`` (six passes: no operand goes in rounded to bfloat16 alone)."""
    return jnp.dot(a, b, precision=_PRECISION, preferred_element_type=jnp.float32)


def _inverse_whole(t_ref, a, a_t, width: int):
    """(I + a)^-1 of strictly lower-triangular a [c, c] with no sub-block
    sliced out (Mosaic takes whole tiles). The diagonal blocks of ``width``
    rows by a forward substitution down their rows on the vector unit, every
    block's row i in one step: row i of ``T`` is ``e_i - sum_{s<i} A[i, s]
    T[s]``, built in ``t_ref`` [c, c] (``a_t``, a's transpose, has row s's
    weight down a lane; it is zero from the diagonal down, so rows i and
    later of the block weigh nothing). Above ``width`` the module's block
    formula under masks: ``T - T A_off T`` with ``A_off`` the part of a inside
    the doubled blocks and outside the present ones is ``[[T11, 0], [-T22 A21
    T11, T22]]`` of every pair."""
    c = a_t.shape[0]
    row, col = lax.broadcasted_iota(jnp.int32, (c, c), 0), lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lane = lax.broadcasted_iota(jnp.int32, (1, c), 1)
    t_ref[...] = (row == col).astype(a_t.dtype)
    for i in range(1, width):
        above = min(-(-i // 8) * 8, width)  # the block's rows before row i, in whole sublane tiles
        for at in range(0, c, width):
            weighed = a_t[at : at + above, at + i : at + i + 1] * t_ref[at : at + above, :]
            t_ref[at + i : at + i + 1, :] = (lane == at + i).astype(a_t.dtype) - jnp.sum(weighed, axis=0, keepdims=True)
    inv = t_ref[...]
    same = lambda w: (row // w) == (col // w)  # noqa: E731
    while width < c:
        off = jnp.where(same(2 * width) & ~same(width), a, 0.0)
        inv = inv - _mm(_mm(inv, off), inv)
        width *= 2
    return inv


def _chunk_kernel(rows_ref, q_ref, k_ref, kt_ref, v_ref, gb_ref, s0_ref, s_any, o_ref, s_out, s_vmem, sem, t_ref, *, c, width, total):
    """One value head of one row of the dispatch: the state it starts from
    (``s0_ref``: gathered before the call, so every row is read before any
    is written) walked through the row's tokens in blocks of ``c``
    (``UNROLL_BLOCKS`` or fewer written out, more round a loop) and written
    to the rows ``rows[0]`` and ``rows[1]`` name (an index outside the array:
    nowhere). ``s_any`` is ``s_out``, aliased: not read here."""
    del s_any
    i, j = pl.program_id(0), pl.program_id(1)
    dk, dv = s_vmem.shape
    row, col = lax.broadcasted_iota(jnp.int32, (c, c), 0), lax.broadcasted_iota(jnp.int32, (c, c), 1)
    column = lambda x: jnp.sum(jnp.where(row == col, x, 0.0), axis=1, keepdims=True)  # noqa: E731  [1, c] -> [c, 1]

    def block(b, s):
        at = pl.ds(b * c, c) if isinstance(b, int) else pl.ds(pl.multiple_of(b * c, c), c)
        q, k, v, kt = q_ref[0, at, :], k_ref[0, at, :], v_ref[0, at, :], kt_ref[0, 0, b]
        g_r, beta_r = gb_ref[0, 0, b, 0:1, :], gb_ref[0, 0, b, 1:2, :]
        g, beta = column(g_r), column(beta_r)
        kq = _mm(jnp.concatenate([k, q], axis=0), kt)  # [2c, c]: k . k^T over q . k^T
        # the decay matrix and A's transpose (k . k^T is its own), masked BEFORE the exponential: no decay is divided by
        gam = jnp.where(row >= col, jnp.exp(jnp.where(row >= col, g - g_r, 0.0)), 0.0)
        a_t = jnp.where(col > row, beta_r * kq[:c] * jnp.exp(jnp.where(col > row, g_r - g, 0.0)), 0.0)
        a = jnp.where(row > col, beta * kq[:c] * gam, 0.0) if width < c else None
        eg = jnp.exp(g)
        uw = _mm(_inverse_whole(t_ref, a, a_t, width), jnp.concatenate([beta * v, (beta * eg) * k], axis=1))  # [c, dv | dk]
        g_end = jnp.sum(jnp.where(col[:1] == c - 1, g_r, 0.0), axis=1, keepdims=True)  # [1, 1]
        held = _mm(jnp.concatenate([uw[:, dv:], q], axis=0), s)  # [2c, dv]: W S over Q S
        # P, over what is left of a step at the block's end times k^T: both meet D
        moved = _mm(jnp.concatenate([kq[c:] * gam, jnp.exp(g_end - g_r) * kt], axis=0), uw[:, :dv] - held[:c])
        o_ref[0, at, :] = eg * held[c:] + moved[:c]
        return jnp.exp(jnp.broadcast_to(g_end, (1, dv))) * s + moved[c:]

    nb = q_ref.shape[1] // c
    if nb <= UNROLL_BLOCKS:
        s = s0_ref[0, 0]
        for b in range(nb):
            s = block(b, s)
        s_vmem[...] = s
    else:
        s_vmem[...] = lax.fori_loop(0, nb, block, s0_ref[0, 0])
    writes = [(rows_ref[w, i], pltpu.make_async_copy(s_vmem, s_out.at[rows_ref[w, i], j], sem.at[w])) for w in (0, 1)]
    for to, write in writes:
        pl.when((to >= 0) & (to < total))(write.start)
    for to, write in writes:
        pl.when((to >= 0) & (to < total))(write.wait)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gdn_chunk_rows(state, rows, q, k, v, log_alpha, beta, *, block: int | None = None, interpret: bool = False):
    """``gdn_chunk`` over the state rows where they lie, in ONE Pallas pass.
    state [R, Hk * r, dk, dv] float32, a layer's whole array; rows [3, n]
    int32: row i of the dispatch starts from ``state[rows[0, i]]`` and its
    state after the last token is written to ``state[rows[1, i]]`` and
    ``state[rows[2, i]]`` (an index outside the array is written nowhere, as
    ``.at[].set(mode="drop")``). EVERY row is read before any is written, as
    the plain path's gather and scatters have it (the scheduler lets a
    dispatch write a snapshot row that a warm admission riding the same
    dispatch still reads: ``DecodeScheduler._snapshot_row``): the n rows'
    states are gathered first ([n, Hk * r, dk, dv], 2 MB a row at
    Qwen3-Next's widths) and the kernel reads that copy, not the array it
    writes. q ... beta as ``gdn_chunk`` takes them. ``block``
    (``KERNEL_BLOCK``) changes no result. Returns (o [n, m, Hk, r, dv], the
    array, updated in place where it was donated): nothing is scattered and
    no other row is touched."""
    n, m, hk, dk = q.shape
    r, dv = v.shape[3:]
    hv, total = hk * r, state.shape[0]
    if state.shape != (total, hv, dk, dv) or state.dtype != jnp.float32:
        raise ValueError(f"state rows {list(state.shape)} {state.dtype} for {hv} heads of [{dk}, {dv}] float32")
    if interpret and jax.default_backend() != "cpu":
        raise ValueError("gdn_chunk_rows(interpret=True) is for the CPU backend")
    c = _solver_rows(min(block or KERNEL_BLOCK, m))
    c += -c % 8  # whole sublane tiles
    width = c
    while width > SOLVE_ROWS and width % 2 == 0:
        width //= 2
    pad = -m % c
    nb = (m + pad) // c
    tokens = lambda t: jnp.pad(t.reshape(n, m, -1), ((0, 0), (0, pad), (0, 0)))  # noqa: E731
    q, k, v, log_alpha, beta = (tokens(t) for t in (q, k, v, log_alpha, beta))
    # a block's own running decay and beta, a value head's tokens along the lanes: [n, hv, nb, 2, c]
    g = jnp.cumsum(log_alpha.reshape(n, nb, c, hv), axis=2)
    gb = jnp.moveaxis(jnp.stack([g, beta.reshape(n, nb, c, hv)], axis=2), -1, 1)
    kt = jnp.transpose(k.reshape(n, nb, c, hk, dk), (0, 3, 1, 4, 2))  # k^T a key head and block: [n, hk, nb, dk, c]
    rows = rows.astype(jnp.int32)
    start = state[rows[0]]  # the reads, all of them before the call that writes
    to = jnp.where(rows[1:] < 0, rows[1:] + total, rows[1:])  # as an index counts from the end
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, c=c, width=width, total=total),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, hv),
            in_specs=[
                # a key head's blocks stay in VMEM while its r value heads pass
                pl.BlockSpec((1, m + pad, dk), lambda i, j, *_: (i, 0, j // r)),
                pl.BlockSpec((1, m + pad, dk), lambda i, j, *_: (i, 0, j // r)),
                pl.BlockSpec((1, 1, nb, dk, c), lambda i, j, *_: (i, j // r, 0, 0, 0)),
                pl.BlockSpec((1, m + pad, dv), lambda i, j, *_: (i, 0, j)),
                pl.BlockSpec((1, 1, nb, 2, c), lambda i, j, *_: (i, j, 0, 0, 0)),
                pl.BlockSpec((1, 1, dk, dv), lambda i, j, *_: (i, j, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[pl.BlockSpec((1, m + pad, dv), lambda i, j, *_: (i, 0, j)), pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32), pltpu.SemaphoreType.DMA((2,)), pltpu.VMEM((c, c), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((n, m + pad, hv * dv), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},  # the state array, in place
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gdn_chunk",
    )(to, q, k, kt, v, gb, start, state)
    return o[:, :m].reshape(n, m, hk, r, dv), state


def _step_kernel(alpha_ref, beta_ref, qk_ref, kq_ref, v_ref, s_in, o_ref, s_out, *, r):
    """``STEP_HEADS`` value heads of one slot row: each head's state read
    once, ``S^T k`` and ``S^T q`` summed down its rows, ``alpha S + k (x) d``
    written once, over the block it came from."""
    i, j = pl.program_id(0), pl.program_id(1)
    hb = s_in.shape[1]
    hv = hb * pl.num_programs(1)
    for h in range(hb):
        head = i * hv + j * hb + h
        alpha, beta = alpha_ref[head], beta_ref[head]
        k = kq_ref[0, 0, :, h // r : h // r + 1]  # [dk, 1]: down the state's rows
        q = kq_ref[0, 0, :, hb // r + h // r : hb // r + h // r + 1]
        s = s_in[0, h]
        d = beta * (v_ref[0, h : h + 1, :] - alpha * jnp.sum(s * k, axis=0, keepdims=True))
        o_ref[0, h : h + 1, :] = alpha * jnp.sum(s * q, axis=0, keepdims=True) + qk_ref[head // r] * d
        s_out[0, h] = alpha * s + k * d


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_step_rows(state, q, k, v, log_alpha, beta, *, interpret: bool = False):
    """``gdn_step`` over the first n rows of a layer's state array where they
    lie, in ONE Pallas pass: state [R, Hk * r, dk, dv] float32 (R >= n; rows
    past n are not touched), q ... beta as ``gdn_step`` takes them (0 and 0:
    the row stands, to the bit). Returns (o [n, Hk, r, dv], the array,
    updated in place where it was donated)."""
    n, hk, dk = q.shape
    r, dv = v.shape[2:]
    hv = hk * r
    if state.shape[1:] != (hv, dk, dv) or state.shape[0] < n or state.dtype != jnp.float32:
        raise ValueError(f"state rows {list(state.shape)} {state.dtype} for {n} rows of {hv} heads of [{dk}, {dv}] float32")
    if interpret and jax.default_backend() != "cpu":
        raise ValueError("gdn_step_rows(interpret=True) is for the CPU backend")
    hb = STEP_HEADS if hv % STEP_HEADS == 0 and STEP_HEADS % r == 0 else hv
    # a block's key heads down the sublanes, k then q: [n, hv / hb, dk, 2 hb / r]
    kq = jnp.concatenate([t.reshape(n, hv // hb, hb // r, dk) for t in (k, q)], axis=2)
    scalars = pl.BlockSpec(memory_space=pltpu.SMEM)
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, r=r),
        grid=(n, hv // hb),
        in_specs=[
            scalars, scalars, scalars,
            pl.BlockSpec((1, 1, dk, 2 * hb // r), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, hb, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((n, hv, dv), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},  # the state array, in place
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gdn_step",
    )(
        jnp.exp(log_alpha).reshape(n * hv), beta.reshape(n * hv), jnp.sum(q * k, axis=-1).reshape(n * hk),
        jnp.swapaxes(kq, 2, 3), v.reshape(n, hv, dv), state,
    )
    return o.reshape(n, hk, r, dv), state
