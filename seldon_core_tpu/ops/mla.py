"""Multi-head latent attention (MLA) over the paged LATENT cache.

The cache row of a token is ``[c_kv | k_r | 0..]``: the normalised
``rank``-wide latent every head's key and value are projections of, then the
one rotated ``rope``-wide key all heads share, then zeros to whole 128-lane
tiles (models/mla_decoder.py writes it and says why; the page kind is
models/decoder.py ``kv_pool_zeros``' one-plane pool). Nothing here
ever holds a token's per-head keys and values for a whole context: a head's
``k_nope = Wuk_h c_kv`` and ``v = Wuv_h c_kv`` are slices of ONE stored
``kv_b`` [rank, heads * (nope + v)], and the two paths differ in where those
products go:

- ABSORBED (the decode step; a short chunk against a long cache): the
  query is folded through ``Wuk`` first, ``qt_h = Wuk_h^T q_nope_h``, scores
  and context are taken on the cache rows as they lie (``[qt_h | q_rope_h]``
  against the 576-wide row, probabilities against its first 512), and
  ``Wuv`` is applied to the 512-wide context after. All heads read the
  SAME row: a page is fetched once for 64 heads;
- EXPANDED (a long chunk): each block of cached rows goes through ``kv_b``
  once, into that block's per-head ``k_nope`` and ``v``, and the chunk's
  queries score against those. Per cached row the expansion costs
  ``rank * heads * (nope + v)`` multiply-adds whatever the chunk's length,
  the absorbed path ``heads * (2 * rank + rope)`` a QUERY: ``expand_cheaper``
  is that arithmetic (171 queries at the published sizes), asked with a
  program's STATIC chunk length. A program of a long chunk also looks at the
  dispatch it is given: where every row's LIVE queries are few (``live`` at
  most ``short``: a wave of 64-token tails riding the 256-token entry of the
  chunk ladder), it absorbs those and leaves the padding rows zero, because
  the expansion and the scores of 192 queries nobody reads were 120 to 260
  ms of such a round (my chip run, PR 37; PERF.md section 6).

Both walk a row's block table in blocks of pages, up to the longest live
row's length and no further (a ``while`` loop with a traced trip count), with
a float32 running maximum, sum and context (the online softmax): what is
gathered at a time is one block of rows in the POOL'S dtype, ``[rows, block,
rank + rope]``, never the tables' whole length and never float32. A block's
float32 scores stay under ``_SCORES_BLOCK_BYTES``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# device scopes, nested under the decoder's ``attn`` scope
SCOPE_MLA_ABSORB = "mla_absorb"  # the Wuk product on the queries, the Wuv product on the context
SCOPE_MLA_CORE = "mla_core"  # page fetches, scores, softmax, context over the latent rows
SCOPE_MLA_EXPAND = "mla_expand"  # kv_b over a block of cached rows (the expanded path)

NEG_INF = -1e30  # the other families' mask value
# a block's float32 scores [rows, heads, queries, keys]: the (64, 256) chunk
# program at the floor of 128 keys a block is 512 MB, a step of 64 slots at
# the cap of 1024 is 16 MB
_SCORES_BLOCK_BYTES = 256 << 20
_MIN_BLOCK_KEYS, _MAX_BLOCK_KEYS = 128, 1024


def expand_cheaper(queries: int, *, rank: int, nope: int, rope: int, v_dim: int) -> bool:
    """Whether a dispatch of ``queries`` a row does fewer multiply-adds a
    cached row and head EXPANDED (``rank * (nope + v)`` for the row's keys and
    values, then ``nope + rope + v`` a query) than absorbed (``2 * rank +
    rope`` a query)."""
    return rank * (nope + v_dim) + queries * (nope + rope + v_dim) < queries * (2 * rank + rope)


def absorb_short(*, rank: int, nope: int, rope: int, v_dim: int) -> int:
    """The live queries a row up to which a long chunk's program absorbs
    instead (``mla_paged_attention``'s ``short``): the largest power of two
    that ``expand_cheaper`` still calls cheaper absorbed (128 at the
    published sizes)."""
    limit = rank * (nope + v_dim) // (2 * rank + rope - (nope + rope + v_dim))
    return 1 << (max(limit, 1).bit_length() - 1)


def block_pages(rows: int, heads: int, queries: int, page_size: int, pages_per_row: int) -> int:
    """Pages of one block of the walk: as many keys as keep the block's
    float32 scores under ``_SCORES_BLOCK_BYTES``, between the floor and the
    cap, in whole pages, at most the table."""
    keys = _SCORES_BLOCK_BYTES // (4 * rows * heads * queries)
    keys = min(max(keys, _MIN_BLOCK_KEYS), _MAX_BLOCK_KEYS)
    return max(1, min(keys // page_size, pages_per_row))


def mla_paged_attention(
    q_nope, q_rope, plane, li: int, bt, q_pos, n_keys, kv_b,
    *, scale: float, expand: bool, short: int = 0, live=None,
):
    """Causal attention of q_nope[n, m, H, nope] / q_rope[n, m, H, rope]
    (rotated), row i's query j at absolute position q_pos[i, j], over layer
    ``li`` of the latent ``plane`` [L, P, ps, >= rank + rope] through the block
    tables bt[n, pages]. ``n_keys`` [n] int32: the leading keys of its table
    a row needs (its last query's position + 1; 1 for a row nobody reads:
    the walk stops at the largest). ``kv_b`` [rank, H, nope + v]: a head's
    columns are ``[Wuk_h | Wuv_h]``. ``expand`` (static) picks the path
    (module docstring); with it, ``short`` (static) and ``live`` (a traced
    scalar: the most queries any row of THIS dispatch really has) let a
    dispatch whose rows all have at most ``short`` absorb those and return
    zeros for the rest. Returns the heads' outputs [n, m, H * v] in the
    queries' dtype."""
    m = q_nope.shape[1]
    walk = functools.partial(_walk, plane=plane, li=li, bt=bt, n_keys=n_keys, kv_b=kv_b, scale=scale)
    if not (expand and live is not None and 0 < short < m):
        return walk(q_nope, q_rope, q_pos, expand=expand)

    def few():
        out = walk(q_nope[:, :short], q_rope[:, :short], q_pos[:, :short], expand=False)
        return jnp.pad(out, ((0, 0), (0, m - short), (0, 0)))

    return lax.cond(live <= short, few, lambda: walk(q_nope, q_rope, q_pos, expand=True))


def _walk(q_nope, q_rope, q_pos, *, plane, li, bt, n_keys, kv_b, scale, expand):
    """``mla_paged_attention`` on one path for the queries given."""
    n, m, heads, nope = q_nope.shape
    rope = q_rope.shape[-1]
    dtype = q_nope.dtype
    ps = plane.shape[2]
    w = kv_b.astype(dtype)  # [rank, H, nope + v]
    rank = w.shape[0]
    v_dim = w.shape[2] - nope
    bp = block_pages(n, heads, m, ps, bt.shape[1])
    keys = bp * ps
    n_blocks = -(-bt.shape[1] // bp)
    bt = jnp.pad(bt, ((0, 0), (0, n_blocks * bp - bt.shape[1])))  # junk page 0: past every query
    if not expand:
        with jax.named_scope(SCOPE_MLA_ABSORB):
            qt = jnp.einsum("nmhd,rhd->nmhr", q_nope, w[:, :, :nope])
            # against the row as it lies, its padding lanes included: [n, m, H, row width]
            pad = jnp.zeros((n, m, heads, plane.shape[3] - rank - rope), dtype)
            qc = jnp.concatenate([qt, q_rope, pad], axis=-1)

    def block(j, carry):
        top, total, acc = carry  # [n, H, m] float32 twice, [n, H, m, rank | v] float32
        rows = plane[li, lax.dynamic_slice_in_dim(bt, j * bp, bp, axis=1)]  # [n, bp, ps, row width]
        rows = rows.reshape(n, keys, -1).astype(dtype)
        if expand:
            with jax.named_scope(SCOPE_MLA_EXPAND):
                kv = jnp.einsum("nkr,rhe->nkhe", rows[..., :rank], w)  # this block's heads, nowhere else
            s = jnp.einsum("nmhd,nkhd->nhmk", q_nope, kv[..., :nope], preferred_element_type=jnp.float32)
            s += jnp.einsum("nmhd,nkd->nhmk", q_rope, rows[..., rank : rank + rope], preferred_element_type=jnp.float32)
        else:
            s = jnp.einsum("nmhc,nkc->nhmk", qc, rows, preferred_element_type=jnp.float32)
        k_pos = j * keys + jnp.arange(keys, dtype=q_pos.dtype)
        seen = k_pos[None, None, :] <= q_pos[:, :, None]  # [n, m, keys]
        s = jnp.where(seen[:, None], s * scale, NEG_INF)
        new_top = jnp.maximum(top, jnp.max(s, axis=-1))
        shrink = jnp.exp(top - new_top)
        p = jnp.exp(s - new_top[..., None])
        if expand:
            ctx = jnp.einsum("nhmk,nkhv->nhmv", p.astype(dtype), kv[..., nope:], preferred_element_type=jnp.float32)
        else:
            ctx = jnp.einsum("nhmk,nkr->nhmr", p.astype(dtype), rows[..., :rank], preferred_element_type=jnp.float32)
        return new_top, total * shrink + jnp.sum(p, axis=-1), acc * shrink[..., None] + ctx

    with jax.named_scope(SCOPE_MLA_CORE):
        # key 0 is seen by every query, so the first block gives every row a
        # real maximum; a block wholly past a row's queries adds exp(-1e30 - top) = 0
        live_blocks = (jnp.max(n_keys) + keys - 1) // keys
        init = (
            jnp.full((n, heads, m), NEG_INF, jnp.float32),
            jnp.zeros((n, heads, m), jnp.float32),
            jnp.zeros((n, heads, m, v_dim if expand else rank), jnp.float32),
        )
        _, total, acc = lax.fori_loop(0, jnp.clip(live_blocks, 1, n_blocks), block, init)
        ctx = (acc / total[..., None]).astype(dtype)
    if expand:
        return ctx.transpose(0, 2, 1, 3).reshape(n, m, heads * v_dim)
    with jax.named_scope(SCOPE_MLA_ABSORB):
        return jnp.einsum("nhmr,rhv->nmhv", ctx, w[:, :, nope:]).reshape(n, m, heads * v_dim)
