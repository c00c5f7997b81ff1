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

Both are ONE algorithm, an online softmax over blocks of a row's block
table with a float32 running maximum, sum and context, and there are two ways
to bring a block in, picked by the pool, the platform and the dispatch's
static shape (no knob; ``serving/decode_programs._step_attn_kernel`` and
``kernel_takes``):

- THE WALK (``_walk``; blocked ``jnp``): a ``while`` loop with a traced trip
  count over blocks of pages, up to the longest live row's length and no
  further; what is gathered at a time is one block of rows in the POOL'S
  dtype, ``[rows, block, rank + rope]``, never the tables' whole length and
  never float32, and a block's float32 scores stay under
  ``_SCORES_BLOCK_BYTES``. It goes through HBM for all of it: the gathered
  block, an expanded block's per-head ``kv``, the scores and the
  probabilities are each written and read back. The CPU backend (where it is
  the oracle of both kernels), a mesh, a geometry Mosaic cannot tile
  (``kernel_tiles``, ``kernel_takes``); ``expand_cheaper`` and
  ``absorb_short`` choose its path there;
- THE KERNELS (Pallas; one two-byte float plane of whole lane tiles and
  16-row pages on one TPU), always ABSORBED: the queries are folded through
  ``Wuk`` outside (``_folded_queries``), ``Wuv`` is applied to the context
  outside, and in between the plane stays in HBM, a row's pages are fetched
  into VMEM in runs of consecutive pages with one DMA a run
  (``page_runs``) with the next block in flight, and scores, probabilities,
  maximum, sum and context never leave VMEM (``_block_copies`` and
  ``_softmax_block`` are what the two share):

  - THE STEP'S (``mla_decode_attention``): ONE query a row, the fused decode
    step. A grid step is a row: its pages fetched once for all heads, up to
    the row's own length. The walk moved every gathered block four times and
    took 20.4 ms alone at the a.x-k1 cell's geometry where the kernel takes
    7.2 (PERF.md section 6, PR 38);
  - THE CHUNK'S (``mla_chunk_attention``): ``m`` > 1 consecutive queries a
    row, every prefill chunk whatever its length. A grid step is a row's
    query block (``CHUNK_Q_ROWS`` query-head rows: whole queries by all
    heads, which read the SAME latent rows, so a key block fetched once
    serves them all on the MXU); causal by position, ragged by row: a key
    block wholly past a query block's last live position is neither fetched
    nor scored, a row stops at its own length, and a row with no live query
    (a padding row of the chunk ladder's entry) or a query block past a row's
    live queries costs nothing and returns zeros, which is what
    ``absorb_short`` saved the walk with a second program body. It does more
    multiply-adds than the expanded walk at 256 queries (1,152 a query-head
    and key against ~330 + the expansion) and wins because nothing is
    materialised: the walk's (2, 256) dispatch of the xing cell wrote 67 MB
    of float32 scores and 33 MB of expanded ``kv`` a block to HBM and read
    them back (PERF.md section 6, PR 45).

  ``interpret=True`` runs either under the Pallas interpreter, for the CPU
  backend's tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
_LANES = 128
# The step's kernel: table entries it fetches with ONE DMA where their pages
# are consecutive, and entries of one work item (a block: fetched, scored and
# summed together). Picked by the kernel alone on a v5e at the a.x-k1 cell's
# geometry, 64 rows of 8.3-8.5k keys over 7 layers (PERF.md section 6, PR 38):
# blocks of 64 pages with runs of 8 / 16 / 32 took 7.44 / 7.21 / 7.17 ms
# (a DMA a page 16.6 / 15.0 / 14.4; the DMAs alone 6.56 in runs, 9.7-12.0 a
# page), runs of 32 in blocks of 32 / 64 / 128 / 256 pages 8.19 / 7.17 / 7.45 /
# 8.22 (a block's products and softmax are one serial chain of ~0.4 us before
# any arithmetic: short blocks pay it often, long ones compute a row's
# half-empty last block). 16 and not 32: within the timing's noise, and a run
# that breaks sends half as many pages to the DMA-a-page path.
RUN_PAGES = 16
BLOCK_PAGES = 64


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
    *, scale: float, expand: bool, short: int = 0, live=None, runs=None, counts=None, interpret: bool = False,
):
    """Causal attention of q_nope[n, m, H, nope] / q_rope[n, m, H, rope]
    (rotated), row i's query j at absolute position q_pos[i, j], over layer
    ``li`` of the latent ``plane`` [L, P, ps, >= rank + rope] through the block
    tables bt[n, pages]. ``n_keys`` [n] int32: the leading keys of its table
    a row needs (its last query's position + 1; 1 for a row nobody reads:
    the walk stops at the largest). ``kv_b`` [rank, H, nope + v]: a head's
    columns are ``[Wuk_h | Wuv_h]``. ``runs`` (``kernel_runs``: not None
    where the program set chose a kernel and the geometry lets this dispatch
    take it) sends the dispatch to a Pallas kernel instead of the walk, the
    step's for one query a row, the chunk's for more; the chunk's takes
    ``counts`` [n] int32 (the LEADING queries of a row somebody reads; None:
    all) and the positions must be consecutive, q_pos[i, j] = q_pos[i, 0] + j;
    ``interpret`` (static) runs either under the Pallas interpreter. On the
    walk ``expand`` (static) picks the path (module docstring); with it,
    ``short`` (static) and ``live`` (a traced scalar: the most queries any
    row of THIS dispatch really has) let a dispatch whose rows all have at
    most ``short`` absorb those and return zeros for the rest. Returns the
    heads' outputs [n, m, H * v] in the queries' dtype."""
    m = q_nope.shape[1]
    if runs is not None and m == 1:
        return _absorbed_step(q_nope, q_rope, plane, li, bt, n_keys, runs, kv_b, scale, interpret)
    if runs is not None:  # more queries a row: the chunk's kernel
        return _absorbed_chunk(q_nope, q_rope, plane, li, bt, q_pos[:, 0], n_keys, counts, runs, kv_b, scale, interpret)
    walk = functools.partial(_walk, plane=plane, li=li, bt=bt, n_keys=n_keys, kv_b=kv_b, scale=scale)
    if not (expand and live is not None and 0 < short < m):
        return walk(q_nope, q_rope, q_pos, expand=expand)

    def few():
        out = walk(q_nope[:, :short], q_rope[:, :short], q_pos[:, :short], expand=False)
        return jnp.pad(out, ((0, 0), (0, m - short), (0, 0)))

    return lax.cond(live <= short, few, lambda: walk(q_nope, q_rope, q_pos, expand=True))


def _folded_queries(q_nope, q_rope, w, row_width: int):
    """The absorbed path's queries against a cache row as it lies, its
    padding lanes included: ``[Wuk_h^T q_nope_h | q_rope_h | 0]``,
    [..., H, row width] for q_nope[..., H, nope] / q_rope[..., H, rope]."""
    nope = q_nope.shape[-1]
    with jax.named_scope(SCOPE_MLA_ABSORB):
        qt = jnp.einsum("...hd,rhd->...hr", q_nope, w[:, :, :nope])
        pad = jnp.zeros((*q_nope.shape[:-1], row_width - w.shape[0] - q_rope.shape[-1]), q_nope.dtype)
        return jnp.concatenate([qt, q_rope, pad], axis=-1)


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
        qc = _folded_queries(q_nope, q_rope, w, plane.shape[3])

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


# ------------------------------------------------------------------------------
# The step's kernel: one query a row, the latent rows read where they lie


def kernel_tiles(row_width: int, page_size: int, dtype) -> bool:
    """Whether Mosaic can tile ``mla_decode_attention`` over a latent plane
    of this geometry: what ``decode_programs._step_attn_kernel`` asks before
    it answers "mosaic" for a one-plane pool. A two-byte float (both
    products go into the MXU as they are stored), rows of whole 128-lane
    tiles, pages of whole sublane tiles (16 rows of a two-byte float: a page
    is the destination of one DMA and a slice of the block the MXU takes)."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize != 2:
        return False
    return row_width % _LANES == 0 and page_size % 16 == 0


def _table_blocks(pages: int, block_pages: int | None = None) -> tuple[int, int, int]:
    """How a kernel walks a table of ``pages`` entries in blocks of
    ``block_pages`` (the step's ``BLOCK_PAGES`` where None): (entries a run
    DMA takes, runs a block, blocks a table). A block is what one work item
    fetches and computes on; small tables (the tests') shrink both."""
    run = min(RUN_PAGES, pages)
    block_runs = min(max((BLOCK_PAGES if block_pages is None else block_pages) // RUN_PAGES, 1), -(-pages // run))
    return run, block_runs, -(-pages // (run * block_runs))


def _pages_held(n_keys, page_size: int, pages: int):
    """Pages of its table each row attends over: ``ceil(n_keys / page_size)``
    (a length past the table is the table, one under a key is a key)."""
    return -(-jnp.clip(n_keys, 1, pages * page_size) // page_size)


def kernel_takes(kernel: str, queries: int, rank: int, heads: int) -> bool:
    """Whether a dispatch of ``queries`` a row takes a kernel (``kernel``:
    ``decode_programs._step_attn_kernel``'s answer, "" | "mosaic" |
    "interpret"): one query a row the step's, more the chunk's, whatever
    their number; for Mosaic a latent of whole lane tiles (the context
    product takes the rows' first ``rank`` lanes) and, in a chunk, query
    blocks of whole sublane tiles (``_query_block`` queries by ``heads``).
    Static: what a program and the scheduler's annotation of its dispatches
    both ask."""
    if not kernel or kernel == "interpret":
        return bool(kernel)
    tq = _query_block(queries, heads)
    return rank % _LANES == 0 and (tq == queries or tq * heads % 16 == 0)


def kernel_runs(kernel: str, queries: int, rank: int, heads: int, bt, n_keys, page_size: int):
    """``page_runs`` where a dispatch of ``queries`` a row takes a kernel
    (``kernel_takes``); None where it walks."""
    if not kernel_takes(kernel, queries, rank, heads):
        return None
    return page_runs(bt, n_keys, page_size)


def page_runs(bt, n_keys, page_size: int):
    """Which groups of ``RUN_PAGES`` consecutive table entries the kernel
    fetches with ONE DMA: runs[n, groups] int32 (the table's groups, padded
    to whole blocks), 1 where the row has all ``RUN_PAGES`` pages of the
    group and their ids are consecutive (``plane[li, first : first +
    RUN_PAGES]`` is then contiguous in HBM and inside the plane, its last
    page being a table entry), else 0: a DMA a page, for the pages the row
    has. The kernel's own view of the table: bt[n, pages], n_keys[n] as
    ``mla_paged_attention`` takes them."""
    n, pages = bt.shape
    run, block_runs, blocks = _table_blocks(pages)
    groups = blocks * block_runs
    ids = jnp.pad(bt, ((0, 0), (0, groups * run - pages))).reshape(n, groups, run)
    consecutive = jnp.all(ids == ids[..., :1] + jnp.arange(run, dtype=bt.dtype), axis=-1)
    held = _pages_held(n_keys, page_size, pages)
    whole = (jnp.arange(groups, dtype=held.dtype)[None, :] + 1) * run <= held[:, None]
    return (consecutive & whole).astype(jnp.int32)


def pages_fetched(n_keys, runs, live, page_size: int, pages: int):
    """int32[2]: the pages the kernel fetches for the ``live`` rows ([n]
    bool), and those among them that come in run DMAs; one layer's. What the
    program counts into FlightFrame ``mla_pages_read`` / ``mla_run_pages``."""
    held = _pages_held(n_keys, page_size, pages)
    in_runs = jnp.sum(runs, axis=1) * _table_blocks(pages)[0]
    return jnp.sum(jnp.where(live[:, None], jnp.stack([held, in_runs], axis=1), 0), axis=0, dtype=jnp.int32)


def _block_copies(plane_hbm, layer, bt_ref, run_ref, n_pages, buf, sem, run: int, block_runs: int, row, blk, b, fn):
    """``fn`` (``_start`` or ``_wait``) on the DMAs of block ``blk`` of
    ``row``'s table into buffer ``b``, group by group: one for a run, else
    one a page the row has (``n_pages(row)``)."""
    for j in range(block_runs):
        g = blk * block_runs + j
        first = g * run

        @pl.when(run_ref[row, g] == 1)
        def _():
            src = plane_hbm.at[layer, pl.ds(bt_ref[row, first], run)]
            fn(pltpu.make_async_copy(src, buf.at[b, pl.ds(j * run, run)], sem.at[b]))

        @pl.when(run_ref[row, g] == 0)
        def _():
            def page(k, _):
                fn(pltpu.make_async_copy(plane_hbm.at[layer, bt_ref[row, first + k]], buf.at[b, j * run + k], sem.at[b]))
                return 0

            lax.fori_loop(0, jnp.clip(n_pages(row) - first, 0, run), page, 0)


def _start(c):
    c.start()


def _wait(c):
    c.wait()


def _softmax_block(q, rows, seen, top_ref, sum_ref, acc_ref, rank: int, scale: float, values=None):
    """One block of the online softmax in a kernel: the queries q[r, w]
    scored against the block's rows[keys, w] on the MXU, keys outside
    ``seen()`` (broadcast to [r, keys]) at probability exactly 0, the
    probabilities, cast to the rows' dtype as ``_walk`` casts them, multiplied
    into the same rows' first ``rank`` lanes (the latent kernels) or into
    ``values`` [keys, v] (ops/gqa_decode.py's chunk kernel); maximum, sum and
    context (float32 scratch) updated in place."""
    s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    s = jnp.where(seen(), s * scale, NEG_INF)  # [r, keys]
    top = top_ref[...]
    new_top = jnp.maximum(top, jnp.max(s, axis=1, keepdims=True))
    shrink = jnp.exp(top - new_top)
    p = jnp.exp(s - new_top)
    ctx = jnp.dot(p.astype(rows.dtype), rows[:, :rank] if values is None else values, preferred_element_type=jnp.float32)
    top_ref[...] = new_top
    sum_ref[...] = sum_ref[...] * shrink + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * shrink + ctx


def _decode_kernel(
    layer_ref, bt_ref, len_ref, run_ref,  # scalar prefetch
    q_ref, plane_hbm,  # row i's folded queries [1, H, w]; the whole plane, left in HBM
    o_ref,  # row i's normalised context [1, H, rank]
    buf, top_ref, sum_ref, acc_ref, sem, cur,  # scratch
    *, page_size: int, run: int, block_runs: int, rank: int, scale: float,
):
    """Grid step i is row i: its blocks of pages in turn, always with the
    next block's rows in flight (the next ROW's first block after the last),
    the online softmax's state in scratch. ``cur`` carries which of the two
    buffers the row's first block was fetched into."""
    i, n = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    block = run * block_runs
    keys = block * page_size

    def n_pages(row):
        return (len_ref[row] + page_size - 1) // page_size

    def n_blocks(row):
        return (n_pages(row) + block - 1) // block

    copies = functools.partial(_block_copies, plane_hbm, layer, bt_ref, run_ref, n_pages, buf, sem, run, block_runs)

    @pl.when(i == 0)
    def _():
        # pages a block does not fetch hold what the buffer held: keep it finite
        buf[...] = jnp.zeros_like(buf)
        cur[0] = 0
        copies(0, 0, 0, _start)

    nb, b0 = n_blocks(i), cur[0]
    top_ref[...] = jnp.full_like(top_ref, NEG_INF)
    sum_ref[...] = jnp.zeros_like(sum_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def one_block(blk, _):
        b = (b0 + blk) % 2

        @pl.when(blk + 1 < nb)
        def _():
            copies(i, blk + 1, 1 - b, _start)

        @pl.when((blk + 1 >= nb) & (i + 1 < n))
        def _():
            copies(i + 1, 0, 1 - b, _start)

        copies(i, blk, b, _wait)
        rows = buf[b].reshape(keys, buf.shape[-1])  # this block's rows, fetched once, used twice

        def seen():
            return blk * keys + lax.broadcasted_iota(jnp.int32, (1, keys), 1) < len_ref[i]

        _softmax_block(q_ref[0], rows, seen, top_ref, sum_ref, acc_ref, rank, scale)
        return 0

    lax.fori_loop(0, nb, one_block, 0)
    cur[0] = (b0 + nb) % 2
    o_ref[0] = (acc_ref[...] / sum_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def mla_decode_attention(qc, plane, layer, bt, n_keys, runs, *, rank: int, scale: float, interpret: bool = False):
    """The absorbed attention of ``n`` rows of ONE query each over the
    latent plane, read in place.

    qc ``[n, H, w]`` (the folded queries, ``_folded_queries``), plane
    ``[L, P, ps, w]`` (the whole plane, left in HBM), ``layer`` the layer to
    read, bt ``[n, pages]`` int32, n_keys ``[n]`` int32 (the leading keys of
    its table a row attends over), runs ``page_runs(bt, n_keys, ps)``.
    Returns the normalised context ``[n, H, rank]`` in qc's dtype: for each
    row and head softmax(scale * qc . rows[:n_keys]) . rows[:n_keys, :rank].

    Row i's table is walked in blocks of ``BLOCK_PAGES`` entries; a block's
    rows are fetched into VMEM ONCE, each group of ``RUN_PAGES`` entries
    with one DMA where ``runs`` says it is a run of consecutive pages, else
    a DMA a page; they are scored against all heads on the MXU, and the
    probabilities, cast to the plane's dtype as ``_walk`` casts them,
    multiplied into the same rows' first ``rank`` lanes; maximum, sum and
    context stay float32. Keys past ``n_keys`` get probability exactly 0
    and pages past ``ceil(n_keys / ps)`` are never fetched. Nothing is
    shared between rows.

    Jitted with ``layer`` traced: a step's calls lower to Mosaic once."""
    n, heads, w = qc.shape
    _, _, ps, pw = plane.shape
    pages = bt.shape[1]
    if pw != w or qc.dtype != plane.dtype:
        raise ValueError(f"queries {qc.dtype}{list(qc.shape)} against plane rows {plane.dtype}{list(plane.shape)}")
    if interpret and jax.default_backend() != "cpu":
        raise ValueError("mla_decode_attention(interpret=True) is for the CPU backend")
    if not interpret and not (kernel_tiles(w, ps, plane.dtype) and rank % _LANES == 0):
        raise ValueError(
            f"mla_decode_attention cannot tile {plane.dtype} rows of {w} (latent {rank}) in pages of {ps} "
            "for Mosaic (kernel_tiles): this geometry keeps the walk"
        )
    run, block_runs, blocks = _table_blocks(pages)
    block = run * block_runs
    kernel = functools.partial(_decode_kernel, page_size=ps, run=run, block_runs=block_runs, rank=rank, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, heads, w), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, rank), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, ps, w), plane.dtype),  # the block in use and the one in flight
                pltpu.VMEM((heads, 1), jnp.float32),  # running maximum
                pltpu.VMEM((heads, 1), jnp.float32),  # running sum
                pltpu.VMEM((heads, rank), jnp.float32),  # running context
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, heads, rank), qc.dtype),
        # a row's first block is started by the row before it
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.pad(bt.astype(jnp.int32), ((0, 0), (0, blocks * block - pages))),
        jnp.clip(n_keys.astype(jnp.int32), 1, pages * ps), runs.astype(jnp.int32),
        qc, plane,
    )


def _absorbed_step(q_nope, q_rope, plane, li, bt, n_keys, runs, kv_b, scale, interpret):
    """``mla_paged_attention`` for one query a row through the kernel: the
    queries folded through ``Wuk`` and ``Wuv`` on the context outside it, as
    ``_walk`` has them."""
    n, nope = q_nope.shape[0], q_nope.shape[-1]
    w = kv_b.astype(q_nope.dtype)
    rank = w.shape[0]
    with jax.named_scope(SCOPE_MLA_ABSORB):
        q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]  # the one query a row
    qc = _folded_queries(q_nope, q_rope, w, plane.shape[3])  # [n, H, row width]
    with jax.named_scope(SCOPE_MLA_CORE):
        ctx = mla_decode_attention(qc, plane, li, bt, n_keys, runs, rank=rank, scale=scale, interpret=interpret)
    with jax.named_scope(SCOPE_MLA_ABSORB):
        return jnp.einsum("nhr,rhv->nhv", ctx, w[:, :, nope:]).reshape(n, 1, -1)


# ------------------------------------------------------------------------------
# The chunk's kernel: many queries a row, causal by position, the same pages

# Query-head rows of one work item (whole queries: 32 of 32 heads, 16 of 64)
# and table entries of one key block. Picked on a v5e by the attention alone
# (fold, kernel, Wuv; every layer; PERF.md section 6, PR 45) at the two latent
# cells' geometries: a (2, 256) dispatch of 32 heads over 1.5-2k keys in 20
# layers took 15.4 / 12.9 / 11.6 ms at 256 / 512 / 1024 rows in blocks of 32
# pages (the walk 16.8), a (4, 256) one 29.1 / 24.4 / 22.1 (50.3), a (2, 64)
# one of 64 heads over 8.3k keys in 7 layers 9.7 / 8.5 / 8.0 (9.9): a key
# block fetched serves more rows, and a block's serial chain is paid less
# often. At 1024 rows, blocks of 32 / 64 pages: 11.6 / 12.6, 22.1 / 24.2, 8.0 /
# 8.4 (a chunk's last key block is half past its queries' positions: short
# blocks compute less of it); 16 pages at 512 rows lost to 32 everywhere.
CHUNK_Q_ROWS = 1024
CHUNK_BLOCK_PAGES = 32


def _query_block(queries: int, heads: int) -> int:
    """Queries of one work item of the chunk's kernel: the largest divisor
    of the chunk's length whose query-head rows are at most ``CHUNK_Q_ROWS``
    (one query where its heads alone are more)."""
    cap = min(max(CHUNK_Q_ROWS // heads, 1), queries)
    return max(d for d in range(1, cap + 1) if queries % d == 0)


def _chunk_kernel(
    layer_ref, bt_ref, len_ref, pos_ref, cnt_ref, next_ref, run_ref,  # scalar prefetch
    q_ref, plane_hbm,  # row i's query block j, folded [1, tq * H, w]; the whole plane, left in HBM
    o_ref,  # its normalised context [1, tq * H, rank]
    buf, top_ref, sum_ref, acc_ref, sem, cur,  # scratch
    *, page_size: int, run: int, block_runs: int, rank: int, scale: float, heads: int, tq: int,
):
    """Grid step (i, j) is query block j of row i, ``tq`` queries by all
    heads (score row r is query ``j * tq + r // heads``). A block with a
    query somebody reads (``j * tq < cnt[i]``) walks the row's key blocks up
    to its last such query's position and no further, always with the next
    block's rows in flight: its own next, else the first block of the next
    work item (the row's next query block, else query block 0 of the next
    row with a query: ``next_ref``). Every other grid step writes zeros and
    touches neither the plane nor the MXU. ``cur`` carries which of the two
    buffers the next work item's first block was fetched into."""
    i, j, n = pl.program_id(0), pl.program_id(1), pl.num_programs(0)
    layer = layer_ref[0]
    keys = run * block_runs * page_size

    def n_pages(row):
        return (len_ref[row] + page_size - 1) // page_size

    def items(row):
        return (cnt_ref[row] + tq - 1) // tq  # its query blocks with a query somebody reads

    copies = functools.partial(_block_copies, plane_hbm, layer, bt_ref, run_ref, n_pages, buf, sem, run, block_runs)

    @pl.when((i == 0) & (j == 0))
    def _():
        # pages a block does not fetch hold what the buffer held: keep it finite
        buf[...] = jnp.zeros_like(buf)
        cur[0] = 0

        @pl.when(next_ref[0] < n)
        def _():
            copies(next_ref[0], 0, 0, _start)

    @pl.when(j >= items(i))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < items(i))
    def _():
        read = jnp.minimum((j + 1) * tq, cnt_ref[i])  # the row's queries up to this block's last that somebody reads
        nb = (jnp.minimum(pos_ref[i] + read, len_ref[i]) + keys - 1) // keys
        b0 = cur[0]
        more, after = j + 1 < items(i), next_ref[i + 1]
        top_ref[...] = jnp.full_like(top_ref, NEG_INF)
        sum_ref[...] = jnp.zeros_like(sum_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        query = j * tq + lax.broadcasted_iota(jnp.int32, (tq * heads, 1), 0) // heads
        # a query nobody reads sees what the row's last read one sees: finite, and no key past the row's length
        q_pos = pos_ref[i] + jnp.minimum(query, cnt_ref[i] - 1)

        def one_block(blk, _):
            b = (b0 + blk) % 2
            last = blk + 1 >= nb

            @pl.when(blk + 1 < nb)
            def _():
                copies(i, blk + 1, 1 - b, _start)

            @pl.when(last & more)
            def _():
                copies(i, 0, 1 - b, _start)

            @pl.when(last & jnp.logical_not(more) & (after < n))
            def _():
                copies(after, 0, 1 - b, _start)

            copies(i, blk, b, _wait)
            rows = buf[b].reshape(keys, buf.shape[-1])  # fetched once for the block's queries and all their heads

            def seen():
                return blk * keys + lax.broadcasted_iota(jnp.int32, (1, keys), 1) <= q_pos

            _softmax_block(q_ref[0], rows, seen, top_ref, sum_ref, acc_ref, rank, scale)
            return 0

        lax.fori_loop(0, nb, one_block, 0)
        cur[0] = (b0 + nb) % 2
        o_ref[0] = (acc_ref[...] / sum_ref[...]).astype(o_ref.dtype)


def mla_chunk_attention(
    qc, plane, layer, bt, n_keys, q_first, counts, runs, *, heads: int, rank: int, scale: float, interpret: bool = False
):
    """The absorbed causal attention of ``n`` rows of ``m`` consecutive
    queries each over the latent plane, read in place: the many-queries form
    of ``mla_decode_attention``, whose arguments it takes.

    qc ``[n, m * H, w]`` (the folded queries; row ``r`` is query ``r // H``,
    head ``r % H``), q_first ``[n]`` int32 the position of a row's query 0
    (query j sits at ``q_first + j`` and sees keys ``<= q_first + j``), counts
    ``[n]`` int32 its LEADING queries somebody reads, n_keys ``[n]`` =
    ``q_first + counts`` where counts > 0 (what ``runs`` were made from).
    Returns the normalised context ``[n, m * H, rank]`` in qc's dtype: ZEROS
    for a row of count 0, for which no page is fetched and nothing computed,
    and for every query block wholly past a row's count (``_query_block``
    queries a block); something finite for a query past the count inside a
    block that has one before it.

    The grid is (row, query block); a key block of ``CHUNK_BLOCK_PAGES``
    table entries, fetched as the step's kernel fetches (``runs``), serves
    the block's ``CHUNK_Q_ROWS`` query-head rows on the MXU, and the scores,
    the probabilities and the softmax state never leave VMEM. A key block
    wholly past a query block's last read position is neither fetched nor
    scored."""
    n, q_rows, w = qc.shape
    _, _, ps, pw = plane.shape
    m = q_rows // heads
    if pw != w or qc.dtype != plane.dtype or q_rows % heads:
        raise ValueError(f"queries {qc.dtype}{list(qc.shape)} of {heads} heads against plane rows {plane.dtype}{list(plane.shape)}")
    if interpret and jax.default_backend() != "cpu":
        raise ValueError("mla_chunk_attention(interpret=True) is for the CPU backend")
    tq = _query_block(m, heads)
    if not interpret and not (kernel_tiles(w, ps, plane.dtype) and kernel_takes("mosaic", m, rank, heads)):
        raise ValueError(
            f"mla_chunk_attention cannot tile {plane.dtype} rows of {w} (latent {rank}) in pages of {ps}, "
            f"{tq} of {m} queries by {heads} heads a block, for Mosaic (kernel_tiles): this geometry keeps the walk"
        )
    tiles = (tq, *_table_blocks(bt.shape[1], CHUNK_BLOCK_PAGES))
    if counts is None:
        counts = jnp.full((n,), m, jnp.int32)
    return _chunk_call(
        qc, plane, layer, bt, n_keys, q_first, counts, runs,
        heads=heads, rank=rank, scale=scale, interpret=interpret, tiles=tiles,
    )


@functools.partial(jax.jit, static_argnames=("heads", "rank", "scale", "interpret", "tiles"))
def _chunk_call(qc, plane, layer, bt, n_keys, q_first, counts, runs, *, heads, rank, scale, interpret, tiles):
    """``mla_chunk_attention`` at its tiles (queries a block; entries a run,
    runs a block, blocks a table). Jitted with ``layer`` traced: a chunk
    program's calls lower to Mosaic once."""
    n, q_rows, w = qc.shape
    ps = plane.shape[2]
    pages = bt.shape[1]
    tq, run, block_runs, blocks = tiles
    block, groups = run * block_runs, blocks * block_runs
    counts = jnp.clip(counts.astype(jnp.int32), 0, q_rows // heads)
    # next_live[0]: the first row with a query somebody reads; next_live[i + 1]: the first such row after i; n: none
    ids = jnp.where(counts > 0, jnp.arange(n, dtype=jnp.int32), n)
    next_live = jnp.concatenate([lax.cummin(ids, reverse=True), jnp.full((1,), n, jnp.int32)])
    runs = jnp.pad(runs.astype(jnp.int32), ((0, 0), (0, max(groups - runs.shape[1], 0))))[:, :groups]
    kernel = functools.partial(
        _chunk_kernel, page_size=ps, run=run, block_runs=block_runs, rank=rank, scale=scale, heads=heads, tq=tq
    )

    def q_block(i, j, _layer, _bt, _len, _pos, cnt, *_):
        # a block nobody reads names the row's last read one: not fetched again
        return i, jnp.minimum(j, jnp.maximum((cnt[i] + tq - 1) // tq - 1, 0)), 0

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(n, q_rows // (tq * heads)),
            in_specs=[
                pl.BlockSpec((1, tq * heads, w), q_block),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, tq * heads, rank), lambda i, j, *_: (i, j, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, ps, w), plane.dtype),  # the key block in use and the one in flight
                pltpu.VMEM((tq * heads, 1), jnp.float32),  # running maximum
                pltpu.VMEM((tq * heads, 1), jnp.float32),  # running sum
                pltpu.VMEM((tq * heads, rank), jnp.float32),  # running context
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, q_rows, rank), qc.dtype),
        # a work item's first block is started by the one before it
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="mla_chunk_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.pad(bt.astype(jnp.int32), ((0, 0), (0, blocks * block - pages))),
        jnp.clip(n_keys.astype(jnp.int32), 1, pages * ps), q_first.astype(jnp.int32), counts, next_live, runs,
        qc, plane,
    )


def _absorbed_chunk(q_nope, q_rope, plane, li, bt, q_first, n_keys, counts, runs, kv_b, scale, interpret):
    """``mla_paged_attention`` for a chunk through the kernel, whatever its
    length: the queries folded through ``Wuk`` and ``Wuv`` on the context
    outside it, as ``_walk`` has them absorbed."""
    n, m, heads, nope = q_nope.shape
    w = kv_b.astype(q_nope.dtype)
    rank = w.shape[0]
    qc = _folded_queries(q_nope, q_rope, w, plane.shape[3]).reshape(n, m * heads, -1)
    with jax.named_scope(SCOPE_MLA_CORE):
        ctx = mla_chunk_attention(
            qc, plane, li, bt, n_keys, q_first, counts, runs, heads=heads, rank=rank, scale=scale, interpret=interpret
        )
    with jax.named_scope(SCOPE_MLA_ABSORB):
        return jnp.einsum("nmhr,rhv->nmhv", ctx.reshape(n, m, heads, rank), w[:, :, nope:]).reshape(n, m, -1)
