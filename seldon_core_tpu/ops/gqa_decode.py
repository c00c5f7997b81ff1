"""Pallas grouped-query attention kernels for TPU over a two-plane float
pool whose token row holds ``kv_heads x head_dim``, K and V pages read where
they lie: ``heads = group x kv_heads`` query heads,

- THE STEP'S (``gqa_decode_attention``): one query a slot, the fused decode
  step;
- THE CHUNK'S (``gqa_chunk_attention``): ``m`` > 1 consecutive queries a row,
  causal by position and ragged by row, every prefill chunk.

The programs of a family with grouped-query attention (models/conv_decoder.py,
models/hybrid_decoder.py and models/moe_decoder.py over both its page kinds)
used to gather every row's WHOLE block table out of the pool, upcast it to
float32, split its heads and score every virtual position, whatever the rows
held: 25.8 ms of a 45.6 ms step at the lfm2-24b-a2b cell's geometry (PERF.md
section 5, PR 41), 29 of 44.2 at the laguna-s-2.1 cell's (section 6, PR 48),
and in that cell's (4, 256) chunk 14.5 ms for two full-attention layers whose
products are 1.4 ms of the MXU (section 6, PR 52: each row's 464-page table
as a 2 x 30 MB float32 copy, 365 MB of float32 scores a row, the rows one at
a time). The kernels leave both planes in HBM and, for each row, fetch the
pages its table names up to its length ONCE for all the query heads, in
blocks, each group of ``RUN_PAGES`` table entries with ONE DMA a plane where
their pages are consecutive (ops/mla.py ``page_runs``), else a DMA a page;
scores, probabilities and the softmax state never leave VMEM.

What the two share with each other and with ops/mla.py's pair (one
algorithm, no third copy): ``RUN_PAGES`` and ``page_runs``; the double
buffer, a block's DMAs by runs (``_kv_copies``: ``mla._block_copies`` over two
planes) and the prefetch of the NEXT work item's first block behind the
current one's last; the zeroing of V's rows nobody may see; and, in the
chunk's kernel, ``mla._softmax_block`` itself, ``mla._query_block``, and the
latent chunk kernel's scalars (lengths, first positions, counts, ``next``).

A sliding-window layer hands either kernel the windowed sub-table of its page
kind (``moe_decoder._window_table``: the pages that cover the queries'
windows, the older entries of the kind's table being junk page 0 once given
back). The step's takes one more vector a slot, ``first``: the in-table
position of the oldest key the query sees (``step_reads``); the chunk's the
``window`` itself, static: a lower bound a query beside the causal upper one,
and a walk that starts at the key block of a query block's oldest visible
key. Either form is a static variant: a call without it traces the kernel
without it. Query heads come in any whole number of groups (48 / 8, 72 / 8).

THE STEP'S QUERY is laid out BLOCK-DIAGONALLY, head ``h``'s ``head_dim``
numbers in the lanes of kv head ``h // group`` and zero elsewhere, ``[heads,
row]`` (rows padded to whole sublane tiles): against a K block ``[keys,
row]`` one ``dot_general`` gives every head's scores ``[heads, keys]`` (the
zero lanes add exact zeros), and ``[heads, keys] . V[keys, row]`` every
head's context over EVERY kv head's lanes, of which the caller keeps the
head's own (``_own_lanes``). That spends ``kv_heads`` times the multiplies
the heads need, on a unit that idles behind the page fetches anyway. (Why not
ops/paged_attention.py's kernel with a group loop: that one multiplies on the
VPU, one multiply a K element a query, and a group of four would be four.)

THE CHUNK'S QUERIES cannot afford that: with 256 queries a row the MXU is the
bound, not the fetch. Its grid is (row, query block); a key block's K and V
rows are fetched once for the query block and serve every kv head's group in
turn, a 128-LANE TILE of the row at a time: the tile's ``group x tq`` query
rows ``[R, 128]`` against the block's 128 lanes (``_tile_rows``). At
``head_dim`` 128 a tile is one kv head and nothing is padded. At 64 a tile
holds two, taken as ONE block-diagonal pair (``2 x group x tq`` rows, each
head's numbers in its own 64 lanes, zeros in the other 64): on a 128 x 128
MXU a product with 64 of its 128 contraction lanes or output columns in use
takes the time of the full one, so the pair should cost what two sliced
products would, and needs no lane slicing inside a tile (the pair is what was
built and measured: lfm2-24b-a2b's two layers 2.87 -> 0.9 ms; the sliced form
was not built). The tiles go by in a ROLLED loop (``lax.fori_loop``, a
dynamic 128-aligned lane slice of the query block, the key block and the
scratch): unrolled, laguna-s-2.1's eight read 3.0 ms where the loop reads
2.65, the kernel took five times as long to compile and half again as long
to trace, which a kernel traced once a ladder entry and page kind pays in
every set-up. A second, maskless body for key blocks every query sees whole
was built and read the same to 1% in every geometry: it went. Tiles (the
microbench above ``CHUNK_BLOCK_PAGES``; PERF.md section 6, PR 52): query
blocks of ``mla.CHUNK_Q_ROWS`` = 1024 score rows a tile (128 queries of 6 or
8 heads a kv head, 64 of 9 or 16), key blocks of ``CHUNK_BLOCK_PAGES``
table entries.

Precision, against the gather path (``decoder._paged_gather`` +
``moe_decoder._attend``): q and the pool's rows are two-byte floats there
too (the upcast adds no bits), so two-byte operands with float32
accumulation give the same scores; the scale multiplies the float32 scores;
maximum, sum and context are float32, online over the blocks; keys a query
may not see get probability exactly 0, and V's rows that no query of the
work item may see (the tail of a row's last page, the rows before a window's
oldest key) are zeroed in VMEM so that not even a NaN there reaches the
output. The probabilities go into the context product
- in the step's kernel as ``P_TERMS`` two-byte terms (``p = p1 + p2 + p3``,
  each the rounding of what the terms before left), all in ONE product with
  the terms stacked: float32 probabilities to their last bit against the
  pool's own values, which the gather path's ``einsum("ngqk,ngkd->ngqd")`` at
  the chip's default precision does not keep (PERF.md section 6, PR 42);
- in the chunk's kernel as ONE two-byte term, which is what that ``einsum``
  takes them as (PR 42 measured the gather path's context 2.35e-4 off the
  float64 one where one term reads 2.27e-4): no coarser than the path it
  replaces. Three terms there would triple the context product, which is
  half the chunk kernel's MXU time, where the step's rides free behind its
  page fetches.

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

from seldon_core_tpu.ops import mla
from seldon_core_tpu.ops.paged_attention import slot_lengths

NEG_INF = -1e30  # the gather path's mask value
_LANES = 128
# two-byte terms the float32 probabilities enter the context product as
P_TERMS = 3


def gqa_tiles(row_width: int, heads: int, kv_heads: int, page_size: int, dtype) -> bool:
    """Whether Mosaic can tile ``gqa_decode_attention`` over a pool of this
    geometry: what ``decode_programs._step_attn_kernel`` asks before it
    answers "mosaic" for a two-plane pool whose ``kv_heads`` are fewer than
    the query's ``heads``. A two-byte float (both products take the rows
    into the MXU as they are stored), rows of whole 128-lane tiles, pages of
    whole sublane tiles (16 rows of a two-byte float: a page is the
    destination of one DMA and a slice of the block the MXU takes), a head
    that divides a lane tile or is whole lane tiles (256: the step's
    block-diagonal query spans a kv head's lanes whatever their number, the
    chunk's kernel takes the head's tiles as one, ``_tile_lanes``), and query
    heads in whole groups of two or more
    (48 / 8, 72 / 8: the block-diagonal query's rows are padded to whole
    sublane tiles, ``_block_diagonal``; a group of ONE is
    ops/paged_attention.py's geometry, not this kernel's)."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize != 2:
        return False
    if row_width % _LANES or row_width % kv_heads:
        return False
    if _LANES % (row_width // kv_heads) and (row_width // kv_heads) % _LANES:
        return False
    return page_size % 16 == 0 and heads % kv_heads == 0 and heads > kv_heads


def _table_blocks(pages: int, block_pages: int | None = None) -> tuple[int, int, int]:
    """How a kernel walks a table of ``pages`` entries: (entries a run DMA
    takes, runs a block, blocks a table). A block is what one work item
    fetches and computes on: the fewest blocks of at most ``block_pages``
    entries (the step's ``BLOCK_PAGES`` where None), all of one size (a table
    of 144 entries is three blocks of 48, not two of 64 and one of 16
    computed as 64)."""
    run = min(mla.RUN_PAGES, pages)
    n_runs = -(-pages // run)
    blocks = -(-n_runs // max((mla.BLOCK_PAGES if block_pages is None else block_pages) // mla.RUN_PAGES, 1))
    return run, -(-n_runs // blocks), blocks


def step_reads(bt, positions, rows, page_size: int, k0=None, window: int = 0):
    """What the kernel reads in a step, from what the step program is given:
    (lengths [n] int32, runs [n, groups] int32). A slot that generates
    (``rows``; None: every slot) attends over ``positions + 1`` keys; any
    other slot (prefilling, free) over ONE, so the kernel fetches one page
    for it whatever its table holds and nobody reads its output. ``runs``
    is ops/mla.py ``page_runs`` over this kernel's groups.

    With ``k0`` [n] (``bt`` a sliding layer's windowed sub-table whose first
    row is the key at absolute position ``k0``: models/moe_decoder.py
    ``_window_table``) the lengths count from ``k0`` and there is a third
    vector, ``first`` [n] int32: the in-table position of the oldest key the
    query sees, ``positions - (window - 1) - k0`` (0 where the window
    reaches back past the table's first row, and for a slot outside
    ``rows``)."""
    if k0 is not None:
        positions = positions - k0
    lengths = slot_lengths(positions, page_size, bt.shape[1]).astype(jnp.int32)
    if rows is not None:
        lengths = jnp.where(rows, lengths, 1)
    run, block_runs, blocks = _table_blocks(bt.shape[1])
    groups = blocks * block_runs
    runs = mla.page_runs(bt, lengths, page_size)  # groups past the table's last are 0
    runs = jnp.pad(runs, ((0, 0), (0, max(groups - runs.shape[1], 0))))[:, :groups]
    if k0 is None:
        return lengths, runs
    return lengths, runs, jnp.clip(lengths - window, 0, None)


def pages_fetched(lengths, runs, page_size: int, pages: int):
    """int32[2]: the pages the kernel fetches for one layer's K (as many
    again for V) over ALL slots of tables of ``pages`` entries, and those
    among them that come in run DMAs."""
    held = -(-lengths // page_size)
    in_runs = jnp.sum(runs, axis=1) * _table_blocks(pages)[0]
    return jnp.stack([jnp.sum(held), jnp.sum(in_runs)]).astype(jnp.int32)


def _kv_copies(
    k_hbm, v_hbm, layer, bt_ref, run_ref, n_pages, kbuf, vbuf, sem, run: int, block_runs: int, slot, blk, b, fn,
    rolled: bool = False,
):
    """``fn`` (start or wait) on the DMAs of block ``blk`` of ``slot``'s table
    into buffer ``b``, group by group and plane by plane: one for a run, else
    one a page the slot has (``n_pages(slot)``). ops/mla.py ``_block_copies``
    over two planes under one pair of conditions; what the step's and the
    chunk's kernel share. ``rolled`` (static): the groups go by in a loop the
    kernel runs, not one the trace unrolls: the same copies in the same order
    from a quarter of the equations, for a kernel that is traced once a
    ladder entry and page kind (the chunk's; the step's is traced once a page
    kind and keeps the text it had)."""

    def group(j, _):
        g = blk * block_runs + j
        first = g * run

        @pl.when(run_ref[slot, g] == 1)
        def _():
            src = pl.ds(bt_ref[slot, first], run)
            for pi, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                fn(pltpu.make_async_copy(hbm.at[layer, src], buf.at[b, pl.ds(j * run, run)], sem.at[pi, b]))

        @pl.when(run_ref[slot, g] == 0)
        def _():
            def page(k, _):
                src = bt_ref[slot, first + k]
                for pi, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                    fn(pltpu.make_async_copy(hbm.at[layer, src], buf.at[b, j * run + k], sem.at[pi, b]))
                return 0

            lax.fori_loop(0, jnp.clip(n_pages(slot) - first, 0, run), page, 0)

        return 0

    if rolled:
        lax.fori_loop(0, block_runs, group, 0)
    else:
        for j in range(block_runs):
            group(j, 0)


def _decode_kernel(*refs, page_size: int, run: int, block_runs: int, scale: float, terms: int, windowed: bool):
    """Grid step i is slot i: its blocks of pages in turn, always with the
    next block's K and V rows in flight (the next SLOT's first block after
    the last), the online softmax's state in scratch. ``cur`` carries which
    of the two buffers the slot's first block was fetched into.

    ``windowed`` (static): one more scalar-prefetch vector, ``first``: keys
    at in-table positions under ``first[i]`` weigh exactly 0, as keys from
    the length on do, and their V rows are zeroed in VMEM like the last
    page's tail (a page given back reads as the junk page, whatever it
    holds). Without it the kernel is what it was, argument for argument."""
    (
        layer_ref, bt_ref, len_ref, run_ref,  # scalar prefetch
        *first_ref,  # [n] with ``windowed``
        q_ref, k_hbm, v_hbm,  # slot i's block-diagonal query [1, H, w]; the two planes, left in HBM
        o_ref,  # slot i's normalised context over every kv head's lanes [1, H, w]
        kbuf, vbuf, top_ref, sum_ref, acc_ref, sem, cur,  # scratch
    ) = refs
    i, n = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    block = run * block_runs
    keys = block * page_size
    heads = q_ref.shape[1]

    def n_pages(slot):
        return (len_ref[slot] + page_size - 1) // page_size

    def n_blocks(slot):
        return (n_pages(slot) + block - 1) // block

    copies = functools.partial(
        _kv_copies, k_hbm, v_hbm, layer, bt_ref, run_ref, n_pages, kbuf, vbuf, sem, run, block_runs
    )

    @pl.when(i == 0)
    def _():
        # pages a block does not fetch hold what the buffer held: V's must be
        # finite (probability 0 times it); K's scores are masked by a select
        vbuf[...] = jnp.zeros_like(vbuf)
        cur[0] = 0
        copies(0, 0, 0, mla._start)

    nb, b0 = n_blocks(i), cur[0]
    top_ref[...] = jnp.full_like(top_ref, NEG_INF)
    sum_ref[...] = jnp.zeros_like(sum_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def one_block(blk, _):
        b = (b0 + blk) % 2

        @pl.when(blk + 1 < nb)
        def _():
            copies(i, blk + 1, 1 - b, mla._start)

        @pl.when((blk + 1 >= nb) & (i + 1 < n))
        def _():
            copies(i + 1, 0, 1 - b, mla._start)

        copies(i, blk, b, mla._wait)

        @pl.when(blk + 1 >= nb)
        def _():
            # V's rows past the length in the slot's last page: probability 0
            # times whatever lies there must be 0, a NaN's included
            last = n_pages(i) - 1
            tail = lax.broadcasted_iota(jnp.int32, (page_size, 1), 0) + last * page_size < len_ref[i]
            at = last - blk * block
            vbuf[b, at] = jnp.where(tail, vbuf[b, at], jnp.zeros((), vbuf.dtype))

        if windowed:
            # V's rows before the slot's first key, in the pages of this block that hold any (the window
            # starts inside the sub-table's first pages; they may be the junk page): zeroed like the tail
            first = first_ref[0][i]

            def head(j, _):
                seen = lax.broadcasted_iota(jnp.int32, (page_size, 1), 0) + (blk * block + j) * page_size >= first
                vbuf[b, j] = jnp.where(seen, vbuf[b, j], jnp.zeros((), vbuf.dtype))
                return 0

            lax.fori_loop(0, jnp.clip((first + page_size - 1) // page_size - blk * block, 0, block), head, 0)

        k = kbuf[b].reshape(keys, kbuf.shape[-1])
        v = vbuf[b].reshape(keys, vbuf.shape[-1])
        s = lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        k_pos = blk * keys + lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        # (a block wholly before the first key scores NEG_INF throughout, weighs 1 a key against a maximum of
        # NEG_INF, and is wiped by the rescaling at the first block with a key in it: every slot has one)
        seen = (k_pos < len_ref[i]) & (k_pos >= first) if windowed else k_pos < len_ref[i]
        s = jnp.where(seen, s * scale, NEG_INF)  # [H, keys]
        top = top_ref[...]
        new_top = jnp.maximum(top, jnp.max(s, axis=1, keepdims=True))
        shrink = jnp.exp(top - new_top)
        p = jnp.exp(s - new_top)
        parts, rest = [], p
        for _t in range(terms):
            parts.append(rest.astype(v.dtype))
            rest = rest - parts[-1].astype(jnp.float32)
        ctx = jnp.dot(jnp.concatenate(parts, axis=0), v, preferred_element_type=jnp.float32)  # [terms * H, w]
        top_ref[...] = new_top
        sum_ref[...] = sum_ref[...] * shrink + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * shrink + sum(ctx[t * heads : (t + 1) * heads] for t in range(terms))
        return 0

    lax.fori_loop(0, nb, one_block, 0)
    cur[0] = (b0 + nb) % 2
    o_ref[0] = (acc_ref[...] / sum_ref[...]).astype(o_ref.dtype)


def _block_diagonal(q, kv_heads: int):
    """q[n, H, d] -> [n, H', kv_heads * d]: head h's numbers in the lanes of
    kv head ``h // (H / kv_heads)``, zero elsewhere; H' is H rounded up to
    whole sublane tiles of a two-byte float (16 rows: 48 stays, 72 -> 80),
    the rows past H all zero (``_own_lanes`` drops them)."""
    n, heads, d = q.shape
    own = jnp.arange(heads)[:, None] // (heads // kv_heads) == jnp.arange(kv_heads * d)[None, :] // d
    wide = jnp.where(own[None], jnp.tile(q, (1, 1, kv_heads)), jnp.zeros((), q.dtype))
    return wide if heads % 16 == 0 else jnp.pad(wide, ((0, 0), (0, -heads % 16), (0, 0)))


def _own_lanes(ctx, kv_heads: int, heads: int):
    """ctx[n, H', kv_heads * d], every head over every kv head's lanes ->
    [n, H * d]: each of the first ``heads`` rows' own kv head's lanes, heads
    merged."""
    n, padded, w = ctx.shape
    if padded != heads:
        ctx = ctx[:, :heads]
    d = w // kv_heads
    by_group = ctx.reshape(n, kv_heads, heads // kv_heads, kv_heads, d)
    own = jnp.eye(kv_heads, dtype=bool)[None, :, None, :, None]
    # one term of the sum is the head's own, the others exact zeros
    return jnp.sum(jnp.where(own, by_group, jnp.zeros((), ctx.dtype)), axis=3).reshape(n, heads * d)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def gqa_decode_attention(
    q, pool_k, pool_v, layer, bt, lengths, runs, first=None, *, scale: float, interpret: bool = False
):
    """Decode attention of ``n`` slots of ONE query each over the page pool,
    read in place.

    q ``[n, H, d]`` (normed and rotated as the family has it, NOT scaled),
    pool_k / pool_v ``[L, P, ps, g * d]`` (the whole planes, left in HBM; H a
    multiple of g), ``layer`` the layer to read, bt ``[n, pages]`` int32,
    lengths ``[n]`` int32 in ``[1, pages * ps]`` and runs as ``step_reads``
    gives them. Returns ctx ``[n, H * d]`` in q's dtype: for each slot and
    head softmax(scale * q . K[:length]) . V[:length] over the head's kv head
    (``h // (H / g)``), heads merged. q goes into the products in the pool's
    dtype.

    Slot i's table is walked in blocks (``_table_blocks``); a block's K and
    V rows are fetched into VMEM ONCE, each group of ``mla.RUN_PAGES`` entries
    with one DMA a plane where ``runs`` says it is a run of consecutive
    pages, else a DMA a page; pages past ``ceil(length / ps)`` are never
    fetched, rows past the length weigh exactly 0. Nothing is shared between
    slots. A slot's first key is its table's first row unless ``first``
    ([n] int32, with ``lengths`` and ``runs`` what ``step_reads`` gives for a
    windowed sub-table) says where in the table its window starts: rows
    before it weigh exactly 0 too, whatever their pages hold. With ``first``
    None the traced kernel is the one without it.

    Jitted with ``layer`` traced: a step's calls lower to Mosaic once a
    table width and head count."""
    n, heads, d = q.shape
    _, _, ps, w = pool_k.shape
    pages = bt.shape[1]
    if w % d or heads % (w // d) or pool_v.shape != pool_k.shape or pool_v.dtype != pool_k.dtype:
        raise ValueError(f"queries {list(q.shape)} against pool rows {list(pool_k.shape)} / {list(pool_v.shape)}")
    kv_heads = w // d
    if interpret and jax.default_backend() != "cpu":
        raise ValueError("gqa_decode_attention(interpret=True) is for the CPU backend")
    if not interpret and not gqa_tiles(w, heads, kv_heads, ps, pool_k.dtype):
        raise ValueError(
            f"gqa_decode_attention cannot tile {heads} / {kv_heads} heads over {pool_k.dtype} rows of {w} in "
            f"pages of {ps} for Mosaic (gqa_tiles): this geometry keeps the gather path"
        )
    run, block_runs, blocks = _table_blocks(pages)
    block = run * block_runs
    if runs.shape != (n, blocks * block_runs):
        raise ValueError(f"runs {list(runs.shape)} for {blocks} blocks of {block_runs} groups (step_reads)")
    # float32 rows (the interpreter's tests) take the probabilities whole
    terms = P_TERMS if jnp.dtype(pool_k.dtype).itemsize < 4 else 1
    windowed = first is not None
    kernel = functools.partial(
        _decode_kernel, page_size=ps, run=run, block_runs=block_runs, scale=scale, terms=terms, windowed=windowed
    )
    rows = heads + -heads % 16  # the block-diagonal query's rows: the heads in whole sublane tiles
    ctx = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 if windowed else 4,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, rows, w), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, rows, w), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, ps, w), pool_k.dtype),  # K: the block in use and the one in flight
                pltpu.VMEM((2, block, ps, w), pool_v.dtype),  # V
                pltpu.VMEM((rows, 1), jnp.float32),  # running maximum
                pltpu.VMEM((rows, 1), jnp.float32),  # running sum
                pltpu.VMEM((rows, w), jnp.float32),  # running context, every kv head's lanes
                pltpu.SemaphoreType.DMA((2, 2)),  # plane, buffer
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, rows, w), q.dtype),
        # a slot's first block is started by the slot before it
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gqa_decode_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.pad(bt.astype(jnp.int32), ((0, 0), (0, blocks * block - pages))),
        jnp.clip(lengths.astype(jnp.int32), 1, pages * ps), runs.astype(jnp.int32),
        *((first.astype(jnp.int32),) if windowed else ()),
        _block_diagonal(q.astype(pool_k.dtype), kv_heads), pool_k, pool_v,
    )
    return _own_lanes(ctx, kv_heads, heads)


# ------------------------------------------------------------------------------
# The chunk's kernel: many queries a row, causal by position, the same pages

# Table entries of one key block of the chunk's kernel (ops/mla.py
# ``CHUNK_Q_ROWS`` = 1024 bounds a query block's score rows a lane tile).
# Picked on a v5e by the kernel alone, two layers, at the cells' geometries
# (my chip run, PR 52, `.scratch/mb.py`; the gather path beside it). Blocks of
# 32 / 64 / 128 entries took, in ms: laguna-s-2.1's (4, 256) full layers (48 /
# 8 heads of 128, 6.7-7.2k keys) 4.65 / 2.66 / 3.02 (gather 14.53), its sliding
# layers (72 / 8, window 512: a 49-entry sub-table) 1.47 / 0.95 / 0.93 (2.16),
# lfm2-24b-a2b's (32 / 8 of 64, 1.3-2k keys) 1.19 / 0.89 / 0.93 (2.87),
# nemotron-3-nano's (32 / 2 of 128) 1.08 / 0.81 / 0.85 (2.84), mellum2's (2, 64)
# (32 / 4 of 128, 3.1k keys) 0.28 / 0.26 / 0.27 (0.32), granite's (2, 64) (32 / 8
# of 64, 576 keys) 0.25 / 0.27 / 0.27 (0.25: a tie, inside the 0.23-0.27 its own
# variants span). At 64 entries, query blocks of 512 / 1024 / 2048 rows: 2.80 /
# 2.66 / 2.61, 1.05 / 0.95 / 0.99, 1.22 / 0.89 / 0.83, 0.86 / 0.81 / 0.76 (1024 is
# the latent kernel's constant: one number, and half the VMEM of 2048).
CHUNK_BLOCK_PAGES = 64


def _tile_lanes(row_width: int, kv_heads: int) -> int:
    """Lanes of one tile of a pool row as the chunk's kernel takes it: 128
    (a row narrower than that, the interpreter's tests, is one tile), or a
    whole kv head where that is wider (head 256: two lane tiles taken as one,
    the scores summed over both in the product itself)."""
    return max(min(_LANES, row_width), row_width // kv_heads)


def _chunk_query_block(queries: int, heads: int, kv_heads: int, row_width: int) -> int:
    """Queries of one work item of the chunk's kernel: ops/mla.py
    ``_query_block`` over the score rows a query adds to a LANE TILE's
    product (its group's heads, of every kv head the tile holds)."""
    return mla._query_block(queries, _tile_lanes(row_width, kv_heads) * heads // row_width)


def gqa_chunk_tiles(kernel: str, queries: int, heads: int, kv_heads: int, head_dim: int) -> bool:
    """Whether a chunk of ``queries`` a row takes ``gqa_chunk_attention``
    (``kernel``: ``decode_programs._step_attn_kernel``'s answer, "" |
    "mosaic" | "interpret", which has asked ``gqa_tiles`` for the pool: the
    step's kernel reads the same planes): more than one query (one is the
    step's kernel), query heads in whole groups and kv heads in whole lane
    tiles; for Mosaic also a query block (``_chunk_query_block``) whose score
    rows are whole sublane tiles of a two-byte float. Static: what a
    family's chunk program and its ``chunk_attn`` (the scheduler's count of
    the dispatches that took the kernel) both ask."""
    row_width = kv_heads * head_dim
    lanes = _tile_lanes(row_width, kv_heads)
    if not kernel or queries < 2 or heads % kv_heads or row_width % lanes or lanes % head_dim:
        return False
    if kernel == "interpret":
        return True
    return _chunk_query_block(queries, heads, kv_heads, row_width) * lanes * heads // row_width % 16 == 0


def chunk_reads(bt, positions, counts, page_size: int, k0=None):
    """What the chunk's kernel reads, from what a chunk program is given:
    (lengths [n], q_first [n], counts [n], next_live [n + 1], runs [n,
    groups]), all int32. Row i's query j sits at in-table position
    ``q_first[i] + j`` (``positions``, less ``k0`` where ``bt`` is a sliding
    layer's windowed sub-table whose first row is the key at absolute
    position ``k0``: models/moe_decoder.py ``_window_table``); its LEADING
    ``counts[i]`` queries are real; ``lengths`` = ``q_first + counts`` keys of
    its table (1 for a row of count 0, which fetches nothing); ``next_live[0]``
    is the first row with a real query, ``next_live[i + 1]`` the first such
    row after ``i``, ``n`` for none; ``runs`` is ops/mla.py ``page_runs`` over
    this kernel's groups."""
    n, pages = bt.shape
    q_first = (positions if k0 is None else positions - k0).astype(jnp.int32)
    counts = counts.astype(jnp.int32)
    lengths = jnp.where(counts > 0, jnp.clip(q_first + counts, 1, pages * page_size), 1)
    ids = jnp.where(counts > 0, jnp.arange(n, dtype=jnp.int32), n)
    next_live = jnp.concatenate([lax.cummin(ids, reverse=True), jnp.full((1,), n, jnp.int32)])
    _, block_runs, blocks = _table_blocks(pages, CHUNK_BLOCK_PAGES)
    groups = blocks * block_runs
    runs = mla.page_runs(bt, lengths, page_size)
    runs = jnp.pad(runs, ((0, 0), (0, max(groups - runs.shape[1], 0))))[:, :groups]
    return lengths, q_first, counts, next_live, runs


def _chunk_kernel(
    layer_ref, bt_ref, len_ref, pos_ref, cnt_ref, next_ref, run_ref,  # scalar prefetch
    q_ref, k_hbm, v_hbm,  # row i's query block j [1, 1, R, w] (``_tile_rows``); the two planes, left in HBM
    o_ref,  # its normalised context, every row over every lane tile [1, 1, R, w]
    kbuf, vbuf, top_ref, sum_ref, acc_ref, sem, cur,  # scratch
    *, page_size: int, run: int, block_runs: int, scale: float, tq: int, window: int, lanes: int,
):
    """Grid step (i, j) is query block j of row i: ``tq`` queries by all
    heads, laid out a lane tile (score row r of a tile is query ``j * tq + r
    % tq``). A block with a real query (``j * tq < cnt[i]``) walks the row's
    key blocks from the one that holds the oldest key its first query sees
    (block 0 without ``window``) up to its last real query's position and no
    further, always with the next block's K and V rows in flight: its own
    next, else the first block of the next work item (ops/mla.py
    ``_chunk_kernel``, whose scalars these are). A key block's rows, fetched
    once, serve every lane tile in turn: the tile's group rows ``[R, 128]``
    against the block's 128 lanes (``lanes``, static: ``_tile_lanes``; a head
    of 256 is one tile of 256). Every other grid step writes zeros and
    touches neither the planes nor the MXU.

    ``window`` (static; 0: none): a key weighs exactly 0 for a query with
    ``q_pos - k_pos >= window`` too, and V's rows before the block's oldest
    visible key are zeroed in VMEM like the last page's tail (their pages may
    have been given back: the junk page, whatever it holds)."""
    i, j, n = pl.program_id(0), pl.program_id(1), pl.num_programs(0)
    layer = layer_ref[0]
    block = run * block_runs
    keys = block * page_size
    rows, w = q_ref.shape[2], q_ref.shape[3]
    # (scalars by ``lax``: nothing here is negative, and a ``//`` or a ``jnp.minimum`` of traced scalars is a
    # dozen equations and a nested call each, which a kernel traced once a ladder entry and page kind pays in set-up)

    def n_pages(row):
        return lax.div(len_ref[row] + (page_size - 1), page_size)

    def items(row):
        return lax.div(cnt_ref[row] + (tq - 1), tq)  # its query blocks with a real query

    def oldest(row, item):
        """The in-table position of the oldest key query block ``item`` of ``row`` sees (under 0: the table's first)."""
        return pos_ref[row] + item * tq - (window - 1)

    def first_block(row, item):
        return lax.div(lax.max(oldest(row, item), 0), keys) if window else 0

    copies = functools.partial(
        _kv_copies, k_hbm, v_hbm, layer, bt_ref, run_ref, n_pages, kbuf, vbuf, sem, run, block_runs, rolled=True
    )
    live = items(i)

    @pl.when((i == 0) & (j == 0))
    def _():
        # pages a block does not fetch hold what the buffer held: V's must be
        # finite (probability 0 times it); K's scores are masked by a select
        vbuf[...] = jnp.zeros_like(vbuf)
        cur[0] = 0

        @pl.when(next_ref[0] < n)
        def _():
            copies(next_ref[0], first_block(next_ref[0], 0), 0, mla._start)

    @pl.when(j >= live)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < live)
    def _():
        read = lax.min((j + 1) * tq, cnt_ref[i])  # the row's queries up to this block's last real one
        nb = lax.div(lax.min(pos_ref[i] + read, len_ref[i]) + (keys - 1), keys)
        f0, b0 = first_block(i, j), cur[0]
        more, after = j + 1 < live, next_ref[i + 1]
        top_ref[...] = jnp.full_like(top_ref, NEG_INF)
        sum_ref[...] = jnp.zeros_like(sum_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        query = j * tq + lax.rem(lax.broadcasted_iota(jnp.int32, (rows, 1), 0), tq)
        # a query past the count sees what the row's last real one sees: finite, and no key past the row's length
        q_pos = pos_ref[i] + lax.min(query, cnt_ref[i] - 1)
        tail = n_pages(i) - 1  # the row's last page

        def one_block(blk, _):
            b = lax.rem(b0 + blk - f0, 2)
            last = blk + 1 >= nb
            # the block to have in flight: this work item's next, else the first of the next work item (the row's
            # next query block, else query block 0 of the next row with a real query)
            ahead = lax.select(last & jnp.logical_not(more), lax.min(after, n - 1), i)
            ahead_blk = lax.select(last, first_block(ahead, lax.select(more, j + 1, 0)), blk + 1)

            @pl.when(jnp.logical_not(last) | more | (after < n))
            def _():
                copies(ahead, ahead_blk, 1 - b, mla._start)

            copies(i, blk, b, mla._wait)

            @pl.when(lax.div(tail, block) == blk)
            def _():
                # V's rows past the length in the row's last page: probability 0 times whatever lies there must be 0
                real = lax.broadcasted_iota(jnp.int32, (page_size, 1), 0) + tail * page_size < len_ref[i]
                at = tail - blk * block
                vbuf[b, at] = jnp.where(real, vbuf[b, at], jnp.zeros((), vbuf.dtype))

            if window:
                # V's rows before the oldest key this query block sees, in the pages of this key block that hold any
                def head(pg, _):
                    seen = lax.broadcasted_iota(jnp.int32, (page_size, 1), 0) + (blk * block + pg) * page_size >= oldest(i, j)
                    vbuf[b, pg] = jnp.where(seen, vbuf[b, pg], jnp.zeros((), vbuf.dtype))
                    return 0

                before = lax.div(lax.max(oldest(i, j), 0) + (page_size - 1), page_size) - blk * block
                lax.fori_loop(0, lax.clamp(0, before, block), head, 0)

            k_pos = blk * keys + lax.broadcasted_iota(jnp.int32, (1, keys), 1)
            seen = (k_pos <= q_pos) & (k_pos > q_pos - window) if window else k_pos <= q_pos

            def tile(t, _):
                at = pl.ds(pl.multiple_of(t * lanes, lanes), lanes)
                mla._softmax_block(
                    q_ref[0, 0, :, at], kbuf[b, :, :, at].reshape(keys, lanes), lambda: seen,
                    top_ref.at[t], sum_ref.at[t], acc_ref.at[t], 0, scale,
                    values=vbuf[b, :, :, at].reshape(keys, lanes),
                )
                return 0

            lax.fori_loop(0, w // lanes, tile, 0)
            return 0

        lax.fori_loop(f0, nb, one_block, 0)
        cur[0] = lax.rem(b0 + nb - f0, 2)

        def normalised(t, _):
            o_ref[0, 0, :, pl.ds(pl.multiple_of(t * lanes, lanes), lanes)] = (acc_ref[t] / sum_ref[t]).astype(o_ref.dtype)
            return 0

        lax.fori_loop(0, w // lanes, normalised, 0)


def _tile_rows(q, kv_heads: int, tq: int):
    """q[n, m, H, d] -> [n, m / tq, R, kv_heads * d], the chunk kernel's
    queries a LANE TILE: row ``(u * r + a) * tq + x`` of query block ``j``
    holds, in the lanes of tile ``t``'s kv head ``u`` (of the ``128 / d`` a
    tile holds), head ``(t * hp + u) * r + a`` of query ``j * tq + x``, and
    zeros in the tile's other heads' lanes (``d`` 64: the two kv heads of a
    tile go through the MXU as one block-diagonal pair, exact zeros added;
    ``d`` 128: nothing is padded)."""
    n, m, heads, d = q.shape
    hp, r = _tile_lanes(kv_heads * d, kv_heads) // d, heads // kv_heads
    by_tile = q.reshape(n, m // tq, tq, kv_heads // hp, hp, r, d).transpose(0, 1, 4, 5, 2, 3, 6)  # [.., u, a, x, t, d]
    own = jnp.eye(hp, dtype=bool)[:, None, None, None, :, None]  # [u, 1, 1, 1, u', 1]
    wide = jnp.where(own, by_tile[..., None, :], jnp.zeros((), q.dtype))  # [.., u, a, x, t, u', d]
    return wide.reshape(n, m // tq, hp * r * tq, kv_heads * d)


def _own_tile_lanes(ctx, heads: int, kv_heads: int, tq: int):
    """``_tile_rows``' inverse on the kernel's output: [n, m / tq, R,
    kv_heads * d] -> [n, m, H * d], each row's own kv head's lanes of every
    tile, heads merged."""
    n, blocks, _, w = ctx.shape
    d = w // kv_heads
    hp, r = _tile_lanes(w, kv_heads) // d, heads // kv_heads
    wide = ctx.reshape(n, blocks, hp, r, tq, kv_heads // hp, hp, d)
    own = jnp.eye(hp, dtype=bool)[:, None, None, None, :, None]
    # one term of the sum is the head's own, the others exact zeros
    by_tile = jnp.sum(jnp.where(own, wide, jnp.zeros((), ctx.dtype)), axis=-2)  # [n, blocks, u, a, x, t, d]
    return by_tile.transpose(0, 1, 4, 5, 2, 3, 6).reshape(n, blocks * tq, heads * d)


def gqa_chunk_attention(
    q, pool_k, pool_v, layer, bt, lengths, q_first, counts, next_live, runs,
    *, scale: float, window: int = 0, interpret: bool = False,
):
    """Causal attention of ``n`` rows of ``m`` > 1 consecutive queries each
    over the page pool, read in place: the many-queries form of
    ``gqa_decode_attention``.

    q ``[n, m, H, d]`` (normed and rotated as the family has it, NOT
    scaled), pool_k / pool_v ``[L, P, ps, g * d]`` (the whole planes, left in
    HBM), ``layer`` the layer to read, bt ``[n, pages]`` int32 and the five
    vectors as ``chunk_reads`` gives them for it. Returns ctx ``[n, m, H *
    d]`` in q's dtype: for row i's query j < counts[i] and each head,
    softmax(scale * q . K[lo : q_first + j + 1]) . V[the same] over the
    head's kv head, ``lo`` the table's first row, or with ``window`` (static;
    bt a windowed sub-table) ``q_first + j - window + 1``; ZEROS for a row of
    count 0, for which no page is fetched and nothing computed, and for every
    query block wholly past a row's count; something finite for a query past
    the count inside a block that has a real one.

    The grid is (row, query block of ``_chunk_query_block`` queries); a key
    block of ``CHUNK_BLOCK_PAGES`` table entries, fetched as the step's
    kernel fetches (``runs``), serves the block's queries by all heads on the
    MXU, a lane tile at a time, and the scores, the probabilities and the
    softmax state never leave VMEM. A key block wholly past a query block's
    last real position, or wholly before its first query's window, is
    neither fetched nor scored. The probabilities enter the context product
    in the pool's dtype (module docstring)."""
    n, m, heads, d = q.shape
    _, _, ps, w = pool_k.shape
    if w % d or heads % (w // d) or pool_v.shape != pool_k.shape or pool_v.dtype != pool_k.dtype:
        raise ValueError(f"queries {list(q.shape)} against pool rows {list(pool_k.shape)} / {list(pool_v.shape)}")
    kv_heads = w // d
    if interpret and jax.default_backend() != "cpu":
        raise ValueError("gqa_chunk_attention(interpret=True) is for the CPU backend")
    tiled = gqa_chunk_tiles("interpret" if interpret else "mosaic", m, heads, kv_heads, d)
    if not tiled or not (interpret or gqa_tiles(w, heads, kv_heads, ps, pool_k.dtype)):
        raise ValueError(
            f"gqa_chunk_attention cannot tile {m} queries of {heads} / {kv_heads} heads over {pool_k.dtype} rows of "
            f"{w} in pages of {ps} (gqa_tiles, gqa_chunk_tiles): this geometry keeps the gather path"
        )
    tiles = (_chunk_query_block(m, heads, kv_heads, w), *_table_blocks(bt.shape[1], CHUNK_BLOCK_PAGES))
    return _chunk_call(
        q, pool_k, pool_v, layer, bt, lengths, q_first, counts, next_live, runs,
        scale=scale, window=window, interpret=interpret, tiles=tiles,
    )


@functools.partial(jax.jit, static_argnames=("scale", "window", "interpret", "tiles"))
def _chunk_call(q, pool_k, pool_v, layer, bt, lengths, q_first, counts, next_live, runs, *, scale, window, interpret, tiles):
    """``gqa_chunk_attention`` at its tiles (queries a block; entries a run,
    runs a block, blocks a table). Jitted with ``layer`` traced: a chunk
    program's calls lower to Mosaic once a table width, head count and chunk
    length."""
    n, m, heads, d = q.shape
    _, _, ps, w = pool_k.shape
    pages = bt.shape[1]
    kv_heads = w // d
    tq, run, block_runs, blocks = tiles
    block = run * block_runs
    if runs.shape != (n, blocks * block_runs):
        raise ValueError(f"runs {list(runs.shape)} for {blocks} blocks of {block_runs} groups (chunk_reads)")
    lanes = _tile_lanes(w, kv_heads)
    rows, n_tiles = tq * lanes * heads // w, w // lanes
    kernel = functools.partial(
        _chunk_kernel, page_size=ps, run=run, block_runs=block_runs, scale=scale, tq=tq, window=window, lanes=lanes
    )

    def q_block(i, j, _layer, _bt, _len, _pos, cnt, *_):
        # a block without a real query names the row's last with one: not fetched again
        return i, jnp.minimum(j, jnp.maximum((cnt[i] + tq - 1) // tq - 1, 0)), 0, 0

    item = jnp.dtype(pool_k.dtype).itemsize
    vmem = (
        4 * block * ps * w * item  # K and V, the block in use and the one in flight
        + 3 * n_tiles * rows * lanes * 4  # maximum, sum (a lane tile a column) and context
        + 4 * rows * w * item  # the query block and its output, each in two buffers
        + 4 * rows * block * ps * 4  # a tile's scores and probabilities in flight
    )
    ctx = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(n, m // tq),
            in_specs=[
                pl.BlockSpec((1, 1, rows, w), q_block),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, w), lambda i, j, *_: (i, j, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, ps, w), pool_k.dtype),  # K: the key block in use and the one in flight
                pltpu.VMEM((2, block, ps, w), pool_v.dtype),  # V
                pltpu.VMEM((n_tiles, rows, 1), jnp.float32),  # running maximum, a lane tile's rows
                pltpu.VMEM((n_tiles, rows, 1), jnp.float32),  # running sum
                pltpu.VMEM((n_tiles, rows, lanes), jnp.float32),  # running context
                pltpu.SemaphoreType.DMA((2, 2)),  # plane, buffer
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, m // tq, rows, w), q.dtype),
        # a work item's first block is started by the one before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=min(max(2 * vmem, 32 << 20), 100 << 20)
        ),
        interpret=interpret,
        name="gqa_chunk_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.pad(bt.astype(jnp.int32), ((0, 0), (0, blocks * block - pages))),
        jnp.clip(lengths.astype(jnp.int32), 1, pages * ps), q_first.astype(jnp.int32),
        jnp.clip(counts.astype(jnp.int32), 0, m), next_live.astype(jnp.int32), runs.astype(jnp.int32),
        _tile_rows(q.astype(pool_k.dtype), kv_heads, tq), pool_k, pool_v,
    )
    return _own_tile_lanes(ctx, heads, kv_heads, tq)
