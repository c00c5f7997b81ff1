"""Pallas grouped-query DECODE kernel for TPU: one query a slot, ``heads =
group x kv_heads`` query heads over a two-plane float pool whose token row
holds ``kv_heads x head_dim``, K and V pages read where they lie.

The step of a family with grouped-query attention (models/conv_decoder.py,
models/hybrid_decoder.py and, since PR 48, models/moe_decoder.py over both
its page kinds) used to gather every slot's WHOLE block table out of the
pool, upcast it to float32, split its heads and score every virtual
position, whatever the slots held: 25.8 ms of a 45.6 ms step at the
lfm2-24b-a2b cell's geometry (PERF.md section 5, PR 41), 29 of 44.2 at the
laguna-s-2.1 cell's (section 6, PR 48). This kernel leaves both planes in
HBM and, for each slot, fetches the ``ceil(length / ps)`` pages its table
names ONCE for all the query heads, in blocks, each group of ``RUN_PAGES``
table entries with ONE DMA a plane where their pages are consecutive
(ops/mla.py ``page_runs``), else a DMA a page.

A sliding-window layer hands it the windowed sub-table of its page kind
(``moe_decoder._window_table``: the pages that cover the query's window, the
older entries of the kind's table being junk page 0 once given back) with
one more vector a slot, ``first``: the in-table position of the oldest key
the query sees (``step_reads``). That form is a static variant: a call
without ``first`` traces the kernel it traced before there was one. Query
heads come in any whole number of groups (48 / 8, 72 / 8): the
block-diagonal query's rows are padded to whole sublane tiles.

Why not ops/paged_attention.py's kernel with a group loop: that one multiplies
on the VPU, one multiply a K element a query, and a group of four would be
four; here both products go to the MXU in the latent kernel's form (ops/mla.py
``mla_decode_attention``). The query is laid out BLOCK-DIAGONALLY, head ``h``'s
``head_dim`` numbers in the lanes of kv head ``h // group`` and zero
elsewhere, ``[heads, row]``: against a K block ``[keys, row]`` one
``dot_general`` gives every head's scores ``[heads, keys]`` (the zero lanes
add exact zeros), and ``[heads, keys] . V[keys, row]`` every head's context
over EVERY kv head's lanes, of which the caller keeps the head's own
(``_own_lanes``). That spends ``kv_heads`` times the multiplies the heads
need, on a unit that idles behind the page fetches anyway.

Precision, against the gather path (``decoder._paged_gather`` +
``moe_decoder._attend``): q and the pool's rows are two-byte floats there
too (the upcast adds no bits), so two-byte operands with float32
accumulation give the same scores; the scale multiplies the float32 scores;
maximum, sum and context are float32, online over the blocks; keys past the
length get probability exactly 0, and the tail of a slot's last page is
zeroed in VMEM so that not even a NaN there reaches the output. The
probabilities go into the context product as ``P_TERMS`` two-byte terms
(``p = p1 + p2 + p3``, each the rounding of what the terms before left), all
in ONE product with the terms stacked: float32 probabilities to their last
bit against the pool's own values, which the gather path's
``einsum("ngqk,ngkd->ngqd")`` at the chip's default precision does not keep
(PERF.md section 6, PR 42).

``interpret=True`` runs the Pallas interpreter, for the CPU backend's tests.
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
    that divides a lane tile, and query heads in whole groups of two or more
    (48 / 8, 72 / 8: the block-diagonal query's rows are padded to whole
    sublane tiles, ``_block_diagonal``; a group of ONE is
    ops/paged_attention.py's geometry, not this kernel's)."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize != 2:
        return False
    if row_width % _LANES or row_width % kv_heads or _LANES % (row_width // kv_heads):
        return False
    return page_size % 16 == 0 and heads % kv_heads == 0 and heads > kv_heads


def _table_blocks(pages: int) -> tuple[int, int, int]:
    """How the kernel walks a table of ``pages`` entries: (entries a run DMA
    takes, runs a block, blocks a table). A block is what one work item
    fetches and computes on: the fewest blocks of at most ``BLOCK_PAGES``
    entries, all of one size (a table of 144 entries is three blocks of 48,
    not two of 64 and one of 16 computed as 64)."""
    run = min(mla.RUN_PAGES, pages)
    n_runs = -(-pages // run)
    blocks = -(-n_runs // max(mla.BLOCK_PAGES // mla.RUN_PAGES, 1))
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

    def copies(slot, blk, b, fn):
        """``fn`` (start or wait) on a block's DMAs into buffer b, group by
        group and plane by plane: one for a run, else one a page the slot
        has."""
        for j in range(block_runs):
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

    def start(c):
        c.start()

    def wait(c):
        c.wait()

    @pl.when(i == 0)
    def _():
        # pages a block does not fetch hold what the buffer held: V's must be
        # finite (probability 0 times it); K's scores are masked by a select
        vbuf[...] = jnp.zeros_like(vbuf)
        cur[0] = 0
        copies(0, 0, 0, start)

    nb, b0 = n_blocks(i), cur[0]
    top_ref[...] = jnp.full_like(top_ref, NEG_INF)
    sum_ref[...] = jnp.zeros_like(sum_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def one_block(blk, _):
        b = (b0 + blk) % 2

        @pl.when(blk + 1 < nb)
        def _():
            copies(i, blk + 1, 1 - b, start)

        @pl.when((blk + 1 >= nb) & (i + 1 < n))
        def _():
            copies(i + 1, 0, 1 - b, start)

        copies(i, blk, b, wait)

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
