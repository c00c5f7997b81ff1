"""Pallas paged-attention DECODE kernel for TPU: one query a slot, K/V read
out of the page pool where they lie.

The fused decode step (models/decoder.py ``_layer_step_paged``, one query a
slot) used to gather every slot's whole block table into a float32 virtual
cache, split its heads and score every virtual position, whatever the slots
held. This kernel takes the WHOLE pool components ``[L, P, ps, h*hd]`` as
they are, left in HBM, and for each slot fetches only the ``ceil(length /
ps)`` pages its table names, ``pool[layer, bt[i, j]]``, a few pages at a
time, double buffered. A page is a block of token rows (PR 27), 128-multiple
wide and contiguous, so a DMA fetches it as it lies; heads stay inside the
row — no ``[.., h, hd]`` relayout of K or V anywhere.

One program walks a flat list of work items — for each slot its K blocks,
then its V blocks — and always has the next item's pages in flight while it
computes on this one, across slot boundaries too. All arithmetic is float32
on the VPU, as the gather path's ``multiply-reduce`` fusions were; the MXU is
not used, so there is no matmul precision to choose. What replaces the head
split is a transpose of each 128-lane tile of a block (two heads of 64 here)
on the XLU, which puts the tokens in the lanes and a head's lanes in the
sublanes, where a per-head sum is plain vector adds:

- a K item multiplies the block's rows by the slot's query, transposes each
  tile and sums each head's sublanes: a row of scores ``[1, tokens]`` a head;
- after a slot's last K item its scores (``[heads, length]``, a few hundred
  KB at most) get ONE softmax over the whole length, the gather path's own
  arithmetic: max, exp, sum, divide. No online rescaling;
- a V item transposes each tile, multiplies it by its heads' probability
  rows and adds it to a per-tile accumulator ``[128, tokens]``; after the
  last one each accumulator is transposed back and summed over the tokens:
  the slot's context row ``[h*hd]``, already merged.

Rows past a slot's length are masked to probability exactly 0, so whatever a
page holds past the length never reaches the output; pages past
``ceil(length / ps)`` are never fetched. A free slot (position 0, table all
zero) costs one junk page.

Measured alone on a v5e at gpt2-large's geometry (PERF.md section 6, PR
29): 36 layers over 16 slots of 577-704 tokens 6.9 ms against the gather
path's 35.1; a product with a 0/1 head-indicator matrix on the MXU in three
exact bfloat16 terms, the other way to sum a head's lanes, took 8.4.

Compiles under Mosaic; ``interpret=True`` runs the Pallas interpreter and is
for the CPU backend's tests only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # the gather path's mask value
_LANES = 128
# pages fetched and computed on per work item: 8 pages of 16 rows = 128 token
# rows, which a transposed tile holds in its 128 lanes (16 pages a block are
# 0.9 ms faster a step with 16 long slots and 0.6 ms slower with 13 free ones)
PAGES_PER_BLOCK = 8


def slot_lengths(positions, page_size: int, pages_per_slot: int):
    """Keys each slot's one query attends over: ``positions + 1``, clipped to
    the table (a position past it sees all of it, as the gather path's mask
    does). numpy or jax alike: the program computes the kernel's lengths with
    it and the scheduler, on the host, the pages they cover."""
    return (positions + 1).clip(1, pages_per_slot * page_size)


def pages_read(positions: np.ndarray, page_size: int, pages_per_slot: int) -> np.ndarray:
    """Pages the kernel fetches for one layer's K (or V) of each slot,
    ``ceil(length / page_size)``: what the scheduler counts into FlightFrame
    ``attn_pages_read``."""
    return -(-slot_lengths(np.asarray(positions), page_size, pages_per_slot) // page_size)


def window_pages(window: int, queries: int, page_size: int) -> int:
    """Entries of a sliding layer's windowed sub-table: the pages that cover
    the windows of ``queries`` consecutive queries wherever the first falls
    in its page (models/moe_decoder.py ``_window_table``)."""
    return -(-(window + queries) // page_size) + 1


def window_first_page(positions, window: int, page_size: int, pages_per_slot: int, pw: int):
    """The table entry a windowed sub-table of ``pw`` entries starts at, for
    queries from ``positions``: the page of the oldest key the first query
    sees, kept inside the table. numpy or jax alike, as ``slot_lengths``:
    the program takes the sub-table with it and the scheduler counts the
    pages the step's kernel fetches from it."""
    return ((positions - (window - 1)) // page_size).clip(0, pages_per_slot - pw)


def mosaic_tiles(row_width: int, heads: int, page_size: int, dtype) -> bool:
    """Whether Mosaic can tile the kernel at this geometry — what the
    scheduler asks before it picks the kernel (``decode_scheduler.
    _step_attn_kernel``), so a geometry outside it keeps the gather path
    instead of failing to compile on the chip. A float pool whose token row
    is whole 128-lane tiles (gpt2-xl's 1600 is not), heads that divide a
    tile (64 here; 32 and 128 compile too), and pages of whole sublane
    tiles, 8 rows of float32 or 16 of a two-byte float (a page is the
    destination of one DMA). Found by compiling for a described v5e over
    rows 128-1600, heads of 32-128, pages of 4-32 rows and tables of 1-44
    pages (tests/test_tpu_compile.py keeps the edges)."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize not in (2, 4):
        return False
    if row_width % _LANES or row_width % heads or _LANES % (row_width // heads):
        return False
    return page_size % (8 * 4 // dtype.itemsize) == 0


def _decode_kernel(
    layer_ref, bt_ref, len_ref,  # scalar prefetch
    q_ref, k_hbm, v_hbm,  # inputs
    o_ref,  # output
    buf, s_ref, acc_ref, sem,  # scratch
    *, n_slots: int, page_size: int, pages_per_block: int, head_dim: int,
):
    bt_rows = pages_per_block * page_size  # token rows of one block
    n_tiles, lt = acc_ref.shape[0], acc_ref.shape[1]  # lane tiles of a token row
    per_tile = lt // head_dim  # whole heads in a tile
    layer = layer_ref[0]

    def n_pages(slot):
        return (len_ref[slot] + page_size - 1) // page_size

    def n_blocks(slot):
        return (n_pages(slot) + pages_per_block - 1) // pages_per_block

    def page_copies(slot, kind, blk, b, fn):
        """``fn`` (start or wait) on the block's page DMAs, a descriptor a
        page: ``pool[layer, bt[slot, blk*B + i]]`` into rows ``[i*ps,
        (i+1)*ps)`` of buffer ``b``, for the pages the slot has."""
        first = blk * pages_per_block
        cnt = jnp.minimum(n_pages(slot) - first, pages_per_block)
        for i in range(pages_per_block):
            @pl.when(i < cnt)
            def _():
                page = bt_ref[slot, first + i]
                dst = buf.at[b, pl.ds(i * page_size, page_size)]
                for k, pool_hbm in enumerate((k_hbm, v_hbm)):
                    @pl.when(kind == k)
                    def _():
                        fn(pltpu.make_async_copy(pool_hbm.at[layer, page], dst, sem.at[b]))

    def start(c):
        c.start()

    def wait(c):
        c.wait()

    def advance(slot, kind, blk):
        """The item after (slot, kind, blk): next block, else the slot's V
        blocks, else the next slot's K blocks."""
        last = blk + 1 >= n_blocks(slot)
        return (
            jnp.where(last & (kind == 1), slot + 1, slot),
            jnp.where(last, 1 - kind, kind),
            jnp.where(last, 0, blk + 1),
        )

    def tile(ref, j):
        """Lane tile ``j`` of a ``[.., rows, w]`` ref's last two dimensions."""
        return ref[:, j * lt:(j + 1) * lt].astype(jnp.float32)

    total = lax.fori_loop(0, n_slots, lambda i, t: t + 2 * n_blocks(i), jnp.int32(0))
    # unfetched rows of a block hold whatever the buffer held, and the score
    # rows past the last head are never written: keep both finite
    buf[...] = jnp.zeros_like(buf)
    s_ref[...] = jnp.zeros_like(s_ref)
    page_copies(0, 0, 0, 0, start)

    def item(t, carry):
        slot, kind, blk = carry
        b = t % 2
        nxt = advance(slot, kind, blk)

        @pl.when(t + 1 < total)
        def _():
            page_copies(*nxt, 1 - b, start)

        page_copies(slot, kind, blk, b, wait)
        nb = n_blocks(slot)

        @pl.when(kind == 0)
        def _scores():
            tok = blk * bt_rows + lax.broadcasted_iota(jnp.int32, (1, bt_rows), 1)
            live = tok < len_ref[slot]
            for j in range(n_tiles):
                prod_t = (tile(buf.at[b], j) * tile(q_ref.at[slot], j)).T  # [lt, bt_rows]
                for hh in range(per_tile):
                    head = j * per_tile + hh
                    row = jnp.sum(prod_t[hh * head_dim:(hh + 1) * head_dim, :], axis=0, keepdims=True)
                    s_ref[blk, head:head + 1, :] = jnp.where(live, row, NEG_INF)

            @pl.when(blk + 1 >= nb)
            def _softmax():
                heads_pad = s_ref.shape[1]
                m = lax.fori_loop(
                    0, nb,
                    lambda i, m: jnp.maximum(m, jnp.max(s_ref[i], axis=1, keepdims=True)),
                    jnp.full((heads_pad, 1), NEG_INF, jnp.float32),
                )

                def exp_sum(i, l):
                    e = jnp.exp(s_ref[i] - m)
                    s_ref[i] = e
                    return l + jnp.sum(e, axis=1, keepdims=True)

                l = lax.fori_loop(0, nb, exp_sum, jnp.zeros((heads_pad, 1), jnp.float32))

                def divide(i, _):
                    s_ref[i] = s_ref[i] / l
                    return 0

                lax.fori_loop(0, nb, divide, 0)
                acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(kind == 1)
        def _context():
            for j in range(n_tiles):
                p_t = jnp.concatenate(  # each head's probability row over its sublanes
                    [
                        jnp.broadcast_to(
                            s_ref[blk, j * per_tile + hh:j * per_tile + hh + 1, :], (head_dim, bt_rows)
                        )
                        for hh in range(per_tile)
                    ],
                    axis=0,
                )
                acc_ref[j] += tile(buf.at[b], j).T * p_t

            @pl.when(blk + 1 >= nb)
            def _out():
                for j in range(n_tiles):
                    o_ref[slot, :, j * lt:(j + 1) * lt] = jnp.sum(acc_ref[j].T, axis=0, keepdims=True)

        return nxt

    lax.fori_loop(0, total, item, (jnp.int32(0), jnp.int32(0), jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def paged_attention_decode(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    layer,
    bt: jax.Array,
    lengths: jax.Array,
    *,
    heads: int,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention of ``n`` slots over the page pool.

    q ``[n, h*hd]`` (already scaled), pool_k / pool_v ``[L, P, ps, h*hd]``
    (the whole pool, any float dtype), ``layer`` the layer to read, bt ``[n,
    pages_per_slot]`` int32, lengths ``[n]`` int32 in ``[1, pages_per_slot *
    ps]`` (``positions + 1``). Returns ctx ``[n, h*hd]`` float32: for each
    slot softmax(q·K[:length]) · V[:length], per head, heads merged.

    Jitted with ``layer`` traced: a step's 36 calls trace the kernel and
    lower it to Mosaic once, not 36 times (a second of Python each, every
    boot, before the compile cache is even asked)."""
    n, w = q.shape
    _, _, ps, pw = pool_k.shape
    if pw != w or pool_v.shape != pool_k.shape:
        raise ValueError(f"query width {w} against pool rows {pool_k.shape} / {pool_v.shape}")
    if interpret and jax.default_backend() != "cpu":
        raise ValueError("paged_attention_decode(interpret=True) is for the CPU backend")
    if not interpret and not mosaic_tiles(w, heads, ps, pool_k.dtype):
        raise ValueError(
            f"paged_attention_decode cannot tile {heads} heads over {pool_k.dtype} rows of {w} in "
            f"pages of {ps} for Mosaic (mosaic_tiles): this geometry keeps the gather path"
        )
    # a row's lane tiles: 128 wide on the chip; the interpreter's tiny sizes may be one odd tile
    lt = _LANES if w % _LANES == 0 else w
    if w % heads or lt % (w // heads):
        raise ValueError(f"{heads} heads over a row of {w}: a head must divide a {lt}-lane tile")
    pages_per_slot = bt.shape[1]
    bpb = min(PAGES_PER_BLOCK, pages_per_slot)
    bt_rows = bpb * ps
    n_blk = -(-pages_per_slot // bpb)
    kernel = functools.partial(
        _decode_kernel, n_slots=n, page_size=ps, pages_per_block=bpb, head_dim=w // heads
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(),
            in_specs=[vmem, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, bt_rows, w), pool_k.dtype),  # the two blocks in flight / in use
                pltpu.VMEM((n_blk, -(-heads // 8) * 8, bt_rows), jnp.float32),  # a slot's scores, then probabilities
                pltpu.VMEM((w // lt, lt, bt_rows), jnp.float32),  # a slot's context, transposed, tokens not yet summed
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, 1, w), jnp.float32),
        interpret=interpret,
        name="paged_attention_decode",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), bt.astype(jnp.int32), lengths.astype(jnp.int32),
        q.astype(jnp.float32).reshape(n, 1, w), pool_k, pool_v,
    )
    return out.reshape(n, w)
