"""The grouped-query kernels (ops/gqa_decode.py: the step's, one query a
slot; the chunk's, many) in the Pallas interpreter on the CPU, against
``_paged_gather`` + ``_attend`` (the gather path of models/conv_decoder.py,
models/hybrid_decoder.py and models/moe_decoder.py): float32 pool, float32
mathematics on both sides, read from the pool's pages in place and only as
far as each slot's length; then a whole step of each of the two families
with the kernel against the same step through the gather, and a scheduler
whose frames count what the kernel fetched.

Tolerance: both sides are float32 on the same products (a float32 pool's
probabilities go into the context product whole, ``gqa_decode_attention``).
They differ in the order of the sums and in the online softmax's rescaling:
``ATOL`` 2e-5 on values of order 1 (measured here: under 1e-6). One bfloat16
cast of K, V, the scores or the probabilities would show as 4e-3.

What the interpreter cannot see (tiling, VMEM, DMA alignment) is
tests/test_tpu_compile.py's: the same kernel compiled for a described v5e.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models import conv_decoder as cd
from seldon_core_tpu.models import hybrid_decoder as hd
from seldon_core_tpu.models.decoder import _paged_gather
from seldon_core_tpu.models.moe_decoder import _attend, _window_table
from seldon_core_tpu.ops import gqa_decode as gqa
from seldon_core_tpu.ops import mla as mla_ops
from seldon_core_tpu.serving import decode_programs as dp
from seldon_core_tpu.serving import decode_scheduler as ds

ATOL = 2e-5
L, N_PAGES, PS, HEAD_DIM = 2, 48, 4, 8
PAGES = 10  # a table: with runs of 2 entries in blocks of 4, three blocks (4, 4, 2 + 2 of padding)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Runs of 2 table entries, blocks of 4: a 10-page table has a block
    boundary inside it and a last block half of padding."""
    monkeypatch.setattr(mla_ops, "RUN_PAGES", 2)
    monkeypatch.setattr(mla_ops, "BLOCK_PAGES", 4)


def _pool(rng, kv_heads):
    return tuple(
        jnp.asarray(rng.standard_normal((L, N_PAGES, PS, kv_heads * HEAD_DIM)), jnp.float32) for _ in range(2)
    )


def _gather_attention(q, pool, li, bt, positions, scale):
    """The families' gather path for one query a slot: the oracle."""
    ck, cv = _paged_gather(pool, li, bt, pool[0].shape[-1] // HEAD_DIM)
    visible = jnp.arange(ck.shape[2])[None, None, :] <= positions[:, None, None]
    return np.asarray(_attend(q[:, None], ck, cv, visible, scale=scale)[:, 0])


def _kernel(q, pool, li, bt, positions, rows, scale):
    reads = gqa.step_reads(bt, positions, rows, PS)
    return np.asarray(gqa.gqa_decode_attention(q, pool[0], pool[1], li, bt, *reads, scale=scale, interpret=True))


def _tables(kind: str):
    """(bt [n, PAGES], positions [n]) of a named case; every slot's pages are
    its own (page 0 is the junk page)."""
    rng = np.random.default_rng(7)
    scattered = 1 + rng.permutation(N_PAGES - 1)[: 4 * PAGES].reshape(4, PAGES)
    # scattered: no two neighbours of a table consecutive
    scattered = np.where(np.diff(scattered, axis=1, append=-5) == 1, scattered[:, ::-1], scattered)
    in_runs = 1 + np.arange(4 * PAGES).reshape(4, PAGES)
    mixed = in_runs.copy()
    mixed[:, 2:4] = mixed[:, 3:1:-1]  # the second group descends: a DMA a page between two runs
    mixed[:, 7] = scattered[:, 7] + 0
    full = PAGES * PS - 1
    # lengths: one key, a page's last key and the next page's first, a block's
    # (4 pages = 16 keys) last and the next block's first, the whole table
    edges = {
        "page_edges": [0, PS - 1, PS, 2 * PS - 1],
        "block_edges": [4 * PS - 1, 4 * PS, 8 * PS - 1, 8 * PS],
        "long": [full, full - 1, 5 * PS + 1, 8 * PS + 2],
    }
    return {"runs": in_runs, "scattered": scattered, "mixed": mixed}[kind], edges


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("kind", ["runs", "scattered", "mixed"])
def test_kernel_matches_the_gather_path(kind, group):
    """Groups of 1, 4 and 8 query heads a K/V head over tables that lie in
    runs, are wholly scattered, and mix the two, lengths at page and block
    edges, both layers: the gather path's context to float32 rounding, and
    the run flags are what the tables say."""
    kv_heads = 2
    heads = group * kv_heads
    rng = np.random.default_rng(11)
    pool = _pool(rng, kv_heads)
    bt_np, edges = _tables(kind)
    bt = jnp.asarray(bt_np, jnp.int32)
    rows = jnp.ones((4,), bool)
    for name, positions in edges.items():
        positions = jnp.asarray(positions, jnp.int32)
        q = jnp.asarray(rng.standard_normal((4, heads, HEAD_DIM)), jnp.float32)
        for li in range(L) if name == "long" else (1,):
            want = _gather_attention(q, pool, li, bt, positions, 0.3)
            got = _kernel(q, pool, li, bt, positions, rows, 0.3)
            assert got.shape == (4, heads * HEAD_DIM)
            np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    lengths, runs = gqa.step_reads(bt, jnp.full((4,), PAGES * PS - 1, jnp.int32), rows, PS)
    assert runs.shape == (4, 6)  # three blocks of two groups; the last group is past the table
    want_runs = {"runs": [1, 1, 1, 1, 1, 0], "scattered": [0] * 6, "mixed": [1, 0, 1, 0, 1, 0]}[kind]
    assert np.asarray(runs).tolist() == [want_runs] * 4
    assert np.asarray(gqa.pages_fetched(lengths, runs, PS, PAGES)).tolist() == [4 * PAGES, 4 * 2 * sum(want_runs)]


def test_kernel_stops_at_the_length_and_a_nan_past_it_never_reaches_the_output():
    """Every row no slot's query may see holds NaN, in K and in V: the tail
    of each slot's last page (fetched with the page: weighed exactly 0) and
    every later page of its table (never fetched). The output is the clean
    pool's."""
    rng = np.random.default_rng(13)
    clean = _pool(rng, 2)
    bt_np, _ = _tables("mixed")
    positions = np.array([0, PS + 1, 4 * PS - 1, 8 * PS + 2])
    seen = np.zeros((N_PAGES, PS), bool)
    for row, pos in zip(bt_np, positions):
        for j, page in enumerate(row):
            seen[page, : max(0, min(PS, pos + 1 - j * PS))] = True
    assert not seen[bt_np].all(axis=-1).all()  # there are tails
    poisoned = tuple(jnp.where(seen[None, :, :, None], a, jnp.nan) for a in clean)
    bt, pos = jnp.asarray(bt_np, jnp.int32), jnp.asarray(positions, jnp.int32)
    q = jnp.asarray(rng.standard_normal((4, 8, HEAD_DIM)), jnp.float32)
    rows = jnp.ones((4,), bool)
    want = _gather_attention(q, clean, 1, bt, pos, 0.3)
    got = _kernel(q, poisoned, 1, bt, pos, rows, 0.3)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    np.testing.assert_array_equal(got, _kernel(q, clean, 1, bt, pos, rows, 0.3))


def test_a_slot_outside_rows_costs_one_page_whatever_its_table_holds():
    """A prefilling slot (a cursor deep in its table) and a free slot (a
    table of junk pages) beside two that generate: the kernel reads ONE key
    of each, so every page of theirs but the first may hold NaN; the
    generating slots' outputs are what they are alone."""
    rng = np.random.default_rng(17)
    pool = _pool(rng, 2)
    bt_np, _ = _tables("runs")
    bt_np[3] = 0  # a free slot: the junk page all along
    positions = jnp.asarray([5 * PS, 7 * PS + 1, PAGES * PS - 1, 0], jnp.int32)
    rows = jnp.asarray([True, False, True, False])
    poisoned = tuple(a.at[:, bt_np[1, 1:]].set(jnp.nan) for a in pool)
    bt = jnp.asarray(bt_np, jnp.int32)
    q = jnp.asarray(rng.standard_normal((4, 8, HEAD_DIM)), jnp.float32)
    lengths, runs = gqa.step_reads(bt, positions, rows, PS)
    assert np.asarray(lengths).tolist() == [5 * PS + 1, 1, PAGES * PS, 1]
    # 6 + 1 + 10 + 1 pages, of them the generating slots' whole groups in runs
    assert np.asarray(gqa.pages_fetched(lengths, runs, PS, PAGES)).tolist() == [18, 6 + 10]
    got = _kernel(q, poisoned, 0, bt, positions, rows, 0.3)
    want = _gather_attention(q, pool, 0, bt, jnp.where(rows, positions, 0), 0.3)
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)  # one key: that key's V row, for every head of its group
    np.testing.assert_allclose(got[1].reshape(2, 4, HEAD_DIM)[:, 0], np.asarray(pool[1][0, bt_np[1, 0], 0]).reshape(2, HEAD_DIM), atol=ATOL)


def test_two_byte_pool_takes_the_probabilities_in_three_terms():
    """A bfloat16 pool under the interpreter: the probabilities enter the
    context product as three bfloat16 terms, so the context is the float32
    gather path's over the same stored values to float32 rounding, where one
    term would leave bfloat16's 4e-3."""
    rng = np.random.default_rng(19)
    pool = tuple(a.astype(jnp.bfloat16) for a in _pool(rng, 2))
    bt_np, _ = _tables("mixed")
    bt = jnp.asarray(bt_np, jnp.int32)
    positions = jnp.asarray([PAGES * PS - 1, 3, 4 * PS, 6 * PS + 2], jnp.int32)
    q = jnp.asarray(rng.standard_normal((4, 8, HEAD_DIM)), jnp.float32).astype(jnp.bfloat16).astype(jnp.float32)
    want = _gather_attention(q, pool, 1, bt, positions, 0.3)  # the gather upcasts the rows; q is float32 here
    got = _kernel(q, pool, 1, bt, positions, jnp.ones((4,), bool), 0.3)
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)


@pytest.mark.parametrize(
    "width, heads, kv_heads, page_size, dtype, want",
    [
        (512, 32, 8, 16, jnp.bfloat16, True),  # the lfm2-24b-a2b and granite-4.0-h-micro cells
        (512, 32, 8, 32, jnp.float16, True),
        (512, 32, 4, 16, jnp.bfloat16, True),  # heads of 128
        (512, 32, 8, 16, jnp.float32, False),  # a four-byte pool
        (512, 32, 8, 16, jnp.int8, False),
        (512, 32, 8, 8, jnp.bfloat16, False),  # a page under a two-byte sublane tile
        (320, 20, 5, 16, jnp.bfloat16, False),  # rows of 2.5 lane tiles
        (768, 24, 4, 16, jnp.bfloat16, False),  # heads of 192 neither divide a tile nor are whole tiles
        (512, 16, 2, 16, jnp.bfloat16, True),  # the qwen3-next-80b-a3b cell: heads of 256, two lane tiles each
        (512, 8, 8, 16, jnp.bfloat16, False),  # 8 query heads: half a sublane tile of the query
    ],
)
def test_gqa_tiles_names_what_mosaic_can_tile(width, heads, kv_heads, page_size, dtype, want):
    assert gqa.gqa_tiles(width, heads, kv_heads, page_size, dtype) is want


@pytest.mark.parametrize(
    "width, heads, kv_heads, want",
    [
        (1024, 48, 8, True),  # the laguna-s-2.1 cell's full layers: 6 to a group, three sublane tiles
        (1024, 72, 8, True),  # its sliding layers: 9 to a group, padded to 80 rows
        (512, 32, 4, True),  # the mellum2-12b-a2.5b cell: 8 to a group
        (1024, 40, 8, True),  # 5 to a group
        (1024, 44, 8, False),  # 5.5 to a group
        (1024, 8, 8, False),  # one to a group: ops/paged_attention.py's geometry
    ],
)
def test_gqa_tiles_takes_any_whole_number_of_groups(width, heads, kv_heads, want):
    assert gqa.gqa_tiles(width, heads, kv_heads, 16, jnp.bfloat16) is want


# --------------------------------- a windowed sub-table with a first key a slot

WINDOW = 8  # two pages: a sub-table of ceil(9 / 4) + 1 = 4 entries, one block of two runs


def _window_gather_attention(q, pool, li, bt, positions, window, scale):
    """A sliding layer's gather path for one query a slot (``moe_decoder.
    _layer``): the windowed sub-table's pages, masked by absolute position."""
    bt_w, k0 = _window_table(bt, positions, 1, PS, window)
    ck, cv = _paged_gather(pool, li, bt_w, pool[0].shape[-1] // q.shape[-1])
    k_pos = k0[:, None] + jnp.arange(ck.shape[2])[None, :]
    visible = (k_pos <= positions[:, None]) & (positions[:, None] - k_pos < window)
    return np.asarray(_attend(q[:, None], ck, cv, visible[:, None, :], scale=scale)[:, 0])


def _window_kernel(q, pool, li, bt, positions, rows, window, scale):
    bt_w, k0 = _window_table(bt, positions, 1, PS, window)
    reads = gqa.step_reads(bt_w, positions, rows, PS, k0, window)
    out = gqa.gqa_decode_attention(q, pool[0], pool[1], li, bt_w, *reads, scale=scale, interpret=True)
    return np.asarray(out), reads


@pytest.mark.parametrize("window", [WINDOW, 20, 64], ids=["one_block", "two_blocks", "whole_table"])
def test_windowed_table_with_a_first_key_matches_the_gather_and_never_reads_a_given_back_page(window):
    """The window kind's table as the allocator leaves it: every entry wholly
    older than a slot's window is junk page 0, and page 0 holds NaN in K and
    V (so do the tails past each slot's position). The kernel walks the
    sub-table from its first entry, weighs the rows before ``first`` exactly
    0 and zeroes their V in VMEM: the clean pool's gather-path context. The
    positions put the first key on a page's first row, inside a page, two
    pages into the sub-table (the table's end clips the sub-table's start),
    and before the table's first row (a context shorter than the window)."""
    rng = np.random.default_rng(31)
    clean = _pool(rng, 2)
    bt_np = 1 + np.arange(4 * PAGES).reshape(4, PAGES)
    bt_np[1] = bt_np[1, ::-1]  # one slot's pages descend: a DMA a page
    full = PAGES * PS - 1
    positions = np.minimum([window - 1 + PS, window + 5, full, min(window, PAGES * PS) - 3], full)
    seen = np.zeros((N_PAGES, PS), bool)
    for row, pos in zip(bt_np, positions):
        for at in range(max(pos - window + 1, 0), pos + 1):
            seen[row[at // PS], at % PS] = True
        row[: max(pos - window + 1, 0) // PS] = 0  # given back
    assert (bt_np == 0).any() or window >= PAGES * PS
    poisoned = tuple(jnp.where(seen[None, :, :, None], a, jnp.nan) for a in clean)
    assert np.isnan(np.asarray(poisoned[1][0, 0])).all()  # the junk page
    bt, pos = jnp.asarray(bt_np, jnp.int32), jnp.asarray(positions, jnp.int32)
    q = jnp.asarray(rng.standard_normal((4, 8, HEAD_DIM)), jnp.float32)
    rows = jnp.ones((4,), bool)
    want = _window_gather_attention(q, clean, 1, bt, pos, window, 0.3)
    got, (lengths, runs, first) = _window_kernel(q, poisoned, 1, bt, pos, rows, window, 0.3)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    pw = min(-(-(window + 1) // PS) + 1, PAGES)
    assert runs.shape[0] == 4 and np.asarray(lengths).max() <= pw * PS
    if window < PAGES * PS:
        # the oldest visible key's place in the sub-table: 0 on a page's first row, then inside a page, then
        # two pages and three rows in (clipped at the table's end), then a context shorter than the window
        p0 = np.clip((positions - (window - 1)) // PS, 0, PAGES - pw)
        assert np.asarray(first).tolist() == np.maximum(positions - (window - 1) - p0 * PS, 0).tolist()
        assert np.asarray(first)[2] >= 2 * PS and np.asarray(first)[3] == 0
        assert np.asarray(lengths).tolist() == (positions + 1 - p0 * PS).tolist()
    # a slot outside ``rows`` reads one key of its sub-table's first page, whatever lies after
    some = jnp.asarray([True, False, True, False])
    got_some, (lengths, _runs, first) = _window_kernel(q, poisoned, 1, bt, pos, some, window, 0.3)
    assert np.asarray(lengths)[[1, 3]].tolist() == [1, 1] and np.asarray(first)[[1, 3]].tolist() == [0, 0]
    np.testing.assert_array_equal(got_some[[0, 2]], got[[0, 2]])


def test_a_block_wholly_before_the_first_key_adds_nothing():
    """``first`` past the first block's last row (no table of ``_window_table``
    puts it there; the kernel does not lean on that): the block's scores are
    all masked, and what its keys weigh against a maximum of the mask value
    is wiped by the online softmax's rescaling at the first block that holds
    a visible key."""
    rng = np.random.default_rng(37)
    pool = _pool(rng, 2)
    bt = jnp.asarray(1 + np.arange(2 * PAGES).reshape(2, PAGES), jnp.int32)
    positions = jnp.asarray([PAGES * PS - 1, 7 * PS], jnp.int32)
    first = jnp.asarray([4 * PS + 3, 5 * PS], jnp.int32)  # blocks of 4 pages = 16 rows
    lengths, runs = gqa.step_reads(bt, positions, None, PS)
    q = jnp.asarray(rng.standard_normal((2, 8, HEAD_DIM)), jnp.float32)
    got = np.asarray(gqa.gqa_decode_attention(q, pool[0], pool[1], 0, bt, lengths, runs, first, scale=0.3, interpret=True))
    ck, cv = _paged_gather(pool, 0, bt, 2)
    k_pos = jnp.arange(ck.shape[2])[None, :]
    visible = (k_pos <= positions[:, None]) & (k_pos >= first[:, None])
    want = np.asarray(_attend(q[:, None], ck, cv, visible[:, None, :], scale=0.3)[:, 0])
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)


@pytest.mark.parametrize("heads", [48, 72])
def test_groups_of_six_and_nine_heads_of_128_match_the_gather_path(heads):
    """The laguna-s-2.1 cell's two head counts over 8 K/V heads of 128 (rows
    of 1024): 48 query heads are three sublane tiles of the block-diagonal
    query, 72 are padded to 80 with zero rows that the caller never sees.
    Full table and windowed sub-table alike."""
    rng = np.random.default_rng(41)
    d, kv = 128, 8
    pool = tuple(jnp.asarray(rng.standard_normal((1, 12, PS, kv * d)), jnp.float32) for _ in range(2))
    bt = jnp.asarray([[1, 2, 3, 4, 5], [11, 9, 10, 7, 8]], jnp.int32)
    positions = jnp.asarray([5 * PS - 1, 2 * PS + 1], jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, heads, d)), jnp.float32)
    wide = np.asarray(gqa._block_diagonal(q, kv))
    assert wide.shape == (2, 48 if heads == 48 else 80, kv * d) and not wide[:, heads:].any()
    ck, cv = _paged_gather(pool, 0, bt, kv)
    visible = jnp.arange(ck.shape[2])[None, None, :] <= positions[:, None, None]
    want = np.asarray(_attend(q[:, None], ck, cv, visible, scale=d**-0.5)[:, 0])
    reads = gqa.step_reads(bt, positions, None, PS)
    got = np.asarray(gqa.gqa_decode_attention(q, pool[0], pool[1], 0, bt, *reads, scale=d**-0.5, interpret=True))
    assert got.shape == (2, heads * d)
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    want = _window_gather_attention(q, pool, 0, bt, positions, 6, d**-0.5)
    got, _reads = _window_kernel(q, pool, 0, bt, positions, None, 6, d**-0.5)
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)


def test_heads_of_two_lane_tiles_match_the_gather_path():
    """The qwen3-next-80b-a3b cell's 16 query heads over 2 K/V heads of 256
    (rows of 512): a head's numbers span two lane tiles of the block-diagonal
    query, the scores are summed over both in the one product."""
    rng = np.random.default_rng(57)
    d, kv, heads = 256, 2, 16
    pool = tuple(jnp.asarray(rng.standard_normal((1, 12, PS, kv * d)), jnp.float32) for _ in range(2))
    bt = jnp.asarray([[1, 2, 3, 4, 5], [11, 9, 10, 7, 8]], jnp.int32)
    positions = jnp.asarray([5 * PS - 1, 2 * PS + 1], jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, heads, d)), jnp.float32)
    ck, cv = _paged_gather(pool, 0, bt, kv)
    visible = jnp.arange(ck.shape[2])[None, None, :] <= positions[:, None, None]
    want = np.asarray(_attend(q[:, None], ck, cv, visible, scale=d**-0.5)[:, 0])
    reads = gqa.step_reads(bt, positions, None, PS)
    got = np.asarray(gqa.gqa_decode_attention(q, pool[0], pool[1], 0, bt, *reads, scale=d**-0.5, interpret=True))
    assert got.shape == (2, heads * d)
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)


def test_the_kernel_refuses_what_it_cannot_read():
    rng = np.random.default_rng(1)
    pool = _pool(rng, 2)
    bt = jnp.ones((2, PAGES), jnp.int32)
    reads = gqa.step_reads(bt, jnp.zeros((2,), jnp.int32), None, PS)
    q = jnp.zeros((2, 6, HEAD_DIM), jnp.float32)
    with pytest.raises(ValueError, match="against pool rows"):  # 6 heads over 2 K/V heads is fine, over rows of 3 is not
        gqa.gqa_decode_attention(jnp.zeros((2, 6, 3), jnp.float32), pool[0], pool[1], 0, bt, *reads, scale=1.0, interpret=True)
    with pytest.raises(ValueError, match="gqa_tiles"):  # a float32 pool of pages of 4 is not Mosaic's
        gqa.gqa_decode_attention(q, pool[0], pool[1], 0, bt, *reads, scale=1.0)
    with pytest.raises(ValueError, match="step_reads"):
        gqa.gqa_decode_attention(q, pool[0], pool[1], 0, bt, reads[0], reads[1][:, :2], scale=1.0, interpret=True)


# ------------------------------------------------- a whole step of each family

CONV = cd.ConvDecoderConfig(
    vocab=96, hidden=64, layers=4, attn_layers=(1, 3), heads=8, kv_heads=2, head_dim=HEAD_DIM, dense_layers=1,
    dense_ffn=96, ffn=32, experts=8, experts_held=4, first_expert=2, experts_per_tok=2,
)
HYBRID = hd.HybridDecoderConfig(
    vocab=96, hidden=64, layers=3, attn_layers=(1,), heads=8, kv_heads=2, head_dim=HEAD_DIM, ffn=64, ssm_heads=4,
    ssm_head_dim=16, ssm_state=8, ssm_conv=4,
)


def _lively(params, seed=3):
    """Norm weights drawn round one (at exactly one a head's norm commutes
    with its rotation and their order could not be told) and every matrix
    four times larger, as tests/test_conv_decoder.py draws them."""
    keys = iter(jax.random.split(jax.random.key(seed), 64))

    def leaf(path, a):
        name = path[-1].key
        if name in ("ln1", "ln2", "ln_f", "q_norm", "k_norm", "ssm_norm"):
            return (1.0 + 0.3 * jax.random.normal(next(keys), a.shape)).astype(a.dtype)
        small = ("tok_emb", "router_bias", "conv_w", "conv_b", "A_log", "D", "dt_bias")
        return a if name in small or a.ndim < 2 else a * 4

    return jax.tree_util.tree_map_with_path(leaf, params)


def _family(name):
    if name == "conv":
        fam = cd.conv_family(CONV)
        return fam, _lively(cd.init_conv_decoder(CONV, seed=5, dtype=jnp.float32))
    fam = hd.hybrid_family(HYBRID)
    return fam, _lively(hd.init_hybrid_decoder(HYBRID, seed=5, dtype=jnp.float32))


@pytest.mark.parametrize("name", ["conv", "hybrid"])
def test_a_family_step_with_the_kernel_equals_the_gather_step(name):
    """Three slots: one mid-generation over a prefilled context, one
    prefilling (outside ``rows``), one free. A chunk prefills through the
    gather, then three steps run twice from the same pool and state rows: the logits of
    the generating slot agree to float32 rounding, the pool and the state
    rows come back the same where anyone reads them, and the step counts the
    run pages."""
    fam, params = _family(name)
    rng = np.random.default_rng(23)
    n, ctx = 3, 22
    pool = fam.paged_kv_init(params, 1 + 2 * PAGES, PS, jnp.float32)
    rec = fam.state_init(params, n + 2)
    bt = np.zeros((n, PAGES), np.int32)
    bt[0] = 1 + np.arange(PAGES)
    bt[1] = 1 + PAGES + np.arange(PAGES)[::-1]  # the prefilling slot's pages descend
    ids = rng.integers(0, 96, (n, ctx)).astype(np.int32)
    own = np.arange(n, dtype=np.int32)
    rows3 = jnp.asarray(np.stack([np.full(n, n + 1), own, np.full(n, n + 2)]), jnp.int32)  # read the zero row, write the slot's own
    logits, pool, rec, _ = fam.paged_forward(
        params, pool, rec, jnp.asarray(bt), jnp.asarray(ids), jnp.zeros((n,), jnp.int32),
        counts=jnp.asarray([ctx, 9, 0], jnp.int32), pick=jnp.asarray([ctx - 1, 8, 0], jnp.int32), state_rows=rows3,
    )
    assert gqa.step_reads(jnp.asarray(bt), jnp.zeros((n,), jnp.int32), None, PS)[1].shape == (n, 6)
    chunked = fam.paged_forward(  # a chunk through the chunk's kernel counts no run page: the count is the step's
        params, pool, rec, jnp.asarray(bt), jnp.asarray(ids[:, :2]), jnp.zeros((n,), jnp.int32),
        counts=jnp.zeros((n,), jnp.int32), pick=jnp.zeros((n,), jnp.int32), state_rows=rows3 * 0 + n + 2,
        attn_kernel="interpret",
    )
    assert int(chunked[3][-1]) == 0
    tok = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
    state = {k: (pool, rec, tok.copy()) for k in ("", "interpret")}
    rows = jnp.asarray([True, False, False])
    for at in range(3):
        out = {}
        for kernel in state:
            pool_k, rec_k, tok_k = state[kernel]
            positions = jnp.asarray([ctx + at, 9, 0], jnp.int32)
            logits, pool_k, rec_k, counted = fam.paged_forward(
                params, pool_k, rec_k, jnp.asarray(bt), jnp.asarray(tok_k)[:, None], positions, rows=rows,
                attn_kernel=kernel,
            )
            out[kernel] = np.asarray(logits[0, 0])
            # slot 0 holds ctx + at + 1 keys, its pages 1.. in order: every whole group of 2 a run; the others one page each
            want_runs = 2 * (-(-(ctx + at + 1) // PS) // 2) if kernel else 0
            assert int(counted[-1]) == want_runs
            assert len(counted) == len(fam.frame_counters) and fam.frame_counters[-1] == "attn_run_pages"
            state[kernel] = (pool_k, rec_k, np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32))
        # to 1e-4 of the logits' range (the hybrid family's are small: logits_scaling): a bfloat16 cast would leave 4e-3
        np.testing.assert_allclose(out["interpret"], out[""], rtol=0, atol=1e-4 * np.abs(out[""]).max())
    # the generating slot's pages and every state row (the others' junk rows,
    # written where no mask reaches, follow a context of one key: not compared)
    for a, b in zip(state[""][0], state["interpret"][0]):
        np.testing.assert_allclose(np.asarray(a[:, bt[0]]), np.asarray(b[:, bt[0]]), rtol=0, atol=ATOL)
    for a, b in zip(state[""][1], state["interpret"][1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=ATOL)
    np.testing.assert_array_equal(state[""][2][0], state["interpret"][2][0])


# ------------------------------------------------------------- the scheduler

SEQ, MAX_NEW = 24, 6


def _scheduler(fam, params, **kw):
    return ds.DecodeScheduler(
        params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4, prefix_slots=0, prefill_chunk=8, kv_page_size=PS,
        family=fam, **kw,
    )


@pytest.mark.parametrize("name", ["conv", "hybrid"])
async def test_scheduler_with_the_kernel_step_serves_the_gather_steps_tokens_and_counts_its_pages(name, monkeypatch):
    """With the ONE place of choice answering "interpret" the scheduler
    serves the gather path's greedy tokens with no recompile, and every
    plain round's frame carries what the kernel's own length and run
    arithmetic fetch for the tables, positions and rows the step was handed:
    a generating slot's pages up to its length, ONE page for a prefilling or
    a free slot, and the pages among them in runs."""
    fam, params = _family(name)
    rng = np.random.default_rng(29)
    prompts = rng.integers(0, 96, (3, SEQ)).astype(np.int32)

    async def serve(sched):
        first = asyncio.ensure_future(sched.submit(prompts[0]))
        for _ in range(200):  # until the first request generates: the next two prefill beside it, 3 chunk rounds each
            await asyncio.sleep(0)
            if any(f.tokens and not f.prefilling for f in sched.flight.snapshot()):
                break
        return await asyncio.gather(first, *(sched.submit(p) for p in prompts[1:]))

    gather = _scheduler(fam, params)
    assert gather.programs.attn_kernel == ""  # the CPU backend: the oracle path
    gather.warmup()
    want = await serve(gather)
    table = 4 * gather.pool.pages_per_slot
    plain = [f for f in gather.flight.snapshot() if f.attn_pages_table]
    assert plain and all((f.attn_pages_read, f.attn_pages_table, f.attn_run_pages) == (table, table, 0) for f in plain)
    await gather.close()

    monkeypatch.setattr(dp, "_step_attn_kernel", lambda family, pool_state, mesh, heads, kv_heads: "interpret")
    kernel = _scheduler(fam, params)
    assert kernel.programs.attn_kernel == "interpret"
    kernel.warmup()
    handed = []
    step = kernel.programs.step

    def spy(bt, toks, pos, temps, topks, tick, rows):
        handed.append((np.array(bt), np.array(pos), np.array(rows)))
        return step(bt, toks, pos, temps, topks, tick, rows)

    monkeypatch.setattr(kernel.programs, "step", spy)
    got = await serve(kernel)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert kernel.recompiles_since_warmup() == 0
    frames = kernel.flight.snapshot()
    plain = [f for f in frames if f.attn_pages_table]
    assert len(plain) == len(handed) and {f.attn_pages_table for f in plain} == {table}
    pages = kernel.pool.pages_per_slot
    mixed = 0
    for f, (bt, pos, rows) in zip(plain, handed):
        reads = gqa.step_reads(jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(rows), PS)
        assert [f.attn_pages_read, f.attn_run_pages] == np.asarray(gqa.pages_fetched(*reads, PS, pages)).tolist()
        held = -(-(pos[rows] + 1) // PS)
        assert f.attn_pages_read == held.sum() + (~rows).sum()  # one page a slot that does not generate
        assert 0 <= f.attn_run_pages <= f.attn_pages_read and f.attn_run_pages % 2 == 0
        prefilling = int(((pos > 0) & ~rows).sum())
        mixed += bool(rows.any() and prefilling and (~rows).sum() > prefilling)
    assert mixed  # rounds with generating, prefilling AND free slots were among them
    assert any(f.attn_run_pages for f in plain)
    with_runs = next(f for f in plain if f.attn_run_pages)
    assert with_runs.to_dict()["attn_run_pages"] == with_runs.attn_run_pages
    # counted in the rounds that ran a plain step, and in no other
    assert all((f.attn_pages_table > 0) == (f.busy_ns[1] > 0) for f in frames)
    assert not any(f.attn_run_pages for f in frames if not f.attn_pages_table)
    await kernel.close()


# ------------------------------------------------ the chunk's kernel: many queries a row

M = 8  # a chunk's queries a row; query blocks of 4 (``_small_query_blocks``)


def _small_query_blocks(monkeypatch, heads, kv_heads, d, tq=4):
    """Key blocks of 4 table entries and query blocks of ``tq`` queries: a
    chunk of ``M`` is two work items a row."""
    monkeypatch.setattr(gqa, "CHUNK_BLOCK_PAGES", 4)
    monkeypatch.setattr(mla_ops, "CHUNK_Q_ROWS", tq * (gqa._tile_lanes(kv_heads * d, kv_heads) // d) * heads // kv_heads)
    assert gqa._chunk_query_block(M, heads, kv_heads, kv_heads * d) == tq


def _chunk_gather(q, pool, li, bt, positions, scale, window=0):
    """The families' gather path for a chunk (``moe_decoder.
    _gathered_attention``): the whole table, or a sliding layer's windowed
    sub-table, masked by absolute position. The oracle."""
    m, d = q.shape[1], q.shape[3]
    bt_l, k0 = _window_table(bt, positions, m, PS, window) if window else (bt, jnp.zeros_like(positions))
    ck, cv = _paged_gather(pool, li, bt_l, pool[0].shape[-1] // d)
    q_pos = positions[:, None] + jnp.arange(m)[None, :]
    k_pos = k0[:, None] + jnp.arange(ck.shape[2])[None, :]
    visible = k_pos[:, None, :] <= q_pos[:, :, None]
    if window:
        visible &= q_pos[:, :, None] - k_pos[:, None, :] < window
    return np.asarray(_attend(q, ck, cv, visible, scale=scale))


def _chunk_kernel(q, pool, li, bt, positions, counts, scale, window=0):
    m = q.shape[1]
    bt_l, k0 = _window_table(bt, positions, m, PS, window) if window else (bt, None)
    reads = gqa.chunk_reads(bt_l, positions, counts, PS, k0)
    out = gqa.gqa_chunk_attention(q, pool[0], pool[1], li, bt_l, *reads, scale=scale, window=window, interpret=True)
    return np.asarray(out), reads


def _agree_where_read(got, want, counts, tq=4):
    """Row i's real queries agree; a query block wholly past its count (and a
    row of count 0) is zeros; a query past the count inside a block with a
    real one is something finite."""
    assert np.isfinite(got).all()
    for i, c in enumerate(np.asarray(counts).tolist()):
        np.testing.assert_allclose(got[i, :c], want[i, :c], rtol=0, atol=ATOL)
        assert not got[i, -(-c // tq) * tq :].any()


# positions and counts of one dispatch: a context that ends mid-page (3 + 8 = 11 keys), one that ends on a key
# block's edge (8 + 8 = 16 keys = 4 pages), a padding row, one live query, three (the second query block: zeros)
CHUNK_POSITIONS, CHUNK_COUNTS = [3, 8, 0, 17, 29], [M, M, 0, 1, 3]


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("group", [4, 6, 9, 16])
@pytest.mark.parametrize("kind", ["runs", "scattered"])
def test_chunk_kernel_matches_the_gather_path(kind, group, d, monkeypatch):
    """Groups of 4, 6, 9 and 16 query heads a K/V head, heads of 64 (two K/V
    heads a lane tile: a block-diagonal pair) and 128, tables in runs and
    scattered; one dispatch holds counts of m, 0, 1 and 3, a context that
    ends mid-page and one on a key block's edge, and a query block past a
    row's live queries. Both layers."""
    kv_heads = 2
    heads = group * kv_heads
    _small_query_blocks(monkeypatch, heads, kv_heads, d)
    rng = np.random.default_rng(43)
    pool = tuple(jnp.asarray(rng.standard_normal((L, 64, PS, kv_heads * d)), jnp.float32) for _ in range(2))
    ids = 1 + np.arange(5 * PAGES).reshape(5, PAGES)
    if kind == "scattered":
        ids = 1 + rng.permutation(63)[: 5 * PAGES].reshape(5, PAGES)
        ids = np.where(np.diff(ids, axis=1, append=-5) == 1, ids[:, ::-1], ids)
    bt = jnp.asarray(ids, jnp.int32)
    positions, counts = jnp.asarray(CHUNK_POSITIONS, jnp.int32), jnp.asarray(CHUNK_COUNTS, jnp.int32)
    q = jnp.asarray(rng.standard_normal((5, M, heads, d)), jnp.float32)
    for li in range(L):
        got, reads = _chunk_kernel(q, pool, li, bt, positions, counts, 0.3)
        assert got.shape == (5, M, heads * d)
        _agree_where_read(got, _chunk_gather(q, pool, li, bt, positions, 0.3), counts)
    lengths, q_first, cnt, next_live, runs = (np.asarray(a) for a in reads)
    assert lengths.tolist() == [11, 16, 1, 18, 32] and q_first.tolist() == CHUNK_POSITIONS and cnt.tolist() == CHUNK_COUNTS
    assert next_live.tolist() == [0, 1, 3, 3, 4, 5]  # the padding row is nobody's next
    assert runs.shape == (5, 6)  # three key blocks of two groups of two entries
    whole = [[1, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0], [0] * 6, [1, 1, 0, 0, 0, 0], [1, 1, 1, 1, 0, 0]]
    assert runs.tolist() == (whole if kind == "runs" else [[0] * 6] * 5)


@pytest.mark.parametrize("kind", ["runs", "scattered"])
def test_chunk_kernel_takes_a_head_of_two_lane_tiles_as_one_tile(kind, monkeypatch):
    """Heads of 256 (the qwen3-next-80b-a3b cell's 16 / 2): a K/V head is the
    kernel's tile of 256 lanes (``_tile_lanes``), one product sums the scores
    over both lane tiles; the same dispatch as the narrower heads' case."""
    kv_heads, heads, d = 2, 16, 256
    assert gqa._tile_lanes(kv_heads * d, kv_heads) == 256 and gqa._tile_lanes(512, 8) == 128 and gqa._tile_lanes(32, 2) == 32
    _small_query_blocks(monkeypatch, heads, kv_heads, d)
    rng = np.random.default_rng(44)
    pool = tuple(jnp.asarray(rng.standard_normal((L, 64, PS, kv_heads * d)), jnp.float32) for _ in range(2))
    ids = 1 + np.arange(5 * PAGES).reshape(5, PAGES)
    if kind == "scattered":
        ids = 1 + rng.permutation(63)[: 5 * PAGES].reshape(5, PAGES)
        ids = np.where(np.diff(ids, axis=1, append=-5) == 1, ids[:, ::-1], ids)
    bt = jnp.asarray(ids, jnp.int32)
    positions, counts = jnp.asarray(CHUNK_POSITIONS, jnp.int32), jnp.asarray(CHUNK_COUNTS, jnp.int32)
    q = jnp.asarray(rng.standard_normal((5, M, heads, d)), jnp.float32)
    got, _ = _chunk_kernel(q, pool, 1, bt, positions, counts, d**-0.5)
    assert got.shape == (5, M, heads * d)
    _agree_where_read(got, _chunk_gather(q, pool, 1, bt, positions, d**-0.5), counts)


def test_chunk_kernel_reads_no_row_past_a_length_and_none_of_a_padding_rows_table(monkeypatch):
    """NaN in K and V on the junk page, on every row past each row's length
    (the tail of its last page, fetched with it; every later page, never
    fetched) and over the whole table of the row of count 0: the clean pool's
    context."""
    heads, kv_heads, d = 8, 2, 64
    _small_query_blocks(monkeypatch, heads, kv_heads, d)
    rng = np.random.default_rng(47)
    clean = tuple(jnp.asarray(rng.standard_normal((L, 64, PS, kv_heads * d)), jnp.float32) for _ in range(2))
    bt_np = 1 + np.arange(5 * PAGES).reshape(5, PAGES)
    seen = np.zeros((64, PS), bool)
    for row, pos, c in zip(bt_np, CHUNK_POSITIONS, CHUNK_COUNTS):
        for at in range(pos + c if c else 0):
            seen[row[at // PS], at % PS] = True
    poisoned = tuple(jnp.where(seen[None, :, :, None], a, jnp.nan) for a in clean)
    assert np.isnan(np.asarray(poisoned[1][0, 0])).all() and np.isnan(np.asarray(poisoned[0][0, bt_np[2]])).all()
    bt = jnp.asarray(bt_np, jnp.int32)
    positions, counts = jnp.asarray(CHUNK_POSITIONS, jnp.int32), jnp.asarray(CHUNK_COUNTS, jnp.int32)
    q = jnp.asarray(rng.standard_normal((5, M, heads, d)), jnp.float32)
    got, _reads = _chunk_kernel(q, poisoned, 1, bt, positions, counts, 0.3)
    _agree_where_read(got, _chunk_gather(q, clean, 1, bt, positions, 0.3), counts)


@pytest.mark.parametrize("heads", [8, 18])
@pytest.mark.parametrize("window", [6, 13, 64], ids=["inside_a_page", "two_blocks", "whole_table"])
def test_windowed_chunk_matches_the_gather_and_never_reads_a_given_back_page(window, heads, monkeypatch):
    """A sliding layer's chunk over the window kind's table as the allocator
    leaves it: every entry wholly older than the FIRST query's window is junk
    page 0, which holds NaN in K and V (so do the rows past each length). The
    kernel walks the sub-table from the key block that holds the oldest key a
    query block's first query sees, weighs a key exactly 0 for a query a
    window or more past it, and zeroes V's rows before that oldest key in
    VMEM: the clean pool's gather-path context. Windows that start inside
    the sub-table's first page, that span two key blocks, and that hold the
    whole table; groups of 4 and 9 heads of 128."""
    kv_heads, d = 2, 128
    _small_query_blocks(monkeypatch, heads, kv_heads, d)
    rng = np.random.default_rng(53)
    clean = tuple(jnp.asarray(rng.standard_normal((L, 64, PS, kv_heads * d)), jnp.float32) for _ in range(2))
    bt_np = 1 + np.arange(5 * PAGES).reshape(5, PAGES)
    bt_np[1] = bt_np[1, ::-1]  # one row's pages descend: a DMA a page
    positions = np.minimum([window + 1, 3, 0, 22, 30], PAGES * PS - M)
    counts = np.array(CHUNK_COUNTS)
    seen = np.zeros((64, PS), bool)
    for row, pos, c in zip(bt_np, positions, counts):
        for at in range(max(pos - window + 1, 0), pos + c if c else 0):
            seen[row[at // PS], at % PS] = True
        row[: max(pos - window + 1, 0) // PS] = 0  # given back
    assert (bt_np == 0).any() or window >= PAGES * PS
    poisoned = tuple(jnp.where(seen[None, :, :, None], a, jnp.nan) for a in clean)
    assert np.isnan(np.asarray(poisoned[1][0, 0])).all()  # the junk page
    bt, pos, cnt = jnp.asarray(bt_np, jnp.int32), jnp.asarray(positions, jnp.int32), jnp.asarray(counts, jnp.int32)
    q = jnp.asarray(rng.standard_normal((5, M, heads, d)), jnp.float32)
    got, (lengths, q_first, *_rest) = _chunk_kernel(q, poisoned, 1, bt, pos, cnt, d**-0.5, window)
    _agree_where_read(got, _chunk_gather(q, clean, 1, bt, pos, d**-0.5, window), counts)
    pw = -(-(window + M) // PS) + 1
    if pw < PAGES:
        p0 = np.clip((positions - (window - 1)) // PS, 0, PAGES - pw)
        assert np.asarray(q_first).tolist() == (positions - p0 * PS).tolist()
        assert np.asarray(lengths).tolist() == np.where(counts > 0, positions + counts - p0 * PS, 1).tolist()
        assert window != 6 or (np.asarray(q_first)[0] - (window - 1)) % PS  # the first key inside a page


def test_gqa_chunk_tiles_names_what_the_chunk_kernel_takes():
    """The five cells' geometries and every entry of their chunk ladders take
    the kernel under "mosaic"; no kernel, one query, a head that does not
    divide a lane tile and query heads outside whole groups do not; the
    interpreter takes any whole groups."""
    cells = {"H.full": (48, 8, 128), "H.win": (72, 8, 128), "C": (32, 4, 128), "I": (32, 2, 128), "D, F": (32, 8, 64),
             "J": (16, 2, 256)}
    for heads, kv_heads, d in cells.values():
        assert all(gqa.gqa_chunk_tiles("mosaic", c, heads, kv_heads, d) for c in (16, 64, 256))
        assert gqa._chunk_query_block(256, heads, kv_heads, kv_heads * d) in (64, 128)
    assert not gqa.gqa_chunk_tiles("", 256, 48, 8, 128)
    assert not gqa.gqa_chunk_tiles("mosaic", 1, 48, 8, 128)
    assert not gqa.gqa_chunk_tiles("mosaic", 256, 44, 8, 128)
    assert not gqa.gqa_chunk_tiles("mosaic", 256, 32, 8, 96)
    assert not gqa.gqa_chunk_tiles("mosaic", 2, 9, 1, 128)  # 18 score rows: not whole sublane tiles
    assert gqa.gqa_chunk_tiles("interpret", 2, 9, 1, 128) and gqa.gqa_chunk_tiles("interpret", 8, 8, 2, 8)
