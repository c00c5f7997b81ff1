"""The paged-attention decode kernel (ops/paged_attention.py) in the Pallas
interpreter on the CPU, against ``_paged_gather`` + the gather path's
attention (models/decoder.py): the same float32 mathematics, read from the
pool's pages in place and only as far as each slot's length.

Tolerance: both sides are float32 throughout, on the same products. They
differ in the ORDER of two kinds of sum — a head's 64 (here 32) products of a
score, and the up to ``length`` terms of a softmax denominator and of a
context lane — so they agree to a few float32 roundings of sums of O(1)
terms: ``ATOL`` 2e-5 on values of order 1 (measured here: under 2e-6; on the
chip at the cell's sizes 1.2e-7). One bfloat16 cast of K, V, the scores or
the probabilities would show as 4e-3, two hundred times that.

What the interpreter cannot see (tiling, VMEM, DMA alignment) is
tests/test_tpu_compile.py's: the same kernel compiled for a described v5e.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.decoder import (
    _paged_gather, init_decoder, paged_decode_step, paged_kv_init,
)
from seldon_core_tpu.ops.paged_attention import paged_attention_decode, pages_read, slot_lengths

ATOL = 2e-5
L, HEADS, HEAD_DIM, N_PAGES = 2, 2, 32, 32
W = HEADS * HEAD_DIM


def _gather_attention(q, pool, li, bt, positions):
    """``_layer_step_paged``'s gather path for one query a slot: the oracle."""
    cache_k, cache_v = _paged_gather(pool, li, bt, HEADS)
    n = q.shape[0]
    qh = q.reshape(n, HEADS, 1, HEAD_DIM)
    s = jnp.einsum("nhqd,nhkd->nhqk", qh, cache_k)
    valid = jnp.arange(cache_k.shape[2])[None, None, :] <= positions[:, None, None]
    s = jnp.where(valid[:, None, :, :], s, -1e30)
    ctx = jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(s, axis=-1), cache_v)
    return np.asarray(ctx.transpose(0, 2, 1, 3).reshape(n, W))


def _pool(rng, ps, scale=1.0):
    return tuple(
        jnp.asarray(scale * rng.standard_normal((L, N_PAGES, ps, W)), jnp.float32) for _ in range(2)
    )


def _kernel(q, pool, li, bt, positions):
    lengths = slot_lengths(positions, pool[0].shape[2], bt.shape[1])
    return np.asarray(
        paged_attention_decode(q, pool[0], pool[1], li, bt, lengths, heads=HEADS, interpret=True)
    )


def _tables(case: str, ps: int, pps: int):
    """(block tables [n, pps], positions [n]) of a named case. ``pps`` is
    12: with the kernel's 8 pages a block, a full table is two blocks, the
    second half empty."""
    rng = np.random.default_rng(7)
    distinct = 1 + rng.permutation(N_PAGES - 1)[: 2 * pps].reshape(2, pps)
    full = pps * ps - 1
    if case == "ragged":  # 1 token, a page boundary - 1, exactly a boundary, the full table
        bt = np.stack([distinct[0], distinct[1], distinct[0][::-1], distinct[1][::-1]])
        return bt, np.array([0, ps - 2, ps - 1, full])
    if case == "block_edges":  # the kernel's 8-page block: one short of it, exactly it, one over
        bt = np.stack([distinct[0], distinct[1], distinct[0]])
        return bt, np.array([8 * ps - 2, 8 * ps - 1, 8 * ps])
    if case == "free_slot":  # table all zero at position 0, between two live slots
        bt = np.stack([distinct[0], np.zeros(pps, np.int64), distinct[1]])
        return bt, np.array([3 * ps + 1, 0, full])
    if case == "shared_pages":  # a prefix of physical pages in three tables, then own tails
        bt = np.stack([distinct[0], distinct[0], distinct[0]])
        bt[1, 3:] = distinct[1][3:]
        bt[2, 5:] = distinct[1][::-1][5:]
        return bt, np.array([2 * ps + 3, 5 * ps, full])
    if case == "junk_page_among_live":  # page 0 named inside a live table, before the length
        bt = np.stack([distinct[0], distinct[1]])
        bt[0, 1] = 0
        bt[1, [0, 4]] = 0
        return bt, np.array([4 * ps, full])
    raise AssertionError(case)


CASES = ["ragged", "block_edges", "free_slot", "shared_pages", "junk_page_among_live"]


@pytest.mark.parametrize("ps", [8, 16])
@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_the_gather_path(case, ps):
    pps = 12
    rng = np.random.default_rng(11)
    pool = _pool(rng, ps)
    bt, positions = _tables(case, ps, pps)
    bt, positions = jnp.asarray(bt, jnp.int32), jnp.asarray(positions, jnp.int32)
    q = jnp.asarray(rng.standard_normal((bt.shape[0], W)) / HEAD_DIM**0.5, jnp.float32)
    for li in range(L) if case == "ragged" else (1,):
        want = _gather_attention(q, pool, li, bt, positions)
        got = _kernel(q, pool, li, bt, positions)
        np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)


@pytest.mark.parametrize("ps", [8, 16])
def test_kernel_stops_at_the_length(ps):
    """Rows past a slot's length hold large garbage — the tail of its last
    page and every later page its table names: the output is the clean
    pool's to rounding, so the kernel's sums end at the length."""
    pps = 12
    rng = np.random.default_rng(13)
    clean = _pool(rng, ps)
    bt, positions = _tables("ragged", ps, pps)
    positions = np.array([0, ps - 2, ps + 3, 9 * ps + 1])  # the last: into the second block
    live = np.zeros((N_PAGES, ps), bool)
    for row, pos in zip(bt, positions):
        for j, page in enumerate(row):
            live[page, : max(0, min(ps, pos + 1 - j * ps))] = True
    assert not live.all()
    garbage = jnp.asarray(np.where(live, 0.0, 1e6)[None, :, :, None], jnp.float32)
    dirty = tuple(jnp.where(garbage > 0, garbage, c) for c in clean)
    bt, positions = jnp.asarray(bt, jnp.int32), jnp.asarray(positions, jnp.int32)
    q = jnp.asarray(rng.standard_normal((4, W)) / HEAD_DIM**0.5, jnp.float32)
    want = _gather_attention(q, clean, 1, bt, positions)
    got = _kernel(q, dirty, 1, bt, positions)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)


def test_decode_step_with_the_kernel_matches_the_gather_step():
    """The whole paged decode step both ways (``attn_kernel`` static): the
    same rows written to the pool, logits equal to float32 rounding through
    two layers; a chunk dispatch ignores the request and gathers."""
    from seldon_core_tpu.models.decoder import paged_chunk_prefill, _paged_forward

    params = init_decoder(seed=5, vocab=96, hidden=64, layers=2, ffn=128, max_len=128)
    ps, pps, n = 8, 6, 3
    rng = np.random.default_rng(17)
    pool = tuple(
        jnp.asarray(rng.standard_normal(c.shape), jnp.float32)
        for c in paged_kv_init(params, 20, ps)
    )
    bt = jnp.asarray(1 + rng.permutation(19)[: n * pps].reshape(n, pps), jnp.int32)
    tokens = jnp.asarray(rng.integers(0, 96, n), jnp.int32)
    positions = jnp.asarray([0, ps - 1, 3 * ps + 2], jnp.int32)
    want_logits, want_hidden, want_pool = paged_decode_step(params, pool, bt, tokens, positions)
    logits, hidden, got_pool = paged_decode_step(
        params, pool, bt, tokens, positions, attn_kernel="interpret"
    )
    for a, b in zip(got_pool, want_pool):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=ATOL)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits), rtol=0, atol=2e-4)
    np.testing.assert_array_equal(np.argmax(logits, -1), np.argmax(want_logits, -1))
    # more than one query a slot: the gather path, bit for bit, whatever was asked
    ids = jnp.asarray(rng.integers(0, 96, (n, 4)), jnp.int32)
    counts = jnp.asarray([4, 2, 0], jnp.int32)
    a = paged_chunk_prefill(params, pool, bt, ids, positions, counts)
    b = _paged_forward(params, pool, bt, ids, positions, counts, attn_kernel="interpret")
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


def test_pages_read_counts_what_the_kernel_fetches():
    pos = np.array([0, 15, 16, 703, 5000, 31])
    np.testing.assert_array_equal(pages_read(pos, 16, 44), [1, 1, 2, 44, 44, 2])


# ------------------------------------------------------- through the scheduler

SEQ, MAX_NEW, PS = 8, 10, 4


def _scheduler(params, **kw):
    from seldon_core_tpu.serving.decode_scheduler import DecodeScheduler

    s = DecodeScheduler(
        params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2, kv_page_size=PS,
        prefix_slots=4, **kw
    )
    s.warmup()
    return s


async def test_scheduler_with_the_kernel_step_serves_the_gather_steps_tokens(monkeypatch):
    """A scheduler whose ONE place of choice (``_step_attn_kernel``) answers
    the kernel — in the interpreter, the CPU's stand-in for "mosaic" — serves
    the greedy tokens of the gather path over admission, generation,
    retirement, slot reuse and a prefix hit, with 0 recompiles; and its
    frames count the pages the step's attention read."""
    import asyncio

    from seldon_core_tpu.serving import decode_programs as ds

    params = init_decoder(seed=3, vocab=128, hidden=64, layers=2, ffn=128, max_len=64)
    rng = np.random.default_rng(21)
    shared, other = rng.integers(0, 128, (2, SEQ)).astype(np.int32)
    ids = [shared, shared, other, shared]

    async def serve(sched):
        outs = [await sched.submit(ids[0], max_new_tokens=6)]  # alone: hand-countable rounds
        outs += await asyncio.gather(*(sched.submit(row) for row in ids[1:]))
        return outs

    gather = _scheduler(params)
    assert gather.programs.attn_kernel == ""  # the CPU backend: the oracle path
    want = await serve(gather)
    table = 2 * gather.pool.pages_per_slot
    frames = [f for f in gather.flight.snapshot() if f.attn_pages_table]
    assert frames and all(f.attn_pages_read == f.attn_pages_table == table for f in frames)
    await gather.close()

    monkeypatch.setattr(ds, "_step_attn_kernel", lambda family, pool_state, mesh, heads, kv_heads: "interpret")
    kernel = _scheduler(params)
    assert kernel.programs.attn_kernel == "interpret"
    got = await serve(kernel)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert kernel.stat_prefix_hits == gather.stat_prefix_hits >= 1
    assert kernel.recompiles_since_warmup() == 0
    # the first request ran alone in slot 0 of two: its chunk round's token is
    # consumed at position SEQ, so the five plain steps read ceil((SEQ + i +
    # 1) / PS) pages for it and one junk page for the free slot
    steps = [f for f in kernel.flight.snapshot() if f.attn_pages_table][:5]
    assert [f.attn_pages_read for f in steps] == [-(-(SEQ + i + 1) // PS) + 1 for i in range(5)]
    assert {f.attn_pages_table for f in steps} == {table}
    assert steps[0].to_dict()["attn_pages"] == [4, table]
    # counted in the rounds that ran a plain step, and in no other
    assert all((f.attn_pages_table > 0) == (f.busy_ns[1] > 0) for f in kernel.flight.snapshot())
    await kernel.close()
