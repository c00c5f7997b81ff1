"""The latent-attention decoder family (models/mla_decoder.py, ops/mla.py, the
held-expert layer of ops/moe.py) held to its plain reference
(benchmarks/reference/a.x-k1.py) at a small size on the CPU: hidden 64, 4
heads of nope 8 / rope 4 / v 8 over a 16-wide latent (cache rows of 20 in one
128-lane tile), a
leading dense layer then two expert layers of a shared expert + top 4 of 16
sigmoid-routed experts in 4 groups (2 kept), of which this chip holds 4 from
the fifth; pages of 4; YaRN factor 32 over an original context of 16. Seeded
random weights; every case counts on its own.
"""

import asyncio
import dataclasses
import functools
import json
import math
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness import cells  # noqa: E402
from harness.correct import judge_generated  # noqa: E402

from seldon_core_tpu.models import decoder as dec  # noqa: E402
from seldon_core_tpu.models import hybrid_decoder as hd  # noqa: E402
from seldon_core_tpu.models import mla_decoder as mla  # noqa: E402
from seldon_core_tpu.models import moe_decoder as md  # noqa: E402
from seldon_core_tpu.models.decoder import FamilyNotServed, init_decoder  # noqa: E402
from seldon_core_tpu.ops import mla as mla_ops  # noqa: E402
from seldon_core_tpu.ops import moe  # noqa: E402
from seldon_core_tpu.serving import decode_scheduler as ds  # noqa: E402
from seldon_core_tpu.serving.decode_programs import _step_attn_kernel  # noqa: E402

SIZES = dict(
    vocab=96, hidden=64, layers=3, heads=4, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
    dense_layers=1, dense_ffn=96, ffn=32, experts=16, experts_per_tok=4, n_group=4, topk_group=2,
    yarn_factor=32.0, yarn_original=16,
)
CFG = mla.MLADecoderConfig(**SIZES, experts_held=4, first_expert=4)  # one chip's share: experts 4..7
# the same sizes under the published config's keys, for the reference
PUBLISHED = {
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8, "kv_lora_rank": 16, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "first_k_dense_replace": 1, "n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "share": {"first_expert": 4},
}
PS = 4  # page size
CTX = 40
FAM = mla.mla_family(CFG)
ROUTE = dict(top_k=4, n_group=4, topk_group=2, scale=2.5)


@pytest.fixture(scope="module")
def ref():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return cells.load_module(ROOT, json.load(f), "reference", "a.x-k1")


@pytest.fixture(scope="module")
def weights():
    return {d: mla.init_mla_decoder(CFG, seed=5, dtype=d) for d in (jnp.float32, jnp.bfloat16)}


def _ref_logits(ref, params, ids, precision):
    return np.asarray(
        ref.logits(params, np.asarray(ids)[None], 0, n_head=CFG.heads, precision=precision, config=PUBLISHED)
    )[0]


@functools.lru_cache(maxsize=None)
def _programs(cfg, tag="", kernel=""):
    """(chunk, step) jitted over ``_forward``; ``tag`` keys a trace made
    under a planted fault apart from the clean one; ``kernel``: the
    programs' ``attn_kernel``."""
    chunk = jax.jit(lambda p, pool, bt, t, pos, cnt: mla._forward(cfg, p, pool, bt, t, pos, counts=cnt, attn_kernel=kernel)[::2])
    step = jax.jit(lambda p, pool, bt, t, pos, rows: mla._forward(cfg, p, pool, bt, t, pos, rows=rows, attn_kernel=kernel)[::2])
    return chunk, step


def _serve(params, ids, *, chunks, prefix_from=None, dtype=jnp.float32, cfg=CFG, tag="", greedy_after=None, width=0, kernel=""):
    """Teacher-forced through the paged programs: chunked prefill of
    ``sum(chunks)`` tokens, then single-token steps along ``ids``; returns
    logits [len(ids), vocab]. The sequence sits in slot 1 of 3 (slots 0 and 2
    ride as junk). ``prefix_from`` = (pool, pages, n): the first n tokens'
    pages of an earlier run are MAPPED (a prefix hit), only the rest is
    computed. ``greedy_after``: from that position on each next token is the
    served argmax (written into ``ids``). ``width``: the chunk programs'
    static chunk length where it is more than the chunk (the rest is padding).
    ``kernel``: the programs' ``attn_kernel`` ("interpret": chunks and steps
    through ops/mla.py's kernels)."""
    fam = mla.mla_family(cfg)
    chunk, step = _programs(cfg, tag, kernel)
    n_slots, pages = 3, CTX // PS
    if prefix_from is None:
        pool = fam.paged_kv_init(params, 1 + 2 * pages, PS, dtype)
        mine, done = 1 + np.arange(pages), 0
    else:
        pool, theirs, done = prefix_from
        assert done % PS == 0
        mine = np.concatenate([theirs[: done // PS], 1 + pages + np.arange(pages - done // PS)])
    bt = np.zeros((n_slots, pages), np.int32)
    bt[1] = mine
    out = np.zeros((len(ids), cfg.vocab), np.float32)
    pos = done
    for c in chunks:
        toks = np.zeros((n_slots, max(c, width)), np.int32)
        toks[1, :c] = ids[pos : pos + c]
        logits, pool = chunk(params, pool, jnp.asarray(bt), jnp.asarray(toks), jnp.array([0, pos, 0], jnp.int32),
                             jnp.array([0, c, 0], jnp.int32))
        out[pos : pos + c] = np.asarray(logits[1, :c])
        pos += c
    if greedy_after is not None and greedy_after < pos < len(ids):
        ids[pos] = int(np.argmax(out[pos - 1]))  # the first generated token comes from the last chunk
    while pos < len(ids):
        logits, pool = step(params, pool, jnp.asarray(bt), jnp.array([[0], [ids[pos]], [0]], jnp.int32),
                            jnp.array([0, pos, 0], jnp.int32), jnp.array([False, True, False]))
        out[pos] = np.asarray(logits[1, 0])
        if greedy_after is not None and greedy_after <= pos < len(ids) - 1:
            ids[pos + 1] = int(np.argmax(out[pos]))
        pos += 1
    return out, pool, mine


def _ids(seed=0, n=CTX):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(np.int32)


# (a) chunked prefill then decode through the latent pages == the reference's full forward


def test_the_static_chunk_length_picks_the_path():
    """Expanded costs rank * (nope + v) a cached row and head whatever the
    chunk, absorbed 2 * rank + rope a QUERY: 16 queries at this size, 171 at
    the published one (a 64-token tail is absorbed, a 256-token chunk not)."""
    small = dict(rank=16, nope=8, rope=4, v_dim=8)
    assert [mla_ops.expand_cheaper(m, **small) for m in (1, 16, 17, 24)] == [False, False, True, True]
    full = dict(rank=512, nope=128, rope=64, v_dim=128)
    assert [mla_ops.expand_cheaper(m, **full) for m in (1, 64, 170, 171, 256)] == [False, False, False, True, True]
    # a block of the walk: 1024 keys for the step's 64 slots, 128 for the (64, 256) chunk program's scores
    assert mla_ops.block_pages(64, 64, 1, 16, 533) == 64 and mla_ops.block_pages(64, 64, 256, 16, 533) == 8


@pytest.mark.parametrize(
    "chunks", [(8, 8, 8), (24,), (5, 19), (16, 1, 7), (3,)], ids=["absorbed", "expanded", "both", "ladder", "short"]
)
def test_cold_prefill_then_decode_equals_reference_float32(ref, weights, chunks):
    """Across page boundaries (pages of 4) and both prefill paths (a chunk of
    17 or more expands the cached rows, a shorter one absorbs the queries),
    then absorbed steps: every position's logits, to 1e-5."""
    params, ids = weights[jnp.float32], _ids(1)
    got, _, _ = _serve(params, ids, chunks=chunks)
    np.testing.assert_allclose(got, _ref_logits(ref, params, ids, "highest"), atol=1e-5, rtol=0)


@pytest.mark.parametrize("chunks", [(8, 8, 8), (16, 3, 5), (20, 4)], ids=["few", "at_the_limit", "long_then_few"])
def test_a_long_chunks_program_absorbs_a_dispatch_of_few_live_queries(ref, weights, chunks):
    """The 24-token program (expanded by its static length) given rows of at
    most 16 live queries absorbs those (``absorb_short``: 16 here, 128 at the
    published sizes) and expands a dispatch with more: either way the live
    positions' logits are the reference's."""
    assert mla_ops.absorb_short(rank=16, nope=8, rope=4, v_dim=8) == 16
    assert mla_ops.absorb_short(rank=512, nope=128, rope=64, v_dim=128) == 128
    params, ids = weights[jnp.float32], _ids(1)
    got, _, _ = _serve(params, ids, chunks=chunks, width=24)
    np.testing.assert_allclose(got, _ref_logits(ref, params, ids, "highest"), atol=1e-5, rtol=0)


@pytest.mark.parametrize("shared,chunks", [(8, (3, 3)), (20, (2,)), (16, (20,))])
def test_prefix_hit_equals_reference_float32(ref, weights, shared, chunks):
    """A second sequence maps the first one's latent pages for its first
    ``shared`` tokens (the rows hold the ROTATED shared key: a hit needs
    nothing new) and computes only the rest, absorbed or expanded."""
    params = weights[jnp.float32]
    first, second = _ids(2), _ids(3)
    second[:shared] = first[:shared]
    _, pool, pages = _serve(params, first, chunks=(10, 10))
    got, _, _ = _serve(params, second, chunks=chunks, prefix_from=(pool, pages, shared))
    want = _ref_logits(ref, params, second, "highest")
    np.testing.assert_allclose(got[shared:], want[shared:], atol=1e-5, rtol=0)


@pytest.mark.parametrize(
    "chunks, width", [((8, 8, 8), 0), ((24,), 0), ((5, 19), 0), ((16, 1, 7), 0), ((3, 5), 24)],
    ids=["short", "long", "both", "ladder", "few_live_in_a_wide_entry"],
)
def test_cold_prefill_through_the_chunk_kernel_equals_reference_float32(ref, weights, small_chunk_blocks, chunks, width):
    """The chunk PROGRAM with the kernels on (``attn_kernel`` "interpret":
    every chunk length through ``mla_chunk_attention``, the steps after it
    through the step's kernel; slots 0 and 2 ride as dead rows): every
    position's logits equal the reference's, to the walk's 1e-5."""
    params, ids = weights[jnp.float32], _ids(1)
    got, _, _ = _serve(params, ids, chunks=chunks, width=width, kernel="interpret")
    np.testing.assert_allclose(got, _ref_logits(ref, params, ids, "highest"), atol=1e-5, rtol=0)


@pytest.mark.parametrize("shared,chunks", [(8, (3, 3)), (20, (2,)), (16, (20,))])
def test_prefix_hit_through_the_chunk_kernel_equals_reference_float32(ref, weights, small_chunk_blocks, shared, chunks):
    """The second sequence's chunks start past the mapped pages (a query
    block's first position is not 0, its keys begin in another request's
    pages): through the kernel, the reference's logits."""
    params = weights[jnp.float32]
    first, second = _ids(2), _ids(3)
    second[:shared] = first[:shared]
    _, pool, pages = _serve(params, first, chunks=(10, 10), kernel="interpret")
    got, _, _ = _serve(params, second, chunks=chunks, prefix_from=(pool, pages, shared), kernel="interpret")
    want = _ref_logits(ref, params, second, "highest")
    np.testing.assert_allclose(got[shared:], want[shared:], atol=1e-5, rtol=0)


def test_bfloat16_serving_within_the_harness_delta(ref, weights):
    """The harness's rule (benchmarks/harness/correct.py) at the small size:
    along greedy tokens served in bfloat16 from bfloat16 latent pages, the
    reference's exact logit of each served token trails its best by at most
    twice the rounding delta between the reference at the stated precision
    and at "highest"."""
    params, ids, first = weights[jnp.bfloat16], _ids(4), 23
    _serve(params, ids, chunks=(12, 12), dtype=jnp.bfloat16, greedy_after=first)
    exact, noisy = (_ref_logits(ref, params, ids, p)[None, first:] for p in ("highest", "default"))
    verdict = judge_generated([ids.tolist()], exact, noisy, first)
    assert verdict["ok"], verdict
    assert verdict["rounding_delta"] > 1e-4  # bfloat16 activations do round


# (b) what the comparison sees: four planted faults, each outside it


def _unrotated_key(monkeypatch):
    real = mla._rope
    monkeypatch.setattr(mla, "_rope", lambda x, pos, f, a: x if x.shape[2] == 1 else real(x, pos, f, a))
    return CFG


def _no_mscale(monkeypatch):
    return dataclasses.replace(CFG, mscale_all_dim=0.0)  # m = 1: the scores lose m^2 = 1.81


def _no_shared_expert(monkeypatch):
    real = mla.gated_mlp
    monkeypatch.setattr(mla, "gated_mlp", lambda gu, down, x: 0.0 * real(gu, down, x) if gu.shape[1] == 2 * CFG.ffn else real(gu, down, x))
    return CFG


def _gates_over_the_held_picks(monkeypatch):
    real = moe.held_picks

    def renormalised(gates, experts, first, held):
        g, e, here = real(gates, experts, first, held)
        return g * jnp.sum(gates, -1, keepdims=True) / jnp.maximum(jnp.sum(g, -1, keepdims=True), 1e-9), e, here

    monkeypatch.setattr(moe, "held_picks", renormalised)
    return CFG


def _sharp(params):
    """The same weights with the query and key/value up-projections times 8:
    at std 0.02 the scores are ~0.01 and every softmax is uniform, so nothing
    done to a score would show; at 64 times that, attention picks keys."""
    layers = [{**p, "q_b": p["q_b"] * 8, "kv_b": p["kv_b"] * 8, "kv_a": p["kv_a"] * 8} for p in params["layers"]]
    return {**params, "layers": layers}


@pytest.mark.parametrize(
    "fault", [_unrotated_key, _no_mscale, _no_shared_expert, _gates_over_the_held_picks], ids=lambda f: f.__name__[1:]
)
def test_a_planted_fault_fails_the_comparison(ref, weights, monkeypatch, fault):
    """The shared key left unrotated, m^2 left off the score scale, the
    shared expert left out, the gates normalised over the picks that are held
    instead of all 8: each moves logits (std 0.16) a hundred times past the
    1e-5 of the cases above."""
    params, ids = _sharp(weights[jnp.float32]), _ids(1)
    want = _ref_logits(ref, params, ids, "highest")
    np.testing.assert_allclose(_serve(params, ids, chunks=(8, 16))[0], want, atol=1e-5, rtol=0)
    cfg = fault(monkeypatch)
    got, _, _ = _serve(params, ids, chunks=(8, 16), cfg=cfg, tag=fault.__name__)
    assert np.abs(got - want).max() > 1e-3


# (c) the two attention paths, and the walk in blocks


def _attention_case(seed, n=3, m=5, heads=4, nope=8, rope=4, v=8, rank=16, pages=12, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 4)
    plane = jax.random.normal(ks[0], (2, 1 + n * pages, PS, rank + rope + 12), dtype)  # 12 lanes of padding, never read as keys
    bt = 1 + jnp.arange(n * pages, dtype=jnp.int32).reshape(n, pages)
    q_nope = jax.random.normal(ks[1], (n, m, heads, nope), dtype)
    q_rope = jax.random.normal(ks[2], (n, m, heads, rope), dtype)
    kv_b = jax.random.normal(ks[3], (rank, heads, nope + v), dtype) * 0.3
    return plane, bt, q_nope, q_rope, kv_b


def _plain_attention(plane, li, bt, q_nope, q_rope, q_pos, kv_b, rank, scale):
    """Every row's table gathered whole, every head's keys and values expanded."""
    n, m, heads, nope = q_nope.shape
    rows = np.asarray(plane[li][bt]).reshape(n, -1, plane.shape[-1])
    kv = np.einsum("nkr,rhe->nkhe", rows[..., :rank], np.asarray(kv_b))
    rope = q_rope.shape[-1]
    s = np.einsum("nmhd,nkhd->nhmk", q_nope, kv[..., :nope]) + np.einsum("nmhd,nkd->nhmk", q_rope, rows[..., rank : rank + rope])
    seen = np.arange(rows.shape[1])[None, None, :] <= np.asarray(q_pos)[:, :, None]
    s = np.where(seen[:, None], s * scale, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("nhmk,nkhv->nmhv", p, kv[..., nope:]).reshape(n, m, -1)


@pytest.mark.parametrize("block_keys", [8, 16, 1024], ids=["blocks_of_8", "blocks_of_16", "one_block"])
@pytest.mark.parametrize("expand", [False, True], ids=["absorbed", "expanded"])
def test_both_paths_equal_plain_attention_in_any_blocks(monkeypatch, expand, block_keys):
    monkeypatch.setattr(mla_ops, "_MIN_BLOCK_KEYS", min(block_keys, 128))
    monkeypatch.setattr(mla_ops, "_MAX_BLOCK_KEYS", block_keys)
    plane, bt, q_nope, q_rope, kv_b = _attention_case(0)
    pos = jnp.array([0, 17, 43], jnp.int32)  # tables of 48 keys: one, three and six blocks of 8
    q_pos = pos[:, None] + jnp.arange(5)[None, :]
    got = mla_ops.mla_paged_attention(q_nope, q_rope, plane, 1, bt, q_pos, pos + 5, kv_b, scale=0.3, expand=expand)
    want = _plain_attention(plane, 1, bt, q_nope, q_rope, q_pos, kv_b, 16, 0.3)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)


def test_the_walk_stops_at_the_longest_live_row(monkeypatch):
    """Pages past every live row's keys are never read: NaNs planted there
    stay out, and a row nobody reads (n_keys 1) does not lengthen the walk."""
    monkeypatch.setattr(mla_ops, "_MIN_BLOCK_KEYS", 8)
    monkeypatch.setattr(mla_ops, "_MAX_BLOCK_KEYS", 8)
    plane, bt, q_nope, q_rope, kv_b = _attention_case(1, m=1)
    plane = plane.at[:, bt[:, 4:].reshape(-1)].set(jnp.nan)  # keys 16.. of every table
    q_pos = jnp.array([[3], [15], [40]], jnp.int32)
    n_keys = jnp.array([4, 16, 1], jnp.int32)  # the third row rides as junk
    got = mla_ops.mla_paged_attention(q_nope, q_rope, plane, 0, bt, q_pos, n_keys, kv_b, scale=0.3, expand=False)
    assert np.isfinite(np.asarray(got[:2])).all()


def test_the_step_gathers_blocks_in_the_pools_dtype_never_a_float32_table(weights):
    """In the lowered step there is no float32 array as long as a slot's
    table (10 pages x 4 rows x 20 wide) and no per-head key or value of it:
    what is gathered is one block of bfloat16 rows at a time."""
    params = weights[jnp.bfloat16]
    pool = FAM.paged_kv_init(params, 21, PS, jnp.bfloat16)
    step = _programs(CFG)[1]
    text = step.lower(params, pool, jnp.zeros((3, 10), jnp.int32), jnp.zeros((3, 1), jnp.int32),
                      jnp.zeros((3,), jnp.int32), jnp.ones((3,), bool)).as_text()
    assert "tensor<3x10x4x128xbf16>" in text  # the gathered block (the whole table here: one block)
    assert "3x10x4x128xf32" not in text and "3x40x128xf32" not in text
    assert "3x40x4x16x" not in text  # [rows, keys, heads, nope + v]: no expansion in the step


# (c2) the step's kernel (ops/mla.py mla_decode_attention) under the Pallas interpreter


def _kernel_tables(kind):
    """Three rows' tables of 12 pages (runs of 2 entries, blocks of 4: six
    groups in three blocks a row) over a plane of 40 pages."""
    if kind == "one_run":
        return 1 + np.arange(36, dtype=np.int32).reshape(3, 12)
    if kind == "scattered":
        return 1 + np.random.default_rng(3).permutation(36).astype(np.int32).reshape(3, 12)
    return np.array(
        [
            [1, 2, 3, 5, 6, 7, 8, 9, 20, 21, 22, 23],  # a run that breaks inside a group; one that starts mid-group
            [30, 10, 11, 13, 14, 16, 17, 5, 6, 33, 34, 4],  # consecutive pages only ACROSS the groups' edges: no run
            [24, 25, 26, 27, 39, 38, 37, 36, 28, 29, 31, 32],  # runs, a descending stretch, runs
        ],
        np.int32,
    )


# lengths that end inside a page, inside a group (page 3 of 4 and 2 of 2), on a group's and on a block's edge,
# a whole table, and a row nobody reads
_KERNEL_LENGTHS = {"inside_a_page": (6, 21, 45), "inside_a_group": (9, 24, 40), "on_the_edges": (8, 16, 48),
                   "a_row_nobody_reads": (1, 47, 13)}


def _kernel_case(tables, lengths, dtype):
    ks = jax.random.split(jax.random.key(7), 4)
    plane = jax.random.normal(ks[0], (2, 40, PS, 128), jnp.float32)
    plane = plane.at[..., 20:].set(0.0)  # the row's padding lanes, as the program writes them
    bt = np.asarray(_kernel_tables(tables))
    n_keys = np.asarray(lengths, np.int32)
    junk, unread = np.asarray(plane).copy(), np.asarray(plane).copy()
    for i, length in enumerate(n_keys):
        held = -(-int(length) // PS)
        last = bt[i, held - 1]
        junk[:, last, length - (held - 1) * PS :] = 1e4  # past the length, inside the last page: finite junk
        unread[:, last, length - (held - 1) * PS :] = 1e4
        own = np.setdiff1d(bt[i, held:], np.concatenate([bt[j, : -(-int(n_keys[j]) // PS)] for j in range(3)]))
        unread[:, own] = np.nan  # pages past the row's own: never fetched
    q_nope = jax.random.normal(ks[1], (3, 1, 4, 8), jnp.float32).astype(dtype)
    q_rope = jax.random.normal(ks[2], (3, 1, 4, 4), jnp.float32).astype(dtype)
    kv_b = (jax.random.normal(ks[3], (16, 4, 16), jnp.float32) * 0.3).astype(dtype)
    return jnp.asarray(junk, dtype), jnp.asarray(unread, dtype), jnp.asarray(bt), jnp.asarray(n_keys), q_nope, q_rope, kv_b


@pytest.fixture()
def small_blocks(monkeypatch):
    monkeypatch.setattr(mla_ops, "RUN_PAGES", 2)
    monkeypatch.setattr(mla_ops, "BLOCK_PAGES", 4)


@pytest.mark.parametrize("lengths", sorted(_KERNEL_LENGTHS))
@pytest.mark.parametrize("tables", ["one_run", "scattered", "mixed"])
def test_the_step_kernel_equals_the_walk_and_plain_attention_float32(small_blocks, tables, lengths):
    """One query a row through the kernel == ``_walk(expand=False)`` ==
    every head's keys and values expanded over the whole gathered table,
    whatever the table's runs and wherever the lengths end; junk past a
    length inside its page and NaNs in pages past it never reach the output."""
    plane, unread, bt, n_keys, q_nope, q_rope, kv_b = _kernel_case(tables, _KERNEL_LENGTHS[lengths], jnp.float32)
    q_pos = (n_keys - 1)[:, None]
    runs = mla_ops.page_runs(bt, n_keys, PS)
    args = dict(scale=0.3, expand=False)
    got = mla_ops.mla_paged_attention(q_nope, q_rope, plane, 1, bt, q_pos, n_keys, kv_b, runs=runs, interpret=True, **args)
    walked = mla_ops.mla_paged_attention(q_nope, q_rope, plane, 1, bt, q_pos, n_keys, kv_b, **args)
    plain = _plain_attention(plane, 1, bt, q_nope, q_rope, q_pos, kv_b, 16, 0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(walked), atol=2e-6)
    np.testing.assert_allclose(np.asarray(got), plain, atol=2e-6)
    again = mla_ops.mla_paged_attention(q_nope, q_rope, unread, 1, bt, q_pos, n_keys, kv_b, runs=runs, interpret=True, **args)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


@pytest.mark.parametrize("tables", ["one_run", "scattered", "mixed"])
def test_the_step_kernel_equals_the_walk_bfloat16(small_blocks, tables):
    """At the pool's bfloat16 the kernel and the walk round alike (bfloat16
    operands, float32 sums, the probabilities cast before the context
    product): they differ by the order of the blocks' sums alone, and both
    lie within bfloat16's tolerance of float32 attention."""
    plane, _unread, bt, n_keys, q_nope, q_rope, kv_b = _kernel_case(tables, (6, 24, 45), jnp.bfloat16)
    q_pos = (n_keys - 1)[:, None]
    runs = mla_ops.page_runs(bt, n_keys, PS)
    args = dict(scale=0.3, expand=False)
    got = mla_ops.mla_paged_attention(q_nope, q_rope, plane, 0, bt, q_pos, n_keys, kv_b, runs=runs, interpret=True, **args)
    walked = mla_ops.mla_paged_attention(q_nope, q_rope, plane, 0, bt, q_pos, n_keys, kv_b, **args)
    assert got.dtype == walked.dtype == jnp.bfloat16
    f32 = [a.astype(jnp.float32) for a in (q_nope, q_rope, plane, kv_b)]
    plain = _plain_attention(f32[2], 0, bt, f32[0], f32[1], q_pos, f32[3], 16, 0.3)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(walked, np.float32), atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32), plain, atol=4e-2)


def test_page_runs_flags_whole_groups_of_consecutive_pages_a_row_has(small_blocks):
    bt = jnp.asarray(_kernel_tables("mixed"))
    n_keys = jnp.array([48, 45, 17], jnp.int32)  # 12, 12 and 5 pages
    runs = np.asarray(mla_ops.page_runs(bt, n_keys, PS))
    assert runs.tolist() == [[1, 0, 1, 1, 1, 1], [0, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0]]
    every, last_two = jnp.array([True, True, True]), jnp.array([False, True, True])
    assert np.asarray(mla_ops.pages_fetched(n_keys, jnp.asarray(runs), every, PS, 12)).tolist() == [12 + 12 + 5, 10 + 0 + 4]
    assert np.asarray(mla_ops.pages_fetched(n_keys, jnp.asarray(runs), last_two, PS, 12)).tolist() == [12 + 5, 4]
    # a length past the table is the table; one under a page is a page
    none = jnp.zeros((3, 6), jnp.int32)
    assert np.asarray(mla_ops.pages_fetched(jnp.array([500, 0, 1]), none, every, PS, 12)).tolist() == [12 + 1 + 1, 0]


def test_which_dispatch_shapes_take_which_kernel(small_blocks):
    """Inside a program the dispatch's static shape decides (``kernel_takes``;
    ``kernel_runs`` hands whichever kernel its runs): one query a row the
    step's kernel, more the chunk's, whatever their number; None (the walk)
    where no kernel was chosen, and under Mosaic for a latent that is not
    whole lane tiles or a query block that is not whole sublane tiles (257
    queries of 4 heads: a prime past a block's 256, so blocks of one query,
    4 rows). ``MLADecoder.chunk_attn`` is the same answer by
    name, for the dispatch's annotation and the frames."""
    _plane, bt, *_ = _attention_case(2)
    n_keys = jnp.array([5, 22, 45], jnp.int32)
    want = np.asarray(mla_ops.page_runs(bt, n_keys, PS))
    for kernel, queries, rank, heads, taken in [
        ("interpret", 1, 16, 4, True), ("mosaic", 1, 512, 64, True), ("mosaic", 1, 16, 4, False),
        ("interpret", 5, 16, 4, True), ("mosaic", 16, 512, 32, True), ("mosaic", 64, 512, 64, True),
        ("mosaic", 256, 512, 32, True), ("mosaic", 256, 512, 64, True), ("mosaic", 64, 16, 4, False),
        ("mosaic", 257, 512, 4, False), ("mosaic", 264, 512, 4, True), ("", 1, 512, 64, False), ("", 64, 512, 64, False),
    ]:
        assert mla_ops.kernel_takes(kernel, queries, rank, heads) == taken
        got = mla_ops.kernel_runs(kernel, queries, rank, heads, bt, n_keys, PS)
        assert (got is not None) == taken
        if taken:
            np.testing.assert_array_equal(np.asarray(got), want)
    assert [FAM.chunk_attn(k, 16) for k in ("", "interpret", "mosaic")] == ["walk", "kernel", "walk"]  # a 16-wide latent
    full = mla.mla_family(mla.MLADecoderConfig(heads=32, kv_rank=512, rope_dim=64))
    assert [full.chunk_attn("mosaic", c) for c in (16, 64, 256)] == ["kernel"] * 3 and full.chunk_attn("", 256) == "walk"


# (c3) the chunk's kernel (ops/mla.py mla_chunk_attention) under the Pallas interpreter


@pytest.fixture()
def small_chunk_blocks(small_blocks, monkeypatch):
    monkeypatch.setattr(mla_ops, "CHUNK_BLOCK_PAGES", 8)  # key blocks of 32: 2 to 10 a row


def _chunk_case(m, heads, tables, live, dtype=jnp.float32):
    """Three rows of ``m`` queries by ``heads`` heads (query blocks of 1024
    query-head rows, as on the chip: 32 or 16 queries), at positions 0 (cold),
    37 (inside a page and a run) and 5. ``live``: "full" (every row all its
    queries) or "ragged": row 1 has a third of its queries (its last live
    query block ends inside the causal triangle, the blocks after it are
    nobody's) and row 2 NONE (a padding row of the ladder entry); or the three
    counts themselves. Returns the
    plane, the same with NaN in every page no live query may see (past a
    row's own keys, and the dead row's whole table), the tables and the
    rest."""
    n, first = 3, np.array([0, 37, 5], np.int32)
    pages = (37 + m) // PS + 3
    ks = jax.random.split(jax.random.key(m + heads), 4)
    total = 1 + n * pages
    plane = np.array(jax.random.normal(ks[0], (2, total, PS, 128), jnp.float32))
    plane[..., 20:] = 0.0  # the row's padding lanes, as the program writes them
    ids = np.arange(1, total, dtype=np.int32)
    if tables == "scattered":
        ids = np.random.default_rng(3).permutation(ids)
    bt = ids.reshape(n, pages)
    counts = np.array({"full": [m, m, m], "ragged": [m, m // 3 + 1, 0]}.get(live, live), np.int32)
    unread = plane.copy()
    for i in range(n):
        held = -(-int(first[i] + counts[i]) // PS) if counts[i] else 0
        unread[:, bt[i, held:]] = np.nan
    q_nope = jax.random.normal(ks[1], (n, m, heads, 8), jnp.float32).astype(dtype)
    q_rope = jax.random.normal(ks[2], (n, m, heads, 4), jnp.float32).astype(dtype)
    kv_b = (jax.random.normal(ks[3], (16, heads, 16), jnp.float32) * 0.3).astype(dtype)
    return (jnp.asarray(plane, dtype), jnp.asarray(unread, dtype), jnp.asarray(bt), jnp.asarray(first), jnp.asarray(counts),
            q_nope, q_rope, kv_b)


def _chunk_through_the_kernel(plane, bt, first, counts, q_nope, q_rope, kv_b, li=1):
    m = q_nope.shape[1]
    q_pos = first[:, None] + jnp.arange(m, dtype=jnp.int32)[None, :]
    n_keys = jnp.where(counts > 0, first + counts, 1)  # as ``_forward`` hands them
    runs = mla_ops.page_runs(bt, n_keys, PS)
    return mla_ops.mla_paged_attention(q_nope, q_rope, plane, li, bt, q_pos, n_keys, kv_b, scale=0.3, expand=False,
                                       runs=runs, counts=counts, interpret=True)


def _assert_live_queries_equal(got, want, counts, heads, atol):
    """The queries somebody reads equal ``want``; a query block wholly past a
    row's count (and so a whole dead row) is ZEROS; whatever lies between is
    finite."""
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    tq = mla_ops._query_block(got.shape[1], heads)
    for i, c in enumerate(np.asarray(counts)):
        np.testing.assert_allclose(got[i, :c], np.asarray(want, np.float32)[i, :c], atol=atol)
        assert not got[i, -(-int(c) // tq) * tq :].any()


@pytest.mark.parametrize("tables, live", [("one_run", "full"), ("scattered", "ragged")])
@pytest.mark.parametrize("heads", [32, 64])
@pytest.mark.parametrize("m", [16, 64, 256])
def test_the_chunk_kernel_equals_the_walk_and_plain_attention_float32(small_chunk_blocks, m, heads, tables, live):
    """Many queries a row through the kernel == ``_walk`` (absorbed AND
    expanded) == every head's keys and values expanded over the whole
    gathered table, for the queries somebody reads: causal by position, each
    row to its own length, over tables of runs and of scattered pages. The
    plane the kernel reads holds NaN in every page past a row's own keys and
    in the dead row's whole table: none is fetched (a fetched NaN would
    reach the output through 0 x NaN), and the dead row comes back zeros."""
    plane, unread, bt, first, counts, q_nope, q_rope, kv_b = _chunk_case(m, heads, tables, live)
    q_pos = first[:, None] + jnp.arange(m, dtype=jnp.int32)[None, :]
    got = _chunk_through_the_kernel(unread, bt, first, counts, q_nope, q_rope, kv_b)
    assert got.shape == (3, m, heads * 8)
    all_keys = jnp.where(counts > 0, first + m, 1)
    walked = [mla_ops.mla_paged_attention(q_nope, q_rope, plane, 1, bt, q_pos, all_keys, kv_b, scale=0.3, expand=e) for e in (False, True)]
    plain = _plain_attention(plane, 1, bt, q_nope, q_rope, q_pos, kv_b, 16, 0.3)
    for want in (*walked, plain):
        _assert_live_queries_equal(got, want, counts, heads, atol=3e-6)


@pytest.mark.parametrize("m, heads, tables", [(16, 32, "scattered"), (64, 64, "one_run"), (256, 32, "scattered")])
def test_the_chunk_kernel_equals_the_walk_bfloat16(small_chunk_blocks, m, heads, tables):
    """At the pool's bfloat16 the kernel and the absorbed walk round alike
    (bfloat16 operands, float32 sums, the probabilities cast before the
    context product): the step kernel's tolerances."""
    plane, _unread, bt, first, counts, q_nope, q_rope, kv_b = _chunk_case(m, heads, tables, "ragged", jnp.bfloat16)
    q_pos = first[:, None] + jnp.arange(m, dtype=jnp.int32)[None, :]
    got = _chunk_through_the_kernel(plane, bt, first, counts, q_nope, q_rope, kv_b, li=0)
    walked = mla_ops.mla_paged_attention(q_nope, q_rope, plane, 0, bt, q_pos, jnp.where(counts > 0, first + m, 1), kv_b,
                                         scale=0.3, expand=False)
    assert got.dtype == walked.dtype == jnp.bfloat16
    f32 = [a.astype(jnp.float32) for a in (q_nope, q_rope, plane, kv_b)]
    plain = _plain_attention(f32[2], 0, bt, f32[0], f32[1], q_pos, f32[3], 16, 0.3)
    _assert_live_queries_equal(got, walked, counts, heads, atol=2e-2)
    _assert_live_queries_equal(got, plain, counts, heads, atol=4e-2)


def test_few_live_queries_in_a_256_wide_entry_skip_the_blocks_nobody_reads(small_chunk_blocks):
    """A wave of short tails riding the 256-token entry: rows of 3, 17 and 0
    live queries. Each row's first query block (32 queries) is computed and
    equals the walk; the 7, 7 and 8 blocks after it are zeros without a fetch (NaN
    everywhere past the rows' own keys), which is what the walk's
    ``absorb_short`` branch saved with a second program body."""
    plane, unread, bt, first, counts, q_nope, q_rope, kv_b = _chunk_case(256, 32, "scattered", (3, 17, 0))
    got = _chunk_through_the_kernel(unread, bt, first, counts, q_nope, q_rope, kv_b)
    q_pos = first[:, None] + jnp.arange(256, dtype=jnp.int32)[None, :]
    want = _plain_attention(plane, 1, bt, q_nope, q_rope, q_pos, kv_b, 16, 0.3)
    _assert_live_queries_equal(got, want, counts, 32, atol=3e-6)
    assert not np.asarray(got)[:2, 32:].any() and not np.asarray(got)[2].any()


def test_a_geometry_mosaic_cannot_tile_is_refused_by_name_before_the_compiler():
    """Outside the interpreter the chunk kernel refuses what ``kernel_tiles``
    / ``kernel_takes`` rule out, by name: float32 rows, pages of 4, a latent
    that is not whole lane tiles, a query block of 4 rows (257 queries, a
    prime, of 4 heads)."""
    qc = jnp.zeros((2, 257 * 4, 128), jnp.bfloat16)
    bt, z = jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32)
    runs = jnp.zeros((2, 4), jnp.int32)
    for plane, rank, heads in [
        (jnp.zeros((1, 9, 16, 128), jnp.float32), 128, 4), (jnp.zeros((1, 9, 4, 128), jnp.bfloat16), 128, 4),
        (jnp.zeros((1, 9, 16, 128), jnp.bfloat16), 16, 4), (jnp.zeros((1, 9, 16, 128), jnp.bfloat16), 128, 4),
    ]:
        q = qc.astype(plane.dtype)
        with pytest.raises(ValueError, match="mla_chunk_attention cannot tile|against plane rows"):
            mla_ops.mla_chunk_attention(q, plane, 0, bt, z + 1, z, z + 3, runs, heads=heads, rank=rank, scale=1.0)


# (d) the router against a literal transcription; the held share of the expert layer


def _literal_router(w, x, top_k, n_group, topk_group, scale):
    """Token by token, as the family's public inference code has it (a gate
    without a correction bias): returns ({expert: gate} a token)."""
    out = []
    for row in np.asarray(x, np.float64):
        s = 1.0 / (1.0 + np.exp(-(row @ np.asarray(w, np.float64))))
        per = len(s) // n_group
        best = [max(s[g * per : (g + 1) * per]) for g in range(n_group)]
        groups = sorted(range(n_group), key=lambda g: (-best[g], g))[:topk_group]
        inside = [e for e in range(len(s)) if e // per in groups]
        picks = sorted(inside, key=lambda e: (-s[e], e))[:top_k]
        total = sum(s[e] for e in picks)
        out.append({e: scale * s[e] / total for e in picks})
    return out


@pytest.mark.parametrize("case", ["random", "ties", "one_group_dominates"])
def test_router_equals_a_literal_transcription(ref, case):
    w = np.asarray(jax.random.normal(jax.random.key(3), (CFG.hidden, CFG.experts))) * 0.5
    x = np.asarray(jax.random.normal(jax.random.key(4), (40, CFG.hidden)))
    if case == "ties":  # equal columns score equal: the lower index wins, inside a group and between groups
        w[:, 5], w[:, 9], w[:, 13] = w[:, 4], w[:, 8], w[:, 12]
        w[:, 8:12] = w[:, 0:4]
    if case == "one_group_dominates":
        w[:, 12:] += 2.0 * np.sign(x[0])[:, None] / np.sqrt(CFG.hidden)
    want = _literal_router(w, x, **ROUTE)
    gates, experts = moe.route_sigmoid_grouped(jnp.asarray(w), jnp.asarray(x), 4, 4, 2, 2.5)
    dense = np.asarray(ref.router(jnp.asarray(w), jnp.asarray(x), **ROUTE))
    for t, picks in enumerate(want):
        assert sorted(int(e) for e in experts[t]) == sorted(picks)
        for g, e in zip(np.asarray(gates[t]), np.asarray(experts[t])):
            assert g == pytest.approx(picks[int(e)], rel=1e-5)
        assert set(np.flatnonzero(dense[t])) == set(picks)
        np.testing.assert_allclose(dense[t][sorted(picks)], [picks[e] for e in sorted(picks)], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-6)  # normalised over the 4 picks, then scaled
    assert gates.dtype == jnp.float32


def _expert_layer(seed=0, held=16):
    ks = jax.random.split(jax.random.key(seed), 5)
    d, f = CFG.hidden, CFG.ffn
    return {
        "router": jax.random.normal(ks[0], (d, CFG.experts)) * 0.5,
        "gate_up": jax.random.normal(ks[1], (held, d, 2 * f)) * 0.1,
        "down": jax.random.normal(ks[2], (held, f, d)) * 0.1,
        "shared_gate_up": jax.random.normal(ks[3], (d, 2 * f)) * 0.1,
        "shared_down": jax.random.normal(ks[4], (f, d)) * 0.1,
    }


def _share(p, first, held):
    return {**p, "gate_up": p["gate_up"][first : first + held], "down": p["down"][first : first + held]}


def _held_ffn(p, x, first, valid=None):
    """The latent family's gate, then the held-expert layer over its picks
    (two functions since PR 41: the family routes)."""
    gates, experts = moe.route_sigmoid_grouped(p["router"], x, 4, 4, 2, 2.5)
    return moe.moe_held_ffn(p, x, gates, experts, first, valid)


def test_the_sixteen_experts_four_shares_and_the_shared_expert_once_sum_to_the_uncut_layer(ref):
    """Four chips of four experts each: their routed parts (a pick that lands
    on an absent expert adds nothing, gates over all four picks) plus the
    shared expert counted ONCE equal the uncut layer of the reference."""
    p = _expert_layer()
    n2 = jax.random.normal(jax.random.key(8), (24, CFG.hidden))
    uncut = ref.expert_ffn(p, n2, first_expert=0, act="float32", **ROUTE)
    parts = [_held_ffn(_share(p, 4 * s, 4), n2, 4 * s) for s in range(4)]
    total = sum(y for y, _ in parts) + moe.gated_mlp(p["shared_gate_up"], p["shared_down"], n2)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=5e-6)
    assert sum(int(c[3]) for _, c in parts) == 24 * 4  # every pick landed on exactly one chip
    # and share by share, against the reference given the same share
    for s, (y, _) in enumerate(parts):
        want = ref.expert_ffn(_share(p, 4 * s, 4), n2, first_expert=4 * s, act="float32", shared=False, **ROUTE)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=5e-6)


def _relu2(p):
    """The same layer with squared-ReLU experts: ``up`` (the gated one's up
    half) and ``down``, no gate projection (the hybrid family's, PR 51)."""
    rest = {k: v for k, v in p.items() if k != "gate_up"}
    return {**rest, "up": p["gate_up"][..., p["gate_up"].shape[-1] // 2 :]}


def _plain_relu2_share(p, x, gates, experts, first):
    """down_e(relu(up_e x)^2) of every held expert over every row, weighed by
    the gates of the picks that name it: no sort, no capacity, no form."""
    held = p["down"].shape[0]
    dense = jnp.sum(jnp.where(experts[:, :, None] == first + jnp.arange(held)[None, None, :], gates[:, :, None], 0.0), axis=1)
    h = jnp.square(jax.nn.relu(jnp.einsum("td,edf->etf", x, p["up"], precision="highest")))
    return jnp.einsum("etf,efd,te->td", h, p["down"], dense, precision="highest")


@pytest.mark.parametrize("act", ["gated_silu", "relu2"])
@pytest.mark.parametrize(
    "rows,form",
    [(24, "masked"), (300, "grouped"), (512, "refused"), (512, "compact"), (300, "overflow"), (300, "all_held")],
)
def test_the_held_layer_in_each_form_equals_the_reference_share(ref, monkeypatch, rows, form, act):
    """The activation follows the expert's weights (``gate_up`` + ``down``:
    gated SiLU, held to the latent family's reference; ``up`` + ``down``:
    squared ReLU, held to the plain sum over experts), ONE body a form.
    By static row count: masked to 256 rows, grouped above, and refused
    above ``GROUPED_BLOCK_ROWS`` (128 in the third case; no served program is
    that wide). Junk rows come back zero and stay out of the counts, which
    are over the experts HELD. Since PR 49 the grouped form over a SHARE of
    the experts runs COMPACT, in blocks of ``held_capacity`` sorted
    assignments (768 of the 300 rows' 1,200, 1,280 of the 512 rows' 2,048):
    one block where the held picks + a junk row an expert fit it; a routing
    that sends every pick to the held experts ("overflow": 1,180 picks + 4
    do not fit 768) takes two, drops nothing and says so in the last
    counter; a layer that holds all sixteen gets its assignments as its
    capacity and the full-width form, no loop."""
    monkeypatch.setattr(moe, "GROUPED_BLOCK_ROWS", 128 if form == "refused" else 4096)
    first, n_held = (0, 16) if form == "all_held" else (4, 4)
    p = _share(_expert_layer(1), first, n_held)
    if act == "relu2":
        p = _relu2(p)
    n2 = jax.random.normal(jax.random.key(rows), (rows, CFG.hidden))
    if form == "overflow":  # a coordinate every row shares, and router weights that score the held experts on it
        n2 = n2.at[:, 0].set(3.0)
        p["router"] = p["router"].at[0].set(jnp.where((jnp.arange(CFG.experts) // 4) == 1, 5.0, -5.0))
    valid = jnp.arange(rows) < rows - 5
    held = jax.jit(lambda p, x, v: _held_ffn(p, x, first, v))
    if form == "refused":
        with pytest.raises(ValueError, match="512 rows in one dispatch .* above 128"):
            held(p, n2, valid)
        return
    y, counted = held(p, n2, valid)
    gates, experts = moe.route_sigmoid_grouped(p["router"], n2, 4, 4, 2, 2.5)
    if act == "relu2":
        want = np.asarray(_plain_relu2_share(p, n2, gates, experts, first))
    else:
        want = np.asarray(ref.expert_ffn(p, n2, first_expert=first, act="float32", shared=False, **ROUTE))
    np.testing.assert_allclose(np.asarray(y[: rows - 5]), want[: rows - 5], atol=5e-6)
    assert not np.asarray(y[rows - 5 :]).any()
    mine = np.asarray((experts >= first) & (experts < first + n_held))[: rows - 5]
    loads = np.bincount(np.asarray(experts)[: rows - 5][mine] - first, minlength=n_held)
    assert [int(c) for c in counted[:4]] == [rows - 5, int((loads > 0).sum()), int(loads.max()), int(mine.sum())]
    cap = moe.held_capacity(rows * 4, n_held, CFG.experts)
    assert cap == {"masked": 96, "grouped": 768, "compact": 1280, "overflow": 768, "all_held": 1200}[form]
    fits = int(mine.sum()) + n_held <= cap
    assert fits == (form != "overflow") and (form != "overflow" or int(mine.sum()) == (rows - 5) * 4)
    # [.., the layer ran the grouped form, and ran it compact]
    compact = form in ("grouped", "compact")
    assert [int(c) for c in counted[4:]] == [int(form != "masked"), int(compact)]
    from tests.test_sampling import _primitives

    _, prims = _primitives(jax.make_jaxpr(lambda p, x, v: _held_ffn(p, x, first, v))(p, n2, valid).jaxpr)
    # the blocks' loop, and no branch beside it; masked, and all experts held: neither
    assert ("while" in prims) == (form in ("grouped", "compact", "overflow")) and "cond" not in prims


def test_the_compact_capacity_is_twice_the_even_share_plus_a_junk_row_an_expert_in_row_tiles():
    """``held_capacity`` at the three cells' widest chunk entries (ISSUE 49):
    32 of 256 at top 10 over (4, 256), 8 of 64 at top 4 over (2, 256) and
    (4, 256); a chip that holds all its experts keeps every assignment."""
    assert moe.held_capacity(10240, 32, 256) == 2816
    assert moe.held_capacity(2048, 8, 64) == 768 and moe.held_capacity(4096, 8, 64) == 1280
    assert moe.held_capacity(10240, 64, 64) == 10240 and moe.held_capacity(1200, 16, 16) == 1200
    assert moe.held_capacity(300, 1, 256) == 256 and moe.held_capacity(200, 1, 2) == 200  # never above the assignments
    assert all(moe.held_capacity(a, 12, 192) % 256 == 0 for a in (4096, 8192, 16384))


# (e) frequencies and the score scale against the closed forms


@pytest.mark.parametrize("sizes", ["small", "published"])
def test_rope_frequencies_and_score_scale(ref, sizes):
    pub = PUBLISHED if sizes == "small" else ref.published()
    rs = pub["rope_scaling"]
    cfg = mla.MLADecoderConfig(
        rope_dim=pub["qk_rope_head_dim"], nope_dim=pub["qk_nope_head_dim"], rope_theta=pub["rope_theta"],
        yarn_factor=rs["factor"], yarn_original=rs["original_max_position_embeddings"],
        yarn_beta_fast=rs["beta_fast"], yarn_beta_slow=rs["beta_slow"], mscale_all_dim=rs["mscale_all_dim"],
    )
    np.testing.assert_allclose(cfg.inv_freq, ref.inv_freq(pub), rtol=1e-6)
    assert cfg.inv_freq[0] == pytest.approx(1.0)  # the fastest dimension keeps its frequency
    d = cfg.rope_dim
    assert cfg.inv_freq[-1] == pytest.approx(cfg.rope_theta ** (-(d - 2) / d) / cfg.yarn_factor, rel=1e-6)
    m = 0.1 * math.log(32) + 1
    assert cfg.score_scale == pytest.approx((cfg.nope_dim + d) ** -0.5 * m * m, rel=1e-12)
    assert cfg.score_scale == pytest.approx(ref.score_scale(pub), rel=1e-12)
    if sizes == "published":
        assert m == pytest.approx(1.34657, abs=1e-5) and cfg.score_scale == pytest.approx(192**-0.5 * 1.81326, rel=1e-5)


# (f) served through DecodeScheduler

SEQ, MAX_NEW = 24, 8


def _zoo(**kw):
    from seldon_core_tpu.models.zoo import get_model

    return get_model(
        "mla_decoder", **SIZES, experts_held=4, first_expert=4, seq=SEQ, max_new_tokens=MAX_NEW,
        param_dtype="float32", seed=11, **kw,
    )


async def test_scheduler_serves_the_family_streams_counts_and_never_recompiles():
    ms = _zoo()
    fam = ms.generative["family"]
    assert fam.name == "mla" and fam is mla.mla_family(CFG)
    sched = ds.DecodeScheduler(
        ms.params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4, prefix_slots=2, prefill_chunk=16,
        kv_page_size=PS, family=fam,
    )
    assert len(sched.pool.state) == 1 and sched.pool.state[0].shape[-1] == CFG.row_width  # latent pages
    assert sched.programs.attn_kernel == ""  # the CPU backend: the walk, which fetches nothing through the kernel
    sched.warmup()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 96, (5, SEQ)).astype(np.int32)
    prompts[1:, :16] = prompts[0, :16]
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    streamed: list = []
    first = await sched.submit(prompts[0], cache_prefix=16, on_token=lambda t, i: streamed.append(int(t)))
    np.testing.assert_array_equal(first, oracle[0])  # alone in 4 slots: 3 junk rows a step
    assert streamed == oracle[0, SEQ:].tolist()  # token by token, as they were sampled
    rest = await asyncio.gather(*(sched.submit(p) for p in prompts[1:]))
    for got, want in zip(rest, oracle[1:]):
        np.testing.assert_array_equal(got, want)  # prefix hits on the captured latent pages: the same greedy tokens
    assert sched.stat_prefix_hits == 4
    assert sched.recompiles_since_warmup() == 0
    sched.pool.alloc.check()
    frames = sched.flight.snapshot()
    steps = [f for f in frames if f.busy_ns[0] == 0 and f.moe_rows]
    assert steps
    expert_layers, held, k = 2, 4, 4
    for f in steps:
        assert f.moe_rows == f.active  # junk rows (free slots) are not counted
        assert 0 <= f.moe_local_picks <= f.moe_rows * k * expert_layers
        assert f.moe_experts_hit <= expert_layers * held and f.moe_load_max <= f.moe_rows * expert_layers
        assert f.moe_local_picks >= f.moe_load_max
        # each generating slot attends over its prompt and what it has generated: SEQ + 1 .. SEQ + MAX_NEW keys
        assert f.active * (SEQ + 1) <= f.mla_ctx_rows <= f.active * (SEQ + MAX_NEW)
    chunked = [f for f in frames if f.busy_ns[0] > 0]
    assert chunked and all(f.mla_ctx_rows > 0 for f in chunked)
    assert steps[0].to_dict()["mla"] == [steps[0].mla_ctx_rows, steps[0].moe_local_picks]
    await sched.close()


async def test_scheduler_counts_the_wide_chunks_layer_calls_that_ran_compact(monkeypatch):
    """A chunk dispatch wide enough for the grouped form (the (4, 64) entry's
    256 rows, with the masked form's reach cut to 128 here; the cells' are
    the 256-token entries) counts its two expert layers' calls into
    ``moe_grouped_calls`` and, their routing fitting ``held_capacity`` (768
    of 1,024 assignments), into ``moe_compact_calls``: the frame says
    ``"moe_compact": [2, 2]``. Narrower dispatches and steps count neither.
    The tokens are the oracle's."""
    from seldon_core_tpu.models.zoo import get_model

    monkeypatch.setattr(moe, "MASKED_MAX_ROWS", 128)
    seq, new = 72, 4
    ms = get_model(
        "mla_decoder", **SIZES, experts_held=4, first_expert=4, seq=seq, max_new_tokens=new, param_dtype="float32", seed=11,
    )
    sched = ds.DecodeScheduler(
        ms.params, seq_len=seq, max_new_tokens=new, n_slots=4, prefill_chunk=64, kv_page_size=PS,
        family=ms.generative["family"],
    )
    assert (4, 64) in sched.chunk_buckets and moe.held_capacity(4 * 64 * 4, 4, CFG.experts) == 768
    sched.warmup()
    prompts = np.random.default_rng(3).integers(0, 96, (4, seq)).astype(np.int32)
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    got = await asyncio.gather(*(sched.submit(p) for p in prompts))
    for mine, want in zip(got, oracle):
        np.testing.assert_array_equal(mine, want)
    assert sched.recompiles_since_warmup() == 0
    frames = sched.flight.snapshot()
    wide = [f for f in frames if f.chunk_rows * f.chunk_c > 128]
    assert wide and all((f.moe_grouped_calls, f.moe_compact_calls) == (2, 2) for f in wide)  # a call an expert layer
    assert wide[0].to_dict()["moe_compact"] == [2, 2]
    rest = [f for f in frames if f not in wide]
    assert rest and not any(f.moe_grouped_calls or f.moe_compact_calls or "moe_compact" in f.to_dict() for f in rest)
    await sched.close()


async def test_scheduler_with_the_step_kernel_serves_the_same_tokens_and_counts_its_pages(monkeypatch):
    """With the ONE place of choice answering "interpret" (the chip's answer
    is "mosaic"; the interpreter is the CPU's way to run the same kernels) the
    scheduler serves the oracle's greedy tokens, and each step round's frame
    carries the pages the kernel fetched for the generating slots: ceil(keys
    / page size) each, and of those the ones in whole runs of consecutive
    pages. The prefill chunks ride the chunk's kernel under the same answer:
    every chunk round's frame counts its prefilling rows into
    ``chunk_rows_kernel`` and its rows' pages into ``mla_pages_read``."""
    from seldon_core_tpu.serving import decode_programs as dp

    monkeypatch.setattr(mla_ops, "RUN_PAGES", 2)
    monkeypatch.setattr(mla_ops, "BLOCK_PAGES", 4)
    monkeypatch.setattr(mla_ops, "CHUNK_BLOCK_PAGES", 4)
    monkeypatch.setattr(dp, "_step_attn_kernel", lambda family, pool_state, mesh, heads, kv_heads: "interpret")
    ms = _zoo()
    fam = ms.generative["family"]
    sched = ds.DecodeScheduler(
        ms.params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4, prefix_slots=2, prefill_chunk=16,
        kv_page_size=PS, family=fam,
    )
    assert sched.programs.attn_kernel == "interpret"
    sched.warmup()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 96, (3, SEQ)).astype(np.int32)
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    first = await sched.submit(prompts[0])  # alone: pages 1.. in the order the fresh pool hands them out
    np.testing.assert_array_equal(first, oracle[0])
    rest = await asyncio.gather(*(sched.submit(p) for p in prompts[1:]))
    for got, want in zip(rest, oracle[1:]):
        np.testing.assert_array_equal(got, want)
    assert sched.recompiles_since_warmup() == 0
    frames = sched.flight.snapshot()
    steps = [f for f in frames if f.busy_ns[0] == 0 and f.moe_rows]
    assert steps and all(f.mla_pages_read for f in steps)
    for f in steps:
        # each generating slot has SEQ + 1 .. SEQ + MAX_NEW keys: 7 or 8 pages of 4, at most the 6 or 8 of them in runs of 2
        assert 7 * f.active <= f.mla_pages_read <= 8 * f.active
        if f.active == 1:
            assert f.mla_pages_read == -(-f.mla_ctx_rows // PS)
        assert 0 <= f.mla_run_pages <= f.mla_pages_read and f.mla_run_pages % 2 == 0
    alone = [f for f in steps if f.active == 1][:MAX_NEW - 1]
    # the first request had the fresh pool to itself: pages 1, 2, 3 .. in order, every whole group a run
    assert [f.mla_run_pages for f in alone] == [2 * (-(-f.mla_ctx_rows // PS) // 2) for f in alone]
    assert alone[0].to_dict()["mla_pages"] == [alone[0].mla_run_pages, alone[0].mla_pages_read]
    assert not any(f.chunk_rows_kernel for f in steps if not f.chunk_rows)
    chunked = [f for f in frames if f.chunk_rows]
    assert chunked and [sched.programs.chunk_attn(c) for _rows, c in sched.chunk_buckets] == ["kernel"] * len(sched.chunk_buckets)
    for f in chunked:
        assert f.chunk_rows_kernel == f.chunk_rows_live > 0  # static a program: all of a dispatch's rows
        assert f.to_dict()["chunk_rows_kernel"] == f.chunk_rows_kernel
    assert all(f.mla_pages_read for f in chunked)  # the chunk's own fetches ride the same two counts
    # a row's pages up to its last live query, counted once: the first request's chunk of 16 alone in its round
    # sees 4 pages, all in runs; its chunk of 8 (6 pages) shares a round with the first step (25 keys: 7 pages)
    assert (chunked[0].busy_ns[1], chunked[0].mla_pages_read, chunked[0].mla_run_pages) == (0, 4, 4)
    assert (chunked[1].mla_pages_read, chunked[1].mla_ctx_rows) == (6 + 7, 24 + 25)
    await sched.close()


def test_the_step_program_counts_the_pages_of_a_table_built_by_hand(weights, monkeypatch):
    """The two counts ride the token readback after ``mla_ctx_rows``: the
    pages of the LIVE rows (a row nobody reads fetches its junk page and is
    not counted), and those of them in groups that are whole runs."""
    monkeypatch.setattr(mla_ops, "RUN_PAGES", 2)
    monkeypatch.setattr(mla_ops, "BLOCK_PAGES", 4)
    params = weights[jnp.float32]
    pool = FAM.paged_kv_init(params, 21, PS, jnp.float32)
    step, _chunk = FAM.fused_programs("interpret")
    bt = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [11, 12, 14, 13, 15, 16, 17, 19, 0, 0], [20, 0, 0, 0, 0, 0, 0, 0, 0, 0]], jnp.int32)
    positions = jnp.array([30, 22, 9], jnp.int32)  # 31, 23 and 10 keys: 8, 6 and 3 pages
    zi, zf = jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.float32)
    out, _pool = jax.jit(step)(params, pool, bt, zi, positions, zf, zi, jnp.int32(0), jnp.int32(0), jnp.array([True, True, False]))
    counted = np.asarray(out)[3:]
    assert len(counted) == len(FAM.frame_counters) == 9
    named = dict(zip(FAM.frame_counters, counted.tolist()))
    assert named["mla_ctx_rows"] == 31 + 23
    assert named["mla_pages_read"] == 8 + 6
    assert named["mla_run_pages"] == 8 + 4  # row 0: four whole runs; row 1: (11, 12) and (15, 16), not (14, 13)
    walked, _pool = jax.jit(FAM.fused_programs()[0])(
        params, FAM.paged_kv_init(params, 21, PS, jnp.float32), bt, zi, positions, zf, zi, jnp.int32(0), jnp.int32(0),
        jnp.array([True, True, False]))
    assert np.asarray(walked)[3:].tolist()[-2:] == [0, 0]  # the walk fetched nothing through the kernel
    np.testing.assert_array_equal(np.asarray(walked)[:8], np.asarray(out)[:8])  # the same tokens and the same other counts


def test_the_run_pages_reader_reads_the_frames_and_gives_none_without_the_fields():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == "mla_run_pages_pct")
    cells_listed = entry.pop("workloads")  # the family's cells: the fifth, and whatever configuration joined it since
    assert entry == {"name": "mla_run_pages_pct", "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "kernels", "moves": "itl_p95_ms"} and cells_listed[0] == "a.x-k1.doc-qa-closed-64"
    reader = cells.load_module(ROOT, bench, "layer_metrics", "mla_run_pages_pct")

    def frame(**kw):
        return types.SimpleNamespace(mode="plain", busy_ns=(0, 5), **kw)

    step = dict(mla_ctx_rows=500, moe_rows=2)
    assert reader.read({"frames": [frame(**step, mla_pages_read=40, mla_run_pages=32), frame(**step, mla_pages_read=60, mla_run_pages=64 - 8)]}) == 88.0
    assert reader.read({"frames": [frame(**step)]}) is None  # the parent's frames: no such field
    assert reader.read({"frames": [frame(**step, mla_pages_read=0, mla_run_pages=0)]}) is None  # the walk ran
    assert reader.read({"frames": [frame(moe_rows=3)]}) is None  # another family's frames
    assert reader.read({"frames": []}) is None and reader.read({}) is None


# (g) the latent page kind; what the family does not serve is refused by name


def test_the_pool_is_one_plane_of_latent_rows(weights):
    d = FAM.decoder_dims(weights[jnp.float32])
    assert (d["kv_planes"], d["kv_heads"], d["head_dim"], d["kv_layers"]) == (1, 1, CFG.row_width, 3)
    (plane,) = FAM.paged_kv_init(weights[jnp.float32], 7, PS, jnp.bfloat16)
    assert plane.shape == (3, 7, PS, 128) and plane.dtype == jnp.bfloat16  # 20 numbers a row, in one lane tile
    assert mla.MLADecoderConfig(kv_rank=512, rope_dim=64).row_width == 640  # the published 576, in five
    with pytest.raises(ValueError, match="latent"):
        FAM.paged_kv_init(weights[jnp.float32], 7, PS, jnp.bfloat16, "int8")
    # a copied page carries its rows in every layer (copy-on-write's primitive walks the one plane)
    pool = (plane.at[:, 2].set(1.0),)
    (copied,) = dec.paged_copy(pool, jnp.array([2, 0]), jnp.array([5, 0]))
    assert bool(jnp.all(copied[:, 5] == 1.0)) and not bool(jnp.any(copied[:, 4]))


@pytest.mark.parametrize(
    "what", ["draft", "spec_tree", "tp", "kv_int8", "host_tier", "prefix_export", "gpt2_dims", "moe_dims", "hybrid_dims", "mla_dims"]
)
def test_what_the_family_does_not_serve_is_refused_by_name(what, weights):
    params = weights[jnp.float32]
    kw = dict(seq_len=8, max_new_tokens=4, n_slots=2, family=FAM)
    with pytest.raises(FamilyNotServed) as e:
        if what == "draft":
            draft = init_decoder(seed=0, vocab=96, hidden=64, layers=1, ffn=64, max_len=64)
            ds.DecodeScheduler(params, draft_params=draft, spec_k=2, **kw)
        elif what == "spec_tree":
            ds.DecodeScheduler(params, spec_tree="2,1", **kw)
        elif what == "tp":
            ds.DecodeScheduler(params, mesh_axes={"model": 2}, **kw)
        elif what == "kv_int8":
            ds.DecodeScheduler(params, kv_dtype="int8", **kw)
        elif what == "host_tier":
            ds.DecodeScheduler(params, kv_host_bytes=1 << 20, prefix_slots=2, **kw)
        elif what == "prefix_export":
            ds.DecodeScheduler(params, prefix_slots=2, **kw).export_prefix_state()
        elif what == "gpt2_dims":
            dec.gpt2_family.decoder_dims(params)  # not a KeyError
        elif what == "moe_dims":
            md.moe_family(md.MoEDecoderConfig()).decoder_dims(params)
        elif what == "hybrid_dims":
            hd.hybrid_family(hd.HybridDecoderConfig()).decoder_dims(params)
        else:
            FAM.decoder_dims(md.init_moe_decoder(md.MoEDecoderConfig(), seed=0, dtype=jnp.float32))
    if what in ("draft", "spec_tree", "tp", "kv_int8", "host_tier", "prefix_export"):
        assert "'mla' decoder family" in str(e.value)


@pytest.mark.parametrize(
    "platform, lanes, page, dtype, mesh, want",
    [
        ("tpu", 640, 16, jnp.bfloat16, None, "mosaic"),  # the a.x-k1 cell's pool
        ("tpu", 128, 32, jnp.bfloat16, None, "mosaic"),
        ("cpu", 640, 16, jnp.bfloat16, None, ""),  # the CPU backend: the walk is the oracle
        ("tpu", 640, 16, jnp.bfloat16, "a mesh", ""),
        ("tpu", 576, 16, jnp.bfloat16, None, ""),  # the published row, not whole lane tiles
        ("tpu", 640, 8, jnp.bfloat16, None, ""),  # a page under a two-byte float's sublane tile
        ("tpu", 640, 16, jnp.float32, None, ""),  # not a two-byte float
        ("tpu", 640, 16, jnp.int8, None, ""),
    ],
)
def test_the_step_attention_kernel_is_chosen_where_the_latent_plane_tiles(platform, lanes, page, dtype, mesh, want):
    """``_step_attn_kernel`` for this family: its kernel for ONE plane of a
    two-byte float in whole lane tiles and pages of 16 rows or more, on one
    TPU device and no mesh; the walk everywhere else."""
    device = types.SimpleNamespace(platform=platform)
    plane = types.SimpleNamespace(shape=(3, 7, page, lanes), dtype=jnp.dtype(dtype), sharding=types.SimpleNamespace(device_set=[device]))
    assert "attn_kernel" in FAM.serves
    assert _step_attn_kernel(FAM, (plane,), mesh, CFG.heads, 1) == want


def test_the_cpu_backends_pool_keeps_the_walk(weights):
    pool = FAM.paged_kv_init(weights[jnp.float32], 7, 16, jnp.bfloat16)
    assert mla_ops.kernel_tiles(128, 16, jnp.bfloat16) and _step_attn_kernel(FAM, pool, None, CFG.heads, 1) == ""


def test_the_fused_fallback_generates_through_the_same_forward(ref, weights):
    """Without ``tpu.decode_slots`` the zoo model's apply decodes whole
    batches greedily (``decoder.paged_greedy_generate``): the reference's
    argmax along them."""
    params = weights[jnp.float32]
    ids = np.stack([_ids(6, 12), _ids(7, 12)])
    out = np.asarray(jax.jit(lambda p, x: FAM.generate(p, x, 5))(params, jnp.asarray(ids)))
    assert out.shape == (2, 17) and (out[:, :12] == ids).all()
    for row in out:
        want = _ref_logits(ref, params, row, "highest")
        np.testing.assert_array_equal(row[12:], np.argmax(want[11:16], axis=-1))
