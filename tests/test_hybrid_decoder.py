"""The hybrid state-space / attention family (models/hybrid_decoder.py) held to
its plain reference (benchmarks/reference/granite-4.0-h-micro.py) at a small
size on the CPU: hidden 64, 6 layers of which 2 attend (4 query / 2
key-value heads of 16), 8 Mamba-2 heads of 16 with state 16, pages of 4.
Seeded random weights; every case counts on its own.
"""

import asyncio
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness import cells  # noqa: E402
from harness.correct import judge_generated  # noqa: E402

from seldon_core_tpu.models import hybrid_decoder as hd  # noqa: E402
from seldon_core_tpu.models import moe_decoder as md  # noqa: E402
from seldon_core_tpu.models.decoder import (  # noqa: E402
    FamilyNotServed,
    _fused_chunk,
    _fused_step,
    gpt2_family,
    init_decoder,
)
from seldon_core_tpu.serving import decode_scheduler as ds  # noqa: E402
from seldon_core_tpu.serving.kv_pool import PageAllocator  # noqa: E402

ATTN = (2, 5)
CFG = hd.HybridDecoderConfig(
    vocab=96, hidden=64, layers=6, attn_layers=ATTN, heads=4, kv_heads=2, head_dim=16, ffn=96,
    ssm_heads=8, ssm_head_dim=16, ssm_state=16, attention_multiplier=0.0625,
)
# the same sizes under the published config's keys, for the reference
PUBLISHED = {
    "rms_norm_eps": 1e-5, "residual_multiplier": 0.22, "embedding_multiplier": 12.0,
    "attention_multiplier": 0.0625, "logits_scaling": 8.0, "num_key_value_heads": 2, "mamba_n_heads": 8,
    "mamba_d_state": 16, "layer_types": ["attention" if i in ATTN else "mamba" for i in range(6)],
}
PS = 4  # page size
CTX = 40
FAM = hd.hybrid_family(CFG)
# state rows of the hand-driven cases: slots 0..2, one snapshot row, the zero row; 5 drops a write
SNAP, ZERO, DROP = 3, 4, 5


@pytest.fixture(scope="module")
def ref():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return cells.load_module(ROOT, json.load(f), "reference", "granite-4.0-h-micro")


@pytest.fixture(scope="module")
def weights():
    out = {}
    for d in (jnp.float32, jnp.bfloat16):
        p = hd.init_hybrid_decoder(CFG, seed=5, dtype=d)
        # at this width the layers add little: a smaller embedding lets them decide the logits
        p["tok_emb"] = (p["tok_emb"].astype(jnp.float32) * 0.25).astype(d)
        out[d] = p
    return out


def _ref_logits(ref, params, ids, precision):
    return np.asarray(
        ref.logits(params, np.asarray(ids)[None], 0, n_head=CFG.heads, precision=precision, config=PUBLISHED)
    )[0]


def _ids(seed=0, n=CTX):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(np.int32)


def _serve(params, ids, *, chunks, dtype=jnp.float32, start=None, snap_at=None, others=False):
    """Teacher-forced through the paged programs: chunked prefill of
    ``sum(chunks)`` tokens, then single-token steps along ``ids``; returns
    (logits [len(ids), vocab], pool, rec, pages). The sequence sits in slot 1
    of 3. ``start`` = (pool, rec, pages, n): the first n tokens' pages of an
    earlier run are MAPPED and its snapshot row read (a prefix hit), only the
    rest is computed. ``snap_at``: the chunk that ends there also writes the
    snapshot row. ``others``: slots 0 and 2 generate junk tokens in every
    step instead of riding masked."""
    n_slots, pages = 3, CTX // PS
    if start is None:
        pool = FAM.paged_kv_init(params, 1 + 2 * pages, PS, dtype)
        rec = FAM.state_init(params, DROP)
        mine, done, read = 1 + np.arange(pages), 0, ZERO
    else:
        pool, rec, theirs, done = start
        assert done % PS == 0
        mine = np.concatenate([theirs[: done // PS], 1 + pages + np.arange(pages - done // PS)])
        read = SNAP
    bt = np.zeros((n_slots, pages), np.int32)
    bt[1] = mine
    out = np.zeros((len(ids), CFG.vocab), np.float32)
    pos = done
    for c in chunks:
        toks = np.zeros((n_slots, max(chunks)), np.int32)
        toks[1, :c] = ids[pos : pos + c]
        rows3 = np.array(
            [[ZERO, read, ZERO], [DROP, 1, DROP], [DROP, SNAP if snap_at == pos + c else DROP, DROP]], np.int32
        )
        logits, pool, rec, _ = FAM.paged_forward(
            params, pool, rec, jnp.asarray(bt), jnp.asarray(toks), jnp.array([0, pos, 0], jnp.int32),
            counts=jnp.array([0, c, 0], jnp.int32), state_rows=jnp.asarray(rows3),
        )
        out[pos : pos + c] = np.asarray(logits[1, :c])
        pos, read = pos + c, 1
    while pos < len(ids):
        logits, pool, rec, _ = FAM.paged_forward(
            params, pool, rec, jnp.asarray(bt), jnp.array([[7], [ids[pos]], [9]], jnp.int32),
            jnp.array([0, pos, 0], jnp.int32), rows=jnp.array([others, True, others]),
        )
        out[pos] = np.asarray(logits[1, 0])
        pos += 1
    return out, pool, rec, mine


# (a) chunked prefill then decode through both caches == the reference's full forward


@pytest.mark.parametrize(
    "chunks", [(1, 1, 1), (5, 5, 5, 5), (7, 7, 7, 1), (13,), (8, 8, 8), (9, 2, 1, 6)],
    ids=["by1", "by5", "by7", "one", "pages", "inside_conv_reach"],
)
def test_cold_prefill_then_decode_equals_reference_float32(ref, weights, chunks):
    """Splits at 1, at odd lengths, at whole pages (4) and 2 and 1 tokens
    after a boundary, inside the convolution's 3-token reach: every
    position's logits, chunked scan then recurrence, to 1e-5."""
    ids, params = _ids(), weights[jnp.float32]
    got, _, rec, _ = _serve(params, ids, chunks=chunks)
    np.testing.assert_allclose(got, _ref_logits(ref, params, ids, "highest"), atol=1e-5)
    assert not any(np.asarray(a[ZERO]).any() for a in rec)  # every layer's zero row, state and conv


@pytest.mark.parametrize("m", [1, 3, 16, 33])
def test_chunked_scan_equals_token_by_token_scan(m):
    """``_scan_chunk`` against the recurrence it stands for, from a non-zero
    state, rows of 0 time step (past a slot's count) leaving the state."""
    rng = np.random.default_rng(m)
    n, h, p, k = 3, 4, 8, 16
    dt = jnp.asarray(rng.uniform(1e-3, 0.5, (n, m, h)), jnp.float32).at[1, m // 2 :].set(0.0)
    a_neg = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    xs, b, c = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((n, m, h, p), (n, m, k), (n, m, k)))
    s = jnp.asarray(rng.normal(size=(n, h, p, k)), jnp.float32)
    y, s_out = hd._scan_chunk(dt, a_neg, xs, b, c, s)
    want = []
    for t in range(m):
        s = jnp.exp(dt[:, t] * a_neg)[:, :, None, None] * s + (
            (dt[:, t, :, None] * xs[:, t])[..., None] * b[:, t, None, None, :]
        )
        want.append(jnp.einsum("nhpk,nk->nhp", s, c[:, t]))
    np.testing.assert_allclose(np.asarray(y), np.stack(want, 1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_out), np.asarray(s), atol=2e-5)
    yb, sb = hd._scan_blocked(dt[:2], a_neg, xs[:2], b[:2], c[:2], jnp.zeros((2, h, p, k)))
    y1, s1 = hd._scan_chunk(dt[:2], a_neg, xs[:2], b[:2], c[:2], jnp.zeros((2, h, p, k)))
    np.testing.assert_array_equal(np.asarray(yb), np.asarray(y1))  # under the byte limit: one block


def test_scan_goes_in_blocks_of_rows_above_the_byte_limit(monkeypatch):
    rng = np.random.default_rng(0)
    n, m, h, p, k = 4, 8, 2, 4, 4
    args = [jnp.asarray(rng.uniform(0.01, 0.2, (n, m, h)), jnp.float32), -jnp.ones((h,)),
            *(jnp.asarray(rng.normal(size=s), jnp.float32) for s in ((n, m, h, p), (n, m, k), (n, m, k), (n, h, p, k)))]
    whole = hd._scan_chunk(*args)
    monkeypatch.setattr(hd, "_SCAN_BLOCK_BYTES", 4 * h * m * m)  # one row a block
    for got, want in zip(hd._scan_blocked(*args), whole):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# (b) a prefix hit starts from the entry's snapshot


@pytest.mark.parametrize("shared", [8, 20])
def test_prefix_hit_from_a_snapshot_equals_reference_float32(ref, weights, shared):
    """A second sequence shares ``shared`` tokens: their pages are mapped and
    the first chunk reads the snapshot row the first one's chunk wrote at the
    boundary; every computed position equals the reference on ITS tokens."""
    params = weights[jnp.float32]
    a, b = _ids(0), _ids(1)
    b[:shared] = a[:shared]
    _, pool, rec, pages = _serve(params, a, chunks=(shared, 6), snap_at=shared)
    got, _, _, _ = _serve(params, b, chunks=(5, 3), start=(pool, rec, pages, shared))
    np.testing.assert_allclose(got[shared:], _ref_logits(ref, params, b, "highest")[shared:], atol=1e-5)


def _faulty(rec, fault):
    """A copy of the state cache (a serve donates nothing here, but rebinds)
    with the snapshot row's state or conv inputs zeroed."""
    n = CFG.ssm_layers
    hit = {"zero_row": range(n), "conv_dropped": range(n, 2 * n), "": ()}[fault]
    return tuple(a.at[SNAP].set(0.0) if i in hit else a for i, a in enumerate(rec)),


@pytest.mark.parametrize("fault", ["zero_row", "conv_dropped"])
def test_what_the_comparison_sees_by_control(ref, weights, fault):
    """The two faults a state cache can have, put in by hand: a hit that
    starts from the zero row, a conv cache dropped at the boundary. Either
    moves the logits after the boundary far past float32 rounding."""
    params = weights[jnp.float32]
    ids, shared = _ids(0), 8
    _, pool, rec, pages = _serve(params, ids, chunks=(shared,), snap_at=shared)
    want = _ref_logits(ref, params, ids, "highest")[shared:]
    clean, _, _, _ = _serve(params, ids, chunks=(5, 3), start=(pool, *_faulty(rec, ""), pages, shared))
    got, _, _, _ = _serve(params, ids, chunks=(5, 3), start=(pool, *_faulty(rec, fault), pages, shared))
    assert np.abs(got[shared:] - want).max() > 100 * max(np.abs(clean[shared:] - want).max(), 1e-8)


# (c) the step leaves every state it was not asked to advance


def test_step_advances_the_rows_that_generate_and_no_other(weights):
    """Slot 1 prefills 9 tokens, rides three steps as a junk row while slots
    0 and 2 generate, then prefills on: its state and logits are those of a
    lone prefill, bit for bit; free slots' and padding rows' writes do not
    reach it."""
    params = weights[jnp.float32]
    ids = _ids(3)
    lone, _, rec_lone, _ = _serve(params, ids[:20], chunks=(9, 11))
    pages = CTX // PS
    pool = FAM.paged_kv_init(params, 1 + 3 * pages, PS, jnp.float32)
    rec = FAM.state_init(params, DROP)
    bt = np.zeros((3, pages), np.int32)
    bt[1] = 1 + np.arange(pages)
    bt[0], bt[2] = 1 + pages + np.arange(pages), 1 + 2 * pages + np.arange(pages)

    def chunk(pos, c, read):
        toks = np.zeros((3, 11), np.int32)
        toks[1, :c] = ids[pos : pos + c]
        rows3 = np.array([[ZERO, read, ZERO], [DROP, 1, DROP], [DROP, DROP, DROP]], np.int32)
        return FAM.paged_forward(
            params, pool, rec, jnp.asarray(bt), jnp.asarray(toks), jnp.array([0, pos, 0], jnp.int32),
            counts=jnp.array([0, c, 0], jnp.int32), state_rows=jnp.asarray(rows3),
        )

    _, pool, rec, counted = chunk(0, 9, ZERO)
    assert int(counted[0]) == 1  # one row's state advanced: the padding rows are not counted
    mid = [np.asarray(r[1]) for r in rec]
    for t in range(3):
        _, pool, rec, counted = FAM.paged_forward(
            params, pool, rec, jnp.asarray(bt), jnp.array([[5], [0], [6]], jnp.int32),
            jnp.array([t, 9, t], jnp.int32), rows=jnp.array([True, False, True]),
        )
        assert int(counted[0]) == 2
    for before, after in zip(mid, rec):
        np.testing.assert_array_equal(before, np.asarray(after[1]))
    assert np.asarray(rec[0][0]).any() and np.asarray(rec[0][2]).any()  # the others did advance
    logits, pool, rec, _ = chunk(9, 11, 1)
    np.testing.assert_array_equal(np.asarray(logits[1, :11]), lone[9:20])
    for got, want in zip(rec, rec_lone):
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        assert not np.asarray(got[ZERO]).any()


# (d) bfloat16 serving against the harness's rule


@pytest.mark.parametrize("path", ["cold", "hit"])
def test_bfloat16_serving_within_the_harness_delta(ref, weights, path):
    """Greedy tokens served in bfloat16 through both caches, judged as
    benchmarks/harness/correct.py judges a run: the float32 reference's logit
    of each served token within twice the reference's own bfloat16 rounding."""
    params = weights[jnp.bfloat16]
    ids, first = _ids(2), 23
    start, chunks = None, (12, 12)
    if path == "hit":
        _, pool, rec, pages = _serve(params, ids, chunks=(8,), dtype=jnp.bfloat16, snap_at=8)
        start, chunks = (pool, rec, pages, 8), (8, 8)
    served = list(ids[: first + 1])
    while len(served) < CTX:
        got, _, _, _ = _serve(params, np.asarray(served, np.int32), chunks=chunks, dtype=jnp.bfloat16, start=start)
        served.append(int(got[len(served) - 1].argmax()))
    exact, noisy = (_ref_logits(ref, params, served, p)[None, first:] for p in ("highest", "default"))
    verdict = judge_generated([served], exact, noisy, first)
    assert verdict["ok"], verdict


# (e) served through DecodeScheduler

SEQ, MAX_NEW = 24, 8


def _zoo(**kw):
    from seldon_core_tpu.models.zoo import get_model

    ms = get_model(
        "hybrid_decoder", vocab=96, hidden=64, layers=6, attn_layers="2,5", heads=4, kv_heads=2, head_dim=16,
        ffn=96, ssm_heads=8, ssm_head_dim=16, ssm_state=16, seq=SEQ, max_new_tokens=MAX_NEW,
        param_dtype="float32", seed=11, **kw,
    )
    ms.params["tok_emb"] = ms.params["tok_emb"] * 0.25
    return ms


def _sched(ms, **kw):
    kw = {"n_slots": 4, "prefix_slots": 2, "prefill_chunk": 16, "kv_page_size": PS, **kw}
    return ds.DecodeScheduler(
        ms.params, seq_len=SEQ, max_new_tokens=MAX_NEW, family=ms.generative["family"], **kw
    )


async def test_scheduler_serves_the_family_restores_snapshots_and_never_recompiles():
    ms = _zoo()
    sched = _sched(ms)
    sched.warmup()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 96, (6, SEQ)).astype(np.int32)
    prompts[1:, :12] = prompts[0, :12]
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    first = await sched.submit(prompts[0], cache_prefix=12)
    np.testing.assert_array_equal(first, oracle[0])  # chunks 12 (the hint's boundary), 12
    rest = await asyncio.gather(*(sched.submit(p) for p in prompts[1:]))
    for got, want in zip(rest, oracle[1:]):
        np.testing.assert_array_equal(got, want)  # started from the snapshot: the same greedy tokens
    assert (sched.stat_prefix_hits, sched.stat_prefix_captures) == (5, 1)
    assert sched.stat_prefix_capture_skips == 5  # unhinted requests capture nothing
    assert sched.recompiles_since_warmup() == 0
    frames = sched.flight.snapshot()
    assert sum(f.state_restores for f in frames) == 5 and sum(f.state_captures for f in frames) == 1
    steps = [f for f in frames if f.busy_ns[0] == 0 and f.ssm_rows]
    assert steps and all(f.ssm_rows == f.active for f in steps)  # junk rows are not counted
    assert "ssm" in steps[0].to_dict() and all(f.moe_rows == 0 for f in frames)
    sched.pool.alloc.check()
    assert sched.pool.alloc.snapshot()["state_rows_free"] == 1
    assert not any(np.asarray(a[sched.pool.zero_row]).any() for a in sched.pool.recurrent)
    await sched.close()


async def test_a_common_depth_short_of_the_entry_reuses_nothing_and_a_reused_slot_starts_clean():
    ms = _zoo()
    sched = _sched(ms, n_slots=1, kv_pages=20)  # room for the entry's pinned pages beside a context
    sched.warmup()
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, 96, (3, SEQ)).astype(np.int32)
    prompts[1, :8] = prompts[0, :8]  # shares 8 of the entry's 12 tokens: no snapshot there
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    for i, hint in ((0, 12), (1, None), (2, None), (0, None)):
        got = await sched.submit(prompts[i], cache_prefix=hint)
        np.testing.assert_array_equal(got, oracle[i])  # the one slot, reused: no state left over
    assert (sched.stat_prefix_hits, sched.stat_prefix_misses) == (1, 3)  # the exact repeat alone hits
    assert sched.stat_prefix_tokens_saved == 12
    await sched.close()


async def test_a_slot_that_prefills_over_rounds_keeps_its_state_while_others_decode():
    """Chunks of 4 over a 24-token prompt, interleaved with the other slots'
    steps: the tokens a lone request gets."""
    ms = _zoo()
    sched = _sched(ms, prefill_chunk=4, prefix_slots=0)
    sched.warmup()
    prompts = np.random.default_rng(2).integers(0, 96, (4, SEQ)).astype(np.int32)
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    first = asyncio.ensure_future(sched.submit(prompts[0]))
    await asyncio.sleep(0)
    outs = await asyncio.gather(first, *(sched.submit(p) for p in prompts[1:]))
    for got, want in zip(outs, oracle):
        np.testing.assert_array_equal(got, want)
    assert any(f.prefilling and f.mode == "plain" and f.tokens for f in sched.flight.snapshot())
    assert sched.recompiles_since_warmup() == 0
    await sched.close()


@pytest.mark.parametrize("by", ["index_cap", "pin_reclaim"])
async def test_eviction_frees_the_state_row(by):
    ms = _zoo()
    # pin_reclaim: 7 pages a context (28 tokens), a pool of 10: an entry's 3
    # pinned pages are reclaimed when the next request needs them
    sched = _sched(ms, n_slots=1, prefix_slots=1 if by == "index_cap" else 2,
                   kv_pages=0 if by == "index_cap" else 10)
    sched.warmup()
    prompts = np.random.default_rng(3).integers(0, 96, (3, SEQ)).astype(np.int32)
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    alloc = sched.pool.alloc
    for i in range(3):
        got = await sched.submit(prompts[i], cache_prefix=12)
        np.testing.assert_array_equal(got, oracle[i])
        alloc.check()
        bound = sorted(p.state_row for p in alloc._pins.values())
        assert bound == sorted(e.state_row for e in sched._prefix_index.entries.values())
        assert len(bound) + alloc.snapshot()["state_rows_free"] == sched.prefix_slots
    assert sched.stat_prefix_captures == 3
    if by == "index_cap":
        assert sched.stat_prefix_evictions == 2 and len(sched._prefix_index.entries) == 1
    else:
        assert alloc.snapshot()["pin_reclaims"] >= 1 and sched.stat_prefix_evictions >= 1
    again = await sched.submit(prompts[2])
    np.testing.assert_array_equal(again, oracle[2])  # the newest entry survived: a hit
    assert sched.stat_prefix_hits == 1
    await sched.close()


def test_allocator_hands_out_binds_and_takes_back_snapshot_rows():
    a = PageAllocator(20, 4, 2, 4, n_state_rows=2)
    assert sorted(a._state_free) == [2, 3] and a.snapshot()["state_rows_free"] == 2
    row = a.take_state_row()
    assert a.try_admit(0, (), 0) and not a.prepare_write(0, 0, 8)
    pin = a.capture(0, 8)
    pin.state_row = row
    a.check()
    other = a.take_state_row()
    assert {row, other} == {2, 3} and a.take_state_row() == -1
    a.give_state_row(other)
    a.release(pin.pin_id)
    a.check()
    assert a.snapshot()["state_rows_free"] == 2
    # a free row that an admission has yet to read is passed over, whichever lies on top
    top = a._state_free[-1]
    assert a.take_state_row({top}) == 5 - top and a.take_state_row({top}) == -1
    assert a.take_state_row({7}) == top and a.take_state_row() == -1
    assert PageAllocator(20, 4, 2, 4).snapshot()["state_rows_free"] == 0  # a family without a state cache


# (f) the other families are as they were; what this one does not serve is refused by name


def test_the_other_families_keep_their_own_fused_programs(weights):
    step, chunk = gpt2_family.fused_programs()
    assert step is _fused_step and chunk is _fused_chunk and gpt2_family.state_init is None
    moe = md.moe_family(md.MoEDecoderConfig())
    # two page kinds (sliding layers): the int8 pool and (PR 48) the step's kernel; the tiers and prefix export where there is one kind
    assert moe.state_init is None and moe.serves == {"attn_kernel", "kv_int8"} and moe.cfg.two_kinds
    assert {"kv_int8", "host_tier", "prefix_export"} <= md.moe_family(md.MoEDecoderConfig(period=1)).serves
    hstep, hchunk = FAM.fused_programs()
    assert (hstep.__name__, hchunk.__name__) == ("_fused_step", "_fused_chunk")  # one name in a trace
    assert hd.hybrid_family(hd.HybridDecoderConfig(**vars(CFG))).fused_programs() == (hstep, hchunk)
    assert FAM.serves == frozenset({"attn_kernel"}) and FAM.frame_counters == ("ssm_rows", "attn_run_pages")


@pytest.mark.parametrize("dims_of", ["gpt2", "moe", "hybrid"])
def test_the_pool_has_the_layers_that_hold_kv(dims_of, weights):
    if dims_of == "gpt2":
        params, fam = init_decoder(seed=0, vocab=64, hidden=128, layers=2, ffn=256, max_len=32), gpt2_family
    elif dims_of == "moe":
        cfg = md.MoEDecoderConfig()
        params, fam = md.init_moe_decoder(cfg, 0, jnp.float32), md.moe_family(cfg)
    else:
        params, fam = weights[jnp.float32], FAM
    d = fam.decoder_dims(params)
    want = len(ATTN) if dims_of == "hybrid" else d["layers"]
    assert d["kv_layers"] == want
    full = want - d.get("kv_window_layers", 0)  # sliding layers' pages are a kind of their own, after the full kind's planes
    assert fam.paged_kv_init(params, 3, 4)[0].shape == (full, 3, 4, d["kv_heads"] * d["head_dim"])
    assert fam.paged_kv_init(params, 3, 4, kv_dtype="int8")[1].shape == (full, 3, 4)
    if dims_of == "moe":
        assert (full, d["kv_window_layers"]) == (1, 3) and fam.paged_kv_init(params, (3, 5), 4)[2].shape[:2] == (3, 5)
    if dims_of == "hybrid":
        rec = fam.state_init(params, 7)  # an array a Mamba layer: states, then conv inputs
        assert [a.shape for a in rec] == [(7, 8, 16, 16)] * 4 + [(7, 3 * (8 * 16 + 2 * 16))] * 4
        assert all(a.dtype == jnp.float32 for a in rec)


@pytest.mark.parametrize(
    "what", ["draft", "spec_tree", "tp", "int8_pool", "host_tier", "store_tier", "export", "preseed",
             "export_entry", "gpt2_dims", "hybrid_dims"])
def test_what_the_family_does_not_serve_is_refused_by_name(what, weights):
    params = weights[jnp.float32]
    kw = dict(seq_len=8, max_new_tokens=4, n_slots=2, family=FAM)
    with pytest.raises(FamilyNotServed, match="hybrid|not a "):
        if what == "draft":
            draft = init_decoder(seed=0, vocab=96, hidden=64, layers=1, ffn=64, max_len=64)
            ds.DecodeScheduler(params, draft_params=draft, spec_k=2, **kw)
        elif what == "spec_tree":
            ds.DecodeScheduler(params, spec_tree="2,1", **kw)
        elif what == "tp":
            ds.DecodeScheduler(params, mesh_axes={"model": 2}, **kw)
        elif what == "int8_pool":
            ds.DecodeScheduler(params, kv_dtype="int8", **kw)
        elif what == "host_tier":
            ds.DecodeScheduler(params, prefix_slots=2, kv_host_bytes=1 << 20, **kw)
        elif what == "store_tier":
            ds.DecodeScheduler(params, prefix_slots=2, kv_store_url="memory://", **kw)
        elif what == "gpt2_dims":
            gpt2_family.decoder_dims(params)  # not a KeyError
        elif what == "hybrid_dims":
            FAM.decoder_dims(init_decoder(seed=0, vocab=64, hidden=64, layers=1, ffn=64, max_len=32))
        else:
            sched = ds.DecodeScheduler(params, prefix_slots=2, **kw)
            if what == "export":
                sched.export_prefix_state()
            elif what == "preseed":
                sched.preseed_prefix_state({"entries": []})
            else:
                sched.export_prefix_entry(np.zeros(8, np.int32))


def test_the_step_attention_kernel_is_not_chosen_on_the_cpu_backend(weights):
    from seldon_core_tpu.serving import decode_programs as dp

    pool = FAM.paged_kv_init(weights[jnp.float32], 3, 16, jnp.bfloat16)
    assert dp._step_attn_kernel(FAM, pool, None, CFG.heads, CFG.kv_heads) == ""
