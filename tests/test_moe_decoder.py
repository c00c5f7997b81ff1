"""The sparse-expert decoder family (models/moe_decoder.py, ops/moe.py) held
to its plain reference (benchmarks/reference/mellum2-12b-a2.5b.py) at a
small size on the CPU: hidden 64, 4 query / 2 key-value heads of 16, 8
experts top-2 of width 32, window 8, pages of 4, two periods of 3 sliding
layers + 1 full one, YaRN factor 4 over an original context of 16, contexts
to five windows. Seeded random weights; every case counts on its own.
"""

import asyncio
import dataclasses
import math
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

from seldon_core_tpu.models.decoder import _fused_chunk, _fused_step, gpt2_family  # noqa: E402
from seldon_core_tpu.models import moe_decoder as md  # noqa: E402
from seldon_core_tpu.models.decoder import FamilyNotServed, init_decoder  # noqa: E402
from seldon_core_tpu.ops import moe  # noqa: E402
from seldon_core_tpu.serving import decode_scheduler as ds  # noqa: E402

CFG = md.MoEDecoderConfig(
    vocab=96, hidden=64, layers=8, heads=4, kv_heads=2, head_dim=16, ffn=32, experts=8,
    experts_per_tok=2, window=8, period=4, rope_theta=10000.0, yarn_factor=4.0, yarn_original=16,
)
# the same sizes under the published config's keys, for the reference
PUBLISHED = {
    "head_dim": 16, "num_key_value_heads": 2, "num_experts_per_tok": 2, "rms_norm_eps": 1e-6,
    "sliding_window": 8,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 2,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0, "original_max_position_embeddings": 16,
            "beta_fast": 32, "beta_slow": 1, "attention_factor": 0.1 * math.log(4.0) + 1.0,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0},
    },
}
PS = 4  # page size
CTX = 40  # five windows
FAM = md.moe_family(CFG)


@pytest.fixture(scope="module")
def ref():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        import json

        return cells.load_module(ROOT, json.load(f), "reference", "mellum2-12b-a2.5b")


@pytest.fixture(scope="module")
def weights():
    return {d: md.init_moe_decoder(CFG, seed=5, dtype=d) for d in (jnp.float32, jnp.bfloat16)}


def _ref_logits(ref, params, ids, precision):
    return np.asarray(
        ref.logits(params, np.asarray(ids)[None], 0, n_head=CFG.heads, precision=precision, config=PUBLISHED)
    )[0]


def _serve(params, ids, *, chunks, prefix_from=None, dtype=jnp.float32, attn_kernel=""):
    """Teacher-forced through the paged programs: chunked prefill of
    ``sum(chunks)`` tokens, then single-token steps along ``ids``; returns
    logits [len(ids), vocab]. The sequence sits in slot 1 of 3 (slots 0 and
    2 ride as junk). ``prefix_from`` = (pool, pages, n): the first n tokens'
    pages of an earlier run are MAPPED (a prefix hit), only the rest is
    computed. ``attn_kernel``: what the chunks are handed as
    ``decode_programs._step_attn_kernel``'s answer (the steps gather)."""
    n_slots, pages = 3, CTX // PS
    if prefix_from is None:
        pool = FAM.paged_kv_init(params, 1 + 2 * pages, PS, dtype)
        mine, done = 1 + np.arange(pages), 0
    else:
        pool, theirs, done = prefix_from
        assert done % PS == 0
        mine = np.concatenate([theirs[: done // PS], 1 + pages + np.arange(pages - done // PS)])
    bt = np.zeros((n_slots, pages), np.int32)
    bt[1] = mine
    out = np.zeros((len(ids), CFG.vocab), np.float32)
    pos = done
    for c in chunks:
        toks = np.zeros((n_slots, max(chunks)), np.int32)
        toks[1, :c] = ids[pos : pos + c]
        counts = np.array([0, c, 0], np.int32)
        logits, _h, pool, _ = FAM.paged_forward(
            params, pool, jnp.asarray(bt), jnp.asarray(toks), jnp.array([0, pos, 0], jnp.int32), jnp.asarray(counts),
            attn_kernel=attn_kernel,
        )
        out[pos : pos + c] = np.asarray(logits[1, :c])
        pos += c
    while pos < len(ids):
        logits, _h, pool = FAM.paged_decode_step(
            params, pool, jnp.asarray(bt), jnp.array([0, ids[pos], 0], jnp.int32), jnp.array([0, pos, 0], jnp.int32)
        )
        out[pos] = np.asarray(logits[1])
        pos += 1
    return out, pool, mine


def _ids(seed=0, n=CTX):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(np.int32)


# (a) chunked prefill then decode through the pool == the reference's full forward


@pytest.mark.parametrize("chunks", [(5, 5, 5, 5), (7, 7, 7, 1), (13,)], ids=["by5", "by7", "one"])
def test_cold_prefill_then_decode_equals_reference_float32(ref, weights, chunks):
    """Past several windows and across page boundaries (pages of 4, chunks
    of 5, 7 and 13): every position's logits, to 1e-5."""
    params, ids = weights[jnp.float32], _ids(1)
    got, _, _ = _serve(params, ids, chunks=chunks)
    np.testing.assert_allclose(got, _ref_logits(ref, params, ids, "highest"), atol=1e-5, rtol=0)


@pytest.mark.parametrize("shared", [8, 20, 32])
def test_prefix_hit_equals_reference_float32(ref, weights, shared):
    """A second sequence maps the first one's pages for its first ``shared``
    tokens (rotated keys are in the pages: a hit needs nothing new) and
    computes only the rest."""
    params = weights[jnp.float32]
    first, second = _ids(2), _ids(3)
    second[:shared] = first[:shared]
    _, pool, pages = _serve(params, first, chunks=(10, 10))
    got, _, _ = _serve(params, second, chunks=(3, 3), prefix_from=(pool, pages, shared))
    want = _ref_logits(ref, params, second, "highest")
    np.testing.assert_allclose(got[shared:], want[shared:], atol=1e-5, rtol=0)


@pytest.mark.parametrize("path", ["cold", "hit"])
def test_bfloat16_serving_within_the_harness_delta(ref, weights, path):
    """The harness's rule (benchmarks/harness/correct.py) at the small size:
    along greedy tokens served in bfloat16, the reference's exact logit of
    each served token trails its best by at most twice the rounding delta
    measured between the reference at the stated precision and at
    "highest"."""
    params = weights[jnp.bfloat16]
    ids = _ids(4)
    first = 23
    prefix = None
    if path == "hit":
        _, pool, pages = _serve(params, ids[:24].tolist() + _ids(9, 16).tolist(), chunks=(12, 12), dtype=jnp.bfloat16)
        prefix = (pool, pages, 16)
    for pos in range(first + 1, CTX):  # greedy: each token from the served logits of the one before
        got, _, _ = _serve(params, ids[: pos], chunks=(8,) if prefix else (12, 12), prefix_from=prefix, dtype=jnp.bfloat16)
        ids[pos] = int(np.argmax(got[pos - 1]))
    exact, noisy = (_ref_logits(ref, params, ids, p)[None, first:] for p in ("highest", "default"))
    verdict = judge_generated([ids.tolist()], exact, noisy, first)
    assert verdict["ok"], verdict
    assert verdict["rounding_delta"] > 1e-4  # bfloat16 activations do round


# (b) a sliding layer forgets what left its window; a full layer does not


@pytest.mark.parametrize("chunks", [(8, 8, 8, 8), (9, 2, 1, 6), (5, 5, 5, 5), (27,)], ids=["pages", "ragged", "by5", "one"])
def test_a_chunk_program_with_the_kernel_equals_the_gather_chunk(weights, small_chunk_kernel_blocks, chunks):
    """The prefill chunks through ops/gqa_decode.py's chunk kernel (the
    Pallas interpreter; both page kinds: the full layers' whole table, the
    sliding layers' windowed sub-table) give the gather chunks' logits to
    float32 rounding at every position and leave the same pool, whatever the
    chunks' lengths and wherever they start; the steps after them agree too."""
    ids, params = _ids(), weights[jnp.float32]
    want, pool_g, mine = _serve(params, ids, chunks=chunks)
    got, pool_k, _ = _serve(params, ids, chunks=chunks, attn_kernel="interpret")
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * np.abs(want).max())
    for a, b in zip(pool_g, pool_k):
        np.testing.assert_allclose(np.asarray(a[:, mine]), np.asarray(b[:, mine]), rtol=0, atol=2e-5)


def test_chunk_attn_names_the_kernel_where_every_layer_kind_takes_it():
    """``chunk_attn`` is the program's own static test by name: "kernel"
    under a chosen kernel where BOTH head counts tile (the published 48 and 72
    over 8 K/V heads of 128 at every entry of the ladder), else "gather"."""
    assert [FAM.chunk_attn(k, 8) for k in ("", "interpret", "mosaic")] == ["gather", "kernel", "kernel"]
    assert FAM.chunk_attn("interpret", 1) == "gather"  # one query a slot is the step's kernel
    wide = dict(heads=48, kv_heads=8, head_dim=128, hidden=3072)
    full = md.moe_family(dataclasses.replace(CFG, heads_window=72, **wide))
    assert [full.chunk_attn("mosaic", c) for c in (16, 64, 256)] == ["kernel"] * 3 and full.chunk_attn("", 256) == "gather"
    odd = md.moe_family(dataclasses.replace(CFG, heads_window=9, heads=48, kv_heads=1, head_dim=128))
    assert odd.chunk_attn("mosaic", 2) == "gather" and odd.chunk_attn("mosaic", 16) == "kernel"  # 18 score rows


@pytest.mark.parametrize("layer,moves", [(0, False), (2, False), (3, True), (7, True)])
def test_rows_older_than_the_window(weights, layer, moves):
    params = weights[jnp.float32]
    ids = _ids(5)
    _, pool, pages = _serve(params, ids, chunks=(13, 13, 13))
    bt = np.zeros((3, CTX // PS), np.int32)
    bt[1] = pages
    x = jax.random.normal(jax.random.key(layer), (3, 1, CFG.hidden))
    pos = jnp.array([0, 39, 0], jnp.int32)

    def run(pool):
        return md._layer(CFG, layer, params["layers"][layer], x, pool, jnp.asarray(bt), pos, None,
                         jnp.ones((3, 1), bool))[0][1]

    before = run(pool)
    # positions 0..27 (pages 0..6 of the slot) are older than 39 - 8: overwrite them in this layer
    old = jnp.asarray(pages[:7])
    # the layer's planes are its page kind's half of the state tuple (full kind first), under its index there
    kind = (0, 1) if CFG.is_full(layer) else (2, 3)
    junk = tuple(a.at[CFG.plane_layer(layer), old].set(7.0) if i in kind else a for i, a in enumerate(pool))
    after = run(junk)
    assert bool(jnp.allclose(before, after, atol=1e-6)) is not moves


def test_window_table_covers_each_querys_window():
    """ceil((window + m) / page) + 1 pages, taken by position: the first
    query's oldest visible key and the last query's own key are inside."""
    bt = jnp.arange(3 * 64, dtype=jnp.int32).reshape(3, 64)
    for m in (1, 5, 16):
        pos = jnp.array([0, 37, 64 * PS - m], jnp.int32)
        got, k0 = md._window_table(bt, pos, m, PS, CFG.window)
        assert got.shape[1] == -(-(CFG.window + m) // PS) + 1
        assert bool(jnp.all(k0 <= jnp.maximum(pos - (CFG.window - 1), 0)))
        assert bool(jnp.all(k0 + got.shape[1] * PS > pos + m - 1))
        np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(bt[jnp.arange(3), k0 // PS]))


# (c) the grouped expert layer == a per-token loop; the masked form == the grouped


def _expert_weights(seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    e, d, f = CFG.experts, CFG.hidden, CFG.ffn
    return {
        "router": jax.random.normal(ks[0], (d, e)) * 0.5,
        "gate_up": jax.random.normal(ks[1], (e, d, 2 * f)) * 0.1,
        "down": jax.random.normal(ks[2], (e, f, d)) * 0.1,
    }


def _loop(p, x, gates, experts, valid):
    f = CFG.ffn
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(experts.shape[1]):
            if valid[t]:
                e = int(experts[t, j])
                h = np.asarray(x[t] @ p["gate_up"][e])
                out[t] += float(gates[t, j]) * np.asarray((jax.nn.silu(h[:f]) * h[f:]) @ p["down"][e])
    return out


@pytest.mark.parametrize("form", [moe.moe_experts_grouped, moe.moe_experts_masked], ids=["grouped", "masked"])
@pytest.mark.parametrize("routing", ["one_expert", "even", "routed"])
def test_expert_forms_equal_a_per_token_loop(form, routing):
    p = _expert_weights()
    t, k, e = 24, CFG.experts_per_tok, CFG.experts
    x = jax.random.normal(jax.random.key(7), (t, CFG.hidden))
    valid = jnp.arange(t) < 20  # four junk rows
    if routing == "routed":
        gates, experts = moe.route_topk(p["router"], x, k)
    else:
        gates = jnp.tile(jnp.array([[0.75, 0.25]], jnp.float32), (t, 1))
        pair = jnp.array([[3, 5]]) if routing == "one_expert" else None
        experts = jnp.tile(pair, (t, 1)) if pair is not None else (
            (2 * jnp.arange(t)[:, None] + jnp.arange(k)[None, :]) % e)
        experts = experts.astype(jnp.int32)
    y, counted = jax.jit(form)(p, x, gates, experts, valid)
    np.testing.assert_allclose(np.asarray(y), _loop(p, x, gates, experts, valid), atol=2e-6)
    assert not np.asarray(y[20:]).any()  # junk rows come back zero
    rows, hit, load_max = (int(c) for c in counted)
    assert rows == 20
    if routing == "one_expert":
        assert (hit, load_max) == (2, 20)  # no capacity: every row reached both experts
    if routing == "even":
        assert (hit, load_max) == (e, 20 * k // e)


@pytest.mark.parametrize("junk_rows", [0, 2, 12])
def test_grouped_form_visits_every_expert_where_junk_rows_allow(monkeypatch, junk_rows):
    """A chunk round's time must not follow the routing: each expert gets one
    junk assignment (gate zero) while junk assignments last, and the counters
    still see the real rows alone."""
    p = _expert_weights()
    t, k, e = 24, CFG.experts_per_tok, CFG.experts
    x = jax.random.normal(jax.random.key(9), (t, CFG.hidden))
    valid = jnp.arange(t) < t - junk_rows
    gates = jnp.tile(jnp.array([[0.75, 0.25]], jnp.float32), (t, 1))
    experts = jnp.tile(jnp.array([[3, 5]], jnp.int32), (t, 1))  # everyone picks the same two
    seen = []
    inner = moe._grouped_dot

    def spy(xs, w, sizes, out_dtype):
        seen.append(np.asarray(sizes))
        return inner(xs, w, sizes, out_dtype)

    monkeypatch.setattr(moe, "_grouped_dot", spy)
    y, counted = moe.moe_experts_grouped(p, x, gates, experts, valid)  # eager: the spy sees values
    padded = min(junk_rows * k, e)
    for sizes in seen:
        assert int(sizes.sum()) == (t - junk_rows) * k + padded
        assert set(np.flatnonzero(sizes)) == set(range(padded)) | {3, 5}
    assert [int(c) for c in counted] == [t - junk_rows, 2, t - junk_rows]
    np.testing.assert_allclose(np.asarray(y), _loop(p, x, gates, experts, valid), atol=2e-6)


def test_router_gates_sum_to_one_over_the_top_k():
    p = _expert_weights(3)
    x = jax.random.normal(jax.random.key(1), (50, CFG.hidden))
    gates, experts = moe.route_topk(p["router"], x, CFG.experts_per_tok)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-6)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    np.testing.assert_array_equal(np.asarray(experts[:, 0]), np.asarray(jnp.argmax(probs, -1)))
    assert gates.dtype == jnp.float32


# (d) frequencies against the closed forms


def _closed_form(d, theta, factor, orig, fast, slow):
    i = np.arange(d // 2)
    plain = theta ** (-2.0 * i / d)
    c = lambda n: d * math.log(orig / (2 * math.pi * n)) / (2 * math.log(theta))  # noqa: E731
    lo, hi = max(math.floor(c(fast)), 0), min(math.ceil(c(slow)), d - 1)
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    return plain, (1 - ramp) * plain + ramp * plain / factor


@pytest.mark.parametrize("sizes", ["small", "published"])
def test_rope_frequencies_against_the_closed_forms(ref, sizes):
    if sizes == "small":
        cfg, rope = CFG, PUBLISHED["rope_parameters"]
    else:
        rope = ref.published()["rope_parameters"]
        y = rope["full_attention"]
        cfg = md.MoEDecoderConfig(head_dim=128, rope_theta=y["rope_theta"], yarn_factor=y["factor"],
                                  yarn_original=y["original_max_position_embeddings"],
                                  yarn_beta_fast=y["beta_fast"], yarn_beta_slow=y["beta_slow"])
        assert y["attention_factor"] == pytest.approx(0.1 * math.log(16) + 1, abs=1e-15)
    plain, yarn = _closed_form(cfg.head_dim, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original,
                               cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    np.testing.assert_allclose(md.rope_inv_freq(cfg, False), plain, rtol=1e-6)
    np.testing.assert_allclose(md.rope_inv_freq(cfg, True), yarn, rtol=1e-6)
    # fast dimensions keep their frequency, slow ones are divided by the factor
    assert md.rope_inv_freq(cfg, True)[0] == pytest.approx(1.0)
    assert md.rope_inv_freq(cfg, True)[-1] == pytest.approx(plain[-1] / cfg.yarn_factor, rel=1e-6)
    assert cfg.attention_factor == pytest.approx(0.1 * math.log(cfg.yarn_factor) + 1)
    # the reference computes its own, from the published keys
    for kind, full in (("sliding_attention", False), ("full_attention", True)):
        freq, att = ref.inv_freq(rope[kind], cfg.head_dim)
        np.testing.assert_allclose(freq, md.rope_inv_freq(cfg, full), rtol=1e-6)
        assert att == pytest.approx(cfg.attention_factor if full else 1.0)


# (e) served through DecodeScheduler


SEQ, MAX_NEW = 24, 8


def _zoo(**kw):
    from seldon_core_tpu.models.zoo import get_model

    return get_model(
        "moe_decoder", vocab=96, hidden=64, layers=4, heads=4, kv_heads=2, head_dim=16, ffn=32, experts=8,
        experts_per_tok=2, window=8, period=4, yarn_factor=4.0, yarn_original=16, seq=SEQ,
        max_new_tokens=MAX_NEW, param_dtype="float32", seed=11, **kw,
    )


async def test_scheduler_serves_the_family_counts_real_rows_and_never_recompiles():
    ms = _zoo()
    sched = ds.DecodeScheduler(
        ms.params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4, prefix_slots=2, prefill_chunk=16,
        kv_page_size=PS, family=ms.generative["family"],
    )
    sched.warmup()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 96, (5, SEQ)).astype(np.int32)
    prompts[1:, :16] = prompts[0, :16]
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    first = await sched.submit(prompts[0], cache_prefix=16)
    np.testing.assert_array_equal(first, oracle[0])  # alone in 4 slots: 3 junk rows a step
    rest = await asyncio.gather(*(sched.submit(p) for p in prompts[1:]))
    for got, want in zip(rest, oracle[1:]):
        np.testing.assert_array_equal(got, want)  # prefix hits: the same greedy tokens
    assert sched.stat_prefix_hits == 4
    assert sched.recompiles_since_warmup() == 0
    frames = sched.flight.snapshot()
    # the three sliding layers' pages are a kind of their own (PR 47): given back past the window, all back at the end
    win = sched.pool.alloc.win
    assert sched.pool.windowed and win.live_pages == 0 and 0 < win.stat_released < win.stat_written
    assert sum(f.kv_win_released for f in frames) == win.stat_released and max(f.kv_win_live for f in frames) > 0
    sched.pool.alloc.check()
    layers, experts = 4, 8
    steps = [f for f in frames if f.busy_ns[0] == 0 and f.moe_rows]
    assert steps
    for f in steps:
        assert f.moe_rows == f.active  # junk rows (free slots) are not counted
        assert f.moe_rows <= f.moe_load_max <= f.moe_rows * layers
        assert layers * 2 <= f.moe_experts_hit <= layers * min(experts, 2 * f.moe_rows)
    lone = [f for f in steps if f.active == 1]
    assert lone and all(f.moe_load_max == layers for f in lone)
    chunked = [f for f in frames if f.busy_ns[0] > 0]
    assert chunked and all(f.moe_rows > 0 for f in chunked)
    assert sum(f.moe_rows for f in frames) >= 5 * MAX_NEW - 5 + SEQ + 4 * (SEQ - 16)
    assert "moe" in steps[0].to_dict()
    await sched.close()


# (f) the GPT-2 family is as it was; what the new family does not serve is refused by name


def test_gpt2_family_keeps_its_own_fused_programs():
    step, chunk = gpt2_family.fused_programs()
    assert step is _fused_step and chunk is _fused_chunk
    mstep, mchunk = FAM.fused_programs()
    assert (mstep.__name__, mchunk.__name__) == ("_fused_step", "_fused_chunk")  # one name in a trace
    assert md.moe_family(md.MoEDecoderConfig(**vars(CFG))).fused_programs() == (mstep, mchunk)


async def test_tiny_gpt_serves_bit_identical_tokens_through_the_family_seam():
    from seldon_core_tpu.models.decoder import generate

    params = init_decoder(seed=3, vocab=128, hidden=64, layers=2, ffn=128, max_len=64)
    ids = np.random.default_rng(2).integers(0, 128, (3, 8)).astype(np.int32)
    sched = ds.DecodeScheduler(params, seq_len=8, max_new_tokens=10, n_slots=2)
    assert sched.family is gpt2_family and sched._frame_counters == ()
    sched.warmup()
    outs = await asyncio.gather(*(sched.submit(r) for r in ids))
    np.testing.assert_array_equal(np.stack(outs), np.asarray(generate(params, jnp.asarray(ids), 10)))
    assert all(f.moe_rows == 0 for f in sched.flight.snapshot())
    await sched.close()


@pytest.mark.parametrize("dims_of", ["gpt2", "moe"])
def test_decoder_dims_say_the_pool_row_for_both_families(dims_of, weights):
    if dims_of == "gpt2":
        params = init_decoder(seed=0, vocab=64, hidden=128, layers=1, ffn=256, max_len=32)
        d = gpt2_family.decoder_dims(params)
        assert (d["kv_heads"], d["q_width"], d["ffn"]) == (d["heads"], 128, 256)
        pool = gpt2_family.paged_kv_init(params, 3, 4)
    else:
        d = FAM.decoder_dims(weights[jnp.float32])
        assert (d["heads"], d["kv_heads"], d["head_dim"], d["q_width"]) == (4, 2, 16, 64)
        pool = FAM.paged_kv_init(weights[jnp.float32], 3, 4)
    # the sliding layers' pages are a kind of their own: the full kind's planes hold the full layers
    assert pool[0].shape == (d["layers"] - d.get("kv_window_layers", 0), 3, 4, d["kv_heads"] * d["head_dim"])
    if dims_of == "moe":
        assert (d["kv_window_layers"], d["kv_window"], len(pool)) == (6, 8, 4) and pool[2].shape[0] == 6


@pytest.mark.parametrize("what", ["draft", "spec_tree", "tp", "tp_problems", "gpt2_dims", "moe_dims"])
def test_what_the_family_does_not_serve_is_refused_by_name(what, weights):
    params = weights[jnp.float32]
    kw = dict(seq_len=8, max_new_tokens=4, n_slots=2, family=FAM)
    with pytest.raises(FamilyNotServed):
        if what == "draft":
            draft = init_decoder(seed=0, vocab=96, hidden=64, layers=1, ffn=64, max_len=64)
            ds.DecodeScheduler(params, draft_params=draft, spec_k=2, **kw)
        elif what == "spec_tree":
            ds.DecodeScheduler(params, spec_tree="2,1", **kw)
        elif what == "tp":
            ds.DecodeScheduler(params, mesh_axes={"model": 2}, **kw)
        elif what == "tp_problems":
            from seldon_core_tpu.parallel.tp import decode_mesh_problems

            decode_mesh_problems({"model": 2}, params)
        elif what == "gpt2_dims":
            gpt2_family.decoder_dims(params)  # not a KeyError
        else:
            FAM.decoder_dims(init_decoder(seed=0, vocab=64, hidden=64, layers=1, ffn=64, max_len=32))


def test_heads_of_64_are_named_as_gpt2s_convention():
    with pytest.raises(ValueError, match="GPT-2's head_dim-64 convention"):
        init_decoder(hidden=200)


# (g) what PR 47 added to the block, piece by piece (the whole of it: tests/test_window_pages.py)


@pytest.mark.parametrize("share", [0.25, 0.5, 1.0])
def test_partial_rotary_turns_the_first_dimensions_and_passes_the_rest(share):
    cfg = md.MoEDecoderConfig(head_dim=16, rotary_full=share, rope_theta=50000.0, rope_theta_window=10000.0)
    rot = int(16 * share)
    full, plain = md.rope_inv_freq(cfg, True), md.rope_inv_freq(cfg, False)
    assert full.shape == (rot // 2,) and plain.shape == (8,)
    want_plain, want_yarn = _closed_form(rot, 50000.0, cfg.yarn_factor, cfg.yarn_original, cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    np.testing.assert_allclose(full, want_yarn, rtol=1e-6)  # the ramp runs over the dimensions that rotate
    np.testing.assert_allclose(plain, 10000.0 ** (-2.0 * np.arange(8) / 16), rtol=1e-6)  # the sliding layers' own theta
    x = jax.random.normal(jax.random.key(0), (2, 3, 4, 16))
    pos = jnp.array([[0, 1, 2], [7, 8, 9]], jnp.int32)
    y = md._rope(x, pos, full, 1.25)
    np.testing.assert_array_equal(np.asarray(y[..., rot:]), np.asarray(x[..., rot:]))  # untouched
    np.testing.assert_allclose(np.asarray(y[0, 0, :, :rot]), 1.25 * np.asarray(x[0, 0, :, :rot]), rtol=1e-6)  # position 0: cos 1
    assert not np.allclose(np.asarray(y[1, :, :, :rot]), 1.25 * np.asarray(x[1, :, :, :rot]))


@pytest.mark.parametrize("key, value, message", [
    ("heads_window", 5, "heads=5 not a multiple of kv_heads=2"),
    ("rotary_full", 0.3, "must be even"),
    ("dense_layers", 9, "dense_layers=9 of layers=4"),
    ("dense_layers", 1, "dense_ffn=0"),
    ("experts_held", 6, r"experts \[4, \+6\) of 8"),
])
def test_the_configuration_refuses_sizes_that_cannot_be(key, value, message):
    with pytest.raises(ValueError, match=message):
        md.MoEDecoderConfig(**{key: value, **({"first_expert": 4} if key == "experts_held" else {})})


@pytest.mark.parametrize("pattern, want", [
    (dict(layers=8, period=4), (False, [0, 1, 2, 0, 3, 4, 5, 1])),
    (dict(layers=8, period=4, full_first=True), (True, [0, 0, 1, 2, 1, 3, 4, 5])),
    (dict(layers=3, period=4), (False, [0, 1, 2])),  # sliding layers alone: one page kind, every layer its own index
    (dict(layers=4, period=1), (True, [0, 1, 2, 3])),  # full layers alone
], ids=["full_last", "full_first", "all_sliding", "all_full"])
def test_a_layer_indexes_its_page_kinds_planes(pattern, want):
    cfg = md.MoEDecoderConfig(**pattern)
    assert (cfg.is_full(0), [cfg.plane_layer(i) for i in range(cfg.layers)]) == want
    assert cfg.two_kinds == (0 < cfg.window_layers < cfg.layers)
