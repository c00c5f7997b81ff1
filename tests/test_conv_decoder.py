"""The short-convolution / attention family (models/conv_decoder.py) held to
its plain reference (benchmarks/reference/lfm2-24b-a2b.py) at a small size on
the CPU, with the published pattern: the first 8 entries of ``layer_types``
(conv, conv, attention, conv, conv, conv, attention, conv), the first 2
layers dense; hidden 64, 4 query / 2 key-value heads of 16, 16 experts of
which 4 are held (experts 4..7), top 4, pages of 4. Seeded random weights in
float32, LOGITS compared; every case counts on its own.

Tolerances. The served path and the reference compute the same float32
mathematics in another order (paged gather, chunk boundaries, masked
experts): logits of std ~0.2 agree to a few 1e-6; ``ATOL`` 2e-5 leaves that
five times of room and is a hundredth of what bfloat16 matrix products
would leave (2^-8 relative on every product: 1e-3 and more, as the bfloat16
case below shows against the same bar).
"""

import asyncio
import dataclasses
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness.correct import judge_generated  # noqa: E402

from seldon_core_tpu.models import conv_decoder as cd  # noqa: E402
from seldon_core_tpu.models.decoder import FamilyNotServed, require_served  # noqa: E402
from seldon_core_tpu.ops import moe  # noqa: E402
from seldon_core_tpu.serving import decode_scheduler as ds  # noqa: E402

ATTN = (2, 6)
TYPES = ["full_attention" if i in ATTN else "conv" for i in range(8)]
CFG = cd.ConvDecoderConfig(
    vocab=96, hidden=64, layers=8, attn_layers=ATTN, heads=4, kv_heads=2, head_dim=16, dense_layers=2,
    dense_ffn=96, ffn=32, experts=16, experts_held=4, first_expert=4, experts_per_tok=4,
)
# the same sizes under the published config's keys, for the reference
PUBLISHED = {
    "hidden_size": 64, "num_attention_heads": 4, "norm_eps": 1e-5, "layer_types": TYPES, "num_dense_layers": 2,
    "num_experts_per_tok": 4, "routed_scaling_factor": 1.0, "rope_parameters": {"rope_theta": 1000000.0},
    "share": {"first_expert": 4},
}
PS = 4  # page size
CTX = 40
FAM = cd.conv_family(CFG)
# state rows of the hand-driven cases: slots 0..2, one snapshot row, the zero row; 5 drops a write
SNAP, ZERO, DROP = 3, 4, 5
ATOL = 2e-5


def _load_ref():
    """The reference as a NEW module object: a case that swaps one of its
    helpers (the planted faults) traces what it swapped, and no other case
    sees it."""
    path = os.path.join(ROOT, "benchmarks", "reference", "lfm2-24b-a2b.py")
    spec = importlib.util.spec_from_file_location("bench_reference_lfm2_24b_a2b", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load_ref()


def _lively(params, seed=3):
    """The family's draw with what random weights at this width leave
    invisible made visible: every matrix four times larger (at hidden 64 a
    0.02 draw adds a tenth of the embedding a layer), and every norm's weight
    drawn round one (at exactly one a head's norm commutes with its rotation,
    and the order of the two could not be told)."""
    keys = iter(jax.random.split(jax.random.key(seed), 64))

    def leaf(path, a):
        name = path[-1].key
        if name in ("ln1", "ln2", "ln_f", "q_norm", "k_norm"):
            return (1.0 + 0.3 * jax.random.normal(next(keys), a.shape)).astype(a.dtype)
        return a if name in ("tok_emb", "router_bias", "conv_w") else a * 4

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def weights():
    f32 = _lively(cd.init_conv_decoder(CFG, seed=5, dtype=jnp.float32))
    return {jnp.float32: f32, jnp.bfloat16: jax.tree_util.tree_map(
        lambda a: a if a.shape == (CFG.experts,) else a.astype(jnp.bfloat16), f32)}


def _ref_logits(ref, params, ids, precision="highest", config=PUBLISHED):
    return np.asarray(
        ref.logits(params, np.asarray(ids)[None], 0, n_head=CFG.heads, precision=precision, config=config)
    )[0]


def _ids(seed=0, n=CTX):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(np.int32)


def _serve(
    params, ids, *, chunks, dtype=jnp.float32, start=None, snap_at=None, others=False, between=None, attn_kernel=""
):
    """Teacher-forced through the paged programs: chunked prefill of
    ``sum(chunks)`` tokens, then single-token steps along ``ids``; returns
    (logits [len(ids), vocab], pool, rec, pages). The sequence sits in slot 1
    of 3. ``start`` = (pool, rec, pages, n): the first n tokens' pages of an
    earlier run are MAPPED and its snapshot row read (a prefix hit), only the
    rest is computed. ``snap_at``: the chunk that ends there also writes the
    snapshot row. ``others``: slots 0 and 2 generate junk tokens in every
    step instead of riding masked. ``between(rec)``: what a case does to the
    state rows between two dispatches (a planted fault). ``attn_kernel``:
    what every dispatch is handed as ``decode_programs._step_attn_kernel``'s
    answer."""
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
            counts=jnp.array([0, c, 0], jnp.int32), state_rows=jnp.asarray(rows3), attn_kernel=attn_kernel,
        )
        out[pos : pos + c] = np.asarray(logits[1, :c])
        pos, read = pos + c, 1
        if between is not None:
            rec = between(rec)
    while pos < len(ids):
        logits, pool, rec, _ = FAM.paged_forward(
            params, pool, rec, jnp.asarray(bt), jnp.array([[7], [ids[pos]], [9]], jnp.int32),
            jnp.array([0, pos, 0], jnp.int32), rows=jnp.array([others, True, others]), attn_kernel=attn_kernel,
        )
        out[pos] = np.asarray(logits[1, 0])
        pos += 1
    return out, pool, rec, mine


@pytest.mark.parametrize("chunks", [(8, 8, 8, 8), (9, 2, 1, 6), (5, 5, 5, 5), (27,)], ids=["pages", "ragged", "by5", "one"])
def test_a_chunk_program_with_the_kernel_equals_the_gather_chunk(weights, small_chunk_kernel_blocks, chunks):
    """The prefill chunks through ops/gqa_decode.py's chunk kernel and the
    steps after them through its step kernel (the Pallas interpreter) give
    the gather path's logits to float32 rounding at every position and leave
    the same pool and state rows, whatever the chunks' lengths and wherever
    they start."""
    ids, params = _ids(), weights[jnp.float32]
    want, pool_g, rec_g, mine = _serve(params, ids, chunks=chunks)
    got, pool_k, rec_k, _ = _serve(params, ids, chunks=chunks, attn_kernel="interpret")
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * max(np.abs(want).max(), 1.0))
    for a, b in zip(pool_g, pool_k):
        np.testing.assert_allclose(np.asarray(a[:, mine]), np.asarray(b[:, mine]), rtol=0, atol=2e-5)
    for a, b in zip(rec_g, rec_k):
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]), rtol=0, atol=2e-5)


def test_chunk_attn_names_the_kernel_where_the_head_group_takes_it():
    """``chunk_attn`` is the program's own static test by name: "kernel"
    under a chosen kernel where the query block tiles (the published head
    counts at every entry of the ladder), else "gather"."""
    assert [FAM.chunk_attn(k, 8) for k in ("", "interpret", "mosaic")] == ["gather", "kernel", "kernel"]
    assert FAM.chunk_attn("interpret", 1) == "gather"  # one query a slot is the step's kernel
    full = cd.conv_family(dataclasses.replace(CFG, heads=32, kv_heads=8, head_dim=64))  # lfm2-24b-a2b
    assert [full.chunk_attn("mosaic", c) for c in (16, 64, 256)] == ["kernel"] * 3 and full.chunk_attn("", 256) == "gather"
    assert FAM.chunk_attn("mosaic", 2) == "gather"  # 8 score rows: not whole sublane tiles of a two-byte float


# (a) chunked prefill then decode through pages and state rows == the reference's full forward


@pytest.mark.parametrize(
    "chunks", [(1, 1, 1), (3, 3, 3, 3), (8, 8, 8), (27,), (9, 2, 1, 6)],
    ids=["by1", "by3", "by8", "whole", "inside_the_taps_reach"],
)
def test_cold_prefill_then_decode_equals_reference_float32(ref, weights, chunks):
    """The same prompt cut into chunks of 1, 3, 8, whole, and 2 and 1 tokens
    after a boundary (inside the filter's 3-token reach), then decoded a
    token a step: every position's logits are the reference's, so the two
    carried inputs cross every kind of boundary and the page holds the
    normed, rotated key."""
    ids, params = _ids(), weights[jnp.float32]
    got, _, _, _ = _serve(params, ids, chunks=chunks)
    np.testing.assert_allclose(got, _ref_logits(ref, params, ids), atol=ATOL)


def test_the_fused_fallback_generates_what_the_reference_predicts(ref, weights):
    params = weights[jnp.float32]
    ids = np.stack([_ids(4, 12), _ids(5, 12)])
    out = np.asarray(jax.jit(lambda p, x: FAM.generate(p, x, 6))(params, ids))
    for row in out:
        want = _ref_logits(ref, params, row)
        gap = [want[t - 1].max() - want[t - 1, row[t]] for t in range(12, 18)]
        assert max(gap) <= ATOL  # each generated token is the reference's best, to rounding


# (b) a prefix hit: pages mapped + snapshot row restored == the cold path


@pytest.mark.parametrize("shared", [8, 20])
def test_prefix_hit_from_a_snapshot_equals_reference_float32(ref, weights, shared):
    params = weights[jnp.float32]
    a, b = _ids(0), _ids(1)
    b[:shared] = a[:shared]
    _, pool, rec, pages = _serve(params, a, chunks=(shared, 6), snap_at=shared)
    got, _, _, _ = _serve(params, b, chunks=(5, 3), start=(pool, rec, pages, shared))
    np.testing.assert_allclose(got[shared:], _ref_logits(ref, params, b)[shared:], atol=ATOL)


# (c) the step leaves every state it was not asked to advance; short counts


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
    assert [int(counted[0]), int(counted[6])] == [9, 1]  # nine real rows, one row's state advanced
    mid = [np.asarray(r[1]) for r in rec]
    for t in range(3):
        _, pool, rec, counted = FAM.paged_forward(
            params, pool, rec, jnp.asarray(bt), jnp.array([[5], [0], [6]], jnp.int32),
            jnp.array([t, 9, t], jnp.int32), rows=jnp.array([True, False, True]),
        )
        assert int(counted[6]) == 2
    for before, after in zip(mid, rec):
        np.testing.assert_array_equal(before, np.asarray(after[1]))
    assert np.asarray(rec[0][0]).any() and np.asarray(rec[0][2]).any()  # the others did advance
    logits, pool, rec, _ = chunk(9, 11, 1)
    np.testing.assert_array_equal(np.asarray(logits[1, :11]), lone[9:20])
    for got, want in zip(rec, rec_lone):
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        assert not np.asarray(got[ZERO]).any()


def test_a_row_short_of_the_dispatchs_width_leaves_what_a_shorter_dispatch_would(weights):
    """5 real tokens in a dispatch 11 wide: the slot's pages, its state row
    and the snapshot row hold what a dispatch 5 wide leaves."""
    params = weights[jnp.float32]
    ids = _ids(6)
    pages = CTX // PS
    bt = np.zeros((3, pages), np.int32)
    bt[1] = 1 + np.arange(pages)

    def run(width):
        toks = np.full((3, width), 11, np.int32)  # what lies past the count is real-looking junk
        toks[1, :5] = ids[:5]
        rows3 = np.array([[ZERO, ZERO, ZERO], [DROP, 1, DROP], [DROP, SNAP, DROP]], np.int32)
        logits, pool, rec, _ = FAM.paged_forward(
            params, FAM.paged_kv_init(params, 1 + pages, PS, jnp.float32), FAM.state_init(params, DROP),
            jnp.asarray(bt), jnp.asarray(toks), jnp.zeros((3,), jnp.int32), counts=jnp.array([0, 5, 0], jnp.int32),
            state_rows=jnp.asarray(rows3),
        )
        return np.asarray(logits[1, :5]), [np.asarray(a[:, 1:]) for a in pool], [np.asarray(a) for a in rec]

    (la, pa, ra), (lb, pb, rb) = run(11), run(5)
    np.testing.assert_array_equal(la, lb)
    for got, want in zip(pa + ra, pb + rb):
        np.testing.assert_array_equal(got, want)
    assert ra[0][1].any() and (ra[0][1] == ra[0][SNAP]).all() and not ra[0][0].any()


# (d) the share adds up, and the bias chooses without weighing


def _expert_layer(seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    return {
        "router": jax.random.normal(k[0], (CFG.hidden, 16)) * 0.3,
        "router_bias": jax.random.normal(k[1], (16,)) * cd.EXPERT_BIAS_STD,
        "gate_up": jax.random.normal(k[2], (16, CFG.hidden, 2 * CFG.ffn)) * 0.1,
        "down": jax.random.normal(k[3], (16, CFG.ffn, CFG.hidden)) * 0.1,
    }


def _share(p, first, held):
    return {**p, "gate_up": p["gate_up"][first : first + held], "down": p["down"][first : first + held]}


def test_the_sixteen_experts_four_shares_sum_to_the_uncut_layer(ref):
    """Four chips of four experts each: their routed parts (a pick that lands
    on an absent expert adds nothing, gates over all four picks) with the
    residual and the norm counted ONCE equal the reference's uncut layer;
    and share by share, the reference given the same share."""
    p = {"ln2": 1.0 + 0.3 * jax.random.normal(jax.random.key(1), (CFG.hidden,)), "moe": _expert_layer()}
    x = jax.random.normal(jax.random.key(8), (24, CFG.hidden))
    uncut = ref._experts(p, x, first_expert=0, top_k=4, scale=1.0, eps=1e-5, act="float32")
    n2 = ref._rms(p["ln2"], x, 1e-5, jnp.float32)
    gates, experts = moe.route_sigmoid_biased(p["moe"]["router"], p["moe"]["router_bias"], n2, 4, 1.0)
    parts = [moe.moe_held_ffn(_share(p["moe"], 4 * s, 4), n2, gates, experts, 4 * s) for s in range(4)]
    np.testing.assert_allclose(np.asarray(x + sum(y for y, _ in parts)), np.asarray(uncut), atol=5e-6)
    assert sum(int(c[3]) for _, c in parts) == 24 * 4  # every pick landed on exactly one chip
    for s, (y, _) in enumerate(parts):
        want = ref.expert_ffn(_share(p["moe"], 4 * s, 4), n2, first_expert=4 * s, top_k=4, scale=1.0, act="float32")
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=5e-6)


def test_the_bias_chooses_and_does_not_weigh():
    """With the bias drawn as the family draws it some rows' picks differ
    from top4(scores), every expert is picked by some row and by no means
    all, and a pick's gate is its UNBIASED score over the picks' sum + 1e-6."""
    p = _expert_layer(2)
    n2 = jax.random.normal(jax.random.key(3), (256, CFG.hidden))
    gates, experts = (np.asarray(a) for a in moe.route_sigmoid_biased(p["router"], p["router_bias"], n2, 4, 1.0))
    s = np.asarray(jax.nn.sigmoid(n2 @ p["router"]))
    plain = np.argsort(-s, axis=1, kind="stable")[:, :4]
    differ = [set(a) != set(b) for a, b in zip(experts.tolist(), plain.tolist())]
    assert 0 < sum(differ) < 256
    biased = np.argsort(-(s + np.asarray(p["router_bias"])), axis=1, kind="stable")[:, :4]
    np.testing.assert_array_equal(experts, biased)
    picked = np.take_along_axis(s, experts, axis=1)
    np.testing.assert_allclose(gates, picked / (picked.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
    load = np.bincount(experts.reshape(-1), minlength=16)
    assert load.min() > 0 and load.max() < 256


# (e) planted faults: each one line, each must move the logits far past the tolerance



FAULTS = {
    # in the reference (a fresh module object a case): what the program computes must NOT equal these
    "no_qk_norm": lambda r: setattr(r, "_normed_rotated", lambda w, x, f, eps, act: r._rope(x, f)),
    "norm_after_the_rotation": lambda r: setattr(
        r, "_normed_rotated", lambda w, x, f, eps, act: r._rms(w, r._rope(x, f), eps, act)),
    "key_paged_before_its_norm": lambda r: setattr(r, "_keys", lambda w, x, f, eps, act: r._rope(x, f)),
    "bias_in_the_gate_weights": lambda r: setattr(r, "_pick_weights", lambda s, b: s + b),
    "taps_reversed": lambda r: setattr(r, "_short_conv", (lambda orig: lambda z, w: orig(z, w[::-1]))(r._short_conv)),
    "c_gates_before_the_filter": lambda r: setattr(r, "_gated_conv", lambda b, c, x, w: r._short_conv(c * b * x, w)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_in_the_mathematics_fails(weights, fault):
    params, ids = weights[jnp.float32], _ids()
    got, _, _, _ = _serve(params, ids, chunks=(9, 2, 1, 6))
    faulty = _load_ref()
    FAULTS[fault](faulty)
    assert np.abs(got - _ref_logits(faulty, params, ids)).max() > 100 * ATOL


def test_the_oldest_input_not_carried_across_a_chunk_boundary_fails(ref, weights):
    """z_{t-2} zeroed in every state row between dispatches: the positions
    just after each boundary move far past the tolerance."""
    params, ids = weights[jnp.float32], _ids()
    drop_oldest = lambda rec: tuple(a.at[:, : CFG.hidden].set(0.0) for a in rec)  # noqa: E731
    got, _, _, _ = _serve(params, ids, chunks=(9, 2, 1, 6), between=drop_oldest)
    assert np.abs(got - _ref_logits(ref, params, ids)).max() > 100 * ATOL


def test_the_gates_epsilon_dropped_is_allowed_to_pass(weights):
    """1e-6 beside a sum of four sigmoid scores (about 2) moves a gate by
    5e-7 of itself: under float32 rounding, so this comparison cannot see
    it, and says so."""
    params, ids = weights[jnp.float32], _ids()
    got, _, _, _ = _serve(params, ids, chunks=(27,))
    faulty = _load_ref()
    faulty.GATE_EPS = 0.0
    np.testing.assert_allclose(got, _ref_logits(faulty, params, ids), atol=ATOL)


def test_bfloat16_matrix_products_would_fail_the_float32_tolerance(ref, weights):
    """The bar is tight enough: the same path served in bfloat16 misses it by
    two orders, and stays inside the harness's own rule (twice the
    reference's bfloat16 rounding)."""
    params, ids, first = weights[jnp.bfloat16], _ids(2), 23
    got, _, _, _ = _serve(params, ids, chunks=(12, 12), dtype=jnp.bfloat16)
    assert np.abs(got - _ref_logits(ref, params, ids)).max() > 100 * ATOL
    served = list(ids[: first + 1])
    while len(served) < CTX:
        got, _, _, _ = _serve(params, np.asarray(served, np.int32), chunks=(12, 12), dtype=jnp.bfloat16)
        served.append(int(got[len(served) - 1].argmax()))
    exact, noisy = (_ref_logits(ref, params, served, p)[None, first:] for p in ("highest", "default"))
    verdict = judge_generated([served], exact, noisy, first)
    assert verdict["ok"], verdict


# (f) served through DecodeScheduler

SEQ, MAX_NEW = 24, 8


def _zoo(**kw):
    from seldon_core_tpu.models.zoo import get_model

    ms = get_model(
        "conv_decoder", vocab=96, hidden=64, layers=8, attn_layers="2,6", heads=4, kv_heads=2, head_dim=16,
        dense_layers=2, dense_ffn=96, ffn=32, experts=16, experts_held=4, first_expert=4, experts_per_tok=4,
        seq=SEQ, max_new_tokens=MAX_NEW, param_dtype="float32", seed=11, **kw,
    )
    ms.params.update(_lively(ms.params))
    return ms


def _sched(ms, **kw):
    kw = {"n_slots": 4, "prefix_slots": 2, "prefill_chunk": 16, "kv_page_size": PS, **kw}
    return ds.DecodeScheduler(
        ms.params, seq_len=SEQ, max_new_tokens=MAX_NEW, family=ms.generative["family"], **kw
    )


async def test_scheduler_serves_the_family_restores_snapshots_and_never_recompiles():
    ms = _zoo()
    sched = _sched(ms)
    assert (sched.programs._counted, sched.programs._stateful, sched.programs.attn_kernel) == (8, True, "")
    assert len(sched.pool.state) == 2 and sched.pool.state[0].shape[0] == 2  # planes for the attention layers only
    assert len(sched.pool.recurrent) == 6 and sched.pool.recurrent[0].shape == (4 + 2 + 1, 2 * 64)
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
    assert sched.recompiles_since_warmup() == 0
    frames = sched.flight.snapshot()
    assert sum(f.state_restores for f in frames) == 5 and sum(f.state_captures for f in frames) == 1
    steps = [f for f in frames if f.busy_ns[0] == 0 and f.conv_rows]
    assert steps and all(f.conv_rows == f.active == f.moe_rows for f in steps)  # junk rows are not counted
    assert all(f.ssm_rows == 0 and f.mla_ctx_rows == 0 for f in frames)
    assert any(f.to_dict().get("conv", [0, 0, 0])[1] for f in frames)  # the restores reach the frame's dict
    assert 0 < sum(f.moe_local_picks for f in frames) < sum(f.moe_rows for f in frames) * 4 * 6
    sched.pool.alloc.check()
    assert not any(np.asarray(a[sched.pool.zero_row]).any() for a in sched.pool.recurrent)
    await sched.close()


async def test_interleaved_admissions_over_four_slots_generate_the_fallbacks_tokens():
    """Chunks of 4 over a 24-token prompt, eight requests over four slots,
    admitted while others decode and prefill: each gets the tokens the fused
    fallback ``generate`` gives it alone."""
    ms = _zoo()
    sched = _sched(ms, prefill_chunk=4, prefix_slots=0)
    sched.warmup()
    prompts = np.random.default_rng(2).integers(0, 96, (8, SEQ)).astype(np.int32)
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    first = asyncio.ensure_future(sched.submit(prompts[0]))
    await asyncio.sleep(0)
    outs = await asyncio.gather(first, *(sched.submit(p) for p in prompts[1:]))
    for got, want in zip(outs, oracle):
        np.testing.assert_array_equal(got, want)
    assert any(f.prefilling and f.mode == "plain" and f.tokens for f in sched.flight.snapshot())
    assert sched.recompiles_since_warmup() == 0
    await sched.close()


@pytest.mark.parametrize("what, kw", [
    ("speculation", {"spec_tree": "2,1"}), ("decode_mesh", {"mesh_axes": {"model": 2}}),
    ("kv_int8", {"kv_dtype": "int8"}),
])
def test_what_the_family_does_not_serve_is_refused_by_name(what, kw, weights):
    assert FAM.serves == frozenset({"attn_kernel"}) and FAM.name == "conv"
    with pytest.raises(FamilyNotServed, match="'conv' decoder family"):
        require_served(FAM, what)
    with pytest.raises(FamilyNotServed, match="'conv' decoder family"):
        ds.DecodeScheduler(weights[jnp.float32], seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4, family=FAM, **kw)


def test_another_familys_parameters_are_refused(weights):
    from seldon_core_tpu.models import hybrid_decoder as hd

    other = hd.init_hybrid_decoder(hd.HybridDecoderConfig(vocab=96), seed=0, dtype=jnp.float32)
    with pytest.raises(FamilyNotServed):
        FAM.decoder_dims(other)
    with pytest.raises(FamilyNotServed):
        hd.hybrid_family(hd.HybridDecoderConfig(vocab=96)).decoder_dims(weights[jnp.float32])
    dims = FAM.decoder_dims(weights[jnp.float32])
    assert (dims["layers"], dims["kv_layers"], dims["kv_heads"], dims["head_dim"]) == (8, 2, 2, 16)
