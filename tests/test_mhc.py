"""The multi-stream residual path of the latent-attention family
(ops/mhc.py; models/mla_decoder.py with ``hc_mult`` 4 and the bias-selected
gate) held to its plain reference (benchmarks/reference/xing4.0-29b-a4b.py)
at a small size on the CPU: hidden 64 in 4 streams (maps over 256 numbers),
4 heads over a 16-wide latent, a leading dense layer then two expert layers
of a shared expert + top 4 of 16 bias-selected sigmoid experts, of which this
chip holds 4 from the fifth; pages of 4; YaRN factor 64 over an original
context of 16; 20 Sinkhorn iterations. Seeded random weights; every case
counts on its own.
"""

import asyncio
import dataclasses
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

from seldon_core_tpu.models import mla_decoder as mla  # noqa: E402
from seldon_core_tpu.ops import mhc, moe  # noqa: E402
from seldon_core_tpu.serving import decode_scheduler as ds  # noqa: E402
from tests.test_mla_decoder import CTX, PS, SIZES as K1_SIZES, _ids, _serve  # noqa: E402

SIZES = {**K1_SIZES, "n_group": 0, "topk_group": 0, "gate_bias": True, "routed_scale": 2.0, "yarn_factor": 64.0,
         "hc_mult": 4}
CFG = mla.MLADecoderConfig(**SIZES, experts_held=4, first_expert=4)  # one chip's share: experts 4..7
PUBLISHED = {
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8, "kv_lora_rank": 16, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "first_k_dense_replace": 1, "n_group": 1, "topk_group": 1, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.0, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "share": {"first_expert": 4},
}
HC = dict(iters=20, hc_eps=1e-6, clamp=30.0, eps=1e-6)


@pytest.fixture(scope="module")
def ref():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return cells.load_module(ROOT, json.load(f), "reference", "xing4.0-29b-a4b")


@pytest.fixture(scope="module")
def weights():
    return {d: mla.init_mla_decoder(CFG, seed=5, dtype=d) for d in (jnp.float32, jnp.bfloat16)}


def _ref_logits(ref, params, ids, precision, config=PUBLISHED):
    return np.asarray(
        ref.logits(params, np.asarray(ids)[None], 0, n_head=CFG.heads, precision=precision, config=config)
    )[0]


# (a) chunked prefill then decode through the latent pages == the reference's full forward


@pytest.mark.parametrize("chunks", [(24,), (12, 12), (6, 6, 6, 6), (16, 1, 7)], ids=["one", "two", "four", "ladder"])
def test_cold_prefill_then_decode_equals_reference_float32(ref, weights, chunks):
    """The prompt in 1, 2 and 4 chunks (absorbed and expanded attention, both
    pool-write forms), then steps: every position's LOGITS, to 1e-5. The
    state between blocks is four streams wide in every program."""
    params, ids = weights[jnp.float32], _ids(1)
    got, _, _ = _serve(params, ids, chunks=chunks, cfg=CFG)
    np.testing.assert_allclose(got, _ref_logits(ref, params, ids, "highest"), atol=1e-5, rtol=0)


@pytest.mark.parametrize("shared,chunks", [(8, (3, 3)), (16, (20,))])
def test_prefix_hit_equals_reference_float32(ref, weights, shared, chunks):
    """A second sequence maps the first one's latent pages: the cache row is
    the latent family's, the four streams are activations and leave nothing
    in it, so a hit needs nothing new."""
    params = weights[jnp.float32]
    first, second = _ids(2), _ids(3)
    second[:shared] = first[:shared]
    _, pool, pages = _serve(params, first, chunks=(10, 10), cfg=CFG)
    got, _, _ = _serve(params, second, chunks=chunks, prefix_from=(pool, pages, shared), cfg=CFG)
    want = _ref_logits(ref, params, second, "highest")
    np.testing.assert_allclose(got[shared:], want[shared:], atol=1e-5, rtol=0)


def test_bfloat16_serving_within_the_harness_delta(ref, weights):
    """The harness's rule at the small size: along greedy tokens served in
    bfloat16 (a bfloat16 state, float32 maps), the reference's exact logit of
    each served token trails its best by at most twice the rounding delta."""
    params, ids, first = weights[jnp.bfloat16], _ids(4), 23
    _serve(params, ids, chunks=(12, 12), dtype=jnp.bfloat16, greedy_after=first, cfg=CFG)
    exact, noisy = (_ref_logits(ref, params, ids, p)[None, first:] for p in ("highest", "default"))
    verdict = judge_generated([ids.tolist()], exact, noisy, first)
    assert verdict["ok"], verdict
    assert verdict["rounding_delta"] > 1e-4  # a bfloat16 state does round


# (b) what the comparison sees: five planted faults, each outside it


def _one_sinkhorn_iteration(monkeypatch):
    return dataclasses.replace(CFG, hc_sinkhorn_iters=1)


def _post_without_its_factor(monkeypatch):
    real = mhc.stream_maps

    def halved(*a, **kw):
        h_pre, h_post, h_res = real(*a, **kw)
        return h_pre, 0.5 * h_post, h_res

    monkeypatch.setattr(mhc, "stream_maps", halved)
    return CFG


def _res_is_the_identity(monkeypatch):
    real = mhc.stream_maps

    def identity(*a, **kw):
        h_pre, h_post, h_res = real(*a, **kw)
        return h_pre, h_post, jnp.broadcast_to(jnp.eye(h_res.shape[-1], dtype=h_res.dtype), h_res.shape)

    monkeypatch.setattr(mhc, "stream_maps", identity)
    return CFG


def _streams_averaged_at_the_exit(monkeypatch):
    real = mhc.merged
    monkeypatch.setattr(mhc, "merged", lambda x: real(x) / x.shape[0])
    return CFG


def _the_gates_bias_weighs(monkeypatch):
    def weighing(router_w, bias, x, k, scale):
        s = jax.nn.sigmoid(x.astype(jnp.float32) @ router_w.astype(jnp.float32)) + bias
        top_s, top_e = jax.lax.top_k(s, k)
        return top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-6) * scale, top_e.astype(jnp.int32)

    monkeypatch.setattr(mla, "route_sigmoid_biased", weighing)
    return CFG


def _loud(params):
    """The same weights with every block's output projection times 60: at std
    0.02 and hidden 64 a block adds a twentieth of the embedding (std 1) that
    all four streams start as, the streams stay one another's copies, and a
    doubly stochastic mix of copies is the copy whatever the mix; at 60 times
    that the streams part within a layer (the published depth and widths do
    it over 40 blocks)."""
    def one(p):
        p = {**p, "attn_o": p["attn_o"] * 60}
        if "mlp" in p:
            return {**p, "mlp": {**p["mlp"], "down": p["mlp"]["down"] * 60}}
        return {**p, "moe": {**p["moe"], "down": p["moe"]["down"] * 60, "shared_down": p["moe"]["shared_down"] * 60}}

    return {**params, "layers": [one(p) for p in params["layers"]]}


FAULTS = [_one_sinkhorn_iteration, _post_without_its_factor, _res_is_the_identity, _streams_averaged_at_the_exit,
          _the_gates_bias_weighs]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__[1:])
def test_a_planted_fault_fails_the_comparison(ref, weights, monkeypatch, fault):
    """One Sinkhorn iteration instead of 20, H_post without its factor 2,
    H_res replaced by the identity, the gate's bias weighing: each moves
    logits a hundred times past the 1e-5 of the cases above. The streams
    averaged instead of summed at the exit do NOT: the final norm divides
    any scale out (the logits move by 4e-7, its epsilon's share), so no
    comparison of logits can see it, the harness's included. What it moves
    is ``hidden``, which the programs hand the feature head: that is held to
    the reference's summed streams instead."""
    params, ids = _loud(weights[jnp.float32]), _ids(1)
    want = _ref_logits(ref, params, ids, "highest")
    np.testing.assert_allclose(_serve(params, ids, chunks=(8, 16), cfg=CFG)[0], want, atol=1e-5, rtol=0)
    cfg = fault(monkeypatch)
    if fault is not _streams_averaged_at_the_exit:
        got, _, _ = _serve(params, ids, chunks=(8, 16), cfg=cfg, tag=fault.__name__)
        assert np.abs(got - want).max() > 1e-3
        return
    with jax.default_matmul_precision("highest"):
        top = np.asarray(ref.hidden(params, ids, n_head=CFG.heads, act="float32", cfg=PUBLISHED))

    def served():
        pool = mla.mla_family(CFG).paged_kv_init(params, 1 + CTX // PS, PS, jnp.float32)
        bt = 1 + jnp.arange(CTX // PS, dtype=jnp.int32)[None]
        return np.asarray(mla._forward(CFG, params, pool, bt, jnp.asarray(ids)[None], jnp.zeros((1,), jnp.int32))[1][0])

    monkeypatch.undo()
    np.testing.assert_allclose(served(), top, atol=2e-5)  # [n, m, hidden]: the summed streams
    fault(monkeypatch)
    assert np.abs(served() - top).max() > 1.0


# (c) Sinkhorn-Knopp, the maps


def _logits(case, n=512):
    z = np.asarray(jax.random.normal(jax.random.key(7), (n, 4, 4)), np.float32)
    if case == "seeded":  # the family's own: std 0.24 round a diagonal of 1.5
        return 0.24 * z + mla.HC_RES_DIAG * np.eye(4, dtype=np.float32)
    if case == "wide":
        return 1.0 * z
    if case == "at_the_clamp":  # +30 on the diagonal, -30 on an off-diagonal entry, the rest seeded
        out = 0.24 * z + 30.0 * np.eye(4, dtype=np.float32)
        out[:, 0, 3] = -30.0
        return out
    out = 0.24 * z  # "permuted": +30 along a cyclic shift, -30 on the diagonal
    out += 30.0 * np.roll(np.eye(4, dtype=np.float32), 1, axis=1) - 30.0 * np.eye(4, dtype=np.float32)
    return out


@pytest.mark.parametrize("case", ["seeded", "wide", "at_the_clamp", "permuted"])
def test_sinkhorn_is_doubly_stochastic_and_the_references_literal_loop(ref, case):
    logits = jnp.clip(jnp.asarray(_logits(case)), -30.0, 30.0)
    got = np.asarray(jax.jit(lambda l: mhc.sinkhorn_plain(l, 20, 1e-6))(logits))
    tol = 1e-5 if case != "wide" else 2e-2  # logits of std 1: 20 iterations have not converged on every map
    assert np.abs(got.sum(-1) - 1).max() < tol and np.abs(got.sum(-2) - 1).max() < tol
    assert (got >= 0).all()
    np.testing.assert_allclose(got, np.asarray(ref.sinkhorn_knopp(logits, 20, 1e-6)), rtol=1e-5, atol=1e-30)
    ppm = int(mhc.doubly_stochastic_residual(jnp.asarray(got)))
    assert ppm == round(1e6 * max(np.abs(got.sum(-1) - 1).max(), np.abs(got.sum(-2) - 1).max()))
    if case == "seeded":
        assert ppm < 10  # what the frames' canary reads in float32
        half = jax.jit(lambda l: mhc.sinkhorn_plain(l.astype(jnp.bfloat16), 20, 1e-6))(logits)
        assert int(mhc.doubly_stochastic_residual(half.astype(jnp.float32))) > 1000  # and in bfloat16


@pytest.mark.parametrize("shape,groups", [((64, 1), 32), ((3, 5), 32), ((1000,), 32), ((700,), 2), ((5,), 1)],
                         ids=["step", "small_chunk", "padded_rows", "three_blocks", "one_group"])
def test_the_sinkhorn_kernel_equals_the_plain_loop(monkeypatch, shape, groups):
    """The Pallas form under the interpreter: rows-minor tiles, rows padded
    to whole 128-lane groups and whole blocks, a grid over the blocks: the
    plain loop's numbers to float32 rounding, the seeded maps and the clamped
    ones alike."""
    monkeypatch.setattr(mhc, "SINKHORN_BLOCK_GROUPS", groups)
    z = jax.random.normal(jax.random.key(11), (*shape, 4, 4))
    for logits in (0.24 * z + mla.HC_RES_DIAG * jnp.eye(4), jnp.clip(12.0 * z, -30.0, 30.0)):
        want = mhc.sinkhorn_plain(logits, 20, 1e-6)
        got = jax.jit(lambda l: mhc.sinkhorn_kernel(l, 20, 1e-6, interpret=True))(logits)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-30)
    assert mhc.sinkhorn_tiles(4) and not mhc.sinkhorn_tiles(16)


def test_the_backend_chooses_the_sinkhorn_form_and_the_served_logits_do_not_move(ref, weights, monkeypatch):
    """``sinkhorn`` takes the kernel where ``_sinkhorn_mode`` names one (a TPU:
    "mosaic"; here "interpret") and the plain loop elsewhere: prefill and
    steps through the kernel are the reference's logits like the plain form's."""
    assert mhc._sinkhorn_mode() == ""  # the CPU backend: the plain loop
    calls = []
    real = mhc.sinkhorn_kernel
    monkeypatch.setattr(mhc, "sinkhorn_kernel", lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    monkeypatch.setattr(mhc, "_sinkhorn_mode", lambda: "interpret")
    params, ids = weights[jnp.float32], _ids(1)
    got, _, _ = _serve(params, ids, chunks=(16, 8), cfg=CFG, tag="sinkhorn_kernel")
    assert calls and all(kw == {"interpret": True} for kw in calls)
    np.testing.assert_allclose(got, _ref_logits(ref, params, ids, "highest"), atol=1e-5, rtol=0)


def test_the_maps_equal_the_references_and_differ_between_tokens(ref, weights):
    """The seeded parameters let the dynamic part decide: over the tokens of
    a prompt H_pre, H_post and H_res move by far more than the comparison's
    tolerance, while staying the reference's to float32 rounding."""
    params = weights[jnp.float32]
    maps = params["layers"][1]["hc_mlp"]
    x = mhc.spread(params["tok_emb"][_ids(6)], 4) + 0.5 * jax.random.normal(jax.random.key(2), (4, CTX, CFG.hidden))
    got = mhc.stream_maps(maps, x, iters=20, eps=1e-6, clamp=30.0, rms_eps=1e-6)
    want = ref.stream_maps(maps, jnp.moveaxis(x, 0, 1), **HC)  # the program keeps the streams major, the reference a token's
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-6)
    h_pre, h_post, h_res = (np.asarray(g) for g in got)
    assert h_pre.std(0).min() > 0.05 and h_post.std(0).min() > 0.1 and h_res.std(0).min() > 0.01
    assert 0.4 < h_res[:, np.arange(4), np.arange(4)].mean() < 0.8  # the diagonal is favoured, not absolute
    assert h_post.max() > 1.0  # twice a sigmoid


def test_the_mixes_equal_the_references(ref, weights):
    params = weights[jnp.float32]
    maps = params["layers"][0]["hc_attn"]
    x = jax.random.normal(jax.random.key(3), (4, CTX, CFG.hidden))
    block = lambda u: jnp.tanh(u) * 3.0  # noqa: E731 - any block
    h_pre, h_post, h_res = mhc.stream_maps(maps, x, iters=20, eps=1e-6, clamp=30.0, rms_eps=1e-6)
    u = mhc.pre_mix(x, h_pre)
    got = mhc.post_mix(x, block(u), h_post, h_res)
    want = ref.through_streams(maps, jnp.moveaxis(x, 0, 1), block, act=jnp.float32, **HC)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(got, 0, 1)), np.asarray(want), atol=1e-5)


def test_forty_blocks_do_not_average_the_streams_into_one(weights):
    """After 80 applications of a layer's maps to a seeded state the four
    streams still differ from their mean by some hundredths of their size
    (0.05; with H_res the identity it would be 0.3, with a uniform H_res 0.03):
    H_post writes each stream its own share of every block's output and the
    favoured diagonal keeps part of it."""
    params = weights[jnp.float32]
    maps = params["layers"][2]["hc_mlp"]
    x = mhc.spread(params["tok_emb"][_ids(6)], 4)
    for b in range(80):
        h_pre, h_post, h_res = mhc.stream_maps(maps, x, iters=20, eps=1e-6, clamp=30.0, rms_eps=1e-6)
        # a stand-in block: outputs a third of the embedding's size, one block's unlike the next's
        o = jax.random.normal(jax.random.key(100 + b), (CTX, CFG.hidden)) / 3
        x = mhc.post_mix(x, o, h_post, h_res)
    spread = jnp.linalg.norm(x - x.mean(0, keepdims=True)) / jnp.linalg.norm(x)
    assert float(spread) > 0.03


# (d) the share


def _expert_block(seed=0):
    """(maps, the expert layer's parameters with all 16 experts, ln2) at the small size."""
    ks = jax.random.split(jax.random.key(seed), 7)
    d, f = CFG.hidden, CFG.ffn
    moe_p = {
        "router": jax.random.normal(ks[0], (d, CFG.experts)) * 0.5,
        "router_bias": jax.random.normal(ks[5], (CFG.experts,)) * 0.05,
        "gate_up": jax.random.normal(ks[1], (16, d, 2 * f)) * 0.1,
        "down": jax.random.normal(ks[2], (16, f, d)) * 0.1,
        "shared_gate_up": jax.random.normal(ks[3], (d, 2 * f)) * 0.1,
        "shared_down": jax.random.normal(ks[4], (f, d)) * 0.1,
    }
    maps = mhc.init_maps(ks[6], 4, d, jnp.float32, logit_std=mla.HC_LOGIT_STD, res_diag=mla.HC_RES_DIAG)
    return maps, moe_p, jnp.ones((d,), jnp.float32)


def test_the_four_shares_of_an_expert_blocks_new_state_sum_to_the_uncut_block(ref):
    """Four chips of four experts each (eight of eight at the published
    size): every chip computes the same maps and H_res X; its routed part
    goes through H_post like the rest. The shares' routed parts through
    H_post, plus H_res X and the shared expert's H_post . o counted ONCE,
    are the uncut reference's X'."""
    maps, moe_p, ln2 = _expert_block()
    x = jax.random.normal(jax.random.key(9), (4, 24, CFG.hidden))
    layer = {"moe": moe_p, "ln2": ln2}
    route = dict(top_k=4, scale=2.0, eps=1e-6, act=jnp.float32)
    uncut = ref.through_streams(maps, jnp.moveaxis(x, 0, 1), lambda u: ref.expert_ffn(layer, u, first_expert=0, **route),
                                act=jnp.float32, **HC)
    h_pre, h_post, h_res = mhc.stream_maps(maps, x, iters=20, eps=1e-6, clamp=30.0, rms_eps=1e-6)
    u = mhc.pre_mix(x, h_pre)
    n2 = mla._rms(ln2, u, 1e-6)
    gates, experts = moe.route_sigmoid_biased(moe_p["router"], moe_p["router_bias"], n2, 4, 2.0)
    zero = jnp.zeros_like(x)
    total, picks = mhc.post_mix(x, jnp.zeros_like(u), h_post, h_res), 0  # H_res X, once
    total = total + mhc.post_mix(zero, moe.gated_mlp(moe_p["shared_gate_up"], moe_p["shared_down"], n2), h_post, h_res)
    for s in range(4):
        share = {**moe_p, "gate_up": moe_p["gate_up"][4 * s : 4 * s + 4], "down": moe_p["down"][4 * s : 4 * s + 4]}
        y, counted = moe.moe_held_ffn(share, n2, gates, experts, 4 * s)
        total = total + mhc.post_mix(zero, y, h_post, h_res)  # a share's routed part alone, through H_post
        picks += int(counted[3])
        want = ref.expert_ffn({"moe": share, "ln2": ln2}, u, first_expert=4 * s, shared=False, **route)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=5e-6)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(total, 0, 1)), np.asarray(uncut), atol=2e-5)
    assert picks == 24 * 4  # every pick landed on exactly one chip


# (e) one family: hc_mult 1 and the grouped gate are a.x-k1's programs


def test_one_stream_and_the_grouped_gate_are_the_family_as_it_was():
    """``hc_mult`` 1 draws no map and traces no stream axis: whatever the
    Sinkhorn keys say, the weights are the same draws and the logits the same
    bits as the configuration that never named them, and they are the a.x-k1
    reference's (tests/test_decode_programs.py holds the lowered text)."""
    from tests.test_mla_decoder import CFG as K1, PUBLISHED as K1_PUBLISHED

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        k1_ref = cells.load_module(ROOT, json.load(f), "reference", "a.x-k1")
    named = dataclasses.replace(K1, hc_mult=1, hc_sinkhorn_iters=3, hc_eps=1e-3, hc_res_clamp=5.0)
    a, b = (mla.init_mla_decoder(c, seed=5, dtype=jnp.float32) for c in (K1, named))
    assert jax.tree.structure(a) == jax.tree.structure(b) and "hc_attn" not in a["layers"][0]
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert mla.mla_family(named).frame_counters == mla.mla_family(K1).frame_counters
    assert len(mla.mla_family(CFG).frame_counters) == 10 and mla.mla_family(CFG).frame_counters[-1] == "mhc_resid_ppm"
    ids = _ids(1)
    got_a, got_b = (_serve(a, ids, chunks=(8, 16), cfg=c)[0] for c in (K1, named))
    np.testing.assert_array_equal(got_a, got_b)
    want = np.asarray(k1_ref.logits(a, ids[None], 0, n_head=K1.heads, precision="highest", config=K1_PUBLISHED))[0]
    np.testing.assert_allclose(got_a, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize(
    "bad", [dict(n_group=4, topk_group=2), dict(n_group=1, topk_group=1), dict(hc_mult=0), dict(experts_per_tok=17)],
    ids=["groups_beside_a_bias", "one_group_is_a_group", "no_stream", "more_picks_than_experts"],
)
def test_the_configuration_refuses_what_it_cannot_mean(bad):
    with pytest.raises(ValueError):
        mla.MLADecoderConfig(**{**SIZES, **bad})


def test_parameters_of_the_other_residual_path_are_refused(weights):
    one = mla.init_mla_decoder(dataclasses.replace(CFG, hc_mult=1), seed=5, dtype=jnp.float32)
    with pytest.raises(mla.FamilyNotServed, match="hc_mult"):
        mla.mla_family(CFG).decoder_dims(one)
    with pytest.raises(mla.FamilyNotServed, match="hc_mult"):
        mla.mla_family(dataclasses.replace(CFG, hc_mult=1)).decoder_dims(weights[jnp.float32])


# (f) served


SEQ, MAX_NEW = 24, 8


def _zoo(**kw):
    from seldon_core_tpu.models.zoo import get_model

    return get_model(
        "mla_decoder", **SIZES, experts_held=4, first_expert=4, seq=SEQ, max_new_tokens=MAX_NEW,
        param_dtype="float32", seed=11, **kw,
    )


async def test_scheduler_serves_the_family_streams_counts_and_never_recompiles(ref):
    ms = _zoo()
    fam = ms.generative["family"]
    assert fam.name == "mla" and fam is mla.mla_family(CFG)
    sched = ds.DecodeScheduler(
        ms.params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4, prefix_slots=2, prefill_chunk=16,
        kv_page_size=PS, family=fam,
    )
    assert len(sched.pool.state) == 1 and sched.pool.state[0].shape[-1] == CFG.row_width  # latent pages, nothing else
    assert sched.programs._counted == 10 and not sched.programs._stateful  # the streams are no state
    sched.warmup()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 96, (5, SEQ)).astype(np.int32)
    prompts[1:, :16] = prompts[0, :16]
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    # the fused fallback's tokens are the reference's greedy tokens
    want = _ref_logits(ref, ms.params, oracle[0, :-1], "highest")
    assert oracle[0, SEQ:].tolist() == np.argmax(want[SEQ - 1 :], axis=-1).tolist()
    streamed: list = []
    first = await sched.submit(prompts[0], cache_prefix=16, on_token=lambda t, i: streamed.append(int(t)))
    np.testing.assert_array_equal(first, oracle[0])
    assert streamed == oracle[0, SEQ:].tolist()
    rest = await asyncio.gather(*(sched.submit(p) for p in prompts[1:]))
    for got, want in zip(rest, oracle[1:]):
        np.testing.assert_array_equal(got, want)  # prefix hits on the captured latent pages: the same greedy tokens
    assert sched.stat_prefix_hits == 4
    assert sched.recompiles_since_warmup() == 0
    sched.pool.alloc.check()
    frames = sched.flight.snapshot()
    steps = [f for f in frames if f.busy_ns[0] == 0 and f.moe_rows]
    assert steps
    for f in steps:
        assert f.moe_rows == f.active and f.mla_ctx_rows >= f.active * (SEQ + 1)
        assert 0 < f.mhc_resid_ppm < 10  # float32 Sinkhorn: its epsilon (1 ppm) and rounding
    assert steps[0].to_dict()["mhc_resid_ppm"] == steps[0].mhc_resid_ppm
    chunked = [f for f in frames if f.busy_ns[0] > 0]
    assert chunked and all(0 < f.mhc_resid_ppm < 20 for f in chunked)
    await sched.close()


def test_the_other_families_frames_say_nothing_of_streams():
    from seldon_core_tpu.telemetry.flight import FlightFrame

    f = FlightFrame(0, 0, "plain", 1, 0, 0, 0, 0, 0, 1, 0, 0, 0, (0, 1, 0, 0), 0, 1, 1, 0, 0, mla_ctx_rows=3)
    assert "mhc_resid_ppm" not in f.to_dict() and f.mhc_resid_ppm == 0
