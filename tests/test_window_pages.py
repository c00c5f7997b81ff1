"""The window page kind (serving/kv_pool.py ``WindowPages``) and the block it
was built for (models/moe_decoder.py with two head counts, a per-head output
gate, half-rotary full layers that lead their period, a leading dense layer,
a shared expert and a share of the routed ones), held to the plain reference
(benchmarks/reference/laguna-s-2.1.py) at a small size on the CPU: hidden 64,
4 / 6 query heads (full / sliding) over 2 key-value heads of 16, 16 experts
top-3 of width 32 with 4 held, window 8, pages of 4, contexts to five windows.
Seeded random weights, float32 unless a case says otherwise. The block tables
come from the allocator itself: a window-kind page is given back as the
sequence passes it, and the logits must not notice.
"""

import asyncio
import copy
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

from seldon_core_tpu.models import moe_decoder as md  # noqa: E402
from seldon_core_tpu.models.decoder import FamilyNotServed  # noqa: E402
from seldon_core_tpu.ops import gqa_decode as gqa  # noqa: E402
from seldon_core_tpu.ops import mla as mla_ops  # noqa: E402
from seldon_core_tpu.serving import decode_programs as dp  # noqa: E402
from seldon_core_tpu.serving import decode_scheduler as ds  # noqa: E402
from seldon_core_tpu.serving.kv_pool import PageAllocator, PagedKVPool, ring_pages, window_pool_pages  # noqa: E402

LAYERS, PS, CTX, WINDOW = 4, 4, 40, 8
SIZES = dict(
    vocab=96, hidden=64, layers=LAYERS, heads=4, heads_window=6, kv_heads=2, head_dim=16, ffn=32, experts=16,
    experts_per_tok=3, experts_held=4, window=WINDOW, period=4, full_first=True, rope_theta=50000.0,
    rope_theta_window=10000.0, rotary_full=0.5, yarn_factor=4.0, yarn_original=16, attn_gate=True,
    dense_layers=1, dense_ffn=96, shared_expert=True, routed_scale=2.5,
)
CFG = md.MoEDecoderConfig(**SIZES)
FAM = md.moe_family(CFG)
# the same sizes under the published config's keys, for the reference
PUBLISHED = {
    "head_dim": 16, "num_key_value_heads": 2, "num_experts_per_tok": 3, "rms_norm_eps": 1e-6, "sliding_window": WINDOW,
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"] * 2,
    "num_attention_heads_per_layer": [4, 6, 6, 6] * 2, "gating": "per-head", "mlp_only_layers": [0],
    "moe_routed_scaling_factor": 2.5, "share": {"first_expert": 0},
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 50000.0, "factor": 4.0, "original_max_position_embeddings": 16,
            "beta_fast": 32, "beta_slow": 1, "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0, "partial_rotary_factor": 1},
    },
}


def _load_ref():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return cells.load_module(ROOT, json.load(f), "reference", "laguna-s-2.1")


@pytest.fixture(scope="module")
def ref():
    return _load_ref()


def _lively(params):
    """The draw's matrices times 6: at hidden 64 a std of 0.02 makes every
    block a rounding error on the embedding (0.02 x sqrt(64) = 0.16 a
    product; 1.1 at the published 3072), and no planted fault would show."""
    def grow(path, a):
        return a if a.ndim < 2 or "tok_emb" in jax.tree_util.keystr(path) else a * 6
    return jax.tree_util.tree_map_with_path(grow, params)


@pytest.fixture(scope="module")
def weights():
    return {d: _lively(md.init_moe_decoder(CFG, seed=5, dtype=d)) for d in (jnp.float32, jnp.bfloat16)}


def _ref_logits(ref, params, ids, precision="highest", config=PUBLISHED):
    return np.asarray(ref.logits(params, np.asarray(ids)[None], 0, n_head=4, precision=precision, config=config))[0]


def _ids(seed=0, n=CTX):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(np.int32)


class Pool:
    """Three slots over both page kinds, the tables the allocator's own."""

    def __init__(self, params, dtype=jnp.float32, max_write=16, n_prefix=2, fam=FAM):
        per = CTX // PS
        self.n_win = window_pool_pages(3, n_prefix, WINDOW, max_write, PS)
        self.alloc = PageAllocator(
            3 * per + 2, PS, 3, per, window=(self.n_win, WINDOW, min(ring_pages(WINDOW, max_write, PS), per))
        )
        self.fam = fam
        self.state = fam.paged_kv_init(params, (3 * per + 2, self.n_win), PS, dtype)

    def tables(self, slot):
        """Both kinds' tables for a dispatch that ``slot`` alone rides: the
        others' rows read junk page 0, as the scheduler hands them."""
        mine = np.arange(3)[:, None] == slot
        return tuple(jnp.asarray(np.where(mine, bt, 0)) for bt in (self.alloc.block_tables, self.alloc.win.block_tables))

    def copy(self, copies):
        half = len(self.state) // 2
        for src, dst, *kind in copies:
            planes = range(half, 2 * half) if kind else range(half)
            self.state = tuple(
                a.at[:, dst].set(a[:, src]) if i in planes else a for i, a in enumerate(self.state)
            )

    def serve(self, params, ids, slot, *, chunks, start=0, check=True):
        """Teacher-forced: chunks of the prompt from ``start``, then single
        steps along ``ids``. Returns logits [len(ids), vocab] (zeros before ``start``)."""
        out = np.zeros((len(ids), CFG.vocab), np.float32)
        pos = start
        for c in chunks:
            self.copy(self.alloc.prepare_write(slot, pos, c))
            toks = np.zeros((3, max(chunks)), np.int32)
            toks[slot, :c] = ids[pos : pos + c]
            positions, counts = np.zeros(3, np.int32), np.zeros(3, np.int32)
            positions[slot], counts[slot] = pos, c
            logits, _h, self.state = self.fam.paged_chunk_prefill(
                params, self.state, self.tables(slot), jnp.asarray(toks), jnp.asarray(positions), jnp.asarray(counts)
            )
            out[pos : pos + c] = np.asarray(logits[slot, :c])
            pos += c
            if check:
                self.alloc.check()
        while pos < len(ids):
            self.copy(self.alloc.prepare_write(slot, pos, 1))
            toks, positions = np.zeros(3, np.int32), np.zeros(3, np.int32)
            toks[slot], positions[slot] = ids[pos], pos
            logits, _h, self.state = self.fam.paged_decode_step(
                params, self.state, self.tables(slot), jnp.asarray(toks), jnp.asarray(positions)
            )
            out[pos] = np.asarray(logits[slot])
            pos += 1
        if check:
            self.alloc.check()
        return out


# (a) cold prefill in 1, 2 and 4 chunks then decode == the reference, pages given back on the way


@pytest.mark.parametrize("chunks", [(16,), (8, 8), (4, 4, 4, 4), (7, 7, 7, 1)], ids=["one", "two", "four", "by7"])
def test_cold_prefill_then_decode_equals_reference_float32(ref, weights, chunks):
    params, ids = weights[jnp.float32], _ids(1)
    pool = Pool(params)
    assert pool.alloc.try_admit(1, (), 0)
    got = pool.serve(params, ids, 1, chunks=chunks)
    np.testing.assert_allclose(got, _ref_logits(ref, params, ids), atol=2e-5, rtol=0)
    win = pool.alloc.win
    # 40 positions = 10 pages written, at most the ring mapped at once, the oldest given back
    assert win.stat_written == CTX // PS and win.stat_released >= CTX // PS - win.ring
    assert len(win.slot_pages(1)) <= win.ring < CTX // PS == len(pool.alloc.slot_pages(1))
    pool.alloc.retire(1)
    pool.alloc.check()
    assert win.free_pages == win.n_pages - 1 and pool.alloc.free_pages == pool.alloc.n_pages - 1


# (b) a prefix hit longer than the window: every full-kind page, the last window's window-kind pages


@pytest.mark.parametrize("shared", [16, 18, 27], ids=["aligned", "mid_page", "odd"])
def test_prefix_hit_longer_than_the_window_equals_reference(ref, weights, shared):
    params = weights[jnp.float32]
    first, second = _ids(2), _ids(3)
    second[:shared] = first[:shared]
    pool = Pool(params)
    assert pool.alloc.try_admit(0, (), 0, extra_reserve=int(shared % PS != 0))  # its own copy of the boundary page
    pool.serve(params, first[:shared], 0, chunks=(shared,))  # a chunk that ENDS at the span: the capture's moment
    pin = pool.alloc.capture(0, shared)
    assert pin is not None and len(pin.pages) == -(-shared // PS)
    assert pin.win_first == max(0, shared - WINDOW) // PS and pin.win_first + len(pin.win_pages) == -(-shared // PS)
    pool.serve(params, first, 0, chunks=(), start=shared)  # the first goes on, copy-on-write at an unaligned end
    assert pool.alloc.pin_covers(pin.pin_id, shared) and not pool.alloc.pin_covers(pin.pin_id, shared - WINDOW)
    assert pool.alloc.try_admit(2, pin.pages, shared, pin_id=pin.pin_id)
    cow0 = (pool.alloc.stat_cow_copies, pool.alloc.win.stat_cow_copies)
    got = pool.serve(params, second, 2, chunks=(5,), start=shared)
    want = _ref_logits(ref, params, second)
    np.testing.assert_allclose(got[shared:], want[shared:], atol=2e-5, rtol=0)
    unaligned = int(shared % PS != 0)  # the boundary page is shared in BOTH kinds, and copied in both
    assert (pool.alloc.stat_cow_copies - cow0[0], pool.alloc.win.stat_cow_copies - cow0[1]) == (unaligned, unaligned)
    # a capture once the slot has moved past the span's window: nothing to pin
    assert pool.alloc.capture(2, shared) is None
    for slot in (0, 2):
        pool.alloc.retire(slot)
    pool.alloc.check()
    pool.alloc.release(pin.pin_id)
    pool.alloc.check()
    assert pool.alloc.win.free_pages == pool.alloc.win.n_pages - 1


# (c) bfloat16 inside the harness's delta


def test_bfloat16_serving_within_the_harness_delta(ref, weights):
    params, ids, first = weights[jnp.bfloat16], _ids(4), 23
    for pos in range(first + 1, CTX):  # greedy: each token from the served logits of the one before
        pool = Pool(params, jnp.bfloat16)
        pool.alloc.try_admit(1, (), 0)
        got = pool.serve(params, ids[:pos], 1, chunks=(12, 11), check=False)
        ids[pos] = int(np.argmax(got[pos - 1]))
    exact, noisy = (_ref_logits(ref, params, ids, p)[None, first:] for p in ("highest", "default"))
    verdict = judge_generated([ids.tolist()], exact, noisy, first)
    assert verdict["ok"], verdict
    assert verdict["rounding_delta"] > 1e-4


# (d) planted faults, each of which must FAIL the comparison of (a)


def _fault(name):
    cfg = copy.deepcopy(PUBLISHED)
    if name == "gate_left_out":
        cfg["gating"] = "none"
    elif name == "full_rotary":
        cfg["rope_parameters"]["full_attention"]["partial_rotary_factor"] = 1
    elif name == "pattern_shifted":
        cfg["layer_types"] = cfg["layer_types"][1:] + cfg["layer_types"][:1]
        cfg["num_attention_heads_per_layer"] = [4, 6, 6, 6] * 2  # the head counts stay with the weights
    elif name == "scale_dropped":
        cfg["moe_routed_scaling_factor"] = 1.0
    elif name == "window_one_short":
        cfg["sliding_window"] = WINDOW - 1
    elif name == "plain_full_theta":
        cfg["rope_parameters"]["full_attention"]["rope_theta"] = 10000.0
    return cfg


@pytest.mark.parametrize("fault", ["gate_left_out", "full_rotary", "pattern_shifted", "scale_dropped",
                                   "window_one_short", "plain_full_theta", "shared_dropped", "heads_swapped"])
def test_a_planted_fault_fails_the_comparison(ref, weights, fault):
    params, ids = weights[jnp.float32], _ids(1)
    pool = Pool(params)
    pool.alloc.try_admit(1, (), 0)
    got = pool.serve(params, ids, 1, chunks=(8, 8), check=False)
    if fault == "heads_swapped":
        # weights of the other kind's shape: 6 heads' worth where 4 are published, and the reverse
        swapped = md.MoEDecoderConfig(**{**SIZES, "heads": 6, "heads_window": 4})
        wrong = _lively(md.init_moe_decoder(swapped, seed=5, dtype=jnp.float32))
        assert wrong["layers"][0]["attn_qkv"].shape != params["layers"][0]["attn_qkv"].shape
        with pytest.raises(FamilyNotServed, match="head counts"):
            FAM.decoder_dims(wrong)
        bad = {**PUBLISHED, "num_attention_heads_per_layer": [6, 4, 4, 4] * 2}
        want = np.asarray(ref.logits(wrong, ids[None], 0, n_head=6, precision="highest", config=bad))[0]
    elif fault == "shared_dropped":
        def no_shared(layer):
            if "moe" not in layer:
                return layer
            return {**layer, "moe": {**layer["moe"], "shared_down": jnp.zeros_like(layer["moe"]["shared_down"])}}

        want = _ref_logits(ref, {**params, "layers": [no_shared(lp) for lp in params["layers"]]}, ids)
    else:
        want = _ref_logits(ref, params, ids, config=_fault(fault))
    assert np.abs(got - want).max() > 1e-3, fault


# (e) the eight shares' routed parts + the shared expert once == the uncut layer


def test_the_shares_routed_parts_and_the_shared_expert_once_equal_the_uncut_layer():
    whole = md.MoEDecoderConfig(**{**SIZES, "experts_held": 0})
    p = md.init_moe_decoder(whole, seed=3, dtype=jnp.float32)["layers"][1]
    h = jax.random.normal(jax.random.key(2), (24, whole.hidden))
    valid = jnp.arange(24) < 20
    uncut, cnt = md._ffn(whole, p, h, valid)
    parts = jnp.zeros_like(uncut)
    picks = 0
    for share in range(8):
        cfg = md.MoEDecoderConfig(**{**SIZES, "experts_held": 2, "first_expert": 2 * share, "shared_expert": share == 0})
        moe = {**p["moe"], "gate_up": p["moe"]["gate_up"][2 * share : 2 * share + 2],
               "down": p["moe"]["down"][2 * share : 2 * share + 2]}
        y, c = md._ffn(cfg, {**p, "moe": moe}, h, valid)
        parts, picks = parts + y, picks + int(c[3])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(uncut), atol=2e-6)
    assert picks == 20 * whole.experts_per_tok and int(cnt[0]) == 20  # every pick of a real row landed on one share
    assert not np.asarray(uncut[20:]).any() or whole.shared_expert  # junk rows: the routed part is zero


# (f) pages given back are poisoned and taken by another slot; nobody's logits move


def test_released_pages_poisoned_and_reused_leave_the_logits_unchanged(ref, weights):
    params = weights[jnp.float32]
    a, b = _ids(6), _ids(7)
    pool = Pool(params)
    assert pool.alloc.try_admit(0, (), 0) and pool.alloc.try_admit(2, (), 0)
    got_a = pool.serve(params, a[:24], 0, chunks=(8, 8, 8))
    win = pool.alloc.win
    mapped = set(win.slot_pages(0))
    given_back = [p for p in range(1, win.n_pages) if win.refs[p] == 0 and p not in mapped]
    assert win.stat_released >= 2
    half = len(pool.state) // 2
    free = jnp.asarray(given_back)
    pool.state = tuple(a_.at[:, free].set(1e4) if i >= half else a_ for i, a_ in enumerate(pool.state))
    got_b = pool.serve(params, b, 2, chunks=(8, 8))  # takes the poisoned pages (the free list is last in, first out)
    assert set(win.slot_pages(2)) & set(given_back) or win.stat_written > len(given_back)
    rest_a = pool.serve(params, a, 0, chunks=(), start=24)
    np.testing.assert_allclose(got_b, _ref_logits(ref, params, b), atol=2e-5, rtol=0)
    want_a = _ref_logits(ref, params, a)
    np.testing.assert_allclose(got_a[:24], want_a[:24], atol=2e-5, rtol=0)
    np.testing.assert_allclose(rest_a[24:], want_a[24:], atol=2e-5, rtol=0)


# (g) through DecodeScheduler: the ladder, hits, both kinds' counters, no recompile


SEQ, MAX_NEW = 24, 12


def _zoo(**kw):
    from seldon_core_tpu.models.zoo import get_model

    sizes = {k: v for k, v in SIZES.items() if k not in ("heads", "heads_window")}
    return get_model("moe_decoder", heads="4,6", seq=SEQ, max_new_tokens=MAX_NEW, param_dtype="float32", seed=11,
                     **{**sizes, **kw})


async def test_scheduler_serves_both_page_kinds_to_the_references_tokens(ref):
    ms = _zoo()
    fam = ms.generative["family"]
    assert fam.cfg == CFG and fam.frame_counters[3:] == (*md.HELD_COUNTERS, "attn_run_pages")
    sched = ds.DecodeScheduler(
        ms.params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4, prefix_slots=2, prefill_chunk=16,
        kv_page_size=PS, family=fam,
    )
    pool = sched.pool
    assert pool.windowed and len(pool.state) == 4
    assert pool.state[0].shape[:2] == (1, pool.n_pages) and pool.state[2].shape[:2] == (3, pool.n_window_pages)
    assert pool.n_window_pages == window_pool_pages(4, 2, WINDOW, 16, PS) < pool.n_pages
    sched.warmup()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 96, (6, SEQ)).astype(np.int32)
    prompts[1:, :16] = prompts[0, :16]
    first = await sched.submit(prompts[0], cache_prefix=16)
    assert sched.stat_prefix_captures == 1  # taken at the hint's boundary, not at the prompt's end
    rest = await asyncio.gather(*(sched.submit(p) for p in prompts[1:]))
    assert sched.stat_prefix_hits == 5 and sched.recompiles_since_warmup() == 0
    for got in [first, *rest]:
        got = np.asarray(got)
        want = _ref_logits(ref, ms.params, got)  # teacher-forced along the served tokens
        np.testing.assert_array_equal(got[SEQ:], want[SEQ - 1 : -1].argmax(-1))
    pool.alloc.check()
    a = pool.alloc
    assert a.win.live_pages == 0 and a.live_pages == 0 and a.win.stat_released > 0
    assert sched.stat_kv_win_released == a.win.stat_released and sched.stat_kv_win_written == a.win.stat_written
    assert 0 < sched.stat_kv_win_live_peak <= 4 * a.win.ring + a.win.n_slots
    frames = sched.flight.snapshot()
    assert sum(f.kv_win_released for f in frames) == a.win.stat_released
    assert sum(f.kv_win_written for f in frames) == a.win.stat_written
    assert any("kv_win" in f.to_dict() for f in frames) and any(f.moe_local_picks for f in frames)
    # a round's named counts are the sum of its dispatches; the step's own ride beside them
    stepped = [f for f in frames if f.busy_ns[1] > 0]
    # the four routing counts, the grouped and compact layer calls (a step's rows take the masked form: none), then
    # the run pages of the step's kernel (none on the CPU: the step gathers)
    assert stepped and all(len(f.step_counts) == 7 and f.step_counts[0] <= f.moe_rows for f in stepped)
    assert not any(any(f.step_counts[4:]) or f.attn_run_pages for f in stepped)
    assert all(f.step_counts == () for f in frames if f.busy_ns[1] == 0)
    for f in stepped:
        own = (f.moe_rows, f.moe_experts_hit, f.moe_load_max, f.moe_local_picks, f.moe_grouped_calls,
               f.moe_compact_calls, f.attn_run_pages)
        assert (f.step_counts == own) == (f.chunk_rows == 0) or f.step_counts == own  # alone in its round: the same numbers
        assert ("step_counts" in f.to_dict()) == bool(f.chunk_rows)
    await sched.close()


# (h) the step's kernel (ops/gqa_decode.py) over both page kinds, under the interpreter


@pytest.fixture
def small_blocks(monkeypatch):
    """Runs of 2 table entries, blocks of 4 (tests/test_gqa_decode.py): the
    full kind's tables of 9-10 pages have block boundaries inside them."""
    monkeypatch.setattr(mla_ops, "RUN_PAGES", 2)
    monkeypatch.setattr(mla_ops, "BLOCK_PAGES", 4)


def _kinds_fetched(bt, pos, rows):
    """[pages read, pages in runs] of one layer a kind, by the kernel's own
    arithmetic, for the tables, positions and rows a step was handed."""
    total = np.zeros(2, np.int64)
    pos, rows = jnp.asarray(pos), jnp.asarray(rows)
    full, win = jnp.asarray(bt[0]), jnp.asarray(bt[1])
    reads = gqa.step_reads(full, pos, rows, PS)
    total += np.asarray(gqa.pages_fetched(*reads, PS, full.shape[1]))
    sub, k0 = md._window_table(win, pos, 1, PS, WINDOW)
    reads = gqa.step_reads(sub, pos, rows, PS, k0, WINDOW)
    total += np.asarray(gqa.pages_fetched(*reads[:2], PS, sub.shape[1]))
    return total.tolist()


def test_the_kernel_step_reads_both_kinds_where_they_lie_and_never_a_page_given_back(weights, small_blocks):
    """A context of four windows served through the gather (pages given back
    on the way), then one more step twice from the same pool: through the
    gather, and through the kernel over a pool whose junk page holds NaN in
    both kinds (what the window kind's given-back entries and the other
    slots' tables name). The generating slot's logits agree to float32
    rounding, its new rows land in the same pages, a chunk asked for the
    kernel gathers all the same, and the step counts its run pages."""
    params = weights[jnp.float32]
    ids = _ids(8)
    pool = Pool(params)
    assert pool.alloc.try_admit(1, (), 0)
    at = 33
    pool.serve(params, ids[:at], 1, chunks=(8, 8, 8))
    assert pool.alloc.win.stat_released >= 4
    pool.copy(pool.alloc.prepare_write(1, at, 1))
    tables = jnp.stack(pool.tables(1))
    assert (np.asarray(tables[1, 1, : (at - WINDOW) // PS]) == 0).all()  # given back: the junk page
    toks, positions = np.zeros((3, 1), np.int32), np.zeros(3, np.int32)
    toks[1], positions[1] = ids[at], at
    rows = jnp.asarray([False, True, False])
    args = (jnp.asarray(toks), jnp.asarray(positions))
    want, _h, state_g, counted_g = FAM.paged_forward(params, pool.state, tables, *args, rows=rows)
    poisoned = tuple(a.at[:, 0].set(jnp.nan) for a in pool.state)
    got, _h, state_k, counted_k = FAM.paged_forward(params, poisoned, tables, *args, rows=rows, attn_kernel="interpret")
    assert np.isfinite(np.asarray(got[1])).all()
    np.testing.assert_allclose(np.asarray(got[1, 0]), np.asarray(want[1, 0]), rtol=0, atol=2e-5)
    assert len(counted_k) == len(FAM.frame_counters) == 7 and int(counted_g[-1]) == 0
    assert counted_k[:6].tolist() == counted_g[:6].tolist()  # the routing counts are the real rows' alone
    assert int(counted_k[-1]) == _kinds_fetched(np.asarray(tables), positions, rows)[1] > 0
    mine = [np.asarray(t)[1][np.asarray(t)[1] > 0] for t in tables]
    for i, (a, b) in enumerate(zip(state_g, state_k)):
        np.testing.assert_allclose(np.asarray(a[:, mine[i // 2]]), np.asarray(b[:, mine[i // 2]]), rtol=0, atol=2e-5)
    chunked = FAM.paged_forward(
        params, pool.state, tables, jnp.asarray(np.zeros((3, 2), np.int32)), jnp.asarray(positions),
        counts=jnp.zeros((3,), jnp.int32), attn_kernel="interpret",
    )
    assert int(chunked[3][-1]) == 0


async def test_scheduler_with_the_kernel_step_serves_the_gather_steps_tokens_past_a_window(monkeypatch, small_blocks):
    """With the ONE place of choice answering "interpret" a scheduler over
    both page kinds serves the gather scheduler's greedy tokens over contexts
    of four windows (window-kind pages given back mid-run, a hinted prefix
    hit), with no recompile, and every plain round's frame carries what the
    kernel's own arithmetic fetches from BOTH kinds' tables for the
    positions and rows the step was handed."""
    ms = _zoo()
    fam = ms.generative["family"]
    kw = dict(seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4, prefix_slots=2, prefill_chunk=16, kv_page_size=PS, family=fam)
    prompts = np.random.default_rng(3).integers(0, 96, (5, SEQ)).astype(np.int32)
    prompts[1:, :16] = prompts[0, :16]

    async def serve(sched):
        first = await sched.submit(prompts[0], cache_prefix=16)
        return [first, *await asyncio.gather(*(sched.submit(p) for p in prompts[1:]))]

    gather = ds.DecodeScheduler(ms.params, **kw)
    assert gather.programs.attn_kernel == "" and "attn_kernel" in fam.serves  # the CPU backend: the oracle path
    gather.warmup()
    want = await serve(gather)
    pages = gather.pool.pages_per_slot
    plain = [f for f in gather.flight.snapshot() if f.attn_pages_table]
    # through the gather a slot's whole full-kind table and its sub-table of ceil((8 + 1) / 4) + 1 window-kind entries
    assert plain and all((f.attn_pages_read, f.attn_pages_table, f.attn_run_pages) == (4 * (pages + 4), 8 * pages, 0) for f in plain)
    await gather.close()

    asked = []
    monkeypatch.setattr(dp, "_step_attn_kernel", lambda *a: asked.append(a[3:]) or "interpret")
    kernel = ds.DecodeScheduler(ms.params, **kw)
    assert kernel.programs.attn_kernel == "interpret" and asked == [(4, 2, 6)]  # both kinds' query heads, the K/V heads
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
    assert kernel.recompiles_since_warmup() == 0 and kernel.stat_prefix_hits == 4
    assert kernel.pool.alloc.win.stat_released > 0
    kernel.pool.alloc.check()
    plain = [f for f in kernel.flight.snapshot() if f.attn_pages_table]
    assert len(plain) == len(handed) and {f.attn_pages_table for f in plain} == {8 * pages}
    for f, (bt, pos, rows) in zip(plain, handed):
        assert [f.attn_pages_read, f.attn_run_pages] == _kinds_fetched(bt, pos, rows)
        assert f.step_counts[-1] == f.attn_run_pages
        # the full kind up to each generating slot's position, the window kind at most its sub-table, one page each for the rest
        held = -(-(pos[rows] + 1) // PS)
        assert held.sum() + rows.sum() + 2 * (~rows).sum() <= f.attn_pages_read <= held.sum() + 4 * rows.sum() + 2 * (~rows).sum()
    assert any(f.attn_run_pages for f in plain) and any(rows.any() and not rows.all() for _bt, _pos, rows in handed)
    assert max(pos[rows].max() for _bt, pos, rows in handed if rows.any()) >= 4 * WINDOW
    await kernel.close()


async def test_admission_throttles_by_kind_and_never_deadlocks():
    """A full-kind budget of one context + slack: the second request waits for
    the first's pages, in whichever kind runs out, and both finish."""
    ms = _zoo()
    per = -(-(SEQ + MAX_NEW) // PS)
    sched = ds.DecodeScheduler(
        ms.params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2, prefill_chunk=16, kv_page_size=PS,
        kv_pages=per + 3, family=ms.generative["family"],
    )
    sched.warmup()
    prompts = np.random.default_rng(1).integers(0, 96, (3, SEQ)).astype(np.int32)
    outs = await asyncio.gather(*(sched.submit(p) for p in prompts))
    assert len(outs) == 3 and sched.stat_admit_blocked_rounds > 0 and sched.recompiles_since_warmup() == 0
    sched.pool.alloc.check()
    await sched.close()


@pytest.mark.parametrize("what", ["host_tier", "export", "preseed"])
def test_what_two_page_kinds_do_not_carry_is_refused_by_name(what):
    ms = _zoo()
    fam = ms.generative["family"]
    kw = dict(seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2, prefix_slots=2, kv_page_size=PS, family=fam)
    with pytest.raises(FamilyNotServed, match="'moe' decoder family"):
        if what == "host_tier":
            ds.DecodeScheduler(ms.params, kv_host_bytes=1 << 20, **kw)
        elif what == "export":
            ds.DecodeScheduler(ms.params, **kw).export_prefix_state()
        else:
            ds.DecodeScheduler(ms.params, **kw).preseed_prefix_state({"entries": []})
    one_kind = md.moe_family(md.MoEDecoderConfig(period=1))  # every layer full: one page kind, all three served
    assert {"kv_int8", "host_tier", "prefix_export"} <= one_kind.serves and not one_kind.cfg.two_kinds


def test_the_int8_pool_has_both_kinds(weights):
    pool = PagedKVPool(
        weights[jnp.float32], n_slots=2, cache_ctx=CTX, page_size=PS, kv_dtype="int8", kv_init=FAM.paged_kv_init,
        window=WINDOW, max_write=8, n_prefix=1,
    )
    assert len(pool.state) == 12 and pool.state[0].dtype == jnp.int8 and pool.state[6].shape[0] == 3
    pool.warmup()
    assert pool.compile_count() == 2 * len(pool.copy_buckets)
    full, win = pool.block_tables(np.array([1, -1]))
    assert full.shape == win.shape == (2, CTX // PS)


async def test_the_int8_pool_serves_both_kinds_through_the_scheduler():
    """``decode_kv_dtype: int8`` on a pool of two page kinds: twelve planes, a
    hinted prefix captured at an unaligned boundary and hit (the boundary page
    copied in both kinds, scales and zero points with it), both kinds' audit,
    no recompile, and at this size the float pool's greedy tokens."""
    ms = _zoo()
    kw = dict(seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=4, prefill_chunk=16, kv_page_size=PS, family=ms.generative["family"])
    sched = ds.DecodeScheduler(ms.params, prefix_slots=2, kv_dtype="int8", **kw)
    sched.warmup()
    prompts = np.random.default_rng(0).integers(0, 96, (5, SEQ)).astype(np.int32)
    prompts[1:, :18] = prompts[0, :18]
    first = await sched.submit(prompts[0], cache_prefix=18)
    await asyncio.gather(*(sched.submit(p) for p in prompts[1:]))
    a = sched.pool.alloc
    assert len(sched.pool.state) == 12 and sched.stat_prefix_hits == 4 and sched.recompiles_since_warmup() == 0
    assert a.win.stat_cow_copies == a.stat_cow_copies == 5  # the writer's and the four readers' own boundary pages, a kind
    a.check()
    fp = ds.DecodeScheduler(ms.params, **kw)
    fp.warmup()
    np.testing.assert_array_equal(np.asarray(first)[SEQ:], np.asarray(await fp.submit(prompts[0]))[SEQ:])
    await sched.close()
    await fp.close()


def test_zoo_refuses_a_parameter_it_does_not_know():
    with pytest.raises(ValueError, match=r"does not know the parameter\(s\) \['heads_by_kind'\]"):
        _zoo(heads_by_kind="4,6")
    with pytest.raises(ValueError, match="invalid literal"):  # what an older tree says to "4,6": by value, at once
        int("4,6")
