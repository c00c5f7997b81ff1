"""Prefix-cache KV reuse + chunked prefill (serving/decode_scheduler.py).

The load-bearing invariants:

- a prefix-HIT admission (pool gather + suffix-only prefill) emits greedy
  tokens bit-identical to a cold prefill and to the fused oracle, for any
  chunk partition of the suffix;
- the pool is ref-counted (never recycled under an in-flight reader) and
  LRU-evicted;
- every chunk/gather/capture/admit program is compiled at warmup() and a
  mixed chunked + prefix + speculative workload compiles NOTHING after it
  (the tier-1 zero-recompile guard);
- the spec-admit path reuses target-side prefixes while the draft cache
  gets a full, consistent prompt prefill.
"""

import asyncio

import numpy as np
import pytest

import jax.numpy as jnp

from seldon_core_tpu.models.decoder import generate, init_decoder
from seldon_core_tpu.serving.decode_scheduler import DecodeScheduler, PrefixIndex

SEQ = 8
MAX_NEW = 10
VOCAB = 128


def _params(**kw):
    return init_decoder(
        seed=3, vocab=VOCAB, hidden=64, layers=2, ffn=128, max_len=64, **kw
    )


def _shared_prompts(n, shared=5, seed=1):
    """n prompts sharing their first ``shared`` tokens (the system-prompt
    shape), random tails."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, (n, SEQ)).astype(np.int32)
    ids[1:, :shared] = ids[0, :shared]
    return ids


def _oracle(params, ids, max_new=MAX_NEW):
    return np.asarray(generate(params, jnp.asarray(ids), max_new))


def _scheduler(params, n_slots=2, **kw) -> DecodeScheduler:
    s = DecodeScheduler(
        params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=n_slots, **kw
    )
    s.warmup()
    return s


# ------------------------------------------------------------ radix index


def test_prefix_index_lcp_match_insert_evict():
    """Longest-common-prefix semantics: a prompt sharing only part of a
    longer entry still matches at the shared depth; dedup-covered inserts
    are the caller's job (match depth tells it); the entry cap evicts the
    LRU entry (returning it so the caller releases its pool pin) and
    rebuilds the trie."""
    idx = PrefixIndex(2)
    a = np.array([1, 2, 3, 4], np.int32)
    ea, ev = idx.insert(a, [1, 2], pin_id=0)
    assert ev is None and ea.length == 4 and ea.pages == [1, 2]
    # exact, partial, and divergent lookups
    e, d = idx.match(np.array([1, 2, 3, 4, 9], np.int32))
    assert e is ea and d == 4
    e, d = idx.match(np.array([1, 2, 9, 9], np.int32))
    assert e is ea and d == 2
    _, d = idx.match(np.array([9, 9], np.int32))
    assert d == 0
    eb, ev = idx.insert(np.array([5, 6], np.int32), [3], pin_id=1)
    assert ev is None and len(idx.entries) == 2
    # at the cap: inserting a third evicts the LRU (ea is older than eb —
    # but a recent match refreshed ea, so eb is the victim) and returns it
    # so the caller can release its pool pin
    idx.match(a)
    ec, ev = idx.insert(np.array([7, 8], np.int32), [4], pin_id=2)
    assert ev is eb and idx.evictions == 1
    _, d = idx.match(np.array([5, 6], np.int32))
    assert d == 0  # eb's tokens are gone from the trie
    e, d = idx.match(a)
    assert e is ea and d == 4  # survivor intact after the rebuild
    # pool-pressure reclaim drops by pin id (the allocator's batched
    # callback — one trie rebuild per reclaim wave)
    assert idx.remove_by_pins([ec.pin_id, 999]) == 1
    assert idx.evictions == 2
    _, d = idx.match(np.array([7, 8], np.int32))
    assert d == 0


@pytest.mark.parametrize("prompt,want", [
    ([1, 2, 3, 4, 9, 9], ("long", 4)),  # holds all of both entries: the longer
    ([1, 2, 9, 9], ("short", 2)),  # shares 2 of the long entry's 4: only the short one is whole
    ([1, 9], (None, 0)),  # holds neither whole, though it shares a token with both
    ([1, 2], ("short", 2)),
])
def test_prefix_index_whole_match_is_the_depth_rule_for_state_rows(prompt, want):
    """``match(whole=True)``: a recurrent state is reusable at its entry's own
    length only, so only an entry the prompt holds ALL of counts, the longest
    such; the plain match still answers the common depth with any entry. An
    entry carries the snapshot row it was inserted with."""
    idx = PrefixIndex(4)
    short, _ = idx.insert(np.array([1, 2], np.int32), [1], pin_id=0, state_row=7)
    long_, _ = idx.insert(np.array([1, 2, 3, 4], np.int32), [1, 2], pin_id=1, state_row=8)
    named = {"short": short, "long": long_, None: None}
    e, d = idx.match(np.asarray(prompt, np.int32), touch=False, whole=True)
    assert (e, d) == (named[want[0]], want[1])
    assert (short.state_row, long_.state_row) == (7, 8) and idx.insert(np.array([5], np.int32), [3], 2)[0].state_row == -1
    _, plain = idx.match(np.asarray(prompt, np.int32), touch=False)
    assert plain >= d


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_prefix_index_matches_a_token_by_token_walk(seed):
    """The vectorised match against a plain walk over every entry: the
    deepest common prefix, and of entries equally deep the newest insert."""
    rng = np.random.default_rng(seed)
    idx = PrefixIndex(6)
    stems = [rng.integers(0, 4, 12) for _ in range(3)]
    for pin in range(9):  # past the cap: the LRU entries go
        stem = stems[pin % 3]
        tokens = np.concatenate([stem[: rng.integers(1, 13)], rng.integers(0, 4, rng.integers(0, 6))])
        idx.insert(tokens.astype(np.int32), [pin], pin_id=pin)
        if pin % 4 == 3:
            idx.remove_by_pins([pin - 2])
    assert len(idx.entries) <= 6
    for _ in range(50):
        stem = stems[rng.integers(0, 3)]
        prompt = np.concatenate([stem[: rng.integers(0, 13)], rng.integers(0, 4, rng.integers(1, 8))])
        want, deep = None, 0
        for e in idx.entries.values():  # oldest insert first
            d = 0
            while d < min(e.length, len(prompt)) and e.tokens[d] == prompt[d]:
                d += 1
            if d and d >= deep:
                want, deep = e, d
        got, depth = idx.match(prompt, touch=False)
        assert (got, depth) == (want, deep)


def test_reader_safety_pages_survive_entry_eviction():
    """The paged twin of the old refcount-blocks-eviction guarantee: an
    entry whose pages a live reader slot has mapped CAN be evicted (the
    index drops it) but the PAGES survive through the reader's own
    refcounts — nothing is recycled under the reader until it retires."""
    from seldon_core_tpu.serving.kv_pool import PageAllocator

    alloc = PageAllocator(n_pages=8, page_size=4, n_slots=2, pages_per_slot=3)
    # slot 0 admits, materializes 2 pages, captures them as a prefix pin
    assert alloc.try_admit(0, (), 0)
    assert alloc.prepare_write(0, 0, 8) == []
    pin = alloc.capture(0, 8)
    assert pin is not None and len(pin.pages) == 2
    alloc.retire(0)
    # a reader maps the pinned pages copy-free
    assert alloc.try_admit(1, pin.pages, reuse=7)
    assert alloc.slot_pages(1) == pin.pages
    alloc.check()
    # entry eviction (index cap or reclaim) releases the pin — the shared
    # pages stay alive under the reader, only the unshared refs free
    alloc.release(pin.pin_id)
    alloc.check()
    for p in pin.pages:
        assert alloc.refs[p] == 1  # reader's reference survives
    # the reader's first divergent write copy-on-writes nothing now (it
    # owns the pages exclusively after the pin dropped)
    assert alloc.prepare_write(1, 7, 1) == []
    alloc.retire(1)
    alloc.check()
    assert alloc.free_pages == 7  # everything back, nothing leaked


# ------------------------------------------------- bit-equivalence: warm/cold


async def test_prefix_hit_bit_identical_greedy():
    """The acceptance invariant: a warm admission (prefix gather + suffix
    prefill) emits token-for-token what the cold path and the fused oracle
    emit. Request 0 seeds the pool via its cache_prefix hint at prefill
    completion; the followers hit."""
    params = _params()
    ids = _shared_prompts(4, shared=5, seed=11)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=2, prefix_slots=4)
    out0 = await sched.submit(ids[0], cache_prefix=5)
    np.testing.assert_array_equal(out0, oracle[0])
    assert sched.stat_prefix_captures == 1  # hinted capture at prefill end
    outs = await asyncio.gather(*(sched.submit(row) for row in ids[1:]))
    for row, out in zip(oracle[1:], outs):
        np.testing.assert_array_equal(out, row)
    assert sched.stat_prefix_hits == 3
    assert sched.stat_prefix_tokens_saved == 3 * 5
    assert sched.recompiles_since_warmup() == 0
    await sched.close()


async def test_auto_capture_from_retiring_slots_hits_without_hints():
    """No client hints at all: the first retiring slot's full prompt is
    captured automatically, and the radix index's longest-common-prefix
    match turns it into hits for every later sharer."""
    params = _params()
    ids = _shared_prompts(3, shared=6, seed=23)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=1, prefix_slots=4)
    for i, row in enumerate(ids):
        np.testing.assert_array_equal(await sched.submit(row), oracle[i])
    # request 0 missed; 1 and 2 reused >= the 6 shared tokens
    assert sched.stat_prefix_misses == 1
    assert sched.stat_prefix_hits == 2
    assert sched.stat_prefix_tokens_saved >= 2 * 6
    await sched.close()


async def test_prefix_hit_sampled_top_k1_matches_oracle():
    """temperature > 0 with top_k=1 drives the sampled branch through
    one-hot distributions (deterministic with the fixed seed), so warm
    admissions must still reproduce the greedy oracle exactly — the
    fixed-seed sampled twin of the greedy bit-equivalence test."""
    params = _params()
    ids = _shared_prompts(3, shared=5, seed=7)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=2, prefix_slots=4, seed=5)
    out0 = await sched.submit(ids[0], temperature=5.0, top_k=1, cache_prefix=5)
    np.testing.assert_array_equal(out0, oracle[0])
    outs = await asyncio.gather(
        *(sched.submit(row, temperature=5.0, top_k=1) for row in ids[1:])
    )
    for row, out in zip(oracle[1:], outs):
        np.testing.assert_array_equal(out, row)
    assert sched.stat_prefix_hits >= 2
    await sched.close()


async def test_exact_duplicate_prompt_leaves_suffix_token():
    """An exact-duplicate prompt matches at full length but reuse clamps
    to seq_len - 1: the last prompt token must still be consumed to
    produce the first generated token's logits."""
    params = _params()
    ids = _shared_prompts(1, seed=31)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=1, prefix_slots=2)
    np.testing.assert_array_equal(await sched.submit(ids[0]), oracle[0])
    np.testing.assert_array_equal(await sched.submit(ids[0]), oracle[0])
    assert sched.stat_prefix_hits == 1
    assert sched.stat_prefix_tokens_saved == SEQ - 1
    await sched.close()


# --------------------------------------------------------- chunked prefill


@pytest.mark.parametrize("chunk", [1, 3])
async def test_chunked_prefill_matches_oracle_mixed_lengths(chunk):
    """Chunked prefill under mixed effective suffix lengths (different
    shared-prefix spans -> different chunk bucket sequences) with decode
    steps interleaving: every sequence still matches the fused oracle and
    nothing recompiles after warmup."""
    params = _params()
    rng = np.random.default_rng(4)
    ids = rng.integers(0, VOCAB, (6, SEQ)).astype(np.int32)
    ids[1, :6] = ids[0, :6]  # long shared prefix -> short suffix
    ids[2, :2] = ids[0, :2]  # short shared prefix -> long suffix
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=3, prefix_slots=4, prefill_chunk=chunk)
    outs = await asyncio.gather(*(sched.submit(row) for row in ids))
    for row, out in zip(oracle, outs):
        np.testing.assert_array_equal(out, row)
    assert sched.stat_chunk_dispatches > 0
    assert sched.recompiles_since_warmup() == 0, sched.compile_counts()
    await sched.close()


async def test_chunking_without_prefix_cache_and_tag_tighten():
    """decode_prefill_chunk alone (no prefix pool) still serves through
    the incremental path, and the per-request prefill_chunk override
    tightens (a smaller chunk -> more rounds) but never widens."""
    params = _params()
    ids = _shared_prompts(2, seed=17)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=2, prefill_chunk=4)
    assert not sched.prefix_enabled and sched.incremental
    out = await sched.submit(ids[0])
    np.testing.assert_array_equal(out, oracle[0])
    d0 = sched.stat_chunk_dispatches
    assert d0 == 2  # 8-token prompt at chunk 4
    out = await sched.submit(ids[1], prefill_chunk=100)  # clamps to 4
    np.testing.assert_array_equal(out, oracle[1])
    assert sched.stat_chunk_dispatches - d0 == 2
    out = await sched.submit(ids[1], prefill_chunk=1)  # genuinely tighter
    np.testing.assert_array_equal(out, oracle[1])
    # values < 1 are ignored (a request can't widen chunking off — nor
    # accidentally fall to 1-token rounds): the deployment cap applies
    d1 = sched.stat_chunk_dispatches
    out = await sched.submit(ids[1], prefill_chunk=0)
    np.testing.assert_array_equal(out, oracle[1])
    assert sched.stat_chunk_dispatches - d1 == 2
    assert sched.recompiles_since_warmup() == 0
    await sched.close()


async def test_decode_keeps_emitting_during_chunked_prefill():
    """The ITL contract chunking exists for: while a long prompt prefills
    chunk-by-chunk, an already-running slot keeps emitting tokens (its
    token count advances between the newcomer's admission and first
    token)."""
    params = _params()
    ids = _shared_prompts(2, seed=19)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=2, prefill_chunk=1)

    running_at_admit = {}
    running_at_first = {}
    a_started = asyncio.Event()

    def on_a(tok, idx):
        if idx >= 1:
            a_started.set()

    t_a = asyncio.ensure_future(sched.submit(ids[0], on_token=on_a))
    await a_started.wait()

    seq_a = next(s for s in sched._slots if s is not None)
    running_at_admit["n"] = len(seq_a.tokens)

    def on_b(tok, idx):
        if idx == 0:
            running_at_first["n"] = len(seq_a.tokens)

    t_b = asyncio.ensure_future(sched.submit(ids[1], on_token=on_b))
    outs = await asyncio.gather(t_a, t_b)
    for row, out in zip(oracle, outs):
        np.testing.assert_array_equal(out, row)
    # 8 chunk rounds ran before b's first token; a emitted during them
    # (unless a already finished its budget — then the assertion is moot)
    if running_at_first.get("n", MAX_NEW) < MAX_NEW:
        assert running_at_first["n"] > running_at_admit["n"]
    await sched.close()


# ----------------------------------------------------- eviction under load


async def test_lru_eviction_end_to_end_and_reader_safety():
    """A pool smaller than the distinct-prefix set evicts LRU under load
    while live readers stay correct; the eviction counter and metric
    fire."""
    from seldon_core_tpu.metrics import NullMetrics

    class _Rec(NullMetrics):
        def __init__(self):
            self.evictions = 0

        def decode_prefix_evicted(self, deployment):
            self.evictions += 1

    params = _params()
    rng = np.random.default_rng(6)
    ids = rng.integers(0, VOCAB, (6, SEQ)).astype(np.int32)  # all distinct
    oracle = _oracle(params, ids)
    rec = _Rec()
    sched = _scheduler(params, n_slots=2, prefix_slots=2, metrics=rec)
    outs = await asyncio.gather(*(sched.submit(row) for row in ids))
    for row, out in zip(oracle, outs):
        np.testing.assert_array_equal(out, row)
    assert sched.stat_prefix_evictions >= 1
    assert rec.evictions == sched.stat_prefix_evictions
    # repeats of the survivors still hit and still match
    out = await sched.submit(ids[-1])
    np.testing.assert_array_equal(out, oracle[-1])
    await sched.close()


# ------------------------------------------------------------- speculation


def _draft_pair():
    tgt = _params(resid_scale=0.1)
    drf = init_decoder(
        seed=3, vocab=VOCAB, hidden=64, layers=1, ffn=128, max_len=64, resid_scale=0.1
    )
    return tgt, drf


@pytest.mark.parametrize("pair", ["high_accept", "low_accept"])
async def test_spec_mode_prefix_admit_vs_plain_oracle(pair):
    """Spec-admit over the prefix path: target-side prefixes are reused,
    the draft cache takes a full transition-time prefill, and greedy
    output stays bit-identical to the plain scheduler and the oracle for
    any draft. The high-accept pair must KEEP its accept rate — proof the
    draft cache stayed consistent through prefix/chunked admission."""
    if pair == "high_accept":
        params, draft = _draft_pair()
    else:
        params, draft = _params(), init_decoder(
            seed=99, vocab=VOCAB, hidden=64, layers=1, ffn=128, max_len=64
        )
    ids = _shared_prompts(4, shared=5, seed=29)
    oracle = _oracle(params, ids)
    plain = _scheduler(params, n_slots=2)
    plain_outs = await asyncio.gather(*(plain.submit(row) for row in ids))
    await plain.close()
    sched = _scheduler(
        params, n_slots=2, draft_params=draft, spec_k=3,
        prefix_slots=4, prefill_chunk=3,
    )
    out0 = await sched.submit(ids[0], cache_prefix=5)
    outs = [out0] + list(await asyncio.gather(*(sched.submit(r) for r in ids[1:])))
    for row, plain_row, out in zip(oracle, plain_outs, outs):
        np.testing.assert_array_equal(plain_row, row)
        np.testing.assert_array_equal(out, row)
    assert sched.stat_prefix_hits >= 3
    assert sched.stat_spec_dispatches > 0
    if pair == "high_accept":
        assert sched.stat_spec_accepted / sched.stat_spec_proposed > 0.5
    assert sched.recompiles_since_warmup() == 0, sched.compile_counts()
    await sched.close()


# ------------------------------------------------------- the tier-1 guard


async def test_warmup_compiles_every_bucket_and_mixed_traffic_recompiles_nothing():
    """CI guard: warmup() compiles the FULL chunk/gather/capture/draft-
    admit/step/draft/verify program set up front — one executable per
    chunk and admit bucket — and a mixed chunked + prefix + speculative
    workload (varying budgets, sampling, spec_k opt-outs, chunk
    overrides, hits and misses) leaves recompiles_since_warmup() at 0."""
    params, draft = _draft_pair()
    sched = _scheduler(
        params, n_slots=3, draft_params=draft, spec_k=2,
        prefix_slots=3, prefill_chunk=3,
    )
    base = sched.compile_counts()
    # every program the mixed workload can touch exists before traffic;
    # ladders are warmed bucket-by-bucket (jit caches count executables)
    assert base["chunk"] >= len(sched.chunk_buckets)
    assert base["draft_admit"] >= len(sched.programs.admit_buckets)
    assert base["copy"] >= len(sched.pool.copy_buckets)
    for prog in ("step", "draft", "verify"):
        assert base.get(prog, 0) >= 1, (prog, base)
    ids = _shared_prompts(8, shared=4, seed=41)
    oracle = _oracle(params, ids)
    outs = await asyncio.gather(
        *(
            sched.submit(
                row,
                max_new_tokens=2 + i,
                temperature=0.5 * (i % 2),
                top_k=i % 3,
                spec_k=i % 3,
                prefill_chunk=1 + i % 3,
                cache_prefix=4 if i == 0 else None,
            )
            for i, row in enumerate(ids)
        )
    )
    for i, out in enumerate(outs):
        if ids[i].tolist() not in [r.tolist() for r in ids[:i]]:
            # greedy rows must match the oracle prefix for their budget
            if 0.5 * (i % 2) == 0:
                np.testing.assert_array_equal(out, oracle[i][: SEQ + 2 + i])
    assert sched.stat_prefix_hits > 0 and sched.stat_chunk_dispatches > 0
    assert sched.stat_spec_dispatches > 0
    assert sched.recompiles_since_warmup() == 0, sched.compile_counts()
    await sched.close()


# -------------------------------------------------------- serving wiring


def _predictor(**tpu_extra):
    from seldon_core_tpu.graph.spec import PredictorSpec

    return PredictorSpec.model_validate(
        {
            "name": "p",
            "graph": {
                "name": "gpt",
                "type": "MODEL",
                "implementation": "JAX_MODEL",
                "parameters": [
                    {"name": "model", "value": "tiny_gpt", "type": "STRING"},
                    {"name": "seq", "value": str(SEQ), "type": "INT"},
                    {"name": "max_new_tokens", "value": "6", "type": "INT"},
                    {"name": "vocab", "value": str(VOCAB), "type": "INT"},
                ],
            },
            "tpu": {"max_batch": 4, "batch_buckets": [4], **tpu_extra},
        }
    )


async def test_serving_wiring_and_meta_tags():
    """TpuSpec knobs -> scheduler_for_executor -> warm serving: buffered
    responses match the fused zoo apply, meta.tags.cache_prefix seeds the
    pool, and the second request's admission is a hit."""
    from seldon_core_tpu.core.message import Meta, SeldonMessage
    from seldon_core_tpu.models.zoo import get_model
    from seldon_core_tpu.serving.server import PredictorServer

    server = PredictorServer(
        _predictor(decode_slots=2, decode_prefix_slots=4, decode_prefill_chunk=4),
        deployment_name="d",
    )
    sched = server.decode_scheduler
    assert sched is not None and sched.prefix_enabled and sched.prefill_chunk == 4
    server.warmup()
    try:
        ids = _shared_prompts(2, shared=5, seed=13)
        ms = get_model("tiny_gpt", seq=SEQ, max_new_tokens=6, vocab=VOCAB)
        oracle = np.asarray(ms.apply_fn(ms.params, jnp.asarray(ids)))
        out = await server.service.predict(
            SeldonMessage.from_array(ids[:1], meta=Meta(tags={"cache_prefix": 5}))
        )
        np.testing.assert_array_equal(np.asarray(out.array).astype(np.int32), oracle[:1])
        out = await server.service.predict(SeldonMessage.from_array(ids[1:]))
        np.testing.assert_array_equal(np.asarray(out.array).astype(np.int32), oracle[1:])
        assert sched.stat_prefix_hits >= 1
        assert sched.recompiles_since_warmup() == 0
        # typed tag errors surface as 400-class APIException
        from seldon_core_tpu.core.errors import APIException

        with pytest.raises(APIException, match="cache_prefix"):
            sched.request_params_from_meta(Meta(tags={"cache_prefix": "lots"}))
    finally:
        await sched.close()
        if server.batcher is not None:
            await server.batcher.close()


def test_validation_rejects_bad_prefix_knobs():
    from seldon_core_tpu.graph.defaulting import default_deployment
    from seldon_core_tpu.graph.spec import SeldonDeployment
    from seldon_core_tpu.graph.validation import ValidationError, validate_deployment

    def _dep(**tpu):
        return default_deployment(
            SeldonDeployment.from_dict(
                {
                    "spec": {
                        "name": "d",
                        "predictors": [
                            {
                                "name": "p",
                                "graph": {
                                    "name": "m",
                                    "type": "MODEL",
                                    "implementation": "JAX_MODEL",
                                },
                                "tpu": tpu,
                            }
                        ],
                    }
                }
            )
        )

    validate_deployment(
        _dep(decode_slots=4, decode_prefix_slots=8, decode_prefill_chunk=4)
    )
    with pytest.raises(ValidationError, match="decode_prefix_slots must be >= 0"):
        validate_deployment(_dep(decode_prefix_slots=-1))
    with pytest.raises(ValidationError, match="decode_prefix_ctx needs"):
        validate_deployment(_dep(decode_slots=4, decode_prefix_ctx=16))
    # prefix/chunk knobs without the scheduler would be silently ignored —
    # validation refuses instead
    with pytest.raises(ValidationError, match="need decode_slots"):
        validate_deployment(_dep(decode_prefix_slots=8))
    with pytest.raises(ValidationError, match="need decode_slots"):
        validate_deployment(_dep(decode_prefill_chunk=8))


@pytest.mark.slow
async def test_prefix_soak_staggered_mixed_budgets():
    """Soak-adjacent: dozens of staggered arrivals over a shared system
    prompt with mixed budgets, chunking, and a small pool — every greedy
    row matches its oracle, counters reconcile, nothing recompiles."""
    params = _params()
    ids = _shared_prompts(24, shared=5, seed=42)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=4, prefix_slots=3, prefill_chunk=2)
    rng = np.random.default_rng(0)

    async def one(i):
        await asyncio.sleep(float(rng.uniform(0, 0.05)))
        budget = int(rng.integers(2, MAX_NEW + 1))
        out = await sched.submit(ids[i], max_new_tokens=budget)
        np.testing.assert_array_equal(out, oracle[i][: SEQ + budget])

    await asyncio.gather(*(one(i) for i in range(len(ids))))
    assert sched.stat_admitted == sched.stat_retired == len(ids)
    assert sched.stat_prefix_hits + sched.stat_prefix_misses == len(ids)
    assert sched.stat_prefix_hits > 0
    assert sched.recompiles_since_warmup() == 0
    await sched.close()
