"""Continuous-batching decode scheduler (serving/decode_scheduler.py).

The load-bearing invariant: iteration-level scheduling over the slot KV
cache is TOKEN-FOR-TOKEN equivalent to the fused whole-batch oracle
(models/decoder.generate) under greedy decoding — for every sequence,
regardless of admission order, mid-stream admission, slot reuse, or which
other sequences share the step. Plus the serving behaviors the fused path
cannot express: admission under full slots, EOS retirement, per-request
sampling params, per-token streaming through the fast ingress, and zero
XLA recompiles across changing batch composition.
"""

import asyncio
import json

import numpy as np
import pytest

import jax.numpy as jnp

from seldon_core_tpu.models.decoder import generate, init_decoder
from seldon_core_tpu.serving.decode_scheduler import DecodeScheduler

SEQ = 8
MAX_NEW = 10
VOCAB = 128


def _params():
    return init_decoder(seed=3, vocab=VOCAB, hidden=64, layers=2, ffn=128, max_len=64)


def _prompts(n, seed=1):
    return np.random.default_rng(seed).integers(0, VOCAB, (n, SEQ)).astype(np.int32)


def _scheduler(params, n_slots=2, **kw) -> DecodeScheduler:
    s = DecodeScheduler(
        params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=n_slots, **kw
    )
    s.warmup()
    return s


def _oracle(params, ids, max_new=MAX_NEW) -> np.ndarray:
    return np.asarray(generate(params, jnp.asarray(ids), max_new))


def test_decoder_slot_blocks_match_oracle():
    """The raw building blocks (models/decoder.py): a prompt chunk-prefilled
    into the pages of slot 2 of 4, then per-slot paged_decode_step,
    greedy-sampled via sample_tokens, reproduces the fused oracle; so does
    the flat decode_step the draft's cache runs, fed the same sequence a
    token at a time."""
    import jax
    from seldon_core_tpu.models.decoder import (
        decode_step, init_slot_cache, paged_chunk_prefill, paged_decode_step, paged_kv_init, sample_tokens,
    )

    params = _params()
    ids = _prompts(1, seed=6)
    oracle = _oracle(params, ids)[0]
    slot, n_slots, ps = 2, 4, 4
    pps = -(-(SEQ + MAX_NEW) // ps)
    pool = paged_kv_init(params, 1 + n_slots * pps, ps)
    bt = np.zeros((n_slots, pps), np.int32)
    bt[slot] = np.arange(1 + slot * pps, 1 + (slot + 1) * pps)
    bt = jnp.asarray(bt)
    toks = np.zeros((n_slots, SEQ), np.int32)
    toks[slot] = ids[0]
    counts = np.zeros(n_slots, np.int32)
    counts[slot] = SEQ
    logits, _, pool = paged_chunk_prefill(
        params, pool, bt, jnp.asarray(toks), jnp.zeros(n_slots, jnp.int32), jnp.asarray(counts)
    )
    greedy_t = jnp.zeros(n_slots)
    greedy_k = jnp.zeros(n_slots, jnp.int32)
    got = [int(sample_tokens(logits[:, SEQ - 1], greedy_t, greedy_k, jax.random.key(0))[slot])]
    tok1 = np.zeros(n_slots, np.int32)
    pos = np.zeros(n_slots, np.int32)
    for i in range(MAX_NEW - 1):
        tok1[slot], pos[slot] = got[-1], SEQ + i
        logits, _, pool = paged_decode_step(params, pool, bt, jnp.asarray(tok1), jnp.asarray(pos))
        got.append(int(sample_tokens(logits, greedy_t, greedy_k, jax.random.key(i))[slot]))
    np.testing.assert_array_equal(got, oracle[SEQ:])
    # the flat cache: every consumed token of the oracle's row through decode_step
    ck, cv = init_slot_cache(params, n_slots, SEQ + MAX_NEW)
    flat = []
    for i, t in enumerate(oracle[:-1]):
        tok1[slot], pos[slot] = t, i
        logits, ck, cv = decode_step(params, ck, cv, jnp.asarray(tok1), jnp.asarray(pos))
        flat.append(int(np.argmax(np.asarray(logits)[slot])))
    np.testing.assert_array_equal(flat[SEQ - 1 :], oracle[SEQ:])


async def test_matches_oracle_with_midstream_admission():
    """The acceptance invariant: same tokens greedy-decoded with and
    without mid-stream admission — a sequence admitted while two others
    are mid-generation decodes exactly what the fused batch produces."""
    params = _params()
    ids = _prompts(3)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=3)

    a_started = asyncio.Event()

    def on_token(tok, idx):
        if idx >= 2:
            a_started.set()

    t_a = asyncio.ensure_future(sched.submit(ids[0], on_token=on_token))
    t_b = asyncio.ensure_future(sched.submit(ids[1]))
    await a_started.wait()  # a and b are mid-generation now
    t_c = asyncio.ensure_future(sched.submit(ids[2]))
    outs = await asyncio.gather(t_a, t_b, t_c)
    for row, out in zip(oracle, outs):
        np.testing.assert_array_equal(out, row)
    await sched.close()


async def test_admission_under_full_slots_and_slot_reuse():
    """More requests than slots: the overflow waits, admits as slots free,
    and every sequence still matches the oracle (slot reuse cannot leak
    stale K/V — the prefill scatter overwrites the retired tenant's)."""
    params = _params()
    ids = _prompts(5, seed=9)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=2)
    outs = await asyncio.gather(*(sched.submit(row) for row in ids))
    for row, out in zip(oracle, outs):
        np.testing.assert_array_equal(out, row)
    assert sched.stat_peak_active <= 2
    assert sched.stat_admitted == 5 and sched.stat_retired == 5
    assert sched.active == 0 and len(sched._free) == 2
    await sched.close()


async def test_eos_retirement_frees_slot_early():
    params = _params()
    ids = _prompts(1, seed=4)
    oracle = _oracle(params, ids)[0]
    # pick the 3rd greedy token as the EOS id: generation must stop there
    eos = int(oracle[SEQ + 2])
    sched = _scheduler(params, n_slots=2, eos_id=eos)
    out = await sched.submit(ids[0])
    # everything up to AND INCLUDING the first eos, nothing after
    cut = SEQ + list(oracle[SEQ:]).index(eos) + 1
    np.testing.assert_array_equal(out, oracle[:cut])
    assert len(out) < len(oracle)
    assert sched.active == 0  # slot freed the step eos appeared
    await sched.close()


async def test_per_request_sampling_params():
    params = _params()
    ids = _prompts(2, seed=5)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=2)
    # top_k=1 at any temperature IS argmax — sampling plumbing must
    # reproduce the greedy oracle exactly
    out = await sched.submit(ids[0], temperature=5.0, top_k=1)
    np.testing.assert_array_equal(out, oracle[0])
    # per-request max_new_tokens: a 3-token budget is a prefix of the
    # oracle's generation and the slot frees after 3
    out = await sched.submit(ids[1], max_new_tokens=3)
    np.testing.assert_array_equal(out, oracle[1][: SEQ + 3])
    # budgets clamp to the deployment cap (cache is sized for it)
    out = await sched.submit(ids[1], max_new_tokens=10_000)
    np.testing.assert_array_equal(out, oracle[1])
    await sched.close()


async def test_zero_recompiles_across_batch_composition():
    """The no-live-compile policy: after warmup, admissions, retirements,
    EOS exits, and every batch composition in between reuse the same four
    XLA executables (prefill, slot write, step, sampler x2 shapes)."""
    params = _params()
    ids = _prompts(6, seed=2)
    sched = _scheduler(params, n_slots=3)
    assert sched.recompiles_since_warmup() == 0
    outs = await asyncio.gather(
        *(
            sched.submit(row, max_new_tokens=3 + i, temperature=0.5 * (i % 2), top_k=i)
            for i, row in enumerate(ids)
        )
    )
    assert all(len(o) > SEQ for o in outs)
    assert sched.recompiles_since_warmup() == 0
    await sched.close()


async def test_wrong_prompt_length_rejected():
    from seldon_core_tpu.core.errors import APIException

    sched = _scheduler(_params())
    with pytest.raises(APIException, match="seq_len"):
        await sched.submit(np.zeros(SEQ + 3, np.int32))
    await sched.close()


async def test_queue_timeout_expires_unadmitted_requests():
    """The micro-batcher's REQUEST_TIMEOUT contract carries over: a request
    that cannot get a slot within queue_timeout_s fails with 303 instead of
    waiting unboundedly; admitted work is unaffected."""
    from seldon_core_tpu.core.errors import APIException

    params = _params()
    ids = _prompts(2, seed=8)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=1, queue_timeout_s=1e-4)
    t_a = asyncio.ensure_future(sched.submit(ids[0]))
    t_b = asyncio.ensure_future(sched.submit(ids[1]))
    np.testing.assert_array_equal(await t_a, oracle[0])
    with pytest.raises(APIException, match="timed out waiting"):
        await t_b
    await sched.close()


async def test_closed_scheduler_rejects_and_drains():
    from seldon_core_tpu.core.errors import APIException

    params = _params()
    ids = _prompts(1)
    sched = _scheduler(params)
    out_task = asyncio.ensure_future(sched.submit(ids[0]))
    await asyncio.sleep(0)  # let it admit
    await sched.close()
    # in-flight generation finished, not aborted
    np.testing.assert_array_equal(await out_task, _oracle(params, ids)[0])
    with pytest.raises(APIException, match="closed"):
        await sched.submit(ids[0])


# --------------------------------------------------------- serving wiring


def _predictor(n_slots: int, **tpu_extra):
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
            "tpu": {
                "max_batch": 4,
                "batch_buckets": [4],
                "decode_slots": n_slots,
                **tpu_extra,
            },
        }
    )


async def test_smoke_scheduler_through_server_and_batcher():
    """Tier-1 smoke: tiny model, n_slots=2, the REAL serving wiring — the
    micro-batcher hands generative rows to the scheduler and the buffered
    response matches the fused zoo apply exactly."""
    from seldon_core_tpu.core.message import SeldonMessage
    from seldon_core_tpu.models.zoo import get_model
    from seldon_core_tpu.serving.server import PredictorServer

    server = PredictorServer(_predictor(2), deployment_name="d")
    assert server.decode_scheduler is not None
    server.warmup()
    try:
        ids = _prompts(3, seed=7)
        out = await server.service.predict(SeldonMessage.from_array(ids))
        ms = get_model("tiny_gpt", seq=SEQ, max_new_tokens=6, vocab=VOCAB)
        oracle = np.asarray(ms.apply_fn(ms.params, jnp.asarray(ids)))
        np.testing.assert_array_equal(np.asarray(out.array).astype(np.int32), oracle)
        assert out.meta.tags["gen_lens"] == [6, 6, 6]
        # zero recompiles across the whole serving path
        assert server.decode_scheduler.recompiles_since_warmup() == 0
    finally:
        await server.decode_scheduler.close()


async def test_non_generative_graph_ignores_decode_slots():
    """decode_slots on a non-generative deployment must not break serving —
    the scheduler opt-in degrades to the normal path with a warning."""
    from seldon_core_tpu.graph.spec import PredictorSpec
    from seldon_core_tpu.serving.server import PredictorServer

    pred = PredictorSpec.model_validate(
        {
            "name": "p",
            "graph": {
                "name": "m",
                "type": "MODEL",
                "implementation": "JAX_MODEL",
                "parameters": [{"name": "model", "value": "iris_mlp", "type": "STRING"}],
            },
            "tpu": {"max_batch": 4, "batch_buckets": [4], "decode_slots": 4},
        }
    )
    server = PredictorServer(pred, deployment_name="d")
    assert server.decode_scheduler is None
    from seldon_core_tpu.core.message import SeldonMessage

    out = await server.service.predict(
        SeldonMessage.from_array(np.ones((2, 4), np.float32))
    )
    assert np.asarray(out.array).shape == (2, 3)


# ------------------------------------------------------------- streaming


async def _read_sse_response(reader):
    """Read one chunked HTTP response; return (status, headers, list of SSE
    data objects, number of separately-received chunks)."""
    status = int((await reader.readline()).split(b" ")[1])
    headers = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b""):
            break
        k, _, v = line.decode().partition(":")
        headers[k.strip().lower()] = v.strip()
    assert headers.get("transfer-encoding") == "chunked"
    chunks = []
    while True:
        size_line = await reader.readline()
        size = int(size_line.strip(), 16)
        if size == 0:
            await reader.readline()  # trailing CRLF
            break
        chunk = await reader.readexactly(size)
        await reader.readexactly(2)  # CRLF
        chunks.append(chunk)
    events = []
    for frame in b"".join(chunks).split(b"\n\n"):
        if frame.startswith(b"data: "):
            events.append(json.loads(frame[len(b"data: "):]))
    return status, headers, events, len(chunks)


async def test_streaming_e2e_through_fast_ingress():
    """SSE end-to-end on the fast ingress: tokens arrive as separate chunks
    while the generation is still running, and their concatenation equals
    the buffered /predictions response for the same prompt."""
    from tests.conftest import free_port
    from seldon_core_tpu.serving.fast_http import engine_routes, start_fast_server
    from seldon_core_tpu.serving.server import PredictorServer

    server = PredictorServer(_predictor(2), deployment_name="d")
    server.warmup()
    port = free_port()
    fast = await start_fast_server(
        engine_routes(server.service, {"paused": False}), "127.0.0.1", port
    )
    try:
        ids = _prompts(1, seed=11)
        body = json.dumps({"data": {"ndarray": ids.tolist()}}).encode()

        async def post(path):
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            req = (
                f"POST {path} HTTP/1.1\r\nHost: t\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n"
            ).encode() + body
            writer.write(req)
            await writer.drain()
            return reader, writer

        reader, writer = await post("/api/v0.1/predictions/stream")
        status, headers, events, n_chunks = await _read_sse_response(reader)
        writer.close()
        assert status == 200
        assert headers["content-type"] == "text/event-stream"
        # per-token events then the terminal done event
        token_events = [e for e in events if "token" in e]
        done = events[-1]
        assert done["done"] is True and done["puid"]
        assert len(token_events) == 6 == done["gen_lens"][0]
        # streamed incrementally, not one buffered blob
        assert n_chunks >= len(token_events)
        # tokens == the buffered response's generated tail
        reader, writer = await post("/api/v0.1/predictions")
        status_line = await reader.readline()
        assert int(status_line.split(b" ")[1]) == 200
        clen = 0
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b""):
                break
            if line.lower().startswith(b"content-length"):
                clen = int(line.split(b":")[1])
        buffered = json.loads(await reader.readexactly(clen))
        writer.close()
        ids_out = np.asarray(buffered["data"]["ndarray"], np.int64)[0]
        np.testing.assert_array_equal(
            [e["token"] for e in token_events], ids_out[SEQ:]
        )
        np.testing.assert_array_equal(done["ids"][0], ids_out)
        # streaming error path stays a plain status-JSON failure (head not
        # yet committed): wrong prompt length
        bad = json.dumps({"data": {"ndarray": [[1, 2, 3]]}}).encode()
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        req = (
            "POST /api/v0.1/predictions/stream HTTP/1.1\r\nHost: t\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(bad)}\r\n\r\n"
        ).encode() + bad
        writer.write(req)
        await writer.drain()
        status_line = await reader.readline()
        assert int(status_line.split(b" ")[1]) == 400
        writer.close()
    finally:
        fast.close()
        await fast.wait_closed()
        await server.decode_scheduler.close()
        if server.batcher is not None:
            await server.batcher.close()


# ------------------------------------------------------------ speculation


def _draft_pair():
    """(target, draft) with the depth-scaled residual init: the draft is
    the target's seed-shared 1-of-2-layer truncation (init_decoder draws
    positionally, so same seed/vocab/hidden/ffn/max_len + fewer layers =
    the deeper build's prefix) — a high-accept pair."""
    from seldon_core_tpu.models.decoder import init_decoder

    tgt = init_decoder(
        seed=3, vocab=VOCAB, hidden=64, layers=2, ffn=128, max_len=64, resid_scale=0.1
    )
    drf = init_decoder(
        seed=3, vocab=VOCAB, hidden=64, layers=1, ffn=128, max_len=64, resid_scale=0.1
    )
    return tgt, drf


def _unrelated_draft():
    """A draft with no relation to the target — accept rate ~0, so every
    round exercises the reject + bonus path."""
    return init_decoder(seed=99, vocab=VOCAB, hidden=64, layers=1, ffn=128, max_len=64)


def _spec_scheduler(params, draft, n_slots=2, spec_k=3, **kw) -> DecodeScheduler:
    s = DecodeScheduler(
        params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=n_slots,
        draft_params=draft, spec_k=spec_k, **kw
    )
    s.warmup()
    return s


@pytest.mark.parametrize("pair", ["high_accept", "low_accept"])
async def test_speculative_greedy_bit_identical_midstream(pair):
    """The speculative acceptance invariant: greedy output is bit-identical
    to the non-speculative scheduler, the fused scan oracle, AND the
    cache-less reference — for ANY draft (acceptance only keeps proposals
    matching the target's own argmax chain), under mid-stream admission
    and retirement."""
    from seldon_core_tpu.models.decoder import reference_generate

    if pair == "high_accept":
        params, draft = _draft_pair()
    else:
        params, draft = _params(), _unrelated_draft()
    ids = _prompts(4, seed=21)
    oracle = _oracle(params, ids)
    np.testing.assert_array_equal(oracle, reference_generate(params, ids, MAX_NEW))
    plain = _scheduler(params, n_slots=2)
    plain_outs = await asyncio.gather(*(plain.submit(row) for row in ids))
    await plain.close()

    sched = _spec_scheduler(params, draft, n_slots=2, spec_k=3)
    started = asyncio.Event()
    t_a = asyncio.ensure_future(
        sched.submit(ids[0], on_token=lambda t, i: i >= 2 and started.set())
    )
    t_b = asyncio.ensure_future(sched.submit(ids[1]))
    await started.wait()  # a (and likely b) mid-generation
    outs = [await t_a, await t_b] + list(
        await asyncio.gather(*(sched.submit(row) for row in ids[2:]))
    )
    for row, plain_row, out in zip(oracle, plain_outs, outs):
        np.testing.assert_array_equal(plain_row, row)
        np.testing.assert_array_equal(out, row)
    assert sched.stat_spec_dispatches > 0
    if pair == "high_accept":
        # the seed-shared truncation genuinely speculates: most proposals
        # survive and dispatches amortize over multiple tokens
        assert sched.stat_spec_accepted / sched.stat_spec_proposed > 0.5
        assert sched.stat_spec_emitted / sched.stat_spec_dispatches > 1.5
    assert sched.recompiles_since_warmup() == 0
    await sched.close()


async def test_speculative_sampled_top_k1_matches_oracle():
    """temperature > 0 with top_k=1 drives the SAMPLED acceptance branch
    (p/q ratios, residual resampling) through distributions that are
    exactly one-hot — so the emitted tokens must still equal the greedy
    oracle token-for-token: a deterministic proof the residual-resampling
    plumbing preserves the target distribution."""
    params, draft = _draft_pair()
    ids = _prompts(3, seed=5)
    oracle = _oracle(params, ids)
    sched = _spec_scheduler(params, draft, n_slots=2, spec_k=3)
    outs = await asyncio.gather(
        *(sched.submit(row, temperature=5.0, top_k=1) for row in ids)
    )
    for row, out in zip(oracle, outs):
        np.testing.assert_array_equal(out, row)
    assert sched.stat_spec_dispatches > 0
    await sched.close()


async def test_spec_k0_fallback_and_tighten_only():
    """Per-request spec_k=0 opts out: an all-opted-out workload runs the
    plain step program (no draft dispatches), and spec_k clamps tighten-
    only. Mixed rounds (one slot speculating, one opted out) still match
    the oracle."""
    params, draft = _draft_pair()
    ids = _prompts(4, seed=13)
    oracle = _oracle(params, ids)
    sched = _spec_scheduler(params, draft, n_slots=2, spec_k=3)
    outs = await asyncio.gather(*(sched.submit(row, spec_k=0) for row in ids[:2]))
    for row, out in zip(oracle[:2], outs):
        np.testing.assert_array_equal(out, row)
    assert sched.stat_spec_dispatches == 0  # plain program served everything
    # widen attempts clamp to the deployment k; mixed opt-outs share rounds
    outs = await asyncio.gather(
        sched.submit(ids[2], spec_k=100), sched.submit(ids[3], spec_k=0)
    )
    np.testing.assert_array_equal(outs[0], oracle[2])
    np.testing.assert_array_equal(outs[1], oracle[3])
    assert sched.stat_spec_dispatches > 0
    assert sched.recompiles_since_warmup() == 0
    await sched.close()


async def test_spec_zero_recompiles_mixed_workload():
    """The acceptance criterion: a mixed speculative/plain workload —
    varying budgets, sampling params, and per-request spec_k including 0 —
    compiles nothing after warmup, and compile_counts() reports the draft
    and verify programs."""
    params, draft = _draft_pair()
    ids = _prompts(6, seed=2)
    sched = _spec_scheduler(params, draft, n_slots=3, spec_k=3)
    counts = sched.compile_counts()
    for prog in ("draft_admit", "draft", "verify", "step", "chunk", "copy"):
        assert counts.get(prog, 0) >= 1, counts
    assert sched.recompiles_since_warmup() == 0
    outs = await asyncio.gather(
        *(
            sched.submit(
                row,
                max_new_tokens=3 + i,
                temperature=0.5 * (i % 2),
                top_k=i,
                spec_k=i % 3,
            )
            for i, row in enumerate(ids)
        )
    )
    assert all(len(o) > SEQ for o in outs)
    assert sched.recompiles_since_warmup() == 0
    await sched.close()


async def test_spec_eos_retirement_and_metrics_emission():
    """EOS retirement mid-round (an accepted token may BE the EOS — the
    slot frees there, later accepted tokens are dropped) plus the accept
    metrics contract: decode_spec() fires per verify dispatch and its
    counters reconcile with the emitted tokens."""
    from seldon_core_tpu.metrics import NullMetrics

    class _Rec(NullMetrics):
        def __init__(self):
            self.calls = []

        def decode_spec(self, deployment, proposed, accepted, emitted, mode="chain"):
            assert mode == "chain"  # spec_k deployments label the chain shape
            self.calls.append((proposed, accepted, emitted))

    params, draft = _draft_pair()
    ids = _prompts(1, seed=4)
    oracle = _oracle(params, ids)[0]
    eos = int(oracle[SEQ + 2])  # retire on the 3rd generated token
    rec = _Rec()
    sched = _spec_scheduler(params, draft, n_slots=2, spec_k=3, eos_id=eos, metrics=rec)
    out = await sched.submit(ids[0])
    cut = SEQ + list(oracle[SEQ:]).index(eos) + 1
    np.testing.assert_array_equal(out, oracle[:cut])
    assert sched.active == 0
    assert rec.calls, "decode_spec never fired"
    assert sum(c[2] for c in rec.calls) == sched.stat_spec_emitted
    assert sum(c[0] for c in rec.calls) == sched.stat_spec_proposed >= sum(
        c[1] for c in rec.calls
    )
    # emitted = generated minus the admission token (prefill emits token 0)
    assert sched.stat_spec_emitted == len(out) - SEQ - 1
    await sched.close()


async def test_spec_requires_draft_and_serving_wiring():
    """Ctor fail-fast without a draft; the full serving path (TpuSpec
    decode_draft_model/decode_spec_k -> scheduler_for_executor) builds a
    speculating scheduler whose buffered response matches the fused zoo
    apply, with the spec_k meta.tags override parsed tighten-only."""
    with pytest.raises(ValueError, match="draft"):
        DecodeScheduler(_params(), seq_len=SEQ, max_new_tokens=MAX_NEW, spec_k=2)

    from seldon_core_tpu.core.message import Meta, SeldonMessage
    from seldon_core_tpu.models.zoo import get_model
    from seldon_core_tpu.serving.server import PredictorServer

    server = PredictorServer(
        _predictor(
            2,
            decode_spec_k=3,
            decode_draft_model="zoo://draft?hidden=64&ffn=128&layers=1",
        ),
        deployment_name="d",
    )
    sched = server.decode_scheduler
    assert sched is not None and sched.spec_enabled and sched.spec_k == 3
    # vocab/max_len injected from the target
    assert sched.draft_params["tok_emb"].shape[0] == VOCAB
    server.warmup()
    try:
        ids = _prompts(2, seed=7)
        out = await server.service.predict(
            SeldonMessage.from_array(ids, meta=Meta(tags={"spec_k": 100}))
        )
        ms = get_model("tiny_gpt", seq=SEQ, max_new_tokens=6, vocab=VOCAB)
        oracle = np.asarray(ms.apply_fn(ms.params, jnp.asarray(ids)))
        np.testing.assert_array_equal(np.asarray(out.array).astype(np.int32), oracle)
        assert sched.recompiles_since_warmup() == 0
        # tighten-only: the 100 clamped to the deployment's 3
        assert sched.request_params_from_meta(Meta(tags={"spec_k": 100})) == {
            "spec_k": 100
        }  # parsed raw here; submit() clamps
    finally:
        await sched.close()


# ---------------------------------------------------------- pipelined rounds
#
# The double-buffered round loop (ENGINE_DECODE_PIPELINE, on by default):
# round N+1's host phases run under round N's in-flight dispatch against
# shadow pending state, reconciled at readback. The contract these tests
# pin: bit-identical greedy output vs the serial loop (and the oracle) for
# every round shape, zero recompiles, and a rollback-safe deferred-admit
# path under tight page budgets.


def _serial(s: DecodeScheduler) -> DecodeScheduler:
    """Force the serial loop on one scheduler instance (the per-run
    equivalent of the ENGINE_DECODE_PIPELINE=off kill switch — what
    bench's A/B leg flips)."""
    s.pipeline_enabled = False
    return s


async def _staggered(sched, ids, budgets=None, stagger=0.002):
    async def one(i):
        await asyncio.sleep(i * stagger)
        kw = {} if budgets is None else {"max_new_tokens": int(budgets[i])}
        return await sched.submit(ids[i], **kw)

    return await asyncio.gather(*(one(i) for i in range(len(ids))))


async def test_pipelined_greedy_bit_identical_midstream():
    """The tentpole contract: pipelined greedy output is bit-identical to
    the serial loop's (and the oracle's) under mixed mid-stream admits and
    retirements — identical round composition by construction
    (flight-decided admissions install before the next round's serial
    walk; deferred heads retry there against the post-retire pool)."""
    params = _params()
    ids = _prompts(6, seed=31)
    budgets = [3, MAX_NEW, 5, 2, MAX_NEW, 4]
    oracle = _oracle(params, ids)
    serial = _serial(_scheduler(params, n_slots=2))
    serial_outs = await _staggered(serial, ids, budgets)
    await serial.close()
    assert serial.stat_pipelined_rounds == 0

    piped = _scheduler(params, n_slots=2)
    assert piped._pipeline_on()
    outs = await _staggered(piped, ids, budgets)
    for i, (a, b) in enumerate(zip(serial_outs, outs)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(b, oracle[i][: SEQ + budgets[i]])
    assert piped.stat_pipelined_rounds > 0
    # host work was genuinely hidden under in-flight dispatches, and the
    # phase accounting survived (overlapped work never lands in phase_ns,
    # so sum(phase) <= gap still holds)
    agg = piped.flight.aggregate()
    assert agg["overlap_of_gap"] > 0.0
    assert agg["overlap_of_gap"] + agg["bubble_residual"] == pytest.approx(
        1.0, abs=2e-4
    )
    assert agg["phase_of_gap"] <= 1.0
    await piped.close()


@pytest.mark.parametrize("offloaded", [False, True], ids=["inline", "off_the_loop"])
async def test_plain_round_yields_once_inline_or_through_its_dispatch(offloaded):
    """A round whose dispatches wait off the loop (accelerator backends, a
    fleet's replicas) has yielded already: it takes no closing
    ``sleep(0)``, and the streams' writers run under the next dispatch. An
    inline round (CPU backend) keeps the closing yield. Either way other
    tasks get the loop every round, and the tokens are the oracle's."""
    params = _params()
    ids = _prompts(3, seed=41)
    s = _scheduler(params, n_slots=2)
    s._offload_dispatch = offloaded
    seen = []  # (tokens emitted so far, whether the round had yielded) at each foreign wake-up
    done = asyncio.Event()

    async def bystander():
        while not done.is_set():
            seen.append((s.stat_tokens, s._round_yielded))
            await asyncio.sleep(0)

    task = asyncio.ensure_future(bystander())
    outs = await asyncio.gather(*(s.submit(p) for p in ids))
    done.set()
    await task
    for got, want in zip(outs, _oracle(params, ids)):
        np.testing.assert_array_equal(got, want)
    # the bystander ran between token emissions all along: never starved
    assert len({n for n, _ in seen}) >= MAX_NEW
    assert any(y for _, y in seen) == offloaded
    await s.close()


@pytest.mark.parametrize("shape", ["chain", "tree"])
async def test_pipelined_spec_rounds_bit_identical(shape):
    """Speculative rounds through the pipelined dispatch twin: the round
    pair (draft + widened verify) enqueues, the overlap window runs, and
    the verify readback reconciles — chain and tree modes both stay
    bit-identical to the serial loop and the oracle."""
    params, draft = _draft_pair()
    ids = _prompts(4, seed=17)
    kw = {"spec_tree": "2,2,1"} if shape == "tree" else {}
    oracle = _oracle(params, ids)
    serial = _serial(_spec_scheduler(params, draft, n_slots=2, spec_k=3, **kw))
    serial_outs = await _staggered(serial, ids)
    await serial.close()

    piped = _spec_scheduler(params, draft, n_slots=2, spec_k=3, **kw)
    outs = await _staggered(piped, ids)
    for i, (a, b) in enumerate(zip(serial_outs, outs)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(b, oracle[i])
    assert piped.stat_spec_dispatches > 0
    assert piped.stat_pipelined_rounds > 0
    assert piped.recompiles_since_warmup() == 0
    await piped.close()


async def test_pipelined_prefix_warm_admissions():
    """Prefix-warm admissions under the pipeline: the seed request's
    retirement captures its prompt, concurrent sharers then admit against
    the warm index (some decided mid-flight) — outputs identical to the
    serial loop, hits register the same."""
    params = _params()
    shared = _prompts(1, seed=8)[0]
    distinct = _prompts(1, seed=9)[0]
    ids = np.stack([shared, shared, shared, distinct])

    def _mk(pipe: bool) -> DecodeScheduler:
        s = DecodeScheduler(
            params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2,
            prefix_slots=4,
        )
        s.warmup()
        return s if pipe else _serial(s)

    serial = _mk(False)
    serial_outs = [await serial.submit(ids[0])]  # capture seeds the index
    serial_outs += await _staggered(serial, ids[1:])
    await serial.close()

    piped = _mk(True)
    outs = [await piped.submit(ids[0])]
    outs += await _staggered(piped, ids[1:])
    for a, b in zip(serial_outs, outs):
        np.testing.assert_array_equal(a, b)
    assert piped.stat_prefix_hits == serial.stat_prefix_hits >= 1
    assert piped.stat_pipelined_rounds > 0
    assert piped.recompiles_since_warmup() == 0
    await piped.close()


async def test_pipelined_tight_pages_deferred_admit_rollback():
    """The deferred-admit path: a page budget that fits ONE slot's
    worst case forces the mid-flight admission attempt to refuse (the
    pre-retire pool cannot guarantee the reservation) — the head defers
    to the serial walk after the reconcile and admits once the retirement
    frees its pages. Outputs stay oracle-identical, the allocator audit
    stays clean, and the deferral is counted."""
    params = _params()
    ids = _prompts(3, seed=23)
    oracle = _oracle(params, ids)
    sched = DecodeScheduler(
        params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2,
        kv_page_size=4, kv_pages=7,  # pages_per_slot=5: one full slot + slack
    )
    sched.warmup()
    assert sched._pipeline_on()
    outs = await _staggered(sched, ids, stagger=0.001)
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(out, oracle[i])
    # the tight budget actually serialized occupancy through the pool...
    assert sched.stat_admit_blocked_rounds > 0
    # ...and at least one admission attempt was made (and refused) under
    # an in-flight dispatch — the deferred path
    assert sched.stat_pipeline_deferred > 0
    sched.pool.alloc.check()
    await sched.close()


async def test_pipeline_expiry_never_fails_a_decided_admit_and_failed_futures_roll_back():
    """Two reconcile edges of the shadow admissions: (a) the overlap
    window's expiry sweep must NOT time out a waiter the same window
    already flight-decided (the serial walk pops admitted seqs before
    expiry sees them; failing the caller while installing the slot would
    burn the whole budget for a dead request), and (b) a pending admit
    whose future settled during the flight — cancelled OR failed — rolls
    its reservation back instead of installing."""
    import time as _time

    from seldon_core_tpu.core.errors import APIException, ErrorCode
    from seldon_core_tpu.serving.decode_scheduler import _Seq

    params = _params()
    sched = _scheduler(params, n_slots=2)
    loop = asyncio.get_running_loop()

    # (a) decided-then-expired: deadline already past when the sweep runs
    seq = _Seq(_prompts(1, seed=41)[0], 4, 0.0, 0, 0, None, loop.create_future())
    seq.uid = 10_001
    seq.deadline = _time.perf_counter() - 1.0
    sched._waiting.append(seq)
    sched._overlap_window()  # decides the admission, then runs the sweep
    assert len(sched._pending_admits) == 1
    assert not seq.future.done(), "sweep expired a flight-decided admit"
    sched._apply_pending()
    assert sched._slots[seq.slot] is seq and seq.prefilling

    # (b) failed-in-flight: the reconcile rolls the reservation back
    seq2 = _Seq(_prompts(1, seed=43)[0], 4, 0.0, 0, 0, None, loop.create_future())
    seq2.uid = 10_002
    sched._waiting.append(seq2)
    sched._pipeline_admit()
    assert len(sched._pending_admits) == 1
    seq2.future.set_exception(
        APIException(ErrorCode.REQUEST_TIMEOUT, "expired mid-flight")
    )
    free_before = len(sched._free)
    sched._apply_pending()
    assert sched.stat_pipeline_rollbacks == 1
    assert len(sched._free) == free_before  # the slot never left the pool
    assert all(s is None or s is seq for s in sched._slots)
    sched.pool.alloc.check()
    seq.future.cancel()
    await sched.close()


async def test_pipeline_reconcile_upgrades_to_post_capture_prefix_hit():
    """A flight-decided admission can predate a capture the same round's
    consume walk performs (a retiring tenant captures the very prompt the
    decided sharer carries). The reconcile re-matches against the
    post-capture index and upgrades the install to the warm mapping — the
    hit the serial loop would have served — instead of silently paying
    the full prefill the stale mid-flight index implied."""
    from seldon_core_tpu.serving.decode_scheduler import _PendingAdmit, _Seq

    params = _params()
    sched = DecodeScheduler(
        params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2, prefix_slots=4
    )
    sched.warmup()
    shared = _prompts(1, seed=51)[0]
    # a completed tenant captures the prompt at retirement (the real path)
    await sched.submit(shared)
    assert sched._prefix_index.entries, "retirement capture did not land"
    hits_before = sched.stat_prefix_hits
    # a pending admit decided BEFORE that capture: reuse 0, no entry, the
    # worst-case reservation already made (what _pipeline_admit records)
    loop = asyncio.get_running_loop()
    seq = _Seq(shared, 4, 0.0, 0, 0, None, loop.create_future())
    seq.uid = 20_001
    slot = sched._free[-1]
    assert sched.pool.alloc.try_admit(slot, (), 0, 0)
    sched._waiting.append(seq)
    sched._pending_admits.append(_PendingAdmit(seq, slot, None, 0, 0))
    sched._apply_pending()
    assert sched._slots[slot] is seq
    assert seq.prefix_len > 0, "reconcile kept the stale cold decision"
    assert sched.stat_prefix_hits == hits_before + 1
    sched.pool.alloc.check()
    seq.future.cancel()
    await sched.close()


async def test_pipelined_zero_recompiles_and_kill_switch():
    """Zero-recompile guard with the pipeline on (the enqueue/overlap/
    readback split presents exactly the warmed signatures), and the kill
    switch semantics: ``pipeline_enabled`` off forces the serial loop."""
    params = _params()
    ids = _prompts(5, seed=37)
    sched = _scheduler(params, n_slots=3)
    outs = await asyncio.gather(
        *(
            sched.submit(row, max_new_tokens=2 + i, temperature=0.5 * (i % 2), top_k=i)
            for i, row in enumerate(ids)
        )
    )
    assert all(len(o) > SEQ for o in outs)
    assert sched.stat_pipelined_rounds > 0
    assert sched.recompiles_since_warmup() == 0
    await sched.close()

    forced = _scheduler(params, n_slots=2)
    forced.pipeline_enabled = False  # ENGINE_DECODE_PIPELINE=off equivalent
    assert not forced._pipeline_on()
    out = await forced.submit(ids[0])
    np.testing.assert_array_equal(out, _oracle(params, ids[:1])[0])
    assert forced.stat_pipelined_rounds == 0
    await forced.close()


@pytest.mark.parametrize("pipelined", [True, False], ids=["pipelined", "serial"])
@pytest.mark.parametrize("kw", [dict(n_slots=3), dict(n_slots=2, prefill_chunk=4), dict(n_slots=2, spec_k=3)],
                         ids=["plain", "chunk", "spec"])
async def test_the_ready_mark_changes_no_token(monkeypatch, kw, pipelined):
    """ISSUE 53: the blocking read waits, marks, then collects. On one fixed
    schedule (mixed budgets, a sampled row) the tokens are those of a read
    made in one piece, as the parent made it, and every reading dispatch of
    the schedule booked its return leg."""
    from seldon_core_tpu.serving import decode_scheduler as ds
    from seldon_core_tpu.telemetry.flight import F_COPY, F_DRAFT

    params = _params()
    ids = _prompts(5, seed=53)
    kw = dict(kw)
    if "spec_k" in kw:
        kw["draft_params"] = _unrelated_draft()

    async def serve():
        sched = _scheduler(params, **kw)
        sched.pipeline_enabled = pipelined
        outs = await asyncio.gather(
            *(sched.submit(row, max_new_tokens=2 + i, temperature=0.5 * (i % 2), top_k=i) for i, row in enumerate(ids))
        )
        await sched.close()
        return outs, sched.flight.snapshot()

    split, frames = await serve()
    monkeypatch.setattr(ds._Dispatch, "_collect", lambda self, out, read: read())  # one piece, no mark
    whole, unmarked = await serve()
    for a, b in zip(split, whole):
        np.testing.assert_array_equal(a, b)
    for f in frames:
        for rdy, rdb, busy in zip(f.rdy_ns, f.rdb_ns, f.busy_ns):
            assert 0 <= rdy <= rdb <= busy
        assert f.rdy_ns[F_DRAFT] == f.rdy_ns[F_COPY] == 0  # a dispatch that reads nothing books nothing
        assert sum(f.rdy_ns) > 0 or not sum(f.rdb_ns)  # every round that read, marked
    assert any(sum(f.rdy_ns) for f in frames)
    assert all(f.rdy_ns == (0, 0, 0, 0, 0) for f in unmarked)  # no mark, no return leg: never negative, never the wall


async def test_a_slow_round_names_what_held_it(caplog):
    """A round over SLOW_ROUND_NS is logged with the side that held it: a
    planted hang inside the loop (engine/faults.py) reads as host time
    between dispatches; ordinary rounds stay silent."""
    from seldon_core_tpu.engine.faults import DecodeFaultSpec, install_decode_faults

    params = _params()
    sched = _scheduler(params, n_slots=2)
    sched.SLOW_ROUND_NS = 500_000_000
    install_decode_faults(sched, DecodeFaultSpec(hang_at_round=3, hang_s=0.8))
    with caplog.at_level("WARNING", logger="seldon_core_tpu.serving.decode_scheduler"):
        out = await sched.submit(_prompts(1, seed=41)[0])
    np.testing.assert_array_equal(out, _oracle(params, _prompts(1, seed=41))[0])
    slow = [r.getMessage() for r in caplog.records if "decode round" in r.getMessage()]
    assert slow and all("host between dispatches" in m for m in slow)
    assert max(float(m.split("took ")[1].split(" s")[0]) for m in slow) >= 0.8
    assert len(slow) < sched.flight.rounds
    await sched.close()


@pytest.mark.parametrize("hint,cap,want", [
    (0, 0, [24]), (0, 16, [16, 8]), (12, 16, [16, 8]),  # a family without a state cache ignores the hint
])
def test_chunk_plan_of_a_family_without_state_rows_is_the_cap_alone(hint, cap, want):
    """``_next_chunk``: the rest of the prompt, at most the per-round cap; the
    hint's boundary cuts a chunk only for a recurrent family
    (tests/test_hybrid_decoder.py drives that side)."""
    from seldon_core_tpu.serving.decode_scheduler import _Seq

    params = init_decoder(seed=1, vocab=64, hidden=64, layers=1, ffn=64, max_len=64)
    sched = DecodeScheduler(params, seq_len=24, max_new_tokens=4, n_slots=2, prefix_slots=2, prefill_chunk=cap)
    assert not sched._stateful and sched.pool.recurrent == () and sched.pool.alloc.n_state_rows == 0
    seq = _Seq(np.zeros(24, np.int32), 4, 0.0, 0, 0, None, None)
    seq.chunk_cap, seq.cache_prefix = sched.prefill_chunk, hint
    got, pos = [], 0
    while pos < 24:
        got.append(sched._next_chunk(seq, pos))
        pos += got[-1]
    assert got == want and sched._hint_boundary(seq) == 0


async def test_chunked_admission_reuses_fewer_pages_than_monolithic_on_a_fixed_schedule():
    """ROADMAP S9, as a count: a prefix is captured when its prefill
    COMPLETES, so a sharer admitted while the first holder still prefills
    misses it. One schedule through both admissions: a runner whose tokens
    are the clock (one a round), and at each of its first six tokens one
    request of six that share 24 of 32 prompt tokens (three pages of 8).
    Monolithic admission prefills the first holder in the round after it
    arrives: the five behind it reuse three pages each. Chunks of 8 take
    four rounds over the same prompt: the three that arrive meanwhile miss,
    two hit. Counts of the CPU backend, not a speed."""
    n, seq, shared = 6, 32, 24
    rng = np.random.default_rng(7)
    ids = rng.integers(0, VOCAB, (n + 1, seq)).astype(np.int32)
    ids[1:, :shared] = ids[1, :shared]  # row 0 is the runner's: it shares nothing

    async def reused(chunk):
        sched = DecodeScheduler(
            _params(), seq_len=seq, max_new_tokens=MAX_NEW, n_slots=n + 1,
            prefix_slots=4, prefill_chunk=chunk, kv_page_size=8,
        )
        sched.warmup()
        sharers = []

        def clock(tok, idx):
            if 1 <= idx <= n:
                sharers.append(asyncio.ensure_future(
                    sched.submit(ids[idx], max_new_tokens=2, cache_prefix=shared)
                ))

        await sched.submit(ids[0], on_token=clock)
        await asyncio.gather(*sharers)
        counts = (sched.stat_prefix_hits, sched.pool.alloc.stat_pages_shared, sched.stat_prefix_tokens_saved)
        assert sched.stat_prefix_hits + sched.stat_prefix_misses == n + 1
        await sched.close()
        return counts

    mono, chunked = await reused(0), await reused(8)
    assert mono == (5, 15, 5 * shared)
    assert chunked == (2, 6, 2 * shared)
    assert chunked[1] < mono[1]


@pytest.mark.slow
async def test_staggered_arrival_soak():
    """Soak-adjacent: dozens of staggered arrivals with mixed budgets and
    sampling params over few slots — every greedy row still matches its
    oracle, counters reconcile, occupancy stays within bounds."""
    params = _params()
    ids = _prompts(24, seed=42)
    oracle = _oracle(params, ids)
    sched = _scheduler(params, n_slots=4)
    rng = np.random.default_rng(0)

    async def one(i):
        await asyncio.sleep(float(rng.uniform(0, 0.05)))
        budget = int(rng.integers(2, MAX_NEW + 1))
        out = await sched.submit(ids[i], max_new_tokens=budget)
        np.testing.assert_array_equal(out, oracle[i][: SEQ + budget])

    await asyncio.gather(*(one(i) for i in range(len(ids))))
    assert sched.stat_admitted == sched.stat_retired == len(ids)
    assert sched.stat_peak_active <= 4
    assert sched.recompiles_since_warmup() == 0
    await sched.close()
