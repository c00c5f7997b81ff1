"""Generative serving tier (models/decoder.py + zoo tiny_gpt): the
KV-cache lax.scan decode must match the cache-less full-forward reference
token-for-token, and the model must serve as a normal deployment."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.decoder import (
    generate,
    init_decoder,
    reference_generate,
)


def _prompt(b=2, s=8, vocab=256, seed=1):
    return (np.random.default_rng(seed).integers(0, vocab, (b, s))).astype(np.int32)


def test_kv_cache_decode_matches_full_forward_reference():
    params = init_decoder(seed=3, vocab=256, hidden=64, layers=2, ffn=128, max_len=64)
    ids = _prompt()
    got = np.asarray(generate(params, jnp.asarray(ids), 10))
    ref = reference_generate(params, ids, 10)
    np.testing.assert_array_equal(got, ref)
    # prompt echoed, then generated
    np.testing.assert_array_equal(got[:, :8], ids)
    assert got.shape == (2, 18)


def test_decode_is_jittable_and_deterministic():
    params = init_decoder(seed=0, vocab=128, hidden=64, layers=1, max_len=32)
    ids = _prompt(b=1, s=4, vocab=128)
    f = jax.jit(lambda p, x: generate(p, x, 6))
    a = np.asarray(f(params, jnp.asarray(ids)))
    b = np.asarray(f(params, jnp.asarray(ids)))
    np.testing.assert_array_equal(a, b)
    assert a.dtype == np.int32


def test_context_overflow_rejected():
    params = init_decoder(max_len=16)
    with pytest.raises(ValueError, match="position table"):
        generate(params, jnp.zeros((1, 10), jnp.int32), 10)


def test_tiny_gpt_serves_as_deployment():
    """The zoo entry through the real serving runtime: ids wire in, the
    generated sequence out, exact integers end to end."""
    from seldon_core_tpu.graph.spec import PredictiveUnit, TpuSpec
    from seldon_core_tpu.models.zoo import get_model, make_jax_model_unit

    spec = PredictiveUnit.model_validate(
        {
            "name": "gpt",
            "type": "MODEL",
            "implementation": "JAX_MODEL",
            "parameters": [
                {"name": "model", "value": "tiny_gpt", "type": "STRING"},
                {"name": "seq", "value": "8", "type": "INT"},
                {"name": "max_new_tokens", "value": "5", "type": "INT"},
                {"name": "vocab", "value": "128", "type": "INT"},
            ],
        }
    )
    unit = make_jax_model_unit(
        spec, {"tpu": TpuSpec(batch_buckets=[2], max_batch=2)}
    )
    ids = _prompt(b=2, s=8, vocab=128, seed=7)
    out = np.asarray(unit.runtime.predict(ids))
    assert out.shape == (2, 13)
    # serving output equals the direct generate (ids stay exact through
    # the wire dtype policy)
    ms = get_model("tiny_gpt", seq=8, max_new_tokens=5, vocab=128)
    direct = np.asarray(ms.apply_fn(ms.params, jnp.asarray(ids)))
    np.testing.assert_array_equal(out.astype(np.int32), direct)


def test_tiny_gpt_decodes_on_data_mesh():
    """Generative serving shards like everything else: the same CR on a
    data-axis mesh produces token-for-token the single-device output (the
    KV caches are created inside jit and inherit the batch sharding)."""
    from seldon_core_tpu.graph.spec import PredictiveUnit, TpuSpec
    from seldon_core_tpu.models.zoo import make_jax_model_unit
    from seldon_core_tpu.parallel.mesh import mesh_from_spec

    spec = PredictiveUnit.model_validate(
        {
            "name": "gpt",
            "type": "MODEL",
            "implementation": "JAX_MODEL",
            "parameters": [
                {"name": "model", "value": "tiny_gpt", "type": "STRING"},
                {"name": "seq", "value": "8", "type": "INT"},
                {"name": "max_new_tokens", "value": "4", "type": "INT"},
                {"name": "vocab", "value": "64", "type": "INT"},
            ],
        }
    )
    mesh = mesh_from_spec({"data": 4})
    sharded = make_jax_model_unit(
        spec, {"tpu": TpuSpec(batch_buckets=[4], max_batch=4), "mesh": mesh}
    )
    plain = make_jax_model_unit(
        spec, {"tpu": TpuSpec(batch_buckets=[4], max_batch=4)}
    )
    ids = _prompt(b=4, s=8, vocab=64, seed=11)
    np.testing.assert_array_equal(
        np.asarray(sharded.runtime.predict(ids)).astype(np.int32),
        np.asarray(plain.runtime.predict(ids)).astype(np.int32),
    )


def test_tiny_gpt_overflowing_config_rejected_at_build():
    from seldon_core_tpu.models.zoo import get_model

    with pytest.raises(ValueError, match="max_len"):
        get_model("tiny_gpt", seq=120, max_new_tokens=32, max_len=128)


# ----------------------------------------------------- speculative blocks


def test_paged_verify_matches_sequential_steps_and_the_plain_forward():
    """The widened verify program is the k+1-query generalization of the
    step: given the same consumed tokens, its per-position logits equal
    k+1 sequential single-token steps over the same pages, both equal the
    teacher-forced forward (``sequence_logits``), and its argmax chain is
    ``generate``'s."""
    from seldon_core_tpu.models.decoder import (
        paged_chunk_prefill, paged_decode_step, paged_kv_init, paged_verify_step, sequence_logits,
    )

    params = init_decoder(seed=3, vocab=256, hidden=64, layers=2, ffn=128, max_len=64)
    ids = _prompt(b=1, s=8)
    slot, n_slots, k, ps, pps = 1, 3, 3, 4, 8
    chain = [int(t) for t in np.asarray(generate(params, jnp.asarray(ids), k + 2))[0, 8:]]
    oracle = np.asarray(sequence_logits(params, jnp.asarray([list(ids[0]) + chain[: k + 1]])))[0, 8:]
    pool = paged_kv_init(params, 1 + n_slots * pps, ps)
    bt = np.zeros((n_slots, pps), np.int32)  # the free slots write to the junk page
    bt[slot] = own = np.arange(1 + slot * pps, 1 + (slot + 1) * pps)
    bt = jnp.asarray(bt)
    toks = np.zeros((n_slots, 8), np.int32)
    toks[slot] = ids[0]
    counts = np.zeros(n_slots, np.int32)
    counts[slot] = 8
    logits, _, pool = paged_chunk_prefill(
        params, pool, bt, jnp.asarray(toks), jnp.zeros(n_slots, jnp.int32), jnp.asarray(counts)
    )
    assert int(np.argmax(np.asarray(logits)[slot, 7])) == chain[0]
    # sequential chain: consume the first token + its greedy successors one at a time
    tok1 = np.zeros(n_slots, np.int32)
    pos = np.zeros(n_slots, np.int32)
    seq_logits = []
    spool = pool
    for j in range(k + 1):
        tok1[slot], pos[slot] = chain[j], 8 + j
        lg, _, spool = paged_decode_step(params, spool, bt, jnp.asarray(tok1), jnp.asarray(pos))
        seq_logits.append(np.asarray(lg)[slot])
    # widened: the same k+1 consumed tokens in ONE call
    queries = np.zeros((n_slots, k + 1), np.int32)
    queries[slot] = chain[: k + 1]
    pos[slot] = 8
    wlg, _, wpool = paged_verify_step(params, pool, bt, jnp.asarray(queries), jnp.asarray(pos))
    wlg = np.asarray(wlg)[slot]
    np.testing.assert_allclose(wlg, np.stack(seq_logits), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(wlg, oracle, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.argmax(wlg, axis=-1), chain[1:])
    # the slot's pages agree wherever the sequential path wrote (positions 0..8+k)
    for plane, splane in zip(wpool, spool):
        np.testing.assert_allclose(np.asarray(plane)[:, own], np.asarray(splane)[:, own], rtol=1e-6, atol=1e-6)


def test_speculative_accept_greedy_unit():
    """Acceptance on hand-built one-hot logits: longest matching prefix,
    bonus at the first mismatch, tighten-only limit clamp, and the
    all-accepted bonus from the k+1th position."""
    from seldon_core_tpu.models.decoder import speculative_accept

    n, k, vocab = 4, 3, 16
    # target greedy chain per row: tokens 1, 2, 3, 4
    tl = np.full((n, k + 1, vocab), -10.0, np.float32)
    for j in range(k + 1):
        tl[:, j, j + 1] = 10.0
    drafts = np.array(
        [
            [1, 2, 3],  # all match -> accept 3, bonus = chain[3] = 4
            [1, 9, 3],  # mismatch at 1 -> accept 1, bonus = chain[1] = 2
            [7, 2, 3],  # mismatch at 0 -> accept 0, bonus = chain[0] = 1
            [1, 2, 3],  # limit 1 clamps a full match -> accept 1, bonus 2
        ],
        np.int32,
    )
    dl = np.zeros((n, k, vocab), np.float32)
    limits = np.array([3, 3, 3, 1], np.int32)
    out, acc = speculative_accept(
        jnp.asarray(tl), jnp.asarray(drafts), jnp.asarray(dl),
        jnp.asarray(limits), jnp.zeros(n), jnp.zeros(n, jnp.int32),
        jax.random.key(0),
    )
    out, acc = np.asarray(out), np.asarray(acc)
    np.testing.assert_array_equal(acc, [3, 1, 0, 1])
    emitted = [list(out[i, : acc[i] + 1]) for i in range(n)]
    assert emitted == [[1, 2, 3, 4], [1, 2], [1], [1, 2]]


def test_resid_scale_shares_seed_prefix():
    """resid_scale scales only the residual output projections, after the
    rng draws — so a fewer-layers build is still the deeper build's
    prefix (embeddings + leading layers bitwise equal), which is what
    makes zoo://draft an early-exit truncation of its target."""
    tgt = init_decoder(seed=5, vocab=128, hidden=64, layers=3, ffn=128,
                       max_len=32, resid_scale=0.1)
    drf = init_decoder(seed=5, vocab=128, hidden=64, layers=1, ffn=128,
                       max_len=32, resid_scale=0.1)
    np.testing.assert_array_equal(tgt["tok_emb"], drf["tok_emb"])
    np.testing.assert_array_equal(tgt["pos_emb"], drf["pos_emb"])
    for key in ("qkv", "attn_out", "mlp_in", "mlp_out"):
        np.testing.assert_array_equal(
            tgt["layers"][0][key]["w"], drf["layers"][0][key]["w"]
        )
    # and the scale actually applied vs the unscaled build
    plain = init_decoder(seed=5, vocab=128, hidden=64, layers=3, ffn=128, max_len=32)
    np.testing.assert_allclose(
        tgt["layers"][0]["attn_out"]["w"],
        plain["layers"][0]["attn_out"]["w"] * np.float32(0.1),
        rtol=1e-7,
    )
    np.testing.assert_array_equal(tgt["layers"][0]["qkv"]["w"], plain["layers"][0]["qkv"]["w"])
