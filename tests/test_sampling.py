"""The sampler against the sort it replaced, bit for bit.

``_oracle_transform`` / ``_oracle_sample`` are models/decoder.py's
``_transform_logits`` / ``sample_tokens`` as they were while the cutoff was
looked up in the sorted row and every row was transformed and drawn for
whatever it asked: the new ones (a selected cutoff behind a gate on
``top_k``, the draw behind a gate on ``temperature``) must give the same
bits for the same key.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.decoder import _kth_largest, _transform_logits, sample_tokens

VOCAB = 257  # odd and prime: no tile holds it whole


def _oracle_transform(logits, temperature, top_k):
    vocab = logits.shape[-1]
    temperature = jnp.broadcast_to(temperature, logits.shape[:-1])
    top_k = jnp.broadcast_to(top_k, logits.shape[:-1])
    sorted_desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    k_idx = jnp.clip(top_k - 1, 0, vocab - 1)
    thresh = jnp.take_along_axis(sorted_desc, k_idx[..., None], axis=-1)
    restricted = jnp.where(logits < thresh, -jnp.inf, logits)
    masked = jnp.where(top_k[..., None] > 0, restricted, logits)
    return masked / jnp.maximum(temperature, 1e-6)[..., None].astype(logits.dtype)


def _oracle_sample(logits, temperature, top_k, key):
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = _oracle_transform(logits, temperature, top_k)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


def _logits(rows, dtype, seed=0):
    """Rows with what a cutoff has to survive: exact ties (values drawn from
    a 32-point grid), a run of -inf, both zeros."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-16, 16, size=(rows, VOCAB)).astype(np.float32) / 4 + rng.normal(size=(rows, 1)).astype(np.float32)
    x[:, 5:40] = -np.inf
    x[:, 40], x[:, 41] = 0.0, -0.0
    x[rows // 2] = rng.normal(size=VOCAB)  # one row without a tie
    return jnp.asarray(x).astype(dtype)


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


# (temperature, top_k) a row: what a dispatch's rows can ask for
ROWS = {
    "all_greedy": ([0.0] * 6, [0, 5, 0, 300, 1, 0]),
    "sampling_no_topk": ([0.7, 1.0, 1.3, 0.2, 1.0, 2.0], [0] * 6),
    "mixed": ([0.0, 0.8, 1.0, 0.0, 1.2, 0.5], [4, 0, 7, 0, 50, 3]),
    "k_1": ([1.0] * 6, [1] * 6),
    "k_vocab": ([1.0] * 6, [VOCAB] * 6),
    "k_over_vocab": ([0.9] * 6, [VOCAB + 1, 1000, 2**30, VOCAB + 7, 999, 300]),
    # 35 of 257 entries are -inf: these cutoffs fall inside that run
    "k_into_the_inf_run": ([1.0] * 6, [VOCAB - 34, VOCAB - 35, VOCAB - 1, VOCAB - 20, 230, 222]),
    # a grid of 32 values over 222 finite entries: nearly every cutoff is tied
    "tied_at_the_cutoff": ([1.0] * 6, [2, 3, 10, 20, 100, 200]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(ROWS))
def test_sampler_is_bit_equal_to_the_sort_based_oracle(case, dtype):
    temps, topks = (jnp.asarray(v, t) for v, t in zip(ROWS[case], (jnp.float32, jnp.int32)))
    logits = _logits(6, dtype)
    got = jax.jit(_transform_logits)(logits, temps, topks)
    want = _oracle_transform(logits, temps, topks)
    # the gate hands a greedy row's top_k on as 0: compare the rows that sample
    # through sample_tokens, and the transform with every row's own top_k here
    assert got.dtype == want.dtype and np.array_equal(_bits(got), _bits(want))
    for seed in (0, 7):
        key = jax.random.key(seed)
        assert np.array_equal(
            jax.jit(sample_tokens)(logits, temps, topks, key), _oracle_sample(logits, temps, topks, key)
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_transform_is_bit_equal_at_rank_3_the_speculative_callers_shapes(dtype):
    """``speculative_accept`` hands [n, k + 1, vocab] logits with
    ``temperature[:, None]`` and ``top_k[:, None]``."""
    n, m = 4, 3
    logits = _logits(n * m, dtype, seed=3).reshape(n, m, VOCAB)
    temps = jnp.asarray([0.0, 0.7, 1.0, 1.5], jnp.float32)
    for topks in ([0, 0, 0, 0], [3, 0, 40, VOCAB + 2]):
        topks = jnp.asarray(topks, jnp.int32)
        got = jax.jit(_transform_logits)(logits, temps[:, None], topks[:, None])
        want = _oracle_transform(logits, temps[:, None], topks[:, None])
        assert got.shape == (n, m, VOCAB) and np.array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("k", [1, 2, 36, 128, 222, 223, 256, VOCAB])
def test_kth_largest_is_the_sorted_rows_entry(k):
    """Every float32 there is: NaN of either sign (the sort's largest), both
    infinities, both zeros (equal to the sort and to ``<``), subnormals."""
    rng = np.random.default_rng(k)
    x = rng.normal(size=(5, VOCAB)).astype(np.float32)
    x[0, :9] = [np.inf, -np.inf, 0.0, -0.0, 1e-45, -1e-45, 3.4e38, -3.4e38, np.nan]
    x[1, :4] = np.asarray([0x7FC00001, 0xFFC00000, 0xFF800000, 0x7F800000], np.uint32).view(np.float32)
    x[2] = np.where(rng.random(VOCAB) < 0.5, -0.0, 0.0)
    x[3] = -np.inf
    x[4] = np.repeat(rng.normal(size=VOCAB // 8 + 1).astype(np.float32), 8)[:VOCAB]  # runs of eight equal entries
    got = np.asarray(jax.jit(_kth_largest)(jnp.asarray(x), jnp.full((5,), k, jnp.int32)))[:, 0]
    want = np.asarray(jnp.flip(jnp.sort(jnp.asarray(x), axis=-1), axis=-1))[:, k - 1]
    # equal as the mask reads them (logits < cutoff): NaN with NaN, -0.0 with 0.0
    assert np.array_equal(got, want, equal_nan=True)
    both = ~np.isnan(want) & (want != 0)
    assert np.array_equal(_bits(got[both]), _bits(want[both]))


def test_same_key_same_tokens_and_a_greedy_rows_top_k_moves_no_sampled_row():
    logits = _logits(6, jnp.float32, seed=11)
    temps = jnp.asarray([0.0, 0.8, 1.0, 0.0, 1.2, 0.5], jnp.float32)
    topks = jnp.asarray([4, 0, 7, 9, 50, 3], jnp.int32)
    key = jax.random.key(5)
    a = sample_tokens(logits, temps, topks, key)
    assert np.array_equal(a, sample_tokens(logits, temps, topks, key))
    assert np.array_equal(a, sample_tokens(logits, temps, topks.at[0].set(0).at[3].set(1), key))
    assert not np.array_equal(a, sample_tokens(logits, temps, topks, jax.random.key(6)))
    assert np.array_equal(np.asarray(a)[[0, 3]], np.argmax(np.asarray(logits), axis=-1)[[0, 3]])
    # top_k 1 is the argmax whatever the temperature and the key (rows without a tie at the top)
    smooth = jax.random.normal(jax.random.key(1), (6, VOCAB))
    ones = sample_tokens(smooth, jnp.full((6,), 1.5, jnp.float32), jnp.ones((6,), jnp.int32), key)
    assert np.array_equal(ones, np.argmax(np.asarray(smooth), axis=-1))


def _primitives(jaxpr):
    """Primitive names of a jaxpr's top level, and of everything under it."""
    top = [e.primitive.name for e in jaxpr.eqns]
    under = list(top)
    for e in jaxpr.eqns:
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    under += _primitives(inner)[1]
    return top, under


def test_the_draw_and_the_cutoff_sit_behind_their_gates_and_nothing_sorts():
    """What an all-greedy dispatch runs is the program's top level: the
    argmax and two predicates. The random bits are under a ``cond``, the
    cutoff's loop under a second one inside it, and no ``sort`` is anywhere."""
    logits = _logits(6, jnp.float32)
    temps, topks = jnp.zeros((6,), jnp.float32), jnp.zeros((6,), jnp.int32)
    top, under = _primitives(jax.make_jaxpr(sample_tokens)(logits, temps, topks, jax.random.key(0)).jaxpr)
    assert "cond" in top and "argmax" in top
    assert not {"sort", "random_bits", "scan", "while"} & set(top)
    assert "random_bits" in under and "scan" in under and "sort" not in under
    top, under = _primitives(jax.make_jaxpr(_transform_logits)(logits, temps, topks).jaxpr)
    assert "cond" in top and "scan" not in top and "scan" in under and "sort" not in under
