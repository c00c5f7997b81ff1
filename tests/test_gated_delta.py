"""ops/gated_delta.py held to the recurrence it stands for, token by token in
float64-free plain numpy-style jnp on the CPU: the step, the blocked chunk
form for block lengths that do and do not divide the chunk, ragged rows, a
non-zero entering state, and the unit lower-triangular inverse on its own.

Tolerance 5e-5 on outputs and states of O(1) (2.8e-5 read at 300 tokens in
blocks of 256, 2e-5 held to 128): float32 sums in another order
(the blocked form adds a block's contributions as matrix products, the
recurrence one token at a time); a float32 product rounded to bfloat16
(``Precision.DEFAULT`` on the chip) is 4e-3.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.ops import gated_delta as gd

ATOL = 5e-5


def _inputs(seed, n, m, hk=2, r=2, dk=8, dv=8, alike=0.0):
    """Random q, k (l2-normalised as the caller does), v, decay and beta.
    ``alike``: how much of every key is one shared direction (keys that
    resemble each other make the triangular system stiff)."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q, k = f(n, m, hk, dk), f(n, m, hk, dk) + alike * f(1, 1, hk, dk) * 3
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = f(n, m, hk, r, dv)
    log_alpha = -jnp.asarray(rng.uniform(0.0, 16.0, (hk, r)), jnp.float32) * jnp.asarray(rng.uniform(0.01, 1.5, (n, m, hk, r)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (n, m, hk, r)), jnp.float32)
    state = f(n, hk, r, dk, dv)
    return state, q, k, v, log_alpha, beta


def _recurrence(state, q, k, v, log_alpha, beta):
    """The equations of the module docstring, a token at a time, value head
    (h, j) reading key head h."""
    outs = []
    for t in range(q.shape[1]):
        s = jnp.exp(log_alpha[:, t])[..., None, None] * state
        held = jnp.einsum("nhrkv,nhk->nhrv", s, k[:, t], precision="highest")
        d = beta[:, t][..., None] * (v[:, t] - held)
        state = s + k[:, t][:, :, None, :, None] * d[..., None, :]
        outs.append(jnp.einsum("nhrkv,nhk->nhrv", state, q[:, t], precision="highest"))
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("m", [1, 2, 7])
def test_the_step_equals_the_recurrence_and_a_standing_row_keeps_its_state(m):
    state, q, k, v, la, beta = _inputs(m, 3, m)
    la, beta = la.at[1].set(0.0), beta.at[1].set(0.0)  # row 1 stands (a slot that does not generate)
    want_o, want_s = _recurrence(state, q, k, v, la, beta)
    s = state
    for t in range(m):
        o, s = gd.gdn_step(s, q[:, t], k[:, t], v[:, t], la[:, t], beta[:, t])
        np.testing.assert_allclose(np.asarray(o), np.asarray(want_o[:, t]), atol=ATOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(state[1]))  # to the bit


@pytest.mark.parametrize("alike", [0.0, 0.7], ids=["random_keys", "alike_keys"])
@pytest.mark.parametrize(
    "m,block", [(1, 64), (9, 64), (16, 16), (33, 16), (33, 8), (64, 64), (100, 64), (100, 24), (128, 32), (96, 128), (256, 256), (300, 256)]
)
def test_the_blocked_chunk_form_equals_the_recurrence(m, block, alike):
    """Blocks that divide the chunk and blocks that do not (the last one is
    padded with positions that leave the state alone), one block and many,
    from a non-zero state."""
    args = _inputs(m + block, 2, m, alike=alike)
    want_o, want_s = _recurrence(*args)
    o, s = jax.jit(gd.gdn_chunk, static_argnames="block")(*args, block=block)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=ATOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=ATOL)


def test_ragged_rows_leave_the_state_as_it_was_past_their_counts():
    """Row r's positions from counts[r] on carry decay 1 and beta 0 (what
    ``hybrid_decoder._valid`` makes of them): the state after the chunk is the
    state after counts[r] tokens, a count of 0 leaves it to the bit."""
    m, counts = 40, np.array([40, 17, 0])
    state, q, k, v, la, beta = _inputs(5, 3, m)
    live = jnp.asarray(np.arange(m)[None, :] < counts[:, None])[..., None, None]
    la, beta = jnp.where(live, la, 0.0), jnp.where(live, beta, 0.0)
    o, s = gd.gdn_chunk(state, q, k, v, la, beta, block=16)
    for r, c in enumerate(counts[:2]):
        want_o, want_s = _recurrence(state[r : r + 1], *(t[r : r + 1, :c] for t in (q, k, v, la, beta)))
        np.testing.assert_allclose(np.asarray(s[r]), np.asarray(want_s[0]), atol=ATOL)
        np.testing.assert_allclose(np.asarray(o[r, :c]), np.asarray(want_o[0]), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(s[2]), np.asarray(state[2]))


def test_two_chunks_with_the_state_carried_equal_one():
    args = _inputs(2, 2, 48)
    o, s = gd.gdn_chunk(*args, block=16)
    o0, mid = gd.gdn_chunk(args[0], *(t[:, :20] for t in args[1:]), block=16)
    o1, end = gd.gdn_chunk(mid, *(t[:, 20:] for t in args[1:]), block=64)
    np.testing.assert_allclose(np.concatenate([o0, o1], 1), np.asarray(o), atol=ATOL)
    np.testing.assert_allclose(np.asarray(end), np.asarray(s), atol=ATOL)


def test_the_chunk_goes_in_blocks_of_rows_above_the_byte_limit(monkeypatch):
    args = _inputs(3, 4, 32)
    want = gd.gdn_chunk(*args, block=16)
    monkeypatch.setattr(gd, "_BLOCK_BYTES", 4 * 2 * 32 * 4 * 16)  # two rows' decay matrices
    got = gd.gdn_chunk(*args, block=16)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("c", [1, 2, 5, 16, 24, 28, 64, 256])
def test_the_unit_lower_inverse_inverts(c):
    """Against numpy's solve in float64, entries up to 0.3 everywhere below
    the diagonal (alike keys at half strength): the halving keeps what the
    Neumann product over all 64 rows would lose."""
    assert gd._solver_rows(c) == c
    rng = np.random.default_rng(c)
    a = np.tril(rng.uniform(-0.1, 0.3, (3, c, c)), -1)
    got = np.asarray(gd._unit_lower_inverse(jnp.asarray(a, jnp.float32)), np.float64)
    want = np.linalg.inv(np.eye(c) + a)
    np.testing.assert_allclose(got, want, atol=2e-5 * max(1.0, np.abs(want).max()))


def test_solver_rows_rounds_a_block_up_to_what_halves_evenly():
    assert [gd._solver_rows(c) for c in (1, 9, 16, 17, 27, 33, 64, 65, 100)] == [1, 9, 16, 18, 28, 36, 64, 72, 104]
