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


# ------------------------------------------------------------------------------
# The kernels (``gdn_step_rows`` / ``gdn_chunk_rows``) under the Pallas
# interpreter: the same recurrence, over a layer's whole state array whose
# rows are read and written where they lie


def _array(state, rows_total, at):
    """A layer's state array of ``rows_total`` rows of noise with ``state``'s
    rows ([n, hk, r, dk, dv]) at ``at``."""
    n, hk, r, dk, dv = state.shape
    arr = jnp.asarray(np.random.default_rng(11).normal(size=(rows_total, hk * r, dk, dv)), jnp.float32)
    return arr.at[jnp.asarray(at)].set(state.reshape(n, hk * r, dk, dv))


def _heads(s):
    return np.asarray(s).reshape(s.shape[0], -1, *s.shape[-2:])


@pytest.mark.parametrize("hk", [16, 2], ids=["two_programs_a_row", "a_row_a_program"])
@pytest.mark.parametrize("m", [1, 2, 7])
def test_the_step_kernel_equals_the_recurrence_and_a_standing_row_keeps_its_state(m, hk):
    """32 value heads go ``STEP_HEADS`` a program, two programs a row; 4, which
    ``STEP_HEADS`` does not divide, a row a program."""
    state, q, k, v, la, beta = _inputs(m, 3, m, hk=hk)
    la, beta = la.at[1].set(0.0), beta.at[1].set(0.0)  # row 1 stands
    want_o, want_s = _recurrence(state, q, k, v, la, beta)
    arr = _array(state, 5, [0, 1, 2])
    s = arr
    for t in range(m):
        o, s = gd.gdn_step_rows(s, q[:, t], k[:, t], v[:, t], la[:, t], beta[:, t], interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want_o[:, t]), atol=ATOL)
    np.testing.assert_allclose(np.asarray(s[:3]), _heads(want_s), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(arr[1]))  # to the bit
    np.testing.assert_array_equal(np.asarray(s[3:]), np.asarray(arr[3:]))  # the rows past the slots: not touched


@pytest.mark.parametrize("alike", [0.0, 0.7], ids=["random_keys", "alike_keys"])
@pytest.mark.parametrize("m,block", [(1, 64), (9, 16), (16, 16), (33, 16), (33, 8), (64, 64), (100, 32), (40, 24), (96, 128)])
def test_the_chunk_kernel_equals_the_recurrence(m, block, alike):
    """Blocks that divide the chunk and blocks that do not, one block and
    many, from a non-zero state read at one row and written at two others."""
    state, *args = _inputs(m + block, 2, m, alike=alike)
    want_o, want_s = _recurrence(state, *args)
    arr = _array(state, 6, [4, 5])
    rows = jnp.asarray([[4, 5], [0, 1], [2, 9]], jnp.int32)
    o, s = gd.gdn_chunk_rows(arr, rows, *args, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=ATOL)
    np.testing.assert_allclose(np.asarray(s[:2]), _heads(want_s), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(s[2]), np.asarray(s[0]))  # the snapshot is the same state
    np.testing.assert_array_equal(np.asarray(s[3:]), np.asarray(arr[3:]))  # the rows read, and the others: as they were


@pytest.mark.parametrize("block", [16, 32, 64, 128])
def test_the_chunk_kernel_halves_above_the_rows_it_substitutes(block):
    """Diagonal blocks of ``SOLVE_ROWS`` = 16 rows are inverted by forward
    substitution and merged by the block formula above them: no halving in
    blocks of 16, one in 32, two in 64, three in ``KERNEL_BLOCK`` = 128 (what
    the chip runs): the same result."""
    assert (gd.SOLVE_ROWS, gd.KERNEL_BLOCK) == (16, 128)
    state, *args = _inputs(block, 2, 140, alike=0.7)
    want_o, want_s = _recurrence(state, *args)
    own = jnp.asarray([[0, 1], [0, 1], [9, 9]], jnp.int32)
    o, s = gd.gdn_chunk_rows(_array(state, 2, [0, 1]), own, *args, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=ATOL)
    np.testing.assert_allclose(np.asarray(s), _heads(want_s), atol=ATOL)


def test_the_chunk_kernel_leaves_the_state_as_it_was_past_a_rows_count():
    m, counts = 40, np.array([40, 17, 0])
    state, q, k, v, la, beta = _inputs(5, 3, m)
    live = jnp.asarray(np.arange(m)[None, :] < counts[:, None])[..., None, None]
    la, beta = jnp.where(live, la, 0.0), jnp.where(live, beta, 0.0)
    arr = _array(state, 4, [0, 1, 2])
    rows = jnp.asarray([[0, 1, 2], [0, 1, 2], [9, 9, 9]], jnp.int32)
    o, s = gd.gdn_chunk_rows(arr, rows, q, k, v, la, beta, block=16, interpret=True)
    for r, c in enumerate(counts[:2]):
        want_o, want_s = _recurrence(state[r : r + 1], *(t[r : r + 1, :c] for t in (q, k, v, la, beta)))
        np.testing.assert_allclose(np.asarray(s[r]), _heads(want_s)[0], atol=ATOL)
        np.testing.assert_allclose(np.asarray(o[r, :c]), np.asarray(want_o[0]), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(s[2:]), np.asarray(arr[2:]))  # a count of 0: to the bit


def test_two_chunks_of_the_kernel_with_the_state_carried_equal_one():
    state, *args = _inputs(2, 2, 48)
    arr = _array(state, 3, [0, 1])
    own = jnp.asarray([[0, 1], [0, 1], [9, 9]], jnp.int32)
    o, s = gd.gdn_chunk_rows(arr, own, *args, block=16, interpret=True)
    o0, mid = gd.gdn_chunk_rows(arr, own, *(t[:, :20] for t in args), block=16, interpret=True)
    o1, end = gd.gdn_chunk_rows(mid, own, *(t[:, 20:] for t in args), block=32, interpret=True)
    np.testing.assert_allclose(np.concatenate([o0, o1], 1), np.asarray(o), atol=ATOL)
    np.testing.assert_allclose(np.asarray(end), np.asarray(s), atol=ATOL)
    want_o, want_s = gd.gdn_chunk(state, *args, block=16)  # and both equal the plain form
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=ATOL)
    np.testing.assert_allclose(np.asarray(s[:2]), _heads(want_s), atol=ATOL)


@pytest.mark.parametrize(
    "rows,written",
    [([[1], [3], [5]], {3, 5}), ([[1], [1], [7]], {1}), ([[6], [9], [0]], {0}), ([[2], [7], [8]], set()), ([[4], [-1], [7]], {6}),
     ([[4], [-8], [-7]], {0}), ([[4], [-9], [2**31 - 1]], set())],
    ids=["two_other_rows", "its_own_row_and_nowhere", "a_snapshot_alone", "nowhere", "an_index_from_the_end",
         "before_the_array_and_its_first_row", "far_outside_both_ways"],
)
def test_a_chunk_dispatch_touches_only_the_rows_it_writes(rows, written):
    """A dispatch that reads row a and writes rows b and c leaves every other
    row of the array as it was to the bit; a destination past the array is
    written nowhere (``.at[].set(mode="drop")``'s rule, the plain path's)."""
    state, *args = _inputs(3, 1, 20)
    arr = _array(state, 7, [rows[0][0]])
    want = arr
    s_in = arr[rows[0][0]][None].reshape(state.shape)
    _, s_new = gd.gdn_chunk(s_in, *args, block=16)
    for to in rows[1:]:
        want = want.at[jnp.asarray(to)].set(s_new.reshape(1, *arr.shape[1:]), mode="drop")
    _, got = gd.gdn_chunk_rows(arr, jnp.asarray(rows, jnp.int32), *args, block=16, interpret=True)
    for r in range(7):
        if r in written:
            np.testing.assert_allclose(np.asarray(got[r]), np.asarray(want[r]), atol=ATOL)
            assert not np.array_equal(np.asarray(got[r]), np.asarray(arr[r]))
        else:
            np.testing.assert_array_equal(np.asarray(got[r]), np.asarray(arr[r]))


@pytest.mark.parametrize(
    "rows",
    [[[0, 1, 3], [4, 3, 5], [6, 9, 9]], [[5, 6, 2], [0, 1, 2], [6, 5, 9]], [[2, 2, 2], [0, 1, 2], [9, 3, 9]]],
    ids=["a_row_reads_what_an_earlier_row_writes", "two_rows_swap_their_snapshots", "three_rows_from_one_snapshot"],
)
def test_a_chunk_dispatch_reads_every_row_before_it_writes_any(rows):
    """The scheduler lets a dispatch write a snapshot row (an evicted LRU
    entry's) that a warm admission riding the same dispatch still starts
    from (``DecodeScheduler._snapshot_row``): the kernel's rows see the array
    as it came in, whatever the order they run in, as the plain path's gather
    and scatters do. (The interpreter keeps the array read apart from the
    array written, so it holds the answer and not the order: on the chip the
    kernel reads a copy gathered before the call and never the array it
    writes; the crossing case there is in PERF.md section 6, PR 58.)"""
    state, *args = _inputs(7, 3, 20)
    arr = _array(state, 7, [0, 1, 2])
    s_in = arr[jnp.asarray(rows[0])].reshape(state.shape)
    want_o, s_new = gd.gdn_chunk(s_in, *args, block=16)
    want = arr
    for to in rows[1:]:
        want = want.at[jnp.asarray(to)].set(s_new.reshape(3, *arr.shape[1:]), mode="drop")
    o, got = gd.gdn_chunk_rows(arr, jnp.asarray(rows, jnp.int32), *args, block=16, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=ATOL)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    untouched = sorted(set(range(7)) - {t for to in rows[1:] for t in to})
    np.testing.assert_array_equal(np.asarray(got)[untouched], np.asarray(arr)[untouched])


@pytest.mark.parametrize(
    "backend,dk,dv,dtype,want",
    [("tpu", 128, 128, jnp.float32, "mosaic"), ("tpu", 256, 128, jnp.float32, "mosaic"), ("tpu", 8, 8, jnp.float32, ""),
     ("tpu", 128, 64, jnp.float32, ""), ("tpu", 128, 128, jnp.bfloat16, ""), ("cpu", 128, 128, jnp.float32, ""),
     ("gpu", 128, 128, jnp.float32, "")],
)
def test_the_kernels_run_on_a_tpu_on_the_lane_tile_and_the_plain_forms_elsewhere(monkeypatch, backend, dk, dv, dtype, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert gd.kernel_mode(dk, dv, dtype) == want


def test_the_kernels_refuse_what_they_cannot_take_by_name():
    state, q, k, v, la, beta = _inputs(1, 2, 4)
    arr = _array(state, 3, [0, 1])
    with pytest.raises(ValueError, match="float32"):
        gd.gdn_step_rows(arr.astype(jnp.bfloat16), q[:, 0], k[:, 0], v[:, 0], la[:, 0], beta[:, 0], interpret=True)
    with pytest.raises(ValueError, match="state rows"):
        gd.gdn_chunk_rows(arr[:, :3], jnp.zeros((3, 2), jnp.int32), q, k, v, la, beta, interpret=True)
    with pytest.raises(ValueError, match="2 rows"):
        gd.gdn_step_rows(arr[:1], q[:, 0], k[:, 0], v[:, 0], la[:, 0], beta[:, 0], interpret=True)
