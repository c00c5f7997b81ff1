"""The hybrid family's third shape through ops/gated_delta.py's kernels (PR
58), here under the Pallas interpreter: what a TPU runs where the head is
whole lane tiles. A file of its own beside tests/test_hybrid_decoder.py
(whose helpers and weights these are), so that the interpreter's minutes ride
another worker of a parallel run; the step kernel's rows and the frames'
counts are held there."""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.test_hybrid_decoder import (  # noqa: F401  (qref, qweights: the module's fixtures)
    MAX_NEW, PS, QATOL, QFAM, SEQ, SNAP, ZERO, _ids, _qlively, _qref_logits, _qzoo, _serve, ds, hd, judge_generated, qref,
    qweights,
)


@pytest.mark.parametrize("chunks", [(8, 8), (9, 2, 1, 6), (19,)], ids=["two", "inside_conv_reach", "padded_block"])
def test_third_shape_through_the_delta_rule_kernels_equals_the_plain_forms(qref, qweights, monkeypatch, chunks):
    """What a TPU runs on the lane tile, here under the Pallas interpreter
    (``kernel_mode`` answers for the platform and the head): the chunks
    through ``gdn_chunk_rows`` and the steps through ``gdn_step_rows`` give
    the plain forms' logits at every position and leave the same state rows,
    the zero row zero and every row no dispatch named as it was."""
    ids, params = _ids()[:24], qweights[jnp.float32]
    want, _, rec_want, _ = _serve(params, ids, chunks=chunks, fam=QFAM, snap_at=chunks[0])
    monkeypatch.setattr(hd, "gdn_kernel_mode", lambda *a: "interpret")
    got, _, rec, _ = _serve(params, ids, chunks=chunks, fam=QFAM, snap_at=chunks[0])
    np.testing.assert_allclose(got, want, atol=QATOL)
    np.testing.assert_allclose(got, _qref_logits(qref, params, ids), atol=QATOL)
    for a, b in zip(rec, rec_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=QATOL)  # the slot's row and its snapshot among them
        assert np.asarray(a[SNAP]).any()
        assert not np.asarray(a[ZERO]).any()
        assert not np.asarray(a[0]).any() and not np.asarray(a[2]).any()  # the slots that rode masked: never written


@pytest.mark.parametrize("kernel", ["", "interpret"], ids=["plain", "kernels"])
async def test_a_dispatch_writes_a_snapshot_row_that_a_warm_admission_riding_it_still_reads(qref, monkeypatch, kernel):
    """One snapshot row, bound to prefix A's entry. A cold request hinted at
    its first chunk's end and a request that hits A ride ONE chunk dispatch:
    the cold row's snapshot takes the only row there is, A's (its entry goes
    first, as ``DecodeScheduler._snapshot_row`` allows: "the program reads
    before it writes"), and the warm row, LATER in the dispatch, still starts
    from A's state and not from what the earlier row left there. Both forms
    serve the tokens the reference's logits allow."""
    monkeypatch.setattr(hd, "gdn_kernel_mode", lambda *a: kernel)
    jax.clear_caches()  # the other form's trace of the same programs is not this one's
    ms = _qzoo()
    params = _qlively(ms.params)
    sched = ds.DecodeScheduler(
        params, seq_len=SEQ, max_new_tokens=MAX_NEW, family=ms.generative["family"], n_slots=2, prefix_slots=1,
        prefill_chunk=8, kv_page_size=PS)
    sched.warmup()
    prompts = np.random.default_rng(5).integers(0, 96, (3, SEQ)).astype(np.int32)
    prompts[2, :16] = prompts[0, :16]  # 0 leaves A's entry, 1 is cold, 2 hits A
    first = await sched.submit(prompts[0], cache_prefix=16)
    a_row = next(iter(sched._prefix_index.entries.values())).state_row
    chunk, seen = sched.programs.chunk, []

    def spy(*args):
        seen.append(np.asarray(args[-1]))
        return chunk(*args)

    sched.programs.chunk = spy
    rest = await asyncio.gather(sched.submit(prompts[1], cache_prefix=8), sched.submit(prompts[2]))
    crossed = [r for r in seen if r[0, 1] == a_row and r[2, 0] == a_row]
    assert len(crossed) == 1, seen  # row 0 snapshots into the row that row 1 restores from
    served = [[int(t) for t in out] for out in [first, *rest]]
    exact = np.stack([_qref_logits(qref, params, s)[SEQ - 1 :] for s in served])
    verdict = judge_generated(served, exact, exact, SEQ - 1)
    assert verdict["ok"] and verdict["tokens_judged"] == 3 * MAX_NEW, verdict
    assert (sched.stat_prefix_hits, sched.stat_prefix_evictions) == (1, 1) and sched.recompiles_since_warmup() == 0
    sched.pool.alloc.check()
    await sched.close()
    jax.clear_caches()
