"""Paged KV memory subsystem (serving/kv_pool.py + models/decoder.py paged
attention + the scheduler riding them).

The load-bearing invariants:

- allocator soundness: across thousands of random admit / write / capture /
  retire / release sequences, no page is leaked or double-freed, refcounts
  reconcile exactly with block tables + pins, and the reservation
  invariant (free + reclaimable >= outstanding reservations) never breaks;
- the paged attention blocks give the plain teacher-forced forward's
  logits, and the scheduler over the pool stays TOKEN-identical to the
  fused scan oracle (fp KV mode) across admit/retire/CoW/spec/chunk;
- copy-free sharing actually buys capacity: at a fixed page budget a
  shared-system-prompt workload sustains >= 2x the concurrent slots of the
  flat-equivalent layout;
- int8 KV mode is tolerance-close (teacher-forced logit parity) and
  mechanically sound end-to-end;
- the paged gather / CoW-ladder programs obey the tier-1 zero-recompile
  guard under mixed paged workloads.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.decoder import generate, init_decoder
from seldon_core_tpu.serving.decode_scheduler import DecodeScheduler
from seldon_core_tpu.serving.kv_pool import PageAllocator

SEQ = 8
MAX_NEW = 10
VOCAB = 128


def _params(**kw):
    return init_decoder(
        seed=3, vocab=VOCAB, hidden=64, layers=2, ffn=128, max_len=96, **kw
    )


def _oracle(params, ids, max_new=MAX_NEW):
    return np.asarray(generate(params, jnp.asarray(ids), max_new))


def _scheduler(params, n_slots=2, seq_len=SEQ, max_new=MAX_NEW, **kw) -> DecodeScheduler:
    s = DecodeScheduler(
        params, seq_len=seq_len, max_new_tokens=max_new, n_slots=n_slots, **kw
    )
    s.warmup()
    return s


def _shared_prompts(n, seq=SEQ, shared=5, seed=1):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, VOCAB, (n, seq)).astype(np.int32)
    ids[1:, :shared] = ids[0, :shared]
    return ids


# ------------------------------------------------------ allocator invariants


def test_allocator_invariants_random_admit_retire_fork_sequences():
    """Property-style soak of the host allocator: 10k random operations —
    admissions (with and without prefix sharing), sequential writes (fresh
    allocation + CoW), captures (pins), entry releases, retirements — with
    the full consistency audit run throughout: no leak, no double-free,
    refcounts exact, reservation invariant intact."""
    rng = np.random.default_rng(0)
    n_slots, ps, pps = 4, 4, 5  # 20-token virtual context in 4-token pages
    alloc = PageAllocator(n_pages=3 * pps + 2, page_size=ps, n_slots=n_slots,
                          pages_per_slot=pps)
    seq_len = 12
    cursor = [-1] * n_slots  # -1 = slot free, else next write position
    # the allocator's capture-while-writing contract (what the scheduler's
    # cache_prefix extra_reserve encodes): a slot may take at most ONE
    # unaligned mid-flight capture per tenancy, reserved up front
    forked = [False] * n_slots
    pins: list = []
    ops = 0
    for step in range(10_000):
        ops += 1
        free_slots = [s for s in range(n_slots) if cursor[s] < 0]
        busy = [s for s in range(n_slots) if cursor[s] >= 0]
        r = rng.random()
        if r < 0.30 and free_slots:
            slot = int(rng.choice(free_slots))
            pin = pins[int(rng.integers(len(pins)))] if pins and rng.random() < 0.6 else None
            if pin is not None:
                reuse = int(rng.integers(1, len(pin.pages) * ps + 1))
                ok = alloc.try_admit(slot, pin.pages, reuse, extra_reserve=1)
                start = reuse
            else:
                ok = alloc.try_admit(slot, (), 0, extra_reserve=1)
                start = 0
            if ok:
                cursor[slot] = start
                forked[slot] = False
        elif r < 0.65 and busy:
            slot = int(rng.choice(busy))
            count = int(rng.integers(1, ps + 2))
            copies = alloc.prepare_write(slot, cursor[slot], count)
            for s_, d_ in copies:
                assert s_ != d_ and d_ != 0
            cursor[slot] = min(cursor[slot] + count, pps * ps)
        elif r < 0.80 and busy:
            slot = int(rng.choice(busy))
            # fork: pin a prefix of whatever the slot has materialized
            upto = min(cursor[slot], seq_len)
            if upto >= 1 and not forked[slot]:
                pin = alloc.capture(slot, int(rng.integers(1, upto + 1)))
                if pin is not None:
                    pins.append(pin)
                    forked[slot] = True  # the extra_reserve covers ONE CoW
        elif r < 0.92 and busy:
            slot = int(rng.choice(busy))
            alloc.retire(slot)
            cursor[slot] = -1
        elif pins:
            pin = pins.pop(int(rng.integers(len(pins))))
            alloc.release(pin.pin_id)
        if step % 50 == 0:
            # prune pins the pool reclaimed behind our back
            pins = [p for p in pins if p.pin_id in alloc._pins]
            alloc.check()
    pins = [p for p in pins if p.pin_id in alloc._pins]
    alloc.check()
    # drain everything: the pool must come back whole
    for slot in range(n_slots):
        if cursor[slot] >= 0:
            alloc.retire(slot)
    for pin in pins:
        alloc.release(pin.pin_id)
    alloc.check()
    assert alloc.free_pages == alloc.n_pages - 1, "pages leaked after drain"
    assert ops == 10_000


def test_allocator_budget_floor_and_deadlock_guard():
    """A page budget below one slot's residency (+ junk page + slack) must
    error at construction instead of deadlocking admission later; alloc
    past a slot's reservation is a hard error (the invariant's teeth)."""
    with pytest.raises(ValueError, match="minimal residency"):
        PageAllocator(n_pages=5, page_size=4, n_slots=2, pages_per_slot=5)
    alloc = PageAllocator(n_pages=8, page_size=4, n_slots=2, pages_per_slot=3)
    assert alloc.try_admit(0, (), 0)
    alloc.prepare_write(0, 0, 12)  # full residency: reservation spent
    with pytest.raises(RuntimeError, match="reservation"):
        alloc._alloc(0)


def test_scheduler_rejects_undersized_page_budget():
    with pytest.raises(ValueError, match="minimal residency"):
        DecodeScheduler(
            _params(), seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=2,
            kv_page_size=4, kv_pages=3,
        )


# ------------------------------------------ paged attention vs the plain forward


def test_paged_blocks_match_the_plain_forward_chunk_decode_and_verify_logits():
    """The paged gather/scatter attention against the teacher-forced forward
    (``sequence_logits``, no cache at all): a whole-prompt chunk, a decode
    step and a widened verify over the same pages give that forward's logits
    at their positions, with the junk-page redirection leaving the free
    slots' pages untouched."""
    from seldon_core_tpu.models.decoder import (
        paged_chunk_prefill, paged_decode_step, paged_kv_init, paged_verify_step, sequence_logits,
    )

    params = _params()
    ps, ctx = 4, SEQ + MAX_NEW
    pps = -(-ctx // ps)
    n_slots = 3
    rng = np.random.default_rng(5)
    ids = rng.integers(0, VOCAB, SEQ).astype(np.int32)
    slot = 1
    pool = paged_kv_init(params, 1 + n_slots * pps, ps)
    bt = np.zeros((n_slots, pps), np.int32)
    bt[slot] = np.arange(1 + slot * pps, 1 + (slot + 1) * pps)
    toks = np.zeros((n_slots, SEQ), np.int32)
    toks[slot] = ids
    zero = np.zeros(n_slots, np.int32)
    counts = np.zeros(n_slots, np.int32)
    counts[slot] = SEQ
    pl, _, pool = paged_chunk_prefill(params, pool, jnp.asarray(bt), jnp.asarray(toks), jnp.asarray(zero), jnp.asarray(counts))
    tok = int(np.argmax(np.asarray(pl[slot, SEQ - 1])))
    t1 = np.zeros(n_slots, np.int32)
    p1 = np.zeros(n_slots, np.int32)
    t1[slot], p1[slot] = tok, SEQ
    dl, _, pool = paged_decode_step(params, pool, jnp.asarray(bt), jnp.asarray(t1), jnp.asarray(p1))
    # junk writes from the free slots above landed only in page 0
    for other in range(n_slots):
        if other != slot:
            assert not np.any(np.asarray(pool[0][:, 1 + other * pps]))
    q = np.zeros((n_slots, 3), np.int32)
    q[slot] = [int(np.argmax(np.asarray(dl[slot]))), 4, 7]
    p1[slot] = SEQ + 1
    vl, _, _ = paged_verify_step(params, pool, jnp.asarray(bt), jnp.asarray(q), jnp.asarray(p1))
    want = np.asarray(sequence_logits(params, jnp.asarray([[*ids, tok, *q[slot]]])))[0]
    # the paged programs reduce over the page-rounded virtual length (20),
    # the plain forward over the sequence's own: XLA groups the reduction
    # lanes differently, so this comparison is reduction-order-tight, not
    # bitwise. Bitwise TOKEN equality vs the oracle is the scheduler-level
    # contract (test_paged_scheduler_* / test_decode_scheduler.py).
    tight = dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(pl[slot]), want[:SEQ], **tight)
    np.testing.assert_allclose(np.asarray(dl[slot]), want[SEQ], **tight)
    np.testing.assert_allclose(np.asarray(vl[slot]), want[SEQ + 1 :], **tight)


# ------------------------------------------------ the token-row pool layout


def _np_quant_rows(x):
    """The per-row asymmetric int8 quantiser, in plain numpy float32."""
    lo, hi = x.min(axis=1), x.max(axis=1)
    zp = (hi + lo) * np.float32(0.5)
    scale = np.maximum((hi - lo) / np.float32(254.0), np.float32(1e-8))
    q = np.clip(np.round((x - zp[:, None]) / scale[:, None]), -127, 127).astype(np.int8)
    return q, scale, zp


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
def test_paged_write_then_gather_round_trips_against_a_numpy_page_table(kv_dtype):
    """What one layer's write stores and its gather reads back, held to a
    plain numpy page table (a dict of token rows by (page, row)): a run
    crossing a page boundary, a ``counts`` mask, rows past the virtual
    length (both junk-redirected to page 0), unmapped table entries. Same
    values, bit for bit, as the head-major pool stored — only where they
    sit changed — and no other layer of the pool is touched."""
    from seldon_core_tpu.models.decoder import (
        _paged_gather, _paged_write, decoder_dims, paged_kv_init,
    )

    params = _params()
    d = decoder_dims(params)
    h, w = d["heads"], d["heads"] * d["head_dim"]
    n, m, ps, pps, li = 3, 5, 4, 3, 1
    n_pages = 1 + n * pps
    rng = np.random.default_rng(7)
    bt = np.arange(1, n_pages, dtype=np.int32).reshape(n, pps)
    bt[1, 2] = 0  # an unmapped tail entry: reads the junk page
    positions = np.array([2, 0, pps * ps - 2], np.int32)  # slot 2 runs off the end
    counts = np.array([m, 2, m], np.int32)  # slot 1 persists two rows only
    k = rng.standard_normal((n, m, w)).astype(np.float32)
    v = rng.standard_normal((n, m, w)).astype(np.float32)

    pool = _paged_write(
        paged_kv_init(params, n_pages, ps, kv_dtype=kv_dtype), li,
        jnp.asarray(k), jnp.asarray(v), jnp.asarray(bt), jnp.asarray(positions),
        jnp.asarray(counts),
    )
    got_k, got_v = (np.asarray(a) for a in _paged_gather(pool, li, jnp.asarray(bt), h))
    assert got_k.shape == (n, h, pps * ps, d["head_dim"])

    table_k, table_v = {}, {}  # (page, row) -> the token row read back
    for i in range(n):
        for j in range(int(counts[i])):
            pos = int(positions[i]) + j
            if pos >= pps * ps or bt[i, pos // ps] == 0:
                continue  # junk-redirected
            for table, src in ((table_k, k), (table_v, v)):
                row = src[i, j]
                if kv_dtype == "int8":
                    q, sc, zp = _np_quant_rows(row[None, :])
                    row = (q.astype(np.float32) * sc[:, None] + zp[:, None])[0]
                table[(int(bt[i, pos // ps]), pos % ps)] = row
    assert len(table_k) == 5 + 2 + 2
    for i in range(n):
        for pos in range(pps * ps):
            page = int(bt[i, pos // ps])
            if page == 0:
                continue  # the junk sink holds whatever was redirected
            for table, got in ((table_k, got_k), (table_v, got_v)):
                want = table.get((page, pos % ps), np.zeros(w, np.float32))
                np.testing.assert_array_equal(got[i, :, pos, :].reshape(w), want)
    for comp in pool:  # every other layer is still its init
        others = np.delete(np.asarray(comp), li, axis=0)
        assert np.all(others == others.flat[0])
    if kv_dtype == "int8":
        assert pool[0].dtype == jnp.int8 and pool[0].shape == (d["layers"], n_pages, ps, w)
        assert pool[1].shape == (d["layers"], n_pages, ps)
        assert np.abs(got_k[0, :, 2, :].reshape(w) - k[0, 0]).max() > 0  # it IS quantized
    else:
        assert pool[0].dtype == jnp.float32 and pool[0].shape == (d["layers"], n_pages, ps, w)


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
def test_paged_copy_moves_a_pages_rows_and_planes_together(kv_dtype):
    """Copy-on-write's primitive deals in page indices only: every layer's
    token rows of the source page, and in int8 mode their scale and
    zero-point planes, land in the destination page; no other page moves."""
    from seldon_core_tpu.models.decoder import (
        _paged_write, decoder_dims, paged_copy, paged_kv_init,
    )

    params = _params()
    d = decoder_dims(params)
    w = d["heads"] * d["head_dim"]
    ps, n_pages = 4, 6
    rng = np.random.default_rng(9)
    pool = paged_kv_init(params, n_pages, ps, kv_dtype=kv_dtype)
    bt = jnp.asarray([[1, 2]], jnp.int32)
    for li in range(d["layers"]):
        rows = jnp.asarray(rng.standard_normal((1, 2 * ps, w)).astype(np.float32))
        pool = _paged_write(pool, li, rows, -rows, bt, jnp.zeros(1, jnp.int32), None)
    before = [np.asarray(a) for a in pool]
    after = [
        np.asarray(a)
        for a in paged_copy(pool, jnp.asarray([2, 0], jnp.int32), jnp.asarray([4, 0], jnp.int32))
    ]
    assert len(after) == (6 if kv_dtype == "int8" else 2)
    for b, a in zip(before, after):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a[:, 4], b[:, 2])
        assert np.any(a[:, 4] != b[:, 4])  # the destination did change
        keep = [0, 1, 2, 3, 5]
        np.testing.assert_array_equal(a[:, keep], b[:, keep])


# ------------------------------------------------ the latent page kind


def _latent_pool(**kw):
    """A PagedKVPool over the latent-attention family's one-plane pages
    (models/mla_decoder.py): 3 layers, 2 slots of 4 pages of 4 rows."""
    from seldon_core_tpu.models import mla_decoder as mla
    from seldon_core_tpu.serving.kv_pool import PagedKVPool

    fam = mla.mla_family(mla.MLADecoderConfig(vocab=32, experts_held=16))
    params = mla.init_mla_decoder(fam.cfg, seed=0, dtype=jnp.float32)
    return fam, PagedKVPool(params, n_slots=2, cache_ctx=16, page_size=4, kv_init=fam.paged_kv_init, **kw)


def test_the_pool_sizes_warms_copies_and_resets_a_one_plane_latent_pool():
    """Nothing in the pool manager knows the page kind: the state is the
    family's tuple (ONE plane of latent rows, whole lane tiles wide), the
    copy ladder walks every component, a reset gives the plane back zeroed
    with every mapping dropped."""
    from seldon_core_tpu.models.decoder import _paged_write_latent

    fam, pool = _latent_pool(n_pages=12)
    (plane,) = pool.state
    assert plane.shape == (3, 12, 4, 128) and pool.pages_per_slot == 4 and fam.cfg.row_width == 128
    pool.warmup()
    warmed = pool.compile_count()  # the jit cache is keyed on ``paged_copy``: other pools of the process count in it
    assert warmed >= len(pool.copy_buckets) and len(pool.state) == 1
    assert pool.alloc.try_admit(0, [], 0)
    assert pool.alloc.prepare_write(0, 0, 8) == []  # two fresh pages
    rows = jnp.arange(8 * 128, dtype=jnp.float32).reshape(1, 8, 128) + 1.0
    bt = jnp.asarray(pool.block_tables(np.array([0])))
    pool.state = _paged_write_latent(pool.state, 1, rows, bt, jnp.zeros(1, jnp.int32), None)
    # a second reader shares the first page, then writes into it: copy-on-write through the ladder
    pin = pool.alloc.capture(0, 8)
    assert pool.alloc.try_admit(1, pin.pages, 6)
    copies = pool.alloc.prepare_write(1, 6, 1)
    assert len(copies) == 1 and copies[0][0] == pin.pages[1]
    before = np.asarray(pool.state[0])
    pool.run_copies(copies)
    after = np.asarray(pool.state[0])
    src, dst = copies[0]
    np.testing.assert_array_equal(after[:, dst], before[:, src])  # every layer's rows of the page
    assert after[1, dst].any() and not after[0, dst].any()  # layer 1 was written, layer 0 never
    keep = [p for p in range(12) if p != dst]
    np.testing.assert_array_equal(after[:, keep], before[:, keep])
    pool.alloc.check()
    assert pool.compile_count() == warmed  # the warmed ladder served it
    pool.reset()
    assert len(pool.state) == 1 and not np.asarray(pool.state[0]).any() and pool.alloc.free_pages == 11


def test_the_latent_write_lands_rows_by_position_and_sends_junk_to_page_0():
    """One scatter of [rows, c, width] through the block tables: a row at
    positions[i] + j lands in page bt[i, (pos + j) // ps], row (pos + j) % ps;
    rows beyond ``counts`` and a padding row's land in junk page 0."""
    from seldon_core_tpu.models.decoder import _paged_write_latent

    _, pool = _latent_pool(n_pages=12)
    bt = jnp.asarray([[3, 5, 7, 9], [0, 0, 0, 0]], jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(1.0, 7.0)[None, :, None], (2, 6, 128))
    (plane,) = _paged_write_latent(pool.state, 2, rows, bt, jnp.asarray([2, 0], jnp.int32), jnp.asarray([5, 0], jnp.int32))
    got = np.asarray(plane[2])
    assert got[3, 2:, 0].tolist() == [1.0, 2.0] and got[5, :3, 0].tolist() == [3.0, 4.0, 5.0]
    assert not got[5, 3:].any() and not got[7].any() and not got[9].any()  # the sixth row is past counts
    assert not np.asarray(plane[:2]).any()  # the other layers stand
    live = [p for p in range(1, 12) if p not in (3, 5)]
    assert not got[live].any()


# ------------------------------------------------ the write's two granules


def _random_pool(kind: str, layers: int, n_pages: int, ps: int, w: int, rng) -> tuple:
    """A pool of ``kind`` that already HOLDS something in every row, so a
    write that loses a row it should have kept shows."""
    from seldon_core_tpu.models.decoder import kv_pool_zeros

    d = {"kv_layers": layers, "kv_heads": 1, "head_dim": w, "kv_planes": 1 if kind == "latent" else 2}
    dtype = jnp.bfloat16 if kind == "bfloat16" else jnp.float32
    blank = kv_pool_zeros(d, n_pages, ps, dtype, "int8" if kind == "int8" else "")
    return tuple(
        jnp.asarray(rng.integers(-127, 128, a.shape), a.dtype) if a.dtype == jnp.int8
        else jnp.asarray(rng.standard_normal(a.shape), a.dtype)
        for a in blank
    )


def _write(pool, li, k, v, *rest):
    from seldon_core_tpu.models.decoder import _paged_write, _paged_write_latent

    return _paged_write_latent(pool, li, k, *rest) if len(pool) == 1 else _paged_write(pool, li, k, v, *rest)


def _spy_on_the_forms(monkeypatch) -> list:
    """The names of the write forms the dispatcher takes from here on, in order."""
    from seldon_core_tpu.models import decoder as dec

    took = []
    for form in ("_write_rows", "_write_pages"):
        real = getattr(dec, form)
        monkeypatch.setattr(dec, form, lambda *a, real=real, form=form: took.append(form) or real(*a))
    return took


@pytest.mark.parametrize("start_row", [0, 1, 15])
@pytest.mark.parametrize("m", [16, 64, 256, 48])
@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8", "latent"])
def test_the_page_form_leaves_the_pool_as_the_row_form_does(kind, m, start_row, monkeypatch):
    """A chunk's write goes by whole pages (``_write_pages``: read, merge by
    row mask, write); the pool after it is, outside junk page 0, bit for bit
    the pool after one scatter index a row (``_write_rows``), in every
    component. One dispatch holds every edge: a full row, a row whose count
    ends mid-page (its table shares the prefix pages with the first row's;
    neither writes them), a padding row (count 0, an all-junk table), a row
    whose positions run past the virtual length, and a row with a single
    valid entry; all start ``start_row`` rows into a page."""
    from seldon_core_tpu.models import decoder as dec

    ps, w, layers, li, lead = 16, 8, 3, 1, 3
    pw = m // ps + 1
    n_log = lead + pw + 2
    rng = np.random.default_rng(1000 * m + start_row)
    n = 5
    own = 1 + lead + np.arange(n * (n_log - lead), dtype=np.int32).reshape(n, n_log - lead)
    bt = np.concatenate([np.tile(np.arange(1, lead + 1, dtype=np.int32), (n, 1)), own], axis=1)
    bt[2] = 0  # the padding row of a ladder entry
    n_pages = int(bt.max()) + 3  # and two pages no table names
    start = lead * ps + start_row
    positions = np.array([start, start, 0, n_log * ps - m // 2 + start_row, start], np.int32)
    counts = np.array([m, m - ps // 2 - 3, 0, m, 1], np.int32)
    pool = _random_pool(kind, layers, n_pages, ps, w, rng)
    k = jnp.asarray(rng.standard_normal((n, m, w)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n, m, w)), jnp.float32)
    args = (li, k, v, jnp.asarray(bt), jnp.asarray(positions))

    took = _spy_on_the_forms(monkeypatch)
    for cnt in (jnp.asarray(counts), None):
        took.clear()
        by_page = _write(pool, *args, cnt)
        assert took == ["_write_pages"]  # m >= ps: the dispatcher's own choice
        with monkeypatch.context() as mp:
            mp.setattr(dec, "_write_pages", dec._write_rows)
            by_row = _write(pool, *args, cnt)
        assert len(by_page) == len(by_row) == {"int8": 6, "latent": 1}.get(kind, 2)
        # the pages a row form writes: row i's entries below its count, inside the virtual length
        c = counts if cnt is not None else np.full(n, m)
        owned = {int(bt[i, (positions[i] + j) // ps]) for i in range(n) for j in range(c[i])
                 if positions[i] + j < n_log * ps} - {0}
        assert len(owned) > pw and not owned & set(range(1, lead + 1))
        others = [p for p in range(1, n_pages) if p not in owned]
        for held, a, b in zip(pool, by_page, by_row):
            assert a.dtype == b.dtype == held.dtype and a.shape == held.shape
            raw = lambda x: np.asarray(x).view(np.uint16 if x.dtype == jnp.bfloat16 else None)  # noqa: E731
            np.testing.assert_array_equal(raw(a)[:, 1:], raw(b)[:, 1:])
            # pages the dispatch does not own, the shared prefix among them, and every other layer
            np.testing.assert_array_equal(raw(a)[:, others], raw(held)[:, others])
            np.testing.assert_array_equal(np.delete(raw(a), li, 0), np.delete(raw(held), li, 0))
            assert np.any(raw(a)[li, sorted(owned)] != raw(held)[li, sorted(owned)])


@pytest.mark.parametrize("kind", ["float32", "int8", "latent"])
def test_fewer_rows_than_a_page_take_the_row_form(kind, monkeypatch):
    """A step's one row a slot, a verify's few columns and the tree commit's
    path keep one scatter index a row: for them it is already the least
    bytes. The choice is the dispatch's static shape against the page size."""
    ps, w = 16, 8
    pool = _random_pool(kind, 2, 6, ps, w, np.random.default_rng(3))
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    took = _spy_on_the_forms(monkeypatch)
    for m in (1, 5, ps - 1, ps):
        rows = jnp.ones((2, m, w), jnp.float32)
        _write(pool, 0, rows, -rows, bt, jnp.asarray([14, 3], jnp.int32), None)
    assert took == ["_write_rows"] * 3 + ["_write_pages"]
    step = jax.make_jaxpr(lambda p, r: _write(p, 0, r, -r, bt, jnp.asarray([14, 3], jnp.int32), None))(
        pool, jnp.ones((2, 1, w), jnp.float32))
    shapes = {a.shape for a in pool}
    ops = [(e.primitive.name, e.invars[0].aval.shape) for e in step.jaxpr.eqns if e.invars]
    assert sum(name == "scatter" and shape in shapes for name, shape in ops) == len(pool)
    assert not [shape for name, shape in ops if name == "gather" and shape in shapes]  # a step reads no page back


# ------------------------------------------------ scheduler over the pool


async def test_paged_scheduler_cow_and_reclaim_zero_recompiles():
    """A tight explicit page budget under shared-prefix traffic drives the
    whole allocator surface — copy-free shares, boundary-page CoW, pin
    reclaim under pressure — while greedy output stays token-identical to
    the oracle and nothing recompiles after warmup (the tier-1 guard
    extended to the paged gather/CoW ladder)."""
    params = _params()
    ids = _shared_prompts(10, shared=5, seed=11)
    oracle = _oracle(params, ids)
    sched = _scheduler(
        params, n_slots=2, prefix_slots=4, prefill_chunk=4,
        kv_page_size=4, kv_pages=14,
    )
    base = sched.compile_counts()
    assert base["copy"] >= len(sched.pool.copy_buckets)
    out0 = await sched.submit(ids[0], cache_prefix=5)
    np.testing.assert_array_equal(out0, oracle[0])
    outs = await asyncio.gather(*(sched.submit(row) for row in ids[1:]))
    for row, out in zip(oracle[1:], outs):
        np.testing.assert_array_equal(out, row)
    a = sched.pool.alloc
    assert sched.stat_prefix_hits >= 8
    assert a.stat_pages_shared > 0, "prefix hits never mapped pages copy-free"
    assert a.stat_cow_copies > 0, "divergent writes never copy-on-wrote"
    assert sched.recompiles_since_warmup() == 0, sched.compile_counts()
    a.check()
    await sched.close()


async def test_paged_capacity_2x_flat_at_fixed_page_budget():
    """The acceptance criterion at test scale: page_size=16, a 56-token
    shared system prompt on a 64-token prompt bucket — at a fixed page
    budget the paged layout admits >= 2x the concurrent slots the
    flat-equivalent layout could hold in the same KV bytes (the shared
    pages are counted once pool-wide instead of per slot)."""
    params = _params()
    seq, max_new, ps = 64, 16, 16
    pages_per_slot = (seq + max_new + ps - 1) // ps  # 5
    budget = 1 + 4 + 8 * 2  # junk sink + pinned prefix + 8 sharers' tails
    flat_equiv_slots = (budget * ps) // (seq + max_new)  # same bytes, flat
    ids = _shared_prompts(11, seq=seq, shared=56, seed=3)
    sched = _scheduler(
        params, n_slots=8, seq_len=seq, max_new=max_new,
        prefix_slots=4, kv_page_size=ps, kv_pages=budget,
    )
    oracle = _oracle(params, ids, max_new)
    out0 = await sched.submit(ids[0], cache_prefix=56)
    np.testing.assert_array_equal(out0, oracle[0])
    outs = await asyncio.gather(*(sched.submit(row) for row in ids[1:]))
    for row, out in zip(oracle[1:], outs):
        np.testing.assert_array_equal(out, row)
    assert sched.pool.pages_per_slot == pages_per_slot
    assert sched.stat_prefix_hits == 10
    assert sched.stat_peak_active >= 2 * flat_equiv_slots, (
        sched.stat_peak_active, flat_equiv_slots
    )
    assert sched.pool.alloc.stat_pages_shared >= 10 * 3
    assert sched.recompiles_since_warmup() == 0
    await sched.close()


async def test_page_budget_throttles_admission_without_deadlock():
    """A budget too small for every slot still serves every request: the
    reservation check defers admission (counted) until retirements free
    pages — nothing deadlocks, everything stays oracle-identical."""
    params = _params()
    ids = _shared_prompts(6, shared=0, seed=9)  # no sharing: worst case
    oracle = _oracle(params, ids)
    sched = _scheduler(
        params, n_slots=4, kv_page_size=4,
        # pages_per_slot = ceil(18/4) = 5; budget fits ~2 slots, not 4
        kv_pages=12,
    )
    outs = await asyncio.gather(*(sched.submit(row) for row in ids))
    for row, out in zip(oracle, outs):
        np.testing.assert_array_equal(out, row)
    assert sched.stat_peak_active <= 2
    assert sched.stat_admit_blocked_rounds > 0
    sched.pool.alloc.check()
    assert sched.pool.alloc.free_pages == sched.pool.n_pages - 1
    await sched.close()


# --------------------------------------------------------------- int8 KV


def test_int8_kv_teacher_forced_logit_parity():
    """The tolerance-based parity test for quantized KV: the same token
    stream (teacher-forced from the fp pool, so quantization error cannot
    compound through token choices) decoded through the int8 pool yields
    logits within a small absolute tolerance at every step."""
    from seldon_core_tpu.models.decoder import (
        paged_chunk_prefill, paged_decode_step, paged_kv_init,
    )

    params = _params()
    ps, ctx = 4, SEQ + MAX_NEW
    pps = -(-ctx // ps)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, VOCAB, SEQ).astype(np.int32)
    pools = {
        "fp": paged_kv_init(params, 1 + pps, ps),
        "int8": paged_kv_init(params, 1 + pps, ps, kv_dtype="int8"),
    }
    bt = np.arange(1, 1 + pps, dtype=np.int32)[None, :]
    toks = ids[None, :]
    counts = np.array([SEQ], np.int32)
    zero = np.zeros(1, np.int32)
    logit_stream = {}
    for name in pools:
        lg, _, pools[name] = paged_chunk_prefill(
            params, pools[name], jnp.asarray(bt), jnp.asarray(toks),
            jnp.asarray(zero), jnp.asarray(counts),
        )
        logit_stream[name] = [np.asarray(lg[0, SEQ - 1])]
    tok = int(np.argmax(logit_stream["fp"][0]))
    for i in range(MAX_NEW - 1):
        t1 = np.array([tok], np.int32)
        p1 = np.array([SEQ + i], np.int32)
        for name in pools:
            lg, _, pools[name] = paged_decode_step(
                params, pools[name], jnp.asarray(bt), jnp.asarray(t1), jnp.asarray(p1)
            )
            logit_stream[name].append(np.asarray(lg[0]))
        tok = int(np.argmax(logit_stream["fp"][-1]))  # teacher-forced
    worst = max(
        float(np.abs(a - b).max())
        for a, b in zip(logit_stream["fp"], logit_stream["int8"])
    )
    assert worst < 0.25, f"int8 KV drifted {worst} in logits"
    assert worst > 0.0  # it IS quantized — identical would mean a bypass


async def test_int8_kv_scheduler_end_to_end():
    """int8 pool through the full scheduler: mixed shared-prefix traffic
    with chunking and CoW completes with well-formed outputs, high greedy
    agreement with the fp oracle, and zero recompiles."""
    params = _params()
    ids = _shared_prompts(6, shared=5, seed=21)
    oracle = _oracle(params, ids)
    sched = _scheduler(
        params, n_slots=2, prefix_slots=4, prefill_chunk=4,
        kv_page_size=4, kv_dtype="int8",
    )
    outs = await asyncio.gather(*(sched.submit(row) for row in ids))
    agree = total = 0
    for row, out in zip(oracle, outs):
        assert out.shape == row.shape and np.all(out >= 0) and np.all(out < VOCAB)
        np.testing.assert_array_equal(out[:SEQ], row[:SEQ])  # prompt echoed
        agree += int(np.sum(out[SEQ:] == row[SEQ:]))
        total += MAX_NEW
    # tolerance contract: most greedy tokens survive quantization on this
    # geometry (bit-exactness is the FP pool's contract, not int8's)
    assert agree / total > 0.5, f"int8 greedy agreement {agree}/{total}"
    assert sched.recompiles_since_warmup() == 0
    await sched.close()


# ------------------------------------------------------- serving wiring


def test_validation_rejects_bad_kv_knobs():
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
        _dep(decode_slots=4, decode_kv_page_size=16, decode_kv_pages=32,
             decode_kv_dtype="int8", decode_prefill_chunk=16)
    )
    # kv knobs without the scheduler would be silently ignored — refuse
    with pytest.raises(ValidationError, match="need decode_slots"):
        validate_deployment(_dep(decode_kv_dtype="int8"))
    with pytest.raises(ValidationError, match="need decode_slots"):
        validate_deployment(_dep(decode_kv_pages=32))
    with pytest.raises(ValidationError, match="unsupported"):
        validate_deployment(_dep(decode_slots=4, decode_kv_dtype="int4"))
    # chunk rounds must land on page boundaries with an explicit page size
    with pytest.raises(ValidationError, match="multiple of"):
        validate_deployment(
            _dep(decode_slots=4, decode_kv_page_size=16, decode_prefill_chunk=12)
        )
    # a budget below the configured concurrency is unservable as asked
    with pytest.raises(ValidationError, match="cannot host"):
        validate_deployment(_dep(decode_slots=8, decode_kv_pages=6))
    with pytest.raises(ValidationError, match="must be >= 0"):
        validate_deployment(_dep(decode_slots=4, decode_kv_pages=-1))


async def test_kv_pool_serving_wiring_metrics_and_spans():
    """TpuSpec kv knobs -> scheduler_for_executor -> warm serving: the
    pool geometry lands, occupancy gauges + share/CoW counters fire, and
    admission records the decode.kv_alloc span event."""
    from seldon_core_tpu.core.message import Meta, SeldonMessage
    from seldon_core_tpu.graph.spec import PredictorSpec
    from seldon_core_tpu.metrics import NullMetrics
    from seldon_core_tpu.serving.server import PredictorServer
    from seldon_core_tpu import telemetry

    class _Rec(NullMetrics):
        def __init__(self):
            self.pool_calls = []
            self.shared = 0
            self.cow = 0

        def decode_kv_pool(self, deployment, free, live, prefix):
            self.pool_calls.append((free, live, prefix))

        def decode_kv_shared(self, deployment, pages):
            self.shared += pages

        def decode_kv_cow(self, deployment, copies):
            self.cow += copies

    pred = PredictorSpec.model_validate(
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
                "max_batch": 4, "batch_buckets": [4], "decode_slots": 2,
                "decode_prefix_slots": 4, "decode_kv_page_size": 4,
            },
        }
    )
    server = PredictorServer(pred, deployment_name="d")
    sched = server.decode_scheduler
    assert sched is not None and sched.pool.page_size == 4
    rec = _Rec()
    sched._metrics = rec
    server.warmup()
    try:
        ids = _shared_prompts(2, shared=5, seed=13)
        await server.service.predict(
            SeldonMessage.from_array(ids[:1], meta=Meta(tags={"cache_prefix": 5}))
        )
        await server.service.predict(SeldonMessage.from_array(ids[1:]))
        assert sched.stat_prefix_hits >= 1
        assert rec.pool_calls, "pool occupancy gauge never set"
        free, live, prefix = rec.pool_calls[-1]
        assert free + live + prefix == sched.pool.n_pages - 1
        assert prefix > 0  # the captured prefix pin
        assert rec.shared >= 1 and rec.cow >= 1
        # the admission span carries the kv_alloc event: submit under an
        # explicit trace and inspect its buffer directly
        tracer = telemetry.Tracer(enabled=True)
        buf, root, token = tracer.begin_request("test", force=True)
        try:
            await sched.submit(ids[0])
        finally:
            tracer.finish_request(buf, root, token)
        admit_spans = [
            sp for sp in buf.spans if sp.name in ("decode.prefix_match", "decode.admit")
        ]
        assert admit_spans, [sp.name for sp in buf.spans]
        events = {ev.name for sp in admit_spans for ev in (sp.events or [])}
        assert "kv_alloc" in events
    finally:
        await sched.close()
        if server.batcher is not None:
            await server.batcher.close()


# ------------------------------------------------- the window page kind (PR 47)


def _two_kinds(n_slots=4, ps=4, pps=12, window=8, max_write=8, n_prefix=2, n_pages=0):
    from seldon_core_tpu.serving.kv_pool import ring_pages, window_pool_pages

    n_win = window_pool_pages(n_slots, n_prefix, window, max_write, ps)
    return PageAllocator(
        n_pages or n_slots * pps + 2, ps, n_slots, pps,
        window=(n_win, window, min(ring_pages(window, max_write, ps), pps)),
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_window_kind_invariants_random_admit_write_capture_retire_sequences(seed):
    """The soak of the one-kind allocator over both kinds: admissions (cold,
    and hits at the entry's whole length), writes of 1 to a chunk's positions
    (the window kind gives pages back inside ``prepare_write``), captures where
    the slot stands, retirements, releases, with both kinds' audit throughout;
    a slot never maps more window-kind pages than its ring, and both pools come
    back whole."""
    rng = np.random.default_rng(seed)
    n_slots, ps, pps, window, max_write = 4, 4, 12, 8, 8
    alloc = _two_kinds(n_slots, ps, pps, window, max_write)
    cursor = [-1] * n_slots
    forked = [False] * n_slots
    pins: list = []  # (pin, its span's length)
    for step in range(4000):
        free_slots = [s for s in range(n_slots) if cursor[s] < 0]
        busy = [s for s in range(n_slots) if 0 <= cursor[s]]
        r = rng.random()
        if r < 0.25 and free_slots:
            slot = int(rng.choice(free_slots))
            pin, length = pins[int(rng.integers(len(pins)))] if pins and rng.random() < 0.6 else (None, 0)
            if pin is not None and not alloc.pin_covers(pin.pin_id, length):
                pin, length = None, 0
            ok = alloc.try_admit(slot, pin.pages if pin else (), length, extra_reserve=1,
                                 pin_id=pin.pin_id if pin else -1)
            if ok:
                cursor[slot], forked[slot] = length, False
        elif r < 0.70 and busy:
            slot = int(rng.choice(busy))
            count = min(int(rng.integers(1, max_write + 1)), pps * ps - cursor[slot])
            if count > 0:
                for src, dst, *_kind in alloc.prepare_write(slot, cursor[slot], count):
                    assert src != dst and dst != 0
                cursor[slot] += count
                assert len(alloc.win.slot_pages(slot)) <= alloc.win.ring
        elif r < 0.82 and busy:
            slot = int(rng.choice(busy))
            if cursor[slot] >= 1 and not forked[slot]:
                pin = alloc.capture(slot, cursor[slot])  # where the slot stands: its last window is mapped
                if pin is not None:
                    pins.append((pin, cursor[slot]))
                    forked[slot] = True
                    assert len(pin.win_pages) <= -(-window // ps) + 1
        elif r < 0.93 and busy:
            slot = int(rng.choice(busy))
            alloc.retire(slot)
            cursor[slot] = -1
        elif pins:
            pin, _ = pins.pop(int(rng.integers(len(pins))))
            alloc.release(pin.pin_id)
        if step % 25 == 0:
            pins = [(p, n) for p, n in pins if p.pin_id in alloc._pins]
            alloc.check()
    for slot in range(n_slots):
        if cursor[slot] >= 0:
            alloc.retire(slot)
    for pin, _ in pins:
        alloc.release(pin.pin_id)
    alloc.check()
    assert alloc.free_pages == alloc.n_pages - 1 and alloc.win.free_pages == alloc.win.n_pages - 1
    assert alloc.win.stat_written > alloc.win.stat_released > 0


@pytest.mark.parametrize("write", [1, 3, 8], ids=["steps", "by3", "chunks"])
def test_window_kind_holds_a_ring_whatever_the_context(write):
    """One slot through its whole context: the full kind maps every page, the
    window kind the pages of [position - window + 1, position) and no more than
    its ring; a page wholly older is back on the free list before the next is
    taken, so another slot's write can have it in the same round."""
    alloc = _two_kinds(n_slots=2, pps=12, window=8, max_write=8)
    assert alloc.try_admit(0, (), 0) and alloc.try_admit(1, (), 0)
    assert (alloc.win._reserved[0], alloc._reserved[0]) == (alloc.win.ring, 12)
    pos = 0
    while pos < 48:
        n = min(write, 48 - pos)
        assert alloc.prepare_write(0, pos, n) == []
        pos += n
        mapped = alloc.win.slot_pages(0)
        assert alloc.win._lo[0] == max(0, pos - n - 8 + 1) // 4 and alloc.win._hi[0] == -(-pos // 4)
        assert len(mapped) <= alloc.win.ring and 0 not in mapped
        assert not alloc.win.block_tables[0, : alloc.win._lo[0]].any()  # given back: the table reads junk page 0
        alloc.check()
    assert len(alloc.slot_pages(0)) == 12 and alloc.win.stat_released == alloc.win.stat_written - len(alloc.win.slot_pages(0))
    freed = set(range(1, alloc.win.n_pages)) - set(alloc.win.slot_pages(0))
    alloc.prepare_write(1, 0, 8)
    assert set(alloc.win.slot_pages(1)) <= freed  # handed to another slot
    snap = alloc.snapshot()
    assert (snap["win_live"], snap["win_written"], snap["win_released"]) == (
        alloc.win.live_pages, alloc.win.stat_written, alloc.win.stat_released)


@pytest.mark.parametrize("length", [16, 18], ids=["page_end", "mid_page"])
def test_window_kind_pin_holds_the_last_window_and_cow_works_on_both_kinds(length):
    """A capture pins every full-kind page of its span and the window-kind
    pages of its last window; a hit maps exactly those; the writer and the
    reader each copy the boundary page in BOTH kinds where the span ends inside
    one; a hit at another length is not served; dropping the pin frees both."""
    alloc = _two_kinds(n_slots=3, pps=12, window=8, max_write=8)
    assert alloc.try_admit(0, (), 0, extra_reserve=1)
    for pos in range(0, length, 6):
        alloc.prepare_write(0, pos, min(6, length - pos))
    pin = alloc.capture(0, length)
    assert len(pin.pages) == -(-length // 4) and pin.win_first == (length - 8) // 4
    assert len(pin.win_pages) == -(-length // 4) - pin.win_first
    assert alloc.pin_covers(pin.pin_id, length) and not alloc.pin_covers(pin.pin_id, length - 8)
    with pytest.raises(ValueError, match="pin_covers"):
        alloc.try_admit(1, pin.pages, length - 8, pin_id=pin.pin_id)
    assert alloc.try_admit(1, pin.pages, length, pin_id=pin.pin_id)
    assert alloc.win.slot_pages(1) == pin.win_pages and alloc.slot_pages(1) == pin.pages
    want = 2 * (length % 4 != 0)  # one copy a kind
    for slot in (0, 1):
        copies = alloc.prepare_write(slot, length, 3)
        assert len(copies) == want and sorted(len(c) for c in copies) == [2, 3][: want]
        alloc.check()
    assert alloc.win.reclaimable() == (length % 4 != 0)  # both still map the pin's older pages; each has its own boundary page
    alloc.retire(0)
    alloc.retire(1)
    assert alloc.win.reclaimable() == len(pin.win_pages) and alloc.capture(1, length) is None
    alloc.release(pin.pin_id)
    alloc.check()
    assert alloc.win.free_pages == alloc.win.n_pages - 1 and alloc.free_pages == alloc.n_pages - 1


def test_window_kind_admission_is_by_kind_and_pressure_reclaims_pins():
    """Admission asks both kinds: with the window kind's pages promised away
    the third slot waits though the full kind has room; a prefix's pin-only
    pages count as reclaimable and go, LRU first, when a write finds the free
    list empty (the owner hears of it once)."""
    from seldon_core_tpu.serving.kv_pool import WindowPages

    alloc = _two_kinds(n_slots=3, pps=12, window=8, max_write=8, n_prefix=0)
    alloc.win = WindowPages(2 * alloc.win.ring + 2, 4, 3, 12, 8, alloc.win.ring)  # two rings + junk + slack
    alloc.win.on_empty = lambda: alloc._reclaim_until_free(alloc.win)
    heard = []
    alloc.on_pins_reclaimed = heard.append
    assert alloc.try_admit(0, (), 0) and alloc.try_admit(1, (), 0)
    assert not alloc.try_admit(2, (), 0) and alloc.free_pages - alloc.reserved_total() >= 12
    assert not alloc._mapped[2] and not alloc._reserved[2] and not alloc.win._reserved[2]  # neither kind mapped anything
    alloc.retire(1)
    alloc.prepare_write(0, 0, 8)
    pin = alloc.capture(0, 8)
    alloc.retire(0)  # the pin alone holds two window-kind pages now: reclaimable
    assert alloc.win.reclaimable() == 2 and alloc.try_admit(1, (), 0) and alloc.try_admit(2, (), 0)
    for start, n in ((0, 1), (1, 8), (9, 8)):  # from a page's second row: both slots reach their whole ring
        for slot in (1, 2):
            alloc.prepare_write(slot, start, n)
        alloc.check()
    assert len(alloc.win.slot_pages(1)) == len(alloc.win.slot_pages(2)) == alloc.win.ring
    assert heard == [[pin.pin_id]] and alloc.stat_pin_reclaims == 1 and pin.pin_id not in alloc._pins
    with pytest.raises(RuntimeError, match="past its ring"):
        alloc.win.prepare_write(1, 17, 48)  # a write wider than the ring was sized for
