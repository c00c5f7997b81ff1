"""The decode scheduler's program set (serving/decode_programs.py): one
convention a round kind whatever the deployment, every handle warmed and
counted in one class, and a decoder family that is asked, not recognised.

The rounds that call the set are covered where they always were
(test_decode_scheduler, test_flight_recorder, test_feature_draft,
test_spec_tree, test_moe_decoder); here the set itself is driven with the
scheduler's argument shapes, one deployment of each convention."""

import asyncio
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import moe_decoder as md
from seldon_core_tpu.models.decoder import (
    FamilyNotServed,
    decoder_family,
    generate,
    gpt2_family,
    init_decoder,
    init_feature_draft,
    require_served,
)
from seldon_core_tpu.serving.decode_scheduler import DecodeScheduler, _PendingAdmit, _Seq

SEQ, MAX_NEW, N = 8, 6, 2
MOE = md.moe_family(
    md.MoEDecoderConfig(
        vocab=96, hidden=64, layers=4, heads=4, kv_heads=2, head_dim=16, ffn=32, experts=4,
        experts_per_tok=2, window=8, period=4, rope_theta=10000.0, yarn_factor=4.0, yarn_original=16,
    )
)
# convention -> (mode the set reports, compile_counts keys)
CONVENTIONS = {
    "gpt2": ("", {"step", "chunk", "copy"}),
    "counting": ("", {"step", "chunk", "copy"}),
    "chain": ("chain", {"step", "chunk", "copy", "draft", "verify", "draft_admit"}),
    "tree": ("tree", {"step", "chunk", "copy", "draft_tree", "tree_verify", "draft_admit"}),
    "feature": ("feature", {"step_f", "chunk_f", "copy", "draft_feat", "ftree_verify"}),
}


@functools.lru_cache(maxsize=None)
def _sched(convention: str) -> DecodeScheduler:
    """One warmed scheduler a convention, shared by the module's cases."""
    kw = dict(seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=N, kv_page_size=4)
    if convention == "counting":
        params = md.init_moe_decoder(MOE.cfg, seed=0, dtype=jnp.float32)
        kw["family"] = MOE
    else:
        params = init_decoder(seed=3, vocab=128, hidden=64, layers=2, ffn=128, max_len=64)
    if convention in ("chain", "tree"):
        kw["draft_params"] = init_decoder(seed=5, vocab=128, hidden=64, layers=1, ffn=64, max_len=64)
    if convention == "feature":
        kw["draft_params"] = init_feature_draft(seed=3, vocab=128, hidden=64, ffn=128, max_len=64)
    if convention == "chain":
        kw["spec_k"] = 2
    if convention in ("tree", "feature"):
        kw["spec_tree"] = "2,1"
    s = DecodeScheduler(params, **kw)
    s.warmup()
    return s


def _inputs(s: DecodeScheduler):
    zi, zf = np.zeros(N, np.int32), np.zeros(N, np.float32)
    # all-zero block tables, counts 0, no generating row: junk page 0 only
    return s.pool.block_tables(), zi, zf, np.zeros(N, bool)


@pytest.mark.parametrize("kind", ["step", "chunk"])
@pytest.mark.parametrize("convention", list(CONVENTIONS))
def test_step_and_chunk_keep_one_contract_under_every_convention(convention, kind):
    """``step`` and ``chunk`` take the same arguments and return ``(out,
    read)`` with ``read() -> (tokens[rows], counts | None)`` whether the
    program also takes ``rows``, round-trips a feature buffer, or appends a
    counting family's counts to its readback — and compile nothing that
    ``warmup`` had not."""
    s = _sched(convention)
    p = s.programs
    mode, keys = CONVENTIONS[convention]
    assert p.mode == mode and set(p.compile_counts()) == keys
    base = p.compile_counts()
    bt, zi, zf, rows = _inputs(s)
    tick = np.int32(7)
    if kind == "step":
        out, read = p.step(bt, zi, zi, zf, zi, tick, rows)
    else:
        rows, bucket = s.chunk_buckets[0]
        assert rows == N  # two slots: the compact width is the full width
        out, read = p.chunk(bt, np.zeros((N, bucket), np.int32), zi, zi, zf, zi, tick)
    jax.block_until_ready(out)  # what a sync-timing run blocks on
    toks, counts = read()
    assert toks.shape == (N,) and toks.dtype == np.int32
    if convention == "counting":
        assert out.shape == (N + len(MOE.frame_counters),)
        assert counts.shape == (len(MOE.frame_counters),)
        assert int(counts[0]) == 0  # moe_rows: no row was real
    else:
        assert counts is None and out.shape == (N,)
    assert p.compile_counts() == base
    assert s.recompiles_since_warmup() == 0


@pytest.mark.parametrize("convention", ["chain", "tree", "feature"])
def test_draft_and_verify_keep_one_contract_under_every_convention(convention):
    """A speculative pair is ``draft`` then ``verify`` whatever proposes
    (k-chain, token tree, feature head) and reads back ``(out_tokens
    [n, depth + 1], n_accepted [n])``; the draft cache and the feature
    carry stay the set's."""
    s = _sched(convention)
    p = s.programs
    base = p.compile_counts()
    bt, zi, zf, rows = _inputs(s)
    tick = np.int32(9)
    wlimits = None if s.spec_tree is None else np.zeros((N, s.spec_tree.depth), np.int32)
    proposal = p.draft(zi, zi, zf, zi, tick)
    jax.block_until_ready(proposal)
    (out_dev, acc_dev), read = p.verify(bt, zi, proposal, zi, zf, zi, zi, wlimits, rows, tick)
    out_t, acc = read()
    assert out_t.shape == (N, s.spec_k + 1) and acc.shape == (N,)
    assert not acc.any()  # limits 0: nothing may be accepted
    assert not p.dck.is_deleted() and not s.pool.state[0].is_deleted()
    assert (p.feat is not None) == (convention == "feature")
    assert p.compile_counts() == base
    assert bool(p.admit_buckets) == (convention != "feature")  # the head's prompt K/V rides the chunk ladder


# ---- the chunk dispatch's batch is the slots that prefill (ISSUE 33) ----
# 16 slots, 40-token prompts in chunks of at most 20: c climbs from 16, two
# rows at every c, four rows at the top c only, and there the ladder ends: a
# round takes at most four slots, the first arrivals (ISSUE 44)
WIDE, WSEQ, CAP, WNEW = 16, 40, 20, 3
LADDER = ((2, 16), (2, 20), (4, 20))
ROWS_CAP = 4


def _wide(family: str, vocab: int):
    """(scheduler over 16 slots, not warmed; greedy oracle). A test that
    counts compiles passes a ``vocab`` no other scheduler of the process has."""
    kw = dict(seq_len=WSEQ, max_new_tokens=WNEW, n_slots=WIDE, kv_page_size=4, prefill_chunk=CAP)
    if family == "counting":
        fam = md.moe_family(md.MoEDecoderConfig(**{**MOE.cfg.__dict__, "vocab": vocab}))
        params = md.init_moe_decoder(fam.cfg, seed=0, dtype=jnp.float32)
        return DecodeScheduler(params, family=fam, **kw), lambda ids: fam.generate(params, ids, WNEW)
    params = init_decoder(seed=3, vocab=vocab, hidden=64, layers=2, ffn=128, max_len=64)
    return DecodeScheduler(params, **kw), lambda ids: generate(params, ids, WNEW)


@functools.lru_cache(maxsize=None)
def _wide_warm(family: str) -> DecodeScheduler:
    s, _ = _wide(family, vocab=96)
    s.warmup()
    return s


def _chunk_dispatch(s, pool0, bt, slots, ids, pos, counts):
    """One greedy chunk dispatch from the pool bytes ``pool0`` whose rows
    are ``slots`` of the per-slot inputs (-1: a padding row, count 0, block
    table row of junk page 0): (a token a slot, counts, the pool after)."""
    slots = np.asarray(slots)

    def take(x):
        return np.where((slots >= 0).reshape((-1,) + (1,) * (x.ndim - 1)), x[slots], 0).astype(x.dtype)

    s.pool.state = tuple(jnp.asarray(a) for a in pool0)
    z = np.zeros(len(slots))
    _out, read = s.programs.chunk(
        take(bt), take(ids), take(pos), take(counts), z.astype(np.float32), z.astype(np.int32), np.int32(5)
    )
    toks, counted = read()
    return {int(i): int(toks[r]) for r, i in enumerate(slots) if i >= 0}, counted, [np.asarray(a) for a in s.pool.state]


@pytest.mark.parametrize("live", [(3, 11), (11,)], ids=["two_rows", "one_row_and_padding"])
@pytest.mark.parametrize("family", ["gpt2", "counting"])
def test_a_compact_chunk_dispatch_is_the_full_width_one_for_its_slots(family, live):
    """Slots 3 and 11 prefill (a cold 16-token chunk; 13 tokens from position
    8), slot 5 generates: the 2-row dispatch over ``bt[[3, 11]]`` gives those
    slots the tokens and the pool pages the 16-row dispatch gives them, and
    neither touches another live page; a padding row writes junk page 0 only."""
    s = _wide_warm(family)
    n_log, ps = s.pool.pages_per_slot, s.pool.page_size
    rng = np.random.default_rng(0)
    pool0 = [rng.normal(size=a.shape).astype(a.dtype) for a in s.pool.state]
    bt = np.zeros((WIDE, n_log), np.int32)
    # the pages the three slots' positions reach, from either page kind's planes (a window kind has fewer pages)
    reach = min(n_log, (min(a.shape[1] for a in s.pool.state) - 1) // 3)
    for j, slot in enumerate((3, 11, 5)):
        bt[slot, :reach] = 1 + j * reach + np.arange(reach)
    ids = rng.integers(0, 96, (WIDE, 16)).astype(np.int32)
    pos, counts = np.zeros(WIDE, np.int32), np.zeros(WIDE, np.int32)
    pos[11] = 8
    counts[list(live)] = [16, 13][2 - len(live):]
    written = {int(bt[i, p // ps]) for i in live for p in range(pos[i], pos[i] + counts[i])}
    toks_c, counted_c, pool_c = _chunk_dispatch(s, pool0, bt, list(live) + [-1] * (2 - len(live)), ids, pos, counts)
    toks_w, counted_w, pool_w = _chunk_dispatch(s, pool0, bt, np.arange(WIDE), ids, pos, counts)
    assert toks_c == {i: toks_w[i] for i in live}
    if family == "counting":
        assert int(counted_c[0]) == int(counted_w[0]) == int(counts.sum())  # moe_rows: real rows only
        np.testing.assert_array_equal(counted_c, counted_w)
    else:
        assert counted_c is None
    for a0, ac, aw in zip(pool0, pool_c, pool_w):
        untouched = [p for p in range(1, a0.shape[1]) if p not in written]
        np.testing.assert_array_equal(ac[:, untouched], a0[:, untouched])  # slot 5's pages among them
        np.testing.assert_array_equal(aw[:, untouched], a0[:, untouched])
        np.testing.assert_allclose(ac[:, sorted(written)], aw[:, sorted(written)], rtol=1e-5, atol=1e-6)
        assert not np.array_equal(ac[:, sorted(written)], a0[:, sorted(written)])


def _spy_on_the_selection(s):
    """Record every call of ``_chunk_rows_taken`` as (uids with a chunk to
    run in slot order, uids taken in slot order)."""
    calls, taken = [], s._chunk_rows_taken

    def spy(rows):
        out = taken(rows)
        calls.append(([r[1] for r in rows], [r[1] for r in out]))
        return out

    s._chunk_rows_taken = spy
    return calls


@pytest.mark.parametrize("family", ["gpt2", "counting"])
async def test_a_chunk_round_dispatches_at_the_first_entry_that_holds_its_slots(family):
    """k prefilling slots dispatch at the smallest rows entry >= k; a wave of
    ``n_slots`` over a 4-row bound rides rounds of at most four live rows, the
    oldest ``uid``s first, and the frames count the slots each round left for
    a later one; a mixed sequence of both compiles nothing after ``warmup``,
    which compiled the ladder's entries and no more (no full-width program);
    the frames count the rows dispatched and the slots live in them; every
    request reads the greedy oracle's tokens whichever round and row it rode."""
    s, oracle = _wide(family, vocab={"gpt2": 160, "counting": 112}[family])
    assert s.chunk_buckets == LADDER and s.chunk_rows_cap == ROWS_CAP
    base = s.compile_counts()["chunk"]
    s.warmup()
    assert s.compile_counts()["chunk"] == base + len(LADDER)
    calls = _spy_on_the_selection(s)
    prompts = np.random.default_rng(1).integers(0, 96, (23, WSEQ)).astype(np.int32)
    want = np.asarray(oracle(jnp.asarray(prompts)))
    got = [await s.submit(prompts[0])]
    rounds = {}
    for lo, hi in ((1, 3), (3, 19), (19, 22), (22, 23)):  # two together, a wave of 16, three, one
        rounds[hi - lo] = s.flight.rounds
        got += await asyncio.gather(*(s.submit(p) for p in prompts[lo:hi]))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert s.recompiles_since_warmup() == 0
    assert s.compile_counts()["chunk"] == base + len(LADDER)
    frames = [f for f in s.flight.snapshot() if f.chunk_rows]
    assert len(frames) == s.stat_chunk_dispatches
    for f in frames:
        assert f.busy_ns[0] > 0 and f.chunk_rows == next(r for r in (2, ROWS_CAP) if r >= f.chunk_rows_live)
        assert f.to_dict()["chunk_rows"] == [f.chunk_rows_live, f.chunk_rows]
        assert f.to_dict().get("chunk_rows_held", 0) == f.chunk_rows_held
    assert sum(f.chunk_rows_live for f in frames) == 23 * 2  # every prompt is two chunks of 20
    assert {1, 2, 3, ROWS_CAP} == {f.chunk_rows_live for f in frames}
    assert not any(f.chunk_rows or f.chunk_rows_held for f in s.flight.snapshot() if f.busy_ns[0] == 0)
    # the wave: four slots a round by arrival, each four's first chunk and then its second
    wave = [f for f in frames if rounds[16] <= f.seq < rounds[3]]
    assert [f.chunk_rows_held for f in wave] == [12, 12, 8, 8, 4, 4, 0, 0]
    assert [f.chunk_rows_held for f in wave[::2]] == [12, 8, 4, 0]  # over its first chunk's rounds
    assert all(f.chunk_rows_live == f.chunk_rows == ROWS_CAP for f in wave)
    assert sum(f.chunk_rows_held for f in frames) == s.stat_chunk_rows_held == 48
    assert not any(f.chunk_rows_held for f in frames if f not in wave)
    assert len(calls) >= len(frames)
    for had, took in calls:
        assert took == [u for u in had if u in sorted(had)[:ROWS_CAP]]  # the oldest, still in slot order
    await s.close()


PLAN_CASES = {
    # slots waiting with their uid's rank by arrival -> the slots the round takes, in slot order
    "two_slots": ({}, [3, 11]),
    "three_slots_in_four_rows": ({7: 2}, [3, 7, 11, -1]),
    "five_slots_and_the_newest_waits": ({7: 2, 0: 3, 15: 4}, [0, 3, 7, 11]),
    "five_slots_and_a_middle_one_waits": ({7: 4, 0: 2, 15: 3}, [0, 3, 11, 15]),
}


@pytest.mark.parametrize("case", list(PLAN_CASES))
async def test_the_overlap_built_chunk_plan_is_the_serial_build(case):
    """``_pipeline_plan_chunk`` (under the previous dispatch) and the serial
    chunk round go through one selection and one builder: the same slots with
    a chunk to run give the same key and the same arrays, compact in slot
    order with padding after them; past the ladder's widest entry both leave
    the same slot out, the plan is taken (no discard), and the slot left out
    is in no array."""
    pending, want = PLAN_CASES[case]
    s = _wide_warm("gpt2")
    loop = asyncio.get_running_loop()
    rng = np.random.default_rng(2)
    rows = []
    for slot, pp, temp, rank in [(3, 0, 0.0, 0), (11, 20, 0.7, 1)] + [(i, 8, 0.0, r) for i, r in pending.items()]:
        seq = _Seq(rng.integers(0, 96, WSEQ).astype(np.int32), WNEW, temp, 0, 0, None, loop.create_future())
        seq.uid, seq.chunk_cap, seq.prefilling, seq.prefill_pos = 100 + rank, CAP, True, pp
        rows.append((slot, seq.uid, pp, min(CAP, WSEQ - pp), seq))
    used = s.stat_pipeline_plans_used
    try:
        for slot, _uid, _pp, _c, seq in rows[:2]:
            s._slots[slot] = seq
        for slot, _uid, pp, _c, seq in rows[2:]:  # admissions decided under the flight, not installed yet
            s._pending_admits.append(_PendingAdmit(seq, slot, None, pp, 0))
        s._pipeline_plan_chunk()
        rows.sort(key=lambda r: r[0])
        taken = s._chunk_rows_taken(rows)  # what the serial round does with its slots
        assert [r[0] for r in taken] == [i for i in want if i >= 0]
        assert len(rows) - len(taken) == max(0, len(rows) - ROWS_CAP)
        plan = s._pipeline_take_chunk_plan(tuple(r[:4] for r in taken))
        assert plan is not None and s._pending_chunk_plan is None
        assert s.stat_pipeline_plans_used == used + 1
        serial = s._chunk_input_arrays(taken)
        for a, b in zip(plan[1:], serial):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
        slots, ids, pos, counts, temps, _topks = serial
        assert (len(slots), ids.shape[1]) in LADDER
        assert slots.tolist() == want
        r11 = slots.tolist().index(11)
        assert (pos[r11], counts[r11], temps[r11]) == (20, 20, np.float32(0.7))
        np.testing.assert_array_equal(ids[r11, :20], next(r[4] for r in rows if r[0] == 11).prompt[20:40])
        # (the slot axis is the last but one: a pool of two page kinds hands [2, rows, pages])
        np.testing.assert_array_equal(
            s.pool.block_tables(slots)[..., slots >= 0, :], s.pool.block_tables()[..., slots[slots >= 0], :]
        )
        assert not s.pool.block_tables(slots)[..., slots < 0, :].any()
    finally:
        s._slots[3] = s._slots[11] = None
        s._pending_admits.clear()
        for r in rows:
            r[4].future.cancel()


# ---- a slot a round leaves out keeps its state (ISSUE 44): the two families with state rows ----
SSLOTS, HINT = 8, 12


def _stateful(family: str, **kw):
    """(model spec, scheduler over 8 slots, not warmed) of a recurrent or a
    short-convolution family at its own tests' rehearse size: 24-token
    prompts in chunks of at most 16, a 12-token hint's boundary ends a chunk."""
    from tests import test_conv_decoder, test_hybrid_decoder

    mod = {"hybrid": test_hybrid_decoder, "hybrid_experts": test_hybrid_decoder, "conv": test_conv_decoder}[family]
    ms = mod._nzoo() if family == "hybrid_experts" else mod._zoo()  # the hybrid family's single-sublayer shape (PR 51)
    return ms, mod._sched(ms, n_slots=SSLOTS, **kw)


def _spy_on_the_state_rows(s, calls):
    """Wrap ``programs.chunk``: for every chunk dispatch record the slots left
    out of it (from the selection's last call) and, of every state array, the
    rows those slots own and the rows they have yet to read, before and after."""
    chunk = s.programs.chunk

    def rows_of(idx):
        return [np.asarray(a)[idx] for a in s.pool.recurrent]

    seen = []

    def spy(*args):
        had, took = calls[-1]
        left = [i for i, q in enumerate(s._slots) if q is not None and q.uid in had and q.uid not in took]
        idx = left + [s._slots[i].state_src for i in left if s._slots[i].state_src >= 0]
        before = rows_of(idx)
        out = chunk(*args)
        seen.append((left, before, rows_of(idx)))
        return out

    s.programs.chunk = spy
    return seen


@pytest.mark.parametrize("family", ["hybrid", "hybrid_experts", "conv"])
async def test_a_slot_left_out_of_a_round_keeps_its_state_rows(family):
    """Eight admissions that hit one hinted prefix over a 4-row bound: the
    round takes the four oldest, and the four it leaves out keep their own
    state and conv rows and the snapshot row they have yet to read bit for bit
    over the rounds they sit out; all eight restore from the snapshot and read
    the oracle's tokens; nothing compiles after ``warmup``."""
    ms, s = _stateful(family)
    assert s.chunk_rows_cap == ROWS_CAP and max(r for r, _c in s.chunk_buckets) == ROWS_CAP
    s.warmup()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 96, (9, s.seq_len)).astype(np.int32)
    prompts[1:, :HINT] = prompts[0, :HINT]
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    np.testing.assert_array_equal(await s.submit(prompts[0], cache_prefix=HINT), oracle[0])
    calls = _spy_on_the_selection(s)
    seen = _spy_on_the_state_rows(s, calls)
    for got, want in zip(await asyncio.gather(*(s.submit(p) for p in prompts[1:])), oracle[1:]):
        np.testing.assert_array_equal(got, want)
    sat_out = [(left, before, after) for left, before, after in seen if left]
    assert [len(left) for left, _b, _a in sat_out] == [4]  # one chunk each past the prefix: 12 tokens
    for _left, before, after in sat_out:
        assert len(before) == len(s.pool.recurrent) >= 6
        for b, a in zip(before, after):
            np.testing.assert_array_equal(a, b)
    frames = s.flight.snapshot()
    assert [f.chunk_rows_held for f in frames if f.chunk_rows][-2:] == [4, 0]
    assert sum(f.state_restores for f in frames) == 8 and s.stat_prefix_hits == 8
    assert s.recompiles_since_warmup() == 0
    s.pool.alloc.check()
    await s.close()


async def test_no_round_writes_a_snapshot_row_that_a_slot_it_left_out_has_yet_to_read():
    """One snapshot row, bound to prefix A's entry. A wave of four cold hinted
    requests (prefix B, older) and four that hit A (newer): the first round
    takes the four cold ones, each wants a row for its own boundary, the only
    one is A's and evicting A's entry would free it, but four waiting slots
    have yet to read it: the round captures nothing and evicts nothing, and
    the four read A's state."""
    ms, s = _stateful("hybrid", prefix_slots=1)
    s.warmup()
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, 96, (9, s.seq_len)).astype(np.int32)
    prompts[5:, :HINT] = prompts[0, :HINT]  # A
    prompts[2:5, :HINT] = prompts[1, :HINT]  # B
    oracle = np.asarray(jax.jit(ms.apply_fn)(ms.params, jnp.asarray(prompts)))
    np.testing.assert_array_equal(await s.submit(prompts[0], cache_prefix=HINT), oracle[0])
    row = next(iter(s._prefix_index.entries.values())).state_row
    kept = [np.asarray(a)[row] for a in s.pool.recurrent]
    calls = _spy_on_the_selection(s)
    seen = _spy_on_the_state_rows(s, calls)
    skips = s.stat_prefix_capture_skips
    entry, evictions = next(iter(s._prefix_index.entries.values())), s._prefix_index.evictions
    first = s._chunk_round

    async def one_round():
        await first()
        if len(seen) == 1:  # the round that left the four out: A's entry outlived it
            assert s._prefix_index.entries.get(entry.pin_id) is entry and s._prefix_index.evictions == evictions

    s._chunk_round = one_round
    outs = await asyncio.gather(*(s.submit(p, cache_prefix=HINT if i < 4 else None) for i, p in enumerate(prompts[1:])))
    for got, want in zip(outs, oracle[1:]):
        np.testing.assert_array_equal(got, want)
    left, before, after = seen[0]
    assert len(left) == 4
    for b, a, k in zip(before, after, kept):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a[-1], k)  # the last row recorded is the one they wait for
    assert s.stat_prefix_hits == 4 and s.stat_prefix_capture_skips >= skips + 4
    s.pool.alloc.check()
    await s.close()


# ---- a slot the rounds have left out so far writes nothing into the prefix it hit (ISSUE 44) ----
PSLOTS, PNEW, PHINT = 8, 16, 14  # pages of 4 rows: position 14 is row 2 of the boundary page the readers share


@pytest.mark.parametrize("round_kind", ["plain", "chain"])
async def test_a_slot_left_out_of_a_round_writes_nothing_into_the_prefix_it_hit(round_kind):
    """An entry of a whole 40-token prompt (a retired request's) and a live
    donor's hinted 14 tokens, the donor still generating; six admissions hit
    one or the other at depth 14, which is no page's edge, so each maps a
    boundary page it shares. Two rounds take the four oldest and the two they
    leave out ride the donor's step (or speculative round) at their cursor,
    position 14:
    what the dispatch writes for them goes to the junk page, not to row 2 of
    the shared page, which is the longer entry's position 14 and the donor's
    own. The entries' pages end bit for bit as they were, and the donor, the
    six and a later reader of the whole long prompt read the oracle's tokens."""
    params = init_decoder(seed=3, vocab=96, hidden=64, layers=2, ffn=128, max_len=64)
    kw = dict(seq_len=WSEQ, max_new_tokens=PNEW, n_slots=PSLOTS, kv_page_size=4, prefill_chunk=CAP, prefix_slots=8)
    if round_kind == "chain":
        kw.update(draft_params=init_decoder(seed=5, vocab=96, hidden=64, layers=1, ffn=64, max_len=64), spec_k=2)
    s = DecodeScheduler(params, **kw)
    s.warmup()
    rng = np.random.default_rng(4)
    prompts = rng.integers(0, 96, (8, WSEQ)).astype(np.int32)  # 0: the long entry's, 1: the donor's, then the six
    prompts[2::2, :PHINT], prompts[3::2, :PHINT] = prompts[0, :PHINT], prompts[1, :PHINT]
    prompts[2:, PHINT] = (prompts[(0, 1) * 3, PHINT] + 1) % 96  # and not a token further
    want = np.asarray(generate(params, jnp.asarray(prompts), PNEW))
    np.testing.assert_array_equal(await s.submit(prompts[0]), want[0])
    donor = asyncio.ensure_future(s.submit(prompts[1], cache_prefix=PHINT))
    while s.stat_prefix_captures < 2:
        await asyncio.sleep(0)
    entries = list(s._prefix_index.entries.values())
    assert sorted(e.length for e in entries) == [PHINT, WSEQ]
    pages = sorted({p for e in entries for p in e.pages})
    held = [np.asarray(a)[:, pages] for a in s.pool.state]
    wave = s.flight.rounds
    got = await asyncio.gather(*(s.submit(p) for p in prompts[2:]))
    # the round that left two out went on to the donor's step, or its draft and verify
    assert [f.mode for f in s.flight.snapshot() if f.chunk_rows_held] == [round_kind] * 2
    for g, w in zip([await donor] + got, want[1:]):
        np.testing.assert_array_equal(g, w)
    assert s.stat_prefix_hits == 6 and s.stat_prefix_tokens_saved == 6 * PHINT
    # 26 tokens past the hit are two chunks: the four oldest ride both before the last two ride theirs
    assert [f.chunk_rows_held for f in s.flight.snapshot() if f.seq >= wave and f.chunk_rows] == [2, 2, 0, 0]
    assert s.stat_chunk_rows_held == 4
    assert all(e.pin_id in s._prefix_index.entries for e in entries)
    for a, b in zip(s.pool.state, held):
        np.testing.assert_array_equal(np.asarray(a)[:, pages], b)
    np.testing.assert_array_equal(await s.submit(prompts[0]), want[0])
    assert s.stat_prefix_hits == 7 and s.stat_prefix_tokens_saved == 6 * PHINT + WSEQ - 1
    assert s.recompiles_since_warmup() == 0
    s.pool.alloc.check()
    await s.close()


@pytest.mark.parametrize("mechanism, message", [
    ("speculation", "speculative decoding (draft, tree, feature head) is not served for the 'moe' decoder family"),
    ("decode_mesh", "tensor-parallel decode (parallel/tp.py) is not served for the 'moe' decoder family"),
])
@pytest.mark.parametrize("family", ["gpt2", "moe"])
def test_a_family_answers_what_it_serves(family, mechanism, message):
    """Asked, not recognised: the GPT-2 family serves both; the
    sparse-expert family refuses each by name, in the scheduler's build too."""
    if family == "gpt2":
        assert decoder_family(None) is gpt2_family
        require_served(gpt2_family, mechanism)
        assert "attn_kernel" in gpt2_family.serves and gpt2_family.frame_counters == ()
        return
    with pytest.raises(FamilyNotServed) as e:
        require_served(MOE, mechanism)
    assert str(e.value) == message
    # what it keeps serving of the mechanisms PR 34 named for the third family to refuse
    # (two page kinds since PR 47: the tiers and prefix export move a prefix as ONE list of pages, and are refused),
    # and since PR 48 a step that reads the pool in place
    assert decoder_family(MOE) is MOE and MOE.serves == {"attn_kernel", "kv_int8"} and MOE.cfg.two_kinds
    params = md.init_moe_decoder(MOE.cfg, seed=0, dtype=jnp.float32)
    kw = {"spec_tree": "2,1"} if mechanism == "speculation" else {"mesh_axes": {"model": 2}}
    with pytest.raises(FamilyNotServed) as e:
        DecodeScheduler(params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=N, family=MOE, **kw)
    assert str(e.value) == message


# what the three families that were served before the fourth lowered to at the
# commit before it (PR 37's parent, a7fd829): sha256 of ``jit(program).lower(
# shapes).as_text()`` at the sizes of ``_old_family_program``. A change to a
# helper the families share (models/decoder.py's pool helpers and program
# wrappers, ops/moe.py's forms) that alters what one of them computes, or the
# order it computes it in, changes its text. The three STEPS still lower to
# that text; the three chunks to what they lower to since PR 40, whose pool
# write goes by whole pages where a dispatch writes a page's worth of rows
# (``decoder._write_pages``): a step, one row a slot, keeps the row scatter.
# The hybrid family's two are hashed at PR 42, which gave its step a kernel to
# choose on a TPU and its readback a second count (``attn_run_pages``: a
# constant 0 on this, the gather path, and the one thing that differs from the
# text before: one more element of the token readback).
LOWERED_BEFORE_THE_FOURTH_FAMILY = {
    "gpt2.step": "bb7a50487387849fd45d78852252e0ffa0ee36b76863d5227bbd13f0b4cf0ecb",
    "gpt2.chunk": "7163dbb2c6b8d35237c6f47fad429c857fd27984e3ca32250bdfaa3c4106c27d",
    # the sparse-expert family's two were re-made at PR 47 (e07abc72... / fc28eed0... before): its pool
    # has two page kinds (the state tuple is the full layers' planes then the sliding layers', both kinds'
    # block tables in one [2, n, pages] array, a layer indexing its kind's planes), so the programs' arguments and every pool
    # write and gather differ; its logits against its reference do not (tests/test_moe_decoder.py). Re-made again at
    # PR 48 (cd6f1c49... / 873438f2... before), which gave its step a kernel to choose on a TPU and its readback one
    # more count (``attn_run_pages``: a constant 0 on this, the gather path), as PR 42 did to the hybrid family's two:
    # one more element of the token readback and the constant's place in the text, and ``_window_table``'s clip
    # written as the array's method (``paged_attention.window_first_page``, shared with the scheduler's page count):
    # the same operations on the same values, in the same order
    "moe.step": "42204fab3ec8f1bab50e4c5a8033fe8852a86791662e937e4feed3b3119d132d",
    "moe.chunk": "81498d460f77aa4ccb1a22b569d1a1c92c31100c184919b620733a47bfb6c95e",
    "hybrid.step": "e17c5b7e1f238a00f5912f73a9d8ac916a23ef288193feb8f68c4e229296e2fd",
    "hybrid.chunk": "aba78efa101c5b7c9b363b2bc7ecda74de78a9b76aee6df7351237888a48a43c",
}
# the fourth family's two at the commit before the fifth (PR 41's parent,
# 934f8c5), hashed there: PR 41 took the router out of ``ops/moe.py``
# ``moe_held_ffn`` (the family now hands it the picks), and the latent
# family's programs had to lower to the text they lowered to before. They
# still do after PR 45, which gave the family's CHUNKS a kernel to choose on a
# TPU: on this, the CPU backend, both programs walk (``attn_kernel`` ""), and
# the walk's text is what it was. Re-made at PR 49 (dcb7e826... / b2aaef81...
# before), which gave ``moe_held_ffn``'s counts two more entries (the layer
# calls that ran the grouped form, and ran it compact: constants 0 at these
# programs' 4 and 16 rows, the masked form): the counters' vector is six wide
# where it was four, the readback two elements longer, and the helpers and
# loop arguments after it are numbered on; the same operations on the same
# values, in the same order
LOWERED_BEFORE_THE_FIFTH_FAMILY = {
    "mla.step": "48c7343865de7c891712c95124d96faeb7f0819105f41cf4db240dbc741e1c3a",
    "mla.chunk": "3797537ce0a6e7cf046937d1aa202da8785488cf0d730440d67b7c315bf18cf8",
}
LOWERED = {**LOWERED_BEFORE_THE_FOURTH_FAMILY, **LOWERED_BEFORE_THE_FIFTH_FAMILY}


def _old_family_program(family: str, kind: str):
    """(program, argument shapes) of one family's step or (2, 8) chunk at a
    tiny size: 4 slots, pages of 4, tables of 5 pages."""
    from seldon_core_tpu.models import decoder as dec
    from seldon_core_tpu.models import hybrid_decoder as hd

    def sds(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    rec = ()
    if family == "gpt2":
        fam = gpt2_family
        p = init_decoder(0, vocab=96, hidden=32, layers=2, ffn=64, max_len=64)
    elif family == "moe":
        fam = md.moe_family(md.MoEDecoderConfig(
            vocab=96, hidden=64, layers=4, heads=4, kv_heads=2, head_dim=16, ffn=32, experts=8, experts_per_tok=2,
            window=8, period=4, yarn_factor=4.0, yarn_original=16))
        p = md.init_moe_decoder(fam.cfg, seed=1, dtype=jnp.float32)
    elif family == "mla":
        from seldon_core_tpu.models import mla_decoder as mla

        fam = mla.mla_family(mla.MLADecoderConfig(vocab=96, layers=3, experts=16, experts_held=4, first_expert=4))
        p = mla.init_mla_decoder(fam.cfg, seed=1, dtype=jnp.float32)
    else:
        fam = hd.hybrid_family(hd.HybridDecoderConfig(
            vocab=96, hidden=64, layers=3, attn_layers=(1,), heads=4, kv_heads=2, head_dim=16, ffn=64, ssm_heads=4,
            ssm_head_dim=16, ssm_state=8, ssm_conv=4))
        p = hd.init_hybrid_decoder(fam.cfg, seed=1, dtype=jnp.float32)
        rec = (sds(fam.state_init(p, 7)),)
    # the sparse-expert family's pool has two page kinds since PR 47 (its sliding layers' pages are
    # their own, fewer): the scheduler hands it both kinds' block tables as one [2, n, pages] array, and so does this
    two_kinds = family == "moe"
    pool = fam.paged_kv_init(p, (24, 12) if two_kinds else 24, 4)
    step, chunk = fam.fused_programs("")
    n = 4 if kind == "step" else 2
    bt = i32(2, n, 5) if two_kinds else i32(n, 5)
    tail = (f32(n), i32(n), i32(), i32())  # temperatures, top-k, seed, tick
    if kind == "step":
        rows = () if family == "gpt2" else (jax.ShapeDtypeStruct((n,), bool),)
        return step, (sds(p), sds(pool), *rec, bt, i32(n), i32(n), *tail, *rows)
    state_rows = (i32(3, n),) if rec else ()
    return chunk, (sds(p), sds(pool), *rec, bt, i32(n, 8), i32(n), i32(n), *tail, *state_rows)


@functools.lru_cache(maxsize=None)
def _lowered_in_a_fresh_process() -> dict:
    """{program: sha256 of its lowered text}, every program of ``LOWERED``
    lowered in ONE child process that has traced nothing else, as the hashes
    were made: which inner jitted helpers (`_where`, `clip`, ...) two call
    sites share in the text follows what the process has traced before, and
    ``jax.clear_caches()`` does not undo all of it: under six workers
    `moe.step` (PR 43) and `moe.chunk` (PR 52) each read another text once in
    a worker that had run other files first, and the right one alone. The
    child is pinned to the CPU backend: it never loads the TPU's library."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import hashlib, json, jax\n"
        "from tests import test_decode_programs as t\n"
        "print(json.dumps({p: hashlib.sha256(jax.jit(f).lower(*a).as_text().encode()).hexdigest()\n"
        "    for p in sorted(t.LOWERED) for f, a in [t._old_family_program(*p.split('.'))]}))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.pathsep.join([root, os.environ.get("PYTHONPATH", "")])}
    done = subprocess.run([sys.executable, "-c", code], cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", sorted(LOWERED))
def test_the_older_families_programs_lower_to_the_text_they_had(program):
    assert _lowered_in_a_fresh_process()[program] == LOWERED[program]


def test_the_fourth_family_rides_the_counting_convention():
    """The latent-attention family's programs take what the sparse-expert
    family's take (pool, tables, tokens, positions, the sampler's four, rows)
    and append their seven counts to the token readback: the set needs no
    fourth convention."""
    from seldon_core_tpu.models import mla_decoder as mla

    fam = mla.mla_family(mla.MLADecoderConfig(vocab=96, experts_held=8))
    params = mla.init_mla_decoder(fam.cfg, seed=0, dtype=jnp.float32)
    sched = DecodeScheduler(params, seq_len=SEQ, max_new_tokens=MAX_NEW, n_slots=N, kv_page_size=4, family=fam)
    progs = sched.programs
    assert (progs.mode, progs.attn_kernel, progs._counted, progs._stateful) == ("", "", 9, False)
    sched.warmup()
    assert set(progs.compile_counts()) == {"step", "chunk", "copy"}
    zi, zf = np.zeros(N, np.int32), np.zeros(N, np.float32)
    out, read = progs.step(sched.pool.block_tables(), zi, zi, zf, zi, np.int32(1), np.ones(N, bool))
    toks, counted = read()
    assert toks.shape == (N,) and counted.shape == (9,) and counted[0] == N  # both rows counted as real
    assert counted[4:6].tolist() == [0, 0]  # a step's rows take the masked form: no grouped call, none compact
    assert counted[6] == N  # each attended over one latent row (position 0)
    assert counted[7:].tolist() == [0, 0]  # the CPU backend's step walks: no page fetched by the kernel
    assert sched.recompiles_since_warmup() == 0


# ---- the grouped-query families' chunks take ops/gqa_decode.py's chunk kernel where the step takes its own (ISSUE 52) ----


@pytest.mark.parametrize("family", ["moe", "hybrid", "hybrid_experts", "conv"])
async def test_a_grouped_query_familys_chunk_rounds_count_the_rows_the_kernel_took(
    family, monkeypatch, small_chunk_kernel_blocks
):
    """With the ONE place of choice answering "interpret", ``chunk_attn``
    names the kernel for every entry of the ladder, every chunk round's frame
    counts its prefilling rows into ``chunk_rows_kernel``, and the scheduler
    serves the tokens it serves through the gather (whose ``chunk_attn`` says
    "gather" and whose frames count none) with no recompile. The
    sparse-expert family's chunks read BOTH page kinds in place."""
    from seldon_core_tpu.serving import decode_programs as dp
    from tests import test_moe_decoder

    def build():
        if family != "moe":
            return _stateful(family)
        ms = test_moe_decoder._zoo()
        sched = DecodeScheduler(
            ms.params, seq_len=test_moe_decoder.SEQ, max_new_tokens=test_moe_decoder.MAX_NEW, n_slots=4,
            prefix_slots=0, prefill_chunk=16, kv_page_size=4, family=ms.generative["family"],
        )
        return ms, sched

    rng = np.random.default_rng(3)
    served = {}
    for kernel in ("", "interpret"):
        if kernel:
            monkeypatch.setattr(dp, "_step_attn_kernel", lambda *a: kernel)
        ms, sched = build()
        assert sched.programs.attn_kernel == kernel
        sched.warmup()
        if not served:
            prompts = rng.integers(0, 96, (3, sched.seq_len)).astype(np.int32)
        first = await sched.submit(prompts[0])
        served[kernel] = [first, *await asyncio.gather(*(sched.submit(p) for p in prompts[1:]))]
        assert sched.recompiles_since_warmup() == 0
        frames = sched.flight.snapshot()
        chunked = [f for f in frames if f.chunk_rows]
        names = {sched.programs.chunk_attn(c) for _rows, c in sched.chunk_buckets}
        assert chunked and names == ({"kernel"} if kernel else {"gather"})
        for f in chunked:
            assert f.chunk_rows_live > 0 and f.chunk_rows_kernel == (f.chunk_rows_live if kernel else 0)
        assert not any(f.chunk_rows_kernel for f in frames if not f.chunk_rows)
        await sched.close()
    for got, want in zip(served["interpret"], served[""]):
        np.testing.assert_array_equal(got, want)
