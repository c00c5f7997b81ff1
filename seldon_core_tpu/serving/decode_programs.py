"""The decode scheduler's program set: every fused device program, its jit
handle, and the one convention each round kind is called by.

``DecodePrograms`` is built once by ``DecodeScheduler.__init__``. It owns

- the fused speculation and feature programs below (the step and chunk
  bodies are the decoder family's own answer, ``family.fused_programs``;
  all of them are named ``_fused_*``, which is how a device trace and
  analysis/trace_safety.py find them);
- their ``jax.jit`` handles, each with its donations, static arguments and
  (on a decode mesh) pinned output shardings beside the function it wraps;
- the device state only programs touch between rounds: the draft's flat
  cache pair, the feature carry, the feature head's window starts;
- ``warmup`` / ``compile_counts``: every handle warmed and counted here.

The caller's convention is one per round kind — ``step``, ``chunk``,
``draft`` + ``verify``, ``draft_admit`` — whatever the deployment: whether
``rows`` reaches the program, whether a feature buffer rides along, whether
a counting family's counts are split off the readback, whether a recurrent
family's state rows ride beside the pool (``pool.recurrent``: donated and
put back with ``pool.state``), chain or tree or feature tree, is decided in
here, once. A call ENQUEUES: it hands the
donated ``pool.state`` in, puts the program's back, and returns ``(out,
read)`` — the device handle(s) the dispatch waits on before it marks, and the
host read. Timing and naming a dispatch stay the scheduler's (``_Dispatch``).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.decoder import (
    draft_propose,
    draft_propose_features,
    draft_propose_tree,
    draft_tree_commit,
    feature_chunk_prefill,
    init_slot_cache,
    is_feature_draft,
    paged_chunk_prefill,
    paged_decode_step,
    paged_tree_commit,
    paged_tree_verify,
    paged_verify_step,
    prefill,
    sample_tokens,
    speculative_accept,
    speculative_accept_tree,
)
from seldon_core_tpu.ops.gqa_decode import gqa_tiles
from seldon_core_tpu.ops.mla import kernel_tiles
from seldon_core_tpu.ops.paged_attention import mosaic_tiles
from seldon_core_tpu.parallel.tp import kv_sharding, tree_node_sharding


def _scatter_prefill_rows(cache_k, cache_v, k_new, v_new, row_for_slot, valid_slot):
    """Write a prefill wave's K/V into each row's own slot as ONE masked
    gather + slice update, vectorized over SLOTS (DRAFT cache only since
    the paged pool took over the target side — the draft keeps the flat
    slot layout because its whole point is to be small): slot j takes wave
    row ``row_for_slot[j]`` iff ``valid_slot[j]`` and keeps its current
    bytes otherwise. Pivoting the mapping to the slot axis makes the write
    conflict-free by construction (each slot SELECTS its row — no scatter
    with duplicate destination indices exists)."""
    s = k_new.shape[3]
    sel_k = jnp.take(k_new, row_for_slot, axis=1)  # [L, n_slots, h, s, hd]
    sel_v = jnp.take(v_new, row_for_slot, axis=1)
    mask = valid_slot[None, :, None, None, None]
    cache_k = cache_k.at[:, :, :, :s, :].set(
        jnp.where(mask, sel_k, cache_k[:, :, :, :s, :])
    )
    cache_v = cache_v.at[:, :, :, :s, :].set(
        jnp.where(mask, sel_v, cache_v[:, :, :, :s, :])
    )
    return cache_k, cache_v


def _step_attn_kernel(family, pool_state: tuple, mesh, heads: int, kv_heads: int, heads_window: int = 0) -> str:
    """How the fused decode step's attention reads the pool — THE place the
    choice is made, from what the set can observe and nothing else (no
    knob): "mosaic", a Pallas kernel that reads the pool's pages in place
    and stops at each slot's length, where the family has a kernel to choose
    at all (``serves``), there is no decode mesh, the pool lies on one
    device that is a TPU, and Mosaic can tile the pool's rows and pages.
    WHICH kernel follows from the pool and the family's heads
    (``decoder_dims``), and each has its own predicate: the two-plane float
    pool's is ops/paged_attention.py where every query head has its own
    K/V head (the GPT-2 block; ``mosaic_tiles``: gpt2-xl's rows of 1600 and
    pages of 4 rows are outside it) and ops/gqa_decode.py
    ``gqa_decode_attention`` where ``kv_heads`` are fewer than ``heads``
    (the short-convolution, hybrid and sparse-expert families;
    ``gqa_tiles``: a two-byte float, rows of whole lane tiles, pages of 16
    rows or more, a head that divides a tile, query heads in whole groups);
    a pool of TWO page kinds (``heads_window``: the sliding layers' query
    heads, from a family whose ``decoder_dims`` has ``kv_window_layers``;
    the state is the full layers' planes then the sliding layers') is asked
    once a kind, with that kind's planes and head count, and takes the
    kernel only if both tile; the ONE-plane latent pool's is ops/mla.py
    ``mla_decode_attention`` (``kernel_tiles``: a two-byte float, rows of
    whole lane tiles, pages of 16 rows or more);
    "" — the page gather and the flat path's attention, or the latent
    family's blocked walk — everywhere else: the int8 pool (six planes a
    kind), a tensor-parallel mesh, a family without a kernel, a geometry the kernel
    cannot tile, the CPU backend (where the gather and the walk are the
    oracles). The dispatch's shape is the program's own to see: one query a
    slot takes the step's kernel (models/decoder.py ``_layer_step_paged`` and
    ``_paged_step_reads``, models/moe_decoder.py ``_step_reads``); a prefill
    chunk takes a many-queries kernel under the same answer where the family
    has one and its static test holds for the chunk's length: the latent
    family's ops/mla.py ``mla_chunk_attention`` (``kernel_takes``), the
    grouped-query families' ops/gqa_decode.py ``gqa_chunk_attention``
    (``gqa_chunk_tiles``; the sparse-expert family's over both page kinds);
    ``DecodePrograms.chunk_attn`` says which a chunk length took. The GPT-2
    family's chunks, and every family's verify and tree programs (several
    queries a slot without ``counts``), gather whatever this says."""
    half = len(pool_state) // 2
    kinds = [(pool_state[:half], heads), (pool_state[half:], heads_window)] if heads_window else [(pool_state, heads)]
    if "attn_kernel" not in family.serves or mesh is not None or any(len(planes) > 2 for planes, _ in kinds):
        return ""
    devices = pool_state[0].sharding.device_set
    if len(devices) != 1 or next(iter(devices)).platform != "tpu":
        return ""

    def tiles(planes: tuple, heads: int) -> bool:
        _layers, _pages, page_size, row_width = planes[0].shape
        dtype = planes[0].dtype
        if len(planes) == 1:
            return kernel_tiles(row_width, page_size, dtype)
        if kv_heads < heads:
            return gqa_tiles(row_width, heads, kv_heads, page_size, dtype)
        return mosaic_tiles(row_width, heads, page_size, dtype)

    return "mosaic" if all(tiles(planes, h) for planes, h in kinds) else ""


def _fused_draft_admit(params, dcache_k, dcache_v, ids, row_for_slot, valid_slot):
    """Draft-side prompt prefill for slots whose TARGET prefill completed:
    the draft shares no K/V with the target's page pool, so its flat cache
    takes the FULL prompt in one bucketed dispatch at transition time —
    target-side prefix reuse never skews the draft's proposal distribution
    (and greedy acceptance is bit-exact for ANY draft state regardless)."""
    _, k_new, v_new = prefill(params, ids)
    return _scatter_prefill_rows(
        dcache_k, dcache_v, k_new, v_new, row_for_slot, valid_slot
    )


def _fused_draft(params, cache_k, cache_v, tokens, positions, temps, topks, seed, tick, k):
    """One device program per speculation round, draft side: k
    autoregressive draft steps (models/decoder.draft_propose) with the
    per-tick RNG stream forked from the step programs' (fold_in 1)."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), tick), 1)
    return draft_propose(
        params, cache_k, cache_v, tokens, positions, temps, topks, key, k
    )


def _fused_verify(
    params, pool, bt, tokens, drafts, draft_logits,
    positions, limits, temps, topks, seed, tick,
):
    """One device program per speculation round, target side: the widened
    [n, k+1] paged verify step + the acceptance rule, reading back only
    (out_tokens [n, k+1], n_accepted [n]). The draft's proposals and raw
    logits stay on device between the two dispatches."""
    queries = jnp.concatenate([tokens[:, None], drafts], axis=1)  # [n, k+1]
    logits, _hidden, pool = paged_verify_step(params, pool, bt, queries, positions)
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), tick), 2)
    out, acc = speculative_accept(
        logits, drafts, draft_logits, limits, temps, topks, key
    )
    return out, acc, pool


def _fused_draft_tree(
    params, cache_k, cache_v, tokens, positions, temps, topks, seed, tick, tree
):
    """One device program per TREE speculation round, draft side: a root
    decode step + ``tree.depth`` unrolled widened expansions proposing the
    whole candidate tree (models/decoder.draft_propose_tree). The
    speculative node K/V comes back in-register — the draft cache gains
    only the root's entry; the verify dispatch commits the accepted path."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), tick), 1)
    return draft_propose_tree(
        params, cache_k, cache_v, tokens, positions, temps, topks, key, tree
    )


def _fused_tree_verify(
    params, pool, bt, tokens, node_tokens, block_logits, node_k, node_v,
    dck, dcv, positions, width_limits, temps, topks, seed, tick, tree,
):
    """One device program per TREE speculation round, target side: the
    whole flattened tree scored in ONE widened dispatch
    (paged_tree_verify — the pool is NOT written by the forward), the
    longest-accepted-path walk, then BOTH commits: the accepted path's
    target K/V through the block tables (non-accepted columns
    junk-redirected — the pool never holds speculative garbage) and its
    draft K/V into the flat draft cache. Readback is (out_tokens
    [n, depth+1], n_accepted [n]); everything else stays on device."""
    queries = jnp.concatenate([tokens[:, None], node_tokens], axis=1)  # [n, width]
    logits, _hidden, new_k, new_v = paged_tree_verify(
        params, pool, bt, queries, positions, tree
    )
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), tick), 2)
    out, acc, path_idx = speculative_accept_tree(
        logits, queries, block_logits, width_limits, temps, topks, key, tree
    )
    pool = paged_tree_commit(pool, bt, new_k, new_v, path_idx, positions, acc)
    dck, dcv = draft_tree_commit(dck, dcv, node_k, node_v, path_idx, positions, acc)
    return out, acc, pool, dck, dcv


def _fused_step_feat(
    params, pool, bt, tokens, positions, feats, fmask, temps, topks, seed, tick
):
    """``_fused_step`` for feature-draft deployments: the same fused
    decode+sample dispatch, additionally round-tripping the per-slot
    FEATURE buffer — the consumed position's final-layer hidden replaces
    the slot's carried feature wherever ``fmask`` (generating,
    non-prefilling slots) holds, so a degraded/mixed plain round keeps
    the next speculative round's draft root correctly conditioned."""
    logits, hidden, pool = paged_decode_step(params, pool, bt, tokens, positions)
    key = jax.random.fold_in(jax.random.key(seed), tick)
    new_feats = jnp.where(fmask[:, None], hidden, feats)
    return sample_tokens(logits, temps, topks, key), new_feats, pool


def _fused_chunk_feat(
    params, fparams, pool, bt, dck, dcv, ids, positions, counts, feats,
    starts, temps, topks, seed, tick,
):
    """``_fused_chunk`` for feature-draft deployments: the target chunk
    prefill PLUS the head's teacher-forced prefill over the same chunk
    (models/decoder.feature_chunk_prefill — the head's K/V is written
    under the same counts mask, so the separate draft-admit program is
    gone in feature mode), and the per-slot feature carry: slots that
    consumed prompt tokens this round update their feature to the chunk's
    last computed hidden; everyone else keeps theirs."""
    logits, hidden, pool = paged_chunk_prefill(params, pool, bt, ids, positions, counts)
    c = ids.shape[1]
    rows = jnp.arange(ids.shape[0])
    idx = jnp.clip(counts - 1, 0, c - 1)
    last = logits[rows, idx]  # [n, vocab]
    dck, dcv = feature_chunk_prefill(
        fparams, dck, dcv, ids, hidden, feats, positions, counts, starts
    )
    new_feats = jnp.where((counts > 0)[:, None], hidden[rows, idx], feats)
    key = jax.random.fold_in(jax.random.key(seed), tick)
    return sample_tokens(last, temps, topks, key), new_feats, pool, dck, dcv


def _fused_draft_feat(
    fparams, dck, dcv, feats, tokens, positions, starts, temps, topks, seed, tick, tree
):
    """One device program per FEATURE speculation round, draft side: the
    head's root step (fusing the slot's carried target feature with the
    last emitted token) + ``tree.depth`` unrolled feature-autoregressive
    expansions (models/decoder.draft_propose_features). Same RNG stream
    and return layout as the token tree draft."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), tick), 1)
    return draft_propose_features(
        fparams, dck, dcv, feats, tokens, positions, starts, temps, topks, key, tree
    )


def _fused_ftree_verify(
    params, pool, bt, tokens, node_tokens, block_logits, node_k, node_v,
    dck, dcv, feats, fmask, positions, width_limits, temps, topks, seed,
    tick, tree,
):
    """``_fused_tree_verify`` for feature-draft deployments: identical
    widened verify + longest-accepted-path walk + both commits, plus the
    FEATURE carry the head needs for the next round's root — the target's
    final-layer hidden at the accepted path's LAST block (root when
    nothing accepted), selected on device so the readback stays
    (out_tokens, n_accepted)."""
    queries = jnp.concatenate([tokens[:, None], node_tokens], axis=1)  # [n, width]
    logits, hidden, new_k, new_v = paged_tree_verify(
        params, pool, bt, queries, positions, tree
    )
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), tick), 2)
    out, acc, path_idx = speculative_accept_tree(
        logits, queries, block_logits, width_limits, temps, topks, key, tree
    )
    pool = paged_tree_commit(pool, bt, new_k, new_v, path_idx, positions, acc)
    dck, dcv = draft_tree_commit(dck, dcv, node_k, node_v, path_idx, positions, acc)
    rows = jnp.arange(tokens.shape[0])
    last_blk = jnp.take_along_axis(path_idx, acc[:, None], axis=1)[:, 0]
    new_feats = jnp.where(fmask[:, None], hidden[rows, last_blk], feats)
    return out, acc, pool, dck, dcv, new_feats


class DecodePrograms:
    """One scheduler's compiled programs, their device state and call
    conventions (module docstring). ``mode`` is the speculation the
    deployment runs: "" | "chain" | "tree" | "feature" (a feature head
    always rides a tree: the scheduler promotes a chain to the branching-1
    tree before it builds the set). ``place(params, arrs)`` commits fresh
    buffers to the serving-steady sharding (``DecodeScheduler._commit_kv``).

    The pool state tuple is donated so page updates are in-place in HBM.
    The step program is ONE executable; the chunk ladder compiles one per
    bucket; the pool's CoW copy ladder one per copy bucket — all at
    ``warmup``. With speculation on, the round pair and (token drafts) the
    draft's transition-time flat prompt prefill join; the plain step stays
    warm either way — it serves rounds where every active slot's effective
    spec_k is 0. On a decode mesh, OUTPUT shardings are pinned to the mesh
    layout so the donated pool/draft state round-trips every program with
    one stable signature (warmup == live traffic — zero recompiles, same as
    single-device)."""

    def __init__(
        self, family, params, draft_params, pool, *, dims, n_slots, seq_len, seed,
        mesh, tp_axis, spec_k, spec_tree, draft_ctx, dtype, place,
    ):
        self.params, self.draft_params, self.pool = params, draft_params, pool
        self.n_slots, self.seq_len = n_slots, seq_len
        self.seed = np.int32(seed)
        self.spec_k, self.tree = spec_k, spec_tree
        self.mode = (
            "" if draft_params is None
            else "feature" if is_feature_draft(draft_params)
            else "tree" if spec_tree is not None
            else "chain"
        )
        feature = self.mode == "feature"
        # a counting family's step also takes the rows that generate, and its
        # readback carries the counts after the tokens (``_tokens``)
        self._counted = len(family.frame_counters)
        # a recurrent family's programs take the state rows after the pool,
        # donated with it, and the chunk the rows each batch row reads and writes
        self._stateful = bool(pool.recurrent)
        self._place, self._draft_ctx, self._dtype = place, draft_ctx, dtype
        self._hidden = dims["hidden"]
        # per-slot draft attention window start (host data: the computed
        # suffix boundary on warm prefix-reuse admissions; the feature
        # programs' ``starts``)
        self.draft_start = np.zeros(n_slots, np.int32)
        self.reset()
        rep = pool_sh = kvp = None
        dc_sh = ()
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(mesh, P())
            pool_sh = pool.state_shardings
            if self.mode:
                dc_sh = tuple(kv_sharding(mesh, tp_axis, a) for a in (self.dck, self.dcv))
            # tree round pair: the in-register node K/V rides head-sharded
            # like every 5-D KV buffer; the TREE axis is replicated (heads
            # stay sharded — parallel/tp.py), so the widened dispatch
            # needs no new collective beyond the fused all-reduces
            kvp = tree_node_sharding(mesh, tp_axis)

        def out(*shardings):
            return {"out_shardings": shardings} if mesh is not None else {}

        # the plain step's read side (the feature twins keep the gather)
        self.attn_kernel = (
            "" if feature
            # a pool of two page kinds names the second kind's head count too
            else _step_attn_kernel(
                family, pool.state, mesh, dims["heads"], dims["kv_heads"],
                *((dims["heads_window"],) if dims.get("kv_window_layers") else ()),
            )
        )
        # a family with a chunk kernel says which chunk lengths take it (the feature twins gather)
        self._chunk_attn = None if feature else getattr(family, "chunk_attn", None)
        if feature:
            # feature mode swaps the step/chunk pair for feature-carrying
            # twins (the chunk one also teacher-forces the head's prompt
            # K/V, so there is no draft-admit ladder); the feat buffer
            # [n_slots, hidden] is replicated (it feeds the fc fuse on
            # every device)
            self._step_f_fn = jax.jit(
                _fused_step_feat, donate_argnums=(1, 5), **out(rep, rep, pool_sh)
            )
            self._chunk_f_fn = jax.jit(
                _fused_chunk_feat, donate_argnums=(2, 4, 5, 9), **out(rep, rep, pool_sh, *dc_sh)
            )
            self._draft_feat_fn = jax.jit(
                _fused_draft_feat, donate_argnums=(1, 2), static_argnums=(11,),
                **out(rep, rep, kvp, kvp, *dc_sh),
            )
            self._ftree_verify_fn = jax.jit(
                _fused_ftree_verify, donate_argnums=(1, 8, 9, 10), static_argnums=(18,),
                **out(rep, rep, pool_sh, *dc_sh, rep),
            )
        else:
            step, chunk = family.fused_programs(self.attn_kernel)
            donated = (1, 2) if self._stateful else (1,)
            self._step_fn = jax.jit(step, donate_argnums=donated, **out(rep, pool_sh))
            self._chunk_fn = jax.jit(chunk, donate_argnums=donated, **out(rep, pool_sh))
        if self.mode == "tree":
            # tree mode subsumes the chain (a branching-1 tree IS the
            # chain), so the chain draft/verify pair is not compiled —
            # per-request chain/plain tightening rides the SAME tree
            # programs through data-only width masks
            self._draft_tree_fn = jax.jit(
                _fused_draft_tree, donate_argnums=(1, 2), static_argnums=(9,),
                **out(rep, rep, kvp, kvp, *dc_sh),
            )
            self._tree_verify_fn = jax.jit(
                _fused_tree_verify, donate_argnums=(1, 8, 9), static_argnums=(16,),
                **out(rep, rep, pool_sh, *dc_sh),
            )
        elif self.mode == "chain":
            self._draft_fn = jax.jit(
                _fused_draft, donate_argnums=(1, 2), static_argnums=(9,), **out(rep, rep, *dc_sh)
            )
            self._verify_fn = jax.jit(_fused_verify, donate_argnums=(1,), **out(rep, rep, pool_sh))
        self.admit_buckets: tuple[int, ...] = ()
        if self.mode in ("chain", "tree"):
            self._draft_admit_fn = jax.jit(
                _fused_draft_admit, donate_argnums=(1, 2), **out(*dc_sh)
            )
            # wave buckets for the draft's transition-time flat prefill
            # (the target side admits through the chunk programs, and the
            # feature head's prompt K/V rides the chunk ladder)
            buckets, b = [], 1
            while b < n_slots:
                buckets.append(b)
                b *= 2
            self.admit_buckets = tuple(buckets) + (n_slots,)

    def reset(self) -> None:
        """(Re)allocate the device state only programs touch — at build,
        and after a dispatch that had it donated raised."""
        n = self.n_slots
        self.dck = self.dcv = self.feat = None
        if self.mode:
            # the draft keeps a flat slot cache (its whole point is to be small)
            self.dck, self.dcv = self._place(
                self.draft_params,
                init_slot_cache(self.draft_params, n, self._draft_ctx, self._dtype),
            )
        if self.mode == "feature":
            # per-slot carried target feature f_{pos-1}: round-tripped through
            # every fused program that can move a slot's position, so the
            # next round's draft root is always conditioned on the LAST
            # consumed position's hidden
            self.feat = self._place(
                self.params, (jnp.zeros((n, self._hidden), self._dtype),)
            )[0]
        self.draft_start[:] = 0

    # ------------------------------------------------------- the round kinds
    def _tokens(self, out) -> tuple:
        """The blocking read of a step or chunk: (one token a row of the
        dispatch, a counting family's trailing counts | None)."""
        toks = np.asarray(out)
        if not self._counted:
            return toks, None
        return toks[: -self._counted], toks[-self._counted :]

    def step(self, bt, toks, pos, temps, topks, tick, rows):
        """Enqueue one plain decode step. ``rows`` marks the generating
        slots: the counting family's real rows, the feature carry's mask (a
        junk-riding slot must not clobber its carried feature)."""
        pool = self.pool
        if self.mode == "feature":
            out, self.feat, pool.state = self._step_f_fn(
                self.params, pool.state, bt, toks, pos, self.feat, rows, temps, topks,
                self.seed, tick,
            )
        elif self._stateful:
            out, pool.state, pool.recurrent = self._step_fn(
                self.params, pool.state, pool.recurrent, bt, toks, pos, temps, topks,
                self.seed, tick, rows,
            )
        else:
            out, pool.state = self._step_fn(
                self.params, pool.state, bt, toks, pos, temps, topks, self.seed, tick,
                *((rows,) if self._counted else ()),
            )
        return out, lambda: self._tokens(out)

    def chunk(self, bt, ids, pos, counts, temps, topks, tick, state_rows=None):
        """Enqueue one prefill chunk round at a ``[rows, c]`` entry of the
        chunk ladder: ``ids`` and the block-table rows ``bt`` of the slots
        that prefill, one row each (counts 0: a padding row, its writes
        junk-sink), whichever slots they are; the read gives a token a
        row. ``state_rows`` (a recurrent family: ``pool.state_rows``) names
        the state row each batch row reads, writes and snapshots. The
        feature twin carries its buffers by slot: all ``n_slots`` rows, row
        r slot r."""
        pool = self.pool
        if self.mode == "feature":
            out, self.feat, pool.state, self.dck, self.dcv = self._chunk_f_fn(
                self.params, self.draft_params, pool.state, bt, self.dck, self.dcv, ids,
                pos, counts, self.feat, self.draft_start, temps, topks, self.seed, tick,
            )
        elif self._stateful:
            out, pool.state, pool.recurrent = self._chunk_fn(
                self.params, pool.state, pool.recurrent, bt, ids, pos, counts, temps, topks,
                self.seed, tick, state_rows,
            )
        else:
            out, pool.state = self._chunk_fn(
                self.params, pool.state, bt, ids, pos, counts, temps, topks, self.seed, tick
            )
        return out, lambda: self._tokens(out)

    def chunk_attn(self, c: int) -> str:
        """How the chunk program of ``c`` tokens a row reads the pool:
        "kernel" where the family has a chunk kernel and the set's choice
        (``attn_kernel``) and the static shape send this program there, else
        the family's name for its fallback (the latent family's "walk", the
        grouped-query families' "gather"); "walk" for a family without the
        hook (the GPT-2 family's page gather) and for the feature twin."""
        return "walk" if self._chunk_attn is None else self._chunk_attn(self.attn_kernel, c)

    def draft(self, toks, pos, temps, topks, tick) -> tuple:
        """Enqueue a speculative round's draft side. Returns the proposal
        ``verify`` takes, device-resident (a timing run may block on it)."""
        if self.mode == "feature":
            *proposal, self.dck, self.dcv = self._draft_feat_fn(
                self.draft_params, self.dck, self.dcv, self.feat, toks, pos,
                self.draft_start, temps, topks, self.seed, tick, self.tree,
            )
        elif self.mode == "tree":
            *proposal, self.dck, self.dcv = self._draft_tree_fn(
                self.draft_params, self.dck, self.dcv, toks, pos, temps, topks,
                self.seed, tick, self.tree,
            )
        else:
            *proposal, self.dck, self.dcv = self._draft_fn(
                self.draft_params, self.dck, self.dcv, toks, pos, temps, topks,
                self.seed, tick, self.spec_k,
            )
        return tuple(proposal)

    def verify(self, bt, toks, proposal, pos, temps, topks, limits, wlimits, rows, tick):
        """Enqueue a speculative round's target side: the widened verify of
        ``proposal`` (``limits`` per slot on a chain, per-depth ``wlimits``
        on a tree), acceptance and the commits. Reads back (out_tokens
        [n, depth + 1], n_accepted [n])."""
        pool = self.pool
        if self.mode == "feature":
            out_t, acc, pool.state, self.dck, self.dcv, self.feat = self._ftree_verify_fn(
                self.params, pool.state, bt, toks, *proposal, self.dck, self.dcv,
                self.feat, rows, pos, wlimits, temps, topks, self.seed, tick, self.tree,
            )
        elif self.mode == "tree":
            out_t, acc, pool.state, self.dck, self.dcv = self._tree_verify_fn(
                self.params, pool.state, bt, toks, *proposal, self.dck, self.dcv,
                pos, wlimits, temps, topks, self.seed, tick, self.tree,
            )
        else:
            out_t, acc, pool.state = self._verify_fn(
                self.params, pool.state, bt, toks, *proposal, pos, limits, temps, topks,
                self.seed, tick,
            )
        return (out_t, acc), lambda: (np.asarray(out_t), np.asarray(acc))

    def draft_admit(self, prompts: list) -> None:
        """Enqueue the token draft's flat prompt prefill for the ``(slot,
        prompt)`` pairs whose target prefill completed this round: one
        dispatch at the wave's ``admit_buckets`` bucket (no readback)."""
        bucket = next(b for b in self.admit_buckets if b >= len(prompts))
        ids = np.zeros((bucket, self.seq_len), np.int32)
        row_for_slot = np.zeros(self.n_slots, np.int32)
        valid_slot = np.zeros(self.n_slots, bool)
        for r, (slot, prompt) in enumerate(prompts):
            ids[r] = prompt
            row_for_slot[slot] = r
            valid_slot[slot] = True
        self.dck, self.dcv = self._draft_admit_fn(
            self.draft_params, self.dck, self.dcv, ids, row_for_slot, valid_slot
        )

    # ------------------------------------------------------ compile discipline
    def warmup(self, chunk_buckets) -> None:
        """Compile every program ahead of traffic by the conventions live
        rounds use (so a warmed signature IS a live one): the chunk ladder's
        ``(rows, c)`` entries, the pool's CoW copy ladder, the draft-admit
        ladder, the step and the speculative round pair. All-zero block
        tables and counts: every write lands in junk page 0, no live bytes
        touched."""
        n = self.n_slots
        zi, zf, none = np.zeros(n, np.int32), np.zeros(n, np.float32), np.zeros(n, bool)
        bt0 = self.pool.block_tables()
        tick = np.int32(0)
        for rows, c in chunk_buckets:
            pad = np.full(rows, -1)
            self.chunk(
                self.pool.block_tables(pad), np.zeros((rows, c), np.int32),
                zi[:rows], zi[:rows], zf[:rows], zi[:rows], tick,
                self.pool.state_rows(pad) if self._stateful else None,
            )
        self.pool.warmup()  # the CoW copy ladder (page0 self-copies)
        for b in self.admit_buckets:
            self.dck, self.dcv = self._draft_admit_fn(
                self.draft_params, self.dck, self.dcv,
                np.zeros((b, self.seq_len), np.int32), zi, none,
            )
        out, _ = self.step(bt0, zi, zi, zf, zi, tick, none)
        if self.mode:
            wl0 = None if self.tree is None else np.zeros((n, self.tree.depth), np.int32)
            proposal = self.draft(zi, zi, zf, zi, tick)
            pair, _ = self.verify(bt0, zi, proposal, zi, zf, zi, zi, wl0, none, tick)
            jax.block_until_ready(pair)
        jax.block_until_ready(out)

    def compile_counts(self) -> dict[str, int]:
        """jit cache sizes per program. The pjit cache is keyed on the
        UNDERLYING function, so counts accumulate across scheduler
        instances in one process (multi-tenant) — the zero-recompile
        assertion is therefore relative: recompiles_since_warmup()."""
        if self.mode == "feature":
            return {
                "step_f": self._step_f_fn._cache_size(),
                "chunk_f": self._chunk_f_fn._cache_size(),
                "copy": self.pool.compile_count(),
                "draft_feat": self._draft_feat_fn._cache_size(),
                "ftree_verify": self._ftree_verify_fn._cache_size(),
            }
        counts = {
            "step": self._step_fn._cache_size(),
            "chunk": self._chunk_fn._cache_size(),
            "copy": self.pool.compile_count(),
        }
        if self.mode == "tree":
            counts["draft_tree"] = self._draft_tree_fn._cache_size()
            counts["tree_verify"] = self._tree_verify_fn._cache_size()
        elif self.mode == "chain":
            counts["draft"] = self._draft_fn._cache_size()
            counts["verify"] = self._verify_fn._cache_size()
        if self.admit_buckets:
            counts["draft_admit"] = self._draft_admit_fn._cache_size()
        return counts
