"""Continuous-batching decode scheduler for the generative tier.

The whole-batch ``lax.scan`` path (models/decoder.generate) runs one batch
to completion: a request arriving mid-generation waits for the previous
generation to finish (head-of-line blocking), every sequence pays
``max_new_tokens`` steps even after it stops, and clients see nothing until
the last token lands. This module brings Orca-style iteration-level
scheduling over a vLLM-style PAGED KV pool into the stack:

- ONE compiled per-step program (``paged_decode_step``) runs over the
  shared page pool through static-shape ``[n_slots, max_pages]`` block
  tables; slots are assigned per sequence and freed on completion.
- Between steps the scheduler admits newly-arrived prefilled sequences into
  free slots and retires finished ones (EOS or per-request
  ``max_new_tokens``), so batch composition changes at STEP boundaries with
  zero recompiles — active-slot masking, never shape changes.
- Tokens stream to the caller as they are chosen (``on_token``), which is
  what the fast ingress's SSE endpoint forwards to clients.
- Paged KV memory (serving/kv_pool.py): K/V lives in ONE device-resident
  page pool of token rows ``[L, n_pages, page_size, h*hd]`` shared by
  live slots and the prefix cache; each slot carries a static-shape block
  table and the attention programs gather through it (vLLM's
  PagedAttention memory model). The fused programs take the pool donated
  and write it in place — one scatter per layer into the whole array.
  Slot memory stops being ``n_slots * max_ctx`` worst-case: a host-side
  allocator tracks per-page refcounts, copies-on-write at the
  first divergent write into a shared page, reclaims unreferenced prefix
  pages LRU-first, and admits sequences against a reservation invariant
  instead of deadlocking when an explicit ``tpu.decode_kv_pages`` budget
  runs tight. ``tpu.decode_kv_dtype: int8`` stores the pool quantized
  (per-page-row scale/zero-point, dequant fused into the gather) for
  roughly double the effective capacity again.
- Prefix-cache KV reuse (``tpu.decode_prefix_slots``): a host-side
  index over prompt token prefixes whose entries REFERENCE pool pages.
  On admit the longest match maps the shared pages into the reader's
  block table (refcount bump — copy-free; the old gather-copy is gone)
  and only the uncovered suffix is prefilled — the RadixAttention
  observation that shared system prompts dominate real chat/agent
  traffic. Entries are captured from retiring slots (full prompt) and
  explicit ``meta.tags.cache_prefix`` hints (at prefill completion) by
  pinning the pages in place.
- Recurrent state beside pages (a family with ``state_init``:
  models/hybrid_decoder.py): the pool also holds STATE ROWS, a row a slot, a
  row a cached prefix and a row of zeros (serving/kv_pool.py). A chunk
  dispatch names the row each batch row reads and writes; the step advances
  the generating slots' own rows and leaves every other as it was. A state
  is reusable only where a snapshot of it was kept, so a prefix hit reuses
  an entry's WHOLE length or nothing, a ``cache_prefix`` hint makes the
  chunk plan end a chunk at the hint's boundary (that dispatch writes the
  snapshot row), and a request without a hint captures nothing.
- Chunked prefill (``tpu.decode_prefill_chunk``): prompt suffixes are
  computed in fixed-size chunk buckets interleaved with decode steps
  (Sarathi-style), so a long admission wave no longer stalls every
  running slot's inter-token latency for a whole monolithic prefill.
- Tensor-parallel decode (``tpu.decode_mesh_axes``, e.g. ``{"tp": 4}``):
  every fused program runs SPMD over a named device mesh
  (parallel/tp.py): decoder params, the paged page pool, and the
  draft's flat cache shard on the attention HEAD axis, the FFN on its
  hidden axis, with the per-layer all-reduces fused into the step
  programs by GSPMD. Block tables, the allocator, and the prefix index
  stay host-side and device-agnostic — admission/CoW/reclaim logic is
  untouched, and greedy output stays token-identical to the
  single-device scheduler at any width.
- Draft-model speculation (``tpu.decode_draft_model`` + ``decode_spec_k``)
  amortizes each target dispatch over k proposed tokens: a small draft
  decoder proposes k tokens per slot in ONE fused dispatch, the target
  scores all k+1 queries in ONE widened verify dispatch against the same
  slot cache, and slots advance by their accepted length. Rejected cache
  writes need no copy-rollback — positions only advance over accepted
  tokens, so stale entries sit beyond every later attention mask until
  the next consumed token overwrites them.
- Feature-level drafting (``zoo://draft?features=1`` — EAGLE-style): the
  draft is a one-layer HEAD conditioned on the TARGET's final-layer
  hidden state, which the fused step/verify/chunk programs thread out
  per committed position. The scheduler carries a per-slot feature
  buffer round-tripped through feature-carrying program twins
  (``step_f``/``chunk_f``/``draft_feat``/``ftree_verify``); the chunk
  dispatch also teacher-forces the head's prompt K/V (no separate
  draft-admit ladder), warm prefix admissions open the head's attention
  window at the computed suffix, and feature mode always rides the tree
  round programs (a chain config promotes to the branching-1 tree).
  Accepted tokens/dispatch beats the truncated-layer draft because the
  target's own feature summarizes the whole prefix; greedy stays
  bit-identical to plain for ANY head. An accept-driven auto-tuner
  (``_TreeAutoTuner``, same ``decode_spec_accept_floor`` knob) also
  reshapes the per-depth tree width masks from the accepted-path-length
  reach EWMA — data-only, never wider than the configured tree, probe
  rounds tagged in the flight frames.

- Pipelined decode rounds (``ENGINE_DECODE_PIPELINE``, default on): the
  host-bubble microscope measured the serial loop's per-round gap as
  dominated by admission + allocator work that does NOT depend on the
  in-flight dispatch's result — so the loop double-buffers: while round
  N's fused step/verify dispatch is enqueued and awaiting readback, round
  N+1's host phases run against SHADOW state (admission decisions into a
  pending list via the same ``_admit_decide`` the serial walk uses, plus
  the next chunk round's input build as a snapshot-keyed plan), then the
  readback walks reconcile against the unchanged dispatch-time slot
  table, ``_apply_pending`` installs the flight-decided admissions, and
  the round commits through the single ``_commit_round`` funnel. The
  speculative side is rollback-safe by construction: a reservation made
  against the pre-retire pool is conservative (retirements only free
  pages), ``alloc.retire(slot)`` fully undoes it, and a head the tight
  pool cannot yet guarantee simply defers to the serial walk after the
  reconcile. Stages are gated per-phase on their own measured cost
  (``_PipelineGate`` — cheap phases are not worth moving across the
  round boundary). ``ENGINE_DECODE_PIPELINE=off`` forces the serial loop,
  and greedy output is bit-identical either way.
- Flight recorder (telemetry/flight.py): every scheduler round commits ONE
  compact frame — mode, slot/queue occupancy, admissions/retirements and
  the blocked cause, tokens/accepted/effective depth, device-busy split per
  fused program family vs host bubble, and the page pool's state — at the
  single ``_commit_round`` point, into a fixed ring read out by
  ``GET /decode/flight`` / ``GET /decode/health``. Goodput (tokens to
  requests that met their deadline budget) and TTFT/ITL SLO attainment
  (``tpu.decode_slo_{ttft,itl}_ms``) ride the same substrate; breaches
  auto-dump the ring into the span store with a metric exemplar linking
  back. ``ENGINE_FLIGHT=off`` kills it.

Equivalence contract: with greedy sampling the scheduler produces token-
for-token the fused oracle's output for every sequence, regardless of when
each sequence was admitted — speculative or not (acceptance keeps exactly
the draft prefix matching the target's own argmax chain); temperature > 0
speculation uses residual resampling so the output distribution is the
target's (tests/test_decode_scheduler.py proves this against ``generate``).

Compile discipline: every device program is compiled once at ``warmup()``;
``compile_counts()`` exposes the jit cache sizes so serving can assert zero
recompiles across changing batch composition (the same no-live-compile
policy ModelRuntime enforces with shape buckets).

The fused programs themselves, their jit handles, the device state only
they touch and the convention each round kind is called by live in
serving/decode_programs.py (``DecodePrograms``); a decoder family's step
and chunk bodies are the family's own (``family.fused_programs``). This
module is the loop: admission, the rounds (each written once), timing.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import time
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from seldon_core_tpu.core.errors import APIException, ErrorCode
from seldon_core_tpu.core.message import Meta, SeldonMessage
from seldon_core_tpu.engine.resilience import current_deadline
from seldon_core_tpu.metrics import NullMetrics
from seldon_core_tpu import telemetry
from seldon_core_tpu.telemetry import flight as flight_mod
from seldon_core_tpu.telemetry import profile as profile_mod
from seldon_core_tpu.telemetry.flight import (
    ANN_COPYOUT,
    ANN_DISPATCH,
    ANN_ENQUEUE,
    ANN_IDLE_WAIT,
    ANN_PHASE,
    ANN_READBACK,
    ANN_ROUND,
    F_CHUNK,
    F_COPY,
    F_DRAFT,
    F_STEP,
    F_VERIFY,
    P_ACCEPT_WALK,
    P_ADMIT,
    P_ALLOC,
    P_COMMIT,
    P_EMIT_SLO,
    P_PREFIX_MATCH,
    P_SAMPLING,
    P_SCATTER,
    FlightFrame,
    FlightRecorder,
    PhaseTimer,
    decode_pipeline_enabled,
)
from seldon_core_tpu.telemetry.flight import register as flight_register
from seldon_core_tpu.models.decoder import (
    decoder_dims,
    decoder_family,
    is_feature_draft,
    require_served,
    write_form,
)
from seldon_core_tpu.models.spec_tree import MAX_TREE_NODES, SpecTree, parse_spec_tree
from seldon_core_tpu.parallel.tp import (
    tp_width,
    decode_mesh_problems,
    decode_tp_mesh,
    decoder_param_shardings,
    kv_sharding,
)
from seldon_core_tpu.serving.affinity_router import (
    capture_prefix_len,
    usable_prefix_len,
)
from seldon_core_tpu.ops.paged_attention import pages_read, window_first_page, window_pages
from seldon_core_tpu.serving.decode_programs import DecodePrograms
from seldon_core_tpu.serving.kv_host_tier import KVHostTier
from seldon_core_tpu.serving.kv_pool import PagedKVPool
from seldon_core_tpu.persistence.state import make_state_store

log = logging.getLogger(__name__)

OnToken = Callable[[int, int], None]  # (token_id, index-within-generation)


class _TreeAutoTuner:
    """Accept-driven speculation controller: the depth-only ``_SpecAdapt``
    EWMA policy (plain-decode degrade below ``floor``, periodic depth-1
    probe, linear depth ramp to the ceiling) EXTENDED with per-depth tree
    reshaping from the accepted-path-length signal the
    ``spec_tree_{nodes,accepted_path_len}`` histograms record. Adaptation
    changes only DATA (per-slot accept limits / per-depth width masks),
    never program shapes — zero recompiles by construction — and NEVER
    widens past the configured tree (per depth ``min`` with the
    deployment branching).

    Width policy: ``reach[d]`` is an EWMA of the probability that a
    riding slot's accepted path REACHES depth d+1 (i.e. accepted >= d
    tokens, estimated only over slots whose limit allowed it). A depth
    that paths rarely reach holds nodes that are almost never on the
    accepted path — pure verify-width waste — so its width scales down
    proportionally (``reach / reach_hi``, floor 1) and is cut entirely
    below ``reach_lo``. While any depth is narrowed, every
    ``probe_every``-th speculative round runs the FULL configured shape
    (``probe=True``) so ``reach`` can recover when the workload turns —
    the same explore/exploit escape the depth controller's plain-probe
    uses. ``floor <= 0`` disables ALL adaptation (fixed shape), the
    documented ``decode_spec_accept_floor`` contract."""

    def __init__(
        self,
        floor: float,
        ceiling: int,
        tree: SpecTree | None = None,
        alpha: float = 0.2,
        probe_every: int = 16,
        reach_hi: float = 0.5,
        reach_lo: float = 0.05,
    ):
        self.floor = float(floor)
        self.ceiling = int(ceiling)
        self.tree = tree
        self.alpha = float(alpha)
        self.probe_every = int(probe_every)
        self.reach_hi = float(reach_hi)
        self.reach_lo = float(reach_lo)
        # optimistic start: the first rounds run the full configured shape
        # so a warm workload never pays a ramp-up
        self.rate = 1.0
        self.reach = [1.0] * (tree.depth if tree is not None else 0)
        self.plain_rounds = 0
        self.spec_rounds = 0
        self.probes = 0
        self.probing = False  # the LAST decide() returned a probe round

    def update(self, accepted: int, allowed: int, paths=None) -> None:
        """Per-round observation: total accepted/allowed (the depth
        controller's EWMA) and optionally the per-slot ``(accepted,
        limit)`` pairs of riding slots (the reach estimate). Probe rounds
        feed both — that is their whole point."""
        if allowed > 0:
            self.rate += self.alpha * (accepted / allowed - self.rate)
        if not paths or self.tree is None:
            return
        # reach[0] stays pinned at 1.0 — depth-1 nodes are reachable by
        # construction (the walk always considers the root's children),
        # so only deeper levels carry an estimate
        for d in range(1, len(self.reach)):
            samples = [1.0 if a >= d else 0.0 for a, lim in paths if lim >= d + 1]
            if samples:
                mean = sum(samples) / len(samples)
                self.reach[d] += self.alpha * (mean - self.reach[d])

    def depth(self) -> int:
        """Effective speculation depth for the NEXT round (0 = plain).
        Mutates the probe counters — call once per round (``decide``)."""
        if self.floor <= 0.0:
            return self.ceiling
        if self.rate < self.floor:
            self.plain_rounds += 1
            if self.probe_every and self.plain_rounds % self.probe_every == 0:
                self.probes += 1
                self.probing = True
                return 1
            return 0
        self.plain_rounds = 0
        frac = (self.rate - self.floor) / max(1.0 - self.floor, 1e-6)
        return max(1, min(self.ceiling, int(np.ceil(frac * self.ceiling))))

    def widths(self) -> tuple[int, ...] | None:
        """Tuned per-depth width ceiling for the NEXT round (None = no
        tree / adaptation off — use the configured shape). Never exceeds
        the configured branching; depth 1 always keeps its configured
        width (its nodes are reachable by construction — reach has
        nothing to say about them; a round with no depth-1 node is a
        plain round, which the depth controller owns)."""
        if self.tree is None or self.floor <= 0.0:
            return None
        base = self.tree.branching
        out = []
        for d, b in enumerate(base):
            if d == 0:
                out.append(b)
                continue
            r = self.reach[d]
            if r < self.reach_lo:
                out.append(0)
                continue
            if r >= self.reach_hi:
                out.append(b)
            else:
                out.append(max(1, int(np.ceil(b * r / self.reach_hi))))
        if self.probing or tuple(out) == base:
            return base if self.probing else tuple(out)
        # narrowed: periodic full-shape probe so reach can recover
        self.spec_rounds += 1
        if self.probe_every and self.spec_rounds % self.probe_every == 0:
            self.probes += 1
            self.probing = True
            return base
        return tuple(out)

    def decide(self) -> tuple[int, tuple[int, ...] | None, bool]:
        """One call per round: (effective depth, tuned width ceiling or
        None, probe flag). Probe rounds — the depth controller's depth-1
        recovery probe and the width tuner's full-shape probe — are
        flagged so the flight frame can tag them (aggregates must not
        read deliberate exploration as genuine accept degradation).
        Depth 0 skips the width tuner entirely: a plain round runs no
        speculative dispatch, so scheduling (and counting) a width probe
        there would burn the probe cadence on rounds that cannot
        observe anything."""
        self.probing = False
        d = self.depth()
        if d == 0:
            return 0, None, False
        w = self.widths()
        return d, w, self.probing


class _PipelineGate:
    """Per-stage cost gate for the pipelined loop's overlap window: an
    EWMA of each stage's measured host cost, with a floor below which the
    stage stops riding the pipeline — moving a trivially cheap phase
    across the round boundary buys nothing and costs shadow-state surface
    (the measured-cost gating the ROADMAP item calls for). A skipped
    stage still probes every ``probe_every``-th opportunity so a workload
    whose host cost grows re-enables it. Optimistic start: an unmeasured
    stage always runs, so the smoke geometries the pipeline is judged on
    never pay a ramp-up."""

    __slots__ = ("floor_ns", "alpha", "probe_every", "ewma", "skips")

    def __init__(
        self, floor_ns: float = 1_000.0, alpha: float = 0.2, probe_every: int = 32
    ):
        self.floor_ns = float(floor_ns)
        self.alpha = float(alpha)
        self.probe_every = int(probe_every)
        self.ewma: dict[str, float] = {}
        self.skips: dict[str, int] = {}

    def allow(self, stage: str) -> bool:
        mean = self.ewma.get(stage)
        if mean is None or mean >= self.floor_ns:
            return True
        n = self.skips.get(stage, 0) + 1
        self.skips[stage] = n
        return self.probe_every > 0 and n % self.probe_every == 0

    def note(self, stage: str, ns: int) -> None:
        prev = self.ewma.get(stage)
        self.ewma[stage] = (
            float(ns) if prev is None else prev + self.alpha * (ns - prev)
        )


class _EnqueueSpan:
    """``with d.enqueue(F_X):`` round the call(s) of the jitted program:
    the calling thread's ``ANN_ENQUEUE`` annotation; a family other than
    the dispatch's own (the draft of a speculative round pair) gets its
    wall booked to its own busy column and carved out of the pair's."""

    __slots__ = ("d", "family", "t0", "ann")

    def __init__(self, d: "_Dispatch", family: int):
        self.d = d
        self.family = family

    def __enter__(self):
        self.ann = flight_mod.annotate(ANN_ENQUEUE[self.family], **self.d.stats)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        d = self.d
        if self.family != d.family:
            dt = time.perf_counter_ns() - self.t0
            d.s._rb_busy[self.family] += dt
            d.carved += dt
        self.ann.__exit__(None, None, None)
        return False


class _Dispatch:
    """One device dispatch as the round loop sees it — THE place a
    dispatch is timed into the round's flight frame and named for a
    profiler session; ``_timed_call``, the step round and the speculative
    round pair all go through ``with self._dispatch(F_X) as d:`` (one
    preallocated handle per family; the loop awaits each dispatch, so a
    family never nests in itself).

    On the loop the handle spans hand-off to readback return
    (``ANN_DISPATCH``): that wall is the family's ``busy_ns``, and the part
    after the enqueue->readback mark its ``rdb_ns``. Inside it, a program
    set call (``DecodePrograms``: enqueues, returns ``(out, read)``) goes
    one of two ways: ``with d.enqueue():`` round the call on the loop, the
    overlap window, then ``await d.readback(out, read)`` (the pipelined
    round); or ``await d.run(fn)``, which makes call, mark and read in one
    piece off the loop (the serial round, the chunk round, the copy ladder).

    Either way the blocking read is ``_collect``: the one moment of a
    dispatch at which the host KNOWS where the device is (``out`` just
    became ready) is marked there, and the part of ``rdb_ns`` after that
    mark is the family's ``rdy_ns``: the copy to the host, ``read``'s own
    work, the return through ``_device_call`` to the loop. That is the
    host's return leg of the dispatch as a duration on the host's clock;
    wall less it less the device's time is the launch leg.

    Its ``ANN_DISPATCH`` annotation and the ``ANN_ENQUEUE`` /
    ``ANN_READBACK`` / ``ANN_COPYOUT`` under it say WHICH dispatch they are
    (``stats``): ``seq``, the scheduler's dispatch serial (monotonic over
    all families), ``round``, the index the round's frame will commit
    under, and what the call site noted through
    ``DecodeScheduler._dispatch(family, **stats)`` (a chunk's ``rows`` /
    ``c`` / ``live`` and the form its program's pool write took, ``write``
    "page" | "row"; a step's ``rows`` / ``live``). With no profiler session
    ``flight.annotate`` drops them."""

    __slots__ = ("s", "family", "t0", "carved", "ann", "noted", "stats")

    def __init__(self, sched: "DecodeScheduler", family: int):
        self.s = sched
        self.family = family
        self.carved = 0
        self.noted: dict = {}  # the call site's stats for the NEXT entry
        self.stats: dict = {}  # the open dispatch's

    def __enter__(self):
        s = self.s
        s._rb_mark_ns = s._rb_ready_ns = 0
        self.carved = 0
        s._dispatch_seq += 1
        self.stats = {"seq": s._dispatch_seq, "round": s.flight.rounds, **self.noted}
        self.noted = {}
        self.ann = flight_mod.annotate(ANN_DISPATCH[self.family], **self.stats)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t2 = time.perf_counter_ns()
        s = self.s
        s._rb_busy[self.family] += t2 - self.t0 - self.carved
        s._rb_rdb[self.family] += t2 - (s._rb_mark_ns or t2)
        s._rb_rdy[self.family] += t2 - (s._rb_ready_ns or t2)  # no read, no mark: 0
        self.ann.__exit__(None, None, None)
        return False

    def enqueue(self, family: int | None = None) -> _EnqueueSpan:
        return _EnqueueSpan(self, self.family if family is None else family)

    def _collect(self, out, read):
        """The blocking read, on the calling thread, in two steps: wait
        until the dispatch's ``out`` is computed, MARK, then ``read()`` it
        under ``ANN_COPYOUT``. The copy to the host is started before the
        wait, so it follows the program on the device with no host round
        trip between them; ``read`` then collects what is already on its
        way."""
        for a in jax.tree_util.tree_leaves(out):
            a.copy_to_host_async()
        jax.block_until_ready(out)
        self.s._rb_ready_ns = time.perf_counter_ns()
        ann = flight_mod.annotate(ANN_COPYOUT[self.family], **self.stats)
        try:
            return read()
        finally:
            ann.__exit__(None, None, None)

    async def run(self, fn):
        """``fn`` enqueues and returns a program set call's ``(out,
        read)``: run it through ``_device_call`` under ``ANN_ENQUEUE``,
        mark the enqueue->readback boundary — the family's wall splits
        there and the thread's annotation turns to ``ANN_READBACK`` — and
        make the blocking read (``_collect``). ``fn`` returning None reads
        nothing back: the whole call counts as enqueue (the copy ladder)."""
        s = self.s
        stats = self.stats

        def call():
            ann = flight_mod.annotate(ANN_ENQUEUE[self.family], **stats)
            try:
                queued = fn()
                if queued is None:
                    return None
                s._rb_mark_ns = time.perf_counter_ns()
                ann.__exit__(None, None, None)
                ann = flight_mod.annotate(ANN_READBACK[self.family], **stats)
                return self._collect(*queued)
            finally:
                ann.__exit__(None, None, None)

        out = await s._device_call(call)
        if s._faults is not None:
            # chaos readback stall: the dispatch completed but the
            # host-transfer wait drags — attributed to the family's
            # readback column like a real slow transfer would be
            stall = s._faults.readback_stall_s()
            if stall > 0:
                await asyncio.sleep(stall)
        return out

    async def readback(self, out, read):
        """The blocking host read of a dispatch the loop enqueued itself
        (the pipelined rounds; ``out``, ``read`` as the program set call
        returned them): marks, then runs ``_collect`` through
        ``_device_call`` under ``ANN_READBACK``."""
        self.s._rb_mark_ns = time.perf_counter_ns()
        stats = self.stats

        def call():
            ann = flight_mod.annotate(ANN_READBACK[self.family], **stats)
            try:
                return self._collect(out, read)
            finally:
                ann.__exit__(None, None, None)

        return await self.s._device_call(call)


class _PendingAdmit:
    """One flight-decided admission (shadow round state): the decision's
    operands held UN-installed until ``_apply_pending`` — the reconcile
    walks must see exactly the dispatch-time slot table. The allocator
    reservation (``try_admit``) is the decision's only live footprint, so
    ``alloc.retire(slot)`` is the complete rollback."""

    __slots__ = ("seq", "slot", "entry", "reuse", "t0")

    def __init__(self, seq: "_Seq", slot: int, entry, reuse: int, t0: int):
        self.seq = seq
        self.slot = slot
        self.entry = entry
        self.reuse = reuse
        self.t0 = t0


class _PrefixEntry:
    """One cached prefix: the token string it holds plus a REFERENCE to
    the pool pages carrying its K/V (a kv_pool pin id) — no private pool
    row, no copy anywhere in its lifecycle."""

    __slots__ = ("tokens", "length", "pages", "pin_id", "last_use", "hits", "state_row")

    def __init__(self, tokens: np.ndarray, pages: list[int], pin_id: int, state_row: int = -1):
        self.tokens = np.asarray(tokens, np.int32)
        self.length = int(self.tokens.shape[0])
        self.pages = list(pages)
        self.pin_id = pin_id
        # a recurrent family: the pool row that holds the state after
        # ``length`` tokens (bound to the pin: kv_pool ``PoolPin.state_row``)
        self.state_row = state_row
        self.last_use = 0
        self.hits = 0


class PrefixIndex:
    """Host-side index over token prefixes whose K/V lives in POOL PAGES
    (serving/kv_pool.py): a hit maps the entry's pages into the reader's
    block table (refcount bump) instead of copying anything.

    The depth a hit may reuse, by cache kind. PAGES: matching is
    longest-COMMON-prefix against ANY entry, not whole-entry match: causal
    K/V at position i depends only on tokens 0..i, so a partial overlap
    with a longer cached entry is exactly as reusable as a full one (what
    makes shared system prompts hit without any client hint: the first
    full-prompt capture seeds every later request's common prefix). STATE
    ROWS (``match(whole=True)``): a recurrent state after n tokens says
    nothing of the state after fewer, so an entry is reusable at its own
    length only, where its snapshot was taken, and only by a prompt that
    holds all of it: the longest entry the prompt begins with. The entries
    are few (``max_entries``) and their tokens one int32 array each, so a
    match is one vectorised compare an entry: a 3072-token prefix costs
    microseconds on the admission path, which lies between two dispatches
    with the device idle, and insert and evict build nothing.

    Capacity is bounded twice: ``max_entries`` caps the index itself
    (insert evicts the LRU entry and returns it so the caller can release
    its pin), and the PAGE POOL reclaims pin-only pages LRU-first under
    allocation pressure (the allocator calls back and the entries drop via
    ``remove_by_pins``). Readers never pin entries: once admission maps
    the pages, the slot's own refcounts keep them alive — an entry is
    always safe to evict."""

    def __init__(self, max_entries: int):
        self.max_entries = max_entries
        self.entries: dict[int, _PrefixEntry] = {}  # pin_id -> entry, oldest insert first
        self._clock = 0
        self.evictions = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match(
        self, prompt, touch: bool = True, whole: bool = False
    ) -> tuple["_PrefixEntry | None", int]:
        """Longest common prefix between ``prompt`` and any entry:
        (entry, depth); of entries equally deep the newest insert wins.
        ``whole``: only an entry the prompt holds ALL of counts (the class
        docstring's rule for state rows). ``touch=False`` peeks without
        bumping LRU age (the capture-dedup probe must not keep its own
        victim warm)."""
        prompt = np.asarray(prompt, np.int32)
        ent, depth = None, 0
        for e in self.entries.values():
            n = min(e.length, prompt.shape[0])
            if n < max(depth, 1):
                continue
            same = e.tokens[:n] == prompt[:n]
            d = n if same.all() else int(same.argmin())
            if whole and d < e.length:
                continue
            if d >= max(depth, 1):
                ent, depth = e, d
        if ent is None:
            return None, 0
        if touch:
            ent.last_use = self._tick()
            ent.hits += 1
        return ent, depth

    def insert(
        self, tokens, pages: list[int], pin_id: int, state_row: int = -1
    ) -> tuple["_PrefixEntry", "_PrefixEntry | None"]:
        """Index a captured prefix; returns (entry, evicted) where
        ``evicted`` is the LRU entry pushed out by the max_entries cap (the
        caller must release its pool pin) or None."""
        evicted = self.evict_lru() if len(self.entries) >= self.max_entries else None
        e = _PrefixEntry(tokens, pages, pin_id, state_row)
        e.last_use = self._tick()
        self.entries[pin_id] = e
        return e, evicted

    def remove(self, e: "_PrefixEntry") -> None:
        del self.entries[e.pin_id]

    def lru(self) -> "_PrefixEntry | None":
        """The least recently used entry; None where the index is empty."""
        return min(self.entries.values(), key=lambda e: e.last_use, default=None)

    def evict_lru(self) -> "_PrefixEntry | None":
        """Drop and return the least recently used entry (the caller releases
        its pool pin); None where the index is empty."""
        lru = self.lru()
        if lru is not None:
            self.remove(lru)
            self.evictions += 1
        return lru

    def remove_by_pins(self, pin_ids) -> int:
        """Pool-pressure reclaim callback: the allocator already dropped
        the pins' refs; drop the index entries that held them. Returns how
        many entries actually dropped."""
        dropped = 0
        for pin_id in pin_ids:
            if self.entries.pop(pin_id, None) is not None:
                dropped += 1
        self.evictions += dropped
        return dropped

    def clear(self) -> None:
        self.entries.clear()


class _Seq:
    """One in-flight generation request."""

    __slots__ = (
        "prompt", "max_new", "temperature", "top_k", "spec_k", "tree_widths",
        "on_token", "future", "uid",
        "tokens", "slot", "pos", "t_enqueued", "t_admitted", "t_first_token",
        "t_last_token",
        "deadline", "trace_ctxs", "gen_spans",
        "prefilling", "prefill_pos", "prefix_len", "chunk_cap",
        "cache_prefix", "chunk_idx", "state_src",
        "slo_deadline", "slo_ok", "slo_sink",
        "replay", "emit_base", "kv_tier",
    )

    def __init__(self, prompt, max_new, temperature, top_k, spec_k, on_token, future):
        self.prompt = prompt
        self.max_new = max_new
        self.temperature = temperature
        self.top_k = top_k
        self.spec_k = spec_k
        # tree mode: per-depth branching widths this request rides (the
        # deployment tree tightened by meta.tags.spec_tree); () elsewhere
        self.tree_widths: tuple[int, ...] = ()
        self.on_token = on_token
        self.future = future
        # scheduler-assigned serial (submit order): the chunk-plan snapshot
        # key needs slot occupancy disambiguated across slot reuse — id()
        # can alias after a retire frees the object
        self.uid = 0
        self.tokens: list[int] = []
        self.slot = -1
        self.pos = 0
        self.t_enqueued = time.perf_counter()
        self.t_admitted = 0.0  # slot assignment (_install_admit)
        self.t_first_token = 0.0
        self.t_last_token = 0.0
        self.deadline = 0.0  # admission deadline (0 = none)
        # incremental (prefix/chunk) prefill state: prefill_pos is the next
        # prompt position to compute; prefix_len the pool-reused span
        self.prefilling = False
        self.prefill_pos = 0
        self.prefix_len = 0
        self.chunk_cap = 0  # per-round prefill token cap (0 = whole suffix)
        self.cache_prefix = 0  # meta.tags.cache_prefix capture hint
        self.chunk_idx = 0
        # a recurrent family: the state row the NEXT chunk reads where it is
        # not the slot's own (the zero row, an entry's snapshot row), else -1
        self.state_src = -1
        # goodput/SLO attribution: the request's deadline budget (absolute
        # perf_counter; 0 = none) captured from the DEADLINE contextvar at
        # submit, whether every configured SLO held so far, and an optional
        # callback execute_message uses to tag the response
        self.slo_deadline = 0.0
        self.slo_ok = True
        self.slo_sink = None
        # migration replay (fleet fault recovery): the tokens a dead
        # replica already emitted for this request. Positions below
        # emit_base are teacher-forced from ``replay`` and re-emission is
        # suppressed — the resumed stream picks up at emit_base with no
        # duplicate or missing tokens.
        self.replay: tuple[int, ...] = ()
        self.emit_base = 0
        # tiered-KV opt-out (meta.tags.kv_tier, tighten-only): "" = full
        # ladder, "host" = no store consult, "off" = cold-only for this
        # request (device prefix match still applies — the tag governs
        # PROMOTION, the tiers below the device)
        self.kv_tier = ""
        # the submitter's trace context(s), captured at submit: the decode
        # loop runs in its OWN task (no ambient request context), so spans
        # are attached to each sequence's originating trace explicitly
        self.trace_ctxs = telemetry.current_contexts()
        self.gen_spans: list = []  # open "decode.generate" spans, one/ctx


class DecodeScheduler:
    """Slot-based continuous-batching decode loop for one decoder model.

    ``params`` is the decoder param pytree (models/decoder layout — already
    device-placed by ModelRuntime when built through serving). ``seq_len``
    is the fixed prompt bucket (the deployment's wire feature shape) and
    ``max_new_tokens`` the per-request generation cap the cache is sized
    for (``max_ctx = seq_len + max_new_tokens``)."""

    # a round that takes this long is logged with what held it (_commit_round)
    SLOW_ROUND_NS = 1_000_000_000
    # the rows ladder of the compact chunk programs (``chunk_buckets``): the
    # first takes every c, the rest the top c only; the last one a deployment
    # has slots for is the most slots a chunk round takes (``chunk_rows_cap``)
    CHUNK_ROWS = (2, 4)

    def __init__(
        self,
        params,
        *,
        seq_len: int,
        max_new_tokens: int,
        n_slots: int = 8,
        eos_id: int = -1,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        queue_timeout_s: float = 0.0,
        draft_params=None,
        spec_k: int = 0,
        spec_tree: str = "",
        spec_accept_floor: float = 0.0,
        prefix_slots: int = 0,
        prefix_ctx: int = 0,
        prefill_chunk: int = 0,
        kv_page_size: int = 0,
        kv_pages: int = 0,
        kv_dtype: str = "",
        kv_host_bytes: int = 0,
        kv_store_url: str = "",
        mesh_axes: dict | None = None,
        slo_ttft_ms: float = 0.0,
        slo_itl_ms: float = 0.0,
        metrics: NullMetrics | None = None,
        deployment_name: str = "",
        replica_id: int = 0,
        dtype=jnp.float32,
        family=None,
    ):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if spec_k > 0 and draft_params is None:
            raise ValueError(
                f"spec_k={spec_k} needs a draft model (decode_draft_model)"
            )
        # the decoder family: the model's spec names it
        # (ModelSpec.generative["family"]); what it does not serve it
        # refuses by name (FamilyNotServed)
        self.family = decoder_family(family)
        self._frame_counters = tuple(self.family.frame_counters)
        # a family with delta-rule layers: their passes a "step" or "chunk" dispatch and those in a kernel, as traced
        self._gdn_passes = getattr(self.family, "gdn_passes", None)
        if draft_params is not None or spec_k > 0 or str(spec_tree or "").strip():
            require_served(self.family, "speculation")
        if tp_width(mesh_axes) > 1:
            require_served(self.family, "decode_mesh")
        if kv_dtype == "int8":
            require_served(self.family, "kv_int8")
        if int(kv_host_bytes) > 0 or kv_store_url:
            require_served(self.family, "host_tier")
        # a family whose layers carry a recurrent state keeps it in state
        # rows beside the pages (module docstring)
        self._stateful = self.family.state_init is not None
        dims = self.family.decoder_dims(params)
        self.max_ctx = seq_len + max_new_tokens
        if self.max_ctx > dims["max_len"]:
            raise ValueError(
                f"seq_len {seq_len} + max_new_tokens {max_new_tokens} exceeds "
                f"the position table ({dims['max_len']})"
            )
        self.params = params
        self.seq_len = seq_len
        self.max_new_tokens = max_new_tokens
        self.n_slots = n_slots
        self.eos_id = int(eos_id)
        self.default_temperature = float(temperature)
        self.default_top_k = int(top_k)
        # how long a request may wait UN-ADMITTED before REQUEST_TIMEOUT —
        # the same queue contract the micro-batcher enforces (generation
        # time after admission is legitimate work and is not capped)
        self.queue_timeout_s = float(queue_timeout_s)
        self._metrics = metrics or NullMetrics()
        self._deployment = deployment_name
        # which replica of a scale-out fleet this scheduler is (0 on
        # single-scheduler deployments) — rides the flight recorder into
        # /decode/health so the affinity router can address it
        self.replica_id = int(replica_id)
        self._dtype = dtype
        # monotonically increasing RNG tick, folded into the seed key
        # inside the compiled programs (a traced scalar — never a recompile)
        self._tick = 0

        # speculation state: spec_k proposed tokens per round, a draft
        # slot cache beside the target's, and k columns of cache headroom —
        # the widened verify writes a fixed [k+1]-wide K/V block at each
        # slot's position, and a slot one token from its budget must not
        # have that block clamp backwards over accepted entries.
        # decode_spec_tree upgrades the round from a k-chain to a token
        # TREE (models/spec_tree.py): the draft proposes branching[d]
        # candidates per depth, ONE widened target dispatch scores the
        # whole flattened tree, and acceptance walks the longest valid
        # path — spec_k then reads as the tree's DEPTH (the per-request
        # spec_k tighten caps depth; meta.tags.spec_tree tightens widths).
        tree_text = str(spec_tree or "").strip()
        self.spec_tree: SpecTree | None = None
        if tree_text:
            if draft_params is None:
                raise ValueError(
                    "decode_spec_tree needs a draft model (decode_draft_model)"
                )
            self.spec_tree = SpecTree.from_text(tree_text)
            # the knob string as span-attribute text ("4,2,1") — traces
            # name the shape without re-deriving it from branching
            self._tree_text = ",".join(str(b) for b in self.spec_tree.branching)
            if self.spec_tree.n_tree > MAX_TREE_NODES:
                raise ValueError(
                    f"decode_spec_tree {tree_text!r} flattens to "
                    f"{self.spec_tree.n_tree} nodes — the widened verify "
                    f"dispatch caps at {MAX_TREE_NODES}"
                )
        self.spec_enabled = draft_params is not None and (
            spec_k >= 1 or self.spec_tree is not None
        )
        # feature-level drafting (EAGLE-style): the draft is the one-layer
        # feature HEAD (models/decoder.init_feature_draft — the ``fc``
        # fuse marks the layout) conditioned on the target's final-layer
        # hidden instead of re-embedded tokens. Feature mode always rides
        # the TREE round programs: a chain-only config (decode_spec_k
        # without decode_spec_tree) is promoted to the degenerate
        # branching-1 tree, which IS the chain.
        self.feature_draft = self.spec_enabled and is_feature_draft(draft_params)
        if self.feature_draft and self.spec_tree is None:
            if int(spec_k) > MAX_TREE_NODES:
                # the promoted branching-1 tree rides the same widened
                # dispatch — enforce the verify-width headroom HERE, since
                # the chain-shaped check below only runs when no tree
                # exists (it would be bypassed by the promotion)
                raise ValueError(
                    f"decode_spec_k={int(spec_k)} exceeds the widened-verify "
                    f"headroom ({MAX_TREE_NODES} proposed tokens per dispatch)"
                )
            self.spec_tree = SpecTree.chain(max(1, int(spec_k)))
            self._tree_text = ",".join(str(b) for b in self.spec_tree.branching)
        self.spec_k = (
            self.spec_tree.depth
            if self.spec_tree is not None
            else (int(spec_k) if self.spec_enabled else 0)
        )
        if self.spec_tree is None and self.spec_k > MAX_TREE_NODES:
            # same verify-width headroom cap as the tree (a k-chain IS a
            # branching-1 tree of k nodes) — enforced here so an oversized
            # decode_spec_k fails at build, not at trace time
            raise ValueError(
                f"decode_spec_k={self.spec_k} exceeds the widened-verify "
                f"headroom ({MAX_TREE_NODES} proposed tokens per dispatch)"
            )
        self.draft_params = draft_params if self.spec_enabled else None
        # accept-driven speculation controller: the EWMA of
        # accepted/allowed drives the EFFECTIVE depth between plain decode
        # (rate < floor) and the configured ceiling, and — on tree
        # deployments — the per-depth reach estimate reshapes the width
        # masks within the configured tree. Data-only adaptation, zero
        # recompiles. floor <= 0 pins the configured shape.
        self._adapt = (
            _TreeAutoTuner(spec_accept_floor, self.spec_k, self.spec_tree)
            if self.spec_enabled
            else None
        )

        # prefix cache: the prefix index over pool-page references.
        # prefix_slots caps the INDEX (entries), not device rows — pages
        # live in the shared pool and reclaim under allocation pressure.
        self.prefix_enabled = prefix_slots > 0
        self.prefix_slots = int(prefix_slots) if self.prefix_enabled else 0
        self.prefix_ctx = (
            min(int(prefix_ctx) or seq_len, seq_len) if self.prefix_enabled else 0
        )
        self.prefill_chunk = min(max(0, int(prefill_chunk)), seq_len)
        # ALL admission is incremental now (the monolithic admit program is
        # gone): prompt compute rides the chunk ladder — one dispatch for a
        # whole wave at the top bucket, or Sarathi-interleaved rounds when
        # decode_prefill_chunk caps it. Kept as an attribute for
        # bench/test introspection.
        self.incremental = True
        top = self.prefill_chunk or seq_len
        # the chunk ladder: the (rows, c) batches a chunk round dispatches
        # at, cheapest first — a round takes the first entry that holds
        # its prefilling slots and their longest chunk, so the program
        # computes the slots that prefill, not all ``n_slots`` (at steady
        # traffic one or two slots prefill a round; every term of a chunk
        # program but the weights' read grows with its rows). c climbs by
        # powers of FOUR from 16: each entry is a full-transformer program
        # and the ladder dominates warmup, round COUNTS are set by the
        # chunk cap, not the bucket (a 5-token suffix rides c = 16 with
        # junk-masked slack), and under 16 positions a few rows cost what
        # the weights' read costs, like a step. Rows climb CHUNK_ROWS, every
        # entry past the first at the top c only, and END at
        # ``chunk_rows_cap`` = min(n_slots, CHUNK_ROWS[-1]): a round takes
        # at most that many slots, by arrival (``_chunk_rows_taken``), and
        # the rest prefill in the next round. There is no ``(n_slots, top)``
        # entry: past four rows a row costs the same or more (the weights'
        # read is long paid for), a wave of five in 64 slots computed 64
        # rows, and while it ran no slot emitted; between two compact rounds
        # the generating slots step. The feature head's twin carries its
        # feature buffer and the draft's flat cache by slot, so it keeps
        # every c at full width (row r is slot r) and is not capped.
        cs, b = [], 16
        while b < top:
            cs.append(b)
            b *= 4
        cs.append(top)
        if self.feature_draft:
            self.chunk_rows_cap, rows = n_slots, []
        else:
            self.chunk_rows_cap = min(n_slots, self.CHUNK_ROWS[-1])
            rows = [r for r in self.CHUNK_ROWS if r < self.chunk_rows_cap]
        rows.append(self.chunk_rows_cap)
        self.chunk_buckets = tuple(
            [(rows[0], c) for c in cs] + [(r, top) for r in rows[1:]]
        )
        # paged pool geometry: the write mask junk-redirects out-of-range
        # entries, so the pool needs NO verify/chunk headroom columns —
        # virtual context is exactly seq + max_new (rounded up to pages).
        # The flat DRAFT cache still needs the spec_k headroom (its
        # dynamic_update_slice would clamp backwards at the context edge).
        self._cache_ctx = self.max_ctx
        self._draft_ctx = self.max_ctx + self.spec_k
        if self.spec_enabled:
            ddims = decoder_dims(draft_params)
            if ddims["vocab"] != dims["vocab"]:
                raise ValueError(
                    f"draft vocab {ddims['vocab']} != target vocab "
                    f"{dims['vocab']} — speculation needs a shared vocabulary"
                )
            if self.max_ctx > ddims["max_len"]:
                raise ValueError(
                    f"draft position table ({ddims['max_len']}) is smaller "
                    f"than seq_len + max_new_tokens ({self.max_ctx})"
                )
            if self.feature_draft and ddims["hidden"] != dims["hidden"]:
                raise ValueError(
                    f"feature draft hidden {ddims['hidden']} != target "
                    f"hidden {dims['hidden']} — the head's fc fuse consumes "
                    "the target's feature vector directly"
                )

        # tensor-parallel decode mesh (parallel/tp.py): params (target AND
        # draft) are committed to the head/FFN partitioning up front, so
        # every jit below traces against the sharded layout and GSPMD
        # fuses the per-layer all-reduces into the already-fused programs.
        # Raises on an unservable request (too many devices, indivisible
        # heads/ffn) — the serving builder pre-checks and warn-disables.
        self.mesh, self._tp_axis, self.tp = decode_tp_mesh(
            mesh_axes, params, self.draft_params
        )
        if self.mesh is not None:
            self.params = params = jax.device_put(
                params, decoder_param_shardings(params, self.mesh, self._tp_axis)
            )
            if self.spec_enabled:
                self.draft_params = draft_params = jax.device_put(
                    draft_params,
                    decoder_param_shardings(draft_params, self.mesh, self._tp_axis),
                )
        elif self.spec_enabled:
            # no decode mesh: commit the draft to the TARGET params'
            # sharding. On the defaulted serving path the runtime commits
            # the target to the deployment mesh while the builder
            # device_put the draft bare (single device) — the verify
            # program takes both and jit refuses mixed device sets
            # (latent since PR 4; only a defaulted boot presents it).
            leaves = [
                leaf
                for leaf in jax.tree_util.tree_leaves(params)
                if isinstance(leaf, jax.Array)
            ]
            if leaves:
                sharding = leaves[0].sharding
                self.draft_params = draft_params = jax.tree.map(
                    lambda a: jax.device_put(a, sharding), draft_params
                )
        # span attributes distinguishing sharded deployments in /traces
        self._mesh_attrs = (
            {
                "tp": self.tp,
                "mesh_axes": ",".join(f"{k}={v}" for k, v in (mesh_axes or {}).items()),
            }
            if self.mesh is not None
            else {}
        )

        if self.prefix_enabled:
            self._prefix_index = PrefixIndex(self.prefix_slots)

        # the paged KV pool both live slots and the prefix cache allocate
        # from (serving/kv_pool.py) — geometry/validation live there. On a
        # decode mesh the pool payloads commit HEAD-sharded (int8 scale
        # planes replicated) and the CoW ladder pins matching output
        # shardings; single-device keeps the PR 5 behavior of matching
        # the params' sharding (the defaulted serving path).
        self.pool = PagedKVPool(
            params,
            n_slots=n_slots,
            cache_ctx=self._cache_ctx,
            page_size=kv_page_size,
            n_pages=kv_pages,
            kv_dtype=kv_dtype,
            dtype=dtype,
            place=lambda arrs: self._commit_kv(params, arrs),
            shardings_fn=(
                (lambda a: kv_sharding(self.mesh, self._tp_axis, a))
                if self.mesh is not None
                else None
            ),
            kv_init=self.family.paged_kv_init,
            state_init=self.family.state_init,
            n_state_rows=self.prefix_slots,
            # a family with sliding-window layers: the second page kind, its
            # count derived from the slots' rings and the entries' last windows
            window=dims.get("kv_window", 0),
            max_write=top,
            n_prefix=self.prefix_slots,
        )
        # a cache that is reusable only at the length it was kept at: state
        # rows (a snapshot) or window-kind pages (the last window before it).
        # Such a family captures at a hint's boundary and hits whole entries.
        self._boundary_cache = self._stateful or self.pool.windowed
        if self.prefix_enabled:
            self.pool.alloc.on_pins_reclaimed = self._on_pins_reclaimed
        # demand-paged prefix-page tiers below the device pool
        # (serving/kv_host_tier.py): entries the pool/index evict demote
        # to host RAM (then the store); admission misses promote back
        # through preseed_pin-pinned free pages. Host-only state — zero
        # recompiles, bit-identical greedy output. A bad store URL raises
        # here (direct construction is strict; scheduler_for_executor
        # pre-checks and warn-disables).
        self._host_tier = None
        if self.prefix_enabled and int(kv_host_bytes) > 0:
            self._host_tier = KVHostTier(
                int(kv_host_bytes),
                page_size=self.pool.page_size,
                kv_dtype=self.pool.kv_dtype,
                store=make_state_store(kv_store_url) if kv_store_url else None,
                deployment=deployment_name or "decode",
                metrics=metrics,
            )
        # the compiled programs, the device state only they touch (draft
        # cache pair, feature carry) and the one convention each round kind
        # is called by: serving/decode_programs.py
        self.programs = DecodePrograms(
            self.family, params, self.draft_params, self.pool, dims=dims,
            n_slots=n_slots, seq_len=seq_len, seed=seed, mesh=self.mesh,
            tp_axis=self._tp_axis, spec_k=self.spec_k, spec_tree=self.spec_tree,
            draft_ctx=self._draft_ctx, dtype=dtype, place=self._commit_kv,
        )
        # on an accelerator, device dispatch + token readback block the
        # calling thread for the device-step latency — run them on the
        # shared compute pool so the serving event loop (ingress, batcher
        # timers, co-hosted tenants) stays responsive, exactly like the
        # executor's _settle_to_host. CPU-backend calls are the compute
        # itself and gain nothing from the hop.
        self._host_backend = all(d.platform == "cpu" for d in jax.devices())
        # multi-replica fleets override the CPU-backend inline-dispatch
        # default: each replica's dispatches hop to the shared compute pool
        # (XLA releases the GIL during execution) so N replicas' device
        # work genuinely overlaps instead of serializing on the one event
        # loop — the same rationale as offload_compute for co-hosted
        # tenants. Single schedulers keep the inline fast path (the hop
        # buys nothing when there is nothing to overlap with).
        self._offload_dispatch = False
        # fleet replicas get a DEDICATED single-thread dispatch executor
        # (one dispatch stream per replica — the in-process twin of one
        # engine thread per pod); None falls back to the shared pool
        self._dispatch_pool = None
        # whether this round's dispatches waited off the loop (_device_call):
        # the plain round's closing yield is for the rounds that did not
        self._round_yielded = False
        self._slots: list[_Seq | None] = [None] * n_slots
        self._free: list[int] = list(range(n_slots - 1, -1, -1))
        self._waiting: collections.deque[_Seq] = collections.deque()
        self._wake = asyncio.Event()
        self._task: asyncio.Task | None = None
        self._closed = False
        # decode-tier chaos profile (engine/faults.py install_decode_faults):
        # consulted at the top of each active round (hang / induced
        # allocator-OOM), per device readback (stall), and per health probe
        # (dropped response). None = no faults armed.
        self._faults = None

        # attribution counters (bench/diagnostics; prometheus carries the
        # production twins via metrics.decode_*)
        self.stat_steps = 0
        self.stat_tokens = 0
        self.stat_admitted = 0
        self.stat_retired = 0
        self.stat_occupancy_sum = 0.0  # active-slot fraction summed per step
        self.stat_peak_active = 0
        # speculation attribution: accept rate = accepted/proposed, and
        # emitted/dispatches is the realized tokens-per-target-dispatch
        self.stat_spec_dispatches = 0
        self.stat_spec_proposed = 0
        self.stat_spec_accepted = 0
        self.stat_spec_emitted = 0
        # slot-rides: occupied generating slots that rode a spec round
        # with a nonzero limit, and the tokens THOSE slots emitted —
        # ride_emitted/rides is the PER-SLOT accepted-tokens-per-dispatch
        # (the amortization a single sequence sees; emitted/dispatches is
        # the batch-wide one and also counts limit-0 slots' plain-
        # equivalent tokens, which must not inflate the per-ride figure)
        self.stat_spec_rides = 0
        self.stat_spec_ride_emitted = 0
        # prefix cache / chunked prefill attribution
        self.stat_prefix_hits = 0
        self.stat_prefix_misses = 0
        self.stat_prefix_tokens_saved = 0
        self.stat_prefix_captures = 0
        # captures that could not be made: a span without pages, and in a
        # recurrent family a request without a hint (its prompt-boundary
        # state no longer exists when it retires) or without a free row
        self.stat_prefix_capture_skips = 0
        # entries pre-seeded from another replica's spill at warm boot
        self.stat_prefix_preseeded = 0
        # tiered-KV attribution (serving/kv_host_tier.py holds the tier's
        # own counters; these track the scheduler's ladder traffic):
        # device evictions demoted to host/store, misses promoted back,
        # and how many promotions landed inside a pipeline overlap window
        self.stat_tier_demotions = 0
        self.stat_tier_promotions = 0
        self.stat_tier_promote_overlap = 0
        self.stat_chunk_dispatches = 0
        # slots that had a chunk to run in a round and were left for a later
        # one (``_chunk_rows_taken``), summed over the chunk dispatches
        self.stat_chunk_rows_held = 0
        # paged-pool attribution (the allocator owns the counters; these
        # track what the scheduler itself dispatched/declined)
        self.stat_kv_copy_rounds = 0
        # the window page kind (a family with sliding-window layers): pages
        # slots allocated, those they gave back as they moved past them, the
        # most live at a round's commit (the frames carry each round's)
        self.stat_kv_win_written = 0
        self.stat_kv_win_released = 0
        self.stat_kv_win_live_peak = 0
        self._kv_win_released_gauged = 0  # what /metrics' counter has been told of
        # scheduler rounds whose queue head could not reserve pages (one
        # waiting request blocked for N rounds counts N — a round counter,
        # not an admission counter)
        self.stat_admit_blocked_rounds = 0

        # SLO targets the goodput/attainment telemetry is judged against
        # (tpu.decode_slo_{ttft,itl}_ms; 0 = not configured). The deadline
        # leg needs no knob: a request that arrived under a deadline budget
        # (tpu.deadline_ms / meta.tags.deadline_ms) is judged against it at
        # retirement, and its tokens count as goodput only when it held.
        self.slo_ttft_s = max(0.0, float(slo_ttft_ms)) / 1e3
        self.slo_itl_s = max(0.0, float(slo_itl_ms)) / 1e3
        # decode-loop flight recorder (telemetry/flight.py): ONE compact
        # frame per scheduler round into a bounded ring, committed at the
        # single _commit_round point so per-round accounting cannot drift
        # between the spec and plain paths. ENGINE_FLIGHT=off is the kill
        # switch; the operator API serves the registry (GET /decode/flight,
        # GET /decode/health).
        self.flight = flight_register(
            FlightRecorder(
                n_slots=n_slots,
                name=deployment_name or "decode",
                slo_ttft_ms=float(slo_ttft_ms),
                slo_itl_ms=float(slo_itl_ms),
                replica_id=self.replica_id,
            )
        )
        # live O(1) queue-depth read for /decode/health — what the replica
        # router's bounded-load shed polls
        self.flight.queue_depth_source = lambda: len(self._waiting)
        # per-round host-phase timer (telemetry/flight.py PHASES): every
        # host segment of the loop runs under `with self._phase(P_X):` so
        # the frame's gap decomposes into admission / alloc / scatter /
        # emission / accept-walk / sampling / commit — the measurement the
        # pipelined-decode ROADMAP item is designed against. Rides the
        # flight kill switch (disabled timer = shared no-op handles).
        self._phases = PhaseTimer(enabled=self.flight.enabled)
        self._dispatches = tuple(_Dispatch(self, f) for f in range(len(ANN_DISPATCH)))
        self._dispatch_seq = 0  # dispatches entered, all families: the annotations' ``seq``
        # marked submits that reached the queue and their time on the loop
        # since the request's bytes (flight.Ingress): running totals, written
        # by submit alone; a frame carries what they rose by since the last
        # commit (the marks below, written by _commit_round alone). Not among
        # the _rb_* set: a submit lands between rounds too, and an idle wait's
        # _round_reset must not drop the one that woke it
        self.stat_ingress_ns = self.stat_ingress_requests = 0
        self._ingress_committed = (0, 0)
        self._round_ann = None  # the open ANN_ROUND trace annotation
        # pipelined decode rounds: while round N's step/verify dispatch is
        # in flight, round N+1's host phases run against the SHADOW state
        # below (pending admissions + a snapshot-keyed chunk-input plan),
        # reconciled at readback through _apply_pending and committed at
        # the single _commit_round funnel. ENGINE_DECODE_PIPELINE=off forces
        # the serial path; bench's A/B leg flips the attribute per run.
        self.pipeline_enabled = decode_pipeline_enabled()
        self._gate = _PipelineGate()
        self._pending_admits: list[_PendingAdmit] = []
        self._pending_chunk_plan: tuple | None = None
        # whether the last overlap window ran the admission sundries
        # (expiry sweep + gauges) — consumed by the serial walk's
        # take-accessor; survives _round_reset (it crosses the commit
        # boundary to the next round's walk)
        self._pending_admit_sweep = False
        self._seq_uid = 0
        self.stat_pipelined_rounds = 0  # rounds that ran an overlap window
        self.stat_pipeline_admits = 0  # admissions decided under a flight
        # admissions the pre-retire pool deferred to the serial walk
        self.stat_pipeline_deferred = 0
        # pending admits rolled back at reconcile (caller vanished in flight)
        self.stat_pipeline_rollbacks = 0
        self.stat_pipeline_plans_used = 0  # overlap-built chunk plans consumed
        # whether the loop is currently inside an overlap window — read by
        # the promotion path to attribute a promotion's transfer cost to
        # the in-flight dispatch it hid behind (host-only observability
        # state; single-writer: _overlap_window)
        self._in_overlap = False
        self._round_reset(open_round=False)

    def _commit_kv(self, params, arrs):
        """Commit cache/pool buffers to their serving-steady sharding
        before any compile. On a decode mesh that is the tensor-parallel
        layout (5-D KV payloads head-sharded, scale planes replicated —
        parallel/tp.py); otherwise the PR 5 behavior: match the params'
        sharding so the defaulted (mesh-committed-params) serving path
        warms the exact signatures live traffic presents."""
        if self.mesh is not None:
            return tuple(
                jax.device_put(a, kv_sharding(self.mesh, self._tp_axis, a))
                for a in arrs
            )
        return self._place_like(params, arrs)

    @staticmethod
    def _scatter_preserving_placement(dst, src, pages):
        """Eagerly write ``src`` into ``dst[:, pages]`` without changing
        the buffer's placement SIGNATURE — sharding and committed-ness
        both key the jit caches, so a device_put that merely re-commits
        an uncommitted pool buffer would force every compiled program
        (step/chunk/copy) to recompile on the next live round. Only
        re-place when the eager scatter actually moved the layout."""
        out = dst.at[:, pages].set(jnp.asarray(src))
        if out.sharding == dst.sharding and getattr(
            out, "committed", True
        ) == getattr(dst, "committed", True):
            return out
        return jax.device_put(out, dst.sharding)

    @staticmethod
    def _place_like(params, arrs):
        """Commit cache/pool buffers to the params' sharding up front.
        When the runtime device_put the params with a mesh sharding
        (the defaulted serving path), a jit call's output caches adopt it
        — so fresh UNCOMMITTED zeros would make the first warmup call per
        program compile a signature live traffic never presents again,
        and the first live dispatch would recompile. Committing to the
        steady-state sharding before any compile keeps warmup's
        signatures exactly the serving ones (host-numpy params — tests,
        direct use — are left alone)."""
        leaves = [
            leaf
            for leaf in jax.tree_util.tree_leaves(params)
            if isinstance(leaf, jax.Array)
        ]
        if not leaves:
            return tuple(arrs)
        return tuple(jax.device_put(a, leaves[0].sharding) for a in arrs)

    # ---------------------------------------------------------------- warmup
    def warmup(self) -> None:
        """Compile every device program ahead of traffic (the chunk ladder,
        the step program, the pool's CoW copy ladder, the draft-admit
        ladder and the speculation pair: ``DecodePrograms.warmup``).
        Serving must never pay an XLA compile on a live request —
        compile_counts() after this is the zero-recompile baseline."""
        t0 = time.perf_counter()
        self.programs.warmup(self.chunk_buckets)
        # record the compile cost on the existing compile metric (bucket
        # label = slot count)
        self._metrics.compile(self._deployment, self.n_slots, time.perf_counter() - t0)
        self._warmup_compile_counts = self.compile_counts()

    def _step_attn_pages(self, pos: np.ndarray, rows: np.ndarray) -> tuple[int, int]:
        """(pages a plain step's attention reads for one layer's K, pages
        its block tables name) from the positions the round built and the
        slots that generate (``rows``) — no readback. The kernel path
        (``programs.attn_kernel``) fetches each slot's ``ceil((pos + 1) /
        page_size)`` pages: a free slot's one junk page, a prefilling slot's
        up to its cursor, or, where the step is told its rows (a counting
        family: ops/gqa_decode.py ``step_reads``), one page for every slot
        that does not generate; the gather path all of them. A pool of two
        page kinds counts one layer of EACH: the full kind as above, the
        window kind the sliding layers' sub-table (``window_pages`` entries a
        slot through the gather; through the kernel from the sub-table's
        first page to the position's, one for a slot that does not
        generate)."""
        pool = self.pool
        ps, pages = pool.page_size, pool.pages_per_slot
        window = pool.alloc.win.window if pool.windowed else 0
        pw = min(window_pages(window, 1, ps), pages) if window else 0
        table = self.n_slots * pages * (2 if window else 1)
        if not self.programs.attn_kernel:
            return self.n_slots * (pages + pw), table
        if self._stateful or self._frame_counters:
            pos = np.where(rows, pos, 0)
        read = int(pages_read(pos, ps, pages).sum())
        if window:
            read += int(pages_read(pos - window_first_page(pos, window, ps, pages, pw) * ps, ps, pw).sum())
        return read, table

    def compile_counts(self) -> dict[str, int]:
        """jit cache sizes per program (``DecodePrograms.compile_counts``)."""
        return self.programs.compile_counts()

    @property
    def stat_prefix_evictions(self) -> int:
        return self._prefix_index.evictions if self.prefix_enabled else 0

    def recompiles_since_warmup(self) -> int:
        """Number of XLA compiles since warmup() — the serving invariant is
        that this stays 0 across every batch composition (admissions,
        retirements, per-request sampling params)."""
        base = getattr(self, "_warmup_compile_counts", None)
        if base is None:
            return -1  # warmup never ran; nothing meaningful to report
        now = self.compile_counts()
        return sum(now.values()) - sum(base.values())

    # ---------------------------------------------------------------- submit
    @property
    def active(self) -> int:
        return self.n_slots - len(self._free)

    @property
    def queue_depth(self) -> int:
        """Requests waiting un-admitted — the autoscale/shed signal
        (/decode/health ``queue_depth``)."""
        return len(self._waiting)

    # ---------------------------------------------- warm scale-up spill
    def export_prefix_state(self, top_n: int = 0) -> dict | None:
        """Spill the prefix cache's hottest entries — prompt tokens plus
        their pool pages' bytes AS STORED (an int8 pool spills quantized
        planes + scale/zp verbatim; no dequant round-trip) — so a new
        replica can pre-seed its own pool (serving/affinity_router.py).
        Ranked by how referenced each entry's pages are (allocator
        refcounts: live sharers = heat), then index hits. ``top_n`` caps
        the entries (0 = all). Returns None when the prefix cache is
        off."""
        require_served(self.family, "prefix_export")
        if not self.prefix_enabled:
            return None
        alloc = self.pool.alloc
        entries = sorted(
            self._prefix_index.entries.values(),
            key=lambda e: (
                sum(int(alloc.refs[p]) for p in e.pages),
                e.hits,
                e.last_use,
            ),
            reverse=True,
        )
        if top_n > 0:
            entries = entries[: int(top_n)]
        # gather ONLY the selected entries' pages device-side and read
        # back those slices — never the whole pool (a full-pool host copy
        # is the entire KV cache's bytes, and the autoscale spill runs
        # this on the serving loop at peak load by design)
        return {
            "page_size": self.pool.page_size,
            "kv_dtype": self.pool.kv_dtype,
            "entries": [
                {
                    "tokens": np.asarray(e.tokens, np.int32).copy(),
                    "components": [
                        np.asarray(comp[:, jnp.asarray(e.pages, jnp.int32)])
                        for comp in self.pool.state
                    ],
                }
                for e in entries
            ],
        }

    def preseed_prefix_state(self, payload: dict | None) -> int:
        """Pre-seed the page pool + prefix index from a spilled payload
        (``export_prefix_state``), so this replica's FIRST shared-prompt
        request admits on the warm TTFT path. Pure boot-time work: pages
        come straight off the free list into prefix pins (reservation
        invariant untouched), bytes land with one eager update per pool
        component, and the arrays are re-committed to their existing
        sharding so the warmed program signatures stay exactly the live
        ones. Entries that don't fit this deployment's geometry are
        skipped; pool pressure stops the walk. Returns entries seeded."""
        require_served(self.family, "prefix_export")
        if not self.prefix_enabled or not payload:
            return 0
        if (
            payload.get("page_size") != self.pool.page_size
            or payload.get("kv_dtype") != self.pool.kv_dtype
        ):
            log.warning(
                "prefix spill geometry mismatch (page_size/kv_dtype) — "
                "preseed skipped"
            )
            return 0
        state = list(self.pool.state)
        # stage every entry first, then apply ONE scatter per pool
        # component: a per-entry .at[].set materializes a full component
        # copy each time, multiplying boot time (and peak device memory)
        # by the entry count on a real pool
        staged: list[tuple[np.ndarray, object]] = []  # (span tokens, pin)
        staged_bytes: list[list[np.ndarray]] = [[] for _ in state]
        for entry in payload.get("entries", ()):
            tokens = np.asarray(entry.get("tokens"), np.int32).reshape(-1)
            comps = entry.get("components") or []
            if len(comps) != len(state):
                continue
            # whole pages only: a partial tail page has no donor slot to
            # copy-on-write from here, so clamp DOWN to the page boundary
            # (the uncovered tail prefills — same as any partial hit)
            length = capture_prefix_len(len(tokens), self.prefix_ctx, self.seq_len)
            length = (length // self.pool.page_size) * self.pool.page_size
            n_pages = self.pool.alloc.pages_for(length)
            if n_pages < 1:
                continue
            span = tokens[:length]
            _, depth = self._prefix_index.match(span, touch=False)
            if depth >= length or any(
                len(t) >= length and np.array_equal(t[:length], span)
                for t, _ in staged
            ):
                continue  # already covered (existing or staged entry)
            # every axis validated BEFORE the pin allocation — including
            # the page axis on every sibling component (a truncated/
            # corrupt spill must be SKIPPED per the contract, not raise
            # out of the boot with a pin leaked)
            ok = True
            entry_bytes = []
            for ci, dst in enumerate(state):
                full = np.asarray(comps[ci])
                if (
                    full.ndim != len(dst.shape)
                    or full.shape[0] != dst.shape[0]
                    or full.shape[1] < n_pages
                    or full.shape[2:] != tuple(dst.shape[2:])
                    or full.dtype != dst.dtype
                ):
                    ok = False
                    break
                entry_bytes.append(full[:, :n_pages])
            if not ok:
                continue
            pin = self.pool.alloc.preseed_pin(n_pages)
            if pin is None:
                break  # free list exhausted — stop seeding, keep serving
            staged.append((span, pin))
            for ci, src in enumerate(entry_bytes):
                staged_bytes[ci].append(src)
        if not staged:
            return 0
        pages = np.asarray(
            [p for _, pin in staged for p in pin.pages], np.int64
        )
        for ci, dst in enumerate(state):
            src = np.concatenate(staged_bytes[ci], axis=1)
            state[ci] = self._scatter_preserving_placement(dst, src, pages)
        self.pool.state = tuple(state)
        for span, pin in staged:
            _, evicted = self._prefix_index.insert(span, pin.pages, pin.pin_id)
            if evicted is not None:
                self._demote_entry(evicted)
                self.pool.alloc.release(evicted.pin_id)
                self._metrics.decode_prefix_evicted(self._deployment)
        self.stat_prefix_preseeded += len(staged)
        self._metrics.router_preseed(self._deployment, int(len(pages)))
        self._kv_gauges()
        return len(staged)

    async def submit(
        self,
        prompt,
        *,
        max_new_tokens: int | None = None,
        temperature: float | None = None,
        top_k: int | None = None,
        spec_k: int | None = None,
        spec_tree: str | None = None,
        cache_prefix: int | None = None,
        prefill_chunk: int | None = None,
        kv_tier: str | None = None,
        on_token: OnToken | None = None,
        ingress: "flight_mod.Ingress | None" = None,
        _slo_sink=None,
        _replay_tokens=None,
    ) -> np.ndarray:
        """Generate for one prompt [seq_len]; resolves with the full int32
        sequence (prompt echoed, generated ids appended). ``on_token`` is
        called inline from the decode loop per generated token — keep it
        cheap (the streaming endpoint pushes into an asyncio.Queue).
        ``spec_k`` tightens (never widens) the deployment's speculative
        proposal length; 0 opts this request out of speculation.
        ``cache_prefix`` hints how many leading prompt tokens are worth
        capturing into the prefix pool (a shared system prompt's length);
        ``prefill_chunk`` tightens (never widens) the deployment's
        per-round prefill chunk — both are ignored when the corresponding
        tier is disabled. ``_replay_tokens`` (fleet migration only) is the
        token prefix a dead replica already emitted: those positions are
        teacher-forced and not re-streamed, so the resumed request is
        bit-identical to an uninterrupted greedy run. ``ingress`` is the
        mark the serving layer made when it had the request's bytes in hand
        (``flight.Ingress``): its time to this request's place in the queue
        goes into the frame of the round it lands in."""
        if self._closed:
            raise APIException(
                ErrorCode.ENGINE_MICROSERVICE_ERROR, "decode scheduler closed"
            )
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if prompt.shape[0] != self.seq_len:
            raise APIException(
                ErrorCode.ENGINE_INVALID_JSON,
                f"prompt length {prompt.shape[0]} != deployment seq_len "
                f"{self.seq_len} (the generative tier serves one prompt bucket)",
            )
        max_new = int(max_new_tokens) if max_new_tokens is not None else self.max_new_tokens
        max_new = max(1, min(max_new, self.max_new_tokens))
        temp = float(temperature) if temperature is not None else self.default_temperature
        k = int(top_k) if top_k is not None else self.default_top_k
        sk = self.spec_k if spec_k is None else max(0, min(int(spec_k), self.spec_k))
        loop = asyncio.get_running_loop()
        seq = _Seq(prompt, max_new, temp, k, sk, on_token, loop.create_future())
        self._seq_uid += 1
        seq.uid = self._seq_uid
        # goodput attribution: a request submitted under a deadline budget
        # (tpu.deadline_ms stamped into the DEADLINE contextvar by the
        # service) is judged against it at retirement — its tokens count
        # as goodput only if the budget held
        d = current_deadline()
        if d is not None:
            seq.slo_deadline = time.perf_counter() + max(d.remaining(), 0.0)
        seq.slo_sink = _slo_sink
        if _replay_tokens:
            seq.replay = tuple(int(t) for t in _replay_tokens)
            seq.emit_base = len(seq.replay)
        if self.spec_tree is not None:
            # per-request branching tighten (meta.tags.spec_tree): per
            # depth min(request, deployment), omitted depths -> 0 (depth
            # tightening) — a request can narrow or shorten the tree,
            # never widen it; malformed strings are a client error
            widths = self.spec_tree.branching
            if spec_tree is not None:
                try:
                    # min_branch=0: a 0 width is the documented per-
                    # request opt-out (depth truncation / full plain)
                    widths = self.spec_tree.tighten(
                        parse_spec_tree(spec_tree, min_branch=0)
                    )
                except ValueError as e:
                    raise APIException(
                        ErrorCode.ENGINE_INVALID_JSON, f"meta.tags.spec_tree: {e}"
                    )
            seq.tree_widths = widths
        seq.chunk_cap = self.prefill_chunk
        if prefill_chunk is not None:
            pc = int(prefill_chunk)
            # tighten-only against the deployment cap (a smaller chunk
            # is tighter); with no deployment cap a request may still
            # ask for one. Values < 1 are IGNORED, not clamped to 1:
            # "0 = whole suffix" is the deployment knob's widest
            # setting, and a request must not widen past the
            # deployment's cap (nor accidentally get 1-token rounds)
            if pc >= 1:
                seq.chunk_cap = (
                    min(pc, self.prefill_chunk) if self.prefill_chunk else pc
                )
        if self.prefix_enabled and cache_prefix is not None:
            seq.cache_prefix = max(0, min(int(cache_prefix), self.prefix_ctx))
        if kv_tier is not None:
            # tighten-only tier opt-out (meta.tags.kv_tier): "off" skips
            # promotion entirely, "host" stops the consult at host RAM —
            # a request can narrow the ladder, never widen it. Ignored
            # (like every tier knob) when the tier is disabled.
            kt = str(kv_tier)
            if kt not in ("", "off", "host"):
                raise APIException(
                    ErrorCode.ENGINE_INVALID_JSON,
                    f"meta.tags.kv_tier '{kt}' must be 'off' or 'host'",
                )
            seq.kv_tier = kt
        if self.queue_timeout_s > 0:
            seq.deadline = seq.t_enqueued + self.queue_timeout_s
        self._waiting.append(seq)
        ns = ingress.done() if ingress is not None else None
        if ns is not None:
            self.stat_ingress_ns += ns
            self.stat_ingress_requests += 1
        self._ensure_loop()
        self._wake.set()
        return await seq.future

    # ----------------------------------------------------------------- loop
    def _ensure_loop(self) -> None:
        if self._task is None or self._task.done():
            self._task = asyncio.ensure_future(self._run())

    def _emit(self, seq: _Seq, tok: int) -> int:
        """Record one generated token: stream it, time it. Returns the
        EFFECTIVE token — during a migration replay the recorded token
        overrides the freshly computed one, and every consumer (finish
        check, next-round input via seq.tokens[-1]) must use the returned
        value. Runs under the emit/SLO phase — inside the accept/sampling
        walks the inner phase wins, so emission cost reads apart from the
        walk around it."""
        with self._phase(P_EMIT_SLO):
            return self._emit_inner(seq, tok)

    def _emit_inner(self, seq: _Seq, tok: int) -> int:
        idx = len(seq.tokens)
        if idx < seq.emit_base:
            # migration replay: teacher-force the token the dead replica
            # already emitted (and streamed). No metrics, no on_token —
            # the original emission was the real one; this pass only
            # rebuilds KV state so generation resumes at emit_base with
            # the exact context of the uninterrupted run.
            tok = int(seq.replay[idx])
            seq.tokens.append(tok)
            seq.t_last_token = time.perf_counter()
            if idx == 0:
                seq.t_first_token = seq.t_last_token
            return tok
        now = time.perf_counter()
        seq.tokens.append(tok)
        if len(seq.tokens) == 1:
            seq.t_first_token = now
            self._rb_prefill += int((now - seq.t_admitted) * 1e9)
            self._rb_first_tokens += 1
            ttft = now - seq.t_enqueued
            self._metrics.decode_ttft(self._deployment, ttft)
            if self.prefix_enabled:
                # cold-vs-warm TTFT split: the latency contract prefix
                # reuse exists to move
                self._metrics.decode_ttft_split(
                    self._deployment,
                    ttft,
                    "warm" if seq.prefix_len > 0 else "cold",
                )
            if self.slo_ttft_s > 0:
                # TTFT attainment against the deployment SLO; a breach
                # auto-dumps the flight ring (rate-limited) and the dump's
                # trace id rides the breach counter as an exemplar, so a
                # dashboard breach links to the rounds surrounding it
                ok = ttft <= self.slo_ttft_s
                if not ok:
                    seq.slo_ok = False
                tid = self.flight.note_ttft(ok)
                self._metrics.decode_slo(
                    self._deployment, "ttft", ok, trace_id=tid or None
                )
            # TTFT as a trace event on the sequence's generate span — the
            # latency contract a streaming client actually feels
            for sp in seq.gen_spans:
                sp.add_event(
                    "first_token",
                    {"ttft_ms": round(ttft * 1e3, 3)},
                )
        else:
            itl = now - seq.t_last_token
            self._metrics.decode_inter_token(self._deployment, itl)
            if self.slo_itl_s > 0:
                ok = itl <= self.slo_itl_s
                if not ok:
                    seq.slo_ok = False
                tid = self.flight.note_itl(ok)
                self._metrics.decode_slo(
                    self._deployment, "itl", ok, trace_id=tid or None
                )
        seq.t_last_token = now
        self.stat_tokens += 1
        self._rb_tokens += 1
        if seq.on_token is not None:
            try:
                seq.on_token(tok, len(seq.tokens) - 1)
            except Exception:  # noqa: BLE001 - a slow/broken consumer must not kill the loop
                log.exception("on_token callback failed")
        return tok

    def _finished(self, seq: _Seq, tok: int) -> bool:
        return tok == self.eos_id or len(seq.tokens) >= seq.max_new

    def _resolve(self, seq: _Seq) -> None:
        if not seq.future.done():
            seq.future.set_result(
                np.concatenate([seq.prompt, np.asarray(seq.tokens, np.int32)])
            )

    def _on_pins_reclaimed(self, pin_ids: list[int]) -> None:
        """Allocator callback, once per reclaim wave: pool pressure
        reclaimed prefix pins — drop the index entries that held them
        (their pages are gone/repurposed). The demotion window: the
        allocator fires this BEFORE any reclaimed page is repurposed, so
        a device readback here still yields the entries' exact bytes —
        the eviction becomes a demotion into the host tier instead of a
        loss."""
        if self._host_tier is not None:
            for pin_id in pin_ids:
                entry = self._prefix_index.entries.get(pin_id)
                if entry is not None:
                    self._demote_entry(entry)
        dropped = self._prefix_index.remove_by_pins(pin_ids)
        for _ in range(dropped):
            self._metrics.decode_prefix_evicted(self._deployment)
        self._metrics.decode_kv_reclaimed(self._deployment, len(pin_ids))

    def _demote_entry(self, entry) -> None:
        """Demote one evicted prefix entry's pages device → host tier:
        gather its page columns from every pool component (bytes exactly
        as stored — an int8 pool's quantized planes + scale/zp verbatim)
        and hand them to the host tier's byte-budget LRU. Must run while
        the entry's pages are still intact (before release/repurpose).
        Failures degrade — a demotion is an optimization, never worth
        aborting an eviction over."""
        if self._host_tier is None:
            return
        try:
            pages = jnp.asarray(np.asarray(entry.pages, np.int64), jnp.int32)
            comps = [np.asarray(comp[:, pages]) for comp in self.pool.state]
        except Exception:  # noqa: BLE001 - demotion is best-effort by contract
            log.exception("prefix-entry demotion readback failed")
            return
        if self._host_tier.put(entry.tokens, comps):
            self.stat_tier_demotions += 1

    def _promote(self, seq: _Seq, depth: int) -> bool:
        """Consult the host (then store) tier for an entry deeper than
        the device match and promote it into pinned free pages. Runs on
        both admission paths — serial ``_admit`` and ``_pipeline_admit``
        under an in-flight dispatch, where the eager page scatter is
        dataflow-safe (pool.state already points at the round's output
        futures) and ``preseed_pin`` keeps the reservation invariant.
        Returns whether the device index gained a deeper entry."""
        tier = self._host_tier
        include_store = seq.kv_tier != "host"
        if tier.probe(seq.prompt, include_store=include_store) <= depth:
            return False
        got = tier.fetch(seq.prompt, min_depth=depth, include_store=include_store)
        if got is None:
            return False
        tokens, comps, src_tier = got
        t0 = telemetry.now_ns()
        if not self._install_promoted(tokens, comps):
            return False
        self.stat_tier_promotions += 1
        self._rb_promotions += 1
        if self._in_overlap:
            self.stat_tier_promote_overlap += 1
        self._metrics.decode_kv_promotion(self._deployment, src_tier, 1)
        nbytes = int(sum(int(np.asarray(c).nbytes) for c in comps))
        for c in seq.trace_ctxs:
            ms = c.buf.begin(
                "decode.kv_promote",
                c.span.span_id,
                {
                    "tier": src_tier,
                    "bytes": nbytes,
                    "overlap": self._in_overlap,
                    **self._mesh_attrs,
                },
                start_ns=t0,
            )
            ms.add_event("promoted", {"tokens": int(np.asarray(tokens).shape[0])})
            ms.end()
        return True

    def _install_promoted(self, tokens, comps) -> bool:
        """Install one promoted entry's bytes into ``preseed_pin``-pinned
        free pages + the prefix index — the single-entry twin of
        ``preseed_prefix_state`` (same geometry clamps, same validate-
        every-axis-before-pinning discipline, same eager scatter
        re-committed to the resident sharding so warmed program
        signatures are untouched). False degrades to cold prefill."""
        state = list(self.pool.state)
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if len(comps) != len(state):
            return False
        length = capture_prefix_len(len(tokens), self.prefix_ctx, self.seq_len)
        length = (length // self.pool.page_size) * self.pool.page_size
        n_pages = self.pool.alloc.pages_for(length)
        if n_pages < 1:
            return False
        span = tokens[:length]
        _, depth = self._prefix_index.match(span, touch=False)
        if depth >= length:
            return False  # a device entry at least as deep already landed
        entry_bytes = []
        for ci, dst in enumerate(state):
            full = np.asarray(comps[ci])
            if (
                full.ndim != len(dst.shape)
                or full.shape[0] != dst.shape[0]
                or full.shape[1] < n_pages
                or full.shape[2:] != tuple(dst.shape[2:])
                or full.dtype != dst.dtype
            ):
                return False
            entry_bytes.append(full[:, :n_pages])
        pin = self.pool.alloc.preseed_pin(n_pages)
        if pin is None:
            # free-list pressure: a promotion must never trigger the
            # reclaim ladder it would immediately feed — cold prefill
            # through the normal reservation path instead
            return False
        pages = np.asarray(pin.pages, np.int64)
        for ci, dst in enumerate(state):
            state[ci] = self._scatter_preserving_placement(
                dst, entry_bytes[ci], pages
            )
        self.pool.state = tuple(state)
        _, evicted = self._prefix_index.insert(span, pin.pages, pin.pin_id)
        if evicted is not None:
            self._demote_entry(evicted)
            self.pool.alloc.release(evicted.pin_id)
            self._metrics.decode_prefix_evicted(self._deployment)
        return True

    def prefix_probe_depth(self, prompt) -> int:
        """How deep ANY local tier (device prefix index, host pool, store
        index) could serve ``prompt`` — the sibling-pull guard's cheap
        local check. Host-only metadata, no transfers, no LRU touch."""
        if not self.prefix_enabled:
            return 0
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        _, depth = self._prefix_index.match(prompt, touch=False)
        if self._host_tier is not None:
            depth = max(depth, self._host_tier.probe(prompt))
        return int(depth)

    def export_prefix_entry(self, prompt) -> dict | None:
        """One-entry spill payload (``export_prefix_state`` schema) for
        the deepest local-tier entry covering ``prompt`` — what a
        rendezvous home answers a sibling pull with. A host/store hit
        reuses the demoted bytes directly; a device hit gathers that one
        entry's page columns. None when no tier covers the prompt."""
        require_served(self.family, "prefix_export")
        if not self.prefix_enabled:
            return None
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        entry, depth = self._prefix_index.match(prompt, touch=False)
        host_depth = (
            self._host_tier.probe(prompt) if self._host_tier is not None else 0
        )
        if host_depth > depth:
            got = self._host_tier.fetch(prompt)
            if got is not None:
                tokens, comps, _tier = got
                return {
                    "page_size": self.pool.page_size,
                    "kv_dtype": self.pool.kv_dtype,
                    "entries": [
                        {
                            "tokens": np.asarray(tokens, np.int32).copy(),
                            "components": [np.asarray(c) for c in comps],
                        }
                    ],
                }
        if entry is None or depth < 1:
            return None
        pages = jnp.asarray(np.asarray(entry.pages, np.int64), jnp.int32)
        return {
            "page_size": self.pool.page_size,
            "kv_dtype": self.pool.kv_dtype,
            "entries": [
                {
                    "tokens": np.asarray(entry.tokens, np.int32).copy(),
                    "components": [
                        np.asarray(comp[:, pages]) for comp in self.pool.state
                    ],
                }
            ],
        }

    def _kv_gauges(self) -> None:
        a = self.pool.alloc
        self._metrics.decode_kv_pool(
            self._deployment, a.free_pages, a.live_pages, a.prefix_pages
        )
        if a.win is not None:
            released = a.win.stat_released - self._kv_win_released_gauged
            self._kv_win_released_gauged = a.win.stat_released
            self._metrics.decode_kv_window_pool(
                self._deployment, a.win.free_pages, a.win.live_pages, released
            )
        # pages resident per device: the page axis is NOT sharded (every
        # device holds all pages x its head shard), so the count matches
        # the pool-wide allocation while per-page BYTES scale 1/tp — the
        # tp label is what makes the gauge readable as per-device HBM
        self._metrics.decode_kv_per_device(
            self._deployment, a.live_pages + a.prefix_pages, self.tp
        )

    def shard_audit(self) -> dict:
        """Per-shard audit of the device pools on a decode mesh (the soak
        harness runs this beside the allocator's host-side ``check()``):
        every pool/draft-cache component must be laid out across exactly
        the mesh devices, the payloads carrying heads/tp per shard (the
        4-D pool's token-row axis, the 5-D draft cache's head axis) and
        replicated components full-size. Raises AssertionError on any
        divergence; returns a small report dict."""
        if self.mesh is None:
            return {
                "tp": 1,
                "kv_pages_per_device": self.pool.alloc.live_pages
                + self.pool.alloc.prefix_pages,
            }
        mesh_devices = set(self.mesh.devices.flat)
        audited = 0

        def _check(name: str, arr) -> None:
            nonlocal audited
            devs = {s.device for s in arr.addressable_shards}
            if devs != mesh_devices:
                raise AssertionError(
                    f"{name}: shards on {len(devs)} devices, mesh has "
                    f"{len(mesh_devices)}"
                )
            try:  # the ONE rule of which axis carries heads
                want = list(
                    kv_sharding(self.mesh, self._tp_axis, arr).shard_shape(arr.shape)
                )
            except ValueError as e:
                raise AssertionError(f"{name}: head axis % tp != 0 ({e})") from e
            for s in arr.addressable_shards:
                if list(s.data.shape) != want:
                    raise AssertionError(
                        f"{name}: shard shape {list(s.data.shape)} != {want}"
                    )
            audited += 1

        for i, a in enumerate(self.pool.state):
            _check(f"pool[{i}]", a)
        if self.spec_enabled:
            _check("draft_k", self.programs.dck)
            _check("draft_v", self.programs.dcv)
        return {
            "tp": self.tp,
            "mesh_devices": len(mesh_devices),
            "components_audited": audited,
            "kv_pages_per_device": self.pool.alloc.live_pages
            + self.pool.alloc.prefix_pages,
        }

    def _maybe_capture(self, seq: _Seq, slot: int, length: int, state_row: int = -1) -> None:
        """Pin ``slot``'s leading prompt pages as a prefix entry when the
        index doesn't already cover prompt[:length] — a refcount bump, NO
        device work (the capture-copy dispatch of the flat layout is
        gone). Called at prefill completion for hinted captures
        (meta.tags.cache_prefix — the prefix K/V exists from that moment)
        and at retirement for the automatic full-prompt policy. A
        recurrent family (``state_row`` >= 0: the snapshot row the chunk
        dispatch that ended at ``length`` just wrote, ``_snapshot_row``)
        also binds that row to the pin, so whatever drops the pin frees it."""
        alloc = self.pool.alloc
        if state_row < 0:
            length = capture_prefix_len(length, self.prefix_ctx, self.seq_len)
            if length < 1:
                return
            _, depth = self._prefix_index.match(seq.prompt, touch=False)
            if depth >= length:
                return  # already covered verbatim (or by a longer entry)
        elif self._snapshot_held(seq, length):
            # another row of the same dispatch captured the same span first
            alloc.give_state_row(state_row)
            return
        pin = alloc.capture(slot, length)
        if pin is None:
            # the span's pages aren't materialized (shouldn't happen for
            # a completed prefill) — skip rather than stall the loop
            self.stat_prefix_capture_skips += 1
            if state_row >= 0:
                alloc.give_state_row(state_row)
            return
        pin.state_row = state_row
        if state_row >= 0:
            self._rb_state_captures += 1
        _, evicted = self._prefix_index.insert(
            seq.prompt[:length], pin.pages, pin.pin_id, state_row
        )
        if evicted is not None:
            # index-cap LRU eviction: demote the displaced entry to the
            # host tier while its pages are intact, then release the pin
            # (its pages free unless live readers still map them)
            self._demote_entry(evicted)
            self.pool.alloc.release(evicted.pin_id)
            self._metrics.decode_prefix_evicted(self._deployment)
        self.stat_prefix_captures += 1

    def _snapshot_row(self, seq: _Seq, end: int, unread: set) -> int:
        """A recurrent family, while a chunk round is planned: the snapshot
        row the dispatch must also write for ``seq``, whose chunk ends at
        prompt position ``end``; -1 where it captures nothing there (``end``
        is not its hint's boundary, or an entry of that length holds the
        span already). With no free row the index's LRU entry goes first,
        as at the index cap; its row may be written in the same dispatch a
        warm admission still reads it in: the program reads before it
        writes. Rows are only ever written by chunk dispatches, and a
        decided admission's first chunk rides the next one unless the round
        leaves it out (``_chunk_rows_taken``): ``unread`` holds the rows
        such admissions have yet to read, and a dispatch they do not ride
        must not write one, whoever dropped its entry meanwhile. A free row
        among them is passed over, an LRU entry whose row is among them
        stays, and with no other row the span is not captured."""
        if end != self._hint_boundary(seq) or self._snapshot_held(seq, end):
            return -1
        alloc = self.pool.alloc
        row = alloc.take_state_row(unread)
        if row < 0:
            lru = self._prefix_index.lru()
            if lru is not None and lru.state_row not in unread:
                self._prefix_index.evict_lru()
                alloc.release(lru.pin_id)
                self._metrics.decode_prefix_evicted(self._deployment)
                row = alloc.take_state_row(unread)
        if row < 0:
            self.stat_prefix_capture_skips += 1
        return row

    def _snapshot_held(self, seq: _Seq, length: int) -> bool:
        """Whether an entry of exactly ``length`` tokens already holds the
        snapshot of ``seq``'s first ``length`` prompt tokens."""
        return self._prefix_index.match(seq.prompt[:length], touch=False, whole=True)[1] == length

    def _hint_boundary(self, seq: _Seq) -> int:
        """A recurrent family, or a pool with window-kind pages: the prompt
        position a hinted request's state is snapshotted at, or its pages
        pinned while the last window before it is still mapped (its
        ``cache_prefix``, inside what a later request can reuse: at least one
        suffix token stays); 0 = none."""
        if not (self._boundary_cache and self.prefix_enabled and seq.cache_prefix > 0):
            return 0
        return usable_prefix_len(
            capture_prefix_len(seq.cache_prefix, self.prefix_ctx, self.seq_len), self.seq_len
        )

    def _next_chunk(self, seq: _Seq, pos: int) -> int:
        """The prompt tokens ``seq``'s next chunk takes from position
        ``pos``: the rest of the prompt, at most its per-round cap, and in a
        recurrent family no further than its hint's boundary, so that one
        chunk ENDS there and the state at the boundary can be kept."""
        rem = self.seq_len - pos
        c = min(rem, seq.chunk_cap or rem)
        boundary = self._hint_boundary(seq)
        return min(c, boundary - pos) if boundary > pos else c

    def _retire(self, slot: int) -> None:
        seq = self._slots[slot]
        self._slots[slot] = None
        self._free.append(slot)
        self.stat_retired += 1
        self._rb_retired += 1
        if seq is not None:
            # goodput: this request's tokens count as delivered-within-SLO
            # only when its deadline budget (captured at submit) held at
            # retirement — the signal an SLO-tiered scheduler or a
            # reward-driven router consumes (ROADMAP)
            met = True
            if seq.slo_deadline:
                met = time.perf_counter() <= seq.slo_deadline
                if not met:
                    seq.slo_ok = False
                tid = self.flight.note_deadline(met)
                self._metrics.decode_slo(
                    self._deployment, "deadline", met, trace_id=tid or None
                )
            self.flight.note_goodput(len(seq.tokens), met)
            self._metrics.decode_goodput(self._deployment, len(seq.tokens), met)
            if seq.slo_sink is not None:
                try:
                    seq.slo_sink(seq.slo_ok)
                except Exception:  # noqa: BLE001 - tagging must not kill the loop
                    log.exception("slo_sink callback failed")
            if self.prefix_enabled:
                # automatic capture policy: a request that declared its
                # reusable span (cache_prefix) captured at prefill
                # completion; everyone else contributes their full prompt
                # here. A sequence cancelled mid-prefill has incomplete
                # prompt K/V and must not be captured. Capture pins pages
                # BEFORE retire returns them to the pool.
                if not seq.prefilling and seq.cache_prefix == 0:
                    if self._stateful:
                        # the state at the prompt's end was advanced by every
                        # token since: nothing left to capture
                        self.stat_prefix_capture_skips += 1
                    else:
                        # (window-kind pages: only while the prompt's last
                        # window is still mapped, else a skip)
                        self._maybe_capture(seq, slot, self.seq_len)
            self.pool.alloc.retire(slot)
            self._kv_gauges()
            if seq.gen_spans:
                t = telemetry.now_ns()
                for sp in seq.gen_spans:
                    if sp.attrs is not None:
                        sp.attrs["tokens"] = len(seq.tokens)
                    sp.end(t)
                seq.gen_spans = []
            self._resolve(seq)

    def _next_tick(self) -> np.int32:
        self._tick += 1
        return np.int32(self._tick)

    async def _device_call(self, fn):
        """Run a device dispatch + readback off the event loop on accel
        backends (XLA releases the GIL); inline on the CPU backend —
        unless this scheduler is one replica of a fleet, whose dispatches
        must overlap the siblings' (``_offload_dispatch``)."""
        if self._host_backend and not self._offload_dispatch:
            return fn()
        from seldon_core_tpu.models.base import compute_pool

        pool = self._dispatch_pool
        self._round_yielded = True  # the loop's other tasks run while this waits
        return await asyncio.get_running_loop().run_in_executor(
            pool if pool is not None else compute_pool(), fn
        )

    # --------------------------------------------------- round flight frame
    def _round_reset(self, t_ns: int | None = None, open_round: bool = True) -> None:
        """Reset the per-round flight accumulators (one set of plain int
        attrs — written on the hot path, read only at _commit_round) and
        turn the round's trace annotation over (``_round_mark``;
        ``open_round=False`` only where no loop runs: construction)."""
        self._rb_busy = [0, 0, 0, 0, 0]  # ns per flight.FAMILIES entry
        self._rb_rdb = [0, 0, 0, 0, 0]  # blocked-readback share of busy
        self._rb_rdy = [0, 0, 0, 0, 0]  # of rdb, after the result was ready
        self._rb_mark_ns = self._rb_ready_ns = 0
        self._rb_t0 = t_ns if t_ns is not None else time.perf_counter_ns()
        self._rb_admitted = 0
        self._rb_retired = 0
        self._rb_blocked = ""
        self._rb_tokens = 0
        self._rb_cow = 0
        self._rb_accepted = 0
        self._rb_proposed = 0
        self._rb_depth = 0
        self._rb_active = 0
        self._rb_overlap = 0
        self._rb_probe = False
        self._rb_widths = ()
        self._rb_promotions = 0
        # the time to first token as the program sees it, split at slot
        # assignment: queue wait of this round's admissions, admission ->
        # first token of this round's first emissions (and their count)
        self._rb_admit_wait = 0
        self._rb_prefill = 0
        self._rb_first_tokens = 0
        # pages the plain step's attention read, of the pages its tables name
        self._rb_attn_pages = (0, 0)
        # rows the round's chunk dispatches computed, the prefilling slots
        # among them, and the slots with a chunk to run that they left out
        self._rb_chunk_rows = self._rb_chunk_rows_live = self._rb_chunk_c = 0
        self._rb_chunk_rows_held = self._rb_chunk_rows_kernel = 0
        # rows of the round's dispatches that asked the sampler for a draw,
        # and those among them that asked for top_k (_count_sampling)
        self._rb_sample_rows = self._rb_sample_topk_rows = 0
        # a recurrent family: admissions that began from a snapshot row,
        # snapshots bound to a new entry
        self._rb_state_restores = self._rb_state_captures = 0
        # a counting family's per-dispatch counts, summed over the round
        # (nothing to build each round for a family that counts nothing)
        if self._frame_counters:
            self._rb_counts = np.zeros(len(self._frame_counters), np.int64)
        self._rb_step_counts = ()
        self._rb_gdn = [0, 0]  # the delta-rule layer passes of the round's step and chunk dispatches, those in a kernel
        # stale shadow admissions (a round error between the overlap
        # window and the reconcile): the normal flow drains the list at
        # _apply_pending before the round commits, so anything still here
        # is error-path residue — roll the reservations back. (After a
        # pool.reset the allocator is fresh and retire() no-ops.)
        if self._pending_admits:
            for p in self._pending_admits:
                self.pool.alloc.retire(p.slot)
            self._pending_admits.clear()
        self._phases.reset()
        self._round_mark(open_round)

    def _round_mark(self, open_next: bool) -> None:
        """End the open ANN_ROUND trace annotation and, on the running
        loop, start the next: one per round from ``_round_reset`` to
        ``_commit_round``, awaits included. Its ``round`` stat is the index
        the round's FlightFrame will commit under and ``t_ns`` the round
        clock's start, so a profiler session joins a trace round to its
        frame and the recorder's clock to the trace's."""
        if self._round_ann is not None:
            self._round_ann.__exit__(None, None, None)
            self._round_ann = None
        if open_next:
            self._round_ann = flight_mod.annotate(
                ANN_ROUND, round=self.flight.rounds, t_ns=self._rb_t0
            )

    def _phase(self, p: int):
        """The round's host-phase ``with`` handle for a flight P_*
        constant (telemetry/flight.PhaseTimer — innermost-phase
        attribution, no-op under the flight kill switch; each handle also
        writes its ``decode.phase.<name>`` trace annotation). Never hold a
        phase across a device dispatch: busy time is _dispatch's."""
        return self._phases.phase(p)

    def _dispatch(self, family: int, **stats) -> _Dispatch:
        """The ``with``-handle for one dispatch of a flight F_* family:
        THE timing-and-naming point of every dispatch (``_Dispatch``).
        ``stats`` (what the call site holds: integers, and a chunk's
        ``write`` and ``attn`` forms) ride the family's next
        dispatch's trace annotations; ``_timed_call`` enters the handle
        itself, so its callers note theirs here first."""
        d = self._dispatches[family]
        if stats:
            d.noted = stats
        return d

    async def _timed_call(self, family: int, fn):
        """``fn`` (a program set call, or the copy ladder) through
        ``_Dispatch.run``: its wall time attributed to one fused program
        family in the current round's flight frame, split enqueue vs
        blocked readback at the mark."""
        with self._dispatches[family] as d:  # = self._dispatch(family)
            return await d.run(fn)

    def _commit_round(self, mode: str, *, step: bool) -> None:
        """THE single per-round commit point: round stats, prometheus round
        metrics, and the flight frame all land here. (stat_occupancy_sum
        used to be updated separately on the spec and plain paths — one
        commit point means the two accounting paths cannot drift.) ``step``
        marks rounds that ran a decode/verify dispatch; chunk-only rounds
        keep stat_steps' historical meaning (decode steps, not prefill
        rounds) but still record a frame."""
        # the whole commit is named for a profiler session (the phase
        # TIMER stops earlier, where the frame's clock does: below)
        with flight_mod.annotate(ANN_PHASE[P_COMMIT]):
            t_c0 = time.perf_counter_ns()
            active = self._rb_active if step else self.active
            if step:
                self.stat_steps += 1
                self.stat_occupancy_sum += active / self.n_slots
                self._metrics.decode_step(self._deployment, active, self.n_slots)
            # freeze the phase array BEFORE the round clock stops so the
            # commit phase (this function's own cost so far) stays inside the
            # gap it is attributed to — sum(phase_ns) <= gap_ns by
            # construction; the frame build below lands in the next round
            phase_ns = (
                self._phases.commit(P_COMMIT, t_c0)
                if self.flight.enabled
                else ()
            )
            now_ns = time.perf_counter_ns()
            busy = sum(self._rb_busy)
            gap = max(now_ns - self._rb_t0 - busy, 0)
            if busy + gap > self.SLOW_ROUND_NS:
                # a silence names itself in the log: which side held the
                # round, the dispatches (enqueue to readback, by family)
                # or the host between them
                log.warning(
                    "decode round %d (%s) took %.2f s: dispatches %s ms, of that "
                    "blocked on readback %s ms, host between dispatches %.0f ms",
                    self.flight.rounds, mode, (busy + gap) / 1e9,
                    {f: b // 10**6 for f, b in zip(flight_mod.FAMILIES, self._rb_busy) if b},
                    {f: b // 10**6 for f, b in zip(flight_mod.FAMILIES, self._rb_rdb) if b},
                    gap / 1e6,
                )
            if self.flight.enabled:
                # the kill switch removes the whole frame cost (pool snapshot,
                # slot scan, frame object), not just the ring store
                snap = self.pool.alloc.snapshot()
                ingress = (self.stat_ingress_ns, self.stat_ingress_requests)
                prefilling = sum(
                    1 for s in self._slots if s is not None and s.prefilling
                )
                self.flight.record(
                    FlightFrame(
                        self.flight.rounds, now_ns, mode, active, prefilling,
                        len(self._waiting), self._rb_admitted, self._rb_retired,
                        self._rb_blocked, self._rb_tokens, self._rb_accepted,
                        self._rb_proposed, self._rb_depth, tuple(self._rb_busy),
                        gap, snap["free"], snap["live"], snap["prefix"],
                        self._rb_cow, phase_ns, tuple(self._rb_rdb),
                        self._rb_overlap, self._rb_probe, tuple(self._rb_widths),
                        self._rb_promotions, self._rb_admit_wait,
                        self._rb_prefill, self._rb_first_tokens,
                        *self._rb_attn_pages,
                        self._rb_chunk_rows, self._rb_chunk_rows_live,
                        self._rb_sample_rows, self._rb_sample_topk_rows,
                        state_restores=self._rb_state_restores,
                        state_captures=self._rb_state_captures,
                        chunk_c=self._rb_chunk_c,
                        chunk_rows_held=self._rb_chunk_rows_held,
                        chunk_rows_kernel=self._rb_chunk_rows_kernel,
                        **self._window_frame(snap),
                        step_counts=self._rb_step_counts,
                        rdy_ns=tuple(self._rb_rdy),
                        gdn_passes=self._rb_gdn[0],
                        gdn_kernel_passes=self._rb_gdn[1],
                        ingress_ns=ingress[0] - self._ingress_committed[0],
                        ingress_requests=ingress[1] - self._ingress_committed[1],
                        **(
                            dict(zip(self._frame_counters, self._rb_counts.tolist()))
                            if self._frame_counters
                            else {}
                        ),
                    )
                )
                self._ingress_committed = ingress
                if self.spec_enabled:
                    # adaptive-speculation state for /decode/health: the tuned
                    # shape, the controller's EWMA, and the effective depth
                    # the NEXT round will see (latest-wins attribute — the
                    # per-round history is in the frames)
                    self.flight.spec_state = {
                        "tree": getattr(self, "_tree_text", ""),
                        "widths": list(self._rb_widths),
                        "nodes": (
                            self.spec_tree.nodes_for_widths(self._rb_widths)
                            if self.spec_tree is not None and self._rb_widths
                            else 0
                        ),
                        "accept_ewma": round(self._adapt.rate, 4),
                        "depth": self._rb_depth,
                        "probes": self._adapt.probes,
                    }
            self._metrics.decode_round(self._deployment, busy / 1e9, gap / 1e9)
            if self.flight.enabled and self.flight.rounds % 64 == 0:
                # refresh the cumulative bubble gauge off the O(1) totals —
                # per-64-rounds, not per-round, so the gauge write never shows
                # up in the recorder's own overhead budget
                self._metrics.decode_bubble(
                    self._deployment, self.flight.bubble_fraction()
                )
        self._round_reset(now_ns)

    def _window_frame(self, snap: dict) -> dict:
        """The round's window-kind frame fields from the allocator's snapshot
        (cumulative counts: the round's are what was added since the last
        frame), and the ``stat_*`` twins; nothing for a pool of one kind."""
        if "win_live" not in snap:
            return {}
        written = snap["win_written"] - self.stat_kv_win_written
        released = snap["win_released"] - self.stat_kv_win_released
        self.stat_kv_win_written, self.stat_kv_win_released = snap["win_written"], snap["win_released"]
        self.stat_kv_win_live_peak = max(self.stat_kv_win_live_peak, snap["win_live"])
        return {"kv_win_live": snap["win_live"], "kv_win_released": released, "kv_win_written": written}

    async def _run_copies(self, copies: list[tuple]) -> None:
        """Dispatch a round's copy-on-write page copies (batched through
        the pool's warmed ladder) BEFORE the round's write dispatch."""
        if not copies:
            return
        await self._timed_call(F_COPY, lambda: self.pool.run_copies(copies))
        self.stat_kv_copy_rounds += 1
        self._rb_cow += len(copies)
        self._metrics.decode_kv_cow(self._deployment, len(copies))

    def _admit_decide(self, seq: _Seq, slot: int) -> tuple:
        """The admission DECISION for one waiting sequence into ``slot``:
        longest-prefix match, the cache_prefix boundary-page reserve, and
        the allocator's worst-case page reservation (``try_admit`` maps
        shared pages into the slot's block table — refcount bumps, no
        device work). Shared between the serial ``_admit`` walk and the
        pipelined ``_pipeline_admit``, where it runs UNDER an in-flight
        dispatch: the reservation is rollback-safe (``alloc.retire(slot)``
        undoes it completely) and conservative (round N's retirements can
        only free pages, never invalidate a reservation made against the
        pre-retire pool). Returns ``(entry, reuse, admitted)``."""
        entry, reuse = None, 0
        if self.prefix_enabled:
            with self._phase(P_PREFIX_MATCH):
                # a recurrent family reuses an entry's whole length or
                # nothing (PrefixIndex: the depth rule per cache kind)
                entry, depth = self._prefix_index.match(seq.prompt, whole=self._boundary_cache)
                # device-pool miss (or shallow hit): consult the tiers
                # below — a host/store entry deeper than the device match
                # promotes into pinned free pages and the re-match rides
                # it. Promotion installs a cache entry (monotone), so the
                # pipelined path's rollback discipline needs no undo; the
                # kv_tier tag tightens the consult (off = cold-only,
                # host = no store).
                if (
                    self._host_tier is not None
                    and seq.kv_tier != "off"
                    and self._promote(seq, depth)
                ):
                    entry, depth = self._prefix_index.match(seq.prompt)
            # the shared prompt->prefix normalization (affinity_router):
            # always leave >= 1 suffix token — the last prompt position's
            # logits are the first generated token's distribution. The
            # replica router normalizes the SAME way, so a prompt it
            # judged warm is one admission judges warm too.
            reuse = usable_prefix_len(depth, self.seq_len)
            if reuse <= 0 or (self._boundary_cache and reuse < depth) or not (
                self.pool.alloc.pin_covers(entry.pin_id, reuse)
            ):
                entry, reuse = None, 0
        # a cache_prefix hint pins pages at prefill completion; if the
        # hinted span's last page extends past seq_len, this slot's own
        # GENERATION writes will copy-on-write it — reserve for exactly
        # that case (page-aligned prompts need no extra, so a full
        # hinted burst still reaches every slot on the auto budget)
        extra = 0
        if self.prefix_enabled and seq.cache_prefix > 0:
            alloc = self.pool.alloc
            hint_end = alloc.pages_for(seq.cache_prefix) * alloc.page_size
            extra = 1 if hint_end > self.seq_len else 0
            # a capture at the hint's boundary, mid-prompt (state rows, window
            # pages): the slot's next chunk writes into the boundary page it
            # just pinned, unless the boundary is a page's end
            if self._hint_boundary(seq) % alloc.page_size:
                extra = 1
        with self._phase(P_ALLOC):
            admitted = self.pool.alloc.try_admit(
                slot, entry.pages if entry is not None else (), reuse, extra,
                pin_id=entry.pin_id if entry is not None else -1,
            )
        return entry, reuse, admitted

    def _install_admit(self, seq: _Seq, slot: int, entry, reuse: int, t0: int) -> None:
        """Install an admission decision into the LIVE slot table — the
        part the pipelined loop defers to the reconcile so the readback
        walks never see a mid-flight admission. Callers own the queue /
        free-list bookkeeping (the serial walk pops, _apply_pending
        removes by identity)."""
        seq.slot = slot
        seq.prefilling = True
        self._slots[slot] = seq
        self.stat_admitted += 1
        self._rb_admitted += 1
        seq.t_admitted = time.perf_counter()
        self._rb_admit_wait += int((seq.t_admitted - seq.t_enqueued) * 1e9)
        # a feature head's attention window opens at the computed suffix:
        # the prefix-reused span has no draft-side K/V (the chunk rounds
        # teacher-force only what they compute)
        self.programs.draft_start[slot] = reuse
        shared_pages = self.pool.alloc.pages_for(reuse) if reuse else 0
        if self.prefix_enabled:
            if entry is not None:
                self.pool.alloc.touch(entry.pin_id)
                self.stat_prefix_hits += 1
                self.stat_prefix_tokens_saved += reuse
                self._metrics.decode_prefix(self._deployment, True, reuse)
                self._metrics.decode_kv_shared(self._deployment, shared_pages)
            else:
                self.stat_prefix_misses += 1
                self._metrics.decode_prefix(self._deployment, False, 0)
        seq.prefill_pos = reuse
        seq.prefix_len = reuse
        if self._stateful:
            # the first chunk starts from the entry's snapshot, or from zeros
            seq.state_src = entry.state_row if entry is not None else self.pool.zero_row
            self._rb_state_restores += entry is not None
        for c in seq.trace_ctxs:
            ms = c.buf.begin(
                "decode.prefix_match" if self.prefix_enabled else "decode.admit",
                c.span.span_id,
                {"slot": slot, "hit": reuse > 0, **self._mesh_attrs},
                start_ns=t0,
            )
            ms.add_event("reuse", {"tokens": reuse})
            ms.add_event(
                "kv_alloc",
                {
                    "shared_pages": shared_pages,
                    "reserved_pages": int(self.pool.alloc._reserved[slot]),
                    "free_pages": self.pool.alloc.free_pages,
                },
            )
            if self._stateful:
                ms.add_event("state_row", {"read": seq.state_src, "restored": entry is not None})
            ms.end()
        self.stat_peak_active = max(self.stat_peak_active, self.active)

    async def _admit(self) -> None:
        """Move waiting sequences into free slots — pure host work now:
        slot assignment, the longest-prefix match, copy-free page mapping
        (refcount bumps into the block table), and the worst-case page
        reservation. The uncovered suffix is computed by chunk rounds
        interleaved with decode steps in the run loop, and the first token
        is emitted when the last chunk lands.

        Admission is page-budget aware: a sequence admits only when the
        pool can GUARANTEE its exclusive page need on top of every running
        slot's outstanding reservation (kv_pool's no-deadlock invariant).
        When the budget is tight the head of the queue waits for
        retirements — FIFO, like slot contention.

        On the pipelined loop this is also the serial TAIL of admission:
        flight-decided admissions were installed by ``_apply_pending``
        before the previous round committed, and whatever still waits
        (arrivals during the flight, heads the pre-retire pool deferred)
        admits here against the post-retire pool — so the admitted set
        per round is identical to the serial loop's."""
        while self._waiting and self._free:
            seq = self._waiting[0]
            if seq.future.cancelled():
                self._waiting.popleft()
                continue
            t0 = telemetry.now_ns()
            slot = self._free[-1]
            entry, reuse, admitted = self._admit_decide(seq, slot)
            if not admitted:
                self.stat_admit_blocked_rounds += 1
                self._rb_blocked = "pages"
                break
            self._waiting.popleft()
            self._free.pop()
            self._install_admit(seq, slot, entry, reuse, t0)
        if not self._pipeline_take_admit_sweep():
            # the admission sundries — pool gauges + the queue-deadline
            # expiry sweep — unless the pipelined overlap window already
            # ran them under the previous round's in-flight dispatch
            self._kv_gauges()
            self._expire_waiting()
        if self._waiting and not self._free and not self._rb_blocked:
            # queue behind fully-occupied slots (the page-budget cause is
            # recorded where try_admit refused above) — the flight frame's
            # blocked-admission attribution
            self._rb_blocked = "slots"

    def _expire_waiting(self) -> None:
        """Expire waiting requests past the queue deadline (the
        micro-batcher's REQUEST_TIMEOUT contract) — runs every round
        while slots are contended, from the serial admission walk or
        hoisted under the in-flight dispatch by ``_pipeline_sundries``
        (expiry touches only un-admitted waiters, so mid-flight is
        observably identical). A waiter the SAME window already
        flight-decided is admitted, not waiting — the serial walk pops
        admitted seqs before expiry ever sees them, and the pipelined
        walk must match (expiring a decided admit would fail the caller
        while _apply_pending installs the slot anyway)."""
        if not self._waiting:
            return
        decided = {p.seq.uid for p in self._pending_admits}
        now = time.perf_counter()
        for seq in [
            s
            for s in self._waiting
            if s.deadline and s.deadline < now and s.uid not in decided
        ]:
            self._waiting.remove(seq)
            if not seq.future.done():
                seq.future.set_exception(
                    APIException(
                        ErrorCode.REQUEST_TIMEOUT,
                        "request timed out waiting for a decode slot",
                    )
                )

    # ------------------------------------------------- pipelined round state
    def _pipeline_on(self) -> bool:
        """Whether this round may run the double-buffered path: the
        ENGINE_DECODE_PIPELINE kill switch (captured at build into
        ``pipeline_enabled`` — bench's A/B leg flips the attribute per
        run)."""
        return self.pipeline_enabled

    def _overlap_window(self) -> None:
        """Round N+1's host phases, run while round N's dispatch is in
        flight (between the enqueue and the blocking readback). Each
        stage is gated on its OWN measured cost (_PipelineGate): a phase
        the microscope measures as trivially cheap is not worth moving
        across the round boundary. Phase timers route to the frame's
        ``overlap_ns`` here (PhaseTimer overlap mode) — this wall sits
        inside the dispatch's busy window, so booking it into phase_ns
        would break sum(phase) <= gap."""
        t0 = time.perf_counter_ns()
        self._phases.begin_overlap()
        self._in_overlap = True
        try:
            if self._waiting and self._free and self._gate.allow("admit"):
                g0 = time.perf_counter_ns()
                with self._phase(P_ADMIT):
                    self._pipeline_admit()
                self._gate.note("admit", time.perf_counter_ns() - g0)
            if (
                self._pending_admits
                or any(s is not None and s.prefilling for s in self._slots)
            ) and self._gate.allow("build"):
                g0 = time.perf_counter_ns()
                with self._phase(P_ALLOC):
                    self._pipeline_plan_chunk()
                self._gate.note("build", time.perf_counter_ns() - g0)
            # the per-round admission sundries ride EVERY window, ungated:
            # guaranteed per-round work that the flight hides for free
            self._pipeline_sundries()
        finally:
            self._in_overlap = False
            self._phases.end_overlap()
            self._rb_overlap += time.perf_counter_ns() - t0
            self.stat_pipelined_rounds += 1

    def _pipeline_admit(self) -> None:
        """Round N+1's admission DECISIONS under round N's in-flight
        dispatch, recorded into the shadow pending list — the sequence is
        installed into the live slot table only at ``_apply_pending``
        after the readback walks. Conservative by construction: slots
        come from the CURRENT free list (never a predicted retirement)
        and reservations run against the pre-retire pool, so a decision
        made here is valid no matter how round N retires. A head the
        tight pool cannot yet guarantee is NOT a failure: it defers to
        the serial ``_admit`` after the reconcile, where round N's
        retirements may have freed its pages (the deferred-admit path
        ``stat_pipeline_deferred`` counts)."""
        pending = self._pending_admits
        taken = {p.slot for p in pending}
        queued = {p.seq.uid for p in pending}
        avail = [s for s in self._free if s not in taken]
        for seq in self._waiting:
            if seq.uid in queued:
                continue
            if seq.future.cancelled():
                # the serial walk owns queue cleanup; skipping keeps this
                # pass read-only on the waiting deque
                continue
            if not avail:
                break
            slot = avail[-1]
            t0 = telemetry.now_ns()
            entry, reuse, admitted = self._admit_decide(seq, slot)
            if not admitted:
                # FIFO: the head defers, everyone behind waits with it
                self.stat_pipeline_deferred += 1
                break
            avail.pop()
            pending.append(_PendingAdmit(seq, slot, entry, reuse, t0))

    def _pipeline_sundries(self) -> None:
        """The serial walk's per-round sundries, hoisted under the
        flight: the queue-deadline expiry sweep (O(queue) every contended
        round) and the pool gauges. Both touch only un-admitted waiters /
        metrics, so running them mid-flight is observably identical — the
        serial _admit skips them for one round via the take-accessor (a
        retire refreshes the gauges on its own path regardless)."""
        with self._phase(P_ADMIT):
            self._expire_waiting()
            self._kv_gauges()
        self._pending_admit_sweep = True

    def _pipeline_take_admit_sweep(self) -> bool:
        """One-shot: whether the last overlap window already ran the
        admission sundries (expiry sweep + gauges) for this round — the
        serial walk consumes the marker so a serialized round (no window,
        kill switch, sync timing) runs them itself."""
        swept = self._pending_admit_sweep
        self._pending_admit_sweep = False
        return swept

    def _pipeline_plan_chunk(self) -> None:
        """Round N+1's chunk-round INPUT BUILD against the shadow state:
        the prefilling slots' next chunk plus the pending admissions'
        first, as the same compact arrays ``_chunk_round`` would build.
        Pure array construction — page residency (prepare_write / CoW)
        stays in the serial chunk round, because a CoW copy is not
        rollback-safe while a numpy build is. The plan carries a snapshot
        key; ``_pipeline_take_chunk_plan`` hands it out only when the
        live state still matches, so any cancellation, extra admission,
        or error-path reset in between silently invalidates it — discard
        IS the rollback."""
        rows: list[tuple[int, int, int, int, _Seq]] = []
        for i, seq in enumerate(self._slots):
            if seq is None or not seq.prefilling or seq.future.cancelled():
                continue
            c = self._next_chunk(seq, seq.prefill_pos)
            if c > 0:
                rows.append((i, seq.uid, seq.prefill_pos, c, seq))
        for p in self._pending_admits:
            if p.seq.future.cancelled():
                continue
            c = self._next_chunk(p.seq, p.reuse)
            if c > 0:
                rows.append((p.slot, p.seq.uid, p.reuse, c, p.seq))
        if not rows:
            self._pending_chunk_plan = None
            return
        rows.sort(key=lambda r: r[0])
        rows = self._chunk_rows_taken(rows)
        key = tuple(r[:4] for r in rows)
        self._pending_chunk_plan = (key,) + self._chunk_input_arrays(rows)

    def _chunk_rows_taken(self, rows: list) -> list:
        """The rows of ``rows`` (every slot with a chunk to run, in slot
        order) that ONE chunk round takes: all of them up to
        ``chunk_rows_cap``, the ladder's widest entry; beyond it the
        ``chunk_rows_cap`` that arrived first (lowest ``uid``: no slot
        starves, and the oldest request reaches its first token first),
        still in slot order. The rest stay ``prefilling`` at their
        ``prefill_pos`` for a later round and are in nothing this round
        builds: applied to the list before anything reads it, by the serial
        round and the overlap-window plan alike, so the plan's snapshot key
        and the round's agree."""
        if len(rows) <= self.chunk_rows_cap:
            return rows
        first = sorted(r[1] for r in rows)[self.chunk_rows_cap - 1]
        return [r for r in rows if r[1] <= first]

    def _chunk_input_arrays(self, rows: list) -> tuple:
        """The chunk round's input arrays from ``(slot, uid, prefill_pos,
        count, seq)`` rows in slot order — ONE builder shared by the
        serial chunk round and the overlap-window plan, so the array
        layout cannot drift between the two paths (the plan's snapshot key
        covers the rows, not the layout). The batch is the first
        ``chunk_buckets`` entry that holds the rows (what
        ``_chunk_rows_taken`` left: no more than the widest entry) and
        their longest chunk: the slots that prefill, one row each in slot
        order, then padding rows (slot -1, count 0: their writes
        junk-sink). At ``n_slots`` rows (the feature twin, a deployment of
        four slots or fewer) row r is slot r. Returns ``(slots, ids, pos,
        counts, temps, topks)``, each ``[rows]`` (``ids`` ``[rows, c]``)."""
        need = max(r[3] for r in rows)
        n, bucket = next(
            (n, c) for n, c in self.chunk_buckets if n >= len(rows) and c >= need
        )
        slots = np.full(n, -1, np.int32)
        ids = np.zeros((n, bucket), np.int32)
        pos = np.zeros(n, np.int32)
        counts = np.zeros(n, np.int32)
        temps = np.zeros(n, np.float32)
        topks = np.zeros(n, np.int32)
        at = [r[0] for r in rows] if n == self.n_slots else range(len(rows))
        for r, (slot, _uid, pp, c, seq) in zip(at, rows):
            slots[r] = slot
            ids[r, :c] = seq.prompt[pp : pp + c]
            pos[r] = pp
            counts[r] = c
            temps[r] = seq.temperature
            topks[r] = seq.top_k
        return slots, ids, pos, counts, temps, topks

    def _count_sampling(self, temps: np.ndarray, topks: np.ndarray) -> None:
        """Book what a dispatch's ``temps`` / ``topks`` ask of the programs'
        sampler into the round's frame: the same two predicates
        ``sample_tokens`` computes on the device to decide whether it draws
        at all and whether it looks for a top_k cutoff."""
        sampling = temps > 0
        if sampling.any():
            self._rb_sample_rows += int(np.count_nonzero(sampling))
            self._rb_sample_topk_rows += int(np.count_nonzero(sampling & (topks > 0)))

    def _pipeline_take_chunk_plan(self, key: tuple):
        """Hand the overlap-built chunk plan to the chunk round iff the
        live state still matches its snapshot key — one-shot either way
        (taken or stale, the slot clears). Stale is normal, not an error:
        it means the state the plan speculated against moved (a
        cancellation, an admission the serial walk added, a reset) and
        the serial build runs instead."""
        plan = self._pending_chunk_plan
        self._pending_chunk_plan = None
        if plan is not None and plan[0] == key:
            self.stat_pipeline_plans_used += 1
            return plan
        return None

    def _apply_pending(self) -> None:
        """THE reconcile funnel for the shadow admissions: install the
        flight-decided entries into the live slot table — after the
        readback walks (which must see exactly the dispatch-time slot
        state) and before ``_commit_round`` (the admissions belong to
        this round's frame, exactly like the serial walk's). A pending
        entry whose caller vanished during the flight rolls back:
        ``alloc.retire`` releases the reservation and refcounts, the
        decision's only live footprint."""
        if not self._pending_admits:
            return
        while self._pending_admits:
            p = self._pending_admits.pop(0)
            if p.seq.future.done():
                # the caller vanished during the flight — cancelled, or
                # failed by anything that settles futures (a decided admit
                # cannot have a RESULT: only retirement resolves, and the
                # seq was never installed). Installing would burn a slot
                # generating for a request that already failed.
                self.pool.alloc.retire(p.slot)
                self.stat_pipeline_rollbacks += 1
                try:
                    self._waiting.remove(p.seq)
                except ValueError:
                    # defensive: the waiting deque never drops un-admitted
                    # entries mid-flight (expiry skips decided admits)
                    pass
                continue
            entry, reuse = p.entry, p.reuse
            if self.prefix_enabled and reuse < self.seq_len - 1:
                # the flight decision matched an index that predates this
                # round's CAPTURES (a retire in the consume walk can
                # capture the very prompt a flight-decided sharer carries
                # — the serial walk, admitting after the walks, would see
                # it). Re-match at reconcile and upgrade: host-only work,
                # and it keeps warm-hit behavior identical to the serial
                # loop instead of silently paying a full prefill.
                with self._phase(P_PREFIX_MATCH):
                    _, depth = self._prefix_index.match(
                        p.seq.prompt, touch=False, whole=self._boundary_cache
                    )
                if usable_prefix_len(depth, self.seq_len) > reuse:
                    self.pool.alloc.retire(p.slot)  # undo the shallow mapping
                    entry, reuse, ok = self._admit_decide(p.seq, p.slot)
                    if not ok:
                        # post-retire + deeper reuse can only need FEWER
                        # pages, so this is defensive: leave the head in
                        # the queue for the serial walk (FIFO intact)
                        self.stat_pipeline_deferred += 1
                        continue
            try:
                self._waiting.remove(p.seq)
            except ValueError:
                # defensive: the waiting deque never drops un-admitted
                # entries mid-flight (expiry skips decided admits)
                pass
            self._free.remove(p.slot)
            self._install_admit(p.seq, p.slot, entry, reuse, p.t0)
            self.stat_pipeline_admits += 1
        self._kv_gauges()

    async def _chunk_round(self) -> None:
        """One prefill chunk round: the PREFILLING slots, at most
        ``chunk_rows_cap`` of them and then those that arrived first
        (``_chunk_rows_taken``), each consume up to their per-round chunk
        cap of prompt tokens in one fused dispatch whose batch is those
        slots (``_chunk_input_arrays``: the first entry of the warmed chunk
        ladder that holds them; a generating or free slot is not in it). A
        slot left out keeps its ``prefill_pos`` and rides a later round: it
        gets no page, no copy, no state or snapshot row and no span here,
        and the frame counts it (``chunk_rows_held``). Slots whose prompt
        completes emit their first token and transition to generating —
        decode steps for running slots interleave between rounds instead
        of stalling behind a monolithic wave prefill."""
        with self._phase(P_ALLOC):
            rows: list[tuple[int, int, int, int, _Seq]] = []
            for i, seq in enumerate(self._slots):
                if seq is None or not seq.prefilling:
                    continue
                if seq.future.cancelled():
                    self._retire(i)
                    continue
                c = self._next_chunk(seq, seq.prefill_pos)
                if c > 0:
                    rows.append((i, seq.uid, seq.prefill_pos, c, seq))
            if not rows:
                return
            waiting = len(rows)
            rows = self._chunk_rows_taken(rows)
            held = waiting - len(rows)
            # the pipelined loop may have prebuilt this round's input
            # arrays under the previous round's dispatch — valid only if
            # the live state still matches the plan's snapshot key
            key = tuple(r[:4] for r in rows)
            plan = self._pipeline_take_chunk_plan(key)
            slots, ids, pos, counts, temps, topks = (
                plan[1:] if plan is not None else self._chunk_input_arrays(rows)
            )
            copies: list[tuple[int, int]] = []
            for i, _uid, pp, c, _seq in rows:
                # page residency for this slot's write range: allocate fresh
                # pages, copy-on-write the shared boundary page (the reader's
                # first divergent write into a prefix-mapped page) — always
                # serial: a CoW copy is not rollback-safe, so residency is
                # never decided under an in-flight dispatch
                copies += self.pool.alloc.prepare_write(i, pp, c)
        await self._run_copies(copies)
        state_rows, snaps = None, {}
        with self._phase(P_ALLOC):
            bt = self.pool.block_tables(slots)
            if self._stateful:
                # which state row each batch row reads, writes and snapshots:
                # serial like page residency (taking a row may evict an entry)
                riding = {r[0] for r in rows}
                unread = {
                    s.state_src
                    for i, s in enumerate(self._slots)
                    if s is not None and s.state_src >= 0 and i not in riding
                }
                for i, _uid, pp, c, seq in rows:
                    row = self._snapshot_row(seq, pp + c, unread)
                    if row >= 0:
                        snaps[i] = row
                state_rows = self.pool.state_rows(
                    slots, {r[0]: r[4].state_src for r in rows if r[4].state_src >= 0}, snaps
                )
        tick = self._next_tick()
        t0 = telemetry.now_ns()
        # which chunk_buckets entry the dispatch is, for a trace and the frame
        self._rb_chunk_c = ids.shape[1]
        attn = self.programs.chunk_attn(ids.shape[1])
        self._dispatch(
            F_CHUNK, rows=len(slots), c=ids.shape[1], live=len(rows),
            write=write_form(ids.shape[1], self.pool.page_size), attn=attn,
        )
        toks, counted = await self._timed_call(
            F_CHUNK,
            lambda: self.programs.chunk(bt, ids, pos, counts, temps, topks, tick, state_rows),
        )
        if counted is not None:
            self._rb_counts += counted
            self._count_gdn("chunk")
        t1 = telemetry.now_ns()
        self.stat_chunk_dispatches += 1
        self.stat_chunk_rows_held += held
        self._rb_chunk_rows += len(slots)
        self._rb_chunk_rows_live += len(rows)
        self._rb_chunk_rows_held += held
        if attn == "kernel":
            self._rb_chunk_rows_kernel += len(rows)
        self._count_sampling(temps, topks)
        bucket = ids.shape[1]
        finishing: list[tuple[_Seq, int, int]] = []  # (seq, slot, its row's token)
        with self._phase(P_SCATTER):
            for r, i in enumerate(slots.tolist()):
                seq = self._slots[i] if i >= 0 else None
                if seq is None:
                    continue
                seq.prefill_pos += int(counts[r])
                seq.state_src = -1  # from here on the slot's own row
                if i in snaps:
                    # the dispatch kept the state at the hint's boundary: pin
                    # the pages up to it and bind the row, so that the very
                    # next admission can start from both
                    self._maybe_capture(seq, i, seq.prefill_pos, snaps[i])
                elif self.pool.windowed and int(counts[r]) and seq.prefill_pos == self._hint_boundary(seq):
                    # window-kind pages: pin the span while the slot still maps
                    # its last window (the next write gives the oldest back)
                    self._maybe_capture(seq, i, seq.prefill_pos)
                for c in seq.trace_ctxs:
                    cs = c.buf.begin(
                        "decode.prefill_chunk",
                        c.span.span_id,
                        {
                            "slot": i, "chunk": seq.chunk_idx,
                            "tokens": int(counts[r]), "bucket": bucket,
                            "reused": seq.prefix_len,
                        },
                        start_ns=t0,
                    )
                    cs.end(t1)
                seq.chunk_idx += 1
                if seq.prefill_pos >= self.seq_len:
                    finishing.append((seq, i, int(toks[r])))
        if finishing and self.programs.admit_buckets:
            # (a feature head needs no transition-time draft prefill — its
            # prompt K/V was teacher-forced by the chunk dispatches)
            # async dispatch: this is enqueue cost; the device time lands
            # in the next dispatch's blocked readback
            with self._dispatch(F_DRAFT) as d, d.enqueue():
                self.programs.draft_admit([(i, seq.prompt) for seq, i, _ in finishing])
        t2 = telemetry.now_ns()
        with self._phase(P_SCATTER):
            for seq, i, first in finishing:
                seq.prefilling = False
                seq.pos = self.seq_len
                if self.prefix_enabled and seq.cache_prefix > 0 and not self._boundary_cache:
                    # hinted capture at prefill completion — the hinted
                    # span's pages are pinned from this moment, so the very
                    # next admission can already map them
                    self._maybe_capture(seq, i, seq.cache_prefix)
                for c in seq.trace_ctxs:
                    seq.gen_spans.append(
                        c.buf.begin(
                            "decode.generate",
                            c.span.span_id,
                            {"slot": i, **self._mesh_attrs},
                            start_ns=t2,
                        )
                    )
                tok = self._emit(seq, first)
                if self._finished(seq, tok):
                    self._retire(i)

    async def _spec_round(
        self, bt, toks, pos, temps, topks, limits, wlimits, rows, tick
    ) -> None:
        """One speculative round: ONE draft dispatch proposes spec_k
        tokens per slot (or the whole candidate TREE on tree deployments),
        ONE widened target dispatch verifies them, and every slot advances
        by its accepted length + the bonus token (limit-0 slots —
        per-request opt-outs, budget edges, free slots — ride the same
        round and get exactly their plain-step token). Emission,
        EOS/budget retirement, and per-token streaming run token-by-token
        exactly as on the plain path, so mid-burst retirement and SSE keep
        working. Tree rounds roll the caches forward by PATH positions:
        ``out_t``'s row layout ([n, depth+1], accepted-path tokens + bonus)
        is identical to the chain's, so the host-side emission walk
        (``_consume_spec``) is shared between the modes.

        The draft/verify wall split feeds the flight frame's per-family
        attribution: ``d.enqueue(F_DRAFT)`` books the draft's segment to
        its own column, the verify side splits again into enqueue vs
        blocked readback at the mark — with async dispatch the draft and
        verify-enqueue segments are host-side dispatch cost and the verify
        readback carries the blocked wait of the whole round pair.
        Pipelined, the pair enqueues back-to-back on the loop, round N+1's
        host phases run under it (``_overlap_window``: inside the verify
        family's busy wall, recorded apart as the frame's overlap_ns), and
        only then does the host block on the verify readback. Serial, the
        pair and its read run in one piece (``d.run``)."""
        programs = self.programs
        t0 = telemetry.now_ns()
        with self._dispatch(F_VERIFY) as d:

            def draft():
                with d.enqueue(F_DRAFT):
                    return programs.draft(toks, pos, temps, topks, tick)

            def verify(proposal):
                return programs.verify(
                    bt, toks, proposal, pos, temps, topks, limits, wlimits, rows, tick
                )

            self._rb_active = self.active  # dispatch-time occupancy
            if self._pipeline_on():
                proposal = draft()
                with d.enqueue():
                    queued = verify(proposal)
                self._overlap_window()
                out_t, acc = await d.readback(*queued)
            else:
                out_t, acc = await d.run(lambda: verify(draft()))
        t1 = telemetry.now_ns()
        self._consume_spec(out_t, acc, limits, wlimits, t0, t1)

    def _consume_spec(self, out_t, acc, limits, wlimits, t0: int, t1: int) -> None:
        """The readback-dependent half of a speculative round: the
        accept/emission walk over the verify readback, retirements,
        speculation attribution, and the adaptive controller's update."""
        tree = self.spec_tree
        self.stat_spec_dispatches += 1
        # ``proposed`` is the round's ACCEPTANCE OPPORTUNITY — depth
        # positions a path could advance through — for both modes, so
        # accept rate means the same thing on chain and tree deployments
        # (and is what the adaptive controller steers on)
        proposed = int(limits.sum())
        accepted = int(acc.sum())  # limit-0 and free slots contribute 0
        emitted = 0
        mode = "chain" if tree is None else "tree"
        with self._phase(P_ACCEPT_WALK):
            for i, seq in enumerate(list(self._slots)):
                if seq is None or seq.prefilling:
                    # prefilling slots ride the round at limit 0 with their
                    # junk landing at their own prefill cursor — no emission
                    continue
                # one decode.verify span per round on the sequence's own
                # trace(s), the accept count as an event — per-round, not
                # per-token, so a k=4 generation adds ~len/5 spans. Tree
                # rounds carry the tree shape + this slot's allowed node
                # budget so traces explain the per-round speedup.
                riding = int(limits[i]) > 0
                attrs = {"slot": i, "proposed": int(limits[i]), **self._mesh_attrs}
                if tree is not None:
                    nodes = int(wlimits[i].sum())
                    attrs["tree"] = self._tree_text
                    attrs["tree_nodes"] = nodes
                    if riding:
                        # limit-0 slots (opt-outs, budget edges) would record
                        # structural nodes=0 samples and skew the histogram
                        self._metrics.decode_spec_tree(
                            self._deployment, nodes, int(acc[i])
                        )
                for c in seq.trace_ctxs:
                    vs = c.buf.begin(
                        "decode.verify", c.span.span_id, attrs, start_ns=t0
                    )
                    ev = {"accepted": int(acc[i])}
                    if tree is not None:
                        ev["path_depth"] = int(acc[i])
                    vs.add_event("accept", ev)
                    vs.end(t1)
                for j in range(int(acc[i]) + 1):
                    seq.pos += 1
                    tok = self._emit(seq, int(out_t[i, j]))
                    emitted += 1
                    if riding:
                        # only tokens from slots that actually speculated count
                        # toward the per-ride amortization — a limit-0 slot's
                        # plain-equivalent token would inflate emitted/rides
                        self.stat_spec_ride_emitted += 1
                    if self._finished(seq, tok):
                        self._retire(i)
                        break
        self.stat_spec_proposed += proposed
        self.stat_spec_accepted += accepted
        self.stat_spec_emitted += emitted
        self.stat_spec_rides += int((limits > 0).sum())
        self._rb_accepted = accepted
        self._rb_proposed = proposed
        if self._adapt is not None:
            # the per-slot (accepted, limit) pairs of riding slots feed
            # the auto-tuner's per-depth reach estimate — the signal the
            # width masks are reshaped from
            paths = [
                (int(acc[i]), int(limits[i]))
                for i in range(self.n_slots)
                if limits[i] > 0
            ]
            self._adapt.update(accepted, proposed, paths=paths)
        self._metrics.decode_spec(
            self._deployment, proposed, accepted, emitted, mode=mode
        )

    async def _step_round(self, bt, toks, pos, temps, topks, rows, tick) -> np.ndarray:
        """The plain round's dispatch: the fused step, its token readback.
        Pipelined, the step is enqueued on the loop, round N+1's host
        phases run under the in-flight dispatch (``_overlap_window``), then
        the host blocks on the readback: the step family's busy column
        spans the whole enqueue->readback window (the overlap work sits
        INSIDE the device-busy wall — recorded apart as the frame's
        overlap_ns) and rdb is the true post-overlap block. Serial
        (ENGINE_DECODE_PIPELINE=off), call and read run in one piece
        (``d.run``)."""

        def step():
            return self.programs.step(bt, toks, pos, temps, topks, tick, rows)

        with self._dispatch(F_STEP, rows=self.n_slots, live=int(np.count_nonzero(rows))) as d:
            self._rb_active = self.active  # dispatch-time occupancy
            if self._pipeline_on():
                with d.enqueue():
                    queued = step()
                self._overlap_window()
                nxt, counted = await d.readback(*queued)
            else:
                nxt, counted = await d.run(step)
        if counted is not None:
            self._rb_counts += counted
            self._rb_step_counts = tuple(counted.tolist())  # the step's own, beside the round's sum
            self._count_gdn("step")
        return nxt

    def _count_gdn(self, kind: str) -> None:
        """A counted ``kind`` dispatch ("step" | "chunk") of a family with
        delta-rule layers: its layer passes and those that its program runs
        in a kernel (the family's ``gdn_passes``), onto the round's frame."""
        if self._gdn_passes is not None:
            passes, kernel = self._gdn_passes(kind)
            self._rb_gdn[0] += passes
            self._rb_gdn[1] += kernel

    async def _run(self) -> None:
        # while the loop runs, a collection of the interpreter's oldest
        # generation names itself in a trace and counts on the recorder
        gc2 = flight_mod.Gc2Watch(self.flight)
        gc2.install()
        try:
            # register this loop's thread with the process-global sampling
            # profiler (telemetry/profile.py — GET /decode/profile); a
            # no-op under ENGINE_DECODE_PROFILE=off
            profile_mod.watch_decode_thread()
            # the round clock starts when the LOOP does: everything between
            # __init__ and the first submit (warmup compiles, idle boot
            # time) is not decode bubble and must not land in frame 0's gap
            self._round_reset()
            while True:
                self._round_yielded = False
                # _admit is async-shaped but never suspends (pure host
                # work), so the phase handle held across the await times
                # exactly the admission walk
                with self._phase(P_ADMIT):
                    await self._admit()
                if self.active == 0:
                    if not self._waiting:
                        if self._closed:
                            return
                        self._wake.clear()
                        with flight_mod.annotate(ANN_IDLE_WAIT):
                            await self._wake.wait()
                        # idle wait is not decode bubble: restart the
                        # round clock so the next frame's host gap is the
                        # loop's own, not the queue's silence
                        self._round_reset()
                    continue
                if self._faults is not None:
                    # decode-tier chaos (install_decode_faults): a hung
                    # round sleeps here with slots held — exactly what a
                    # wedged device dispatch looks like from outside —
                    # and an induced OOM arms the allocator so this
                    # round's KV write fails through the REAL error path
                    await self._chaos_round()
                # one prefill chunk per round, interleaved with the decode
                # step below — running slots keep emitting while long
                # prompts prefill chunk by chunk, and an admission wave
                # ``chunk_rows_cap`` slots a round (with no chunk cap a
                # prompt prefills in one top-bucket dispatch)
                await self._chunk_round()

                with self._phase(P_SAMPLING):
                    # next-dispatch input build: the sampled-token /
                    # position vectors every generating slot rides.
                    # ``fmask`` marks the generating rows — the feature
                    # programs' carry mask (a junk-riding slot must not
                    # clobber its carried feature)
                    toks = np.zeros(self.n_slots, np.int32)
                    pos = np.zeros(self.n_slots, np.int32)
                    temps = np.zeros(self.n_slots, np.float32)
                    topks = np.zeros(self.n_slots, np.int32)
                    fmask = np.zeros(self.n_slots, bool)
                    n_gen = 0
                    unridden: list[int] = []
                    for i, seq in enumerate(self._slots):
                        if seq is None:
                            continue
                        if seq.future.cancelled():
                            # client vanished mid-generation (stream
                            # closed): free the slot instead of decoding
                            # its full budget
                            self._retire(i)
                            continue
                        if seq.prefilling:
                            # still mid-prefill: ride the step like a free
                            # slot but park the junk write at the slot's
                            # own prefill cursor, where the next chunk
                            # overwrites it before any attention mask can
                            # reach it. The page under the cursor is the
                            # slot's own only once a chunk round has taken
                            # the slot (``prepare_write``); one that the
                            # rounds have left out so far (``_chunk_rows_taken``)
                            # may still share it with the prefix entry it
                            # hit, so its table row is blanked below
                            pos[i] = seq.prefill_pos
                            if seq.chunk_idx == 0:
                                unridden.append(i)
                            continue
                        toks[i] = seq.tokens[-1]
                        pos[i] = seq.pos
                        temps[i] = seq.temperature
                        topks[i] = seq.top_k
                        fmask[i] = True
                        n_gen += 1
                if self.active == 0:
                    # chunk round retired everyone (EOS at prompt end,
                    # cancellations): commit the round's frame without a
                    # decode step
                    self._commit_round("chunk", step=False)
                    continue
                if n_gen == 0:
                    # pure-prefill round (every occupied slot still mid-
                    # prompt): loop straight to the next chunk round
                    self._commit_round("chunk", step=False)
                    await asyncio.sleep(0)
                    continue
                limits = None
                wlimits = None
                if self.spec_enabled:
                    # accept-driven shape for THIS round: the controller's
                    # effective depth (ceiling = configured spec_k / tree
                    # depth, 0 = plain decode) and — on tree deployments —
                    # the tuned per-depth width ceiling, both data-only so
                    # the program set never changes. Probe rounds (the
                    # depth-1 recovery probe, the full-shape width probe)
                    # are tagged into the flight frame.
                    ad, tuned, probe = self._adapt.decide()
                    self._rb_depth = int(ad)
                    self._rb_probe = bool(probe)
                    limits = np.zeros(self.n_slots, np.int32)
                    for i, seq in enumerate(self._slots):
                        if seq is None or seq.prefilling:
                            continue
                        # propose at most what the remaining budget can
                        # still emit beyond the bonus token (a round emits
                        # accepted + 1 tokens) — a slot one token from its
                        # budget rides the round with limit 0
                        limits[i] = max(
                            0, min(seq.spec_k, ad, seq.max_new - len(seq.tokens) - 1)
                        )
                    if self.spec_tree is not None:
                        # per-slot per-depth branching widths: the request's
                        # tightened tree, cut by the auto-tuner's width
                        # ceiling (never widening past the configured tree)
                        # and the slot's depth allowance (budget +
                        # adaptation). Width 0 at a depth ends the
                        # acceptance walk there as a limit clamp.
                        base = self.spec_tree.branching
                        self._rb_widths = tuned if tuned is not None else base
                        wlimits = np.zeros(
                            (self.n_slots, self.spec_tree.depth), np.int32
                        )
                        for i, seq in enumerate(self._slots):
                            if seq is None or seq.prefilling or limits[i] <= 0:
                                continue
                            w = seq.tree_widths or base
                            if tuned is not None:
                                w = tuple(
                                    min(w[d], tuned[d]) for d in range(len(w))
                                )
                            for d in range(min(int(limits[i]), len(w))):
                                if w[d] <= 0:
                                    break
                                wlimits[i, d] = w[d]
                            # limits[i] must equal the depth the walk can
                            # actually reach: a spec_tree tighten ("0", or
                            # a short/zeroed width string) otherwise leaves
                            # unreachable depth positions in `proposed`,
                            # which skews the accept-rate estimate (and the
                            # adaptive floor) down for the whole deployment
                            limits[i] = int((wlimits[i] > 0).sum())
                tick = self._next_tick()
                spec_round = (
                    bool(wlimits.any())
                    if wlimits is not None
                    else (limits is not None and bool(limits.any()))
                )
                if not spec_round and self.spec_enabled:
                    # a probe the controller scheduled can still fall to a
                    # plain round here (every riding slot at its budget
                    # edge zeroes its limit) — the plain frame must not be
                    # tagged as exploration nor advertise a tree shape the
                    # round never ran
                    self._rb_probe = False
                    self._rb_widths = ()

                # page residency for the round's writes: 1 token per
                # generating slot on the plain step, the full [k+1]-wide
                # block (accepted or junk) on a speculative round.
                # Prefilling slots need nothing — their junk parks in pages
                # their chunk rounds made their own (allocated or copied on
                # write) or, past those and for a slot no chunk round has
                # taken yet, in the junk sink.
                width = self.spec_k + 1 if spec_round else 1
                copies: list[tuple[int, int]] = []
                with self._phase(P_ALLOC):
                    for i, seq in enumerate(self._slots):
                        if seq is None or seq.prefilling:
                            continue
                        copies += self.pool.alloc.prepare_write(i, seq.pos, width)
                await self._run_copies(copies)
                with self._phase(P_ALLOC):
                    # a free slot's row: whatever this dispatch writes for
                    # them lands in junk page 0, not in a prefix's pages
                    bt = self.pool.block_tables(junk=unridden)
                    if not self._pipeline_on():
                        # per-round pool gauges: this round's prepare_write
                        # may have allocated/CoW'd pages with no admission
                        # between. The pipelined loop refreshes them inside
                        # every overlap window (_pipeline_sundries) — at
                        # most one round stale, hidden under the flight.
                        self._kv_gauges()

                self._count_sampling(temps, topks)
                if spec_round:
                    await self._spec_round(
                        bt, toks, pos, temps, topks, limits, wlimits, fmask, tick
                    )
                    # reconcile the shadow admissions decided under the
                    # round pair's flight BEFORE the frame commits (they
                    # belong to this round, like the serial walk's)
                    with self._phase(P_ADMIT):
                        self._apply_pending()
                    self._commit_round(
                        "tree" if self.spec_tree is not None else "chain",
                        step=True,
                    )
                    await asyncio.sleep(0)
                    continue

                self._rb_attn_pages = self._step_attn_pages(pos, fmask)
                nxt = await self._step_round(bt, toks, pos, temps, topks, fmask, tick)
                with self._phase(P_SAMPLING):
                    # sampled-token consumption: the readback array walked
                    # into per-slot emissions/retirements
                    for i, seq in enumerate(self._slots):
                        if seq is None or seq.prefilling:
                            continue
                        seq.pos += 1
                        tok = self._emit(seq, int(nxt[i]))
                        if self._finished(seq, tok):
                            self._retire(i)
                # reconcile the shadow admissions decided under the flight
                with self._phase(P_ADMIT):
                    self._apply_pending()
                self._commit_round("plain", step=True)
                # yield between steps so admissions/ingress interleave with
                # the decode loop instead of starving behind it — unless the
                # round's own dispatches already waited off the loop
                # (``_device_call``): then the streams' writers had the whole
                # flight to run in, and the tokens emitted above are flushed
                # under the NEXT dispatch instead of between two, where the
                # device would idle through sixteen socket writes (1.3 ms of a
                # 24 ms round, and what varied most from one process to the
                # next: my chip runs, PR 28)
                if not self._round_yielded:
                    await asyncio.sleep(0)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 - fail every waiter, not just one
            log.exception("decode loop failed")
            # flight auto-dump: the rounds LEADING UP to the error are the
            # diagnostic; force-retain them in the span store before the
            # ring keeps rolling (forced dumps bypass the rate limit)
            self.flight.dump("round_error", force=True)
            for seq in list(self._slots) + list(self._waiting):
                if seq is None:
                    continue
                for sp in seq.gen_spans:
                    sp.error = True
                    sp.end()
                seq.gen_spans = []
                if not seq.future.done():
                    seq.future.set_exception(
                        APIException(ErrorCode.ENGINE_MICROSERVICE_ERROR, str(e))
                    )
            self._slots = [None] * self.n_slots
            self._free = list(range(self.n_slots - 1, -1, -1))
            self._waiting.clear()
            self._reset_device_state()
        finally:
            gc2.remove()
            self._round_mark(False)

    def _reset_device_state(self) -> None:
        """Error-path device-state rebuild: the pool state (and in spec
        mode the draft caches / feature buffer) was DONATED into the call
        that just raised — its buffers may be invalidated, which would
        poison every later admission with 'array has been deleted'.
        Reallocate (pool.reset also rebuilds the host allocator, so every
        page mapping drops with the bytes) and clear the index entries
        that pointed into it."""
        self.pool.reset()
        self.programs.reset()
        # the fresh allocator counts from zero: so do the window kind's twins
        self.stat_kv_win_written = self.stat_kv_win_released = self._kv_win_released_gauged = 0
        if self.prefix_enabled:
            self._prefix_index.clear()

    async def close(self) -> None:
        """Drain: stop accepting NEW work, finish everything in flight AND
        queued (same shutdown contract as MicroBatcher.close — no caller is
        left with an unresolved future)."""
        self._closed = True
        self._wake.set()
        if self._task is not None:
            try:
                await self._task
            except Exception:  # noqa: BLE001 - loop errors already routed to futures
                pass
            self._task = None

    async def abort(self) -> None:
        """Hard stop for an EVICTED fleet replica: close() drains, but a
        hung loop never drains. Cancel the loop task mid-round, cancel any
        still-unsettled futures (the router has already migrated the live
        generations — anything left has no consumer), and rebuild the
        device pool so the post-mortem allocator audit runs against a
        consistent allocator instead of a torn mid-round snapshot."""
        self._closed = True
        self._wake.set()
        task = self._task
        self._task = None
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        for seq in list(self._slots) + list(self._waiting):
            if seq is None:
                continue
            for sp in seq.gen_spans:
                sp.error = True
                sp.end()
            seq.gen_spans = []
            if not seq.future.done():
                seq.future.cancel()
        self._slots = [None] * self.n_slots
        self._free = list(range(self.n_slots - 1, -1, -1))
        self._waiting.clear()
        self._reset_device_state()

    # ------------------------------------------------- fleet health / chaos
    def health_probe(self) -> dict:
        """In-process liveness probe the fleet health poller calls each
        interval — the in-process twin of polling GET /decode/health on an
        out-of-process replica. Raises when a chaos drop is armed (the
        equivalent of a dropped HTTP response). ``ticks`` is the loop's
        dispatch counter: a probe that answers while ``active`` slots show
        no tick progress between polls is a HUNG loop — the probe itself
        is host-side and survives a wedged dispatch."""
        if self._faults is not None and self._faults.health_drop():
            raise TimeoutError(
                f"chaos: dropped decode health response (replica "
                f"{self.replica_id})"
            )
        return {
            "replica_id": self.replica_id,
            "queue_depth": len(self._waiting),
            "active": self.active,
            "ticks": int(self._tick),
            "closed": bool(self._closed),
        }

    async def _chaos_round(self) -> None:
        """Apply this round's armed decode fault (top of the active round,
        before any dispatch)."""
        d = self._faults.round_decision()
        if d.action == "hang":
            log.warning(
                "chaos: decode replica %d hanging for %.1fs",
                self.replica_id, d.delay_s,
            )
            self._metrics.fault_injected(self._deployment, "decode", "hang")
            await asyncio.sleep(d.delay_s)
        elif d.action == "oom":
            log.warning(
                "chaos: decode replica %d arming induced allocator OOM",
                self.replica_id,
            )
            self._metrics.fault_injected(self._deployment, "decode", "oom")
            self.pool.alloc.chaos_oom_writes = 1

    # ------------------------------------------------------ message adapter
    def request_params_from_meta(self, meta: Meta) -> dict:
        """Per-request overrides ride meta.tags (the JSON envelope's
        ``meta.tags`` — no schema change for existing clients): temperature,
        top_k, max_new_tokens, spec_k, spec_tree, cache_prefix,
        prefill_chunk. Values clamp to the deployment's caps (spec_k,
        spec_tree, and prefill_chunk are tighten-only: a request can
        reduce or disable them, never widen past the deployment's;
        cache_prefix clamps to decode_prefix_ctx)."""
        tags = meta.tags or {}
        out: dict = {}
        for key, cast in (
            ("max_new_tokens", int),
            ("temperature", float),
            ("top_k", int),
            ("spec_k", int),
            ("cache_prefix", int),
            ("prefill_chunk", int),
        ):
            if key in tags:
                try:
                    out[key] = cast(tags[key])
                except (TypeError, ValueError):
                    raise APIException(
                        ErrorCode.ENGINE_INVALID_JSON,
                        f"meta.tags.{key} must be a number, got {tags[key]!r}",
                    )
        if "spec_tree" in tags:
            # per-depth branching tighten, e.g. "2,1" — validated against
            # the deployment tree at submit; ignored on non-tree
            # deployments (the tighten-only contract: nothing to narrow)
            out["spec_tree"] = str(tags["spec_tree"])
        if "kv_tier" in tags:
            # tiered-KV opt-out ("off" | "host") — tighten-only: a
            # request can narrow the promotion ladder, never widen it;
            # validated at submit
            out["kv_tier"] = str(tags["kv_tier"])
        return out

    async def execute_message(self, msg: SeldonMessage) -> SeldonMessage:
        """Buffered serving entry (what the micro-batcher hands generative
        requests to): every row of the request becomes its own sequence,
        admitted independently — rows of one request ride exactly the same
        slots, admission, and retirement as rows of different requests.

        The response mirrors the fused path's shape contract
        ([b, seq + max_new]): EOS-retired rows are right-padded with the
        EOS id so the tensor stays rectangular; per-row generated lengths
        ride meta.tags.gen_lens."""
        arr = msg.array
        if arr is None:
            raise APIException(
                ErrorCode.ENGINE_INVALID_JSON,
                "generative predictor needs tensor token ids",
            )
        rows = np.atleast_2d(np.asarray(arr)).astype(np.int32)
        overrides = self.request_params_from_meta(msg.meta)
        # SLO outcome tagging: when the deployment declares TTFT/ITL SLOs
        # or the request rode in under a deadline budget, each row's
        # met/breached verdict is reported back via meta.tags.slo (what the
        # access log and a fleet router read)
        track_slo = bool(self.slo_ttft_s or self.slo_itl_s) or (
            current_deadline() is not None
        )
        slo_flags: list[bool] = [True] * len(rows)

        def _sink(i: int):
            if not track_slo:
                return None
            return lambda ok: slo_flags.__setitem__(i, ok)

        # settle EVERY row before failing the request: plain gather would
        # raise on the first row's error while sibling rows keep decoding
        # detached (wasted slots) with their exceptions never retrieved
        outs = await asyncio.gather(
            *(
                self.submit(row, **overrides, _slo_sink=_sink(i))
                for i, row in enumerate(rows)
            ),
            return_exceptions=True,
        )
        for o in outs:
            if isinstance(o, BaseException):
                raise o
        max_new = overrides.get("max_new_tokens", self.max_new_tokens)
        max_new = max(1, min(int(max_new), self.max_new_tokens))
        width = rows.shape[1] + max_new
        pad_id = self.eos_id if self.eos_id >= 0 else 0
        full = np.full((len(outs), width), pad_id, np.int32)
        gen_lens = []
        for i, o in enumerate(outs):
            full[i, : len(o)] = o
            gen_lens.append(int(len(o) - rows.shape[1]))
        tags = {**msg.meta.tags, "gen_lens": gen_lens}
        if track_slo:
            tags["slo"] = ["met" if ok else "breached" for ok in slo_flags]
        meta = Meta(
            puid=msg.meta.puid,
            tags=tags,
            routing=dict(msg.meta.routing),
            request_path=dict(msg.meta.request_path),
        )
        # derived from the request msg (not from_array) so the response
        # mirrors the request's data KIND (ndarray vs tensor), exactly like
        # the fused model path
        return msg.with_array_meta(full, meta)


def scheduler_for_executor(executor, tpu_spec, *, metrics=None, deployment_name=""):
    """Build a DecodeScheduler for a predictor when its graph is ONE
    decoder-backed JAX model and the deployment opted in
    (tpu.decode_slots > 0). Multi-node graphs keep the fused path — the
    scheduler owns the whole device loop and cannot sit inside a DAG walk.
    Returns None when the predictor doesn't qualify (with a log line saying
    why, so a silently-ignored opt-in is diagnosable)."""
    if getattr(tpu_spec, "decode_slots", 0) <= 0:
        return None
    root = executor.root
    runtime = getattr(root.unit, "runtime", None)
    gen = getattr(runtime, "generative", None) if runtime is not None else None
    if root.children or gen is None:
        log.warning(
            "decode_slots=%s set but the graph is not a single generative "
            "model node — falling back to the fused whole-batch path",
            tpu_spec.decode_slots,
        )
        return None
    if getattr(runtime, "weight_quant", ""):
        log.warning(
            "decode scheduler does not support weight_quant yet — falling "
            "back to the fused whole-batch path"
        )
        return None
    draft_uri = str(getattr(tpu_spec, "decode_draft_model", "") or "")
    spec_k = int(getattr(tpu_spec, "decode_spec_k", 0))
    spec_tree = str(getattr(tpu_spec, "decode_spec_tree", "") or "").strip()
    if spec_tree:
        # pre-check the tree shape with the same parser/caps the scheduler
        # ctor enforces as hard errors — through serving an unservable
        # opt-in degrades with a log line (the spec-mode precedent)
        try:
            if SpecTree.from_text(spec_tree).n_tree > MAX_TREE_NODES:
                raise ValueError(
                    f"flattens past the {MAX_TREE_NODES}-node verify headroom"
                )
        except ValueError as e:
            log.warning(
                "decode_spec_tree=%r unservable (%s) — tree speculation "
                "disabled", spec_tree, e,
            )
            spec_tree = ""
    if spec_tree and not draft_uri:
        log.warning(
            "decode_spec_tree=%r needs decode_draft_model — tree "
            "speculation disabled", spec_tree,
        )
        spec_tree = ""
    if not spec_tree and spec_k > MAX_TREE_NODES:
        # the chain rides the same widened-dispatch headroom (a k-chain
        # IS a branching-1 tree) — same warn-disable precedent as an
        # unservable tree, so a stale CR degrades instead of failing boot
        log.warning(
            "decode_spec_k=%s exceeds the %s-token verify headroom — "
            "speculation disabled", spec_k, MAX_TREE_NODES,
        )
        spec_k = 0
    draft_params = None
    if draft_uri and (spec_k > 0 or spec_tree):
        from seldon_core_tpu.models.zoo import _parse_zoo_uri, get_model

        if draft_uri.startswith("zoo://"):
            dname, dkw = _parse_zoo_uri(draft_uri)
        else:
            dname, dkw = draft_uri, {}
        # the draft must share the target's vocabulary and position-table
        # reach — inject both from the target unless the URI pins them. A
        # feature-head draft (zoo://draft?features=1) must also match the
        # target's hidden width (its fc fuse consumes the target's
        # feature vector), so that defaults from the target too — and so
        # does ffn, because the distill recipe sizes the head's FFN to
        # the target's by default (the documented distill-then-serve flow
        # must line up without pinning ffn in the URI).
        # a target whose family serves no speculation is refused here by
        # name (FamilyNotServed), before a draft is built for it
        family = decoder_family(gen.get("family"))
        require_served(family, "speculation")
        dims = family.decoder_dims(runtime.params)
        dkw = {"vocab": dims["vocab"], "max_len": dims["max_len"], **dkw}
        if dkw.get("features"):
            dkw = {"hidden": dims["hidden"], "ffn": dims["ffn"], **dkw}
        dspec = get_model(dname, **dkw)
        if not (isinstance(dspec.params, dict) and "tok_emb" in dspec.params):
            log.warning(
                "decode_draft_model=%r is not a decoder (models/decoder.py "
                "layout) — speculation disabled",
                draft_uri,
            )
            spec_k = 0
            spec_tree = ""
        else:
            draft_params = jax.device_put(dspec.params)
    elif draft_uri or spec_k > 0:
        log.warning(
            "speculative decoding needs BOTH decode_draft_model and "
            "decode_spec_k > 0 (or decode_spec_tree) — got %r / %s — "
            "speculation disabled",
            draft_uri, spec_k,
        )
        spec_k = 0
    mesh_axes = dict(getattr(tpu_spec, "decode_mesh_axes", {}) or {})
    if mesh_axes:
        # the spec-mode precedent: an unservable opt-in degrades to the
        # working config with a log line, instead of failing the boot —
        # here that means single-device dispatch when the mesh request
        # exceeds the attached devices or the decoder's head/FFN geometry
        # isn't divisible by the tensor-parallel width
        problems = decode_mesh_problems(mesh_axes, runtime.params, draft_params)
        if problems:
            log.warning(
                "decode_mesh_axes=%s unservable (%s) — tensor-parallel "
                "decode disabled, running single-device",
                mesh_axes, "; ".join(problems),
            )
            mesh_axes = {}
    kv_store_url = str(getattr(tpu_spec, "decode_kv_store_tier", "") or "")
    if kv_store_url:
        # pre-check the store URL with the same factory the ctor uses as
        # a hard error — through serving a bad URL degrades the STORE
        # tier only (host tier keeps working) with a log line
        try:
            make_state_store(kv_store_url)
        except ValueError as e:
            log.warning(
                "decode_kv_store_tier=%r unservable (%s) — store tier "
                "disabled, host tier only", kv_store_url, e,
            )
            kv_store_url = ""
    sched_kwargs = dict(
        seq_len=int(gen["seq"]),
        max_new_tokens=int(gen["max_new_tokens"]),
        n_slots=int(tpu_spec.decode_slots),
        eos_id=int(getattr(tpu_spec, "decode_eos_id", -1)),
        temperature=float(getattr(tpu_spec, "decode_temperature", 0.0)),
        top_k=int(getattr(tpu_spec, "decode_top_k", 0)),
        seed=int(getattr(tpu_spec, "decode_seed", 0)),
        queue_timeout_s=float(getattr(tpu_spec, "queue_timeout_ms", 0.0)) / 1000.0,
        spec_k=spec_k if draft_params is not None else 0,
        spec_tree=spec_tree if draft_params is not None else "",
        spec_accept_floor=float(getattr(tpu_spec, "decode_spec_accept_floor", 0.0)),
        prefix_slots=int(getattr(tpu_spec, "decode_prefix_slots", 0)),
        prefix_ctx=int(getattr(tpu_spec, "decode_prefix_ctx", 0)),
        prefill_chunk=int(getattr(tpu_spec, "decode_prefill_chunk", 0)),
        kv_page_size=int(getattr(tpu_spec, "decode_kv_page_size", 0)),
        kv_pages=int(getattr(tpu_spec, "decode_kv_pages", 0)),
        kv_dtype=str(getattr(tpu_spec, "decode_kv_dtype", "") or ""),
        kv_host_bytes=int(getattr(tpu_spec, "decode_kv_host_bytes", 0)),
        kv_store_url=kv_store_url,
        slo_ttft_ms=float(getattr(tpu_spec, "decode_slo_ttft_ms", 0.0)),
        slo_itl_ms=float(getattr(tpu_spec, "decode_slo_itl_ms", 0.0)),
        metrics=metrics,
        dtype=runtime.dtype,
        family=gen.get("family"),
    )
    replicas = max(1, int(getattr(tpu_spec, "decode_replicas", 1) or 1))
    autoscale_max = int(getattr(tpu_spec, "decode_autoscale_replicas", 0) or 0)
    if max(replicas, autoscale_max) > 1 and mesh_axes:
        # replica scale-out and tensor parallelism partition the same
        # device budget; composing them (TP groups per replica) is future
        # work — the warn-disable precedent keeps a stale CR serving
        log.warning(
            "decode_replicas/decode_autoscale_replicas with decode_mesh_axes "
            "is not supported yet — running one tensor-parallel scheduler"
        )
        replicas, autoscale_max = 1, 0
    if max(replicas, autoscale_max) <= 1:
        return DecodeScheduler(
            runtime.params,
            draft_params=draft_params,
            mesh_axes=mesh_axes,
            deployment_name=deployment_name,
            **sched_kwargs,
        )

    # multi-replica decode scale-out (serving/affinity_router.py): N full
    # scheduler replicas — each with its own params copy, page pool, and
    # prefix index on its own device (round-robin over the attached
    # devices: N replicas = N independent dispatch streams) — behind the
    # prefix-affinity router with the reward-driven fallback policy.
    import os

    from seldon_core_tpu.serving.affinity_router import ReplicatedDecodeScheduler
    from seldon_core_tpu.utils import env as envmod

    base_name = deployment_name or "decode"
    devices = jax.devices()
    target_params = runtime.params

    def _replica_factory(i: int) -> DecodeScheduler:
        # EVERY replica (0 included) gets its own single-device params
        # copy: replica i lives wholly on device i (mod host size). The
        # runtime's own placement may span the deployment mesh — a replica
        # dispatching replicated over N devices would serialize the whole
        # fleet through every device
        dev = devices[i % len(devices)]
        p = jax.device_put(target_params, dev)
        dp = None if draft_params is None else jax.device_put(draft_params, dev)
        return DecodeScheduler(
            p,
            draft_params=dp,
            deployment_name=f"{base_name}/r{i}",
            replica_id=i,
            **sched_kwargs,
        )

    store_factory = None
    if autoscale_max > replicas:
        # spill through the persistence store — SAME default as the
        # microservice's unit-state persistence (file://./.seldon_state),
        # so an operator restart (or an out-of-process replica) boots
        # from the payload the last scale-up wrote. Resolved lazily at
        # the first spill (the file store's ctor mkdirs its directory).
        spill_url = os.environ.get(
            envmod.PERSISTENCE_STORE, "file://./.seldon_state"
        )

        def store_factory():
            try:
                return make_state_store(spill_url)
            except ValueError:
                log.warning(
                    "PERSISTENCE_STORE %r unusable — replica spill stays "
                    "in-process", spill_url,
                )
                return None

    return ReplicatedDecodeScheduler(
        _replica_factory,
        replicas,
        policy=str(getattr(tpu_spec, "decode_router_policy", "") or ""),
        affinity_block=int(getattr(tpu_spec, "decode_kv_page_size", 0) or 0) or 16,
        autoscale_replicas=autoscale_max,
        autoscale_queue_depth=int(
            getattr(tpu_spec, "decode_autoscale_queue_depth", 0) or 0
        ),
        spill_store_factory=store_factory,
        health_poll_ms=float(getattr(tpu_spec, "decode_health_poll_ms", 0.0) or 0.0),
        health_miss_threshold=int(
            getattr(tpu_spec, "decode_health_miss_threshold", 3) or 3
        ),
        drain_timeout_ms=float(
            getattr(tpu_spec, "decode_drain_timeout_ms", 5000.0) or 5000.0
        ),
        metrics=metrics,
        deployment_name=base_name,
        seed=int(getattr(tpu_spec, "decode_seed", 0)),
    )
