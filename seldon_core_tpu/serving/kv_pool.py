"""Paged KV memory subsystem for the generative tier.

The flat slot cache (PR 1-5) sized KV memory at ``n_slots * max_ctx``
worst-case per slot, and the prefix cache COPIED matched K/V into each
reader's slot row — HBM, not compute, capped concurrent users per chip.
This module replaces both with vLLM-style block-table paging (Kwon et al.,
SOSP 2023):

- ONE device-resident page pool of token rows ``[L, n_pages, page_size,
  h*hd]`` that live slots AND the prefix cache allocate from
  (models/decoder.py ``paged_kv_init`` / ``paged_copy`` own the device
  layout; the paged attention programs gather K/V through per-slot block
  tables and scatter new rows into the donated pool in place). This
  module deals in page indices — axis 1 of every component — only;
- a host-side allocator (``PageAllocator``): free list, per-page
  refcounts, copy-on-write on the first divergent write into a shared
  page, and LRU reclaim of prefix pins when the free list runs dry;
- block tables carried as a static-shape ``[n_slots, max_pages]`` int32
  array — tiny per-dispatch host->device traffic, zero recompiles;
- reservation-based admission: a sequence admits only when the pool can
  guarantee its worst-case EXCLUSIVE page need (its full context minus
  the fully-shared prefix pages, which are counted once pool-wide), so
  admission throttles gracefully instead of deadlocking mid-decode.

Sharing model: a prefix-cache hit maps the entry's pages straight into the
reader's block table (refcount bump — no gather, no copy). Pages below the
reuse boundary are never written again by the reader; the partially-shared
boundary page is copy-on-written at the reader's first divergent write
(one page copy, batched through the ``paged_copy`` ladder). A capture pins
a retiring/prefilled slot's prompt pages (refcount bump — the old
capture-copy dispatch is gone); pinned pages whose only reference is the
pin are reclaimed LRU-first under pool pressure.

Conventions: physical page 0 is a reserved junk sink — free slots' block
tables are all-zero and masked-off writes land there, so no static-shape
dispatch can corrupt a live page. Page 0 is never allocated.

A second kind of cache, for a family whose layers carry a recurrent state
(models/hybrid_decoder.py): STATE ROWS beside the pages, in the same
manager (``PagedKVPool.recurrent``: allocated, placed and reset with the
pool, donated to the same programs). Row r < n_slots is slot r's own; the
``n_state_rows`` rows after them hold cached prefixes' snapshots, handed out
by the allocator and bound to the prefix pin that owns the snapshot, so
whatever drops the pin (index cap, pin reclaim) gives the row back;
``zero_row`` is never written (a cold admission's first chunk reads it);
``drop_row`` is one past the last row: a write addressed there is dropped
(a padding row's, a dispatch without a snapshot). Rows are addressed by
index inside the programs: nothing here copies one, and ``paged_copy`` and
the copy ladder address pages only.
"""

from __future__ import annotations

import logging

import numpy as np

import jax

from seldon_core_tpu.models.decoder import paged_copy, paged_kv_init

log = logging.getLogger(__name__)


class PoolPin:
    """One prefix-cache pin: a refcount held on a page list (plus LRU age).
    The radix index entry that owns it stores the pin_id; eviction drops
    the refs and frees whatever nothing else references."""

    __slots__ = ("pin_id", "pages", "last_use", "state_row")

    def __init__(self, pin_id: int, pages: list[int]):
        self.pin_id = pin_id
        self.pages = list(pages)
        self.last_use = 0
        self.state_row = -1  # the snapshot row bound to this prefix (a recurrent family), else -1


class PageAllocator:
    """Host-side page accounting. Pure host state — the only device work it
    ever ASKS for is the (src, dst) page-copy list ``prepare_write``
    returns, which the caller batches through the pool's copy ladder
    BEFORE its write dispatch.

    Invariant (what makes admission deadlock-free): at all times
    ``free + reclaimable >= sum(outstanding reservations)``, where
    reclaimable counts pages whose only references are prefix pins.
    ``try_admit`` refuses any admission that would break it; ``_alloc``
    only spends reservation the slot holds."""

    def __init__(
        self, n_pages: int, page_size: int, n_slots: int, pages_per_slot: int, n_state_rows: int = 0
    ):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        floor = max(pages_per_slot + 2, n_slots + 1)
        if n_pages < floor:
            raise ValueError(
                f"decode_kv_pages={n_pages} is below the minimal residency "
                f"for n_slots={n_slots} at {pages_per_slot} pages/slot "
                f"(need >= {floor}: junk page + one slot's full context + "
                "one page of slack) — admission would deadlock, erroring "
                "instead"
            )
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.n_slots = int(n_slots)
        self.pages_per_slot = int(pages_per_slot)
        self.refs = np.zeros(n_pages, np.int32)
        self.refs[0] = 1  # page 0: reserved junk sink, never allocated
        self.pin_count = np.zeros(n_pages, np.int32)
        self._free: list[int] = list(range(n_pages - 1, 0, -1))
        self.block_tables = np.zeros((n_slots, pages_per_slot), np.int32)
        self._mapped = np.zeros(n_slots, np.int32)  # logical pages mapped
        self._reserved = np.zeros(n_slots, np.int64)  # pages still claimable
        self._pins: dict[int, PoolPin] = {}
        self._next_pin = 0
        self._clock = 0
        # snapshot rows of a recurrent family's state cache (module
        # docstring): the rows after the slots' own, free until taken for a
        # capture and bound to its pin
        self.n_state_rows = int(n_state_rows)
        self._state_free: list[int] = list(range(n_slots + self.n_state_rows - 1, n_slots - 1, -1))
        # called ONCE per reclaim wave with the list of reclaimed pin ids
        # (batched so the owner — the prefix index — hears of a wave
        # once, not once per pin, on the hot decode path)
        self.on_pins_reclaimed = None
        # chaos hook (engine/faults.py install_decode_faults): when > 0, the
        # next prepare_write raises as if the page budget were exhausted —
        # an induced allocator-OOM that exercises the decode loop's error
        # path without actually corrupting accounting
        self.chaos_oom_writes = 0
        self.stat_chaos_ooms = 0
        self.stat_pages_shared = 0
        self.stat_cow_copies = 0
        self.stat_reclaimed_pages = 0
        self.stat_pin_reclaims = 0

    # ------------------------------------------------------- introspection
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def prefix_pages(self) -> int:
        """Pages whose only references are prefix pins (reclaimable)."""
        return int(np.sum((self.pin_count > 0) & (self.refs == self.pin_count)))

    @property
    def live_pages(self) -> int:
        """Pages referenced by at least one live slot (shared or not)."""
        return self.n_pages - 1 - self.free_pages - self.prefix_pages

    def reserved_total(self) -> int:
        return int(self._reserved.sum())

    def snapshot(self) -> dict:
        """One cheap host-side read of the pool's occupancy + event
        counters — the flight recorder's per-round hook and the soak/bench
        summaries read this instead of poking individual properties (one
        definition of "pool state at time t" for every consumer)."""
        return {
            "free": self.free_pages,
            "live": self.live_pages,
            "prefix": self.prefix_pages,
            "reserved": self.reserved_total(),
            "shared_total": self.stat_pages_shared,
            "cow_total": self.stat_cow_copies,
            "pin_reclaims": self.stat_pin_reclaims,
            "state_rows_free": len(self._state_free),
        }

    def pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_size)

    def slot_pages(self, slot: int) -> list[int]:
        return [int(p) for p in self.block_tables[slot, : int(self._mapped[slot])]]

    def _reclaimable(self, exclude=()) -> int:
        mask = (self.pin_count > 0) & (self.refs == self.pin_count)
        cnt = int(mask.sum())
        for p in set(exclude):
            if mask[p]:
                cnt -= 1
        return cnt

    def check(self) -> None:
        """Internal-consistency audit (tests): every page is exactly one of
        {junk sink, free, referenced}; refs reconcile with block tables +
        pins; no free page is referenced or mapped."""
        refs = np.zeros(self.n_pages, np.int64)
        refs[0] = 1
        for s in range(self.n_slots):
            for p in self.slot_pages(s):
                refs[p] += 1
        pins = np.zeros(self.n_pages, np.int64)
        for pin in self._pins.values():
            for p in pin.pages:
                refs[p] += 1
                pins[p] += 1
        if not np.array_equal(refs, self.refs):
            raise AssertionError("refcounts diverged from block tables + pins")
        if not np.array_equal(pins, self.pin_count):
            raise AssertionError("pin counts diverged from pins")
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("double-free: duplicate page in free list")
        if 0 in free:
            raise AssertionError("junk page 0 leaked into the free list")
        for p in free:
            if self.refs[p] != 0:
                raise AssertionError(f"free page {p} still referenced")
        for p in range(1, self.n_pages):
            if self.refs[p] == 0 and p not in free:
                raise AssertionError(f"page {p} leaked (unreferenced, not free)")
        if self.free_pages + self._reclaimable() < self.reserved_total():
            raise AssertionError("reservation invariant broken")
        bound = [pin.state_row for pin in self._pins.values() if pin.state_row >= 0]
        rows = sorted(bound + self._state_free)
        if rows != list(range(self.n_slots, self.n_slots + self.n_state_rows)):
            raise AssertionError("snapshot rows diverged: each is free or bound to one pin")

    # ----------------------------------------------------------- admission
    def try_admit(self, slot: int, shared_pages, reuse: int, extra_reserve: int = 0) -> bool:
        """Admit a sequence into ``slot``: map its matched prefix pages
        (refcount bump — the copy-free share) and reserve its worst-case
        exclusive page need. Returns False — mapping nothing — when the
        pool cannot GUARANTEE the reservation; the caller leaves the
        request queued until retirements free pages.

        ``reuse`` is the matched token span; only its fully-covered pages
        are exempt from the reservation (the partial boundary page will be
        copy-on-written at the first divergent write). ``extra_reserve``
        covers CoW the caller knows is coming (a cache_prefix capture hint
        pinning pages mid-generation)."""
        if self._mapped[slot] or self._reserved[slot]:
            raise RuntimeError(f"slot {slot} admitted while still mapped")
        n_map = self.pages_for(reuse) if reuse > 0 else 0
        shared = [int(p) for p in list(shared_pages)[:n_map]]
        if len(shared) < n_map:
            raise ValueError("matched entry holds fewer pages than reuse needs")
        need = self.pages_per_slot - (int(reuse) // self.page_size) + int(extra_reserve)
        avail = self.free_pages + self._reclaimable(exclude=shared)
        if avail - self.reserved_total() < need:
            return False
        for lp, p in enumerate(shared):
            self.block_tables[slot, lp] = p
            self.refs[p] += 1
        self._mapped[slot] = n_map
        self._reserved[slot] = need
        self.stat_pages_shared += n_map
        return True

    # ---------------------------------------------------------- allocation
    def _alloc(self, slot: int) -> int:
        if self._reserved[slot] <= 0:
            raise RuntimeError(
                f"slot {slot} allocating past its reservation — the "
                "no-deadlock invariant would be void"
            )
        if not self._free:
            self._reclaim_until_free()
        p = self._free.pop()
        self.refs[p] = 1
        self._reserved[slot] -= 1
        return p

    def _reclaim_until_free(self) -> None:
        reclaimed: list[int] = []
        while not self._free and self._pins:
            # prefer the LRU pin that actually FREES a page (one whose
            # pages include a refs==1 page): dropping a pin whose pages
            # live readers still map would destroy a prefix entry without
            # relieving any pressure. Fall back to plain LRU when no
            # single pin frees anything (e.g. a page held by two pins
            # needs both dropped — still progress).
            freeing = [
                p for p in self._pins.values()
                if any(self.refs[pg] == 1 for pg in p.pages)
            ]
            pin = min(freeing or self._pins.values(), key=lambda q: q.last_use)
            self._drop_pin(pin, reclaim=True)
            reclaimed.append(pin.pin_id)
        if reclaimed and self.on_pins_reclaimed is not None:
            self.on_pins_reclaimed(reclaimed)
        if not self._free:
            raise RuntimeError(
                "kv page pool exhausted with nothing reclaimable — "
                "reservation invariant broken (bug)"
            )

    def prepare_write(self, slot: int, start: int, count: int) -> list[tuple[int, int]]:
        """Make positions [start, start + count) writable by ``slot``:
        allocate not-yet-mapped logical pages and copy-on-write shared
        ones. Returns the (src, dst) page copies the caller MUST dispatch
        (through the pool's copy ladder) before its write dispatch.
        Positions beyond the slot's virtual length are ignored — the
        device-side write mask junk-redirects them to page 0."""
        ps = self.page_size
        end = min(int(start) + int(count), self.pages_per_slot * ps)
        if count <= 0 or start >= end:
            return []
        if self.chaos_oom_writes > 0:
            self.chaos_oom_writes -= 1
            self.stat_chaos_ooms += 1
            raise RuntimeError(
                "chaos: induced allocator OOM (page budget exhausted by "
                f"fault injection) preparing write for slot {slot}"
            )
        copies: list[tuple[int, int]] = []
        bt = self.block_tables
        for lp in range(int(start) // ps, (end - 1) // ps + 1):
            if lp >= self._mapped[slot]:
                for lpn in range(int(self._mapped[slot]), lp + 1):
                    bt[slot, lpn] = self._alloc(slot)
                self._mapped[slot] = lp + 1
            else:
                p = int(bt[slot, lp])
                if self.refs[p] > 1:
                    fresh = self._alloc(slot)
                    copies.append((p, fresh))
                    bt[slot, lp] = fresh
                    self.refs[p] -= 1
                    self.stat_cow_copies += 1
        return copies

    # ---------------------------------------------------------- retirement
    def retire(self, slot: int) -> None:
        """Return the slot's page references to the pool: pages nothing
        else references go back to the free list; pages pinned as prefix
        entries (or shared with other readers) survive."""
        for lp in range(int(self._mapped[slot])):
            p = int(self.block_tables[slot, lp])
            self.refs[p] -= 1
            if self.refs[p] == 0:
                self._free.append(p)
        self.block_tables[slot, :] = 0
        self._mapped[slot] = 0
        self._reserved[slot] = 0

    # -------------------------------------------------------- prefix pins
    def capture(self, slot: int, length: int) -> PoolPin | None:
        """Pin the pages covering the slot's leading ``length`` tokens as a
        prefix entry — a refcount bump, NO copy (the old capture dispatch
        is gone). Returns None if the span isn't materialized yet."""
        n = self.pages_for(length)
        if n < 1 or n > self._mapped[slot]:
            return None
        pin = PoolPin(self._next_pin, self.slot_pages(slot)[:n])
        self._next_pin += 1
        self._clock += 1
        pin.last_use = self._clock
        for p in pin.pages:
            self.refs[p] += 1
            self.pin_count[p] += 1
        self._pins[pin.pin_id] = pin
        return pin

    def preseed_pin(self, n: int) -> PoolPin | None:
        """Allocate ``n`` free pages directly into a prefix pin (warm
        scale-up: a new replica's pool is seeded from another replica's
        spilled pages before it serves traffic — serving/affinity_router).
        Returns None when the free list cannot cover it. The reservation
        invariant holds unchanged: the pages leave the free list but enter
        the pin-only (reclaimable) set, so ``free + reclaimable`` is
        constant."""
        n = int(n)
        if n < 1 or n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        pin = PoolPin(self._next_pin, pages)
        self._next_pin += 1
        self._clock += 1
        pin.last_use = self._clock
        for p in pages:
            self.refs[p] = 1
            self.pin_count[p] = 1
        self._pins[pin.pin_id] = pin
        return pin

    # -------------------------------------------------------- snapshot rows
    def take_state_row(self, unread=()) -> int:
        """A free snapshot row for a capture about to be dispatched, passing
        over the rows in ``unread`` (free, but an admission that was decided
        while an entry held them has yet to read them); -1 where no other is
        free (the caller evicts an entry and asks again)."""
        for k in range(len(self._state_free) - 1, -1, -1):
            if self._state_free[k] not in unread:
                return self._state_free.pop(k)
        return -1

    def give_state_row(self, row: int) -> None:
        """Hand back a taken row that was bound to no pin after all."""
        self._state_free.append(int(row))

    def touch(self, pin_id: int) -> None:
        pin = self._pins.get(pin_id)
        if pin is not None:
            self._clock += 1
            pin.last_use = self._clock

    def release(self, pin_id: int) -> None:
        """Drop a pin its owner no longer wants (index-cap eviction)."""
        pin = self._pins.get(pin_id)
        if pin is not None:
            self._drop_pin(pin, reclaim=False)

    def _drop_pin(self, pin: PoolPin, reclaim: bool) -> None:
        del self._pins[pin.pin_id]
        if pin.state_row >= 0:
            self._state_free.append(pin.state_row)
        freed = 0
        for p in pin.pages:
            self.pin_count[p] -= 1
            self.refs[p] -= 1
            if self.refs[p] == 0:
                self._free.append(p)
                freed += 1
        if reclaim:
            self.stat_reclaimed_pages += freed
            self.stat_pin_reclaims += 1


class PagedKVPool:
    """Device pool state + host allocator + the CoW copy-ladder program.

    ``cache_ctx`` is the per-slot virtual context (seq + max_new; the paged
    write mask replaces the flat layout's verify/chunk headroom columns).
    ``n_pages=0`` auto-sizes to flat-equivalent capacity (every slot can
    hold its full context with zero sharing); smaller explicit budgets are
    where paging pays — admission then throttles on the reservation
    invariant instead of deadlocking."""

    def __init__(
        self,
        params,
        *,
        n_slots: int,
        cache_ctx: int,
        page_size: int = 0,
        n_pages: int = 0,
        kv_dtype: str = "",
        dtype=None,
        place=None,
        shardings_fn=None,
        kv_init=paged_kv_init,
        state_init=None,
        n_state_rows: int = 0,
    ):
        import jax.numpy as jnp

        if kv_dtype not in ("", "int8"):
            raise ValueError(
                f"decode_kv_dtype {kv_dtype!r} unsupported (want '' or 'int8')"
            )
        self.page_size = int(page_size) or 16
        self.pages_per_slot = -(-int(cache_ctx) // self.page_size)
        self.n_pages = int(n_pages) or (n_slots * self.pages_per_slot + 2)
        self.kv_dtype = kv_dtype
        self._params = params
        # the decoder family's zeroed-pool builder (the GPT-2 family's by
        # default; models/moe_decoder.py brings its own row width)
        self._kv_init = kv_init
        self._dtype = dtype if dtype is not None else jnp.float32
        self._place = place or (lambda arrs: tuple(arrs))
        self.n_slots = int(n_slots)
        # a recurrent family's zeroed state rows (module docstring); none otherwise
        self._state_init = state_init
        self.n_state_rows = int(n_state_rows) if state_init is not None else 0
        self.zero_row = self.n_slots + self.n_state_rows
        self.drop_row = self.zero_row + 1
        self.alloc = PageAllocator(
            self.n_pages, self.page_size, self.n_slots, self.pages_per_slot, self.n_state_rows
        )
        self.state = self._place(
            kv_init(params, self.n_pages, self.page_size, self._dtype, kv_dtype)
        )
        self.recurrent = self._recurrent_zeros()
        # tensor-parallel decode (parallel/tp.py): the scheduler hands a
        # per-buffer sharding resolver so the pool state is committed to
        # the decode mesh (payloads head-sharded, int8 scale planes
        # replicated) and the CoW copy ladder pins the SAME shardings on
        # its outputs — the donated state round-trips every program with
        # one stable layout, which is what keeps warmup's signatures
        # exactly the live ones (zero recompiles on the sharded geometry)
        self.state_shardings = (
            tuple(shardings_fn(a) for a in self.state)
            if shardings_fn is not None
            else None
        )
        copy_kw = (
            {"out_shardings": self.state_shardings}
            if self.state_shardings is not None
            else {}
        )
        self._copy_fn = jax.jit(paged_copy, donate_argnums=(0,), **copy_kw)
        buckets, b = [], 1
        while b < self.n_slots:
            buckets.append(b)
            b *= 2
        self.copy_buckets = tuple(buckets) + (self.n_slots,)
        self.stat_copy_dispatches = 0

    def _recurrent_zeros(self) -> tuple:
        if self._state_init is None:
            return ()
        return self._place(self._state_init(self._params, self.drop_row))

    @property
    def virtual_ctx(self) -> int:
        return self.pages_per_slot * self.page_size

    def state_rows(self, slots: np.ndarray, read=None, snap=None) -> np.ndarray:
        """The ``[3, rows]`` int32 row indices of one chunk dispatch over
        ``slots`` (-1: a padding row): the row each batch row reads (``read``
        {slot: row} where it is not the slot's own: ``zero_row`` for a cold
        first chunk, an entry's snapshot row for a warm one), the row it
        writes (its own) and the snapshot row it also writes (``snap`` {slot:
        row}). A padding row reads ``zero_row`` and writes nothing."""
        out = np.full((3, len(slots)), self.drop_row, np.int32)
        out[0] = self.zero_row
        for r, slot in enumerate(slots.tolist()):
            if slot >= 0:
                out[0, r] = (read or {}).get(slot, slot)
                out[1, r] = slot
                out[2, r] = (snap or {}).get(slot, self.drop_row)
        return out

    def block_tables(self, slots: np.ndarray | None = None) -> np.ndarray:
        """Fresh host copy of the block tables for one dispatch (the jit
        argument must not alias the live allocator state): every slot's
        row, or the rows of ``slots`` in their order, a row of junk page 0
        for each -1 among them (a compact dispatch's padding)."""
        bt = self.alloc.block_tables
        if slots is None:
            return bt.copy()
        return np.where(slots[:, None] >= 0, bt[slots], bt.dtype.type(0))

    def run_copies(self, copies: list[tuple[int, int]]) -> None:
        """Dispatch the round's CoW page copies through the warmed ladder
        (padding entries copy junk page 0 onto itself)."""
        i = 0
        while i < len(copies):
            batch = copies[i : i + self.copy_buckets[-1]]
            bucket = next(b for b in self.copy_buckets if b >= len(batch))
            src = np.zeros(bucket, np.int32)
            dst = np.zeros(bucket, np.int32)
            for j, (s, d) in enumerate(batch):
                src[j] = s
                dst[j] = d
            self.state = self._copy_fn(self.state, src, dst)
            self.stat_copy_dispatches += 1
            i += len(batch)

    def warmup(self) -> None:
        """Compile the copy ladder (page0 -> page0 self-copies touch no
        live bytes)."""
        for b in self.copy_buckets:
            self.state = self._copy_fn(
                self.state, np.zeros(b, np.int32), np.zeros(b, np.int32)
            )

    def compile_count(self) -> int:
        return self._copy_fn._cache_size()

    def reset(self) -> None:
        """Post-failure recovery: the state tuple was donated into a call
        that raised, so its buffers may be invalidated — reallocate, and
        drop every host mapping with it."""
        on_reclaimed = self.alloc.on_pins_reclaimed
        self.alloc = PageAllocator(
            self.n_pages, self.page_size, self.n_slots, self.pages_per_slot, self.n_state_rows
        )
        self.alloc.on_pins_reclaimed = on_reclaimed
        self.state = self._place(
            self._kv_init(
                self._params, self.n_pages, self.page_size, self._dtype, self.kv_dtype
            )
        )
        self.recurrent = self._recurrent_zeros()
