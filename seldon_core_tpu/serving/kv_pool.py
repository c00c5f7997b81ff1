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

A second kind of PAGE, for a family with sliding-window layers
(models/moe_decoder.py; ``decoder_dims``' ``kv_window_layers`` / ``kv_window``):
the window layers' planes follow the full layers' in the one state tuple, with
a page axis, a block table ``[n_slots, max_pages]`` and an allocator of their
own (``WindowPages``, ``PageAllocator.win``) under the same manager, pins and
copy ladder (``block_tables()`` hands both kinds' tables as one ``[2, rows,
max_pages]`` array). The table is indexed by logical page like the full kind's, but a
slot maps only a RANGE of it: a page wholly older than every query still to
come (``position - window + 1``) is given back when the slot next writes
(``prepare_write``; the table entry reads junk page 0, the window mask hides
it), so a slot never holds more than its ring, ``ring_pages``, whatever its
context. Admission reserves the ring in this kind and the whole exclusive
context in the full kind; a prefix pin holds every full-kind page of its span
and the window-kind pages of its last ``window`` tokens, so a hit reuses an
entry's whole length or nothing (as a state snapshot does) and a capture has
to be taken when the sequence stands AT the span's end. ``decode_kv_pages``
stays the full kind's count; the window kind's is derived (``window_pool_pages``).
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

    __slots__ = ("pin_id", "pages", "last_use", "state_row", "win_pages", "win_first")

    def __init__(self, pin_id: int, pages: list[int]):
        self.pin_id = pin_id
        self.pages = list(pages)
        self.last_use = 0
        self.state_row = -1  # the snapshot row bound to this prefix (a recurrent family), else -1
        # the window kind's pages of the span's last window, and the logical page of the first
        self.win_pages: list[int] = []
        self.win_first = 0


def ring_pages(window: int, max_write: int, page_size: int) -> int:
    """The most window-kind pages a slot maps at once: those that cover the
    window of the oldest query of a dispatch of ``max_write`` positions
    through its newest position, from any row of a page."""
    return -(-(int(window) + int(max_write)) // int(page_size)) + 1


def span_pages(window: int, page_size: int) -> int:
    """The window-kind pages a prefix pin holds: its span's last window, from any row of a page."""
    return -(-int(window) // int(page_size)) + 1


def window_pool_pages(n_slots: int, n_prefix: int, window: int, max_write: int, page_size: int) -> int:
    """The window kind's page count of a deployment: every slot's ring, every
    prefix entry's last window, the junk page and one page of slack."""
    return n_slots * ring_pages(window, max_write, page_size) + n_prefix * span_pages(window, page_size) + 2


class WindowPages:
    """The window page kind's host accounting (module docstring), driven by
    the ``PageAllocator`` that owns it: its own free list, refcounts, block
    tables and reservations; the pins are the owner's. A slot maps the
    logical pages ``[_lo, _hi)``.

    Invariant, as for the full kind: ``free + reclaimable >= sum(reserved)``.
    A slot's reservation is its ring of pages of its OWN (``_owner``):
    allocating one spends it, giving one back refunds it (nobody else maps a
    page a slot owns, so it goes straight to the free list). A page a pin
    holds is nobody's own: a capture hands the slot's pages of the span to
    the pin and refunds the slot, which is refused where the pool could not
    guarantee the refund; a reader maps them without paying and gives them
    back without a refund."""

    def __init__(self, n_pages: int, page_size: int, n_slots: int, pages_per_slot: int, window: int, ring: int):
        if n_pages < ring + 2:
            raise ValueError(f"{n_pages} window-kind pages cannot hold one slot's ring of {ring} (+ junk + slack)")
        self.n_pages, self.page_size, self.n_slots = int(n_pages), int(page_size), int(n_slots)
        self.window, self.ring = int(window), int(ring)
        self.refs = np.zeros(n_pages, np.int32)
        self.refs[0] = 1  # page 0: the junk sink, as in the full kind
        self.pin_count = np.zeros(n_pages, np.int32)
        self._owner = np.full(n_pages, -1, np.int32)
        self._free: list[int] = list(range(n_pages - 1, 0, -1))
        self.block_tables = np.zeros((n_slots, pages_per_slot), np.int32)
        self._lo = np.zeros(n_slots, np.int32)
        self._hi = np.zeros(n_slots, np.int32)
        self._reserved = np.zeros(n_slots, np.int64)
        self.stat_written = 0  # pages a slot allocated (copy-on-write included)
        self.stat_released = 0  # of those, given back before the slot retired
        self.stat_cow_copies = 0
        # called where an allocation finds the free list empty: the owner drops prefix pins until it is not
        self.on_empty = lambda: None

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        """Pages at least one slot maps."""
        return int(np.sum(self.refs[1:] > self.pin_count[1:]))

    def reclaimable(self, exclude=()) -> int:
        mask = (self.pin_count > 0) & (self.refs == self.pin_count)
        return int(mask.sum()) - sum(1 for p in set(exclude) if mask[p])

    def headroom(self, exclude=()) -> int:
        """Pages the pool can still promise: free + reclaimable - reserved."""
        return self.free_pages + self.reclaimable(exclude) - int(self._reserved.sum())

    def slot_pages(self, slot: int) -> list[int]:
        return [int(p) for p in self.block_tables[slot, int(self._lo[slot]) : int(self._hi[slot])]]

    def first_needed(self, position: int) -> int:
        """The logical page of the oldest key a query at ``position`` sees."""
        return max(0, int(position) - self.window + 1) // self.page_size

    # ------------------------------------------------------- the slot's range
    def shared_for(self, pin: PoolPin | None, reuse: int) -> list[int] | None:
        """The pin's pages a hit at ``reuse`` would map, those of ``[reuse -
        window, reuse)``; None where the pin does not hold the whole of that
        window."""
        if pin is None or reuse <= 0:
            return []
        lo, hi = self.first_needed(reuse) - pin.win_first, -(-int(reuse) // self.page_size) - pin.win_first
        if lo < 0 or hi > len(pin.win_pages):
            return None
        return pin.win_pages[lo:hi]

    def admit(self, slot: int, shared: list[int], reuse: int) -> None:
        """Map ``shared`` (``shared_for``'s answer: the pages that end at
        ``reuse``) and reserve the ring (``PageAllocator.try_admit`` checked
        both kinds first)."""
        hi = -(-int(reuse) // self.page_size) if shared else 0
        lo = hi - len(shared)
        self.block_tables[slot, lo:hi] = shared
        self.refs[shared] += 1  # a pin's pages are distinct
        self._lo[slot], self._hi[slot] = lo, hi
        self._reserved[slot] = self.ring

    def _alloc(self, slot: int) -> int:
        if self._reserved[slot] <= 0:
            raise RuntimeError(f"slot {slot} allocating a window-kind page past its ring of {self.ring}")
        if not self._free:
            self.on_empty()
        p = self._free.pop()
        self.refs[p] = 1
        self._owner[p] = slot
        self._reserved[slot] -= 1
        self.stat_written += 1
        return p

    def _drop(self, slot: int, p: int, released: bool) -> None:
        """The slot's reference on page ``p`` goes; a page of its own goes
        back to the free list and refunds the ring."""
        if self._owner[p] == slot:
            self._owner[p] = -1
            self._reserved[slot] += 1
            self.stat_released += released
        self.refs[p] -= 1
        if self.refs[p] == 0:
            self._free.append(p)

    def prepare_write(self, slot: int, start: int, end: int) -> list[tuple[int, int, int]]:
        """Positions [start, end) of ``slot``: give back the pages no query
        from ``start`` on can see, map the pages the write needs, copy-on-write
        a shared one. Returns (src, dst, 1) page copies of this kind."""
        ps, bt = self.page_size, self.block_tables
        lo, hi, first, last = int(self._lo[slot]), int(self._hi[slot]), int(start) // ps, (end - 1) // ps
        if first == last < hi and self.first_needed(start) <= lo and self.refs[bt[slot, last]] == 1:
            return []  # a step inside a page of the slot's own, nothing to give back: the usual round
        keep = min(self.first_needed(start), int(self._hi[slot]))
        for lp in range(int(self._lo[slot]), keep):
            self._drop(slot, int(bt[slot, lp]), released=True)
            bt[slot, lp] = 0
        self._lo[slot] = max(int(self._lo[slot]), keep)
        copies = []
        for lp in range(int(start) // ps, (end - 1) // ps + 1):
            if lp >= self._hi[slot]:
                if self._lo[slot] == self._hi[slot]:  # an empty range starts where the write does
                    self._lo[slot] = self._hi[slot] = lp
                for lpn in range(int(self._hi[slot]), lp + 1):
                    bt[slot, lpn] = self._alloc(slot)
                self._hi[slot] = lp + 1
            elif self.refs[bt[slot, lp]] > 1:
                shared = int(bt[slot, lp])
                fresh = self._alloc(slot)
                copies.append((shared, fresh, 1))
                bt[slot, lp] = fresh
                self.refs[shared] -= 1
                self.stat_cow_copies += 1
        return copies

    def retire(self, slot: int) -> None:
        for lp in range(int(self._lo[slot]), int(self._hi[slot])):
            self._drop(slot, int(self.block_tables[slot, lp]), released=False)
        self.block_tables[slot, :] = 0
        self._lo[slot] = self._hi[slot] = 0
        self._reserved[slot] = 0

    # ------------------------------------------------------------------ pins
    def capture(self, slot: int, length: int, pin: PoolPin) -> bool:
        """Bind the slot's pages of ``[length - window, length)`` to ``pin``;
        False (nothing bound) where the slot no longer maps all of them, or
        the pool could not refund the slot the pages of its own among them."""
        first, last = max(0, int(length) - self.window) // self.page_size, -(-int(length) // self.page_size)
        if first < self._lo[slot] or last > self._hi[slot]:
            return False
        pages = [int(p) for p in self.block_tables[slot, first:last]]
        own = [p for p in pages if self._owner[p] == slot]
        if self.headroom() < len(own):
            return False
        for p in own:
            self._owner[p] = -1
        self._reserved[slot] += len(own)
        for p in pages:
            self.refs[p] += 1
            self.pin_count[p] += 1
        pin.win_pages, pin.win_first = pages, first
        return True

    def drop_pin(self, pin: PoolPin) -> None:
        for p in pin.win_pages:
            self.pin_count[p] -= 1
            self.refs[p] -= 1
            if self.refs[p] == 0:
                self._free.append(p)

    def check(self, pins) -> None:
        """``PageAllocator.check`` for this kind."""
        refs = np.zeros(self.n_pages, np.int64)
        refs[0] = 1
        for s in range(self.n_slots):
            row = self.block_tables[s]
            lo, hi = int(self._lo[s]), int(self._hi[s])
            if row[:lo].any() or row[hi:].any() or not row[lo:hi].all():
                raise AssertionError(f"window kind: slot {s} maps pages outside [{lo}, {hi}) or junk inside")
            if hi - lo > self.ring:
                raise AssertionError(f"window kind: slot {s} maps {hi - lo} pages, above its ring of {self.ring}")
            for p in row[lo:hi]:
                refs[p] += 1
        held = np.zeros(self.n_pages, np.int64)
        for pin in pins:
            for p in pin.win_pages:
                refs[p] += 1
                held[p] += 1
        if not np.array_equal(refs, self.refs) or not np.array_equal(held, self.pin_count):
            raise AssertionError("window kind: refcounts diverged from block tables + pins")
        free = set(self._free)
        if len(free) != len(self._free) or 0 in free:
            raise AssertionError("window kind: double-free, or junk page 0 in the free list")
        for p in range(1, self.n_pages):
            if (self.refs[p] == 0) != (p in free):
                raise AssertionError(f"window kind: page {p} is free and referenced, or neither")
            o = int(self._owner[p])
            if o >= 0 and (self.refs[p] != 1 or p not in self.block_tables[o]):
                raise AssertionError(f"window kind: page {p} is slot {o}'s own and shared or unmapped")
        own = np.bincount(self._owner[self._owner >= 0], minlength=self.n_slots)
        if (own + self._reserved > self.ring).any():
            raise AssertionError("window kind: a slot's own pages + reservation exceed its ring")
        if self.headroom() < 0:
            raise AssertionError("window kind: reservation invariant broken")


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
        self, n_pages: int, page_size: int, n_slots: int, pages_per_slot: int, n_state_rows: int = 0,
        window: tuple[int, int, int] | None = None,
    ):
        """``window`` = (pages, window tokens, ring pages) gives the pool its
        second page kind (``WindowPages``, ``self.win``)."""
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
        self.win = None
        if window is not None:
            self.win = WindowPages(window[0], page_size, n_slots, pages_per_slot, window[1], window[2])
            self.win.on_empty = lambda: self._reclaim_until_free(self.win)
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
            **(
                {
                    "win_free": self.win.free_pages, "win_live": self.win.live_pages,
                    "win_written": self.win.stat_written, "win_released": self.win.stat_released,
                }
                if self.win is not None else {}
            ),
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
        if self.win is not None:
            self.win.check(self._pins.values())

    # ----------------------------------------------------------- admission
    def try_admit(self, slot: int, shared_pages, reuse: int, extra_reserve: int = 0, pin_id: int = -1) -> bool:
        """Admit a sequence into ``slot``: map its matched prefix pages
        (refcount bump — the copy-free share) and reserve its worst-case
        exclusive page need. Returns False — mapping nothing — when the
        pool cannot GUARANTEE the reservation; the caller leaves the
        request queued until retirements free pages.

        ``reuse`` is the matched token span; only its fully-covered pages
        are exempt from the reservation (the partial boundary page will be
        copy-on-written at the first divergent write). ``extra_reserve``
        covers CoW the caller knows is coming (a cache_prefix capture hint
        pinning pages mid-generation). With a window kind, ``pin_id`` names
        the matched entry's pin, whose pages of the last window before
        ``reuse`` are mapped beside the full kind's, and the slot's ring is
        reserved there: both kinds admit, or neither maps anything."""
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
        if self.win is not None:
            pin = self._pins.get(pin_id) if reuse > 0 else None
            win_shared = self.win.shared_for(pin, reuse)
            if win_shared is None or (reuse > 0 and pin is None):
                raise ValueError("the matched entry's pin does not hold the window before reuse (pin_covers)")
            if self.win.headroom(exclude=win_shared) < self.win.ring:
                return False
            self.win.admit(slot, win_shared, reuse)
        for lp, p in enumerate(shared):
            self.block_tables[slot, lp] = p
            self.refs[p] += 1
        self._mapped[slot] = n_map
        self._reserved[slot] = need
        self.stat_pages_shared += n_map
        return True

    def pin_covers(self, pin_id: int, reuse: int) -> bool:
        """Whether a hit at ``reuse`` tokens can be served from this pin: always
        with one page kind; with a window kind, where the pin holds the
        window-kind pages of the whole last window before ``reuse``."""
        if self.win is None:
            return True
        pin = self._pins.get(pin_id)
        return pin is not None and self.win.shared_for(pin, reuse) is not None

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

    def _reclaim_until_free(self, kind=None) -> None:
        """Drop prefix pins, LRU first, until ``kind`` (this allocator, or
        its window kind) has a free page."""
        kind = kind or self
        reclaimed: list[int] = []
        while not kind._free and self._pins:
            # prefer the LRU pin that actually FREES a page (one whose
            # pages include a refs==1 page): dropping a pin whose pages
            # live readers still map would destroy a prefix entry without
            # relieving any pressure. Fall back to plain LRU when no
            # single pin frees anything (e.g. a page held by two pins
            # needs both dropped — still progress).
            freeing = [
                p for p in self._pins.values()
                if any(kind.refs[pg] == 1 for pg in (p.pages if kind is self else p.win_pages))
            ]
            pin = min(freeing or self._pins.values(), key=lambda q: q.last_use)
            self._drop_pin(pin, reclaim=True)
            reclaimed.append(pin.pin_id)
        if reclaimed and self.on_pins_reclaimed is not None:
            self.on_pins_reclaimed(reclaimed)
        if not kind._free:
            raise RuntimeError(
                "kv page pool exhausted with nothing reclaimable — "
                "reservation invariant broken (bug)"
            )

    def prepare_write(self, slot: int, start: int, count: int) -> list[tuple[int, int]]:
        """Make positions [start, start + count) writable by ``slot``:
        allocate not-yet-mapped logical pages and copy-on-write shared
        ones, in both page kinds where there are two (the window kind first
        gives back the pages the write's queries no longer see). Returns the
        (src, dst) page copies, (src, dst, 1) in the window kind, the caller
        MUST dispatch (through the pool's copy ladder) before its write dispatch.
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
        copies: list[tuple] = []
        if self.win is not None:
            # the window kind's pages of the same positions: (src, dst, 1) copies
            copies += self.win.prepare_write(slot, int(start), end)
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
        if self.win is not None:
            self.win.retire(slot)

    # -------------------------------------------------------- prefix pins
    def capture(self, slot: int, length: int) -> PoolPin | None:
        """Pin the pages covering the slot's leading ``length`` tokens as a
        prefix entry — a refcount bump, NO copy (the old capture dispatch
        is gone). Returns None if the span isn't materialized yet, or, with
        a window kind, if the slot has moved past the span's last window."""
        n = self.pages_for(length)
        if n < 1 or n > self._mapped[slot]:
            return None
        pin = PoolPin(self._next_pin, self.slot_pages(slot)[:n])
        if self.win is not None and not self.win.capture(slot, length, pin):
            return None
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
        if n < 1 or n > len(self._free) or self.win is not None:  # a pin of one kind's pages serves no hit here
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
        if self.win is not None:
            self.win.drop_pin(pin)
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
        window: int = 0,
        max_write: int = 0,
        n_prefix: int = 0,
    ):
        """``window`` > 0 (the family's ``decoder_dims``' ``kv_window``) gives
        the pool its second page kind, sized from the slots' rings
        (``max_write``: the most positions one dispatch writes a slot) and
        ``n_prefix`` prefix entries' last windows (``window_pool_pages``)."""
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
        # the window page kind (module docstring): (pages, window, ring) | None
        self._window = None
        if window > 0:
            write = min(int(max_write) or int(cache_ctx), int(cache_ctx))
            self._window = (
                window_pool_pages(self.n_slots, int(n_prefix), window, write, self.page_size),
                int(window), min(ring_pages(window, write, self.page_size), self.pages_per_slot),
            )
        self.alloc = self._new_alloc()
        self.state = self._place(self._kv_zeros())
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
        if self._window is None:
            self._copy_fns = (jax.jit(paged_copy, donate_argnums=(0,), **copy_kw),)
        else:
            # a copy addresses ONE kind's page axis: its half of the state tuple
            # (full planes first), the other half passing through the donation
            half = len(self.state) // 2

            def copy_full(pool, src, dst):
                return paged_copy(pool[:half], src, dst) + tuple(pool[half:])

            def copy_window(pool, src, dst):
                return tuple(pool[:half]) + paged_copy(pool[half:], src, dst)

            self._copy_fns = tuple(jax.jit(f, donate_argnums=(0,), **copy_kw) for f in (copy_full, copy_window))
        buckets, b = [], 1
        while b < self.n_slots:
            buckets.append(b)
            b *= 2
        self.copy_buckets = tuple(buckets) + (self.n_slots,)
        self.stat_copy_dispatches = 0

    @property
    def windowed(self) -> bool:
        """Whether the pool holds the window page kind beside the full one."""
        return self._window is not None

    @property
    def n_window_pages(self) -> int:
        return self._window[0] if self._window is not None else 0

    def _new_alloc(self) -> PageAllocator:
        return PageAllocator(
            self.n_pages, self.page_size, self.n_slots, self.pages_per_slot, self.n_state_rows, self._window
        )

    def _kv_zeros(self) -> tuple:
        n_pages = self.n_pages if self._window is None else (self.n_pages, self._window[0])
        return self._kv_init(self._params, n_pages, self.page_size, self._dtype, self.kv_dtype)

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

    def block_tables(self, slots: np.ndarray | None = None, junk=None):
        """Fresh host copy of the block tables for one dispatch (the jit
        argument must not alias the live allocator state): every slot's
        row (those ``junk`` [n_slots] bool marks as rows of junk page 0), or
        the rows of ``slots`` in their order, a row of junk page 0 for each
        -1 among them (a compact dispatch's padding). With a window kind,
        ONE array ``[2, rows, max_pages]``, the full kind's table then the
        window kind's: one transfer a dispatch, as with one kind."""
        win = self.alloc.win
        bt = self.alloc.block_tables if win is None else np.stack([self.alloc.block_tables, win.block_tables])
        if slots is None:
            bt = bt.copy() if win is None else bt  # np.stack copied already
            if junk is not None:
                bt[..., junk, :] = 0
            return bt
        return np.where(slots[:, None] >= 0, bt[..., slots, :], bt.dtype.type(0))

    def run_copies(self, copies: list[tuple]) -> None:
        """Dispatch the round's CoW page copies through the warmed ladder
        (padding entries copy junk page 0 onto itself), a kind at a time:
        (src, dst) in the full kind, (src, dst, 1) in the window kind."""
        for kind, copy_fn in enumerate(self._copy_fns):
            mine = [c[:2] for c in copies if (len(c) > 2 and c[2]) == kind]
            i = 0
            while i < len(mine):
                batch = mine[i : i + self.copy_buckets[-1]]
                bucket = next(b for b in self.copy_buckets if b >= len(batch))
                src = np.zeros(bucket, np.int32)
                dst = np.zeros(bucket, np.int32)
                for j, (s, d) in enumerate(batch):
                    src[j] = s
                    dst[j] = d
                self.state = copy_fn(self.state, src, dst)
                self.stat_copy_dispatches += 1
                i += len(batch)

    def warmup(self) -> None:
        """Compile the copy ladder (page0 -> page0 self-copies touch no
        live bytes)."""
        for copy_fn in self._copy_fns:
            for b in self.copy_buckets:
                self.state = copy_fn(self.state, np.zeros(b, np.int32), np.zeros(b, np.int32))

    def compile_count(self) -> int:
        return sum(f._cache_size() for f in self._copy_fns)

    def reset(self) -> None:
        """Post-failure recovery: the state tuple was donated into a call
        that raised, so its buffers may be invalidated — reallocate, and
        drop every host mapping with it."""
        on_reclaimed = self.alloc.on_pins_reclaimed
        self.alloc = self._new_alloc()
        self.alloc.on_pins_reclaimed = on_reclaimed
        self.state = self._place(self._kv_zeros())
        self.recurrent = self._recurrent_zeros()
