"""Paged KV-cache bookkeeping: Attn-PIM bank-row allocator + block tables.

PAPI's Attn-PIM units hold the KV cache in fixed-size DRAM banks (§5.2/§5.3);
the natural allocation quantum is one bank *row* — what this module calls a
page.  Instead of pre-reserving a dense `(slots, capacity, ...)` slab per
request (worst-case provisioning: every request pays for the longest), the
engine maps each request's KV onto physical pages through a block table:

  logical token position  t  of slot  s
      -> logical block    t // page_size
      -> physical page    block_tables[s, t // page_size]
      -> bank row offset  t %  page_size

Three pieces live here, all host-side (the device only ever sees the
`[max_slots, max_blocks]` int32 block-table array):

  * `PageAllocator` — a LIFO free list with **admission reservations**: a
    request is admitted only if its whole worst-case page budget
    (prompt + max_new_tokens + speculative window) is available, but pages
    are *mapped* lazily as the sequence grows.  Reserved-but-unmapped pages
    are subtracted from the headroom every admission checks, so a grow()
    can never fail mid-flight and a speculative rewind can safely return
    pages to the free list (the reservation keeps them claimable).
  * `BlockTables` — the host mirror of the device block tables.  Unmapped
    entries point at the shared GARBAGE_PAGE (see below) and the device
    array is re-materialized only when a row actually changed.
  * `PagedKVManager` — the engine-facing facade tying both together and
    translating token counts to page counts.

The garbage page
----------------
Physical page 0 is permanently reserved and never allocated.  Idle slots in
the fixed-shape decode batch still execute (their outputs are masked on the
host — the standard padded-batch trade), and their KV writes must land
*somewhere* that no live request owns.  Every unmapped block-table entry
points at page 0, so garbage writes collide harmlessly there; live requests
never reference it (entries past a request's mapped prefix are either
never read by the paged kernel, which reads only live pages, or masked by
`cache_len`).

Invariants (property-tested in `tests/test_kv_pages.py`):
  * a physical page is never mapped to two owners at once;
  * free + mapped partitions the usable pool exactly;
  * reserved-unmapped never exceeds the free count (grow() cannot fail);
  * after all owners finish, the pool is back to all-free.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

import numpy as np

GARBAGE_PAGE = 0


def pages_for(tokens: int, page_size: int) -> int:
    """Number of pages covering `tokens` KV entries (>= 1 page per owner so
    a mapped row always exists for the first write)."""
    return max(1, -(-int(tokens) // page_size))


@dataclasses.dataclass(frozen=True)
class PageStats:
    """Pool-level snapshot surfaced per iteration via `IterStats`."""
    num_pages: int            # usable pool size (garbage page excluded)
    page_size: int
    free: int                 # pages on the free list right now
    mapped: int               # pages currently holding live KV
    reserved_unmapped: int    # admission-reserved, not yet mapped
    watermark: int            # peak mapped page count over the pool lifetime
    fragmentation: float      # 1 - used_tokens / (mapped * page_size)


class PageAllocator:
    """Free-list page allocator with admission reservations.

    Pages are plain ints in `[first_page, first_page + num_pages)`.  The
    free list is LIFO — recently-freed (cache-warm) pages are reused first.

    The reservation model: `admit(owner, budget, initial)` maps `initial`
    pages now and records `budget - initial` as reserved-unmapped.  The
    admission headroom is `free - total_reserved_unmapped`, so once a
    request is in, its `grow()` calls draw from its own reservation and are
    guaranteed to succeed; `rewind()` puts mapped pages back on the free
    list but *keeps* the reservation, so speculative rollback can never
    strand a request (the pages it returns stay claimable by it alone).
    """

    def __init__(self, num_pages: int, page_size: int, *, first_page: int = 0):
        assert num_pages >= 1 and page_size >= 1, (num_pages, page_size)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.first_page = int(first_page)
        # LIFO: low page ids come off the stack first (reversed range)
        self._free: list[int] = list(
            range(first_page + num_pages - 1, first_page - 1, -1))
        self._mapped: dict[int, list[int]] = {}
        self._reserved: dict[int, int] = {}
        self.watermark = 0

    # ----------------------------------------------------------- accounting
    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def mapped_count(self) -> int:
        return sum(len(p) for p in self._mapped.values())

    @property
    def reserved_unmapped(self) -> int:
        return sum(self._reserved.values())

    @property
    def available(self) -> int:
        """Pages an admission may still claim (free minus already-promised)."""
        return len(self._free) - self.reserved_unmapped

    def owners(self) -> list[int]:
        return list(self._mapped)

    def pages_of(self, owner: int) -> list[int]:
        return list(self._mapped.get(owner, ()))

    # ------------------------------------------------------------ lifecycle
    def can_admit(self, budget_pages: int) -> bool:
        return 0 < budget_pages <= self.available

    def admit(self, owner: int, budget_pages: int,
              initial_pages: int) -> list[int]:
        """Reserve `budget_pages` for `owner`, mapping `initial_pages` now."""
        assert owner not in self._mapped and owner not in self._reserved, owner
        assert 1 <= initial_pages <= budget_pages, (initial_pages, budget_pages)
        if not self.can_admit(budget_pages):
            raise MemoryError(
                f"admit({owner}): {budget_pages} pages > {self.available} "
                "available")
        pages = [self._free.pop() for _ in range(initial_pages)]
        self._mapped[owner] = pages
        self._reserved[owner] = budget_pages - initial_pages
        self.watermark = max(self.watermark, self.mapped_count)
        return list(pages)

    def grow(self, owner: int, n_pages: int) -> list[int]:
        """Map `n_pages` more for `owner`.  Draws from the owner's
        reservation first (guaranteed present), then — e.g. when the engine
        widens the speculative window mid-flight — from the uncommitted
        headroom; only the latter can fail."""
        if n_pages <= 0:
            return []
        assert owner in self._mapped, owner
        over = n_pages - self._reserved[owner]
        if over > 0 and over > self.available:
            raise MemoryError(
                f"grow({owner}, {n_pages}): {over} pages beyond the "
                f"reservation, {self.available} uncommitted available")
        pages = [self._free.pop() for _ in range(n_pages)]
        self._mapped[owner].extend(pages)
        self._reserved[owner] = max(0, self._reserved[owner] - n_pages)
        self.watermark = max(self.watermark, self.mapped_count)
        return list(pages)

    def reserve_more(self, owner: int, n_pages: int) -> None:
        """Adjust `owner`'s unmapped reservation by `n_pages` (the engine
        re-budgets live requests when the speculative window changes
        mid-flight).  Widening draws on the uncommitted headroom and fails
        if it isn't there; shrinking clamps at zero — an owner whose mapped
        pages already exceed the new budget simply has nothing reserved."""
        assert owner in self._mapped, owner
        if n_pages > 0:
            if n_pages > self.available:
                raise MemoryError(
                    f"reserve_more({owner}, {n_pages}): only "
                    f"{self.available} uncommitted pages available")
            self._reserved[owner] += n_pages
        else:
            self._reserved[owner] = max(0, self._reserved[owner] + n_pages)

    def rewind(self, owner: int, keep_pages: int) -> list[int]:
        """Return mapped pages beyond the first `keep_pages` to the free
        list, **keeping the reservation** (speculative rollback: the pages
        stay claimable by this owner).  Returns the freed page ids so the
        caller can scrub its block-table row."""
        assert owner in self._mapped, owner
        row = self._mapped[owner]
        keep_pages = max(1, keep_pages)       # never unmap the first page
        if keep_pages >= len(row):
            return []
        freed = row[keep_pages:]
        del row[keep_pages:]
        self._reserved[owner] += len(freed)
        self._free.extend(reversed(freed))    # LIFO: rewound pages reused next
        return list(freed)

    def finish(self, owner: int) -> list[int]:
        """Release everything `owner` holds — mapped pages and reservation."""
        pages = self._mapped.pop(owner, [])
        self._reserved.pop(owner, None)
        self._free.extend(reversed(pages))
        return list(pages)

    # -------------------------------------------------------------- queries
    def fragmentation(self, used_tokens: int) -> float:
        """Internal fragmentation: share of mapped bank rows holding no live
        token (tail-of-page waste).  0.0 when nothing is mapped."""
        cap = self.mapped_count * self.page_size
        if cap == 0:
            return 0.0
        return 1.0 - min(int(used_tokens), cap) / cap

    def stats(self, used_tokens: int = 0) -> PageStats:
        return PageStats(
            num_pages=self.num_pages,
            page_size=self.page_size,
            free=self.free_count,
            mapped=self.mapped_count,
            reserved_unmapped=self.reserved_unmapped,
            watermark=self.watermark,
            fragmentation=self.fragmentation(used_tokens),
        )

    def snapshot(self) -> dict:
        """Plain-dict state dump for diagnostics: the structured engine
        errors (`EngineStallError`, `AllocatorInvariantError`) attach this
        so a post-mortem can see exactly who held what."""
        return {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "free": self.free_count,
            "mapped": {owner: list(row)
                       for owner, row in self._mapped.items()},
            "reserved": dict(self._reserved),
            "available": self.available,
            "watermark": self.watermark,
        }

    def check(self) -> None:
        """Assert the pool invariants (used by the property tests)."""
        mapped = [p for row in self._mapped.values() for p in row]
        assert len(mapped) == len(set(mapped)), "page double-mapped"
        assert not (set(mapped) & set(self._free)), "mapped page on free list"
        assert len(mapped) + len(self._free) == self.num_pages, (
            "pages leaked", len(mapped), len(self._free), self.num_pages)
        assert self.reserved_unmapped <= len(self._free), (
            "reservation exceeds free pool — grow() could fail")
        lo, hi = self.first_page, self.first_page + self.num_pages
        assert all(lo <= p < hi for p in mapped + self._free)


class BlockTables:
    """Host mirror of the device block tables: `[max_slots, max_blocks]`
    int32 physical page ids.  Unmapped entries hold GARBAGE_PAGE.  The
    device array is rebuilt lazily, only after a mutation."""

    def __init__(self, max_slots: int, max_blocks: int):
        self.max_slots, self.max_blocks = int(max_slots), int(max_blocks)
        self.host = np.full((max_slots, max_blocks), GARBAGE_PAGE, np.int32)
        self._device = None

    def set_row(self, slot: int, pages: Iterable[int]) -> None:
        pages = list(pages)
        assert len(pages) <= self.max_blocks, (len(pages), self.max_blocks)
        self.host[slot, :len(pages)] = pages
        self.host[slot, len(pages):] = GARBAGE_PAGE
        self._device = None

    def clear_row(self, slot: int) -> None:
        self.host[slot, :] = GARBAGE_PAGE
        self._device = None

    def device(self):
        """The jnp array the jitted steps consume (cached until dirty)."""
        if self._device is None:
            import jax.numpy as jnp
            self._device = jnp.asarray(self.host)
        return self._device


class PagedKVManager:
    """Engine-facing facade: token-count API over the allocator + tables.

    One manager serves both the target and (when speculating) the draft
    cache: the draft's KV lives at the same logical positions, so both
    caches index their own page pools through the SAME block tables —
    `page_size` and `num_pages` are shared geometry, the page *contents*
    (k/v arrays) are per-model.
    """

    def __init__(self, *, num_pages: int, page_size: int, max_slots: int,
                 max_blocks: int | None = None):
        usable = int(num_pages) - 1          # page 0 = garbage page
        assert usable >= 1, f"num_pages={num_pages} leaves no usable page"
        if max_blocks is None:
            max_blocks = usable
        # optional telemetry sink: the engine attaches its Tracer here
        # (under debug_invariants or an opted-in tracer — per-call page
        # events are the trace's highest-volume kind) and every map/unmap/
        # reserve below emits a typed event.  None = zero-cost.
        self.tracer = None
        self.page_size = int(page_size)
        # a table wider than the pool would let admission accept a budget
        # the allocator can never satisfy even when fully drained — the
        # request would defer forever (livelock, since deferral blocks the
        # queue waiting for pages that do not exist)
        self.max_blocks = min(int(max_blocks), usable)
        self.alloc = PageAllocator(usable, page_size, first_page=1)
        self.tables = BlockTables(max_slots, self.max_blocks)

    @property
    def max_context(self) -> int:
        """Longest sequence one request can hold (table width bound)."""
        return self.max_blocks * self.page_size

    def pages_for(self, tokens: int) -> int:
        return pages_for(tokens, self.page_size)

    def can_admit(self, budget_tokens: int) -> bool:
        need = self.pages_for(budget_tokens)
        return need <= self.max_blocks and self.alloc.can_admit(need)

    def admit(self, slot: int, budget_tokens: int,
              initial_tokens: int) -> None:
        budget = self.pages_for(budget_tokens)
        pages = self.alloc.admit(slot, budget,
                                 self.pages_for(initial_tokens))
        self.tables.set_row(slot, pages)
        if self.tracer is not None:
            self.tracer.emit("page_reserve", slot=slot, budget_pages=budget,
                             mapped_pages=len(pages))

    def coverage(self, slot: int) -> int:
        """Tokens the slot's mapped pages can hold right now.  Under the
        continuous-batching serve loop this is the live-pressure frontier:
        admission maps only chunk 0's pages and each later wave `ensure()`s
        its own chunk, so coverage trails the reserved budget until the
        prompt finishes prefilling (the pool watermark follows demand, not
        the worst case)."""
        return len(self.alloc.pages_of(slot)) * self.page_size

    def ensure(self, slot: int, tokens: int) -> int:
        """Grow slot coverage to `tokens`; returns pages newly mapped."""
        have = len(self.alloc.pages_of(slot))
        need = self.pages_for(tokens)
        if need <= have:
            return 0
        self.alloc.grow(slot, need - have)
        self.tables.set_row(slot, self.alloc.pages_of(slot))
        if self.tracer is not None:
            self.tracer.emit("page_map", slot=slot, pages=need - have)
        return need - have

    def rewind(self, slot: int, tokens: int) -> int:
        """Return pages past `tokens` coverage to the pool (speculative
        rollback); returns pages freed."""
        freed = self.alloc.rewind(slot, self.pages_for(tokens))
        if freed:
            self.tables.set_row(slot, self.alloc.pages_of(slot))
            if self.tracer is not None:
                self.tracer.emit("page_unmap", slot=slot, pages=len(freed),
                                 cause="rewind")
        return len(freed)

    def release(self, slot: int) -> int:
        """Drop everything `slot` holds — mapped pages AND the unmapped
        reservation — and scrub its block-table row back to the garbage
        page.  This is the preemption/cancel/timeout drain as much as the
        normal finish: a preempted request re-enters admission later as a
        fresh `admit()` with a fresh reservation, and the scrubbed row
        guarantees its old pages can be re-issued to any other slot without
        aliasing."""
        freed = self.alloc.finish(slot)
        self.tables.clear_row(slot)
        if self.tracer is not None and freed:
            self.tracer.emit("page_unmap", slot=slot, pages=len(freed),
                             cause="release")
        return len(freed)

    def stats(self, used_tokens: int = 0) -> PageStats:
        return self.alloc.stats(used_tokens)
