"""Host-side page-pool allocator for the paged serving KV cache.

A ring a slot would give every slot a fixed
``(W, kv_heads, head_dim)`` arena regardless of request length: a
12-token question strands the same HBM as a window-filling novel, and
N users sharing one system prompt each pay full prefill AND full
residency. The paged layout (ROADMAP item 2; the same never-materialize
discipline as memory-efficient array redistribution, arXiv 2112.01075)
splits the arena into fixed-size pages of ``PAGE_TOKENS`` ring slots
and lets requests hold only the pages they can ever touch:

* **Free-list allocation.** Pages are interchangeable fixed-size
  blocks, so allocation is a stack pop and "defragmentation" is a
  non-problem — there is no external fragmentation to compact, which
  is the reason the pool has no defrag pass.
* **Refcounts + copy-on-write.** A page may back several slots at
  once (a shared prompt prefix). Writers never mutate a shared page:
  the scheduler's pre-tick pass copies any page a slot is about to
  write while ``refcount > 1`` (one device-side page copy), so a
  reader's bytes are immutable for as long as it holds its reference.
* **Prefix hash table.** Admission hashes the prompt's page-aligned
  prefix with a CHAINED digest (page j's key covers ``prompt[:(j+1) *
  PAGE_TOKENS]`` — K/V at position p depend on every token <= p, so
  the chain is the exact content determinant) and shares already-
  resident pages by bumping refcounts, skipping their prefill
  entirely. Registration is first-wins; a page leaves the table when
  it is freed or when its (sole) owner is about to overwrite it.

This module is deliberately jax-free (numpy + hashlib): the pool is
pure host bookkeeping, and the device-side page arrays, gathers, and
copies live in :mod:`.serving`. ``NULL_PAGE`` (page 0) is reserved:
page-table entries that no valid ring slot can reach point at it, so
stray writes from retired-but-still-ticking rows land in bytes nothing
ever reads unmasked.
"""

from __future__ import annotations

import hashlib

import numpy as np

__all__ = [
    "NULL_PAGE",
    "PagePool",
    "PagePoolExhausted",
    "prefix_page_digests",
]

NULL_PAGE = 0


class PagePoolExhausted(RuntimeError):
    """No free page satisfies the allocation. Admission treats this as
    "wait for retirements"; a mid-decode raise means the admission-time
    budget accounting is wrong (a bug, not an operating condition)."""


def prefix_page_digests(prompt, page_tokens: int,
                        max_pages: int | None = None) -> list[bytes]:
    """Chained page-aligned prefix digests of an int token sequence:
    ``digests[j]`` keys the content of ring page ``j`` and covers
    ``prompt[:(j+1) * page_tokens]`` (K/V at a position depend on the
    whole prefix through attention, so nothing shorter determines the
    page's bytes). Only FULLY covered pages get a digest; ``max_pages``
    caps the walk (the scheduler passes the ring's page count — pages
    past the window hold wrapped content and are never shareable)."""
    toks = np.ascontiguousarray(np.asarray(prompt, np.int32).reshape(-1))
    n = toks.size // int(page_tokens)
    if max_pages is not None:
        n = min(n, int(max_pages))
    out: list[bytes] = []
    h = hashlib.sha256()
    for j in range(n):
        h.update(toks[j * page_tokens:(j + 1) * page_tokens].tobytes())
        out.append(h.digest())
    return out


class PagePool:
    """Free-list page allocator with refcounts and a prefix-share hash
    table. Pure host state — single-threaded by design (it lives
    inside the scheduler's tick loop, like the rest of the host-side
    bookkeeping).

    Reservation: shared pages are only ever WRITTEN by a request that
    wraps its ring (decode writes land past the prompt until position
    W), and each such write needs one COW copy. Every :meth:`share`
    that can end in a COW — the sharer wraps, or the page's owner does
    (the page is ``volatile``) — therefore attaches one reserved page
    to the shared page. :meth:`can_alloc` admits only against ``free -
    reserved`` and :meth:`cow_alloc` consumes the page's attached
    reservation, which is what makes :class:`PagePoolExhausted`
    unreachable mid-decode regardless of WHICH holder writes first.
    Reservations a retirement strands (the sharer never wrapped)
    release automatically: a page can never carry more reservations
    than ``refcount - 1`` future COWs.
    """

    def __init__(self, n_pages: int, page_tokens: int):
        if n_pages < 2:
            raise ValueError(
                f"n_pages must be >= 2 (page 0 is the reserved null "
                f"page), got {n_pages}"
            )
        if page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
        self.n_pages = int(n_pages)
        self.page_tokens = int(page_tokens)
        # LIFO free list: recently freed pages are re-used first (their
        # bytes are most likely still resident in whatever cache level)
        self._free: list[int] = list(range(self.n_pages - 1, 0, -1))
        self._ref = np.zeros(self.n_pages, np.int64)
        self._ref[NULL_PAGE] = 1  # permanently held, never allocatable
        self._digest_to_page: dict[bytes, int] = {}
        self._page_digest: dict[int, bytes] = {}
        # per-page count of CURRENT holders whose request wraps its
        # ring (and will therefore overwrite the page): sharing a page
        # with any wrapper needs a COW reservation. A count, not a
        # sticky flag — when the last wrapping holder retires (or COWs
        # away), later sharers stop paying reservations the page can
        # no longer consume (review r11: a sticky flag collapsed the
        # shared-capacity win once the registering owner retired).
        self._wrappers: dict[int, int] = {}
        # per-page attached COW reservations + their total
        self._page_reserved: dict[int, int] = {}
        self._reserved = 0
        # lifetime counters, exported by the scheduler's instruments as
        # serving_prefix_share_hits_total / serving_cow_copies_total
        self.share_hits = 0
        self.cow_copies = 0
        # fleet-cache hooks (cache/ package, opt-in): called with the
        # digest whenever a prefix page enters or leaves the share
        # table, so a fleet-level directory can mirror THIS pool's
        # registrations without polling. None = dark (no per-call cost
        # beyond the `is not None` check); the pool itself never knows
        # what is on the other end.
        self.register_hook = None
        self.unregister_hook = None

    # -- capacity -------------------------------------------------------

    @property
    def free(self) -> int:
        """Pages on the free list (null page excluded)."""
        return len(self._free)

    @property
    def used(self) -> int:
        """Allocated pages (null page excluded)."""
        return self.n_pages - 1 - len(self._free)

    @property
    def reserved(self) -> int:
        """Pages promised to admitted requests for future COW copies."""
        return self._reserved

    def can_alloc(self, n: int, *, reserve: int = 0) -> bool:
        """Would ``n`` allocations plus ``reserve`` new reservations
        fit without eating into existing reservations?"""
        return n + reserve + self._reserved <= len(self._free)

    # -- alloc / refcount ----------------------------------------------

    def alloc(self) -> int:
        """Pop a free page (refcount 1). Never dips into reserved
        pages — those belong to admitted requests' future COWs."""
        if self._reserved >= len(self._free):
            raise PagePoolExhausted(
                f"no unreserved free pages ({len(self._free)} free, "
                f"{self._reserved} reserved, {self.used} used of "
                f"{self.n_pages - 1})"
            )
        pid = self._free.pop()
        self._ref[pid] = 1
        return pid

    def incref(self, pid: int) -> None:
        if self._ref[pid] < 1 or pid == NULL_PAGE:
            raise ValueError(f"incref of unallocated page {pid}")
        self._ref[pid] += 1

    def decref(self, pid: int, *, wrapper: bool = False) -> bool:
        """Drop one reference; returns True when the page was freed
        (and unregistered from the prefix table). ``wrapper=True``
        means the LEAVING holder's request wraps its ring — the page's
        wrapper count drops with it, so sharers stop reserving against
        a writer that no longer exists. Reservations the drop strands
        — a page can carry at most ``refcount - 1`` future COWs —
        release automatically."""
        if pid == NULL_PAGE or self._ref[pid] < 1:
            raise ValueError(f"decref of unallocated page {pid}")
        self._ref[pid] -= 1
        if wrapper:
            n = self._wrappers.get(pid, 0)
            if n > 1:
                self._wrappers[pid] = n - 1
            else:
                self._wrappers.pop(pid, None)
        if self._ref[pid] > 0:
            self._clamp_reservation(pid)
            return False
        self._release_reservation(pid)
        self._wrappers.pop(pid, None)
        d = self._page_digest.pop(pid, None)
        if d is not None:
            self._digest_to_page.pop(d, None)
            if self.unregister_hook is not None:
                self.unregister_hook(d)
        self._free.append(pid)
        return True

    def refcount(self, pid: int) -> int:
        return int(self._ref[pid])

    def _clamp_reservation(self, pid: int) -> None:
        cap = int(self._ref[pid]) - 1
        have = self._page_reserved.get(pid, 0)
        if have > cap:
            self._reserved -= have - cap
            if cap:
                self._page_reserved[pid] = cap
            else:
                self._page_reserved.pop(pid, None)

    def _release_reservation(self, pid: int) -> None:
        self._reserved -= self._page_reserved.pop(pid, 0)

    # -- prefix sharing + copy-on-write ---------------------------------

    def lookup(self, digest: bytes) -> int | None:
        """Resident page holding this prefix digest, or None."""
        return self._digest_to_page.get(digest)

    def registered(self, pid: int) -> bool:
        """Is ``pid`` published in the prefix table? The validity
        witness the QoS cold-page cache keys on: registration drops
        the moment a page's bytes stop matching its digest
        (:meth:`note_write`, COW retarget, free), so a registered
        sole-held page is safe to keep resident for future sharers."""
        return pid in self._page_digest

    def digest_of(self, pid: int) -> bytes | None:
        """The prefix digest ``pid`` is registered under, or None. The
        spill path reads this BEFORE the freeing decref — a registered
        page's bytes still match its digest, which is what makes the
        page's content portable to the host-DRAM tier."""
        return self._page_digest.get(pid)

    def is_volatile(self, pid: int) -> bool:
        """Will a CURRENT holder eventually overwrite this page (some
        holder's request wraps its ring)? Sharing a volatile page
        always needs a COW reservation, however short the sharer."""
        return self._wrappers.get(pid, 0) > 0

    def share_needs_reserve(self, pid: int, sharer_wraps: bool) -> bool:
        """Does sharing ``pid`` require reserving a COW page? Yes when
        any party can ever write it: the sharer wraps, or a current
        holder does."""
        return sharer_wraps or self.is_volatile(pid)

    def share(self, pid: int, *, reserve: bool,
              wrapper: bool = False) -> None:
        """Take a reference on a prefix page (the admission hit path);
        ``reserve=True`` attaches one COW reservation to the page —
        whichever holder writes it first consumes the reservation via
        :meth:`cow_alloc`, so the copy can never fail. ``wrapper=True``
        records that the SHARER's request wraps (it joins the page's
        wrapper count like a wrapping owner does at registration)."""
        self.incref(pid)
        if wrapper:
            self._wrappers[pid] = self._wrappers.get(pid, 0) + 1
        if reserve:
            if self._reserved >= len(self._free):
                # callers gate on can_alloc first; this is the
                # belt-and-braces invariant guard
                raise PagePoolExhausted(
                    "cannot attach a COW reservation: all free pages "
                    "are already reserved"
                )
            self._page_reserved[pid] = self._page_reserved.get(pid, 0) + 1
            self._reserved += 1
        self.share_hits += 1

    def cow_alloc(self, pid: int) -> int:
        """Allocate the destination page for a copy-on-write of
        ``pid``, consuming the page's attached reservation when one
        exists (the caller then copies bytes, retargets its table
        entry, and decrefs ``pid``)."""
        have = self._page_reserved.get(pid, 0)
        if have:
            if have == 1:
                self._page_reserved.pop(pid)
            else:
                self._page_reserved[pid] = have - 1
            self._reserved -= 1
        elif self._reserved >= len(self._free):
            raise PagePoolExhausted(
                f"COW of page {pid} has no reservation and all free "
                "pages are reserved (admission accounting bug)"
            )
        if not self._free:
            raise PagePoolExhausted(
                f"no free pages ({self.used} used of {self.n_pages - 1})"
            )
        new = self._free.pop()
        self._ref[new] = 1
        self.cow_copies += 1
        return new

    def register(self, digest: bytes, pid: int, *,
                 volatile: bool = False) -> None:
        """Publish ``pid`` as the resident page for ``digest``.
        First-wins: an existing mapping (another slot registered the
        identical prefix first) is kept, and a page already registered
        under another digest keeps its original key. ``volatile=True``
        marks the page as eventually-overwritten by its owner (see
        :meth:`is_volatile`)."""
        if self._ref[pid] < 1:
            raise ValueError(f"register of unallocated page {pid}")
        if digest in self._digest_to_page or pid in self._page_digest:
            return
        self._digest_to_page[digest] = pid
        self._page_digest[pid] = digest
        if volatile:
            self._wrappers[pid] = self._wrappers.get(pid, 0) + 1
        if self.register_hook is not None:
            self.register_hook(digest, pid)

    def note_write(self, pid: int) -> None:
        """A sole owner is about to overwrite ``pid`` (ring wrap): its
        registered prefix digest — if any — no longer describes its
        future bytes, so drop it from the share table. Shared pages
        never reach here (the scheduler COWs them instead)."""
        d = self._page_digest.pop(pid, None)
        if d is not None:
            self._digest_to_page.pop(d, None)
            if self.unregister_hook is not None:
                self.unregister_hook(d)

    # -- invariants (tests + postmortems) -------------------------------

    def check(self) -> None:
        """Structural invariants: free + used == n_pages - 1, free
        pages have refcount 0, registered/volatile pages are live,
        per-page reservations fit ``refcount - 1`` and sum to the
        total, which never exceeds the free list."""
        if len(self._free) + self.used != self.n_pages - 1:
            raise AssertionError("free/used accounting drifted")
        if len(set(self._free)) != len(self._free):
            raise AssertionError("double-free: duplicate page on free list")
        for pid in self._free:
            if self._ref[pid] != 0:
                raise AssertionError(f"free page {pid} has refcount "
                                     f"{self._ref[pid]}")
        for d, pid in self._digest_to_page.items():
            if self._ref[pid] < 1:
                raise AssertionError(f"registered page {pid} is free")
            if self._page_digest.get(pid) != d:
                raise AssertionError("digest tables disagree")
        for pid, n in self._wrappers.items():
            if self._ref[pid] < 1:
                raise AssertionError(f"volatile page {pid} is free")
            if n < 1 or n > self._ref[pid]:
                raise AssertionError(
                    f"page {pid} counts {n} wrappers at refcount "
                    f"{self._ref[pid]}"
                )
        for pid, n in self._page_reserved.items():
            if n < 1 or n > self._ref[pid] - 1:
                raise AssertionError(
                    f"page {pid} carries {n} reservations at refcount "
                    f"{self._ref[pid]}"
                )
        if self._reserved != sum(self._page_reserved.values()):
            raise AssertionError("reservation totals drifted")
        if self._reserved > len(self._free):
            raise AssertionError("reservations exceed the free list")

    def stats(self) -> dict:
        return {
            "n_pages": self.n_pages - 1,
            "free": self.free,
            "used": self.used,
            "reserved": self._reserved,
            "registered_prefix_pages": len(self._digest_to_page),
            "share_hits": self.share_hits,
            "cow_copies": self.cow_copies,
        }
