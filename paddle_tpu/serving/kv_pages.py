"""Paged KV-cache bookkeeping — the host side of continuous batching.

XLA executables are fixed-shape, so the decode engine's KV cache is a
static pool ``[n_layers, n_pages, page_size, kv_heads, head_dim]`` and
all dynamism lives in *integer indices*: each active slot owns a set of
pages, listed in a per-slot page TABLE that is fed to the decode-step
program every dispatch. Joining a batch is allocating pages and writing
a table row; leaving is returning the pages. Nothing about request
churn ever changes a traced shape (the vLLM PagedAttention idea, under
this repo's one-executable-per-program discipline).

Page 0 is reserved as the **null page**: inactive slots point every
table entry at it, so their (discarded) lockstep writes land somewhere
harmless, and the attention length mask guarantees it is never read
back into a real row. Freed pages are NOT zeroed — the mask already
makes stale contents unobservable (pinned by test: a request reusing a
retired request's pages is bit-identical to running it alone); the
allocator only enforces the integer invariants (no double alloc, no
double free, exhaustion is a typed shed).

A model may keep caches of more than one KIND, and a kind is how long
an entry lives. ``sequence``, the kind every model has: a page for every
``page_size`` positions of the request, held until it retires. ``window``
(a model with sliding-window attention layers, models/hybrid_moe.py): a
RING of a few pages a request, position p in ring page ``(p //
page_size) % ring pages``, so a request holds its window's worth of
pages however long it grows, and a position that falls out of the window
is overwritten where it lies (the engine counts each such turn of a ring
page in ``window_pages_recycled_total``). ``state`` (a model with
state-space layers, models/hybrid_ssm.py): ONE entry a request for the
request's life, whatever its length: the layer's recurrent state, which no
position indexes; a "page" of this kind is an entry, and page 0 the null
entry that rows which are not live read and write. UNLIKE A PAGE OF THE
OTHER KINDS, A REUSED ENTRY'S OLD CONTENTS ARE OBSERVABLE: no length mask
hides them, so it is the programs that start a request (a whole-prompt
prefill, a prompt's first chunk) which begin from zeros without reading
the entry, and the engine counts those dispatches
(``state_resets_total``). The pools of two kinds have
pages of different shapes (other layers, other head counts), so each
kind has its own page ids, its own null page 0 and its own free list,
under ONE allocator that keeps the integer invariants for each:
``add_kind`` declares one, and ``alloc`` / ``free`` / the capacity
queries take ``kind=`` (the ``sequence`` kind where it is not given, so
a model with one kind never names it).

Pure host-side integers: no jax, no numpy, trivially unit-testable.
"""
import heapq

from .batching import QueueFullError

__all__ = ["PagesExhaustedError", "PageAllocator"]


class PagesExhaustedError(QueueFullError):
    """The page pool cannot satisfy an allocation. Subclasses
    QueueFullError deliberately: to a client this is the same load-shed
    contract — back off and retry (or the request can NEVER fit, which
    submit() rejects up front)."""


class PageAllocator:
    """Fixed pool of ``n_pages`` KV pages of ``page_size`` positions.

    Page 0 is the reserved null page and is never handed out; the
    usable pool is pages 1..n_pages-1. ``alloc`` returns pages in
    ascending order (determinism for tests), ``free`` returns them.
    ``n_pages`` are the ``sequence`` kind's; ``add_kind`` declares a
    further cache kind with a pool of its own (see the module's
    docstring). ``n_pages``, ``usable_pages``, ``available`` and
    ``in_use`` speak of the ``sequence`` kind, ``*_of(kind)`` of any.
    """

    SEQUENCE = "sequence"

    def __init__(self, n_pages, page_size):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = int(page_size)
        # a kind's free pages twice: the set answers "is it free", the
        # heap hands out the lowest without sorting the pool (a row that
        # takes its pages as it writes them asks every few dispatches)
        self._n_pages, self._free_of, self._heap_of = {}, {}, {}
        self.add_kind(self.SEQUENCE, n_pages)

    def add_kind(self, kind, n_pages):
        """Declare cache kind ``kind`` with ``n_pages`` pages of its own
        (page 0 its null page)."""
        if kind in self._n_pages:
            raise ValueError(f"cache kind {kind!r} is declared already")
        if n_pages < 2:
            raise ValueError(
                f"n_pages must be >= 2 (page 0 is the reserved null "
                f"page), got {n_pages}")
        self._n_pages[kind] = int(n_pages)
        self._free_of[kind] = set(range(1, int(n_pages)))
        self._heap_of[kind] = list(range(1, int(n_pages)))

    @property
    def kinds(self):
        return tuple(self._n_pages)

    @property
    def n_pages(self):
        return self._n_pages[self.SEQUENCE]

    @property
    def _free(self):
        return self._free_of[self.SEQUENCE]

    # -- capacity queries ------------------------------------------------
    def usable_of(self, kind):
        """Total allocatable pages of ``kind`` (its pool minus the null
        page)."""
        return self._n_pages[kind] - 1

    def available_of(self, kind):
        return len(self._free_of[kind])

    def in_use_of(self, kind):
        return self.usable_of(kind) - len(self._free_of[kind])

    @property
    def usable_pages(self):
        return self.usable_of(self.SEQUENCE)

    @property
    def available(self):
        return self.available_of(self.SEQUENCE)

    @property
    def in_use(self):
        return self.in_use_of(self.SEQUENCE)

    def pages_for(self, n_positions):
        """Pages needed to cover ``n_positions`` sequence positions."""
        if n_positions < 1:
            raise ValueError(
                f"n_positions must be >= 1, got {n_positions}")
        return -(-int(n_positions) // self.page_size)

    # -- alloc / free ----------------------------------------------------
    def alloc(self, n, kind=SEQUENCE):
        """Allocate ``n`` pages of ``kind`` or raise PagesExhaustedError
        (leaving the pool untouched — no partial grants)."""
        n = int(n)
        free = self._free_of[kind]
        if n < 1:
            raise ValueError(f"alloc needs n >= 1, got {n}")
        if n > len(free):
            raise PagesExhaustedError(
                f"KV page pool exhausted: need {n} {kind} pages, "
                f"{len(free)}/{self.usable_of(kind)} free — load "
                "shed, retry with backoff (or grow n_pages)")
        heap = self._heap_of[kind]
        got = [heapq.heappop(heap) for _ in range(n)]
        free.difference_update(got)
        return got

    def free(self, pages, kind=SEQUENCE):
        """Return pages of ``kind`` to its pool. Double-free and
        null-page returns are invariant violations and raise."""
        pages = list(pages)
        free = self._free_of[kind]
        for p in pages:
            if not 1 <= p < self._n_pages[kind]:
                raise ValueError(
                    f"free of page {p} outside the usable pool "
                    f"[1, {self._n_pages[kind]}) of {kind} pages")
            if p in free:
                raise ValueError(f"double free of {kind} page {p}")
        if len(set(pages)) != len(pages):
            raise ValueError(f"double free among {kind} pages {pages}")
        free.update(pages)
        for p in pages:
            heapq.heappush(self._heap_of[kind], p)

    # -- KV handoff hooks ------------------------------------------------
    def export_state(self, pages, kind=SEQUENCE):
        """Bookkeeping half of a KV handoff export: validate that
        every page is a live allocation of THIS pool (exporting a
        freed or out-of-range page would ship garbage the length mask
        no longer protects) and return the allocator-level state that
        travels with the page contents. Page ids are exporter-local —
        import allocates fresh pages, so the blob is
        location-independent."""
        pages = [int(p) for p in pages]
        for p in pages:
            if not 1 <= p < self._n_pages[kind]:
                raise ValueError(
                    f"cannot export page {p}: outside the usable "
                    f"pool [1, {self._n_pages[kind]})")
            if p in self._free_of[kind]:
                raise ValueError(
                    f"cannot export page {p}: not a live allocation")
        return {"pages": pages, "page_size": self.page_size}

    def import_alloc(self, state, total=None, kind=SEQUENCE):
        """Allocation half of a KV handoff import: check geometry
        compatibility (a page_size mismatch would silently misalign
        every position past the first page) and allocate fresh local
        pages — at least as many as the export used, or ``total`` if
        the importer needs headroom for decode. Raises
        PagesExhaustedError like any alloc (the caller requeues)."""
        if int(state.get("page_size", -1)) != self.page_size:
            raise ValueError(
                f"handoff page_size {state.get('page_size')!r} does "
                f"not match this pool's page_size {self.page_size}")
        n = len(state["pages"])
        if total is not None:
            n = max(n, int(total))
        return self.alloc(n, kind)
