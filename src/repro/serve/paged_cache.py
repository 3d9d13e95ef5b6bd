"""Paged/blocked KV cache: a refcounted free-list page allocator over one
shared arena, with prefix sharing and copy-on-write.

The single-sequence engine preallocates a dense ``(B, max_len, ...)`` cache
per batch — fine for one request, wasteful for a server where prompt and
generation lengths are heterogeneous.  Here every attention cache leaf is
backed by ONE arena of fixed-size pages (``page_size`` token positions
each); a sequence owns ``ceil(len / page_size)`` pages through a per-
sequence page table and grows one page at a time mid-decode.  Pages are
recycled through a FIFO free list, so N concurrent requests share the
arena without per-request preallocation.

**Prefix sharing** (``prefix_sharing=True``) makes the page the unit of
reuse, not just of allocation: every fully-written prompt page is
registered in a prefix index keyed by the cumulative hash of the token
ids it covers, and ``alloc_seq`` maps a new request's page-aligned prompt
prefix onto already-resident pages with the same token history — the
request attaches under a per-page refcount instead of allocating and
re-prefilling.  Pages with refcount > 1 are immutable: any write goes
through copy-on-write (``_writable_page``), so divergent continuations
never corrupt a sibling's KV.  The index holds only *resident* pages
(entries drop when the last reference is released); sharing is therefore
exact — a hit means the bytes are already in the arena.

Leaf classification is structural, not name-based: two cache templates are
built with different ``s_max`` and every leaf whose shape changes carries a
sequence axis (GQA/MLA k/v) and is paged; shape-stable leaves (Mamba conv/
ssm state, cross-attention KV) are per-sequence *state* and stored whole.
This keeps the cache format-agnostic — a new mixer with a sequence axis is
paged automatically.  (Prefix sharing requires a fully-paged cache: state
leaves summarize the whole prompt and cannot be reconstructed from a
shared page span — the scheduler enforces this.)

Arenas are device arrays, held for the cache's lifetime; the host keeps
the books (allocator, page tables, refcounts, prefix digests, ``seq_len``,
parked blobs).  A decode step passes the arenas and an int32 page table
``(B, view_pages)`` (``gather``) to one jitted program that gathers the
dense ``(repeat, B, S_view, ...)`` batch view inside its trace, decodes,
and writes each lane's new position back into its page (``gather_view``,
``write_slots``).  The page each lane writes is resolved on the host
before the step (``append_token(rid, None, pos)``: growth, copy-on-write),
so page exhaustion is met before the device runs, never after.  Page
copies (COW) and the zeroing of recycled pages (a copy of the zero page)
are queued and applied in one batch: by the next decode step itself, or
by ``flush`` before any other device read or write of the arenas.
Prefill writes scatter the prefill's device cache into pages on the
device.  Page id ``num_pages`` is a reserved always-zero page used to pad
the table for lanes that have not allocated that far yet, so a gathered
view is bit-identical to the dense reference cache over every written
position and zero beyond it.  Leaves without a sequence axis (state) stay
per sequence on the host.

Eviction parks a sequence's *private* pages + state on the host (the only
KV that crosses between host and device, counted in ``parked_bytes``) and
frees them; pages shared with other sequences (refcount > 1) are retained
under the parked sequence's reference — they are already resident, so
parking copies nothing and frees nothing for them.  ``resume`` reallocates
the private pages and restores bit-for-bit, so a preempted sequence
continues decoding losslessly.  ``release_parked_shared`` demotes a parked
sequence's retained shared pages to host copies when the arena is under
terminal pressure.

Page-capacity failures raise the typed ``PagesExhausted`` (a
``RuntimeError`` subclass) so the scheduler can respond by evicting
instead of dying.
"""
from __future__ import annotations

import collections
import functools
import hashlib
import math
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs
from ..models.config import ModelConfig
from ..models.transformer import init_cache

__all__ = [
    "PageAllocator", "PagedKVCache", "PagesExhausted", "copy_pages",
    "gather_view", "write_slots",
]


# ---------------------------------------------------------------------- #
# device-side page operations: plain functions for use inside a trace
# (the decode step), and jitted with the arenas donated for the cache's
# own updates.  An arena is (num_pages + 1, repeat, page_size, ...feat);
# a page id past the zero page is out of range and its write is dropped.
# ---------------------------------------------------------------------- #
def gather_view(arena, table):
    """The dense ``(repeat, B, VP * page_size, ...feat)`` view of ``arena``
    through the int32 page ``table`` ``(B, VP)``."""
    a = jnp.moveaxis(arena[table], 2, 0)  # (repeat, B, VP, ps, ...feat)
    return a.reshape(a.shape[:2] + (-1,) + a.shape[4:])


def copy_pages(arenas, src, dst):
    """``arena[dst] = arena[src]`` in every arena, every read before any
    write; out-of-range ``dst`` entries are padding and write nothing."""
    return [a.at[dst].set(a[src], mode="drop") for a in arenas]


def write_slots(arenas, pages, positions, slices):
    """Write lane ``b``'s ``slices[k][b]`` ``(repeat, ...feat)`` at
    ``positions[b] % page_size`` of page ``pages[b]`` in arena ``k``."""
    out = []
    for a, sl in zip(arenas, slices):
        off = positions % a.shape[2]
        out.append(a.at[pages, :, off].set(sl.astype(a.dtype), mode="drop"))
    return out


_copy_pages = jax.jit(copy_pages, donate_argnums=0)
_write_slots = jax.jit(write_slots, donate_argnums=0)


@functools.partial(jax.jit, donate_argnums=0)
def _put_pages(arenas, pages, blocks):
    return [a.at[pages].set(b, mode="drop") for a, b in zip(arenas, blocks)]


@jax.jit
def _take_pages(arenas, pages):
    return [a[pages] for a in arenas]


@functools.partial(jax.jit, static_argnames="s_max")
def _read_pages(arenas, table, length, *, s_max):
    """Dense ``(repeat, 1, s_max, ...feat)`` leaves of one sequence through
    its page ``table``, zero from ``length`` on."""
    out = []
    for a in arenas:
        v = gather_view(a, table[None])[:, :, :s_max]
        keep = (jnp.arange(s_max) < length).reshape((1, 1, -1) + (1,) * (v.ndim - 3))
        out.append(jnp.where(keep, v, jnp.zeros((), v.dtype)))
    return out


@jax.jit
def _view(arenas, table):
    return [gather_view(a, table) for a in arenas]


@functools.partial(jax.jit, donate_argnums=0)
def _write_span_pages(arenas, leaves, pages, j0, start, end, zero_tail):
    """Write positions ``[start, end)`` of the dense single-sequence
    ``leaves`` ``(repeat, 1, S, ...feat)`` into ``pages`` (slots ``j0,
    j0+1, ...``; padded past the zero page).  Positions of those pages
    below ``start`` keep their bytes; positions from ``end`` on are zeroed
    when ``zero_tail``, else kept."""
    out = []
    for a, leaf in zip(arenas, leaves):
        ps, n, S = a.shape[2], pages.shape[0], leaf.shape[2]
        pos = (j0 + jnp.arange(n))[:, None] * ps + jnp.arange(ps)[None, :]
        src = jnp.take(leaf[:, 0], jnp.clip(pos, 0, S - 1), axis=1)
        src = jnp.moveaxis(src, 1, 0).astype(a.dtype)  # (n, repeat, ps, ...)
        old = a.at[pages].get(mode="clip")
        shape = (n, 1, ps) + (1,) * (a.ndim - 3)
        written = ((pos >= start) & (pos < end)).reshape(shape)
        zeroed = (zero_tail & (pos >= end)).reshape(shape)
        new = jnp.where(written, src, jnp.where(zeroed, jnp.zeros((), a.dtype), old))
        out.append(a.at[pages].set(new, mode="drop"))
    return out


def _bucket(n: int) -> int:
    """Padded length of a batch of ``n`` page operations: a power of two,
    at least 8, so a handful of programs serves every count."""
    return max(8, 1 << max(0, n - 1).bit_length())


class PagesExhausted(RuntimeError):
    """A write needed a page the allocator could not provide.  The cache
    state is consistent (the failed operation wrote nothing past its last
    completed page); the scheduler handles this by evicting per policy and
    retrying, instead of the step dying on a bare RuntimeError."""


class PageAllocator:
    """FIFO free-list page allocator.  Deterministic: pages are handed out
    in ascending id order initially and recycled in free order, so a fixed
    request sequence always produces the same page tables (the golden
    serving fixture freezes exactly this)."""

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError("need at least one page")
        self.num_pages = int(num_pages)
        self._free = collections.deque(range(self.num_pages))
        self._held: set = set()
        self.total_allocated = 0  # cumulative pages handed out (bench)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_held(self) -> int:
        return len(self._held)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages atomically; None (state unchanged) if the
        free list is short."""
        if n < 0:
            raise ValueError("negative allocation")
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        self._held.update(pages)
        self.total_allocated += n
        return pages

    def free(self, pages) -> None:
        for p in pages:
            if p not in self._held:
                raise ValueError(f"double free / foreign page {p}")
            self._held.discard(p)
            self._free.append(p)

    def check(self) -> None:
        """Invariant: every page is exactly once free or held."""
        assert len(self._free) + len(self._held) == self.num_pages
        assert set(self._free) | self._held == set(range(self.num_pages))
        assert not (set(self._free) & self._held)


def _flatten(tree):
    return jax.tree_util.tree_flatten(tree)


class PagedKVCache:
    """Model-shaped paged cache arena (see module docstring).

    Parameters
    ----------
    cfg : ModelConfig (decoder-only; enc-dec goes through the legacy path)
    num_pages : total allocatable pages shared by all sequences
    page_size : token positions per page
    max_len : per-sequence logical capacity; the dense batch view is
        ``view_pages * page_size`` wide with ``view_pages =
        ceil(max_len / page_size)``
    prefix_sharing : maintain the prefix index so ``alloc_seq(tokens=...)``
        attaches to resident pages with the same token prefix (COW on
        write); off by default — the golden serving transcript pins the
        unshared schedule
    """

    def __init__(
        self,
        cfg: ModelConfig,
        num_pages: int,
        page_size: int,
        max_len: int,
        dtype=None,
        prefix_sharing: bool = False,
    ):
        if cfg.is_encdec:
            raise ValueError(
                "PagedKVCache is decoder-only; enc-dec serving uses the "
                "single-sequence compatibility path"
            )
        self.cfg = cfg
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.prefix_sharing = bool(prefix_sharing)
        self.view_pages = math.ceil(self.max_len / self.page_size)
        if num_pages < self.view_pages:
            raise ValueError(
                f"num_pages={num_pages} cannot hold even one max_len="
                f"{max_len} sequence ({self.view_pages} pages needed)"
            )
        self.allocator = PageAllocator(num_pages)
        self.zero_page = num_pages  # reserved, always zero, never allocated

        # structural classification: leaves whose shape varies with s_max
        # carry the sequence axis (paged); the rest are per-seq state
        ta, _ = _flatten(init_cache(cfg, 1, 2, dtype=dtype))
        tb, self.treedef = _flatten(init_cache(cfg, 1, 3, dtype=dtype))
        self.num_leaves = len(tb)
        self.paged: List[bool] = []
        self.seq_axis: List[Optional[int]] = []
        self._arenas: List[Optional[jax.Array]] = []
        self._state_shape: List[Optional[tuple]] = []
        self._dtypes = []
        for la, lb in zip(ta, tb):
            self._dtypes.append(np.dtype(lb.dtype))
            if la.shape != lb.shape:
                diffs = [
                    i for i, (x, y) in enumerate(zip(la.shape, lb.shape)) if x != y
                ]
                assert diffs == [2], (
                    f"expected a single seq axis at 2, got {diffs} for "
                    f"{la.shape} vs {lb.shape}"
                )
                self.paged.append(True)
                self.seq_axis.append(2)
                feat = tuple(lb.shape[3:])
                repeat = lb.shape[0]
                self._arenas.append(
                    jnp.zeros(
                        (num_pages + 1, repeat, self.page_size) + feat,
                        lb.dtype,
                    )
                )
                self._state_shape.append(None)
            else:
                self.paged.append(False)
                self.seq_axis.append(None)
                self._arenas.append(None)
                self._state_shape.append(tuple(lb.shape))

        # per-sequence bookkeeping
        self.page_table: Dict[str, List[int]] = {}
        self.seq_len: Dict[str, int] = {}
        self._state: Dict[str, List[Optional[np.ndarray]]] = {}
        self._parked: Dict[str, dict] = {}

        # refcounts + prefix index (page -> owners; digest <-> page)
        self._ref: Dict[int, int] = {}
        self._prefix_index: Dict[bytes, int] = {}
        self._page_digest: Dict[int, bytes] = {}
        # per-seq prompt digests + share cap (last-token page never shared)
        self._share_info: Dict[str, dict] = {}
        self._hit_rids: set = set()
        self.share_stats = {
            "prefix_hits": 0,
            "pages_shared": 0,
            "cow_copies": 0,
        }
        self.zero_writes = 0  # pages zeroed (prefill-path bandwidth audit)
        # queued page copies, dst -> src (zeroing copies the zero page)
        self._pending: Dict[int, int] = {}
        # writes the next decode step makes: rid -> (page, position)
        self._step_writes: Dict[str, tuple] = {}
        self.parked_bytes = 0  # KV bytes copied by evict / resume / release

    # ------------------------------------------------------------------ #
    # the device arenas and their queued page copies
    # ------------------------------------------------------------------ #
    @property
    def arenas(self) -> List[jax.Array]:
        """The paged leaves' arenas, in leaf order (empty for a cache with
        no sequence axis).  A program that takes them donated hands the
        updated ones back through the setter."""
        return [a for a in self._arenas if a is not None]

    @arenas.setter
    def arenas(self, new) -> None:
        it = iter(new)
        self._arenas = [None if a is None else next(it) for a in self._arenas]

    def take_copies(self, n: int):
        """The queued page copies as int32 ``(src, dst)`` of length ``n``
        (padding copies the zero page out of range), for a program that
        applies them (``copy_pages``); the queue is emptied.  A queue
        longer than ``n`` is flushed here first."""
        if len(self._pending) > n:
            self.flush()
        src = np.full(n, self.zero_page, np.int32)
        dst = np.full(n, self.zero_page + 1, np.int32)
        for k, (d, s) in enumerate(self._pending.items()):
            dst[k], src[k] = d, s
        self._pending.clear()
        return src, dst

    def flush(self) -> None:
        """Apply the queued page copies on the device, in one call."""
        if self._pending:
            src, dst = self.take_copies(_bucket(len(self._pending)))
            self.arenas = _copy_pages(self.arenas, src, dst)

    def _queue_copy(self, dst: int, src: int) -> None:
        # a source that is itself a queued destination reads what it will
        # hold: the queue applies every read before any write
        if self.arenas:
            self._pending[dst] = self._pending.get(src, src)

    def _fetch_pages(self, pages: List[int]) -> List[List[np.ndarray]]:
        """Host copies of ``pages``, one list of per-arena blobs a page, in
        one device read; counted in ``parked_bytes``."""
        if not pages or not self.arenas:
            return [[None] * self.num_leaves for _ in pages]
        self.flush()
        got = [np.asarray(x) for x in _take_pages(self.arenas, np.asarray(pages, np.int32))]
        self.parked_bytes += sum(int(x.nbytes) for x in got)
        out = []
        for k in range(len(pages)):
            it = iter(got)
            out.append([None if a is None else next(it)[k] for a in self._arenas])
        return out

    # ------------------------------------------------------------------ #
    # allocation
    # ------------------------------------------------------------------ #
    def pages_needed(self, n_tokens: int) -> int:
        # n_tokens == 0 needs 0 pages (a former max(1, ...) here made
        # zero-token allocations hold a page forever)
        return math.ceil(n_tokens / self.page_size)

    def can_alloc(self, n_tokens: int) -> bool:
        return self.allocator.num_free >= self.pages_needed(n_tokens)

    def _digests(self, tokens) -> List[bytes]:
        """Cumulative blake2b digest per full ``page_size`` token chunk:
        digest j identifies tokens[0 : (j+1)*page_size] — a page is only
        reusable when its entire token history matches."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int64).reshape(-1))
        h = hashlib.blake2b(str(self.page_size).encode(), digest_size=16)
        out = []
        for j in range(len(toks) // self.page_size):
            h.update(toks[j * self.page_size : (j + 1) * self.page_size].tobytes())
            out.append(h.copy().digest())
        return out

    def alloc_seq(
        self,
        rid: str,
        n_tokens: int,
        tokens=None,
        *,
        reserve: Optional[int] = None,
        zero: bool = True,
    ) -> bool:
        """Reserve pages for ``n_tokens`` positions and zero-init state.
        False (nothing changes) if the free list is short.

        With ``prefix_sharing`` on and the prompt ``tokens`` given, full
        pages whose cumulative token hash is already in the prefix index
        are attached by reference instead of allocated — ``seq_len[rid]``
        comes back equal to the shared span (the caller prefills only the
        tail; the last prompt token is never shared so its logits are
        always computed).

        ``reserve`` caps the initial page reservation to cover only
        ``reserve`` tokens (chunked prefill admits with the first chunk's
        pages, growing per chunk); default reserves the full ``n_tokens``.
        ``zero=False`` skips zero-initializing the fresh pages — only for
        callers that immediately overwrite every reserved page
        (``write_prefill`` / ``write_span`` zero the written span's tail
        themselves)."""
        if rid in self.page_table:
            raise ValueError(f"sequence {rid!r} already allocated")
        if n_tokens > self.max_len:
            raise ValueError(f"{n_tokens} tokens > max_len={self.max_len}")

        shared: List[int] = []
        digests: List[bytes] = []
        if self.prefix_sharing and tokens is not None and n_tokens > 1:
            digests = self._digests(tokens)
            # never share the page holding the last prompt token: its
            # logits seed the first generated token and must be computed
            cap = (n_tokens - 1) // self.page_size
            for j in range(min(cap, len(digests))):
                page = self._prefix_index.get(digests[j])
                if page is None:
                    break
                shared.append(page)

        target = max(n_tokens if reserve is None else min(reserve, n_tokens),
                     len(shared) * self.page_size)
        fresh = self.allocator.alloc(self.pages_needed(target) - len(shared))
        if fresh is None:
            return False
        for p in shared:
            self._ref[p] += 1
        for p in fresh:
            self._ref[p] = 1
            if zero:
                self._zero_page(p)
        self.page_table[rid] = shared + fresh
        self.seq_len[rid] = len(shared) * self.page_size
        self._state[rid] = [
            None if s is None else np.zeros(s, self._dtypes[i])
            for i, s in enumerate(self._state_shape)
        ]
        if digests:
            self._share_info[rid] = {"digests": digests, "cap": cap}
        if shared:
            if rid not in self._hit_rids:
                self._hit_rids.add(rid)
                self.share_stats["prefix_hits"] += 1
            self.share_stats["pages_shared"] += len(shared)
        return True

    def attach_shared(self, rid: str) -> int:
        """Late prefix attachment for chunked prefill: requests admitted in
        the same step as the prefix's first writer find the index empty at
        ``alloc_seq`` — so a mid-prefill sequence re-probes before each
        chunk and swaps its next (page-aligned, unwritten) slots for index
        pages that have since become resident, skipping those chunks'
        compute.  Returns the token positions newly covered."""
        info = self._share_info.get(rid)
        if info is None or rid not in self.page_table:
            return 0
        ps = self.page_size
        attached = 0
        while True:
            sl = self.seq_len[rid]
            if sl % ps:
                break  # mid-page frontier (unaligned chunk): can't attach
            j = sl // ps
            if j >= info["cap"] or j >= len(info["digests"]):
                break
            d = info["digests"][j]
            page = None if d is None else self._prefix_index.get(d)
            if page is None:
                break
            pt = self.page_table[rid]
            if j < len(pt):
                # slot was reserved with a fresh private page that nothing
                # has written yet (seq_len <= j*ps): swap it for the
                # shared one and return it to the pool
                self._decref(pt[j])
                pt[j] = page
            else:
                pt.append(page)
            self._ref[page] += 1
            self.seq_len[rid] = sl + ps
            attached += 1
        if attached:
            if rid not in self._hit_rids:
                self._hit_rids.add(rid)
                self.share_stats["prefix_hits"] += 1
            self.share_stats["pages_shared"] += attached
        return attached * ps

    def shared_prefix_len(self, rid: str) -> int:
        """Token positions of ``rid`` attached from the prefix index at
        ``alloc_seq`` time (== initial ``seq_len``)."""
        pt = self.page_table[rid]
        return self.page_size * sum(1 for p in pt if self._ref[p] > 1)

    def ensure_capacity(self, rid: str, n_tokens: int, *, zero: bool = True) -> bool:
        """Grow the page table to cover ``n_tokens`` positions."""
        need = self.pages_needed(n_tokens) - len(self.page_table[rid])
        if need <= 0:
            return True
        pages = self.allocator.alloc(need)
        if pages is None:
            return False
        for p in pages:
            self._ref[p] = 1
            if zero:
                self._zero_page(p)
        self.page_table[rid].extend(pages)
        return True

    def free_seq(self, rid: str) -> None:
        """Release ``rid`` — live or parked.  A parked sequence (finish /
        cancel while preempted) drops its host copies and releases the
        shared pages it retained; this is the path that must never
        double-free (the allocator's check would catch it)."""
        if rid in self._parked:
            park = self._parked.pop(rid)
            for slot in park["slots"]:
                if slot["page"] is not None:
                    self._decref(slot["page"])
        else:
            for p in self.page_table.pop(rid):
                self._decref(p)
        self.seq_len.pop(rid, None)
        self._state.pop(rid, None)
        self._step_writes.pop(rid, None)
        self._share_info.pop(rid, None)
        self._hit_rids.discard(rid)

    def _decref(self, page: int) -> None:
        r = self._ref[page] - 1
        if r > 0:
            self._ref[page] = r
            return
        del self._ref[page]
        self._deregister(page)
        self.allocator.free([page])

    def _zero_page(self, page: int) -> None:
        # recycled pages may hold a dead sequence's KV; zeroing keeps every
        # gathered view bit-identical to the dense reference cache
        self.zero_writes += 1
        self._queue_copy(page, self.zero_page)

    # ------------------------------------------------------------------ #
    # prefix index
    # ------------------------------------------------------------------ #
    def _register(self, rid: str) -> None:
        """Advertise ``rid``'s fully-written full prompt pages in the
        prefix index (first writer wins)."""
        info = self._share_info.get(rid)
        if info is None:
            return
        digests = info["digests"]
        pt = self.page_table[rid]
        n_full = min(self.seq_len[rid] // self.page_size, len(digests), len(pt))
        for j in range(n_full):
            d = digests[j]
            if d is None or d in self._prefix_index:
                continue
            page = pt[j]
            if page in self._page_digest:
                continue
            self._prefix_index[d] = page
            self._page_digest[page] = d

    def _deregister(self, page: int) -> None:
        d = self._page_digest.pop(page, None)
        if d is not None:
            self._prefix_index.pop(d, None)

    def _mark_overwritten(self, rid: str, start: int, end: int) -> None:
        """A write below the frontier mutates prompt pages away from their
        token digests: void those slots' digests for this sequence so a
        later ``_register`` can never advertise the mutated content."""
        old_len = self.seq_len[rid]
        if start >= old_len:
            return
        info = self._share_info.get(rid)
        if info is None:
            return
        ps = self.page_size
        for j in range(start // ps, min((end - 1) // ps + 1, len(info["digests"]))):
            info["digests"][j] = None

    def _writable_page(self, rid: str, j: int) -> int:
        """Page backing slot ``j`` of ``rid``, made safe to write: shared
        pages (refcount > 1) are copied first (COW) so siblings keep the
        original bytes; a sole-owned page still advertised in the prefix
        index is deregistered (its content is about to change)."""
        pt = self.page_table[rid]
        page = pt[j]
        if self._ref[page] > 1:
            got = self.allocator.alloc(1)
            if got is None:
                raise PagesExhausted(
                    f"copy-on-write for {rid!r} page slot {j}: no free pages"
                )
            new = got[0]
            self._queue_copy(new, page)
            self._ref[new] = 1
            self._ref[page] -= 1
            pt[j] = new
            self.share_stats["cow_copies"] += 1
            return new
        self._deregister(page)
        return page

    # ------------------------------------------------------------------ #
    # writes
    # ------------------------------------------------------------------ #
    def write_prefill(self, rid: str, cache, length: int, start: int = 0) -> int:
        """Copy a dense single-sequence cache (leaves ``(repeat, 1, S, ...)``
        with ``S >= length``) into this sequence's pages + state.  With
        ``start > 0`` only positions ``[start, length)`` are written —
        chunked prefill and the tail after a shared prefix.  Returns the
        bytes read back from the device (see ``write_span``)."""
        return self.write_span(rid, cache, start, length)

    def write_span(self, rid: str, cache, start: int, end: int) -> int:
        """Write positions ``[start, end)`` from a dense cache into pages on
        the device; state leaves are replaced wholesale on the host.  Grows
        the page table to cover ``end`` (unzeroed — every grown page is
        covered by this write plus the explicit tail zero), raising
        ``PagesExhausted`` when it can't.  Returns the bytes read back from
        the device: the state leaves that are device arrays (the paged
        leaves never leave it)."""
        leaves, _ = _flatten(cache)
        assert len(leaves) == self.num_leaves
        bytes_d2h = sum(
            int(x.nbytes)
            for x, p in zip(leaves, self.paged)
            if not p and isinstance(x, jax.Array)
        )
        with obs.span("kv.write", rid=rid, bytes_d2h=bytes_d2h):
            self._write_span(rid, leaves, start, end)
        return bytes_d2h

    def _write_span(self, rid: str, leaves, start: int, end: int) -> None:
        if not self.ensure_capacity(rid, end, zero=False):
            raise PagesExhausted(f"no pages for prefill of {rid!r}")
        self._mark_overwritten(rid, start, end)
        ps = self.page_size
        old_len = self.seq_len[rid]
        j0, j1 = start // ps, (max(end, start + 1) - 1) // ps
        pages = [self._writable_page(rid, j) for j in range(j0, j1 + 1)]
        paged = [leaf for leaf, p in zip(leaves, self.paged) if p]
        if paged:
            # one program per cache width: the page list is padded to the
            # pages that width spans
            n = max(len(pages), math.ceil(paged[0].shape[2] / ps))
            table = np.full(n, self.zero_page + 1, np.int32)
            table[: len(pages)] = pages
            self.flush()
            self.arenas = _write_span_pages(
                self.arenas, paged, table, np.int32(j0), np.int32(start),
                np.int32(end), np.bool_(end >= old_len),
            )
        for i, leaf in enumerate(leaves):
            if not self.paged[i]:
                self._state[rid][i] = np.array(leaf)
        self.seq_len[rid] = max(old_len, end)
        if self.prefix_sharing:
            self._register(rid)

    def prepare_write(self, rid: str, position: int) -> int:
        """Resolve the page that takes ``rid``'s write at ``position``:
        grow the table (zeroing the new page), void overwritten prompt
        digests, copy a shared page first (COW).  Raises ``PagesExhausted``
        before anything is written."""
        if not self.ensure_capacity(rid, position + 1):
            raise PagesExhausted(f"no pages to append to {rid!r}")
        self._mark_overwritten(rid, position, position + 1)
        return self._writable_page(rid, position // self.page_size)

    def append_token(self, rid: str, slices, position: int) -> None:
        """Write one decode step's output for one lane: ``slices`` is a
        flat leaf list — paged leaves ``(repeat, ...feat)`` (the KV written
        at ``position``, batch/seq axes squeezed), state leaves
        ``(repeat, 1, ...)`` replace the stored state wholesale.

        With ``slices=None`` the page is resolved (``prepare_write``) and
        the write is left to the next decode step's program, which reads
        the target from ``step_pages``; ``commit_token`` completes it."""
        page = self.prepare_write(rid, position)
        if slices is None:
            self._step_writes[rid] = (page, position)
            return
        self.flush()
        paged = [np.asarray(x)[None] for x, p in zip(slices, self.paged) if p]
        if paged:
            self.arenas = _write_slots(
                self.arenas, np.asarray([page], np.int32),
                np.asarray([position], np.int32), paged,
            )
        for i, leaf in enumerate(slices):
            if not self.paged[i]:
                self._state[rid][i] = np.array(leaf)
        self.seq_len[rid] = max(self.seq_len[rid], position + 1)

    def step_pages(self, rids: List[Optional[str]]) -> np.ndarray:
        """int32 ``(B,)``: the page each lane's decode write goes to, as
        ``append_token(rid, None, pos)`` resolved it; out of range (the
        write is dropped) for empty lanes and lanes with no write."""
        out = np.full(len(rids), self.zero_page + 1, np.int32)
        for b, rid in enumerate(rids):
            if rid in self._step_writes:
                out[b] = self._step_writes[rid][0]
        return out

    def commit_token(self, rid: str, states) -> None:
        """Complete the decode write ``append_token(rid, None, pos)``
        resolved, once the step has written its page: ``seq_len`` grows
        and ``states`` (the state leaves, in leaf order) are stored."""
        rec = self._step_writes.pop(rid, None)
        if rec is None:
            return
        it = iter(states)
        for i, p in enumerate(self.paged):
            if not p:
                self._state[rid][i] = np.array(next(it))
        self.seq_len[rid] = max(self.seq_len[rid], rec[1] + 1)

    # ------------------------------------------------------------------ #
    # reads
    # ------------------------------------------------------------------ #
    def _full_table(self, rid: Optional[str]) -> List[int]:
        pt = [] if rid is None else self.page_table[rid]
        return list(pt) + [self.zero_page] * (self.view_pages - len(pt))

    def gather(self, rids: List[Optional[str]]) -> np.ndarray:
        """The int32 page table ``(B, view_pages)`` of a list of lanes
        (None = empty lane), padded with the zero page: what a decode step
        gathers its batch view through (``gather_view``)."""
        return np.asarray([self._full_table(r) for r in rids], np.int32)

    def lane_states(self, rids: List[Optional[str]]) -> List[np.ndarray]:
        """The state leaves of a list of lanes, each ``(repeat, B, ...)``
        (zeros for an empty lane), in leaf order."""
        out = []
        for i in range(self.num_leaves):
            if self.paged[i]:
                continue
            zero = np.zeros(self._state_shape[i], self._dtypes[i])
            out.append(
                np.concatenate(
                    [zero if r is None else self._state[r][i] for r in rids], axis=1
                )
                if rids
                else zero
            )
        return out

    def view(self, rids: List[Optional[str]]):
        """The dense batch view of a list of lanes (None = empty lane, all
        zeros), built on the device as a decode step builds it.  Leaves
        come back shaped like ``init_cache(cfg, B, view_pages *
        page_size)``."""
        self.flush()
        paged = iter(_view(self.arenas, self.gather(rids)) if self.arenas else [])
        states = iter(self.lane_states(rids))
        leaves = [next(paged) if p else next(states) for p in self.paged]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def read_dense(self, rid: str, s_max: Optional[int] = None):
        """Dense single-sequence cache for ``rid`` — shaped like
        ``init_cache(cfg, 1, s_max)`` with every written position equal to
        the page contents bit-for-bit (the property-test contract) and zero
        beyond.  Paged leaves are gathered on the device and stay there."""
        length = self.seq_len[rid]
        s_max = length if s_max is None else s_max
        if s_max < length:
            raise ValueError("s_max shorter than written length")
        n = max(1, self.pages_needed(s_max))
        table = (list(self.page_table[rid]) + [self.zero_page] * n)[:n]
        self.flush()
        paged = iter(_read_pages(
            self.arenas, np.asarray(table, np.int32), np.int32(length), s_max=s_max
        ) if self.arenas else [])
        leaves = [
            next(paged) if p else self._state[rid][i].copy()
            for i, p in enumerate(self.paged)
        ]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # ------------------------------------------------------------------ #
    # eviction / resume (lossless preemption)
    # ------------------------------------------------------------------ #
    def evict(self, rid: str) -> None:
        """Park ``rid``: private pages (refcount 1) are copied to the host
        and freed; shared pages stay resident under this sequence's
        reference (parking copies and frees nothing for them — the prefix
        span survives for siblings and for our own resume)."""
        pt = self.page_table.pop(rid)
        self._step_writes.pop(rid, None)
        private = iter(self._fetch_pages([p for p in pt if self._ref[p] == 1]))
        slots = []
        for p in pt:
            if self._ref[p] > 1:
                slots.append({"page": p, "blobs": None})
            else:
                slots.append({"page": None, "blobs": next(private)})
                self._decref(p)
        self._parked[rid] = {
            "slots": slots,
            "state": [
                None if s is None else s.copy() for s in self._state.pop(rid)
            ],
            "seq_len": self.seq_len.pop(rid),
        }

    def resume(self, rid: str) -> bool:
        """Re-own pages for a parked sequence and restore its contents
        bit-for-bit: retained shared pages re-attach in place (their bytes
        never changed — writers COW away), private pages reallocate and
        refill.  False (still parked, nothing changes) if pages are short."""
        park = self._parked[rid]
        private = [j for j, s in enumerate(park["slots"]) if s["page"] is None]
        pages = self.allocator.alloc(len(private))
        if pages is None:
            return False
        table: List[int] = []
        it = iter(pages)
        for slot in park["slots"]:
            if slot["page"] is not None:
                table.append(slot["page"])  # ref was retained at evict
                continue
            p = next(it)
            self._ref[p] = 1
            table.append(p)
        self._put_blobs(pages, [park["slots"][j]["blobs"] for j in private])
        self.page_table[rid] = table
        self.seq_len[rid] = park["seq_len"]
        self._state[rid] = park["state"]
        del self._parked[rid]
        return True

    def _put_blobs(self, pages: List[int], blobs) -> None:
        """Write host page copies back into ``pages`` in one device call
        (padded to a bucket of page counts); counted in ``parked_bytes``."""
        if not pages or not self.arenas:
            return
        n = _bucket(len(pages))
        table = np.full(n, self.zero_page + 1, np.int32)
        table[: len(pages)] = pages
        blocks = []
        for k, a in enumerate(self._arenas):
            if a is None:
                continue
            blk = np.zeros((n,) + a.shape[1:], self._dtypes[k])
            for j, b in enumerate(blobs):
                blk[j] = b[k]
            self.parked_bytes += int(blk[: len(pages)].nbytes)
            blocks.append(blk)
        self.flush()
        self.arenas = _put_pages(self.arenas, table, blocks)

    def is_parked(self, rid: str) -> bool:
        return rid in self._parked

    def parked_shared_pages(self, rid: str) -> int:
        """Pages a parked ``rid`` still holds resident by reference."""
        return sum(
            1 for s in self._parked[rid]["slots"] if s["page"] is not None
        )

    def release_parked_shared(self, rid: str) -> int:
        """Demote a parked sequence's retained shared pages to host copies,
        dropping its references (pages whose refcount hits zero free).
        Lossless — resume re-allocates them like any private page.  Returns
        the number of references released (the terminal-pressure escape
        valve: without it, parked siblings could pin the arena)."""
        slots = [s for s in self._parked[rid]["slots"] if s["page"] is not None]
        blobs = self._fetch_pages([s["page"] for s in slots])
        released = 0
        for slot, blob in zip(slots, blobs):
            page = slot["page"]
            slot["blobs"] = blob
            slot["page"] = None
            self._decref(page)
            released += 1
        return released

    # ------------------------------------------------------------------ #
    def stats(self) -> dict:
        return {
            "num_pages": self.allocator.num_pages,
            "free_pages": self.allocator.num_free,
            "held_pages": self.allocator.num_held,
            "pages_allocated_total": self.allocator.total_allocated,
            "page_size": self.page_size,
            "view_pages": self.view_pages,
            "sequences": len(self.page_table),
            "parked": len(self._parked),
            "indexed_prefix_pages": len(self._prefix_index),
            "shared_pages_now": sum(1 for r in self._ref.values() if r > 1),
            "zero_writes": self.zero_writes,
            **self.share_stats,
        }
