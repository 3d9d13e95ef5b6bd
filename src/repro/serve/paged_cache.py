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

Arenas are host (numpy) arrays: the scheduler gathers the active lanes
into a dense ``(repeat, B, S_view, ...)`` batch view per decode step (the
page-table indirection happens here, outside the jitted step) and scatters
each lane's newly written position back afterwards.  Page id
``num_pages`` is a reserved always-zero page used to pad the view for
lanes that have not allocated that far yet, so a gathered view is
bit-identical to the dense reference cache over every written position
and zero beyond it.

Eviction parks a sequence's *private* pages + state on the host and frees
them; pages shared with other sequences (refcount > 1) are retained under
the parked sequence's reference — they are already resident, so parking
copies nothing and frees nothing for them.  ``resume`` reallocates the
private pages and restores bit-for-bit, so a preempted sequence continues
decoding losslessly.  ``release_parked_shared`` demotes a parked
sequence's retained shared pages to host copies when the arena is under
terminal pressure.

Page-capacity failures raise the typed ``PagesExhausted`` (a
``RuntimeError`` subclass) so the scheduler can respond by evicting
instead of dying.
"""
from __future__ import annotations

import collections
import hashlib
import math
from typing import Dict, List, Optional

import numpy as np
import jax

from .. import obs
from ..models.config import ModelConfig
from ..models.transformer import init_cache

__all__ = ["PageAllocator", "PagedKVCache", "PagesExhausted"]


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
        self._arenas: List[Optional[np.ndarray]] = []
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
                    np.zeros(
                        (num_pages + 1, repeat, self.page_size) + feat,
                        np.dtype(lb.dtype),
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

    # ------------------------------------------------------------------ #
    # mask pytree for the lane decoder (True = leaf has a sequence axis)
    # ------------------------------------------------------------------ #
    @property
    def paged_mask(self):
        return jax.tree_util.tree_unflatten(self.treedef, list(self.paged))

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
        for a in self._arenas:
            if a is not None:
                a[page] = 0

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
            for a in self._arenas:
                if a is not None:
                    a[new] = a[page]
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
        """Write positions ``[start, end)`` from a dense cache into pages;
        state leaves are replaced wholesale.  Grows the page table to cover
        ``end`` (unzeroed — every grown page is covered by this write plus
        the explicit tail zero), raising ``PagesExhausted`` when it can't.
        Returns the bytes read back from the device: the whole of every
        leaf that is a device array."""
        leaves, _ = _flatten(cache)
        assert len(leaves) == self.num_leaves
        bytes_d2h = sum(int(x.nbytes) for x in leaves if isinstance(x, jax.Array))
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
        for i, leaf in enumerate(leaves):
            arr = np.asarray(leaf)
            if self.paged[i]:
                for j, page in zip(range(j0, j1 + 1), pages):
                    lo, hi = max(j * ps, start), min((j + 1) * ps, end)
                    if hi <= lo:
                        continue
                    self._arenas[i][page, :, lo - j * ps : hi - j * ps] = (
                        arr[:, 0, lo:hi]
                    )
                # the frontier page may be fresh (allocated unzeroed by the
                # ensure_capacity above): zero the not-yet-written tail so
                # gathered views stay bit-identical to the dense reference
                if end >= old_len and end % ps:
                    self._arenas[i][pages[-1], :, end % ps :] = 0
            else:
                self._state[rid][i] = arr.copy()
        self.seq_len[rid] = max(old_len, end)
        if self.prefix_sharing:
            self._register(rid)

    def append_token(self, rid: str, slices, position: int) -> None:
        """Write one decode step's output for one lane: ``slices`` is a
        flat leaf list — paged leaves ``(repeat, ...feat)`` (the KV written
        at ``position``, batch/seq axes squeezed), state leaves
        ``(repeat, 1, ...)`` replace the stored state wholesale."""
        if not self.ensure_capacity(rid, position + 1):
            raise PagesExhausted(f"no pages to append to {rid!r}")
        self._mark_overwritten(rid, position, position + 1)
        page = self._writable_page(rid, position // self.page_size)
        off = position % self.page_size
        for i, leaf in enumerate(slices):
            arr = np.asarray(leaf)
            if self.paged[i]:
                self._arenas[i][page, :, off] = arr
            else:
                self._state[rid][i] = arr.copy()
        self.seq_len[rid] = max(self.seq_len[rid], position + 1)

    # ------------------------------------------------------------------ #
    # reads
    # ------------------------------------------------------------------ #
    def _full_table(self, rid: Optional[str]) -> List[int]:
        pt = [] if rid is None else self.page_table[rid]
        return list(pt) + [self.zero_page] * (self.view_pages - len(pt))

    def gather(self, rids: List[Optional[str]]):
        """Materialize the dense batch view for a list of lanes (None =
        empty lane, all zeros).  Leaves come back shaped like
        ``init_cache(cfg, B, view_pages * page_size)``."""
        with obs.span("kv.gather") as sp:
            B = len(rids)
            tables = np.asarray([self._full_table(r) for r in rids], np.int64)
            leaves = []
            for i in range(self.num_leaves):
                if self.paged[i]:
                    a = self._arenas[i][tables]  # (B, VP, repeat, ps, ...feat)
                    a = np.moveaxis(a, 2, 0)  # (repeat, B, VP, ps, ...)
                    leaves.append(
                        a.reshape(a.shape[:2] + (-1,) + a.shape[4:])
                    )
                else:
                    zero = np.zeros(self._state_shape[i], self._dtypes[i])
                    leaves.append(
                        np.concatenate(
                            [
                                (zero if r is None else self._state[r][i])
                                for r in rids
                            ],
                            axis=1,
                        )
                        if B
                        else zero
                    )
            sp.set_metadata(bytes=sum(int(x.nbytes) for x in leaves))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def read_dense(self, rid: str, s_max: Optional[int] = None):
        """Dense single-sequence cache for ``rid`` — shaped like
        ``init_cache(cfg, 1, s_max)`` with every written position equal to
        the page contents bit-for-bit (the property-test contract)."""
        length = self.seq_len[rid]
        s_max = length if s_max is None else s_max
        if s_max < length:
            raise ValueError("s_max shorter than written length")
        ps = self.page_size
        leaves = []
        for i in range(self.num_leaves):
            if self.paged[i]:
                a = self._arenas[i]
                repeat, feat = a.shape[1], a.shape[3:]
                out = np.zeros(
                    (repeat, 1, s_max) + feat, self._dtypes[i]
                )
                for j, page in enumerate(self.page_table[rid]):
                    w = min(ps, length - j * ps)
                    if w <= 0:
                        break
                    out[:, 0, j * ps : j * ps + w] = a[page, :, :w]
                leaves.append(out)
            else:
                leaves.append(self._state[rid][i].copy())
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
        slots = []
        for p in pt:
            if self._ref[p] > 1:
                slots.append({"page": p, "blobs": None})
            else:
                slots.append({
                    "page": None,
                    "blobs": [
                        None if a is None else a[p].copy()
                        for a in self._arenas
                    ],
                })
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
            for a, blob in zip(self._arenas, slot["blobs"]):
                if a is not None:
                    a[p] = blob
            table.append(p)
        self.page_table[rid] = table
        self.seq_len[rid] = park["seq_len"]
        self._state[rid] = park["state"]
        del self._parked[rid]
        return True

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
        released = 0
        for slot in self._parked[rid]["slots"]:
            page = slot["page"]
            if page is None:
                continue
            slot["blobs"] = [
                None if a is None else a[page].copy() for a in self._arenas
            ]
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
