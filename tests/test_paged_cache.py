"""Property-based tests for the paged KV cache (serve/paged_cache.py).

Random alloc/append/free/evict/resume interleavings must never leak or
double-allocate pages, and every page-table read must equal a dense
reference cache maintained in parallel BIT-FOR-BIT — the contract that
makes continuous-batching decode token-identical to the single-sequence
path.  Runs under real hypothesis when installed, else the deterministic
fixed-seed sampler in ``_hypothesis_stub``.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # keep deterministic sampling without hypothesis
    from _hypothesis_stub import given, settings, st

import jax

from repro.configs import get_config
from repro.models.transformer import init_cache
from repro.serve.paged_cache import PageAllocator, PagedKVCache, PagesExhausted


# ---------------------------------------------------------------------- #
# allocator
# ---------------------------------------------------------------------- #
@settings(max_examples=20)
@given(seed=st.integers(0, 10_000), num_pages=st.integers(1, 24))
def test_allocator_never_leaks_or_double_allocates(seed, num_pages):
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(num_pages)
    held = []  # list of page lists we own
    for _ in range(60):
        alloc.check()
        if held and rng.random() < 0.4:
            pages = held.pop(int(rng.integers(len(held))))
            alloc.free(pages)
        else:
            n = int(rng.integers(0, num_pages + 2))
            got = alloc.alloc(n)
            if n > alloc.num_free + (0 if got is None else n):
                assert got is None
            if got is None:
                continue
            assert len(got) == n
            held.append(got)
        # no page is owned twice
        flat = [p for ps in held for p in ps]
        assert len(flat) == len(set(flat))
        assert alloc.num_held == len(flat)
    for ps in held:
        alloc.free(ps)
    assert alloc.num_free == num_pages
    alloc.check()


def test_allocator_rejects_double_free_and_oversize():
    a = PageAllocator(4)
    got = a.alloc(3)
    assert a.alloc(2) is None and a.num_free == 1  # atomic: nothing taken
    a.free(got)
    with pytest.raises(ValueError):
        a.free(got[:1])
    a.check()


# ---------------------------------------------------------------------- #
# paged cache vs dense reference
# ---------------------------------------------------------------------- #
def _random_prefill_cache(cfg, length, rng):
    """A fake dense prefill result: init_cache(cfg, 1, length) with random
    contents in every leaf."""
    cache = init_cache(cfg, 1, length)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    filled = [
        rng.standard_normal(leaf.shape).astype(leaf.dtype) for leaf in leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, filled), filled


def _random_slices(kv, rng):
    """One decode step's write for one lane, shaped as the scheduler's
    lane decoder emits it."""
    out = []
    for i in range(kv.num_leaves):
        if kv.paged[i]:
            a = kv._arenas[i]
            out.append(
                rng.standard_normal((a.shape[1],) + a.shape[3:]).astype(
                    kv._dtypes[i]
                )
            )
        else:
            out.append(
                rng.standard_normal(kv._state_shape[i]).astype(kv._dtypes[i])
            )
    return out


class _DenseRef:
    """Parallel dense reference: per-sequence leaf arrays grown position
    by position, bit-for-bit what PagedKVCache must reproduce."""

    def __init__(self, kv):
        self.kv = kv
        self.seqs = {}

    def prefill(self, rid, flat, length):
        self.seqs[rid] = {
            "len": length,
            "leaves": [
                leaf[:, :, :length].copy() if self.kv.paged[i] else leaf.copy()
                for i, leaf in enumerate(flat)
            ],
        }

    def append(self, rid, slices, position):
        s = self.seqs[rid]
        for i, sl in enumerate(slices):
            if self.kv.paged[i]:
                cur = s["leaves"][i]
                if position >= cur.shape[2]:
                    pad = np.zeros(
                        cur.shape[:2] + (position + 1 - cur.shape[2],) + cur.shape[3:],
                        cur.dtype,
                    )
                    cur = np.concatenate([cur, pad], axis=2)
                cur[:, 0, position] = sl
                s["leaves"][i] = cur
            else:
                s["leaves"][i] = sl.copy()
        s["len"] = max(s["len"], position + 1)

    def check(self, rid):
        s = self.seqs[rid]
        got, _ = jax.tree_util.tree_flatten(self.kv.read_dense(rid))
        assert self.kv.seq_len[rid] == s["len"]
        for i, (g, r) in enumerate(zip(got, s["leaves"])):
            if self.kv.paged[i]:
                np.testing.assert_array_equal(
                    g[:, :, : s["len"]], r[:, :, : s["len"]], err_msg=f"leaf {i}"
                )
            else:
                np.testing.assert_array_equal(g, r, err_msg=f"leaf {i}")


@pytest.fixture(scope="module")
def gqa_cfg():
    return get_config("llama3.2-3b", reduced=True)


@pytest.fixture(scope="module")
def mamba_cfg():
    return get_config("mamba2-1.3b", reduced=True)


@settings(max_examples=5)
@given(seed=st.integers(0, 10_000), page_size=st.integers(2, 6))
def test_random_ops_match_dense_reference(gqa_cfg, seed, page_size):
    rng = np.random.default_rng(seed)
    max_len = 4 * page_size
    kv = PagedKVCache(gqa_cfg, num_pages=14, page_size=page_size, max_len=max_len)
    ref = _DenseRef(kv)
    live, parked, next_rid = [], [], 0
    for _ in range(50):
        kv.allocator.check()
        op = rng.random()
        if op < 0.35 or not live:
            P = int(rng.integers(1, max_len // 2 + 1))
            rid = f"q{next_rid}"
            if not kv.can_alloc(P) or kv.allocator.num_free < kv.pages_needed(P):
                continue
            assert kv.alloc_seq(rid, P)
            cache, flat = _random_prefill_cache(gqa_cfg, P, rng)
            kv.write_prefill(rid, cache, P)
            ref.prefill(rid, flat, P)
            live.append(rid)
            next_rid += 1
        elif op < 0.70:
            rid = live[int(rng.integers(len(live)))]
            posn = kv.seq_len[rid]
            if posn >= max_len or not kv.ensure_capacity(rid, posn + 1):
                continue
            sl = _random_slices(kv, rng)
            kv.append_token(rid, sl, posn)
            ref.append(rid, sl, posn)
        elif op < 0.82 and live:
            rid = live.pop(int(rng.integers(len(live))))
            kv.evict(rid)
            parked.append(rid)
        elif op < 0.90 and parked:
            rid = parked[int(rng.integers(len(parked)))]
            if kv.resume(rid):
                parked.remove(rid)
                live.append(rid)
                ref.check(rid)  # resume must be lossless
        elif live:
            rid = live.pop(int(rng.integers(len(live))))
            kv.free_seq(rid)
            del ref.seqs[rid]
        if live:
            ref.check(live[int(rng.integers(len(live)))])
    for rid in live:
        kv.free_seq(rid)
    for rid in parked:
        assert kv.resume(rid)
        ref.check(rid)
        kv.free_seq(rid)
    # nothing leaks
    assert kv.allocator.num_free == kv.allocator.num_pages
    kv.allocator.check()


@settings(max_examples=3)
@given(seed=st.integers(0, 10_000))
def test_state_leaves_roundtrip_mamba(mamba_cfg, seed):
    """Mamba conv/ssm state has no sequence axis: it must classify as
    per-sequence state and survive evict/resume bit-for-bit."""
    rng = np.random.default_rng(seed)
    kv = PagedKVCache(mamba_cfg, num_pages=8, page_size=4, max_len=16)
    assert any(not p for p in kv.paged), "mamba must have state leaves"
    ref = _DenseRef(kv)
    assert kv.alloc_seq("m0", 5)
    cache, flat = _random_prefill_cache(mamba_cfg, 5, rng)
    kv.write_prefill("m0", cache, 5)
    ref.prefill("m0", flat, 5)
    for posn in range(5, 9):
        sl = _random_slices(kv, rng)
        kv.append_token("m0", sl, posn)
        ref.append("m0", sl, posn)
    ref.check("m0")
    kv.evict("m0")
    assert kv.is_parked("m0")
    assert kv.resume("m0")
    ref.check("m0")
    kv.free_seq("m0")
    kv.allocator.check()


def test_gather_pads_with_zero_page(gqa_cfg):
    """The batch view for a short sequence is zero beyond its pages — the
    dense-reference property the masked decode relies on."""
    kv = PagedKVCache(gqa_cfg, num_pages=8, page_size=4, max_len=16)
    rng = np.random.default_rng(0)
    assert kv.alloc_seq("a", 3)
    cache, _ = _random_prefill_cache(gqa_cfg, 3, rng)
    kv.write_prefill("a", cache, 3)
    view = kv.view(["a", None])
    leaves, _ = jax.tree_util.tree_flatten(view)
    ref_leaves, _ = jax.tree_util.tree_flatten(kv.read_dense("a", s_max=16))
    for i, (v, r) in enumerate(zip(leaves, ref_leaves)):
        if kv.paged[i]:
            assert v.shape[1] == 2 and v.shape[2] == 16
            np.testing.assert_array_equal(v[:, :1], r, err_msg=f"leaf {i}")
            assert not np.any(v[:, 1])  # empty lane all zeros
            assert not np.any(v[:, 0, 3:])  # beyond written length
        else:
            np.testing.assert_array_equal(v[:, :1], r, err_msg=f"leaf {i}")
    kv.free_seq("a")


def test_capacity_failures_are_clean(gqa_cfg):
    kv = PagedKVCache(gqa_cfg, num_pages=4, page_size=4, max_len=16)
    assert kv.alloc_seq("a", 12)  # 3 pages
    assert not kv.alloc_seq("b", 8)  # needs 2, only 1 free — clean refusal
    assert "b" not in kv.page_table and kv.allocator.num_free == 1
    assert kv.alloc_seq("c", 4)
    assert not kv.ensure_capacity("c", 8)  # growth refusal leaves state
    assert len(kv.page_table["c"]) == 1
    with pytest.raises(ValueError):
        kv.alloc_seq("d", 17)  # beyond max_len
    with pytest.raises(ValueError):
        PagedKVCache(gqa_cfg, num_pages=2, page_size=4, max_len=16)
    kv.free_seq("a")
    kv.free_seq("c")
    kv.allocator.check()


def test_encdec_rejected():
    cfg = get_config("seamless-m4t-large-v2", reduced=True)
    with pytest.raises(ValueError):
        PagedKVCache(cfg, num_pages=4, page_size=4, max_len=8)


# ---------------------------------------------------------------------- #
# bugfix sweep: zero-token allocs, zero-init bandwidth, typed exhaustion,
# finish-while-parked
# ---------------------------------------------------------------------- #
def test_pages_needed_zero_holds_no_page(gqa_cfg):
    """pages_needed(0) used to return 1, so a zero-token allocation held a
    page forever; it must hold nothing and grow only when asked to."""
    kv = PagedKVCache(gqa_cfg, num_pages=4, page_size=4, max_len=16)
    assert kv.pages_needed(0) == 0
    assert kv.alloc_seq("z", 0)
    assert kv.page_table["z"] == []
    assert kv.allocator.num_held == 0
    assert kv.ensure_capacity("z", 1)
    assert len(kv.page_table["z"]) == 1
    kv.free_seq("z")
    assert kv.allocator.num_free == 4
    kv.allocator.check()


def test_prefill_path_does_not_double_zero(gqa_cfg):
    """alloc_seq(zero=False) + write_prefill must touch each page exactly
    once (the write, plus one partial-tail memset) — no full-page zeroing
    of pages the prefill immediately overwrites — while the gathered view
    stays zero beyond the written length."""
    kv = PagedKVCache(gqa_cfg, num_pages=8, page_size=4, max_len=16)
    rng = np.random.default_rng(0)
    assert kv.alloc_seq("a", 10, zero=False)
    assert kv.zero_writes == 0
    cache, _ = _random_prefill_cache(gqa_cfg, 10, rng)
    kv.write_prefill("a", cache, 10)
    assert kv.zero_writes == 0
    view = kv.view(["a"])
    leaves, _ = jax.tree_util.tree_flatten(view)
    for i, v in enumerate(leaves):
        if kv.paged[i]:
            assert not np.any(v[:, 0, 10:]), f"leaf {i} dirty beyond prefill"
    # the default (decode-growth) path still zeroes recycled pages
    assert kv.alloc_seq("b", 3)
    assert kv.zero_writes == 1
    kv.free_seq("a")
    kv.free_seq("b")
    kv.allocator.check()


def test_exhaustion_is_typed(gqa_cfg):
    """Capacity failures inside writes raise PagesExhausted (a RuntimeError
    the scheduler catches to evict per policy), never a bare RuntimeError."""
    kv = PagedKVCache(gqa_cfg, num_pages=4, page_size=4, max_len=16)
    rng = np.random.default_rng(0)
    assert kv.alloc_seq("a", 16)  # whole pool
    cache, _ = _random_prefill_cache(gqa_cfg, 16, rng)
    kv.write_prefill("a", cache, 16)
    assert kv.alloc_seq("b", 0)
    with pytest.raises(PagesExhausted):
        kv.append_token("b", _random_slices(kv, rng), 0)
    with pytest.raises(PagesExhausted):
        kv.write_prefill("b", cache, 4)
    assert issubclass(PagesExhausted, RuntimeError)
    # the failed writes left "b" consistent: still zero pages, still usable
    assert kv.page_table["b"] == [] and kv.seq_len["b"] == 0
    kv.free_seq("a")
    kv.append_token("b", _random_slices(kv, rng), 0)
    kv.free_seq("b")
    kv.allocator.check()


def test_finish_while_parked_does_not_double_free(gqa_cfg):
    """A request evicted and then finished (client cancel, max-tokens cut)
    must release its parked copies without touching the allocator twice."""
    kv = PagedKVCache(gqa_cfg, num_pages=8, page_size=4, max_len=16)
    rng = np.random.default_rng(1)
    assert kv.alloc_seq("a", 6)
    cache, _ = _random_prefill_cache(gqa_cfg, 6, rng)
    kv.write_prefill("a", cache, 6)
    kv.evict("a")
    assert kv.is_parked("a")
    assert kv.allocator.num_held == 0  # private pages freed at evict
    kv.free_seq("a")  # finish-while-parked: drops the parked copies
    assert not kv.is_parked("a")
    assert kv.allocator.num_free == 8
    with pytest.raises(KeyError):
        kv.free_seq("a")  # second finish is a real bug, loudly
    kv.allocator.check()


# ---------------------------------------------------------------------- #
# prefix sharing + copy-on-write
# ---------------------------------------------------------------------- #
def _det_cache(cfg, kv, tokens):
    """Dense prefill cache whose paged-leaf contents are a pure function of
    (leaf, position, token id): two prompts agreeing on a token prefix get
    bit-identical content over it, so a shared page (written by another
    request) is indistinguishable from a recomputed one — exactly the
    serving situation the COW property test models."""
    P = len(tokens)
    cache = init_cache(cfg, 1, P)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    out = []
    for i, leaf in enumerate(leaves):
        arr = np.zeros(leaf.shape, leaf.dtype)
        if kv.paged[i]:
            for pos in range(P):
                r = np.random.default_rng(
                    (i * 7919 + pos) * 65537 + int(tokens[pos])
                )
                arr[:, 0, pos] = r.standard_normal(
                    arr.shape[:1] + arr.shape[3:]
                ).astype(arr.dtype)
        else:
            r = np.random.default_rng(hash(tuple(int(t) for t in tokens)) % 2**32)
            arr[...] = r.standard_normal(arr.shape).astype(arr.dtype)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out), out


def test_prefix_sharing_allocates_prefix_once_and_cow_isolates(gqa_cfg):
    kv = PagedKVCache(
        gqa_cfg, num_pages=12, page_size=4, max_len=16, prefix_sharing=True
    )
    rng = np.random.default_rng(3)
    tokens = rng.integers(1, 50, 12)
    cache, _ = _det_cache(gqa_cfg, kv, tokens)
    assert kv.alloc_seq("r1", 12, tokens=tokens, zero=False)
    assert kv.seq_len["r1"] == 0  # index empty: nothing shared yet
    kv.write_prefill("r1", cache, 12)

    assert kv.alloc_seq("r2", 12, tokens=tokens, zero=False)
    # cap = (12-1)//4 = 2 pages shared; the last-token page is recomputed
    assert kv.seq_len["r2"] == 8
    assert kv.page_table["r2"][:2] == kv.page_table["r1"][:2]
    assert kv.allocator.num_held == 4  # 3 (r1) + 1 (r2 tail), not 6
    assert kv.share_stats["prefix_hits"] == 1
    assert kv.share_stats["pages_shared"] == 2
    kv.write_prefill("r2", cache, 12, start=8)
    ref1, _ = jax.tree_util.tree_flatten(kv.read_dense("r1"))
    ref2, _ = jax.tree_util.tree_flatten(kv.read_dense("r2"))
    for a, b in zip(ref1, ref2):
        np.testing.assert_array_equal(a, b)

    # write INTO the shared span: r2 gets a private copy, r1 is untouched
    sl = _random_slices(kv, rng)
    kv.append_token("r2", sl, 5)  # page 1, refcount 2 -> COW
    assert kv.share_stats["cow_copies"] == 1
    assert kv.page_table["r2"][1] != kv.page_table["r1"][1]
    got1, _ = jax.tree_util.tree_flatten(kv.read_dense("r1"))
    for a, b in zip(got1, ref1):
        np.testing.assert_array_equal(a, b)  # sibling bit-identical
    got2, _ = jax.tree_util.tree_flatten(kv.read_dense("r2"))
    for i, (a, b) in enumerate(zip(got2, ref2)):
        if kv.paged[i]:
            np.testing.assert_array_equal(a[:, 0, 5], sl[i])

    # eviction keeps the still-shared page resident by reference
    kv.evict("r2")
    assert kv.parked_shared_pages("r2") == 1  # page 0 only (page 1 COWed)
    assert kv.resume("r2")
    got2b, _ = jax.tree_util.tree_flatten(kv.read_dense("r2"))
    for a, b in zip(got2b, got2):
        np.testing.assert_array_equal(a, b)

    # freeing the registrant keeps the page alive under r2's refcount
    p0 = kv.page_table["r1"][0]
    kv.free_seq("r1")
    assert kv.page_table["r2"][0] == p0
    kv.free_seq("r2")
    assert kv.allocator.num_free == 12
    kv.allocator.check()


def test_release_parked_shared_frees_pages(gqa_cfg):
    """The terminal-pressure escape valve: a parked sequence's retained
    shared refs demote to host copies (freeing sole-owned pages) and the
    sequence still resumes bit-for-bit."""
    kv = PagedKVCache(
        gqa_cfg, num_pages=8, page_size=4, max_len=16, prefix_sharing=True
    )
    tokens = np.arange(1, 13)
    cache, _ = _det_cache(gqa_cfg, kv, tokens)
    assert kv.alloc_seq("w", 12, tokens=tokens, zero=False)
    kv.write_prefill("w", cache, 12)
    assert kv.alloc_seq("s", 12, tokens=tokens, zero=False)
    kv.write_prefill("s", cache, 12, start=8)
    want, _ = jax.tree_util.tree_flatten(kv.read_dense("s"))
    kv.evict("s")
    kv.free_seq("w")  # shared pages now held only by the parked "s"
    held_before = kv.allocator.num_held
    assert kv.release_parked_shared("s") == 2
    assert kv.allocator.num_held < held_before  # refcount hit 0 -> freed
    assert kv.resume("s")
    got, _ = jax.tree_util.tree_flatten(kv.read_dense("s"))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    kv.free_seq("s")
    assert kv.allocator.num_free == 8
    kv.allocator.check()


@settings(max_examples=4)
@given(seed=st.integers(0, 10_000))
def test_shared_prefix_random_ops_match_dense_reference(gqa_cfg, seed):
    """The COW analogue of the paged-vs-dense property: random
    interleavings of {submit-with-shared-prefix, decode-append, overwrite
    (COW trigger), evict, resume, finish, finish-while-parked} keep every
    per-request view bit-identical to an unshared dense reference."""
    rng = np.random.default_rng(seed)
    ps = 4
    max_len = 24
    kv = PagedKVCache(
        gqa_cfg, num_pages=40, page_size=ps, max_len=max_len,
        prefix_sharing=True,
    )
    ref = _DenseRef(kv)
    vocab = 40
    families = [rng.integers(1, vocab, 8), rng.integers(1, vocab, 12)]
    live, parked, n = [], [], 0
    for _ in range(60):
        kv.allocator.check()
        op = rng.random()
        if op < 0.30 or not live:
            fam = families[int(rng.integers(len(families)))]
            suffix = rng.integers(1, vocab, int(rng.integers(1, 5)))
            tokens = np.concatenate([fam, suffix])
            P = len(tokens)
            rid = f"s{n}"
            if not kv.alloc_seq(rid, P, tokens=tokens, zero=False):
                continue
            n += 1
            start = kv.seq_len[rid]
            cache, flat = _det_cache(gqa_cfg, kv, tokens)
            kv.write_prefill(rid, cache, P, start=start)
            ref.prefill(rid, flat, P)
            live.append(rid)
        elif op < 0.55:
            rid = live[int(rng.integers(len(live)))]
            posn = kv.seq_len[rid]
            if posn >= max_len:
                continue
            sl = _random_slices(kv, rng)
            try:
                kv.append_token(rid, sl, posn)
            except PagesExhausted:
                continue
            ref.append(rid, sl, posn)
        elif op < 0.65:
            # overwrite a position inside the (possibly shared) span:
            # COW must keep every sibling's view bit-identical
            rid = live[int(rng.integers(len(live)))]
            posn = int(rng.integers(0, kv.seq_len[rid]))
            sl = _random_slices(kv, rng)
            try:
                kv.append_token(rid, sl, posn)
            except PagesExhausted:
                continue
            ref.append(rid, sl, posn)
        elif op < 0.78:
            rid = live.pop(int(rng.integers(len(live))))
            kv.evict(rid)
            parked.append(rid)
        elif op < 0.86 and parked:
            rid = parked[int(rng.integers(len(parked)))]
            if kv.resume(rid):
                parked.remove(rid)
                live.append(rid)
                ref.check(rid)  # resume must be lossless
        elif op < 0.93 and parked:
            rid = parked.pop(int(rng.integers(len(parked))))
            kv.free_seq(rid)  # finish-while-parked
            del ref.seqs[rid]
        elif live:
            rid = live.pop(int(rng.integers(len(live))))
            kv.free_seq(rid)
            del ref.seqs[rid]
        for check_rid in live:
            ref.check(check_rid)
    for rid in live:
        ref.check(rid)
        kv.free_seq(rid)
    for rid in parked:
        assert kv.resume(rid)
        ref.check(rid)
        kv.free_seq(rid)
    assert kv.allocator.num_free == kv.allocator.num_pages
    kv.allocator.check()
