"""Engine conformance suite for the block-paged KV cache.

Three layers of guarantees, checked bottom-up:

  * ``BlockAllocator`` / ``StateStore`` — free-list and slab-lifecycle
    invariants (no double allocation, no aliasing, conservation,
    all-or-nothing failure, stale state flagged until reset) under unit
    + property tests;
  * the paged decode path — bit-for-bit identical logits to the dense
    decode path, including through a *shuffled* page table, and the
    paged Pallas kernel against its oracle;
  * the ``ServeEngine`` paged scheduler — mid-decode joins produce the
    same tokens as a fresh dense run (the left-pad approximation the
    paged cache removes), eviction returns every block and state slab
    to their pools, and a request that does not fit either pool stays
    queued without crashing.

The engine guarantees run as a **cross-family conformance matrix**: the
``family_model`` fixture parametrizes them over transformer, pure-mamba,
xLSTM (mLSTM+sLSTM), and hybrid (attention+mamba, jamba-style) stacks —
one stream-pipeline substrate serving any network as a filter is the
paper's core claim, so every engine guarantee must hold for every model
family, not just attention.  (CI runs one matrix job per family via
``-k`` so a regression is attributable to its family in the Actions UI.)

``hypothesis`` is optional (mirrors tests/test_property.py): the
property tests skip without it, deterministic randomized fallbacks
always run.
"""
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import RECURRENT_FAMILIES
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serving import (BlockAllocator, CacheFullError, ServeEngine,
                           StateStore)

HAVE_HYPOTHESIS = importlib.util.find_spec("hypothesis") is not None

TINY = ModelConfig(
    arch_id="tiny-paged", family="dense", n_layers=2, d_model=32,
    n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
    norm="rmsnorm", mlp_act="swiglu", rope="rope",
    param_dtype="float32", compute_dtype="float32")


@pytest.fixture(scope="module")
def tiny_model():
    model = build_model(TINY)
    return model, model.init(jax.random.PRNGKey(0))


def _fresh_dense_tokens(model, params, prompt, max_new, capacity=64,
                        eos_id=None):
    """Oracle: the prompt served alone, dense prefill + dense decode."""
    logits, cache = model.prefill(params, jnp.asarray(prompt)[None],
                                  capacity=capacity, cache_dtype=jnp.float32)
    toks = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    while len(toks) < max_new and toks[-1] != eos_id:
        tok = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, cache = model.decode_step(params, cache, tok, jnp.int32(pos))
        toks.append(int(jnp.argmax(logits[0])))
        pos += 1
    return toks


# -- BlockAllocator -----------------------------------------------------------

def test_allocator_acquire_release_roundtrip():
    a = BlockAllocator(num_blocks=8, block_size=4)
    got = a.acquire(3)
    assert len(got) == len(set(got)) == 3
    assert a.n_free == 5 and a.n_live == 3
    assert all(a.ref(b) == 1 for b in got)
    a.release(got)
    assert a.n_free == 8 and a.n_live == 0


def test_allocator_full_is_all_or_nothing():
    a = BlockAllocator(num_blocks=4, block_size=2)
    a.acquire(3)
    before = a.n_free
    with pytest.raises(CacheFullError):
        a.acquire(2)                   # only 1 free
    assert a.n_free == before          # state untouched by the failure
    assert len(a.acquire(1)) == 1      # the last block is still available


def test_allocator_double_release_raises():
    a = BlockAllocator(num_blocks=4, block_size=2)
    (b,) = a.acquire(1)
    a.release([b])
    with pytest.raises(ValueError, match="double free"):
        a.release([b])
    with pytest.raises(ValueError):
        a.release([99])                # foreign block


def test_allocator_refcount_share_release():
    a = BlockAllocator(num_blocks=4, block_size=2)
    (b,) = a.acquire(1)
    a.share([b])
    a.share([b])
    assert a.ref(b) == 3
    assert a.n_shared == 1 and a.n_live == 1
    a.release([b])
    a.release([b])
    assert a.ref(b) == 1 and a.n_shared == 0
    assert a.n_free == 3               # still held by the last reference
    a.release([b])
    assert a.ref(b) == 0 and a.n_free == 4
    with pytest.raises(ValueError, match="share free"):
        a.share([b])                   # unregistered freed blocks: no refs


def test_allocator_content_table_roundtrip():
    from repro.serving import ROOT_DIGEST, chain_digest
    a = BlockAllocator(num_blocks=4, block_size=4)
    b0, b1 = a.acquire(2)
    toks0, toks1 = (1, 2, 3, 4), (5, 6, 7, 8)
    a.register(b0, ROOT_DIGEST, toks0)
    d0 = chain_digest(ROOT_DIGEST, toks0)
    a.register(b1, d0, toks1)
    assert a.lookup(ROOT_DIGEST, toks0) == b0
    assert a.lookup(d0, toks1) == b1
    assert a.lookup(ROOT_DIGEST, toks1) is None   # chain position matters
    # partial-tail match: a completed block whose page starts with the tail
    assert a.lookup_tail(d0, (5, 6)) == b1
    assert a.lookup_tail(d0, (5, 9)) is None
    assert a.n_table == 2
    # a registered block at refcount 0 is *retained*: its entry (and
    # KV) stays addressable for future prefix hits...
    a.release([b1])
    assert a.lookup(d0, toks1) == b1
    assert a.retained_blocks() == {b1}
    assert a.n_free == 3               # retained blocks count as free
    # ...and share() resurrects it off the free list
    a.share([b1])
    assert a.ref(b1) == 1 and a.n_retained == 0
    a.release([b0, b1])
    assert a.n_retained == 2 and a.n_table == 2
    # recycling is what finally unregisters — plain free blocks go
    # first, then retained blocks oldest-first (LRU)
    a.acquire(2)                       # the two never-registered blocks
    assert a.n_table == 2
    (got,) = a.acquire(1)
    assert got == b0                   # b0 was released before b1
    assert a.lookup(ROOT_DIGEST, toks0) is None
    assert a.lookup(d0, toks1) == b1


def test_allocator_register_guards():
    a = BlockAllocator(num_blocks=4, block_size=4)
    from repro.serving import ROOT_DIGEST
    (b,) = a.acquire(1)
    with pytest.raises(ValueError, match="full blocks"):
        a.register(b, ROOT_DIGEST, (1, 2))       # partial page
    with pytest.raises(ValueError, match="free block"):
        a.register(3, ROOT_DIGEST, (1, 2, 3, 4))  # not allocated
    # first writer wins: duplicate content does not steal the entry
    (b2,) = a.acquire(1)
    a.register(b, ROOT_DIGEST, (1, 2, 3, 4))
    a.register(b2, ROOT_DIGEST, (1, 2, 3, 4))
    assert a.lookup(ROOT_DIGEST, (1, 2, 3, 4)) == b
    assert a.registered_blocks() == {b}


def test_allocator_blocks_for():
    a = BlockAllocator(num_blocks=4, block_size=8)
    assert a.blocks_for(0) == 1        # a slot always owns >= 1 block
    assert a.blocks_for(8) == 1
    assert a.blocks_for(9) == 2


def _retain_n(a, n, start=0):
    """Acquire, register and release ``n`` blocks with distinct content
    so each lands on the retained list (oldest first)."""
    from repro.serving import ROOT_DIGEST
    blocks = a.acquire(n)
    for i, b in enumerate(blocks):
        a.register(b, ROOT_DIGEST,
                   tuple(range(start + i * a.block_size,
                               start + (i + 1) * a.block_size)))
        a.release([b])
    return blocks


def test_allocator_retain_cap_evicts_oldest():
    from repro.serving import ROOT_DIGEST
    a = BlockAllocator(num_blocks=8, block_size=2, retain_cap=2)
    blocks = _retain_n(a, 4)
    # only the 2 newest chains stay addressable; the oldest were retired
    # to the plain free list and unregistered
    assert a.n_retained == 2 and a.retained_blocks() == set(blocks[2:])
    assert a.n_retain_evictions == 2
    assert a.lookup(ROOT_DIGEST, (0, 1)) is None
    assert a.lookup(ROOT_DIGEST, (4, 5)) == blocks[2]
    # retention never costs capacity: every block is still allocatable
    assert a.n_free == a.num_blocks
    got = a.acquire(8)
    assert len(got) == 8 and a.n_table == 0


def test_allocator_retain_cap_zero_disables_retention():
    from repro.serving import ROOT_DIGEST
    a = BlockAllocator(num_blocks=4, block_size=2, retain_cap=0)
    _retain_n(a, 2)
    assert a.n_retained == 0 and a.n_table == 0
    assert a.lookup(ROOT_DIGEST, (0, 1)) is None
    assert a.n_free == 4


def test_allocator_retain_cap_spares_resurrected_blocks():
    a = BlockAllocator(num_blocks=8, block_size=2, retain_cap=1)
    (b0, b1) = _retain_n(a, 2)         # b0 retired by the cap, b1 retained
    a.share([b1])                      # resurrect: live again, not retained
    assert a.ref(b1) == 1 and a.n_retained == 0
    _retain_n(a, 1, start=100)         # a new retained block fits the cap
    assert a.n_retained == 1 and a.ref(b1) == 1
    a.release([b1])


def test_allocator_retain_ttl_expires_by_age():
    from repro.serving import ROOT_DIGEST
    now = [0.0]
    a = BlockAllocator(num_blocks=8, block_size=2, retain_ttl_s=10.0,
                       clock=lambda: now[0])
    (b0,) = _retain_n(a, 1)
    now[0] = 5.0
    (b1,) = _retain_n(a, 1, start=100)
    assert a.n_retained == 2
    now[0] = 11.0                      # b0 is 11s old, b1 only 6s
    a.acquire(0)                       # any allocator mutation sweeps
    assert a.retained_blocks() == {b1}
    assert a.lookup(ROOT_DIGEST, (0, 1)) is None
    assert a.lookup(ROOT_DIGEST, (100, 101)) == b1
    now[0] = 16.0
    a.acquire(0)
    assert a.n_retained == 0 and a.n_table == 0
    assert a.n_free == a.num_blocks


def test_allocator_sweep_expires_without_traffic():
    """Regression: TTL expiry used to piggyback on acquire()/release()
    only, so an idle allocator kept expired retained blocks (and their
    content-table entries) pinned forever.  ``sweep()`` must retire them
    with no allocation traffic at all."""
    from repro.serving import ROOT_DIGEST
    now = [0.0]
    a = BlockAllocator(num_blocks=8, block_size=2, retain_ttl_s=10.0,
                       clock=lambda: now[0])
    _retain_n(a, 2)
    assert a.n_retained == 2 and a.n_table == 2
    assert a.sweep() == 0              # nothing expired yet: no-op
    assert a.n_retained == 2
    now[0] = 11.0                      # both blocks are now 11s old
    assert a.sweep() == 2              # no acquire/release needed
    assert a.n_retained == 0 and a.n_table == 0
    assert a.lookup(ROOT_DIGEST, (0, 1)) is None
    assert a.n_free == a.num_blocks
    assert a.sweep() == 0              # idempotent on an empty list


def test_allocator_sweep_noop_without_ttl():
    a = BlockAllocator(num_blocks=4, block_size=2)
    _retain_n(a, 2)
    assert a.sweep() == 0              # no TTL configured: retain forever
    assert a.n_retained == 2


def test_allocator_retention_unbounded_by_default():
    a = BlockAllocator(num_blocks=6, block_size=2)
    _retain_n(a, 6)
    assert a.n_retained == 6 and a.n_retain_evictions == 0


def test_allocator_retain_param_guards():
    with pytest.raises(ValueError, match="retain_cap"):
        BlockAllocator(num_blocks=4, block_size=2, retain_cap=-1)
    with pytest.raises(ValueError, match="retain_ttl_s"):
        BlockAllocator(num_blocks=4, block_size=2, retain_ttl_s=0.0)


def _run_alloc_sequence(ops):
    """Shared property body for acquire/share/register/release
    interleavings.  ``ops`` is a list of (kind, x) with kind in 0..3:

      0: acquire x blocks (x mod 4 + 1);
      1: release a reference group picked by x;
      2: share a group picked by x (refcount + 1, later released);
      3: register a live block picked by x under a synthetic chain key.

    Invariants after every op: refcounts mirror a host-side model; every
    block is free xor live exactly once; a freed block is never
    releasable again; content-table entries never outlive their block.
    """
    from repro.serving import ROOT_DIGEST
    a = BlockAllocator(num_blocks=12, block_size=4)
    groups = []                        # each: list of blocks, one ref apiece
    refs: dict = {}                    # mirror refcounts
    n_keys = 0
    for kind, x in ops:
        if kind == 0:
            n = x % 4 + 1
            try:
                got = a.acquire(n)
            except CacheFullError:
                assert n > a.n_free    # only legitimate overflow raises
                continue
            assert not set(got) & set(refs), "double allocation"
            for b in got:
                refs[b] = 1
            groups.append(got)
        elif kind == 1 and groups:
            g = groups.pop(x % len(groups))
            a.release(g)
            for b in g:
                refs[b] -= 1
                if refs[b] == 0:
                    del refs[b]
        elif kind == 2 and groups:
            g = list(groups[x % len(groups)])
            a.share(g)
            for b in g:
                refs[b] += 1
            groups.append(g)           # the extra refs get released too
        elif kind == 3 and refs:
            b = sorted(refs)[x % len(refs)]
            n_keys += 1
            a.register(b, ROOT_DIGEST,
                       (n_keys,) * a.block_size)   # unique synthetic page
        # conservation + refcount mirror + table liveness
        assert a.n_free + len(refs) == a.num_blocks
        assert a.n_live == len(refs)
        for b, r in refs.items():
            assert a.ref(b) == r
        assert a.n_shared == sum(1 for r in refs.values() if r > 1)
        # every table entry points at a live block or a retained one —
        # never at a recycled (rewritable) block
        assert a.registered_blocks() <= set(refs) | a.retained_blocks(), \
            "content-table entry outlived its block"
        assert not a.retained_blocks() & set(refs), \
            "retained block still has references"
    for g in groups:
        a.release(g)
    assert a.n_free == a.num_blocks and a.n_live == 0
    # drained: every surviving table entry is a retained block, and
    # recycling the whole pool unregisters them all
    assert a.n_table == a.n_retained
    a.release(a.acquire(a.num_blocks))
    assert a.n_table == 0 and a.n_retained == 0
    # fully drained: nothing is double-releasable
    with pytest.raises(ValueError):
        a.release([0])


def test_allocator_random_sequences_deterministic():
    rng = np.random.default_rng(7)
    for _ in range(20):
        ops = [(int(rng.integers(0, 4)), int(rng.integers(0, 16)))
               for _ in range(60)]
        _run_alloc_sequence(ops)


if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 15)),
                    max_size=80))
    def test_allocator_property_refcount_conservation(ops):
        _run_alloc_sequence(ops)


# -- StateStore: recurrent state slab lifecycle -------------------------------

def test_state_store_admit_evict_roundtrip():
    s = StateStore(num_slots=3)
    a, b = s.admit(10), s.admit(11)
    assert a != b
    assert s.n_free == 1 and s.n_live == 2
    assert s.slab_of(10) == a and s.owner_of(a) == 10
    assert s.slab_of(99) is None and s.owner_of(2) is None
    assert s.evict(10) == a
    assert s.owner_of(a) is None and s.slab_of(10) is None
    assert s.n_free == 2 and s.n_live == 1
    s.evict(11)
    assert s.n_free == 3 and s.n_live == 0


def test_state_store_full_is_all_or_nothing():
    s = StateStore(num_slots=1)
    s.admit(0)
    with pytest.raises(CacheFullError):
        s.admit(1)
    assert s.n_live == 1 and s.slab_of(1) is None  # store unchanged
    s.evict(0)
    assert s.admit(1) is not None                  # the slab is reusable


def test_state_store_lifecycle_guards():
    s = StateStore(num_slots=2)
    with pytest.raises(ValueError):
        StateStore(num_slots=0)
    slab = s.admit(7)
    with pytest.raises(ValueError, match="already holds"):
        s.admit(7)                                 # one slab per request
    s.evict(7)
    with pytest.raises(ValueError, match="double evict"):
        s.evict(7)
    with pytest.raises(ValueError, match="free slab"):
        s.mark_reset(slab)                         # reset needs an owner


def test_state_store_stale_until_reset():
    """Evicted state stays flagged until the next owner resets it —
    the host-side mirror of 'state never survives eviction'."""
    s = StateStore(num_slots=1)
    slab = s.admit(0)
    assert not s.is_stale(slab)                    # never-used slab is clean
    s.evict(0)
    assert s.is_stale(slab)                        # evictee's state resident
    assert s.admit(1) == slab
    assert s.is_stale(slab)                        # still dirty at handoff
    s.mark_reset(slab)
    assert not s.is_stale(slab)


def _run_state_sequence(ops):
    """Shared property body for admit/evict interleavings.  ``ops`` is a
    list of (kind, x): kind 0 admits a fresh request id, kind 1 evicts
    the x-th live request.  Invariants after every op: slab ownership
    mirrors a host-side model; no slab is ever owned by two requests;
    free + live == capacity; a full store fails all-or-nothing; a
    recycled slab that ever held state arrives flagged stale (state
    cannot silently survive eviction) and admit/mark_reset clears it.
    """
    store = StateStore(num_slots=6)
    live = {}                          # mirror: rid -> slab
    used = set()                       # slabs that ever held an owner
    next_rid = 0
    for kind, x in ops:
        if kind == 0:
            try:
                slab = store.admit(next_rid)
            except CacheFullError:
                assert store.n_free == 0   # only a full store may refuse
                continue
            assert 0 <= slab < store.num_slots
            assert slab not in live.values(), "slab aliased to two requests"
            if slab in used:
                assert store.is_stale(slab), \
                    "recycled slab handed over without a stale flag"
            store.mark_reset(slab)     # the engine zeroes on first step
            assert not store.is_stale(slab)
            live[next_rid] = slab
            used.add(slab)
            next_rid += 1
        elif kind == 1 and live:
            rid = sorted(live)[x % len(live)]
            slab = store.evict(rid)
            assert slab == live.pop(rid)
            assert store.owner_of(slab) is None
            assert store.is_stale(slab)
        # conservation + ownership mirror
        assert store.n_free + store.n_live == store.num_slots
        assert store.n_live == len(live)
        for rid, slab in live.items():
            assert store.slab_of(rid) == slab and store.owner_of(slab) == rid
        slabs = list(live.values())
        assert len(set(slabs)) == len(slabs), "slab leak / alias"
    for rid in list(live):
        store.evict(rid)
    assert store.n_free == store.num_slots and store.n_live == 0
    with pytest.raises(ValueError):
        store.evict(-1)                # fully drained: nothing evictable


def test_state_store_random_sequences_deterministic():
    rng = np.random.default_rng(17)
    for _ in range(20):
        ops = [(int(rng.integers(0, 2)), int(rng.integers(0, 16)))
               for _ in range(60)]
        _run_state_sequence(ops)


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 1), st.integers(0, 15)),
                    max_size=80))
    def test_state_store_property_slab_lifecycle(ops):
        _run_state_sequence(ops)


# -- paged_scatter: the pool layout, written position by position ------------

@pytest.mark.parametrize("bs,hd,T,layers", [
    (16, 64, 5, 2),    # folded: two positions a 128-lane row
    (8, 32, 7, 0),     # folded four to a row, one unstacked pool
    (4, 16, 3, 1),     # a page too small to fold: (bs, hd) as it is
])
def test_paged_scatter_writes_each_position(bs, hd, T, layers):
    """``paged_scatter`` puts token t of slot b at position lengths + t
    of head h's rows in block ``page_table[b, pos // bs]`` -- in the
    unfolded (bs, hd) view of the pool -- for real tokens only, and
    leaves every other element, other layers' included, as it was; a
    scale pool's rows likewise."""
    from repro.models.attention import paged_page_shape, paged_scatter
    rng = np.random.default_rng(bs + hd)
    KV, nb, P, B = 3, 12, 3, 4
    rows, lanes = paged_page_shape(bs, hd)
    lead = (layers,) if layers else ()
    layer = layers - 1 if layers else None
    pool = rng.standard_normal(lead + (nb, KV, rows, lanes)).astype(np.float32)
    scale = rng.standard_normal(lead + (nb, KV, bs)).astype(np.float32)
    vals = rng.standard_normal((B, T, KV, hd)).astype(np.float32)
    svals = rng.standard_normal((B, T, KV)).astype(np.float32)
    pt = rng.permutation(nb)[:B * P].reshape(B, P).astype(np.int32)
    lengths = np.array([0, bs - 1, 2 * bs - 2, P * bs - 2], np.int32)
    t_valid = np.array([T, T, 0, T], np.int32)
    args = [jnp.asarray(a) for a in (pt, lengths, t_valid)] + [layer]
    got = np.asarray(paged_scatter(jnp.asarray(pool), jnp.asarray(vals),
                                   *args))
    got_s = np.asarray(paged_scatter(jnp.asarray(scale), jnp.asarray(svals),
                                     *args))
    want = pool.reshape(lead + (nb, KV, bs, hd)).copy()
    want_s = scale.copy()
    at = (layer,) if layers else ()
    for b in range(B):
        for t in range(int(t_valid[b])):
            pos = int(lengths[b]) + t
            if pos < P * bs:         # past the page table: dropped
                want[at + (pt[b, pos // bs], slice(None), pos % bs)] = \
                    vals[b, t]
                want_s[at + (pt[b, pos // bs], slice(None), pos % bs)] = \
                    svals[b, t]
    np.testing.assert_array_equal(got, want.reshape(pool.shape))
    np.testing.assert_array_equal(got_s, want_s)


# -- paged decode vs dense decode: bit-for-bit --------------------------------

def _copy_dense_cache_to_pages(dense_cache, paged_cache, page_table,
                               block_size, slab=0):
    """Scatter a B=1 dense cache into the paged layout: K/V rows land in
    pool blocks per the page table, recurrent state (conv/ssm/mlstm/
    slstm leaves — anything that is not a "k"/"v" store) lands in slab
    row ``slab`` of its state array."""
    from jax.tree_util import DictKey, tree_map_with_path
    pt = np.asarray(page_table)[0]
    cap = len(pt) * block_size

    def cp(path, dense_leaf, paged_leaf):
        key = next((p.key for p in reversed(path)
                    if isinstance(p, DictKey)), None)
        src = np.asarray(dense_leaf)[:, 0]         # strip batch: (L, ...)
        out = np.asarray(paged_leaf).copy()
        if key in ("k", "v"):                      # (L, C, kv, hd) -> blocks
            # the pool's pages (L, nb, kv, rows, lanes), unfolded to
            # (L, nb, kv, bs, hd) -- a view, so writes land in ``out``
            pages = out.reshape(out.shape[:3] + (block_size, -1))
            for logical in range(min(cap, src.shape[1])):
                blk, off = pt[logical // block_size], logical % block_size
                pages[:, blk, :, off] = src[:, logical]
        else:                                      # state -> its slab row
            out[:, slab] = src
        return jnp.asarray(out)

    return tree_map_with_path(cp, dense_cache, paged_cache)


def test_paged_decode_logits_match_dense_bitwise(family_model):
    """Same cache content, shuffled physical placement: the paged read/
    write path must reproduce dense decode logits exactly, step after
    step (both caches evolve through their own insert paths) — for every
    model family, with recurrent state carried in a non-trivial slab.

    xLSTM is held to float32 reassociation instead of bit identity.  Its
    paged and dense T=1 steps run the same ``_mlstm_step`` and
    ``_slstm_step`` code, but each sits in a different compiled scan
    body (the paged one gathers and scatters its slab row), and XLA fuses
    the exponential-gated reductions differently in the two: the mLSTM
    matrix memory already differs by one ulp after the first layer.
    The logits stay within a few float32 ulps of their own scale (about
    3 ulps, not growing over the steps), and the greedy token must
    still agree at every step."""
    family, model, params = family_model
    bs, P = 4, 8                       # C = 32
    cap = bs * P
    prompt = np.array([5, 9, 3, 17, 30], np.int32)
    logits_d, dense = model.prefill(params, jnp.asarray(prompt)[None],
                                    capacity=cap, cache_dtype=jnp.float32)
    pt = jnp.asarray(
        np.random.default_rng(1).permutation(P).astype(np.int32)[None])
    slab = 2                           # state deliberately not at row 0
    kw = {"num_state_slots": 4} if model.has_recurrent_state() else {}
    paged = _copy_dense_cache_to_pages(
        dense, model.init_paged_cache(P, bs, dtype=jnp.float32, **kw),
        pt, bs, slab=slab)
    state_slots = jnp.asarray([slab], jnp.int32)
    lengths = jnp.asarray([len(prompt)], jnp.int32)
    ones = jnp.asarray([1], jnp.int32)
    tok = jnp.asarray([[int(jnp.argmax(logits_d[0]))]], jnp.int32)
    for step in range(8):
        ld, dense = model.decode_step(params, dense, tok,
                                      jnp.int32(int(lengths[0])))
        lp, paged = model.paged_step(params, paged, tok, pt, lengths, ones,
                                     state_slots)
        ld_np, lp_np = np.asarray(ld), np.asarray(lp)
        if family == "xlstm":
            ulp = np.finfo(np.float32).eps * np.abs(ld_np).max()
            assert np.abs(ld_np - lp_np).max() <= 16 * ulp, \
                f"{family}: paged/dense logits diverged at decode step {step}"
            assert ld_np.argmax(-1) == lp_np.argmax(-1)
        else:
            assert np.array_equal(ld_np, lp_np), \
                f"{family}: paged/dense logits diverged at decode step {step}"
        tok = jnp.asarray([[int(jnp.argmax(ld[0]))]], jnp.int32)
        lengths = lengths + 1


def test_chunked_prefill_invariant_to_chunk_size(family_model):
    """The same prompt prefilled in 1/3/16-token chunks must land in the
    same engine tokens — chunking is a scheduling choice, not semantics,
    for attention page tables and recurrent state slabs alike."""
    family, model, params = family_model
    prompt = np.arange(1, 11, dtype=np.int32)
    runs = []
    for chunk in (1, 3, 16):
        eng = ServeEngine(model, params, batch_size=2, capacity=32,
                          max_new_tokens=5, block_size=4,
                          prefill_chunk=chunk)
        assert eng.paged
        runs.append(list(eng.serve([prompt])[0].tokens))
    assert runs[0] == runs[1] == runs[2], family


# -- engine conformance: joins, eviction, cache-full --------------------------

def test_mid_decode_join_matches_fresh_dense_run(family_model):
    """The tentpole claim: a request joining mid-decode decodes at its
    *true* positions (no left-pad shift — which for recurrent layers
    would run pad tokens through the state recurrence), so its tokens
    equal a fresh dense run of that prompt alone."""
    family, model, params = family_model
    eng = ServeEngine(model, params, batch_size=2, capacity=32,
                      max_new_tokens=8, block_size=4, prefill_chunk=4)
    assert eng.paged, f"{family} fell back to the dense engine"
    rng = np.random.default_rng(3)
    first = rng.integers(1, TINY.vocab_size, 6).astype(np.int32)
    eng.submit(first)
    for _ in range(4):                 # decode well past the join point
        eng.step()
    late = rng.integers(1, TINY.vocab_size, 9).astype(np.int32)
    eng.submit(late)
    results = []
    while eng.has_work:
        results += eng.step()
    assert eng.n_joins == 1
    by_id = {r.request_id: list(r.tokens) for r in results}
    assert by_id[0] == _fresh_dense_tokens(model, params, first, 8), family
    assert by_id[1] == _fresh_dense_tokens(model, params, late, 8), family


def test_concurrent_slots_each_match_fresh_runs(family_model):
    family, model, params = family_model
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, TINY.vocab_size, n).astype(np.int32)
               for n in (5, 9, 3, 7, 12)]
    eng = ServeEngine(model, params, batch_size=2, capacity=32,
                      max_new_tokens=6, block_size=4, prefill_chunk=4)
    res = eng.serve(prompts)
    assert [r.request_id for r in res] == [0, 1, 2, 3, 4]
    for p, r in zip(prompts, res):
        assert list(r.tokens) == _fresh_dense_tokens(model, params, p, 6), \
            family
    assert eng.n_prefill_chunks > eng.n_prefills == 5  # chunked, not one-shot


def test_eviction_frees_all_blocks(family_model):
    """Eviction must return every resource to its pool: KV blocks,
    reservations, and — for recurrent families — state slabs."""
    family, model, params = family_model
    eng = ServeEngine(model, params, batch_size=2, capacity=32,
                      max_new_tokens=4, block_size=4, prefill_chunk=4)
    total = eng.allocator.num_blocks
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, TINY.vocab_size, n).astype(np.int32)
               for n in (11, 4, 6)]
    eng.serve(prompts)
    assert eng.n_evictions == 3
    assert eng.allocator.n_free == total
    assert eng.allocator.n_live == 0
    assert eng._reserved == 0
    if family in RECURRENT_FAMILIES:
        assert eng.state_store is not None
        assert eng.state_store.n_live == 0
        assert eng.state_store.n_free == eng.num_state_slots
    else:
        assert eng.state_store is None


def test_loop_stats_count_attention_pages(tiny_model):
    """``loop_stats()`` names the attention path (the jnp one on CPU)
    and counts, per device step, the pages each working slot holds
    after it against the page table's capacity.  A 9-token prompt in
    8-token chunks, then 3 decode steps, 4-token pages: extents 8, 9,
    10, 11, 12 are 2, 3, 3, 3, 3 pages over 5 steps of 8 pages."""
    model, params = tiny_model
    eng = ServeEngine(model, params, batch_size=1, capacity=32,
                      max_new_tokens=4, block_size=4, prefill_chunk=8,
                      burst=8)
    eng.serve([np.arange(1, 10, dtype=np.int32)])
    stats = eng.loop_stats()
    assert stats["attn_kernel"] == "jnp"
    assert stats["n_device_steps"] == 5
    assert stats["n_attn_pages_live"] == 2 + 3 + 3 + 3 + 3
    assert stats["n_attn_pages_capacity"] == 5 * 8


def test_blocks_freed_as_each_request_finishes(tiny_model):
    """Pool usage must shrink the moment a slot is evicted, not at
    drain: that is what lets new requests join mid-decode."""
    model, params = tiny_model
    eng = ServeEngine(model, params, batch_size=2, capacity=32,
                      max_new_tokens=3, block_size=4, prefill_chunk=8)
    short = np.array([2, 3], np.int32)
    long = np.arange(1, 13, dtype=np.int32)
    eng.submit(short)
    eng.submit(long)
    in_flight_free = None
    while eng.has_work:
        done = eng.step()
        if done and eng.n_active == 1 and in_flight_free is None:
            in_flight_free = eng.allocator.n_free
    assert in_flight_free is not None
    # after the short request finished, only the long one's blocks remain
    assert in_flight_free > 0
    assert eng.allocator.n_free == eng.allocator.num_blocks


def test_engine_idle_step_sweeps_expired_retention(tiny_model):
    """Regression: an idle server never retired TTL-expired retained
    blocks.  Expiry was only checked inside acquire()/release(), so with
    no new traffic the retained set (and its content-table entries)
    stayed pinned past its TTL indefinitely.  ``ServeEngine.step()``
    must now sweep on its periodic path even when there is no work."""
    model, params = tiny_model
    now = [0.0]
    eng = ServeEngine(model, params, batch_size=2, capacity=32,
                      max_new_tokens=4, block_size=4, share_prefix=True,
                      retain_ttl_s=10.0)
    eng.allocator._clock = lambda: now[0]
    rng = np.random.default_rng(21)
    eng.serve([rng.integers(1, TINY.vocab_size, 9).astype(np.int32)])
    assert eng.allocator.n_retained > 0     # prefix pages were retained
    assert eng.allocator.n_table > 0
    now[0] = 11.0                           # past the TTL, server idle
    assert not eng.has_work
    assert eng.step() == []                 # pure idle tick
    assert eng.allocator.n_retained == 0    # ...still sweeps
    assert eng.allocator.n_table == 0
    assert eng.allocator.n_free == eng.allocator.num_blocks


def _check_pool_invariants(eng):
    """Accounting identities that must hold at every observable point."""
    s = eng.pool_stats()
    for key in ("num_blocks", "n_free", "n_live", "n_shared", "n_private",
                "n_retained", "n_table", "n_reserved", "bytes_per_block",
                "pool_bytes"):
        assert s[key] >= 0, (key, s)
    assert eng.allocator.n_retain_evictions >= 0
    # n_free counts retained blocks (they are reclaimable), so the pool
    # partitions as: plain-free + retained + live == everything
    assert (s["n_free"] - s["n_retained"]) >= 0, s
    assert (s["n_free"] - s["n_retained"]) + s["n_retained"] + s["n_live"] \
        == s["num_blocks"], s
    assert s["n_private"] == s["n_live"] - s["n_shared"], s
    assert s["pool_bytes"] == s["bytes_per_block"] * s["num_blocks"], s
    ls = eng.loop_stats()
    for k, v in ls.items():
        if isinstance(v, (int, np.integer)):
            assert v >= 0, (k, ls)


def test_pool_accounting_invariants_under_churn(tiny_model):
    """Property: through admission, prefix sharing, COW forks, a
    mid-flight preempt+restore, and final drain, the pool partition
    (plain-free + retained + live == num_blocks) and every counter stay
    consistent at each step boundary."""
    model, params = tiny_model
    eng = ServeEngine(model, params, batch_size=2, capacity=32,
                      max_new_tokens=6, block_size=4, prefill_chunk=4,
                      share_prefix=True)
    rng = np.random.default_rng(17)
    shared = rng.integers(1, TINY.vocab_size, 8).astype(np.int32)
    prompts = [shared,                       # seeds the prefix table
               np.concatenate([shared, [3]]).astype(np.int32),  # shares+forks
               rng.integers(1, TINY.vocab_size, 5).astype(np.int32),
               np.concatenate([shared, [7, 9]]).astype(np.int32)]
    rids = [eng.submit(p, lane="batch") for p in prompts]
    _check_pool_invariants(eng)
    preempted = False
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
        _check_pool_invariants(eng)
        if not preempted and steps >= 2:
            # preempt whichever slot is still running (if any): spills
            # its pages to the queue-side and must keep the books clean
            live = [r for r in rids
                    if any(sl is not None and sl.rid == r
                           for sl in eng._slots)]
            if live:
                eng.preempt(live[-1])
                preempted = True
                _check_pool_invariants(eng)
        assert steps < 400, "engine failed to drain"
    assert preempted, "churn test never exercised preemption"
    _check_pool_invariants(eng)
    s = eng.pool_stats()
    assert s["n_live"] == 0 and eng._reserved == 0
    assert s["n_free"] == s["num_blocks"]


def test_bytes_per_block_consistent_across_kv_dtypes(tiny_model):
    """bytes_per_block must track the storage dtype exactly: f32 is 2x
    bf16, and int8 (values + per-row f32 scales) buys at least the 2x
    capacity the quantization exists for."""
    model, params = tiny_model
    bpb = {}
    for kv_dtype in (None, "bf16", "int8"):
        eng = ServeEngine(model, params, batch_size=2, capacity=32,
                          max_new_tokens=4, block_size=4,
                          kv_dtype=kv_dtype)
        s = eng.pool_stats()
        assert s["bytes_per_block"] == eng.kv_bytes_per_block()
        bpb[kv_dtype] = s["bytes_per_block"]
        assert s["kv_dtype"] == ("f32" if kv_dtype is None else kv_dtype)
    assert bpb[None] == 2 * bpb["bf16"]
    assert bpb[None] >= 2 * bpb["int8"]


def test_cache_full_request_stays_queued(family_model):
    """A pool sized for one worst-case request at a time: the second
    request must wait (no crash, no partial admission) and still run to
    the correct tokens once the first evicts."""
    family, model, params = family_model
    # worst case per request: ceil((8 prompt + 4 new) / 4) = 3 blocks
    eng = ServeEngine(model, params, batch_size=2, capacity=16,
                      max_new_tokens=4, block_size=4, num_blocks=3,
                      prefill_chunk=4)
    rng = np.random.default_rng(9)
    a = rng.integers(1, TINY.vocab_size, 8).astype(np.int32)
    b = rng.integers(1, TINY.vocab_size, 8).astype(np.int32)
    res = eng.serve([a, b])
    assert len(res) == 2
    assert eng.n_joins == 0            # b could only start after a evicted
    for p, r in zip((a, b), res):
        assert list(r.tokens) == _fresh_dense_tokens(model, params, p, 4,
                                                     capacity=32), family
    assert eng.allocator.n_free == eng.allocator.num_blocks


def test_state_slots_full_request_stays_queued(family_model):
    """Recurrent families have a second exhaustible pool: with a single
    state slab, the second request must stay queued — all-or-nothing
    across both pools — then run correctly on the recycled (and reset)
    slab once the first evicts."""
    family, model, params = family_model
    if family not in RECURRENT_FAMILIES:
        pytest.skip("transformer stacks carry no recurrent state")
    eng = ServeEngine(model, params, batch_size=2, capacity=32,
                      max_new_tokens=4, block_size=4, prefill_chunk=4,
                      num_state_slots=1)
    rng = np.random.default_rng(13)
    a = rng.integers(1, TINY.vocab_size, 7).astype(np.int32)
    b = rng.integers(1, TINY.vocab_size, 5).astype(np.int32)
    res = eng.serve([a, b])
    assert len(res) == 2
    assert eng.n_joins == 0            # blocks were free; only slabs gated
    assert eng.allocator.num_blocks > 6  # the block pool was never the limit
    for p, r in zip((a, b), res):
        assert list(r.tokens) == _fresh_dense_tokens(model, params, p, 4), \
            family                     # b is clean on the recycled slab
    assert eng.state_store.n_free == 1 and eng.state_store.n_live == 0
    assert eng.allocator.n_free == eng.allocator.num_blocks


def test_paged_mode_autodetects_and_validates(tiny_model):
    class NoPaged:
        def prefill(self, *a, **k): ...
        def decode_step(self, *a, **k): ...

    with pytest.raises(ValueError, match="paged=True"):
        ServeEngine(NoPaged(), params={}, paged=True)
    eng = ServeEngine(NoPaged(), params={})
    assert not eng.paged               # dense fallback, no allocator
    assert eng.allocator is None
    assert not eng.share_prefix        # sharing is a paged-mode feature
    with pytest.raises(ValueError, match="share_prefix"):
        ServeEngine(NoPaged(), params={}, share_prefix=True)
    # sampling no longer forces the dense path: paged mode stays auto-on
    model, params = tiny_model
    eng = ServeEngine(model, params, greedy=False, temperature=0.7)
    assert eng.paged
    eng = ServeEngine(model, params, greedy=False, paged=True)
    assert eng.paged and eng.share_prefix


def test_share_prefix_rejected_for_recurrent_families(family_model):
    """A recurrent layer's state summarizes its whole prefix, so mapping
    resident KV pages cannot seed a joiner: requesting share_prefix=True
    must fail loudly (naming the reason), auto must resolve to off —
    and neither may silently fall back to the dense engine."""
    family, model, params = family_model
    if family not in RECURRENT_FAMILIES:
        eng = ServeEngine(model, params)   # transformer: sharing stays auto-on
        assert eng.paged and eng.share_prefix
        return
    with pytest.raises(ValueError, match="recurrent layers"):
        ServeEngine(model, params, share_prefix=True)
    eng = ServeEngine(model, params)       # auto: paged on, sharing off
    assert eng.paged and not eng.share_prefix
    assert eng.state_store is not None
    eng = ServeEngine(model, params, share_prefix=False)
    assert eng.paged and not eng.share_prefix


# -- prefix sharing + copy-on-write -------------------------------------------

def _serve_staggered(model, params, prompts, *, share, max_new=4,
                     block_size=4, prefill_chunk=16, batch_size=4):
    """Serve ``prompts[0]`` until its prefill completes (its pages are
    then registered), then submit the rest.  ``prefill_chunk`` covers
    every prompt, so the sharing-on and sharing-off runs execute the
    same sequence of jit shapes — any logit difference is semantic, not
    scheduling.  Returns (engine, tokens by rid, per-step occupancy)."""
    eng = ServeEngine(model, params, batch_size=batch_size, capacity=32,
                      max_new_tokens=max_new, block_size=block_size,
                      prefill_chunk=prefill_chunk, share_prefix=share,
                      trace_logits=True)
    assert eng.paged and eng.share_prefix == share
    eng.submit(prompts[0])
    while eng.n_prefills < 1:
        eng.step()
    for p in prompts[1:]:
        eng.submit(p)
    results, occupancy = [], []
    while eng.has_work:
        results += eng.step()
        need = sum(-(-int(l) // block_size)
                   for i, l in enumerate(eng._lengths)
                   if eng._slots[i] is not None and l > 0)
        occupancy.append((eng.n_active, eng.allocator.n_live, need))
    return eng, {r.request_id: list(r.tokens) for r in results}, occupancy


def test_prefix_sharing_bit_identical_and_fewer_blocks(tiny_model):
    """The tentpole acceptance check: 4 requests sharing a 2-block
    prefix produce logits *bit-identical* to the sharing-disabled run,
    while strictly fewer blocks are live — occupancy drops below the
    sum of per-slot page needs, which only sharing can achieve."""
    model, params = tiny_model
    rng = np.random.default_rng(21)
    prefix = rng.integers(1, TINY.vocab_size, 8).astype(np.int32)
    prompts = [np.concatenate([prefix, np.asarray(s, np.int32)])
               for s in ((60, 61), (58, 59), (56, 57), (54, 55))]
    eng_off, toks_off, occ_off = _serve_staggered(model, params, prompts,
                                                  share=False)
    eng_on, toks_on, occ_on = _serve_staggered(model, params, prompts,
                                               share=True)
    assert toks_on == toks_off
    assert set(eng_on.logit_trace) == set(eng_off.logit_trace) == {0, 1, 2, 3}
    for rid, trace in eng_off.logit_trace.items():
        assert len(eng_on.logit_trace[rid]) == len(trace)
        for step, (a, b) in enumerate(zip(eng_on.logit_trace[rid], trace)):
            assert np.array_equal(a, b), \
                f"sharing changed logits of request {rid} at step {step}"
    # the prefix was actually shared, not re-prefilled
    assert eng_on.n_prefix_hits == 3
    assert eng_on.n_shared_tokens == 3 * len(prefix)
    assert eng_off.n_prefix_hits == 0
    # pool occupancy: strictly fewer live blocks at full residency, and
    # below the sum of per-slot page needs (impossible without sharing)
    peak_on = max(l for _, l, _ in occ_on)
    peak_off = max(l for _, l, _ in occ_off)
    assert peak_on < peak_off
    assert any(live < need for active, live, need in occ_on if active == 4)
    assert all(live >= need for _, live, need in occ_off)
    # everything drains: refcounts and reservations return to zero; table
    # entries for retained (refcount-0, reusable) blocks survive the drain
    for eng in (eng_on, eng_off):
        assert eng.allocator.n_free == eng.allocator.num_blocks
        assert eng.allocator.n_table == eng.allocator.n_retained
        assert eng._reserved == 0


def test_cow_fork_isolates_identical_prompts(tiny_model):
    """A joiner whose whole (block-aligned) prompt is resident maps
    every page; re-running its last token then writes into a shared
    block, which must be forked — not corrupted in place — so both the
    original and the joiner still decode the oracle sequence."""
    model, params = tiny_model
    rng = np.random.default_rng(31)
    prompt = rng.integers(1, TINY.vocab_size, 8).astype(np.int32)  # 2 blocks
    eng = ServeEngine(model, params, batch_size=2, capacity=32,
                      max_new_tokens=6, block_size=4, prefill_chunk=16)
    eng.submit(prompt)
    while eng.n_prefills < 1:
        eng.step()
    eng.submit(prompt.copy())          # identical prompt, still resident
    results = []
    while eng.has_work:
        results += eng.step()
    assert eng.n_prefix_hits == 1
    assert eng.n_shared_tokens == 7    # capped at len(prompt) - 1
    assert eng.n_cow_forks >= 1        # the write into the shared tail forked
    oracle = _fresh_dense_tokens(model, params, prompt, 6)
    by_id = {r.request_id: list(r.tokens) for r in results}
    assert by_id[0] == oracle          # original unharmed by the fork
    assert by_id[1] == oracle          # joiner decodes the same sequence
    assert eng.allocator.n_free == eng.allocator.num_blocks


def test_tail_block_sharing_maps_partial_page(tiny_model):
    """A joiner's final *partial* page can land on another sequence's
    completed block (rows past the joiner's length are masked), covering
    prompt tokens that extend into the original's generated stream."""
    model, params = tiny_model
    rng = np.random.default_rng(41)
    p1 = rng.integers(1, TINY.vocab_size, 10).astype(np.int32)
    eng = ServeEngine(model, params, batch_size=2, capacity=32,
                      max_new_tokens=6, block_size=4, prefill_chunk=16)
    eng.submit(p1)
    while int(eng._lengths[0]) < 12:   # page 2 (positions 8..11) complete
        eng.step()
    oracle1 = _fresh_dense_tokens(model, params, p1, 6)
    # 11-token prompt: pages 0/1 match by chain, tail (p1[8:], oracle1[0])
    # matches the first 3 rows of the original's completed page 2
    p2 = np.concatenate([p1, np.asarray(oracle1[:1], np.int32)])
    eng.submit(p2)
    results = []
    while eng.has_work:
        results += eng.step()
    assert eng.n_prefix_hits == 1
    assert eng.n_shared_tokens == 10   # 8 full-page + 2 tail (one re-run)
    assert eng.n_cow_forks >= 1        # tail page forked before the write
    by_id = {r.request_id: list(r.tokens) for r in results}
    assert by_id[0] == oracle1
    assert by_id[1] == _fresh_dense_tokens(model, params, p2, 6)
    assert eng.allocator.n_free == eng.allocator.num_blocks


def test_no_sharing_between_disjoint_prompts(tiny_model):
    """Different prompts must never map each other's blocks."""
    model, params = tiny_model
    rng = np.random.default_rng(51)
    a = rng.integers(1, TINY.vocab_size, 8).astype(np.int32)
    b = (a + 1) % TINY.vocab_size      # differs at every position
    b[b == 0] = 1
    eng = ServeEngine(model, params, batch_size=2, capacity=32,
                      max_new_tokens=4, block_size=4, prefill_chunk=16)
    eng.submit(a)
    while eng.n_prefills < 1:
        eng.step()
    eng.submit(b)
    while eng.has_work:
        eng.step()
    assert eng.n_prefix_hits == 0 and eng.n_cow_forks == 0
    assert eng.allocator.n_free == eng.allocator.num_blocks


# -- paged decode-attention kernel vs oracle ----------------------------------

def test_paged_kernel_matches_paged_ref():
    """One decode token per slot (T=1): the paged attention kernel
    equals the paged decode oracle over the same cache content."""
    from repro.kernels.decode_attention.kernel import paged_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    rng = np.random.default_rng(0)
    B, H, KV, hd = 3, 4, 2, 16
    nb, bs, P = 12, 8, 3
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((nb, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, KV, bs, hd)), jnp.float32)
    pt = jnp.asarray(rng.choice(nb, size=(B, P), replace=False).astype(np.int32))
    lengths = jnp.asarray([5, P * bs, 1], jnp.int32)
    # the token at position lengths - 1 is this step's: lengths - 1 cached
    o = paged_attention(q[:, None], kp[None], vp[None], pt, lengths - 1,
                        jnp.ones_like(lengths), 0, interpret=True)
    r = paged_decode_attention_ref(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(r),
                               atol=1e-5, rtol=1e-5)


def test_paged_ops_wrapper_matches_ref_in_engine_layout():
    """ops.paged_attention_bthd takes the ServeEngine leaf layout — each
    KV head's page folded onto 128 lanes, ``paged_page_shape`` — and
    reads it as is: the result equals the oracle on the unfolded pool."""
    from repro.kernels.decode_attention import ops
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.models.attention import paged_page_shape
    rng = np.random.default_rng(4)
    B, H, KV, hd = 2, 4, 2, 16
    nb, bs, P = 10, 8, 3
    assert paged_page_shape(bs, hd) == (1, 128)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    k_eng = jnp.asarray(rng.standard_normal((nb, KV, 1, 128)), jnp.float32)
    v_eng = jnp.asarray(rng.standard_normal((nb, KV, 1, 128)), jnp.float32)
    pt = jnp.asarray(rng.choice(nb, size=(B, P), replace=False).astype(np.int32))
    lengths = jnp.asarray([6, 20], jnp.int32)
    o = ops.paged_attention_bthd(q, k_eng, v_eng, pt, lengths - 1,
                                 jnp.ones_like(lengths))
    r = paged_decode_attention_ref(q[:, 0], k_eng.reshape(nb, KV, bs, hd),
                                   v_eng.reshape(nb, KV, bs, hd), pt, lengths)
    assert o.shape == (B, 1, H, hd)
    np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(r),
                               atol=1e-5, rtol=1e-5)


def test_paged_ref_equals_dense_ref_on_contiguous_table():
    """Identity page table == plain dense cache: the two oracles must
    coincide, tying the paged kernel stack back to the dense one."""
    from repro.kernels.decode_attention.ref import (
        decode_attention_ref, paged_decode_attention_ref)
    rng = np.random.default_rng(2)
    B, H, KV, hd = 2, 4, 4, 8
    bs, P = 4, 4
    C = bs * P
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    kd = jnp.asarray(rng.standard_normal((B, KV, C, hd)), jnp.float32)
    vd = jnp.asarray(rng.standard_normal((B, KV, C, hd)), jnp.float32)
    lengths = jnp.asarray([7, C], jnp.int32)
    # identity layout: row b uses blocks [b*P .. b*P+P-1] in order
    kp = jnp.moveaxis(kd.reshape(B, KV, P, bs, hd), 1, 2).reshape(
        B * P, KV, bs, hd)
    vp = jnp.moveaxis(vd.reshape(B, KV, P, bs, hd), 1, 2).reshape(
        B * P, KV, bs, hd)
    pt = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    r_paged = paged_decode_attention_ref(q, kp, vp, pt, lengths)
    r_dense = decode_attention_ref(q, kd, vd, lengths)
    np.testing.assert_array_equal(np.asarray(r_paged), np.asarray(r_dense))
