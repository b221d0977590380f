"""Paged K/V state: page pool, radix prefix tree, memory-bounded engine.

Property tests (hypothesis, or tests/_hypothesis_stub.py when absent)
check the pool/tree invariants the serving engine leans on: refcounts
partition pages exactly, ``match`` returns the longest fully-paged
published prefix (against a reference model), splits preserve lookups,
and eviction only reclaims tree-only (refcount-1) leaves.  Engine tests
check the admission contract: resident concurrency is bounded by the
page budget, not ``max_batch``.  The decode write-back test checks that a
paged decode step changes exactly one token row per live sequence.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS, reduced
from repro.core import planner
from repro.models import lm
from repro.nn import attention as attn_lib
from repro.nn import layers
from repro.serving import ServeConfig, ServingEngine
from repro.serving.engine import Request
from repro.serving.paged import PagePool, RadixCache


# ---------------------------------------------------------------- PagePool
def test_pool_alloc_is_deterministic_lowest_first():
    pool = PagePool(8, 4)
    assert pool.alloc(3) == [1, 2, 3]
    pool.decref(2)
    pool.decref(1)
    # freed pages return to the tail and are reused first (LIFO), then
    # the untouched descending tail resumes lowest-first
    assert pool.alloc(5) == [1, 2, 4, 5, 6]
    assert pool.alloc(2) is None            # only page 7 is free
    assert pool.alloc(1) == [7]
    assert pool.n_free == 0


def test_pool_guards_scratch_and_free_pages():
    pool = PagePool(4, 2)
    with pytest.raises(ValueError):
        pool.incref(PagePool.SCRATCH)
    with pytest.raises(ValueError):
        pool.decref(PagePool.SCRATCH)
    with pytest.raises(ValueError):
        pool.incref(1)                      # free: nothing to share
    (pg,) = pool.alloc(1)
    pool.incref(pg)
    pool.decref(pg)
    pool.decref(pg)                         # back to free
    with pytest.raises(ValueError):
        pool.decref(pg)
    with pytest.raises(ValueError):
        PagePool(1, 2)                      # no room for scratch + data


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2 ** 16), n_pages=st.integers(2, 12))
def test_pool_refcounts_partition_pages(seed, n_pages):
    """Under random alloc/incref/decref traffic, {free} and {refcount>0}
    exactly partition the allocatable pages, and n_free/n_used agree."""
    rng = random.Random(seed)
    pool = PagePool(n_pages, 4)
    held = []                               # (page, model_refcount)
    for _ in range(60):
        op = rng.randrange(3)
        if op == 0:
            got = pool.alloc(rng.randrange(0, n_pages))
            if got is not None:
                held.extend((pg, 1) for pg in got)
        elif op == 1 and held:
            i = rng.randrange(len(held))
            pg, rc = held[i]
            pool.incref(pg)
            held[i] = (pg, rc + 1)
        elif op == 2 and held:
            i = rng.randrange(len(held))
            pg, rc = held[i]
            pool.decref(pg)
            held[i] = (pg, rc - 1)
            if rc == 1:
                held.pop(i)
        model = {}
        for pg, rc in held:
            model[pg] = model.get(pg, 0) + rc
        assert {pg for pg in range(pool.n_pages)
                if pool.refcounts[pg] > 0} == set(model)
        assert all(pool.refcounts[pg] == rc for pg, rc in model.items())
        assert set(pool.free_pages) == (
            set(range(1, n_pages)) - set(model))
        assert pool.n_free + pool.n_used == n_pages - 1


# --------------------------------------------------------------- RadixCache
def test_radix_split_on_mid_node_divergence():
    """A second prompt diverging inside a path-compressed node splits it;
    both full paths and the shared stem keep matching."""
    pool = PagePool(32, 2)
    tree = RadixCache(2)
    a = [1, 1, 2, 2, 3, 3, 4, 4]
    pa = pool.alloc(4)
    tree.insert(a, pa, pool)
    assert tree.n_nodes() == 1 and tree.n_pages() == 4
    b = [1, 1, 2, 2, 9, 9]
    shared = tree.match(b)
    assert shared == pa[:2]
    pb = shared + pool.alloc(1)
    tree.insert(b, pb, pool)
    assert tree.n_nodes() == 3               # stem + two tails
    assert tree.n_pages() == 5               # shared stem stored once
    assert tree.match(a) == pa
    assert tree.match(b) == pb
    assert tree.match([1, 1, 2, 2]) == pa[:2]
    assert tree.match([7, 7]) == []
    # partial pages never match: 5 tokens -> only 2 full pages of prefix
    assert tree.match([1, 1, 2, 2, 3]) == pa[:2]


def test_radix_evict_respects_refcounts_and_lru():
    pool = PagePool(32, 2)
    tree = RadixCache(2)
    pa = pool.alloc(2)
    tree.insert([1, 1, 2, 2], pa, pool)      # refcounts 2 (seq + tree)
    pb = pool.alloc(2)
    tree.insert([5, 5, 6, 6], pb, pool)
    for pg in pa + pb:
        pool.decref(pg)                      # sequences released: tree-only
    tree.match([1, 1, 2, 2])                 # bump A -> B is now LRU
    assert tree.evict(1, pool) == 2          # whole leaf B goes at once
    assert tree.match([5, 5, 6, 6]) == []
    assert tree.match([1, 1, 2, 2]) == pa
    pool.incref(pa[0])                       # a borrower pins A
    assert tree.evict(4, pool) == 0          # nothing evictable left
    pool.decref(pa[0])
    assert tree.evict(4, pool) == 2
    assert tree.n_pages() == 0 and tree.n_nodes() == 0
    assert pool.n_free == pool.n_pages - 1   # every page returned


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2 ** 16), page=st.integers(1, 3))
def test_radix_match_equals_reference_model(seed, page):
    """Random publish/lookup traffic against a flat reference model: the
    trie's match must return exactly the pages of the longest prefix
    whose every full page was published, and the tree must hold exactly
    one pool reference per published page."""
    rng = random.Random(seed)
    pool = PagePool(256, page)
    tree = RadixCache(page)
    published = {}                  # key-path tuple -> physical page
    releasable = []
    for _ in range(10):
        toks = [rng.randrange(3) for _ in range(rng.randrange(0, 9 * page))]
        keys = [tuple(toks[i * page:(i + 1) * page])
                for i in range(len(toks) // page)]
        expect = []
        for i in range(len(keys)):
            pg = published.get(tuple(keys[:i + 1]))
            if pg is None:
                break
            expect.append(pg)
        assert tree.match(toks) == expect
        # admit like the engine: borrow the match, alloc the rest, publish
        for pg in expect:
            pool.incref(pg)
        fresh = pool.alloc(len(keys) - len(expect))
        pages = expect + fresh
        tree.insert(toks[:len(keys) * page], pages, pool)
        for i in range(len(keys)):
            published.setdefault(tuple(keys[:i + 1]), pages[i])
        releasable.extend(pages)
    for pg in releasable:           # every sequence releases its refs
        pool.decref(pg)
    # tree-only now: exactly one reference per published physical page
    assert tree.n_pages() == len(set(published.values()))
    for pg in set(published.values()):
        assert pool.refcounts[pg] == 1
    tree.evict(len(published) + 1, pool)
    assert pool.n_free == pool.n_pages - 1


# ------------------------------------------------------- engine admission
@pytest.fixture(scope="module")
def model():
    cfg = reduced(ARCHS["qwen2-0.5b"])
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_concurrency_exceeds_max_batch(model):
    """Admission is page-budget-bounded: with short requests the engine
    keeps more sequences resident than dispatch rows, round-robining the
    decode ticks — the dense path would cap residency at max_batch."""
    cfg, params = model
    engine = ServingEngine(cfg, params,
                           ServeConfig(max_batch=2, max_seq=32,
                                       kv_pages=40, page_size=8))
    reqs = [Request(prompt=[3 + i, 4 + i, 5 + i], max_new_tokens=4, rid=i)
            for i in range(8)]
    for r in reqs:
        engine.submit(r)
    engine.run_to_completion()
    assert all(r.done and len(r.out_tokens) == 4 for r in reqs)
    assert engine.stats["concurrency_peak"] > engine.sc.max_batch
    assert engine.stats["concurrency_peak"] == 8
    assert engine.pool.n_used == 0          # all reservations released


def test_engine_admission_blocks_on_page_budget(model):
    """When the pool cannot hold everyone, admission is head-of-line FIFO:
    later requests wait for pages, everyone still completes, and peak page
    usage never exceeds the pool."""
    cfg, params = model
    # 4 data pages; each request reserves ceil((3+4)/8)=1 page -> at most
    # 4 resident, the rest queue head-of-line
    engine = ServingEngine(cfg, params,
                           ServeConfig(max_batch=2, max_seq=32,
                                       kv_pages=5, page_size=8))
    reqs = [Request(prompt=[3 + i, 4 + i, 5 + i], max_new_tokens=4, rid=i)
            for i in range(8)]
    for r in reqs:
        engine.submit(r)
    engine.run_to_completion()
    assert all(r.done and len(r.out_tokens) == 4 for r in reqs)
    assert engine.stats["concurrency_peak"] <= 4    # 4 data pages
    assert engine.stats["pages_used_peak"] <= 4
    assert engine.pool.n_used == 0


def test_engine_validates_page_geometry(model):
    cfg, params = model
    with pytest.raises(ValueError, match="divide max_seq"):
        ServingEngine(cfg, params,
                      ServeConfig(max_batch=2, max_seq=32,
                                  kv_pages=10, page_size=7))
    with pytest.raises(ValueError, match="need at least"):
        ServingEngine(cfg, params,
                      ServeConfig(max_batch=2, max_seq=32,
                                  kv_pages=2, page_size=8))
    with pytest.raises(ValueError, match="batched"):
        ServingEngine(cfg, params,
                      ServeConfig(max_batch=2, max_seq=32, kv_pages=10,
                                  page_size=8, prefill_mode="token"))


def test_planner_page_plan_divides_max_seq():
    for S in (16, 32, 64, 128, 256):
        page = planner.page_plan(S)
        assert page > 0 and S % page == 0
    # waste pressure: short expected lengths pull the page size down
    assert planner.page_plan(128, expected_len=8) <= \
        planner.page_plan(128, expected_len=128)


# ------------------------------------------------------ decode write-back
def _decode_paged_page_writeback(p, x, cfg, cache, pos, bt, norm_scale):
    """The whole-page formula of the paged decode: take the written page
    back out of the gathered view and scatter the page.  Returns the
    attention output, both pools and the new K/V rows."""
    cd = lm._cdtype(cfg)
    q, k_new, v_new = lm._proj_qkv(p, x, None, cfg, cd, norm_scale)
    B = x.shape[0]
    positions = pos[:, None]
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k_new = layers.apply_rope(k_new, positions, cfg.rope_theta)
    n_pg, page = bt.shape[1], cache["kp"].shape[1]
    L = n_pg * page
    k_view = cache["kp"][bt].reshape((B, L) + cache["kp"].shape[2:])
    v_view = cache["vp"][bt].reshape((B, L) + cache["vp"].shape[2:])
    slot = jnp.minimum(pos, L - 1)
    hit = (jnp.arange(L)[None, :] == slot[:, None])[:, :, None, None]
    k_view = jnp.where(hit, k_new.astype(cache["kp"].dtype), k_view)
    v_view = jnp.where(hit, v_new.astype(cache["vp"].dtype), v_view)
    out = attn_lib.decode_attention(q, k_view, v_view, pos, window=0,
                                    backend=cfg.gemm_backend,
                                    interpret=cfg.pallas_interpret)
    pg_idx = slot // page
    phys = jnp.take_along_axis(bt, pg_idx[:, None], axis=1)[:, 0]
    KV, hd = k_view.shape[2], k_view.shape[3]
    sel = jnp.broadcast_to(pg_idx[:, None, None, None, None],
                           (B, 1, page, KV, hd))
    kpage = jnp.take_along_axis(
        k_view.reshape(B, n_pg, page, KV, hd), sel, axis=1)[:, 0]
    vpage = jnp.take_along_axis(
        v_view.reshape(B, n_pg, page, KV, hd), sel, axis=1)[:, 0]
    out = layers.linear(p["wo"], out.reshape(B, 1, -1), cd, site="attn.wo",
                        backend=cfg.gemm_backend,
                        interpret=cfg.pallas_interpret)
    return (out, cache["kp"].at[phys].set(kpage),
            cache["vp"].at[phys].set(vpage), k_new[:, 0], v_new[:, 0])


@pytest.mark.parametrize("seed", [0, 1])
def test_decode_writes_one_row_per_live_sequence(model, seed):
    """``attn_decode_paged`` returns each row's new K/V, and the layer
    scan's write of them (``lm._write_rows``) returns the input pool with
    exactly the rows ``(phys[b], slot[b] % page)`` of live sequences
    replaced; the attention output is, bit for bit, the whole-page
    write-back's.  The pool is a stack of two layers, read and written at
    the second.  Rows cover ``slot % page`` at 0 and ``page - 1``, the
    first and the last page, a clamped ``pos >= L``, a prefix page shared
    by two sequences, and two idle rows aliased to scratch page 0."""
    cfg, params = model
    page, n_pg, n_pages = 4, 4, 24
    L = n_pg * page
    # (pos, live): first page at offsets 0 and page-1, last page at
    # offsets 0 and page-1, pos past L (clamped to L-1), two idle rows
    rows = [(0, True), (page - 1, True), (L - page, True), (L - 1, True),
            (L + 5, True), (0, False), (0, False)]
    B = len(rows)
    rng = np.random.default_rng(seed)
    owned = rng.permutation(np.arange(1, n_pages))
    bt = np.zeros((B, n_pg), np.int32)
    for b, (_, live) in enumerate(rows):
        if live:
            bt[b] = owned[b * n_pg:(b + 1) * n_pg]
    bt[3, 0] = bt[2, 0]                 # a prefix page neither row writes
    pos = jnp.asarray([r[0] for r in rows], jnp.int32)
    bt = jnp.asarray(bt)
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    cache = {"kp": jax.random.normal(k1, (2, n_pages, page, KV * hd),
                                     jnp.bfloat16),
             "vp": jax.random.normal(k2, (2, n_pages, page, KV * hd),
                                     jnp.bfloat16)}
    x = jax.random.normal(k3, (B, 1, cfg.d_model), lm._cdtype(cfg))
    blk = jax.tree.map(lambda a: a[0], params["blocks"][0])
    norm_scale = blk["ln1"]["scale"]

    out, new_rows = lm.attn_decode_paged(blk["attn"], x, cfg, cache, 1, pos,
                                         bt, norm_scale=norm_scale)
    layer1 = {k: v[1].reshape(n_pages, page, KV, hd)
              for k, v in cache.items()}
    ref_out, ref_kp, ref_vp, k_new, v_new = _decode_paged_page_writeback(
        blk["attn"], x, cfg, layer1, pos, bt, norm_scale)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    # the layer scan hands over both layers' rows: layer 0 rewrites its
    # own bytes, layer 1 the new rows
    slot = np.minimum(np.asarray(pos), L - 1)
    phys_all = np.asarray(bt)[np.arange(B), slot // page]
    stacked = {k: jnp.stack([v[0, phys_all, slot % page], new_rows[k]])
               for k, v in cache.items()}
    (new,) = lm._write_rows((cache,), (stacked,), pos, None, bt)

    live = np.array([r[1] for r in rows])
    phys, off = phys_all[live], (slot % page)[live]
    assert set(off) == {0, page - 1}
    for name, rows_new, ref in (("kp", k_new, ref_kp), ("vp", v_new, ref_vp)):
        np.testing.assert_array_equal(
            np.asarray(new_rows[name]),
            np.asarray(rows_new.astype(jnp.bfloat16)).reshape(B, -1))
        want = np.array(cache[name])
        want[1, phys, off] = np.asarray(
            rows_new.astype(jnp.bfloat16)).reshape(B, -1)[live]
        got = np.asarray(new[name])
        # scratch page 0 takes the idle rows' writes; live rows never
        # attend it
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
        np.testing.assert_array_equal(
            got[1, 1:], np.asarray(ref).reshape(n_pages, page, -1)[1:])


def test_paged_steps_match_the_per_layer_pool_path(model):
    """A prefill and three decodes with the pool donated and updated in
    place give the logits and the whole pool, bit for bit, of the steps
    that passed the pool through the layer scan (tests/_paged_pool):
    a shared prefix page, lazily reserved table entries and an idle row."""
    from _paged_pool import check_steps_against_reference
    cfg, params = model
    check_steps_against_reference(cfg, params)
