"""Paged KV-cache subsystem tests: allocator, prefix trie, paged decode
kernel parity, and paged-vs-dense serving-engine equivalence.

The load-bearing invariant (extends the PR-2 varlen contract): the same
ragged workload served by the paged engine — prefix sharing on or off,
chunked prefill on or off, even through a preemption — must reproduce the
dense-slab engine's generated tokens token-for-token on the xla backend,
while using strictly fewer cache pages than the dense slab footprint.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import AnchorConfig, AttentionSpec
from repro.kernels import ops as kernel_ops
from repro.kernels.decode import pages_per_step
from repro.models import model as model_lib
from repro.models.cache import PagedKVLayout, gather_pages, supports_paged
from repro.models.layers import decode_attention
from repro.serving import PagePool, PrefixCache, Request, ServingEngine

ANCHOR = AnchorConfig(block_q=16, block_kv=16, step=2, theta=1e9)


# ------------------------------------------------------------- PagePool ----


class TestPagePool:
    def test_alloc_free_refcount(self):
        pool = PagePool(num_pages=4, page_size=8)
        a, b = pool.alloc(), pool.alloc()
        assert a != b and 0 not in (a, b)
        assert pool.pages_in_use == 2 and pool.free_pages == 2
        pool.share(a)
        assert pool.refcount(a) == 2
        assert not pool.release(a)  # still referenced
        assert pool.release(a)  # now freed
        assert pool.release(b)
        assert pool.pages_in_use == 0
        pool.check_consistency()

    def test_exhaustion_and_atomic_alloc_many(self):
        pool = PagePool(num_pages=3, page_size=8)
        pool.alloc()
        with pytest.raises(MemoryError):
            pool.alloc_many(3)
        assert pool.free_pages == 2  # nothing leaked by the failed request
        pages = pool.alloc_many(2)
        assert len(pages) == 2
        with pytest.raises(MemoryError):
            pool.alloc()

    def test_double_free_rejected(self):
        pool = PagePool(num_pages=2, page_size=8)
        p = pool.alloc()
        pool.release(p)
        with pytest.raises(ValueError, match="double free"):
            pool.release(p)

    def test_high_water_mark(self):
        pool = PagePool(num_pages=4, page_size=8)
        pages = pool.alloc_many(3)
        for p in pages:
            pool.release(p)
        pool.alloc()
        assert pool.stats.pages_hwm == 3
        assert pool.stats.pages_in_use == 1

    def test_copy_on_write(self):
        pool = PagePool(num_pages=4, page_size=8)
        p = pool.alloc()
        same, copied = pool.ensure_writable(p)
        assert same == p and not copied  # sole owner: write in place
        pool.share(p)
        fresh, copied = pool.ensure_writable(p)
        assert copied and fresh != p
        assert pool.refcount(p) == 1 and pool.refcount(fresh) == 1
        assert pool.stats.cow_copies == 1
        pool.check_consistency()


# ---------------------------------------------------------- PrefixCache ----


class TestPrefixCache:
    def test_match_insert_divergence(self):
        pool = PagePool(num_pages=8, page_size=4)
        cache = PrefixCache(pool)
        toks_a = np.arange(10, dtype=np.int32)  # 2 full pages + tail
        pages_a = pool.alloc_many(3)
        assert cache.match(toks_a) == []
        cache.insert(toks_a, pages_a)
        assert len(cache) == 2  # only full pages indexed

        # Identical prompt: both full pages shared, refcounts bumped.
        got = cache.match(toks_a)
        assert got == pages_a[:2]
        assert pool.refcount(pages_a[0]) == 3  # owner + trie + new match

        # Divergence inside page 2: only page 1 shared.
        toks_b = np.concatenate([toks_a[:4], np.full(6, 99, np.int32)])
        assert cache.match(toks_b) == pages_a[:1]
        assert cache.stats.hits == 2 and cache.stats.queries == 3

    def test_evict_lru_leaf_first(self):
        pool = PagePool(num_pages=4, page_size=4)
        cache = PrefixCache(pool)
        toks = np.arange(8, dtype=np.int32)
        pages = pool.alloc_many(2)
        cache.insert(toks, pages)
        for p in pages:  # retire the owning sequence
            pool.release(p)
        assert pool.pages_in_use == 2  # kept alive by the trie
        freed = cache.evict(want_free=3)
        assert freed == 1 and len(cache) == 1
        # The *leaf* (deeper page) went first; the prefix page remains.
        assert cache.match(toks) == [pages[0]]
        pool.release(pages[0])
        cache.clear()
        assert pool.pages_in_use == 0
        pool.check_consistency()

    def test_tags_namespace_the_trie(self):
        """Pages are only shared between same-tag (same attention math)
        prefills — an anchor wave must never decode against KV produced
        by a dense-fallback or chunked prefill."""
        pool = PagePool(num_pages=4, page_size=4)
        cache = PrefixCache(pool)
        toks = np.arange(4, dtype=np.int32)
        page = pool.alloc()
        cache.insert(toks, [page], tag="anchor")
        assert cache.match(toks, tag="chunked") == []
        assert cache.match(toks, tag="anchor") == [page]

    def test_evict_spares_live_shared_pages(self):
        pool = PagePool(num_pages=4, page_size=4)
        cache = PrefixCache(pool)
        toks = np.arange(4, dtype=np.int32)
        page = pool.alloc()
        cache.insert(toks, [page])
        cache.evict(want_free=pool.num_pages + 1)
        # Trie ref released, but the live owner still holds the page.
        assert pool.refcount(page) == 1
        assert pool.pages_in_use == 1


# ---------------------------------------------- paged_flash_decode parity ----


# The kernel's own cases: (Hq, Hkv) of MHA, internlm2's group of 2 and
# Yi's group of 8, B = 2, page 16 over a shuffled 40-page table, at cache
# lengths around a page and the kernel's block of pages; and at one step
# + 1 with both rows sharing their first 20 physical pages, as prefix
# sharing lays them out.
PARITY_HEADS = [(8, 8), (16, 8), (32, 4)]
PARITY_PAGE, PARITY_PAGES, SHARED_PAGES = 16, 40, 20
CACHE_LEN_CASES = ("one", "page_minus_one", "one_step", "one_step_plus_one",
                   "whole_table")
KERNEL_CASES = [(hq, hkv, case) for hq, hkv in PARITY_HEADS
                for case in CACHE_LEN_CASES + ("shared_prefix",)]
# The original cases: page 8, B = 3, 4/2 heads, ids drawn with replacement
# from an 11-page pool, so rows and positions repeat physical pages.
REPEATS = (4, 2)
PARITY_CASES = KERNEL_CASES + [(*REPEATS, n) for n in (1, 17, 40)]
NULL_CASES = KERNEL_CASES + [(*REPEATS, 20)]


def _case_id(case):
    hq, hkv, length = case
    return f"{hq}q{hkv}kv-" + (f"repeats_len{length}"
                               if (hq, hkv) == REPEATS else length)


def _repeats_table(seed, b=3, hq=4, hkv=2, d=16, ps=8, n_pages=5, pool_p=12):
    rng = np.random.default_rng(seed)
    k_pages = jnp.asarray(rng.normal(size=(pool_p, hkv, ps, d)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(pool_p, hkv, ps, d)), jnp.float32)
    pt = jnp.asarray(rng.integers(1, pool_p, size=(b, n_pages)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)), jnp.float32)
    return q, k_pages, v_pages, pt


def _parity_case(hq, hkv, length, seed, b=2, d=16):
    """q, pool, table and cache length of one case, and the page size.
    ``length`` is a cache length for the repeated table, else one of
    ``CACHE_LEN_CASES`` or ``"shared_prefix"``; a step is the kernel's own
    block of pages."""
    if (hq, hkv) == REPEATS:
        return (*_repeats_table(seed), jnp.asarray(length, jnp.int32)), 8
    rng = np.random.default_rng(seed)
    pool = (1 + b * PARITY_PAGES, hkv, PARITY_PAGE, d)
    kp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    vp = jnp.asarray(rng.normal(size=pool), jnp.float32)
    pt = jnp.asarray(1 + rng.permutation(b * PARITY_PAGES), jnp.int32)
    pt = pt.reshape(b, PARITY_PAGES)
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)), jnp.float32)
    step = PARITY_PAGE * pages_per_step(PARITY_PAGE, PARITY_PAGES, hkv, d, 4)
    assert step < PARITY_PAGE * PARITY_PAGES  # the table spans two steps
    if length == "shared_prefix":
        pt = pt.at[1:, :SHARED_PAGES].set(pt[0, :SHARED_PAGES])
        assert SHARED_PAGES * PARITY_PAGE < step + 1
    clen = {"one": 1, "page_minus_one": PARITY_PAGE - 1, "one_step": step,
            "one_step_plus_one": step + 1, "shared_prefix": step + 1,
            "whole_table": PARITY_PAGE * PARITY_PAGES}[length]
    return (q, kp, vp, pt, jnp.asarray(clen, jnp.int32)), PARITY_PAGE


def _unread_pages(pool_pages, pt, clen, page):
    """Pool pages that no row reads below the cache length."""
    live = np.asarray(pt[:, :-(-int(clen) // page)]).reshape(-1)
    return jnp.asarray(np.setdiff1d(np.arange(pool_pages), live), jnp.int32)


class TestPagedFlashDecode:
    def test_xla_matches_gathered_dense_decode_exactly(self):
        q, kp, vp, pt = _repeats_table(0)
        clen = jnp.asarray(29, jnp.int32)
        ref = decode_attention(q, gather_pages(kp, pt), gather_pages(vp, pt),
                               clen)
        out = kernel_ops.paged_flash_decode(q, kp, vp, pt, clen, backend="xla")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    @pytest.mark.parametrize("hq,hkv,length", PARITY_CASES,
                             ids=map(_case_id, PARITY_CASES))
    def test_pallas_interpret_parity(self, hq, hkv, length):
        """Pool pages that no row reads below its length, stale pages past
        it among them, hold NaN for the kernel."""
        (q, kp, vp, pt, clen), page = _parity_case(hq, hkv, length, seed=1)
        ref = kernel_ops.paged_flash_decode(q, kp, vp, pt, clen, backend="xla")
        dead = _unread_pages(kp.shape[0], pt, clen, page)
        out = kernel_ops.paged_flash_decode(
            q, kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan), pt, clen,
            backend="pallas_interpret")
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(out), atol=2e-6, rtol=1e-5)

    def test_registered_backends(self):
        from repro.kernels import dispatch

        assert dispatch.registered_backends("paged_flash_decode") == [
            "pallas_interpret", "pallas_tpu", "xla"]

    @pytest.mark.parametrize("hq,hkv,length", NULL_CASES,
                             ids=map(_case_id, NULL_CASES))
    def test_null_page_entries_are_masked(self, hq, hkv, length):
        """Unassigned table slots (page 0) beyond cache_len never leak,
        on either backend, with the null page holding NaN for the kernel
        and junk for the xla twin."""
        (q, kp, vp, pt, clen), page = _parity_case(hq, hkv, length, seed=2)
        pt = pt.at[:, -(-int(clen) // page):].set(0)
        ref = kernel_ops.paged_flash_decode(q, kp, vp, pt, clen, backend="xla")
        junk = kernel_ops.paged_flash_decode(
            q, kp.at[0].set(1e4), vp.at[0].set(-1e4), pt, clen, backend="xla")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(junk))
        out = kernel_ops.paged_flash_decode(
            q, kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan), pt, clen,
            backend="pallas_interpret")
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(out), atol=2e-6, rtol=1e-5)


class TestFlashDecodeBlockS:
    """Regression: flash_decode must accept cache lengths that are not a
    multiple of block_s (it used to assert at trace time)."""

    @pytest.mark.parametrize("s_len,block_s", [(29, 8), (500, 512), (640, 512)])
    def test_non_divisible_cache_len(self, s_len, block_s):
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(2, 4, 1, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 2, s_len, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 2, s_len, 16)), jnp.float32)
        clen = jnp.asarray(min(20, s_len), jnp.int32)
        ref = decode_attention(q, k, v, clen)
        out = kernel_ops.flash_decode(q, k, v, clen, block_s=block_s,
                                      backend="pallas_interpret")
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-6, rtol=1e-5)


# -------------------------------------------------- engine equivalence ----


@pytest.fixture(scope="module")
def served():
    cfg = get_reduced_config("internlm2_1p8b")
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    spec = AttentionSpec(algorithm="anchor", backend="xla", anchor=ANCHOR)
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    # Ragged multi-turn workload: shared system prompt + ragged user turns.
    prompts = [
        np.concatenate([sys_prompt,
                        rng.integers(0, cfg.vocab_size, n).astype(np.int32)])
        for n in (9, 23, 26, 14)
    ]
    return cfg, params, spec, prompts


def _run(engine, prompts, max_new=6):
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=p.copy(), max_new_tokens=max_new))
    done = engine.run_to_completion()
    engine.pool.check_consistency() if engine.pool is not None else None
    return {r.uid: r.generated for r in done}


class TestPagedEngineEquivalence:
    """Acceptance: the paged engine reproduces the dense-slab engine
    token-for-token on xla while sharing prefix pages and staying under
    the dense footprint."""

    @pytest.fixture(scope="class")
    def dense_tokens(self, served):
        cfg, params, spec, prompts = served
        engine = ServingEngine(params, cfg, max_batch=4, max_len=128,
                               spec=spec)
        return _run(engine, prompts)

    def test_prefix_sharing_reproduces_dense_tokens(self, served, dense_tokens):
        cfg, params, spec, prompts = served
        engine = ServingEngine(params, cfg, max_batch=4, max_len=128,
                               spec=spec, cache_layout="paged", page_size=8,
                               num_pages=40)
        gen = _run(engine, prompts)
        assert gen == dense_tokens
        snap = engine.snapshot()
        assert snap["prefix_hits"] > 0
        assert snap["shared_pages"] > 0
        assert snap["dense_fallbacks"] == 0
        # Strictly below the dense slab footprint for this workload.
        dense_slab_pages = 4 * 128 // 8
        assert snap["pages_hwm"] < dense_slab_pages
        # All live pages reclaimed on retirement; only trie-held prefix
        # pages may remain.
        assert snap["pages_in_use"] <= snap["pages_hwm"]

    def test_sharing_off_also_reproduces_dense_tokens(self, served,
                                                      dense_tokens):
        cfg, params, spec, prompts = served
        engine = ServingEngine(params, cfg, max_batch=4, max_len=128,
                               spec=spec, cache_layout="paged", page_size=8,
                               num_pages=64, prefix_sharing=False)
        gen = _run(engine, prompts)
        assert gen == dense_tokens
        snap = engine.snapshot()
        assert snap["prefix_hits"] == 0 and snap["shared_pages"] == 0
        assert snap["pages_in_use"] == 0  # full reclamation, no trie

    def test_sharing_uses_fewer_pages_than_no_sharing(self, served):
        cfg, params, spec, prompts = served
        kw = dict(max_batch=4, max_len=128, spec=spec, cache_layout="paged",
                  page_size=8, num_pages=64)
        on = ServingEngine(params, cfg, prefix_sharing=True, **kw)
        off = ServingEngine(params, cfg, prefix_sharing=False, **kw)
        assert _run(on, prompts) == _run(off, prompts)
        assert on.snapshot()["pages_hwm"] < off.snapshot()["pages_hwm"]

    def test_chunked_prefill_reproduces_dense_tokens(self, served):
        cfg, params, spec, prompts = served
        rng = np.random.default_rng(7)
        longp = rng.integers(0, cfg.vocab_size, 90).astype(np.int32)
        workload = [longp, prompts[0], prompts[1]]
        dense = ServingEngine(params, cfg, max_batch=4, max_len=128,
                              spec=spec)
        ref = _run(dense, workload)
        chunked = ServingEngine(params, cfg, max_batch=4, max_len=128,
                                spec=spec, cache_layout="paged", page_size=8,
                                num_pages=60, chunk_tokens=64)
        gen = _run(chunked, workload)
        assert gen == ref
        snap = chunked.snapshot()
        assert snap["chunked_prefills"] == 1  # only the 90-token prompt
        assert snap["prefill_chunks"] == 2  # ceil(90 / 64)
        # Anchor-spec chunks run the index-driven sparse path, not the
        # dense history-attention fallback.
        assert snap["sparse_chunks"] == 2

    def test_chunked_prefill_with_shared_prefix_offset(self, served):
        """Regression: a prefix hit used to offset the chunk start to a
        page (not chunk) boundary, so the final window overran max_len
        and the clamped write clobbered history K/V."""
        cfg, params, spec, _ = served
        rng = np.random.default_rng(11)
        longp = rng.integers(0, cfg.vocab_size, 90).astype(np.int32)
        workload = [longp, longp.copy()]  # identical: full prefix hit
        dense = ServingEngine(params, cfg, max_batch=2, max_len=128,
                              spec=spec)
        ref = _run(dense, workload)
        chunked = ServingEngine(params, cfg, max_batch=2, max_len=128,
                                spec=spec, cache_layout="paged", page_size=8,
                                num_pages=48, chunk_tokens=64)
        # Serve the two turns SEQUENTIALLY: chunked prompts index their
        # pages on completion, so the second turn's prefix hit (and the
        # chunk-start offset it causes) only happens after the first
        # retires.
        gen = _run(chunked, workload[:1])
        chunked.submit(Request(uid=1, prompt=workload[1].copy(),
                               max_new_tokens=6))
        gen.update({r.uid: r.generated
                    for r in chunked.run_to_completion()})
        snap = chunked.snapshot()
        assert snap["prefix_hits"] > 0  # the offset path actually ran
        assert gen == ref

    def test_rejects_max_len_not_chunk_multiple(self, served):
        """Regression: a chunk window overrunning max_len corrupted the
        cache via a clamped dynamic_update_slice; now rejected up front."""
        cfg, params, spec, _ = served
        with pytest.raises(ValueError, match="chunk_tokens"):
            ServingEngine(params, cfg, max_batch=2, max_len=96, spec=spec,
                          cache_layout="paged", page_size=8, chunk_tokens=64)

    def test_oversized_prompt_rejected_not_wedged(self, served):
        cfg, params, spec, prompts = served
        engine = ServingEngine(params, cfg, max_batch=2, max_len=64,
                               spec=spec, cache_layout="paged", page_size=8)
        with pytest.raises(ValueError, match="do not fit"):
            engine.submit(Request(
                uid=9, prompt=np.zeros(64, np.int32), max_new_tokens=2))
        # A bad request smuggled past submit() must not wedge the engine.
        bad = Request(uid=8, prompt=np.zeros(64, np.int32), max_new_tokens=2)
        engine.queue.append(bad)
        engine.submit(Request(uid=0, prompt=prompts[0][:16].copy(),
                              max_new_tokens=3))
        done = engine.run_to_completion()
        assert engine.stats["rejections"] == 1
        assert {r.uid for r in done} == {8, 0}
        assert bad.done and bad.generated == []
        assert len([r for r in done if r.uid == 0][0].generated) == 3

    @pytest.mark.parametrize("theta", [1e9, 3.0])
    def test_preemption_recompute_is_exact(self, served, theta):
        """Preempted requests re-prefill their prompt and REPLAY emitted
        tokens through decode steps, so the reconstruction is exact even
        when anchor is actually sparse (theta=3.0) — replaying them
        through prefill instead would swap the attention algorithm that
        produced their KV."""
        cfg, params, _, _ = served
        anchor = AnchorConfig(block_q=16, block_kv=16, step=2, theta=theta)
        spec = AttentionSpec(algorithm="anchor", backend="xla", anchor=anchor)
        rng = np.random.default_rng(1)
        # Page-aligned prompts: the first decode token needs a fresh page,
        # and a 13-page pool (3 x 4 prompt pages + 1) forces a preemption.
        prompts = [rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
                   for _ in range(3)]
        dense = ServingEngine(params, cfg, max_batch=3, max_len=64, spec=spec)
        ref = _run(dense, prompts)
        tight = ServingEngine(params, cfg, max_batch=3, max_len=64, spec=spec,
                              cache_layout="paged", page_size=8, num_pages=13,
                              prefix_sharing=False)
        gen = _run(tight, prompts)
        snap = tight.snapshot()
        assert snap["preemptions"] > 0
        assert gen == ref

    def test_observability_counters(self, served, dense_tokens):
        cfg, params, spec, prompts = served
        engine = ServingEngine(params, cfg, max_batch=4, max_len=128,
                               spec=spec)
        _run(engine, prompts)
        snap = engine.snapshot()
        assert snap["decode_steps"] > 0
        assert snap["length_truncations"] == 0
        assert "queued" in snap and "active_slots" in snap

    def test_length_truncation_counted(self, served):
        cfg, params, spec, _ = served
        engine = ServingEngine(params, cfg, max_batch=1, max_len=64,
                               spec=spec)
        prompt = np.arange(32, dtype=np.int32) % cfg.vocab_size
        engine.submit(Request(uid=0, prompt=prompt, max_new_tokens=1000))
        done = engine.run_to_completion()
        assert done[0].done
        assert engine.stats["length_truncations"] == 1


class TestPagedEngineValidation:
    def test_rejects_recurrent_arch(self, served):
        cfg = get_reduced_config("mamba2_2p7b")
        assert not supports_paged(cfg)
        params = jax.eval_shape(
            lambda k: model_lib.init(k, cfg), jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="paged KV layout"):
            ServingEngine(params, cfg, max_batch=2, max_len=64,
                          cache_layout="paged", page_size=8)

    def test_rejects_misaligned_page_size(self, served):
        cfg, params, spec, _ = served
        with pytest.raises(ValueError, match="multiple of"):
            ServingEngine(params, cfg, max_batch=2, max_len=60, spec=spec,
                          cache_layout="paged", page_size=8)
        with pytest.raises(ValueError, match="superblock"):
            # superblock is 32; page_size 24 divides neither 32 nor max_len
            ServingEngine(params, cfg, max_batch=2, max_len=96, spec=spec,
                          cache_layout="paged", page_size=24)

    def test_rejects_misaligned_chunk(self, served):
        cfg, params, spec, _ = served
        with pytest.raises(ValueError, match="chunk_tokens"):
            ServingEngine(params, cfg, max_batch=2, max_len=128, spec=spec,
                          cache_layout="paged", page_size=8, chunk_tokens=40)

    def test_paged_layout_validation(self):
        with pytest.raises(ValueError):
            PagedKVLayout(page_size=0, num_pages=4, pages_per_seq=2)
        layout = PagedKVLayout(page_size=8, num_pages=4, pages_per_seq=2)
        assert layout.total_pages == 5  # +1 for the null page
        assert layout.max_len == 16
