"""GQA-native index-driven sparse computation (DESIGN.md §3, §9).

Three contracts, per op, with Hkv < Hq:

1. **Repeat-expanded parity** — every attention op called with grouped
   K/V must reproduce the same op called with K/V repeat-expanded to
   Hq == Hkv: bit-for-bit on the ``xla`` backend, within kernel
   tolerance on ``pallas_interpret``.  (The expanded call *is* the old
   gather-based per-head pipeline's math, so this is also the
   index-vs-gather acceptance check at Hq width.)
2. **No Hq-wide KV buffers** — jaxpr inspection of the xla anchor
   pipeline: no equation expands a key-dimensioned (…, Hkv, …, D_k)
   tensor to Hq width.  The detector is validated against an old-style
   ``jnp.repeat`` gather pipeline (positive control).
3. **Index-driven ≡ gather-based** — the staged sparse stage fed the
   same :class:`repro.kernels.indexing.StripeIndex` tables must be
   bit-identical whether it gathers tiles inside the scan (index-driven)
   or consumes pre-materialized (B, Hkv, T_s, C, D) tiles — including
   varlen ``lengths`` batches, which must stay bit-for-bit equal to
   per-sequence calls.

Plus the ``pack_stripe_indices`` capacity regression (N=200,
block_c=128) and the chunked-anchor ≡ one-shot-anchor equivalence.
The fused-identification suites (fused ≡ staged, compact-select ≡
dense-mask compaction, jaxpr footprint) live in
``tests/test_fused_identification.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AnchorConfig, AttentionSpec
from repro.kernels import indexing
from repro.kernels import ops as kernel_ops
from repro.kernels.decode import pages_per_step
from repro.kernels.xla import (
    sparse_attention_gathered,
    staged_anchor_stats,
    staged_sparse_attention,
    staged_stripe_mask,
)

CFG = AnchorConfig(block_q=32, block_kv=32, step=2, theta=3.0)
B, HQ, HKV, N, D = 2, 4, 2, 256, 32
BACKENDS = ("xla", "pallas_interpret")
# Paged decode: (Hq, Hkv) of MHA, internlm2 and Yi at cache lengths around
# the kernel's page and block of pages, and the original HQ/HKV case.
DECODE_HEADS = [(8, 8), (16, 8), (32, 4)]
DECODE_LENS = ("one", "page_minus_one", "one_step", "one_step_plus_one",
               "whole_table")
DECODE_CASES = ([(hq, hkv, n) for hq, hkv in DECODE_HEADS for n in DECODE_LENS]
                + [(HQ, HKV, "table4_len50")])


def _qkv(seed, b=B, hq=HQ, hkv=HKV, n=N, d=D):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, hq, n, d)),
            jax.random.normal(ks[1], (b, hkv, n, d)),
            jax.random.normal(ks[2], (b, hkv, n, d)))


def _expand(k, v, rep=HQ // HKV):
    return jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)


def _check(backend, out, ref, *, xla_exact=True):
    """GQA parity.  ``xla_exact=False`` is the ulp-level contract for ops
    whose XLA dots change shape with the group width: the grouped einsum
    contracts G query heads per KV head where the expanded oracle
    contracts one, and XLA:CPU picks its contraction kernel by operand
    shape, so the two agree to a few f32 ulp but not bitwise (DESIGN.md
    §3)."""
    if backend == "xla" and xla_exact:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    elif backend == "xla":
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-6, rtol=1e-6)
    else:
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=2e-5, rtol=1e-4)


def _check_decode(backend, out, ref):
    """Decode ops: ulp-level tolerance on xla instead of bit-equality.

    The grouped one-token einsum contracts with M = G rows where the
    expanded oracle contracts with M = 1 (gemv vs gemm accumulation).
    Decode is beyond the paper (prefill only).
    """
    _check(backend, out, ref, xla_exact=False)


def _dense_from_tables(tables: indexing.StripeIndex, n: int) -> np.ndarray:
    """(B, Hkv, G, T_s, N) int mask reconstructed from compact tables —
    the per-head selection a table encodes, for structural comparisons."""
    idx = np.asarray(tables.tile_idx)
    valid = np.asarray(tables.valid)
    b, hkv, t_s, c_t = idx.shape
    g = valid.shape[2]
    tile = tables.tile
    out = np.zeros((b, hkv, g, t_s, n), np.int32)
    for bi in np.ndindex(b, hkv, t_s):
        for c in range(c_t):
            t = idx[bi[0], bi[1], bi[2], c]
            bits = valid[bi[0], bi[1], :, bi[2], c * tile:(c + 1) * tile]
            sl = out[bi[0], bi[1], :, bi[2], t * tile:(t + 1) * tile]
            np.maximum(sl, bits, out=sl)
    return out


class TestRepeatExpandedParity:
    """Grouped K/V ≡ repeat-expanded K/V per op: exact on xla."""

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_flash_attention(self, backend):
        q, k, v = _qkv(0)
        kr, vr = _expand(k, v)
        out = kernel_ops.flash_attention(q, k, v, block_q=32, block_kv=32,
                                         backend=backend)
        ref = kernel_ops.flash_attention(q, kr, vr, block_q=32, block_kv=32,
                                         backend=backend)
        _check(backend, out, ref)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_anchor_phase(self, backend):
        q, k, v = _qkv(1)
        kr, _ = _expand(k, v)
        got = kernel_ops.anchor_phase(q, k, CFG, backend=backend)
        want = kernel_ops.anchor_phase(q, kr, CFG, backend=backend)
        for o, r in zip(got, want):
            _check(backend, o, r)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_stripe_select(self, backend):
        """Grouped and expanded tables encode identical per-head
        selections (the tables differ structurally — union layout vs
        per-head layout — so compare the reconstructed masks)."""
        q, k, v = _qkv(2)
        kr, _ = _expand(k, v)
        q_mean, m_bar = kernel_ops.anchor_phase(q, k, CFG, backend="xla")
        sel, counts = kernel_ops.stripe_select(
            q_mean, m_bar, k, CFG, 32, backend=backend)
        sel_x, counts_x = kernel_ops.stripe_select(
            q_mean, m_bar, kr, CFG, 32, backend=backend)
        got = _dense_from_tables(sel, N).reshape(B, HQ, -1, N)
        want = _dense_from_tables(sel_x, N).reshape(B, HQ, -1, N)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(counts_x))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_sparse_attention(self, backend):
        q, k, v = _qkv(3)
        kr, vr = _expand(k, v)
        q_mean, m_bar = kernel_ops.anchor_phase(q, k, CFG, backend="xla")
        sel, _ = kernel_ops.stripe_select(
            q_mean, m_bar, k, CFG, 32, backend="xla")
        sel_x, _ = kernel_ops.stripe_select(
            q_mean, m_bar, kr, CFG, 32, backend="xla")
        tables = kernel_ops.merge_anchor_slots(sel, N, CFG)
        tables_x = kernel_ops.merge_anchor_slots(sel_x, N, CFG)
        out = kernel_ops.sparse_attention(q, k, v, tables, CFG,
                                          backend=backend)
        ref = kernel_ops.sparse_attention(q, kr, vr, tables_x, CFG,
                                          backend=backend)
        _check(backend, out, ref, xla_exact=False)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_anchor_attention(self, backend):
        q, k, v = _qkv(4)
        kr, vr = _expand(k, v)
        out = kernel_ops.anchor_attention(q, k, v, CFG, backend=backend)
        ref = kernel_ops.anchor_attention(q, kr, vr, CFG, backend=backend)
        _check(backend, out, ref)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_anchor_attention_capacity_limited(self, backend):
        """Finite cfg.capacity budgets each QUERY head (pre-index
        semantics), so GQA stays exact vs the expanded oracle even under
        overflow."""
        cfg = AnchorConfig(block_q=32, block_kv=32, step=2, theta=8.0,
                           capacity=16)
        q, k, v = _qkv(20)
        kr, vr = _expand(k, v)
        out = kernel_ops.anchor_attention(q, k, v, cfg, backend=backend)
        ref = kernel_ops.anchor_attention(q, kr, vr, cfg, backend=backend)
        _check(backend, out, ref)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_anchor_attention_varlen(self, backend):
        q, k, v = _qkv(5)
        lengths = jnp.asarray([130, 256], jnp.int32)
        kr, vr = _expand(k, v)
        out = kernel_ops.anchor_attention(q, k, v, CFG, lengths=lengths,
                                          backend=backend)
        ref = kernel_ops.anchor_attention(q, kr, vr, CFG, lengths=lengths,
                                          backend=backend)
        _check(backend, out, ref)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_flash_decode(self, backend):
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q = jax.random.normal(ks[0], (B, HQ, 1, D))
        kc = jax.random.normal(ks[1], (B, HKV, 128, D))
        vc = jax.random.normal(ks[2], (B, HKV, 128, D))
        kr, vr = _expand(kc, vc)
        out = kernel_ops.flash_decode(q, kc, vc, jnp.asarray(100),
                                      block_s=32, backend=backend)
        ref = kernel_ops.flash_decode(q, kr, vr, jnp.asarray(100),
                                      block_s=32, backend=backend)
        _check_decode(backend, out, ref)

    @pytest.mark.parametrize("hq,hkv,case", DECODE_CASES,
                             ids=[f"{a}q{b}kv-{c}" for a, b, c in DECODE_CASES])
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_paged_flash_decode(self, backend, hq, hkv, case):
        """Grouped ≡ expanded on each backend, and the kernel ≡ the xla
        twin, at lengths around the kernel's block of pages."""
        q, kp, vp, pt, clen = _paged_case(hq, hkv, case)
        out = kernel_ops.paged_flash_decode(q, kp, vp, pt, clen,
                                            backend=backend)
        kr, vr = (jnp.repeat(x, hq // hkv, axis=1) for x in (kp, vp))
        ref = kernel_ops.paged_flash_decode(q, kr, vr, pt, clen,
                                            backend=backend)
        _check_decode(backend, out, ref)
        if backend != "xla":
            twin = kernel_ops.paged_flash_decode(q, kp, vp, pt, clen,
                                                 backend="xla")
            _check_decode(backend, out, twin)


def _paged_case(hq, hkv, case):
    """q, pool, table and cache length of one paged decode case: the
    original 4-page rows at length 50, or a shuffled 40-page table at
    one of ``DECODE_LENS``."""
    if case == "table4_len50":
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (B, hq, 1, D))
        kp = jax.random.normal(ks[1], (9, hkv, 16, D))
        vp = jax.random.normal(ks[2], (9, hkv, 16, D))
        pt = jnp.asarray([[1, 3, 5, 7], [2, 4, 6, 8]], jnp.int32)
        return q, kp, vp, pt, jnp.asarray(50)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    n_pages, page_size = 40, 16
    pool = (1 + B * n_pages, hkv, page_size, D)
    q = jax.random.normal(ks[0], (B, hq, 1, D))
    kp = jax.random.normal(ks[1], pool)
    vp = jax.random.normal(ks[2], pool)
    pt = (1 + jax.random.permutation(ks[3], B * n_pages)).astype(
        jnp.int32).reshape(B, n_pages)
    step = page_size * pages_per_step(page_size, n_pages, hkv, D, 4)
    clen = jnp.asarray({"one": 1, "page_minus_one": page_size - 1,
                        "one_step": step, "one_step_plus_one": step + 1,
                        "whole_table": n_pages * page_size}[case])
    return q, kp, vp, pt, clen


# -------------------------------------------------- jaxpr inspection ----


def _walk_eqns(jaxpr, fn):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def sub_jaxprs(val):
        if isinstance(val, ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, Jaxpr):
            yield val
        elif hasattr(val, "jaxpr") and isinstance(
                getattr(val, "jaxpr", None), Jaxpr):
            yield val.jaxpr
        elif isinstance(val, (tuple, list)):
            for v in val:
                yield from sub_jaxprs(v)

    for eqn in jaxpr.eqns:
        fn(eqn)
        for val in eqn.params.values():
            for sub in sub_jaxprs(val):
                _walk_eqns(sub, fn)


def _hq_wide_kv_expansions(fn, hq, hkv, d_k, *args):
    """Equations that take a key-dimensioned Hkv-width tensor to Hq width.

    A ``jnp.repeat`` of K (or any head-axis expansion of a (…, Hkv, …,
    D_k) buffer into (…, Hq, …, D_k)) shows up as such an equation; the
    index-driven path must have none.  V is given a distinct head dim by
    the callers so legitimate output/accumulator reshapes (which carry
    D_v) never match.
    """
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    offenders = []

    def check(eqn):
        # Call-like equations (pjit, scan, ...) are just boundaries — their
        # bodies are walked separately, and a boundary computes nothing, so
        # "K in, pooled-q out" signatures across one are not expansions.
        if any(hasattr(v, "jaxpr") or isinstance(v, (tuple, list))
               and any(hasattr(x, "jaxpr") for x in v)
               for v in eqn.params.values()):
            return
        for out in eqn.outvars:
            osh = getattr(out.aval, "shape", ())
            if len(osh) < 4 or osh[1] != hq or osh[-1] != d_k:
                continue
            for inv in eqn.invars:
                ish = getattr(getattr(inv, "aval", None), "shape", ())
                if len(ish) >= 4 and ish[1] == hkv and ish[-1] == d_k:
                    offenders.append(str(eqn.primitive))

    _walk_eqns(jaxpr, check)
    return offenders


class TestNoHqWideKVBuffers:
    def test_detector_fires_on_old_style_gather(self):
        """Positive control: the pre-index gather pipeline IS detected."""
        dv = D // 2  # distinct V head dim so only K-shaped buffers match
        q, k, _ = _qkv(8)
        v = jax.random.normal(jax.random.PRNGKey(9), (B, HKV, N, dv))

        def old_style(q, k, v):
            rep = HQ // HKV
            k_full = jnp.repeat(k, rep, axis=1)
            v_full = jnp.repeat(v, rep, axis=1)
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k_full)
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s), v_full)

        assert _hq_wide_kv_expansions(old_style, HQ, HKV, D, q, k, v)

    def test_index_driven_pipeline_is_clean(self):
        dv = D // 2
        q, k, _ = _qkv(10)
        v = jax.random.normal(jax.random.PRNGKey(11), (B, HKV, N, dv))

        def pipeline(q, k, v):
            return kernel_ops.anchor_attention(q, k, v, CFG, backend="xla")

        assert _hq_wide_kv_expansions(pipeline, HQ, HKV, D, q, k, v) == []

    def test_dense_blockwise_is_clean(self):
        dv = D // 2
        q, k, _ = _qkv(12)
        v = jax.random.normal(jax.random.PRNGKey(13), (B, HKV, N, dv))

        def dense(q, k, v):
            return kernel_ops.flash_attention(q, k, v, backend="xla")

        assert _hq_wide_kv_expansions(dense, HQ, HKV, D, q, k, v) == []


# --------------------------------------------- index-driven vs gathered ----


class TestIndexVsGather:
    """The STAGED sparse stage (the parity oracle) is index-driven too:
    inline tile gathers inside its scan must equal the materialized
    gather twin bit-for-bit on shared tables."""

    def _stages(self, seed, lengths=None):
        q, k, v = _qkv(seed)
        kw = {} if lengths is None else {"lengths": lengths}
        m, l, acc = staged_anchor_stats(q, k, v, CFG, **kw)
        t_m = N // CFG.block_q
        q_mean = jnp.mean(q.reshape(B, HQ, t_m, CFG.block_q, D), axis=3)
        m_bar = jnp.mean(m.reshape(B, HQ, t_m, CFG.block_q), axis=3)
        hit = staged_stripe_mask(q_mean, m_bar, k, CFG, **kw)
        tables, _ = indexing.compact_stripe_tiles(hit, HKV, 32)
        return q, k, v, tables, m, l, acc

    def test_bit_exact_on_xla(self):
        q, k, v, tables, m, l, acc = self._stages(14)
        out_idx = staged_sparse_attention(q, k, v, tables, m, l, acc, CFG)
        k_sel = indexing.gather_stripe_tiles(k, tables)
        v_sel = indexing.gather_stripe_tiles(v, tables)
        out_gat = sparse_attention_gathered(
            q, k_sel, v_sel, tables, m, l, acc, CFG)
        np.testing.assert_array_equal(np.asarray(out_idx), np.asarray(out_gat))
        # Footprint: the materialized tiles are Hkv-wide, not Hq-wide.
        assert k_sel.shape[1] == HKV

    def test_bit_exact_on_xla_varlen(self):
        lengths = jnp.asarray([100, 256], jnp.int32)
        q, k, v, tables, m, l, acc = self._stages(15, lengths)
        out_idx = staged_sparse_attention(q, k, v, tables, m, l, acc, CFG)
        k_sel = indexing.gather_stripe_tiles(k, tables)
        v_sel = indexing.gather_stripe_tiles(v, tables)
        out_gat = sparse_attention_gathered(
            q, k_sel, v_sel, tables, m, l, acc, CFG)
        np.testing.assert_array_equal(np.asarray(out_idx), np.asarray(out_gat))

    def test_varlen_batched_equals_per_sequence(self):
        """The PR-2 varlen contract survives the fused pipeline."""
        lens = [100, 192, 256]
        q, k, v = _qkv(17, b=3)
        lengths = jnp.asarray(lens, jnp.int32)
        spec = AttentionSpec(algorithm="anchor", backend="xla", anchor=CFG,
                             masking="padded")
        out = kernel_ops.attention(q, k, v, spec, lengths=lengths)
        for j, nj in enumerate(lens):
            single = kernel_ops.attention(
                q[j:j + 1], k[j:j + 1], v[j:j + 1], spec,
                lengths=jnp.asarray([nj], jnp.int32))
            np.testing.assert_array_equal(
                np.asarray(out[j]), np.asarray(single[0]))
            assert np.all(np.asarray(out[j, :, nj:]) == 0.0)


# ------------------------------------------------- packing regression ----


class TestPackingCapacityRegression:
    def test_capacity_rounds_past_n(self):
        """N=200, block_c=128: the pre-fix pipeline rounded capacity=None
        up to the next block_c multiple (256 > N) and fed jax.lax.top_k
        an out-of-range k; pack_stripe_indices must instead clamp the
        top_k and pad the extra slots invalid."""
        n, block_c = 200, 128
        cap = -(-n // block_c) * block_c  # the old pipeline's rounding
        assert cap == 256 and cap > n
        rng = np.random.default_rng(0)
        hit = jnp.asarray(rng.integers(0, 2, size=(3, 2, n)), jnp.int32)
        idx, valid = indexing.pack_stripe_indices(hit, cap)
        assert idx.shape == (3, 2, cap) and valid.shape == (3, 2, cap)
        idx_n, valid_n = np.asarray(idx), np.asarray(valid)
        for pos in np.ndindex(hit.shape[:-1]):
            recon = np.zeros(n, np.int32)
            recon[idx_n[pos][valid_n[pos] == 1]] = 1
            np.testing.assert_array_equal(recon, np.asarray(hit)[pos])
            assert (valid_n[pos][n:] == 0).all()  # padded tail invalid


# --------------------------------------------------- chunked anchor ----


class TestChunkedAnchor:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_chunks_reproduce_one_shot_prefill(self, backend):
        cfg = AnchorConfig(block_q=16, block_kv=16, step=2, theta=3.0)
        q, k, v = _qkv(18, b=1, n=256, d=16)
        full = kernel_ops.anchor_attention(q, k, v, cfg, backend=backend)
        chunk = 64  # two identification superblocks
        outs = [
            kernel_ops.chunk_anchor_attention(
                q[:, :, c0:c0 + chunk], k, v, jnp.asarray(c0, jnp.int32),
                cfg, backend=backend)
            for c0 in range(0, 256, chunk)
        ]
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate(outs, axis=2)), np.asarray(full),
            atol=2e-5, rtol=1e-4)

    def test_partial_final_chunk_matches_varlen_one_shot(self):
        """A zero-padded final chunk must reproduce the one-shot varlen
        prefill for its LIVE rows at a selective theta: without the
        ``live`` pooling mask, pad-row queries sharing a block_q block
        with real rows shift q_mean/m_bar and change the stripe
        selection (found in review; theta=1e9 tests can't see it, and
        theta must sit where per-block selections differ — without the
        mask this exact setup diverges by ~0.38)."""
        cfg = AnchorConfig(block_q=16, block_kv=16, step=2, theta=2.0)
        n_pad, n_live, chunk = 128, 90, 64
        q, k, v = _qkv(21, b=1, n=n_pad, d=16)
        # Junk in the pad region makes contamination loud if unmasked.
        junk = 100.0 * jax.random.normal(jax.random.PRNGKey(22), q.shape)
        pad = jnp.arange(n_pad)[None, None, :, None] >= n_live
        qj = jnp.where(pad, junk, q)
        one_shot = kernel_ops.anchor_attention(
            q, k, v, cfg, lengths=jnp.asarray([n_live], jnp.int32),
            backend="xla")
        outs = []
        for c0 in range(0, n_pad, chunk):
            live = jnp.asarray(min(n_live - c0, chunk), jnp.int32)
            outs.append(kernel_ops.chunk_anchor_attention(
                qj[:, :, c0:c0 + chunk], k, v, jnp.asarray(c0, jnp.int32),
                cfg, live=live, backend="xla"))
        chunked = jnp.concatenate(outs, axis=2)
        np.testing.assert_allclose(
            np.asarray(chunked[:, :, :n_live]),
            np.asarray(one_shot[:, :, :n_live]), atol=2e-5, rtol=1e-4)

    def test_rejects_unaligned_chunk(self):
        cfg = AnchorConfig(block_q=16, block_kv=16, step=2, theta=3.0)
        q, k, v = _qkv(19, b=1, n=256, d=16)
        with pytest.raises(ValueError, match="superblock"):
            kernel_ops.chunk_anchor_attention(
                q[:, :, :48], k, v, jnp.asarray(0, jnp.int32), cfg,
                backend="xla")
