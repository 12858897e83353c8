"""Fused zero-materialization identification (DESIGN.md §9).

Contracts of the fused AnchorAttention pipeline:

1. **Fused ≡ staged** — the fused pipeline (scores-only Alg. 1 →
   compact Alg. 2 → one zero-state sparse sweep) reproduces the staged
   oracle (:func:`repro.kernels.ops.anchor_attention_staged`) at
   tolerance (the fused sweep changes the summation order) across GQA,
   varlen, capacity, share_kv_groups, the use_anchor ablation, and
   ragged superblocks, on ``xla`` and ``pallas_interpret``.
2. **Compact select ≡ dense-mask compaction** — ``stripe_select``'s
   in-scan/in-kernel compaction is bit-identical to
   ``compact_stripe_tiles`` over the staged dense hit mask.
3. **Footprint** — jaxpr inspection: the fused xla pipeline contains no
   ``(…, T_s, N)`` hit-mask equation and no f32 full-resolution
   statistics (``(…, N)`` row stats / ``(…, N, Dv)`` accumulator).  The
   detector is validated on the staged oracle, which materializes all
   three (positive control).
4. **Anchor slots** — the guaranteed leading table slots plus the
   in-sweep causal trim reproduce exactly the per-row anchor region of
   ``core.masks.anchor_region_mask``.

Plus unit tests for the shared varlen plumbing helper
(``length_grid_operand``) that flash/anchor/stripe-select now share.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AnchorConfig
from repro.core import masks as masks_lib
from repro.kernels import indexing
from repro.kernels import ops as kernel_ops
from repro.kernels.xla import staged_stripe_mask

BACKENDS = ("xla", "pallas_interpret")


def _qkv(seed, b=2, hq=4, hkv=2, n=256, d=32, dv=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, hq, n, d)),
            jax.random.normal(ks[1], (b, hkv, n, d)),
            jax.random.normal(ks[2], (b, hkv, n, dv or d)))


def _tol(backend):
    return dict(atol=2e-5, rtol=1e-4)


# ------------------------------------------------------ fused ≡ staged ----


class TestFusedEqualsStaged:
    CASES = [
        # (name, cfg kwargs, qkv kwargs, lengths)
        ("base", {}, {}, None),
        ("varlen", {}, {}, [130, 256]),
        ("capacity", dict(capacity=16, theta=8.0), {}, None),
        ("share", dict(share_kv_groups=True), {}, None),
        ("no_anchor", dict(use_anchor=False, theta=-2.0), {}, None),
        ("mha", {}, dict(hq=2, hkv=2), None),
        ("capacity_varlen", dict(capacity=16, theta=8.0), {}, [100, 224]),
    ]

    @pytest.mark.quick
    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize(
        "name,cfg_kw,qkv_kw,lens", CASES, ids=[c[0] for c in CASES])
    def test_pipeline_matches_staged_oracle(self, backend, name, cfg_kw,
                                            qkv_kw, lens):
        cfg = AnchorConfig(**{**dict(block_q=32, block_kv=32, step=2,
                                     theta=3.0), **cfg_kw})
        q, k, v = _qkv(hash(name) % 1000, **qkv_kw)
        lengths = None if lens is None else jnp.asarray(lens, jnp.int32)
        fused = kernel_ops.anchor_attention(
            q, k, v, cfg, lengths=lengths, backend=backend)
        staged = kernel_ops.anchor_attention_staged(
            q, k, v, cfg, lengths=lengths)
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(staged), **_tol(backend))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_ragged_superblock(self, backend):
        """N not a multiple of the superblock: the trailing partial
        superblock's anchor window clips to N."""
        cfg = AnchorConfig(block_q=32, block_kv=32, step=4, theta=3.0)
        q, k, v = _qkv(7, n=320)  # sb_q = 128, N = 2.5 superblocks
        fused = kernel_ops.anchor_attention(q, k, v, cfg, backend=backend)
        staged = kernel_ops.anchor_attention_staged(q, k, v, cfg)
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(staged), **_tol(backend))

    def test_return_stats_counts_match_staged(self):
        cfg = AnchorConfig(block_q=32, block_kv=32, step=2, theta=3.0)
        q, k, v = _qkv(9)
        _, fused = kernel_ops.anchor_attention(
            q, k, v, cfg, return_stats=True, backend="xla")
        _, staged = kernel_ops.anchor_attention_staged(
            q, k, v, cfg, return_stats=True)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(staged))

    def test_mla_asymmetric_value_dim(self):
        """Dv != Dk (MLA decompressed views) flows through the fused
        sweep."""
        cfg = AnchorConfig(block_q=32, block_kv=32, step=2, theta=3.0)
        q, k, v = _qkv(11, dv=16)
        fused = kernel_ops.anchor_attention(q, k, v, cfg, backend="xla")
        staged = kernel_ops.anchor_attention_staged(q, k, v, cfg)
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(staged), **_tol("xla"))


# --------------------------------- compact select ≡ dense compaction ----


class TestCompactSelectEquivalence:
    @pytest.mark.quick
    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("cfg_kw,lens", [
        ({}, None),
        ({}, [100, 256]),
        (dict(capacity=16, theta=8.0), None),
        (dict(share_kv_groups=True), None),
        (dict(capacity=4, share_kv_groups=True, theta=8.0), [130, 256]),
    ])
    def test_tables_bitwise_equal(self, backend, cfg_kw, lens):
        cfg = AnchorConfig(**{**dict(block_q=32, block_kv=32, step=2,
                                     theta=3.0), **cfg_kw})
        q, k, _ = _qkv(13)
        lengths = None if lens is None else jnp.asarray(lens, jnp.int32)
        kw = {} if lengths is None else {"lengths": lengths}
        q_mean, m_bar = kernel_ops.anchor_phase(q, k, cfg, backend="xla",
                                                **kw)
        got, counts = kernel_ops.stripe_select(
            q_mean, m_bar, k, cfg, 32, backend=backend, **kw)
        hit = staged_stripe_mask(q_mean, m_bar, k, cfg, **kw)
        want, want_counts = indexing.compact_stripe_tiles(
            hit, k.shape[1], 32, cfg.capacity, share=cfg.share_kv_groups)
        np.testing.assert_array_equal(np.asarray(got.tile_idx),
                                      np.asarray(want.tile_idx))
        np.testing.assert_array_equal(np.asarray(got.tile_valid),
                                      np.asarray(want.tile_valid))
        np.testing.assert_array_equal(np.asarray(got.valid),
                                      np.asarray(want.valid))
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))


# ------------------------------------------------------- anchor slots ----


class TestAnchorSlots:
    def _slots_to_rowmask(self, n, cfg, tile):
        t_s = cfg.num_superblocks(n)
        idx, tvalid, valid = indexing.anchor_tile_slots(n, t_s, tile, cfg)
        idx, tvalid, valid = (np.asarray(x) for x in (idx, tvalid, valid))
        a = idx.shape[1]
        region = np.zeros((n, n), bool)
        sb_q = cfg.superblock_q()
        for s in range(t_s):
            cols = np.zeros(n, bool)
            for c in range(a):
                bits = valid[s, c * tile:(c + 1) * tile].astype(bool)
                if tvalid[s, c]:
                    t = idx[s, c]
                    cols[t * tile:(t + 1) * tile] |= bits
            for r in range(s * sb_q, min((s + 1) * sb_q, n)):
                region[r] = cols & (np.arange(n) <= r)  # in-sweep causal trim
        return region

    @pytest.mark.parametrize("tile", [16, 32, 64, 128])
    def test_slots_reproduce_anchor_region(self, tile):
        """Anchor slots + causal trim ≡ the dense anchor-region mask, for
        tiles smaller and LARGER than block_kv (partial-tile valid bits)."""
        cfg = AnchorConfig(block_q=32, block_kv=32, step=2, theta=3.0)
        n = 256
        got = self._slots_to_rowmask(n, cfg, tile)
        want = np.asarray(masks_lib.anchor_region_mask(n, cfg))
        np.testing.assert_array_equal(got, want)

    def test_ragged_last_superblock_clips(self):
        cfg = AnchorConfig(block_q=32, block_kv=32, step=4, theta=3.0)
        n = 320  # 2.5 superblocks
        got = self._slots_to_rowmask(n, cfg, 32)
        want = np.asarray(masks_lib.anchor_region_mask(n, cfg))
        np.testing.assert_array_equal(got, want)

    def test_merge_prepends_and_preserves_selection(self):
        cfg = AnchorConfig(block_q=32, block_kv=32, step=2, theta=3.0)
        q, k, _ = _qkv(15)
        q_mean, m_bar = kernel_ops.anchor_phase(q, k, cfg, backend="xla")
        sel, _ = kernel_ops.stripe_select(q_mean, m_bar, k, cfg, 32,
                                          backend="xla")
        merged = kernel_ops.merge_anchor_slots(sel, 256, cfg)
        a = merged.tile_idx.shape[-1] - sel.tile_idx.shape[-1]
        assert a == indexing.num_anchor_slots(32, cfg)
        np.testing.assert_array_equal(
            np.asarray(merged.tile_idx[..., a:]), np.asarray(sel.tile_idx))
        np.testing.assert_array_equal(
            np.asarray(merged.valid[..., a * 32:]), np.asarray(sel.valid))


# -------------------------------------------------- jaxpr footprint ----


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
        subs = [s for val in eqn.params.values() for s in sub_jaxprs(val)]
        if subs:  # call boundary: walk the body, skip the boundary itself
            for sub in subs:
                _walk_eqns(sub, fn)
        else:
            fn(eqn)


def _identification_offenders(fn, n, t_s, hq, dv, *args):
    """Equations materializing what fused identification must not.

    * ``mask``: any (…, T_s, N) array — the dense stripe hit mask grows
      quadratically with context length;
    * ``row_stats``: f32 with a trailing N axis — per-row ``m``/``l``
      statistics or pooled-score rows at full key resolution;
    * ``acc``: f32 (B, Hq, N, Dv) — the Hq-wide accumulator round-trip
      (2× the bf16 output's bytes).  The Hq head-axis requirement keeps
      legitimately input-sized Hkv-wide V arrays (the f32 upcast and the
      contiguous window gather) out of scope: those are O(N·Hkv) data,
      not per-query-head statistics.
    """
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    offenders = {"mask": [], "row_stats": [], "acc": []}

    def check(eqn):
        for out in eqn.outvars:
            aval = getattr(out, "aval", None)
            shape = getattr(aval, "shape", ())
            dtype = getattr(aval, "dtype", None)
            if len(shape) >= 3 and shape[-1] == n and shape[-2] == t_s:
                offenders["mask"].append(str(eqn.primitive))
            if dtype == jnp.float32 and len(shape) >= 2:
                if shape[-1] == n:
                    offenders["row_stats"].append(str(eqn.primitive))
                if (len(shape) >= 4 and shape[1] == hq
                        and shape[-1] == dv and shape[-2] == n):
                    offenders["acc"].append(str(eqn.primitive))

    _walk_eqns(jaxpr, check)
    return offenders


class TestIdentificationFootprint:
    # Dimensions chosen pairwise-distinct so shape matching is unambiguous,
    # with a capacity that genuinely binds (c_sel·tile < N).
    B, HQ, HKV, N, D, DV = 2, 4, 2, 2048, 32, 16
    CFG = AnchorConfig(block_q=32, block_kv=32, step=4, theta=8.0,
                       capacity=6)
    BLOCK_C = 64  # tile 64 ⇒ 32 tiles, c_sel = 12 ⇒ tables < N wide
    T_S = 16

    def _inputs(self):
        ks = jax.random.split(jax.random.PRNGKey(23), 3)
        # bf16 inputs: every f32 full-resolution array in the jaxpr is a
        # pipeline-created intermediate, not an input alias.
        return (jax.random.normal(ks[0], (self.B, self.HQ, self.N, self.D)
                                  ).astype(jnp.bfloat16),
                jax.random.normal(ks[1], (self.B, self.HKV, self.N, self.D)
                                  ).astype(jnp.bfloat16),
                jax.random.normal(ks[2], (self.B, self.HKV, self.N, self.DV)
                                  ).astype(jnp.bfloat16))

    @pytest.mark.quick
    def test_detector_fires_on_staged_oracle(self):
        """Positive control: the staged pipeline materializes the dense
        mask, the f32 row statistics, AND the f32 accumulator."""
        q, k, v = self._inputs()

        def staged(q, k, v):
            return kernel_ops.anchor_attention_staged(
                q, k, v, self.CFG, block_c=self.BLOCK_C)

        off = _identification_offenders(
            staged, self.N, self.T_S, self.HQ, self.DV, q, k, v)
        assert off["mask"], "staged dense hit mask not detected"
        assert off["row_stats"], "staged f32 row statistics not detected"
        assert off["acc"], "staged f32 accumulator not detected"

    @pytest.mark.quick
    def test_fused_pipeline_is_clean(self):
        """The fused path materializes none of the three: identification
        intermediates are O(capacity) per (KV head, superblock)."""
        q, k, v = self._inputs()

        def fused(q, k, v):
            return kernel_ops.anchor_attention(
                q, k, v, self.CFG, block_c=self.BLOCK_C, backend="xla")

        off = _identification_offenders(
            fused, self.N, self.T_S, self.HQ, self.DV, q, k, v)
        assert off == {"mask": [], "row_stats": [], "acc": []}, off

    def test_fused_chunk_is_clean(self):
        """Chunked prefill identification is equally compact."""
        q, k, v = self._inputs()
        chunk = self.CFG.superblock_q() * 4

        def fused_chunk(qc, k, v):
            return kernel_ops.chunk_anchor_attention(
                qc, k, v, jnp.asarray(chunk, jnp.int32), self.CFG,
                block_c=self.BLOCK_C, backend="xla")

        off = _identification_offenders(
            fused_chunk, self.N, self.T_S, self.HQ, self.DV,
            q[:, :, chunk:2 * chunk], k, v)
        assert off == {"mask": [], "row_stats": [], "acc": []}, off


# -------------------------------------------------- compact metrics ----


class TestCompactMetrics:
    def test_matches_mask_metrics_on_same_selection(self):
        """stripe_tables_metrics ≡ the retired mask-based metrics when
        the dense mask is reconstructed from the SAME compact tables."""
        from repro.core import metrics as metrics_lib

        cfg = AnchorConfig(block_q=32, block_kv=32, step=2, theta=3.0)
        n, d = 256, 32
        ks = jax.random.split(jax.random.PRNGKey(33), 2)
        q = jax.random.normal(ks[0], (n, d))
        k = jax.random.normal(ks[1], (n, d))
        qm, mb = kernel_ops.anchor_phase(q[None, None], k[None, None], cfg,
                                         backend="xla")
        tables, counts = kernel_ops.stripe_select(
            qm, mb, k[None, None], cfg, 32, backend="xla")
        got = metrics_lib.stripe_tables_metrics(q, k, tables, counts, cfg)

        # Dense oracle on the SAME selection.
        idx = np.asarray(tables.tile_idx[0, 0])
        valid = np.asarray(tables.valid[0, 0, 0])
        t_s, c_t = idx.shape
        tile = tables.tile
        sel = np.zeros((t_s, n), bool)
        for s in range(t_s):
            for c in range(c_t):
                t = idx[s, c]
                sel[s, t * tile:(t + 1) * tile] |= (
                    valid[s, c * tile:(c + 1) * tile] != 0)
        per_row = np.repeat(sel, cfg.superblock_q(), axis=0)[:n]
        mask = jnp.asarray(per_row) | masks_lib.anchor_region_mask(n, cfg)
        mask &= masks_lib.causal_mask(n)
        r, sp = metrics_lib.mask_recall_sparsity(q, k, mask)
        assert abs(got["recall"] - float(r)) < 1e-5
        assert abs(got["sparsity"] - float(sp)) < 1e-9


# ------------------------------------------- shared varlen plumbing ----


class TestLengthGridOperand:
    """The shared varlen operand every Pallas kernel scalar-prefetches."""

    @pytest.mark.quick
    def test_values_and_spec(self):
        lengths = jnp.asarray([3, 7], jnp.int32)
        operand = indexing.prefetch_lengths(lengths, 2, 32)
        # One int32 per batch row, 1-D: kernels index it from SMEM with
        # their grid row's batch id, whatever the grid arity is.
        assert operand.shape == (2,)
        np.testing.assert_array_equal(np.asarray(operand), [3, 7])

    def test_none_means_fully_valid(self):
        operand = indexing.prefetch_lengths(None, 3, 17)
        assert operand.shape == (3,)
        assert (np.asarray(operand) == 17).all()

    def test_dtype_coerced(self):
        operand = indexing.prefetch_lengths(jnp.asarray([4.0, 5.0]), 2, 8)
        assert operand.dtype == jnp.int32
