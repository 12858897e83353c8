"""Backend-dispatch layer tests.

Parity: the ``xla`` and ``pallas_interpret`` backends of every public op
must agree with the dense oracles in ``kernels/ref.py`` (and with each
other).  The JAX APIs the kernels and collectives call directly
(``pltpu.CompilerParams``, ``AbstractMesh``, ``jax.shard_map``) are
checked against the installed JAX.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AnchorConfig
from repro.kernels import dispatch
from repro.kernels import ops as kernel_ops
from repro.kernels.ref import (
    anchor_attention_ref,
    anchor_phase_ref,
    flash_attention_ref,
    ssd_ref,
    stripe_mask_ref,
)

PARITY_BACKENDS = ("xla", "pallas_interpret")


def _qkv(seed, b, hq, hkv, n, d):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, hq, n, d))
    k = jax.random.normal(k2, (b, hkv, n, d))
    v = jax.random.normal(k3, (b, hkv, n, d))
    return q, k, v


class TestBackendParity:
    """Every public op: xla ≡ pallas_interpret ≡ ref oracle."""

    @pytest.mark.parametrize("backend", PARITY_BACKENDS)
    def test_flash_attention(self, backend):
        q, k, v = _qkv(0, 2, 4, 2, 128, 32)
        out = kernel_ops.flash_attention(
            q, k, v, block_q=32, block_kv=32, backend=backend)
        kr, vr = jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1)
        ref = jax.vmap(jax.vmap(flash_attention_ref))(q, kr, vr)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("backend", PARITY_BACKENDS)
    def test_anchor_phase(self, backend):
        """Scores-only Alg. 1: pooled (q_mean, m_bar) match the pooled
        dense oracle statistics."""
        cfg = AnchorConfig(block_q=32, block_kv=32, step=2, theta=2.0)
        q, k, v = _qkv(1, 1, 2, 1, 128, 32)
        q_mean, m_bar = kernel_ops.anchor_phase(q, k, cfg, backend=backend)
        kr, vr = jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1)
        t_m = 128 // 32
        for h in range(2):
            mr, _, _ = anchor_phase_ref(q[0, h], kr[0, h], vr[0, h], cfg)
            np.testing.assert_allclose(
                np.asarray(m_bar[0, h]),
                np.asarray(jnp.mean(mr.reshape(t_m, 32), axis=1)),
                atol=1e-5)
            np.testing.assert_allclose(
                np.asarray(q_mean[0, h]),
                np.asarray(jnp.mean(
                    q[0, h].reshape(t_m, 32, 32).astype(jnp.float32),
                    axis=1)),
                atol=1e-5)

    @pytest.mark.parametrize("backend", PARITY_BACKENDS)
    def test_stripe_select(self, backend):
        """Compact Alg. 2: tables ≡ compact_stripe_tiles over the dense
        oracle mask (no dense mask exists on the op path)."""
        cfg = AnchorConfig(block_q=32, block_kv=32, step=2, theta=2.0)
        q, k, v = _qkv(2, 1, 2, 1, 128, 32)
        q_mean, m_bar = kernel_ops.anchor_phase(q, k, cfg, backend="xla")
        tables, counts = kernel_ops.stripe_select(
            q_mean, m_bar, k, cfg, 32, backend=backend)
        kr = jnp.repeat(k, 2, 1)
        t_m, t_s = 128 // 32, cfg.num_superblocks(128)
        hits = []
        for h in range(2):
            # The dense oracle, fed the op's own pooled threshold inputs.
            s = (q_mean[0, h].astype(jnp.float32)
                 @ kr[0, h].T.astype(jnp.float32)) / jnp.sqrt(32.0)
            hit = (m_bar[0, h][:, None] - s) <= cfg.theta
            hit = hit.reshape(t_s, cfg.step, 128).any(axis=1)
            kidx = jnp.arange(128)[None, :]
            w_start = (jnp.maximum(
                1, jnp.arange(t_s) * cfg.step * cfg.r) * cfg.block_kv)[:, None]
            hits.append(hit & (kidx >= cfg.block_kv) & (kidx < w_start))
        dense = jnp.stack(hits)[None].astype(jnp.int32)  # (1, Hq, T_s, N)
        want, want_counts = kernel_ops.compact_stripe_tiles(dense, 1, 32)
        np.testing.assert_array_equal(np.asarray(tables.tile_idx),
                                      np.asarray(want.tile_idx))
        np.testing.assert_array_equal(np.asarray(tables.tile_valid),
                                      np.asarray(want.tile_valid))
        np.testing.assert_array_equal(np.asarray(tables.valid),
                                      np.asarray(want.valid))
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))

    @pytest.mark.parametrize("backend", PARITY_BACKENDS)
    def test_anchor_attention_end_to_end(self, backend):
        """Exercises sparse_attention too (Alg. 3 resumes inside the
        pipeline on the pallas path and in core on the xla path)."""
        cfg = AnchorConfig(block_q=32, block_kv=32, step=2, theta=3.0)
        q, k, v = _qkv(3, 1, 4, 2, 256, 32)
        out = kernel_ops.anchor_attention(q, k, v, cfg, block_c=32,
                                          backend=backend)
        kr, vr = jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1)
        ref = jax.vmap(jax.vmap(
            lambda a, b_, c: anchor_attention_ref(a, b_, c, cfg)))(q, kr, vr)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)

    def test_sparse_attention_cross_backend(self):
        """Direct op parity on synthesized index tables (GQA, Hkv < Hq):
        anchor slots + random stripe selection, one fused sweep."""
        cfg = AnchorConfig(block_q=32, block_kv=32, step=2, theta=1e9)
        b, hq, hkv, n, d, tile = 1, 4, 2, 128, 16, 32
        t_s = cfg.num_superblocks(n)
        ks = jax.random.split(jax.random.PRNGKey(4), 4)
        q = jax.random.normal(ks[0], (b, hq, n, d))
        k = jax.random.normal(ks[1], (b, hkv, n, d))
        v = jax.random.normal(ks[2], (b, hkv, n, d))
        # Random stripe hits restricted to the candidate range, so the
        # merged tables describe a real (anchor ∪ stripes) pattern.
        hit = jax.random.bernoulli(ks[3], 0.3, (b, hq, t_s, n))
        kidx = jnp.arange(n)[None, :]
        w_start = (jnp.maximum(
            1, jnp.arange(t_s) * cfg.step * cfg.r) * cfg.block_kv)[:, None]
        hit &= ((kidx >= cfg.block_kv) & (kidx < w_start))[None, None]
        sel, _ = kernel_ops.compact_stripe_tiles(
            hit.astype(jnp.int32), hkv, tile)
        tables = kernel_ops.merge_anchor_slots(sel, n, cfg)
        outs = [
            np.asarray(kernel_ops.sparse_attention(
                q, k, v, tables, cfg, block_c=tile, backend=be))
            for be in PARITY_BACKENDS
        ]
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("backend", PARITY_BACKENDS)
    def test_flash_decode(self, backend):
        from repro.models.layers import decode_attention

        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (2, 4, 1, 32))
        kc = jax.random.normal(ks[1], (2, 2, 128, 32))
        vc = jax.random.normal(ks[2], (2, 2, 128, 32))
        out = kernel_ops.flash_decode(q, kc, vc, jnp.asarray(100),
                                      block_s=32, backend=backend)
        ref = decode_attention(q, kc, vc, jnp.asarray(100))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("backend", PARITY_BACKENDS)
    def test_ssd(self, backend):
        keys = jax.random.split(jax.random.PRNGKey(6), 5)
        bh, l, p, s = 2, 128, 16, 8
        x = jax.random.normal(keys[0], (bh, l, p))
        dt = jax.nn.softplus(jax.random.normal(keys[1], (bh, l))) * 0.1
        a = -jnp.exp(jax.random.normal(keys[2], (bh,)) * 0.5)
        b = jax.random.normal(keys[3], (bh, l, s))
        c = jax.random.normal(keys[4], (bh, l, s))
        y, h = kernel_ops.ssd_chunked(x, dt, a, b, c, chunk=32,
                                      backend=backend)
        yr, hr = jax.vmap(ssd_ref)(x, dt, a, b, c)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   atol=1e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.quick
class TestDispatchRegistry:
    def test_all_ops_have_all_backends(self):
        ops = dispatch.registered_ops()
        assert set(ops) >= {
            "flash_attention", "flash_decode", "anchor_phase",
            "stripe_select", "sparse_attention", "ssd", "anchor_attention",
        }
        for op in ops:
            assert dispatch.registered_backends(op) == sorted(
                dispatch.BACKENDS), op

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown backend"):
            dispatch.resolve_backend("triton")
        with pytest.raises(ValueError, match="unknown backend"):
            dispatch.set_default_backend("cuda")

    def test_unknown_op_reports_registered_backends(self):
        with pytest.raises(NotImplementedError, match="op unknown"):
            dispatch.lookup("no_such_op", "xla")

    def test_default_backend_override_and_env(self, monkeypatch):
        dispatch.set_default_backend("xla")
        try:
            assert dispatch.default_backend() == "xla"
            assert dispatch.resolve_backend(None) == "xla"
            assert dispatch.resolve_backend("pallas_interpret") == (
                "pallas_interpret")
        finally:
            dispatch.set_default_backend(None)
        monkeypatch.setenv("REPRO_BACKEND", "xla")
        assert dispatch.default_backend() == "xla"
        monkeypatch.delenv("REPRO_BACKEND")
        assert dispatch.default_backend() in ("pallas_interpret", "pallas_tpu")


class TestCompatShims:
    """The installed JAX's own spellings, as the call sites use them."""

    def test_tpu_compiler_params_real_jax(self):
        from jax.experimental.pallas import tpu as pltpu

        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
        assert params.dimension_semantics == ("parallel", "arbitrary")

    def test_abstract_mesh_real_jax(self):
        m = jax.sharding.AbstractMesh((8, 2), ("data", "model"))
        assert tuple(m.axis_names) == ("data", "model")

    def test_real_shard_map_runs(self):
        """jax.shard_map executes on the real single-device mesh."""
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh((1,), ("data",))
        out = jax.shard_map(
            lambda x: x * 2, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False)(jnp.arange(4.0))
        np.testing.assert_allclose(np.asarray(out), [0.0, 2.0, 4.0, 6.0])
