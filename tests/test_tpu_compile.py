"""Ahead-of-time compiles of the main-path Pallas kernels for TPU v5e.

Interpret mode accepts kernels that Mosaic refuses (block shapes off the
(8, 128) tiling, unsupported shape casts, primitives with no TPU
lowering, too much fast memory).  These tests compile every main-path
kernel with ``backend="pallas_tpu"`` for one chip of a *described* v5e
topology — the TPU compiler runs here, no chip is attached — at
internlm2-1.8b attention widths (D=128, N=8192; Hq/Hkv = 16/8 and
32/8), plus one 32k ``anchor_attention`` whose compiled memory must fit
a 16 GB chip.  Nothing runs, so nothing here checks results.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.config import AnchorConfig
from repro.kernels import indexing
from repro.kernels import ops as kernel_ops

TPU = "pallas_tpu"
D, N, N_LONG = 128, 8192, 32768
HEADS = [(16, 8), (32, 8)]
CFG = AnchorConfig(capacity=4096)  # paper blocking 128/128/16
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _qkv(sharding, hq, hkv, n=N):
    return (_shape(sharding, (1, hq, n, D)),
            _shape(sharding, (1, hkv, n, D)),
            _shape(sharding, (1, hkv, n, D)))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _selected_tables(q, k, n):
    """Stripe tables for ``sparse_attention``, identified on xla."""
    q_mean, m_bar = kernel_ops.anchor_phase(q, k, CFG, backend="xla")
    sel, _ = kernel_ops.stripe_select(q_mean, m_bar, k, CFG, CFG.block_kv,
                                      backend="xla")
    return indexing.merge_anchor_slots(sel, n, CFG)


def _lengths(sharding):
    return _shape(sharding, (1,), jnp.int32)


KERNELS = {
    "flash_attention": lambda s, q, k, v: (
        lambda q, k, v: kernel_ops.flash_attention(q, k, v, backend=TPU),
        (q, k, v)),
    "flash_attention_lengths": lambda s, q, k, v: (
        lambda q, k, v, n: kernel_ops.flash_attention(
            q, k, v, lengths=n, backend=TPU),
        (q, k, v, _lengths(s))),
    "anchor_phase": lambda s, q, k, v: (
        lambda q, k: kernel_ops.anchor_phase(q, k, CFG, backend=TPU),
        (q, k)),
    "stripe_select": lambda s, q, k, v: (
        lambda qm, mb, k: kernel_ops.stripe_select(
            qm, mb, k, CFG, CFG.block_kv, backend=TPU),
        (_shape(s, (1, q.shape[1], N // CFG.block_q, D), jnp.float32),
         _shape(s, (1, q.shape[1], N // CFG.block_q), jnp.float32), k)),
    "sparse_attention": lambda s, q, k, v: (
        lambda q, k, v: kernel_ops.sparse_attention(
            q, k, v, _selected_tables(q, k, N), CFG, backend=TPU),
        (q, k, v)),
    "anchor_attention": lambda s, q, k, v: (
        lambda q, k, v, n: kernel_ops.anchor_attention(
            q, k, v, CFG, lengths=n, backend=TPU),
        (q, k, v, _lengths(s))),
    "flash_decode": lambda s, q, k, v: (
        lambda q, k, v, n: kernel_ops.flash_decode(q, k, v, n, backend=TPU),
        (_shape(s, (1, q.shape[1], 1, D)), k, v,
         _shape(s, (), jnp.int32))),
    "paged_flash_decode": lambda s, q, k, v: (
        lambda q, kp, vp, pt, n: kernel_ops.paged_flash_decode(
            q, kp, vp, pt, n, backend=TPU),
        (_shape(s, (1, q.shape[1], 1, D)),
         _shape(s, (1 + N // 16, k.shape[1], 16, D)),
         _shape(s, (1 + N // 16, k.shape[1], 16, D)),
         _shape(s, (1, N // 16), jnp.int32), _shape(s, (), jnp.int32))),
}


@pytest.mark.parametrize("hq,hkv", HEADS, ids=[f"{a}q{b}kv" for a, b in HEADS])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, hq, hkv):
    fn, args = KERNELS[kernel](one_chip, *_qkv(one_chip, hq, hkv))
    _compile(fn, *args)


def test_anchor_attention_32k_fits_one_chip(one_chip):
    q, k, v = _qkv(one_chip, 16, 8, N_LONG)
    compiled = _compile(
        lambda q, k, v: kernel_ops.anchor_attention(q, k, v, CFG,
                                                    backend=TPU),
        q, k, v)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, mem
