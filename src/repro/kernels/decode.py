"""Flash-decode Pallas kernel: one-token attention over a KV cache.

The TPU production path for the decode_32k / long_500k shapes: streams the
cache through VMEM in ``block_s`` tiles with an online softmax carried in
scratch — the kernel twin of the blockwise XLA path introduced in §Perf
iteration A3 (scores never touch HBM).

Grid: ``(batch*heads, S // block_s)`` with the cache axis sequential.
``cache_len`` arrives via scalar prefetch (SMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch
from repro.kernels.indexing import kv_head_index

_NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, ms_ref, ls_ref,
                   acc_ref, *, block_s, scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ms_ref[...] = jnp.full_like(ms_ref, _NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (1, D)
    k = k_ref[0].astype(jnp.float32)  # (block_s, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (1, block_s)
    cache_len = len_ref[0]
    col = j * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = col < cache_len
    s = jnp.where(valid, s, _NEG_INF)
    m_prev = ms_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    ls_ref[...] = ls_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    # Zero masked V rows too: a grid padded with a partial tail block reads
    # garbage (possibly NaN) beyond s_len, and 0 * NaN would poison acc.
    # The row mask is built in V's own (block_s, D) layout.
    v = v_ref[0].astype(jnp.float32)
    v_row = j * block_s + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    v = jnp.where(v_row < cache_len, v, 0.0)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    ms_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(ls_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,
    block_s: int = 512,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """One-token decode attention.

    q: (B, Hq, 1, D); caches: (B, Hkv, S, D); cache_len: () int32.
    Returns (B, Hq, 1, D).
    """
    b, hq, _, d = q.shape
    hkv, s_len = k_cache.shape[1], k_cache.shape[2]
    # Any cache length works: clamp the tile to the cache, then pad the
    # grid with a (masked) tail block when block_s does not divide s_len.
    # Tail-block columns land at >= s_len >= cache_len, so the existing
    # `col < cache_len` mask already zeroes their contribution.
    block_s = max(1, min(block_s, s_len))
    num_s_blocks = -(-s_len // block_s)
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b * hq, 1, d)
    kf = k_cache.reshape(b * hkv, s_len, d)
    vf = v_cache.reshape(b * hkv, s_len, d)
    len_arr = jnp.full((1,), cache_len, jnp.int32)

    def q_index(bh, j, len_ref):
        del j, len_ref
        return bh, 0, 0

    def kv_index(bh, j, len_ref):
        del len_ref
        return kv_head_index(bh, hq, hkv), j, 0

    kernel = functools.partial(_decode_kernel, block_s=block_s, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hq, num_s_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, d), q_index),
            pl.BlockSpec((1, block_s, d), kv_index),
            pl.BlockSpec((1, block_s, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(len_arr, qf, kf, vf)
    return out.reshape(b, hq, 1, d)


dispatch.register("flash_decode", "pallas_interpret")(
    functools.partial(flash_decode, interpret=True))
dispatch.register("flash_decode", "pallas_tpu")(
    functools.partial(flash_decode, interpret=False))


# ------------------------------------------------------ paged flash decode ----


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         ms_ref, ls_ref, acc_ref, *, page_size, scale):
    del pt_ref  # consumed by the BlockSpec index maps
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ms_ref[...] = jnp.full_like(ms_ref, _NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (1, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (page_size, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (1, page_size)
    # Logical column of each lane: the page table maps logical page j onto
    # an arbitrary physical page, but the *positions* it holds are always
    # [j*page_size, (j+1)*page_size) — trash/unassigned pages sit at
    # logical positions >= cache_len and are masked out here.
    col = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = col < len_ref[0]
    s = jnp.where(valid, s, _NEG_INF)
    m_prev = ms_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    ls_ref[...] = ls_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    ms_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(ls_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_decode(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,
    cache_len: jnp.ndarray,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """One-token decode attention over a *paged* KV cache.

    The page-table indirection lives in the BlockSpec index map: logical
    KV tile ``j`` of batch row ``b`` is DMA'd from physical page
    ``page_tables[b, j]`` of the shared pool — the serving-side twin of
    the paper's discrete KV position loading (the kernel streams scattered
    pages exactly like the sparse path streams scattered stripes).
    ``page_tables`` arrives via scalar prefetch so the indices are on-core
    before the grid body runs.

    q: (B, Hq, 1, D); pages: (P, Hkv, page_size, D);
    page_tables: (B, n_pages) int32 physical page ids (0 = null page);
    cache_len: () int32.  Returns (B, Hq, 1, D).
    """
    b, hq, _, d = q.shape
    hkv, page_size = k_pages.shape[1], k_pages.shape[2]
    n_pages = page_tables.shape[1]
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b * hq, 1, d)
    pt = page_tables.astype(jnp.int32)
    len_arr = jnp.full((1,), cache_len, jnp.int32)

    def q_index(bh, j, pt_ref, len_ref):
        return bh, 0, 0

    def kv_index(bh, j, pt_ref, len_ref):
        # Page-table indirection + the shared GQA fold: physical page id
        # from the scalar-prefetched table, KV head from kv_head_index
        # (modulo the batch term, which the page axis already encodes).
        return pt_ref[bh // hq, j], kv_head_index(bh % hq, hq, hkv), 0, 0

    kernel = functools.partial(
        _paged_decode_kernel, page_size=page_size, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hq, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, d), q_index),
            pl.BlockSpec((1, 1, page_size, d), kv_index),
            pl.BlockSpec((1, 1, page_size, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pt, len_arr, qf, k_pages, v_pages)
    return out.reshape(b, hq, 1, d)


dispatch.register("paged_flash_decode", "pallas_interpret")(
    functools.partial(paged_flash_decode, interpret=True))
dispatch.register("paged_flash_decode", "pallas_tpu")(
    functools.partial(paged_flash_decode, interpret=False))
