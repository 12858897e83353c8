"""Flash-decode Pallas kernel: one-token attention over a KV cache.

The TPU production path for the decode_32k / long_500k shapes: streams the
cache through VMEM in ``block_s`` tiles with an online softmax carried in
scratch — the kernel twin of the blockwise XLA path introduced in §Perf
iteration A3 (scores never touch HBM).

Grid: ``(batch*heads, S // block_s)`` with the cache axis sequential.
``cache_len`` arrives via scalar prefetch (SMEM).

``paged_flash_decode`` reads the serving engine's page pool instead:
grid ``(batch, ceil(n_pages / pages_per_step))``, one step per block of
pages for every KV head and its whole query group, steps past
``cache_len`` idle.
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


# Positions one grid step of ``paged_flash_decode`` covers, and the VMEM
# its double-buffered K and V pages may take.
_STEP_POSITIONS = 512
_KV_BUFFER_BYTES = 8 << 20


def pages_per_step(page_size: int, n_pages: int, hkv: int, d: int,
                   itemsize: int) -> int:
    """Pages ``paged_flash_decode`` loads per grid step: about
    ``_STEP_POSITIONS`` positions, no more than the table holds, and few
    enough that two buffers each of K and V fit ``_KV_BUFFER_BYTES``."""
    page_bytes = hkv * page_size * d * itemsize
    return max(1, min(_STEP_POSITIONS // page_size, n_pages,
                      _KV_BUFFER_BYTES // (4 * page_bytes)))


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, ms_ref, ls_ref, acc_ref, *,
                         scale):
    b, j = pl.program_id(0), pl.program_id(1)
    n_steps = pl.num_programs(1)
    _, pages, hkv, page_size, d = k_buf.shape
    block = pages * page_size
    cache_len = len_ref[0]
    slot = j % 2

    def copies(step, slot):
        """DMAs of step ``step``'s pages (every KV head of each) into
        buffer ``slot``; the table is flat, ``pages`` entries a step."""
        base = (b * n_steps + step) * pages
        return [pltpu.make_async_copy(src.at[pt_ref[base + i]],
                                      buf.at[slot, i], sems.at[kv, slot])
                for kv, (src, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf)))
                for i in range(pages)]

    @pl.when(j == 0)
    def _init():
        ms_ref[...] = jnp.full_like(ms_ref, _NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(cache_len > 0)
        def _first():
            for c in copies(0, 0):
                c.start()

    # Steps at or past the cache length move no bytes and do no work.
    @pl.when((j + 1) * block < cache_len)
    def _prefetch():
        for c in copies(j + 1, 1 - slot):
            c.start()

    @pl.when(j * block < cache_len)
    def _step():
        for c in copies(j, slot):
            c.wait()
        # Logical position of each column / V row: table entry j*pages+i
        # holds positions [(j*pages+i)*page_size, ...) whatever physical
        # page it names; those >= cache_len (the partial last page, null
        # or stale pages, possibly NaN) are masked, in V's own layout too
        # so that 0 * NaN never reaches acc.
        g = q_ref.shape[2]
        col = j * block + jax.lax.broadcasted_iota(jnp.int32, (g, block), 1)
        valid = col < cache_len
        v_row = j * block + jax.lax.broadcasted_iota(jnp.int32, (block, d), 0)
        for h in range(hkv):  # the G query rows of KV head h in one product
            q = q_ref[0, h].astype(jnp.float32)  # (G, D)
            k = k_buf[slot, :, h].astype(jnp.float32).reshape(block, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, block)
            s = jnp.where(valid, s, _NEG_INF)
            m_prev = ms_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            ls_ref[h] = ls_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            v = v_buf[slot, :, h].astype(jnp.float32).reshape(block, d)
            v = jnp.where(v_row < cache_len, v, 0.0)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ms_ref[h] = m_new

    @pl.when(j == n_steps - 1)
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

    Each grid step DMAs a block of ``pages_per_step`` pages, every KV
    head of each, from the shared pool into VMEM (the serving-side twin
    of the paper's discrete KV position loading), while the next block
    loads into the other buffer.  The ``G = Hq // Hkv`` query heads of a
    KV head score its pages in one ``(G, D) x (D, block)`` product, so
    each page is read once per token.  Steps whose first position is at
    or past ``cache_len`` load and compute nothing.  ``page_tables`` and
    ``cache_len`` arrive via scalar prefetch.

    q: (B, Hq, 1, D); pages: (P, Hkv, page_size, D);
    page_tables: (B, n_pages) int32 physical page ids (0 = null page);
    cache_len: () int32.  Returns (B, Hq, 1, D).
    """
    b, hq, _, d = q.shape
    hkv, page_size = k_pages.shape[1], k_pages.shape[2]
    g = hq // hkv
    n_pages = page_tables.shape[1]
    pages = pages_per_step(page_size, n_pages, hkv, d,
                           k_pages.dtype.itemsize)
    n_steps = -(-n_pages // pages)
    scale = 1.0 / (d ** 0.5)

    qg = q.reshape(b, hkv, g, d)
    # Flat table padded to whole steps with the null page; positions past
    # the table are masked like those past the cache.
    pt = jnp.pad(page_tables.astype(jnp.int32),
                 ((0, 0), (0, n_steps * pages - n_pages))).reshape(-1)
    len_arr = jnp.full((1,), jnp.minimum(cache_len, n_pages * page_size),
                       jnp.int32)

    def row_index(i, j, pt_ref, len_ref):
        return i, 0, 0, 0

    buf = pltpu.VMEM((2, pages, hkv, page_size, d), k_pages.dtype)
    kernel = functools.partial(_paged_decode_kernel, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_steps),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), row_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), row_index),
        scratch_shapes=[
            buf, buf,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_flash_decode",
    )(pt, len_arr, qg, k_pages, v_pages)
    return out.reshape(b, hq, 1, d)


dispatch.register("paged_flash_decode", "pallas_interpret")(
    functools.partial(paged_flash_decode, interpret=True))
dispatch.register("paged_flash_decode", "pallas_tpu")(
    functools.partial(paged_flash_decode, interpret=False))
