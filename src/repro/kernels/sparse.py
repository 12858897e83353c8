"""Fine-Grained Sparse Computation — Pallas kernel (paper Alg. 3),
index-driven and FUSED.

ONE online-softmax sweep from zero state over the discrete KV tiles
named by a :class:`repro.kernels.indexing.StripeIndex` table whose
leading slots are the guaranteed anchor region (KV block 0 + each
superblock's local diagonal window — ``merge_anchor_slots``) and whose
remaining slots are the difference-aware selected stripes.  There is no
``(m0, l0, acc0)`` resume state: the anchor statistics never round-trip
through HBM (DESIGN.md §9).  The causal (and varlen) mask is applied
in-kernel from global positions — a no-op for stripe slots (strictly
below each superblock's window) and exactly the diagonal trim for the
anchor slots.

The tile ids arrive via scalar prefetch (``PrefetchScalarGridSpec``) and
feed the K/V BlockSpec index maps, so each grid step DMAs one selected
tile straight out of the original ``(B, Hkv, N, D)`` arrays — no
gathered ``k_sel``/``v_sel`` copies in HBM, no ``jnp.repeat`` of K/V for
GQA (DESIGN.md §3).  The query-head group dimension is folded into the
block shapes: one KV tile feeds all ``G = Hq // Hkv`` query heads of its
group, and selection stays stripe-granular via the per-query-head
``valid`` rows.

Grid: ``(batch * Hkv, T_m, C_t)`` with the tile-slot axis sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config import AnchorConfig
from repro.kernels import dispatch
from repro.kernels.indexing import StripeIndex, prefetch_lengths

_NEG_INF = -1e30


def _slot_index(bh, i, c, cfg: AnchorConfig, t_s: int, c_t: int):
    """Flat position of tile slot ``c`` of query block ``i``'s superblock
    in the 1-D scalar-prefetched tile table (1-D keeps SMEM unpadded)."""
    return (bh * t_s + i // cfg.step) * c_t + c


def _sparse_kernel(
    idx_ref, len_ref, off_ref, q_ref, k_ref, v_ref, valid_ref,
    o_ref, ms_ref, ls_ref, accs_ref, *, cfg: AnchorConfig, scale, g, tile,
    t_s, c_t, kv_heads
):
    bh = pl.program_id(0)
    i = pl.program_id(1)
    c = pl.program_id(2)
    block_q = cfg.block_q
    rows = g * block_q

    @pl.when(c == 0)
    def _init():
        ms_ref[...] = jnp.full_like(ms_ref, _NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)
        accs_ref[...] = jnp.zeros_like(accs_ref)

    q = q_ref[0].astype(jnp.float32).reshape(rows, q_ref.shape[-1])
    k = k_ref[0].astype(jnp.float32)  # (tile, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G*block_q, tile)
    # Per-query-head stripe validity of this tile slot: (G, tile) -> rows.
    vld = valid_ref[0, 0, 0]
    ok = jnp.broadcast_to(vld[:, None, :], (g, block_q, tile))
    ok = ok.reshape(rows, tile) != 0
    # Causal + varlen trim from global positions: the row offset comes in
    # via scalar prefetch (chunked prefill sets it to the chunk start).
    tile_id = idx_ref[_slot_index(bh, i, c, cfg, t_s, c_t)]
    col = tile_id * tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    row = (off_ref[0] + i * block_q
           + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % block_q)
    length = len_ref[bh // kv_heads]
    ok &= (col <= row) & (col < length) & (row < length)
    s = jnp.where(ok, s, _NEG_INF)
    m_prev = ms_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)
    # Varlen padding rows keep m == -1e30 with everything masked; without
    # this guard exp(s - m_new) above is exp(0) = 1 there.
    p = jnp.where(s <= _NEG_INF, 0.0, p)
    alpha = jnp.exp(m_prev - m_new)
    ls_ref[...] = ls_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    accs_ref[...] = accs_ref[...] * alpha + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ms_ref[...] = m_new

    @pl.when(c == pl.num_programs(2) - 1)
    def _finish():
        # l >= 1 for causal rows (the anchor slots contain the diagonal);
        # the guard only protects varlen padding rows (exact zeros).
        out = accs_ref[...] / jnp.maximum(ls_ref[...], 1e-30)
        o_ref[0] = out.reshape(g, block_q, accs_ref.shape[-1]).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("cfg", "block_c", "interpret"))
def sparse_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    tables: StripeIndex,
    cfg: AnchorConfig,
    lengths: jnp.ndarray | None = None,
    q_offset: jnp.ndarray | None = None,
    block_c: int | None = None,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Alg. 3 (fused) for batched heads, index-driven.

    Args:
      q: (B, Hq, N, D) queries.
      k, v: (B, Hkv, Nk, D/Dv) — the ORIGINAL key/value arrays (``Nk``
        may exceed N, e.g. a cache view under chunked prefill).
      tables: :class:`StripeIndex` over the ``Nk`` axis with the anchor
        slots leading (tile must divide Nk).
      lengths: optional (B,) int32 — varlen mask (padded rows emit
        exact zeros, padding keys contribute nothing).
      q_offset: optional () int32 global position of query row 0.
      block_c: accepted for signature parity; the DMA tile width is
        fixed by ``tables``.

    Returns:
      (B, Hq, N, Dv) final attention output in q.dtype.
    """
    del block_c
    batch, hq, n, d = q.shape
    hkv, nk = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    tile = tables.tile
    t_s, c_t = tables.tile_idx.shape[2], tables.tile_idx.shape[3]
    t_m = cfg.num_q_blocks(n)
    scale = 1.0 / (d ** 0.5)
    assert nk % tile == 0, (nk, tile)

    qf = q.reshape(batch * hkv, g, n, d)
    kf = k.reshape(batch * hkv, nk, d)
    vf = v.reshape(batch * hkv, nk, dv)
    # Kernel layout of the validity rows: one (G, tile) trailing tile per
    # (KV row, superblock, slot).
    validf = tables.valid.reshape(batch * hkv, g, t_s, c_t, tile).transpose(
        0, 2, 3, 1, 4)
    idxf = tables.tile_idx.reshape(-1).astype(jnp.int32)
    lens = prefetch_lengths(lengths, batch, nk)
    offf = (jnp.zeros((1,), jnp.int32) if q_offset is None
            else jnp.asarray(q_offset, jnp.int32).reshape(1))

    def q_index(bh, i, c, idx_ref, len_ref, off_ref):
        del c, idx_ref, len_ref, off_ref
        return bh, 0, i, 0

    def kv_index(bh, i, c, idx_ref, len_ref, off_ref):
        del len_ref, off_ref
        return bh, idx_ref[_slot_index(bh, i, c, cfg, t_s, c_t)], 0

    def valid_index(bh, i, c, idx_ref, len_ref, off_ref):
        del idx_ref, len_ref, off_ref
        return bh, i // cfg.step, c, 0, 0

    kernel = functools.partial(
        _sparse_kernel, cfg=cfg, scale=scale, g=g, tile=tile, t_s=t_s,
        c_t=c_t, kv_heads=hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch * hkv, t_m, c_t),
        in_specs=[
            pl.BlockSpec((1, g, cfg.block_q, d), q_index),
            pl.BlockSpec((1, tile, d), kv_index),
            pl.BlockSpec((1, tile, dv), kv_index),
            pl.BlockSpec((1, 1, 1, g, tile), valid_index),
        ],
        out_specs=pl.BlockSpec((1, g, cfg.block_q, dv), q_index),
        scratch_shapes=[
            pltpu.VMEM((g * cfg.block_q, 1), jnp.float32),
            pltpu.VMEM((g * cfg.block_q, 1), jnp.float32),
            pltpu.VMEM((g * cfg.block_q, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch * hkv, g, n, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(idxf, lens, offf, qf, kf, vf, validf)
    return out.reshape(batch, hq, n, dv)


dispatch.register("sparse_attention", "pallas_interpret")(
    functools.partial(sparse_attention_pallas, interpret=True))
dispatch.register("sparse_attention", "pallas_tpu")(
    functools.partial(sparse_attention_pallas, interpret=False))
