"""Difference-aware Stripe Sparsity Identification — Pallas kernel (Alg. 2),
compact-emitting.

Compare pooled-query × key scores against the pooled anchor and emit the
surviving KV tiles DIRECTLY as compact per-(KV-head, superblock) tables:
ascending tile ids, slot occupancy, per-QUERY-head row validity, and
per-head kept counts.  The dense ``(B, Hq, T_s, N)`` hit mask of the
staged pipeline — quadratic in context length — is never materialized
(DESIGN.md §9); the kernel's working set is one ``(step, tile)`` score
tile plus the ``O(capacity)`` output block it compacts into.

Sort-free, like the paper's §3.2: the threshold is a VPU compare +
OR-reduce over the ``step`` pooled rows, and the compaction is a running
slot counter (position-ascending, per-query-head ``capacity`` budget —
bit-identical to ``compact_stripe_tiles`` over the dense mask).

Grid: ``(batch*Hkv, T_s, N // tile)`` with the tile axis sequential
("arbitrary" — it carries the slot counters and the accumulated output
block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config import AnchorConfig
from repro.kernels import dispatch
from repro.kernels.indexing import (
    StripeIndex,
    prefetch_lengths,
    select_capacity,
    window_start_tokens,
)


def _select_kernel(
    len_ref, qm_ref, mb_ref, k_ref, tidx_ref, tvalid_ref, valid_ref,
    counts_ref, hits_ref, kept_ref, slot_ref,
    *, cfg: AnchorConfig, scale, tile, cap_s, c_sel, g, kv_heads
):
    s_idx = pl.program_id(1)
    j = pl.program_id(2)
    length = len_ref[pl.program_id(0) // kv_heads]

    @pl.when(j == 0)
    def _init():
        tidx_ref[...] = jnp.zeros_like(tidx_ref)
        tvalid_ref[...] = jnp.zeros_like(tvalid_ref)
        valid_ref[...] = jnp.zeros_like(valid_ref)
        hits_ref[...] = jnp.zeros_like(hits_ref)
        kept_ref[...] = jnp.zeros_like(kept_ref)
        slot_ref[0] = 0

    w_start = window_start_tokens(s_idx, cfg)
    in_candidate = ((j + 1) * tile > cfg.block_kv) & (j * tile < w_start)

    @pl.when(in_candidate)
    def _compute():
        qm = qm_ref[0, 0]  # (G*step, D) f32
        kt = k_ref[0].astype(jnp.float32)  # (tile, D)
        s = jax.lax.dot_general(
            qm, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        close = ((mb_ref[0, 0] - s) <= cfg.theta).astype(jnp.int32)
        hit_i = jnp.max(close.reshape(g, cfg.step, tile), axis=1)  # (G, tile)
        col = j * tile + jax.lax.broadcasted_iota(jnp.int32, (g, tile), 1)
        # Stripe candidates only; padding keys of a right-padded batch are
        # never stripe-selected.
        ok = (col >= cfg.block_kv) & (col < w_start) & (col < length)
        hit_i = jnp.where(ok, hit_i, 0)
        if cfg.share_kv_groups:
            hit_i = jnp.broadcast_to(
                jnp.max(hit_i, axis=0, keepdims=True), hit_i.shape)
        # Position-ascending per-head budget: global rank = hits seen in
        # earlier tiles + the exclusive in-tile prefix.
        # The in-tile prefix is a matmul with an upper-triangular ones
        # matrix (Mosaic has no cumsum); 0/1 operands keep it exact.
        tri = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
               <= jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
        incl = jax.lax.dot_general(
            hit_i.astype(jnp.float32), tri.astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        rank = hits_ref[...] + incl.astype(jnp.int32) - hit_i
        kept_i = jnp.where(rank < cap_s, hit_i, 0)
        hits_ref[...] += jnp.sum(hit_i, axis=1, keepdims=True)
        kept_ref[...] += jnp.sum(kept_i, axis=1, keepdims=True)

        # In-kernel compaction: a tile with any kept row takes the next
        # free slot.
        slot = slot_ref[0]

        @pl.when((jnp.max(kept_i) > 0) & (slot < c_sel))
        def _take():
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, c_sel), 1) == slot
            tidx_ref[0, 0] = jnp.where(lane, j, tidx_ref[0, 0])
            tvalid_ref[0, 0] = jnp.where(lane, 1, tvalid_ref[0, 0])
            valid_ref[0, 0, slot] = kept_i
            slot_ref[0] = slot + 1

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        counts_ref[0, 0] = kept_ref[...]


@functools.partial(jax.jit, static_argnames=("cfg", "tile", "interpret"))
def stripe_select_pallas(
    q_mean: jnp.ndarray,
    m_bar: jnp.ndarray,
    k: jnp.ndarray,
    cfg: AnchorConfig,
    tile: int,
    *,
    interpret: bool,
    lengths: jnp.ndarray | None = None,
) -> tuple[StripeIndex, jnp.ndarray]:
    """Alg. 2 (compact) for batched heads.

    Args:
      q_mean: (B, Hq, T_m, D) block-pooled queries.
      m_bar: (B, Hq, T_m) block-pooled anchors (zeros for the
        "Without Anchor" ablation; +inf rows are skipped — callers use
        that for all-padding pooled blocks of varlen batches).
      k: (B, Hkv, N, D) keys (``N % tile == 0``).
      tile: KV rows per compacted tile (the sparse stage's DMA width).
      lengths: optional (B,) int32 valid token counts — keys at positions
        >= length are never selected.

    Returns:
      (tables, counts): selected-stripe :class:`StripeIndex` tables (no
      anchor slots) and per-head kept counts (B, Hq, T_s).
    """
    batch, hq, t_m, d = q_mean.shape
    hkv = k.shape[1]
    n = k.shape[2]
    g = hq // hkv
    if n % tile:
        raise ValueError(f"tile ({tile}) must divide N ({n})")
    n_tiles = n // tile
    t_s = (t_m + cfg.step - 1) // cfg.step
    cap_s = n if cfg.capacity is None else min(cfg.capacity, n)
    c_sel = select_capacity(n_tiles, n, cfg.capacity, g, cfg.share_kv_groups)
    scale = 1.0 / (d ** 0.5)

    # Pad T_m up to T_s*step so the step-grouping is exact.
    pad = t_s * cfg.step - t_m
    if pad:
        q_mean = jnp.pad(q_mean, ((0, 0), (0, 0), (0, pad), (0, 0)))
        m_bar = jnp.pad(m_bar, ((0, 0), (0, 0), (0, pad)),
                        constant_values=jnp.inf)

    # Kernel layout: one (G*step, ·) slab per (KV row, superblock), so the
    # pooled rows of a whole query group form the trailing tile.
    qf = q_mean.reshape(batch * hkv, g, t_s, cfg.step, d).transpose(
        0, 2, 1, 3, 4).reshape(batch * hkv, t_s, g * cfg.step, d)
    mf = m_bar.reshape(batch * hkv, g, t_s, cfg.step).transpose(
        0, 2, 1, 3).reshape(batch * hkv, t_s, g * cfg.step, 1)
    kf = k.reshape(batch * hkv, n, d)
    lens = prefetch_lengths(lengths, batch, n)

    def sb_index(b, s, j, len_ref):
        del j, len_ref
        return b, s, 0, 0

    def k_index(b, s, j, len_ref):
        del s, len_ref
        return b, j, 0

    def valid_index(b, s, j, len_ref):
        del j, len_ref
        return b, s, 0, 0, 0

    kernel = functools.partial(
        _select_kernel, cfg=cfg, scale=scale, tile=tile, cap_s=cap_s,
        c_sel=c_sel, g=g, kv_heads=hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch * hkv, t_s, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, g * cfg.step, d), sb_index),
            pl.BlockSpec((1, 1, g * cfg.step, 1), sb_index),
            pl.BlockSpec((1, tile, d), k_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, c_sel), sb_index),
            pl.BlockSpec((1, 1, 1, c_sel), sb_index),
            pl.BlockSpec((1, 1, c_sel, g, tile), valid_index),
            pl.BlockSpec((1, 1, g, 1), sb_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.int32),
            pltpu.VMEM((g, 1), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    tidx, tvalid, valid, counts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch * hkv, t_s, 1, c_sel), jnp.int32),
            jax.ShapeDtypeStruct((batch * hkv, t_s, 1, c_sel), jnp.int32),
            jax.ShapeDtypeStruct((batch * hkv, t_s, c_sel, g, tile),
                                 jnp.int32),
            jax.ShapeDtypeStruct((batch * hkv, t_s, g, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lens, qf.astype(jnp.float32), mf.astype(jnp.float32), kf)
    tables = StripeIndex(
        tidx.reshape(batch, hkv, t_s, c_sel),
        tvalid.reshape(batch, hkv, t_s, c_sel),
        valid.transpose(0, 3, 1, 2, 4).reshape(
            batch, hkv, g, t_s, c_sel * tile),
    )
    return tables, counts.transpose(0, 2, 1, 3).reshape(batch, hq, t_s)


dispatch.register("stripe_select", "pallas_interpret")(
    functools.partial(stripe_select_pallas, interpret=True))
dispatch.register("stripe_select", "pallas_tpu")(
    functools.partial(stripe_select_pallas, interpret=False))
