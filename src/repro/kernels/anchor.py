"""Pattern-based Anchor Computation — Pallas TPU kernel (paper Alg. 1),
scores-only.

For every query block the kernel runs an online MAX (no softmax state)
over the *anchor region only*: KV block 0 (attention sink) plus the local
diagonal window of its superblock.  Since the fused-identification
rewrite (DESIGN.md §9) the softmax statistics ``(l, acc)`` are gone —
the fused sparse sweep recomputes the anchor region from zero state —
so this kernel loads NO value tiles and writes NO per-row f32 arrays to
HBM.  It emits exactly what Alg. 2 consumes: the block-pooled anchor
``m_bar`` and the block-pooled queries ``q_mean`` (the q tile is already
in VMEM for the scores, so the pooling is free), both ``T_m``-sized.

Grid: ``(batch*heads, T_m, 1 + step*r + r)``.  Window slot ``w=0`` is the
init block; slots ``w>=1`` map to KV block ``w_start(k) + w - 1`` via the
BlockSpec index map (clipped in the map, re-validated in-kernel against the
unclipped candidate so aliased loads contribute nothing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config import AnchorConfig
from repro.kernels import dispatch
from repro.kernels.indexing import kv_head_index, prefetch_lengths

_NEG_INF = -1e30


def _candidate_block(i, w, cfg: AnchorConfig):
    """Unclipped KV block id for window slot ``w`` of query block ``i``."""
    k = i // cfg.step
    w_start = jnp.maximum(1, k * cfg.step * cfg.r)
    return jnp.where(w == 0, 0, w_start + (w - 1))


def _anchor_kernel(
    len_ref, q_ref, k_ref, qm_ref, mb_ref, ms_ref,
    *, cfg: AnchorConfig, scale: float, t_n: int, heads: int
):
    i = pl.program_id(1)
    w = pl.program_id(2)
    length = len_ref[pl.program_id(0) // heads]

    @pl.when(w == 0)
    def _init():
        ms_ref[...] = jnp.full_like(ms_ref, _NEG_INF)

    blk = _candidate_block(i, w, cfg)
    last_blk = i * cfg.r + cfg.r - 1
    block_valid = (w == 0) | ((blk >= 1) & (blk <= last_blk) & (blk < t_n))

    @pl.when(block_valid)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        row = i * cfg.block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = blk * cfg.block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((col <= row) & (col < length) & (row < length),
                      s, _NEG_INF)
        ms_ref[...] = jnp.maximum(
            ms_ref[...], jnp.max(s, axis=-1, keepdims=True))

    @pl.when(w == pl.num_programs(2) - 1)
    def _finish():
        # Fused pooling: q is already resident for the scores, so the
        # block means cost nothing extra and nothing row-resolution ever
        # leaves the kernel.  Padded rows (varlen) are excluded; an
        # all-padding block pools to m_bar = +inf (never selected) and
        # q_mean = 0.  Every reduction keeps its (1, ·) shape: the pooled
        # values are stored as one-row tiles, never as scalars.
        rows = i * cfg.block_q + jax.lax.broadcasted_iota(
            jnp.int32, (cfg.block_q, 1), 0)
        rv = rows < length  # (block_q, 1)
        cnt = jnp.sum(rv.astype(jnp.float32), axis=0, keepdims=True)
        denom = jnp.maximum(cnt, 1.0)  # (1, 1)
        m_sum = jnp.sum(jnp.where(rv, ms_ref[...], 0.0), axis=0,
                        keepdims=True)
        mb_ref[0, 0] = jnp.where(cnt == 0.0, jnp.inf, m_sum / denom)
        q = q_ref[0].astype(jnp.float32)
        qm_ref[0, 0] = jnp.sum(jnp.where(rv, q, 0.0), axis=0,
                               keepdims=True) / denom


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def anchor_phase_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    cfg: AnchorConfig,
    *,
    interpret: bool,
    lengths: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Alg. 1 (scores-only) for batched heads.

    q: (B, Hq, N, D); k: (B, Hkv, N, D).  Returns the block-pooled
    ``(q_mean, m_bar)`` with shapes (B, Hq, T_m, D) and (B, Hq, T_m) in
    f32.  With ``lengths`` ((B,) int32), padding keys are masked out of
    the anchor scores and padded rows are excluded from the pooling
    (all-padding blocks emit ``m_bar = +inf``).
    """
    batch, hq, n, d = q.shape
    hkv = k.shape[1]
    t_m = cfg.num_q_blocks(n)
    t_n = cfg.num_kv_blocks(n)
    n_slots = 1 + cfg.step * cfg.r + cfg.r
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(batch * hq, n, d)
    kf = k.reshape(batch * hkv, n, d)
    lens = prefetch_lengths(lengths, batch, n)

    def q_index(b, i, w, len_ref):
        del w, len_ref
        return b, i, 0

    def kv_index(b, i, w, len_ref):
        del len_ref
        blk = jnp.clip(_candidate_block(i, w, cfg), 0, t_n - 1)
        return kv_head_index(b, hq, hkv), blk, 0

    def pooled_index(b, i, w, len_ref):
        del w, len_ref
        return b, i, 0, 0

    kernel = functools.partial(
        _anchor_kernel, cfg=cfg, scale=scale, t_n=t_n, heads=hq)
    # The pooled outputs carry a unit row axis so each grid step writes a
    # whole (1, D) / (1, 1) trailing tile.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch * hq, t_m, n_slots),
        in_specs=[
            pl.BlockSpec((1, cfg.block_q, d), q_index),
            pl.BlockSpec((1, cfg.block_kv, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, d), pooled_index),
            pl.BlockSpec((1, 1, 1, 1), pooled_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_q, 1), jnp.float32),
        ],
    )
    q_mean, m_bar = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch * hq, t_m, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((batch * hq, t_m, 1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lens, qf, kf)
    return (q_mean.reshape(batch, hq, t_m, d),
            m_bar.reshape(batch, hq, t_m))


dispatch.register("anchor_phase", "pallas_interpret")(
    functools.partial(anchor_phase_pallas, interpret=True))
dispatch.register("anchor_phase", "pallas_tpu")(
    functools.partial(anchor_phase_pallas, interpret=False))
