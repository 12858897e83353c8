"""Dense causal FlashAttention — Pallas TPU kernel (baseline, paper §4.1).

Grid: ``(batch*heads, T_m, T_n)`` with the KV axis innermost ("arbitrary"
semantics — it carries the online-softmax state in VMEM scratch).  Blocks
are MXU-aligned ``(block_q, d)`` / ``(block_kv, d)`` VMEM tiles; ``d`` is the
head dim (128 or 256 for every assigned arch ⇒ lane-aligned).

GQA is handled in the K/V index maps (``kv_head = q_head // group``) so
grouped KV is never replicated in HBM.

Variable-length batches: the per-sequence ``lengths`` arrive via scalar
prefetch (one int32 per batch row, in SMEM) and tighten the causal mask to
``col <= row < length`` — padding keys contribute nothing and padded query
rows emit exact zeros (their normalizer is 0; the final divide is guarded).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch
from repro.kernels.indexing import kv_head_index, prefetch_lengths

_NEG_INF = -1e30


def _flash_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, block_q, block_kv, scale, heads
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    length = len_ref[pl.program_id(0) // heads]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal: KV block j intersects rows of q block i iff j*b_kv <= last row.
    @pl.when(j * block_kv <= i * block_q + block_q - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        row = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((col <= row) & (col < length) & (row < length),
                      s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # Fully-masked rows (varlen padding) keep m == -1e30; guard the
        # exp(0) = 1 they would otherwise produce.  No-op for causal rows.
        p = jnp.where(s <= _NEG_INF, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_kv", "interpret")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    block_q: int = 128,
    block_kv: int = 128,
    *,
    interpret: bool,
    lengths: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Causal flash attention.  q: (B, Hq, N, D); k, v: (B, Hkv, N, D).

    ``lengths`` (optional, (B,) int32): valid token counts of a
    right-padded batch (see :mod:`repro.core.spec`).
    """
    batch, hq, n, d = q.shape
    block_q, block_kv = min(block_q, n), min(block_kv, n)
    hkv = k.shape[1]
    t_m, t_n = n // block_q, n // block_kv
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(batch * hq, n, d)
    kf = k.reshape(batch * hkv, n, d)
    vf = v.reshape(batch * hkv, n, d)
    lens = prefetch_lengths(lengths, batch, n)

    def q_index(b, i, j, len_ref):
        del j, len_ref
        return b, i, 0

    def kv_index(b, i, j, len_ref):
        del i, len_ref
        return kv_head_index(b, hq, hkv), j, 0

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_kv=block_kv, scale=scale,
        heads=hq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch * hq, t_m, t_n),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch * hq, n, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(batch, hq, n, d)


dispatch.register("flash_attention", "pallas_interpret")(
    functools.partial(flash_attention, interpret=True))
dispatch.register("flash_attention", "pallas_tpu")(
    functools.partial(flash_attention, interpret=False))
