"""Mamba2 SSD (state-space duality) chunked scan — Pallas TPU kernel.

Needed by the ``mamba2-2.7b`` (pure SSM) and ``jamba-1.5-large`` (hybrid)
assigned architectures.  The chunked algorithm (Dao & Gu, 2024) splits the
sequence into chunks: a quadratic *intra-chunk* term (MXU matmuls over
(chunk × chunk) decay-weighted Gram matrices) plus a recurrent *inter-chunk*
state carried in VMEM scratch — the TPU-friendly dual of the linear
recurrence.

Grid: ``(batch*heads, L // chunk)`` with the chunk axis sequential
("arbitrary": it carries the (S, P) state).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, hout_ref, h_ref):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)  # (chunk, P)
    dt = dt_ref[0].astype(jnp.float32)  # (chunk,)
    a = a_ref[pl.program_id(0)]  # scalar (negative), from SMEM
    b = b_ref[0].astype(jnp.float32)  # (chunk, S)
    c = c_ref[0].astype(jnp.float32)  # (chunk, S)

    da = dt * a  # (chunk,) log-decay increments, <= 0
    cum = jnp.cumsum(da)  # (chunk,)

    # Inter-chunk: contribution of the carried state h_{prev}.
    #   y_i += exp(cum_i) * (c_i @ h_prev)
    y_inter = jnp.exp(cum)[:, None] * jax.lax.dot_general(
        c, h_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # Intra-chunk: decay-weighted causal Gram matrix.
    #   y_i += sum_{j<=i} exp(cum_i - cum_j) * dt_j * (c_i . b_j) * x_j
    decay = jnp.exp(cum[:, None] - cum[None, :])  # (chunk, chunk)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, decay.shape, 0)
        >= jax.lax.broadcasted_iota(jnp.int32, decay.shape, 1)
    )
    gram = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    w = jnp.where(causal, gram * decay, 0.0) * dt[None, :]
    y_intra = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_ref[0] = (y_inter + y_intra).astype(y_ref.dtype)

    # State update: h_new = exp(cum_last) * h + sum_j exp(cum_last - cum_j)
    #                                             * dt_j * b_j ⊗ x_j
    tail = jnp.exp(cum[-1] - cum) * dt  # (chunk,)
    h_ref[...] = jnp.exp(cum[-1]) * h_ref[...] + jax.lax.dot_general(
        b * tail[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        hout_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    chunk: int = 128,
    *,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan for batched heads.

    Args:
      x: (BH, L, P) head inputs; dt: (BH, L) step sizes; a: (BH,) per-head
      decay (negative); b, c: (BH, L, S) input/output projections.

    Returns:
      y: (BH, L, P) outputs, h: (BH, S, P) final states (f32).
    """
    bh, l, p = x.shape
    s = b.shape[-1]
    assert l % chunk == 0, (l, chunk)

    # The per-head decay rides in via scalar prefetch; every index map
    # takes (and ignores) its SMEM ref.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, l // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, t, a_ref: (i, t, 0)),
            pl.BlockSpec((1, chunk), lambda i, t, a_ref: (i, t)),
            pl.BlockSpec((1, chunk, s), lambda i, t, a_ref: (i, t, 0)),
            pl.BlockSpec((1, chunk, s), lambda i, t, a_ref: (i, t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, t, a_ref: (i, t, 0)),
            pl.BlockSpec((1, s, p), lambda i, t, a_ref: (i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((s, p), jnp.float32)],
    )
    y, h = pl.pallas_call(
        _ssd_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, l, p), x.dtype),
            jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a.astype(jnp.float32), x, dt, b, c)
    return y, h


dispatch.register("ssd", "pallas_interpret")(
    functools.partial(ssd_chunked, interpret=True))
dispatch.register("ssd", "pallas_tpu")(
    functools.partial(ssd_chunked, interpret=False))
