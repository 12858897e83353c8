"""Backend-dispatched public entry points for every kernel op.

Each function here resolves its implementation through
:mod:`repro.kernels.dispatch` (``backend=`` argument → process default →
``$REPRO_BACKEND`` → platform), so the same call site runs the pure-XLA
path, the Pallas kernels in interpret mode, or the compiled TPU kernels.

:func:`attention` is the canonical model-facing entry point: it takes a
declarative :class:`repro.core.spec.AttentionSpec` (algorithm × backend ×
masking) plus an optional per-sequence ``lengths`` array for right-padded
variable-length batches, and dispatches to the dense flash path or the
AnchorAttention pipeline accordingly.

``anchor_attention`` is the FUSED identification pipeline (DESIGN.md §9):

* ``anchor_phase`` is scores-only — it emits the block-pooled
  ``(q_mean, m_bar)`` identification inputs directly and never writes
  per-row ``(m, l, acc)`` statistics to HBM;
* ``stripe_select`` emits compact per-(KV-head, superblock) tile ids,
  per-query-head row validity, and kept counts straight from the kernel
  — the dense ``(B, Hq, T_s, N)`` hit mask of the staged pipeline is
  never materialized;
* :func:`repro.kernels.indexing.merge_anchor_slots` prepends the
  guaranteed anchor slots (KV block 0 + each superblock's local
  diagonal window) to the selected tiles;
* ``sparse_attention`` computes anchor + selected tiles in ONE
  online-softmax sweep from zero state, loading discrete KV tiles
  straight from the original ``(B, Hkv, N, D)`` arrays (scalar-prefetch
  BlockSpec indirection on the Pallas backends, a per-slot gather scan
  on XLA).  Nothing Hq-wide is ever materialized; selection itself
  stays stripe-granular (DESIGN.md §3).

Identification memory is ``O(B·Hkv·T_s·capacity)`` end-to-end.  The
pre-fusion staged pipeline survives as :func:`anchor_attention_staged`
(XLA-only, unregistered) — the tolerance oracle for fused-vs-staged
parity tests and the baseline of ``benchmarks/prefill_index.py``.

:func:`chunk_anchor_attention` applies the same fused machinery to one
superblock-aligned chunk of a chunked prefill attending into a KV-cache
view — the serving path that keeps long-prompt chunks sparse instead of
falling back to dense history attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.config import AnchorConfig
from repro.core.spec import AttentionSpec
from repro.kernels import dispatch, indexing
from repro.kernels.indexing import (
    StripeIndex,
    compact_stripe_tiles,
    merge_anchor_slots,
    pack_stripe_indices,
)

# Importing the implementation modules populates the backend registry.
from repro.kernels import anchor as _anchor  # noqa: F401
from repro.kernels import decode as _decode  # noqa: F401
from repro.kernels import flash as _flash  # noqa: F401
from repro.kernels import sparse as _sparse  # noqa: F401
from repro.kernels import ssd as _ssd  # noqa: F401
from repro.kernels import stripe_select as _stripe_select  # noqa: F401
from repro.kernels import xla as _xla  # noqa: F401

_NEG_INF = -1e30

__all__ = [
    "attention",
    "flash_attention",
    "flash_decode",
    "paged_flash_decode",
    "anchor_phase",
    "stripe_select",
    "sparse_attention",
    "ssd_chunked",
    "anchor_attention",
    "anchor_attention_staged",
    "chunk_anchor_attention",
    "pack_stripe_indices",
    "compact_stripe_tiles",
    "merge_anchor_slots",
    "StripeIndex",
]


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    spec: AttentionSpec | None = None,
    *,
    lengths: jnp.ndarray | None = None,
    return_counters: bool = False,
):
    """Canonical attention entry point — ``repro.attention``.

    Args:
      q: (B, Hq, N, D); k, v: (B, Hkv, N, D) with Hq % Hkv == 0 (GQA).
      spec: declarative :class:`AttentionSpec` (default: dense causal on
        the process-default backend).
      lengths: (B,) int32 per-sequence valid token counts — required
        (and only allowed) when ``spec.masking == "padded"``.  Padding
        keys are masked out of scores, statistics, and stripe selection;
        padded query rows return exact zeros.
      return_counters: also return the sparse stage's (4,) int32 work
        counts (``indexing.SPARSE_COUNTERS``; zeros for dense).

    Returns:
      (B, Hq, N, Dv) attention output in ``q.dtype`` (and the counts).
    """
    spec = spec if spec is not None else AttentionSpec()
    if spec.masking == "padded" and lengths is None:
        raise ValueError("spec.masking='padded' requires a lengths array")
    if spec.masking == "causal" and lengths is not None:
        raise ValueError(
            "lengths= passed with spec.masking='causal'; use spec.padded()")
    backend = dispatch.resolve_backend(spec.backend)
    out_dtype = q.dtype
    if backend == "xla":
        # Run the XLA paths on f32 inputs and cast the output back once.
        # Both algorithms upcast to f32 internally anyway, but XLA lowers
        # the mixed bf16→f32 dots of the two algorithms differently, which
        # leaves dense and anchor outputs 1 bf16 ulp apart on a few
        # elements — enough to flip MoE top-k routing downstream (the
        # granite_moe failure).  With f32 inputs both algorithms are
        # numerically f32 end-to-end.  The pallas backends keep their
        # native dtype: on TPU the bf16 K/V tiles are half the VMEM
        # traffic, which is the point.
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    if spec.algorithm == "dense":
        res = flash_attention(q, k, v, lengths=lengths, backend=backend)
        if return_counters:
            res = res, jnp.zeros((len(indexing.SPARSE_COUNTERS),), jnp.int32)
    else:
        res = anchor_attention(q, k, v, spec.anchor, lengths=lengths,
                               backend=backend,
                               return_counters=return_counters)
    if return_counters:
        out, counters = res
        return out.astype(out_dtype), counters
    return res.astype(out_dtype)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    block_q: int | None = None,
    block_kv: int | None = None,
    lengths: jnp.ndarray | None = None,
    backend: str | None = None,
) -> jnp.ndarray:
    """Causal flash attention.  q: (B, Hq, N, D); k, v: (B, Hkv, N, D).

    ``block_q``/``block_kv`` default to each backend's own tiling;
    ``lengths`` ((B,) int32, optional) masks a right-padded batch.
    """
    fn, _ = dispatch.lookup("flash_attention", backend)
    kw = {}
    if block_q is not None:
        kw["block_q"] = block_q
    if block_kv is not None:
        kw["block_kv"] = block_kv
    if lengths is not None:
        kw["lengths"] = lengths
    return fn(q, k, v, **kw)


def flash_decode(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,
    block_s: int | None = None,
    backend: str | None = None,
) -> jnp.ndarray:
    """One-token decode attention.  q: (B, Hq, 1, D); caches: (B, Hkv, S, D)."""
    fn, _ = dispatch.lookup("flash_decode", backend)
    kw = {} if block_s is None else {"block_s": block_s}
    return fn(q, k_cache, v_cache, cache_len, **kw)


def paged_flash_decode(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,
    cache_len: jnp.ndarray,
    backend: str | None = None,
) -> jnp.ndarray:
    """One-token decode attention over a paged KV cache.

    q: (B, Hq, 1, D); pages: (P, Hkv, page_size, D) — the shared pool;
    page_tables: (B, n_pages) int32 physical page ids (0 = null page);
    cache_len: () int32 valid positions.  Logical position ``t`` of batch
    row ``b`` lives at ``pages[page_tables[b, t // page_size], :,
    t % page_size]``.  Returns (B, Hq, 1, D).
    """
    fn, _ = dispatch.lookup("paged_flash_decode", backend)
    return fn(q, k_pages, v_pages, page_tables, cache_len)


def anchor_phase(
    q: jnp.ndarray,
    k: jnp.ndarray,
    cfg: AnchorConfig,
    lengths: jnp.ndarray | None = None,
    backend: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Alg. 1, scores-only: block-pooled ``(q_mean, m_bar)``.

    Loads no V and emits no per-row statistics — the pooled pair is all
    Alg. 2 consumes, and the fused sparse sweep recomputes the anchor
    region from zero state (DESIGN.md §9).  With ``lengths``, padded
    rows are excluded from the pooling (all-padding blocks emit
    ``m_bar = +inf``).
    """
    fn, _ = dispatch.lookup("anchor_phase", backend)
    kw = {} if lengths is None else {"lengths": lengths}
    return fn(q, k, cfg, **kw)


def stripe_select(
    q_mean: jnp.ndarray,
    m_bar: jnp.ndarray,
    k: jnp.ndarray,
    cfg: AnchorConfig,
    tile: int,
    lengths: jnp.ndarray | None = None,
    backend: str | None = None,
) -> tuple[StripeIndex, jnp.ndarray]:
    """Alg. 2, compact: ``(selected-tile tables, kept counts)``.

    Emits per-(KV-head, superblock) tile ids with per-query-head row
    validity straight from the kernel — no dense ``(B, Hq, T_s, N)``
    hit mask exists on any backend.  With ``lengths``, keys at
    positions >= length are never selected.
    """
    fn, _ = dispatch.lookup("stripe_select", backend)
    kw = {} if lengths is None else {"lengths": lengths}
    return fn(q_mean, m_bar, k, cfg, tile, **kw)


def sparse_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    tables: StripeIndex,
    cfg: AnchorConfig,
    lengths: jnp.ndarray | None = None,
    q_offset: jnp.ndarray | None = None,
    block_c: int | None = None,
    backend: str | None = None,
) -> jnp.ndarray:
    """Alg. 3, fused: one online-softmax sweep from zero state.

    ``k``/``v`` are the ORIGINAL (B, Hkv, Nk, D) arrays; ``tables`` is a
    :class:`repro.kernels.indexing.StripeIndex` whose LEADING slots are
    the guaranteed anchor tiles (see ``merge_anchor_slots``) followed by
    the selected stripes.  The sweep applies the causal (and varlen)
    mask in-place from global positions (``q_offset`` offsets chunked
    prefill rows), so no ``(m0, l0, acc0)`` resume state exists.
    """
    fn, _ = dispatch.lookup("sparse_attention", backend)
    kw = {} if block_c is None else {"block_c": block_c}
    if lengths is not None:
        kw["lengths"] = lengths
    if q_offset is not None:
        kw["q_offset"] = q_offset
    return fn(q, k, v, tables, cfg, **kw)


def ssd_chunked(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    chunk: int | None = None,
    backend: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked Mamba2 SSD scan for batched heads."""
    fn, _ = dispatch.lookup("ssd", backend)
    kw = {} if chunk is None else {"chunk": chunk}
    return fn(x, dt, a, b, c, **kw)


def anchor_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: AnchorConfig,
    block_c: int | None = None,
    return_stats: bool = False,
    lengths: jnp.ndarray | None = None,
    backend: str | None = None,
    return_counters: bool = False,
):
    """Full AnchorAttention.  q: (B, Hq, N, D); k, v: (B, Hkv, N, D).

    ``lengths`` ((B,) int32, optional) masks a right-padded batch:
    padding keys never enter statistics or selection, padded rows return
    zeros.  ``return_counters`` pairs the result with the sparse stage's
    (4,) int32 work counts (:func:`repro.kernels.indexing.sparse_counters`).
    """
    fn, _ = dispatch.lookup("anchor_attention", backend)
    kw = {} if block_c is None else {"block_c": block_c}
    if lengths is not None:
        kw["lengths"] = lengths
    if return_counters:
        kw["return_counters"] = True
    return fn(q, k, v, cfg, return_stats=return_stats, **kw)


@functools.partial(
    jax.jit, static_argnames=("cfg", "block_c", "return_stats", "backend",
                              "return_counters")
)
def _anchor_attention_pipeline(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: AnchorConfig,
    block_c: int = 128,
    return_stats: bool = False,
    lengths: jnp.ndarray | None = None,
    *,
    backend: str,
    return_counters: bool = False,
):
    """Fused AnchorAttention: scores → compact select → one sparse sweep.

    All kernel stages run on ``backend``; the only XLA glue left is the
    ``O(T_m)`` ``use_anchor`` ablation rewrite and the ``O(capacity)``
    anchor-slot merge.  Identification materializes nothing dense: no
    per-row ``(m, l, acc)`` statistics, no ``(B, Hq, T_s, N)`` hit mask
    (DESIGN.md §9).  The sparse stage is index-driven and
    GQA-group-native — with ``cfg.share_kv_groups`` the per-head
    validity collapses to the group union (§Perf iteration C4);
    otherwise per-head selection semantics are preserved exactly on the
    shared Hkv-wide tables.
    """
    batch, hq, n, d = q.shape
    tile = indexing.stripe_tile(n, min(block_c, n))

    phase_fn, _ = dispatch.lookup("anchor_phase", backend)
    select_fn, _ = dispatch.lookup("stripe_select", backend)
    sparse_fn, _ = dispatch.lookup("sparse_attention", backend)
    kw = {} if lengths is None else {"lengths": lengths}

    # Alg. 1 — scores-only, pooled in-kernel.
    q_mean, m_bar = phase_fn(q, k, cfg, **kw)
    if not cfg.use_anchor:
        # Table 4 "Without Anchor" ablation: zero the anchor but keep the
        # +inf sentinel of all-padding pooled blocks.
        m_bar = jnp.where(jnp.isinf(m_bar), m_bar, jnp.zeros_like(m_bar))

    # Alg. 2 — compact tile selection (no dense hit mask).
    sel, counts = select_fn(q_mean, m_bar, k, cfg, tile, **kw)

    # Guaranteed anchor slots lead the tables (DESIGN.md §9).
    tables = merge_anchor_slots(sel, n, cfg)

    # Alg. 3 — one fused online-softmax sweep from zero state.
    out = sparse_fn(q, k, v, tables, cfg, **kw)
    if lengths is not None:
        # Padded query rows produce exact zeros.
        rows = jnp.arange(n)[None, None, :, None] < lengths[:, None, None, None]
        out = jnp.where(rows, out, jnp.zeros((), out.dtype))
    res = (out, counts) if return_stats else out
    if return_counters:
        return res, indexing.sparse_counters(
            counts, sel, tables, cfg.num_q_blocks(n), cfg.step)
    return res


for _backend in dispatch.BACKENDS:
    dispatch.register("anchor_attention", _backend)(
        functools.partial(_anchor_attention_pipeline, backend=_backend))


@functools.partial(
    jax.jit, static_argnames=("cfg", "block_c", "return_stats"))
def anchor_attention_staged(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: AnchorConfig,
    block_c: int = 128,
    return_stats: bool = False,
    lengths: jnp.ndarray | None = None,
):
    """The pre-fusion staged pipeline (XLA-only) — the parity oracle.

    Alg. 1 full ``(m, l, acc)`` statistics → XLA pooling glue → dense
    Alg. 2 hit mask → ``compact_stripe_tiles`` → Alg. 3 resume.  Kept
    unregistered for fused-vs-staged parity tests (the fused sweep
    changes the summation order, so the comparison is at tolerance) and
    as the baseline of ``benchmarks/prefill_index.py``; it is also the
    positive control of the jaxpr footprint tests — it DOES materialize
    the ``(B, Hq, N[, Dv])`` f32 statistics and the ``(B, Hq, T_s, N)``
    mask the fused path must not.
    """
    from repro.core.anchor_attention import masked_block_mean
    from repro.kernels.xla import (
        staged_anchor_stats,
        staged_sparse_attention,
        staged_stripe_mask,
    )

    batch, hq, n, d = q.shape
    hkv = k.shape[1]
    t_m = cfg.num_q_blocks(n)
    tile = indexing.stripe_tile(n, min(block_c, n))

    # Alg. 1 — full anchor statistics.
    m, l, acc = staged_anchor_stats(q, k, v, cfg, lengths=lengths)

    # Pooling (XLA glue re-reading q and m).
    if lengths is None:
        q_mean = jnp.mean(
            q.reshape(batch, hq, t_m, cfg.block_q, d).astype(jnp.float32),
            axis=3)
        m_bar = jnp.mean(m.reshape(batch, hq, t_m, cfg.block_q), axis=3)
    else:
        pool = jax.vmap(  # over batch (with its length) ...
            jax.vmap(  # ... then heads (shared length)
                lambda x, L, fill: masked_block_mean(
                    x, cfg.block_q, L, fill=fill),
                in_axes=(0, None, None)),
            in_axes=(0, 0, None))
        q_mean = pool(q, lengths, 0.0)
        m_bar = pool(m, lengths, jnp.inf)
    if not cfg.use_anchor:
        m_bar = jnp.where(jnp.isinf(m_bar), m_bar, jnp.zeros_like(m_bar))

    # Alg. 2 — dense stripe hit mask + tile compaction.
    hit = staged_stripe_mask(q_mean, m_bar, k, cfg, lengths=lengths)
    tables, counts = compact_stripe_tiles(
        hit, hkv, tile, cfg.capacity, share=cfg.share_kv_groups)

    # Alg. 3 — resume the online softmax from the statistics.
    out = staged_sparse_attention(q, k, v, tables, m, l, acc, cfg, block_c)
    if lengths is not None:
        rows = jnp.arange(n)[None, None, :, None] < lengths[:, None, None, None]
        out = jnp.where(rows, out, jnp.zeros((), out.dtype))
    if return_stats:
        return out, counts
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "block_c", "backend",
                                             "return_counters"))
def _chunk_anchor_impl(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,
    cfg: AnchorConfig,
    block_c: int = 128,
    live: jnp.ndarray | None = None,
    *,
    backend: str,
    return_counters: bool = False,
):
    """Fused AnchorAttention for one superblock-aligned chunk over a KV
    cache.

    The chunk's query rows sit at global positions ``[pos, pos + C)``;
    the cache views hold the real history at ``[0, pos)`` and the
    chunk's own K/V at ``[pos, pos + C)`` (the caller writes them before
    attending, exactly like the dense chunk path).  Because chunks are
    superblock-aligned, the anchor region decomposes cleanly:

    * init (sink) block — cache block 0, shared with the history;
    * local window — entirely inside the chunk (a superblock's window
      starts at its own first block);
    * stripe candidates — ``[block_kv, superblock_start)``: pure
      history, selected by the usual difference-aware threshold.

    All three regions feed ONE fused sparse sweep (DESIGN.md §9): the
    identification glue here is scores-only (no V loads, no per-row
    ``(m, l, acc)``), the selection is the compact chunked scan of
    :func:`repro.kernels.xla.stripe_select_xla` with the chunk's global
    superblock offset, and the anchor region rides in the tables'
    guaranteed leading slots with ``q_offset = pos`` aligning the causal
    mask.  For a full prompt processed chunk by chunk this computes
    exactly the same attention as one-shot anchor prefill (same regions,
    same selection rule) — which is what lets the serving engine keep
    long chunked prompts sparse instead of falling back to dense history
    attention.

    ``live`` (() int32, optional) is the number of REAL rows of a
    zero-padded final chunk.  Causality already keeps pad keys out of
    every live row's scores and candidates (pads sit after all live
    rows), but the *pooled* identification statistics cross rows:
    without masking, pad-row queries in a live row's block_q block shift
    ``q_mean``/``m_bar`` and change that block's stripe selection.  Live
    rows must match the one-shot varlen prefill, so pooling excludes
    rows >= live (all-pad blocks pool to +inf, which never selects).
    """
    from repro.kernels.xla import stripe_select_xla

    b, hq, c, d = q.shape
    hkv, s_len = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    sb = cfg.superblock_q()
    if c % sb:
        raise ValueError(
            f"chunk length {c} must be a multiple of the identification "
            f"superblock ({sb})")
    t_mc = c // cfg.block_q
    scale = 1.0 / (d ** 0.5)
    f32 = jnp.float32

    qg = q.reshape(b, hkv, g, c, d).astype(f32)
    row = pos + jnp.arange(c)  # global query positions

    # --- Scores-only Alg. 1 over (init block ∪ in-chunk window): the
    # per-row anchor m, never the (l, acc) softmax state — the fused
    # sweep recomputes the region with V.
    k0 = k_cache[:, :, : cfg.block_kv].astype(f32)
    s0 = jnp.einsum("bkgqd,bknd->bkgqn", qg, k0) * scale
    ok0 = jnp.arange(cfg.block_kv)[None, :] <= row[:, None]  # (C, b_kv)
    s0 = jnp.where(ok0[None, None, None], s0, _NEG_INF)
    kc = jax.lax.dynamic_slice_in_dim(k_cache, pos, c, axis=2).astype(f32)
    sw = jnp.einsum("bkgqd,bknd->bkgqn", qg, kc) * scale
    # Window of row r: [w_start_tok(superblock(r)), r] — in-chunk because
    # chunks are superblock-aligned.
    w_start = jnp.maximum(cfg.block_kv, (row // sb) * sb)  # (C,)
    okw = (row[None, :] >= w_start[:, None]) & (row[None, :] <= row[:, None])
    sw = jnp.where(okw[None, None, None], sw, _NEG_INF)
    m = jnp.maximum(jnp.max(s0, axis=-1), jnp.max(sw, axis=-1))

    # --- Pooled identification inputs (live-masked for padded chunks).
    qb5 = qg.reshape(b, hkv, g, t_mc, cfg.block_q, d)
    mb5 = m.reshape(b, hkv, g, t_mc, cfg.block_q)
    if live is None:
        q_mean = qb5.mean(axis=4)
        m_bar = mb5.mean(axis=4)
    else:
        # Pool only the live rows; all-pad blocks get an m_bar of +inf
        # (never passes the threshold) and a q_mean of zero.
        rv = (jnp.arange(c) < live).reshape(t_mc, cfg.block_q)
        cnt = rv.sum(axis=1)  # (t_mc,)
        denom = jnp.maximum(cnt, 1)[:, None]
        rvq = rv[None, None, None, :, :, None]
        q_mean = jnp.sum(jnp.where(rvq, qb5, 0.0), axis=4) / denom
        m_bar = jnp.sum(jnp.where(rv[None, None, None], mb5, 0.0),
                        axis=4) / denom[..., 0]
        m_bar = jnp.where(cnt[None, None, None] == 0, jnp.inf, m_bar)
    if not cfg.use_anchor:
        m_bar = jnp.where(jnp.isinf(m_bar), m_bar, jnp.zeros_like(m_bar))

    # --- Compact selection over the history + one fused sparse sweep.
    tile = indexing.stripe_tile(s_len, min(block_c, s_len))
    sb0 = pos // sb
    sel, counts = stripe_select_xla(
        q_mean.reshape(b, hq, t_mc, d), m_bar.reshape(b, hq, t_mc),
        k_cache, cfg, tile, sb0=sb0)
    tables = merge_anchor_slots(sel, s_len, cfg, sb0=sb0)
    sparse_fn, _ = dispatch.lookup("sparse_attention", backend)
    out = sparse_fn(q, k_cache, v_cache, tables, cfg, q_offset=pos)
    if return_counters:
        return out.astype(q.dtype), indexing.sparse_counters(
            counts, sel, tables, t_mc, cfg.step)
    return out.astype(q.dtype)


def chunk_anchor_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,
    cfg: AnchorConfig,
    block_c: int | None = None,
    live: jnp.ndarray | None = None,
    backend: str | None = None,
    return_counters: bool = False,
):
    """Fused AnchorAttention for one chunk of a chunked prefill.

    q: (B, Hq, C, D) chunk queries (``C % cfg.superblock_q() == 0``);
    k_cache/v_cache: (B, Hkv, S, D) per-sequence cache views already
    holding ``[0, pos + C)``; pos: () int32 superblock-aligned chunk
    start; live: () int32 real rows of a zero-padded final chunk (rows
    >= live are excluded from the pooled identification statistics and
    their outputs are garbage the caller discards).  Returns
    (B, Hq, C, Dv), paired with the chunk's (4,) int32 sparse work counts
    under ``return_counters``.
    """
    backend = dispatch.resolve_backend(backend)
    kw = {} if block_c is None else {"block_c": block_c}
    return _chunk_anchor_impl(
        q, k_cache, v_cache, pos, cfg, live=live, backend=backend,
        return_counters=return_counters, **kw)
