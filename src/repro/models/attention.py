"""Attention blocks: GQA (with AnchorAttention prefill backend) and MLA.

Prefill attention is configured by a declarative
:class:`repro.core.spec.AttentionSpec` (algorithm × backend × masking) and
executed through the canonical :func:`repro.kernels.ops.attention` entry
point — every path routes through the kernel backend registry
(:mod:`repro.kernels.dispatch`).  Variable-length right-padded batches
pass a per-sequence ``lengths`` array (``spec.masking == "padded"``).

The legacy ``attn_impl`` strings ("dense" | "anchor" | "pallas" |
"pallas_flash") map onto specs via
:func:`repro.core.spec.spec_from_attn_impl` at the model entry points.

Decode always uses dense KV-cache attention (the paper is prefill-only,
Limitations §).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.spec import AttentionSpec
from repro.kernels.indexing import SPARSE_COUNTERS
from repro.models.cache import NULL_PAGE, PagedKVLayout
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_rope,
    chunk_attention,
    decode_attention,
    dense_init,
    rmsnorm,
    rmsnorm_init,
)

Params = dict[str, Any]


def _prefill_attention(q, k, v, spec: AttentionSpec | None,
                       lengths: jnp.ndarray | None = None,
                       return_counters: bool = False):
    from repro.kernels import ops as kernel_ops

    spec = spec if spec is not None else AttentionSpec(backend="xla")
    if lengths is not None and spec.masking != "padded":
        spec = spec.padded()
    return kernel_ops.attention(q, k, v, spec, lengths=lengths,
                                return_counters=return_counters)


# ------------------------------------------------------------------ GQA ----


def gqa_init(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], d, h * hd, dt),
        "wk": dense_init(ks[1], d, hkv * hd, dt),
        "wv": dense_init(ks[2], d, hkv * hd, dt),
        "wo": dense_init(ks[3], h * hd, d, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd)
        p["k_norm"] = rmsnorm_init(hd)
    return p


def gqa_apply(
    x: jnp.ndarray,
    p: Params,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    *,
    spec: AttentionSpec | None = None,
    lengths: jnp.ndarray | None = None,
    return_cache: bool = False,
):
    """Prefill self-attention.  x: (B, N, d_model); positions: (B, N).

    ``return_cache`` (the serving prefill) returns ``(out, cache,
    counters)``: the layer's K/V and the sparse stage's (4,) int32 work
    counts (``repro.kernels.indexing.SPARSE_COUNTERS``; zeros for dense).
    """
    b, n, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, n, h, hd)
    k = (x @ p["wk"]).reshape(b, n, hkv, hd)
    v = (x @ p["wv"]).reshape(b, n, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))  # (B, H, N, D)
    out = _prefill_attention(q, k, v, spec, lengths,
                             return_counters=return_cache)
    if return_cache:
        out, counters = out
    out = jnp.swapaxes(out, 1, 2).reshape(b, n, h * hd)
    out = out @ p["wo"]
    if return_cache:
        # rope'd K — matches gqa_decode layout
        return out, {"k": k, "v": v}, counters
    return out


def gqa_init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    dt = jnp.dtype(cfg.dtype)
    shape = (batch, cfg.num_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def gqa_decode(
    x: jnp.ndarray,
    p: Params,
    cache: Params,
    cfg: ModelConfig,
    pos: jnp.ndarray,
) -> tuple[jnp.ndarray, Params]:
    """One-token decode.  x: (B, 1, d); pos: () int32 current position."""
    b = x.shape[0]
    q, k, v = _gqa_project_decode(x, p, cfg, pos)
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, pos, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, pos, 0))
    out = decode_attention(q, k_cache, v_cache, pos + 1)
    out = jnp.swapaxes(out, 1, 2).reshape(b, 1, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], {"k": k_cache, "v": v_cache}


# ----------------------------------------------------------- GQA (paged) ----


def gqa_init_paged_cache(cfg: ModelConfig, layout: PagedKVLayout) -> Params:
    """Per-layer paged KV pool: (total_pages, Hkv, page_size, head_dim)."""
    dt = jnp.dtype(cfg.dtype)
    shape = (layout.total_pages, cfg.num_kv_heads, layout.page_size,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _gqa_project_decode(x, p, cfg: ModelConfig, pos):
    """Shared one-token q/k/v projection + rope for the decode paths."""
    b = x.shape[0]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, 1, h, hd)
    k = (x @ p["wk"]).reshape(b, 1, hkv, hd)
    v = (x @ p["wv"]).reshape(b, 1, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    posb = jnp.full((b, 1), pos, jnp.int32)
    q = apply_rope(q, posb, cfg.rope_theta)
    k = apply_rope(k, posb, cfg.rope_theta)
    return tuple(jnp.swapaxes(t, 1, 2) for t in (q, k, v))


def gqa_decode_paged(
    x: jnp.ndarray,
    p: Params,
    cache: Params,
    cfg: ModelConfig,
    pos: jnp.ndarray,
    page_tables: jnp.ndarray,
    active: jnp.ndarray | None = None,
    kv_backend: str | None = None,
) -> tuple[jnp.ndarray, Params]:
    """One-token decode against the shared paged KV pool.

    x: (B, 1, d); cache leaves: (P, Hkv, page_size, hd) — no batch axis,
    the pool is shared; page_tables: (B, n_pages) int32.  The new token's
    K/V is scattered into physical page ``page_tables[b, pos //
    page_size]`` at offset ``pos % page_size``.  ``active=False`` slots
    (and unassigned table entries) redirect their write to the null page
    instead of masking — the pool has no batch axis for a ``where``.
    """
    b = x.shape[0]
    q, k, v = _gqa_project_decode(x, p, cfg, pos)  # (B, H*, 1, hd)
    ps = cache["k"].shape[2]
    page_idx = jnp.full((b, 1), pos // ps, jnp.int32)
    pids = jnp.take_along_axis(page_tables, page_idx, axis=1)[:, 0]
    if active is not None:
        pids = jnp.where(active, pids, NULL_PAGE)
    offset = pos % ps
    k_pages = cache["k"].at[pids, :, offset].set(
        k[:, :, 0].astype(cache["k"].dtype))
    v_pages = cache["v"].at[pids, :, offset].set(
        v[:, :, 0].astype(cache["v"].dtype))

    from repro.kernels import ops as kernel_ops

    out = kernel_ops.paged_flash_decode(
        q, k_pages, v_pages, page_tables, pos + 1, backend=kv_backend)
    out = jnp.swapaxes(out, 1, 2).reshape(b, 1, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"], {"k": k_pages, "v": v_pages}


def gqa_chunk_apply(
    x: jnp.ndarray,
    p: Params,
    cache: Params,
    cfg: ModelConfig,
    pos: jnp.ndarray,
    spec: AttentionSpec | None = None,
    live: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, Params]:
    """Chunked-prefill attention: a C-token chunk against dense cache
    views that already hold positions ``[0, pos)`` of each sequence.
    ``live`` (() int32) counts the real rows of a zero-padded final
    chunk (forwarded to the sparse path's pooled statistics).

    x: (B, C, d); cache leaves: (B, Hkv, S, hd) gathered views (see
    :func:`repro.models.cache.gather_pages`).  Writes the chunk's K/V at
    ``[pos, pos + C)`` and attends each row to history + its causal
    prefix of the chunk.

    With an ``anchor`` ``spec`` (and a superblock-aligned chunk/``pos``,
    which the serving engine guarantees), the chunk runs the index-driven
    sparse path — :func:`repro.kernels.ops.chunk_anchor_attention` — so
    chunked long prompts keep AnchorAttention prefill instead of falling
    back to dense history attention.  Otherwise: dense
    :func:`repro.models.layers.chunk_attention`.

    Returns ``(out, cache, counters)``: the sparse chunk's (4,) int32
    work counts (``repro.kernels.indexing.SPARSE_COUNTERS``), zeros on
    the dense path.
    """
    b, c, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, c, h, hd)
    k = (x @ p["wk"]).reshape(b, c, hkv, hd)
    v = (x @ p["wv"]).reshape(b, c, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    positions = jnp.broadcast_to(pos + jnp.arange(c), (b, c))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, 0, pos, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, pos, 0))
    sparse = (spec is not None and spec.algorithm == "anchor"
              and c % spec.anchor.superblock_q() == 0)
    if sparse:
        from repro.kernels import ops as kernel_ops

        out, counters = kernel_ops.chunk_anchor_attention(
            q, k_cache, v_cache, pos, spec.anchor, live=live,
            backend=spec.backend, return_counters=True)
    else:
        out = chunk_attention(q, k_cache, v_cache, pos)
        counters = jnp.zeros((len(SPARSE_COUNTERS),), jnp.int32)
    out = jnp.swapaxes(out, 1, 2).reshape(b, c, h * hd)
    return out @ p["wo"], {"k": k_cache, "v": v_cache}, counters


# ------------------------------------------------------------------ MLA ----


def mla_init(key, cfg: ModelConfig) -> Params:
    """DeepSeek-V2 Multi-head Latent Attention (compressed KV)."""
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    d, h = cfg.d_model, cfg.num_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": dense_init(ks[0], d, h * qk, dt),
        "w_dkv": dense_init(ks[1], d, cfg.kv_lora_rank + cfg.qk_rope_dim, dt),
        "w_uk": dense_init(ks[2], cfg.kv_lora_rank, h * cfg.qk_nope_dim, dt),
        "w_uv": dense_init(ks[3], cfg.kv_lora_rank, h * cfg.v_head_dim, dt),
        "wo": dense_init(ks[4], h * cfg.v_head_dim, d, dt),
        "kv_norm": rmsnorm_init(cfg.kv_lora_rank),
    }


def _mla_qkv(x, p, cfg: ModelConfig, positions):
    """Shared projection logic; returns per-head q, k, v (B, N, H, ·) plus
    the compressed cache streams."""
    b, n, _ = x.shape
    h = cfg.num_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = (x @ p["wq"]).reshape(b, n, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = x @ p["w_dkv"]  # (B, N, lora + rope)
    c_kv, k_rope = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope1 = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)

    k_nope = (c_kv @ p["w_uk"]).reshape(b, n, h, nope)
    v = (c_kv @ p["w_uv"]).reshape(b, n, h, cfg.v_head_dim)
    k_rope_h = jnp.broadcast_to(k_rope1, (b, n, h, rope))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    return q_full, k_full, v, {"ckv": c_kv, "k_rope": k_rope1[:, :, 0]}


def mla_apply(
    x: jnp.ndarray,
    p: Params,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    *,
    spec: AttentionSpec | None = None,
    lengths: jnp.ndarray | None = None,
    return_cache: bool = False,
):
    b, n, _ = x.shape
    q, k, v, cache = _mla_qkv(x, p, cfg, positions)
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    # Note the asymmetric head dims (qk: nope+rope, v: v_head_dim); the
    # anchor/pallas paths support that directly (D only enters via scale).
    out = _prefill_attention(q, k, v, spec, lengths,
                             return_counters=return_cache)
    if return_cache:
        out, counters = out
    out = jnp.swapaxes(out, 1, 2).reshape(b, n, cfg.num_heads * cfg.v_head_dim)
    out = out @ p["wo"]
    if return_cache:
        return out, cache, counters  # as gqa_apply
    return out


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    dt = jnp.dtype(cfg.dtype)
    # MLA caches the *compressed* stream: kv_lora_rank + rope dims per token.
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dt),
    }


def mla_decode_absorbed(
    x: jnp.ndarray, p: Params, cache: Params, cfg: ModelConfig, pos: jnp.ndarray
) -> tuple[jnp.ndarray, Params]:
    """Absorbed-matmul MLA decode (beyond-paper §Perf optimization).

    Instead of decompressing per-head K/V over the whole cache
    (O(S·H·(d_nope+d_v)·R) FLOPs + an (B,S,H,·) temp), absorb the
    up-projections into the query/output:

        score_h(i) = (W_uk_hᵀ q_nope_h) · c_i / √d  +  q_rope_h · k_rope_i
        out_h      = W_uv_hᵀ? -> out_h = (Σ_i p_i c_i) @ W_uv_h

    Attention runs directly against the compressed (B,S,R) cache — MQA on
    the latent stream.  Exactly equal to :func:`mla_decode` in exact
    arithmetic (tested); ~(d_nope+d_v)·R/(R+d_rope) ≈ 230× fewer
    attention FLOPs at 32k and no decompressed temps.
    """
    b = x.shape[0]
    h = cfg.num_heads
    nope, rope, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    posb = jnp.full((b, 1), pos, jnp.int32)

    q = (x @ p["wq"]).reshape(b, 1, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, posb, cfg.rope_theta)

    ckv = x @ p["w_dkv"]
    c_kv, k_rope = ckv[..., :r], ckv[..., r:]
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], posb, cfg.rope_theta)[:, :, 0]

    ckv_c = jax.lax.dynamic_update_slice(
        cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, pos, 0))
    kr_c = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, pos, 0))

    # Absorb W_uk into the query:  (B, H, R)
    w_uk = p["w_uk"].reshape(r, h, nope)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32)
    scale = 1.0 / ((nope + rope) ** 0.5)
    # Blockwise online softmax over cache chunks (§Perf iteration A3):
    # never materializes the (B, H, S) f32 score tensor; bf16 cache
    # operands with f32 accumulation (A2).
    s_len = ckv_c.shape[1]
    chunk = min(4096, s_len)
    n_chunks = s_len // chunk
    q_abs16 = q_abs.astype(ckv_c.dtype)
    q_rope16 = q_rope[:, 0].astype(kr_c.dtype)

    def step(carry, _):
        m, l, ctx_acc, j = carry
        # dynamic_slice along S keeps the native cache layout (no transpose
        # copy — that cost ~2× the cache bytes per layer, iteration A3a).
        ckv_j = jax.lax.dynamic_slice_in_dim(ckv_c, j * chunk, chunk, axis=1)
        kr_j = jax.lax.dynamic_slice_in_dim(kr_c, j * chunk, chunk, axis=1)
        s = jnp.einsum("bhr,bsr->bhs", q_abs16, ckv_j,
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bhe,bse->bhs", q_rope16, kr_j,
                           preferred_element_type=jnp.float32)
        s = s * scale
        valid = (j * chunk + jnp.arange(chunk))[None, None, :] < pos + 1
        s = jnp.where(valid, s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        pv = jnp.exp(s - m_new[..., None])
        pv = jnp.where(valid, pv, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + pv.sum(-1)
        ctx_acc = ctx_acc * alpha[..., None] + jnp.einsum(
            "bhs,bsr->bhr", pv.astype(ckv_j.dtype), ckv_j,
            preferred_element_type=jnp.float32)
        return (m_new, l, ctx_acc, j + 1), None

    init = (jnp.full((b, h), -1e30, jnp.float32),
            jnp.zeros((b, h), jnp.float32),
            jnp.zeros((b, h, r), jnp.float32),
            jnp.asarray(0, jnp.int32))
    (m, l, ctx, _), _ = jax.lax.scan(step, init, None, length=n_chunks)
    ctx = ctx / jnp.maximum(l, 1e-30)[..., None]
    # Absorb W_uv on the way out:  (B, H, d_v)
    w_uv = p["w_uv"].reshape(r, h, cfg.v_head_dim)
    out = jnp.einsum("bhr,rhv->bhv", ctx.astype(w_uv.dtype), w_uv,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, 1, h * cfg.v_head_dim).astype(x.dtype)
    return out @ p["wo"], {"ckv": ckv_c, "k_rope": kr_c}


def mla_decode(
    x: jnp.ndarray, p: Params, cache: Params, cfg: ModelConfig, pos: jnp.ndarray
) -> tuple[jnp.ndarray, Params]:
    b = x.shape[0]
    h = cfg.num_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    posb = jnp.full((b, 1), pos, jnp.int32)

    q = (x @ p["wq"]).reshape(b, 1, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, posb, cfg.rope_theta)

    ckv = x @ p["w_dkv"]
    c_kv, k_rope = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], posb, cfg.rope_theta)[:, :, 0]

    ckv_c = jax.lax.dynamic_update_slice(
        cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, pos, 0))
    kr_c = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, pos, 0))

    # Decompress per head over the cache (simple faithful path; the
    # absorbed-matmul trick is a recorded §Perf lever).
    s_len = ckv_c.shape[1]
    k_nope = (ckv_c @ p["w_uk"]).reshape(b, s_len, h, nope)
    v = (ckv_c @ p["w_uv"]).reshape(b, s_len, h, cfg.v_head_dim)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_c[:, :, None, :], (b, s_len, h, rope))], -1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = decode_attention(
        jnp.swapaxes(q_full, 1, 2),
        jnp.swapaxes(k_full, 1, 2),
        jnp.swapaxes(v, 1, 2),
        pos + 1,
    )
    out = jnp.swapaxes(out, 1, 2).reshape(b, 1, h * cfg.v_head_dim)
    return out @ p["wo"], {"ckv": ckv_c, "k_rope": kr_c}
