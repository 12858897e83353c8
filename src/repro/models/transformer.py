"""Decoder stack: scan-over-groups with per-layer mixer/FFN dispatch.

The stack is a ``lax.scan`` over ``num_groups`` groups; inside a group the
layer sequence is unrolled according to ``config.group_layout()`` (hybrid
archs interleave mamba/attention and dense/MoE FFNs inside one group).
All group parameters are stacked on a leading ``num_groups`` axis so the
HLO contains one group body regardless of depth — essential for the
512-device dry-run compile times and for pipeline-style scheduling.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.core.spec import AttentionSpec
from repro.kernels.indexing import SPARSE_COUNTERS
from repro.models import attention as attn_lib
from repro.models import cache as cache_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ModelConfig
from repro.models.layers import mlp_apply, mlp_init, rmsnorm, rmsnorm_init
from repro.models.moe import MoEParallelism

Params = dict[str, Any]


# ----------------------------------------------------------------- init ----


def _layer_init(key, cfg: ModelConfig, mixer: str, ffn: str) -> Params:
    km, kf = jax.random.split(key)
    p: Params = {"norm_mixer": rmsnorm_init(cfg.d_model)}
    if mixer == "attn":
        p["attn"] = (
            attn_lib.mla_init(km, cfg) if cfg.use_mla else attn_lib.gqa_init(km, cfg)
        )
    else:
        p["mamba"] = ssm_lib.mamba_init(km, cfg)
    if ffn != "none":
        p["norm_ffn"] = rmsnorm_init(cfg.d_model)
        if ffn == "moe":
            p["moe"] = moe_lib.moe_init(kf, cfg)
        else:
            p["mlp"] = mlp_init(kf, cfg.d_model, cfg.d_ff, jnp.dtype(cfg.dtype))
    return p


def group_init(key, cfg: ModelConfig) -> Params:
    layout = cfg.group_layout()
    keys = jax.random.split(key, len(layout))
    return {
        f"l{i}": _layer_init(keys[i], cfg, mixer, ffn)
        for i, (mixer, ffn) in enumerate(layout)
    }


def stack_init(key, cfg: ModelConfig) -> Params:
    """Stacked group params: every leaf gets a leading num_groups axis."""
    keys = jax.random.split(key, cfg.num_groups)
    groups = [group_init(k, cfg) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *groups)


# -------------------------------------------------------------- prefill ----


def _layer_apply(
    x: jnp.ndarray,
    p: Params,
    cfg: ModelConfig,
    mixer: str,
    ffn: str,
    positions: jnp.ndarray,
    spec: AttentionSpec | None,
    lengths: jnp.ndarray | None,
    ssm_impl: str,
    return_cache: bool = False,
    moe_parallel: MoEParallelism | None = None,
    sp_spec=None,
):
    aux = jnp.zeros((), jnp.float32)
    cache = None
    counters = jnp.zeros((len(SPARSE_COUNTERS),), jnp.int32)
    h = rmsnorm(x, p["norm_mixer"], cfg.norm_eps)
    if mixer == "attn":
        apply = attn_lib.mla_apply if cfg.use_mla else attn_lib.gqa_apply
        h = apply(h, p["attn"], cfg, positions, spec=spec,
                  lengths=lengths, return_cache=return_cache)
        if return_cache:
            h, cache, counters = h
    else:
        h = ssm_lib.mamba_apply(h, p["mamba"], cfg, ssm_impl=ssm_impl,
                                return_cache=return_cache)
        if return_cache:
            h, cache = h
    if sp_spec is not None:
        # Megatron-SP: the row-parallel output reduce-scatters onto the
        # sequence dim (over `model`); the saved activation is 1/TP-sized.
        h = jax.lax.with_sharding_constraint(h, sp_spec)
    h = checkpoint_name(h, "tp_mixer_out")
    x = x + h
    if ffn != "none":
        h = rmsnorm(x, p["norm_ffn"], cfg.norm_eps)
        if ffn == "moe":
            h, aux = moe_lib.moe_apply(h, p["moe"], cfg, parallel=moe_parallel)
        else:
            h = mlp_apply(h, p["mlp"], cfg.mlp_act)
        if sp_spec is not None:
            h = jax.lax.with_sharding_constraint(h, sp_spec)
        h = checkpoint_name(h, "tp_ffn_out")
        x = x + h
    return x, aux, cache, counters


def make_group_fn(
    cfg: ModelConfig,
    positions: jnp.ndarray,
    *,
    spec: AttentionSpec | None = None,
    lengths: jnp.ndarray | None = None,
    ssm_impl: str = "xla",
    remat: bool = True,
    remat_policy: str = "nothing",
    return_cache: bool = False,
    moe_parallel: MoEParallelism | None = None,
    sp_spec=None,
):
    """One scan-group body ``(x, group_params) -> (x, (aux, counters,
    caches))``; ``counters`` is the group's (4,) int32 sparse-attention
    work (``repro.kernels.indexing.SPARSE_COUNTERS``), counted by the
    serving prefill (``return_cache``) and zero otherwise.

    Shared by the training/serving stacks AND the roofline cost model
    (dryrun compiles one group with identical remat/sharding to correct
    XLA's once-per-while-body cost accounting — DESIGN.md §7).
    """
    layout = cfg.group_layout()

    def group_fn(x, gp):
        aux_total = jnp.zeros((), jnp.float32)
        counters_total = jnp.zeros((len(SPARSE_COUNTERS),), jnp.int32)
        caches = {}
        for i, (mixer, ffn) in enumerate(layout):
            x, aux, cache, counters = _layer_apply(
                x, gp[f"l{i}"], cfg, mixer, ffn, positions, spec,
                lengths, ssm_impl, return_cache, moe_parallel, sp_spec)
            aux_total = aux_total + aux
            counters_total = counters_total + counters
            if return_cache:
                caches[f"l{i}"] = cache
        if sp_spec is not None:
            x = jax.lax.with_sharding_constraint(x, sp_spec)
        return x, (aux_total, counters_total, caches)

    if remat:
        policy = {
            "dots": jax.checkpoint_policies.dots_saveable,
            "nothing": jax.checkpoint_policies.nothing_saveable,
            # Save the TP-collective outputs so the backward pass never
            # replays the forward all-reduces (§Perf iteration B2); with
            # SP the saved tensors are sequence-sharded (cheap).
            "save_tp": jax.checkpoint_policies.save_only_these_names(
                "tp_mixer_out", "tp_ffn_out"),
        }[remat_policy]
        group_fn = jax.checkpoint(group_fn, policy=policy)
    return group_fn


def stack_apply(
    x: jnp.ndarray,
    stacked: Params,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    *,
    spec: AttentionSpec | None = None,
    lengths: jnp.ndarray | None = None,
    ssm_impl: str = "xla",
    remat: bool = True,
    remat_policy: str = "nothing",
    return_cache: bool = False,
    moe_parallel: MoEParallelism | None = None,
    sp_spec=None,
):
    """Run the decoder stack.  Returns (hidden, aux) or, with
    ``return_cache``, (hidden, aux, cache, counters): ``counters`` is
    (num_groups, 4) int32, each group's sparse-attention work."""
    group_fn = make_group_fn(
        cfg, positions, spec=spec, lengths=lengths,
        ssm_impl=ssm_impl, remat=remat, remat_policy=remat_policy,
        return_cache=return_cache, moe_parallel=moe_parallel,
        sp_spec=sp_spec)
    x, (auxes, counters, caches) = jax.lax.scan(group_fn, x, stacked)
    if return_cache:
        return x, jnp.sum(auxes), caches, counters
    return x, jnp.sum(auxes)


# --------------------------------------------------------------- decode ----


def group_cache_init(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    layout: cache_lib.PagedKVLayout | None = None,
) -> Params:
    cache: Params = {}
    for i, (mixer, _) in enumerate(cfg.group_layout()):
        if mixer == "attn":
            if layout is not None:
                if cfg.use_mla:
                    raise NotImplementedError(
                        "paged KV layout is GQA-only; MLA's latent cache "
                        "keeps the dense slab (see repro.models.cache)")
                cache[f"l{i}"] = attn_lib.gqa_init_paged_cache(cfg, layout)
            else:
                cache[f"l{i}"] = (
                    attn_lib.mla_init_cache(cfg, batch, max_len)
                    if cfg.use_mla
                    else attn_lib.gqa_init_cache(cfg, batch, max_len)
                )
        else:
            if layout is not None:
                raise NotImplementedError(
                    "paged KV layout requires an attention-only arch; "
                    "recurrent-state layers keep the dense slab")
            cache[f"l{i}"] = ssm_lib.mamba_init_cache(cfg, batch)
    return cache


def stack_cache_init(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    layout: cache_lib.PagedKVLayout | None = None,
) -> Params:
    """Decoder-stack cache.  ``layout=None`` (default): per-slot dense
    slabs, leaves (G, B, ..., max_len, ...).  With a
    :class:`repro.models.cache.PagedKVLayout`: one shared paged pool,
    leaves (G, total_pages, Hkv, page_size, hd) — no batch axis; callers
    address sequences through int32 page tables."""
    one = group_cache_init(cfg, batch, max_len, layout=layout)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.num_groups, *a.shape)), one
    )


def stack_decode(
    x: jnp.ndarray,
    stacked: Params,
    cache: Params,
    cfg: ModelConfig,
    pos: jnp.ndarray,
    active: jnp.ndarray | None = None,
    page_tables: jnp.ndarray | None = None,
    kv_backend: str | None = None,
) -> tuple[jnp.ndarray, Params]:
    """One-token decode through the stack.  x: (B, 1, d).

    ``active`` (optional, (B,) bool): batch slots whose caches/states may
    be written this step.  Schedulers that decode position groups of a
    mixed-position batch MUST pass it — without it every decoder writes
    K/V (or advances recurrent state) at ``pos`` for ALL slots, corrupting
    the history of slots that are past ``pos``.

    ``page_tables`` ((B, n_pages) int32, optional): decode against a
    *paged* cache (leaves (G, P, Hkv, page_size, hd); see
    :mod:`repro.models.cache`).  ``active`` masking then happens inside
    the paged write (null-page redirection) — the shared pool has no
    batch axis to ``where`` over.  ``kv_backend`` picks the
    ``paged_flash_decode`` kernel backend (None = process default).
    """
    layout = cfg.group_layout()

    def keep_active(new_leaf, old_leaf):
        mask = active.reshape(-1, *([1] * (new_leaf.ndim - 1)))
        return jnp.where(mask, new_leaf, old_leaf)

    def group_fn(x, inp):
        gp, gc = inp
        new_gc = {}
        for i, (mixer, ffn) in enumerate(layout):
            p = gp[f"l{i}"]
            h = rmsnorm(x, p["norm_mixer"], cfg.norm_eps)
            if mixer == "attn":
                if page_tables is not None:
                    if cfg.use_mla:
                        raise NotImplementedError(
                            "paged decode is GQA-only (see repro.models.cache)")
                    h, nc = attn_lib.gqa_decode_paged(
                        h, p["attn"], gc[f"l{i}"], cfg, pos, page_tables,
                        active=active, kv_backend=kv_backend)
                else:
                    if cfg.use_mla:
                        dec = (attn_lib.mla_decode_absorbed if cfg.mla_absorb
                               else attn_lib.mla_decode)
                    else:
                        dec = attn_lib.gqa_decode
                    h, nc = dec(h, p["attn"], gc[f"l{i}"], cfg, pos)
            else:
                h, nc = ssm_lib.mamba_decode(h, p["mamba"], gc[f"l{i}"], cfg)
            if active is not None and page_tables is None:
                nc = jax.tree.map(keep_active, nc, gc[f"l{i}"])
            new_gc[f"l{i}"] = nc
            x = x + h
            if ffn != "none":
                h = rmsnorm(x, p["norm_ffn"], cfg.norm_eps)
                if ffn == "moe":
                    h, _ = moe_lib.moe_apply(h, p["moe"], cfg)
                else:
                    h = mlp_apply(h, p["mlp"], cfg.mlp_act)
                x = x + h
        return x, new_gc

    x, new_cache = jax.lax.scan(group_fn, x, (stacked, cache))
    return x, new_cache


def stack_chunk_prefill(
    x: jnp.ndarray,
    stacked: Params,
    cache: Params,
    cfg: ModelConfig,
    pos: jnp.ndarray,
    spec: AttentionSpec | None = None,
    live: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, Params]:
    """Chunked prefill: one C-token chunk through the stack with history.

    x: (B, C, d); ``cache`` holds dense (B, Hkv, S, hd) views that already
    contain positions ``[0, pos)`` (for a paged engine: gathered from the
    pool, scattered back after — see :mod:`repro.models.cache`).  Writes
    the chunk's K/V at ``[pos, pos + C)`` and returns (hidden (B, C, d),
    updated cache).  Attention-only (GQA) architectures — recurrent-state
    mixers would need their scan state threaded chunk-to-chunk, and those
    archs keep the dense one-shot path.

    ``spec`` picks the chunk attention math: an ``anchor`` spec runs the
    index-driven sparse chunk path (superblock-aligned chunks only — the
    serving engine enforces the alignment); ``None``/dense runs dense
    history attention.  ``live`` (() int32, optional) is the real-row
    count of a zero-padded final chunk.

    Returns (hidden, cache, counters), ``counters`` (num_groups, 4) int32
    as :func:`stack_apply`'s.
    """
    layout = cfg.group_layout()
    if cfg.use_mla or any(mixer != "attn" for mixer, _ in layout):
        raise NotImplementedError(
            "chunked prefill is GQA-attention-only (see repro.models.cache)")

    def group_fn(x, inp):
        gp, gc = inp
        new_gc = {}
        counters_total = jnp.zeros((len(SPARSE_COUNTERS),), jnp.int32)
        for i, (mixer, ffn) in enumerate(layout):
            p = gp[f"l{i}"]
            h = rmsnorm(x, p["norm_mixer"], cfg.norm_eps)
            h, nc, counters = attn_lib.gqa_chunk_apply(
                h, p["attn"], gc[f"l{i}"], cfg, pos, spec=spec, live=live)
            counters_total = counters_total + counters
            new_gc[f"l{i}"] = nc
            x = x + h
            if ffn != "none":
                h = rmsnorm(x, p["norm_ffn"], cfg.norm_eps)
                if ffn == "moe":
                    h, _ = moe_lib.moe_apply(h, p["moe"], cfg)
                else:
                    h = mlp_apply(h, p["mlp"], cfg.mlp_act)
                x = x + h
        return x, (new_gc, counters_total)

    x, (new_cache, counters) = jax.lax.scan(group_fn, x, (stacked, cache))
    return x, new_cache, counters
