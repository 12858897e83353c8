"""LM wrapper: embeddings (or embed-input stubs), decoder stack, head, loss.

``init`` builds real parameters (smoke tests / examples); the dry-run uses
``jax.eval_shape(init, ...)`` so full-size configs never allocate.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.config import AnchorConfig
from repro.core.spec import AttentionSpec, resolve_attention_spec
from repro.models import cache as cache_lib
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.layers import rmsnorm, rmsnorm_init
from repro.models.moe import MoEParallelism

Params = dict[str, Any]


def init(key, cfg: ModelConfig) -> Params:
    ke, ks, kh = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    p: Params = {
        "embed": (
            jax.random.normal(ke, (cfg.vocab_size, cfg.d_model), jnp.float32)
            * 0.02
        ).astype(dt),
        "blocks": transformer.stack_init(ks, cfg),
        "final_norm": rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (
            jax.random.normal(kh, (cfg.vocab_size, cfg.d_model), jnp.float32)
            * 0.02
        ).astype(dt)
    return p


def _logits(x: jnp.ndarray, params: Params) -> jnp.ndarray:
    head = params.get("lm_head", params["embed"])
    return jnp.einsum("bnd,vd->bnv", x, head)


def forward(
    params: Params,
    tokens: jnp.ndarray | None,
    cfg: ModelConfig,
    *,
    embeds: jnp.ndarray | None = None,
    positions: jnp.ndarray | None = None,
    spec: AttentionSpec | None = None,
    lengths: jnp.ndarray | None = None,
    attn_impl: str | None = None,
    anchor_cfg: AnchorConfig | None = None,
    ssm_impl: str = "xla",
    remat: bool = True,
    remat_policy: str = "nothing",
    moe_parallel: MoEParallelism | None = None,
    sp_spec=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Prefill forward pass -> (logits (B, N, V), aux_loss).

    Attention is configured by ``spec`` (an :class:`AttentionSpec`;
    default: dense on ``xla``).  ``lengths`` ((B,) int32, optional) marks
    a right-padded batch.  ``attn_impl=``/``anchor_cfg=`` are deprecated
    and translate to a spec with a ``DeprecationWarning``.
    """
    spec = resolve_attention_spec(spec, attn_impl, anchor_cfg)
    if cfg.embed_input:
        assert embeds is not None, f"{cfg.name} takes precomputed embeddings"
        x = embeds.astype(jnp.dtype(cfg.dtype))
        b, n = x.shape[:2]
    else:
        assert tokens is not None
        x = jnp.take(params["embed"], tokens, axis=0)
        b, n = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(n), (b, n))
    x, aux = transformer.stack_apply(
        x, params["blocks"], cfg, positions,
        spec=spec, lengths=lengths, ssm_impl=ssm_impl,
        remat=remat, remat_policy=remat_policy, moe_parallel=moe_parallel,
        sp_spec=sp_spec)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _logits(x, params), aux


def loss_fn(
    params: Params,
    batch: dict[str, jnp.ndarray],
    cfg: ModelConfig,
    *,
    spec: AttentionSpec | None = None,
    attn_impl: str | None = None,
    anchor_cfg: AnchorConfig | None = None,
    aux_weight: float = 0.01,
    remat: bool = True,
    remat_policy: str = "nothing",
    moe_parallel: MoEParallelism | None = None,
    sp_spec=None,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """Next-token cross-entropy (+ MoE aux).  batch: tokens/embeds, labels."""
    spec = resolve_attention_spec(spec, attn_impl, anchor_cfg)
    logits, aux = forward(
        params,
        batch.get("tokens"),
        cfg,
        embeds=batch.get("embeds"),
        spec=spec,
        remat=remat,
        remat_policy=remat_policy,
        moe_parallel=moe_parallel,
        sp_spec=sp_spec,
    )
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = nll + aux_weight * aux
    return total, {"nll": nll, "aux": aux}


def prefill(
    params: Params,
    tokens: jnp.ndarray | None,
    cfg: ModelConfig,
    *,
    embeds: jnp.ndarray | None = None,
    spec: AttentionSpec | None = None,
    lengths: jnp.ndarray | None = None,
    attn_impl: str | None = None,
    anchor_cfg: AnchorConfig | None = None,
    ssm_impl: str = "xla",
    moe_parallel: MoEParallelism | None = None,
    return_counters: bool = False,
):
    """Serving prefill: last-position logits + populated per-layer cache.

    This is the step the paper accelerates — the default spec runs
    AnchorAttention on every attention layer (falls back to dense for
    attention-free archs).

    ``lengths`` ((B,) int32, optional) enables right-padded batched
    prefill: each sequence ``b`` occupies ``tokens[b, :lengths[b]]``, the
    returned logits are taken at each sequence's own last valid position,
    and cache positions beyond a sequence's length hold padding (callers
    resume decode at ``pos = lengths[b]``).

    ``return_counters`` pairs the cache with (num_groups, 4) int32, each
    layer group's sparse-attention work in
    ``repro.kernels.indexing.SPARSE_COUNTERS`` order (zeros for dense):
    the result is then ``(logits, (cache, counters))``.
    """
    spec = resolve_attention_spec(spec, attn_impl, anchor_cfg,
                                  default_algorithm="anchor")
    if not cfg.has_attention:
        # mamba2: no attention layers to sparsify.
        spec = spec.with_algorithm("dense")
    if lengths is not None and spec.masking != "padded":
        spec = spec.padded()
    if cfg.embed_input:
        assert embeds is not None
        x = embeds.astype(jnp.dtype(cfg.dtype))
        b, n = x.shape[:2]
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
        b, n = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(n), (b, n))
    x, _, cache, counters = transformer.stack_apply(
        x, params["blocks"], cfg, positions,
        spec=spec, lengths=lengths, ssm_impl=ssm_impl,
        remat=False, return_cache=True, moe_parallel=moe_parallel)
    if lengths is None:
        x_last = x[:, -1:]
    else:
        # Per-sequence last *valid* position of the right-padded batch.
        idx = (lengths.astype(jnp.int32) - 1)[:, None, None]
        x_last = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    x = rmsnorm(x_last, params["final_norm"], cfg.norm_eps)
    if return_counters:
        return _logits(x, params)[:, 0], (cache, counters)
    return _logits(x, params)[:, 0], cache


def decode_step(
    params: Params,
    cache: Params,
    token: jnp.ndarray,
    pos: jnp.ndarray,
    cfg: ModelConfig,
    *,
    embed: jnp.ndarray | None = None,
    active: jnp.ndarray | None = None,
    page_tables: jnp.ndarray | None = None,
    kv_backend: str | None = None,
) -> tuple[jnp.ndarray, Params]:
    """One decode step.  token: (B,) int32 (or embed (B, 1, d)); pos: ().

    ``active`` (optional, (B,) bool) restricts cache/state writes to the
    given batch slots — required when decoding one position group of a
    mixed-position batch (see :func:`transformer.stack_decode`).

    ``page_tables`` ((B, n_pages) int32, optional) decodes against a
    paged cache (``init_cache(..., layout=PagedKVLayout(...))``);
    ``kv_backend`` selects the ``paged_flash_decode`` backend.

    Returns (logits (B, V), new_cache).
    """
    if cfg.embed_input:
        assert embed is not None
        x = embed.astype(jnp.dtype(cfg.dtype))
    else:
        x = jnp.take(params["embed"], token[:, None], axis=0)
    x, new_cache = transformer.stack_decode(
        x, params["blocks"], cache, cfg, pos, active=active,
        page_tables=page_tables, kv_backend=kv_backend)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _logits(x, params)[:, 0], new_cache


def prefill_chunk(
    params: Params,
    tokens: jnp.ndarray,
    cache: Params,
    cfg: ModelConfig,
    pos: jnp.ndarray,
    spec: AttentionSpec | None = None,
    live: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, Params, jnp.ndarray]:
    """One chunk of a chunked prefill.  tokens: (B, C) int32; ``cache``
    holds dense per-sequence views already containing ``[0, pos)``.

    Returns (logits (B, C, V) — the caller reads the row of its last
    valid chunk token — the updated cache views with the chunk's K/V
    written at ``[pos, pos + C)``, and the chunk's (num_groups, 4) int32
    sparse-attention work as :func:`prefill`'s).  GQA-attention-only; see
    :func:`transformer.stack_chunk_prefill`.  An ``anchor`` ``spec``
    keeps the chunk on the index-driven sparse path (superblock-aligned
    chunks); ``None``/dense runs dense history attention.  ``live``
    (() int32) is the real-token count of a zero-padded final chunk.
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    x, new_cache, counters = transformer.stack_chunk_prefill(
        x, params["blocks"], cache, cfg, pos, spec=spec, live=live)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _logits(x, params), new_cache, counters


def init_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    layout: "cache_lib.PagedKVLayout | None" = None,
) -> Params:
    """Decode cache.  Default: dense per-slot slabs.  With ``layout`` (a
    :class:`repro.models.cache.PagedKVLayout`): one shared paged KV pool
    addressed through page tables (GQA attention-only archs)."""
    return transformer.stack_cache_init(cfg, batch, max_len, layout=layout)


def param_count(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
