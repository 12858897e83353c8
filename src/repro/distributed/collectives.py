"""Distributed attention collectives.

``flash_decode_sharded``: long-context decode with the KV cache sequence-
sharded across the ``data`` axis (the long_500k shape: batch=1, 524288-token
cache).  Each device computes a partial online-softmax over its local cache
shard; the partials combine with a cheap psum of rescaled (l, acc) — the
flash-decoding pattern expressed in ``shard_map`` + ``jax.lax`` collectives
(no NCCL-style emulation; DESIGN.md §6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

_NEG_INF = -1e30


def _local_partial(q, k_shard, v_shard, valid):
    """Partial (m, l, acc) over a local KV shard, GQA-group-native.

    q: (B, Hq, 1, D); k/v_shard: (B, Hkv, S_loc, D); valid: (B, 1, 1, 1,
    S_loc).  The einsums are group-batched at Hkv width — the KV shard is
    never replicated to Hq.  Shapes out: (B, Hkv, G, 1[, D]).
    """
    b, hq, _, d = q.shape
    hkv = k_shard.shape[1]
    qg = q.reshape(b, hkv, hq // hkv, 1, d).astype(jnp.float32)
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, k_shard.astype(jnp.float32)) / (d ** 0.5)
    s = jnp.where(valid, s, _NEG_INF)
    m = jnp.max(s, axis=-1)  # (B, Hkv, G, 1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgqk,bhkd->bhgqd", p, v_shard.astype(jnp.float32))
    return m, l, acc


def flash_decode_sharded(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,
    mesh: Mesh,
    seq_axis: str = "data",
) -> jnp.ndarray:
    """Decode attention with a sequence-sharded cache.

    q: (B, Hq, 1, D) replicated along ``seq_axis``;
    k_cache/v_cache: (B, Hkv, S, D) sharded along S over ``seq_axis``;
    cache_len: () int32 — global number of valid positions.

    Combine: m* = pmax(m); l* = psum(l·e^{m−m*}); acc* = psum(acc·e^{m−m*}).
    Wire cost per step: 2·B·H·(1 + D) floats — negligible vs. the cache.
    """
    hkv = k_cache.shape[1]
    n_shards = mesh.shape[seq_axis]
    s_global = k_cache.shape[2]
    s_local = s_global // n_shards

    def body(q, k_shard, v_shard):
        idx = jax.lax.axis_index(seq_axis)
        pos = idx * s_local + jnp.arange(s_local)
        valid = (pos < cache_len)[None, None, None, None, :]
        m, l, acc = _local_partial(q, k_shard, v_shard, valid)
        m_star = jax.lax.pmax(m, seq_axis)
        scale = jnp.exp(m - m_star)
        l_star = jax.lax.psum(l * scale, seq_axis)
        acc_star = jax.lax.psum(acc * scale[..., None], seq_axis)
        out = acc_star / jnp.maximum(l_star, 1e-30)[..., None]
        return out.reshape(q.shape).astype(q.dtype)

    # Head-shard over `model` only when whole KV GROUPS land on each
    # shard (model | Hkv): the in-body GQA fold pairs local query head
    # h with local KV head h // G, which is only the right pairing for
    # contiguous group-aligned shards.  K/V now stay at Hkv width (no
    # repeat-to-Hq), so a model axis wider than Hkv replicates heads
    # instead — the sequence axis still carries the sharding that
    # matters here (the cache).
    shard_heads = ("model" in mesh.axis_names
                   and hkv % mesh.shape["model"] == 0)
    spec_q = P(None, "model", None, None) if shard_heads else P()
    spec_kv = P(None, "model", seq_axis, None) if shard_heads else P(
        None, None, seq_axis, None)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv),
        out_specs=spec_q,
        check_vma=False,
    )
    return fn(q, k_cache, v_cache)


def ring_allgather_kv(k: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Ring all-gather of KV shards via collective_permute — the building
    block for ring-attention prefill over the sequence axis (context
    parallelism lever recorded in §Perf)."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    chunks = [k]
    cur = k
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        chunks.append(cur)
    return jnp.concatenate(chunks, axis=0)
