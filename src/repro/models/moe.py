"""Mixture-of-Experts FFN with top-k routing and capacity-based dispatch.

Dispatch is *rank-based scatter*, not the Mesh-TF one-hot einsum: the
einsum dispatch costs ``O(tokens × E × capacity × d)`` FLOPs, which at
top-8 / 1M tokens is ~100× the useful expert FLOPs (measured in the first
granite train_4k dry-run — see EXPERIMENTS.md §Perf log).  Here each of the
k routes computes its token's *rank* inside its expert via a cumsum over a
(T, E) one-hot, scatters the token into an ``(E, capacity)`` slot buffer,
runs dense per-expert matmuls (MXU-aligned), and gathers back.  Memory and
FLOPs are both linear in tokens; overflow tokens drop only the overflowed
route (keep their other routes).

Expert weights are stacked on a leading E axis (sharded over ``model``);
shared experts (DeepSeek-V2) are always-on gated MLPs.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, mlp_apply

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEParallelism:
    """Explicit expert-parallel execution plan for the shard_map path.

    ``ep_axis``: mesh axis holding the experts (tokens are *replicated*
    along it in the Megatron activation layout, so dispatch needs no
    all-to-all — each shard serves its local experts and one psum merges
    the contributions).  ``batch_axis``: mesh axis/axes sharding tokens.
    ``mesh=None`` (default) selects the single-device fallback.
    """

    mesh: Any = None
    ep_axis: str | None = None
    batch_axis: Any = None

    def __hash__(self):  # mesh objects hash by identity; fine for jit
        return hash((id(self.mesh), self.ep_axis, self.batch_axis))


def moe_init(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.expert_d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / (d ** 0.5)

    def stack(k, shape_in, shape_out):
        return (
            jax.random.normal(k, (e, shape_in, shape_out), jnp.float32) * scale
        ).astype(dt)

    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "wi": stack(ks[1], d, f),
        "wg": stack(ks[2], d, f),
        "wo": (jax.random.normal(ks[3], (e, f, d), jnp.float32) / (f ** 0.5)).astype(dt),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi": dense_init(k1, d, fs, dt),
            "wg": dense_init(k2, d, fs, dt),
            "wo": dense_init(k3, fs, d, dt),
        }
    return p


def _moe_local(
    xf: jnp.ndarray,
    router: jnp.ndarray,
    wi: jnp.ndarray,
    wg: jnp.ndarray,
    wo: jnp.ndarray,
    *,
    num_experts: int,
    top_k: int,
    capacity: int,
    e_offset,
    e_local: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rank-based scatter dispatch over the experts held locally.

    xf: (T, d) local tokens; wi/wg/wo: (E_local, ·, ·) local experts.
    Routing runs over the FULL expert space (router replicated); only
    routes landing in [e_offset, e_offset + e_local) are computed here.
    Returns (partial_output (T, d) f32, aux_loss).
    """
    t, d = xf.shape
    logits = xf.astype(jnp.float32) @ router  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, topk_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    y = jnp.zeros((t, d), jnp.float32)
    for route in range(top_k):
        eidx = topk_idx[:, route] - e_offset  # local expert id
        in_local = (eidx >= 0) & (eidx < e_local)
        eidx_c = jnp.where(in_local, eidx, 0)
        onehot = jax.nn.one_hot(eidx_c, e_local, dtype=jnp.int32) * in_local[:, None]
        rank = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - 1, eidx_c[:, None], axis=1)[:, 0]
        valid = in_local & (rank < capacity)
        slot = eidx_c * capacity + jnp.clip(rank, 0, capacity - 1)
        xe = jnp.zeros((e_local * capacity, d), xf.dtype)
        xe = xe.at[slot].add(jnp.where(valid[:, None], xf, 0))
        xe = xe.reshape(e_local, capacity, d)
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
        hidden = gate * jnp.einsum("ecd,edf->ecf", xe, wi)
        ye = jnp.einsum("ecf,efd->ecd", hidden, wo).reshape(e_local * capacity, d)
        contrib = ye[slot].astype(jnp.float32)
        y = y + contrib * (gate_vals[:, route] * valid)[:, None]

    # Load-balancing aux loss (Switch-style) over the full expert space.
    density = jnp.mean(
        jax.nn.one_hot(topk_idx, num_experts, dtype=jnp.float32).sum(1), axis=0)
    router_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * router_prob) * num_experts
    return y, aux


def moe_apply(
    x: jnp.ndarray,
    p: Params,
    cfg: ModelConfig,
    capacity_factor: float = 1.25,
    parallel: MoEParallelism | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Routed MoE.  x: (B, N, d).  Returns (output, aux_loss).

    Capacity per expert per route: ``ceil(local_tokens/E · cf)``.  With
    ``parallel.mesh`` set, experts run expert-parallel under shard_map
    (DESIGN.md §6): dispatch is shard-local, one psum over ``ep_axis``
    merges expert contributions.
    """
    b, n, d = x.shape
    e, k = cfg.num_experts, cfg.experts_top_k
    tokens = b * n

    if parallel is None or parallel.mesh is None:
        capacity = int(max(1, round(tokens / e * capacity_factor)))
        capacity = min(capacity, tokens)
        y, aux = _moe_local(
            x.reshape(tokens, d), p["router"], p["wi"], p["wg"], p["wo"],
            num_experts=e, top_k=k, capacity=capacity, e_offset=0, e_local=e)
        out = y.reshape(b, n, d).astype(x.dtype)
    else:
        mesh, ep, ba = parallel.mesh, parallel.ep_axis, parallel.batch_axis
        ep_size = mesh.shape[ep]
        assert e % ep_size == 0, (e, ep_size)
        e_local = e // ep_size
        ba_size = 1
        if ba is not None:
            for a in (ba if isinstance(ba, tuple) else (ba,)):
                ba_size *= mesh.shape[a]
        t_local = tokens // ba_size
        capacity = int(max(1, round(t_local / e * capacity_factor)))
        capacity = min(capacity, t_local)
        all_axes = tuple(mesh.axis_names)

        def body(xl, router, wi, wg, wo):
            bl = xl.shape[0]
            xf = xl.reshape(bl * xl.shape[1], d)
            off = jax.lax.axis_index(ep) * e_local
            y, aux = _moe_local(
                xf, router, wi, wg, wo,
                num_experts=e, top_k=k, capacity=capacity,
                e_offset=off, e_local=e_local)
            y = jax.lax.psum(y, ep)  # merge expert contributions
            aux = jax.lax.pmean(aux, all_axes)  # replicated scalar
            return y.reshape(xl.shape).astype(xl.dtype), aux

        out, aux = shard_map(
            body,
            mesh=mesh,
            in_specs=(P(ba, None, None), P(None, None),
                      P(ep, None, None), P(ep, None, None), P(ep, None, None)),
            out_specs=(P(ba, None, None), P()),
            check_vma=False,
        )(x, p["router"], p["wi"], p["wg"], p["wo"])

    if cfg.num_shared_experts:
        out = out + mlp_apply(x, p["shared"], "silu")
    return out.astype(x.dtype), aux
