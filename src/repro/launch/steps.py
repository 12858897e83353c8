"""Step builders shared by train.py / serve.py / dryrun.py.

For every (arch × shape) cell this module provides:
  * ``input_specs(arch, shape, mesh)`` — ShapeDtypeStruct stand-ins for all
    inputs (weak-type-correct, sharded, no device allocation);
  * ``build_step(arch, shape, mesh)`` — the jitted step function with
    in/out shardings and donation, ready to ``.lower().compile()``;
  * ``build_group_probe(...)`` — a single-scan-group version of the same
    step used to correct XLA's once-per-while-body cost accounting
    (DESIGN.md §7).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.config import AnchorConfig
from repro.core.spec import AttentionSpec, spec_from_attn_impl
from repro.configs import SHAPES, ShapeSpec, get_config
from repro.distributed import sharding as sh
from repro.models import model as model_lib
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.moe import MoEParallelism
from repro.optim import adamw

Params = Any

# Production AnchorAttention config: paper hyper-params (θ=12, step=16,
# 128-blocks) with a 4k stripe capacity budget per superblock.
PROD_ANCHOR = AnchorConfig(theta=12.0, step=16, capacity=4096)


@dataclasses.dataclass(frozen=True)
class CellSpec:
    arch: str
    shape: ShapeSpec
    cfg: ModelConfig
    kind: str  # train | prefill | decode
    attn_impl: str  # legacy string, recorded in dry-run JSON
    seq_shard_cache: bool  # long_500k: shard KV cache over `data`

    def attention_spec(self, anchor_cfg: AnchorConfig) -> AttentionSpec:
        """The cell's declarative AttentionSpec (internal translation)."""
        return spec_from_attn_impl(self.attn_impl, anchor_cfg, warn=False)


def make_cell(arch: str, shape_name: str, *, attn_impl: str | None = None,
              cfg_overrides: dict | None = None) -> CellSpec:
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    kind = shape.kind
    if attn_impl is None:
        if kind == "prefill":
            attn_impl = "anchor" if cfg.has_attention else "dense"
        else:
            attn_impl = "dense"
    return CellSpec(
        arch=arch,
        shape=shape,
        cfg=cfg,
        kind=kind,
        attn_impl=attn_impl,
        seq_shard_cache=(shape.name == "long_500k" and cfg.has_attention),
    )


# -------------------------------------------------------------- specs ----


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _batch_axis_spec(mesh: Mesh, b: int):
    """Largest batch PartitionSpec entry that evenly divides ``b``."""
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))
    candidates = [("pod", "data"), ("data",), ("pod",)]
    for axes in candidates:
        if all(a in mesh.axis_names for a in axes):
            prod = 1
            for a in axes:
                prod *= axis_size[a]
            if b % prod == 0:
                return axes if len(axes) > 1 else axes[0]
    return None


def _shape_tree_with(shapes: Params, shardings: Params) -> Params:
    return jax.tree.map(
        lambda s, sh_: _sds(s.shape, s.dtype, sh_), shapes, shardings)


def param_specs(cell: CellSpec, mesh: Mesh) -> Params:
    shapes = jax.eval_shape(
        lambda k: model_lib.init(k, cell.cfg), jax.random.PRNGKey(0))
    return _shape_tree_with(shapes, sh.param_shardings(shapes, mesh))


def optstate_specs(cell: CellSpec, mesh: Mesh, pspecs: Params) -> Params:
    shapes = jax.eval_shape(adamw.init, pspecs)
    zero1 = adamw.AdamWState(
        step=NamedSharding(mesh, P()),
        master=sh.zero1_shardings(shapes.master, mesh),
        m=sh.zero1_shardings(shapes.m, mesh),
        v=sh.zero1_shardings(shapes.v, mesh),
    )
    return _shape_tree_with(shapes, zero1)


def batch_specs(cell: CellSpec, mesh: Mesh) -> dict[str, Any]:
    cfg, shape = cell.cfg, cell.shape
    b, n = shape.global_batch, shape.seq_len
    baxis = _batch_axis_spec(mesh, b)
    spec2 = NamedSharding(mesh, P(baxis, None))
    spec3 = NamedSharding(mesh, P(baxis, None, None))
    out: dict[str, Any] = {"labels": _sds((b, n), jnp.int32, spec2)}
    if cfg.embed_input:
        out["embeds"] = _sds((b, n, cfg.d_model), jnp.bfloat16, spec3)
    else:
        out["tokens"] = _sds((b, n), jnp.int32, spec2)
    return out


def cache_specs(cell: CellSpec, mesh: Mesh) -> Params:
    cfg, shape = cell.cfg, cell.shape
    shapes = jax.eval_shape(
        lambda: model_lib.init_cache(cfg, shape.global_batch, shape.seq_len))
    return _shape_tree_with(
        shapes, sh.cache_shardings(shapes, mesh, seq_shard=cell.seq_shard_cache))


def input_specs(arch: str, shape_name: str, mesh: Mesh) -> dict[str, Any]:
    """All model *data* inputs for a cell (the dry-run contract)."""
    cell = make_cell(arch, shape_name)
    if cell.kind == "train":
        return batch_specs(cell, mesh)
    if cell.kind == "prefill":
        specs = batch_specs(cell, mesh)
        specs.pop("labels")
        return specs
    # decode
    b = cell.shape.global_batch
    baxis = None if cell.seq_shard_cache else _batch_axis_spec(mesh, b)
    tok_sharding = NamedSharding(mesh, P(baxis))
    out = {
        "token": _sds((b,), jnp.int32, tok_sharding),
        "pos": _sds((), jnp.int32, NamedSharding(mesh, P())),
    }
    if cell.cfg.embed_input:
        out["embed"] = _sds((b, 1, cell.cfg.d_model), jnp.bfloat16,
                            NamedSharding(mesh, P(tok_sharding.spec[0], None, None)))
        out.pop("token")
    return out


# -------------------------------------------------------------- steps ----


def make_train_step(
    cell: CellSpec,
    opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig(),
    remat: bool = True,
    remat_policy: str = "nothing",
    moe_parallel: MoEParallelism | None = None,
    sp_spec=None,
    accum_steps: int = 1,
) -> Callable:
    """Train step; ``accum_steps > 1`` scans over microbatches
    (gradient accumulation — activation memory scales with the microbatch
    while the effective batch stays global)."""
    cfg = cell.cfg
    attn_spec = cell.attention_spec(AnchorConfig())

    def loss_and_grad(params, batch):
        def loss(p):
            return model_lib.loss_fn(
                p, batch, cfg, spec=attn_spec, remat=remat,
                remat_policy=remat_policy, moe_parallel=moe_parallel,
                sp_spec=sp_spec)

        return jax.value_and_grad(loss, has_aux=True)(params)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            (loss_val, metrics), grads = loss_and_grad(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                    *x.shape[1:]), batch)

            def body(carry, mb):
                (lv, mets), g = loss_and_grad(params, mb)
                acc_l, acc_g = carry
                return (acc_l + lv,
                        jax.tree.map(lambda a, b: a + b.astype(a.dtype),
                                     acc_g, g)), mets

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss_sum, gsum), metss = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), micro)
            loss_val = loss_sum / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
            metrics = jax.tree.map(lambda x: x.mean(), metss)
        params, opt_state, opt_metrics = adamw.apply_updates(
            params, grads, opt_state, opt_cfg)
        return params, opt_state, {
            "loss": loss_val, **metrics, **opt_metrics}

    return train_step


def make_prefill_step(cell: CellSpec, anchor_cfg: AnchorConfig = PROD_ANCHOR,
                      moe_parallel: MoEParallelism | None = None):
    cfg = cell.cfg
    attn_spec = cell.attention_spec(anchor_cfg)

    def prefill_step(params, batch):
        return model_lib.prefill(
            params,
            batch.get("tokens"),
            cfg,
            embeds=batch.get("embeds"),
            spec=attn_spec,
            moe_parallel=moe_parallel,
        )

    return prefill_step


def make_decode_step(cell: CellSpec):
    cfg = cell.cfg

    def decode_step(params, cache, inputs):
        return model_lib.decode_step(
            params, cache, inputs.get("token"), inputs["pos"], cfg,
            embed=inputs.get("embed"))

    return decode_step


def _moe_parallel(cell: CellSpec, mesh: Mesh) -> MoEParallelism | None:
    """Expert-parallel plan for cells whose arch has routed experts."""
    if not cell.cfg.num_experts or "model" not in mesh.axis_names:
        return None
    if cell.cfg.num_experts % mesh.shape["model"] != 0:
        return None
    if cell.kind == "decode":
        return None  # tiny token counts; fallback path suffices
    baxis = _batch_axis_spec(mesh, cell.shape.global_batch)
    return MoEParallelism(mesh=mesh, ep_axis="model", batch_axis=baxis)


def build_step(
    arch: str,
    shape_name: str,
    mesh: Mesh,
    *,
    attn_impl: str | None = None,
    anchor_cfg: AnchorConfig = PROD_ANCHOR,
    remat: bool = True,
    remat_policy: str = "nothing",
    cfg_overrides: dict | None = None,
    sp: bool = False,
    accum_steps: int = 1,
) -> tuple[Any, tuple]:
    """Returns (jitted_fn, arg_specs) ready to ``.lower(*arg_specs)``."""
    cell = make_cell(arch, shape_name, attn_impl=attn_impl,
                     cfg_overrides=cfg_overrides)
    moe_par = _moe_parallel(cell, mesh)
    sp_spec = None
    if sp and "model" in mesh.axis_names:
        baxis = _batch_axis_spec(mesh, cell.shape.global_batch)
        sp_spec = NamedSharding(mesh, P(baxis, "model", None))
    if cell.kind == "train":
        pspecs = param_specs(cell, mesh)
        ospecs = optstate_specs(cell, mesh, pspecs)
        bspecs = batch_specs(cell, mesh)
        fn = jax.jit(
            make_train_step(cell, remat=remat, remat_policy=remat_policy,
                            moe_parallel=moe_par, sp_spec=sp_spec,
                            accum_steps=accum_steps),
            donate_argnums=(0, 1))
        return fn, (pspecs, ospecs, bspecs)
    if cell.kind == "prefill":
        pspecs = param_specs(cell, mesh)
        bspecs = input_specs(arch, shape_name, mesh)
        fn = jax.jit(make_prefill_step(cell, anchor_cfg=anchor_cfg,
                                       moe_parallel=moe_par))
        return fn, (pspecs, bspecs)
    # decode
    pspecs = param_specs(cell, mesh)
    cspecs = cache_specs(cell, mesh)
    ispecs = input_specs(arch, shape_name, mesh)
    fn = jax.jit(make_decode_step(cell), donate_argnums=(1,))
    return fn, (pspecs, cspecs, ispecs)


# ------------------------------------------------- one-group probe ----


def build_group_probe(
    arch: str,
    shape_name: str,
    mesh: Mesh,
    *,
    attn_impl: str | None = None,
    anchor_cfg: AnchorConfig = PROD_ANCHOR,
    remat: bool = True,
    remat_policy: str = "nothing",
    cfg_overrides: dict | None = None,
    sp: bool = False,
) -> tuple[Any, tuple]:
    """One scan-group worth of the cell's step (same sharding/remat).

    Used to correct ``cost_analysis`` for while-loop bodies: XLA-CPU counts
    the scan body once, so  total ≈ full_report + (G-1) × probe_report_body.
    The probe is the group fwd(+bwd for train) with a dummy cotangent.
    """
    cell = make_cell(arch, shape_name, attn_impl=attn_impl,
                     cfg_overrides=cfg_overrides)
    cfg = cell.cfg
    moe_par = _moe_parallel(cell, mesh)
    sp_spec = None
    if sp and "model" in mesh.axis_names:
        baxis0 = _batch_axis_spec(mesh, cell.shape.global_batch)
        sp_spec = NamedSharding(mesh, P(baxis0, "model", None))
    b, n = cell.shape.global_batch, cell.shape.seq_len
    if cell.kind == "decode":
        b, n = cell.shape.global_batch, 1

    pspecs = param_specs(cell, mesh)
    group_pspecs = jax.tree.map(
        lambda s: _sds(s.shape[1:], s.dtype,
                       NamedSharding(mesh, P(*s.sharding.spec[1:]))
                       if s.sharding is not None else None),
        pspecs["blocks"],
    )
    baxis = (None if (cell.kind == "decode" and cell.seq_shard_cache)
             else _batch_axis_spec(mesh, b))
    x_spec = _sds((b, n, cfg.d_model), jnp.dtype(cfg.dtype),
                  NamedSharding(mesh, P(baxis, None, None)))

    positions = jnp.arange(n)[None].repeat(1, axis=0)  # traced inside

    attn_spec = cell.attention_spec(anchor_cfg)
    if cell.kind == "train":
        def probe(gp, x):
            group_fn = transformer.make_group_fn(
                cfg, jnp.broadcast_to(jnp.arange(n), (x.shape[0], n)),
                spec=attn_spec,
                remat=remat, remat_policy=remat_policy,
                moe_parallel=moe_par, sp_spec=sp_spec)

            def f(gp_):
                y, (aux, _, _) = group_fn(x, gp_)
                return jnp.sum(y.astype(jnp.float32)) + aux

            return jax.grad(f)(gp)

        fn = jax.jit(probe)
        return fn, (group_pspecs, x_spec)

    if cell.kind == "prefill":
        def probe(gp, x):
            group_fn = transformer.make_group_fn(
                cfg, jnp.broadcast_to(jnp.arange(n), (x.shape[0], n)),
                spec=attn_spec,
                remat=False, return_cache=True, moe_parallel=moe_par)
            y, (aux, _, caches) = group_fn(x, gp)
            return y, caches

        fn = jax.jit(probe)
        return fn, (group_pspecs, x_spec)

    # decode probe: one group decode step.
    cspecs = cache_specs(cell, mesh)
    group_cspecs = jax.tree.map(
        lambda s: _sds(s.shape[1:], s.dtype,
                       NamedSharding(mesh, P(*s.sharding.spec[1:]))
                       if s.sharding is not None else None),
        cspecs,
    )
    layout = cfg.group_layout()

    def probe(gp, gc, x):
        from repro.models import attention as attn_lib
        from repro.models import ssm as ssm_lib
        from repro.models.layers import mlp_apply, rmsnorm
        from repro.models import moe as moe_lib

        pos = jnp.asarray(n - 1, jnp.int32)
        new_gc = {}
        for i, (mixer, ffn) in enumerate(layout):
            p = gp[f"l{i}"]
            h = rmsnorm(x, p["norm_mixer"], cfg.norm_eps)
            if mixer == "attn":
                if cfg.use_mla:
                    dec = (attn_lib.mla_decode_absorbed if cfg.mla_absorb
                           else attn_lib.mla_decode)
                else:
                    dec = attn_lib.gqa_decode
                h, nc = dec(h, p["attn"], gc[f"l{i}"], cfg, pos)
            else:
                h, nc = ssm_lib.mamba_decode(h, p["mamba"], gc[f"l{i}"], cfg)
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

    fn = jax.jit(probe, donate_argnums=(1,))
    x_spec1 = _sds((cell.shape.global_batch, 1, cfg.d_model),
                   jnp.dtype(cfg.dtype), x_spec.sharding)
    return fn, (group_pspecs, group_cspecs, x_spec1)
