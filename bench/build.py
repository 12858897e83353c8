"""Builds what a cell serves: the model's shapes from its configuration
file, random weights from the seed, and the serving engine.

The configuration files (``bench/configs/<name>.json``) hold the
published ``config.json`` keys, the cut (if any), and the engine's
settings under ``serving``.  Weights are made here, on the device, in
one jitted call from the seed, in the type they are served in.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH_DIR = Path(__file__).resolve().parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def arch_of(conf: dict) -> dict:
    """The shapes the benchmark counts with, from the published keys."""
    heads = conf["num_attention_heads"]
    return {
        "layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "heads": heads,
        "kv_heads": conf["num_key_value_heads"],
        "head_dim": conf.get("head_dim") or conf["hidden_size"] // heads,
        "d_ff": conf["intermediate_size"],
        "vocab": conf["vocab_size"],
        "rope_theta": float(conf["rope_theta"]),
        "norm_eps": float(conf["rms_norm_eps"]),
        "tied": bool(conf.get("tie_word_embeddings", False)),
    }


def anchor_of(conf: dict) -> dict:
    a = conf["serving"]["attention"]
    return {k: a[k] for k in ("block_q", "block_kv", "step", "theta", "capacity")}


def model_config(conf: dict):
    from repro.models.config import ModelConfig

    a = arch_of(conf)
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU decoders are served by this harness")
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=a["layers"],
        d_model=a["d_model"], num_heads=a["heads"], num_kv_heads=a["kv_heads"],
        head_dim=a["head_dim"], d_ff=a["d_ff"], vocab_size=a["vocab"],
        rope_theta=a["rope_theta"], norm_eps=a["norm_eps"],
        tie_embeddings=a["tied"], dtype=conf.get("torch_dtype", "bfloat16"))


def weight_key(seed: int):
    """A PRNG key for any whole-number seed, 64 bits included."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_weights(arch: dict, dtype: str, seed: int) -> dict:
    """Random weights in the serving engine's parameter layout: decoder
    layers stacked on a leading axis, projections ``(in, out)``, norm
    scales in f32.  Scales follow the usual fan-in rule; norm scales
    scatter about 1 so that a reference that skipped one would show."""
    dt = jnp.dtype(dtype)
    g, d, hq, hkv, hd, f, v = (arch["layers"], arch["d_model"], arch["heads"],
                               arch["kv_heads"], arch["head_dim"],
                               arch["d_ff"], arch["vocab"])

    @jax.jit
    def build(key):
        ks = iter(jax.random.split(key, 16))

        def w(shape, scale):
            x = jax.random.normal(next(ks), shape, jnp.float32) * scale
            return x.astype(dt)

        def norm(shape):
            return 1.0 + 0.05 * jax.random.normal(next(ks), shape, jnp.float32)

        layer = {
            "norm_mixer": {"scale": norm((g, d))},
            "attn": {
                "wq": w((g, d, hq * hd), d ** -0.5),
                "wk": w((g, d, hkv * hd), d ** -0.5),
                "wv": w((g, d, hkv * hd), d ** -0.5),
                "wo": w((g, hq * hd, d), (hq * hd) ** -0.5),
            },
            "norm_ffn": {"scale": norm((g, d))},
            "mlp": {
                "wi": w((g, d, f), d ** -0.5),
                "wg": w((g, d, f), d ** -0.5),
                "wo": w((g, f, d), f ** -0.5),
            },
        }
        p = {"embed": w((v, d), 0.02), "blocks": {"l0": layer},
             "final_norm": {"scale": norm((d,))}}
        if not arch["tied"]:
            p["lm_head"] = w((v, d), 0.02)
        return p

    return build(weight_key(seed))


def check_layout(params: dict, mcfg) -> None:
    """Fail loudly if the program's parameter layout has moved away from
    the one these weights are made in."""
    from repro.models import model as model_lib

    want = jax.eval_shape(lambda: model_lib.init(jax.random.key(0), mcfg))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the engine's parameter layout differs from the "
                           "benchmark's weights; see bench/build.py")


def make_engine(params: dict, mcfg, conf: dict, backend: str | None = None):
    """The serving engine with the cell's settings (``serving`` in the
    configuration file); ``backend`` overrides the kernel backend for a
    dry run on the CPU."""
    from repro.core.config import AnchorConfig
    from repro.core.spec import AttentionSpec
    from repro.serving.engine import ServingEngine

    s = conf["serving"]
    a = s["attention"]
    spec = AttentionSpec(
        algorithm=a["algorithm"], backend=backend or a["backend"],
        anchor=AnchorConfig(block_q=a["block_q"], block_kv=a["block_kv"],
                            step=a["step"], theta=float(a["theta"]),
                            capacity=a["capacity"]))
    return ServingEngine(
        params, mcfg, max_batch=s["max_batch"], max_len=s["max_len"],
        spec=spec, cache_layout=s["cache_layout"], page_size=s["page_size"],
        num_pages=s.get("num_pages"), prefix_sharing=s["prefix_sharing"],
        chunk_tokens=s.get("chunk_tokens"))


def request(uid: int, prompt: np.ndarray, max_new: int):
    from repro.serving.engine import Request

    return Request(uid=uid, prompt=prompt, max_new_tokens=max_new)
