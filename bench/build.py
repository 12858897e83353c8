"""What every family shares in building a cell: the anchor settings, the
weights' seed, the check of the weights' layout, and the serving engine.

The configuration files (``bench/configs/<name>.json``) hold the
published ``config.json`` keys, the cut (if any), the model family that
reads them (``family``, ``bench/families/<family>.py``) and the engine's
settings under ``serving``.  The family makes the weights, on the
device, in one jitted call from the seed, in the type they are served
in.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import numpy as np

BENCH_DIR = Path(__file__).resolve().parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def anchor_of(conf: dict) -> dict:
    a = conf["serving"]["attention"]
    return {k: a[k] for k in ("block_q", "block_kv", "step", "theta", "capacity")}


def weight_key(seed: int):
    """A PRNG key for any whole-number seed, 64 bits included."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


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
                           "benchmark's weights; see the configuration's "
                           "family file")


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
