"""Family ``dense_gqa``: a decoder of SwiGLU MLPs and grouped-query
attention with half-split RoPE and RMSNorm (internlm2, Yi, Llama).  What
a family file defines, and how a configuration names it, is in
``bench/harness.py::load_family``.

Counts follow the work the algorithm needs at the unpadded lengths, not
what an implementation happens to do (``bench/counts.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.build import weight_key
from bench.reference import (F32, anchor_prompt, dense_rows, matmul,
                             padded_rows, rms_norm)

FFN_ROWS = 4096  # token rows per MLP block of the reference
BLOCK_C = 128  # the sparse sweep's widest tile


def arch(conf: dict) -> dict:
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


def model_config(conf: dict):
    from repro.models.config import ModelConfig

    a = arch(conf)
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU decoders are served by this harness")
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=a["layers"],
        d_model=a["d_model"], num_heads=a["heads"], num_kv_heads=a["kv_heads"],
        head_dim=a["head_dim"], d_ff=a["d_ff"], vocab_size=a["vocab"],
        rope_theta=a["rope_theta"], norm_eps=a["norm_eps"],
        tie_embeddings=a["tied"], dtype=conf.get("torch_dtype", "bfloat16"))


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


# ---- the plain reference ----

def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("arch", "anchor", "rows", "control"))
def _layer(x, blocks, i, n, *, arch, anchor, rows, control):
    a, an = dict(arch), dict(anchor)
    lw = jax.tree.map(lambda w: w[i].astype(F32), blocks)
    t = x.shape[0]
    hq, hkv, d = a["heads"], a["kv_heads"], a["head_dim"]
    g = hq // hkv
    mm = functools.partial(matmul, control)
    pos = jnp.arange(t)
    h = rms_norm(x, lw["norm_mixer"]["scale"], a["norm_eps"])
    q = _rope(mm("td,de->te", h, lw["attn"]["wq"]).reshape(t, hq, d), pos,
              a["rope_theta"])
    k = _rope(mm("td,de->te", h, lw["attn"]["wk"]).reshape(t, hkv, d), pos,
              a["rope_theta"])
    v = mm("td,de->te", h, lw["attn"]["wv"]).reshape(t, hkv, d)
    qh = q.reshape(t, hkv, g, d).transpose(1, 2, 0, 3)
    kh, vh = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    o = anchor_prompt(qh, kh, vh, n, an, control)
    o = jax.lax.dynamic_update_slice_in_dim(
        o, dense_rows(qh, kh, vh, n, rows, control), n, axis=2)
    o = o.transpose(2, 0, 1, 3).reshape(t, hq * d)
    x = x + mm("te,ed->td", o, lw["attn"]["wo"])

    def ffn(xc):
        hc = rms_norm(xc, lw["norm_ffn"]["scale"], a["norm_eps"])
        gate = jax.nn.silu(mm("td,df->tf", hc, lw["mlp"]["wg"]))
        up = mm("td,df->tf", hc, lw["mlp"]["wi"])
        return xc + mm("tf,fd->td", gate * up, lw["mlp"]["wo"])

    return jax.lax.map(ffn, x.reshape(-1, math.gcd(FFN_ROWS, t), x.shape[-1])
                       ).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("arch", "rows", "control"))
def _head(x, final, head, n, *, arch, rows, control):
    xs = jax.lax.dynamic_slice_in_dim(x, n - 1, rows + 1, axis=0)
    h = rms_norm(xs, final.astype(F32), dict(arch)["norm_eps"])
    return matmul(control, "td,vd->tv", h, head)


def reference_logits(params: dict, arch: dict, anchor: dict,
                     prompt: np.ndarray, served: np.ndarray,
                     control: bool = False) -> np.ndarray:
    """Logits (len(served), vocab) that predict each served token: at the
    prompt's last position, then at each served token fed back but the
    last."""
    n, rows = len(prompt), len(served) - 1
    toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    t = padded_rows(len(toks), anchor)
    toks = np.pad(toks, (0, t - len(toks)))
    akey = tuple(sorted(arch.items()))
    nkey = tuple(sorted(anchor.items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(toks)].astype(F32)
        n_j = jnp.asarray(n, jnp.int32)
        for i in range(arch["layers"]):
            x = _layer(x, params["blocks"]["l0"], jnp.asarray(i), n_j,
                       arch=akey, anchor=nkey, rows=rows, control=control)
        head = params.get("lm_head", params["embed"])
        logits = _head(x, params["final_norm"]["scale"], head, n_j,
                       arch=akey, rows=rows, control=control)
    return np.asarray(logits)


# ---- work counts ----

def sparse_attention_work(n: int, arch: dict, anchor: dict) -> tuple[float, float]:
    """(flops, bytes) of one layer's sparse attention over an n-token
    prompt: QK^T and PV over the anchor region for every query head;
    Q read and O written once, anchor K and V tiles read once per KV
    head and superblock, all in bf16."""
    hq, hkv, d = arch["heads"], arch["kv_heads"], arch["head_dim"]
    sb = anchor["block_q"] * anchor["step"]
    pairs = counts.anchor_pairs(n, anchor["block_kv"], sb)
    flops = 4.0 * pairs * d * hq
    kv = counts.anchor_kv_tokens(n, anchor["block_kv"], sb)
    nbytes = counts.BF16 * (2 * n * hq * d + 2 * kv * hkv * d)
    return flops, nbytes


def decode_attention_work(ctx: int, arch: dict) -> tuple[float, float]:
    """(flops, bytes) of one layer's decode attention for one sequence
    with ``ctx`` cached positions: every query head against every cached
    key; the cache's K and V read once per KV head, q read, o written."""
    hq, hkv, d = arch["heads"], arch["kv_heads"], arch["head_dim"]
    flops = 4.0 * ctx * d * hq
    nbytes = counts.BF16 * (2 * ctx * hkv * d + 2 * hq * d)
    return flops, nbytes


def matmul_params(arch: dict) -> int:
    """Weights that every token multiplies through, per token, in the
    decoder layers (projections and the gated MLP)."""
    d, hq, hkv, hd, f = (arch["d_model"], arch["heads"], arch["kv_heads"],
                         arch["head_dim"], arch["d_ff"])
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return arch["layers"] * per_layer


def prefill_flops(n: int, arch: dict, anchor: dict) -> float:
    """Model operations of a prefill of n tokens: the layers for every
    token, AnchorAttention's anchor region, and the LM head at the one
    position whose logits are served."""
    attn, _ = sparse_attention_work(n, arch, anchor)
    return (2.0 * matmul_params(arch) * n + arch["layers"] * attn
            + 2.0 * arch["d_model"] * arch["vocab"])


def decode_flops(ctx: int, arch: dict) -> float:
    """Model operations of one decode token with ``ctx`` cached
    positions (its own included)."""
    attn, _ = decode_attention_work(ctx, arch)
    return (2.0 * matmul_params(arch) + arch["layers"] * attn
            + 2.0 * arch["d_model"] * arch["vocab"])


def wave_work(attrs: dict, arch: dict, anchor: dict) -> tuple[float, float]:
    """(flops, bytes) of the sparse attention one prefill wave kept,
    from the attributes of its ``engine.prefill`` span: the anchor region
    of every prompt and layer, plus the kept stripes, each attended by
    the rows of its superblock for its query head (operations from
    ``stripes_kept``), plus the K and V tiles holding them, read once per
    KV head and superblock (bytes from ``stripe_tiles_kept``).  The sweep
    loads tiles of gcd(padded length, 128) rows."""
    d, layers = arch["head_dim"], arch["layers"]
    sb = anchor["block_q"] * anchor["step"]
    tile = math.gcd(attrs["n_pad"], BLOCK_C)
    flops = 4.0 * d * sb * attrs["stripes_kept"]
    nbytes = 2 * counts.BF16 * tile * d * attrs["stripe_tiles_kept"]
    for n in attrs["tokens"]:
        f, b = sparse_attention_work(n, arch, anchor)
        flops += layers * f
        nbytes += layers * b
    return flops, nbytes
