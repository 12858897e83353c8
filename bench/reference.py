"""Plain f32 reference of the served path, with AnchorAttention's
semantics, in blocks so that it fits at 32k tokens.

It imports nothing of the program.  It follows the configuration: a
SwiGLU decoder with RMSNorm and half-split RoPE; prompt rows attend as
AnchorAttention specifies (paper Algorithms 1-3, as
``kernels/ref.py::anchor_attention_ref`` writes them for one head):

* row ``i`` of superblock ``k`` (``block_q·step`` rows) keeps its anchor
  region — the sink block ``[0, block_kv)`` and the local window
  ``[max(1, k·step·r)·block_kv, i]`` — causally;
* ``m`` is a row's largest anchor score, ``m̄`` its mean over the valid
  rows of a ``block_q``-row block, ``q̄`` the block's mean query; key
  ``j`` of the candidates ``[block_kv, window start)`` is a stripe of the
  superblock if ``m̄ - q̄·k_j/√d <= θ`` for any of its blocks;
* each query head keeps at most ``capacity`` stripes per superblock, the
  first ones by position;
* a row attends, by one softmax, to its anchor region and the stripes.

Rows past the prompt (the served tokens fed back) attend densely to
every earlier position, as decoding does.  Everything is f32 with
matmuls at ``highest`` precision.  With ``control=True`` every matmul
operand is first rounded to float8 (e4m3, one scale per tensor), the
precision below the configuration's bf16: the control that the
comparison has to reject.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NEG = -1e30
ROWS = 256  # query rows per attention block
FFN_ROWS = 4096  # token rows per MLP block


def _fp8(x):
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(control: bool, eq: str, a, b):
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a.astype(F32), b.astype(F32))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _anchor_prompt(qh, kh, vh, n, an, control):
    """AnchorAttention over the prompt rows ``[0, n)``.  qh: (Hkv, G, T,
    D); kh, vh: (Hkv, T, D).  Rows at or past ``n`` come out as zeros."""
    hkv, g, t, d = qh.shape
    bq, bkv, step, cap = an["block_q"], an["block_kv"], an["step"], an["capacity"]
    sb = bq * step
    r = bq // bkv
    scale = d ** -0.5
    cols = jnp.arange(t)
    mm = functools.partial(_mm, control)
    rb = math.gcd(ROWS, sb)

    def superblock(kb):
        r0 = kb * sb
        w0 = jnp.maximum(1, kb * step * r) * bkv
        w1 = (kb + 1) * sb
        qs = jax.lax.dynamic_slice_in_dim(qh, r0, sb, axis=2)
        kw = jax.lax.dynamic_slice_in_dim(kh, w0, sb, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(vh, w0, sb, axis=1)
        cw = w0 + jnp.arange(sb)

        def anchor_scores(q, rows):
            ok0 = (cols[:bkv][None] <= rows[:, None]) & (cols[:bkv][None] < n)
            okw = ((cw[None] <= rows[:, None]) & (cw[None] < w1)
                   & (cw[None] < n))
            ok0 &= (rows < n)[:, None]
            okw &= (rows < n)[:, None]
            s0 = jnp.where(ok0, mm("hgqd,hkd->hgqk", q, kh[:, :bkv]) * scale, NEG)
            sw = jnp.where(okw, mm("hgqd,hkd->hgqk", q, kw) * scale, NEG)
            return s0, sw

        # Identification: row anchors, pooled per block, then stripes.
        rows = r0 + jnp.arange(sb)
        s0, sw = anchor_scores(qs, rows)
        m = jnp.maximum(s0.max(-1), sw.max(-1))  # (Hkv, G, sb)
        rv = (rows < n).reshape(step, bq)
        cnt = rv.sum(-1)
        m_bar = jnp.where(rv, m.reshape(hkv, g, step, bq), 0.0).sum(-1)
        m_bar = jnp.where(cnt == 0, jnp.inf, m_bar / jnp.maximum(cnt, 1))
        q_bar = jnp.where(rv[..., None], qs.reshape(hkv, g, step, bq, d),
                          0.0).sum(-2) / jnp.maximum(cnt, 1)[:, None]
        score = mm("hgbd,hkd->hgbk", q_bar, kh) * scale
        hit = (m_bar[..., None] - score <= an["theta"]).any(axis=2)
        hit &= ((cols >= bkv) & (cols < w0) & (cols < n))[None, None]
        idx = jax.vmap(jax.vmap(
            lambda h: jnp.nonzero(h, size=cap, fill_value=0)[0]))(hit)
        kept = jnp.arange(cap) < jnp.minimum(hit.sum(-1), cap)[..., None]
        ks = jax.vmap(lambda kk, ii: kk[ii])(kh, idx)  # (Hkv, G, cap, D)
        vs = jax.vmap(lambda vv, ii: vv[ii])(vh, idx)

        def attend(c):
            q = jax.lax.dynamic_slice_in_dim(qs, c * rb, rb, axis=2)
            rows_c = r0 + c * rb + jnp.arange(rb)
            s0c, swc = anchor_scores(q, rows_c)
            ss = mm("hgqd,hgcd->hgqc", q, ks) * scale
            ss = jnp.where(kept[:, :, None, :] & (rows_c < n)[:, None], ss, NEG)
            s = jnp.concatenate([s0c, swc, ss], -1)
            mx = s.max(-1, keepdims=True)
            p = jnp.where(s > NEG / 2, jnp.exp(s - mx), 0.0)
            p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
            p0, pw, ps = p[..., :bkv], p[..., bkv:bkv + sb], p[..., bkv + sb:]
            return (mm("hgqk,hkd->hgqd", p0, vh[:, :bkv])
                    + mm("hgqk,hkd->hgqd", pw, vw)
                    + mm("hgqc,hgcd->hgqd", ps, vs))

        out = jax.lax.map(attend, jnp.arange(sb // rb))  # (c, Hkv, G, R, D)
        return jnp.moveaxis(out, 0, 2).reshape(hkv, g, sb, d)

    out = jax.lax.map(superblock, jnp.arange(t // sb))  # (T/sb, Hkv, G, sb, D)
    return jnp.moveaxis(out, 0, 2).reshape(hkv, g, t, d)


def _dense_rows(qh, kh, vh, n, rows, control):
    """Dense causal attention of the ``rows`` rows starting at ``n``."""
    hkv, g, t, d = qh.shape
    q = jax.lax.dynamic_slice_in_dim(qh, n, rows, axis=2)
    r = n + jnp.arange(rows)
    s = _mm(control, "hgqd,hkd->hgqk", q, kh) * d ** -0.5
    s = jnp.where(jnp.arange(t)[None] <= r[:, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return _mm(control, "hgqk,hkd->hgqd", p, vh)


@functools.partial(jax.jit, static_argnames=("arch", "anchor", "rows", "control"))
def _layer(x, blocks, i, n, *, arch, anchor, rows, control):
    a, an = dict(arch), dict(anchor)
    lw = jax.tree.map(lambda w: w[i].astype(F32), blocks)
    t = x.shape[0]
    hq, hkv, d = a["heads"], a["kv_heads"], a["head_dim"]
    g = hq // hkv
    mm = functools.partial(_mm, control)
    pos = jnp.arange(t)
    h = _rms(x, lw["norm_mixer"]["scale"], a["norm_eps"])
    q = _rope(mm("td,de->te", h, lw["attn"]["wq"]).reshape(t, hq, d), pos,
              a["rope_theta"])
    k = _rope(mm("td,de->te", h, lw["attn"]["wk"]).reshape(t, hkv, d), pos,
              a["rope_theta"])
    v = mm("td,de->te", h, lw["attn"]["wv"]).reshape(t, hkv, d)
    qh = q.reshape(t, hkv, g, d).transpose(1, 2, 0, 3)
    kh, vh = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    o = _anchor_prompt(qh, kh, vh, n, an, control)
    o = jax.lax.dynamic_update_slice_in_dim(
        o, _dense_rows(qh, kh, vh, n, rows, control), n, axis=2)
    o = o.transpose(2, 0, 1, 3).reshape(t, hq * d)
    x = x + mm("te,ed->td", o, lw["attn"]["wo"])

    def ffn(xc):
        hc = _rms(xc, lw["norm_ffn"]["scale"], a["norm_eps"])
        gate = jax.nn.silu(mm("td,df->tf", hc, lw["mlp"]["wg"]))
        up = mm("td,df->tf", hc, lw["mlp"]["wi"])
        return xc + mm("tf,fd->td", gate * up, lw["mlp"]["wo"])

    return jax.lax.map(ffn, x.reshape(-1, math.gcd(FFN_ROWS, t), x.shape[-1])
                       ).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("arch", "rows", "control"))
def _head(x, final, head, n, *, arch, rows, control):
    xs = jax.lax.dynamic_slice_in_dim(x, n - 1, rows + 1, axis=0)
    h = _rms(xs, final.astype(F32), dict(arch)["norm_eps"])
    return _mm(control, "td,vd->tv", h, head)


def padded_rows(n_total: int, anchor: dict) -> int:
    """Rows the reference runs: a whole number of superblocks, at least
    two, with room for the window slice of superblock 0."""
    sb = anchor["block_q"] * anchor["step"]
    return max(2 * sb, -(-n_total // sb) * sb)


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
