"""AnchorAttention's semantics in plain f32, in blocks so that they fit at
32k tokens: the attention part of every family's reference
(``bench/families/<family>.py``), which builds the rest of its model's
forward around these functions.

It imports nothing of the program.  Prompt rows attend as
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
every earlier position, as decoding does.  Everything is f32, and the
families run it with matmuls at ``highest`` precision.  With
``control=True`` every matmul operand is first rounded to float8 (e4m3,
one scale per tensor), the precision below the configurations' bf16:
the control that the comparison has to reject.  Keys and values may
differ in width; ``d`` below is the query and key width.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30
ROWS = 256  # query rows per attention block


def _fp8(x):
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def matmul(control: bool, eq: str, a, b):
    """``einsum(eq, a, b)`` in f32, its operands first rounded to float8
    for the control."""
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a.astype(F32), b.astype(F32))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def anchor_prompt(qh, kh, vh, n, an, control):
    """AnchorAttention over the prompt rows ``[0, n)``.  qh: (Hkv, G, T,
    D); kh: (Hkv, T, D); vh: (Hkv, T, Dv).  Rows at or past ``n`` come
    out as zeros."""
    hkv, g, t, d = qh.shape
    dv = vh.shape[-1]
    bq, bkv, step, cap = an["block_q"], an["block_kv"], an["step"], an["capacity"]
    sb = bq * step
    r = bq // bkv
    scale = d ** -0.5
    cols = jnp.arange(t)
    mm = functools.partial(matmul, control)
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

        out = jax.lax.map(attend, jnp.arange(sb // rb))  # (c, Hkv, G, R, Dv)
        return jnp.moveaxis(out, 0, 2).reshape(hkv, g, sb, dv)

    out = jax.lax.map(superblock, jnp.arange(t // sb))  # (T/sb, Hkv, G, sb, Dv)
    return jnp.moveaxis(out, 0, 2).reshape(hkv, g, t, dv)


def dense_rows(qh, kh, vh, n, rows, control):
    """Dense causal attention of the ``rows`` rows starting at ``n``."""
    hkv, g, t, d = qh.shape
    q = jax.lax.dynamic_slice_in_dim(qh, n, rows, axis=2)
    r = n + jnp.arange(rows)
    s = matmul(control, "hgqd,hkd->hgqk", q, kh) * d ** -0.5
    s = jnp.where(jnp.arange(t)[None] <= r[:, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return matmul(control, "hgqk,hkd->hgqd", p, vh)


def padded_rows(n_total: int, anchor: dict) -> int:
    """Rows a reference runs: a whole number of superblocks, at least
    two, with room for the window slice of superblock 0."""
    sb = anchor["block_q"] * anchor["step"]
    return max(2 * sb, -(-n_total // sb) * sb)
