"""Smoke run of the sparse-prefill server on one TPU.

    python3 chip_smoke.py

Two phases in one process, each fatal on failure:

(a) kernels at internlm2-1.8b attention widths (B=1, Hq=16, Hkv=8,
    D=128, N=8192, bf16): ``anchor_attention`` on ``pallas_tpu`` against
    ``xla`` (equal kept-stripe counts, outputs within ``OUT_RTOL``),
    ``flash_attention`` against the dense f32 oracle of
    ``repro.kernels.ref``, and ``flash_decode`` / ``paged_flash_decode``
    against ``xla``;
(b) internlm2-1.8b at full width (random weights from ``--seed``)
    through ``ServingEngine`` with the paged KV layout, prefix sharing
    and the paper's AnchorAttention blocking on ``pallas_tpu``: first a
    last-position-logits check of ``model.prefill`` on ``pallas_tpu``
    against ``xla``, then four requests served to completion.

Times printed on the way are smoke output, not measurements.  The last
line of standard output is one JSON object naming the device; it is
printed only when every phase passed.  Without a TPU the script exits
non-zero before doing anything else.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

# Relative L2 error allowed between a Pallas output and its reference.
# Both sides round the attention output to bf16 (unit roundoff 2^-9), and
# the f32 accumulation orders differ; 2e-2 is a few bf16 ulp in norm.
OUT_RTOL = 2e-2
# Relative L2 error allowed between the last-position logits of the two
# backends after 24 layers of bf16 activations.
LOGITS_RTOL = 5e-2

PAPER_BLOCKING = dict(block_q=128, block_kv=128, step=16, theta=12.0)
CAPACITY = 4096


def device_info() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def check(name: str, ok: bool, detail: str) -> None:
    print(f"{'PASS' if ok else 'FAIL'} {name}: {detail}", flush=True)
    if not ok:
        raise SystemExit(f"phase check failed: {name}")


def timed(fn, *args):
    """(result, seconds) of ``fn(*args)`` run to completion on device."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def structured_qkv(key, b, hq, hkv, n, d, dtype):
    """Random Q/K/V whose stripe decisions sit far from the threshold.

    Queries lean along one direction ``u``; about 10% of the keys (per KV
    head) lean along ``+u`` and the rest along ``-u``.  Selected stripes
    then score ~2·sqrt(d) above the rest, so both backends take the
    same keep/drop decision for every stripe whatever their rounding.
    """
    import jax.numpy as jnp

    ku, kq, kk, kv, ks = jax.random.split(key, 5)
    u = jax.random.normal(ku, (d,))
    u = u / jnp.linalg.norm(u) * jnp.sqrt(d)
    q = u + 0.5 * jax.random.normal(kq, (b, hq, n, d))
    sign = jnp.where(jax.random.bernoulli(ks, 0.1, (b, hkv, n)), 1.0, -1.0)
    k = sign[..., None] * u + 0.5 * jax.random.normal(kk, (b, hkv, n, d))
    v = jax.random.normal(kv, (b, hkv, n, d))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


def kernel_phase(key, b, hq, hkv, n, d, dtype, anchor, backend) -> None:
    """(a): every main-path kernel against its reference, on one device."""
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    kq, kd, kp = jax.random.split(key, 3)
    q, k, v = structured_qkv(kq, b, hq, hkv, n, d, dtype)

    def anchor_on(be):
        return jax.jit(lambda q, k, v: ops.anchor_attention(
            q, k, v, anchor, return_stats=True, backend=be))

    fn = anchor_on(backend)
    (out, counts), t_first = timed(fn, q, k, v)
    _, t_again = timed(fn, q, k, v)
    with jax.default_matmul_precision("highest"):
        ref_out, ref_counts = anchor_on("xla")(q, k, v)
    same = bool(jnp.array_equal(counts, ref_counts))
    kept = int(counts.sum())
    check("anchor_attention kept counts", same,
          f"{kept} stripes kept on {backend}, {int(ref_counts.sum())} on xla")
    err = rel_err(out, ref_out)
    check("anchor_attention output", err <= OUT_RTOL,
          f"rel err {err:.3e} <= {OUT_RTOL} "
          f"(first call {t_first:.2f}s, again {t_again:.4f}s)")

    flash = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v,
                                                        backend=backend))
    out, t_first = timed(flash, q, k, v)
    g = hq // hkv
    kr, vr = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    with jax.default_matmul_precision("highest"):
        oracle = jax.jit(lambda q, k, v: jax.lax.map(
            lambda h: ref.flash_attention_ref(*h), (q[0], k[0], v[0])))(
                q, kr, vr)[None]
    err = rel_err(out, oracle)
    check("flash_attention vs dense f32 oracle", err <= OUT_RTOL,
          f"rel err {err:.3e} <= {OUT_RTOL} (first call {t_first:.2f}s)")

    # Decode: one query token against a cache filled to 5/8 of its length.
    qd = jax.random.normal(kd, (b, hq, 1, d)).astype(dtype)
    cache_len = jnp.asarray(n * 5 // 8, jnp.int32)
    fd = lambda be: jax.jit(lambda *a: ops.flash_decode(*a, backend=be))  # noqa: E731
    out, t_first = timed(fd(backend), qd, k, v, cache_len)
    with jax.default_matmul_precision("highest"):
        want = fd("xla")(qd, k, v, cache_len)
    err = rel_err(out, want)
    check("flash_decode vs xla", err <= OUT_RTOL,
          f"rel err {err:.3e} <= {OUT_RTOL} (first call {t_first:.2f}s)")

    # Paged decode: the same cache scattered onto shuffled physical pages
    # (page 0 is the null page).
    page = 16
    n_pages = n // page
    perm = 1 + jax.random.permutation(kp, b * n_pages).reshape(b, n_pages)
    perm = perm.astype(jnp.int32)

    def to_pages(x):
        pages = jnp.zeros((1 + b * n_pages, hkv, page, d), x.dtype)
        blocks = x.reshape(b, hkv, n_pages, page, d).transpose(0, 2, 1, 3, 4)
        return pages.at[perm.reshape(-1)].set(blocks.reshape(-1, hkv, page, d))

    kpg, vpg = to_pages(k), to_pages(v)
    pd = lambda be: jax.jit(  # noqa: E731
        lambda *a: ops.paged_flash_decode(*a, backend=be))
    out, t_first = timed(pd(backend), qd, kpg, vpg, perm, cache_len)
    with jax.default_matmul_precision("highest"):
        want = pd("xla")(qd, kpg, vpg, perm, cache_len)
    err = rel_err(out, want)
    check("paged_flash_decode vs xla", err <= OUT_RTOL,
          f"rel err {err:.3e} <= {OUT_RTOL} (first call {t_first:.2f}s)")


def server_phase(cfg, seed, anchor, backend, prompt_len, prefix_len,
                 other_lens, max_new, page_size) -> None:
    """(b): the model prefill check, then the engine serving 4 requests."""
    import numpy as np

    import jax.numpy as jnp

    from repro.core.spec import AttentionSpec
    from repro.models import model as model_lib
    from repro.serving import Request, ServingEngine

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model_lib.init, static_argnums=1)(
            jax.random.PRNGKey(seed), cfg))
    n_params = model_lib.param_count(params)
    print(f"init: {n_params} params ({cfg.name}, {cfg.dtype}) in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    spec = AttentionSpec(algorithm="anchor", backend=backend, anchor=anchor)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (1, prompt_len)).astype(np.int32)

    def prefill_logits(sp):
        return jax.jit(lambda p, t: model_lib.prefill(p, t, cfg, spec=sp)[0])

    fn = prefill_logits(spec)
    got, t_first = timed(fn, params, jnp.asarray(tokens))
    _, t_again = timed(fn, params, jnp.asarray(tokens))
    want = prefill_logits(spec.with_backend("xla"))(params,
                                                    jnp.asarray(tokens))
    err = rel_err(got, want)
    finite = bool(jnp.isfinite(got).all())
    check("prefill logits vs xla", finite and err <= LOGITS_RTOL,
          f"rel err {err:.3e} <= {LOGITS_RTOL}, finite={finite} "
          f"({prompt_len} tokens; first call {t_first:.1f}s, "
          f"again {t_again:.3f}s)")
    del got, want

    # Cache sized for the longest prompt padded to whole superblocks, plus
    # its generated tokens, so no prefill falls back to dense.
    max_len = anchor.prefill_pad_len(prompt_len) + max_new + page_size
    max_len = -(-max_len // page_size) * page_size
    pages_needed = sum(-(-(n + max_new) // page_size)
                       for n in (prompt_len, prompt_len, *other_lens))
    engine = ServingEngine(
        params, cfg, max_batch=4, max_len=max_len, spec=spec,
        cache_layout="paged", page_size=page_size,
        num_pages=pages_needed + 1, prefix_sharing=True)

    shared = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)

    def shared_prompt():
        own = rng.integers(0, cfg.vocab_size, prompt_len - prefix_len)
        return np.concatenate([shared, own.astype(np.int32)])

    first = [Request(0, shared_prompt(), max_new)] + [
        Request(2 + j, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new) for j, n in enumerate(other_lens)]
    second = Request(1, shared_prompt(), max_new)
    t0 = time.perf_counter()
    for req in first:
        engine.submit(req)
    done = engine.step()
    # One engine step after the first wave is admitted, so the second
    # shared-prefix prompt can hit the pages the first one indexed.
    engine.submit(second)
    steps = 1
    while not engine.idle:
        done += engine.step()
        steps += 1
        if steps > 10 * max_new:
            raise SystemExit("engine did not drain")
    wall = time.perf_counter() - t0
    stats = engine.snapshot()
    print(f"engine: {len(done)} requests in {steps} steps, {wall:.1f}s wall "
          "(compilation included)", flush=True)
    print(f"engine.snapshot(): {json.dumps(stats)}", flush=True)
    gen = {r.uid: len(r.generated) for r in done}
    check("every request returns its tokens",
          sorted(gen) == [0, 1, 2, 3] and set(gen.values()) == {max_new},
          f"generated {gen}")
    check("no dense fallback", stats["dense_fallbacks"] == 0,
          f"dense_fallbacks={stats['dense_fallbacks']}")
    check("prefix sharing hit", stats["prefix_hits"] > 0,
          f"prefix_hits={stats['prefix_hits']}, "
          f"shared_pages={stats['shared_pages']}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"no TPU: JAX sees {dev}", file=sys.stderr)
        raise SystemExit(2)
    print(f"device: {json.dumps(dev)}", flush=True)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.config import AnchorConfig
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    anchor = AnchorConfig(**PAPER_BLOCKING, capacity=CAPACITY)
    key = jax.random.PRNGKey(args.seed)

    t0 = time.perf_counter()
    kernel_phase(key, b=1, hq=16, hkv=8, n=8192, d=128, dtype=jnp.bfloat16,
                 anchor=anchor, backend="pallas_tpu")
    print(f"phase (a) kernels: {time.perf_counter() - t0:.1f}s", flush=True)

    t0 = time.perf_counter()
    server_phase(get_config("internlm2_1p8b"), args.seed, anchor,
                 backend="pallas_tpu", prompt_len=8192, prefix_len=2048,
                 other_lens=(5000, 300), max_new=16, page_size=16)
    print(f"phase (b) server: {time.perf_counter() - t0:.1f}s", flush=True)

    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
