"""Serving driver: batched requests through the continuous-batching engine.

Weights are random from ``--seed``; no checkpoint is read.  Prefill runs
AnchorAttention with the paper's blocking (128/128/16, θ=12) and a
``--capacity`` stripe budget, on the backend ``repro.kernels.dispatch``
picks for the device (``pallas_tpu`` on a TPU) unless ``--backend`` is
given.  With its final stats it prints the engine's spans
(``repro.serving.tracing``): per span name the count, total and longest
seconds.

Full width on one TPU (internlm2-1.8b):
    PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1p8b \
        --requests 4 --prompt-len 8192 --max-new 16 --paged

CPU example (reduced config; prompts pad to two 2048-token superblocks):
    PYTHONPATH=src python -m repro.launch.serve --arch yi_9b --reduced \
        --backend xla --requests 6 --prompt-len 64 --max-new 8

Paged KV-cache serving (shared pool + prefix sharing + chunked prefill):
    PYTHONPATH=src python -m repro.launch.serve --arch yi_9b --reduced \
        --backend xla --requests 6 --prompt-len 4000 --max-new 8 \
        --paged --page-size 16 --shared-prefix 2048 --chunk-tokens 2048
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.core.config import PAPER_CONFIG
from repro.core.spec import AttentionSpec
from repro.kernels import dispatch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.serving import Request, ServingEngine, tracing


def device_info() -> dict:
    """The device JAX runs on, as every result line names it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--theta", type=float, default=PAPER_CONFIG.theta)
    ap.add_argument("--capacity", type=int, default=4096,
                    help="stripes kept per superblock and query head "
                         "(0: all candidates)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default=None, choices=dispatch.BACKENDS,
                    help="kernel backend (default: platform-appropriate)")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged KV-cache pool instead of the "
                         "dense (max_batch, max_len) slab")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool budget (default: dense-equivalent footprint)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable prompt-prefix page sharing")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked-prefill threshold/chunk size (paged only)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of a common system prompt prepended to "
                         "every request (exercises prefix sharing)")
    ap.add_argument("--stats-every", type=int, default=0,
                    help="print an engine.stats snapshot every N engine "
                         "steps (0: only the final snapshot)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.backend:
        dispatch.set_default_backend(args.backend)
    backend = dispatch.default_backend()
    print(f"device: {json.dumps(device_info())} backend: {backend}")

    cfg = get_reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.embed_input:
        raise SystemExit(f"{args.arch} is an embed-input stub arch; "
                         "use a token arch for the serving demo")
    params = model_lib.init(jax.random.PRNGKey(args.seed), cfg)
    anchor_cfg = dataclasses.replace(
        PAPER_CONFIG, theta=args.theta, capacity=args.capacity or None,
        backend=backend)
    spec = AttentionSpec(algorithm="anchor", backend=backend,
                         anchor=anchor_cfg)
    # Cache must fit prompts padded for sparse prefill or the engine
    # records a dense fallback.
    max_len = anchor_cfg.prefill_pad_len(args.prompt_len) + args.max_new + 8
    paged_kw = {}
    if args.paged:
        max_len = -(-max_len // args.page_size) * args.page_size
        if args.chunk_tokens:
            # Chunk windows are fixed-width and chunk-aligned; the engine
            # rejects a max_len that is not a chunk multiple (a clamped
            # final window would clobber history K/V).  chunk_tokens is
            # validated to be a page multiple, so this keeps page
            # alignment too.
            max_len = -(-max_len // args.chunk_tokens) * args.chunk_tokens
        paged_kw = dict(
            cache_layout="paged",
            page_size=args.page_size,
            num_pages=args.num_pages,
            prefix_sharing=not args.no_prefix_cache,
            chunk_tokens=args.chunk_tokens,
        )
    engine = ServingEngine(
        params, cfg, max_batch=args.max_batch, max_len=max_len, spec=spec,
        **paged_kw)

    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab_size,
                          size=args.shared_prefix).astype(np.int32)
    t0 = time.time()
    t0_spans = time.perf_counter()
    for uid in range(args.requests):
        own = max(1, args.prompt_len - args.shared_prefix)
        prompt = np.concatenate(
            [shared, rng.integers(0, cfg.vocab_size, size=own).astype(np.int32)])
        engine.submit(Request(uid=uid, prompt=prompt, max_new_tokens=args.max_new))
    done: list[Request] = []
    for it in range(10_000):
        done.extend(engine.step())
        if args.stats_every and (it + 1) % args.stats_every == 0:
            print(f"stats[iter {it + 1}]: {json.dumps(engine.snapshot())}")
        if engine.idle:
            break
    dt = time.time() - t0
    for req in sorted(done, key=lambda r: r.uid):
        print(f"req {req.uid}: generated {len(req.generated)} tokens: "
              f"{req.generated[:8]}")
    total_tokens = sum(len(r.generated) for r in done)
    print(f"{len(done)} requests, {total_tokens} tokens in {dt:.1f}s "
          f"on {device_info()['kind']} (wall time incl. compilation)")
    print(f"engine stats: {json.dumps(engine.snapshot())}")
    print("engine spans (name, count, total s, max s):")
    for name, (n, total, top) in tracing.totals(
            engine.spans(t0_spans)).items():
        print(f"  {name:24s} {n:6d} {total:10.4f} {top:9.4f}")


if __name__ == "__main__":
    main()
