"""Roofline bound of the sparse attention the window's prefill waves
kept, over the summed device time of the ``sparse_attention`` kernel
events.

Per wave (``engine.prefill`` span, which carries the wave's unpadded
lengths and the engine's counters): the anchor region of every prompt
and layer (``bench/counts.py``), plus the kept stripes, each attended by
the rows of its superblock for its query head (operations from
``stripes_kept``), plus the K and V tiles holding them, read once per KV
head and superblock (bytes from ``stripe_tiles_kept``).  The sweep loads
tiles of gcd(padded length, 128) rows.  Chunked prefills are not
counted.
"""

import math

from bench import counts, spans

KERNELS = ("sparse_attention",)
BLOCK_C = 128  # the sweep's widest tile


def wave_work(wave, arch: dict, anchor: dict) -> tuple[float, float]:
    """(flops, bytes) of one prefill wave's sparse attention."""
    a = wave.attrs
    d, layers = arch["head_dim"], arch["layers"]
    sb = anchor["block_q"] * anchor["step"]
    tile = math.gcd(a["n_pad"], BLOCK_C)
    flops = 4.0 * d * sb * a["stripes_kept"]
    nbytes = 2 * counts.BF16 * tile * d * a["stripe_tiles_kept"]
    for n in a["tokens"]:
        f, b = counts.sparse_attention_work(n, arch, anchor)
        flops += layers * f
        nbytes += layers * b
    return flops, nbytes


def read(ctx):
    t = ctx.kernel_s("sparse_attention")
    log = spans.window(ctx)
    if not t or log is None:
        return None
    waves = [s for s in log if s.name == "engine.prefill"
             and "stripes_kept" in s.attrs]
    if not waves:
        return None
    bound = sum(counts.roofline_seconds(*wave_work(w, ctx.arch, ctx.anchor),
                                        ctx.peaks) for w in waves)
    return 100.0 * bound / t
