"""Roofline bound of the sparse attention the window's prefill waves
kept, over the summed device time of the ``sparse_attention`` kernel
events.

Per wave (``engine.prefill`` span, which carries the wave's unpadded
lengths and the engine's counters), the family's ``wave_work``: the
anchor region of every prompt and layer, plus the kept stripes
(operations from ``stripes_kept``) and the K and V tiles holding them
(bytes from ``stripe_tiles_kept``).  Chunked prefills are not counted.
"""

from bench import counts, spans

KERNELS = ("sparse_attention",)


def read(ctx):
    t = ctx.kernel_s("sparse_attention")
    log = spans.window(ctx)
    if not t or log is None:
        return None
    waves = [s for s in log if s.name == "engine.prefill"
             and "stripes_kept" in s.attrs]
    if not waves:
        return None
    bound = sum(counts.roofline_seconds(
        *ctx.family.wave_work(w.attrs, ctx.arch, ctx.anchor), ctx.peaks)
        for w in waves)
    return 100.0 * bound / t
