"""Model operations of the prefill and decode work done in the window
(counted from shapes by the configuration's family) over the window's
seconds times the chip's peak bf16 FLOP/s."""


def read(ctx):
    if not ctx.peaks or ctx.window_s <= 0:
        return None
    f = ctx.family
    work = sum(f.prefill_flops(n, ctx.arch, ctx.anchor) for n in ctx.prefill_lens)
    work += sum(f.decode_flops(c, ctx.arch) for c in ctx.decode_ctx)
    if work <= 0:
        return None
    return 100.0 * work / (ctx.window_s * ctx.peaks["bf16_flops_s"])
