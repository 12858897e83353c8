"""Model operations of the decode tokens of the steps that only decoded,
over those steps' seconds (host clock) times the chip's peak bf16
FLOP/s: the whole decode step's share, which bounds any decode kernel's
gain."""


def read(ctx):
    steps = [s for s in ctx.steps if s["decode_ctx"] and not s["prefill_lens"]]
    secs = sum(s["t1"] - s["t0"] for s in steps)
    if not ctx.peaks or secs <= 0:
        return None
    work = sum(ctx.family.decode_flops(c, ctx.arch)
               for s in steps for c in s["decode_ctx"])
    return 100.0 * work / (secs * ctx.peaks["bf16_flops_s"])
