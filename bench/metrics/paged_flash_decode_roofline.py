"""Roofline bound of the decode attention of the tokens decoded in the
window (every cached position, every layer; counted by the family) over
the summed device time of the ``paged_flash_decode`` kernel events."""

from bench import counts

KERNELS = ("paged_flash_decode",)


def read(ctx):
    t = ctx.kernel_s("paged_flash_decode")
    if not t or not ctx.decode_ctx:
        return None
    bound = sum(counts.roofline_seconds(
        *ctx.family.decode_attention_work(c, ctx.arch), ctx.peaks)
        for c in ctx.decode_ctx) * ctx.arch["layers"]
    return 100.0 * bound / t
