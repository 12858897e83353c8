"""Roofline bound of the sparse-attention work of the prompts prefilled
in the window (anchor region, every layer; counted by the family) over
the summed device time of the ``sparse_attention`` kernel events."""

from bench import counts

KERNELS = ("sparse_attention",)


def read(ctx):
    t = ctx.kernel_s("sparse_attention")
    if not t or not ctx.prefill_lens:
        return None
    bound = sum(counts.roofline_seconds(
        *ctx.family.sparse_attention_work(n, ctx.arch, ctx.anchor), ctx.peaks)
        for n in ctx.prefill_lens) * ctx.arch["layers"]
    return 100.0 * bound / t
