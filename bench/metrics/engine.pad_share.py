"""Share of the prompt tokens prefilled in the window that were padding
(engine counter ``padded_tokens`` against the unpadded lengths)."""


def read(ctx):
    real = sum(ctx.prefill_lens)
    pad = ctx.delta("padded_tokens")
    if real + pad <= 0:
        return None
    return 100.0 * pad / (real + pad)
