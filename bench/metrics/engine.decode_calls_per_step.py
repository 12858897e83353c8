"""Decode calls per engine step that decoded (engine counter
``decode_steps``): one per distinct position in the batch today."""


def read(ctx):
    steps = [s for s in ctx.steps if s["decode_calls"] > 0]
    if not steps:
        return None
    return sum(s["decode_calls"] for s in steps) / len(steps)
