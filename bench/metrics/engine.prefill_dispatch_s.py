"""Median seconds of the window's ``engine.prefill.dispatch`` spans: the
model's prefill call from entry to return (tracing, lowering, compile
cache loads and enqueue), one per prefill wave."""

from bench import spans


def read(ctx):
    log = spans.window(ctx)
    if log is None:
        return None
    return spans.median_seconds(log, "engine.prefill.dispatch")
