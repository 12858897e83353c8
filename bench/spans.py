"""The program's engine spans, as the per-layer readers see them.

``ServingEngine`` times its work with spans kept in an in-memory log
(``repro.serving.tracing``; names in ``repro.serving.engine.SPAN_NAMES``).
Each span is ``(id, parent, name, t0, t1, attrs)`` on
``time.perf_counter()``, the clock the harness times its window with.
A program without that log gives ``None`` here, and the readers that
need spans then report nothing.
"""

from __future__ import annotations

import statistics


def window(ctx):
    """The spans of the measured window: ``ctx.spans`` where the harness
    gives it, else the program's log between the window's start and its
    close; ``None`` when the program keeps no spans."""
    got = getattr(ctx, "spans", None)
    if got is not None:
        return got
    try:
        from repro.serving import tracing
    except ImportError:
        return None
    return tracing.spans(ctx.t0, ctx.t_close)


def names() -> tuple[str, ...]:
    """The engine's span names (none for a program without spans)."""
    try:
        from repro.serving.engine import SPAN_NAMES
    except ImportError:
        return ()
    return tuple(SPAN_NAMES)


def bench_spans() -> tuple[str, ...]:
    """The host spans that name an idle gap in ``trace.reduce_trace``:
    the harness's own and the engine's."""
    return ("window", "step", "submit") + names()


def seconds(span) -> float:
    return span.t1 - span.t0


def children(log, span) -> list:
    return [s for s in log if s.parent == span.id]


def median_seconds(log, name: str) -> float | None:
    got = [seconds(s) for s in log if s.name == name]
    return statistics.median(got) if got else None


def breakdown(log, root) -> list[tuple[str, float]]:
    """Each span under ``root`` (depth first) with its seconds, its name
    prefixed by its parents' below the root: where a step's time went."""
    out = []

    def walk(span, path):
        for c in children(log, span):
            name = f"{path}/{c.name}" if path else c.name
            out.append((name, seconds(c)))
            walk(c, name)

    walk(root, "")
    return out
