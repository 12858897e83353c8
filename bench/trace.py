"""Reduction of a profiler trace to device busy time, kernel time and
the longest idle gaps, each named by the host span it falls in.

A trace is read into plain lists — ``[(plane, [(line, [(event, start_ns,
duration_ns), ...]), ...]), ...]`` — so that the reduction can be checked
on a small recorded trace kept in ``bench/tests/data``.  Device planes
are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
HLO instruction, named by the instruction's text (``%name.N = ...``).
Control-flow instructions (``while``, ``conditional``, ``call``) span the
instructions they run, so busy time is a union of intervals and the top
operations leave those containers out.
"""

from __future__ import annotations

import glob
import os
import re

CONTAINERS = ("while", "conditional", "call")
TOP = 10


def load_xplane(path: str) -> list:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                 for e in ln.events]) for ln in p.lines])
            for p in pd.planes]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_name(event: str) -> str:
    """``%paged_flash_decode.11 = bf16[...] custom-call(...)`` ->
    ``paged_flash_decode``."""
    head = event.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _host_events(planes: list) -> list[tuple[str, float, float]]:
    return [(name, float(s), float(s) + float(d))
            for pname, lines in planes if pname.startswith("/host:")
            for _, events in lines for name, s, d in events]


def reduce_trace(planes: list, *, window_span: str = "window",
                 bench_spans: tuple[str, ...] = ("window", "step", "submit"),
                 kernels: tuple[str, ...] = ()) -> dict:
    """Seconds of the traced window, device busy time (union of the
    device's operations, averaged over chips), summed time of each
    kernel in ``kernels`` (by instruction-name prefix, per chip), the
    top device operations and the longest idle gaps."""
    host = _host_events(planes)
    spans = [(s, e) for name, s, e in host if name == window_span]
    devices = [(p, lines) for p, lines in planes if p.startswith("/device:TPU")]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    ops = [[(op_name(n), float(s), float(s) + float(d))
            for ln, evs in lines if ln == "XLA Ops" for n, s, d in evs]
           for _, lines in devices]
    if spans:
        w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        w0 = min(s for chip in ops for _, s, _ in chip)
        w1 = max(e for chip in ops for _, _, e in chip)
    chips = len(ops)
    busy_total, kernel_s, op_s, gaps = 0.0, {k: 0.0 for k in kernels}, {}, []
    for chip in ops:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in chip
                   if e > w0 and s < w1]
        union = _union([(s, e) for _, s, e in clipped])
        busy_total += sum(e - s for s, e in union)
        for n, s, e in clipped:
            for k in kernels:
                if n.startswith(k):
                    kernel_s[k] += (e - s) / 1e9 / chips
            if not n.startswith(CONTAINERS):
                op_s[n] = op_s.get(n, 0.0) + (e - s) / 1e9 / chips
        edges = [w0] + [x for iv in union for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_gap_name(host, bench_spans, (s + e) / 2), (e - s) / 1e9]
             for s, e in gaps[:TOP]]
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_total / 1e9 / chips,
        "kernel_s": kernel_s,
        "device_ops": [[n, s] for n, s in top_ops],
        "idle_gaps": named,
        "chips": chips,
    }


def _gap_name(host: list, bench_spans: tuple[str, ...], t: float) -> str:
    """The innermost of the benchmark's spans around ``t``, and the
    innermost host event of any name under it."""
    around = [(e - s, name) for name, s, e in host if s <= t <= e]
    if not around:
        return "outside any host span"
    around.sort()
    ours = next((n for _, n in around if n in bench_spans), "no bench span")
    inner = around[0][1]
    return ours if inner == ours else f"{ours}/{inner}"[:120]
