"""What a per-layer metric's reader gets: the window's record, the model
family (whose counts it calls), the shapes, the peaks and the reduced
trace, with the work done in the window gathered once."""

from __future__ import annotations


class RunContext:
    def __init__(self, rec: dict, family, arch: dict, anchor: dict,
                 peaks: dict | None, trace: dict | None):
        self.family = family
        self.arch, self.anchor, self.peaks, self.trace = arch, anchor, peaks, trace
        self.t0, self.t_close = rec["t0"], rec["t_close"]
        self.window_s = self.t_close - self.t0
        self.steps = [s for s in rec["steps"] if s["t1"] <= self.t_close]
        self.stats0, self.stats1 = rec["stats0"], rec["stats1"]
        self.compiles = rec["compiles"]
        self.prefill_lens = [n for s in self.steps for n in s["prefill_lens"]]
        self.decode_ctx = [c for s in self.steps for c in s["decode_ctx"]]

    def delta(self, counter: str) -> int:
        return self.stats1[counter] - self.stats0[counter]

    def kernel_s(self, prefix: str) -> float | None:
        if self.trace is None:
            return None
        return self.trace["kernel_s"].get(prefix) or None
