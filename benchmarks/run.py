"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (us_per_call column holds the
benchmark's primary scalar; `derived` explains it).

    PYTHONPATH=src python -m benchmarks.run [--only recall_sparsity,...] \
        [--backend xla|pallas_interpret|pallas_tpu]

``--backend`` sets the process-default kernel backend (the registry in
``repro.kernels.dispatch``), so the same harness measures the XLA paths,
the Pallas kernels in interpret mode, or the compiled TPU kernels.
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.kernels import dispatch
from repro.launch.compile_cache import enable_compile_cache

SUITES = [
    "recall_sparsity",  # Fig. 6a + Table 1 + Fig. 5
    "ablation_theta",  # Table 4
    "latency",  # Fig. 2 / 6b / 6c
    "prefill_index",  # gather-based vs index-driven sparse stage
    "ruler_proxy",  # Table 3 proxy
    "roofline_report",  # §Dry-run / §Roofline
    "serving_throughput",  # dense-slab vs paged KV-cache engine
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of suites")
    ap.add_argument("--backend", default=None, choices=dispatch.BACKENDS,
                    help="kernel backend for dispatched ops "
                         "(default: platform-appropriate)")
    args = ap.parse_args()
    enable_compile_cache()
    suites = args.only.split(",") if args.only else SUITES
    if args.backend:
        dispatch.set_default_backend(args.backend)
    print(f"# backend={dispatch.default_backend()}", file=sys.stderr)

    print("name,us_per_call,derived")

    def report(name: str, value: float, derived: str = "") -> None:
        print(f"{name},{value:.4f},{derived}", flush=True)

    for suite in suites:
        mod = __import__(f"benchmarks.{suite}", fromlist=["run"])
        t0 = time.time()
        try:
            mod.run(report)
        except Exception as e:  # noqa: BLE001
            report(f"{suite}_FAILED", 0.0, f"{type(e).__name__}:{e}")
            raise
        print(f"# {suite} done in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
