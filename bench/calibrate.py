"""Readings that a cell's correctness limit is set from (see PERF.md):

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3

For each seed, in one process: weights from the seed, a fresh engine
warmed up as a run warms it, and one window of the cell's traffic driven
by the harness's own driver for the benchmark's ``run_seconds``, its
requests finished as a run finishes them.  The harness's own check then
reads the program's widest logit gap on the sample that a run compares
and, for the control seeds, the gap of the tokens that the float8
control ranks first.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import build, harness  # noqa: E402
from bench.traffic import Traffic  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float,
                   help="window length; the benchmark's run_seconds by default")
    p.add_argument("--benchmark", default=str(ROOT / "BENCHMARK.json"))
    p.add_argument("--dry-run", action="store_true")
    args = p.parse_args(argv)
    c = harness.load_cell(Path(args.benchmark), args.workload)
    conf, limits, family = c["conf"], c["limits"], c["family"]
    seconds = args.seconds or float(c["bench"]["run_seconds"])
    if not args.dry_run:
        harness.enable_compile_cache()
    import jax

    mcfg = family.model_config(conf)
    arch, anchor = family.arch(conf), build.anchor_of(conf)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        params = family.make_weights(arch, conf.get("torch_dtype", "bfloat16"),
                                     seed)
        engine = build.make_engine(params, mcfg, conf,
                                   backend="xla" if args.dry_run else None)
        traffic = Traffic(c["traffic"], seed, arch["vocab"])
        harness.warm_up(engine, traffic)
        rec = harness.drive(engine, traffic, seconds, tracing=False)
        del engine
        gc.collect()
        reqs = rec["requests"]
        row = {"seed": seed, "served": [r.prompt_len for r in reqs]}
        for name, control in (("program", False), ("control", True)):
            if control and seed not in controls:
                continue
            correct, checks, sample = harness.check(
                family, params, arch, anchor, reqs, limits, seed,
                control=control)
            row[name] = {"correct": correct,
                         **{k: v["value"] for k, v in checks.items()}}
            row["sampled"] = [r.prompt_len for r in sample]
        row["seconds"] = time.perf_counter() - t0
        row["device"] = jax.devices()[0].device_kind
        print(json.dumps(row), flush=True)
        del params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
