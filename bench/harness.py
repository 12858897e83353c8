"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the result line.

Set-up (counted in ``setup_s``): the configuration's weights are made on
the device from the seed, the engine is built, and every prefill shape
the traffic uses is served once.  The window then drives
``ServingEngine.submit``/``step`` for ``--seconds`` seconds with the
traffic mix; a token counts as delivered when the ``step()`` call that
produced it returns.  After the window the requests still in flight are
finished untimed, the engine is freed, and a sample of the finished
requests (the longest among them) is compared with the plain reference
of the configuration's model family (``bench/families/<family>.py``).
With ``--control`` the float8 control takes the program's place in that
comparison, so the run has to read ``correct: false`` (calibration and
tests; the benchmark's own runs never pass it).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import build
from bench.build import BENCH_DIR, load_json

ROOT = BENCH_DIR.parent
DRAIN_S = 120.0


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--benchmark", default=str(ROOT / "BENCHMARK.json"),
                   help="benchmark definition (tests point it elsewhere)")
    p.add_argument("--dry-run", action="store_true",
                   help="CPU rehearsal: xla kernels, no device metrics")
    p.add_argument("--control", action="store_true",
                   help="check the float8 control in the program's place; "
                        "the run must read correct false")
    return p.parse_args(argv)


def _find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in the benchmark")


def load_cell(bench_path: Path, workload: str) -> dict:
    bench = load_json(bench_path)
    base = bench_path.parent
    cell = _find(bench["workloads"], workload, "workload")
    entry = _find(bench["configs"], cell["config"], "configuration")
    bench_dir = base / bench["paths"][0]
    conf = load_json(base / entry["file"])
    return {
        "bench": bench,
        "cell": cell,
        "conf": conf,
        "family": load_family(bench_dir / "families", conf, entry["file"]),
        "traffic": load_json(bench_dir / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(bench_dir / "cells" / f"{cell['name']}.json"),
        "metrics_dir": bench_dir / "metrics",
    }


def process_start_wall(fallback: float) -> float:
    """Wall-clock time at which this process started (Linux), so that
    interpreter start-up counts in set-up."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return fallback


def enable_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Compilations and compile-cache loads, by ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.times.append(time.perf_counter())


class Req:
    """A request as the client sees it: when it was sent and when each of
    its tokens came back."""

    def __init__(self, engine_req, client, t_submit):
        self.r = engine_req
        self.client = client
        self.prompt_len = len(engine_req.prompt)
        self.t_submit = t_submit
        self.token_t: list[float] = []
        self.t_done: float | None = None


def warm_up(engine, traffic) -> None:
    """Serve the mix's warm-up waves, so that every prefill and decode
    shape the window uses is compiled (or loaded from the cache)."""
    for w, wave in enumerate(traffic.warmup_waves()):
        for j, n in enumerate(wave):
            engine.submit(build.request(
                -1 - j, traffic.prompt(10_000 + w, j, n), 2))
        engine.run_to_completion()


def drive(engine, traffic, seconds: float, tracing: bool, on_close=None) -> dict:
    """The measured window: submit, step, and time every delivered token.
    ``on_close`` runs when the window closes, before the requests still
    in flight are finished untimed."""
    from jax.profiler import TraceAnnotation

    span = TraceAnnotation if tracing else (lambda name: contextlib.nullcontext())
    reqs: list[Req] = []
    live: dict[int, Req] = {}
    steps: list[dict] = []
    nxt = [0] * traffic.clients
    busy = [False] * traffic.clients
    ready = [0.0] * traffic.clients
    arrivals = traffic.arrivals(seconds) if traffic.kind == "open" else []
    sent_open = 0
    t0 = time.perf_counter()
    end = t0 + seconds

    def submit(client, now):
        idx = nxt[client]
        nxt[client] += 1
        er = build.request(len(reqs), traffic.prompt(client, idx),
                           traffic.output_tokens(client, idx))
        with span("submit"):
            engine.submit(er)
        rq = Req(er, client, now)
        reqs.append(rq)
        live[er.uid] = rq
        return rq

    def one_step():
        before = engine.stats["decode_steps"]
        s0 = time.perf_counter()
        with span("step"):
            finished = engine.step()
        s1 = time.perf_counter()
        new_first, ctxs = [], []
        for rq in list(live.values()):
            got, had = len(rq.r.generated), len(rq.token_t)
            if got > had:
                if not had:
                    new_first.append(rq.prompt_len)
                # Token j >= 1 came from a decode over prompt_len + j cached
                # positions (its own input included).
                ctxs += [rq.prompt_len + j for j in range(max(had, 1), got)]
                rq.token_t += [s1] * (got - had)
        for er in finished:
            rq = live.pop(er.uid, None)
            if rq is not None:
                rq.t_done = s1
                if traffic.kind == "closed":
                    busy[rq.client] = False
                    ready[rq.client] = s1 + traffic.think_s
        steps.append({"t0": s0, "t1": s1, "prefill_lens": new_first,
                      "decode_ctx": ctxs,
                      "decode_calls": engine.stats["decode_steps"] - before})

    stats0 = engine.snapshot()
    with span("window"):
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if traffic.kind == "closed":
                for c in range(traffic.clients):
                    if not busy[c] and now >= ready[c]:
                        busy[c] = True
                        submit(c, now)
            else:
                while sent_open < len(arrivals) and t0 + arrivals[sent_open] <= now:
                    rq = submit(0, now)
                    rq.t_submit = t0 + arrivals[sent_open]
                    sent_open += 1
            if engine.idle:
                time.sleep(min(0.001, max(0.0, end - now)))
                continue
            one_step()
    stats1 = engine.snapshot()
    t_close = time.perf_counter()
    if on_close is not None:
        on_close()
    deadline = t_close + DRAIN_S
    while live and time.perf_counter() < deadline:
        one_step()
    return {"t0": t0, "t1": end, "t_close": t_close, "requests": reqs,
            "steps": steps, "stats0": stats0, "stats1": stats1}


def end_to_end(rec: dict, setup_s: float) -> dict:
    t0, t1 = rec["t0"], rec["t1"]
    reqs = rec["requests"]
    ttft = [r.token_t[0] - r.t_submit for r in reqs
            if r.token_t and t0 <= r.token_t[0] <= t1]
    gaps = [b - a for r in reqs for a, b in zip(r.token_t, r.token_t[1:])
            if t0 <= b <= t1]
    # Prompt tokens prefilled in the window: a prefill runs inside the
    # step() call that returns its first token, and counts by the share
    # of that call which lies in the window.
    prefilled = 0.0
    for st in rec["steps"]:
        n = sum(st["prefill_lens"])
        if n and st["t1"] > st["t0"]:
            inside = max(0.0, min(st["t1"], t1) - max(st["t0"], t0))
            prefilled += n * inside / (st["t1"] - st["t0"])
    out = {"setup_s": setup_s, "prompt_tokens_s": prefilled / (t1 - t0)}
    if ttft:
        out["ttft_p50_s"] = float(np.median(ttft))
    if gaps:
        out["itl_p95_ms"] = float(np.percentile(gaps, 95)) * 1e3
    return out


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(families_dir: Path, conf: dict, conf_file: str):
    """The model family that a configuration names under ``"family"``:
    ``bench/families/<family>.py``.  There is no default, so that a new
    model is never read as another.  A family file defines:

    * ``arch(conf) -> dict``: the shapes that the counts and the traffic
      read, from the configuration's published keys; ``layers`` (the
      attention layers, each doing the per-layer work below) and
      ``vocab`` among them;
    * ``model_config(conf)``: the program's ``ModelConfig``;
    * ``make_weights(arch, dtype, seed)``: random weights in the serving
      engine's parameter layout, made on the device in one jitted call;
    * ``reference_logits(params, arch, anchor, prompt, served,
      control=False)``: the plain f32 forward at ``highest`` precision
      that ``correct`` is decided against, importing nothing of the
      program; with ``control``, the float8 control
      (``bench/reference.py``);
    * the work counts, which the per-layer readers call through
      ``ctx.family``: ``prefill_flops(n, arch, anchor)`` and
      ``decode_flops(ctx, arch)`` of the whole model;
      ``sparse_attention_work(n, arch, anchor)`` and
      ``decode_attention_work(ctx, arch)``, (flops, bytes) of one layer;
      and ``wave_work(attrs, arch, anchor)``, (flops, bytes) of the
      sparse attention that one prefill wave kept, every layer, from the
      attributes of its ``engine.prefill`` span.
    """
    name = conf.get("family")
    if name is None:
        raise SystemExit(f"configuration {conf_file} has no 'family' key: "
                         f"name its model family, a file under {families_dir}")
    path = families_dir / f"{name}.py"
    if not (re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", str(name))
            and path.is_file()):
        raise SystemExit(f"configuration {conf_file} names 'family' {name!r}, "
                         f"but there is no family file {path}")
    return _load_module(path, f"bench_family_{name}")


def load_metric(metrics_dir: Path, name: str):
    """The reader of one per-layer metric: ``bench/metrics/<name>.py``,
    with ``read(ctx) -> float | None`` and, optionally, ``KERNELS``, the
    kernel-name prefixes whose device time it needs from the trace."""
    return _load_module(metrics_dir / f"{name}.py", f"bench_metric_{name}")


def reported_per_layer(bench: dict, cell: dict) -> list[dict]:
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif cell["name"] in e2e[m["moves"]].get("workloads", [cell["name"]]):
            out.append(m)
    return out


def sample_requests(reqs: list[Req], k: int, seed: int) -> list[Req]:
    """The longest finished request and ``k - 1`` others drawn from the
    seed."""
    done = [r for r in reqs if r.t_done is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len, -r.r.uid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(family, params, arch, anchor, sample, control=False) -> dict:
    """Widest gap by which a served token's logit lies below the family
    reference's best, over the sample (or, with ``control``, the gap of
    the token that the float8 control ranks first)."""
    reference_logits = family.reference_logits
    worst, n = 0.0, 0
    for r in sample:
        served = np.asarray(r.r.generated, np.int32)
        ref = reference_logits(params, arch, anchor,
                               np.asarray(r.r.prompt, np.int32), served)
        pick = served
        if control:
            ctl = reference_logits(params, arch, anchor,
                                   np.asarray(r.r.prompt, np.int32), served,
                                   control=True)
            pick = ctl.argmax(-1)
        gaps = ref.max(-1) - ref[np.arange(len(pick)), pick]
        worst = max(worst, float(gaps.max()))
        n += len(pick)
    return {"max_logit_gap": worst, "tokens_compared": n}


def check(family, params, arch, anchor, reqs, limits: dict, seed: int,
          control: bool = False) -> tuple[bool, dict, list]:
    """The decision a run prints as ``correct``: the sample's widest
    logit gap and the tokens compared, each against its limit."""
    sample = sample_requests(reqs, int(limits["sample_requests"]), seed)
    got = compare(family, params, arch, anchor, sample, control=control)
    checks = {
        "max_logit_gap": {"value": got["max_logit_gap"],
                          "limit": limits["max_logit_gap"]},
        "tokens_compared": {"value": got["tokens_compared"],
                            "limit": limits["min_tokens_compared"]},
    }
    correct = (got["max_logit_gap"] <= limits["max_logit_gap"]
               and got["tokens_compared"] >= limits["min_tokens_compared"])
    return bool(correct), checks, sample


def device_info(jax, dry: bool) -> dict:
    d = jax.devices()[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}
    stats = d.memory_stats() if not dry else None
    if stats and "peak_bytes_in_use" in stats:
        info["memory_peak_bytes"] = int(max(
            x.memory_stats().get("peak_bytes_in_use", 0)
            for x in jax.devices()))
    return info


def main(argv, t0_wall: float) -> int:
    args = parse_args(argv)
    start_wall = min(process_start_wall(t0_wall), t0_wall)
    c = load_cell(Path(args.benchmark), args.workload)
    cell, conf, limits, family = c["cell"], c["conf"], c["limits"], c["family"]

    if not args.dry_run:
        enable_compile_cache()
    import jax

    if not args.dry_run:
        devs = jax.devices()
        if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
            print(f"needs {cell['chips']} TPU chip(s); JAX sees "
                  f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
            return 2
    from bench.peaks import peaks_for
    from bench.traffic import Traffic

    peaks = None if args.dry_run else peaks_for(jax.devices()[0].device_kind)
    compiles = CompileCounter()

    # ---- set-up ----
    mcfg = family.model_config(conf)
    arch, anchor = family.arch(conf), build.anchor_of(conf)
    params = family.make_weights(arch, conf.get("torch_dtype", "bfloat16"),
                                 args.seed)
    jax.block_until_ready(params)
    build.check_layout(params, mcfg)
    t_weights = time.time() - start_wall
    engine = build.make_engine(params, mcfg, conf,
                               backend="xla" if args.dry_run else None)
    traffic = Traffic(c["traffic"], args.seed, arch["vocab"])
    warm_up(engine, traffic)

    # ---- window ----
    trace_dir = BENCH_DIR / ".trace" / cell["name"]
    if args.trace and not args.dry_run:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.time() - start_wall
    tracing = bool(args.trace) and not args.dry_run
    rec = drive(engine, traffic, args.seconds, tracing,
                on_close=jax.profiler.stop_trace if tracing else None)
    device = device_info(jax, args.dry_run)
    rec["compiles"] = [t for t in compiles.times
                       if rec["t0"] <= t <= rec["t_close"]]
    del engine
    gc.collect()

    # ---- check ----
    t_check = time.perf_counter()
    correct, checks, sample = check(family, params, arch, anchor,
                                    rec["requests"], limits, args.seed,
                                    control=args.control)
    t_check = time.perf_counter() - t_check

    in_window = [r for r in rec["requests"] if r.t_submit <= rec["t1"]]
    failed = sum(1 for r in in_window
                 if r.t_done is None
                 or len(r.r.generated) != r.r.max_new_tokens)
    result = {"correct": correct, "attempted": len(in_window),
              "failed": failed, "metrics": {}, "device": device}

    # ---- metrics ----
    if args.trace:
        from bench import spans
        from bench import trace as trace_lib
        from bench.context import RunContext

        readers = [(m, load_metric(c["metrics_dir"], m["name"]))
                   for m in reported_per_layer(c["bench"], cell)]
        red = None
        if not args.dry_run:
            kernels = tuple(dict.fromkeys(
                k for _, mod in readers for k in getattr(mod, "KERNELS", ())))
            planes = trace_lib.load_xplane(trace_lib.find_xplane(str(trace_dir)))
            red = trace_lib.reduce_trace(planes, kernels=kernels,
                                         bench_spans=spans.bench_spans())
            shutil.rmtree(trace_dir.parent, ignore_errors=True)
        ctx = RunContext(rec, family, arch, anchor, peaks, red)
        values = {}
        for m, mod in readers:
            v = mod.read(ctx)
            if v is not None and math.isfinite(v):
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        if args.dry_run:
            # Host counts only: a dry run never reports device metrics.
            result["dry_layer_metrics"] = {k: v["value"] for k, v in values.items()}
        else:
            result["metrics"] = values
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    elif args.dry_run:
        result["dry_end_to_end"] = end_to_end(rec, setup_s)
    else:
        e2e = end_to_end(rec, setup_s)
        for m in c["bench"]["end_to_end"]:
            if m["name"] in e2e and cell["name"] in m.get(
                    "workloads", [cell["name"]]):
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    result["checks"] = checks
    t0 = rec["t0"]
    print(json.dumps({
        "setup_s": setup_s, "weights_s": t_weights, "check_s": t_check,
        "requests": [[r.prompt_len, round(r.t_submit - t0, 4),
                      round(r.token_t[0] - t0, 4) if r.token_t else None,
                      round(r.t_done - t0, 4) if r.t_done else None]
                     for r in rec["requests"]],
        "sampled": [r.prompt_len for r in sample],
        "compiles_in_window": len(rec["compiles"]),
        "step_ms": [round((st["t1"] - st["t0"]) * 1e3, 1) for st in rec["steps"]]}),
        file=sys.stderr)
    for name, ck in checks.items():
        print(f"check {name} {ck['value']} limit {ck['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0

