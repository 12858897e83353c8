"""A benchmark definition at a size the CPU runs in seconds: the cell,
metric and family files are written as a later change would add them."""

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny", "source": "test", "family": "dense_gqa", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "serving": {
        "cache_layout": "paged", "page_size": 16, "prefix_sharing": True,
        "max_batch": 1, "max_len": 256, "num_pages": None,
        "chunk_tokens": None,
        "attention": {"algorithm": "anchor", "backend": "pallas_tpu",
                      "block_q": 16, "block_kv": 16, "step": 2,
                      "theta": 12.0, "capacity": 16},
    },
}
TRAFFIC = {"arrival": {"kind": "closed", "clients": 1, "think_s": 0.0},
           "prompt": {"lengths": [64, 64, 96, 32, 90, 38]},
           "output_tokens": 8}
LIMITS = {"sample_requests": 3, "min_tokens_compared": 24,
          "max_logit_gap": 0.02}
REQUESTS_READER = '''"""Throwaway reader: prompts prefilled in the window."""


def read(ctx):
    return float(len(ctx.prefill_lens))
'''
# Throwaway reader of the window's work in GFLOP, by every count of the
# configuration's family (a dry run has no peaks, so the benchmark's own
# readers call none).
WORK_READER = '''"""Throwaway reader: GFLOP of the window's work."""

from bench import spans


def read(ctx):
    f, arch, anchor = ctx.family, ctx.arch, ctx.anchor
    work = sum(f.prefill_flops(n, arch, anchor) for n in ctx.prefill_lens)
    work += sum(f.decode_flops(c, arch) for c in ctx.decode_ctx)
    work += sum(f.wave_work(w.attrs, arch, anchor)[0] for w in spans.window(ctx)
                if w.name == "engine.prefill" and "stripes_kept" in w.attrs)
    return work / 1e9 or None
'''
# Appended to a copy of ``dense_gqa``: every function that a family
# defines notes its name in ``<family>.calls`` beside the file when it
# is called.
RECORDER = '''

def _recorded(fn):
    def call(*a, **kw):
        with open(__file__[:-3] + ".calls", "a") as out:
            out.write(fn.__name__ + "\\n")
        return fn(*a, **kw)
    return call


for _name in CALLED:
    globals()[_name] = _recorded(globals()[_name])
'''
CALLED = ("arch", "model_config", "make_weights", "reference_logits",
          "prefill_flops", "decode_flops", "sparse_attention_work",
          "decode_attention_work", "wave_work")


def write(tmp: Path, config=None, traffic=None, limits=None,
          family: str | None = None) -> Path:
    """The tree; with ``family``, the configuration names a new family
    file of that name, a copy of ``dense_gqa`` that records its calls
    (``calls``)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # The tree as a later change leaves it: the benchmark's own metric
    # readers and families, plus the files that change adds.
    for sub in ("metrics", "families"):
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub,
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic", "cells"):
        (tmp / "bench" / sub).mkdir(parents=True, exist_ok=True)
    config = dict(config or CONFIG)
    if family is not None:
        src = (ROOT / "bench/families/dense_gqa.py").read_text()
        (tmp / f"bench/families/{family}.py").write_text(
            src + f"\nCALLED = {CALLED!r}\n" + RECORDER)
        config["family"] = family
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(config))
    (tmp / "bench/traffic/tinydoc.json").write_text(json.dumps(traffic or TRAFFIC))
    (tmp / "bench/cells/tiny.tinydoc.json").write_text(json.dumps(limits or LIMITS))
    (tmp / "bench/metrics/requests_prefilled.py").write_text(REQUESTS_READER)
    (tmp / "bench/metrics/work_counted.py").write_text(WORK_READER)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny.tinydoc", "config": "tiny",
                               "traffic": "tinydoc", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "requests_prefilled", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "engine (serving/engine.py)",
        "moves": "ttft_p50_s", "workloads": ["tiny.tinydoc"]})
    bench["per_layer"].append({
        "name": "work_counted", "unit": "GFLOP", "better": "higher",
        "source": "program_counter", "layer": "model (models/)",
        "moves": "ttft_p50_s", "workloads": ["tiny.tinydoc"]})
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


def load_family(name: str = "dense_gqa"):
    """A family of the benchmark's own, loaded as the harness loads it."""
    from bench.harness import load_family

    return load_family(ROOT / "bench" / "families", {"family": name}, "test")


def calls(bench_path: Path, family: str) -> set[str]:
    """The names of the recording family's functions called so far."""
    log = bench_path.parent / "bench" / "families" / f"{family}.calls"
    return set(log.read_text().split()) if log.exists() else set()


def run(capsys, bench_path: Path, seed: int = 7, trace: int = 0,
        extra: list[str] = ()) -> dict:
    from bench.harness import main

    rc = main(["--workload", "tiny.tinydoc", "--seed", str(seed),
               "--seconds", "6", "--trace", str(trace),
               "--benchmark", str(bench_path), "--dry-run", *extra], 0.0)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])
