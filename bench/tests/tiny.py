"""A benchmark definition at a size the CPU runs in seconds: the cell
and metric files are written as a later change would add them."""

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny", "source": "test", "hidden_size": 64,
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


def write(tmp: Path, config=None, traffic=None, limits=None) -> Path:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # The tree as a later change leaves it: the benchmark's own metric
    # readers, plus the files that change adds.
    shutil.copytree(ROOT / "bench" / "metrics", tmp / "bench" / "metrics",
                    dirs_exist_ok=True)
    for sub in ("configs", "traffic", "cells"):
        (tmp / "bench" / sub).mkdir(parents=True, exist_ok=True)
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(config or CONFIG))
    (tmp / "bench/traffic/tinydoc.json").write_text(json.dumps(traffic or TRAFFIC))
    (tmp / "bench/cells/tiny.tinydoc.json").write_text(json.dumps(limits or LIMITS))
    (tmp / "bench/metrics/requests_prefilled.py").write_text(REQUESTS_READER)
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
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


def run(capsys, bench_path: Path, seed: int = 7, trace: int = 0,
        extra: list[str] = ()) -> dict:
    from bench.harness import main

    rc = main(["--workload", "tiny.tinydoc", "--seed", str(seed),
               "--seconds", "6", "--trace", str(trace),
               "--benchmark", str(bench_path), "--dry-run", *extra], 0.0)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])
