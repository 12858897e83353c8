"""The readers of the engine's spans and sparse counters, on hand-built
run contexts with every count worked out by hand; idle gaps named by
engine spans; and the benchmark's earlier readers, pinned on the
recorded trace slice."""

import json
from pathlib import Path

import pytest

from bench import build, peaks, spans, trace
from bench.context import RunContext
from bench.harness import load_metric
from bench.tests import tiny
from repro.serving.tracing import Span

METRICS = Path(__file__).resolve().parents[1] / "metrics"
DATA = Path(__file__).parent / "data" / "trace_slice.json"
NEW = ("sparse_attention.slot_use", "sparse_attention_kept_roofline",
       "engine.prefill_dispatch_s", "engine.decode_host_ms")

# Two layers of 4 query heads over 2 KV heads of width 8; superblocks of
# 2 blocks of 16 rows.
ARCH = {"layers": 2, "d_model": 32, "heads": 4, "kv_heads": 2,
        "head_dim": 8, "d_ff": 64, "vocab": 100}
ANCHOR = {"block_q": 16, "block_kv": 16, "step": 2, "theta": 12.0,
          "capacity": None}
PEAKS = {"bf16_flops_s": 1e9, "hbm_bytes_s": 1e8}


def _ctx(steps=(), stats0=None, stats1=None, log=None, trace_=None,
         arch=ARCH, anchor=ANCHOR, peaks_=PEAKS):
    rec = {"t0": 0.0, "t_close": 10.0, "steps": list(steps),
           "stats0": stats0 or {}, "stats1": stats1 or {}, "compiles": []}
    ctx = RunContext(rec, tiny.load_family(), arch, anchor, peaks_, trace_)
    if log is not None:
        ctx.spans = log
    return ctx


def _read(name, ctx):
    return load_metric(METRICS, name).read(ctx)


def test_slot_use():
    ctx = _ctx(stats0={"sparse_slots_used": 100, "sparse_slots_visited": 1000},
               stats1={"sparse_slots_used": 600, "sparse_slots_visited": 3000})
    # (600 - 100) used of (3000 - 1000) visited.
    assert _read("sparse_attention.slot_use", ctx) == 25.0
    # A program without the counters, or a window without sparse prefill.
    assert _read("sparse_attention.slot_use", _ctx(stats1={"decode_steps": 3})) is None
    same = {"sparse_slots_used": 5, "sparse_slots_visited": 9}
    assert _read("sparse_attention.slot_use", _ctx(stats0=same, stats1=same)) is None


def _wave(**counters):
    attrs = {"uids": (0,), "n_pad": 64, "tokens": (64,), **counters}
    return Span(2, 1, "engine.prefill", 1.0, 2.0, attrs)


def test_kept_roofline_by_hand():
    # Anchor region of a 64-token prompt: rows 0..31 see i + 1 keys (528
    # pairs), rows 32..63 the 16 sink keys and their window up to
    # themselves, i - 15 keys (1,040 pairs): 1,568 pairs, 4·8·4 = 128
    # operations each, 200,704 per layer.  Bytes per layer: Q and O,
    # 2·64·4·8, plus K and V of 16 + 16 keys in superblock 0 and 16 + 32
    # in superblock 1 for 2 KV heads, 2·80·2·8; all in bf16: 13,312.
    # Stripes: 10 kept, each met by the 32 rows of its superblock at 4·8
    # operations a pair: 10,240; 3 tiles of gcd(64, 128) = 64 rows of K
    # and V at width 8 in bf16: 6,144 bytes.
    flops = 2 * 200_704 + 10_240
    nbytes = 2 * 13_312 + 6_144
    assert (flops, nbytes) == (411_648, 32_768)
    bound = max(flops / 1e9, nbytes / 1e8)
    trace_ = {"kernel_s": {"sparse_attention": 10 * bound}}
    log = [Span(1, 0, "engine.step", 0.5, 2.5, {}),
           _wave(stripes_kept=10, stripe_tiles_kept=3)]
    ctx = _ctx(steps=[{"t0": 0.5, "t1": 2.5, "prefill_lens": [64],
                       "decode_ctx": [], "decode_calls": 0}],
               log=log, trace_=trace_)
    kept = _read("sparse_attention_kept_roofline", ctx)
    assert kept == pytest.approx(10.0)
    # The anchor region alone reads less: 401,408 operations.
    assert _read("sparse_attention_roofline", ctx) == pytest.approx(
        100 * (2 * 200_704 / 1e9) / (10 * bound))
    assert _read("sparse_attention_roofline", ctx) < kept
    # No waves with counters (an older program), or no kernel time.
    assert _read("sparse_attention_kept_roofline",
                 _ctx(log=[_wave()], trace_=trace_)) is None
    assert _read("sparse_attention_kept_roofline",
                 _ctx(log=log, trace_={"kernel_s": {}})) is None


def test_prefill_dispatch_and_decode_host():
    log = [
        Span(1, 0, "engine.step", 0.0, 9.0, {}),
        Span(2, 1, "engine.prefill.dispatch", 0.1, 0.6, {}),
        Span(3, 1, "engine.prefill.dispatch", 1.0, 1.7, {}),
        Span(4, 1, "engine.prefill.dispatch", 2.0, 2.4, {}),
        Span(5, 1, "engine.decode", 3.0, 3.4, {}),
        Span(6, 5, "engine.decode.sync", 3.001, 3.399, {}),
        Span(7, 1, "engine.decode", 4.0, 4.3, {}),
        Span(8, 7, "engine.decode.pages", 4.0, 4.0005, {}),
        Span(9, 7, "engine.decode.sync", 4.0005, 4.3, {}),
        Span(10, 1, "engine.decode", 5.0, 5.35, {}),
        Span(11, 10, "engine.decode.sync", 5.0, 5.349, {}),
    ]
    ctx = _ctx(log=log)
    # Dispatches of 0.5, 0.7 and 0.4 s.
    assert _read("engine.prefill_dispatch_s", ctx) == pytest.approx(0.5)
    # Host parts of 2, 0.5 and 1 ms.
    assert _read("engine.decode_host_ms", ctx) == pytest.approx(1.0)
    empty = _ctx(log=[])
    assert _read("engine.prefill_dispatch_s", empty) is None
    assert _read("engine.decode_host_ms", empty) is None


def test_readers_take_the_program_log_for_the_window():
    import time

    from repro.serving import tracing

    ctx = _ctx()
    ctx.t0 = time.perf_counter()
    with tracing.span("engine.decode"):
        with tracing.span("engine.decode.sync"):
            pass
    ctx.t_close = time.perf_counter()
    assert [s.name for s in spans.window(ctx)] == [
        "engine.decode", "engine.decode.sync"]
    assert _read("engine.decode_host_ms", ctx) >= 0.0
    ctx.t0, ctx.t_close = -2.0, -1.0  # a window with no spans
    assert _read("engine.decode_host_ms", ctx) is None


def test_breakdown_names_each_span_under_its_parents():
    log = [Span(1, 0, "engine.step", 0.0, 3.0, {}),
           Span(2, 1, "engine.admit", 0.0, 2.0, {}),
           Span(3, 2, "engine.prefill", 0.0, 2.0, {}),
           Span(4, 3, "engine.prefill.sync", 0.5, 2.0, {}),
           Span(5, 1, "engine.decode", 2.0, 3.0, {})]
    assert spans.breakdown(log, log[0]) == [
        ("engine.admit", 2.0), ("engine.admit/engine.prefill", 2.0),
        ("engine.admit/engine.prefill/engine.prefill.sync", 1.5),
        ("engine.decode", 1.0)]


def test_idle_gap_named_by_the_engine_span():
    dev = ["/device:TPU:0", [["XLA Ops", [
        ["%fusion.1 = f32[8] fusion(", 0, 100],
        ["%sparse_attention_pallas.2 = bf16[8] custom-call(", 600, 100]]]]]
    host = ["/host:CPU", [["python3", [
        ["window", 0, 1000], ["step", 0, 1000], ["engine.step", 0, 1000],
        ["engine.admit", 40, 800], ["engine.prefill", 50, 750],
        ["engine.prefill.dispatch", 80, 570],
        ["lower_sharding_computation", 200, 300]]]]]
    planes = [dev, host]
    named = trace.reduce_trace(planes, bench_spans=spans.bench_spans())
    assert named["idle_gaps"][0] == [
        "engine.prefill.dispatch/lower_sharding_computation",
        pytest.approx(500e-9)]
    # Idle time after the last operation, inside the step only.
    assert named["idle_gaps"][1][0] == "engine.step"
    # The harness's own spans alone name the same gap by the step.
    plain = trace.reduce_trace(planes)
    assert plain["idle_gaps"][0][0] == "step/lower_sharding_computation"


# The eight readers the benchmark had before the engine's spans, pinned
# on the recorded trace slice (internlm2-1.8b shapes, v5e peaks).
PINNED = {
    "engine.pad_share": 25.0,
    "engine.decode_calls_per_step": 1.3333333333333333,
    "host.compiles_in_window": 1.0,
    "model.mfu": 20.243467368998985,
    "model.mfu.decode": 0.01392773068020305,
    "sparse_attention_roofline": None,
    "paged_flash_decode_roofline": 78.71437608794916,
    "device.idle_share": 0.0,
}


def test_earlier_readers_read_as_before():
    planes = json.loads(DATA.read_text())["planes"]
    red = trace.reduce_trace(
        planes, window_span="traced_window",
        bench_spans=("traced_window", "step"),
        kernels=("paged_flash_decode", "sparse_attention"))
    conf = build.load_json(METRICS.parent / "configs" / "internlm2-1.8b.json")
    rec = {"t0": 0.0, "t_close": 2.0, "compiles": [0.5],
           "stats0": {"padded_tokens": 0}, "stats1": {"padded_tokens": 8192},
           "steps": [
               {"t0": 0.1, "t1": 1.1, "prefill_lens": [24576],
                "decode_ctx": [24577], "decode_calls": 1},
               {"t0": 1.1, "t1": 1.4, "prefill_lens": [],
                "decode_ctx": [24578], "decode_calls": 1},
               {"t0": 1.4, "t1": 1.7, "prefill_lens": [],
                "decode_ctx": [24579], "decode_calls": 2},
               {"t0": 1.9, "t1": 2.2, "prefill_lens": [],
                "decode_ctx": [24580], "decode_calls": 1}]}
    fam = tiny.load_family()
    ctx = RunContext(rec, fam, fam.arch(conf), build.anchor_of(conf),
                     peaks.peaks_for("TPU v5 lite"), red)
    got = {name: _read(name, ctx) for name in PINNED}
    assert got == pytest.approx(PINNED)
    red["kernel_s"]["sparse_attention"] = 0.05
    assert _read("sparse_attention_roofline", ctx) == pytest.approx(
        56.01171499662944)


def test_dry_run_reports_the_new_host_metrics(tmp_path, capsys):
    path = tiny.write(tmp_path)
    bench = json.loads(path.read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append("tiny.tinydoc")
    path.write_text(json.dumps(bench))
    res = tiny.run(capsys, path, seed=2**31 + 11, trace=1)
    assert res["correct"] is True
    got = res["dry_layer_metrics"]
    assert 0.0 < got["sparse_attention.slot_use"] <= 100.0
    assert got["engine.prefill_dispatch_s"] > 0.0
    assert got["engine.decode_host_ms"] > 0.0
    # No device trace in a dry run, so no kernel time to divide by.
    assert "sparse_attention_kept_roofline" not in got
