"""The trace reduction on a small recorded TPU v5e trace."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace

DATA = Path(__file__).parent / "data" / "trace_slice.json"


@pytest.fixture(scope="module")
def planes():
    return json.loads(DATA.read_text())["planes"]


def _busy_by_microsecond(planes, w0, w1):
    ops = [e for p, lines in planes if p.startswith("/device:TPU")
           for ln, evs in lines if ln == "XLA Ops" for e in evs]
    grid = np.zeros(int((w1 - w0) // 1000), bool)
    for _, s, d in ops:
        a = int(max(0, (s - w0) // 1000))
        b = int(min(len(grid), -(-(s + d - w0) // 1000)))
        if b > a:
            grid[a:b] = True
    return grid.sum() * 1e-6


def test_op_name():
    assert trace.op_name("%paged_flash_decode.11 = bf16[16,1,128] custom-call(") \
        == "paged_flash_decode"
    assert trace.op_name("%bitcast_dynamic-update-slice_fusion.5 = bf16[2]") \
        == "bitcast_dynamic-update-slice_fusion"
    assert trace.op_name("jit_step") == "jit_step"


def test_reduction(planes):
    red = trace.reduce_trace(planes, window_span="traced_window",
                             bench_spans=("traced_window", "step"),
                             kernels=("paged_flash_decode", "sparse_attention"))
    w0 = 2051659146.0
    assert red["window_s"] == pytest.approx(0.03)
    # The while loop spans the whole window: the device is busy throughout.
    assert red["busy_s"] == pytest.approx(_busy_by_microsecond(
        planes, w0, w0 + 3e7), abs=2e-6)
    # Only the part of the kernel's event inside the window counts.
    assert red["kernel_s"]["paged_flash_decode"] == pytest.approx(
        (2051304617.0 + 11598931.0 - w0) / 1e9)
    assert red["kernel_s"]["sparse_attention"] == 0.0
    names = [n for n, _ in red["device_ops"]]
    assert "while" not in names and names[0] == "paged_flash_decode"
    assert len(red["device_ops"]) <= trace.TOP
    assert red["chips"] == 1


def test_idle_gaps_are_named_by_host_spans(planes):
    # Without the while loop the device idles between its operations.
    cut = [[p, [[ln, [e for e in evs if not e[0].startswith("%while")]]
                for ln, evs in lines]] for p, lines in planes]
    red = trace.reduce_trace(cut, window_span="traced_window",
                             bench_spans=("traced_window", "step"))
    assert red["busy_s"] < red["window_s"]
    gaps = red["idle_gaps"]
    assert gaps and all(s > 0 for _, s in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert gaps[0][0].startswith("traced_window")
    total_idle = red["window_s"] - red["busy_s"]
    assert sum(s for _, s in gaps) <= total_idle + 1e-9


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_trace([["/host:CPU", [["python3", []]]]])
