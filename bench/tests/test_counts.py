"""Operation and byte counts against hand-worked shapes."""

import numpy as np
import pytest

from bench import counts

ARCH = {"layers": 24, "d_model": 2048, "heads": 16, "kv_heads": 8,
        "head_dim": 128, "d_ff": 8192, "vocab": 92544}
ANCHOR = {"block_q": 128, "block_kv": 128, "step": 16, "theta": 12.0,
          "capacity": 4096}


def _pairs_by_mask(n, bkv, sb):
    i = np.arange(n)[:, None]
    j = np.arange(n)[None, :]
    k = i // sb
    w = np.maximum(bkv, k * sb)
    return int((((j < bkv) | (j >= w)) & (j <= i)).sum())


def test_anchor_pairs_hand_worked():
    # Superblock 0 (rows 0-3) is causal: 1+2+3+4; rows 4-7 see the
    # 2-key sink and their window from 4: 3+4+5+6.
    assert counts.anchor_pairs(8, 2, 4) == 28
    # A ragged last superblock counts only its valid rows: 28 + (2+1).
    assert counts.anchor_pairs(9, 2, 4) == 31


@pytest.mark.parametrize("n", [1, 100, 2048, 2049, 5000, 24576])
def test_anchor_pairs_match_the_mask(n):
    assert counts.anchor_pairs(n, 128, 2048) == _pairs_by_mask(n, 128, 2048)


def test_anchor_kv_tokens():
    # n=9, sink 2, superblocks of 4: [sink 2 + window 2..3], [2 + 4..7],
    # [2 + 8].
    assert counts.anchor_kv_tokens(9, 2, 4) == (2 + 2) + (2 + 4) + (2 + 1)


def test_sparse_attention_work():
    n = 4096
    pairs = counts.anchor_pairs(n, 128, 2048)
    flops, nbytes = counts.sparse_attention_work(n, ARCH, ANCHOR)
    assert flops == 4.0 * pairs * 128 * 16
    kv = (128 + 1920) + (128 + 2048)
    assert nbytes == 2 * (2 * n * 16 * 128 + 2 * kv * 8 * 128)


def test_decode_and_model_counts():
    flops, nbytes = counts.decode_attention_work(1000, ARCH)
    assert flops == 4.0 * 1000 * 128 * 16
    assert nbytes == 2 * (2 * 1000 * 8 * 128 + 2 * 16 * 128)
    per_layer = (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                 + 3 * 2048 * 8192)
    assert counts.matmul_params(ARCH) == 24 * per_layer == 1_509_949_440
    assert counts.decode_flops(1000, ARCH) == (
        2.0 * 24 * per_layer + 24 * flops + 2.0 * 2048 * 92544)


def test_roofline_seconds_takes_the_larger_bound():
    peaks = {"bf16_flops_s": 100.0, "hbm_bytes_s": 10.0}
    assert counts.roofline_seconds(1000.0, 50.0, peaks) == 10.0
    assert counts.roofline_seconds(100.0, 50.0, peaks) == 5.0


def test_unknown_device_is_an_error():
    from bench.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
