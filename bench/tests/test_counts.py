"""Operation and byte counts against hand-worked shapes, and the
``dense_gqa`` family's counts against values recorded before they moved
into the family file."""

import numpy as np
import pytest

from bench import build, counts
from bench.tests import tiny

FAMILY = tiny.load_family()

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
    flops, nbytes = FAMILY.sparse_attention_work(n, ARCH, ANCHOR)
    assert flops == 4.0 * pairs * 128 * 16
    kv = (128 + 1920) + (128 + 2048)
    assert nbytes == 2 * (2 * n * 16 * 128 + 2 * kv * 8 * 128)


def test_decode_and_model_counts():
    flops, nbytes = FAMILY.decode_attention_work(1000, ARCH)
    assert flops == 4.0 * 1000 * 128 * 16
    assert nbytes == 2 * (2 * 1000 * 8 * 128 + 2 * 16 * 128)
    per_layer = (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                 + 3 * 2048 * 8192)
    assert FAMILY.matmul_params(ARCH) == 24 * per_layer == 1_509_949_440
    assert FAMILY.decode_flops(1000, ARCH) == (
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


# Recorded from ``bench/counts.py`` and the kept-roofline reader before
# the counts moved into ``bench/families/dense_gqa.py``, at the cells'
# shapes and anchor settings.  Each wave is one prefill's counters as the
# capacity-bound selection of random weights keeps them; over the six
# prefills of a window they give PR 10's traced kept rooflines again.
WAVES = {
    "internlm2-1.8b": {"n_pad": 24576, "tokens": (24576,),
                       "stripes_kept": 16416768, "stripe_tiles_kept": 64128},
    "yi-9b-200k": {"n_pad": 32768, "tokens": (32768,),
                   "stripes_kept": 30277632, "stripe_tiles_kept": 29568},
}
GOLDEN = {
    "internlm2-1.8b": {
        "prefill_flops/24576": 79734567862272.0,
        "sparse_attention_work/24576": (229881413632.0, 307757056),
        "prefill_flops/32768": 106329810665472.0,
        "sparse_attention_work/32768": (307224379392.0, 410517504),
        "decode_flops/24577": 8230993920.0,
        "decode_attention_work/24577": (201334784.0, 100675584),
        "decode_flops/32800": 9847701504.0,
        "decode_attention_work/32800": (268697600.0, 134356992),
        "wave_work": (22731382849536.0, 11588861952),
    },
    "yi-9b-200k": {
        "prefill_flops/24576": 143421293461504.0,
        "sparse_attention_work/24576": (459762827264.0, 455868416),
        "prefill_flops/32768": 191251123011584.0,
        "sparse_attention_work/32768": (614448758784.0, 607911936),
        "decode_flops/24577": 12503482368.0,
        "decode_attention_work/24577": (402669568.0, 50350080),
        "decode_flops/32800": 14659092480.0,
        "decode_attention_work/32800": (537395200.0, 67190784),
        "wave_work": (41579578392576.0, 11664359424),
    },
}


@pytest.mark.parametrize("cell", sorted(GOLDEN))
def test_family_counts_are_the_recorded_ones(cell):
    conf = build.load_json(tiny.ROOT / "bench" / "configs" / f"{cell}.json")
    arch, an = FAMILY.arch(conf), build.anchor_of(conf)
    got = {"wave_work": FAMILY.wave_work(WAVES[cell], arch, an)}
    for n in (24576, 32768):
        got[f"prefill_flops/{n}"] = FAMILY.prefill_flops(n, arch, an)
        got[f"sparse_attention_work/{n}"] = FAMILY.sparse_attention_work(n, arch, an)
    for c in (24577, 32800):
        got[f"decode_flops/{c}"] = FAMILY.decode_flops(c, arch)
        got[f"decode_attention_work/{c}"] = FAMILY.decode_attention_work(c, arch)
    assert got == GOLDEN[cell]
