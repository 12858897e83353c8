"""Operations and bytes that the served work needs, counted from shapes.

Counts follow the work the algorithm needs at the unpadded lengths, not
what an implementation happens to do: padding, recomputation and tiles
fetched twice are waste and are left out.  AnchorAttention's needed work
is its anchor region (the sink block plus the superblock's local window,
causally trimmed); selected stripes are left out until the program
exposes its kept counts, so the sparse-attention counts are a floor.
Dense causal attention is never counted as the sparse path's work.
"""

from __future__ import annotations

import numpy as np

BF16 = 2  # bytes


def anchor_pairs(n: int, block_kv: int, superblock: int) -> int:
    """(query row, key) pairs in the anchor region of an n-token prompt.

    Row ``i`` of superblock ``k`` sees the sink keys ``[0, block_kv)``
    and its window ``[w, i]`` with ``w = max(block_kv, k·superblock)``,
    both causally: ``i + 1`` keys in superblock 0 and
    ``block_kv + i - k·superblock + 1`` keys after it.
    """
    i = np.arange(n, dtype=np.int64)
    k = i // superblock
    first = np.minimum(i + 1, block_kv) + np.maximum(0, i - block_kv + 1)
    later = block_kv + (i - k * superblock) + 1
    return int(np.where(k == 0, first, later).sum())


def anchor_kv_tokens(n: int, block_kv: int, superblock: int) -> int:
    """Keys that the anchor region reads, once per KV head and
    superblock: the sink block and the superblock's own window."""
    total = 0
    for k in range(-(-n // superblock)):
        lo = block_kv if k == 0 else k * superblock
        hi = min((k + 1) * superblock, n)
        total += min(block_kv, n) + max(0, hi - lo)
    return total


def sparse_attention_work(n: int, arch: dict, anchor: dict) -> tuple[float, float]:
    """(flops, bytes) of one layer's sparse attention over an n-token
    prompt: QK^T and PV over the anchor region for every query head;
    Q read and O written once, anchor K and V tiles read once per KV
    head and superblock, all in bf16."""
    hq, hkv, d = arch["heads"], arch["kv_heads"], arch["head_dim"]
    sb = anchor["block_q"] * anchor["step"]
    pairs = anchor_pairs(n, anchor["block_kv"], sb)
    flops = 4.0 * pairs * d * hq
    kv = anchor_kv_tokens(n, anchor["block_kv"], sb)
    nbytes = BF16 * (2 * n * hq * d + 2 * kv * hkv * d)
    return flops, nbytes


def decode_attention_work(ctx: int, arch: dict) -> tuple[float, float]:
    """(flops, bytes) of one layer's decode attention for one sequence
    with ``ctx`` cached positions: every query head against every cached
    key; the cache's K and V read once per KV head, q read, o written."""
    hq, hkv, d = arch["heads"], arch["kv_heads"], arch["head_dim"]
    flops = 4.0 * ctx * d * hq
    nbytes = BF16 * (2 * ctx * hkv * d + 2 * hq * d)
    return flops, nbytes


def matmul_params(arch: dict) -> int:
    """Weights that every token multiplies through, per token, in the
    decoder layers (projections and the gated MLP)."""
    d, hq, hkv, hd, f = (arch["d_model"], arch["heads"], arch["kv_heads"],
                         arch["head_dim"], arch["d_ff"])
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return arch["layers"] * per_layer


def prefill_flops(n: int, arch: dict, anchor: dict) -> float:
    """Model operations of a prefill of n tokens: the layers for every
    token, AnchorAttention's anchor region, and the LM head at the one
    position whose logits are served."""
    attn, _ = sparse_attention_work(n, arch, anchor)
    return (2.0 * matmul_params(arch) * n + arch["layers"] * attn
            + 2.0 * arch["d_model"] * arch["vocab"])


def decode_flops(ctx: int, arch: dict) -> float:
    """Model operations of one decode token with ``ctx`` cached
    positions (its own included)."""
    attn, _ = decode_attention_work(ctx, arch)
    return (2.0 * matmul_params(arch) + arch["layers"] * attn
            + 2.0 * arch["d_model"] * arch["vocab"])


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
