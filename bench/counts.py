"""The shape-free parts of the work counts; each family counts its own
model's work with them (``bench/families/<family>.py``).

Counts follow the work the algorithm needs at the unpadded lengths, not
what an implementation happens to do: padding, recomputation and tiles
fetched twice are waste and are left out.  AnchorAttention's needed work
is its anchor region (the sink block plus the superblock's local window,
causally trimmed), a floor; a family's ``wave_work`` adds the stripes
that the program counted as kept.  Dense causal attention is never
counted as the sparse path's work.
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


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
