"""Index tables for index-driven sparse computation (DESIGN.md §3).

This module owns every piece of index plumbing shared by the sparse
kernels: stripe-index packing, tile (block-)compaction of stripe hit
masks into GQA-native index tables, the materialized-gather twin used by
baselines, and the flat-grid GQA fold used by the scalar-prefetch
BlockSpec index maps of :mod:`repro.kernels.sparse` and
:mod:`repro.kernels.decode`.

The central structure is :class:`StripeIndex`: instead of materializing
gathered ``(B, Hq, T_s, capacity, D)`` K/V copies in HBM (the pre-index
pipeline), the sparse stage receives *tables* — per KV head, per
superblock, the ids of the ``tile``-wide KV tiles that contain at least
one selected stripe, plus a per-QUERY-head validity bit for every packed
KV row.  The kernels then load those discrete tiles straight from the
original ``(B, Hkv, N, D)`` arrays (scalar-prefetch BlockSpec
indirection on TPU; a per-tile-slot gather inside an online-softmax scan
on XLA), so

* the gathered-KV footprint is ``O(Hkv * capacity)`` instead of
  ``O(Hq * capacity)`` — one KV tile feeds all ``Hq/Hkv`` query heads of
  its group, and
* selection stays **stripe-granular**: tiles are only the DMA
  granularity; every non-selected row inside a loaded tile is masked out
  of the math by ``valid`` (unlike MInference/FlexPrefill-style
  block-granular *selection*).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class StripeIndex(NamedTuple):
    """GQA-native stripe index tables for one sparse (Alg. 3) stage.

    Attributes:
      tile_idx: (B, Hkv, T_s, C_t) int32 — ids of the KV tiles holding
        this superblock's selected stripes (tile ``t`` covers KV rows
        ``[t*tile, (t+1)*tile)``), packed ascending.  Unoccupied slots
        hold 0 and are fully masked via ``valid``.
      tile_valid: (B, Hkv, T_s, C_t) int32 — slot occupancy.
      valid: (B, Hkv, G, T_s, C_t * tile) int32 — per-QUERY-head
        validity of each packed KV row (``G = Hq // Hkv``).  Row
        ``c*tile + t`` of superblock ``s`` refers to KV position
        ``tile_idx[..., s, c] * tile + t``.
    """

    tile_idx: jnp.ndarray
    tile_valid: jnp.ndarray
    valid: jnp.ndarray

    @property
    def tile(self) -> int:
        """KV rows per indexed tile (the DMA granularity)."""
        return self.valid.shape[-1] // self.tile_idx.shape[-1]

    @property
    def capacity(self) -> int:
        """Packed KV rows per superblock (tile slots × tile width)."""
        return self.valid.shape[-1]


def kv_head_index(bh, hq: int, hkv: int):
    """Flat ``batch*Hq`` program id → flat ``batch*Hkv`` KV row (GQA fold).

    The one GQA index computation shared by every kernel BlockSpec index
    map in this package (flash, anchor, stripe-select, sparse, decode):
    query head ``h`` of batch ``b`` reads KV head ``h // (hq // hkv)``.
    """
    return (bh // hq) * hkv + (bh % hq) // (hq // hkv)


def stripe_tile(n: int, block_c: int) -> int:
    """Largest tile width <= ``block_c`` that divides ``n`` exactly.

    The sparse kernels index KV in ``tile``-row blocks; an exact divisor
    keeps every tile in-bounds (no partial tail tiles to mask).
    """
    return math.gcd(n, max(1, block_c))


def prefetch_lengths(lengths, batch: int, n: int) -> jnp.ndarray:
    """Per-sequence valid-token counts as a ``(batch,)`` int32 operand.

    The one piece of varlen plumbing shared by every Pallas kernel in
    this package: the array rides in as a scalar-prefetch operand
    (``PrefetchScalarGridSpec``), so kernels read ``len_ref[b]`` from
    SMEM for their batch row ``b`` (``lengths=None`` ⇒ every row is
    fully valid).
    """
    if lengths is None:
        return jnp.full((batch,), n, jnp.int32)
    return lengths.astype(jnp.int32)


def select_capacity(n_tiles: int, n: int, capacity: int | None,
                    g: int, share: bool) -> int:
    """Tile-slot budget of a compact stripe selection.

    Each query head keeps at most ``min(capacity, n)`` stripes, which
    touch at most that many tiles; a KV group's union table therefore
    needs at most ``g``× that (1× under ``share``), clamped to the
    number of tiles that exist.
    """
    cap_s = n if capacity is None else min(capacity, n)
    return max(1, min(n_tiles, cap_s * (1 if share else g)))


def window_start_tokens(gs, cfg):
    """First local-window KV token of (global) superblock ``gs``.

    The one region-defining formula shared by the production
    identification/sweep stages (paper Alg. 1 line 8, 0-based:
    ``max(1, gs·step·r)·block_kv``).  ``gs`` may be an int, a traced
    scalar, or an array of superblock ids.  The reference oracles
    (core/, kernels/ref.py) keep their own independent copies on
    purpose — they must not share code with what they check.
    """
    return jnp.maximum(1, gs * cfg.step * cfg.r) * cfg.block_kv


def num_anchor_slots(tile: int, cfg) -> int:
    """Static tile-slot count of the guaranteed anchor region.

    Init (sink) block: ``ceil(block_kv / tile)`` tiles.  Local window:
    spans at most ``superblock_q`` tokens starting at an arbitrary
    offset, so at most ``ceil(superblock_q / tile) + 1`` tiles.
    """
    return -(-cfg.block_kv // tile) + (-(-cfg.superblock_q() // tile) + 1)


def anchor_tile_slots(nk: int, t_s: int, tile: int, cfg, sb0=0):
    """Guaranteed anchor-region slots for ``t_s`` superblocks (DESIGN.md §9).

    The fused sparse sweep computes the anchor region (KV block 0 + the
    superblock's local diagonal window) inside the same online-softmax
    pass as the selected stripes, so the anchor tiles are emitted as
    *leading* table slots rather than as a separate ``(m, l, acc)``
    resume state.  ``sb0`` (int or traced scalar) offsets the superblock
    ids for chunked prefill, where superblock ``s`` of the chunk is
    global superblock ``sb0 + s`` over the cache's ``nk`` keys.

    Returns ``(tile_idx, tile_valid, valid)`` with shapes ``(T_s, A)``,
    ``(T_s, A)``, ``(T_s, A * tile)`` (``A = num_anchor_slots``), int32,
    shared by every batch element and head.  Valid bits mark membership
    in the anchor region only; the causal (and varlen) trimming happens
    per query row inside the sparse sweep.  A window tile that also
    holds init-block or candidate positions carries disjoint valid bits,
    so duplicated tile ids never double-count a position.
    """
    if nk % tile:
        raise ValueError(f"tile ({tile}) must divide the KV length ({nk})")
    n_tiles = nk // tile
    a_init = min(-(-cfg.block_kv // tile), n_tiles)
    a_win = num_anchor_slots(tile, cfg) - -(-cfg.block_kv // tile)
    sb_q = cfg.superblock_q()
    gs = jnp.asarray(sb0) + jnp.arange(t_s)  # global superblock ids
    w_start = window_start_tokens(gs, cfg)  # (T_s,)
    w_end = jnp.minimum((gs + 1) * sb_q, nk)
    off = jnp.arange(tile)

    # Init (sink) slots: tiles overlapping [0, block_kv).
    init_idx = jnp.broadcast_to(
        jnp.arange(a_init, dtype=jnp.int32), (t_s, a_init))
    init_valid = (init_idx[..., None] * tile + off) < cfg.block_kv

    # Window slots: tiles overlapping [w_start(s), w_end(s)).
    win_idx = w_start[:, None] // tile + jnp.arange(a_win)  # (T_s, a_win)
    win_ok = win_idx * tile < w_end[:, None]
    win_idx = jnp.clip(win_idx, 0, n_tiles - 1).astype(jnp.int32)
    cols = win_idx[..., None] * tile + off  # (T_s, a_win, tile)
    win_valid = ((cols >= w_start[:, None, None])
                 & (cols < w_end[:, None, None]) & win_ok[..., None])

    tile_idx = jnp.concatenate([init_idx, win_idx], axis=1)
    tile_valid = jnp.concatenate(
        [jnp.ones_like(init_idx), win_ok.astype(jnp.int32)], axis=1)
    valid = jnp.concatenate([init_valid, win_valid], axis=1)
    return (tile_idx, tile_valid,
            valid.reshape(t_s, -1).astype(jnp.int32))


def merge_anchor_slots(
    sel: StripeIndex, nk: int, cfg, sb0=0
) -> StripeIndex:
    """Prepend the guaranteed anchor slots to a compact stripe selection.

    ``sel`` holds ONLY the difference-aware selected tiles (the
    ``stripe_select`` op output); the result is the full table the fused
    sparse sweep consumes: ``A`` anchor slots (identical across batch,
    heads, and query-group members) followed by the selected slots.
    """
    b, hkv, t_s, _ = sel.tile_idx.shape
    g = sel.valid.shape[2]
    tile = sel.tile
    a_idx, a_tv, a_valid = anchor_tile_slots(nk, t_s, tile, cfg, sb0=sb0)
    a = a_idx.shape[1]
    bcast = lambda x, shape: jnp.broadcast_to(x, shape)  # noqa: E731
    return StripeIndex(
        jnp.concatenate(
            [bcast(a_idx[None, None], (b, hkv, t_s, a)), sel.tile_idx],
            axis=-1),
        jnp.concatenate(
            [bcast(a_tv[None, None], (b, hkv, t_s, a)), sel.tile_valid],
            axis=-1),
        jnp.concatenate(
            [bcast(a_valid[None, None, None], (b, hkv, g, t_s, a * tile)),
             sel.valid],
            axis=-1),
    )


def pack_stripe_indices(
    hit: jnp.ndarray, capacity: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Compact a (…, T_s, N) int32 hit-mask into (…, T_s, capacity) indices.

    Position-ordered packing: priority = hit*2 - pos/N, so selected stripes
    come first (ascending position), padding after.  ``capacity`` may
    exceed ``N`` (e.g. a tile-padded capacity over a non-tile-multiple
    ``N``): the extra slots are padded with ``idx=0, valid=0`` instead of
    feeding ``jax.lax.top_k`` an out-of-range ``k``.  Returns
    ``(idx, valid)``.
    """
    n = hit.shape[-1]
    k_eff = min(capacity, n)
    pos = jnp.arange(n, dtype=jnp.float32) / n
    priority = hit.astype(jnp.float32) * 2.0 - pos
    _, idx = jax.lax.top_k(priority, k_eff)
    valid = jnp.take_along_axis(hit, idx, axis=-1)
    idx = idx.astype(jnp.int32)
    valid = valid.astype(jnp.int32)
    if capacity > k_eff:
        pad_shape = (*hit.shape[:-1], capacity - k_eff)
        idx = jnp.concatenate([idx, jnp.zeros(pad_shape, jnp.int32)], axis=-1)
        valid = jnp.concatenate(
            [valid, jnp.zeros(pad_shape, jnp.int32)], axis=-1)
    return idx, valid


def compact_stripe_tiles(
    hit: jnp.ndarray,
    hkv: int,
    tile: int,
    capacity: int | None = None,
    share: bool = False,
) -> tuple[StripeIndex, jnp.ndarray]:
    """Tile-compact a per-query-head stripe hit mask into GQA-native tables.

    Args:
      hit: (B, Hq, T_s, N) int32/bool stripe hit mask (Alg. 2 output).
      hkv: number of KV heads (``Hq % hkv == 0``).
      tile: KV rows per indexed tile; must divide ``N``.
      capacity: per-superblock, per-query-head stripe budget (``None`` =
        all candidates; exact).  Overflow keeps each head's earliest
        stripes by position — the same per-head semantics as the
        pre-index pipeline (tables then hold the union of the clamped
        per-head selections, so a group's table may span up to
        ``G * capacity`` stripes).  With ``share`` the budget applies to
        the shared (union) selection.
      share: ``AnchorConfig.share_kv_groups`` — every query head of a
        group uses the unioned selection (validity identical across G).

    Returns:
      (tables, counts): the :class:`StripeIndex` tables and the per-head
      kept-stripe counts (B, Hq, T_s) for sparsity accounting.

    Packing is sort-free (cumsum rank + scatter, §Perf iteration C3) and
    position-ascending, which is what makes the tile-slot scan of the
    consumers bit-stable: a query head's kept stripes appear in the same
    relative order whether packed alone (Hq == Hkv) or inside its
    group's union, and slots foreign to a head are exact no-ops.
    """
    b, hq, t_s, n = hit.shape
    if n % tile:
        raise ValueError(f"tile ({tile}) must divide N ({n})")
    g = hq // hkv
    n_tiles = n // tile
    hitb = hit.astype(bool).reshape(b, hkv, g, t_s, n)
    if share:
        hitb = jnp.broadcast_to(hitb.any(axis=2, keepdims=True), hitb.shape)
    cap_s = n if capacity is None else min(capacity, n)
    if cap_s < n:
        # Per-HEAD budget (matches the pre-index pipeline: each query
        # head keeps its own earliest `capacity` stripes); under `share`
        # all heads hold the same mask so this is the union budget.
        rank = jnp.cumsum(hitb.astype(jnp.int32), axis=-1) - 1
        kept_h = hitb & (rank < cap_s)
    else:
        kept_h = hitb  # (B, Hkv, G, T_s, N)
    keep = kept_h.any(axis=2)  # tiles to load: union of kept selections

    # Tile-level compaction of the union: which tiles must be loaded.
    tmask = keep.reshape(b, hkv, t_s, n_tiles, tile).any(axis=-1)
    # Each head's cap_s kept stripes touch at most cap_s tiles; the
    # group union at most `groups_in_table * cap_s` (1 under `share`).
    c_t = min(n_tiles, cap_s * (1 if share else g))
    trank = jnp.cumsum(tmask.astype(jnp.int32), axis=-1) - 1
    slot = jnp.where(tmask & (trank < c_t), trank, c_t)  # overflow -> dump
    bi = jnp.arange(b)[:, None, None, None]
    ki = jnp.arange(hkv)[None, :, None, None]
    si = jnp.arange(t_s)[None, None, :, None]
    tids = jnp.broadcast_to(
        jnp.arange(n_tiles, dtype=jnp.int32)[None, None, None, :], slot.shape)
    buf = jnp.zeros((b, hkv, t_s, c_t + 1), jnp.int32)
    tile_idx = buf.at[bi, ki, si, slot].set(tids, mode="drop")[..., :c_t]
    tcount = jnp.minimum(tmask.sum(axis=-1), c_t)
    tile_valid = (jnp.arange(c_t)[None, None, None, :]
                  < tcount[..., None]).astype(jnp.int32)

    # Per-slot, per-query-head row validity: gather each head's kept bits
    # at the packed tiles, masking unoccupied slots (their tile_idx of 0
    # aliases a real tile).
    kept_t = kept_h.reshape(b, hkv, g, t_s, n_tiles, tile)
    idx6 = jnp.broadcast_to(
        tile_idx[:, :, None, :, :, None], (b, hkv, g, t_s, c_t, 1))
    gathered = jnp.take_along_axis(kept_t, idx6, axis=4)  # (..., C_t, tile)
    occupied = tile_valid[:, :, None, :, :, None].astype(bool)
    valid = (gathered & occupied).reshape(b, hkv, g, t_s, c_t * tile)

    counts = kept_h.sum(axis=-1).reshape(b, hq, t_s).astype(jnp.int32)
    return (
        StripeIndex(tile_idx.astype(jnp.int32), tile_valid,
                    valid.astype(jnp.int32)),
        counts,
    )


def gather_stripe_tiles(
    kv: jnp.ndarray, tables: StripeIndex
) -> jnp.ndarray:
    """Materialize the indexed tiles: (B, Hkv, N, D) → (B, Hkv, T_s, C, D).

    The gather-based twin of the index-driven loaders — used by the
    baseline in ``benchmarks/prefill_index.py`` and by the bit-exactness
    tests (gather-then-compute must equal compute-with-inline-gather).
    Note the result is Hkv-wide; the pre-index pipeline materialized this
    at Hq width *after* a ``jnp.repeat`` of K/V.
    """
    b, hkv, n, d = kv.shape
    tile = tables.tile
    t_s, c_t = tables.tile_idx.shape[2], tables.tile_idx.shape[3]
    kb = kv.reshape(b, hkv, 1, n // tile, tile, d)
    idx = jnp.broadcast_to(
        tables.tile_idx[..., None, None], (b, hkv, t_s, c_t, 1, 1))
    out = jnp.take_along_axis(kb, idx, axis=3)  # (B, Hkv, T_s, C_t, tile, D)
    return out.reshape(b, hkv, t_s, c_t * tile, d)
