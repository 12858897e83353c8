"""Serving engine: AnchorAttention prefill + KV-cache decode with
continuous batching and a paged KV-cache subsystem.

The engine keeps a fixed pool of ``max_batch`` slots.  Incoming requests
prefill with the paper's AnchorAttention (the whole point: prefill is the
quadratic phase), then join the decode batch; finished sequences free
their resources for queued requests.  All compute paths are jitted model
fns — the scheduler is plain Python (it runs on the host in production
too).

Two KV-cache layouts (``cache_layout=``, see :mod:`repro.models.cache`):

* ``"dense"`` — one ``(max_batch, max_len)`` slab per layer.  Every slot
  pays ``max_len`` of HBM whether it uses it or not.  Recurrent-state
  and MLA architectures always use this layout.
* ``"paged"`` — one shared pool of fixed-size pages behind per-sequence
  page tables (:mod:`repro.serving.kv_pool`).  Admission is by free-page
  budget rather than free slots; pages are reclaimed on retirement;
  requests sharing a prompt prefix map their tables onto the same
  physical pages (:mod:`repro.serving.prefix_cache`, copy-on-write as a
  backstop); and when the pool runs dry the engine first evicts cold
  prefix-cache pages, then preempts the youngest sequence
  (recompute-on-readmission: the prompt re-prefills and the generated
  tokens replay through ordinary decode steps, reconstructing the cache
  bit-exactly under any attention config).

Chunked prefill (``chunk_tokens=``, paged layout): prompts longer than
the threshold prefill in superblock/page-aligned chunks, one chunk per
engine step, interleaved with decode — a single 128k prompt no longer
head-of-line-blocks the decode batch.  Chunks run the engine's own
attention algorithm (:func:`repro.models.transformer.
stack_chunk_prefill`): under an anchor spec each chunk goes through the
index-driven sparse entry point
(:func:`repro.kernels.ops.chunk_anchor_attention`) against its gathered
cache view — long prompts keep AnchorAttention prefill instead of
falling back to dense history attention (counted in
``stats["sparse_chunks"]``); dense specs keep the dense chunk path.
Pages already covered by a prefix hit are skipped, so a shared system
prompt is never recomputed on this path.

Variable-length prefill: attention-only architectures right-pad any mix
of prompt lengths up to the next AnchorAttention superblock boundary and
run ONE batched padded prefill per admission wave (``lengths`` masking —
see :mod:`repro.core.spec`).  Architectures with recurrent state
(mamba/hybrid) keep the per-request unpadded path.

Observability: ``engine.stats`` counts prefill requests, batched padded
calls, padded throwaway tokens, dense fallbacks, decode steps,
length-truncated retirements, and the paged-subsystem counters
(pages_in_use / pages_hwm, prefix_hits, shared_pages, chunked_prefills,
preemptions, ...).  ``engine.snapshot()`` returns a self-consistent copy
with the live gauges refreshed.

Sparse prefill adds four counters, counted on the device from the very
tables the sparse sweep consumed (``repro.kernels.indexing.
sparse_counters``) and fetched with the first-token argmax, so no sync
is added: ``stripes_kept`` (kept stripes over sequences, query heads,
superblocks and layers), ``stripe_tiles_kept`` (occupied selection
slots), ``sparse_slots_used`` (occupied slots, anchor slots included,
once per query block that walks them) and ``sparse_slots_visited``
(every slot the sweep's grid walks).  Dense waves add nothing.

Every step is timed by spans (:mod:`repro.serving.tracing`), named in
``SPAN_NAMES``: ``engine.step`` is the root; under it ``engine.admit``
(with one ``engine.prefill`` per wave, whose children are
``engine.prefill.dispatch`` — the model call up to its return: tracing,
lowering, cache loads, enqueue — ``engine.prefill.sync``, the
first-token fetch, and ``engine.prefill.store``, the page scatter),
``engine.chunk`` per prefill chunk, ``engine.decode`` per position group
(children ``engine.decode.pages``, ``engine.decode.dispatch``,
``engine.decode.sync``) and ``engine.retire`` per finished request.
Spans of one request carry its ``uid``.  They land on the profiler's host
plane when a trace records, and always in an in-memory log that
``engine.spans(t0, t1)`` returns.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import AnchorConfig
from repro.core.spec import AttentionSpec, resolve_attention_spec
from repro.kernels.indexing import SPARSE_COUNTERS
from repro.models import cache as cache_lib
from repro.models import model as model_lib
from repro.models.cache import NULL_PAGE, PagedKVLayout
from repro.models.config import ModelConfig
from repro.serving import tracing
from repro.serving.kv_pool import PagePool
from repro.serving.prefix_cache import PrefixCache
from repro.serving.tracing import span

CACHE_LAYOUTS = ("dense", "paged")
SPAN_NAMES = (
    "engine.step",
    "engine.admit",
    "engine.prefill",
    "engine.prefill.dispatch",
    "engine.prefill.sync",
    "engine.prefill.store",
    "engine.chunk",
    "engine.decode",
    "engine.decode.pages",
    "engine.decode.dispatch",
    "engine.decode.sync",
    "engine.retire",
)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class _ChunkState:
    """Progress of an in-flight chunked prefill occupying a slot."""

    req: Request
    tokens: np.ndarray  # full token sequence being prefilled
    pos: int  # next chunk starts here (page-aligned)
    shared_pages: int  # leading pages satisfied by the prefix cache
    append_first: bool  # fresh request: append argmax of the last chunk
    counters: Any = None  # sparse work of the chunks so far, on device


class ServingEngine:
    def __init__(
        self,
        params: Any,
        cfg: ModelConfig,
        max_batch: int = 8,
        max_len: int = 2048,
        spec: AttentionSpec | None = None,
        anchor_cfg: AnchorConfig | None = None,
        attn_impl: str | None = None,
        greedy: bool = True,
        batch_prefill: bool = True,
        cache_layout: str = "dense",
        page_size: int = 16,
        num_pages: int | None = None,
        prefix_sharing: bool = True,
        chunk_tokens: int | None = None,
    ):
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        spec = resolve_attention_spec(spec, attn_impl, anchor_cfg,
                                      default_algorithm="anchor")
        if not cfg.has_attention:
            spec = spec.with_algorithm("dense")
        self.spec = spec
        self.greedy = greedy
        # Padded batched prefill needs every mixer to mask by `lengths`;
        # recurrent mixers (mamba) would scan over the padding.
        self._attention_only = all(
            mixer == "attn" for mixer, _ in cfg.group_layout())
        self.batch_prefill = batch_prefill and self._attention_only

        if cache_layout not in CACHE_LAYOUTS:
            raise ValueError(f"unknown cache_layout {cache_layout!r}; "
                             f"expected one of {CACHE_LAYOUTS}")
        self.cache_layout = cache_layout
        self.queue: collections.deque[Request] = collections.deque()
        self.slot_pos = np.zeros(max_batch, np.int32)  # next write position
        self.slot_req: list[Request | None] = [None] * max_batch
        self.stats: dict[str, int] = {
            "prefill_requests": 0,
            "batched_prefills": 0,
            "dense_fallbacks": 0,
            "padded_tokens": 0,
            "decode_steps": 0,
            "length_truncations": 0,
            # Paged-subsystem counters (zero under the dense layout).
            "pages_in_use": 0,
            "pages_hwm": 0,
            "prefix_queries": 0,
            "prefix_hits": 0,
            "shared_pages": 0,
            "chunked_prefills": 0,
            "prefill_chunks": 0,
            "sparse_chunks": 0,
            "preemptions": 0,
            "cow_copies": 0,
            "prefix_evictions": 0,
            "rejections": 0,
            # Sparse-prefill work (module doc), summed over layers.
            **{name: 0 for name in SPARSE_COUNTERS},
        }
        self._rejected: list[Request] = []

        if cache_layout == "paged":
            self._init_paged(page_size, num_pages, prefix_sharing,
                             chunk_tokens)
        else:
            self.pool = None
            self.prefix = None
            self.chunk_tokens = None
            self.cache = model_lib.init_cache(cfg, max_batch, max_len)

        self._decode = jax.jit(
            lambda p, c, t, pos, act: model_lib.decode_step(
                p, c, t, pos, cfg, active=act))
        kv_backend = spec.backend
        self._decode_paged = jax.jit(
            lambda p, c, t, pos, act, pt: model_lib.decode_step(
                p, c, t, pos, cfg, active=act, page_tables=pt,
                kv_backend=kv_backend))
        # Chunked prefill runs the engine's own attention algorithm: an
        # anchor spec keeps chunks on the index-driven sparse path
        # (chunk_tokens is validated superblock-aligned at init, and
        # chunk starts are chunk-aligned), dense stays dense.
        chunk_spec = self.spec
        self._chunk = jax.jit(
            lambda p, t, c, pos, live: model_lib.prefill_chunk(
                p, t, c, cfg, pos, spec=chunk_spec, live=live))
        self._admit_clock = 0  # admission order, for youngest-first preemption
        self._slot_tick = np.zeros(max_batch, np.int64)
        self._slot_plen = np.zeros(max_batch, np.int64)  # prompt length
        self._chunking: dict[int, _ChunkState] = {}

    def _init_paged(self, page_size: int, num_pages: int | None,
                    prefix_sharing: bool, chunk_tokens: int | None) -> None:
        if not cache_lib.supports_paged(self.cfg):
            raise ValueError(
                f"{self.cfg.name}: paged KV layout needs a GQA "
                "attention-only arch; recurrent-state/MLA families keep "
                "cache_layout='dense' (see repro.models.cache)")
        if self.max_len % page_size:
            raise ValueError(
                f"max_len ({self.max_len}) must be a multiple of "
                f"page_size ({page_size})")
        if (self.spec.algorithm == "anchor"
                and self.spec.anchor.superblock_q() % page_size):
            raise ValueError(
                f"page_size ({page_size}) must divide the anchor "
                f"superblock ({self.spec.anchor.superblock_q()}) so padded "
                "sparse prefill stays page-aligned")
        if chunk_tokens is not None:
            if chunk_tokens % page_size:
                raise ValueError(
                    f"chunk_tokens ({chunk_tokens}) must be a multiple of "
                    f"page_size ({page_size})")
            if (self.spec.algorithm == "anchor"
                    and chunk_tokens % self.spec.anchor.superblock_q()):
                raise ValueError(
                    f"chunk_tokens ({chunk_tokens}) must be superblock-"
                    f"aligned ({self.spec.anchor.superblock_q()})")
            if self.max_len % chunk_tokens:
                # Chunk windows are a fixed chunk_tokens wide and start at
                # chunk-aligned positions; a window overrunning max_len
                # would make the jitted dynamic_update_slice clamp its
                # start and overwrite history K/V.
                raise ValueError(
                    f"max_len ({self.max_len}) must be a multiple of "
                    f"chunk_tokens ({chunk_tokens})")
        self.chunk_tokens = chunk_tokens
        pages_per_seq = self.max_len // page_size
        if num_pages is None:
            num_pages = self.max_batch * pages_per_seq
        self.layout = PagedKVLayout(page_size=page_size, num_pages=num_pages,
                                    pages_per_seq=pages_per_seq)
        self.pool = PagePool(num_pages, page_size)
        self.prefix = PrefixCache(self.pool) if prefix_sharing else None
        self.cache = model_lib.init_cache(
            self.cfg, self.max_batch, self.max_len, layout=self.layout)
        self._pt = np.zeros((self.max_batch, pages_per_seq), np.int32)

    # -------------------------------------------------------- lifecycle ----

    def submit(self, req: Request) -> None:
        if len(req.prompt) + 1 > self.max_len:
            raise ValueError(
                f"request {req.uid}: {len(req.prompt)} prompt tokens do not "
                f"fit max_len={self.max_len}")
        self.queue.append(req)

    def spans(self, t0: float | None = None,
              t1: float | None = None) -> list[tracing.Span]:
        """The span log (module doc) of the window ``[t0, t1]``
        (``time.perf_counter()`` seconds; either bound may be open)."""
        return tracing.spans(t0, t1)

    @property
    def idle(self) -> bool:
        """No queued, prefilling, or decoding work left."""
        return (not self.queue and not self._chunking
                and all(r is None for r in self.slot_req))

    def _admit(self) -> None:
        free = [s for s in range(self.max_batch) if self.slot_req[s] is None
                and s not in self._chunking]
        if not free or not self.queue:
            return
        if self.cache_layout == "paged":
            self._admit_paged(free)
            return
        if not self.batch_prefill:
            for slot in free:
                if not self.queue:
                    break
                self._prefill_single(slot, self.queue.popleft())
            return
        wave: list[Request] = []
        while self.queue and len(wave) < len(free):
            wave.append(self.queue.popleft())
        self._prefill_batch(free[: len(wave)], wave)

    # ----------------------------------------------------- paged admit ----

    def _reserve_pages(self, tokens: np.ndarray,
                       tag: str | None) -> tuple[list[int], int] | None:
        """Page-budget admission: map the prompt's prefix onto shared
        pages, allocate the rest (evicting cold prefix pages if needed).

        ``tag`` names the attention math that will produce this prompt's
        KV — only same-tag pages are shared (``None``: no prefix
        participation at all, e.g. dense-fallback anomaly waves).
        Returns (page ids covering ceil(len/page_size) pages, number of
        shared leading pages), or None when the pool cannot cover the
        request even after eviction."""
        pool = self.pool
        shared: list[int] = []
        if self.prefix is not None and tag is not None:
            shared = self.prefix.match(tokens, tag)
            self.stats["prefix_queries"] = self.prefix.stats.queries
            self.stats["prefix_hits"] = self.prefix.stats.hits
            self.stats["shared_pages"] = self.prefix.stats.shared_pages
        need = pool.pages_for_tokens(len(tokens)) - len(shared)
        if need > pool.free_pages and self.prefix is not None:
            self.stats["prefix_evictions"] += self.prefix.evict(need)
        if need > pool.free_pages:
            for page in shared:  # undo the match refs; retry later
                pool.release(page)
            return None
        return shared + pool.alloc_many(need), len(shared)

    def _prefix_tag(self, n_tokens: int) -> str | None:
        """Which prefix-cache namespace a prompt's pages belong to.

        Pages may only be shared between requests whose prefill computes
        the prefix KV with the *same attention math* — mixing would let a
        request decode against KV it would not itself have produced.
        Anchor prefill at two padded wave lengths agrees to a few f32 ulp
        (``atol = rtol = 1e-6`` on xla, tested; the dot kernel, not the
        selection, is what differs — DESIGN.md §3), so one tag per
        algorithm suffices:

        * chunked prompts — ``"chunked"`` (checked FIRST: with chunking
          on, a long prompt always chunks — under an anchor spec the
          chunks run the index-driven sparse path, so prompts whose
          padded length exceeds ``max_len`` no longer fall back to a
          dense one-shot prefill),
        * normal waves — the engine's spec algorithm,
        * dense-fallback anomaly waves — ``None``: no sharing; they are
          admitted as singleton waves so they never drag an anchor wave
          to dense.
        """
        if self.chunk_tokens is not None and n_tokens > self.chunk_tokens:
            return "chunked"
        if (self.spec.algorithm == "anchor"
                and self.spec.anchor.prefill_pad_len(n_tokens) > self.max_len):
            return None
        return self.spec.algorithm

    def _admit_paged(self, free: list[int]) -> None:
        wave_slots: list[int] = []
        wave: list[Request] = []
        wave_meta: list[tuple[np.ndarray, int]] = []  # (tokens, shared)
        for slot in free:
            req = None
            while self.queue:
                cand = self.queue[0]
                if len(cand.prompt) + 1 > self.max_len:
                    # submit() rejects these up front; if one reaches the
                    # queue anyway (direct append), dropping it beats
                    # raising here — a raise from step() would leave it at
                    # the queue head and permanently wedge every other
                    # request.
                    self.queue.popleft()
                    cand.done = True
                    self._rejected.append(cand)
                    self.stats["rejections"] += 1
                    continue
                req = cand
                break
            if req is None:
                break
            tokens = np.asarray(req.prompt, np.int32)
            tag = self._prefix_tag(len(tokens))
            reserved = self._reserve_pages(tokens, tag)
            if reserved is None:
                break  # pool exhausted — leave the request queued
            self.queue.popleft()
            pages, shared = reserved
            row = np.zeros(self.layout.pages_per_seq, np.int32)
            row[: len(pages)] = pages
            self._pt[slot] = row
            self._admit_clock += 1
            self._slot_tick[slot] = self._admit_clock
            if tag == "chunked":
                # Skip fully prefix-shared tokens, but keep every chunk
                # window chunk-aligned (a shared prefix is rarely a chunk
                # multiple): round DOWN to the last chunk boundary inside
                # the shared region.  Together with max_len % chunk_tokens
                # == 0 this guarantees no window ever overruns the
                # sequence view.  min(..., len-1) keeps at least one live
                # token when the whole prompt matched.
                start = (min(shared * self.pool.page_size, len(tokens) - 1)
                         // self.chunk_tokens * self.chunk_tokens)
                self._chunking[slot] = _ChunkState(
                    req=req, tokens=tokens,
                    pos=start, shared_pages=shared,
                    append_first=not req.generated)
                self.stats["chunked_prefills"] += 1
                self.stats["prefill_requests"] += 1
            elif tag is None:
                # Dense-fallback anomaly: its own singleton wave, so the
                # fallback never drags same-wave anchor prompts to dense.
                self._prefill_batch([slot], [req], meta=[(tokens, 0)])
            else:
                wave_slots.append(slot)
                wave.append(req)
                wave_meta.append((tokens, shared))
                if self.prefix is not None:
                    # Index this prompt's full pages NOW, not after the
                    # prefill: later requests of the SAME admission wave
                    # then share them (the wave's scatter fills every
                    # indexed page before any decode reads it).  Chunked
                    # prompts fill their pages over many steps, so they
                    # only insert on completion.
                    full = len(tokens) // self.pool.page_size
                    self.prefix.insert(tokens, self._pt[slot, :full], tag)
        if wave:
            self._prefill_batch(wave_slots, wave, meta=wave_meta)
        self._touch_gauges()

    # ------------------------------------------------- batched prefill ----

    def _padded_len(self, n_max: int) -> tuple[int, str]:
        """(padded length, algorithm) for a prefill wave of max length
        ``n_max``.

        Anchor runs at ``AnchorConfig.prefill_pad_len(n_max)``; if that
        exceeds the engine's cache, fall back to dense — and count it, so
        the degradation is observable.  The paged layout additionally
        rounds up to a page boundary (a no-op for anchor, whose superblock
        is page-aligned by construction; the varlen `lengths` masking
        keeps outputs bit-identical across padded lengths on xla).
        """
        if self.spec.algorithm != "anchor":
            return self._page_align(n_max), "dense"
        n_pad = self.spec.anchor.prefill_pad_len(n_max)
        if n_pad > self.max_len:
            return self._page_align(n_max), "dense"
        return n_pad, "anchor"

    def _page_align(self, n: int) -> int:
        if self.cache_layout != "paged":
            return n
        ps = self.pool.page_size
        return min(-(-n // ps) * ps, self.max_len)

    def _prefill_batch(
        self,
        slots: list[int],
        reqs: list[Request],
        meta: list[tuple[np.ndarray, int]] | None = None,
    ) -> None:
        """ONE right-padded batched prefill for a whole admission wave.

        Each request's cache is spliced into its slot (dense layout) or
        scattered onto its reserved pages (paged layout; pages covered by
        a prefix hit are skipped — their content is already there);
        first-token logits are read at each sequence's own last valid
        position.
        """
        if meta is None:
            meta = [(np.asarray(r.prompt, np.int32), 0) for r in reqs]
        seqs = [tokens for tokens, _ in meta]
        lens = [len(t) for t in seqs]
        n_pad, algorithm = self._padded_len(max(lens))
        if algorithm == "dense" and self.spec.algorithm == "anchor":
            self.stats["dense_fallbacks"] += len(reqs)
        spec = self.spec.with_algorithm(algorithm).padded()
        toks = np.zeros((len(reqs), n_pad), np.int32)
        for j, seq in enumerate(seqs):
            toks[j, : lens[j]] = seq
        with span("engine.prefill", uids=tuple(r.uid for r in reqs),
                  n_pad=n_pad, tokens=tuple(lens)) as sp:
            with span("engine.prefill.dispatch"):
                logits, (pcache, counters) = model_lib.prefill(
                    self.params, jnp.asarray(toks), self.cfg,
                    spec=spec, lengths=jnp.asarray(lens, jnp.int32),
                    return_counters=True)
            with span("engine.prefill.sync"):  # one device->host transfer
                first_toks, counters = jax.device_get(
                    (jnp.argmax(logits, axis=-1), counters))
            self._count_sparse(counters, sp)
            with span("engine.prefill.store"):
                if self.cache_layout == "paged":
                    self._store_prefill_pages(slots, meta, n_pad, pcache)
                else:
                    self.cache = self._insert_cache(
                        self.cache, pcache, jnp.asarray(slots, jnp.int32))
        self.stats["prefill_requests"] += len(reqs)
        if len(reqs) > 1:
            self.stats["batched_prefills"] += 1
        self.stats["padded_tokens"] += len(reqs) * n_pad - sum(lens)
        for j, (slot, req) in enumerate(zip(slots, reqs)):
            if not req.generated:
                # Preempted requests already hold their tokens: the ones
                # after the prompt are *replayed* through decode steps
                # (see step()), which reproduces the original cache
                # exactly under ANY attention config — unlike replaying
                # them through prefill, whose algorithm (anchor) differs
                # from the decode attention that first produced them.
                req.generated.append(int(first_toks[j]))
            self.slot_req[slot] = req
            self.slot_pos[slot] = lens[j]
            self._slot_plen[slot] = lens[j]

    def _count_sparse(self, counters: np.ndarray, sp: span) -> None:
        """Add one prefill's fetched (num_groups, 4) sparse work counts to
        ``stats`` and to its span's attributes."""
        totals = np.asarray(counters, np.int64).sum(axis=0)
        for name, value in zip(SPARSE_COUNTERS, totals.tolist()):
            self.stats[name] += value
            sp.attrs[name] = value

    def _store_prefill_pages(
        self,
        slots: list[int],
        meta: list[tuple[np.ndarray, int]],
        n_pad: int,
        pcache: Any,
    ) -> None:
        """Scatter a prefill wave's KV onto the wave's reserved pages.

        The write table redirects prefix-shared pages and the padding
        tail to the null page: shared pages already hold this exact KV
        (token KV depends only on the tokens at and before its position),
        and padding KV is garbage by definition.
        """
        ps = self.pool.page_size
        n_pages = n_pad // ps
        write = np.zeros((len(slots), n_pages), np.int32)
        for j, (slot, (tokens, shared)) in enumerate(zip(slots, meta)):
            prompt_pages = self.pool.pages_for_tokens(len(tokens))
            write[j, shared:prompt_pages] = self._pt[slot, shared:prompt_pages]
        self.cache = self._scatter_pages(
            self.cache, pcache, jnp.asarray(write))

    # ------------------------------------------------- chunked prefill ----

    def _prefill_chunk_step(self, slot: int, sp: span) -> None:
        """Run ONE chunk of an in-flight chunked prefill (engine steps
        interleave these with decode, so long prompts never head-of-line
        block the decode batch); ``sp`` is its ``engine.chunk`` span."""
        st = self._chunking[slot]
        ps = self.pool.page_size
        chunk = self.chunk_tokens
        c0 = st.pos
        c1 = min(c0 + chunk, len(st.tokens))
        toks = np.zeros((1, chunk), np.int32)
        toks[0, : c1 - c0] = st.tokens[c0:c1]
        pt_row = jnp.asarray(self._pt[slot:slot + 1])
        view = self._gather_view(self.cache, pt_row)
        logits, view, counters = self._chunk(
            self.params, jnp.asarray(toks), view, jnp.asarray(c0, jnp.int32),
            jnp.asarray(c1 - c0, jnp.int32))
        # Summed on the device, fetched with the first token.
        st.counters = (counters if st.counters is None
                       else st.counters + counters)
        # Scatter back only this chunk's pages, minus prefix-shared ones
        # and the padding tail.
        prompt_pages = self.pool.pages_for_tokens(len(st.tokens))
        write = np.zeros((1, self.layout.pages_per_seq), np.int32)
        lo = max(c0 // ps, st.shared_pages)
        hi = min(-(-c1 // ps), prompt_pages)
        write[0, lo:hi] = self._pt[slot, lo:hi]
        self.cache = self._scatter_view(self.cache, view, jnp.asarray(write))
        self.stats["prefill_chunks"] += 1
        if self.spec.algorithm == "anchor":
            self.stats["sparse_chunks"] += 1
        st.pos = c1
        if c1 < len(st.tokens):
            return
        # Final chunk: sample the first token, hand the slot to decode.
        req = st.req
        first, counters = jax.device_get(
            (jnp.argmax(logits[0, c1 - c0 - 1]), st.counters))
        self._count_sparse(counters, sp)
        if st.append_first:
            req.generated.append(int(first))
        if self.prefix is not None:
            full = len(st.tokens) // ps
            self.prefix.insert(st.tokens, self._pt[slot, :full], "chunked")
        self.slot_req[slot] = req
        self.slot_pos[slot] = len(st.tokens)
        self._slot_plen[slot] = len(st.tokens)
        del self._chunking[slot]

    # ------------------------------------------------- single prefill ----

    def _prefill_single(self, slot: int, req: Request) -> None:
        """One unpadded single-request prefill pass (recurrent-state archs).

        Produces BOTH the first-token logits and the populated KV/state
        cache; the cache is spliced into the engine's batch slot (no
        redundant per-token replay)."""
        prompt = jnp.asarray(req.prompt, jnp.int32)[None]
        n = prompt.shape[1]
        with span("engine.prefill", uids=(req.uid,), n_pad=n,
                  tokens=(n,)) as sp:
            with span("engine.prefill.dispatch"):
                logits, (pcache, counters) = model_lib.prefill(
                    self.params, prompt, self.cfg, spec=self._single_spec(n),
                    return_counters=True)
            with span("engine.prefill.sync"):
                first, counters = jax.device_get(
                    (jnp.argmax(logits[0]), counters))
            self._count_sparse(counters, sp)
            with span("engine.prefill.store"):
                self.cache = self._insert_cache(
                    self.cache, pcache, jnp.asarray([slot], jnp.int32))
        if not req.generated:
            req.generated.append(int(first))
        self.slot_req[slot] = req
        self.slot_pos[slot] = n
        self._slot_plen[slot] = n
        self.stats["prefill_requests"] += 1

    def _single_spec(self, n: int) -> AttentionSpec:
        cfg = self.spec.anchor
        need = cfg.block_q * cfg.step
        if (self.spec.algorithm == "anchor"
                and n % need == 0 and n >= 2 * need):
            return self.spec
        if self.spec.algorithm == "anchor":
            self.stats["dense_fallbacks"] += 1
        return self.spec.with_algorithm("dense")

    # ------------------------------------------------- jitted cache ops ----

    @staticmethod
    @jax.jit
    def _insert_cache(pool, pre, slots):
        """Splice a whole prefill wave into the dense slab in ONE jitted
        call: wave sequence ``j`` of ``pre`` goes into batch slot
        ``slots[j]``.

        Every cache leaf has batch at axis 1 and prefix-aligned content
        (KV/latent caches fill positions [0, n); mamba states are full) —
        per wave entry: take its sequence of ``pre``, paste it at the
        origin of a zeroed one-slot slice of the pool, and write that
        back at the slot index.
        """

        def one(pool_leaf, pre_leaf):
            def body(j, lp):
                seq = jax.lax.dynamic_slice_in_dim(pre_leaf, j, 1, axis=1)
                upd = jnp.zeros_like(
                    jax.lax.dynamic_slice_in_dim(lp, 0, 1, axis=1))
                upd = jax.lax.dynamic_update_slice(
                    upd, seq.astype(upd.dtype), (0,) * seq.ndim)
                return jax.lax.dynamic_update_slice_in_dim(
                    lp, upd, slots[j], axis=1)

            return jax.lax.fori_loop(0, slots.shape[0], body, pool_leaf)

        return jax.tree.map(one, pool, pre)

    @staticmethod
    @jax.jit
    def _scatter_pages(pool, pre, write_tables):
        """Scatter a prefill wave's (G, B, Hkv, n_pad, d) KV onto pages.

        ``write_tables`` (B, n_pad/page_size) holds physical page ids per
        logical page; null entries land in the trash page."""

        def one(pool_leaf, pre_leaf):
            return jax.vmap(
                lambda pg, prg: cache_lib.scatter_pages(pg, prg, write_tables)
            )(pool_leaf, pre_leaf)

        return jax.tree.map(one, pool, pre)

    @staticmethod
    @jax.jit
    def _gather_view(pool, pt_row):
        """Materialize one sequence's dense cache view (G, 1, Hkv, S, d)
        from the paged pool (page table row (1, n_pages))."""

        def one(pool_leaf):
            return jax.vmap(lambda pg: cache_lib.gather_pages(pg, pt_row))(
                pool_leaf)

        return jax.tree.map(one, pool)

    @staticmethod
    @jax.jit
    def _scatter_view(pool, view, write_table):
        """Write a (G, 1, Hkv, S, d) view back onto its pages (null
        entries of ``write_table`` drop to the trash page)."""

        def one(pool_leaf, view_leaf):
            return jax.vmap(
                lambda pg, vw: cache_lib.scatter_pages(pg, vw, write_table)
            )(pool_leaf, view_leaf)

        return jax.tree.map(one, pool, view)

    @staticmethod
    @jax.jit
    def _copy_page(pool, src, dst):
        """Copy-on-write payload copy: physical page ``src`` -> ``dst``."""

        def one(leaf):
            page = jax.lax.dynamic_index_in_dim(leaf, src, axis=1)
            return jax.lax.dynamic_update_index_in_dim(leaf, page, dst, axis=1)

        return jax.tree.map(one, pool)

    # --------------------------------------------------- paged plumbing ----

    def _retire_slot(self, slot: int) -> None:
        self.slot_req[slot] = None
        self.slot_pos[slot] = 0
        if self.cache_layout == "paged":
            self.pool.release_table(self._pt[slot])
            self._pt[slot] = NULL_PAGE
            self._touch_gauges()

    def _preempt_one(self, protect: int | None = None) -> bool:
        """Preempt the youngest occupied slot (recompute-on-readmission):
        free its pages and requeue it at the front.  Returns False when
        there is nothing to preempt."""
        occupied = [s for s in range(self.max_batch)
                    if (self.slot_req[s] is not None or s in self._chunking)
                    and s != protect]
        if not occupied and protect is not None:
            occupied = [protect]
        if not occupied:
            return False
        victim = max(occupied, key=lambda s: self._slot_tick[s])
        st = self._chunking.pop(victim, None)
        req = st.req if st is not None else self.slot_req[victim]
        self._retire_slot(victim)
        self.queue.appendleft(req)
        self.stats["preemptions"] += 1
        return True

    def _grow_page(self, slot: int, pos: int) -> bool:
        """Make position ``pos`` of ``slot`` writable: allocate its page
        on first touch, CoW-copy it if it is shared.  May evict prefix
        pages or preempt (youngest-first); returns False when ``slot``
        itself was preempted."""
        ps = self.pool.page_size
        idx = pos // ps
        pid = int(self._pt[slot, idx])
        if pid != NULL_PAGE:
            if self.pool.refcount(pid) > 1:
                new_pid, copied = self.pool.ensure_writable(pid)
                if copied:
                    self.cache = self._copy_page(
                        self.cache, jnp.asarray(pid), jnp.asarray(new_pid))
                    self._pt[slot, idx] = new_pid
                    self.stats["cow_copies"] = self.pool.stats.cow_copies
            return True
        while True:
            if self.prefix is not None and self.pool.free_pages < 1:
                self.stats["prefix_evictions"] += self.prefix.evict(1)
            if self.pool.free_pages >= 1:
                self._pt[slot, idx] = self.pool.alloc()
                self._touch_gauges()
                return True
            if not self._preempt_one(protect=slot):
                raise MemoryError("KV page pool exhausted and nothing left "
                                  "to preempt")
            if self.slot_req[slot] is None:  # we were our own victim
                return False

    def _touch_gauges(self) -> None:
        if self.pool is not None:
            self.stats["pages_in_use"] = self.pool.pages_in_use
            self.stats["pages_hwm"] = self.pool.stats.pages_hwm

    def snapshot(self) -> dict[str, int]:
        """Self-consistent copy of ``stats`` with live gauges refreshed."""
        self._touch_gauges()
        if self.prefix is not None:
            self.stats["prefix_queries"] = self.prefix.stats.queries
            self.stats["prefix_hits"] = self.prefix.stats.hits
            self.stats["shared_pages"] = self.prefix.stats.shared_pages
        snap = dict(self.stats)
        snap["active_slots"] = sum(r is not None for r in self.slot_req)
        snap["queued"] = len(self.queue)
        return snap

    # ------------------------------------------------------------- step ----

    def step(self) -> list[Request]:
        """One engine iteration: admit, advance one chunk of every
        in-flight chunked prefill, batch-decode, retire.  Returns newly
        finished requests."""
        with span("engine.step"):
            return self._step()

    def _step(self) -> list[Request]:
        with span("engine.admit"):
            self._admit()
        for slot in sorted(self._chunking):
            st = self._chunking[slot]
            with span("engine.chunk", uid=st.req.uid, pos=st.pos) as sp:
                self._prefill_chunk_step(slot, sp)
        active = [s for s in range(self.max_batch) if self.slot_req[s] is not None]
        finished: list[Request] = self._rejected
        self._rejected = []
        if not active:
            return finished
        # NOTE: slots share a single `pos` per step in this lite scheduler;
        # decode each distinct position group together.
        by_pos: dict[int, list[int]] = {}
        for s in active:
            by_pos.setdefault(int(self.slot_pos[s]), []).append(s)
        for pos, slots in by_pos.items():
            with span("engine.decode", pos=pos) as sp:
                done = self._decode_group(pos, slots, sp)
            for s in done:
                finished.append(self.slot_req[s])
                with span("engine.retire", uid=self.slot_req[s].uid):
                    self._retire_slot(s)
        return finished

    def _decode_group(self, pos: int, slots: list[int], sp: span) -> list[int]:
        """Decode one position group; returns the slots whose request
        finished (marked done, not yet retired)."""
        if self.cache_layout == "paged":
            with span("engine.decode.pages"):
                # A grow may preempt a slot of ANY group (even one already
                # grown in this loop) — filter on live occupancy before
                # and after, not just on the grow result.
                slots = [s for s in slots if self.slot_req[s] is not None
                         and self._grow_page(s, pos)]
                slots = [s for s in slots if self.slot_req[s] is not None]
        sp.attrs["uids"] = tuple(self.slot_req[s].uid for s in slots)
        done: list[int] = []
        if not slots:
            return done
        toks = np.zeros(self.max_batch, np.int32)
        act = np.zeros(self.max_batch, bool)
        for s in slots:
            # The input at position p is generated[p - prompt_len].
            # For a fresh request that is always generated[-1]; a
            # preempted request re-enters with its position reset to
            # the prompt end and *replays* its known tokens through
            # ordinary decode steps — bit-exact cache reconstruction
            # under any attention config (sampling suppressed below).
            toks[s] = self.slot_req[s].generated[
                pos - int(self._slot_plen[s])]
            act[s] = True
        # `act` restricts cache/state writes to this position group —
        # without it the write at `pos` would corrupt slots whose own
        # position is past it (mixed-position batches are the norm
        # with ragged batched prefill).
        with span("engine.decode.dispatch"):
            if self.cache_layout == "paged":
                logits, self.cache = self._decode_paged(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(pos), jnp.asarray(act), jnp.asarray(self._pt))
            else:
                logits, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(pos), jnp.asarray(act))
        self.stats["decode_steps"] += 1
        with span("engine.decode.sync"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
        for s in slots:
            req = self.slot_req[s]
            self.slot_pos[s] = pos + 1
            if pos - int(self._slot_plen[s]) < len(req.generated) - 1:
                continue  # replaying a preempted request: token known
            req.generated.append(int(nxt[s]))
            hit_len = self.slot_pos[s] >= self.max_len - 1
            if hit_len:
                self.stats["length_truncations"] += 1
            if len(req.generated) >= req.max_new_tokens or hit_len:
                req.done = True
                done.append(s)
        return done

    def run_to_completion(self, max_iters: int = 10_000) -> list[Request]:
        done: list[Request] = []
        for _ in range(max_iters):
            done.extend(self.step())
            if self.idle:
                break
        return done
