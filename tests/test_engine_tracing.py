"""Engine spans and sparse-prefill counters.

The span log must describe what ``ServingEngine.step`` did: spans nest
inside their parents, every prefill wave has one dispatch and one sync,
a request's spans carry its uid, the log is bounded, and a profiler
trace shows the bare span names on its host plane.  The four sparse
counters must be the tables' own counts: ``stripes_kept`` is the kept
count that ``anchor_attention`` reports, anchor pairs plus kept-stripe
pairs are the pairs the masks oracle attends, used slots never exceed
visited ones, and dense waves count nothing.
"""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import AnchorConfig, AttentionSpec
from repro.core import masks as masks_lib
from repro.kernels import indexing
from repro.kernels import ops as kernel_ops
from repro.kernels.xla import staged_stripe_mask
from repro.models import model as model_lib
from repro.serving import Request, ServingEngine, tracing
from repro.serving.engine import SPAN_NAMES

ANCHOR = AnchorConfig(block_q=16, block_kv=16, step=2, theta=2.0)
MAX_NEW = 4


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced_config("internlm2_1p8b")
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    spec = AttentionSpec(algorithm="anchor", backend="xla", anchor=ANCHOR)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (70, 41, 96)]
    return cfg, params, spec, prompts


def _engine(setup, **kw):
    cfg, params, spec, _ = setup
    kw.setdefault("spec", spec)
    return ServingEngine(params, cfg, max_batch=2, max_len=128,
                         cache_layout="paged", page_size=8, **kw)


def _serve(engine, prompts):
    """Serve ``prompts``; returns (finished requests, the window's spans)."""
    t0 = time.perf_counter()
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=p.copy(),
                              max_new_tokens=MAX_NEW))
    done = engine.run_to_completion()
    return done, engine.spans(t0, time.perf_counter())


@pytest.fixture(scope="module")
def served(setup):
    engine = _engine(setup)
    done, log = _serve(engine, setup[3])
    return engine, done, log


def _children(log, parent):
    return [s for s in log if s.parent == parent.id]


# ------------------------------------------------------------ spans ----


def test_spans_nest_inside_their_parents(served):
    _, _, log = served
    by_id = {s.id: s for s in log}
    assert log and {s.name for s in log} <= set(SPAN_NAMES)
    for s in log:
        assert s.t0 <= s.t1
        if s.parent == 0:
            assert s.name == "engine.step"
            continue
        p = by_id[s.parent]
        assert p.t0 <= s.t0 and s.t1 <= p.t1, (p, s)
    names = {s.name for s in log}
    for want in ("engine.admit", "engine.prefill", "engine.prefill.store",
                 "engine.decode.pages", "engine.decode.dispatch",
                 "engine.retire"):
        assert want in names


def test_each_prefill_wave_has_one_dispatch_and_one_sync(served):
    _, _, log = served
    waves = [s for s in log if s.name == "engine.prefill"]
    assert waves
    for w in waves:
        kids = [c.name for c in _children(log, w)]
        assert kids.count("engine.prefill.dispatch") == 1
        assert kids.count("engine.prefill.sync") == 1
        assert kids.count("engine.prefill.store") == 1
        assert len(w.attrs["uids"]) == len(w.attrs["tokens"])
        assert w.attrs["n_pad"] >= max(w.attrs["tokens"])
    for d in (s for s in log if s.name == "engine.decode"):
        kids = [c.name for c in _children(log, d)]
        assert kids.count("engine.decode.sync") == 1


def test_spans_of_a_request_share_its_uid(served, setup):
    _, done, log = served
    prompts = setup[3]
    assert sorted(r.uid for r in done) == list(range(len(prompts)))
    for r in done:
        waves = [s for s in log if s.name == "engine.prefill"
                 and r.uid in s.attrs["uids"]]
        assert len(waves) == 1
        j = waves[0].attrs["uids"].index(r.uid)
        assert waves[0].attrs["tokens"][j] == len(prompts[r.uid])
        decodes = [s for s in log if s.name == "engine.decode"
                   and r.uid in s.attrs["uids"]]
        assert len(decodes) == MAX_NEW - 1  # the first token is prefill's
        retires = [s for s in log if s.name == "engine.retire"
                   and s.attrs["uid"] == r.uid]
        assert len(retires) == 1


def test_log_stays_bounded():
    t0 = time.perf_counter()
    for i in range(tracing.LOG_SIZE + 10):
        with tracing.span("engine.step", i=i):
            pass
    log = tracing.spans(t0)
    assert len(log) == tracing.LOG_SIZE
    assert log[-1].attrs["i"] == tracing.LOG_SIZE + 9  # newest kept
    assert tracing.totals(log)["engine.step"][0] == tracing.LOG_SIZE


def test_profiler_trace_holds_bare_span_names(setup, tmp_path):
    from jax.profiler import ProfileData

    engine = _engine(setup)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(engine, setup[3][:1])
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    host = {e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events}
    for name in SPAN_NAMES:
        if name != "engine.chunk":  # this engine does not chunk
            assert name in host, name
    assert not [n for n in host if n.startswith("engine.")
                and n not in SPAN_NAMES]


# --------------------------------------------------------- counters ----


def _qkv(b, hq, hkv, n, d, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, hq, n, d)),
            jax.random.normal(ks[1], (b, hkv, n, d)),
            jax.random.normal(ks[2], (b, hkv, n, d)))


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("varlen", [False, True])
def test_stripes_kept_equals_anchor_attention_counts(backend, varlen):
    cfg = AnchorConfig(block_q=16, block_kv=16, step=2, theta=2.0,
                       capacity=5)
    b, hq, hkv, n, d = 2, 4, 2, 256, 16
    q, k, v = _qkv(b, hq, hkv, n, d, seed=3)
    kw = {"lengths": jnp.asarray([n, 150], jnp.int32)} if varlen else {}
    spec = AttentionSpec(algorithm="anchor", backend=backend, anchor=cfg)
    if varlen:
        spec = spec.padded()
    out, counters = kernel_ops.attention(q, k, v, spec, return_counters=True,
                                         **kw)
    want_out, counts = kernel_ops.anchor_attention(
        q, k, v, cfg, return_stats=True, backend=backend, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    kept, tiles, used, visited = np.asarray(counters).tolist()
    assert kept == int(np.asarray(counts).sum()) > 0
    # Grid (B·Hkv, T_m, C_t) with tile 128: 2 tiles, so min(2, 5·G) = 2
    # selection slots, and 1 sink + ceil(32/128) + 1 = 3 anchor slots.
    t_m, c_t = n // 16, 2 + 3
    assert visited == b * hkv * t_m * c_t
    assert 0 < tiles <= b * hkv * (n // 32) * 2
    assert tiles * 2 <= used <= visited  # a selected slot serves 2 blocks


def test_anchor_and_stripe_pairs_equal_the_masks_oracle():
    cfg = AnchorConfig(block_q=16, block_kv=16, step=2, theta=2.0)
    b, hq, hkv, n, d = 1, 4, 2, 256, 16
    q, k, v = _qkv(b, hq, hkv, n, d, seed=5)
    spec = AttentionSpec(algorithm="anchor", backend="xla", anchor=cfg)
    _, counters = kernel_ops.attention(q, k, v, spec, return_counters=True)
    stripes_kept = int(counters[0])

    # Oracle: the staged dense hit mask, row-expanded per superblock,
    # joined with the anchor region under the causal mask.
    qm, mb = kernel_ops.anchor_phase(q, k, cfg, backend="xla")
    hit = np.asarray(staged_stripe_mask(qm, mb, k, cfg)).astype(bool)
    sb = cfg.superblock_q()
    rows = np.repeat(hit, sb, axis=2)[:, :, :n]  # (B, Hq, N, N)
    anchor = np.asarray(masks_lib.anchor_region_mask(n, cfg)
                        & masks_lib.causal_mask(n))
    attended = (rows | anchor) & np.asarray(masks_lib.causal_mask(n))
    anchor_pairs = b * hq * int(anchor.sum())
    assert stripes_kept > 0
    assert anchor_pairs + stripes_kept * sb == int(attended.sum())


def test_engine_counts_what_prefill_counts(setup, served):
    cfg, params, spec, prompts = setup
    engine, _, log = served
    waves = [s for s in log if s.name == "engine.prefill"]
    want = np.zeros(4, np.int64)
    for w in waves:  # the same padded waves, through the model alone
        toks = np.zeros((len(w.attrs["uids"]), w.attrs["n_pad"]), np.int32)
        for j, uid in enumerate(w.attrs["uids"]):
            toks[j, : len(prompts[uid])] = prompts[uid]
        _, (_, c) = model_lib.prefill(
            params, jnp.asarray(toks), cfg, spec=spec.padded(),
            lengths=jnp.asarray(w.attrs["tokens"], jnp.int32),
            return_counters=True)
        assert c.shape == (cfg.num_groups, 4)
        c = np.asarray(c, np.int64).sum(axis=0)
        assert [w.attrs[n] for n in indexing.SPARSE_COUNTERS] == c.tolist()
        want += c
    got = [engine.stats[name] for name in indexing.SPARSE_COUNTERS]
    assert got == want.tolist()
    assert got[0] > 0 and got[2] <= got[3]


def test_chunked_prefill_keeps_as_many_stripes(setup):
    one_shot = _engine(setup)
    _serve(one_shot, setup[3][2:])
    chunked = _engine(setup, chunk_tokens=32)
    _, log = _serve(chunked, setup[3][2:])
    assert chunked.stats["sparse_chunks"] > 1
    assert chunked.stats["stripes_kept"] == one_shot.stats["stripes_kept"] > 0
    assert (chunked.stats["sparse_slots_used"]
            <= chunked.stats["sparse_slots_visited"])
    chunks = [s for s in log if s.name == "engine.chunk"]
    assert {s.attrs["uid"] for s in chunks} == {0}
    assert sum(s.attrs.get("stripes_kept", 0) for s in chunks) == (
        chunked.stats["stripes_kept"])


@pytest.mark.parametrize("kind", ["dense_spec", "dense_fallback"])
def test_dense_waves_count_zero(setup, kind):
    cfg, params, spec, prompts = setup
    if kind == "dense_spec":
        engine = _engine(setup, spec=spec.with_algorithm("dense"))
    else:  # 70 tokens pad to 96 > max_len 80: the wave falls back to dense
        engine = ServingEngine(params, cfg, max_batch=2, max_len=80,
                               spec=spec, cache_layout="paged", page_size=8)
    _serve(engine, prompts[:1])
    if kind == "dense_fallback":
        assert engine.stats["dense_fallbacks"] == 1
    assert [engine.stats[n] for n in indexing.SPARSE_COUNTERS] == [0] * 4
