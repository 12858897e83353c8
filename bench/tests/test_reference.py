"""The blocked f32 reference against the one-head dense oracle of
AnchorAttention, the program's varlen and capacity semantics, and the
engine's logits on the xla backend, at small sizes on the CPU; and the
``dense_gqa`` family's reference against logits recorded before it
moved into the family file."""

from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import build, reference
from bench.tests import tiny

GOLDEN = Path(__file__).parent / "data" / "golden_reference.npz"

N, D = 256, 16


def _an(theta, capacity=N):
    return {"block_q": 16, "block_kv": 16, "step": 2, "theta": theta,
            "capacity": capacity}


def _qkv(seed, hq=1, hkv=1, n=N):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = 2.0 * jax.random.normal(ks[0], (hq, n, D))
    k = jax.random.normal(ks[1], (hkv, n, D))
    v = jax.random.normal(ks[2], (hkv, n, D))
    return q, k, v


def _ours(q, k, v, n, an):
    hkv = k.shape[0]
    qh = q.reshape(hkv, q.shape[0] // hkv, q.shape[1], D)
    return reference.anchor_prompt(qh, k, v, n, an, False).reshape(q.shape)


@pytest.mark.parametrize("theta", [0.5, 2.0, 12.0])
def test_matches_the_one_head_oracle(theta):
    from repro.core.config import AnchorConfig
    from repro.kernels.ref import anchor_attention_ref

    q, k, v = _qkv(0)
    cfg = AnchorConfig(block_q=16, block_kv=16, step=2, theta=theta)
    want = anchor_attention_ref(q[0], k[0], v[0], cfg)
    got = _ours(q, k, v, N, _an(theta))[0]
    np.testing.assert_allclose(got, want, atol=2e-5)
    # Stripes matter at this size: leaving them out moves the output.
    none = _ours(q, k, v, N, _an(-1e6))[0]
    if theta > 1.0:
        assert float(jnp.abs(none - want).max()) > 1e-2


@pytest.mark.parametrize("n,capacity", [(200, N), (256, 4), (131, 3)])
def test_matches_the_programs_varlen_and_capacity(n, capacity):
    from repro.core.config import AnchorConfig
    from repro.kernels import ops

    q, k, v = _qkv(1, hq=4, hkv=2)
    cfg = AnchorConfig(block_q=16, block_kv=16, step=2, theta=2.0,
                       capacity=capacity)
    want = ops.anchor_attention(q[None], k[None], v[None], cfg,
                                lengths=jnp.asarray([n]), backend="xla")[0]
    got = _ours(q, k, v, n, _an(2.0, capacity))
    np.testing.assert_allclose(got[:, :n], want[:, :n], atol=2e-5)
    assert float(jnp.abs(got[:, n:]).sum()) == 0.0


def test_values_may_differ_in_width_from_keys():
    # Each output column depends on its value column alone, so values of
    # twice the key width give the two halves' outputs side by side.
    q, k, v = _qkv(2, hq=4, hkv=2)
    v2 = jnp.concatenate([v, 3.0 * v[:, :, ::-1]], axis=-1)
    qh = q.reshape(2, 2, N, D)
    wide = reference.anchor_prompt(qh, k, v2, 200, _an(2.0, 4), False)
    halves = [reference.anchor_prompt(qh, k, x, 200, _an(2.0, 4), False)
              for x in (v2[..., :D], v2[..., D:])]
    assert wide.shape == (2, 2, N, 2 * D)
    np.testing.assert_allclose(wide, jnp.concatenate(halves, -1), atol=1e-5)


def test_matches_the_engines_logits():
    from repro.serving import engine as engine_lib

    fam = tiny.load_family()
    conf = dict(tiny.CONFIG, torch_dtype="float32")
    arch, anchor = fam.arch(conf), build.anchor_of(conf)
    mcfg = fam.model_config(conf)
    params = fam.make_weights(arch, "float32", 5)
    eng = build.make_engine(params, mcfg, conf, backend="xla")
    seen = []
    prefill = engine_lib.model_lib.prefill
    decode = eng._decode_paged

    def rec_prefill(*a, **kw):
        logits, cache = prefill(*a, **kw)
        seen.append(np.asarray(logits[0]))
        return logits, cache

    def rec_decode(*a):
        logits, cache = decode(*a)
        seen.append(np.asarray(logits[0]))
        return logits, cache

    engine_lib.model_lib.prefill = rec_prefill
    eng._decode_paged = rec_decode
    try:
        prompt = np.random.default_rng(0).integers(0, 512, 90).astype(np.int32)
        eng.submit(build.request(0, prompt, 6))
        done = eng.run_to_completion()
    finally:
        engine_lib.model_lib.prefill = prefill
    served = np.asarray(done[0].generated, np.int32)
    ref = fam.reference_logits(params, arch, anchor, prompt, served)
    got = np.stack(seen[:len(served)])
    assert np.abs(got - ref).max() < 1e-3 * np.abs(ref).max()
    assert (ref.argmax(-1) == served).all()


@pytest.mark.parametrize("control", [False, True], ids=["plain", "control"])
def test_family_reference_is_bitwise_the_recorded_one(control):
    # Recorded from the reference before it moved into
    # ``bench/families/dense_gqa.py``: bf16 weights of seed 5, a 90-token
    # prompt and 6 served tokens drawn from seed 0.
    fam = tiny.load_family()
    conf = tiny.CONFIG
    arch, anchor = fam.arch(conf), build.anchor_of(conf)
    params = fam.make_weights(arch, "bfloat16", 5)
    rec = np.load(GOLDEN)
    got = fam.reference_logits(params, arch, anchor, rec["prompt"],
                               rec["served"], control=control)
    want = rec["control" if control else "plain"]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want)
