"""Property-based tests (hypothesis) for variable-length padded prefill.

The varlen contract (repro.core.spec): a right-padded batch with a
``lengths`` array must be indistinguishable, per sequence, from running
each sequence on its own — bit-for-bit on the ``xla`` backend, within
kernel tolerance on ``pallas_interpret`` — across the dense and anchor
algorithms and arbitrary ragged length mixes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need the 'test' extra: pip install -e '.[test]'")
from hypothesis import given, settings, strategies as st

from repro.configs import get_reduced_config
from repro.core import AnchorConfig, AttentionSpec
from repro.kernels import ops as kernel_ops
from repro.models import model as model_lib

SETTINGS = dict(max_examples=6, deadline=None)
ANCHOR = AnchorConfig(block_q=16, block_kv=16, step=2, theta=3.0)
N_PAD = 64  # two identification superblocks of the test AnchorConfig


def _qkv(seed, b, h, n, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, h, n, d)),
            jax.random.normal(ks[1], (b, h, n, d)),
            jax.random.normal(ks[2], (b, h, n, d)))


lengths_strategy = st.lists(
    st.integers(min_value=17, max_value=N_PAD), min_size=2, max_size=4)


@settings(**SETTINGS)
@given(seed=st.integers(0, 50), lens=lengths_strategy,
       algorithm=st.sampled_from(["dense", "anchor"]))
def test_padded_batch_equals_per_sequence_ops(seed, lens, algorithm):
    """kernels.ops.attention: batched padded call == per-sequence calls
    to a few f32 ulp on xla (XLA:CPU picks its dot kernel by operand
    shape, and the batch size is part of the shape — DESIGN.md §3),
    within kernel tolerance on pallas_interpret; padded rows are exact
    zeros."""
    b = len(lens)
    q, k, v = _qkv(seed, b, 2, N_PAD, 16)
    lengths = jnp.asarray(lens, jnp.int32)

    for backend, (atol, rtol) in (("xla", (1e-6, 1e-6)),
                                  ("pallas_interpret", (2e-5, 1e-4))):
        spec = AttentionSpec(algorithm=algorithm, backend=backend,
                             anchor=ANCHOR, masking="padded")
        out = kernel_ops.attention(q, k, v, spec, lengths=lengths)
        for j, n in enumerate(lens):
            assert np.allclose(np.asarray(out[j, :, n:]), 0.0), (
                backend, j, "padded rows must be exact zeros")
            single = kernel_ops.attention(
                q[j:j + 1], k[j:j + 1], v[j:j + 1], spec,
                lengths=jnp.asarray([n], jnp.int32))
            np.testing.assert_allclose(
                np.asarray(out[j], np.float32),
                np.asarray(single[0], np.float32), atol=atol, rtol=rtol)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_reduced_config("internlm2_1p8b")
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 20), lens=lengths_strategy,
       algorithm=st.sampled_from(["dense", "anchor"]))
def test_padded_batch_prefill_equals_unpadded(seed, lens, algorithm, tiny_model):
    """model.prefill: one padded batched call reproduces per-sequence
    prefill bit-for-bit on xla.  The dense algorithm is additionally
    compared against truly UNPADDED per-sequence prefill (anchor requires
    block-aligned lengths, so its per-sequence reference pads to the same
    boundary with a lengths mask)."""
    cfg, params = tiny_model
    rng = np.random.default_rng(seed)
    seqs = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]
    toks = np.zeros((len(lens), N_PAD), np.int32)
    for j, s in enumerate(seqs):
        toks[j, : len(s)] = s
    lengths = jnp.asarray(lens, jnp.int32)
    spec = AttentionSpec(algorithm=algorithm, backend="xla", anchor=ANCHOR,
                         masking="padded")
    logits, _ = model_lib.prefill(params, jnp.asarray(toks), cfg, spec=spec,
                                  lengths=lengths)
    for j, n in enumerate(lens):
        single = np.zeros((1, N_PAD), np.int32)
        single[0, :n] = seqs[j]
        lj, _ = model_lib.prefill(
            params, jnp.asarray(single), cfg, spec=spec,
            lengths=jnp.asarray([n], jnp.int32))
        np.testing.assert_array_equal(np.asarray(logits[j]), np.asarray(lj[0]))
        if algorithm == "dense":
            lu, _ = model_lib.prefill(
                params, jnp.asarray(seqs[j][None]), cfg,
                spec=AttentionSpec(algorithm="dense", backend="xla"))
            np.testing.assert_array_equal(
                np.asarray(logits[j]), np.asarray(lu[0]))


@settings(**SETTINGS)
@given(seed=st.integers(0, 50), lens=lengths_strategy)
def test_padding_keys_never_in_anchor_stats_or_selection(seed, lens):
    """Corrupting the padding region of K/V must not change any output —
    the masking really is total (statistics, selection, and scores)."""
    b = len(lens)
    q, k, v = _qkv(seed, b, 1, N_PAD, 16)
    lengths = jnp.asarray(lens, jnp.int32)
    spec = AttentionSpec(algorithm="anchor", backend="xla", anchor=ANCHOR,
                         masking="padded")
    out = kernel_ops.attention(q, k, v, spec, lengths=lengths)
    pad_mask = (jnp.arange(N_PAD)[None, None, :, None]
                >= lengths[:, None, None, None])
    junk = 100.0 * jax.random.normal(jax.random.PRNGKey(seed + 1), k.shape)
    k2 = jnp.where(pad_mask, junk, k)
    v2 = jnp.where(pad_mask, junk, v)
    q2 = jnp.where(pad_mask, junk, q)
    out2 = kernel_ops.attention(q2, k2, v2, spec, lengths=lengths)
    valid = ~pad_mask
    np.testing.assert_array_equal(
        np.asarray(jnp.where(valid, out, 0.0)),
        np.asarray(jnp.where(valid, out2, 0.0)))
