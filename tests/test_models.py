"""Model building blocks: RNN, attention twin, norms, rope."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.models.attention import blocked_attention, reference_attention
from repro.models.layers import apply_rope, pad_vocab, rms_norm
from repro.models.rnn import RNNConfig, init_rnn, rnn_apply


def test_rnn_shapes():
    cfg = RNNConfig()
    params = init_rnn(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((7, cfg.window, cfg.input_dim))
    y, u = rnn_apply(params, x, cfg)
    assert y.shape == (7,)
    assert u.shape == (7,)
    assert np.all((np.asarray(u) >= 0) & (np.asarray(u) <= 1))


def test_rnn_no_evl_head():
    cfg = RNNConfig(evl_head=False)
    params = init_rnn(jax.random.PRNGKey(0), cfg)
    y, u = rnn_apply(params, jnp.zeros((3, 20, 5)), cfg)
    assert u is None


@given(st.integers(1, 3), st.integers(16, 64))
@settings(max_examples=10, deadline=None)
def test_blocked_attention_matches_reference(b, s):
    rng = np.random.default_rng(s)
    q = jnp.asarray(rng.standard_normal((b, s, 4, 32)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, 2, 32)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, 2, 32)).astype(np.float32))
    got = blocked_attention(q, k, v, causal=True, q_block=16, kv_block=16)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_rope_preserves_norm_and_relative():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 8, 2, 64)).astype(np.float32))
    pos = jnp.arange(8)[None]
    y = apply_rope(x, pos)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # position 0 is the identity
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)


def test_rms_norm_unit_scale():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (4, 64)).astype(np.float32))
    y = rms_norm(x, jnp.ones(64))
    rms = np.sqrt(np.mean(np.asarray(y) ** 2, -1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


def test_pad_vocab():
    assert pad_vocab(50280) == 50432
    assert pad_vocab(256) == 256
    assert pad_vocab(1) == 256
