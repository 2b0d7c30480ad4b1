"""The paper's model: Input -> 2 x LSTM -> 3 x FC (Table I footnote ¶),
sliding window 20, for stock prediction, plus an extreme-event indicator
head (sigmoid) for the EVL experiments.

Functional LSTM built on ``jax.lax.scan``. The per-step cell routes
through ``repro.kernels.dispatch``, which picks the fused Pallas kernel
(``repro.kernels.lstm``) or the plain XLA lowering per (backend, batch,
hidden) at trace time — train-time ``rnn_features`` and the serving
``step``/``replay`` paths therefore resolve identically.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.models.layers import dense_init

PyTree = Any


@dataclasses.dataclass(frozen=True)
class RNNConfig:
    input_dim: int = 5          # OHLCV
    hidden: int = 64
    num_layers: int = 2         # paper: 2 LSTM layers
    fc_dims: tuple = (32, 16)   # paper: 3 FC layers (2 hidden + output)
    window: int = 20            # paper Table I
    evl_head: bool = True       # extreme-event indicator head
    dtype: Any = jnp.float32


def init_lstm_layer(key, in_dim: int, hidden: int, dtype):
    k1, k2 = jax.random.split(key)
    # gates packed [i, f, g, o] along the last dim
    return {
        "wx": dense_init(k1, (in_dim, 4 * hidden), dtype),
        "wh": dense_init(k2, (hidden, 4 * hidden), dtype),
        # forget-gate bias 1.0 (standard trick for gradient flow)
        "b": jnp.concatenate([
            jnp.zeros((hidden,), dtype), jnp.ones((hidden,), dtype),
            jnp.zeros((2 * hidden,), dtype)]),
    }


def lstm_cell(p, x_t, h, c):
    """Fused LSTM cell: x_t [B, I]; h, c [B, H] -> (h', c'). Dispatch-
    routed: the kernel table picks Pallas or XLA for this (backend,
    batch, hidden) while the surrounding program traces."""
    return dispatch.lstm_cell(x_t, h, c, p["wx"], p["wh"], p["b"])


def lstm_layer_apply(p, xs):
    """xs [B, T, I] -> hs [B, T, H] via scan over time."""
    B = xs.shape[0]
    H = p["wh"].shape[0]
    h0 = jnp.zeros((B, H), xs.dtype)
    c0 = jnp.zeros((B, H), xs.dtype)

    def step(carry, x_t):
        h, c = carry
        h, c = lstm_cell(p, x_t, h, c)
        return (h, c), h

    (_, _), hs = jax.lax.scan(step, (h0, c0), xs.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2)


def init_rnn(key, cfg: RNNConfig) -> PyTree:
    keys = jax.random.split(key, cfg.num_layers + len(cfg.fc_dims) + 2)
    params: dict = {"lstm": [], "fc": []}
    in_dim = cfg.input_dim
    for i in range(cfg.num_layers):
        params["lstm"].append(init_lstm_layer(keys[i], in_dim, cfg.hidden,
                                              cfg.dtype))
        in_dim = cfg.hidden
    dims = (cfg.hidden,) + tuple(cfg.fc_dims)
    for j in range(len(cfg.fc_dims)):
        k = keys[cfg.num_layers + j]
        params["fc"].append({
            "w": dense_init(k, (dims[j], dims[j + 1]), cfg.dtype),
            "b": jnp.zeros((dims[j + 1],), cfg.dtype)})
    k_out = keys[-2]
    params["out"] = {"w": dense_init(k_out, (dims[-1], 1), cfg.dtype),
                     "b": jnp.zeros((1,), cfg.dtype)}
    if cfg.evl_head:
        k_evl = keys[-1]
        params["evl"] = {"w": dense_init(k_evl, (dims[-1], 1), cfg.dtype),
                         "b": jnp.zeros((1,), cfg.dtype)}
    return params


def rnn_features(params: PyTree, x):
    """x [B, T, input_dim] -> last-layer hidden sequence [B, T, H]."""
    h = x
    for lp in params["lstm"]:
        h = lstm_layer_apply(lp, h)
    return h


def rnn_head(params: PyTree, h, cfg: RNNConfig):
    """FC stack + output/EVL heads on a hidden state h [B, H]."""
    for fp in params["fc"]:
        h = jnp.tanh(h @ fp["w"] + fp["b"])
    y = (h @ params["out"]["w"] + params["out"]["b"])[:, 0]
    u = None
    if cfg.evl_head and "evl" in params:
        u = jax.nn.sigmoid((h @ params["evl"]["w"] + params["evl"]["b"]))[:, 0]
    return y, u


def rnn_apply(params: PyTree, x, cfg: RNNConfig):
    """x [B, window, input_dim] -> (y_pred [B], u_extreme [B] or None)."""
    h = rnn_features(params, x)[:, -1, :]     # last time step
    return rnn_head(params, h, cfg)


def rnn_apply_padded(params: PyTree, x, lengths, cfg: RNNConfig):
    """Length-bucketed apply: x [B, T, input_dim] right-padded to a bucket
    length T, lengths [B] int32 giving each example's true length.

    The LSTM stack is causal, so the hidden state at position len-1 depends
    only on x[:len] — gathering there yields exactly the unpadded result,
    which is what lets the serving batcher mix lengths in one bucket.
    """
    hs = rnn_features(params, x)
    idx = (jnp.asarray(lengths, jnp.int32) - 1)[:, None, None]
    h = jnp.take_along_axis(hs, jnp.broadcast_to(
        idx, (hs.shape[0], 1, hs.shape[2])), axis=1)[:, 0, :]
    return rnn_head(params, h, cfg)


def init_rnn_carry(params: PyTree, batch: int, dtype=jnp.float32):
    """Zero (h, c) carries for each LSTM layer — the per-session state
    kept resident by the serving session cache."""
    return tuple(
        (jnp.zeros((batch, lp["wh"].shape[0]), dtype),
         jnp.zeros((batch, lp["wh"].shape[0]), dtype))
        for lp in params["lstm"])


def rnn_step(params: PyTree, x_t, carries, cfg: RNNConfig):
    """One time step: x_t [B, input_dim], carries from ``init_rnn_carry``.

    Returns (y [B], u [B] or None, new_carries). Feeding a window one step
    at a time from zero carries reproduces ``rnn_apply`` on that window —
    O(1) per step for streaming clients instead of O(window) recompute.
    """
    new_carries = []
    h = x_t
    for lp, (hc, cc) in zip(params["lstm"], carries):
        hc, cc = lstm_cell(lp, h, hc, cc)
        new_carries.append((hc, cc))
        h = hc
    y, u = rnn_head(params, h, cfg)
    return y, u, tuple(new_carries)
