"""Public wrapper: padding + jit around the fused LSTM cell kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lstm.kernel import lstm_cell_pallas
from repro.kernels.lstm.ref import lstm_cell_ref


def _on_tpu() -> bool:
    # resolved at TRACE time, not import time: the backend may be
    # configured (jax.config / env) after this module is imported, and a
    # stale import-time snapshot would run the kernel in interpret mode
    # on a real TPU (or worse, compiled mode off one)
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _lstm_cell(x, h, c, wx, wh, b, interpret):
    B, I = x.shape
    block_b = 8
    pad_b = (-B) % block_b
    pad_i = (-I) % 8
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
        h = jnp.pad(h, ((0, pad_b), (0, 0)))
        c = jnp.pad(c, ((0, pad_b), (0, 0)))
    if pad_i:
        x = jnp.pad(x, ((0, 0), (0, pad_i)))
        wx = jnp.pad(wx, ((0, pad_i), (0, 0)))
    h_new, c_new = lstm_cell_pallas(x, h, c, wx, wh, b[None, :],
                                    block_b=block_b, interpret=interpret)
    return h_new[:B], c_new[:B]


def _lstm_cell_fwd(x, h, c, wx, wh, b, interpret):
    return _lstm_cell(x, h, c, wx, wh, b, interpret), (x, h, c, wx, wh, b)


def _lstm_cell_bwd(interpret, res, cotangents):
    _, vjp = jax.vjp(lstm_cell_ref, *res)
    return vjp(cotangents)


_lstm_cell.defvjp(_lstm_cell_fwd, _lstm_cell_bwd)


def lstm_cell_padded(x, h, c, wx, wh, b):
    """Drop-in fused version of ``repro.models.rnn.lstm_cell`` signature:
    (params dict unpacked) -> (h', c'). Pads batch to a sublane multiple
    and the input feature dim to 8. Un-jitted so the dispatch layer can
    inline it into larger programs; ``lstm_cell_fused`` below is the
    jitted standalone entry.

    Differentiable: ``pallas_call`` has no reverse-mode rule, so the cell
    is a ``jax.custom_vjp`` whose forward pass is the kernel and whose
    backward pass is the VJP of ``lstm_cell_ref`` (the same math,
    recomputed from the saved inputs). Training therefore runs the kernel
    forward under ``value_and_grad``, ``vmap`` over workers and
    ``lax.scan`` over time."""
    return _lstm_cell(x, h, c, wx, wh, b, not _on_tpu())


lstm_cell_fused = jax.jit(lstm_cell_padded)
