"""Public wrappers for the SSD kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd.kernel import ssd_decode_pallas, ssd_pallas
from repro.kernels.ssd.ref import ssd_chunk_ref


def _on_tpu() -> bool:
    # trace-time, not import-time: see repro.kernels.lstm.ops._on_tpu
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan_fused(xd, a, B_, C_, chunk: int = 128):
    """Drop-in fused version of ``repro.models.ssm.ssd_chunked`` (no
    initial state). Pads L to a chunk multiple."""
    Bsz, L, H, P = xd.shape
    pad = (-L) % chunk
    if pad:
        xd = jnp.pad(xd, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad a with 0 decay-log => exp(0)=1, but with zero x it is inert
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))
    y, state = ssd_pallas(xd, a, B_, C_, chunk=chunk,
                          interpret=not _on_tpu())
    return y[:, :L], state


def ssd_chunk_fused(xd, a, B_, C_, state):
    """Single-chunk single-(batch,head) entry point (tests)."""
    y, new_state = ssd_pallas(
        xd[None, :, None, :], a[None, :, None], B_[None], C_[None],
        chunk=xd.shape[0], interpret=not _on_tpu())
    # ssd_pallas starts from zero state; fold the provided state like the
    # reference does: y += C @ state^T * exp(cumsum a); state' folds decay.
    cum = jnp.cumsum(a)
    y0 = y[0, :, 0, :] + (C_ @ state.T) * jnp.exp(cum)[:, None]
    st = new_state[0, 0] + state * jnp.exp(cum[-1])
    return y0, st


def decode_inplace() -> bool:
    """Whether the layer-pattern decode updates its stacked SSM state
    with ``ssd_decode_inplace`` (on a TPU) or with XLA's slice, update
    and write-back (elsewhere; the reference for the tests). Resolved
    when the decode traces."""
    return _on_tpu()


def ssd_decode_inplace(states, k, mask, a_t, xd_t, B_t, C_t):
    """``repro.models.ssm.ssd_decode_step`` on layer ``k`` of a stacked
    state, in place: states [L, B, H, P, N] (donate it); k the
    layer (a traced int32 scalar); mask [B] bool, the lanes that step
    (the others keep their state bit for bit); xd_t [B, H, P]; a_t
    [B, H]; B_t, C_t [B, N]. The update runs in float32 and is stored
    in the states' dtype. Returns (y_t [B, H, P] in xd_t's dtype,
    states)."""
    Bsz = xd_t.shape[0]
    f32 = lambda v: v.astype(jnp.float32)             # noqa: E731
    yt, states = ssd_decode_pallas(
        states, k, mask, jnp.exp(f32(a_t))[:, None, :],
        f32(xd_t).transpose(0, 2, 1), f32(B_t).reshape(Bsz, 1, -1),
        f32(C_t).reshape(Bsz, 1, -1), interpret=not _on_tpu())
    return yt.transpose(0, 2, 1).astype(xd_t.dtype), states


ssd_chunk_ref = ssd_chunk_ref  # re-export for the test sweep
