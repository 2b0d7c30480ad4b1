"""Mamba2 SSD chunk kernel (TPU adaptation of the GPU SSD algorithm).

TPU rethink (DESIGN.md §6): the GPU implementation leans on warp-level
shuffles for the intra-chunk scan; on TPU we use the *dual* (quadratic-
in-chunk) form so the intra-chunk work is two MXU matmuls —
[K,N]x[N,K] score matrix and [K,K]x[K,P] mix — plus a VMEM-resident
decay mask built from a cumulative sum. The inter-chunk recurrence is a
sequential grid dimension carrying the [P, N] state in VMEM scratch.

Grid = (batch, heads, chunks); chunks is "arbitrary" (sequential), so the
state never round-trips to HBM between chunks — it is written out once at
the last chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xd_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_scr, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xd = xd_ref[0, :, 0, :].astype(jnp.float32)       # [K, P]
    a = a_ref[0, :, 0].astype(jnp.float32)            # [K]
    B_ = b_ref[0].astype(jnp.float32)                 # [K, N]
    C_ = c_ref[0].astype(jnp.float32)                 # [K, N]
    state = state_scr[...]                            # [P, N]

    cum = jnp.cumsum(a)                               # [K]
    d = cum[:, None] - cum[None, :]
    K = chunk
    mask = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    L = jnp.where(mask, jnp.exp(d), 0.0)

    scores = jnp.dot(C_, B_.T, preferred_element_type=jnp.float32)
    y = jnp.dot(scores * L, xd, preferred_element_type=jnp.float32)
    y = y + jnp.dot(C_, state.T,
                    preferred_element_type=jnp.float32) * jnp.exp(cum)[:, None]

    total = cum[-1]
    decay_k = jnp.exp(total - cum)
    new_state = state * jnp.exp(total) + jnp.dot(
        xd.T, B_ * decay_k[:, None], preferred_element_type=jnp.float32)

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)
    state_scr[...] = new_state

    @pl.when(ic == nc - 1)
    def _done():
        state_out_ref[0, 0] = new_state.astype(state_out_ref.dtype)


def ssd_pallas(xd, a, B_, C_, *, chunk: int = 128, interpret: bool = True):
    """Full SSD scan via the chunk kernel.

    xd [B, L, H, P]; a [B, L, H]; B_, C_ [B, L, N]; L % chunk == 0.
    Returns (y [B, L, H, P], final_state [B, H, P, N]) — float32 state.
    """
    Bsz, L, H, P = xd.shape
    N = B_.shape[-1]
    assert L % chunk == 0
    nc = L // chunk
    grid = (Bsz, H, nc)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, state = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((Bsz, L, H, P), xd.dtype),
                   jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xd, a, B_, C_)
    return y, state


def _decode_kernel(k_ref, mask_ref, decay_ref, xd_ref, b_ref, c_ref,
                   s_ref, yt_ref, s_out_ref):
    del k_ref                                         # used by index_map
    step = mask_ref[pl.program_id(0)] != 0
    b_row = b_ref[0]                                  # [1, N]
    c_row = c_ref[0]                                  # [1, N]
    for h in range(s_ref.shape[2]):                   # heads, unrolled
        s = s_ref[0, 0, h]                            # [P, N]
        new = (s.astype(jnp.float32) * decay_ref[0, :, h:h + 1]
               + xd_ref[0, :, h:h + 1] * b_row)
        s_out_ref[0, 0, h] = jnp.where(step, new.astype(s.dtype), s)
        yt_ref[0, :, h:h + 1] = jnp.sum(new * c_row, axis=1, keepdims=True)


def ssd_decode_pallas(states, k, mask, decay, xd_t, B_t, C_t, *,
                      interpret: bool = True):
    """One decode step of layer ``k`` of a stacked SSM state, in place.

    states [L, B, H, P, N], aliased to the returned state; k int32
    scalar; mask [B] int32 (0: the lane keeps its state); decay
    [B, 1, H] (exp(dt*A)); xd_t [B, P, H] (dt*x, heads on lanes); B_t,
    C_t [B, 1, N]; all float32 but the states, which are computed in
    float32 and stored in their own dtype. Returns (y [B, P, H]
    float32, states): the stepped lanes' y and the states with layer k
    of those lanes stepped. One grid step is one lane's whole layer-k
    state, read and written once; the other layers are never
    touched."""
    L, Bsz, H, P, N = states.shape
    lane = lambda b, k, m: (b, 0, 0)                  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bsz,),
        in_specs=[
            pl.BlockSpec((1, 1, H), lane),
            pl.BlockSpec((1, P, H), lane),
            pl.BlockSpec((1, 1, N), lane),
            pl.BlockSpec((1, 1, N), lane),
            pl.BlockSpec((1, 1, H, P, N),
                         lambda b, k, m: (k[0], b, 0, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, P, H), lane),
            pl.BlockSpec((1, 1, H, P, N),
                         lambda b, k, m: (k[0], b, 0, 0, 0)),
        ),
    )
    return pl.pallas_call(
        _decode_kernel,
        out_shape=(jax.ShapeDtypeStruct((Bsz, P, H), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)),
        grid_spec=grid_spec,
        # operands count the two scalar-prefetch arguments: 6 = states
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.reshape(k, (1,)).astype(jnp.int32), mask.astype(jnp.int32),
      decay, xd_t, B_t, C_t, states)
