"""Mamba2 (state-space duality / SSD) block — arXiv:2405.21060.

Training path: chunked SSD — within a chunk the quadratic "dual" form runs
on the MXU; across chunks a sequential ``lax.scan`` carries the SSM state
(O(L) total). Decode path: the O(1) recurrence

    state <- exp(dt*A) * state + (dt*x) outer B
    y     <- C . state + D * x

This module is the pure-JAX reference; ``repro.kernels.ssd`` implements the
chunk kernel in Pallas with the same block decomposition.

Shapes (single SSM group, as in mamba2-370m / zamba2):
    x  [B, L, H, P]   (H heads, P = head_dim)
    dt [B, L, H]      (positive, after softplus + bias)
    A  [H]            (negative; A = -exp(A_log))
    B_, C_ [B, L, N]  (N = ssm_state)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd.ops import ssd_decode_inplace
from repro.models.layers import silu, rms_norm


def segsum(a):
    """[..., K] -> [..., K, K] lower-triangular segment sums:
    out[..., q, k] = sum_{i in (k, q]} a[..., i] for q >= k, else -inf."""
    K = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    d = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((K, K), bool), 0)
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(xd, a, B_, C_, chunk: int = 128, initial_state=None):
    """Chunked SSD scan.

    Args:
        xd: [B, L, H, P] — dt-scaled inputs (dt * x).
        a:  [B, L, H]    — per-step log decay (dt * A, negative).
        B_, C_: [B, L, N].
        chunk: chunk length (L padded to a multiple).
        initial_state: optional [B, H, P, N].

    Returns (y [B, L, H, P], final_state [B, H, P, N]).
    """
    Bsz, L, H, P = xd.shape
    N = B_.shape[-1]
    pad = (-L) % chunk
    if pad:
        xd = jnp.pad(xd, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))
    Lp = L + pad
    nc = Lp // chunk

    # [nc, B, K, ...]
    xc = xd.reshape(Bsz, nc, chunk, H, P).transpose(1, 0, 2, 3, 4)
    ac = a.reshape(Bsz, nc, chunk, H).transpose(1, 0, 2, 3)
    Bc = B_.reshape(Bsz, nc, chunk, N).transpose(1, 0, 2, 3)
    Cc = C_.reshape(Bsz, nc, chunk, N).transpose(1, 0, 2, 3)

    if initial_state is None:
        initial_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def chunk_step(state, inp):
        x_k, a_k, B_k, C_k = inp       # [B,K,H,P], [B,K,H], [B,K,N], [B,K,N]
        x32 = x_k.astype(jnp.float32)
        a32 = a_k.astype(jnp.float32)
        B32 = B_k.astype(jnp.float32)
        C32 = C_k.astype(jnp.float32)

        a_hk = a32.transpose(0, 2, 1)                 # [B,H,K]
        cum = jnp.cumsum(a_hk, axis=-1)               # [B,H,K]
        Lmat = jnp.exp(segsum(a_hk))                  # [B,H,K,K] lower-tri

        # intra-chunk (dual / attention-like form)
        scores = jnp.einsum("bqn,bkn->bqk", C32, B32)  # [B,K,K]
        Y = jnp.einsum("bqk,bhqk,bkhp->bqhp", scores, Lmat, x32)

        # contribution of the carried state
        decay_q = jnp.exp(cum).transpose(0, 2, 1)      # [B,K,H]
        Y = Y + jnp.einsum("bqn,bqh,bhpn->bqhp", C32, decay_q, state)

        # state update
        total = cum[..., -1]                           # [B,H]
        decay_k = jnp.exp(total[..., None] - cum)      # [B,H,K]
        new_state = state * jnp.exp(total)[..., None, None] + jnp.einsum(
            "bkn,bhk,bkhp->bhpn", B32, decay_k, x32)
        return new_state, Y.astype(xd.dtype)

    final_state, Yc = jax.lax.scan(chunk_step, initial_state,
                                   (xc, ac, Bc, Cc))
    y = Yc.transpose(1, 0, 2, 3, 4).reshape(Bsz, Lp, H, P)
    return y[:, :L], final_state


def ssd_reference(xd, a, B_, C_, initial_state=None):
    """O(L) sequential oracle (tests only)."""
    Bsz, L, H, P = xd.shape
    N = B_.shape[-1]
    state = (jnp.zeros((Bsz, H, P, N), jnp.float32)
             if initial_state is None else initial_state)
    ys = []
    for t in range(L):
        state = (state * jnp.exp(a[:, t]).astype(jnp.float32)[..., None, None]
                 + jnp.einsum("bn,bhp->bhpn", B_[:, t].astype(jnp.float32),
                              xd[:, t].astype(jnp.float32)))
        ys.append(jnp.einsum("bn,bhpn->bhp", C_[:, t].astype(jnp.float32),
                             state))
    return jnp.stack(ys, axis=1).astype(xd.dtype), state


def ssd_decode_step(state, xd_t, a_t, B_t, C_t):
    """One decode step. state [B,H,P,N]; xd_t [B,H,P]; a_t [B,H];
    B_t, C_t [B,N]. Returns (y_t [B,H,P], new_state)."""
    decay = jnp.exp(a_t.astype(jnp.float32))[..., None, None]
    state = state * decay + jnp.einsum(
        "bn,bhp->bhpn", B_t.astype(jnp.float32), xd_t.astype(jnp.float32))
    y = jnp.einsum("bn,bhpn->bhp", C_t.astype(jnp.float32), state)
    return y.astype(xd_t.dtype), state


# -------------------------------------------------------------------------
# Full Mamba2 block (in_proj -> causal conv -> SSD -> gated norm -> out)
# -------------------------------------------------------------------------

def _split_proj(zxbcdt, d_inner, n_state, n_heads):
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:2 * d_inner + 2 * n_state]
    dt = zxbcdt[..., 2 * d_inner + 2 * n_state:]
    assert dt.shape[-1] == n_heads
    return z, xBC, dt


def causal_conv1d(x, w, b):
    """Depthwise causal conv. x [B, L, Cdim]; w [Cdim, K]; b [Cdim]."""
    K = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    # windows: y[t] = sum_k x[t-K+1+k] * w[k]
    out = sum(xp[:, k:k + x.shape[1], :] * w[None, None, :, k]
              for k in range(K))
    return out + b[None, None, :]


def conv_decode_step(conv_state, x_t, w, b):
    """conv_state [B, K-1, Cdim] holds the last K-1 inputs; x_t [B, Cdim]."""
    K = w.shape[-1]
    full = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # [B,K,C]
    y = jnp.einsum("bkc,ck->bc", full, w) + b[None, :]
    return y, full[:, 1:, :] if K > 1 else conv_state


def mamba2_apply(p, x, *, head_dim: int, ssm_state: int, chunk: int = 128,
                 dt_limit=(1e-4, 1e2)):
    """Full block forward. x [B, L, D] -> [B, L, D]."""
    Bsz, L, D = x.shape
    d_inner = p["out_proj"].shape[0]
    H = d_inner // head_dim
    N = ssm_state

    zxbcdt = x @ p["in_proj"]
    z, xBC, dt = _split_proj(zxbcdt, d_inner, N, H)
    xBC = silu(causal_conv1d(xBC, p["conv_w"], p["conv_b"]))
    xs = xBC[..., :d_inner].reshape(Bsz, L, H, head_dim)
    B_ = xBC[..., d_inner:d_inner + N]
    C_ = xBC[..., d_inner + N:]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    dt = jnp.clip(dt, *dt_limit)                                # [B, L, H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                # [H]
    a = dt * A[None, None, :]
    xd = xs * dt[..., None].astype(xs.dtype)

    y, _ = ssd_chunked(xd, a, B_, C_, chunk=chunk)
    y = y + xs * p["D"][None, None, :, None]
    y = y.reshape(Bsz, L, d_inner)
    y = rms_norm(y * silu(z), p["norm_w"])
    return y @ p["out_proj"]


def _mamba2_decode_in(p, x_t, conv_state, *, head_dim: int, ssm_state: int,
                      dt_limit):
    """The decode step up to the SSM update: the projection, the conv
    step and dt. Returns (z, xs [B, H, P], xd_t, a_t, B_t, C_t,
    conv_state)."""
    Bsz, D = x_t.shape
    d_inner = p["out_proj"].shape[0]
    H = d_inner // head_dim
    N = ssm_state

    zxbcdt = x_t @ p["in_proj"]
    z, xBC, dt = _split_proj(zxbcdt, d_inner, N, H)
    xBC, conv_state = conv_decode_step(conv_state, xBC, p["conv_w"],
                                       p["conv_b"])
    xBC = silu(xBC)
    xs = xBC[..., :d_inner].reshape(Bsz, H, head_dim)
    B_ = xBC[..., d_inner:d_inner + N]
    C_ = xBC[..., d_inner + N:]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    dt = jnp.clip(dt, *dt_limit)                                # [B, H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a_t = dt * A[None, :]
    xd_t = xs * dt[..., None].astype(xs.dtype)
    return z, xs, xd_t, a_t, B_, C_, conv_state


def _mamba2_decode_out(p, y, xs, z, norm_eps):
    """The decode step after the SSM update: D skip, gated norm, out
    projection."""
    y = y + xs * p["D"][None, :, None]
    y = y.reshape(y.shape[0], -1)
    y = rms_norm(y * silu(z), p["norm_w"], norm_eps)
    return y @ p["out_proj"]


def mamba2_decode(p, x_t, conv_state, ssm_state_arr, *, head_dim: int,
                  ssm_state: int, dt_limit=(1e-4, 1e2),
                  norm_eps: float = 1e-6):
    """One-token decode. x_t [B, D]. Returns (y [B, D], conv_state, state)."""
    z, xs, xd_t, a_t, B_, C_, conv_state = _mamba2_decode_in(
        p, x_t, conv_state, head_dim=head_dim, ssm_state=ssm_state,
        dt_limit=dt_limit)
    y, ssm_state_arr = ssd_decode_step(ssm_state_arr, xd_t, a_t, B_, C_)
    return (_mamba2_decode_out(p, y, xs, z, norm_eps), conv_state,
            ssm_state_arr)


def mamba2_decode_stacked(p, x_t, conv_state, states, k, mask, *,
                          head_dim: int, ssm_state: int,
                          dt_limit=(1e-4, 1e2), norm_eps: float = 1e-6):
    """``mamba2_decode`` of layer ``k`` of a stacked SSM state
    [L, B, H, P, N], updated in place by the Pallas kernel
    ``ssd_decode_inplace``: lanes outside ``mask`` [B] keep their state.
    Returns (y [B, D], conv_state, states)."""
    z, xs, xd_t, a_t, B_, C_, conv_state = _mamba2_decode_in(
        p, x_t, conv_state, head_dim=head_dim, ssm_state=ssm_state,
        dt_limit=dt_limit)
    y, states = ssd_decode_inplace(states, k, mask, a_t, xd_t, B_, C_)
    return (_mamba2_decode_out(p, y, xs, z, norm_eps), conv_state,
            states)
