"""GQA attention: blocked (flash-style, memory O(S·block)) training path,
single-step decode against a KV cache, sliding-window masking, and
cross-attention (enc-dec).

The blocked path is the pure-JAX twin of ``repro.kernels.attention``
(Pallas); both share the online-softmax algorithm so the Pallas kernel can
be validated against this implementation, and dry-run memory analysis
never sees an S x S score tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _pad_to(x, axis: int, multiple: int):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def blocked_attention(q, k, v, *, causal: bool = True,
                      window: int | None = None,
                      q_block: int = 512, kv_block: int = 1024,
                      q_offset: int = 0, scale: float | None = None):
    """Online-softmax attention.

    Args:
        q: [B, Sq, Hq, D]
        k, v: [B, Skv, Hkv, D] — Hq % Hkv == 0 (GQA).
        causal: apply causal mask (query position = q_offset + index).
        window: sliding-window size (keys within [pos-window+1, pos]).
        q_offset: absolute position of q[0] (for decode/chunked prefill).
        scale: score scale (default 1/sqrt(D)).

    Returns [B, Sq, Hq, D] in q.dtype.
    """
    orig_dtype = q.dtype
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale

    q, Sq0 = _pad_to(q, 1, q_block)
    k, Skv0 = _pad_to(k, 1, kv_block)
    v, _ = _pad_to(v, 1, kv_block)
    Sq_p, Skv_p = q.shape[1], k.shape[1]
    nq, nk = Sq_p // q_block, Skv_p // kv_block

    # [nq, B, qb, Hkv, G, D]
    qb = q.reshape(B, nq, q_block, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(B, nk, kv_block, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, kv_block, Hkv, D).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(Sq_p).reshape(nq, q_block)
    k_pos = jnp.arange(Skv_p).reshape(nk, kv_block)

    def q_step(_, qi):
        q_i, qpos_i = qi

        def kv_step(carry, ki):
            m, l, acc = carry
            k_j, v_j, kpos_j = ki
            # scores: [B, qb, Hkv, G, kvb]
            s = jnp.einsum("bqhgd,bkhd->bqhgk", q_i.astype(jnp.float32),
                           k_j.astype(jnp.float32)) * scale
            mask = kpos_j[None, :] <= qpos_i[:, None] if causal else \
                jnp.ones((q_block, kv_block), bool)
            if window is not None:
                mask = mask & (kpos_j[None, :] > qpos_i[:, None] - window)
            # mask out kv padding
            mask = mask & (kpos_j < Skv0)[None, :]
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            # §Perf: the probability tile is the largest attention tensor
            # (B*H*S^2); storing it in the compute dtype (bf16) halves its
            # HBM traffic while the accumulator stays f32 on the MXU.
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, q_block, Hkv, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_block, Hkv, G), jnp.float32)
        a0 = jnp.zeros((B, q_block, Hkv, G, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (kb, vb, k_pos))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(orig_dtype)

    _, out = jax.lax.scan(q_step, None, (qb, q_pos))
    # [nq, B, qb, Hkv, G, D] -> [B, Sq, Hq, D]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq_p, Hq, D)
    return out[:, :Sq0]


def decode_attention(q, sources):
    """Single-token decode attention over one or more KV sources.

    Serving design (DESIGN.md §5): the big prompt cache ("main") is
    READ-ONLY and can be sharded any way (seq or heads on the model axis)
    because decode never writes it; new tokens land in a small replicated
    ring/"recent" buffer via a clean dynamic-update-slice. Attention
    merges the sources with a shared softmax (single max/denominator),
    which never concatenates differently-sharded buffers.

    Args:
        q: [B, 1, Hq, D] (RoPE already applied).
        sources: list of (k, v, valid_len) with k, v [B, Sk, Hkv, D] and
            valid_len an int32 scalar (entries [0, valid_len) attend).

    Returns [B, 1, Hq, D].
    """
    B, _, Hq, D = q.shape
    Hkv = sources[0][0].shape[2]
    G = Hq // Hkv
    scale = D ** -0.5
    # §Perf: keep k/v in their storage dtype and let the MXU accumulate in
    # f32 (preferred_element_type); a wholesale .astype(f32) on the cache
    # makes XLA hoist an f32 copy of the ENTIRE stacked cache out of the
    # layer scan (observed: +13 GiB on qwen1.5 decode_32k).
    kdt = sources[0][0].dtype
    qh = q[:, 0].reshape(B, Hkv, G, D).astype(kdt)

    scores = []
    for k, v, valid_len in sources:
        s = jnp.einsum("bhgd,bkhd->bhgk", qh, k,
                       preferred_element_type=jnp.float32) * scale
        valid = jnp.arange(k.shape[1]) < valid_len
        scores.append(jnp.where(valid[None, None, None, :], s, NEG_INF))

    m = scores[0].max(axis=-1)
    for s in scores[1:]:
        m = jnp.maximum(m, s.max(axis=-1))
    denom = jnp.zeros_like(m)
    out = jnp.zeros((B, Hkv, G, D), jnp.float32)
    for s, (k, v, _) in zip(scores, sources):
        p = jnp.exp(s - m[..., None])
        denom = denom + p.sum(axis=-1)
        out = out + jnp.einsum("bhgk,bkhd->bhgd", p.astype(kdt), v,
                               preferred_element_type=jnp.float32)
    out = out / jnp.maximum(denom[..., None], 1e-30)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def reference_attention(q, k, v, *, causal: bool = True,
                        window: int | None = None, q_offset: int = 0):
    """Naive O(S^2) oracle — tests only."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    Skv = k.shape[1]
    qh = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qh.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)
