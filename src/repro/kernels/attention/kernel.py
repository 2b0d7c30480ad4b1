"""Flash-style blocked attention kernel (TPU).

Design (DESIGN.md §6): grid = (batch*q_heads, num_q_blocks, num_kv_blocks)
with the kv dimension innermost and marked "arbitrary" (sequential) —
running max / denominator / accumulator live in VMEM scratch across kv
steps, so the S x S score matrix never exists: per step only a
[block_q, block_k] tile is materialized, MXU-shaped (multiples of 128
for paper-scale head dims).

GQA without materializing repeated K/V: the kv BlockSpec index_map folds
the query-head -> kv-head mapping (h_kv = h_q // group), so K/V stream
from HBM once per kv head group.

Causal + sliding-window masking is positional; fully-masked kv blocks are
skipped with ``pl.when`` (the compiler elides the DMA for untouched
blocks on the skipped steps' compute).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window, block_q: int, block_k: int,
                  sm_scale: float, q_offset: int, kv_valid: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = q_offset + iq * block_q
    k_start = ik * block_k
    # block-level skip: entirely above the diagonal / outside the window
    relevant = jnp.asarray(True)
    if causal:
        relevant &= k_start <= q_start + block_q - 1
    if window is not None:
        relevant &= k_start + block_k - 1 > q_start - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [bq, D]
        k = k_ref[0].astype(jnp.float32)            # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        mask = k_pos < kv_valid
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = l_scr[:, 0] * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_scr[:, 0] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window=None,
                           block_q: int = 128, block_k: int = 128,
                           q_offset: int = 0, kv_valid=None,
                           interpret: bool = True):
    """q [BH, Sq, D] (batch*q_heads folded); k, v [BKV, Skv, D] with
    BKV = batch*kv_heads; group = BH // BKV. Sq % block_q == 0,
    Skv % block_k == 0 (wrapper pads). Returns [BH, Sq, D]."""
    BH, Sq, D = q.shape
    BKV, Skv, _ = k.shape
    group = BH // BKV
    if kv_valid is None:
        kv_valid = Skv
    grid = (BH, Sq // block_q, Skv // block_k)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, sm_scale=D ** -0.5, q_offset=q_offset,
        kv_valid=kv_valid)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            # GQA fold: query-head b maps to kv row b // group
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, D), jnp.float32),   # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
