"""Transformer zoo: decoder LMs (dense / MoE / VLM), SSM stacks, hybrids
(Mamba2 + shared attention; Mamba2 and attention by layer pattern), and
encoder-decoder (Whisper backbone).

Functional design:
    params = init_lm(cfg, key)                  (or jax.eval_shape for dry-run)
    logits, aux = lm_forward(cfg, params, batch)            # train
    logits, cache = lm_prefill(cfg, params, batch)          # prefill
    logits, cache = lm_decode_step(cfg, params, tok, cache) # decode

Layers are stacked on a leading [L, ...] dim and driven by ``jax.lax.scan``
(one compiled block body per block type — keeps 94-layer models cheap to
compile) with optional rematerialization.

Whisper deviation (see configs/whisper_medium.py): the decoder uses RoPE
instead of learned positions so parameter shapes stay independent of the
dry-run sequence length; the encoder keeps a learned [n_frames, d] table.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.ssd import ops as ssd_ops
from repro.models.attention import blocked_attention, decode_attention
from repro.models.layers import (
    apply_norm,
    apply_rope,
    dense_init,
    embed_init,
    norm_param,
    rms_norm,
)
from repro.models.mlp import mlp_apply, moe_apply
from repro.models.pshard import constrain
from repro.models import ssm as ssm_mod

PyTree = Any


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ==========================================================================
# Parameter construction
# ==========================================================================

def _init_attn(key, cfg: ArchConfig, dt, n_heads=None, n_kv=None, head_dim=None):
    H = n_heads or cfg.n_heads
    Hkv = n_kv or cfg.n_kv_heads
    hd = head_dim or cfg.head_dim
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dt),
        "wk": dense_init(ks[1], (d, Hkv * hd), dt),
        "wv": dense_init(ks[2], (d, Hkv * hd), dt),
        "wo": dense_init(ks[3], (H * hd, d), dt, scale=1.0),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((Hkv * hd,), dt)
        p["bv"] = jnp.zeros((Hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _init_mlp(key, cfg: ArchConfig, dt):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], (d, f), dt),
         "w2": dense_init(ks[1], (f, d), dt)}
    if cfg.gated_mlp:
        p["w3"] = dense_init(ks[2], (d, f), dt)
    return p


def _init_moe(key, cfg: ArchConfig, dt):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    p = {"router": dense_init(ks[0], (d, E), jnp.float32),
         "w1": dense_init(ks[1], (E, d, f), dt),
         "w2": dense_init(ks[2], (E, f, d), dt)}
    if cfg.gated_mlp:
        p["w3"] = dense_init(ks[3], (E, d, f), dt)
    return p


def _init_ssm_block(key, cfg: ArchConfig, dt):
    d = cfg.d_model
    di = cfg.d_inner
    N = cfg.ssm_state
    H = cfg.ssm_heads
    conv_dim = di + 2 * N
    ks = jax.random.split(key, 3)
    return {
        "in_proj": dense_init(ks[0], (d, 2 * di + 2 * N + H), dt),
        "conv_w": dense_init(ks[1], (conv_dim, cfg.ssm_conv), dt, scale=1.0),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.zeros((H,), jnp.float32),      # A = -1 at init
        "D": jnp.ones((H,), dt),
        "norm_w": jnp.ones((di,), dt),
        "out_proj": dense_init(ks[2], (di, d), dt, scale=1.0),
    }


def _stack(fn, key, n: int):
    """Init ``n`` copies of a param subtree and stack leaves on axis 0."""
    keys = jax.random.split(key, n)
    trees = [fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs, 0), *trees)


def _init_decoder_layer(key, cfg: ArchConfig, dt, cross: bool = False):
    ks = jax.random.split(key, 6)
    p = {"norm1": norm_param(cfg.norm, cfg.d_model, dt),
         "attn": _init_attn(ks[0], cfg, dt),
         "norm2": norm_param(cfg.norm, cfg.d_model, dt)}
    if cfg.n_experts:
        p["moe"] = _init_moe(ks[1], cfg, dt)
    else:
        p["mlp"] = _init_mlp(ks[1], cfg, dt)
    if cross:
        p["norm_x"] = norm_param(cfg.norm, cfg.d_model, dt)
        p["xattn"] = _init_attn(ks[2], cfg, dt)
    return p


def init_lm(cfg: ArchConfig, key) -> PyTree:
    """Build the parameter pytree for any assigned architecture."""
    if cfg.layer_types:
        return _init_pattern(cfg, key)
    dt = _dtype(cfg)
    ks = jax.random.split(key, 8)
    V, d = cfg.padded_vocab, cfg.d_model
    params: dict = {
        "embed": embed_init(ks[0], (V, d), dt),
        "final_norm": norm_param(cfg.norm, d, dt),
        "lm_head": dense_init(ks[1], (d, V), dt),
    }
    if cfg.family == "ssm":
        params["layers"] = _stack(
            lambda k: {"norm1": norm_param(cfg.norm, d, dt),
                       "ssm": _init_ssm_block(k, cfg, dt)},
            ks[2], cfg.n_layers)
    elif cfg.family == "hybrid":
        params["layers"] = _stack(
            lambda k: {"norm1": norm_param(cfg.norm, d, dt),
                       "ssm": _init_ssm_block(k, cfg, dt)},
            ks[2], cfg.n_layers)
        # one SHARED attention+MLP block (tied weights, applied per stage)
        params["shared"] = _init_decoder_layer(ks[3], cfg, dt)
    elif cfg.family == "audio":
        params["enc_pos"] = embed_init(ks[4], (cfg.n_frames, d), dt)
        params["enc_layers"] = _stack(
            lambda k: _init_decoder_layer(k, cfg, dt), ks[5],
            cfg.encoder_layers)
        params["layers"] = _stack(
            lambda k: _init_decoder_layer(k, cfg, dt, cross=True), ks[2],
            cfg.n_layers)
    else:  # dense / moe / vlm
        params["layers"] = _stack(
            lambda k: _init_decoder_layer(k, cfg, dt), ks[2], cfg.n_layers)
    return params


# ==========================================================================
# Attention block (training / prefill path)
# ==========================================================================

def _project_qkv(cfg: ArchConfig, p, x, positions):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, -1, hd)
    k = k.reshape(B, S, -1, hd)
    v = v.reshape(B, S, -1, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if positions is not None:  # rope (None => learned/absolute upstream)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_block(cfg: ArchConfig, p, x, positions, *, causal=True,
                window=None, return_kv=False):
    q, k, v = _project_qkv(cfg, p, x, positions)
    out = blocked_attention(q, k, v, causal=causal, window=window)
    out = out.reshape(x.shape[0], x.shape[1], -1) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def _cross_attn_block(cfg: ArchConfig, p, x, kv):
    """Cross attention: q from x, (k, v) precomputed from encoder output."""
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(B, S, -1, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
    k, v = kv
    out = blocked_attention(q, k, v, causal=False)
    return out.reshape(B, S, -1) @ p["wo"]


def _encode_cross_kv(cfg: ArchConfig, p, enc_out):
    """Per-decoder-layer k/v projections of the encoder output."""
    B, F, _ = enc_out.shape
    hd = cfg.head_dim
    k = enc_out @ p["wk"]
    v = enc_out @ p["wv"]
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(B, F, -1, hd)
    v = v.reshape(B, F, -1, hd)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"])
    return k, v


# ==========================================================================
# Layer bodies (train / prefill)
# ==========================================================================

def _ffn(cfg: ArchConfig, lp, h):
    if cfg.n_experts:
        out, aux = moe_apply(lp["moe"], h, top_k=cfg.top_k,
                             activation=cfg.activation, gated=cfg.gated_mlp,
                             group_size=cfg.moe_group_size,
                             capacity_factor=cfg.moe_capacity_factor)
        return out, aux
    return mlp_apply(lp["mlp"], h, cfg.activation, cfg.gated_mlp), 0.0


def _decoder_block(cfg: ArchConfig, lp, x, positions, window, cross_kv=None):
    x = constrain(x, "batch", None, None)
    h = apply_norm(x, lp["norm1"], cfg.norm)
    x = x + _attn_block(cfg, lp["attn"], h, positions, window=window)
    if cross_kv is not None:
        h = apply_norm(x, lp["norm_x"], cfg.norm)
        x = x + _cross_attn_block(cfg, lp["xattn"], h, cross_kv)
    h = apply_norm(x, lp["norm2"], cfg.norm)
    out, aux = _ffn(cfg, lp, h)
    return constrain(x + out, "batch", None, None), aux


def _ssm_block(cfg: ArchConfig, lp, x):
    x = constrain(x, "batch", None, None)
    h = apply_norm(x, lp["norm1"], cfg.norm)
    y = x + ssm_mod.mamba2_apply(
        lp["ssm"], h, head_dim=cfg.ssm_head_dim, ssm_state=cfg.ssm_state,
        chunk=cfg.ssm_chunk)
    return constrain(y, "batch", None, None)


def _effective_window(cfg: ArchConfig, seq_len: int):
    """SWA window for this forward: native window if the arch has one,
    else the long-context variant window when seq_len is huge (DESIGN §4)."""
    if cfg.window is not None:
        return cfg.window
    if seq_len > 131072 and cfg.family not in ("ssm",):
        return cfg.long_context_window
    return None


def _maybe_remat(cfg: ArchConfig, fn):
    return jax.checkpoint(fn) if cfg.remat else fn


# ==========================================================================
# Forward (training) — returns logits and MoE aux loss
# ==========================================================================

def _embed(cfg: ArchConfig, params, tokens):
    return constrain(params["embed"][tokens], "batch", None, None)


def _run_encoder(cfg: ArchConfig, params, frames):
    """frames: [B, n_frames, d] (stub frontend output) -> [B, n_frames, d]."""
    x = frames.astype(_dtype(cfg)) + params["enc_pos"][None, :frames.shape[1]]

    # bidirectional: the encoder calls the attention block with causal=False.
    def enc_block(x, lp):
        h = apply_norm(x, lp["norm1"], cfg.norm)
        x = x + _attn_block(cfg, lp["attn"], h, None, causal=False)
        h = apply_norm(x, lp["norm2"], cfg.norm)
        out, _ = _ffn(cfg, lp, h)
        return x + out, None

    x, _ = jax.lax.scan(_maybe_remat(cfg, enc_block), x,
                        params["enc_layers"])
    return x


def lm_forward(cfg: ArchConfig, params: PyTree, tokens, frames=None):
    """Training/prefill forward.

    tokens: int32 [B, S]. frames: [B, n_frames, d] for audio archs.
    Returns (logits [B, S, padded_vocab], aux_loss scalar).
    """
    if cfg.layer_types:
        return _pattern_forward(cfg, params, tokens), jnp.zeros((), jnp.float32)
    B, S = tokens.shape
    x = _embed(cfg, params, tokens)
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    window = _effective_window(cfg, S)
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        def body(x, lp):
            return _ssm_block(cfg, lp, x), None
        x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, params["layers"])

    elif cfg.family == "hybrid":
        n_stages = cfg.n_layers // cfg.attn_every
        stacked = jax.tree.map(
            lambda a: a.reshape((n_stages, cfg.attn_every) + a.shape[1:]),
            params["layers"])
        shared = params["shared"]

        def stage(x, stage_params):
            def inner(x, lp):
                return _ssm_block(cfg, lp, x), None
            x, _ = jax.lax.scan(inner, x, stage_params)
            x, _ = _decoder_block(cfg, shared, x, positions, window)
            return x, None

        x, _ = jax.lax.scan(_maybe_remat(cfg, stage), x, stacked)

    elif cfg.family == "audio":
        if frames is None:
            raise ValueError("audio arch requires frame embeddings")
        enc_out = _run_encoder(cfg, params, frames)

        def body(carry, lp):
            x = carry
            kv = _encode_cross_kv(cfg, lp["xattn"], enc_out)
            x, _ = _decoder_block(cfg, lp, x, positions, window, cross_kv=kv)
            return x, None

        x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, params["layers"])

    else:  # dense / moe / vlm
        def body(carry, lp):
            x, aux = carry
            x, a = _decoder_block(cfg, lp, x, positions, window)
            return (x, aux + a), None

        (x, aux_total), _ = jax.lax.scan(
            _maybe_remat(cfg, body), (x, aux_total), params["layers"])

    x = apply_norm(x, params["final_norm"], cfg.norm)
    logits = constrain(x @ params["lm_head"], "batch", None, "model")
    return logits, aux_total


def lm_loss(cfg: ArchConfig, params: PyTree, tokens, frames=None,
            aux_weight: float = 0.01):
    """Next-token cross entropy (+ MoE load-balance aux)."""
    logits, aux = lm_forward(cfg, params, tokens, frames)
    logits = logits[:, :-1].astype(jnp.float32)
    labels = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    return ce + aux_weight * aux


# ==========================================================================
# KV / state caches and decode
# ==========================================================================

def _attn_cache_mode(cfg: ArchConfig, max_len: int) -> tuple[str, int]:
    """('ring', W) for sliding-window archs (cache = W slots, slot =
    pos % W), else ('full', max_len) with a main+recent split."""
    W = _effective_window(cfg, max_len)
    if W is not None and W < max_len:
        return "ring", W
    return "full", max_len


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    if cfg.layer_types:
        return _pattern_cache(cfg, batch, max_len)
    dt = _dtype(cfg)
    hd, Hkv = cfg.head_dim, cfg.n_kv_heads
    cache: dict = {"len": jnp.zeros((), jnp.int32)}

    def attn_bufs(n_stacked: int) -> dict:
        # The replicated decode write buffer ("recent" tokens,
        # cfg.decode_buffer slots): new-token k/v land here via a clean
        # DUS; the big "main" cache is read-only inside a decode step and
        # is folded in by flush_recent — a simplified paged-KV layout
        # that keeps main shardable on any axis (DESIGN.md §5).
        mode, size = _attn_cache_mode(cfg, max_len)
        R = cfg.decode_buffer
        bufs = {
            "k": jnp.zeros((n_stacked, batch, size, Hkv, hd), dt),
            "v": jnp.zeros((n_stacked, batch, size, Hkv, hd), dt),
        }
        if mode == "full":
            bufs["kr"] = jnp.zeros((n_stacked, batch, R, Hkv, hd), dt)
            bufs["vr"] = jnp.zeros((n_stacked, batch, R, Hkv, hd), dt)
            bufs["flushed"] = jnp.zeros((), jnp.int32)
        return bufs

    if cfg.family == "ssm":
        cache.update(_ssm_cache(cfg, batch, cfg.n_layers, dt))
    elif cfg.family == "hybrid":
        n_stages = cfg.n_layers // cfg.attn_every
        cache.update(_ssm_cache(cfg, batch, cfg.n_layers, dt))
        cache.update(attn_bufs(n_stages))
    elif cfg.family == "audio":
        cache.update(attn_bufs(cfg.n_layers))
        cache["xk"] = jnp.zeros((cfg.n_layers, batch, cfg.n_frames, Hkv, hd), dt)
        cache["xv"] = jnp.zeros((cfg.n_layers, batch, cfg.n_frames, Hkv, hd), dt)
    else:
        cache.update(attn_bufs(cfg.n_layers))
    return cache


def _ssm_cache(cfg: ArchConfig, batch: int, n_layers: int, dt):
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state
    return {
        "conv": jnp.zeros((n_layers, batch, cfg.ssm_conv - 1, conv_dim), dt),
        "ssm": jnp.zeros((n_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                          cfg.ssm_state), jnp.float32),
    }


def _decode_attn(cfg: ArchConfig, p, x, bufs, pos, flushed):
    """x: [B, 1, d]; bufs = (k, v) ring or (k, v, kr, vr) full split.
    pos: scalar int32 (token index being decoded); flushed: int32 count
    of tokens already flushed into the main cache (full mode).
    Returns (out [B, 1, d], new_bufs) — main k/v pass through untouched."""
    positions = pos[None, None].repeat(x.shape[0], 0)
    q, k, v = _project_qkv(cfg, p, x, positions)
    if len(bufs) == 2:                      # ring (sliding window)
        kc, vc = bufs
        W = kc.shape[1]
        slot = pos % W
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, slot, axis=1)
        out = decode_attention(q, [(kc, vc, jnp.minimum(pos + 1, W))])
        new_bufs = (kc, vc)
    else:                                   # full: read-only main + recent
        km, vm, kr, vr = bufs
        slot = pos - flushed
        kr = jax.lax.dynamic_update_slice_in_dim(kr, k, slot, axis=1)
        vr = jax.lax.dynamic_update_slice_in_dim(vr, v, slot, axis=1)
        out = decode_attention(
            q, [(km, vm, flushed), (kr, vr, pos - flushed + 1)])
        new_bufs = (km, vm, kr, vr)
    out = out.reshape(x.shape[0], 1, -1) @ p["wo"]
    return out, new_bufs


def _decode_ssm_block(cfg: ArchConfig, lp, x, conv_state, ssm_state):
    h = apply_norm(x, lp["norm1"], cfg.norm)
    y, conv_state, ssm_state = ssm_mod.mamba2_decode(
        lp["ssm"], h[:, 0], conv_state, ssm_state,
        head_dim=cfg.ssm_head_dim, ssm_state=cfg.ssm_state)
    return x + y[:, None], conv_state, ssm_state


def lm_decode_step(cfg: ArchConfig, params: PyTree, token, cache: PyTree,
                   active=None):
    """One decode step. token: int32 [B]. Returns (logits [B, V], cache).

    Attention caches: ring mode writes in place (slot = pos % W); full
    mode writes only the replicated recent buffer — the sharded main
    cache passes through untouched (flushed by ``flush_recent``).

    The layer-pattern hybrid's cache keeps a length per row, and
    ``active`` [B] bool (its only) steps some rows and leaves the others
    as they were (see ``_pattern_decode``)."""
    if cfg.layer_types:
        return _pattern_decode(cfg, params, token, cache, active)
    if active is not None:
        raise ValueError(f"{cfg.name}: rows share one cache length; "
                         f"every row steps")
    pos = cache["len"]
    full = "kr" in cache
    flushed = cache.get("flushed", jnp.zeros((), jnp.int32))
    x = _embed(cfg, params, token[:, None])
    x = constrain(x, "batch", None, None)
    new_cache = dict(cache)

    def attn_xs(extra=()):
        bufs = (cache["k"], cache["v"]) + (
            (cache["kr"], cache["vr"]) if full else ())
        return bufs + tuple(extra)

    def split_bufs(inp):
        if full:
            return inp[:4], inp[4:]
        return inp[:2], inp[2:]

    def updated(new_bufs):
        """Scan outputs: only the written buffers (main is read-only)."""
        if full:
            return new_bufs[2:]             # (kr, vr)
        return new_bufs                     # (k, v)

    def store(out_bufs):
        if full:
            new_cache.update(kr=out_bufs[0], vr=out_bufs[1])
        else:
            new_cache.update(k=out_bufs[0], v=out_bufs[1])

    if cfg.family == "ssm":
        def body(x, inp):
            lp, conv, st = inp
            x, conv, st = _decode_ssm_block(cfg, lp, x, conv, st)
            return x, (conv, st)
        x, (conv, st) = jax.lax.scan(
            body, x, (params["layers"], cache["conv"], cache["ssm"]))
        new_cache.update(conv=conv, ssm=st)

    elif cfg.family == "hybrid":
        n_stages = cfg.n_layers // cfg.attn_every
        re_stage = lambda a: a.reshape((n_stages, cfg.attn_every) + a.shape[1:])
        re_flat = lambda a: a.reshape((cfg.n_layers,) + a.shape[2:])
        stacked = jax.tree.map(re_stage, params["layers"])
        conv_s = re_stage(cache["conv"])
        ssm_s = re_stage(cache["ssm"])
        shared = params["shared"]

        def stage(x, inp):
            sp, conv, st = inp[0], inp[1], inp[2]
            bufs, _ = split_bufs(inp[3:])
            def inner(x, i):
                lp, c, s = i
                x, c, s = _decode_ssm_block(cfg, lp, x, c, s)
                return x, (c, s)
            x, (conv, st) = jax.lax.scan(inner, x, (sp, conv, st))
            h = apply_norm(x, shared["norm1"], cfg.norm)
            a, new_bufs = _decode_attn(cfg, shared["attn"], h, bufs, pos,
                                       flushed)
            x = x + a
            h = apply_norm(x, shared["norm2"], cfg.norm)
            out, _ = _ffn(cfg, shared, h)
            return x + out, (conv, st) + updated(new_bufs)

        x, outs = jax.lax.scan(
            stage, x, (stacked, conv_s, ssm_s) + attn_xs())
        new_cache.update(conv=re_flat(outs[0]), ssm=re_flat(outs[1]))
        store(outs[2:])

    elif cfg.family == "audio":
        def body(x, inp):
            lp = inp[0]
            bufs, rest = split_bufs(inp[1:])
            xk, xv = rest
            h = apply_norm(x, lp["norm1"], cfg.norm)
            a, new_bufs = _decode_attn(cfg, lp["attn"], h, bufs, pos,
                                       flushed)
            x = x + a
            h = apply_norm(x, lp["norm_x"], cfg.norm)
            x = x + _cross_attn_block(cfg, lp["xattn"], h, (xk, xv))
            h = apply_norm(x, lp["norm2"], cfg.norm)
            out, _ = _ffn(cfg, lp, h)
            return x + out, updated(new_bufs)
        x, outs = jax.lax.scan(
            body, x, (params["layers"],) + attn_xs((cache["xk"],
                                                    cache["xv"])))
        store(outs)

    else:  # dense / moe / vlm
        def body(x, inp):
            lp = inp[0]
            bufs, _ = split_bufs(inp[1:])
            h = apply_norm(x, lp["norm1"], cfg.norm)
            a, new_bufs = _decode_attn(cfg, lp["attn"], h, bufs, pos,
                                       flushed)
            x = x + a
            h = apply_norm(x, lp["norm2"], cfg.norm)
            out, _ = _ffn(cfg, lp, h)
            return x + out, updated(new_bufs)
        x, outs = jax.lax.scan(body, x, (params["layers"],) + attn_xs())
        store(outs)

    x = apply_norm(x, params["final_norm"], cfg.norm)
    logits = constrain((x @ params["lm_head"])[:, 0], "batch", "model")
    new_cache["len"] = pos + 1
    return logits, new_cache


def flush_recent(cfg: ArchConfig, cache: PyTree) -> PyTree:
    """Fold the full recent buffer into the main cache (full mode only).
    Called by the serving loop every DECODE_BUFFER tokens; this is the
    only op that writes the (possibly model-axis-sharded) main cache, so
    any resharding cost is amortized over DECODE_BUFFER decode steps."""
    if "kr" not in cache:
        return cache
    flushed = cache["flushed"]
    n_new = cache["len"] - flushed
    out = dict(cache)
    out["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], cache["kr"], flushed, axis=2)
    out["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], cache["vr"], flushed, axis=2)
    out["flushed"] = flushed + n_new
    return out


def _pack_prefill_attn(cfg: ArchConfig, k, v, S: int) -> dict:
    """Convert prefill-computed stacked k/v [Lc, B, S, H, hd] into the
    decode cache layout (ring-rolled for SWA archs; main+empty-recent for
    full attention)."""
    mode, size = _attn_cache_mode(cfg, S)
    if mode == "ring":
        W = size
        k = k[:, :, S - W:]
        v = v[:, :, S - W:]
        if S % W:
            # place absolute position p at slot p % W
            k = jnp.roll(k, S % W, axis=2)
            v = jnp.roll(v, S % W, axis=2)
        return {"k": k, "v": v}
    Lc, B = k.shape[0], k.shape[1]
    R = cfg.decode_buffer
    empty = jnp.zeros((Lc, B, R) + k.shape[3:], k.dtype)
    return {"k": k, "v": v, "kr": empty, "vr": empty,
            "flushed": jnp.asarray(S, jnp.int32)}


def lm_prefill(cfg: ArchConfig, params: PyTree, tokens, frames=None,
               max_len: int | None = None):
    """Prefill: forward over the prompt, building the decode cache.
    Returns (last-token logits [B, V], cache). The layer-pattern
    hybrid's cache holds ``max_len`` positions (default: the prompt's)."""
    if cfg.layer_types:
        x, cache = pattern_prefill(cfg, params, tokens,
                                   max_len or tokens.shape[1])
        return pattern_logits(cfg, params, x[:, -1]), cache
    B, S = tokens.shape
    x = _embed(cfg, params, tokens)
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    window = _effective_window(cfg, S)
    cache: dict = {"len": jnp.asarray(S, jnp.int32)}

    if cfg.family == "ssm":
        def body(x, lp):
            x = constrain(x, "batch", None, None)
            h = apply_norm(x, lp["norm1"], cfg.norm)
            y, conv, st = _ssm_prefill_block(cfg, lp["ssm"], h)
            return x + y, (conv, st)
        x, (conv, st) = jax.lax.scan(body, x, params["layers"])
        cache.update(conv=conv, ssm=st)

    elif cfg.family == "hybrid":
        n_stages = cfg.n_layers // cfg.attn_every
        stacked = jax.tree.map(
            lambda a: a.reshape((n_stages, cfg.attn_every) + a.shape[1:]),
            params["layers"])
        shared = params["shared"]

        def stage(x, sp):
            def inner(x, lp):
                x = constrain(x, "batch", None, None)
                h = apply_norm(x, lp["norm1"], cfg.norm)
                y, conv, st = _ssm_prefill_block(cfg, lp["ssm"], h)
                return x + y, (conv, st)
            x, (conv, st) = jax.lax.scan(inner, x, sp)
            h = apply_norm(x, shared["norm1"], cfg.norm)
            a, (k, v) = _attn_block(cfg, shared["attn"], h, positions,
                                    window=window, return_kv=True)
            x = x + a
            h = apply_norm(x, shared["norm2"], cfg.norm)
            out, _ = _ffn(cfg, shared, h)
            return x + out, (conv, st, k, v)

        x, (conv, st, k, v) = jax.lax.scan(stage, x, stacked)
        cache.update(
            conv=jax.tree.map(lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), conv),
            ssm=jax.tree.map(lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), st))
        cache.update(_pack_prefill_attn(cfg, k, v, S))

    elif cfg.family == "audio":
        enc_out = _run_encoder(cfg, params, frames)

        def body(x, lp):
            x = constrain(x, "batch", None, None)
            kv = _encode_cross_kv(cfg, lp["xattn"], enc_out)
            h = apply_norm(x, lp["norm1"], cfg.norm)
            a, (k, v) = _attn_block(cfg, lp["attn"], h, positions,
                                    window=window, return_kv=True)
            x = x + a
            h = apply_norm(x, lp["norm_x"], cfg.norm)
            x = x + _cross_attn_block(cfg, lp["xattn"], h, kv)
            h = apply_norm(x, lp["norm2"], cfg.norm)
            out, _ = _ffn(cfg, lp, h)
            return x + out, (k, v, kv[0], kv[1])

        x, (k, v, xk, xv) = jax.lax.scan(body, x, params["layers"])
        cache.update(_pack_prefill_attn(cfg, k, v, S))
        cache.update(xk=xk, xv=xv)

    else:
        def body(x, lp):
            x = constrain(x, "batch", None, None)
            h = apply_norm(x, lp["norm1"], cfg.norm)
            a, (k, v) = _attn_block(cfg, lp["attn"], h, positions,
                                    window=window, return_kv=True)
            x = x + a
            h = apply_norm(x, lp["norm2"], cfg.norm)
            out, _ = _ffn(cfg, lp, h)
            return x + out, (k, v)
        x, (k, v) = jax.lax.scan(body, x, params["layers"])
        cache.update(_pack_prefill_attn(cfg, k, v, S))

    x = apply_norm(x[:, -1:], params["final_norm"], cfg.norm)
    logits = constrain((x @ params["lm_head"])[:, 0], "batch", "model")
    return logits, cache


def _ssm_prefill_block(cfg: ArchConfig, p, x):
    """Like mamba2_apply but also returns (conv_state, ssm_state)."""
    Bsz, L, D = x.shape
    d_inner = cfg.d_inner
    H, N = cfg.ssm_heads, cfg.ssm_state

    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:2 * d_inner + 2 * N]
    dt = zxbcdt[..., 2 * d_inner + 2 * N:]
    # the last K-1 conv inputs, zeros before the sequence's start
    conv_state = jnp.pad(xBC, ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0))
                         )[:, -(cfg.ssm_conv - 1):, :]
    xBC = ssm_mod.silu(ssm_mod.causal_conv1d(xBC, p["conv_w"], p["conv_b"]))
    xs = xBC[..., :d_inner].reshape(Bsz, L, H, cfg.ssm_head_dim)
    B_ = xBC[..., d_inner:d_inner + N]
    C_ = xBC[..., d_inner + N:]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    dt = jnp.clip(dt, *cfg.ssm_dt_limit)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a = dt * A[None, None, :]
    xd = xs * dt[..., None].astype(xs.dtype)
    y, final_state = ssm_mod.ssd_chunked(xd, a, B_, C_, chunk=cfg.ssm_chunk)
    y = y + xs * p["D"][None, None, :, None]
    y = y.reshape(Bsz, L, d_inner)
    y = rms_norm(y * ssm_mod.silu(z), p["norm_w"], cfg.norm_eps)
    return y @ p["out_proj"], conv_state, final_state


# ==========================================================================
# Hybrid by layer pattern (cfg.layer_types): granite-4.0-h
# ==========================================================================
#
# Each layer is ``x + r * mixer(norm(x))`` then ``x + r * mlp(norm(x))``,
# the mixer a Mamba2 block or NoPE GQA attention as ``cfg.layer_types``
# says, each layer with weights of its own. Weights are stacked per kind
# (``params["mamba"]``, ``params["attention"]``, and ``params["mlp"]``
# for every layer); a run of consecutive layers of one kind is one loop that
# indexes the stacks in place, so no layer's weights are copied.
#
# The decode cache keeps a length per row (``len`` [B]), so rows at
# different positions step together: the serving lanes' state. Beside
# it, per Mamba2 layer the conv inputs and the SSM state (float32 as
# allocated; a step stores the state back in the cache's own dtype),
# and per attention layer the keys and values of ``max_len`` positions,
# the heads of a position side by side ([L, B, max_len, Hkv * hd]): a
# step writes each row's new position in place, and a minor dimension
# of Hkv * hd (not the 64 of one head) is not padded on the chip.

MAMBA, ATTENTION = "mamba", "attention"


def _runs(cfg: ArchConfig) -> list[tuple[str, int, int, int]]:
    """(kind, first layer, layers, index of the first among its kind)
    for each run of consecutive layers of one kind."""
    runs: list[tuple[str, int, int, int]] = []
    seen = {MAMBA: 0, ATTENTION: 0}
    for i, kind in enumerate(cfg.layer_types):
        if kind not in seen:
            raise ValueError(f"{cfg.name}: unknown layer type {kind!r}")
        if runs and runs[-1][0] == kind:
            k, lo, n, k0 = runs[-1]
            runs[-1] = (k, lo, n + 1, k0)
        else:
            runs.append((kind, i, 1, seen[kind]))
        seen[kind] += 1
    return runs


def _layer_counts(cfg: ArchConfig) -> dict[str, int]:
    return {kind: cfg.layer_types.count(kind) for kind in (MAMBA, ATTENTION)}


def _init_pattern(cfg: ArchConfig, key) -> PyTree:
    dt = _dtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    counts = _layer_counts(cfg)
    params: dict = {"embed": embed_init(ks[0], (cfg.padded_vocab, d), dt),
                    "final_norm": norm_param(cfg.norm, d, dt)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (d, cfg.padded_vocab), dt)
    if counts[MAMBA]:
        params[MAMBA] = _stack(
            lambda k: {"norm": norm_param(cfg.norm, d, dt),
                       "ssm": _init_ssm_block(k, cfg, dt)},
            ks[2], counts[MAMBA])
    if counts[ATTENTION]:
        params[ATTENTION] = _stack(
            lambda k: {"norm": norm_param(cfg.norm, d, dt),
                       "attn": _init_attn(k, cfg, dt)},
            ks[3], counts[ATTENTION])
    params["mlp"] = _stack(lambda k: {"norm": norm_param(cfg.norm, d, dt),
                                      "mlp": _init_mlp(k, cfg, dt)},
                           ks[4], cfg.n_layers)
    return params


def _index(tree, i):
    """Layer ``i`` of a stacked subtree (a static or traced index)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


def _pattern_qkv(cfg: ArchConfig, p, h, positions):
    return _project_qkv(cfg, p, h,
                        positions if cfg.positions == "rope" else None)


def _over_layers(cfg: ArchConfig, params, x, state, mix):
    """Every layer in order. ``mix(kind, k, lp, h, state) -> (y, state)``
    is the mixer of the ``k``-th layer of its kind on the normed input
    ``h``; the residual scalings and the MLP half are common."""
    r = cfg.residual_multiplier
    eps = cfg.norm_eps

    def layer(kind, i, k, x, state):
        lp = _index(params[kind], k)
        h = rms_norm(x, lp["norm"]["w"], eps)
        y, state = mix(kind, k, lp, h, state)
        x = x + (r * y).astype(x.dtype)
        fp = _index(params["mlp"], i)
        h = rms_norm(x, fp["norm"]["w"], eps)
        y = mlp_apply(fp["mlp"], h, cfg.activation, cfg.gated_mlp)
        return x + (r * y).astype(x.dtype), state

    for kind, lo, n, k0 in _runs(cfg):
        if n == 1:
            x, state = layer(kind, lo, k0, x, state)
            continue

        def body(j, carry, kind=kind, lo=lo, k0=k0):
            return layer(kind, lo + j, k0 + j, *carry)

        x, state = jax.lax.fori_loop(0, n, body, (x, state))
    return x, state


def _pattern_embed(cfg: ArchConfig, params, tokens):
    x = params["embed"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = (x * cfg.embedding_multiplier).astype(x.dtype)
    return x


def pattern_logits(cfg: ArchConfig, params, h):
    """float32 logits of final hidden states ``h`` [..., d] (normed
    here): the head, tied or not, over ``logits_scaling``."""
    h = rms_norm(h, params["final_norm"]["w"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.matmul(h, head, preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


def _pattern_forward(cfg: ArchConfig, params, tokens):
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :].repeat(B, 0)

    def mix(kind, k, lp, h, state):
        if kind == MAMBA:
            y, _, _ = _ssm_prefill_block(cfg, lp["ssm"], h)
            return y, state
        q, kk, v = _pattern_qkv(cfg, lp["attn"], h, positions)
        out = blocked_attention(q, kk, v, causal=True,
                                scale=cfg.attention_multiplier)
        return out.reshape(B, S, -1) @ lp["attn"]["wo"], state

    x, _ = _over_layers(cfg, params, _pattern_embed(cfg, params, tokens), {},
                        mix)
    return pattern_logits(cfg, params, x)


def _pattern_cache(cfg: ArchConfig, batch: int, max_len: int) -> PyTree:
    dt = _dtype(cfg)
    counts = _layer_counts(cfg)
    cache: dict = {"len": jnp.zeros((batch,), jnp.int32)}
    cache.update(_ssm_cache(cfg, batch, counts[MAMBA], dt))
    n, hkv, hd = counts[ATTENTION], cfg.n_kv_heads, cfg.head_dim
    cache["k"] = jnp.zeros((n, batch, max_len, hkv * hd), dt)
    cache["v"] = jnp.zeros((n, batch, max_len, hkv * hd), dt)
    return cache


def cache_lane_axis(name: str) -> int:
    """The batch (lane) axis of the layer-pattern cache's entry ``name``:
    ``len`` is [B], every other entry is stacked over layers first."""
    return 0 if name == "len" else 1


def pattern_prefill(cfg: ArchConfig, params, tokens, max_len: int):
    """Prefill of the layer-pattern hybrid: the final hidden states of
    every position [B, S, d] (before the final norm; ``pattern_logits``
    makes logits of them) and the decode cache, ``max_len`` positions per
    row."""
    B, S = tokens.shape
    if S > max_len:
        raise ValueError(f"a prompt of {S} tokens does not fit a cache of "
                         f"{max_len} positions")
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    cache = _pattern_cache(cfg, B, max_len)
    cache["len"] = jnp.full((B,), S, jnp.int32)

    def mix(kind, k, lp, h, state):
        if kind == MAMBA:
            y, conv, st = _ssm_prefill_block(cfg, lp["ssm"], h)
            state["conv"] = jax.lax.dynamic_update_index_in_dim(
                state["conv"], conv.astype(state["conv"].dtype), k, 0)
            state["ssm"] = jax.lax.dynamic_update_index_in_dim(
                state["ssm"], st.astype(state["ssm"].dtype), k, 0)
            return y, state
        q, kk, v = _pattern_qkv(cfg, lp["attn"], h, positions)
        out = blocked_attention(q, kk, v, causal=True,
                                scale=cfg.attention_multiplier)
        for name, new in (("k", kk), ("v", v)):
            state[name] = jax.lax.dynamic_update_slice(
                state[name], new.reshape(B, S, -1)[None].astype(
                    state[name].dtype), (k, 0, 0, 0))
        return out.reshape(B, S, -1) @ lp["attn"]["wo"], state

    state = {n: cache[n] for n in ("conv", "ssm", "k", "v")}
    x, state = _over_layers(cfg, params, _pattern_embed(cfg, params, tokens),
                            state, mix)
    cache.update(state)
    return x, cache


def _write_rows(buf, layer, new, at, active):
    """``buf`` [L, B, S, W] with each active row b's ``new[b]`` [1, W]
    written at layer ``layer`` and position ``at[b]``: one in-place
    update a row."""

    def body(b, buf):
        start = (layer, b, at[b], 0)
        old = jax.lax.dynamic_slice(buf, start, (1, 1) + new.shape[1:])
        row = jax.lax.dynamic_index_in_dim(new, b, 0)[None].astype(buf.dtype)
        return jax.lax.dynamic_update_slice(
            buf, jnp.where(active[b], row, old), start)

    return jax.lax.fori_loop(0, new.shape[0], body, buf)


def _rows_attention(cfg: ArchConfig, q, kc, vc, valid_len):
    """One query per row, q [B, 1, Hq, hd], over the row's own first
    ``valid_len`` [B] positions of keys kc and values vc [B, S, Hkv*hd].

    Both products read the cache as it lies, every head of a position
    side by side: the queries go in as a block-diagonal [Hkv*hd, Hq]
    matrix (a query meets only its own head's keys), and of the values'
    product only each head's own block is kept. That costs Hkv times the
    (small) operations of per-head products, which would want the cache
    transposed, a copy of every layer's keys and values a step."""
    B, _, Hq, hd = q.shape
    S, W = kc.shape[1], kc.shape[2]
    Hkv = W // hd
    G = Hq // Hkv
    eye = jnp.eye(Hkv, dtype=kc.dtype)
    qh = q[:, 0].reshape(B, Hkv, G, hd).astype(kc.dtype)
    qbd = jnp.einsum("bhgd,hj->bhdjg", qh, eye).reshape(B, W, Hq)
    s = jnp.einsum("bkw,bwc->bkc", kc, qbd,
                   preferred_element_type=jnp.float32)        # [B, S, Hq]
    s = s * (hd ** -0.5 if cfg.attention_multiplier is None
             else cfg.attention_multiplier)
    valid = jnp.arange(S)[None, :] < valid_len[:, None]
    s = jnp.where(valid[:, :, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=1)
    o = jnp.einsum("bkc,bkw->bcw", p.astype(vc.dtype), vc,
                   preferred_element_type=jnp.float32)        # [B, Hq, W]
    o = jnp.einsum("bjghd,jh->bjgd", o.reshape(B, Hkv, G, Hkv, hd),
                   eye.astype(o.dtype))
    return o.reshape(B, 1, Hq, hd).astype(q.dtype)


def ssm_decode_inplace(cfg: ArchConfig) -> bool:
    """Whether the layer-pattern decode, traced now, updates its Mamba2
    layers' stacked SSM state in place (the Pallas kernel, on a TPU)
    instead of slicing each layer's state out and writing it back."""
    return MAMBA in cfg.layer_types and ssd_ops.decode_inplace()


def _pattern_decode(cfg: ArchConfig, params, token, cache, active=None):
    """One token for every row. ``active`` [B] bool (default all):
    inactive rows keep their state and length and their logits are
    meaningless. A row writes its token's keys and values at its own
    length, and attends over the positions before it and itself."""
    B = token.shape[0]
    pos = cache["len"]
    if active is None:
        active = jnp.ones((B,), bool)
    x = _pattern_embed(cfg, params, token[:, None])

    def keep(new, old):
        m = active.reshape((B,) + (1,) * (old.ndim - 1))
        return jnp.where(m, new.astype(old.dtype), old)

    ssm_kw = dict(head_dim=cfg.ssm_head_dim, ssm_state=cfg.ssm_state,
                  dt_limit=cfg.ssm_dt_limit, norm_eps=cfg.norm_eps)
    inplace = ssm_decode_inplace(cfg)

    def mix(kind, k, lp, h, state):
        if kind == MAMBA:
            conv = jax.lax.dynamic_index_in_dim(state["conv"], k, 0, False)
            if inplace:
                # the stacked state is read and written once, in place
                y, conv2, state["ssm"] = ssm_mod.mamba2_decode_stacked(
                    lp["ssm"], h[:, 0], conv, state["ssm"], k, active,
                    **ssm_kw)
            else:
                st = jax.lax.dynamic_index_in_dim(state["ssm"], k, 0, False)
                y, conv2, st2 = ssm_mod.mamba2_decode(lp["ssm"], h[:, 0],
                                                      conv, st, **ssm_kw)
                state["ssm"] = jax.lax.dynamic_update_index_in_dim(
                    state["ssm"], keep(st2, st), k, 0)
            state["conv"] = jax.lax.dynamic_update_index_in_dim(
                state["conv"], keep(conv2, conv), k, 0)
            return y[:, None], state
        q, kk, v = _pattern_qkv(cfg, lp["attn"], h, pos[:, None])
        for name, new in (("k", kk), ("v", v)):
            state[name] = _write_rows(state[name], k, new.reshape(B, 1, -1),
                                      pos, active)
        kc = jax.lax.dynamic_index_in_dim(state["k"], k, 0, False)
        vc = jax.lax.dynamic_index_in_dim(state["v"], k, 0, False)
        out = _rows_attention(cfg, q, kc, vc, pos + 1)
        return out.reshape(B, 1, -1) @ lp["attn"]["wo"], state

    state = {n: cache[n] for n in ("conv", "ssm", "k", "v")}
    x, state = _over_layers(cfg, params, x, state, mix)
    new_cache = dict(cache, **state)
    new_cache["len"] = pos + active.astype(jnp.int32)
    return pattern_logits(cfg, params, x[:, 0]), new_cache
