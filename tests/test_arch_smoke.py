"""Per-architecture smoke tests: reduced variant of each assigned family
(2 layers, d_model<=256, <=4 experts) — one forward + one train step +
prefill/decode consistency on CPU, asserting shapes and finiteness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, list_archs
from repro.configs.base import reduced
from repro.data.tokens import synthetic_embedding_batch, synthetic_token_batch
from repro.models import transformer as tfm
from repro.models.model_zoo import build_model
from repro.optim.optimizers import adam, apply_updates

ALL_ARCHS = list_archs()
assert len(ALL_ARCHS) == 11


def _inputs(cfg, batch=2, seq=24, seed=0):
    toks = jnp.asarray(synthetic_token_batch(batch, seq, cfg.vocab,
                                             seed=seed))
    frames = None
    if cfg.family == "audio":
        frames = jnp.asarray(synthetic_embedding_batch(
            batch, cfg.n_frames, cfg.d_model, seed=seed))
    return toks, frames


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_config_constraints(arch):
    cfg = reduced(ARCHS[arch])
    assert cfg.n_layers == 2
    assert cfg.d_model <= 512
    assert cfg.n_experts <= 4


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks, frames = _inputs(cfg)
    logits, aux = model.forward(params, toks, frames)
    assert logits.shape == (2, 24, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    loss = model.loss(params, toks, frames)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_updates_and_finite(arch):
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks, frames = _inputs(cfg)
    opt = adam(clip_norm=1.0)
    state = opt.init(params)

    def loss_fn(p):
        return model.loss(p, toks, frames)

    loss0, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    upd, state = opt.update(grads, state, params, 1e-2)
    params2 = apply_updates(params, upd)
    loss1 = float(jax.jit(loss_fn)(params2))
    assert np.isfinite(float(loss0)) and np.isfinite(loss1)
    # at least one parameter actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32),
                        np.asarray(b, np.float32))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S, prompt = 24, 16
    toks, frames = _inputs(cfg, seq=S)
    logits_all, _ = model.forward(params, toks, frames)
    lp, cache = model.prefill(params, toks[:, :prompt], frames)
    np.testing.assert_allclose(
        np.asarray(lp, np.float32),
        np.asarray(logits_all[:, prompt - 1], np.float32),
        rtol=3e-2, atol=3e-2)

    full = model.init_cache(2, S)

    def place(dst, src):
        if dst.shape == src.shape:
            return src.astype(dst.dtype)
        if dst.ndim == src.ndim and dst.shape[2] != src.shape[2]:
            return dst.at[:, :, :src.shape[2]].set(src)
        return src

    cache = jax.tree.map(place, full, cache)
    for t in range(prompt, S):
        lg, cache = model.decode_step(params, toks[:, t], cache)
        want = np.asarray(logits_all[:, t], np.float32)
        got = np.asarray(lg, np.float32)
        denom = np.max(np.abs(want)) + 1e-9
        assert np.max(np.abs(got - want)) / denom < 0.05, (arch, t)
        # exercise the paged-KV flush (reduced configs use tiny buffers)
        if "kr" in cache and int(cache["len"] - cache["flushed"]) >= \
                cfg.decode_buffer:
            cache = tfm.flush_recent(cfg, cache)


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "qwen3-moe-235b-a22b"])
def test_moe_router_balance_loss(arch):
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks, _ = _inputs(cfg)
    _, aux = model.forward(params, toks)
    # Switch aux loss is ~1 for a balanced router, >=1 otherwise
    assert 0.5 < float(aux) / cfg.n_layers < 4.0


def test_param_counts_close_to_nameplate():
    """Full-config parameter-count formulas land near the nameplate
    sizes (within ~20%, vocab padding and heads included)."""
    expect = {"chameleon-34b": 34e9, "granite-20b": 20e9,
              "qwen2.5-32b": 32e9, "nemotron-4-15b": 15e9,
              "mamba2-370m": 0.37e9, "mixtral-8x7b": 46e9,
              "zamba2-2.7b": 2.7e9, "qwen1.5-4b": 4e9,
              "qwen3-moe-235b-a22b": 235e9}
    for name, n in expect.items():
        got = ARCHS[name].param_count()
        assert 0.7 * n < got < 1.45 * n, (name, got, n)


def test_active_params_moe():
    cfg = ARCHS["qwen3-moe-235b-a22b"]
    active = cfg.active_param_count()
    total = cfg.param_count()
    assert active < total / 5     # 22B active of 235B
