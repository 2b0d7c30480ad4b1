"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in
interpret mode (CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention.ops import flash_attention
from repro.kernels.attention.ref import attention_ref
from repro.kernels.evl.ops import evl_loss_fused
from repro.kernels.evl.ref import evl_loss_ref
from repro.kernels.lstm.ops import lstm_cell_fused
from repro.kernels.lstm.ref import lstm_cell_ref
from repro.kernels.ssd.ops import ssd_scan_fused
from repro.models.ssm import ssd_chunked, ssd_reference

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------- EVL ----

@pytest.mark.parametrize("n", [1, 127, 128, 1000, 4096])
@pytest.mark.parametrize("beta0,beta1,gamma", [(0.9, 0.1, 2.0),
                                               (0.99, 0.01, 1.5)])
def test_evl_kernel_matches_ref(n, beta0, beta1, gamma):
    u = jnp.asarray(RNG.uniform(0.01, 0.99, n).astype(np.float32))
    v = jnp.asarray((RNG.uniform(size=n) < 0.2).astype(np.float32))
    got = evl_loss_fused(u, v, beta0, beta1, gamma, reduce="none")
    want = evl_loss_ref(u, v, beta0, beta1, gamma)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_evl_kernel_reductions():
    u = jnp.asarray(RNG.uniform(0.01, 0.99, 300).astype(np.float32))
    v = jnp.zeros(300)
    m = float(evl_loss_fused(u, v, 0.9, 0.1, 2.0, reduce="mean"))
    s = float(evl_loss_fused(u, v, 0.9, 0.1, 2.0, reduce="sum"))
    np.testing.assert_allclose(s / 300, m, rtol=1e-6)


# --------------------------------------------------------------- LSTM ----

@pytest.mark.parametrize("batch,in_dim,hidden", [
    (1, 5, 64), (13, 5, 64), (32, 7, 32), (8, 16, 128),
    # non-multiple-of-8 shapes: odd batch, odd feature dim, batch=1
    # with a tiny feature dim, odd-everything — the wrapper's sublane
    # padding must keep all of them exact
    (3, 9, 24), (7, 3, 40), (1, 1, 8), (9, 11, 48), (5, 5, 16)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_lstm_kernel_matches_ref(batch, in_dim, hidden, dtype):
    x = jnp.asarray(RNG.standard_normal((batch, in_dim)).astype(dtype))
    h = jnp.asarray(RNG.standard_normal((batch, hidden)).astype(dtype))
    c = jnp.asarray(RNG.standard_normal((batch, hidden)).astype(dtype))
    wx = jnp.asarray((0.1 * RNG.standard_normal(
        (in_dim, 4 * hidden))).astype(dtype))
    wh = jnp.asarray((0.1 * RNG.standard_normal(
        (hidden, 4 * hidden))).astype(dtype))
    b = jnp.asarray((0.1 * RNG.standard_normal(4 * hidden)).astype(dtype))
    hn, cn = lstm_cell_fused(x, h, c, wx, wh, b)
    hr, cr = lstm_cell_ref(x, h, c, wx, wh, b)
    np.testing.assert_allclose(hn, hr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(cn, cr, rtol=1e-5, atol=1e-6)


def test_lstm_kernel_in_model():
    """The fused cell is a drop-in for the model's lstm_cell."""
    from repro.models.rnn import lstm_cell
    p = {"wx": jnp.asarray(0.1 * RNG.standard_normal((5, 256)),
                           jnp.float32),
         "wh": jnp.asarray(0.1 * RNG.standard_normal((64, 256)),
                           jnp.float32),
         "b": jnp.asarray(0.1 * RNG.standard_normal(256), jnp.float32)}
    x = jnp.asarray(RNG.standard_normal((3, 5)), jnp.float32)
    h = jnp.zeros((3, 64)); c = jnp.zeros((3, 64))
    h1, c1 = lstm_cell(p, x, h, c)
    h2, c2 = lstm_cell_fused(x, h, c, p["wx"], p["wh"], p["b"])
    np.testing.assert_allclose(h1, h2, rtol=1e-5, atol=1e-6)


def test_lstm_kernel_resolves_backend_at_trace_time(monkeypatch):
    """Regression: the ops wrappers used to snapshot
    ``jax.default_backend()`` at IMPORT time, so a backend configured
    after import served the wrong ``interpret`` flag forever. The flag
    is now resolved when the wrapper traces."""
    from repro.kernels.lstm import ops as lstm_ops

    captured = {}

    def fake_pallas(x, h, c, wx, wh, b, block_b=8, interpret=None):
        captured["interpret"] = interpret
        return h, c

    monkeypatch.setattr(lstm_ops, "lstm_cell_pallas", fake_pallas)
    monkeypatch.setattr(lstm_ops.jax, "default_backend", lambda: "tpu")
    lstm_ops.lstm_cell_fused.clear_cache()    # force a fresh trace
    try:
        x = jnp.zeros((2, 5), jnp.float32)
        h = c = jnp.zeros((2, 8), jnp.float32)
        wx = jnp.zeros((5, 32), jnp.float32)
        wh = jnp.zeros((8, 32), jnp.float32)
        b = jnp.zeros((32,), jnp.float32)
        lstm_ops.lstm_cell_fused(x, h, c, wx, wh, b)
        # the backend patched in AFTER import must win at trace time
        assert captured["interpret"] is False
    finally:
        # drop the traces built against the patched backend/kernel
        lstm_ops.lstm_cell_fused.clear_cache()


# ----------------------------------------------------------- dispatch ----

def test_dispatch_default_table_cpu_picks_xla():
    from repro.kernels import dispatch
    dispatch.reset_table()
    for batch, hidden in [(1, 8), (8, 64), (128, 256)]:
        assert dispatch.resolve("lstm_cell", batch=batch, hidden=hidden,
                                backend="cpu") == "xla"


def test_dispatch_default_table_tpu_thresholds():
    from repro.kernels import dispatch
    dispatch.reset_table()
    assert dispatch.resolve("lstm_cell", batch=8, hidden=64,
                            backend="tpu") == "pallas"
    assert dispatch.resolve("lstm_cell", batch=1, hidden=64,
                            backend="tpu") == "xla"      # below batch floor
    assert dispatch.resolve("lstm_cell", batch=8, hidden=4,
                            backend="tpu") == "xla"      # below hidden floor


def test_dispatch_unknown_op_and_backend_default_to_xla():
    from repro.kernels import dispatch
    dispatch.reset_table()
    assert dispatch.resolve("nope", batch=64, hidden=64,
                            backend="tpu") == "xla"
    assert dispatch.resolve("lstm_cell", batch=64, hidden=64,
                            backend="rocm") == "xla"     # "default" rules


def test_dispatch_force_overrides_everything(monkeypatch):
    from repro.kernels import dispatch
    dispatch.reset_table()
    with dispatch.force("pallas"):
        assert dispatch.resolve("lstm_cell", batch=1, hidden=8,
                                backend="cpu") == "pallas"
    assert dispatch.resolve("lstm_cell", batch=1, hidden=8,
                            backend="cpu") == "xla"      # restored
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "xla")
    assert dispatch.resolve("lstm_cell", batch=64, hidden=64,
                            backend="tpu") == "xla"
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "bogus")
    with pytest.raises(ValueError):
        dispatch.resolve("lstm_cell", batch=1, hidden=8)


def test_dispatch_resolves_backend_at_trace_time(monkeypatch):
    """Like the ops-wrapper regression: a backend configured after
    import must win when ``resolve`` runs (i.e. when tracing)."""
    from repro.kernels import dispatch
    dispatch.reset_table()
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert dispatch.resolve("lstm_cell", batch=8, hidden=64) == "pallas"


def test_dispatch_table_save_load_roundtrip(tmp_path):
    from repro.kernels import dispatch
    dispatch.reset_table()
    dispatch.set_rules("lstm_cell", "cpu",
                       [{"min_batch": 4, "min_hidden": 0,
                         "impl": "pallas"}])
    path = str(tmp_path / "table.json")
    dispatch.save_table(path)
    dispatch.reset_table()
    assert dispatch.resolve("lstm_cell", batch=4, hidden=8,
                            backend="cpu") == "xla"
    dispatch.load_table(path)
    try:
        assert dispatch.resolve("lstm_cell", batch=4, hidden=8,
                                backend="cpu") == "pallas"
        assert dispatch.resolve("lstm_cell", batch=2, hidden=8,
                                backend="cpu") == "xla"
        # merged over defaults: untouched backends keep their rules
        assert dispatch.resolve("lstm_cell", batch=8, hidden=64,
                                backend="tpu") == "pallas"
    finally:
        dispatch.reset_table()


def test_dispatch_env_table_loads_lazily(tmp_path, monkeypatch):
    from repro.kernels import dispatch
    path = str(tmp_path / "env_table.json")
    dispatch.set_rules("lstm_cell", "cpu",
                       [{"min_batch": 1, "impl": "pallas"}])
    dispatch.save_table(path)
    dispatch.reset_table()
    monkeypatch.setenv("REPRO_DISPATCH_TABLE", path)
    try:
        assert dispatch.resolve("lstm_cell", batch=1, hidden=8,
                                backend="cpu") == "pallas"
    finally:
        dispatch.reset_table()


def test_dispatched_cell_matches_ref_both_impls():
    """The dispatch-routed cell is numerically the ref cell on the XLA
    path (identical expression) and allclose on the forced Pallas
    path — at a non-multiple-of-8 shape to exercise the padding."""
    from repro.kernels import dispatch
    dispatch.reset_table()
    B, I, H = 3, 5, 24
    x = jnp.asarray(RNG.standard_normal((B, I)).astype(np.float32))
    h = jnp.asarray(RNG.standard_normal((B, H)).astype(np.float32))
    c = jnp.asarray(RNG.standard_normal((B, H)).astype(np.float32))
    wx = jnp.asarray(0.1 * RNG.standard_normal((I, 4 * H)), jnp.float32)
    wh = jnp.asarray(0.1 * RNG.standard_normal((H, 4 * H)), jnp.float32)
    b = jnp.asarray(0.1 * RNG.standard_normal(4 * H), jnp.float32)
    want = lstm_cell_ref(x, h, c, wx, wh, b)
    got = dispatch.lstm_cell(x, h, c, wx, wh, b)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    with dispatch.force("pallas"):
        got_p = dispatch.lstm_cell(x, h, c, wx, wh, b)
    np.testing.assert_allclose(got_p[0], want[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_p[1], want[1], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("workers", [None, 2])
def test_paper_loss_grad_pallas_matches_xla(workers):
    """``value_and_grad`` of the paper loss through the forced Pallas
    cell (interpret mode: kernel forward, ``custom_vjp`` backward)
    matches the XLA path, alone and under ``vmap`` over workers — the
    shape of ``local_sgd_round``."""
    from repro.configs.paper_lstm import CONFIG
    from repro.kernels import dispatch
    from repro.models.rnn import init_rnn
    from repro.training.loop import make_loss_fn

    B = 8
    loss_fn = make_loss_fn(CONFIG, evl_weight=0.5, beta0=0.9, beta1=0.1)
    params = init_rnn(jax.random.PRNGKey(3), CONFIG)
    batch = (RNG.standard_normal((B, CONFIG.window, CONFIG.input_dim)),
             RNG.standard_normal(B), (RNG.uniform(size=B) < 0.3) * 1.0,
             np.ones(B))
    batch = tuple(jnp.asarray(a, jnp.float32) for a in batch)
    fn = jax.value_and_grad(loss_fn)
    if workers:
        params = jax.tree.map(lambda a: jnp.stack([a] * workers), params)
        batch = tuple(jnp.stack([a * (1 + w) for w in range(workers)])
                      for a in batch)
        fn = jax.vmap(fn)
    dispatch.reset_table()
    want_loss, want_grads = jax.jit(lambda p, b: fn(p, b))(params, batch)
    with dispatch.force("pallas"):
        got_loss, got_grads = jax.jit(lambda p, b: fn(p, b))(params, batch)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5, atol=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


def test_model_cell_routes_through_dispatch(monkeypatch):
    """``models.rnn.lstm_cell`` consults the dispatch layer — forcing
    Pallas must reach the kernel wrapper."""
    from repro.kernels import dispatch
    from repro.models import rnn as rnn_mod

    called = {"n": 0}
    real = dispatch.lstm_cell_padded

    def spy(*args, **kw):
        called["n"] += 1
        return real(*args, **kw)

    monkeypatch.setattr(dispatch, "lstm_cell_padded", spy)
    p = {"wx": jnp.zeros((5, 64), jnp.float32),
         "wh": jnp.zeros((16, 64), jnp.float32),
         "b": jnp.zeros((64,), jnp.float32)}
    x = jnp.zeros((2, 5), jnp.float32)
    h = c = jnp.zeros((2, 16), jnp.float32)
    rnn_mod.lstm_cell(p, x, h, c)          # cpu -> xla, no kernel call
    assert called["n"] == 0
    with dispatch.force("pallas"):
        rnn_mod.lstm_cell(p, x, h, c)
    assert called["n"] == 1


# ---------------------------------------------------- flash attention ----

@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (1, 128, 4, 4, 64),     # MHA, aligned
    (2, 200, 4, 2, 64),     # GQA, ragged seq
    (1, 300, 8, 1, 32),     # MQA
    (2, 64, 6, 2, 128),     # tiny seq < block
])
@pytest.mark.parametrize("kwargs", [
    dict(causal=True), dict(causal=False), dict(causal=True, window=37)])
def test_flash_attention_matches_ref(B, S, Hq, Hkv, D, kwargs):
    q = jnp.asarray(RNG.standard_normal((B, S, Hq, D)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)).astype(np.float32))
    got = flash_attention(q, k, v, **kwargs)
    want = attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    B, S, H, D = 1, 128, 2, 64
    q = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True).astype(np.float32)
    want = attention_ref(q, k, v, causal=True).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=0.08, atol=0.08)


def test_blocked_attention_model_twin():
    """models.attention.blocked_attention (the pure-JAX twin used inside
    the transformer) agrees with the Pallas kernel."""
    from repro.models.attention import blocked_attention
    B, S, Hq, Hkv, D = 2, 160, 4, 2, 64
    q = jnp.asarray(RNG.standard_normal((B, S, Hq, D)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)).astype(np.float32))
    a = blocked_attention(q, k, v, causal=True, q_block=64, kv_block=64)
    b = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------- SSD -------

@pytest.mark.parametrize("B,L,H,P,N,chunk", [
    (1, 64, 2, 16, 8, 16),
    (2, 96, 3, 16, 8, 32),
    (1, 100, 1, 32, 16, 32),   # ragged: L % chunk != 0
    (2, 128, 4, 64, 32, 128),  # full-size chunk
])
def test_ssd_kernel_matches_refs(B, L, H, P, N, chunk):
    xd = jnp.asarray((0.1 * RNG.standard_normal((B, L, H, P))).astype(np.float32))
    a = -jnp.asarray(RNG.uniform(0.01, 0.5, (B, L, H)).astype(np.float32))
    B_ = jnp.asarray((0.3 * RNG.standard_normal((B, L, N))).astype(np.float32))
    C_ = jnp.asarray((0.3 * RNG.standard_normal((B, L, N))).astype(np.float32))
    y1, s1 = ssd_scan_fused(xd, a, B_, C_, chunk=chunk)
    y2, s2 = ssd_chunked(xd, a, B_, C_, chunk=chunk)
    y3, s3 = ssd_reference(xd, a, B_, C_)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(y1, y3, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s1, s3, rtol=1e-4, atol=1e-5)


def test_ssd_decode_matches_scan_tail():
    """Sequential decode steps reproduce the chunked scan's output."""
    from repro.models.ssm import ssd_decode_step
    B, L, H, P, N = 1, 32, 2, 8, 4
    xd = jnp.asarray((0.1 * RNG.standard_normal((B, L, H, P))).astype(np.float32))
    a = -jnp.asarray(RNG.uniform(0.01, 0.5, (B, L, H)).astype(np.float32))
    B_ = jnp.asarray((0.3 * RNG.standard_normal((B, L, N))).astype(np.float32))
    C_ = jnp.asarray((0.3 * RNG.standard_normal((B, L, N))).astype(np.float32))
    y_scan, _ = ssd_chunked(xd, a, B_, C_, chunk=8)
    state = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(L):
        y, state = ssd_decode_step(state, xd[:, t], a[:, t], B_[:, t],
                                   C_[:, t])
        ys.append(y)
    y_seq = jnp.stack(ys, 1)
    np.testing.assert_allclose(y_seq, y_scan, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("stepped", [[1, 1, 1, 1], [0, 0, 0, 0],
                                     [1, 0, 0, 1]])
def test_ssd_decode_inplace_matches_decode_step(layer, stepped):
    """``ssd_decode_inplace`` (interpret mode) on layer ``layer`` of a
    stacked [L, B, H, P, N] state against ``ssd_decode_step`` on that
    layer: the stepped lanes' state and y agree to float32 rounding,
    the other lanes and every other layer are left bit for bit.

    Float32 rounding: the kernel sums y = C . state over N in another
    order, and the state's two terms may meet in one fused
    multiply-add on one side only, so an entry that cancels to near 0
    carries an error of a few ulps of the terms, not of itself: rtol
    1e-6 plus 1e-6 of the largest magnitude."""
    from repro.kernels.ssd.ops import ssd_decode_inplace
    from repro.models.ssm import ssd_decode_step

    L, B, H, P, N = 3, 4, 16, 8, 128
    states = jnp.asarray(RNG.standard_normal((L, B, H, P, N)), jnp.float32)
    xd = jnp.asarray(0.3 * RNG.standard_normal((B, H, P)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.01, 0.5, (B, H)), jnp.float32)
    B_ = jnp.asarray(RNG.standard_normal((B, N)), jnp.float32)
    C_ = jnp.asarray(RNG.standard_normal((B, N)), jnp.float32)
    mask = np.asarray(stepped, bool)
    before = np.asarray(states)

    y, got = jax.jit(ssd_decode_inplace, donate_argnums=0)(
        states, jnp.int32(layer), jnp.asarray(mask), a, xd, B_, C_)
    y, got = np.asarray(y), np.asarray(got)
    y_want, s_want = (np.asarray(v) for v in ssd_decode_step(
        jnp.asarray(before[layer]), xd, a, B_, C_))

    def close(have, want):
        np.testing.assert_allclose(have[mask], want[mask], rtol=1e-6,
                                   atol=1e-6 * np.abs(want).max())

    close(got[layer], s_want)
    close(y, y_want)
    np.testing.assert_array_equal(got[layer][~mask], before[layer][~mask])
    others = [k for k in range(L) if k != layer]
    np.testing.assert_array_equal(got[others], before[others])
