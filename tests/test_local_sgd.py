"""Core invariants of the paper's technique (async local SGD)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.async_local_sgd as asgd
from repro.core.async_local_sgd import (AsyncLocalSGD, LocalSGDConfig,
                                        broadcast_to_workers,
                                        local_sgd_round, sync_step,
                                        worker_mean)
from repro.core.schedules import SampleSchedule, StepSizeSchedule
from repro.optim.optimizers import apply_updates, sgd


def quad_loss(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 3)).astype(np.float32)
    w_true = np.array([1.0, -2.0, 0.5], np.float32)
    y = x @ w_true + 0.1
    return x, y.astype(np.float32)


def _params():
    return {"w": jnp.zeros((3,)), "b": jnp.zeros(())}


def test_single_worker_single_step_equals_serial_sgd():
    """W=1, H=1 local SGD == one plain SGD step, exactly."""
    opt = sgd()
    x, y = _data(8)
    p = _params()
    stacked = jax.tree.map(lambda a: a[None], p)
    opt_state = jax.vmap(opt.init)(stacked)
    batches = (x[None, None], y[None, None])  # [W=1, H=1, ...]
    newp, _, losses = local_sgd_round(quad_loss, opt, stacked, opt_state,
                                      batches, 0.1)
    # serial
    g = jax.grad(quad_loss)(p, (x, y))
    upd, _ = opt.update(g, opt.init(p), p, 0.1)
    want = apply_updates(p, upd)
    got = jax.tree.map(lambda a: a[0], newp)
    np.testing.assert_allclose(got["w"], want["w"], rtol=1e-6)
    np.testing.assert_allclose(got["b"], want["b"], rtol=1e-6)


def test_model_vs_gradient_exchange_equal_for_plain_sgd():
    """At H=1 with plain SGD, averaging models == averaging gradients
    (linearity) — the regime where the paper's two exchange modes agree."""
    opt = sgd()
    x, y = _data(16)
    p = _params()
    W = 4
    stacked = jax.tree.map(lambda a: jnp.broadcast_to(a[None],
                                                      (W,) + a.shape), p)
    opt_state = jax.vmap(opt.init)(stacked)
    xb = x.reshape(W, 4, 3)
    yb = y.reshape(W, 4)
    p_m, _, _ = sync_step(quad_loss, opt, stacked, opt_state, (xb, yb),
                          0.1, exchange="model")
    p_g, _, _ = sync_step(quad_loss, opt, stacked, opt_state, (xb, yb),
                          0.1, exchange="gradient")
    np.testing.assert_allclose(
        np.asarray(jax.tree.map(lambda a: a[0], p_m)["w"]),
        np.asarray(jax.tree.map(lambda a: a[0], p_g)["w"]), rtol=1e-5)


def test_identical_workers_identical_data_stay_identical():
    opt = sgd()
    x, y = _data(8)
    p = _params()
    W, H = 3, 2
    stacked = jax.tree.map(lambda a: jnp.broadcast_to(a[None],
                                                      (W,) + a.shape), p)
    opt_state = jax.vmap(opt.init)(stacked)
    xb = np.broadcast_to(x[None, None], (W, H, 8, 3))
    yb = np.broadcast_to(y[None, None], (W, H, 8))
    newp, _, _ = local_sgd_round(quad_loss, opt, stacked, opt_state,
                                 (jnp.asarray(xb), jnp.asarray(yb)), 0.05)
    for leaf in jax.tree_util.tree_leaves(newp):
        for w in range(1, W):
            np.testing.assert_allclose(leaf[0], leaf[w], rtol=1e-6)


def test_worker_mean_and_broadcast_roundtrip():
    t = {"w": jnp.arange(6.0).reshape(3, 2)}
    avg = worker_mean(t)
    np.testing.assert_allclose(avg["w"], t["w"].mean(0))
    back = broadcast_to_workers(avg, t)
    assert back["w"].shape == t["w"].shape


def test_trainer_accounting_and_convergence():
    x, y = _data(512)
    cfg = LocalSGDConfig(n_workers=2, schedule=SampleSchedule(a=4),
                         stepsize=StepSizeSchedule(eta0=0.05, beta=0.0))
    trainer = AsyncLocalSGD(quad_loss, sgd(), cfg)
    stacked, opt_state = trainer.init(_params())
    rng = np.random.default_rng(0)
    for r in range(1, 13):
        h = trainer.local_steps_for_round(r)
        idx = rng.integers(0, 512, size=(2, h, 32))
        batches = (x[idx], y[idx])
        stacked, opt_state, loss = trainer.run_round(stacked, opt_state,
                                                     batches)
    assert trainer.rounds_done == 12
    assert trainer.communications == 12
    # linear schedule: iterations >> rounds
    assert trainer.iterations_done > 5 * trainer.rounds_done
    assert trainer.loss_history[-1] < trainer.loss_history[0] * 0.2
    assert trainer.communication_bytes(stacked) == \
        12 * 2 * 2 * trainer.model_bytes(stacked)


def test_delayed_average_consumed_exactly_at_round_r_plus_tau():
    """Definition 1, exactly: with staleness tau the round-r average is
    consumed at round r + tau — verified against a numpy simulation of
    the recursion w <- avg^{(r)} + (w - w^{(r)}), whose values shift if
    consumption is off by even one round."""
    W, H, B, tau, R = 3, 2, 4, 2, 6
    lr = 0.05

    def lin_loss(params, batch):
        # gradient wrt w is exactly mean(x, axis=0): every local step is
        # a predictable constant move, so the whole run is replayable
        (x,) = batch
        return jnp.vdot(params["w"], jnp.mean(x, axis=0))

    cfg = LocalSGDConfig(n_workers=W, tau=tau,
                         stepsize=StepSizeSchedule(eta0=lr, beta=0.0))
    trainer = AsyncLocalSGD(lin_loss, sgd(), cfg)
    stacked, opt_state = trainer.init({"w": jnp.zeros((3,))})

    rng = np.random.default_rng(7)
    rounds = [rng.standard_normal((W, H, B, 3)).astype(np.float32)
              for _ in range(R)]

    # numpy reference of the paper's recursion
    pw = np.zeros((W, 3), np.float64)
    queue, expected_consumed = [], []
    for r, g in enumerate(rounds, start=1):
        for w in range(W):
            for h in range(H):
                pw[w] -= lr * g[w, h].mean(axis=0)
        queue.append((pw.mean(axis=0), pw.copy(), r))
        if len(queue) > tau:
            avg_old, snap_old, r_old = queue.pop(0)
            expected_consumed.append((r, r_old))
            pw = avg_old[None] + (pw - snap_old)

    for g in rounds:
        stacked, opt_state, _ = trainer.run_round(stacked, opt_state, (g,))

    assert trainer.consumed_rounds == expected_consumed
    # consumption starts at round tau + 1 and lags by exactly tau
    assert expected_consumed == [(r, r - tau) for r in range(tau + 1, R + 1)]
    np.testing.assert_allclose(np.asarray(stacked["w"]), pw, rtol=1e-5,
                               atol=1e-6)


def test_gradient_exchange_forces_single_local_step():
    """Paper footnote **: gradient exchange communicates every iteration,
    so a round collapses to H == 1 — the trainer enforces it."""
    opt = sgd()
    x, y = _data(16)
    cfg = LocalSGDConfig(n_workers=4, exchange="gradient",
                         schedule=SampleSchedule(a=16),
                         stepsize=StepSizeSchedule(eta0=0.1, beta=0.0))
    trainer = AsyncLocalSGD(quad_loss, opt, cfg)
    # the schedule may ask for many local steps; gradient exchange pins 1
    for i in (1, 2, 5, 20):
        assert trainer.local_steps_for_round(i) == 1

    stacked, opt_state = trainer.init(_params())
    xb = x.reshape(4, 1, 4, 3)
    yb = y.reshape(4, 1, 4)
    newp, _, _ = trainer.run_round(stacked, opt_state, (xb, yb))
    assert trainer.iterations_done == 4 and trainer.communications == 1
    # matches the synchronous gradient-averaging baseline exactly
    want, _, _ = sync_step(quad_loss, opt, stacked, opt_state,
                           (xb[:, 0], yb[:, 0]), trainer.cfg.stepsize(0),
                           exchange="gradient")
    np.testing.assert_allclose(np.asarray(newp["w"]),
                               np.asarray(want["w"]), rtol=1e-6)

    # an H=2 round is a contract violation, not a silent average
    xb2 = np.broadcast_to(x.reshape(4, 1, 4, 3), (4, 2, 4, 3))
    yb2 = np.broadcast_to(y.reshape(4, 1, 4), (4, 2, 4))
    with pytest.raises(ValueError, match="H == 1"):
        trainer.run_round(newp, opt_state, (jnp.asarray(xb2),
                                            jnp.asarray(yb2)))


def test_gradient_exchange_config_validation():
    with pytest.raises(ValueError):
        LocalSGDConfig(exchange="gradient", tau=1)  # staleness is a
        # model-exchange concept; gradient exchange is synchronous
    with pytest.raises(ValueError):
        LocalSGDConfig(exchange="momentum")


def test_stale_averaging_satisfies_definition_1():
    """tau=1: the model applied at round r contains the global average of
    round r-1 — never older (Definition 1 with constant tau)."""
    x, y = _data(64)
    cfg = LocalSGDConfig(n_workers=2, tau=1,
                         schedule=SampleSchedule(a=2),
                         stepsize=StepSizeSchedule(eta0=0.05, beta=0.0))
    trainer = AsyncLocalSGD(quad_loss, sgd(), cfg)
    stacked, opt_state = trainer.init(_params())
    rng = np.random.default_rng(1)
    for r in range(1, 6):
        h = trainer.local_steps_for_round(r)
        idx = rng.integers(0, 64, size=(2, h, 16))
        stacked, opt_state, _ = trainer.run_round(stacked, opt_state,
                                                  (x[idx], y[idx]))
        assert len(trainer._avg_queue) <= cfg.tau
    # still converges despite staleness
    assert trainer.loss_history[-1] < trainer.loss_history[0]


def _eager_round(p, o, batches, lr, queue, tau, r):
    """One round as the trainer composed it from eager pieces: the local
    steps' program, the exchange on the host (sync, or stale over
    ``queue``), then the mean loss."""
    p, o, losses = local_sgd_round(quad_loss, sgd(), p, o, batches, lr)
    if tau == 0:
        p = broadcast_to_workers(worker_mean(p), p)
    else:
        queue.append((worker_mean(p), p, r))
        if len(queue) > tau:
            avg_old, snap_old, _ = queue.pop(0)
            p = jax.tree.map(lambda a, w, s: a[None] + (w - s),
                             avg_old, p, snap_old)
    return p, o, float(jnp.mean(losses))


@pytest.mark.parametrize("tau,H,rounds", [(0, 1, 1), (0, 3, 1), (0, 7, 1),
                                          (1, 3, 4)])
def test_round_program_matches_eager_exchange(monkeypatch, tau, H, rounds):
    """The exchange and the mean loss inside the round program give the
    models and losses of the eager composition; at tau 0 every round's
    exchange ran in the program, and a round is one program call that
    returns the mean loss as a scalar, with no exchange on the host."""
    W = 3
    x, y = _data(W * H * 8 * rounds, seed=H)
    cfg = LocalSGDConfig(n_workers=W, tau=tau,
                         stepsize=StepSizeSchedule(eta0=0.05, beta=0.0))
    trainer = AsyncLocalSGD(quad_loss, sgd(), cfg)
    rng = np.random.default_rng(H)
    p0 = {"w": jnp.asarray(rng.standard_normal(3), jnp.float32),
          "b": jnp.zeros(())}
    stacked, opt = trainer.init(p0)
    want_p, want_o = stacked, opt
    queue = []
    for r in range(rounds):
        idx = rng.permutation(len(x)).reshape(W, H, -1)[..., :8]
        batches = (x[idx], y[idx])
        lr = trainer.lr_for_round()
        want_p, want_o, want_loss = _eager_round(
            want_p, want_o, batches, lr, queue, tau, r + 1)
        stacked, opt, loss = trainer.run_round(stacked, opt, batches)
        np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(stacked[k]),
                                       np.asarray(want_p[k]), rtol=1e-6)
    assert trainer.loss_history[-1] == loss
    assert trainer.in_program_exchanges == (
        trainer.communications if tau == 0 else 0)
    if tau:
        assert trainer.consumed_rounds == [(r, r - tau)
                                           for r in range(2, rounds + 1)]
    else:
        calls = []
        program = trainer._round

        def counted(*args):
            calls.append(program(*args))
            return calls[-1]

        def on_host(*args):
            raise AssertionError("model exchange ran on the host")

        monkeypatch.setattr(trainer, "_round", counted)
        monkeypatch.setattr(asgd, "worker_mean", on_host)
        monkeypatch.setattr(asgd, "broadcast_to_workers", on_host)
        trainer.run_round(stacked, opt, batches)
        assert len(calls) == 1 and calls[0][2].shape == ()
        assert trainer.in_program_exchanges == trainer.communications
