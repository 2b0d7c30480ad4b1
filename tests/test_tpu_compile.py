"""The paper LSTM's kernel programs compile for a TPU v5e that is
described, not attached: the Pallas LSTM cell at its serving and
training batch sizes, the vmapped training round under
``value_and_grad``, and the decode-slot ``generate``. Each compiled
program must contain the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and
every test worker imports this file."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_lstm import CONFIG

H = CONFIG.hidden


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(os.environ, "TPU_LOG_DIR",
                   os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Trace as the TPU backend (the dispatch table and the kernel's
    interpret flag read ``jax.default_backend()``), with the persistent
    compilation cache off: an entry written for a described chip cannot
    be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(sharding):
    from repro.models.rnn import init_rnn

    return _placed(jax.eval_shape(
        lambda: init_rnn(jax.random.PRNGKey(0), CONFIG)), sharding)


@pytest.mark.parametrize("batch,in_dim", [(8, CONFIG.input_dim),
                                          (32, CONFIG.input_dim),
                                          (64, H)])
def test_lstm_cell_compiles(one_chip, as_tpu, batch, in_dim):
    from repro.kernels.lstm.ops import lstm_cell_padded

    args = [_spec(s, one_chip) for s in ((batch, in_dim), (batch, H),
                                         (batch, H), (in_dim, 4 * H),
                                         (H, 4 * H), (4 * H,))]
    fn = jax.jit(lambda *a: lstm_cell_padded(*a))   # fresh trace
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


def test_training_round_compiles(one_chip, as_tpu):
    """The paper trainer's round: W=4 workers vmapped, batch 32, the
    Pallas cell forward under ``value_and_grad`` inside ``lax.scan``."""
    from repro.core.async_local_sgd import AsyncLocalSGD, LocalSGDConfig
    from repro.models.rnn import init_rnn
    from repro.optim.optimizers import sgd
    from repro.training.loop import make_loss_fn

    W, B = 4, 32
    trainer = AsyncLocalSGD(make_loss_fn(CONFIG), sgd(momentum=0.0),
                            LocalSGDConfig(n_workers=W))
    steps = trainer.local_steps_for_round(1)
    stacked, opt = _placed(jax.eval_shape(
        lambda: trainer.init(init_rnn(jax.random.PRNGKey(0), CONFIG))),
        one_chip)
    batches = (_spec((W, steps, B, CONFIG.window, CONFIG.input_dim),
                     one_chip),) + tuple(
        _spec((W, steps, B), one_chip) for _ in range(3))
    compiled = trainer._round.lower(stacked, opt, batches, 0.01).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_slots_generate_compiles(one_chip, as_tpu):
    """The steady-state decode flush over 64 device-resident lanes,
    stepped in chunks of the decode width (8 rows: the Pallas cell)."""
    from repro.serving.forecaster import _build_rnn_fns

    S = 64
    generate = _build_rnn_fns(CONFIG)["slots_generate_donate"]  # fresh
    carry = tuple((_spec((S, H), one_chip), _spec((S, H), one_chip))
                  for _ in range(CONFIG.num_layers))
    scalar = _spec((), one_chip)
    compiled = generate.lower(
        _params(one_chip), _spec((S, CONFIG.input_dim), one_chip), carry,
        _spec((S,), one_chip, np.bool_), scalar, scalar,
        _spec((), one_chip, np.bool_), gamma=5.0, width=8).compile()
    assert "tpu_custom_call" in compiled.as_text()
