"""Training launcher.

Two modes:
  * ``--arch paper-lstm`` (default): the paper's experiment — async local
    SGD on stock windows, n workers, linear schedule.
  * ``--arch <zoo id>``: train a (reduced or full) transformer config on
    synthetic tokens on whatever devices exist, using the same local-SGD
    round machinery (workers = data shards of the host mesh).

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch paper-lstm \
        --workers 5 --iterations 2000
    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b \
        --reduced --steps 50
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def run_paper_lstm(args, round_callback=None):
    """Paper experiment. ``round_callback(round_idx, avg_params)`` — when
    given — receives every round's worker-averaged parameters as they are
    produced (the online-learning hook ``repro.launch.online`` uses to
    hot-swap weights into a live serving engine); the final model is no
    longer the only artifact the loop emits. Returns the TrainResult."""
    from repro.core.schedules import ConstantSchedule, SampleSchedule
    from repro.data import load_stock, make_windows, train_test_split
    from repro.training.loop import train_rnn_local_sgd, train_rnn_serial

    ohlcv = load_stock(args.ticker, n_days=args.days, seed=args.seed)
    tr, te = train_test_split(ohlcv)
    train_ds, test_ds = make_windows(tr), make_windows(te)
    print(f"{args.ticker}: {len(train_ds)} train / {len(test_ds)} test "
          f"windows; extreme fraction "
          f"{float(np.mean(train_ds.v != 0)):.3f}")

    t0 = time.time()
    if args.workers <= 1:
        res = train_rnn_serial(train_ds, test_ds,
                               iterations=args.iterations,
                               batch=args.batch, seed=args.seed,
                               evl_weight=args.evl_weight)
    else:
        schedule = (ConstantSchedule(size=args.constant_rounds)
                    if args.constant_rounds else SampleSchedule())
        res = train_rnn_local_sgd(
            train_ds, test_ds, n_workers=args.workers,
            iterations=args.iterations, batch=args.batch,
            schedule=schedule, tau=args.tau, seed=args.seed,
            evl_weight=args.evl_weight, round_callback=round_callback)
    dt = time.time() - t0
    print(f"done in {dt:.1f}s: test MSE {res.test_mse:.5f}, "
          f"iterations {res.iterations}, communications "
          f"{res.communications}, comm bytes {res.comm_bytes/1e6:.2f} MB")
    if res.test_extreme:
        print("extreme-event:", res.test_extreme)
    if getattr(args, "save", None):
        _save_serving_checkpoint(args.save, res, train_ds)
    return res


def _save_serving_checkpoint(path: str, res, train_ds) -> None:
    """Persist the trained model as a *serving* checkpoint: EVT-calibrated
    forecaster + model-version metadata (the version is the number of
    cross-worker exchanges that produced the weights, so a registry that
    later loads it slots into the monotone version sequence)."""
    from repro.configs.paper_lstm import CONFIG
    from repro.serving import LSTMForecaster, ModelRegistry

    fc = LSTMForecaster(cfg=CONFIG, params=res.params)
    fc.calibrate(train_ds.x)
    reg = ModelRegistry()
    reg.register("trained", fc, version=max(res.communications, 1))
    reg.save("trained", path)
    print(f"saved serving checkpoint v{reg.version('trained')} -> {path}")


def run_zoo(args) -> None:
    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.data.tokens import synthetic_token_batch
    from repro.launch.specs import make_train_step
    from repro.models import transformer as tfm

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model_params = tfm.init_lm(cfg, jax.random.PRNGKey(args.seed))
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(model_params))
    print(f"{cfg.name}: {n_params/1e6:.1f}M params")
    step, opt = make_train_step(cfg, lr=args.lr)
    opt_state = opt.init(model_params)
    jstep = jax.jit(step)

    losses = []
    for i in range(args.steps):
        toks = jnp.asarray(synthetic_token_batch(
            args.batch, args.seq, cfg.vocab, seed=args.seed + i))
        frames = None
        if cfg.family == "audio":
            from repro.data.tokens import synthetic_embedding_batch
            frames = jnp.asarray(synthetic_embedding_batch(
                args.batch, cfg.n_frames, cfg.d_model, seed=i))
            model_params, opt_state, loss = jstep(model_params, opt_state,
                                                  toks, frames)
        else:
            model_params, opt_state, loss = jstep(model_params, opt_state,
                                                  toks)
        losses.append(float(loss))
        if i % max(1, args.steps // 10) == 0:
            print(f"step {i}: loss {losses[-1]:.4f}", flush=True)
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
    assert np.isfinite(losses[-1])


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-lstm")
    ap.add_argument("--ticker", default="AAPL")
    ap.add_argument("--days", type=int, default=1430)
    ap.add_argument("--iterations", type=int, default=2000)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--tau", type=int, default=0)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--evl-weight", type=float, default=0.0)
    ap.add_argument("--constant-rounds", type=int, default=0,
                    help="use constant local-SGD schedule of this size")
    ap.add_argument("--save", default=None, metavar="PATH",
                    help="save the trained paper model as a serving "
                    "checkpoint (EVT-calibrated, version metadata)")
    ap.add_argument("--reduced", action="store_true")
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> None:
    args = parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.arch == "paper-lstm":
        run_paper_lstm(args)
    else:
        run_zoo(args)


if __name__ == "__main__":
    main()
