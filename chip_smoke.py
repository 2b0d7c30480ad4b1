#!/usr/bin/env python3
"""Smoke run of the paper LSTM on a TPU: async local-SGD training ->
serving checkpoint -> serving engine with streaming sessions, through
the entry points a user calls, at the widths of ``configs/paper_lstm.py``
(5 features, 2 x LSTM with hidden 64, FC 32/16, window 20, EVL head).

    python3 chip_smoke.py              # one chip: train + serve
    python3 chip_smoke.py --chips 4    # four chips: the sharded mesh only

Every input comes from ``--seed``. Any failed check exits non-zero. With
no TPU, or run away from the repository, the script exits non-zero and
prints no result; on success the last line of stdout is one JSON object
naming the device.

Phases:

- train: ``repro.launch.train.run_paper_lstm`` with 4 workers at batch
  32 and at least 3 model exchanges, saved as a serving checkpoint. The
  round loss must be finite and the test MSE below the untrained
  model's; the compiled round program must hold the Pallas LSTM cell.
- serve: ``repro.launch.serve.main`` on that checkpoint with sessions,
  then a checked pass through ``ServingEngine``: predict flushes below
  and at or above 8 rows (the XLA and the Pallas resolution of the
  cell), and streaming steps from more clients than there are decode
  lanes, so lanes spill and reload (with carry donation on a TPU).
  Forecasts are compared with a float64 NumPy LSTM on the same weights.
- ``--chips 4``: a ``ShardedServingEngine`` with one replica per chip
  against the same traffic through one shard, with a ``remove_shard``
  in mid-traffic that migrates session carries across chips.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
KEY = "paper-lstm"

# Forecast tolerance against the float64 reference. A TPU runs float32
# matmuls at its default precision as one bfloat16 pass: operands keep 8
# significant bits (relative error up to 2**-9). Emulating that rounding
# in NumPy on the trained weights moves forecasts of magnitude up to 0.13
# by at most 1.9e-3 (mean 2.5e-4) over 20 steps of two LSTM layers and
# the FC head; the bound leaves a margin of five over that.
FORECAST_ATOL = 1e-2


def check(cond, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu(n_chips: int):
    """The devices this run uses; no TPU (or too few chips) is a
    failure, never a fall back to the CPU."""
    import jax

    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX found {devices[0].platform} devices")
    check(len(devices) >= n_chips,
          f"{n_chips} chips needed, JAX found {len(devices)}")
    return devices


def require_kernel(compiled_text: str, what: str) -> None:
    check("tpu_custom_call" in compiled_text,
          f"the compiled {what} holds no Pallas kernel (tpu_custom_call)")


# -- the float64 reference -------------------------------------------------

def _sigmoid(z):
    return 1.0 / (1.0 + np.exp(-z))


def reference_forecasts(params, xs) -> np.ndarray:
    """Plain float64 NumPy forward of the paper LSTM over xs [B, T, F]:
    the forecast after every step, [B, T] (the last column is the
    forecast for the whole window)."""
    import jax

    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    seq = np.asarray(xs, np.float64)
    B, T, _ = seq.shape
    for lp in p["lstm"]:
        hid = lp["wh"].shape[0]
        h = np.zeros((B, hid))
        c = np.zeros((B, hid))
        out = []
        for t in range(T):
            gates = seq[:, t] @ lp["wx"] + h @ lp["wh"] + lp["b"]
            i, f, g, o = np.split(gates, 4, axis=-1)
            c = _sigmoid(f) * c + _sigmoid(i) * np.tanh(g)
            h = _sigmoid(o) * np.tanh(c)
            out.append(h)
        seq = np.stack(out, axis=1)
    h = seq
    for fp in p["fc"]:
        h = np.tanh(h @ fp["w"] + fp["b"])
    return (h @ p["out"]["w"] + p["out"]["b"])[..., 0]


def client_windows(n_clients: int, window: int, seed: int) -> np.ndarray:
    """One normalized OHLCV window per client, [n_clients, window, 5],
    from the synthetic S&P500 generator (a distinct ticker per client)."""
    from repro.data import load_stock, make_windows

    return np.stack([
        make_windows(load_stock(f"CLIENT{c}", n_days=window + 64,
                                seed=seed + c), window=window).x[0]
        for c in range(n_clients)]).astype(np.float32)


def resolve_all(futures) -> tuple[list, list]:
    """Every future's result (None where it failed) and the failures."""
    results, errors = [], []
    for f in futures:
        try:
            results.append(f.result(timeout=120.0))
        except Exception as e:  # noqa: BLE001 — counted, then reported
            results.append(None)
            errors.append(repr(e))
    return results, errors


# -- phases ----------------------------------------------------------------

def train_phase(ckpt: str, seed: int, iterations: int, days: int):
    """Train through the launcher and save a serving checkpoint."""
    import jax

    from repro.configs.paper_lstm import CONFIG
    from repro.core.async_local_sgd import AsyncLocalSGD, LocalSGDConfig
    from repro.data import load_stock, make_windows, train_test_split
    from repro.launch import train
    from repro.models.rnn import init_rnn
    from repro.optim.optimizers import sgd
    from repro.training.loop import evaluate, make_loss_fn

    workers, batch = 4, 32
    args = train.parse_args([
        "--arch", KEY, "--workers", str(workers), "--batch", str(batch),
        "--iterations", str(iterations), "--days", str(days),
        "--seed", str(seed), "--save", ckpt])
    res = train.run_paper_lstm(args)
    check(res.communications >= 3,
          f"{res.communications} model exchanges, want >= 3")
    check(np.all(np.isfinite(res.loss_history)),
          f"non-finite round loss {res.loss_history}")
    _, test = train_test_split(load_stock(args.ticker, n_days=days,
                                          seed=seed))
    untrained, _ = evaluate(init_rnn(jax.random.PRNGKey(seed), CONFIG),
                            CONFIG, make_windows(test))
    print(f"train: {res.communications} exchanges, round loss "
          f"{res.loss_history[0]!r} -> {res.loss_history[-1]!r}; test MSE "
          f"{res.test_mse!r} (untrained {untrained!r})")
    check(np.isfinite(res.test_mse) and res.test_mse < untrained,
          f"trained test MSE {res.test_mse} not below untrained {untrained}")

    # the trainer's round program at the shapes it ran: the Pallas cell
    # must sit in its forward pass under value_and_grad
    trainer = AsyncLocalSGD(make_loss_fn(CONFIG), sgd(momentum=0.0),
                            LocalSGDConfig(n_workers=workers))
    stacked, opt = trainer.init(init_rnn(jax.random.PRNGKey(seed), CONFIG))
    steps = trainer.local_steps_for_round(1)
    batches = (
        np.zeros((workers, steps, batch, CONFIG.window, CONFIG.input_dim),
                 np.float32),) + tuple(
        np.zeros((workers, steps, batch), np.float32) for _ in range(3))
    require_kernel(trainer._round.lower(stacked, opt, batches, 0.01)
                   .compile().as_text(), "training round")


def serve_phase(ckpt: str, seed: int, n_clients: int, decode_slots: int,
                requests: int) -> None:
    """The serve launcher on the checkpoint, then a checked pass."""
    import jax

    from repro.kernels import dispatch
    from repro.launch import serve
    from repro.serving import BatcherConfig, ModelRegistry, ServingEngine
    from repro.serving.forecaster import _donate_default

    serve.main(["--checkpoint", ckpt, "--model", KEY, "--sessions",
                "--requests", str(requests), "--seed", str(seed)])

    registry = ModelRegistry()
    fc = registry.load(ckpt, key=KEY)
    T, F = fc.window, fc.feature_dim
    streams = client_windows(n_clients, T, seed)
    want = reference_forecasts(fc.params, streams)        # [clients, T]
    config = BatcherConfig(max_batch=32, max_wait_ms=20.0,
                           length_buckets=(T,), decode_slots=decode_slots)
    with ServingEngine(registry, config) as engine:
        engine.warmup(KEY, lengths=(T,))
        runner = engine._step_runner(KEY)
        check(n_clients > runner.num_slots,
              f"{n_clients} clients fit in {runner.num_slots} lanes")
        # predict: a flush of 3 rows (padded to 4: the XLA cell), then
        # flushes of max_batch rows (the Pallas cell on a TPU)
        futures = []
        with dispatch.counting() as counts:
            for burst in (streams[:3], streams):
                futs = [engine.submit(KEY, w) for w in burst]
                resolve_all(futs)
                futures += futs
        predicted, errors = resolve_all(futures)
        check(not errors, f"{len(errors)} failed predicts: {errors[:3]}")
        flushes = sorted({(shape[0], impl) for (_bk, op, impl, shape)
                          in counts.counts if op == "predict"})
        print(f"serve: predict flushes (rows, cell) {flushes}")
        check(min(b for b, _ in flushes) < 8 <= max(b for b, _ in flushes),
              f"predict flushes {flushes} do not straddle 8 rows")
        y_pred = np.asarray([y for y, _ in predicted])
        y_want = np.concatenate([want[:3, -1], want[:, -1]])
        predict_err = float(np.max(np.abs(y_pred - y_want)))

        # streaming: every client steps through its window, one step per
        # client per tick, more clients than lanes
        stepped = np.zeros((n_clients, T))
        for t in range(T):
            futs = [engine.submit_step(KEY, f"client-{c}", streams[c, t])
                    for c in range(n_clients)]
            outs, errors = resolve_all(futs)
            check(not errors, f"{len(errors)} failed steps at tick {t}: "
                  f"{errors[:3]}")
            stepped[:, t] = [y for y, _ in outs]
        slots = runner.slot_stats()
        step_err = float(np.max(np.abs(stepped - want)))
        print(f"serve: {n_clients} clients x {T} steps over "
              f"{runner.num_slots} lanes: {slots['spills']} spills, "
              f"{slots['inserts']} inserts, lane donation "
              f"{'on' if _donate_default() else 'off'}")
        check(slots["spills"] > 0 and slots["inserts"] > n_clients,
              f"lanes never spilled and reloaded: {slots}")
        check(_donate_default() or jax.default_backend() == "cpu",
              "lane donation is off on an accelerator")
        generate = fc._fns["slots_generate_donate"].lower(
            fc.params, np.zeros((runner.num_slots, F), np.float32),
            runner._slots.carry, np.zeros((runner.num_slots,), bool),
            *fc._tail_args(), gamma=float(fc.gamma),
            width=fc.decode_width).compile().as_text()
    print(f"serve: max |forecast - float64 reference|: predict "
          f"{predict_err!r}, step {step_err!r} (tolerance {FORECAST_ATOL})")
    check(predict_err <= FORECAST_ATOL and step_err <= FORECAST_ATOL,
          "forecasts outside the tolerance of the float64 reference")
    require_kernel(generate, "slots generate")

    # recorded, not asserted: does the decode lane keep step == replay
    # == generate bitwise on this device?
    carry = fc.init_carry(1)
    by_step = []
    for t in range(T):
        y, _, carry = fc.step(streams[:1, t], carry)
        by_step.append(float(y[0]))
    by_replay = float(fc.replay(streams[:1])[0][0])
    print(f"serve: bitwise step == generate over {T} steps: "
          f"{by_step == list(stepped[0])}; replay == step: "
          f"{by_replay == by_step[-1]}; replay == generate: "
          f"{by_replay == stepped[0, -1]}")


def mesh_phase(seed: int, n_chips: int, n_clients: int) -> None:
    """One replica per chip behind the router, against one shard; a
    shard leaves in mid-traffic and its session carries move chips."""
    import jax

    from repro.serving import (BatcherConfig, ModelRegistry,
                               ShardedServingEngine, build_lstm_forecaster)

    fc = build_lstm_forecaster(seed=seed)
    T = fc.window
    streams = client_windows(n_clients, T, seed)
    devices = jax.devices()[:n_chips]
    config = BatcherConfig(max_batch=32, max_wait_ms=50.0,
                           length_buckets=(T,))
    leave_at = T // 2

    def run(n_shards: int):
        """Per tick: one step from every client and a window predict
        from a quarter of them. Returns the step forecasts [T, clients],
        the predict forecasts, the failures and the migrated clients."""
        registry = ModelRegistry()
        registry.register(KEY, fc)
        engine = ShardedServingEngine(registry, config, n_shards=n_shards)
        steps, predicts, errors, moved = [], [], [], []
        with engine:
            engine.warmup(KEY, lengths=(T,))
            if n_shards > 1:
                placed = {}
                for sid in engine.shard_ids:
                    replica = engine.swarm.registry_for(sid).get(KEY)
                    devs = {d for leaf in jax.tree_util.tree_leaves(
                        replica.params) for d in leaf.devices()}
                    check(len(devs) == 1,
                          f"shard {sid} params span {devs}")
                    placed[sid] = devs.pop()
                    y, _ = replica._fns["predict"](
                        replica.params, streams[:8],
                        np.full((8,), T, np.int32), *replica._tail_args(),
                        gamma=float(replica.gamma))
                    check(y.devices() == {placed[sid]},
                          f"shard {sid} flush ran on {y.devices()}, its "
                          f"params live on {placed[sid]}")
                print(f"mesh: replica devices {placed}")
                check(set(placed.values()) == set(devices),
                      f"replicas on {placed}, want one per chip "
                      f"{devices}")
            for t in range(T):
                step_futs = [engine.submit_step(KEY, f"client-{c}",
                                                streams[c, t])
                             for c in range(n_clients)]
                predict_futs = [engine.submit(KEY, streams[c],
                                              client_id=f"client-{c}")
                                for c in range(t % 4, n_clients, 4)]
                if n_shards > 1 and t == leave_at:
                    # in mid-traffic: this tick's requests are in flight
                    leaving = engine.shard_for("client-0")
                    moved = [c for c in range(n_clients)
                             if engine.shard_for(f"client-{c}") == leaving]
                    engine.remove_shard(leaving)
                    print(f"mesh: shard {leaving} left at tick {t}, "
                          f"{len(moved)} clients' carries moved to "
                          f"{sorted({engine.shard_for(f'client-{c}') for c in moved})}")
                for futs, out in ((step_futs, steps),
                                  (predict_futs, predicts)):
                    results, failed = resolve_all(futs)
                    out.append([r[0] if r else np.nan for r in results])
                    errors += failed
        return (np.asarray(steps), np.concatenate(predicts), errors,
                moved)

    t0 = time.perf_counter()
    one_steps, one_predicts, one_errors, _ = run(1)
    steps, predicts, errors, moved = run(n_chips)
    n = steps.size + predicts.size
    print(f"mesh: {n} requests over {n_chips} shards, {len(errors)} "
          f"failed; over 1 shard, {len(one_errors)} failed "
          f"({time.perf_counter() - t0:.1f} s with compiles)")
    check(not one_errors and not errors,
          f"failed futures: {(one_errors + errors)[:3]}")
    check(moved, "the departing shard owned no session to migrate")
    # steps run the same decode-lane program on every chip; a predict
    # flush's batch (and so its cell: XLA below 8 rows, Pallas from 8)
    # depends on how many requests reach a shard together
    step_diff = float(np.max(np.abs(steps - one_steps)))
    predict_diff = float(np.max(np.abs(predicts - one_predicts)))
    print(f"mesh: {n_chips} shards vs 1 shard: max |diff| steps "
          f"{step_diff!r} (bitwise {bool(np.array_equal(steps, one_steps))}"
          f"), predicts {predict_diff!r} (bitwise "
          f"{bool(np.array_equal(predicts, one_predicts))})")
    check(step_diff <= 1e-6, f"{n_chips}-shard steps differ from 1 shard "
          f"by {step_diff}")
    check(predict_diff <= FORECAST_ATOL, f"{n_chips}-shard predicts "
          f"differ from 1 shard by {predict_diff}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded mesh across four chips")
    args = ap.parse_args(argv)

    for var in ("REPRO_KERNEL_IMPL", "REPRO_DISPATCH_TABLE"):
        check(var not in os.environ,
              f"{var} is set: the smoke run takes the default dispatch")
    devices = require_tpu(args.chips)
    print(f"device: {devices[0].device_kind} x {len(devices)} "
          f"({devices[0].platform})", flush=True)
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(args.seed, n_chips=4, n_clients=32)
    else:
        with tempfile.TemporaryDirectory() as tmp:
            ckpt = os.path.join(tmp, "paper_lstm.npz")
            train_phase(ckpt, args.seed, iterations=200, days=1430)
            print(f"phase train: {time.perf_counter() - t0:.1f} s "
                  f"(compiles included)", flush=True)
            t1 = time.perf_counter()
            serve_phase(ckpt, args.seed, n_clients=96, decode_slots=64,
                        requests=256)
            print(f"phase serve: {time.perf_counter() - t1:.1f} s "
                  f"(compiles included)", flush=True)
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
