"""Online-learning launcher: train and serve in ONE process — the
paper-faithful "continuously retrain on streaming stock data while
serving forecasts" scenario (ROADMAP north-star, unlocked by the
hot-swap bridge in ``repro.serving.hotswap``).

A background thread runs the async local-SGD round loop over
``data/sp500.py`` windows; after every cross-worker model exchange the
round's worker-averaged parameters are published into the live
``ModelRegistry`` (EVT tail re-calibrated on the new weights), and the
serving engine picks the new version up between micro-batch flushes —
no request is ever dropped by a weight update. The foreground thread
plays client traffic against the engine the whole time and reports
swap count, staleness at serve time, and per-version request counts.

With ``--shards N`` the serving side is the sharded mesh: the publisher
publishes into the swap-propagation swarm's primary registry and every
shard's replica pulls the new weights within ``--max-skew`` versions,
while all shards keep draining traffic. With ``--processes`` the mesh
shards are separate OS processes behind the socket transport
(``repro.serving.transport``): each publish ships a serialized
checkpoint to every worker under the same skew bound.

    PYTHONPATH=src python -m repro.launch.online --ticker AAPL \
        --workers 3 --iterations 600 --requests 400

    PYTHONPATH=src python -m repro.launch.online --shards 4 \
        --iterations 300 --requests 200

    PYTHONPATH=src python -m repro.launch.online --shards 2 --processes \
        --iterations 200 --requests 100
"""

from __future__ import annotations

import argparse
import threading
import time

import numpy as np


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticker", default="AAPL")
    ap.add_argument("--days", type=int, default=800)
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--iterations", type=int, default=600)
    ap.add_argument("--tau", type=int, default=0)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--requests", type=int, default=400,
                    help="minimum client requests to play against the "
                    "engine; traffic keeps flowing until training ends")
    ap.add_argument("--rps", type=float, default=100.0,
                    help="client traffic rate (requests/s), paced so the "
                    "trace spans the whole training run")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--shards", type=int, default=1,
                    help="serve through a sharded mesh with this many "
                    "EngineShard workers (1 = single engine)")
    ap.add_argument("--processes", action="store_true",
                    help="with --shards > 1: one OS process per shard "
                    "over the socket transport")
    ap.add_argument("--max-skew", type=int, default=1,
                    help="mesh staleness bound: versions a shard may lag "
                    "the primary before a publish forces its pull")
    ap.add_argument("--min-publish-interval-ms", type=float, default=0.0,
                    help="rate-limit weight publishes (0 = every round)")
    ap.add_argument("--calib-windows", type=int, default=64,
                    help="reference windows for per-publish EVT "
                    "re-calibration (0 disables re-calibration)")
    ap.add_argument("--save", default=None, metavar="PATH",
                    help="save the final published version as a serving "
                    "checkpoint on exit")
    ap.add_argument("--evl-weight", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve /metrics (Prometheus), /metrics.json and "
                    "/history on this port while training + serving run "
                    "(0 = ephemeral; fleet-merged view on a mesh) — the "
                    "live time-series view of serve-under-churn")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.configs.paper_lstm import CONFIG
    from repro.data import load_stock, make_windows, train_test_split
    from repro.models.rnn import init_rnn
    from repro.serving import (BatcherConfig, LSTMForecaster, ModelRegistry,
                               MultiProcessServingEngine, ServingEngine,
                               ShardedServingEngine, Telemetry,
                               WeightPublisher)
    from repro.training.loop import train_rnn_local_sgd

    import jax

    ohlcv = load_stock(args.ticker, n_days=args.days, seed=args.seed)
    tr, te = train_test_split(ohlcv)
    train_ds, test_ds = make_windows(tr), make_windows(te)
    print(f"{args.ticker}: {len(train_ds)} train windows feeding the "
          f"trainer, {len(test_ds)} test windows as client traffic")

    # v1: freshly initialized paper model, calibrated on the train set —
    # what a cold-started service would host before training catches up
    key = "paper-lstm"
    fc0 = LSTMForecaster(cfg=CONFIG,
                         params=init_rnn(jax.random.PRNGKey(args.seed),
                                         CONFIG))
    fc0.calibrate(train_ds.x[:max(args.calib_windows, 16)])
    registry = ModelRegistry()
    registry.register(key, fc0)

    calib = (train_ds.x[:args.calib_windows]
             if args.calib_windows else None)
    bcfg = BatcherConfig(max_batch=args.max_batch,
                         max_wait_ms=args.max_wait_ms,
                         length_buckets=(CONFIG.window,))
    mesh = args.shards > 1
    if mesh and args.processes:
        engine = MultiProcessServingEngine(registry, bcfg,
                                           n_shards=args.shards,
                                           max_skew=args.max_skew)
        # publish through the mesh facade: each publish ships a
        # serialized checkpoint to every worker process under the
        # skew bound, atomically with the primary swap
        publish_target, pub_telemetry = engine, None
    elif mesh:
        engine = ShardedServingEngine(registry, bcfg,
                                      n_shards=args.shards,
                                      max_skew=args.max_skew)
        # publish into the swarm: the primary swap fans out to every
        # shard's replica within the skew bound (pulls count as swaps
        # on each shard's telemetry, so no publisher telemetry here)
        publish_target, pub_telemetry = engine.swarm, None
    else:
        engine = ServingEngine(registry, bcfg)
        publish_target, pub_telemetry = registry, engine.telemetry
    publisher = WeightPublisher(
        publish_target, key, calib_windows=calib,
        min_interval_s=args.min_publish_interval_ms * 1e-3,
        telemetry=pub_telemetry)

    metrics = None
    if args.metrics_port is not None:
        from repro.obs import MetricsServer

        snapshot_fn = (engine.snapshot if mesh
                       else lambda: engine.telemetry.snapshot())
        metrics = MetricsServer(snapshot_fn, port=args.metrics_port,
                                sample_interval_s=0.5).start()
        print(f"metrics: {metrics.url}/metrics (also /metrics.json, "
              f"/history)")

    trainer_err: list[BaseException] = []

    def train() -> None:
        try:
            train_rnn_local_sgd(
                train_ds, test_ds, n_workers=args.workers,
                iterations=args.iterations, batch=args.batch,
                tau=args.tau, seed=args.seed, evl_weight=args.evl_weight,
                round_callback=publisher)
        except BaseException as e:  # noqa: BLE001 — surfaced after join
            trainer_err.append(e)

    with engine:
        engine.warmup(key, lengths=(CONFIG.window,))
        if mesh:
            engine.reset_clock()
        else:
            engine.telemetry.reset_clock()
        trainer = threading.Thread(target=train, name="online-trainer")
        t0 = time.time()
        trainer.start()
        served = 0
        alerts = 0
        burst = max(1, min(args.max_batch, 8))
        period = burst / max(args.rps, 1e-3)
        next_t = time.perf_counter()
        while trainer.is_alive() or served < args.requests:
            now = time.perf_counter()
            if now < next_t:
                time.sleep(min(next_t - now, 0.05))
                continue
            futs = [engine.submit(key, test_ds.x[(served + j) % len(test_ds)],
                                  client_id=f"client-{(served + j) % 32}")
                    for j in range(burst)]
            for f in futs:
                _, p = f.result(timeout=60.0)
                alerts += p >= 0.9
            served += burst
            next_t += period
            if next_t < time.perf_counter() - 1.0:
                next_t = time.perf_counter()   # engine slower than --rps:
                # shed schedule debt instead of bursting to catch up
        trainer.join()
        # a rate-limited final round must still reach the registry: the
        # served (and --save'd) model is never staler than the trained one
        publisher.flush()
        if mesh:
            # shards converge to the final version before the engine
            # stops (swarm pulls in-process, checkpoint pushes across)
            (engine if args.processes else engine.swarm).propagate(key)
        wall = time.time() - t0
        snap = engine.snapshot() if mesh else engine.telemetry.snapshot()
    if metrics is not None:
        metrics.stop()
    if trainer_err:
        raise trainer_err[0]

    print(f"served {served} requests ({alerts} extreme alerts) while "
          f"training ran, {wall:.1f}s wall"
          + (f" over {args.shards} shards" if mesh else ""))
    print(Telemetry.format(snap))
    if mesh:
        print(f"mesh: requests by shard {snap['requests_by_shard']} | "
              f"{snap['pulls']} weight pulls "
              f"({snap['bytes_pulled']/1e6:.2f} MB) | version vector "
              f"{engine.version_vector(key)} | max skew bound "
              f"{args.max_skew}")
    by_version = snap["requests_by_version"]
    print(f"swaps {snap['swaps']} (publisher: {publisher.published} "
          f"published, {publisher.skipped} rate-limited) | final version "
          f"v{registry.version(key)} | staleness at serve p50 "
          f"{snap['staleness_p50_s']*1e3:.0f} ms")
    print("requests by version: "
          + ", ".join(f"v{v}: {n}" for v, n in sorted(by_version.items())))
    if args.save:
        registry.save(key, args.save)
        print(f"saved v{registry.version(key)} -> {args.save}")


if __name__ == "__main__":
    main()
