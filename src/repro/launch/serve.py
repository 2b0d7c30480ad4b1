"""Serving launcher: thin CLI over ``repro.serving`` — hosts the paper
LSTM and/or zoo archs behind the dynamic micro-batching engine (one
shard, or a sharded mesh with ``--shards``) and replays a simulated
many-client traffic trace against it.

    # stream stock windows from 64 synthetic clients at the paper model
    PYTHONPATH=src python -m repro.launch.serve --model paper-lstm \
        --clients 64 --requests 512 --max-batch 32 --max-wait-ms 2

    # the same trace over a 4-shard serving mesh
    PYTHONPATH=src python -m repro.launch.serve --shards 4 --requests 512

    # the mesh over OS processes (one EngineShard per process, socket
    # transport between router and workers)
    PYTHONPATH=src python -m repro.launch.serve --shards 2 --processes

    # durable state plane: publishes + periodic async session
    # checkpoints land under ./state; a later run with the same
    # --state-dir cold-restarts the fleet from the last good manifest
    PYTHONPATH=src python -m repro.launch.serve --shards 2 --processes \
        --state-dir ./state --checkpoint-interval-s 2

    # host a REAL trained checkpoint (from `-m repro.launch.train
    # --save ckpt.npz`) and score its extreme alerts against the
    # synthetic labels
    PYTHONPATH=src python -m repro.launch.serve --checkpoint ckpt.npz

    # host a zoo arch (reduced config) serving next-token forecasts
    PYTHONPATH=src python -m repro.launch.serve --model qwen1.5-4b \
        --requests 128 --prompt-len 32
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def _traffic_datasets(n_clients: int, window: int, seed: int):
    """Per-client window datasets from the synthetic S&P500 generator
    (distinct ticker per client); ``.x`` feeds traffic, ``.v`` is the
    extreme-event label of each window's next step."""
    from repro.data import load_stock, make_windows

    streams = []
    for c in range(n_clients):
        ohlcv = load_stock(f"CLIENT{c}", n_days=window + 64, seed=seed + c)
        streams.append(make_windows(ohlcv, window=window))
    return streams


def _precision_recall(alerts: np.ndarray, labels: np.ndarray):
    tp = int(np.sum(alerts & (labels != 0)))
    fp = int(np.sum(alerts & (labels == 0)))
    fn = int(np.sum(~alerts & (labels != 0)))
    precision = tp / (tp + fp) if tp + fp else 0.0
    recall = tp / (tp + fn) if tp + fn else 0.0
    return precision, recall, tp, fp, fn


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="paper-lstm",
                    help="'paper-lstm' or any zoo arch name")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="host a trained serving checkpoint (the output "
                    "of `-m repro.launch.train --save`) instead of a "
                    "freshly initialized model, and report alert "
                    "precision/recall against the synthetic extreme "
                    "labels")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the reduced (CPU smoke) zoo config; "
                    "--no-reduced hosts the full config")
    ap.add_argument("--shards", type=int, default=1,
                    help="serve through a sharded mesh with this many "
                    "EngineShard workers (1 = single engine)")
    ap.add_argument("--processes", action="store_true",
                    help="with --shards > 1: run each shard as its own "
                    "OS process behind the socket transport "
                    "(repro.serving.transport) instead of a thread")
    ap.add_argument("--connect", action="append", default=[],
                    metavar="HOST:PORT",
                    help="(implies --processes) also join a shard worker "
                    "already listening at HOST:PORT (started with "
                    "`python -m repro.launch.shard_worker`); repeatable")
    ap.add_argument("--heartbeat-s", type=float, default=0.5,
                    help="process-mesh supervision heartbeat interval "
                    "(crashed workers are detected within "
                    "heartbeat * 4 and respawned)")
    ap.add_argument("--state-dir", default=None, metavar="DIR",
                    help="durable state plane: DurableStore root. Every "
                    "publish lands there before acknowledgement; with "
                    "--processes the mesh also cold-restarts from the "
                    "last good checkpoint (weights, ensemble specs, "
                    "session carries) and a CheckpointDaemon snapshots "
                    "periodically off the hot path")
    ap.add_argument("--checkpoint-interval-s", type=float, default=5.0,
                    help="async checkpoint period for --state-dir "
                    "(a final checkpoint is always taken at shutdown)")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="checkpoint retention for --state-dir: keep "
                    "this many manifests (older ones + unreferenced "
                    "blobs are garbage-collected)")
    ap.add_argument("--max-skew", type=int, default=1,
                    help="mesh swap-propagation staleness bound "
                    "(versions a shard may lag the primary)")
    ap.add_argument("--ensemble", type=int, default=1, metavar="N",
                    help="serve an N-member ensemble of the model "
                    "(distinct init seeds) fused by EVT-weighted "
                    "combination, with the anomaly-aware alert path; "
                    "traffic routes at the ensemble name and every "
                    "request fans out to N per-model fused dispatches")
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--sessions", action="store_true",
                    help="also demo O(1) per-step session serving")
    ap.add_argument("--alert-threshold", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", action="store_true",
                    help="record per-request trace spans (submit -> queue "
                    "-> flush -> ... -> reply) and print a span summary "
                    "of the slowest trace")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve /metrics (Prometheus), /metrics.json, "
                    "/history, /traces and /events on this port while "
                    "the traffic runs (0 = ephemeral; fleet-merged view "
                    "on a mesh)")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="append phase markers + final snapshot as JSONL "
                    "events to PATH (tools/report.py renders them)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the traffic "
                    "phase into DIR (view with TensorBoard / Perfetto)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.obs import EventLog, MetricsServer, Tracer
    from repro.serving import (BatcherConfig, CheckpointDaemon,
                               DurableStore, ModelRegistry,
                               MultiProcessServingEngine, ServingEngine,
                               ShardedServingEngine, Telemetry,
                               build_lstm_forecaster, build_zoo_forecaster)

    store = (DurableStore(args.state_dir, keep_last=args.keep_last)
             if args.state_dir else None)
    registry = ModelRegistry()
    if args.checkpoint:
        fc = registry.load(args.checkpoint, key=args.model)
        print(f"hosting checkpoint {args.checkpoint} as {args.model!r} "
              f"(kind={fc.kind}, v{registry.version(args.model)})")
    elif args.model == "paper-lstm":
        fc = build_lstm_forecaster(seed=args.seed)
    else:
        fc = build_zoo_forecaster(args.model, seed=args.seed,
                                  reduced=args.reduced)
    if args.model not in registry:
        registry.register(args.model, fc)

    serve_key = args.model
    if args.ensemble > 1:
        if args.checkpoint:
            ap.error("--ensemble needs distinct member inits; it does "
                     "not combine with --checkpoint")
        members = [args.model]
        for i in range(1, args.ensemble):
            key = f"{args.model}-{i}"
            if args.model == "paper-lstm":
                m = build_lstm_forecaster(seed=args.seed + i)
            else:
                m = build_zoo_forecaster(args.model, seed=args.seed + i,
                                         reduced=args.reduced)
            registry.register(key, m)
            members.append(key)
        serve_key = f"{args.model}-ensemble"
        registry.register_ensemble(serve_key, members,
                                   alert_threshold=args.alert_threshold)
        print(f"hosting {serve_key!r}: {args.ensemble} members "
              f"{members} fused by EVT-weighted combination")

    labels = None
    if fc.feature_dim:                      # window-stream (LSTM) traffic
        streams = _traffic_datasets(args.clients, fc.window, args.seed)
        payloads, labels_list = [], []
        for i in range(args.requests):
            ds = streams[i % args.clients]
            j = i % len(ds)
            payloads.append(ds.x[j])
            labels_list.append(int(ds.v[j]))
        labels = np.asarray(labels_list)
    else:                                   # token traffic for zoo archs
        from repro.data.tokens import synthetic_token_batch
        toks = synthetic_token_batch(args.requests, args.prompt_len,
                                     fc.cfg.vocab, seed=args.seed)
        payloads = list(toks)

    # bucket exactly the lengths this trace contains: no padding waste
    cfg = BatcherConfig(max_batch=args.max_batch,
                        max_wait_ms=args.max_wait_ms,
                        length_buckets=tuple(sorted(
                            {p.shape[0] for p in payloads})))
    lengths = tuple({p.shape[0] for p in payloads})
    tracer = Tracer(capacity=1024) if args.trace else None
    events = EventLog(path=args.events_out) if args.events_out else None
    if args.connect:
        args.processes = True
        args.shards = max(args.shards, 1)
    if (args.shards > 1 or args.connect) and args.processes:
        engine = MultiProcessServingEngine(registry, cfg,
                                           n_shards=args.shards,
                                           max_skew=args.max_skew,
                                           tracer=tracer,
                                           heartbeat_s=args.heartbeat_s,
                                           events=events,
                                           durable=store)
    elif args.shards > 1:
        if store is not None:
            registry.attach_durable(store)   # weights durable; the
            # session/restart plane needs the process mesh (--processes)
        engine = ShardedServingEngine(registry, cfg, n_shards=args.shards,
                                      max_skew=args.max_skew,
                                      tracer=tracer)
    else:
        if store is not None:
            registry.attach_durable(store)
        engine = ServingEngine(registry, cfg, tracer=tracer)

    is_mesh = args.shards > 1 or bool(args.connect)
    snapshot_fn = (engine.snapshot if is_mesh
                   else lambda: engine.telemetry.snapshot())
    metrics = None
    if args.metrics_port is not None:
        metrics = MetricsServer(snapshot_fn, port=args.metrics_port,
                                tracer=tracer, events=events,
                                sample_interval_s=0.5).start()
        print(f"metrics: {metrics.url}/metrics (also /metrics.json, "
              f"/history, /traces, /events)")
    if events is not None:
        events.log("phase", name="traffic", model=args.model,
                   shards=args.shards, requests=args.requests)

    profile_ctx = None
    if args.profile_dir:
        import jax

        profile_ctx = jax.profiler.trace(args.profile_dir)

    with engine:
        for addr in args.connect:
            sid = engine.connect_shard(addr)
            print(f"joined remote shard worker {addr} as shard {sid}")
        daemon = None
        if store is not None and isinstance(engine,
                                            MultiProcessServingEngine):
            restored = engine.restore_from(store)
            if restored["seq"] is not None:
                print(f"durable restore from {args.state_dir} (manifest "
                      f"{restored['seq']}): models {restored['models']}, "
                      f"{restored['restored_sessions']} sessions resumed"
                      f" ({restored['restored_stale']} stale ->"
                      f" history re-prime)")
            daemon = CheckpointDaemon(
                store, engine, interval_s=args.checkpoint_interval_s,
                events=events).start()
        engine.warmup(serve_key, lengths=lengths)
        if is_mesh:
            engine.reset_clock()
        else:
            engine.telemetry.reset_clock()
        if profile_ctx is not None:
            profile_ctx.__enter__()
        t0 = time.time()
        futures = [engine.submit(serve_key, p,
                                 client_id=f"client-{i % args.clients}")
                   for i, p in enumerate(payloads)]
        results = [f.result(timeout=60.0) for f in futures]
        wall = time.time() - t0
        if profile_ctx is not None:
            profile_ctx.__exit__(None, None, None)
            print(f"profiler capture written to {args.profile_dir}")
        snap = (engine.snapshot() if is_mesh
                else engine.telemetry.snapshot())
        if events is not None:
            events.log("snapshot", phase="traffic", wall_s=wall, **{
                k: v for k, v in snap.items()
                if isinstance(v, (int, float, bool))})
        if args.sessions and fc.feature_dim and is_mesh \
                and args.processes:
            # sessions live in the worker processes' shard-local caches:
            # each step is routed to the client's owning worker
            streams = _traffic_datasets(min(args.clients, 8), fc.window,
                                        args.seed + 1)
            t0s = time.time()
            n_steps = 0
            for step in range(fc.window):
                for c, ds in enumerate(streams):
                    engine.step(serve_key, f"client-{c}", ds.x[0][step])
                    n_steps += 1
            wall_s = time.time() - t0s
            # resident = device-lane residents + spilled-to-cache; the
            # slots figure shows how many sit in decode lanes right now
            by_worker = {
                sid: f"{len(st['clients'])}"
                     f"({st['slots']['active']}/{st['slots']['lanes']}"
                     f" in lanes)"
                for sid, st in engine.shard_stats().items()}
            print(f"sessions (worker-resident): {n_steps} O(1) steps in "
                  f"{wall_s*1e3:.1f} ms "
                  f"({n_steps/max(wall_s,1e-9):.0f} steps/s); "
                  f"resident by worker {by_worker}")
        elif args.sessions and fc.feature_dim:
            # engine-resident sessions over the slotted decode path:
            # carries live in device decode lanes between ticks, so each
            # tick's steps flush as ONE fused slots_generate dispatch
            # per shard instead of one jit dispatch per client
            streams = _traffic_datasets(min(args.clients, 8), fc.window,
                                        args.seed + 1)
            t0s = time.time()
            n_steps = 0
            for step in range(fc.window):
                futs = [engine.submit_step(serve_key, f"client-{c}",
                                           ds.x[0][step])
                        for c, ds in enumerate(streams)]
                for f in futs:
                    f.result(timeout=30.0)
                n_steps += len(futs)
            wall_s = time.time() - t0s
            ssnap = (engine.snapshot() if is_mesh
                     else engine.telemetry.snapshot())
            print(f"sessions (batched decode): {n_steps} steps in "
                  f"{wall_s*1e3:.1f} ms "
                  f"({n_steps/max(wall_s,1e-9):.0f} steps/s); "
                  f"{ssnap['step_batches']} fused flushes, mean batch "
                  f"{ssnap['mean_step_batch']:.1f}, step p95 "
                  f"{ssnap['step_p95_ms']:.2f} ms")
            if events is not None:
                events.log("snapshot", phase="sessions", wall_s=wall_s,
                           **{k: v for k, v in ssnap.items()
                              if isinstance(v, (int, float, bool))})
        if daemon is not None:
            # one last synchronous snapshot: a clean shutdown is as
            # durable as a crash-with-checkpoint, so the next
            # `--state-dir` run resumes every stream
            daemon.stop(final_checkpoint=True)
            print(f"durable: {daemon.commits} checkpoint commits to "
                  f"{args.state_dir} (last manifest {daemon.last_seq})")

    alert_mask = np.asarray([p >= args.alert_threshold
                             for _, p in results], dtype=bool)
    alerts = [(i, y, p) for i, (y, p) in enumerate(results)
              if p >= args.alert_threshold]
    print(f"{serve_key}: {len(results)} requests in {wall*1e3:.1f} ms"
          + (f" over {engine.n_shards} shards" if is_mesh else ""))
    print(Telemetry.format(snap))
    if is_mesh:
        print(f"mesh: requests by shard {snap['requests_by_shard']} | "
              f"{snap['pulls']} weight pulls "
              f"({snap['bytes_pulled']/1e6:.2f} MB) | version vector "
              f"{engine.version_vector(args.model)}")
    print(f"extreme alerts (p >= {args.alert_threshold}): {len(alerts)}"
          + (f", first: req {alerts[0][0]} forecast {alerts[0][1]:+.4f} "
                 f"p {alerts[0][2]:.3f}" if alerts else ""))
    if labels is not None and labels.size:
        precision, recall, tp, fp, fn = _precision_recall(alert_mask,
                                                          labels)
        print(f"alert quality vs synthetic extreme labels: precision "
              f"{precision:.3f}  recall {recall:.3f}  (tp={tp} fp={fp} "
              f"fn={fn}, base rate {float(np.mean(labels != 0)):.3f})")
    if tracer is not None:
        done = tracer.traces()
        if done:
            slow = max(done, key=lambda t: t.duration)
            parts = "  ".join(
                f"{s.name} {s.dur*1e3:.2f}ms"
                for s in sorted(slow.spans, key=lambda s: s.t0))
            print(f"traces: {len(done)} recorded; slowest "
                  f"({slow.op}, {slow.duration*1e3:.2f} ms): {parts}")
    if events is not None:
        events.log("phase", name="done")
        events.close()
        print(f"events written to {args.events_out}")
    if metrics is not None:
        metrics.stop()


if __name__ == "__main__":
    main()
