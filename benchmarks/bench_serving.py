"""Serving throughput/latency across micro-batcher settings (ISSUE 1
acceptance: the dynamic batcher must sustain >= 5x the throughput of
batch-size-1 serving on the reduced paper LSTM config), plus the
streaming DECODE phase through the engine's step flush, plus the SLOTS
phase (device-resident decode slots at >= 32 resident sessions, with
dispatch counting proving one fused generate per flush and nothing
per-session — hard-asserted under ``--smoke``). Speed is judged on the
chip (``bench/``); these rows are CPU records.

Rows: ``serve/<config>,us_per_request,rps=..;p95_ms=..;occ=..``, a
``serve/speedup_vs_batch1`` row with the headline multiple,
``serve/decode_engine`` for the streaming phase, and
``serve/slots_resident`` for the slot-resident phase.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import row
from repro.models.rnn import RNNConfig


def _configs(window: int):
    from repro.serving import BatcherConfig
    buckets = (window,)          # exact-length bucket: no padding waste
    return [
        ("batch1", BatcherConfig(max_batch=1, max_wait_ms=0.0,
                                 length_buckets=buckets)),
        ("micro8_w2ms", BatcherConfig(max_batch=8, max_wait_ms=2.0,
                                      length_buckets=buckets)),
        ("micro32_w2ms", BatcherConfig(max_batch=32, max_wait_ms=2.0,
                                       length_buckets=buckets)),
        ("micro64_w5ms", BatcherConfig(max_batch=64, max_wait_ms=5.0,
                                       length_buckets=buckets)),
    ]


def main(n_requests: int = 512, smoke: bool = False) -> None:
    import jax

    from repro.models.rnn import init_rnn
    from repro.serving import (LSTMForecaster, ModelRegistry,
                               RecurrentSessionRunner, ServingEngine,
                               SessionCache, Telemetry)

    if smoke:
        n_requests = min(n_requests, 128)

    # reduced paper config: same topology (2 LSTM + 3 FC, window 20),
    # smaller widths so the bench isolates serving overhead
    cfg = RNNConfig(input_dim=5, hidden=32, num_layers=2, fc_dims=(16, 8),
                    window=20, evl_head=True)
    fc = LSTMForecaster(cfg=cfg, params=init_rnn(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    fc.calibrate(rng.standard_normal((64, cfg.window, 5)).astype(np.float32)
                 * 0.02)
    reg = ModelRegistry()
    reg.register("m", fc)

    windows = rng.standard_normal(
        (n_requests, cfg.window, 5)).astype(np.float32) * 0.02
    rps = {}
    configs = _configs(cfg.window)
    if smoke:
        configs = [c for c in configs if c[0] in ("batch1", "micro32_w2ms")]
    for name, bcfg in configs:
        with ServingEngine(reg, bcfg, telemetry=Telemetry()) as eng:
            eng.warmup("m", lengths=(cfg.window,))
            eng.telemetry.reset_clock()
            futures = [eng.submit("m", w) for w in windows]
            for f in futures:
                f.result(timeout=120.0)
            snap = eng.telemetry.snapshot()
        rps[name] = snap["throughput_rps"]
        row(f"serve/{name}", 1e6 / max(snap["throughput_rps"], 1e-9),
            f"rps={snap['throughput_rps']:.0f};p95_ms={snap['p95_ms']:.2f};"
            f"occ={snap['batch_occupancy']:.2f}")

    best = max(v for k, v in rps.items() if k != "batch1")
    speedup = best / max(rps["batch1"], 1e-9)
    row("serve/speedup_vs_batch1", 0.0,
        f"{speedup:.1f}x{' (>=5x OK)' if speedup >= 5.0 else ' (BELOW 5x)'}")

    # -- decode phase: multi-session streaming steps -----------------------
    # N sessions each advance one observation per tick, end to end
    # through the engine's step flush grouping: every tick is submitted
    # without waiting (the flush waves keep one client's steps ordered
    # even when several ticks share a flush), so this measures pipelined
    # streaming throughput, not max_wait
    n_sessions = 8 if smoke else 64
    n_ticks = 25 if smoke else 100
    xs = rng.standard_normal(
        (n_ticks, n_sessions, 5)).astype(np.float32) * 0.02
    bcfg = next(c for n, c in _configs(cfg.window) if n == "micro64_w5ms")
    with ServingEngine(reg, bcfg, telemetry=Telemetry()) as eng:
        eng.warmup("m", lengths=(cfg.window,))
        eng.telemetry.reset_clock()
        t0 = time.perf_counter()
        futs = [eng.submit_step("m", f"s{s}", xs[t, s])
                for t in range(n_ticks) for s in range(n_sessions)]
        for f in futs:
            f.result(timeout=60.0)
        engine_sps = n_ticks * n_sessions / (time.perf_counter() - t0)
        snap = eng.telemetry.snapshot()
    row("serve/decode_engine", 1e6 / max(engine_sps, 1e-9),
        f"steps_per_s={engine_sps:.0f};flushes={snap['step_batches']};"
        f"mean_step_batch={snap['mean_step_batch']:.1f};"
        f"step_p95_ms={snap['step_p95_ms']:.2f}")

    # -- slots phase: device-resident lanes --------------------------------
    # Steady state: every session already occupies a device lane, so a
    # flush is ONE fused slots_generate dispatch — no per-session
    # dispatch, no insert. Dispatch counting proves it rather than
    # asserting it by eye.
    from repro.kernels import dispatch

    n_slot_sessions = 32 if smoke else 64     # acceptance floor is 32
    n_slot_ticks = 25 if smoke else 100
    sxs = rng.standard_normal(
        (n_slot_ticks + 1, n_slot_sessions, 5)).astype(np.float32) * 0.02
    runner = RecurrentSessionRunner(
        fc, SessionCache(max_sessions=n_slot_sessions),
        num_slots=n_slot_sessions)
    # first tick makes every session lane-resident (prefill+insert)
    runner.step_many([(f"s{s}", sxs[0, s], None)
                      for s in range(n_slot_sessions)])
    with dispatch.counting() as counts:
        t0 = time.perf_counter()
        for t in range(1, n_slot_ticks + 1):
            runner.step_many([(f"s{s}", sxs[t, s], None)
                              for s in range(n_slot_sessions)])
        slots_sps = n_slot_ticks * n_slot_sessions / (
            time.perf_counter() - t0)
    clean = (counts["slots_generate"] == n_slot_ticks
             and counts["decode_step"] == 0
             and counts["slots_insert"] == 0
             and counts["decode_replay"] == 0)
    row("serve/slots_resident", 1e6 / max(slots_sps, 1e-9),
        f"steps_per_s={slots_sps:.0f};sessions={n_slot_sessions};"
        f"generate_dispatches={counts['slots_generate']};"
        f"per_session_dispatches={counts['decode_step']}"
        f"{';steady_state_clean' if clean else ';DISPATCH LEAK'}")
    if smoke:
        assert clean, (
            f"slots steady state leaked per-session dispatches: "
            f"{dict(counts)} over {n_slot_ticks} flushes")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workload + the slots dispatch assert")
    ap.add_argument("--requests", type=int, default=512)
    args = ap.parse_args()
    main(n_requests=args.requests, smoke=args.smoke)
