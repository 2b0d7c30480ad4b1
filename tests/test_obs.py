"""Observability plane (ISSUE 6): per-request trace spans through the
serving stack (in-process AND stitched across the transport's process
boundary), dispatch accounting ("one fused dispatch per flush" asserted,
not trusted), telemetry time-series/history, and the metrics export
surface (Prometheus text, JSONL events, the stdlib HTTP endpoint)."""

import json
import time
import urllib.request

import jax
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.models.rnn import RNNConfig, init_rnn
from repro.obs import EventLog, MetricsServer, Tracer, now, render_prometheus
from repro.serving import (BatcherConfig, LSTMForecaster, ModelRegistry,
                           MultiProcessServingEngine, ServingEngine,
                           ShardedServingEngine, Telemetry)

CFG = RNNConfig(input_dim=3, hidden=8, num_layers=1, fc_dims=(4,),
                window=8, evl_head=True)
BCFG = BatcherConfig(max_batch=4, max_wait_ms=2.0, length_buckets=(8,))

# residual clock skew allowed between the two processes of a stitched
# trace (same machine, epoch-anchored perf_counter on both sides) plus
# the worker's result-serialization time
EPS_CROSS_PROCESS_S = 0.05


@pytest.fixture(scope="module")
def forecaster():
    fc = LSTMForecaster(cfg=CFG, params=init_rnn(jax.random.PRNGKey(0),
                                                 CFG))
    rng = np.random.default_rng(0)
    fc.calibrate(rng.standard_normal((64, CFG.window, 3)).astype(np.float32)
                 * 0.02)
    return fc


@pytest.fixture()
def registry(forecaster):
    reg = ModelRegistry()
    reg.register("m", forecaster)
    return reg


def _windows(n, t=CFG.window, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, t, 3)).astype(np.float32) * 0.02


def _wait(pred, timeout=5.0):
    deadline = time.perf_counter() + timeout
    while not pred():
        if time.perf_counter() > deadline:
            return False
        time.sleep(0.005)
    return True


# -- tracer unit behavior ---------------------------------------------------

def test_tracer_marks_chain_gapless():
    """Each mark records [t_last, now] and advances t_last, so chained
    spans cover the trace exactly (zero gaps, no epsilon needed)."""
    tracer = Tracer()
    ctx = tracer.start("op")
    for name in ("a", "b", "c"):
        ctx.mark(name)
    trace = ctx.finish()
    assert trace.status == "ok"
    assert trace.names() == ["a", "b", "c"]
    assert trace.gaps(0.0) == []
    spans = sorted(trace.spans, key=lambda s: s.t0)
    for prev, cur in zip(spans, spans[1:]):
        assert cur.t0 == prev.t1


def test_tracer_gaps_detects_uncovered_interval():
    tracer = Tracer()
    ctx = tracer.start("op")
    t = now()
    ctx.span("a", t, t + 1.0)
    ctx.span("b", t + 2.0, t + 3.0)        # hole in (t+1, t+2)
    trace = ctx.finish()
    gaps = trace.gaps(0.0)
    assert len(gaps) == 1
    assert gaps[0] == pytest.approx((t + 1.0, t + 2.0))


def test_tracer_disabled_returns_none_contexts():
    tracer = Tracer(enabled=False)
    assert tracer.start("op") is None
    assert tracer.adopt("some-id") is None
    assert tracer.stats()["started"] == 0


def test_tracer_completed_ring_is_bounded():
    tracer = Tracer(capacity=4)
    for i in range(10):
        tracer.start("op", meta={"i": i}).finish()
    done = tracer.traces()
    assert len(done) == 4
    assert [t.meta["i"] for t in done] == [6, 7, 8, 9]
    assert tracer.stats()["finished"] == 10


def test_tracer_abandoned_traces_do_not_leak():
    """The tracer keeps no registry of live traces — a trace lives only
    on its context, so an abandoned request's trace is simply garbage
    collected (nothing to evict, nothing to leak)."""
    import gc
    import weakref

    tracer = Tracer()
    ctxs = [tracer.start("op") for _ in range(12)]   # never finished
    refs = [weakref.ref(ctx.trace) for ctx in ctxs]
    del ctxs
    gc.collect()
    assert all(r() is None for r in refs)
    assert tracer.traces() == []                     # nothing reached the ring
    assert tracer.stats()["started"] == 12
    assert tracer.stats()["finished"] == 0


def test_tracer_export_makes_later_spans_noops():
    """The transport worker exports mid-flush (inside the set_result
    done-callback); the engine's post-set_result reply/finish must then
    be silently ignored."""
    tracer = Tracer()
    ctx = tracer.start("op")
    ctx.mark("work")
    spans = tracer.export(ctx)
    assert [s["name"] for s in spans] == ["work"]
    assert ctx.mark("reply") is None
    assert ctx.finish() is None
    assert tracer.traces() == []


def test_tracer_adopt_stitches_with_offset_sids():
    """adopt + add_spans reassemble one trace from two processes' spans
    with non-colliding span ids."""
    router, worker = Tracer(), Tracer()
    ctx = router.start("predict")
    ctx.mark("route")
    ctx.mark("submit")
    wctx = worker.adopt(ctx.trace_id, op="predict", t0=ctx.t_last,
                        parent=ctx.last_sid)
    wctx.mark("transport")
    wctx.mark("dispatch")
    shipped = worker.export(wctx)
    router.add_spans(ctx, shipped)
    ctx.t_last = shipped[-1]["t1"]
    ctx.mark("reply")
    trace = ctx.finish()
    assert trace.names() == ["route", "submit", "transport", "dispatch",
                             "reply"]
    assert trace.gaps(0.0) == []
    sids = [s.sid for s in trace.spans]
    assert len(sids) == len(set(sids))       # sid_base offset: no clash


# -- dispatch accounting ----------------------------------------------------

def test_dispatch_counting_inactive_is_default():
    dispatch.record("predict", batch=4, hidden=8)    # no collector: no-op
    with dispatch.counting() as counts:
        dispatch.record("predict", batch=4, hidden=8)
        dispatch.record("predict", batch=4, hidden=8)
        dispatch.record("replay", batch=8, hidden=8, impl="xla")
    assert counts["predict"] == 2
    assert counts["replay"] == 1
    assert counts.total() == 3
    # keys carry (backend, op, impl, shape)
    (key, n), = [(k, v) for k, v in counts.counts.items()
                 if k[1] == "replay"]
    backend, op, impl, shape = key
    assert backend == jax.default_backend()
    assert impl == "xla" and shape == (8, 8)
    # collector uninstalled on exit
    dispatch.record("predict", batch=4, hidden=8)
    assert counts["predict"] == 2


def test_forecaster_dispatch_counts(forecaster):
    """The performance claims of PRs 4-8, asserted: a generate over a
    full lane set is ONE fused dispatch, a replay is ONE scan dispatch,
    a predict is ONE fused dispatch."""
    slots = forecaster.init_slots(forecaster.decode_width)
    xs = np.zeros((slots.num_slots, CFG.input_dim), np.float32)
    lanes = list(range(slots.num_slots))
    forecaster.generate(slots, xs, lanes=lanes)          # warm
    with dispatch.counting() as counts:
        forecaster.generate(slots, xs, lanes=lanes)
    assert counts["slots_generate"] == 1                 # every lane at once
    assert counts.total() == 1                           # and nothing else

    window = np.zeros((1, CFG.window, CFG.input_dim), np.float32)
    forecaster.replay(window)                            # warm
    with dispatch.counting() as counts:
        forecaster.replay(window)
    assert counts["decode_replay"] == 1                  # one scan
    assert counts.total() == 1

    with dispatch.counting() as counts:
        forecaster.predict(_windows(4, seed=3))
    assert counts["predict"] == 1


def test_engine_step_flush_is_one_fused_dispatch(registry):
    """Tier-1 guard on the slots decode path (ISSUE 8): once sessions
    are lane-resident, a step flush is exactly ONE fused
    ``slots_generate`` dispatch — zero per-session steps, zero
    inserts."""
    clients = [f"client-{i}" for i in range(BCFG.max_batch)]
    x = np.zeros(CFG.input_dim, np.float32)
    with ServingEngine(registry, BCFG) as eng:
        eng.warmup("m", lengths=(CFG.window,))
        # round 1: sessions enter lanes (one slots_insert each)
        for f in [eng.submit_step("m", c, x) for c in clients]:
            f.result(timeout=10.0)
        flushes_before = eng.telemetry.step_batches
        # round 2 = steady state: everything is already resident
        with dispatch.counting() as counts:
            futs = [eng.submit_step("m", c, x) for c in clients]
            for f in futs:
                f.result(timeout=10.0)
        flushes = eng.telemetry.step_batches - flushes_before
    assert flushes >= 1
    assert counts["slots_generate"] == flushes
    assert counts["decode_step"] == 0       # nothing went per-session
    assert counts["slots_insert"] == 0      # no lane churn at steady state
    assert counts["decode_replay"] == 0     # no cache miss hit replay
    assert counts.total() == flushes        # and nothing else at all


# -- traces through the serving stack --------------------------------------

def test_engine_trace_covers_submit_to_reply(registry):
    tracer = Tracer()
    with ServingEngine(registry, BCFG, tracer=tracer) as eng:
        eng.warmup("m", lengths=(CFG.window,))
        fut = eng.submit("m", _windows(1, seed=2)[0], client_id="alice")
        fut.result(timeout=10.0)
    assert _wait(lambda: len(tracer.traces()) == 1)
    trace = tracer.traces()[0]
    assert trace.status == "ok"
    assert trace.names() == ["submit", "queue", "gather", "flush",
                             "dispatch", "scatter", "reply"]
    # chained spans: gapless with NO epsilon (single process)
    assert trace.gaps(0.0) == []
    flush = trace.span("flush")
    for inner in ("gather", "dispatch", "scatter"):
        s = trace.span(inner)
        assert flush.t0 <= s.t0 and s.t1 <= flush.t1
    assert trace.duration > 0


def test_engine_step_traces(registry):
    tracer = Tracer()
    with ServingEngine(registry, BCFG, tracer=tracer) as eng:
        eng.warmup("m", lengths=(CFG.window,))
        futs = [eng.submit_step("m", f"c{i}",
                                np.zeros(CFG.input_dim, np.float32))
                for i in range(3)]
        for f in futs:
            f.result(timeout=10.0)
    assert _wait(lambda: len(tracer.traces()) == 3)
    for trace in tracer.traces():
        assert trace.op == "step"
        assert trace.names() == ["submit", "queue", "dispatch", "flush",
                                 "scatter", "reply"]
        assert trace.gaps(0.0) == []


def test_engine_trace_error_status(registry):
    """A synchronously rejected submit finishes the trace with status
    'error' instead of dangling open."""
    tracer = Tracer()
    with ServingEngine(registry, BCFG, tracer=tracer) as eng:
        with pytest.raises(KeyError):
            eng.submit("no-such-model", _windows(1)[0])
        with pytest.raises(ValueError):
            eng.submit_step("m", "alice", np.zeros(99, np.float32))
    assert len(tracer.traces()) == 2
    assert [t.status for t in tracer.traces()] == ["error", "error"]
    assert tracer.stats()["finished"] == tracer.stats()["started"] == 2


def test_engine_tracing_disabled_records_nothing(registry):
    tracer = Tracer(enabled=False)
    with ServingEngine(registry, BCFG, tracer=tracer) as eng:
        eng.warmup("m", lengths=(CFG.window,))
        eng.submit("m", _windows(1)[0]).result(timeout=10.0)
    assert tracer.traces() == []
    assert tracer.stats()["started"] == 0


def test_mesh_trace_has_route_span(registry):
    tracer = Tracer()
    with ShardedServingEngine(registry, BCFG, n_shards=2,
                              tracer=tracer) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        futs = [mesh.submit("m", w, client_id=f"c{i}")
                for i, w in enumerate(_windows(6, seed=4))]
        for f in futs:
            f.result(timeout=10.0)
    assert _wait(lambda: len(tracer.traces()) == 6)
    for trace in tracer.traces():
        assert trace.names()[0] == "route"
        assert trace.names()[-1] == "reply"
        assert trace.gaps(0.0) == []
        assert trace.span("route").meta["shard"] in (0, 1)


def test_cross_process_stitched_trace(forecaster):
    """ISSUE 6 acceptance: a request through the multi-process mesh
    yields ONE trace whose spans cover submit -> reply across the
    process boundary, with no gaps beyond the clock-skew epsilon."""
    reg = ModelRegistry()
    reg.register("m", forecaster)
    tracer = Tracer()
    with MultiProcessServingEngine(reg, BCFG, n_shards=1,
                                   tracer=tracer) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        fut = mesh.submit("m", _windows(1, seed=5)[0], client_id="alice")
        fut.result(timeout=30.0)
        # the synchronous step path stitches too
        y, p = mesh.step("m", "alice", np.zeros(CFG.input_dim, np.float32),
                         history=np.zeros((2, CFG.input_dim), np.float32))
    traces = {t.op: t for t in tracer.traces()}
    assert set(traces) == {"predict", "step"}

    trace = traces["predict"]
    assert trace.status == "ok"
    names = trace.names()
    # router half ... worker half ... final reply, one stitched trace
    assert names[:3] == ["route", "submit", "transport"]
    assert names[-1] == "reply"
    for worker_span in ("queue", "gather", "dispatch", "scatter"):
        assert worker_span in names
    assert trace.gaps(EPS_CROSS_PROCESS_S) == []
    sids = [s.sid for s in trace.spans]
    assert len(sids) == len(set(sids))       # router/worker sids disjoint
    # covers submit -> reply: the reply span is the last thing recorded
    reply = trace.span("reply")
    assert reply.t1 == trace.t_end

    strace = traces["step"]
    assert "transport" in strace.names() and "dispatch" in strace.names()
    assert strace.gaps(EPS_CROSS_PROCESS_S) == []


# -- telemetry: batch reservoir + history ring ------------------------------

def test_snapshot_exposes_batch_percentiles():
    """Regression for the dead ``_batch_sizes`` reservoir: recorded
    batch sizes must surface as batch_p50/batch_p95."""
    tel = Telemetry()
    for n in (1, 2, 2, 3, 8):
        tel.record_batch(n, 8)
    snap = tel.snapshot()
    assert snap["batch_p50"] == 2.0
    assert snap["batch_p95"] == 8.0
    # merge pools the reservoirs across shards
    other = Telemetry()
    other.record_batch(4, 8)
    merged = Telemetry.merge([tel, other])
    assert merged["batch_p50"] in (2.0, 3.0)
    assert merged["batch_p95"] == 8.0


def test_percentiles_single_sort_matches_per_call():
    from repro.serving.telemetry import _percentile, _percentiles

    rng = np.random.default_rng(1)
    data = list(rng.standard_normal(257))
    ps = (50, 95, 99)
    assert _percentiles(data, ps) == [_percentile(data, p) for p in ps]
    assert _percentiles([], ps) == [0.0, 0.0, 0.0]


def test_history_ring_and_sampler():
    tel = Telemetry()
    tel.record_request(0.01)
    snap = tel.sample()
    assert "ts" in snap
    assert tel.history() == [snap]
    tel.start_sampler(interval_s=0.02)
    tel.start_sampler(interval_s=0.02)       # idempotent
    assert _wait(lambda: len(tel.history()) >= 3)
    tel.stop_sampler()
    n = len(tel.history())
    time.sleep(0.06)
    assert len(tel.history()) == n           # stopped means stopped
    assert len(tel.history(2)) == 2
    # bounded ring
    for _ in range(Telemetry.HISTORY_CAPACITY + 10):
        tel.sample()
    assert len(tel.history()) == Telemetry.HISTORY_CAPACITY


# -- export surface ---------------------------------------------------------

def test_render_prometheus_scalars_and_labels():
    text = render_prometheus(
        {"requests": 10, "p95_ms": 1.5, "enabled": True,
         "requests_by_version": {1: 7, 2: 3},
         "requests_by_shard": [6, 4],
         "note": "skipped"},
        prefix="repro", labels={"shard": "fleet"})
    assert 'repro_requests{shard="fleet"} 10' in text
    assert 'repro_p95_ms{shard="fleet"} 1.5' in text
    assert 'repro_enabled{shard="fleet"} 1' in text
    assert 'repro_requests_by_version{shard="fleet",version="1"} 7' in text
    assert 'repro_requests_by_shard{shard="fleet",shard="0"} 6' not in text
    assert 'shard="0"' in text               # list indexed by label
    assert "# TYPE repro_requests gauge" in text
    assert "note" not in text                # non-numeric skipped
    assert text.endswith("\n")


def test_event_log_ring_and_jsonl(tmp_path):
    path = tmp_path / "events.jsonl"
    log = EventLog(capacity=4, path=str(path))
    for i in range(6):
        log.log("tick", i=i)
    assert len(log) == 4                     # ring bounded
    assert [e["i"] for e in log.events()] == [2, 3, 4, 5]
    log.close()
    lines = [json.loads(line) for line in
             path.read_text().strip().splitlines()]
    assert [e["i"] for e in lines] == list(range(6))   # file keeps all
    assert all(e["kind"] == "tick" and "ts" in e for e in lines)


def test_metrics_server_endpoints(registry):
    tracer = Tracer()
    events = EventLog()
    events.log("phase", name="test")
    with ServingEngine(registry, BCFG, tracer=tracer) as eng:
        eng.warmup("m", lengths=(CFG.window,))
        eng.submit("m", _windows(1)[0]).result(timeout=10.0)
        with MetricsServer(eng.telemetry.snapshot, port=0,
                           tracer=tracer, events=events,
                           history_fn=eng.telemetry.history) as srv:
            def get(route):
                with urllib.request.urlopen(f"{srv.url}{route}",
                                            timeout=5.0) as r:
                    return r.read().decode()

            text = get("/metrics")
            assert "repro_requests 1" in text
            snap = json.loads(get("/metrics.json"))
            assert snap["requests"] == 1
            eng.telemetry.sample()
            hist = json.loads(get("/history"))
            assert len(hist) == 1 and hist[0]["requests"] == 1
            assert _wait(lambda: len(tracer.traces()) == 1)
            traces = json.loads(get("/traces"))
            assert len(traces) == 1
            assert [s["name"] for s in traces[0]["spans"]][0] == "submit"
            ev = [json.loads(line) for line in
                  get("/events").strip().splitlines()]
            assert ev[0]["name"] == "test"
            with pytest.raises(urllib.error.HTTPError):
                get("/nope")
