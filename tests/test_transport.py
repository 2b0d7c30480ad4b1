"""Multi-process mesh transport (ISSUE 4 acceptance): the serving mesh
over >= 2 OS processes behind the socket transport — cross-process
serving correctness, weight pushes under the staleness skew bound, and
live shard join/leave mid-traffic with zero dropped requests, session
affinity for unmoved clients, and carry migration for moved ones.

Worker processes are spawned (not forked): each initializes its own jax
backend and compiles its own programs, so this module costs a few
seconds of process startup — kept bounded by a tiny model config.
"""

import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.models.rnn import RNNConfig, init_rnn
from repro.serving import (BatcherConfig, LSTMForecaster, ModelRegistry,
                           MultiProcessServingEngine, WeightPublisher)

CFG = RNNConfig(input_dim=3, hidden=8, num_layers=1, fc_dims=(4,),
                window=8, evl_head=True)
BCFG = BatcherConfig(max_batch=4, max_wait_ms=2.0, length_buckets=(8,))


@pytest.fixture(scope="module")
def forecaster():
    fc = LSTMForecaster(cfg=CFG, params=init_rnn(jax.random.PRNGKey(0),
                                                 CFG))
    rng = np.random.default_rng(0)
    fc.calibrate(rng.standard_normal((64, CFG.window, 3)).astype(np.float32)
                 * 0.02)
    return fc


def _windows(n, t=CFG.window, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, t, 3)).astype(np.float32) * 0.02


def _mesh(forecaster, n_shards=2, **kw):
    reg = ModelRegistry()
    reg.register("m", forecaster)
    return MultiProcessServingEngine(reg, BCFG, n_shards=n_shards, **kw)


def test_transport_serves_across_os_processes(forecaster):
    """Two shard worker PROCESSES serve the same numbers the forecaster
    computes locally; per-shard telemetry and per-client attribution
    cross the process boundary."""
    wins = _windows(16, seed=1)
    with _mesh(forecaster) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        stats = mesh.shard_stats()
        pids = {st["pid"] for st in stats.values()}
        assert len(pids) == 2 and os.getpid() not in pids
        futs = [mesh.submit("m", w, client_id=f"c{i % 5}")
                for i, w in enumerate(wins)]
        got = [f.result(timeout=60.0) for f in futs]
        y_ref, p_ref = forecaster.predict(wins)
        np.testing.assert_allclose([y for y, _ in got], y_ref,
                                   atol=1e-7, rtol=1e-6)
        np.testing.assert_allclose([p for _, p in got], p_ref,
                                   atol=1e-7, rtol=1e-6)
        snap = mesh.snapshot()
        assert snap["requests"] == 16
        assert len(snap["requests_by_shard"]) == 2
        assert all(n > 0 for n in snap["requests_by_shard"])
        assert snap["unique_clients"] == 5
        assert sum(snap["requests_by_client"].values()) == 16

        # streaming sessions live in the OWNING worker's shard-local
        # cache, numerically identical to a local replay
        w = wins[0]
        for t in range(CFG.window):
            y, p = mesh.step("m", "stream-client", w[t])
        y_r, p_r, _ = forecaster.replay(w[None])
        assert (y, p) == (float(y_r[0]), float(p_r[0]))
        sid = mesh.shard_for("stream-client")
        assert "stream-client" in mesh.shard_stats()[sid]["clients"]

        # stopping with submits in flight: the workers drain before
        # acking the goodbye, so every future resolves (zero drops on
        # shutdown — parity with the thread mesh)
        parting = [mesh.submit("m", w, client_id=f"c{i % 5}")
                   for i, w in enumerate(_windows(8, seed=4))]
    assert all(np.isfinite(f.result(timeout=60.0)[0]) for f in parting)


def test_transport_publish_pushes_within_skew_bound(forecaster):
    """Publishes against the primary registry ship serialized
    checkpoints to the workers; every version vector respects max_skew,
    and max_skew=0 is lockstep."""
    with _mesh(forecaster, max_skew=0) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        pub = WeightPublisher(mesh.registry, "m", template=forecaster)
        for i in range(4):
            pub.publish(jax.tree.map(lambda a, s=1.0 + 0.01 * i: a * s,
                                     forecaster.params))
            vec = mesh.version_vector("m")
            shard_vs = [v for k, v in vec.items() if k != "primary"]
            assert set(shard_vs) == {vec["primary"]}, vec
        assert mesh.pulls >= 2 * 4
        assert mesh.bytes_pulled > 0
        # served requests are attributed to the pushed version
        y, p = mesh.predict("m", _windows(1)[0], client_id="c0",
                            timeout=60.0)
        snap = mesh.snapshot()
        assert max(snap["requests_by_version"]) == vec["primary"]


def test_transport_join_leave_mid_traffic(forecaster):
    """THE acceptance scenario: a shard joins and a shard leaves while
    traffic, a publish storm and streaming sessions are all in flight —
    zero dropped requests, the staleness bound holds in every sampled
    version vector, unmoved clients keep their session affinity, and
    moved clients' carries migrate across processes."""
    max_skew = 1
    clients = [f"c{i}" for i in range(16)]
    sess_clients = [f"s{i}" for i in range(6)]
    wins = _windows(32, seed=2)
    sess_wins = _windows(len(sess_clients), seed=3)
    half = CFG.window // 2

    with _mesh(forecaster, n_shards=2, max_skew=max_skew) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        owners_before = {c: mesh.shard_for(c) for c in clients}
        sess_owners = {c: mesh.shard_for(c) for c in sess_clients}

        # stream the first half of every session before any churn
        for i, c in enumerate(sess_clients):
            for t in range(half):
                mesh.step("m", c, sess_wins[i][t])

        stop = threading.Event()
        futures, flock = [], threading.Lock()
        errors = []

        def traffic():
            i = 0
            while not stop.is_set():
                try:
                    f = mesh.submit("m", wins[i % len(wins)],
                                    client_id=clients[i % len(clients)])
                    with flock:
                        futures.append(f)
                except Exception as e:  # noqa: BLE001 — a drop IS the failure
                    errors.append(e)
                i += 1
                time.sleep(0.002)

        # publish through the mesh FACADE: primary publish + worker
        # pushes are then atomic under the lock version_vector samples
        pub = WeightPublisher(mesh, "m", template=forecaster)
        def storm():
            i = 0
            while not stop.is_set():
                pub.publish(jax.tree.map(
                    lambda a, s=1.0 + 0.01 * (i % 3): a * s,
                    forecaster.params))
                i += 1
                time.sleep(0.01)

        skew_violations = []
        def sampler():
            while not stop.is_set():
                stale = mesh.staleness("m")
                if stale > max_skew:
                    skew_violations.append(stale)
                time.sleep(0.002)

        threads = [threading.Thread(target=fn, name=f"storm-{fn.__name__}")
                   for fn in (traffic, storm, sampler)]
        for t in threads:
            t.start()
        try:
            time.sleep(0.3)
            joined = mesh.add_shard()          # join mid-traffic
            time.sleep(0.3)
            mesh.remove_shard(0)               # leave mid-traffic
            time.sleep(0.3)
        finally:
            stop.set()
            for t in threads:
                t.join()

        assert not errors, errors[:3]
        with flock:
            pending = list(futures)
        results = [f.result(timeout=60.0) for f in pending]  # zero drops
        assert len(results) >= 30
        assert all(np.isfinite(y) and 0.0 <= p <= 1.0 for y, p in results)
        assert not skew_violations, skew_violations[:5]

        # membership: exactly one joined, one left
        assert joined == 2 and mesh.shard_ids == [1, 2]

        # affinity: clients that neither lived on the departed shard nor
        # were won by the new one kept their shard assignment
        moved = 0
        for c in clients:
            now = mesh.shard_for(c)
            if owners_before[c] not in (0,) and now != joined:
                assert now == owners_before[c]
            else:
                moved += 1
        assert 0 < moved < len(clients)

        # pin the fleet back to the ORIGINAL weights (the storm cycled
        # scaled variants) so the session streams below have a
        # deterministic local reference, and converge every worker
        pub.publish(forecaster.params)
        mesh.propagate("m")
        vec = mesh.version_vector("m")
        assert set(v for k, v in vec.items() if k != "primary") \
            == {vec["primary"]}

        # sessions: finish every stream; carries survived the churn (on
        # unmoved shards untouched, on moved shards migrated across the
        # process boundary), so each stream ends exactly where an
        # uninterrupted local replay does — the carries were built under
        # the original weights, and the step path carries them across
        # the swap storm's version bumps
        for i, c in enumerate(sess_clients):
            for t in range(half, CFG.window):
                y, p = mesh.step("m", c, sess_wins[i][t])
            y_r, p_r, _ = forecaster.replay(sess_wins[i][None])
            assert (y, p) == (float(y_r[0]), float(p_r[0])), c
        # session affinity: a client owned by neither the departed nor
        # the joined shard is resident exactly where it always was
        stats = mesh.shard_stats()
        unmoved_sessions = [c for c in sess_clients
                            if sess_owners[c] not in (0, joined)]
        for c in unmoved_sessions:
            assert mesh.shard_for(c) == sess_owners[c]
            assert c in stats[sess_owners[c]]["clients"]


def test_transport_rejects_bad_ops(forecaster):
    with _mesh(forecaster) as mesh:
        with pytest.raises(RuntimeError, match="KeyError"):
            mesh.predict("nope", _windows(1)[0], timeout=60.0)
        with pytest.raises(KeyError):
            mesh.remove_shard(99)
        with pytest.raises(ValueError):
            mesh.add_shard(0)                  # already exists
        mesh.remove_shard(0)
        with pytest.raises(ValueError):
            mesh.remove_shard(1)               # never below one shard


# -- PR 7: crash supervision, remote join, hot-path bug sweep --------------

def test_request_fails_fast_when_worker_dies(forecaster):
    """ISSUE 7 satellite: a request issued against a dead worker must
    fail with ConnectionError within the heartbeat budget, NOT hang for
    the full 60 s RPC timeout (the reader loop flags EOF; `_request`
    refuses to register futures nobody will resolve)."""
    import signal

    with _mesh(forecaster, n_shards=2, supervise=False) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        worker = mesh.workers[0]
        os.kill(worker.process.pid, signal.SIGKILL)
        t0 = time.monotonic()
        with pytest.raises(ConnectionError):
            # the send may land in the OS buffer (future fails via
            # reader EOF) or be refused outright — both must be fast
            worker.submit("m", _windows(1)[0]).result(timeout=30.0)
        assert time.monotonic() - t0 < 5.0
        # and once the death is observed, requests fail IMMEDIATELY
        worker.process.join(5.0)
        t0 = time.monotonic()
        for _ in range(3):
            with pytest.raises(ConnectionError):
                worker.submit("m", _windows(1)[0]).result(timeout=30.0)
        assert time.monotonic() - t0 < 1.0


def test_warmup_on_empty_fleet_raises_clear_error(forecaster):
    """ISSUE 7 satellite: warmup before start() (or after the whole
    fleet crashed) used to die with a bare `ValueError: max() arg is an
    empty sequence`."""
    mesh = _mesh(forecaster)                   # never started
    with pytest.raises(RuntimeError, match="no live shards"):
        mesh.warmup("m", lengths=(CFG.window,))


def test_submit_normalizes_wire_dtype(forecaster):
    """ISSUE 7 satellite: submit frames used to ship the caller's dtype
    (float64 by default — 2x the wire bytes); now they normalize to the
    serving dtype at pack time, with results bitwise-equal to the
    in-process engine fed the same float64 window."""
    from repro.serving import ServingEngine

    win64 = _windows(4, seed=7).astype(np.float64)
    with _mesh(forecaster, n_shards=1) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        worker = mesh.workers[0]
        frames = []
        orig_send = worker._conn.send

        def spy(msg):
            frames.append(msg)
            orig_send(msg)

        worker._conn.send = spy
        try:
            got = [mesh.predict("m", w, timeout=60.0) for w in win64]
        finally:
            worker._conn.send = orig_send
        submits = [f for f in frames if f.get("op") == "submit"]
        assert len(submits) == len(win64)
        assert all(f["window"]["dtype"] == "<f4" for f in submits)

    reg = ModelRegistry()
    reg.register("m", forecaster)
    with ServingEngine(reg, BCFG) as local:
        local.warmup("m", lengths=(CFG.window,))
        ref = [local.predict("m", w, timeout=60.0) for w in win64]
    assert got == ref                          # bitwise, not allclose


def test_stats_race_free_under_live_traffic(forecaster):
    """ISSUE 7 satellite: the worker's stats op used to read telemetry
    reservoir buffers unlocked while the flush thread appends — hammer
    stats against live traffic (a race manifests as corrupt frames or
    worker errors, failing the RPC)."""
    wins = _windows(8, seed=9)
    with _mesh(forecaster, n_shards=1) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        stop = threading.Event()
        errors = []

        def traffic():
            i = 0
            while not stop.is_set():
                try:
                    mesh.predict("m", wins[i % len(wins)], timeout=60.0)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                i += 1

        t = threading.Thread(target=traffic)
        t.start()
        try:
            for _ in range(100):
                st = mesh.shard_stats()[0]
                assert all(isinstance(v, float)
                           for v in st["latency_s"])
                assert all(isinstance(v, float)
                           for v in st["staleness_s"])
        finally:
            stop.set()
            t.join()
        assert not errors, errors[:3]


def test_telemetry_raw_samples_locked():
    """Unit half of the stats race fix: raw_samples() snapshots under
    the telemetry lock while writers append concurrently."""
    from repro.serving.telemetry import Telemetry

    tel = Telemetry()
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            tel.record_requests([1e-3, 2e-3], version=1, staleness_s=0.1)
            i += 1

    threads = [threading.Thread(target=writer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(500):
            raw = tel.raw_samples()
            assert set(raw) == {"latency_s", "staleness_s",
                                "batch_sizes", "step_latency_s"}
            for vals in raw.values():
                assert all(isinstance(v, (int, float)) for v in vals)
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_remote_worker_joins_by_address(forecaster):
    """ISSUE 7 tentpole (a): a shard worker started standalone
    (serve_shard — what `python -m repro.launch.shard_worker` runs)
    joins the mesh by address via the hello handshake, receives the
    hosted weights, and serves traffic like any spawned shard."""
    from repro.serving import serve_shard

    bound = {}
    ready = threading.Event()

    def on_bound(port):
        bound["port"] = port
        ready.set()

    srv = threading.Thread(target=serve_shard,
                           args=("127.0.0.1", 0),
                           kwargs={"on_bound": on_bound}, daemon=True)
    srv.start()
    assert ready.wait(10.0)

    wins = _windows(12, seed=11)
    with _mesh(forecaster, n_shards=1) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        sid = mesh.connect_shard(f"127.0.0.1:{bound['port']}")
        assert sid == 1 and mesh.shard_ids == [0, 1]
        assert mesh.workers[sid].addr == f"127.0.0.1:{bound['port']}"
        # the joiner acked every hosted model before taking traffic
        vec = mesh.version_vector("m")
        assert vec[sid] == vec["primary"]
        futs = [mesh.submit("m", w, client_id=f"rc{i}")
                for i, w in enumerate(wins)]
        got = [f.result(timeout=60.0) for f in futs]
        y_ref, p_ref = forecaster.predict(wins)
        np.testing.assert_allclose([y for y, _ in got], y_ref,
                                   atol=1e-7, rtol=1e-6)
        # both shards took some of it
        snap = mesh.snapshot()
        assert len(snap["requests_by_shard"]) == 2
        assert all(n > 0 for n in snap["requests_by_shard"])
    srv.join(10.0)
    assert not srv.is_alive()


def test_socket_steps_fuse_into_batched_decode(forecaster):
    """ISSUE 7 acceptance + tentpole (c): N concurrent cross-process
    streaming steps ride EngineShard.submit_step on the worker — the
    dispatch count shows fused decode_many flushes, NOT N independent
    dispatches (the old recv loop ran runner.step inline, one dispatch
    per frame)."""
    n = 8
    cfg = BatcherConfig(max_batch=8, max_wait_ms=25.0, length_buckets=(8,))
    reg = ModelRegistry()
    reg.register("m", forecaster)
    with MultiProcessServingEngine(reg, cfg, n_shards=1) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        worker = mesh.workers[0]
        xs = _windows(1, seed=13)[0]           # [T, F]: one step per row
        before = worker.stats()["telemetry"]
        worker.count_start()
        futs = [mesh.submit_step("m", f"fuse-{i}", xs[i % CFG.window])
                for i in range(n)]
        got = [f.result(timeout=60.0) for f in futs]
        counts = worker.count_stop()
        after = worker.stats()["telemetry"]
        assert all(np.isfinite(y) for y, _ in got)
        step_requests = after["step_requests"] - before["step_requests"]
        step_batches = after["step_batches"] - before["step_batches"]
        assert step_requests == n
        # fused: strictly fewer flushes than steps, and exactly one
        # slots_generate dispatch per flush (fresh clients additionally
        # insert into their device lanes — once each; the host
        # gather/scatter path stays cold)
        assert 0 < step_batches < n
        assert counts["slots_generate"] == step_batches
        assert counts["slots_insert"] == n     # one lane entry per client
        assert counts["decode_many"] == 0      # no host gather/scatter
        assert counts["decode_step"] == 0      # nothing went per-session


def test_process_mesh_refuses_on_tpu(monkeypatch):
    """A parent on the TPU holds its chips: spawned shard workers could
    not open them, so the process mesh refuses before spawning."""
    from repro.serving import transport

    spawned = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(transport.mp, "get_context",
                        lambda *a: spawned.append(a))
    with pytest.raises(RuntimeError, match="ShardedServingEngine"):
        transport.spawn_shard(0, BCFG)
    with pytest.raises(RuntimeError, match="one process per chip"):
        MultiProcessServingEngine(ModelRegistry(), BCFG, n_shards=2)
    assert spawned == []
