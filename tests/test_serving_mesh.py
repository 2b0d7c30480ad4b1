"""Sharded serving mesh: routing correctness and affinity, fleet-wide
swap propagation under the staleness skew bound, sharded session cache
semantics, and cross-shard telemetry merge."""

import threading
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.models.rnn import RNNConfig, init_rnn
from repro.serving import (BatcherConfig, LSTMForecaster, ModelRegistry,
                           ServingEngine, ShardSwarm, ShardedServingEngine,
                           ShardedSessionCache, Telemetry, WeightPublisher)

CFG = RNNConfig(input_dim=5, hidden=16, num_layers=2, fc_dims=(8, 4),
                window=20, evl_head=True)


@pytest.fixture(scope="module")
def forecaster():
    fc = LSTMForecaster(cfg=CFG, params=init_rnn(jax.random.PRNGKey(0), CFG))
    rng = np.random.default_rng(0)
    fc.calibrate(rng.standard_normal((64, CFG.window, 5)).astype(np.float32)
                 * 0.02)
    return fc


def _windows(n, t=20, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, t, 5)).astype(np.float32) * 0.02


def _mesh(forecaster, n_shards=3, **kw):
    reg = ModelRegistry()
    reg.register("m", forecaster)
    return ShardedServingEngine(reg, BatcherConfig(
        max_batch=4, max_wait_ms=2.0, length_buckets=(CFG.window,)),
        n_shards=n_shards, **kw)


# -- mesh serving ----------------------------------------------------------

def test_mesh_matches_single_engine(forecaster):
    """The mesh must produce the single engine's numbers (same weights;
    different micro-batch tilings allow float32-ulp differences only)."""
    wins = _windows(12)
    reg = ModelRegistry()
    reg.register("m", forecaster)
    cfg = BatcherConfig(max_batch=4, max_wait_ms=2.0,
                        length_buckets=(CFG.window,))
    with ServingEngine(reg, cfg) as eng:
        ref = [eng.predict("m", w, timeout=30.0) for w in wins]
    with _mesh(forecaster) as mesh:
        futs = [mesh.submit("m", w, client_id=f"c{i}")
                for i, w in enumerate(wins)]
        got = [f.result(timeout=30.0) for f in futs]
    np.testing.assert_allclose([y for y, _ in got], [y for y, _ in ref],
                               atol=1e-7, rtol=1e-6)
    np.testing.assert_allclose([p for _, p in got], [p for _, p in ref],
                               atol=1e-7, rtol=1e-6)


def test_mesh_client_affinity(forecaster):
    """Every request of one client lands on the same shard."""
    with _mesh(forecaster) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        mesh.reset_clock()
        sid = mesh.shard_for("sticky-client")
        for w in _windows(6, seed=1):
            mesh.predict("m", w, client_id="sticky-client", timeout=30.0)
        counts = [tel.requests for tel in mesh.shard_telemetries]
    assert counts[sid] == 6
    assert sum(counts) == 6
    assert mesh.shard_for("sticky-client") == sid     # still stable


def test_mesh_anonymous_requests_spread(forecaster):
    """Anonymous submits round-robin their (model, bucket) group: an
    even burst splits exactly evenly across shards."""
    with _mesh(forecaster, n_shards=2) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        mesh.reset_clock()
        futs = [mesh.submit("m", w) for w in _windows(32, seed=2)]
        for f in futs:
            f.result(timeout=30.0)
        counts = [tel.requests for tel in mesh.shard_telemetries]
    assert counts == [16, 16]


def test_mesh_rejects_router_mutation_without_worker(forecaster):
    """Membership changes go through add_shard/remove_shard; mutating
    the router directly leaves a shard id with no worker behind it,
    which must fail loudly instead of mis-routing."""
    with _mesh(forecaster, n_shards=2) as mesh:
        mesh.router.add_shard(7)
        bad = next(cid for cid in (f"c{i}" for i in range(64))
                   if mesh.router.shard_for(cid) == 7)
        with pytest.raises(KeyError):
            mesh.submit("m", _windows(1)[0], client_id=bad)


# -- live membership -------------------------------------------------------

def test_mesh_add_shard_serves_new_clients(forecaster):
    """A joining shard pulls weights + warms BEFORE taking traffic, then
    serves exactly the clients the rendezvous hash moves to it."""
    with _mesh(forecaster, n_shards=2) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        before = {f"c{i}": mesh.shard_for(f"c{i}") for i in range(64)}
        sid = mesh.add_shard()
        assert sid == 2 and sorted(mesh.shards) == [0, 1, 2]
        # the new replica already hosts the model at the primary version
        vec = mesh.version_vector("m")
        assert vec[sid] == vec["primary"]
        # minimal disruption: clients either stay put or move to the
        # new shard
        moved = []
        for cid, old in before.items():
            now = mesh.shard_for(cid)
            assert now in (old, sid)
            if now == sid:
                moved.append(cid)
        assert moved                      # 64 clients: some must move
        mesh.reset_clock()
        for cid in moved[:4]:
            mesh.predict("m", _windows(1)[0], client_id=cid, timeout=30.0)
        assert mesh.shards[sid].telemetry.requests == len(moved[:4])


def test_mesh_remove_shard_drains_and_rehomes(forecaster):
    """Removing a shard mid-traffic: queued requests complete (zero
    drops), only the departing shard's clients are re-homed."""
    with _mesh(forecaster, n_shards=3) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        before = {f"c{i}": mesh.shard_for(f"c{i}") for i in range(48)}
        futs = [mesh.submit("m", w, client_id=f"c{i}")
                for i, w in enumerate(_windows(48, seed=3))]
        victim = 1
        mesh.remove_shard(victim)
        results = [f.result(timeout=30.0) for f in futs]   # none dropped
        assert len(results) == 48
        assert all(np.isfinite(y) and 0.0 <= p <= 1.0 for y, p in results)
        for cid, old in before.items():
            now = mesh.shard_for(cid)
            if old != victim:
                assert now == old         # survivors keep their clients
            else:
                assert now != victim
        # more traffic serves fine on the shrunken mesh
        assert mesh.predict("m", _windows(1)[0], client_id="c0",
                            timeout=30.0)
        mesh.remove_shard(mesh.shard_ids[0])      # down to one shard
        with pytest.raises(ValueError):
            mesh.remove_shard(mesh.shard_ids[0])  # never below one


def test_mesh_membership_migrates_session_carries(forecaster):
    """Session caches attached via ``session_cache()`` follow membership
    changes: a departing shard's clients keep their carries (migrated to
    the new owners), unmoved clients are untouched. The runner holds
    its sessions in decode lanes, so it spills them to the cache before
    the membership change."""
    from repro.serving import RecurrentSessionRunner

    with _mesh(forecaster, n_shards=3) as mesh:
        cache = mesh.session_cache(max_sessions=64)
        runner = RecurrentSessionRunner(forecaster, cache)
        w = _windows(8, seed=9)
        half = CFG.window // 2
        for c in range(8):
            for t in range(half):
                runner.step(f"s{c}", w[c][t])
        owners = {f"s{c}": cache.shard_for(f"s{c}") for c in range(8)}
        victim = owners["s0"]
        assert runner.spill_all() == 8
        mesh.remove_shard(victim)
        # every session survived the membership change, on its new owner
        for c in range(8):
            assert f"s{c}" in cache
            assert cache.shard_for(f"s{c}") == (
                owners[f"s{c}"] if owners[f"s{c}"] != victim
                else cache.shard_for(f"s{c}"))
        # streams continue bitwise-uninterrupted (carries moved, not
        # rebuilt): finish each stream and compare to a clean replay
        finals = {}
        for c in range(8):
            for t in range(half, CFG.window):
                finals[c] = runner.step(f"s{c}", w[c][t])
        for c in range(8):
            y_ref, p_ref, _ = forecaster.replay(w[c][None])
            assert finals[c] == (float(y_ref[0]), float(p_ref[0]))


def test_zoo_forecaster_with_params_shares_compiled_forward():
    """The zoo hot-swap constructor must not rebuild/re-jit the forward
    (a swarm pull would otherwise retrace per shard per publish)."""
    from repro.serving import build_zoo_forecaster

    fc = build_zoo_forecaster("qwen1.5-4b", calibrate_batch=0)
    clone = fc.with_params(fc.params)
    assert clone is not fc
    assert clone._fwd is fc._fwd and clone._model is fc._model
    assert clone.version == 0 and clone.published_at is None


def test_mesh_rejects_bad_submissions(forecaster):
    with _mesh(forecaster) as mesh:
        with pytest.raises(KeyError):
            mesh.submit("nope", _windows(1)[0])
        with pytest.raises(ValueError):
            mesh.submit("m", np.zeros((20,), np.float32), client_id="c")


# -- swap propagation ------------------------------------------------------

def _stub(tag):
    """Stampable stand-in forecaster (no params -> reference pulls)."""
    return SimpleNamespace(tag=tag)


def test_swarm_seeds_replicas_and_registers_through():
    primary = ModelRegistry()
    primary.register("a", _stub("a1"))
    swarm = ShardSwarm(3, primary=primary)
    for sid in range(3):
        assert swarm.registry_for(sid).get("a").tag == "a1"
    swarm.register("b", _stub("b1"))
    for sid in range(3):
        assert swarm.registry_for(sid).get("b").tag == "b1"


def test_swarm_bounded_staleness_and_version_skip():
    swarm = ShardSwarm(2, max_skew=2)
    swarm.register("m", _stub("v1"))
    assert swarm.version_vector("m") == {"primary": 1, 0: 1, 1: 1}
    # v2, v3: within the bound — replicas may (and do) skip them
    swarm.swap("m", _stub("v2"))
    swarm.swap("m", _stub("v3"))
    vec = swarm.version_vector("m")
    assert vec["primary"] == 3 and vec[0] == 1 and vec[1] == 1
    # v4 blows the bound for v1 replicas: they pull the LATEST (v4),
    # never serving v2/v3 — that's the amortization bounded skew buys
    swarm.swap("m", _stub("v4"))
    vec = swarm.version_vector("m")
    assert vec == {"primary": 4, 0: 4, 1: 4}
    assert swarm.staleness("m") == 0 and swarm.skew("m") == 0


def test_swarm_max_skew_zero_is_lockstep():
    swarm = ShardSwarm(3, max_skew=0)
    swarm.register("m", _stub("v1"))
    for i in range(2, 6):
        swarm.swap("m", _stub(f"v{i}"))
        vec = swarm.version_vector("m")
        assert set(vec.values()) == {i}, vec


def test_swarm_propagate_converges_and_counts_pulls():
    swarm = ShardSwarm(2, max_skew=5)
    swarm.register("m", _stub("v1"))
    for i in range(2, 5):
        swarm.swap("m", _stub(f"v{i}"))
    assert swarm.staleness("m") == 3          # bound not hit: replicas lag
    pulled = swarm.propagate("m")
    assert pulled == 2
    assert swarm.version_vector("m") == {"primary": 4, 0: 4, 1: 4}


def test_swarm_direct_primary_publish_propagates():
    """Publishes made against the primary registry itself (not the
    facade) reach the replicas via the subscription callback."""
    primary = ModelRegistry()
    swarm = ShardSwarm(2, primary=primary, max_skew=0)
    primary.register("m", _stub("v1"))
    assert swarm.version_vector("m") == {"primary": 1, 0: 1, 1: 1}
    primary.swap("m", _stub("v2"))
    assert swarm.version_vector("m") == {"primary": 2, 0: 2, 1: 2}


def test_swarm_device_transfer_preserves_predictions(forecaster):
    swarm = ShardSwarm(2, max_skew=0, transfer="device")
    swarm.register("m", forecaster)
    w = _windows(3, seed=5)
    y_ref, p_ref = forecaster.predict(w)
    for sid in range(2):
        replica_fc = swarm.registry_for(sid).get("m")
        assert replica_fc is not forecaster     # per-shard clone
        y, p = replica_fc.predict(w)
        np.testing.assert_allclose(y, y_ref, atol=1e-7, rtol=1e-6)
        np.testing.assert_allclose(p, p_ref, atol=1e-7, rtol=1e-6)
    assert swarm.bytes_pulled > 0


def test_swarm_skew_bound_holds_under_concurrent_publishes():
    """A publish storm on one thread, an observer on another: every
    atomically-sampled version vector respects max_skew."""
    swarm = ShardSwarm(3, max_skew=1)
    swarm.register("m", _stub("v1"))
    stop = threading.Event()
    violations = []

    def observer() -> None:
        while not stop.is_set():
            vec = swarm.version_vector("m")
            lag = vec["primary"] - min(v for k, v in vec.items()
                                       if k != "primary")
            if lag > 1:
                violations.append(vec)

    t = threading.Thread(target=observer)
    t.start()
    try:
        for i in range(2, 60):
            swarm.swap("m", _stub(f"v{i}"))
    finally:
        stop.set()
        t.join()
    assert not violations, violations[:3]


def test_weight_publisher_into_swarm(forecaster):
    """The PR-2 publisher works unchanged against the swarm facade."""
    swarm = ShardSwarm(2, max_skew=0)
    pub = WeightPublisher(swarm, "m", template=forecaster)
    v1 = pub.publish(forecaster.params)
    v2 = pub.publish(jax.tree.map(lambda a: a * 1.01, forecaster.params))
    assert (v1, v2) == (1, 2)
    assert swarm.version_vector("m") == {"primary": 2, 0: 2, 1: 2}
    # each replica serves the published weights
    y0, _ = swarm.registry_for(0).get("m").predict(_windows(2, seed=6))
    y1, _ = swarm.registry_for(1).get("m").predict(_windows(2, seed=6))
    np.testing.assert_array_equal(y0, y1)


def test_mesh_swap_storm_zero_drops_full_attribution(forecaster):
    """Traffic over the mesh while a publisher storms weight versions:
    nothing dropped, every request attributed to some version, skew
    bound held throughout."""
    with _mesh(forecaster, n_shards=2, max_skew=1) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        mesh.reset_clock()
        pub = WeightPublisher(mesh.swarm, "m", template=forecaster)
        stop = threading.Event()

        def storm() -> None:
            i = 0
            while not stop.is_set():
                pub.publish(jax.tree.map(
                    lambda a, s=1.0 + 0.01 * (i % 3): a * s,
                    forecaster.params))
                i += 1
                time.sleep(0.002)

        t = threading.Thread(target=storm)
        t.start()
        try:
            wins = _windows(48, seed=7)
            futs = [mesh.submit("m", w, client_id=f"c{i % 9}")
                    for i, w in enumerate(wins)]
            results = [f.result(timeout=30.0) for f in futs]
        finally:
            stop.set()
            t.join()
        assert mesh.swarm.staleness("m") <= 1
        snap = mesh.snapshot()
    assert len(results) == 48
    assert all(np.isfinite(y) and 0.0 <= p <= 1.0 for y, p in results)
    assert snap["requests"] == 48
    assert sum(snap["requests_by_version"].values()) == 48
    assert snap["pulls"] >= 2                 # propagation actually ran


def test_swarm_detach_stops_fanout_attach_reconciles():
    """A detached swarm ignores direct primary publishes (a stopped
    mesh must not keep pulling); attach catches the replicas up; facade
    publishes propagate even while detached."""
    primary = ModelRegistry()
    swarm = ShardSwarm(2, primary=primary, max_skew=0)
    swarm.register("m", _stub("v1"))
    swarm.detach()
    primary.swap("m", _stub("v2"))           # direct: unobserved
    vec = swarm.version_vector("m")
    assert vec == {"primary": 2, 0: 1, 1: 1}
    swarm.swap("m", _stub("v3"))             # facade: still propagates
    assert swarm.version_vector("m") == {"primary": 3, 0: 3, 1: 3}
    primary.swap("m", _stub("v4"))
    swarm.attach()                           # reconciles missed publishes
    assert swarm.version_vector("m") == {"primary": 4, 0: 4, 1: 4}


def test_stopped_mesh_does_not_pull(forecaster):
    reg = ModelRegistry()
    reg.register("m", forecaster)
    mesh = _mesh(forecaster, n_shards=2, max_skew=0)
    primary = mesh.swarm.primary
    with mesh:
        v_live = primary.swap("m", forecaster.with_params(forecaster.params))
        assert mesh.version_vector("m")[0] == v_live
    pulls_when_stopped = mesh.swarm.pulls
    primary.swap("m", forecaster.with_params(forecaster.params))
    assert mesh.swarm.pulls == pulls_when_stopped      # no dead fan-out
    with mesh:                               # restart reconciles
        assert mesh.version_vector("m")[0] == primary.version("m")


def test_calibration_flip_reuses_compiled_program():
    """Calibrating (tail None -> fitted) must not compile a new serving
    program: uncalibrated and calibrated predicts share one jit entry
    (the alert head's activity is a traced flag)."""
    fc = LSTMForecaster(cfg=CFG, params=init_rnn(jax.random.PRNGKey(1),
                                                 CFG))
    w = _windows(4, seed=11)
    y0, p0 = fc.predict(w)                    # compiles, tail inactive
    predict_jit = fc._fns["predict"]
    size_before = (predict_jit._cache_size()
                   if hasattr(predict_jit, "_cache_size") else None)
    fc.calibrate(w)
    y1, p1 = fc.predict(w)                    # same program, tail active
    np.testing.assert_array_equal(y0, y1)     # forecast unchanged by tail
    if size_before is not None:
        assert predict_jit._cache_size() == size_before


# -- sharded session cache -------------------------------------------------

def test_sharded_session_cache_respects_fleet_budget():
    cache = ShardedSessionCache(n_shards=3, max_sessions=4)
    assert [cache.shards[i].max_sessions for i in range(3)] == [2, 1, 1]
    for i in range(32):                       # hammer one fleet of puts
        cache.put(f"c{i}", i, 8)
    assert len(cache) <= 4                    # never over the fleet budget
    with pytest.raises(ValueError):
        ShardedSessionCache(n_shards=4, max_sessions=3)

def test_sharded_session_cache_routes_and_aggregates():
    cache = ShardedSessionCache(n_shards=2, max_sessions=8)
    for i in range(6):
        cache.put(f"client-{i}", f"carry-{i}", 8, version=i)
    assert len(cache) == 6
    for i in range(6):
        assert f"client-{i}" in cache
        assert cache.get_entry(f"client-{i}") == (f"carry-{i}", i)
        # the entry lives on exactly the routed shard
        sid = cache.shard_for(f"client-{i}")
        assert f"client-{i}" in cache.shards[sid]
        assert f"client-{i}" not in cache.shards[1 - sid]
    assert cache.drop("client-0") and "client-0" not in cache
    st = cache.stats()
    assert st["sessions"] == 5 and st["shards"] == 2
    assert sum(st["sessions_by_shard"]) == 5
    assert st["hits"] == cache.hits


def test_sharded_session_cache_evicts_shard_locally():
    cache = ShardedSessionCache(n_shards=2, max_sessions=4)  # 2 per shard
    on_zero = [f"k{i}" for i in range(64) if cache.shard_for(f"k{i}") == 0]
    for k in on_zero[:3]:
        cache.put(k, k, 8)
    assert len(cache.shards[0]) == 2           # shard-local LRU evicted
    assert len(cache.shards[1]) == 0
    assert cache.evictions == 1


def test_mesh_session_cache_shares_router(forecaster):
    mesh = _mesh(forecaster, n_shards=3)
    cache = mesh.session_cache(max_sessions=12)
    for cid in ("a", "b", "c", "zz-9"):
        assert cache.shard_for(cid) == mesh.shard_for(cid)


def test_sharded_cache_works_with_session_runner(forecaster):
    from repro.serving import RecurrentSessionRunner

    runner = RecurrentSessionRunner(
        forecaster, ShardedSessionCache(n_shards=2, max_sessions=8))
    w = _windows(1, seed=8)[0]
    for t in range(CFG.window):
        y_sharded, p_sharded = runner.step("client", w[t])
    y_ref, p_ref, _ = forecaster.replay(w[None])
    assert y_sharded == float(y_ref[0]) and p_sharded == float(p_ref[0])


# -- batched decode over the mesh ------------------------------------------

def test_mesh_streaming_steps_affine_and_batched(forecaster):
    """Streaming steps route to the client's owning shard, flush as
    fused batches there, and match the single-engine decode path
    bitwise."""
    n, T = 8, 10
    rng = np.random.default_rng(44)
    xs = rng.standard_normal((T, n, 5)).astype(np.float32) * 0.02
    reg = ModelRegistry()
    reg.register("m", forecaster)
    cfg = BatcherConfig(max_batch=16, max_wait_ms=2.0,
                        length_buckets=(CFG.window,))
    ref = {}
    with ServingEngine(reg, cfg) as eng:
        for t in range(T):
            for i in range(n):
                ref[(t, i)] = eng.step("m", f"c{i}", xs[t, i],
                                       timeout=30.0)
    with _mesh(forecaster) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        mesh.reset_clock()
        futs = {}
        for t in range(T):
            for i in range(n):
                futs[(t, i)] = mesh.submit_step("m", f"c{i}", xs[t, i])
            for i in range(n):
                futs[(t, i)].result(timeout=30.0)
        got = {k: f.result(timeout=30.0) for k, f in futs.items()}
        # session affinity: each client's state is resident on exactly
        # the shard the router names (in that shard's decode lanes,
        # spilling to its session cache under pressure)
        for i in range(n):
            sid = mesh.shard_for(f"c{i}")
            assert f"c{i}" in mesh.shards[sid].session_clients()
        snap = mesh.snapshot()
    assert got == ref
    assert snap["step_requests"] == n * T
    assert snap["step_batches"] < n * T            # fused flushes


def test_mesh_remove_shard_migrates_streaming_sessions(forecaster):
    """Removing a shard mid-stream re-homes its engine-resident session
    carries: clients keep streaming with NO change in their numbers."""
    n, T = 6, 12
    rng = np.random.default_rng(45)
    xs = rng.standard_normal((T, n, 5)).astype(np.float32) * 0.02
    reg = ModelRegistry()
    reg.register("m", forecaster)
    cfg = BatcherConfig(max_batch=16, max_wait_ms=2.0,
                        length_buckets=(CFG.window,))
    ref = {}
    with ServingEngine(reg, cfg) as eng:
        for t in range(T):
            for i in range(n):
                ref[i] = eng.step("m", f"c{i}", xs[t, i], timeout=30.0)
    with _mesh(forecaster) as mesh:
        mesh.warmup("m", lengths=(CFG.window,))
        half = T // 2
        for t in range(half):
            for i in range(n):
                mesh.step("m", f"c{i}", xs[t, i], timeout=30.0)
        victim = mesh.shard_for("c0")  # at least c0's carry must move
        mesh.remove_shard(victim)
        got = {}
        for t in range(half, T):
            for i in range(n):
                got[i] = mesh.step("m", f"c{i}", xs[t, i], timeout=30.0)
    assert got == ref                  # bitwise: carries moved intact


# -- telemetry merge -------------------------------------------------------

def test_telemetry_merge_sums_and_pools():
    t1, t2 = Telemetry(), Telemetry()
    t1.record_batch(3, 4)
    t1.record_requests([0.010, 0.020, 0.030], version=1, staleness_s=0.5)
    t2.record_batch(2, 2)
    t2.record_requests([0.040, 0.050], version=2, staleness_s=1.5)
    t2.record_swap()
    snap = Telemetry.merge([t1, t2])
    assert snap["shards"] == 2
    assert snap["requests"] == 5
    assert snap["requests_by_shard"] == [3, 2]
    assert snap["batches"] == 2
    assert snap["requests_by_version"] == {1: 3, 2: 2}
    assert snap["swaps"] == 1
    assert snap["mean_batch"] == pytest.approx(2.5)
    assert snap["batch_occupancy"] == pytest.approx(5 / 6)
    # pooled percentiles span BOTH shards' reservoirs
    assert snap["p50_ms"] == pytest.approx(30.0)
    assert snap["p99_ms"] == pytest.approx(50.0)
    assert snap["staleness_p95_s"] == pytest.approx(1.5)
    assert "p50" in Telemetry.format(snap)    # format() accepts merges


def test_telemetry_merge_attribution_across_versions():
    tels = [Telemetry() for _ in range(3)]
    for sid, tel in enumerate(tels):
        tel.record_requests([0.001] * (sid + 1), version=sid % 2)
    snap = Telemetry.merge(tels)
    assert snap["requests"] == 6
    assert snap["requests_by_version"] == {0: 4, 1: 2}


# -- registry subscriptions ------------------------------------------------

def test_registry_subscribe_sees_all_publish_paths(tmp_path, forecaster):
    reg = ModelRegistry()
    events = []
    reg.subscribe(lambda key, version: events.append((key, version)))
    reg.register("m", forecaster)
    reg.swap("m", forecaster.with_params(forecaster.params))
    path = str(tmp_path / "m.npz")
    reg.save("m", path)
    reg.load(path, key="m2")
    assert events == [("m", 1), ("m", 2), ("m2", 2)]
