"""Ensemble forecasting plane (ISSUE 9): composable model-set serving
with EVT-weighted fusion and the anomaly-aware alert path.

Acceptance pinned here:

- an N-member ensemble predict (and step flush) issues exactly N fused
  per-model dispatches — never N×batch singles (asserted via
  ``kernels.dispatch.counting()``);
- each member's row in the fused result is bitwise-identical to serving
  that member solo through the same engine;
- ensemble specs validate members at registration and swap atomically
  under a monotone version;
- anomaly mode widens the alert threshold and tightens the batcher's
  effective ``max_wait``;
- the mesh co-locates every member of a client's ensemble request on
  ONE shard (rendezvous on client_id only);
- per-member ``model`` labels flow through telemetry into the
  Prometheus export.
"""

import time

import jax
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.models.rnn import RNNConfig, init_rnn
from repro.obs import render_prometheus
from repro.serving import (BatcherConfig, EnsembleForecaster, EnsembleFuser,
                           EnsembleSpec, LSTMForecaster, ModelRegistry,
                           ServingEngine, ShardedServingEngine, Telemetry,
                           fusion_weights)

CFG = RNNConfig(input_dim=5, hidden=16, num_layers=2, fc_dims=(8, 4),
                window=20, evl_head=True)


def _forecaster(seed: int) -> LSTMForecaster:
    fc = LSTMForecaster(cfg=CFG, params=init_rnn(jax.random.PRNGKey(seed),
                                                 CFG))
    rng = np.random.default_rng(seed)
    fc.calibrate(rng.standard_normal((64, CFG.window, 5)).astype(np.float32)
                 * 0.02)
    return fc


@pytest.fixture(scope="module")
def members():
    return _forecaster(0), _forecaster(1)


@pytest.fixture()
def registry(members):
    reg = ModelRegistry()
    reg.register("m1", members[0])
    reg.register("m2", members[1])
    reg.register_ensemble("ens", ["m1", "m2"])
    return reg


def _windows(n, t=20, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, t, 5)).astype(np.float32) * 0.02


# -- spec hosting ----------------------------------------------------------

def test_spec_validation(registry):
    with pytest.raises(KeyError):
        registry.register_ensemble("bad", ["m1", "ghost"])
    with pytest.raises(ValueError):
        registry.register_ensemble("m1", ["m1"])    # name is a model key
    with pytest.raises(ValueError):
        EnsembleSpec(members=("m1", "m1"))          # duplicate members
    with pytest.raises(ValueError):
        EnsembleSpec(members=())
    with pytest.raises(ValueError):
        EnsembleSpec(members=("m1",), anomaly_enter=0.3, anomaly_exit=0.6)
    with pytest.raises(KeyError):
        registry.swap_ensemble("ghost", ["m1"])


def test_spec_swap_is_atomic_and_versioned(registry):
    assert registry.ensemble_version("ens") == 1
    v = registry.swap_ensemble("ens", ["m2"])
    assert v == 2
    assert registry.ensemble("ens").members == ("m2",)
    # invalid swap leaves the hosted spec untouched
    with pytest.raises(KeyError):
        registry.swap_ensemble("ens", ["m2", "ghost"])
    assert registry.ensemble("ens").members == ("m2",)
    assert registry.ensemble_version("ens") == 2


def test_spec_wire_roundtrip():
    spec = EnsembleSpec(members=("a", "b"), temperature=0.5,
                        alert_threshold=0.8, anomaly_wait_scale=0.5)
    assert EnsembleSpec.from_wire(spec.to_wire()) == spec


def test_install_ensemble_skips_stale(registry):
    spec_new = EnsembleSpec(members=("m2",))
    assert registry.install_ensemble("ens", spec_new, 5)
    # older version must not clobber the newer spec
    assert not registry.install_ensemble(
        "ens", EnsembleSpec(members=("m1",)), 3)
    assert registry.ensemble("ens").members == ("m2",)
    assert registry.ensemble_version("ens") == 5


# -- fusion weighting ------------------------------------------------------

def test_fusion_weights_basics():
    w = fusion_weights([1.0, 1.0], [0.0, 0.0])
    np.testing.assert_allclose(w, [0.5, 0.5])
    # lower rolling error -> more weight; sharper EVT prior -> more weight
    w = fusion_weights([1.0, 1.0], [0.1, 2.0])
    assert w[0] > w[1]
    w = fusion_weights([5.0, 1.0], [0.3, 0.3])
    assert w[0] > w[1]
    # single member: exactly 1.0 (not approximately)
    assert fusion_weights([3.0], [7.0])[0] == 1.0
    # pathological histories stay convex
    w = fusion_weights([np.inf, -1.0], [np.nan, np.inf])
    assert np.all(w >= 0.0) and np.isclose(w.sum(), 1.0)


def test_fuser_supervised_errors_shift_weights():
    fuser = EnsembleFuser(2, EnsembleSpec(members=("a", "b"),
                                          error_half_life=1.0))
    for _ in range(8):
        fuser.record_errors([0.0, 5.0])
    w = fuser.weights()
    assert w[0] > 0.9 > w[1]


# -- anomaly-aware alert path ----------------------------------------------

def test_anomaly_hysteresis_widens_alerts_and_tightens_wait():
    spec = EnsembleSpec(members=("a", "b"), alert_threshold=0.9,
                        anomaly_enter=0.6, anomaly_exit=0.3,
                        anomaly_alert_scale=0.5, anomaly_wait_scale=0.25,
                        anomaly_half_life=1.0)
    fuser = EnsembleFuser(2, spec)
    assert not fuser.anomaly
    assert fuser.alert_threshold() == pytest.approx(0.9)
    assert fuser.wait_scale() == 1.0
    calm = [np.zeros(4, np.float32)] * 2
    hot = [np.full(4, 0.95, np.float32)] * 2
    for _ in range(6):                        # extreme regime: EWMA rises
        fuser.fuse(calm, hot)
    assert fuser.anomaly
    assert fuser.alert_threshold() == pytest.approx(0.45)   # widened
    assert fuser.wait_scale() == pytest.approx(0.25)        # flush sooner
    # hysteresis: one calm batch (EWMA still >= exit) stays anomalous
    fuser.fuse(calm, [np.full(4, 0.35, np.float32)] * 2)
    assert fuser.anomaly
    for _ in range(8):                        # calm regime: EWMA decays
        fuser.fuse(calm, [np.zeros(4, np.float32)] * 2)
    assert not fuser.anomaly
    assert fuser.alert_threshold() == pytest.approx(0.9)


def test_engine_anomaly_tightens_effective_wait(registry):
    cfg = BatcherConfig(max_batch=8, max_wait_ms=2.0)
    with ServingEngine(registry, cfg) as eng:
        rt = eng._ensemble("ens")
        spec = registry.ensemble("ens")
        assert eng._wait_scale("ens") == 1.0
        hot = [np.full(2, 0.97, np.float32)] * 2
        for _ in range(40):                   # flip the fuser anomalous
            rt.fuser().fuse([np.zeros(2, np.float32)] * 2, hot)
        assert rt.fuser().anomaly
        eng._note_anomaly("ens", spec, rt)
        # the ensemble AND its members flush on the tightened deadline
        assert eng._wait_scale("ens") == pytest.approx(
            spec.anomaly_wait_scale)
        assert eng._wait_scale("m1") == pytest.approx(
            spec.anomaly_wait_scale)
        assert eng.telemetry.snapshot()["anomaly_mode"] == 1
        # recovery clears the overrides
        for _ in range(64):
            rt.fuser().fuse([np.zeros(2, np.float32)] * 2,
                            [np.zeros(2, np.float32)] * 2)
        eng._note_anomaly("ens", spec, rt)
        assert eng._wait_scale("ens") == 1.0
        assert eng._wait_scale("m1") == 1.0
        assert eng.telemetry.snapshot()["anomaly_mode"] == 0


# -- engine fan-out / fan-in -----------------------------------------------

def test_predict_fans_out_exactly_n_fused_dispatches(registry, members):
    cfg = BatcherConfig(max_batch=8, max_wait_ms=2.0)
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("ens", lengths=(20,))
        w = _windows(1, seed=3)[0]
        eng.predict("ens", w, timeout=30.0)          # steady state
        with dispatch.counting() as counts:
            y, p = eng.predict("ens", w, timeout=30.0)
        # one ensemble request = exactly N per-model fused predicts
        assert counts.by_op() == {"predict": 2}
        assert np.isfinite(y) and 0.0 <= p <= 1.0


def test_fan_in_future_carries_member_attribution(registry, members):
    cfg = BatcherConfig(max_batch=8, max_wait_ms=2.0)
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("ens", lengths=(20,))
        w = _windows(1, seed=4)[0]
        fut = eng.submit("ens", w)
        y, p = fut.result(timeout=30.0)
        assert sorted(fut.members) == ["m1", "m2"]
        assert fut.model_version == (1, 1)
        assert np.isclose(np.sum(fut.weights), 1.0)
        assert fut.alert == (p >= fut.alert_threshold)
        # fused forecast is the convex member combination
        ys = np.array([fut.members[k][0] for k in ("m1", "m2")])
        assert min(ys) - 1e-6 <= y <= max(ys) + 1e-6


def test_member_rows_bitwise_equal_solo_serving(registry, members):
    """Fanned-out member requests ride the same per-model buckets as
    solo traffic, so each member's row is bitwise what the member
    serves alone."""
    cfg = BatcherConfig(max_batch=8, max_wait_ms=2.0)
    w = _windows(1, seed=5)[0]
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("ens", lengths=(20,))
        fut = eng.submit("ens", w)
        fut.result(timeout=30.0)
        solo = {k: eng.predict(k, w, timeout=30.0) for k in ("m1", "m2")}
    for k in ("m1", "m2"):
        assert fut.members[k][0] == solo[k][0]       # bitwise, not approx
        assert fut.members[k][1] == solo[k][1]


def test_step_flush_is_n_fused_dispatches(registry):
    """A streaming flush under an ensemble advances EVERY resident
    session through each member's fused decode lane: N slots_generate
    dispatches per tick, zero per-session singles."""
    cfg = BatcherConfig(max_batch=8, max_wait_ms=4.0, decode_slots=8)
    clients = [f"c{i}" for i in range(3)]
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("ens", lengths=(20,))
        hist = _windows(1, seed=6)[0]
        x1 = _windows(1, seed=7)[0][0]
        # first wave: sessions replay + insert into the decode lanes
        futs = [eng.submit_step("ens", c, x1, history=hist)
                for c in clients]
        [f.result(timeout=30.0) for f in futs]
        before = eng.telemetry.snapshot()["step_batches"]
        with dispatch.counting() as counts:
            futs = [eng.submit_step("ens", c, x1) for c in clients]
            got = [f.result(timeout=30.0) for f in futs]
        flushes = eng.telemetry.snapshot()["step_batches"] - before
        by_op = counts.by_op()
        assert by_op.get("slots_generate", 0) == 2 * flushes
        assert "decode_step" not in by_op            # no singles
        assert all(0.0 <= p <= 1.0 for _, p in got)


def test_ensemble_session_survives_spill(registry):
    """Composite {member: carry} session state spills off the decode
    lanes and reloads bitwise: steps after a spill continue the same
    stream. A singleton ensemble pins this bitwise (multi-member fused
    values evolve with the shared rolling-error state by design)."""
    registry.register_ensemble("solo", ["m1"])
    cfg = BatcherConfig(max_batch=8, max_wait_ms=4.0, decode_slots=8)
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("solo", lengths=(20,))
        hist = _windows(1, seed=8)[0]
        xs = _windows(1, seed=9)[0]
        ref = []
        for t in range(3):
            ref.append(eng.step("solo", "spill-me", xs[t],
                                history=hist if t == 0 else None))
        # same stream, spilled off the lanes mid-way through
        for t in range(2):
            eng.step("solo", "spill-2", xs[t],
                     history=hist if t == 0 else None)
        eng.spill_sessions(["spill-2"])
        y, p = eng.step("solo", "spill-2", xs[2])
        assert (y, p) == ref[2]


def test_engine_swap_ensemble_changes_fusion(registry, members):
    cfg = BatcherConfig(max_batch=8, max_wait_ms=2.0)
    w = _windows(1, seed=10)[0]
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("ens", lengths=(20,))
        rt = eng._ensemble("ens")
        v_before = rt.version
        registry.swap_ensemble("ens", ["m1"])
        assert rt.version != v_before         # session re-prime trigger
        y, p = eng.predict("ens", w, timeout=30.0)
        y1, p1 = eng.predict("m1", w, timeout=30.0)
        assert y == y1 and p == p1            # singleton == member solo


# -- telemetry + export ----------------------------------------------------

def test_per_member_model_labels_reach_prometheus(registry):
    cfg = BatcherConfig(max_batch=8, max_wait_ms=2.0)
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("ens", lengths=(20,))
        futs = [eng.submit("ens", w) for w in _windows(3, seed=11)]
        [f.result(timeout=30.0) for f in futs]
        snap = eng.telemetry.snapshot()
    assert snap["requests_by_model"]["m1"] == 3
    assert snap["requests_by_model"]["m2"] == 3
    assert snap["ensemble_requests"] == 3
    text = render_prometheus(snap, prefix="repro")
    assert 'repro_requests_by_model{model="m1"} 3' in text
    assert 'repro_requests_by_model{model="m2"} 3' in text
    assert "repro_ensemble_requests 3" in text
    assert "repro_anomaly_mode 0" in text
    line = Telemetry.format(snap)
    assert "by model" in line and "ensemble 3 fused" in line


def test_telemetry_merge_sums_model_labels():
    a, b = Telemetry(), Telemetry()
    a.record_requests([0.01] * 2, model="m1")
    b.record_requests([0.01] * 3, model="m1")
    b.record_requests([0.01], model="m2")
    b.record_ensemble(alerts=1, n=2, anomaly=True)
    merged = Telemetry.merge([a, b])
    assert merged["requests_by_model"] == {"m1": 5, "m2": 1}
    assert merged["ensemble_requests"] == 2
    assert merged["ensemble_alerts"] == 1
    assert merged["anomaly_mode"] == 1


# -- mesh ------------------------------------------------------------------

def test_mesh_colocates_members_on_owning_shard(members):
    """Rendezvous keys on client_id alone: every member of a client's
    ensemble request lands on the client's shard — the fan-in never
    crosses a shard boundary."""
    reg = ModelRegistry()
    reg.register("m1", members[0])
    reg.register("m2", members[1])
    mesh = ShardedServingEngine(reg, BatcherConfig(max_batch=8,
                                                   max_wait_ms=2.0),
                                n_shards=2)
    mesh.register_ensemble("ens", ["m1", "m2"])
    with mesh:
        mesh.warmup("ens", lengths=(20,))
        mesh.reset_clock()
        sid = mesh.shard_for("alice")
        futs = [mesh.submit("ens", w, client_id="alice")
                for w in _windows(4, seed=12)]
        [f.result(timeout=30.0) for f in futs]
        tels = {s: t.snapshot() for s, t in
                zip(sorted(mesh.shards), mesh.shard_telemetries)}
    owner, other = tels[sid], tels[[s for s in tels if s != sid][0]]
    assert owner["requests_by_model"] == {"m1": 4, "m2": 4}
    assert owner["ensemble_requests"] == 4
    assert other.get("requests_by_model", {}) == {}
    assert other["requests"] == 0


def test_mesh_ensemble_swap_propagates(members):
    reg = ModelRegistry()
    reg.register("m1", members[0])
    reg.register("m2", members[1])
    mesh = ShardedServingEngine(reg, BatcherConfig(max_batch=8,
                                                   max_wait_ms=2.0),
                                n_shards=2)
    mesh.register_ensemble("ens", ["m1", "m2"])
    with mesh:
        for replica in mesh.swarm.replicas.values():
            assert replica.ensemble("ens").members == ("m1", "m2")
        mesh.swap_ensemble("ens", ["m2"])
        for replica in mesh.swarm.replicas.values():
            assert replica.ensemble("ens").members == ("m2",)
            assert replica.ensemble_version("ens") == 2
        w = _windows(1, seed=13)[0]
        y, p = mesh.predict("ens", w, client_id="bob", timeout=30.0)
        y2, p2 = mesh.predict("m2", w, client_id="bob", timeout=30.0)
        assert y == y2 and p == p2


def test_mesh_join_seeds_ensemble_specs(members):
    reg = ModelRegistry()
    reg.register("m1", members[0])
    reg.register("m2", members[1])
    mesh = ShardedServingEngine(reg, BatcherConfig(max_batch=8,
                                                   max_wait_ms=2.0),
                                n_shards=1)
    mesh.register_ensemble("ens", ["m1", "m2"])
    with mesh:
        sid = mesh.add_shard()
        replica = mesh.swarm.registry_for(sid)
        assert replica.ensemble("ens").members == ("m1", "m2")
