"""Streaming forecast serving: micro-batcher flush policies, padding
correctness, session-cache semantics, and registry round-trips."""

import time

import jax
import numpy as np
import pytest

from repro.models.rnn import RNNConfig, init_rnn, rnn_apply
from repro.serving import (BatcherConfig, LSTMForecaster, ModelRegistry,
                           RecurrentSessionRunner, ServingEngine,
                           SessionCache, Telemetry, build_lstm_forecaster)

CFG = RNNConfig(input_dim=5, hidden=16, num_layers=2, fc_dims=(8, 4),
                window=20, evl_head=True)


@pytest.fixture(scope="module")
def forecaster():
    params = init_rnn(jax.random.PRNGKey(0), CFG)
    fc = LSTMForecaster(cfg=CFG, params=params)
    rng = np.random.default_rng(0)
    fc.calibrate(rng.standard_normal((64, CFG.window, 5)).astype(np.float32)
                 * 0.02)
    return fc


@pytest.fixture()
def registry(forecaster):
    reg = ModelRegistry()
    reg.register("m", forecaster)
    return reg


def _windows(n, t=20, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, t, 5)).astype(np.float32) * 0.02


# -- micro-batcher ---------------------------------------------------------

def test_flush_on_max_batch(registry):
    """With an effectively infinite wait, a full group must still flush
    the moment it reaches max_batch."""
    cfg = BatcherConfig(max_batch=4, max_wait_ms=60_000.0)
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("m", lengths=(20,))
        futs = [eng.submit("m", w) for w in _windows(4)]
        res = [f.result(timeout=10.0) for f in futs]
    assert len(res) == 4
    assert eng.telemetry.batches >= 1
    snap = eng.telemetry.snapshot()
    assert snap["mean_batch"] == 4.0


def test_flush_on_timeout(registry):
    """A partial group must flush once its oldest request has waited
    max_wait_ms, without needing more arrivals."""
    cfg = BatcherConfig(max_batch=64, max_wait_ms=10.0)
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("m", lengths=(20,))
        t0 = time.perf_counter()
        futs = [eng.submit("m", w) for w in _windows(3)]
        res = [f.result(timeout=10.0) for f in futs]
        elapsed = time.perf_counter() - t0
    assert len(res) == 3
    assert elapsed < 5.0                       # did not wait for a full batch
    assert eng.telemetry.snapshot()["mean_batch"] == 3.0


def test_bucket_padding_matches_unbatched(registry, forecaster):
    """Mixed-length windows batched into one padded bucket must produce
    exactly the same predictions as unbatched exact-shape applies."""
    lengths = (12, 20, 17, 9, 20)
    wins = [_windows(1, t, seed=t)[0] for t in lengths]
    cfg = BatcherConfig(max_batch=8, max_wait_ms=5.0)
    with ServingEngine(registry, cfg) as eng:
        futs = [eng.submit("m", w) for w in wins]
        got = [f.result(timeout=10.0) for f in futs]
    # exact-shape reference; batching at a different [B, T] makes XLA pick
    # a different tiling, so agreement is to float32 ulp, not bitwise
    for (y_got, p_got), w in zip(got, wins):
        y_ref, p_ref = forecaster.predict(w[None])
        np.testing.assert_allclose(y_got, y_ref[0], atol=1e-7, rtol=1e-6)
        np.testing.assert_allclose(p_got, p_ref[0], atol=1e-7, rtol=1e-6)
    # within one padded batch the gather must be exact: re-submitting the
    # same mixed-length batch reproduces itself bitwise
    with ServingEngine(registry, cfg) as eng:
        futs = [eng.submit("m", w) for w in wins]
        again = [f.result(timeout=10.0) for f in futs]
    assert got == again


def test_cancelled_request_does_not_kill_engine(registry):
    """A client cancelling its future must not crash the worker thread
    (futures transition to RUNNING at flush, so late cancels fail)."""
    cfg = BatcherConfig(max_batch=64, max_wait_ms=20.0)
    with ServingEngine(registry, cfg) as eng:
        fut = eng.submit("m", _windows(1)[0])
        fut.cancel()                           # may or may not win the race
        res = eng.predict("m", _windows(1)[0], timeout=10.0)
    assert isinstance(res, tuple) and len(res) == 2


def test_engine_submit_requires_running(registry):
    eng = ServingEngine(registry)
    with pytest.raises(RuntimeError):
        eng.submit("m", _windows(1)[0])


def test_engine_rejects_bad_submissions(registry):
    with ServingEngine(registry) as eng:
        with pytest.raises(KeyError):
            eng.submit("nope", _windows(1)[0])         # unknown model
        with pytest.raises(ValueError):
            eng.submit("m", np.zeros((0, 5), np.float32))   # empty window
        with pytest.raises(ValueError):
            eng.submit("m", np.zeros((20,), np.float32))    # wrong rank
        assert eng.predict("m", _windows(1)[0], timeout=10.0)


def test_batch_bucketing_quantizes_shapes():
    cfg = BatcherConfig(max_batch=32, length_buckets=(16, 32))
    assert cfg.bucket_len(9) == 16
    assert cfg.bucket_len(16) == 16
    assert cfg.bucket_len(20) == 32
    assert cfg.bucket_len(40) == 32            # beyond buckets: clamped
    assert cfg.bucket_batch(3) == 4
    assert cfg.bucket_batch(32) == 32


def test_overlong_window_clamps_to_largest_bucket(registry, forecaster):
    """Regression: a request longer than every configured length bucket
    used to keep its raw length — a shape outside the fixed compile set
    (never warmed), recompiling on the serving hot path. It is now
    clamped to the largest bucket, serving the newest rows (the LSTM is
    causal, so those rows ARE the clamped window's forecast)."""
    cfg = BatcherConfig(max_batch=4, max_wait_ms=5.0,
                        length_buckets=(12, 20))
    # every length the hot path can see maps into the configured buckets
    assert {cfg.bucket_len(t) for t in (1, 12, 19, 20, 21, 64)} <= {12, 20}
    long_window = _windows(1, t=33, seed=9)[0]
    longer = _windows(1, t=64, seed=10)[0]
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("m", lengths=(12, 20))
        y_got, p_got = eng.predict("m", long_window, timeout=10.0)
        y_sub, p_sub = eng.submit("m", longer).result(timeout=10.0)
    # the served result is exactly the truncated-window prediction
    y_ref, p_ref = forecaster.predict(long_window[None, -20:])
    np.testing.assert_array_equal(y_got, y_ref[0])
    np.testing.assert_array_equal(p_got, p_ref[0])
    # ``submit`` of a payload three times the largest bucket serves its
    # newest 20 rows, not its oldest
    y_new, p_new = forecaster.predict(longer[None, -20:])
    np.testing.assert_array_equal(y_sub, y_new[0])
    np.testing.assert_array_equal(p_sub, p_new[0])
    y_old, _ = forecaster.predict(longer[None, :20])
    assert y_sub != y_old[0]


def test_client_id_threads_through_to_telemetry(registry):
    """Regression: per-client attribution must survive into the flush
    telemetry and the resolved future."""
    cfg = BatcherConfig(max_batch=4, max_wait_ms=2.0, length_buckets=(20,))
    with ServingEngine(registry, cfg) as eng:
        futs = [eng.submit("m", w, client_id=f"c{i % 2}")
                for i, w in enumerate(_windows(4))]
        futs.append(eng.submit("m", _windows(1)[0]))      # anonymous
        for f in futs:
            f.result(timeout=10.0)
    assert futs[0].client_id == "c0" and futs[1].client_id == "c1"
    assert futs[-1].client_id is None
    snap = eng.telemetry.snapshot()
    assert snap["requests_by_client"] == {"c0": 2, "c1": 2}
    assert snap["unique_clients"] == 2


def test_non_pow2_max_batch_rounds_down(registry):
    """Regression: max_batch=12 used to clamp bucket_batch to 12 — a
    shape outside the '{pow2 batches} x {length buckets}' compile set.
    The config now rounds down at construction, so every emitted batch
    shape is one the warmup compiled."""
    cfg = BatcherConfig(max_batch=12)
    assert cfg.max_batch == 8
    assert {cfg.bucket_batch(n) for n in range(1, cfg.max_batch + 1)} \
        == {1, 2, 4, 8}
    with pytest.raises(ValueError):
        BatcherConfig(max_batch=0)
    # un-padded batching is untouched by the rounding
    assert BatcherConfig(max_batch=12, pad_batch=False).max_batch == 12
    # end to end: a full group at the rounded max_batch flushes as one
    # pow2 batch the warmup covered (no mid-traffic compile, exact batch)
    eng_cfg = BatcherConfig(max_batch=6, max_wait_ms=60_000.0,
                            length_buckets=(20,))
    assert eng_cfg.max_batch == 4
    with ServingEngine(registry, eng_cfg) as eng:
        eng.warmup("m", lengths=(20,))
        futs = [eng.submit("m", w) for w in _windows(4)]
        assert len([f.result(timeout=10.0) for f in futs]) == 4
    snap = eng.telemetry.snapshot()
    assert snap["mean_batch"] == 4.0 and snap["batch_occupancy"] == 1.0


def test_replay_is_one_dispatch_not_a_step_loop(forecaster):
    """Regression: ``replay`` used to loop Python-side over ``step``,
    syncing the device O(window) times per cache miss / swap re-prime.
    It is now a single jitted lax.scan dispatch — and still bitwise
    equal to the step loop (the session cache's contract)."""
    w = _windows(1, seed=13)[0]
    calls = {"n": 0}
    real_step = forecaster.step

    def counting_step(x_t, carry):
        calls["n"] += 1
        return real_step(x_t, carry)

    forecaster.step = counting_step
    try:
        y_scan, p_scan, carry_scan = forecaster.replay(w[None])
    finally:
        forecaster.step = real_step
    assert calls["n"] == 0                     # no per-step host loop
    # bitwise equivalence against the explicit step loop
    carry = forecaster.init_carry(1)
    for t in range(CFG.window):
        y_loop, p_loop, carry = forecaster.step(w[None, t], carry)
    np.testing.assert_array_equal(y_loop, y_scan)
    np.testing.assert_array_equal(p_loop, p_scan)
    for (h1, c1), (h2, c2) in zip(carry, carry_scan):
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_compiled_rnn_builds_once_under_threads():
    """Regression: ``_compiled_rnn`` used to tolerate a 'benign' race —
    two threads could each build a full jit wrapper set for the same
    config during shard-join warmup. Now double-checked-locked: exactly
    one build, every thread gets the same object."""
    import threading

    from repro.serving import forecaster as fmod

    cfg = RNNConfig(input_dim=5, hidden=12, num_layers=1, fc_dims=(6,),
                    window=10, evl_head=True)   # fresh: not yet cached
    fmod._RNN_COMPILED.pop(cfg, None)
    builds = {"n": 0}
    real_build = fmod._build_rnn_fns

    def counting_build(c):
        builds["n"] += 1
        time.sleep(0.05)          # widen the race window
        return real_build(c)

    fmod._build_rnn_fns = counting_build
    results = []
    try:
        barrier = threading.Barrier(8)

        def hit():
            barrier.wait()
            results.append(fmod._compiled_rnn(cfg))

        threads = [threading.Thread(target=hit) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        fmod._build_rnn_fns = real_build
        fmod._RNN_COMPILED.pop(cfg, None)
    assert builds["n"] == 1
    assert all(r is results[0] for r in results)


# -- batched decode path ---------------------------------------------------

def test_batched_step_matches_sequential_bitwise(forecaster):
    """The decode-lane contract: N sessions stepped in ONE generate over
    N lanes are BITWISE the N per-session ``step`` loops (both run the
    same fixed-width lane step)."""
    n, T = 8, CFG.window
    rng = np.random.default_rng(21)
    xs = rng.standard_normal((T, n, 5)).astype(np.float32) * 0.02

    seq = [forecaster.init_carry(1) for _ in range(n)]
    seq_out = [None] * n
    for t in range(T):
        for i in range(n):
            y, p, seq[i] = forecaster.step(xs[t, i:i + 1], seq[i])
            seq_out[i] = (float(y[0]), float(p[0]))
    slots = forecaster.init_slots(n)
    assert slots.num_slots == n
    for i in range(n):
        forecaster.insert(slots, i, forecaster.init_carry(1))
    for t in range(T):
        ys, ps, slots = forecaster.generate(slots, xs[t],
                                            lanes=list(range(n)))
    for i in range(n):
        assert (float(ys[i]), float(ps[i])) == seq_out[i]
        lane = forecaster.extract(slots, i)
        for (h1, c1), (h2, c2) in zip(seq[i], lane):
            np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
            np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_donate_default_platform_gate(monkeypatch):
    """Carry donation defaults ON off-CPU and OFF on CPU, where XLA
    donation is a warn + copy no-op."""
    from repro.serving import forecaster as fc_mod
    assert fc_mod._donate_default() == (jax.default_backend() != "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert fc_mod._donate_default() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert fc_mod._donate_default() is False


def test_donated_step_many_matches_non_donated_bitwise(forecaster,
                                                       monkeypatch):
    """The donating lane programs (insert and generate) must be
    bit-for-bit the non-donating ones.  On CPU an explicit
    ``donate=True`` is gated off, so force the donating variants by
    patching the platform query — XLA then warns and copies, which is
    exactly the behavior the gate exists to avoid, but the numerics
    contract still has to hold."""
    import warnings

    def donated(fn, *args, **kw):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                return fn(*args, donate=True, **kw)
        finally:
            monkeypatch.undo()

    rng = np.random.default_rng(3)
    xs = rng.standard_normal((5, 5)).astype(np.float32) * 0.02
    carry = forecaster.init_carry(1)
    s0 = forecaster.insert(forecaster.init_slots(4), 1, carry,
                           donate=False)
    s1 = donated(forecaster.insert, forecaster.init_slots(4), 1, carry)
    x = np.zeros((s0.num_slots, 5), np.float32)
    x[1] = xs[0]
    ya, pa, s0 = forecaster.generate(s0, x, donate=False)
    yb, pb, s1 = donated(forecaster.generate, s1, x)
    assert np.array_equal(ya, yb) and np.array_equal(pa, pb)
    for a, b in zip(jax.tree_util.tree_leaves(s0.carry),
                    jax.tree_util.tree_leaves(s1.carry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_step_many_partial_and_chunked_flushes(forecaster):
    """A generate that steps some of the lanes (1, 3, 8 or 13 of 16:
    under one decode-lane chunk, one full chunk, across two) matches
    per-session steps bitwise, and the lanes it does not step keep
    their carry."""
    rng = np.random.default_rng(5)
    for n in (1, 3, 8, 13):
        slots = forecaster.init_slots(16)
        assert slots.num_slots == 16
        lanes = np.sort(rng.permutation(16)[:n])
        xs = rng.standard_normal((16, 5)).astype(np.float32) * 0.02
        ys, ps, slots = forecaster.generate(slots, xs, lanes=lanes)
        for lane in lanes:
            y1, p1, c1 = forecaster.step(xs[lane:lane + 1],
                                         forecaster.init_carry(1))
            assert float(ys[lane]) == float(y1[0])
            assert float(ps[lane]) == float(p1[0])
            for a, b in zip(jax.tree_util.tree_leaves(c1),
                            jax.tree_util.tree_leaves(
                                forecaster.extract(slots, lane))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for lane in sorted(set(range(16)) - set(lanes.tolist())):
            for a in jax.tree_util.tree_leaves(
                    forecaster.extract(slots, lane)):
                assert not np.asarray(a).any()     # still the zero carry


def test_decode_slots_zero_is_refused():
    """Every streaming step goes through the decode lanes: an engine
    config without lanes is refused at construction."""
    with pytest.raises(ValueError, match="decode_slots must be >= 1"):
        BatcherConfig(decode_slots=0)
    assert BatcherConfig(decode_slots=1).decode_slots == 1


class _NoLanes:
    """Streams by the one-session API alone: no decode lanes."""

    window, feature_dim, decode_width, kind = 20, 5, 1, "stub"

    def init_carry(self, batch=1):
        return ()

    def carry_nbytes(self, batch=1):
        return 0

    def step(self, x_t, carry):
        raise AssertionError("never stepped")

    def replay(self, window, carry=None):
        raise AssertionError("never replayed")


@pytest.mark.parametrize("case", ["num_slots=0", "no init_slots"])
def test_runner_without_lanes_is_refused(forecaster, case):
    """A session runner steps only through decode lanes: zero lanes, or
    a forecaster without the lane lifecycle, is a TypeError."""
    with pytest.raises(TypeError):
        if case == "num_slots=0":
            RecurrentSessionRunner(forecaster, num_slots=0)
        else:
            RecurrentSessionRunner(_NoLanes())


def test_runner_step_many_matches_step(forecaster):
    """Batched runner steps equal sequential runner steps; the sessions
    stay in their lanes, and a spill hands every carry to the cache."""
    n, T = 6, 10
    rng = np.random.default_rng(9)
    xs = rng.standard_normal((T, n, 5)).astype(np.float32) * 0.02
    r_seq = RecurrentSessionRunner(forecaster,
                                   SessionCache(max_sessions=n))
    r_bat = RecurrentSessionRunner(forecaster,
                                   SessionCache(max_sessions=n))
    for t in range(T):
        seq = [r_seq.step(f"c{i}", xs[t, i]) for i in range(n)]
        bat = r_bat.step_many([(f"c{i}", xs[t, i], None)
                               for i in range(n)])
        assert bat == seq
    # slot runner: sessions live in device lanes, not the cache (the
    # cache is the spill tier and stays empty while lanes suffice)
    assert sorted(r_bat.resident_clients()) == [f"c{i}" for i in range(n)]
    assert len(r_bat.cache) == 0
    # spilling hands every lane's carry to the cache, bitwise intact
    assert r_bat.spill_all() == n
    assert len(r_bat.cache) == n


def test_runner_step_many_duplicate_clients_keep_stream_order(forecaster):
    """Two steps for one client inside a single batched call must see
    each other's carries (waves), exactly like two sequential steps."""
    rng = np.random.default_rng(11)
    x0, x1 = (rng.standard_normal((2, 5)).astype(np.float32) * 0.02)
    r_seq = RecurrentSessionRunner(forecaster,
                                   SessionCache(max_sessions=2))
    a = r_seq.step("dup", x0)
    b = r_seq.step("dup", x1)
    r_bat = RecurrentSessionRunner(forecaster,
                                   SessionCache(max_sessions=2))
    got = r_bat.step_many([("dup", x0, None), ("dup", x1, None)])
    assert got == [a, b]


def test_engine_step_flush_groups_and_matches_runner(registry, forecaster):
    """Engine-level batched decode: a burst of submit_step calls flushes
    as fused batches (telemetry shows >1 sessions per flush) and the
    results equal the plain per-session runner bitwise."""
    n, T = 8, 6
    rng = np.random.default_rng(33)
    xs = rng.standard_normal((T, n, 5)).astype(np.float32) * 0.02
    runner = RecurrentSessionRunner(forecaster,
                                    SessionCache(max_sessions=n))
    ref = {}
    for t in range(T):
        for i in range(n):
            ref[(t, i)] = runner.step(f"c{i}", xs[t, i])
    cfg = BatcherConfig(max_batch=16, max_wait_ms=5.0, length_buckets=(20,))
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("m", lengths=(20,))
        eng.telemetry.reset_clock()
        futs = {}
        for t in range(T):
            for i in range(n):
                futs[(t, i)] = eng.submit_step("m", f"c{i}", xs[t, i])
        got = {k: f.result(timeout=30.0) for k, f in futs.items()}
    assert got == ref
    snap = eng.telemetry.snapshot()
    assert snap["step_requests"] == n * T
    assert snap["step_batches"] < n * T           # actually batched
    assert snap["mean_step_batch"] > 1.0
    assert 0.0 < snap["step_occupancy"] <= 1.0
    # version attribution rides on step futures like predict futures
    assert all(f.model_version == forecaster.version
               for f in futs.values())


def test_engine_step_rejects_bad_submissions(registry):
    with ServingEngine(registry) as eng:
        with pytest.raises(ValueError):
            eng.submit_step("m", None, np.zeros(5, np.float32))
        with pytest.raises(ValueError):
            eng.submit_step("m", "c", np.zeros((3,), np.float32))
        with pytest.raises(KeyError):
            eng.submit_step("nope", "c", np.zeros(5, np.float32))
        # malformed history fails THIS submit, not the whole flush it
        # would later share with other clients' steps
        with pytest.raises(ValueError):
            eng.submit_step("m", "c", np.zeros(5, np.float32),
                            history=np.zeros((4, 6), np.float32))
        with pytest.raises(ValueError):
            eng.submit_step("m", "c", np.zeros(5, np.float32),
                            history=np.zeros((0, 5), np.float32))
        assert eng.step("m", "c", np.zeros(5, np.float32), timeout=10.0)


def test_engine_step_occupancy_counts_waves(registry, forecaster):
    """Regression: padded-slot accounting must reflect the follow-up
    waves duplicate client ids dispatch — 2 clients x 8 steps in one
    flush is 8 padded lane dispatches, not 2."""
    cfg = BatcherConfig(max_batch=16, max_wait_ms=40.0,
                        length_buckets=(20,))
    rng = np.random.default_rng(3)
    xs = rng.standard_normal((8, 2, 5)).astype(np.float32) * 0.02
    with ServingEngine(registry, cfg) as eng:
        eng.warmup("m", lengths=(20,))
        eng.telemetry.reset_clock()
        futs = [eng.submit_step("m", f"c{i}", xs[t, i])
                for t in range(8) for i in range(2)]
        for f in futs:
            f.result(timeout=30.0)
    snap = eng.telemetry.snapshot()
    assert snap["step_requests"] == 16
    W = forecaster.decode_width
    # every wave holds at most 2 real sessions in a W-wide lane
    # dispatch (the pre-fix accounting ignored waves and claimed 1.0)
    assert 0.0 < snap["step_occupancy"] <= 2 / W


def test_engine_step_recovers_evicted_session_via_history(registry,
                                                          forecaster):
    """A step arriving with history after its session was evicted from
    the engine cache replays the prefix — same numbers as an
    uninterrupted stream."""
    w = _windows(1, seed=17)[0]
    runner = RecurrentSessionRunner(forecaster,
                                    SessionCache(max_sessions=4))
    for t in range(CFG.window):
        want = runner.step("c", w[t])
    with ServingEngine(registry) as eng:
        half = CFG.window // 2
        for t in range(half):
            eng.step("m", "c", w[t], timeout=10.0)
        # simulate eviction: the session lives in a decode lane, so
        # spill it to the cache (the spill tier) before dropping it
        assert eng.spill_sessions() == 1
        assert eng.sessions.drop("c")
        for t in range(half, CFG.window):
            got = eng.step("m", "c", w[t], history=w[:t], timeout=10.0)
    assert got == want


# -- session cache ---------------------------------------------------------

def test_session_cache_lru_eviction():
    cache = SessionCache(max_sessions=2)
    cache.put("a", "carry-a", 8)
    cache.put("b", "carry-b", 8)
    assert cache.get("a") == "carry-a"         # refresh a; b is now LRU
    cache.put("c", "carry-c", 8)
    assert cache.get("b") is None              # evicted
    assert cache.get("a") == "carry-a"
    assert cache.get("c") == "carry-c"
    assert cache.evictions == 1
    assert cache.nbytes_in_use == 16


def test_session_cache_ttl_and_bytes():
    now = [0.0]
    cache = SessionCache(max_sessions=8, ttl_s=10.0, max_bytes=20,
                         clock=lambda: now[0])
    cache.put("a", "A", 8)
    now[0] = 5.0
    cache.put("b", "B", 8)
    now[0] = 12.0                              # a expired (idle 12s), b not
    assert cache.get("a") is None
    assert cache.get("b") == "B"
    cache.put("c", "C", 16)                    # 8 + 16 > 20 -> evict LRU (b)
    assert cache.get("b") is None
    assert cache.nbytes_in_use == 16


def test_oversize_carry_warns_and_surfaces_over_budget():
    """Regression: a single carry larger than max_bytes used to evict
    every other session and then sit over budget forever, silently. It
    still gets admitted (rejecting it would silently restart the
    client's stream), but now warns and surfaces the state in stats()."""
    cache = SessionCache(max_sessions=8, max_bytes=20)
    cache.put("a", "A", 8)
    assert cache.stats()["over_budget"] is False
    with pytest.warns(RuntimeWarning, match="over budget"):
        cache.put("big", "B", 64)
    st = cache.stats()
    assert st["over_budget"] is True
    assert st["oversize_admissions"] == 1
    assert cache.nbytes_in_use == 64 and len(cache) == 1
    # a later normal put reclaims the oversize entry via plain LRU: the
    # cache returns under budget (nothing "forever" about it any more)
    cache.put("c", "C", 8)
    assert cache.get("big") is None
    assert cache.stats()["over_budget"] is False
    assert cache.nbytes_in_use == 8


def test_session_carry_matches_full_window_recompute(forecaster):
    """Acceptance: serving a session incrementally through the cache is
    numerically identical to recomputing from the full window."""
    w = _windows(1)[0]                          # [20, 5]
    runner = RecurrentSessionRunner(forecaster, SessionCache(max_sessions=4))
    for t in range(CFG.window):
        y_inc, p_inc = runner.step("client", w[t])
    # full-window recompute through the same compiled step path (what a
    # cache miss executes): bitwise identical
    y_ref, p_ref, _ = forecaster.replay(w[None])
    assert y_inc == float(y_ref[0]) and p_inc == float(p_ref[0])
    # and equal to the batched scan apply to float32 resolution (XLA
    # fuses the full-sequence scan differently, so not bitwise)
    y_scan, _ = rnn_apply(forecaster.params, w[None], CFG)
    np.testing.assert_allclose(y_inc, float(y_scan[0]), atol=1e-6, rtol=0)
    # the session entered its device lane on the first step (one cache
    # miss) and stayed resident for the rest — the spill tier is never
    # touched again
    assert runner.resident_clients() == ["client"]
    assert runner.slot_inserts == 1 and runner.slot_spills == 0
    st = runner.cache.stats()
    assert st["misses"] == 1 and st["hits"] == 0


def test_session_eviction_recovers_via_history_replay(forecaster):
    """Evicting a session mid-stream must not change its predictions when
    the client supplies its window history on the miss."""
    w = _windows(1, seed=3)[0]
    runner = RecurrentSessionRunner(forecaster, SessionCache(max_sessions=4))
    for t in range(CFG.window):
        y_uninterrupted, _ = runner.step("c1", w[t])

    runner2 = RecurrentSessionRunner(forecaster, SessionCache(max_sessions=4))
    half = CFG.window // 2
    for t in range(half):
        runner2.step("c2", w[t])
    # simulate eviction of LIVE state: spill the lane to the cache,
    # then drop the cache entry
    assert runner2.spill(["c2"]) == 1
    assert runner2.cache.drop("c2")
    for t in range(half, CFG.window):
        y_resumed, _ = runner2.step("c2", w[t], history=w[:t])
    assert y_uninterrupted == y_resumed


def test_session_runner_on_miss_error(forecaster):
    runner = RecurrentSessionRunner(forecaster, SessionCache(max_sessions=2),
                                    on_miss="error")
    w = _windows(1)[0]
    with pytest.raises(KeyError):
        runner.step("evicted-client", w[0])            # miss, no history
    y, p = runner.step("evicted-client", w[5], history=w[:5])
    assert np.isfinite(y) and 0.0 <= p <= 1.0


def test_session_cache_telemetry_hit_rate(forecaster):
    tel = Telemetry()
    runner = RecurrentSessionRunner(
        forecaster, SessionCache(max_sessions=4, telemetry=tel))
    w = _windows(1)[0]
    for t in range(10):
        runner.step("c", w[t])
    assert tel.snapshot()["cache_hit_rate"] == pytest.approx(0.9)


# -- registry --------------------------------------------------------------

def test_registry_checkpoint_roundtrip(tmp_path, forecaster):
    reg = ModelRegistry()
    reg.register("paper", forecaster)
    path = str(tmp_path / "paper.npz")
    reg.save("paper", path)
    loaded = reg.load(path, key="paper-v2")
    assert "paper-v2" in reg
    assert loaded.cfg == forecaster.cfg
    assert loaded.tail == pytest.approx(forecaster.tail)
    assert loaded.eps == pytest.approx(forecaster.eps)
    w = _windows(3, seed=7)
    y0, p0 = forecaster.predict(w)
    y1, p1 = loaded.predict(w)
    np.testing.assert_array_equal(y0, y1)
    np.testing.assert_array_equal(p0, p1)


def test_registry_unknown_key():
    reg = ModelRegistry()
    with pytest.raises(KeyError):
        reg.get("missing")


def test_build_lstm_forecaster_is_calibrated():
    fc = build_lstm_forecaster(seed=0, n_days=120)
    assert fc.tail is not None and fc.tail["scale"] > 0
    y, p = fc.predict(_windows(2))
    assert y.shape == (2,) and p.shape == (2,)
    assert np.all((p >= 0) & (p <= 1))
