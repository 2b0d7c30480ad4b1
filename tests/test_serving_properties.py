"""Property tests for the serving subsystem: session-cache invariants
under arbitrary operation sequences, step-vs-replay carry equivalence
across arbitrary evict/re-prime points, micro-batcher bucketing laws
(monotone, power-of-two, >= input), consistent-hash routing laws
(stable, balanced, minimally disruptive on shard join/leave), the
swap-propagation staleness skew bound, and the durable restore laws
(restore is monotone in acknowledged publishes under arbitrary
publish/late-checkpoint/crash/restore interleavings; restored session
frames bitwise equal a spill/reload round trip).

Example counts come from the hypothesis profile (``--hypothesis-profile=ci``
bounds them for the tier-1 timing gate); the exhaustive variants carry the
``slow`` marker.
"""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.models.rnn import RNNConfig, init_rnn
from repro.serving import (BatcherConfig, ConsistentRouter, LSTMForecaster,
                           ModelRegistry, RecurrentSessionRunner,
                           SessionCache, ShardSwarm, ShardedServingEngine,
                           ShardedSessionCache)

CFG = RNNConfig(input_dim=3, hidden=8, num_layers=1, fc_dims=(4,),
                window=8, evl_head=True)


@pytest.fixture(scope="module")
def forecaster():
    return LSTMForecaster(cfg=CFG, params=init_rnn(jax.random.PRNGKey(0),
                                                   CFG))


# -- bucketing laws --------------------------------------------------------

def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


@given(st.integers(1, 2048), st.integers(1, 2048))
@settings(deadline=None)
def test_bucket_len_monotone_pow2_geq_without_buckets(t1, t2):
    cfg = BatcherConfig()
    b1, b2 = cfg.bucket_len(t1), cfg.bucket_len(t2)
    assert b1 >= t1 and _is_pow2(b1)
    if t1 <= t2:
        assert b1 <= b2
    # idempotent: a bucketed length is its own bucket
    assert cfg.bucket_len(b1) == b1


@given(st.lists(st.integers(1, 512), min_size=1, max_size=6, unique=True),
       st.integers(1, 600), st.integers(1, 600))
@settings(deadline=None)
def test_bucket_len_monotone_geq_with_buckets(buckets, t1, t2):
    """With configured buckets a length maps to the smallest bucket that
    holds it, and past the largest bucket to the largest: ``submit``
    serves such a payload's newest rows (the LSTM is causal), so every
    shape the hot path sees is one warmup compiled."""
    cfg = BatcherConfig(length_buckets=tuple(buckets))
    b1, b2 = cfg.bucket_len(t1), cfg.bucket_len(t2)
    assert b1 in buckets                 # always one of the buckets
    if t1 <= max(buckets):
        assert b1 == min(b for b in buckets if b >= t1)
    else:
        assert b1 == max(buckets)        # clamped, never t1 itself
    if t1 <= t2:
        assert b1 <= b2


@given(st.integers(1, 64), st.integers(1, 64), st.integers(1, 64))
@settings(deadline=None)
def test_bucket_batch_monotone_pow2_geq(max_batch, n1, n2):
    cfg = BatcherConfig(max_batch=max_batch)
    # a non-pow2 max_batch is rounded DOWN at construction, so every
    # emitted batch shape is a power of two — the fixed compile-set
    # contract ("{pow2 batches} x {length buckets}") holds unconditionally
    assert _is_pow2(cfg.max_batch) and cfg.max_batch <= max_batch
    n1, n2 = min(n1, cfg.max_batch), min(n2, cfg.max_batch)  # engine
    # flushes groups of at most (the effective) max_batch requests
    b1, b2 = cfg.bucket_batch(n1), cfg.bucket_batch(n2)
    assert n1 <= b1 <= cfg.max_batch
    assert _is_pow2(b1)
    if n1 <= n2:
        assert b1 <= b2
    assert BatcherConfig(max_batch=max_batch,
                         pad_batch=False).bucket_batch(n1) == n1


# -- consistent-hash routing laws ------------------------------------------

_CLIENT_IDS = st.lists(st.text(min_size=1, max_size=12), min_size=1,
                       max_size=40, unique=True)


@given(_CLIENT_IDS, st.integers(1, 8))
@settings(deadline=None)
def test_routing_stable_across_router_instances(client_ids, n_shards):
    """Same client -> same shard, on this router and on any freshly
    built router with the same shard set (the hash is keyed on bytes,
    not Python's per-process seeded hash)."""
    r1 = ConsistentRouter(range(n_shards))
    r2 = ConsistentRouter(range(n_shards))
    for cid in client_ids:
        sid = r1.shard_for(cid)
        assert 0 <= sid < n_shards
        assert r1.shard_for(cid) == sid          # idempotent
        assert r2.shard_for(cid) == sid          # instance-independent


@given(st.integers(2, 8))
@settings(deadline=None)
def test_routing_balanced_within_tolerance(n_shards):
    """Uniform scores split a large client population evenly-ish: every
    shard within ±50% of the fair share (loose — rendezvous hashing is
    binomially concentrated, ~±4 sigma here)."""
    router = ConsistentRouter(range(n_shards))
    n_clients = 256 * n_shards
    counts = [0] * n_shards
    for i in range(n_clients):
        counts[router.shard_for(f"client-{i}")] += 1
    fair = n_clients / n_shards
    assert min(counts) >= 0.5 * fair, counts
    assert max(counts) <= 1.5 * fair, counts


@given(_CLIENT_IDS, st.integers(2, 6), st.data())
@settings(deadline=None)
def test_routing_minimal_disruption_on_leave(client_ids, n_shards, data):
    """Removing a shard moves ONLY the clients that lived on it."""
    router = ConsistentRouter(range(n_shards))
    before = {cid: router.shard_for(cid) for cid in client_ids}
    victim = data.draw(st.integers(0, n_shards - 1))
    router.remove_shard(victim)
    for cid, old in before.items():
        new = router.shard_for(cid)
        if old != victim:
            assert new == old                    # survivors keep clients
        else:
            assert new != victim                 # victims are re-homed


@given(_CLIENT_IDS, st.integers(1, 6))
@settings(deadline=None)
def test_routing_minimal_disruption_on_join(client_ids, n_shards):
    """Adding a shard only moves clients TO the new shard — no client
    is shuffled between two surviving shards."""
    router = ConsistentRouter(range(n_shards))
    before = {cid: router.shard_for(cid) for cid in client_ids}
    router.add_shard(n_shards)
    for cid, old in before.items():
        assert router.shard_for(cid) in (old, n_shards)


# -- live membership: the assignment laws extend to the full stack --------

@given(_CLIENT_IDS, st.data())
@settings(deadline=None)
def test_sharded_cache_membership_assignment_laws(client_ids, data):
    """Across interleaved add_shard/remove_shard on a live
    ``ShardedSessionCache``, only the departing/arriving shards' clients
    move — and every cached carry survives every change, retrievable
    with its original value and version stamp."""
    cache = ShardedSessionCache(n_shards=3, max_sessions=256)
    for i, cid in enumerate(client_ids):
        cache.put(cid, f"carry-{cid}", 8, version=i)
    next_sid = 3
    for _ in range(data.draw(st.integers(1, 6))):
        owners = {cid: cache.shard_for(cid) for cid in client_ids}
        if len(cache.shards) > 1 and data.draw(st.booleans()):
            victim = data.draw(st.sampled_from(sorted(cache.shards)))
            cache.remove_shard(victim)
            for cid, old in owners.items():
                new = cache.shard_for(cid)
                if old != victim:
                    assert new == old            # survivors keep clients
                else:
                    assert new != victim         # victims are re-homed
        else:
            sid = next_sid
            next_sid += 1
            cache.add_shard(sid)
            for cid, old in owners.items():
                assert cache.shard_for(cid) in (old, sid)
        # the fleet budget is re-split, never exceeded
        assert sum(s.max_sessions for s in cache.shards.values()) <= 256
        # migration is lossless: every carry still lives on its
        # (possibly new) owner shard
        for i, cid in enumerate(client_ids):
            assert cache.get_entry(cid) == (f"carry-{cid}", i)
            assert cid in cache.shards[cache.shard_for(cid)]


class _StubForecaster:
    """Minimal forecaster for engine-level membership laws: token-shaped
    windows, instant predict (no jax on the property-test hot path)."""

    feature_dim = 0
    window = 8
    version = 1
    published_at = None

    def predict(self, x, lens):
        n = len(x)
        return (np.zeros((n,), np.float32), np.zeros((n,), np.float32))


@given(_CLIENT_IDS, st.data())
@settings(deadline=None)
def test_mesh_membership_assignment_laws(client_ids, data):
    """Interleaved add_shard/remove_shard on a LIVE ShardedServingEngine:
    routing keeps the assignment laws, and every client is still served
    (on its possibly-new shard) after each change."""
    reg = ModelRegistry()
    reg.register("m", _StubForecaster())
    mesh = ShardedServingEngine(
        reg, BatcherConfig(max_batch=4, max_wait_ms=1.0,
                           length_buckets=(8,)), n_shards=2)
    with mesh:
        for _ in range(data.draw(st.integers(1, 4))):
            owners = {cid: mesh.shard_for(cid) for cid in client_ids}
            if mesh.n_shards > 1 and data.draw(st.booleans()):
                victim = data.draw(st.sampled_from(mesh.shard_ids))
                mesh.remove_shard(victim)
                for cid, old in owners.items():
                    new = mesh.shard_for(cid)
                    if old != victim:
                        assert new == old
                    else:
                        assert new != victim
            else:
                sid = mesh.add_shard()
                for cid, old in owners.items():
                    assert mesh.shard_for(cid) in (old, sid)
            # router and worker set stay in lockstep
            assert sorted(mesh.router.shard_ids) == mesh.shard_ids
        futs = [mesh.submit("m", np.zeros((8,), np.int32), client_id=cid)
                for cid in client_ids[:8]]
        for f in futs:
            assert f.result(timeout=10.0) == (0.0, 0.0)


# -- swap-propagation staleness bound --------------------------------------

@given(st.integers(1, 4), st.integers(0, 3), st.integers(1, 10))
@settings(deadline=None)
def test_swap_propagation_skew_bound(n_shards, max_skew, n_publishes):
    """After every publish through the swarm facade, no shard lags the
    primary by more than max_skew versions — and a final propagate
    converges the whole fleet to the newest version."""
    swarm = ShardSwarm(n_shards, max_skew=max_skew)
    swarm.register("m", SimpleNamespace(tag="v1"))
    for i in range(2, n_publishes + 2):
        swarm.swap("m", SimpleNamespace(tag=f"v{i}"))
        vec = swarm.version_vector("m")
        shard_vs = [v for k, v in vec.items() if k != "primary"]
        assert vec["primary"] - min(shard_vs) <= max_skew, vec
        assert max(shard_vs) <= vec["primary"]   # replicas never ahead
    swarm.propagate("m")
    vec = swarm.version_vector("m")
    assert set(vec.values()) == {n_publishes + 1}, vec


# -- session cache invariants ----------------------------------------------

_OPS = st.lists(
    st.one_of(
        st.tuples(st.just("put"), st.integers(0, 5), st.integers(1, 16)),
        st.tuples(st.just("get"), st.integers(0, 5), st.just(0)),
        st.tuples(st.just("drop"), st.integers(0, 5), st.just(0)),
        st.tuples(st.just("tick"), st.just(0), st.integers(1, 8)),
    ),
    min_size=1, max_size=60)


def _run_cache_ops(ops, max_sessions, max_bytes, ttl_s):
    now = [0.0]
    cache = SessionCache(max_sessions=max_sessions, max_bytes=max_bytes,
                         ttl_s=ttl_s, clock=lambda: now[0])
    for op, k, arg in ops:
        key = f"c{k}"
        if op == "put":
            cache.put(key, f"carry-{k}", arg, version=arg)
        elif op == "get":
            entry = cache.get_entry(key)
            if entry is not None:
                assert entry[0] == f"carry-{k}"
        elif op == "drop":
            cache.drop(key)
        else:
            now[0] += arg
        # the invariants, after every single operation:
        stats = cache.stats()
        assert len(cache) <= max_sessions
        assert stats["sessions"] == len(cache)
        assert stats["nbytes_in_use"] >= 0
        if max_bytes is not None:
            # one oversize session is admitted rather than thrashing
            assert stats["nbytes_in_use"] <= max_bytes or len(cache) == 1
        assert stats["hits"] + stats["misses"] >= 0


@given(_OPS, st.integers(1, 4),
       st.one_of(st.none(), st.integers(8, 48)),
       st.one_of(st.none(), st.floats(1.0, 16.0)))
@settings(deadline=None)
def test_session_cache_never_exceeds_capacity(ops, max_sessions, max_bytes,
                                              ttl_s):
    _run_cache_ops(ops, max_sessions, max_bytes, ttl_s)


@pytest.mark.slow
@given(_OPS, st.integers(1, 4),
       st.one_of(st.none(), st.integers(8, 48)),
       st.one_of(st.none(), st.floats(1.0, 16.0)))
@settings(max_examples=300, deadline=None)
def test_session_cache_never_exceeds_capacity_exhaustive(ops, max_sessions,
                                                         max_bytes, ttl_s):
    _run_cache_ops(ops, max_sessions, max_bytes, ttl_s)


# -- step vs replay equivalence --------------------------------------------

def _stream(forecaster, w, evict_at):
    """Serve window ``w`` step by step, dropping the session (and
    re-priming from history) at every index in ``evict_at``.  The
    session may be lane-resident, so a real eviction is spill (lane ->
    cache) followed by the cache drop."""
    runner = RecurrentSessionRunner(forecaster,
                                    SessionCache(max_sessions=4))
    y = p = None
    for t in range(w.shape[0]):
        if t in evict_at and t > 0:
            runner.spill(["c"])
            runner.cache.drop("c")
        y, p = runner.step("c", w[t], history=w[:t] if t > 0 else None)
    return y, p


@given(st.integers(0, 2 ** 16 - 1),
       st.sets(st.integers(1, CFG.window - 1), max_size=4))
@settings(deadline=None)
def test_step_replay_equivalence_across_evictions(forecaster, seed,
                                                  evict_at):
    """Evict/re-prime at arbitrary points must be invisible: the final
    forecast equals the uninterrupted session's, bitwise (both paths run
    the same compiled step function)."""
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((CFG.window, 3)).astype(np.float32) * 0.02
    y_evicted, p_evicted = _stream(forecaster, w, evict_at)
    y_clean, p_clean = _stream(forecaster, w, set())
    assert y_evicted == y_clean
    assert p_evicted == p_clean
    # and both equal a raw replay through the compiled step path
    y_ref, p_ref, _ = forecaster.replay(w[None])
    assert y_clean == float(y_ref[0]) and p_clean == float(p_ref[0])


# -- batched-step vs per-session-step equivalence --------------------------

def _check_batched_equals_sequential(forecaster, seed, n_clients, n_ticks,
                                     evictions):
    """Serving every tick as one ``step_many`` flush must produce
    BITWISE the results of the per-session ``step`` loop, under
    arbitrary mid-stream evictions (history is supplied, so evicted
    sessions re-prime in both modes)."""
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal(
        (n_ticks, n_clients, 3)).astype(np.float32) * 0.02
    evict = {(t, c % n_clients) for t, c in evictions if t < n_ticks}

    def run(batched: bool):
        runner = RecurrentSessionRunner(
            forecaster, SessionCache(max_sessions=n_clients))
        outs = []
        for t in range(n_ticks):
            for c in range(n_clients):
                if (t, c) in evict:
                    runner.spill([f"c{c}"])
                    runner.cache.drop(f"c{c}")
            hist = lambda c: xs[:t, c] if t > 0 else None  # noqa: E731
            if batched:
                outs.append(runner.step_many(
                    [(f"c{c}", xs[t, c], hist(c))
                     for c in range(n_clients)]))
            else:
                outs.append([runner.step(f"c{c}", xs[t, c],
                                         history=hist(c))
                             for c in range(n_clients)])
        return outs

    assert run(batched=True) == run(batched=False)


@given(st.integers(0, 2 ** 16 - 1),
       st.integers(2, 5),                        # clients
       st.integers(3, CFG.window),               # ticks
       st.sets(st.tuples(st.integers(1, CFG.window - 1),
                         st.integers(0, 4)), max_size=4))
@settings(deadline=None)
def test_batched_step_equals_per_session_step_across_evictions(
        forecaster, seed, n_clients, n_ticks, evictions):
    _check_batched_equals_sequential(forecaster, seed, n_clients, n_ticks,
                                     evictions)


@pytest.mark.slow
@given(st.integers(0, 2 ** 16 - 1), st.integers(2, 8),
       st.integers(3, CFG.window),
       st.sets(st.tuples(st.integers(1, CFG.window - 1),
                         st.integers(0, 7)), max_size=8))
@settings(max_examples=150, deadline=None)
def test_batched_step_equivalence_exhaustive(forecaster, seed, n_clients,
                                             n_ticks, evictions):
    _check_batched_equals_sequential(forecaster, seed, n_clients, n_ticks,
                                     evictions)


# -- slot allocator laws ----------------------------------------------------

@pytest.fixture(scope="module")
def narrow_forecaster(forecaster):
    # decode_width=2 with num_slots=2 means any third active client
    # forces an LRU spill, so arbitrary interleavings below churn
    # through insert/generate/spill/reload continuously.
    return LSTMForecaster(cfg=CFG, params=forecaster.params,
                          decode_width=2)


_SLOT_OPS = st.lists(
    st.one_of(
        st.tuples(st.just("step"),
                  st.sets(st.integers(0, 3), min_size=1, max_size=4)),
        st.tuples(st.just("spill"), st.integers(0, 3)),
        st.tuples(st.just("spill_all"), st.just(0)),
        st.tuples(st.just("evict"), st.integers(0, 3)),
    ),
    min_size=1, max_size=12)


def _check_slot_interleaving(narrow_forecaster, seed, ops):
    """The lane allocator is invisible: any interleaving of
    insert/generate (via ``step_many``), explicit spill, spill_all, and
    evict+reload through 2 lanes (every third active client churns a
    lane through spill and reload) gives BITWISE the outputs of the
    same ops over 4 lanes (which hold every client, never churning),
    and lane occupancy never exceeds ``num_slots``. Both runs apply
    the same evictions, so both re-prime from history at the same
    points."""
    n_clients, num_slots = 4, 2
    rng = np.random.default_rng(seed)
    n_ticks = max(1, sum(1 for kind, _ in ops if kind == "step"))
    xs = rng.standard_normal(
        (n_ticks, n_clients, 3)).astype(np.float32) * 0.02

    def run(num_slots: int):
        runner = RecurrentSessionRunner(
            narrow_forecaster, SessionCache(max_sessions=n_clients),
            num_slots=num_slots)
        outs, t = [], [0] * n_clients
        for kind, arg in ops:
            if kind == "step":
                items = [(f"c{c}", xs[t[c], c],
                          xs[:t[c], c] if t[c] > 0 else None)
                         for c in sorted(arg)]
                outs.append(runner.step_many(items))
                for c in arg:
                    t[c] += 1
            elif kind == "spill":
                runner.spill([f"c{arg}"])
            elif kind == "spill_all":
                runner.spill_all()
            else:                            # evict: spill then drop;
                runner.spill([f"c{arg}"])    # the session re-primes
                runner.cache.drop(f"c{arg}")  # from history on reuse
            assert len(runner.resident_clients()) <= runner.num_slots
            assert runner.slot_stats()["active"] <= runner.num_slots
        return outs

    assert run(num_slots=num_slots) == run(num_slots=n_clients)


@given(st.integers(0, 2 ** 16 - 1), _SLOT_OPS)
@settings(deadline=None)
def test_slot_churn_equals_resident_lanes_and_occupancy_bounded(
        narrow_forecaster, seed, ops):
    _check_slot_interleaving(narrow_forecaster, seed, ops)


@pytest.mark.slow
@given(st.integers(0, 2 ** 16 - 1), _SLOT_OPS)
@settings(max_examples=150, deadline=None)
def test_slot_churn_equals_resident_lanes_exhaustive(narrow_forecaster,
                                                     seed, ops):
    _check_slot_interleaving(narrow_forecaster, seed, ops)


# -- telemetry merge laws ---------------------------------------------------

_LATS = st.lists(st.floats(1e-4, 0.5, allow_nan=False,
                           allow_infinity=False), max_size=16)
_SHARD_EVENTS = st.fixed_dictionaries({
    "lats": _LATS,                               # one predict flush
    "version": st.integers(1, 3),
    "batches": st.lists(st.integers(1, 8), max_size=8),
    "step_lats": _LATS,
    "swaps": st.integers(0, 3),
    "hits": st.integers(0, 5),
    "misses": st.integers(0, 5),
    "evictions": st.integers(0, 2),
    "slot_inserts": st.integers(0, 4),
    "slot_spills": st.integers(0, 4),
})


@given(st.lists(_SHARD_EVENTS, min_size=1, max_size=4))
@settings(deadline=None)
def test_telemetry_merge_laws(shards):
    """The fleet view must be an exact aggregate of the per-shard views:
    counters sum, per-version attribution sums key-wise, and pooled
    percentiles are actual recorded samples bounded by the per-shard
    sample extrema (nearest-rank on the pooled reservoir)."""
    from repro.serving.telemetry import Telemetry

    tels = []
    for ev in shards:
        tel = Telemetry()
        tel.record_requests(ev["lats"], version=ev["version"])
        for n_real in ev["batches"]:
            tel.record_batch(n_real, 8)
        if ev["step_lats"]:
            tel.record_step_batch(ev["step_lats"], n_padded=8)
        tel.record_swap(ev["swaps"])
        for _ in range(ev["hits"]):
            tel.record_cache(True)
        for _ in range(ev["misses"]):
            tel.record_cache(False)
        tel.record_eviction(ev["evictions"])
        tel.record_slots(inserts=ev["slot_inserts"],
                         spills=ev["slot_spills"],
                         active=min(ev["slot_inserts"], 4), lanes=4)
        tels.append(tel)

    snaps = [tel.snapshot() for tel in tels]
    merged = Telemetry.merge(tels)

    # counters: merged == sum over shards, exactly
    for key in ("requests", "batches", "swaps", "cache_evictions",
                "step_requests", "step_batches", "slot_inserts",
                "slot_spills", "slot_active", "slot_lanes"):
        assert merged[key] == sum(s[key] for s in snaps), key
    assert merged["shards"] == len(tels)
    assert merged["requests_by_shard"] == [s["requests"] for s in snaps]

    # per-version attribution sums key-wise (no version lost or invented)
    by_version: dict[int, int] = {}
    for s in snaps:
        for v, n in s["requests_by_version"].items():
            by_version[v] = by_version.get(v, 0) + n
    assert merged["requests_by_version"] == by_version
    assert sum(by_version.values()) == merged["requests"]

    # pooled percentiles: nearest-rank picks an ACTUAL sample, so the
    # fleet percentile is bounded by the per-shard sample extrema and
    # monotone in p (pooling can't extrapolate beyond any shard's data)
    all_lats = [x for ev in shards for x in ev["lats"]]
    if all_lats:
        lo, hi = min(all_lats) * 1e3, max(all_lats) * 1e3
        assert lo <= merged["p50_ms"] <= hi
        assert lo <= merged["p95_ms"] <= hi
        assert lo <= merged["p99_ms"] <= hi
        assert merged["p50_ms"] <= merged["p95_ms"] <= merged["p99_ms"]
    else:
        assert merged["p50_ms"] == merged["p99_ms"] == 0.0
    all_batches = [n for ev in shards for n in ev["batches"]]
    if all_batches:
        assert min(all_batches) <= merged["batch_p50"] <= max(all_batches)
        assert min(all_batches) <= merged["batch_p95"] <= max(all_batches)
        assert merged["batch_p50"] <= merged["batch_p95"]
        assert merged["mean_batch"] == pytest.approx(
            sum(all_batches) / len(all_batches))

    # derived ratios recompute from the summed counters
    hits = sum(ev["hits"] for ev in shards)
    lookups = hits + sum(ev["misses"] for ev in shards)
    assert merged["cache_hit_rate"] == pytest.approx(
        hits / lookups if lookups else 0.0)


# -- ensemble fusion laws ----------------------------------------------------

_ANY_FLOATS = st.floats(allow_nan=True, allow_infinity=True, width=64)


@given(st.integers(1, 6), st.data())
@settings(deadline=None)
def test_fusion_weights_convex_for_any_history(n, data):
    """Fusion weights are a convex combination for ANY priors/errors —
    nan, inf, negative, zero — and a single member always gets exactly
    weight 1.0."""
    from repro.serving import fusion_weights

    priors = data.draw(st.lists(_ANY_FLOATS, min_size=n, max_size=n))
    errors = data.draw(st.lists(_ANY_FLOATS, min_size=n, max_size=n))
    temp = data.draw(_ANY_FLOATS)
    w = fusion_weights(priors, errors, temperature=temp)
    assert w.shape == (n,)
    assert np.all(np.isfinite(w)) and np.all(w >= 0.0)
    assert np.isclose(w.sum(), 1.0, atol=1e-12)
    if n == 1:
        assert w[0] == 1.0


@given(st.integers(2, 4), st.lists(
    st.lists(_ANY_FLOATS, min_size=2, max_size=4), max_size=8),
    st.data())
@settings(deadline=None)
def test_fuser_weights_convex_under_arbitrary_error_updates(
        n, histories, data):
    """The rolling-error EWMA keeps the online weights convex no matter
    what error sequences arrive (supervised updates with nan/inf
    included)."""
    from repro.serving import EnsembleFuser, EnsembleSpec

    spec = EnsembleSpec(
        members=tuple(f"m{i}" for i in range(n)),
        error_half_life=data.draw(st.floats(0.1, 256.0)),
        temperature=data.draw(st.floats(0.01, 16.0)))
    fuser = EnsembleFuser(n, spec)
    for errs in histories:
        errs = (errs + [0.0] * n)[:n]
        fuser.record_errors(errs)
        w = fuser.weights()
        assert np.all(np.isfinite(w)) and np.all(w >= 0.0)
        assert np.isclose(w.sum(), 1.0, atol=1e-12)
        assert np.all(np.isfinite(fuser.errors()))


@given(st.integers(0, 2 ** 16 - 1), st.integers(1, 4))
@settings(deadline=None, max_examples=25)
def test_singleton_ensemble_bitwise_equals_member(forecaster, seed,
                                                  n_steps):
    """A single-member EnsembleForecaster is bitwise-identical to the
    member served solo on every path: step chains, replay, and the
    slotted decode lifecycle (insert -> generate -> extract)."""
    from repro.serving import EnsembleForecaster

    reg = ModelRegistry()
    reg.register("m", forecaster)
    reg.register_ensemble("solo", ["m"])
    ens = EnsembleForecaster(reg, "solo")
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal((n_steps, 1, CFG.input_dim)).astype(
        np.float32) * 0.02
    win = rng.standard_normal((1, CFG.window, CFG.input_dim)).astype(
        np.float32) * 0.02

    # step chain
    c_m, c_e = forecaster.init_carry(), ens.init_carry()
    for t in range(n_steps):
        y_m, p_m, c_m = forecaster.step(xs[t], c_m)
        y_e, p_e, c_e = ens.step(xs[t], c_e)
        assert np.array_equal(y_m, y_e) and np.array_equal(p_m, p_e)
    # replay
    ry_m, rp_m, rc_m = forecaster.replay(win)
    ry_e, rp_e, rc_e = ens.replay(win)
    assert np.array_equal(ry_m, ry_e) and np.array_equal(rp_m, rp_e)
    # slots lifecycle, from the replayed carries
    s_m, s_e = forecaster.init_slots(4), ens.init_slots(4)
    forecaster.insert(s_m, 1, rc_m)
    ens.insert(s_e, 1, {"m": rc_m})
    for t in range(n_steps):
        x_m = np.zeros((s_m.num_slots, CFG.input_dim), np.float32)
        x_m[1] = xs[t][0]
        gy_m, gp_m, _ = forecaster.generate(s_m, x_m, lanes=[1])
        gy_e, gp_e, _ = ens.generate(
            s_e, x_m[:s_e.num_slots], lanes=[1])
        assert np.asarray(gy_m)[1] == np.asarray(gy_e)[1]
        assert np.asarray(gp_m)[1] == np.asarray(gp_e)[1]
    out_m = forecaster.extract(s_m, 1)
    out_e = ens.extract(s_e, 1)
    for (h_m, c2_m), (h_e, c2_e) in zip(out_m, out_e["m"]):
        assert np.array_equal(np.asarray(h_m), np.asarray(h_e))
        assert np.array_equal(np.asarray(c2_m), np.asarray(c2_e))

# -- durable restore laws --------------------------------------------------

@pytest.fixture(scope="module")
def published_models():
    """Distinct parameter sets for successive publishes (v1, v2, ...
    rotate through them)."""
    return [LSTMForecaster(cfg=CFG,
                           params=init_rnn(jax.random.PRNGKey(s), CFG))
            for s in range(3)]


_DURABLE_OPS = st.lists(
    st.one_of(st.just(("publish",)),
              st.tuples(st.just("late-checkpoint"), st.integers(0, 15)),
              st.just(("crash",)),
              st.just(("restore",))),
    min_size=1, max_size=10)


@given(_DURABLE_OPS, st.integers(1, 3))
@settings(deadline=None, max_examples=15)
def test_restore_is_monotone_in_acknowledged_versions(published_models,
                                                      ops, keep_last):
    """Arbitrary interleavings of publish, LATE daemon checkpoint (a
    snapshot serialized any number of publishes ago, committed after
    them), crash (fresh process, cold-boot restore) and restore must
    never resurrect a weight version older than the last acknowledged
    publish: the durable commit precedes the publish ack, and the
    manifest merge is monotone per versioned entry."""
    import shutil
    import tempfile

    from repro.serving.durable import DurableStore, restore_registry

    root = tempfile.mkdtemp(prefix="durable-law-")
    try:
        store = DurableStore(root, keep_last=keep_last)
        registry = ModelRegistry(durable=store)
        acked = 0
        history = []        # (version, ref) of every publish: stale fodder
        for op in ops:
            if op[0] == "publish":
                fc = published_models[acked % len(published_models)]
                if "m" in registry:
                    registry.swap("m", fc)
                else:
                    registry.register("m", fc)
                acked = registry.version("m")
                history.append(
                    (acked, store.put_blob(registry.save_bytes("m"))))
            elif op[0] == "late-checkpoint":
                if history:
                    v, ref = history[op[1] % len(history)]
                    store.commit({"models": {"m": {"version": v,
                                                   "ref": ref}}})
            elif op[0] == "crash":
                store = DurableStore(root, keep_last=keep_last)
                registry = ModelRegistry()          # process died; disk kept
                restore_registry(store, registry)   # the cold-boot recipe
                registry.attach_durable(store)
                if acked:
                    assert registry.version("m") == acked
            else:
                cold = ModelRegistry()
                out = restore_registry(store, cold)
                if acked:
                    assert out is not None and "m" in out["models"]
                    assert cold.version("m") == acked
                else:
                    assert out is None or "m" not in out["models"]
        cold = ModelRegistry()
        restore_registry(store, cold)
        if acked:                     # final restore lands on the last ack
            assert cold.version("m") == acked
            want = published_models[(acked - 1) % len(published_models)]
            for a, b in zip(jax.tree_util.tree_leaves(want.params),
                            jax.tree_util.tree_leaves(cold.get("m").params)):
                assert np.array_equal(np.asarray(a), np.asarray(b))
    finally:
        shutil.rmtree(root, ignore_errors=True)


@given(st.integers(0, 2 ** 16 - 1), st.integers(1, 4),
       st.integers(1, 6))
@settings(deadline=None, max_examples=15)
def test_restored_sessions_equal_spill_reload_roundtrip(forecaster, seed,
                                                        n_clients, n_ticks):
    """Checkpointed session frames, round-tripped through the store's
    blob codec and re-installed into a cold cache, are bitwise what a
    plain spill/reload of the live cache holds — restore is replay-free
    for fresh sessions."""
    import shutil
    import tempfile

    from repro.serving.durable import (DurableStore, pack_frames_blob,
                                       pack_session_frame,
                                       unpack_frames_blob,
                                       unpack_session_frame)

    rng = np.random.default_rng(seed)
    runner = RecurrentSessionRunner(forecaster,
                                    SessionCache(max_sessions=64))
    for t in range(n_ticks):
        runner.step_many([
            (f"c{i}", rng.standard_normal(3).astype(np.float32) * 0.02,
             None) for i in range(n_clients)])
    runner.spill()
    live = runner.cache.snapshot()
    frames = [pack_session_frame(cid, carry, nbytes, version)
              for cid, carry, nbytes, version in live]
    root = tempfile.mkdtemp(prefix="durable-rt-")
    try:
        store = DurableStore(root)
        blob = store.get_blob(store.put_blob(pack_frames_blob(frames)))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    cold = SessionCache(max_sessions=64)
    for frame in unpack_frames_blob(blob):
        cid, carry, nbytes, version = unpack_session_frame(frame)
        assert cold.put_new(cid, carry, nbytes, version=version)
    restored = {cid: (carry, nbytes, version)
                for cid, carry, nbytes, version in cold.snapshot()}
    assert set(restored) == {cid for cid, *_ in live}
    for cid, carry, nbytes, version in live:
        got, got_n, got_v = restored[cid]
        assert (got_n, got_v) == (nbytes, version)
        a_leaves = jax.tree_util.tree_leaves(carry)
        b_leaves = jax.tree_util.tree_leaves(got)
        assert len(a_leaves) == len(b_leaves)
        for a, b in zip(a_leaves, b_leaves):
            assert np.array_equal(np.asarray(a), np.asarray(b))
            assert np.asarray(b).dtype == np.asarray(a).dtype
