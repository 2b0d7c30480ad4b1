"""Streaming forecast serving: dynamic micro-batching, recurrent session
cache, multi-model registry, extreme-event alerting, and a sharded
serving mesh with fleet-wide weight hot-swap propagation.

Layout (DESIGN: one concern per module):

- ``engine.py``     request queue + dynamic micro-batcher (length-bucketed
                    padding, flush on max-batch or max-wait, jit-cached
                    per-bucket apply so the hot path never recompiles);
                    ``EngineShard`` is one worker, ``ServingEngine`` the
                    single-shard special case; ``submit_step`` queues
                    streaming session steps, flushed as ONE fused
                    ``slots_generate`` dispatch over the device-resident
                    decode lanes (``BatcherConfig.decode_slots``);
- ``router.py``     consistent-hash (rendezvous) routing of client ids to
                    shards + ``ShardedServingEngine``, the mesh of
                    per-shard ``EngineShard`` workers behind the same
                    ``submit``/``predict``/``warmup`` API;
- ``swarm.py``      fleet swap propagation: primary registry + per-shard
                    replicas, pull-based weight transfer under a bounded
                    staleness skew (version vector per shard);
- ``sessions.py``   the slot allocator + spill tier: sessions occupy
                    device decode lanes (LRU lane eviction spills the
                    carry to the host ``SessionCache``, bitwise-equal
                    on reload; TTL expires lanes too);
                    ``RecurrentSessionRunner.step_many`` is "ensure
                    resident -> generate -> read requested rows";
                    ``ShardedSessionCache`` shards by client id;
- ``forecaster.py`` the ``Forecaster``/``StreamingForecaster``
                    protocols — one ``predict(window) -> (forecast,
                    p_extreme)`` interface over the paper LSTM and every
                    zoo arch, with the EVT tail alert head;
                    ``DecodeSlots`` + prefill/insert/generate, the
                    device-resident decode lifecycle (lane state donated in
                    and out off-CPU);
- ``ensemble.py``   composable model-set serving: ``EnsembleForecaster``
                    fans one request across N registry members and fuses
                    ``(forecast, p_extreme)`` with EVT-weighted
                    combination (weights = each member's calibrated tail
                    prior, renormalized online from rolling error), plus
                    the anomaly-aware alert path (extreme regime widens
                    alert sensitivity and tightens flush ``max_wait``);
- ``registry.py``   multi-model hosting keyed by name, monotone model
                    versions, atomic weight swap, publish subscriptions,
                    checkpoint I/O;
- ``hotswap.py``    online-learning bridge: the local-SGD round loop
                    publishes worker-averaged params as new versions
                    without dropping in-flight requests (swarm-aware:
                    publishing into a ``ShardSwarm`` fans out fleet-wide);
- ``transport.py``  multi-process mesh: each shard an ``EngineShard`` in
                    its own OS process behind a length-prefixed msgpack
                    socket protocol; weight pushes ship serialized
                    checkpoints under the same ``max_skew`` bound, live
                    join/leave migrates session carries across processes;
                    workers can live on OTHER HOSTS (``serve_shard`` +
                    ``connect_shard``) and are heartbeat-supervised —
                    a SIGKILLed worker is detected, its futures failed
                    fast, and a local replacement respawned in place;
- ``durable.py``    durable state plane: ``DurableStore`` is a
                    content-addressed, atomic-rename, fsync'd blob +
                    manifest layout (torn writes detected by checksum,
                    keep-last-K retention, monotone version merge);
                    ``CheckpointDaemon`` snapshots session carries and
                    weight versions off the hot path; ``restore_from``
                    on the mesh cold-boots the fleet back to the last
                    acknowledged publish and re-homes checkpointed
                    carries (bitwise where fresh, history re-prime
                    where stale);
- ``telemetry.py``  latency percentiles, throughput, batch occupancy,
                    cache hit-rate, swap count, staleness at serve time,
                    per-version request counts, slot insert/spill
                    counters + lane-occupancy gauges, cross-shard
                    ``merge``.
"""

from repro.serving.durable import (CheckpointDaemon, DurableStore,
                                   DurableStoreError, restore_registry)
from repro.serving.engine import BatcherConfig, EngineShard, ServingEngine
from repro.serving.ensemble import (EnsembleForecaster, EnsembleFuser,
                                    EnsembleSlots, EnsembleSpec,
                                    fusion_weights)
from repro.serving.forecaster import (DecodeSlots, Forecaster,
                                      LSTMForecaster, StreamingForecaster,
                                      ZooForecaster, build_lstm_forecaster,
                                      build_zoo_forecaster)
from repro.serving.hotswap import WeightPublisher, stop_the_world_swap
from repro.serving.registry import ModelRegistry, RegistryEntry
from repro.serving.router import ConsistentRouter, ShardedServingEngine
from repro.serving.sessions import (RecurrentSessionRunner, SessionCache,
                                    ShardedSessionCache)
from repro.serving.swarm import ShardSwarm
from repro.serving.telemetry import Telemetry
from repro.serving.transport import (MultiProcessServingEngine, RemoteShard,
                                     connect_shard, serve_shard, spawn_shard)

__all__ = [
    "BatcherConfig",
    "CheckpointDaemon",
    "ConsistentRouter",
    "DecodeSlots",
    "DurableStore",
    "DurableStoreError",
    "EngineShard",
    "EnsembleForecaster",
    "EnsembleFuser",
    "EnsembleSlots",
    "EnsembleSpec",
    "Forecaster",
    "LSTMForecaster",
    "ModelRegistry",
    "MultiProcessServingEngine",
    "RecurrentSessionRunner",
    "RegistryEntry",
    "RemoteShard",
    "ServingEngine",
    "SessionCache",
    "ShardSwarm",
    "ShardedServingEngine",
    "ShardedSessionCache",
    "StreamingForecaster",
    "Telemetry",
    "WeightPublisher",
    "ZooForecaster",
    "build_lstm_forecaster",
    "build_zoo_forecaster",
    "connect_shard",
    "fusion_weights",
    "restore_registry",
    "serve_shard",
    "spawn_shard",
    "stop_the_world_swap",
]
