"""Request routing for the sharded serving mesh.

``ConsistentRouter`` maps client ids to shards by rendezvous (highest-
random-weight) hashing: every ``(shard, key)`` pair gets a stable
64-bit score and the key lives on the shard with the highest score.
That gives the three properties the mesh needs (asserted as hypothesis
properties in ``tests/test_serving_properties.py``): stability (same
client -> same shard, across router instances and processes — the hash
is keyed on bytes, not Python's seeded ``hash``), balance (scores are
uniform, so shards split clients evenly in expectation), and minimal
disruption (removing a shard moves only that shard's clients; adding
one moves only the clients it wins).

``ShardedServingEngine`` is the mesh: one ``EngineShard`` worker per
shard, each serving from its own ``ShardSwarm`` replica registry, with
``submit``/``predict``/``warmup`` keeping the single-engine API. A
request with a ``client_id`` is routed by the consistent hash — the
same shard every time, so that shard's session cache owns the client's
carry; anonymous requests spread over shards round-robin within their
``(model, length-bucket)`` group so every compiled bucket stays hot on
every shard it lands on.
"""

from __future__ import annotations

import hashlib
import itertools
import threading

import numpy as np

from repro.serving.engine import BatcherConfig, EngineShard
from repro.serving.swarm import ShardSwarm
from repro.serving.telemetry import Telemetry


def _score(shard_id: int, key: str) -> int:
    """Stable 64-bit rendezvous score for (shard, key)."""
    digest = hashlib.blake2b(f"{shard_id}|{key}".encode(),
                             digest_size=8).digest()
    return int.from_bytes(digest, "big")


class ConsistentRouter:
    """Rendezvous-hash assignment of string keys to shard ids."""

    def __init__(self, shard_ids):
        self._ids = sorted(set(int(s) for s in shard_ids))
        if not self._ids:
            raise ValueError("router needs at least one shard")

    @property
    def shard_ids(self) -> list[int]:
        return list(self._ids)

    def shard_for(self, key: str) -> int:
        return max(self._ids, key=lambda sid: _score(sid, str(key)))

    def add_shard(self, shard_id: int) -> None:
        if shard_id not in self._ids:
            self._ids = sorted(self._ids + [int(shard_id)])

    def remove_shard(self, shard_id: int) -> None:
        if len(self._ids) == 1:
            raise ValueError("cannot remove the last shard")
        self._ids = [s for s in self._ids if s != shard_id]


class ShardedServingEngine:
    """Router + per-shard ``EngineShard`` workers + swap-propagation
    swarm: the multi-shard serving mesh behind the single-engine API.

    ``registry`` may be a plain ``ModelRegistry`` (it becomes the
    swarm's primary; replicas are seeded from it) or an existing
    ``ShardSwarm`` (``n_shards``/``max_skew``/``transfer`` are then
    taken from it). Weight publishes against the primary — e.g. a
    ``WeightPublisher`` handed this engine's ``.swarm`` (or the plain
    registry itself) — propagate to every shard within the swarm's
    staleness bound while all shards keep draining their queues.

    Membership is LIVE: ``add_shard`` builds a worker over a fresh swarm
    replica, pulls the hosted weights and warms its compile set BEFORE
    the router sends it traffic; ``remove_shard`` takes a shard out of
    the router first, then drains its queue (nothing is dropped) and
    hands its session-cache clients to the surviving owners. Router,
    worker set, swarm replicas and attached session caches stay in
    lockstep — mutate membership through these methods, not the router.
    """

    def __init__(self, registry, config: BatcherConfig | None = None,
                 n_shards: int = 2, max_skew: int = 1,
                 transfer: str = "auto",
                 propagate_interval_s: float = 0.02, tracer=None):
        if isinstance(registry, ShardSwarm):
            self.swarm = registry
        else:
            self.swarm = ShardSwarm(n_shards, primary=registry,
                                    max_skew=max_skew, transfer=transfer)
        self.config = config or BatcherConfig()
        # one mesh-wide tracer (repro.obs.Tracer | None): the router
        # opens each request's trace and every shard chains spans onto
        # the same context, so one request = one trace fleet-wide
        self.tracer = tracer
        self.shards: dict[int, EngineShard] = {
            sid: EngineShard(self.swarm.registry_for(sid), self.config,
                             Telemetry(), shard_id=sid, tracer=tracer)
            for sid in self.swarm.shard_ids}
        # pulls into shard i count as swaps on shard i's telemetry
        self.swarm.telemetries = {sid: s.telemetry
                                  for sid, s in self.shards.items()}
        self.router = ConsistentRouter(self.shards)
        # one round-robin counter per (model, length-bucket) group, so a
        # burst within one group cycles every shard (dict setdefault and
        # itertools.count are both atomic under the GIL)
        self._anon_counters: dict[str, itertools.count] = {}
        self._propagate_interval_s = propagate_interval_s
        # serializes routing against membership changes: a submit never
        # sees a shard that left the router, a removed worker never sees
        # a late submit
        self._membership_lock = threading.Lock()
        # serializes whole add_shard/remove_shard operations (the
        # membership lock is only held for their router/worker-set
        # mutations, so traffic keeps flowing during the slow parts)
        self._admin_lock = threading.RLock()
        self._session_caches: list = []   # caches kept in membership sync
        self._warm_plan: dict[str, tuple | None] = {}
        self._running = False

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def shard_ids(self) -> list[int]:
        return sorted(self.shards)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "ShardedServingEngine":
        # attach first: publishes that happened while stopped reach the
        # replicas before any shard serves a request
        self.swarm.attach()
        for shard in list(self.shards.values()):
            shard.start()
        self._running = True
        self.swarm.start_background(self._propagate_interval_s)
        return self

    def stop(self) -> None:
        self._running = False
        for shard in list(self.shards.values()):
            shard.stop()
        self.swarm.stop_background()
        # a stopped mesh must not keep pulling weights into its replicas
        self.swarm.detach()

    def __enter__(self) -> "ShardedServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client API --------------------------------------------------------
    def shard_for(self, client_id: str) -> int:
        """The session shard that owns ``client_id`` (stable)."""
        return self.router.shard_for(str(client_id))

    def submit(self, model_key: str, window, client_id: str | None = None):
        """Enqueue one window on the owning shard; returns a Future
        resolving to (forecast, p_extreme). With a ``client_id`` the
        request is session-affine (consistent-hashed); without one it
        spreads round-robin within its (model, length-bucket) group."""
        trace = (self.tracer.start("predict", meta={"model": model_key})
                 if self.tracer is not None else None)
        payload = np.asarray(window)
        with self._membership_lock:
            if client_id is not None:
                sid = self.router.shard_for(str(client_id))
            else:
                group = \
                    f"{model_key}|{self.config.bucket_len(payload.shape[0])}"
                counter = self._anon_counters.setdefault(group,
                                                         itertools.count())
                ids = self.router.shard_ids
                sid = ids[next(counter) % len(ids)]
            if trace is not None:
                trace.mark("route", shard=sid)
            return self._shard(sid).submit(model_key, payload,
                                           client_id=client_id, trace=trace)

    def _shard(self, sid: int) -> EngineShard:
        shard = self.shards.get(sid)
        if shard is None:
            raise KeyError(
                f"router returned shard {sid} but this mesh has no such "
                f"worker (have {sorted(self.shards)}) — change membership "
                f"through add_shard/remove_shard, which keep the router "
                f"and the worker set in lockstep, not by mutating the "
                f"router directly")
        return shard

    # -- live membership ---------------------------------------------------
    def add_shard(self, shard_id: int | None = None) -> int:
        """Grow the mesh by one worker. The joining shard pulls the
        hosted weights into a fresh swarm replica and warms its compile
        set first; only then does the router start assigning it traffic
        (and attached session caches migrate exactly the clients the
        rendezvous hash re-homes onto it). Returns the new shard id."""
        self._admin_lock.acquire()
        try:
            return self._add_shard_locked(shard_id)
        finally:
            self._admin_lock.release()

    def _add_shard_locked(self, shard_id: int | None) -> int:
        with self._membership_lock:
            sid = (max(self.shards) + 1 if self.shards else 0) \
                if shard_id is None else int(shard_id)
            if sid in self.shards:
                raise ValueError(f"shard {sid} already exists")
        replica = self.swarm.add_replica(sid)     # weights pulled here
        shard = EngineShard(replica, self.config, Telemetry(),
                            shard_id=sid, tracer=self.tracer)
        try:
            if self._running:
                shard.start()
            # warm every program the hot path can hit on this worker
            # (mostly jit-cache hits: programs are shared per model
            # config) BEFORE it takes traffic
            for model_key, lengths in list(self._warm_plan.items()):
                shard.warmup(model_key, lengths=lengths)
            with self._membership_lock:
                self.shards[sid] = shard
                if self.swarm.telemetries is not None:
                    self.swarm.telemetries[sid] = shard.telemetry
                for cache in self._session_caches:
                    cache.add_shard(sid)  # adds sid to the shared router
                self.router.add_shard(sid)  # idempotent after the caches
        except Exception:
            # roll the half-joined shard back out: nothing may keep
            # routing to it or pulling weights into its replica
            with self._membership_lock:
                self.shards.pop(sid, None)
                if self.swarm.telemetries is not None:
                    self.swarm.telemetries.pop(sid, None)
                if sid in self.router.shard_ids \
                        and len(self.router.shard_ids) > 1:
                    self.router.remove_shard(sid)
            for cache in self._session_caches:
                if sid in cache.shards:
                    try:
                        cache.remove_shard(sid)
                    except (KeyError, ValueError):
                        pass
            shard.stop()
            self.swarm.remove_replica(sid)
            raise
        return sid

    def remove_shard(self, shard_id: int) -> None:
        """Shrink the mesh by one worker: the router stops assigning it
        traffic first, then its queue drains (no request is dropped) and
        attached session caches hand its clients' carries to the new
        owner shards."""
        sid = int(shard_id)
        with self._admin_lock:
            with self._membership_lock:
                if sid not in self.shards:
                    raise KeyError(f"no shard {sid}; have "
                                   f"{sorted(self.shards)}")
                if len(self.shards) == 1:
                    raise ValueError("cannot remove the last shard")
                self.router.remove_shard(sid)
                shard = self.shards.pop(sid)
            # membership lock released: the departing worker finishes
            # every request already queued on it (zero drops) while
            # traffic keeps flowing to the survivors
            shard.stop()
            for cache in self._session_caches:
                cache.remove_shard(sid)  # migrates its clients' carries
            # engine-internal streaming sessions re-home too, carries
            # intact — safe to export here: the worker has drained, so
            # no step flush is in flight on them. Lane-resident sessions
            # spill to the cache first, so the export sees the full
            # session set, decode slots included. (A shard JOINING the
            # mesh takes no carries — its clients miss and rebuild from
            # history, standard consistent-hash cache semantics.)
            shard.spill_sessions()
            if shard._session_cache is not None:
                for cid, carry, nbytes, version in shard.sessions.export():
                    tid = self.router.shard_for(cid)
                    target = self.shards.get(tid)
                    if target is not None:
                        # the carry moves to the new owner's device
                        target.sessions.put_new(
                            cid, self.swarm.place(tid, carry), nbytes,
                            version=version)
            self.swarm.remove_replica(sid)

    def predict(self, model_key: str, window,
                timeout: float | None = 30.0,
                client_id: str | None = None):
        return self.submit(model_key, window,
                           client_id=client_id).result(timeout=timeout)

    def submit_step(self, model_key: str, client_id: str, x_t,
                    history=None):
        """Enqueue one streaming step on the shard that owns
        ``client_id`` (steps are always session-affine: the client's
        carry lives in that shard's session cache). Steps flush as one
        fused decode dispatch per shard — see ``EngineShard.
        submit_step``."""
        if client_id is None:
            raise ValueError("streaming steps require a client_id (the "
                             "session key)")
        trace = (self.tracer.start("step", meta={"model": model_key})
                 if self.tracer is not None else None)
        with self._membership_lock:
            sid = self.router.shard_for(str(client_id))
            if trace is not None:
                trace.mark("route", shard=sid)
            return self._shard(sid).submit_step(model_key, client_id, x_t,
                                                history=history, trace=trace)

    def step(self, model_key: str, client_id: str, x_t, history=None,
             timeout: float | None = 30.0):
        return self.submit_step(model_key, client_id, x_t,
                                history=history).result(timeout=timeout)

    def warmup(self, model_key: str, lengths: tuple[int, ...] | None = None
               ) -> int:
        """Warm every shard's compile set. Compiled programs are shared
        process-wide per model config, so the first shard pays the
        compiles and the rest are cache hits; returns the number of
        programs the hot path can hit (per shard). The warm plan is
        remembered: a shard joining later warms the same programs before
        taking traffic."""
        self.swarm.propagate(model_key)   # every replica hosts the key
        self._warm_plan[model_key] = tuple(lengths) if lengths else None
        # snapshot: a shard joining mid-warmup must not break iteration
        return max(shard.warmup(model_key, lengths=lengths)
                   for shard in list(self.shards.values()))

    # -- ensembles ---------------------------------------------------------
    # Co-location is structural: routing keys on ``client_id`` alone
    # (never the model key), so an ensemble request lands on ONE shard
    # and fans out to its N members inside that shard's EngineShard —
    # member flushes share the shard's batch buckets and the fan-in
    # fuse never crosses a shard boundary.
    def register_ensemble(self, name: str, members, **opts):
        return self.swarm.register_ensemble(name, members, **opts)

    def swap_ensemble(self, name: str, members, **opts) -> int:
        return self.swarm.swap_ensemble(name, members, **opts)

    def ensemble(self, name: str):
        return self.swarm.ensemble(name)

    # -- observation -------------------------------------------------------
    @property
    def shard_telemetries(self) -> list[Telemetry]:
        shards = dict(self.shards)       # snapshot vs live membership
        return [shards[sid].telemetry for sid in sorted(shards)]

    def snapshot(self) -> dict:
        """Fleet-wide telemetry: per-shard counters merged by
        ``Telemetry.merge`` plus the swarm's propagation counters."""
        snap = Telemetry.merge(self.shard_telemetries)
        snap["pulls"] = self.swarm.pulls
        snap["bytes_pulled"] = self.swarm.bytes_pulled
        return snap

    def reset_clock(self) -> None:
        for tel in self.shard_telemetries:
            tel.reset_clock()

    def version_vector(self, model_key: str) -> dict:
        return self.swarm.version_vector(model_key)

    # -- sessions ----------------------------------------------------------
    def session_cache(self, **kwargs):
        """A ``ShardedSessionCache`` whose client -> shard map is THIS
        mesh's router, so a client's carry lives on the shard its
        requests are routed to. The cache is kept in membership sync:
        ``add_shard``/``remove_shard`` on this engine migrate its
        sessions along with the routing. A ``RecurrentSessionRunner``
        over it holds its sessions in decode lanes: spill them
        (``runner.spill_all()``) before a membership change, so every
        carry is in the cache when it migrates."""
        from repro.serving.sessions import ShardedSessionCache

        cache = ShardedSessionCache(router=self.router, **kwargs)
        self._session_caches.append(cache)
        return cache
