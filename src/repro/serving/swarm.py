"""Fleet-wide weight propagation for the sharded serving mesh: one
*primary* ``ModelRegistry`` (where the trainer publishes) plus one
replica registry per serving shard, kept in sync by pull-based weight
transfer under a bounded staleness skew.

Model: ``WeightPublisher`` (or anyone) publishes into the swarm exactly
as into a plain registry — ``ShardSwarm`` exposes the registry facade
(``register`` / ``swap`` / ``get`` / ``version`` / ``in``) over the
primary. Every publication notifies the swarm (via
``ModelRegistry.subscribe``), which *pulls* the newest entry into each
replica that is missing the key or has fallen more than ``max_skew``
versions behind. Replicas therefore skip intermediate versions — a shard
can jump v3 -> v7 in one transfer — which is what bounded staleness
buys: per-publish fan-out cost is amortized while every shard's served
version stays within ``max_skew`` of the primary.

The skew invariant is observable atomically: ``version_vector`` /
``skew`` / ``staleness`` take the same lock the publish path holds, so
a concurrent reader never sees a shard more than ``max_skew`` versions
behind (for publishes routed through the swarm facade; publishes made
directly against the primary registry converge in the subscription
callback, one notify later).

Weight transfer reuses the launch-layer machinery: with
``transfer="device"`` a pull re-materializes the parameters through
``launch/mesh.py`` + ``launch/shardings.py`` (replicated placement on a
host mesh — the single-process stand-in for a cross-host fetch);
``transfer="reference"`` (default) shares the on-host buffers zero-copy.
"""

from __future__ import annotations

import threading
from typing import Any

from repro.serving.registry import ModelRegistry

PyTree = Any


def _params_nbytes(params) -> int:
    import jax

    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(params))


class ShardSwarm:
    """Primary registry + per-shard replicas with bounded-skew pulls.

    Args:
        n_shards: number of replica registries (one per serving shard).
        primary: the registry the trainer publishes into; a fresh one is
            created when omitted. Existing entries seed every replica.
        max_skew: how many versions a replica may lag the primary before
            a publish forces it to pull (0 = every shard sees every
            version; k = shards may skip up to k-1 intermediates).
        transfer: "reference" shares parameter buffers zero-copy;
            "device" re-places each shard's replica on its own device
            (round-robin over ``jax.local_devices()``) through the host
            mesh shardings — the stand-in for a real cross-host weight
            fetch, and what lets shard flushes execute concurrently
            when multiple (real or forced-host) devices exist;
            "auto" picks "device" iff more than one device is visible.
        telemetries: optional ``{shard_id: Telemetry}`` map; a pull into
            shard i records one swap on ``telemetries[i]``.
        durable: optional ``repro.serving.durable.DurableStore``; when
            given, the primary commits every publish to it before the
            replicas (or any subscriber) are notified.

    Membership is live: ``add_replica`` seeds a new shard's registry
    from the primary (the joining shard pulls weights before taking
    traffic) and ``remove_replica`` drops a departing shard's registry.
    """

    def __init__(self, n_shards: int, primary: ModelRegistry | None = None,
                 max_skew: int = 1, transfer: str = "auto",
                 telemetries=None, durable=None):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if max_skew < 0:
            raise ValueError("max_skew must be >= 0")
        if transfer not in ("reference", "device", "auto"):
            raise ValueError("transfer must be 'reference', 'device' or "
                             "'auto'")
        if transfer == "auto":
            import jax

            transfer = "device" if len(jax.local_devices()) > 1 \
                else "reference"
        self.primary = primary if primary is not None else ModelRegistry()
        if durable is not None:
            # publishes through this swarm land in the store before
            # replicas (or anyone else) see the new version
            self.primary.attach_durable(durable)
        self.replicas: dict[int, ModelRegistry] = {
            sid: ModelRegistry() for sid in range(n_shards)}
        self.max_skew = max_skew
        self.telemetries = telemetries
        self._transfer = transfer
        self._shard_shardings: dict[int, Any] = {}
        # RLock: the facade publish path re-enters via the subscription
        # callback on the same thread
        self._lock = threading.RLock()
        self._dirty: set[str] = set()
        self._wake = threading.Event()
        self._stop_evt = threading.Event()
        self._thread: threading.Thread | None = None
        self.pulls = 0               # replica weight transfers performed
        self.bytes_pulled = 0        # parameter bytes copied by device pulls
        self._attached = False
        with self._lock:
            for key, _ in self.primary.entries():
                self._pull_lagging_locked(key, force=True)
            self._sync_ensembles_locked()
        self.attach()

    @property
    def n_shards(self) -> int:
        return len(self.replicas)

    @property
    def shard_ids(self) -> list[int]:
        with self._lock:
            return sorted(self.replicas)

    # -- live membership ---------------------------------------------------
    def add_replica(self, shard_id: int) -> ModelRegistry:
        """Open a replica registry for a joining shard and pull every
        hosted key into it (the join-time weight fetch, BEFORE the shard
        takes traffic). Returns the new replica."""
        sid = int(shard_id)
        with self._lock:
            if sid in self.replicas:
                raise ValueError(f"shard {sid} already has a replica")
            self.replicas[sid] = ModelRegistry()
            for key in self.primary.keys():
                self._pull_locked(sid, key, self.primary.get_entry(key))
            # specs after weights: install validates members hosted
            self._sync_ensembles_locked()
            return self.replicas[sid]

    def remove_replica(self, shard_id: int) -> None:
        """Drop a departing shard's replica registry (no-op if absent —
        the mesh may remove a shard it already detached)."""
        with self._lock:
            self.replicas.pop(int(shard_id), None)
            if self.telemetries is not None:
                self.telemetries.pop(int(shard_id), None)

    # -- primary subscription lifecycle ------------------------------------
    def attach(self) -> "ShardSwarm":
        """(Re)subscribe to the primary's publish notifications and
        catch every replica up to the newest versions — publishes made
        while detached are reconciled here."""
        with self._lock:
            if not self._attached:
                self.primary.subscribe(self._on_publish)
                self.primary.subscribe_ensembles(self._on_ensemble)
                self._attached = True
        self.propagate()
        return self

    def detach(self) -> None:
        """Stop tracking the primary: publishes no longer fan out into
        this swarm's replicas (a stopped mesh must not keep pulling
        weights). Facade publishes still propagate — only *direct*
        primary publishes go unobserved until ``attach``."""
        with self._lock:
            if self._attached:
                self.primary.unsubscribe(self._on_publish)
                self.primary.unsubscribe_ensembles(self._on_ensemble)
                self._attached = False

    # -- registry facade (WeightPublisher-compatible) ----------------------
    def register(self, key: str, forecaster, version: int | None = None):
        with self._lock:
            self.primary.register(key, forecaster, version)
            if not self._attached:    # no callback fired: enforce inline
                self._on_publish(key, self.primary.version(key))
            return forecaster

    def swap(self, key: str, forecaster, version: int | None = None) -> int:
        with self._lock:
            v = self.primary.swap(key, forecaster, version)
            if not self._attached:
                self._on_publish(key, v)
            return v

    # ensemble specs take the same facade shape: publish on the
    # primary, sync into every replica atomically under the swarm lock.
    # Specs live in their OWN registry namespace with their own
    # subscriber list, so the weight path (`_on_publish` ->
    # `get_entry`) never sees a spec name.
    def register_ensemble(self, name: str, members, **opts):
        with self._lock:
            spec = self.primary.register_ensemble(name, members, **opts)
            if not self._attached:
                self._sync_ensembles_locked(name)
            return spec

    def swap_ensemble(self, name: str, members, **opts) -> int:
        with self._lock:
            v = self.primary.swap_ensemble(name, members, **opts)
            if not self._attached:
                self._sync_ensembles_locked(name)
            return v

    def ensemble(self, name: str):
        return self.primary.ensemble(name)

    def ensembles(self) -> dict:
        return self.primary.ensembles()

    def ensemble_version(self, name: str) -> int:
        return self.primary.ensemble_version(name)

    def get(self, key: str):
        return self.primary.get(key)

    def get_entry(self, key: str):
        return self.primary.get_entry(key)

    def version(self, key: str) -> int:
        return self.primary.version(key)

    def keys(self) -> list[str]:
        return self.primary.keys()

    def __contains__(self, key: str) -> bool:
        return key in self.primary

    def registry_for(self, shard_id: int) -> ModelRegistry:
        return self.replicas[shard_id]

    # -- propagation -------------------------------------------------------
    def _on_publish(self, key: str, version: int) -> None:
        # runs on the publishing thread, outside the primary's lock; for
        # facade publishes the swarm lock is already held, so the skew
        # bound below is enforced before the publish becomes observable
        with self._lock:
            self._dirty.add(key)
            self._pull_lagging_locked(key)
        self._wake.set()             # freshness sweep for skipped versions

    def _on_ensemble(self, name: str, spec, version: int) -> None:
        with self._lock:
            self._sync_ensembles_locked(name)

    def _sync_ensembles_locked(self, name: str | None = None) -> int:
        """Install the primary's ensemble specs into every replica
        (stale versions are skipped by ``install_ensemble``)."""
        names = ([name] if name is not None
                 else list(self.primary.ensembles()))
        installed = 0
        for n in names:
            spec = self.primary.ensemble(n)
            if spec is None:
                continue
            v = self.primary.ensemble_version(n)
            for replica in self.replicas.values():
                installed += bool(replica.install_ensemble(n, spec, v))
        return installed

    def _pull_lagging_locked(self, key: str, force: bool = False) -> int:
        entry = self.primary.get_entry(key)
        pulled = 0
        for sid, replica in self.replicas.items():
            have = replica.version(key) if key in replica else None
            behind = have is None or entry.version - have > self.max_skew
            if force:
                behind = have is None or have < entry.version
            if behind:
                self._pull_locked(sid, key, entry)
                pulled += 1
        return pulled

    def _pull_locked(self, sid: int, key: str, entry) -> None:
        replica = self.replicas[sid]
        if key in replica and replica.version(key) >= entry.version:
            return
        fc = entry.forecaster
        params = getattr(fc, "params", None)
        # a forecaster without with_params cannot carry re-placed params,
        # so don't device-transfer (and don't account) what would be
        # dropped — the replica shares the primary's object instead
        can_clone = params is not None and hasattr(fc, "with_params")
        moved = False
        if can_clone and self._transfer == "device":
            params = self._transfer_params(params, sid)
            moved = True
        if can_clone:
            # per-shard clone: each replica owns its version/published_at
            # stamps while sharing the compiled programs of the template
            fc = fc.with_params(params)
        if key in replica:
            replica.swap(key, fc, version=entry.version)
        else:
            replica.register(key, fc, version=entry.version)
        self.pulls += 1
        if moved:
            # only real copies count: reference pulls share buffers
            self.bytes_pulled += _params_nbytes(params)
        if self.telemetries is not None and sid in self.telemetries:
            self.telemetries[sid].record_swap()

    def _transfer_params(self, params: PyTree, sid: int) -> PyTree:
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_host_mesh
        from repro.launch.shardings import as_shardings

        sharding = self._shard_shardings.get(sid)
        if sharding is None:
            devices = jax.local_devices()
            mesh = make_host_mesh(1, 1,
                                  devices=[devices[sid % len(devices)]])
            sharding = as_shardings(mesh, P())
            self._shard_shardings[sid] = sharding
        specs = jax.tree.map(lambda _: sharding, params)
        return jax.device_put(params, specs)

    def place(self, shard_id: int, tree: PyTree) -> PyTree:
        """``tree`` on ``shard_id``'s device — how state that changes
        owner (a migrating session carry) joins the shard's programs,
        which reject arrays committed to another device. Unchanged when
        replicas share buffers (``transfer="reference"``)."""
        if self._transfer != "device":
            return tree
        return self._transfer_params(tree, int(shard_id))

    def propagate(self, key: str | None = None) -> int:
        """Pull every replica up to the primary's newest version for
        ``key`` (or for all keys): the freshness sweep, beyond what the
        skew bound forces. Returns the number of pulls performed."""
        with self._lock:
            spec = self.primary.ensemble(key) if key is not None else None
            if spec is not None:
                # an ensemble name resolves to member weights + the spec
                pulled = sum(self._pull_lagging_locked(m, force=True)
                             for m in spec.members)
                return pulled + self._sync_ensembles_locked(key)
            keys = [key] if key is not None else self.primary.keys()
            pulled = 0
            for k in keys:
                pulled += self._pull_lagging_locked(k, force=True)
                self._dirty.discard(k)
            if key is None:
                pulled += self._sync_ensembles_locked()
            return pulled

    # -- observation -------------------------------------------------------
    def version_vector(self, key: str) -> dict:
        """Atomic fleet snapshot: ``{"primary": v, 0: v0, 1: v1, ...}``
        (missing key -> 0). Taken under the publish lock, so the skew
        bound holds in every vector this returns."""
        with self._lock:
            vec: dict = {"primary": self.primary.version(key)
                         if key in self.primary else 0}
            for sid, replica in sorted(self.replicas.items()):
                vec[sid] = replica.version(key) if key in replica else 0
            return vec

    def skew(self, key: str) -> int:
        """Largest version gap between any two serving shards."""
        vec = self.version_vector(key)
        shard_vs = [v for sid, v in vec.items() if sid != "primary"]
        return max(shard_vs) - min(shard_vs)

    def staleness(self, key: str) -> int:
        """Versions the most-lagging shard is behind the primary."""
        vec = self.version_vector(key)
        shard_vs = [v for sid, v in vec.items() if sid != "primary"]
        return vec["primary"] - min(shard_vs)

    # -- background freshness sweeps ---------------------------------------
    def start_background(self, interval_s: float = 0.02) -> "ShardSwarm":
        """Run freshness sweeps on a daemon thread: replicas that the
        skew bound allowed to skip a version still converge to the
        newest weights within ~interval_s."""
        if self._thread is not None:
            return self
        self._stop_evt.clear()

        def loop() -> None:
            while not self._stop_evt.is_set():
                self._wake.wait(interval_s)
                self._wake.clear()
                if self._stop_evt.is_set():
                    return
                self.propagate()

        self._thread = threading.Thread(target=loop, name="swarm-propagate",
                                        daemon=True)
        self._thread.start()
        return self

    def stop_background(self) -> None:
        if self._thread is None:
            return
        self._stop_evt.set()
        self._wake.set()
        self._thread.join()
        self._thread = None
