"""Multi-model hosting: forecasters keyed by name, with atomic weight
hot-swapping and checkpoint save/load through ``repro.checkpoint.io``
(the forecaster's config, EVT tail calibration, indicator thresholds and
model version ride along as metadata, so a loaded model serves
identically to the one that was saved).

Versioning: every key carries a monotonically increasing model version.
``register`` publishes version 1 (or bumps an existing key); ``swap``
atomically replaces the hosted forecaster and returns the new version.
Readers (`get`) take one reference under the lock, so an in-flight
micro-batch that already resolved its forecaster keeps serving the old
weights while the next flush picks up the new ones — no request is ever
dropped by a swap.
"""

from __future__ import annotations

import threading
import time
from typing import Any, NamedTuple

import jax

from repro.checkpoint.io import (assemble, dump_checkpoint_bytes,
                                 load_checkpoint, load_checkpoint_bytes,
                                 save_checkpoint)
from repro.models.rnn import RNNConfig, init_rnn
from repro.serving.ensemble import EnsembleSpec
from repro.serving.forecaster import LSTMForecaster, ZooForecaster


class RegistryEntry(NamedTuple):
    """Immutable snapshot of one hosted model."""

    forecaster: Any
    version: int
    published_at: float


def _rnn_cfg_meta(cfg: RNNConfig) -> dict:
    return {"input_dim": cfg.input_dim, "hidden": cfg.hidden,
            "num_layers": cfg.num_layers, "fc_dims": list(cfg.fc_dims),
            "window": cfg.window, "evl_head": cfg.evl_head}


def _rnn_cfg_from_meta(m: dict) -> RNNConfig:
    return RNNConfig(input_dim=m["input_dim"], hidden=m["hidden"],
                     num_layers=m["num_layers"],
                     fc_dims=tuple(m["fc_dims"]), window=m["window"],
                     evl_head=m["evl_head"])


class ModelRegistry:
    """Thread-safe name -> forecaster map used by the serving engine."""

    def __init__(self, clock=time.perf_counter, durable=None):
        self._lock = threading.Lock()
        self._clock = clock
        self._entries: dict[str, RegistryEntry] = {}
        self._subscribers: list = []
        self.swap_count = 0
        # ensemble specs live in a separate namespace from model keys:
        # specs are immutable and swapped whole (monotone versions), and
        # they notify their OWN subscriber list — a weight-propagation
        # swarm must not try to pull a checkpoint for a spec name
        self._ensembles: dict[str, EnsembleSpec] = {}
        self._ensemble_versions: dict[str, int] = {}
        self._ensemble_subscribers: list = []
        # durable backing (repro.serving.durable.DurableStore | None):
        # every publish lands on disk BEFORE subscribers fire — i.e.
        # before the mesh pushes it and records the workers' version-
        # vector acks — so a restored registry can never be older than
        # the last acknowledged publish
        self._durable = durable
        self.durable_commits = 0

    def attach_durable(self, store) -> None:
        """Back this registry with a ``DurableStore``: every future
        publish (register/swap/load) commits its weights + version to
        the store before acknowledgement. Models already hosted are
        committed immediately, so attaching to a warm registry persists
        its current state too."""
        with self._lock:
            self._durable = store
        for key in self.keys():
            self._durable_publish(key)
        for name in list(self._ensembles):
            self._durable_publish_ensemble(name)

    def _durable_publish(self, key: str) -> None:
        if self._durable is None:
            return
        entry = self.get_entry(key)
        ref = self._durable.put_blob(self.save_bytes(key))
        self._durable.commit(
            {"models": {key: {"version": entry.version, "ref": ref}}})
        self.durable_commits += 1

    def _durable_publish_ensemble(self, name: str) -> None:
        if self._durable is None:
            return
        with self._lock:
            spec = self._ensembles.get(name)
            version = self._ensemble_versions.get(name, 0)
        if spec is None:
            return
        self._durable.commit(
            {"ensembles": {name: {"version": version,
                                  "spec": spec.to_wire()}}})
        self.durable_commits += 1

    # -- publish notifications ---------------------------------------------
    def subscribe(self, callback) -> None:
        """Register ``callback(key, version)`` to run after every
        publication (register/swap/load). Callbacks fire OUTSIDE the
        registry lock — a subscriber may freely call back into the
        registry — and on the publishing thread. The swap-propagation
        swarm (``repro.serving.swarm``) uses this to track publishes
        made directly against a primary registry."""
        with self._lock:
            self._subscribers.append(callback)

    def unsubscribe(self, callback) -> bool:
        """Detach a subscriber; returns whether it was subscribed. A
        stopped serving mesh detaches its swarm so publishes stop
        fanning out into dead replicas."""
        with self._lock:
            try:
                self._subscribers.remove(callback)
                return True
            except ValueError:
                return False

    def _notify(self, key: str, version: int) -> None:
        with self._lock:
            subscribers = list(self._subscribers)
        for fn in subscribers:
            fn(key, version)

    # -- publication -------------------------------------------------------
    def _publish_locked(self, key: str, forecaster,
                        version: int | None) -> int:
        cur = self._entries.get(key)
        floor = cur.version if cur is not None else 0
        new_version = version if version is not None else floor + 1
        if new_version <= floor:
            raise ValueError(
                f"model version must increase monotonically: {key!r} is at "
                f"v{floor}, refusing v{new_version}")
        now = self._clock()
        try:
            # stamp before publication so readers never see a torn entry
            forecaster.version = new_version
            forecaster.published_at = now
        except AttributeError:
            pass                 # duck-typed stand-ins without attributes
        self._entries[key] = RegistryEntry(forecaster, new_version, now)
        return new_version

    def register(self, key: str, forecaster, version: int | None = None):
        """Host ``forecaster`` under ``key`` (bumping the version if the
        key already exists). Returns the forecaster."""
        with self._lock:
            v = self._publish_locked(key, forecaster, version)
        self._durable_publish(key)
        self._notify(key, v)
        return forecaster

    def swap(self, key: str, forecaster, version: int | None = None) -> int:
        """Atomically replace the forecaster hosted at ``key``; the key
        must already exist (use ``register`` for first publication).
        Returns the new (monotonically increased) version."""
        with self._lock:
            if key not in self._entries:
                raise KeyError(f"cannot swap unknown model {key!r}; "
                               f"hosted: {sorted(self._entries)}")
            v = self._publish_locked(key, forecaster, version)
            self.swap_count += 1
        self._durable_publish(key)
        self._notify(key, v)
        return v

    def unregister(self, key: str) -> None:
        with self._lock:
            self._entries.pop(key, None)

    # -- lookup ------------------------------------------------------------
    def get(self, key: str):
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                raise KeyError(f"unknown model {key!r}; hosted: "
                               f"{sorted(self._entries)}")
            return entry.forecaster

    def get_entry(self, key: str) -> RegistryEntry:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                raise KeyError(f"unknown model {key!r}; hosted: "
                               f"{sorted(self._entries)}")
            return entry

    def version(self, key: str) -> int:
        return self.get_entry(key).version

    def keys(self) -> list[str]:
        with self._lock:
            return sorted(self._entries)

    def items(self) -> list[tuple[str, Any]]:
        """Snapshot of (key, forecaster) pairs taken under the lock —
        safe to iterate while other threads register/unregister/swap."""
        with self._lock:
            return [(k, e.forecaster)
                    for k, e in sorted(self._entries.items())]

    def entries(self) -> list[tuple[str, RegistryEntry]]:
        """Snapshot of (key, entry) pairs, same safety contract as
        ``items``."""
        with self._lock:
            return sorted(self._entries.items())

    def __contains__(self, key: str) -> bool:
        with self._lock:
            return key in self._entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- ensembles ---------------------------------------------------------
    def subscribe_ensembles(self, callback) -> None:
        """Register ``callback(name, spec, version)`` to run after every
        ensemble spec publication (register/swap). Same contract as
        ``subscribe``: fires outside the lock, on the publishing
        thread."""
        with self._lock:
            self._ensemble_subscribers.append(callback)

    def unsubscribe_ensembles(self, callback) -> bool:
        with self._lock:
            try:
                self._ensemble_subscribers.remove(callback)
                return True
            except ValueError:
                return False

    def _notify_ensembles(self, name: str, spec: EnsembleSpec,
                          version: int) -> None:
        with self._lock:
            subscribers = list(self._ensemble_subscribers)
        for fn in subscribers:
            fn(name, spec, version)

    def _validate_spec_locked(self, name: str, spec: EnsembleSpec) -> None:
        if name in self._entries:
            raise ValueError(f"ensemble name {name!r} collides with a "
                             f"hosted model key")
        missing = [m for m in spec.members if m not in self._entries]
        if missing:
            raise KeyError(f"ensemble {name!r} names unhosted members "
                           f"{missing}; hosted: {sorted(self._entries)}")
        fcs = [self._entries[m].forecaster for m in spec.members]
        dims = {getattr(fc, "feature_dim", None) for fc in fcs}
        wins = {getattr(fc, "window", None) for fc in fcs}
        if len(dims) > 1 or len(wins) > 1:
            raise ValueError(
                f"ensemble {name!r} members disagree on input shape: "
                f"feature_dims {sorted(map(str, dims))}, windows "
                f"{sorted(map(str, wins))} — members must serve the "
                f"same windows")

    def register_ensemble(self, name: str, members,
                          **opts) -> EnsembleSpec:
        """Host a named model group: ``members`` is an iterable of
        already-hosted model keys (or a full ``EnsembleSpec``); ``opts``
        are ``EnsembleSpec`` fusion/anomaly fields. Re-registering an
        existing name atomically replaces the whole member list
        (monotone ensemble version) — per-member hotswap/canary
        semantics are untouched because members stay ordinary model
        keys swapped through ``swap``."""
        spec = members if isinstance(members, EnsembleSpec) \
            else EnsembleSpec(members=tuple(members), **opts)
        with self._lock:
            self._validate_spec_locked(name, spec)
            v = self._ensemble_versions.get(name, 0) + 1
            self._ensembles[name] = spec
            self._ensemble_versions[name] = v
        self._durable_publish_ensemble(name)
        self._notify_ensembles(name, spec, v)
        return spec

    def swap_ensemble(self, name: str, members, **opts) -> int:
        """Atomically replace an existing ensemble's member set;
        returns the new spec version. Readers mid-flush keep the spec
        they already resolved — the next flush fuses over the new
        members (the fuser's error state rebuilds with them)."""
        with self._lock:
            if name not in self._ensembles:
                raise KeyError(f"cannot swap unknown ensemble {name!r}; "
                               f"hosted: {sorted(self._ensembles)}")
        spec = members if isinstance(members, EnsembleSpec) \
            else EnsembleSpec(members=tuple(members), **opts)
        with self._lock:
            self._validate_spec_locked(name, spec)
            v = self._ensemble_versions[name] + 1
            self._ensembles[name] = spec
            self._ensemble_versions[name] = v
        self._durable_publish_ensemble(name)
        self._notify_ensembles(name, spec, v)
        return v

    def install_ensemble(self, name: str, spec: EnsembleSpec,
                         version: int) -> bool:
        """Replica-sync path (swarm pull / transport push): install the
        spec AT the given version, skipping stale or already-applied
        versions. No notifications — replicas don't re-propagate."""
        spec = spec if isinstance(spec, EnsembleSpec) \
            else EnsembleSpec.from_wire(spec)
        with self._lock:
            if self._ensemble_versions.get(name, 0) >= int(version):
                return False
            self._validate_spec_locked(name, spec)
            self._ensembles[name] = spec
            self._ensemble_versions[name] = int(version)
            return True

    def ensemble(self, name: str) -> EnsembleSpec | None:
        """The spec hosted under ``name`` (None when the name is not an
        ensemble — how the engine tells fan-out requests from plain
        model requests)."""
        with self._lock:
            return self._ensembles.get(name)

    def ensembles(self) -> dict[str, EnsembleSpec]:
        with self._lock:
            return dict(self._ensembles)

    def ensemble_version(self, name: str) -> int:
        with self._lock:
            if name not in self._ensembles:
                raise KeyError(f"unknown ensemble {name!r}; hosted: "
                               f"{sorted(self._ensembles)}")
            return self._ensemble_versions[name]

    def unregister_ensemble(self, name: str) -> None:
        with self._lock:
            self._ensembles.pop(name, None)
            self._ensemble_versions.pop(name, None)

    # -- persistence -------------------------------------------------------
    def _save_meta(self, key: str):
        """(forecaster, checkpoint metadata) for the hosted ``key``."""
        entry = self.get_entry(key)
        fc = entry.forecaster
        meta: dict = {"kind": fc.kind, "tail": fc.tail, "gamma": fc.gamma,
                      "version": entry.version}
        if fc.kind == "lstm":
            meta["cfg"] = _rnn_cfg_meta(fc.cfg)
            meta["eps"] = list(fc.eps)
        elif fc.kind == "zoo":
            name = fc.cfg.name
            meta["reduced"] = name.endswith("-smoke")
            meta["arch"] = name[:-len("-smoke")] if meta["reduced"] else name
            meta["window"] = fc.window
        else:
            raise ValueError(f"cannot persist forecaster kind {fc.kind!r}")
        return fc, meta

    def save(self, key: str, path: str) -> None:
        fc, meta = self._save_meta(key)
        save_checkpoint(path, fc.params, metadata=meta)

    def save_bytes(self, key: str) -> bytes:
        """The hosted model as in-memory checkpoint bytes (config, EVT
        calibration and version ride along) — what the mesh transport
        ships to shard worker processes on publish and join."""
        fc, meta = self._save_meta(key)
        return dump_checkpoint_bytes(fc.params, metadata=meta)

    def _rebuild(self, flat, meta, origin: str, device_put: bool = False):
        if not meta or "kind" not in meta:
            raise ValueError(f"{origin}: not a serving checkpoint (no kind "
                             "metadata)")
        kind = meta["kind"]
        if kind == "lstm":
            cfg = _rnn_cfg_from_meta(meta["cfg"])
            like = init_rnn(jax.random.PRNGKey(0), cfg)
            params = assemble(flat, like)
            if device_put:
                params = jax.device_put(params)
            fc = LSTMForecaster(cfg=cfg, params=params,
                                tail=meta.get("tail"),
                                eps=tuple(meta.get("eps", (0.01, 0.01))),
                                gamma=meta.get("gamma", 5.0))
        elif kind == "zoo":
            from repro.configs import get_config
            from repro.configs.base import reduced as reduce_cfg
            from repro.models.model_zoo import build_model

            acfg = get_config(meta["arch"])
            if meta.get("reduced"):
                acfg = reduce_cfg(acfg)
            like = build_model(acfg).init(jax.random.PRNGKey(0))
            params = assemble(flat, like)
            if device_put:
                params = jax.device_put(params)
            fc = ZooForecaster(cfg=acfg, params=params,
                               tail=meta.get("tail"),
                               gamma=meta.get("gamma", 5.0),
                               window=meta.get("window", 32))
        else:
            raise ValueError(f"{origin}: unknown forecaster kind {kind!r}")
        fc.version = int(meta.get("version", 0))
        return fc

    def _register_loaded(self, fc, key: str | None):
        if key is not None:
            with self._lock:
                cur = self._entries.get(key)
                saved = fc.version or None
                if cur is not None and saved is not None \
                        and saved <= cur.version:
                    saved = None     # key moved on: fall back to a bump
                v = self._publish_locked(key, fc, saved)
            self._durable_publish(key)
            self._notify(key, v)
        return fc

    def load(self, path: str, key: str | None = None):
        """Rebuild a forecaster from a checkpoint and (optionally)
        register it under ``key`` at the saved version (or the next
        monotone version if the key has already moved past it). Returns
        the forecaster."""
        flat, meta = load_checkpoint(path)
        return self._register_loaded(self._rebuild(flat, meta, path), key)

    def load_bytes(self, data: bytes, key: str | None = None,
                   device_put: bool = False):
        """``load`` for in-memory checkpoint bytes (``save_bytes``
        output). ``device_put=True`` re-materializes the parameters on
        the local default device — what a shard worker process does when
        it receives a weight push over the transport."""
        flat, meta = load_checkpoint_bytes(data)
        return self._register_loaded(
            self._rebuild(flat, meta, "<bytes>", device_put=device_put),
            key)
