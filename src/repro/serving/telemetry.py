"""Serving telemetry: latency percentiles, throughput, batch occupancy
and cache hit-rate. Pure stdlib, thread-safe, O(1) per event — cheap
enough to sit on the hot path of the micro-batcher.
"""

from __future__ import annotations

import threading
import time
from collections import deque


def _pick(data: list[float], p: float) -> float:
    """Nearest-rank percentile from an ALREADY SORTED sample list."""
    if not data:
        return 0.0
    k = min(len(data) - 1, max(0, int(round(p / 100.0 * (len(data) - 1)))))
    return data[k]


def _percentile(data: list[float], p: float) -> float:
    return _pick(sorted(data), p)


def _percentiles(data: list[float], ps) -> list[float]:
    """Several percentiles of one sample set with a SINGLE sort —
    ``snapshot``/``merge`` ask for p50/p95/p99 of the same <= 8192-sample
    reservoir, and sorting it once per snapshot instead of once per
    percentile is a 3x on the read path."""
    data = sorted(data)
    return [_pick(data, p) for p in ps]


class _Reservoir:
    """Fixed-size ring of the most recent samples (enough for stable
    p50/p95/p99 at serving rates without unbounded memory)."""

    def __init__(self, capacity: int = 8192):
        self.capacity = capacity
        self._buf: list[float] = []
        self._pos = 0

    def add(self, value: float) -> None:
        if len(self._buf) < self.capacity:
            self._buf.append(value)
        else:
            self._buf[self._pos] = value
            self._pos = (self._pos + 1) % self.capacity

    def percentiles(self, ps) -> list[float]:
        return _percentiles(self._buf, ps)


class Telemetry:
    """Counters + reservoirs for one serving engine (or one model)."""

    # per-client attribution tracks at most this many distinct client
    # ids (like the reservoirs, memory must stay bounded on a
    # long-running engine); requests from clients beyond the cap are
    # counted in ``untracked_client_requests``
    MAX_TRACKED_CLIENTS = 4096

    # sampled time-series ring: ``sample()`` snapshots land here (the
    # metrics endpoint's /history and the future autoscaler read it)
    HISTORY_CAPACITY = 512

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._lock = threading.Lock()
        self._t0 = clock()
        self._history: deque[dict] = deque(maxlen=self.HISTORY_CAPACITY)
        self._sampler: threading.Thread | None = None
        self._sampler_stop = threading.Event()
        self.requests = 0
        self.batches = 0
        self.padded_slots = 0      # total batch capacity dispatched
        self.real_slots = 0        # non-padding rows dispatched
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_evictions = 0
        self.swaps = 0             # weight hot-swaps observed (cumulative)
        self.reprimes = 0          # session carries re-primed after a swap
        # durable restore: carries re-installed from the checkpoint
        # store on a cold restart / partition re-adoption, and how many
        # of them were stamped with a superseded weight version (those
        # re-prime from history on first touch instead of resuming)
        self.restored_sessions = 0
        self.restored_stale = 0
        self.requests_by_version: dict[int, int] = {}
        self.requests_by_client: dict[str, int] = {}
        # per-model attribution: every flush is tagged with its model
        # key, so /metrics can tell ensemble members apart (each member
        # of a fan-out flushes under its own key)
        self.requests_by_model: dict[str, int] = {}
        self.untracked_client_requests = 0
        # ensemble serving: fused fan-in results and their alert
        # decisions, plus the anomaly-mode gauge (1 while any hosted
        # ensemble's fused stream is extreme)
        self.ensemble_requests = 0
        self.ensemble_alerts = 0
        self.anomaly_mode = 0
        # batched decode path: streaming steps flushed as fused batches
        self.step_requests = 0
        self.step_batches = 0
        self.step_real_slots = 0    # sessions stepped
        self.step_padded_slots = 0  # decode-lane slots dispatched
        # device-resident decode slots: cumulative insert/spill traffic
        # (like the cache counters) plus occupancy gauges (last seen)
        self.slot_inserts = 0       # sessions written into a device lane
        self.slot_spills = 0        # lane carries spilled to the cache
        self.slot_active = 0        # gauge: lanes currently occupied
        self.slot_lanes = 0         # gauge: lanes configured
        self._latency = _Reservoir()
        self._staleness = _Reservoir()   # model age at serve time (s)
        self._batch_sizes = _Reservoir()
        self._step_latency = _Reservoir()

    # -- recording ---------------------------------------------------------
    def record_request(self, latency_s: float, version: int | None = None,
                       staleness_s: float | None = None) -> None:
        with self._lock:
            self.requests += 1
            self._latency.add(latency_s)
            if version is not None:
                self.requests_by_version[version] = \
                    self.requests_by_version.get(version, 0) + 1
            if staleness_s is not None:
                self._staleness.add(staleness_s)

    def record_requests(self, latencies_s, version: int | None = None,
                        staleness_s: float | None = None,
                        client_ids=None, model: str | None = None) -> None:
        """Record one flush's worth of requests under a single lock
        acquisition (the micro-batcher calls this once per flush instead
        of ``record_request`` per row — less lock churn on the hot
        path). All rows share the flush's version/staleness/``model``
        key; ``client_ids`` (optional, one per row, None entries for
        anonymous requests) feed per-client attribution."""
        with self._lock:
            for lat in latencies_s:
                self.requests += 1
                self._latency.add(lat)
                if staleness_s is not None:
                    self._staleness.add(staleness_s)
            if version is not None and latencies_s:
                self.requests_by_version[version] = \
                    self.requests_by_version.get(version, 0) \
                    + len(latencies_s)
            if model is not None and latencies_s:
                self.requests_by_model[model] = \
                    self.requests_by_model.get(model, 0) + len(latencies_s)
            if client_ids:
                for cid in client_ids:
                    if cid is None:
                        continue
                    if cid in self.requests_by_client or \
                            len(self.requests_by_client) \
                            < self.MAX_TRACKED_CLIENTS:
                        self.requests_by_client[cid] = \
                            self.requests_by_client.get(cid, 0) + 1
                    else:
                        self.untracked_client_requests += 1

    def record_swap(self, n: int = 1) -> None:
        with self._lock:
            self.swaps += n

    def record_reprime(self, n: int = 1) -> None:
        with self._lock:
            self.reprimes += n

    def record_restore(self, n: int = 1, stale: int = 0) -> None:
        """``n`` session carries re-installed from the durable store,
        ``stale`` of which carry a superseded weight version (they fall
        back to history re-prime on their next step)."""
        with self._lock:
            self.restored_sessions += n
            self.restored_stale += stale

    def record_step_batch(self, latencies_s, n_padded: int | None = None,
                          model: str | None = None) -> None:
        """One batched streaming-step flush: per-step queue+serve
        latencies under a single lock acquisition, plus decode-lane
        occupancy (``n_padded`` = lane slots dispatched, defaults to the
        real count). ``model`` feeds the same per-model attribution as
        ``record_requests``."""
        latencies_s = list(latencies_s)
        with self._lock:
            self.step_batches += 1
            self.step_requests += len(latencies_s)
            self.step_real_slots += len(latencies_s)
            self.step_padded_slots += (n_padded if n_padded is not None
                                       else len(latencies_s))
            for lat in latencies_s:
                self._step_latency.add(lat)
            if model is not None and latencies_s:
                self.requests_by_model[model] = \
                    self.requests_by_model.get(model, 0) + len(latencies_s)

    def record_ensemble(self, latency_s: float | None = None,
                        alerts: int = 0, n: int = 1,
                        anomaly: bool = False) -> None:
        """``n`` fused ensemble results (one fan-in predict, or a step
        flush's rows), ``alerts`` of which crossed the effective alert
        threshold; ``anomaly`` is the fuser's current mode (gauge)."""
        with self._lock:
            self.ensemble_requests += n
            self.ensemble_alerts += alerts
            self.anomaly_mode = int(bool(anomaly))
            if latency_s is not None:
                self._latency.add(latency_s)

    def record_anomaly(self, anomaly: bool) -> None:
        with self._lock:
            self.anomaly_mode = int(bool(anomaly))

    def record_batch(self, n_real: int, n_padded: int) -> None:
        with self._lock:
            self.batches += 1
            self.real_slots += n_real
            self.padded_slots += n_padded
            self._batch_sizes.add(float(n_real))

    def record_cache(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.cache_hits += 1
            else:
                self.cache_misses += 1

    def record_eviction(self, n: int = 1) -> None:
        with self._lock:
            self.cache_evictions += n

    def record_slots(self, inserts: int = 0, spills: int = 0,
                     active: int | None = None,
                     lanes: int | None = None) -> None:
        """Device-resident decode-slot traffic: ``inserts``/``spills``
        accumulate (steady state adds zero of each — that is the point);
        ``active``/``lanes`` are occupancy gauges overwritten with the
        latest observation."""
        with self._lock:
            self.slot_inserts += inserts
            self.slot_spills += spills
            if active is not None:
                self.slot_active = active
            if lanes is not None:
                self.slot_lanes = lanes

    # -- reading -----------------------------------------------------------
    def _raw_samples_locked(self) -> dict:
        return {
            "latency_s": list(self._latency._buf),
            "staleness_s": list(self._staleness._buf),
            "batch_sizes": list(self._batch_sizes._buf),
            "step_latency_s": list(self._step_latency._buf),
        }

    def raw_samples(self) -> dict:
        """Copies of the raw reservoir samples (latency / staleness /
        batch size / step latency), taken under the telemetry lock.
        This is THE way to read the reservoirs from another thread —
        the buffers themselves are mutated concurrently by flush
        workers, so reaching into ``_latency._buf`` directly races the
        ring writes (the transport ``stats`` op used to do exactly
        that)."""
        with self._lock:
            return self._raw_samples_locked()

    def snapshot(self) -> dict:
        with self._lock:
            elapsed = max(self._clock() - self._t0, 1e-9)
            lookups = self.cache_hits + self.cache_misses
            # one sort per reservoir per snapshot (not one per
            # percentile) — see _percentiles
            lat50, lat95, lat99 = self._latency.percentiles((50, 95, 99))
            stale50, stale95 = self._staleness.percentiles((50, 95))
            batch50, batch95 = self._batch_sizes.percentiles((50, 95))
            step50, step95 = self._step_latency.percentiles((50, 95))
            return {
                "requests": self.requests,
                "batches": self.batches,
                "throughput_rps": self.requests / elapsed,
                "p50_ms": lat50 * 1e3,
                "p95_ms": lat95 * 1e3,
                "p99_ms": lat99 * 1e3,
                "mean_batch": (self.real_slots / self.batches
                               if self.batches else 0.0),
                "batch_p50": batch50,
                "batch_p95": batch95,
                "batch_occupancy": (self.real_slots / self.padded_slots
                                    if self.padded_slots else 0.0),
                "cache_hit_rate": (self.cache_hits / lookups
                                   if lookups else 0.0),
                "cache_evictions": self.cache_evictions,
                "swaps": self.swaps,
                "reprimes": self.reprimes,
                "restored_sessions": self.restored_sessions,
                "restored_stale": self.restored_stale,
                "staleness_p50_s": stale50,
                "staleness_p95_s": stale95,
                "requests_by_version": dict(self.requests_by_version),
                "requests_by_client": dict(self.requests_by_client),
                "requests_by_model": dict(self.requests_by_model),
                "unique_clients": len(self.requests_by_client),
                "untracked_client_requests":
                    self.untracked_client_requests,
                "ensemble_requests": self.ensemble_requests,
                "ensemble_alerts": self.ensemble_alerts,
                "anomaly_mode": self.anomaly_mode,
                "step_requests": self.step_requests,
                "step_batches": self.step_batches,
                "steps_per_s": self.step_requests / elapsed,
                "mean_step_batch": (self.step_real_slots / self.step_batches
                                    if self.step_batches else 0.0),
                "step_occupancy": (self.step_real_slots
                                   / self.step_padded_slots
                                   if self.step_padded_slots else 0.0),
                "step_p50_ms": step50 * 1e3,
                "step_p95_ms": step95 * 1e3,
                "slot_inserts": self.slot_inserts,
                "slot_spills": self.slot_spills,
                "slot_active": self.slot_active,
                "slot_lanes": self.slot_lanes,
                "slot_occupancy": (self.slot_active / self.slot_lanes
                                   if self.slot_lanes else 0.0),
            }

    # -- sampled time series ----------------------------------------------
    def sample(self) -> dict:
        """One snapshot, timestamped and appended to the ``history``
        ring — the time-series view of this engine's own metrics."""
        snap = self.snapshot()
        snap["ts"] = time.time()
        self._history.append(snap)
        return snap

    def history(self, n: int | None = None) -> list[dict]:
        """The sampled snapshot series, oldest first (bounded ring of
        ``HISTORY_CAPACITY`` samples)."""
        out = list(self._history)
        return out if n is None else out[-n:]

    def start_sampler(self, interval_s: float = 1.0) -> None:
        """Sample ``snapshot()`` into the history ring every
        ``interval_s`` on a daemon thread (idempotent)."""
        if interval_s <= 0:
            raise ValueError("interval_s must be > 0")
        if self._sampler is not None:
            return
        self._sampler_stop.clear()

        def loop() -> None:
            while not self._sampler_stop.wait(interval_s):
                self.sample()

        self._sampler = threading.Thread(target=loop,
                                         name="telemetry-sampler",
                                         daemon=True)
        self._sampler.start()

    def stop_sampler(self) -> None:
        if self._sampler is None:
            return
        self._sampler_stop.set()
        self._sampler.join()
        self._sampler = None

    def reset_clock(self) -> None:
        """Restart the measurement window (e.g. after jit warmup):
        throughput counters AND latency/batch reservoirs, so a snapshot
        never mixes pre-reset samples with the new window. Cache and swap
        counters are cumulative state and are kept; per-version request
        counts follow the measurement window."""
        with self._lock:
            self._t0 = self._clock()
            self.requests = 0
            self.batches = 0
            self.real_slots = 0
            self.padded_slots = 0
            self.requests_by_version = {}
            self.requests_by_client = {}
            self.requests_by_model = {}
            self.untracked_client_requests = 0
            self.ensemble_requests = 0
            self.ensemble_alerts = 0
            self.step_requests = 0
            self.step_batches = 0
            self.step_real_slots = 0
            self.step_padded_slots = 0
            self._latency = _Reservoir()
            self._staleness = _Reservoir()
            self._batch_sizes = _Reservoir()
            self._step_latency = _Reservoir()

    @staticmethod
    def merge(telemetries) -> dict:
        """Cross-shard fleet snapshot: counters summed, latency /
        staleness / batch reservoirs pooled for fleet percentiles, and
        per-version request counts merged — so per-version attribution
        stays meaningful mesh-wide. Throughput is total requests over
        the longest shard window (shards serve concurrently).

        Returns the same keys as ``snapshot`` (``Telemetry.format``
        accepts the result) plus ``"shards"`` and per-shard request
        counts under ``"requests_by_shard"``."""
        telemetries = list(telemetries)
        lat: list[float] = []
        stale: list[float] = []
        bsz: list[float] = []
        step_lat: list[float] = []
        totals = {"requests": 0, "batches": 0, "real_slots": 0,
                  "padded_slots": 0, "cache_hits": 0, "cache_misses": 0,
                  "cache_evictions": 0, "swaps": 0, "reprimes": 0,
                  "restored_sessions": 0, "restored_stale": 0,
                  "untracked_client_requests": 0, "step_requests": 0,
                  "step_batches": 0, "step_real_slots": 0,
                  "step_padded_slots": 0, "slot_inserts": 0,
                  "slot_spills": 0, "slot_active": 0, "slot_lanes": 0,
                  "ensemble_requests": 0, "ensemble_alerts": 0}
        by_version: dict[int, int] = {}
        by_client: dict[str, int] = {}
        by_model: dict[str, int] = {}
        by_shard: list[int] = []
        anomaly = 0
        elapsed = 1e-9
        for tel in telemetries:
            with tel._lock:
                elapsed = max(elapsed, tel._clock() - tel._t0)
                for k in totals:
                    totals[k] += getattr(tel, k)
                anomaly = max(anomaly, tel.anomaly_mode)
                by_shard.append(tel.requests)
                for v, n in tel.requests_by_version.items():
                    by_version[v] = by_version.get(v, 0) + n
                for c, n in tel.requests_by_client.items():
                    by_client[c] = by_client.get(c, 0) + n
                for m, n in tel.requests_by_model.items():
                    by_model[m] = by_model.get(m, 0) + n
                raw = tel._raw_samples_locked()
                lat.extend(raw["latency_s"])
                stale.extend(raw["staleness_s"])
                bsz.extend(raw["batch_sizes"])
                step_lat.extend(raw["step_latency_s"])
        lookups = totals["cache_hits"] + totals["cache_misses"]
        lat50, lat95, lat99 = _percentiles(lat, (50, 95, 99))
        stale50, stale95 = _percentiles(stale, (50, 95))
        batch50, batch95 = _percentiles(bsz, (50, 95))
        step50, step95 = _percentiles(step_lat, (50, 95))
        return {
            "shards": len(telemetries),
            "requests": totals["requests"],
            "requests_by_shard": by_shard,
            "batches": totals["batches"],
            "throughput_rps": totals["requests"] / elapsed,
            "p50_ms": lat50 * 1e3,
            "p95_ms": lat95 * 1e3,
            "p99_ms": lat99 * 1e3,
            "mean_batch": (totals["real_slots"] / totals["batches"]
                           if totals["batches"] else 0.0),
            "batch_p50": batch50,
            "batch_p95": batch95,
            "batch_occupancy": (totals["real_slots"] / totals["padded_slots"]
                                if totals["padded_slots"] else 0.0),
            "cache_hit_rate": (totals["cache_hits"] / lookups
                               if lookups else 0.0),
            "cache_evictions": totals["cache_evictions"],
            "swaps": totals["swaps"],
            "reprimes": totals["reprimes"],
            "restored_sessions": totals["restored_sessions"],
            "restored_stale": totals["restored_stale"],
            "staleness_p50_s": stale50,
            "staleness_p95_s": stale95,
            "requests_by_version": by_version,
            "requests_by_client": by_client,
            "requests_by_model": by_model,
            "unique_clients": len(by_client),
            "untracked_client_requests":
                totals["untracked_client_requests"],
            "ensemble_requests": totals["ensemble_requests"],
            "ensemble_alerts": totals["ensemble_alerts"],
            "anomaly_mode": anomaly,
            "step_requests": totals["step_requests"],
            "step_batches": totals["step_batches"],
            "steps_per_s": totals["step_requests"] / elapsed,
            "mean_step_batch": (totals["step_real_slots"]
                                / totals["step_batches"]
                                if totals["step_batches"] else 0.0),
            "step_occupancy": (totals["step_real_slots"]
                               / totals["step_padded_slots"]
                               if totals["step_padded_slots"] else 0.0),
            "step_p50_ms": step50 * 1e3,
            "step_p95_ms": step95 * 1e3,
            "slot_inserts": totals["slot_inserts"],
            "slot_spills": totals["slot_spills"],
            # gauges sum across shards: total occupied / configured lanes
            "slot_active": totals["slot_active"],
            "slot_lanes": totals["slot_lanes"],
            "slot_occupancy": (totals["slot_active"] / totals["slot_lanes"]
                               if totals["slot_lanes"] else 0.0),
        }

    @staticmethod
    def format(snap: dict) -> str:
        line = (f"{snap['requests']} req in {snap['batches']} batches | "
                f"{snap['throughput_rps']:.0f} req/s | "
                f"p50 {snap['p50_ms']:.2f} ms  p95 {snap['p95_ms']:.2f} ms  "
                f"p99 {snap['p99_ms']:.2f} ms | "
                f"mean batch {snap['mean_batch']:.1f} "
                f"(occupancy {snap['batch_occupancy']:.0%}) | "
                f"cache hit {snap['cache_hit_rate']:.0%}")
        if snap.get("swaps"):
            line += (f" | {snap['swaps']} swaps, staleness p95 "
                     f"{snap['staleness_p95_s']:.2f} s, "
                     f"{len(snap['requests_by_version'])} versions served")
        if snap.get("step_requests"):
            line += (f" | {snap['step_requests']} steps in "
                     f"{snap['step_batches']} fused flushes "
                     f"({snap['steps_per_s']:.0f} steps/s, mean batch "
                     f"{snap['mean_step_batch']:.1f}, step p95 "
                     f"{snap['step_p95_ms']:.2f} ms)")
        if snap.get("slot_lanes"):
            line += (f" | slots {snap['slot_active']}/{snap['slot_lanes']} "
                     f"resident ({snap['slot_inserts']} inserts, "
                     f"{snap['slot_spills']} spills)")
        if snap.get("restored_sessions"):
            line += (f" | restored {snap['restored_sessions']} sessions "
                     f"({snap.get('restored_stale', 0)} stale)")
        if len(snap.get("requests_by_model", {})) > 1:
            per = " ".join(f"{m}:{n}" for m, n in
                           sorted(snap["requests_by_model"].items()))
            line += f" | by model {per}"
        if snap.get("ensemble_requests"):
            line += (f" | ensemble {snap['ensemble_requests']} fused, "
                     f"{snap['ensemble_alerts']} alerts"
                     + (", ANOMALY" if snap.get("anomaly_mode") else ""))
        return line
