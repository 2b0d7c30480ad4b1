"""Dynamic micro-batching engine: a request queue drained by a worker
that groups requests by (model, length bucket), right-pads them into
fixed bucket shapes, and dispatches one jitted apply per batch.

Flush policy: a group is dispatched as soon as it holds ``max_batch``
requests, or when its oldest request has waited ``max_wait_ms`` — the
classic latency/throughput knob. Shapes are quantized (lengths to a
bucket, batch to a power of two) so the set of compiled programs is
small and fixed: after ``warmup`` the hot path never recompiles.

``EngineShard`` is one queue + worker thread; ``ServingEngine`` is the
single-shard special case that keeps the original public API. The
sharded mesh in ``repro.serving.router`` runs several ``EngineShard``
workers side by side (each over its own registry replica) and routes
requests between them.

Streaming sessions ride the same queue: ``submit_step`` enqueues one
observation for a client's resident session, and the worker flushes
every queued step for a model as ONE fused decode dispatch per
decode-lane chunk (gather carries -> fused step+alert -> scatter back)
instead of one jit dispatch per client — the batched decode path.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future

import numpy as np

from repro.obs import trace as obs_trace
from repro.obs.trace import _EPOCH as _TRACE_EPOCH
from repro.obs.trace import FlushSpans as _FlushSpans
from repro.obs.trace import finish_all as _finish_all
from repro.serving.forecaster import input_spec, streams
from repro.serving.telemetry import Telemetry


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class BatcherConfig:
    max_batch: int = 32
    max_wait_ms: float = 2.0
    # admissible padded lengths; () -> round up to the next power of two
    length_buckets: tuple[int, ...] = ()
    # pad the batch dim to a power of two (<= max_batch) so compiled
    # shapes are {pow2 batches} x {length buckets}, not arbitrary
    pad_batch: bool = True
    # device-resident decode lanes per model runner (rounded up to the
    # forecaster's decode width): streaming sessions stay resident on
    # device between steps and a flush is ONE fused generate dispatch
    decode_slots: int = 64

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.decode_slots < 1:
            raise ValueError(
                f"decode_slots must be >= 1, got {self.decode_slots}")
        if self.pad_batch and self.max_batch & (self.max_batch - 1):
            # a non-pow2 max_batch would make bucket_batch emit a non-pow2
            # clamped shape, breaking the "{pow2 batches} x {length
            # buckets}" fixed compile-set contract — round it down
            object.__setattr__(self, "max_batch",
                               1 << (self.max_batch.bit_length() - 1))

    def bucket_len(self, t: int) -> int:
        if not self.length_buckets:
            return _next_pow2(max(t, 8))
        for b in sorted(self.length_buckets):
            if t <= b:
                return b
        # longer than every configured bucket: clamp to the largest one
        # instead of emitting an uncompiled shape (the raw length used to
        # escape the fixed compile set and recompile on the serving hot
        # path — ``warmup`` never warms such shapes). ``submit`` truncates
        # the payload to its newest ``bucket`` rows; the LSTM is causal,
        # so those rows are exactly what the clamped window serves.
        return max(self.length_buckets)

    def bucket_batch(self, n: int) -> int:
        if not self.pad_batch:
            return n
        return min(_next_pow2(n), self.max_batch)


class _Request:
    __slots__ = ("payload", "length", "future", "t_enq", "client_id",
                 "trace", "t_trace")

    def __init__(self, payload: np.ndarray, t_enq: float,
                 client_id: str | None = None, trace=None,
                 t_trace=None):
        self.payload = payload
        self.length = payload.shape[0]
        self.future: Future = Future()
        self.t_enq = t_enq
        self.client_id = client_id
        self.trace = trace          # upstream TraceContext | None
        self.t_trace = t_trace      # deferred-trace submit stamp | None


class _StepRequest:
    """One streaming step: a single feature vector for a session whose
    carry lives in a decode lane of the shard's runner (or its session
    cache, spilled). Grouped per model and flushed as ONE fused generate
    (``RecurrentSessionRunner.step_many``), not one dispatch per
    client."""

    __slots__ = ("payload", "history", "future", "t_enq", "client_id",
                 "trace", "t_trace")

    def __init__(self, payload: np.ndarray, t_enq: float, client_id: str,
                 history=None, trace=None, t_trace=None):
        self.payload = payload
        self.history = history
        self.future: Future = Future()
        self.t_enq = t_enq
        self.client_id = client_id
        self.trace = trace          # upstream TraceContext | None
        self.t_trace = t_trace      # deferred-trace submit stamp | None


# pseudo length-bucket under which step requests group in the pending
# map: one flush group per model, orthogonal to the window buckets
_STEP_BUCKET = -1


class _Quiesce:
    """Queue sentinel: when the worker dequeues one, everything enqueued
    before it has reached the pending map — force-flush it all and wake
    the waiter. Lets another thread (e.g. the transport worker's session
    ``extract``) serialize against in-flight steps without stopping the
    engine."""

    __slots__ = ("event",)

    def __init__(self):
        self.event = threading.Event()


class EngineShard:
    """One serving worker: a request queue drained by a thread that
    groups, pads and dispatches micro-batches over a ``ModelRegistry``
    (anything with ``get(key) -> forecaster`` works). ``shard_id``
    names the worker in thread names and mesh telemetry."""

    def __init__(self, registry, config: BatcherConfig | None = None,
                 telemetry: Telemetry | None = None, shard_id: int = 0,
                 session_cache=None, tracer=None):
        self.registry = registry
        self.config = config or BatcherConfig()
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.shard_id = shard_id
        # per-request trace spans (repro.obs.Tracer); None -> no tracing
        self.tracer = tracer
        # trace meta is shared by reference (one dict per model, not one
        # per request) — Tracer.start keeps it without copying
        self._trace_meta: dict[str, dict] = {}
        self._queue: queue.Queue = queue.Queue()
        self._pending: dict[tuple[str, int], list] = {}
        self._running = False
        # makes submit's running-check + enqueue atomic w.r.t. stop()
        self._state_lock = threading.Lock()
        self._thread: threading.Thread | None = None
        # streaming sessions: shard-local carry cache + one batched
        # runner per hosted model, built lazily on the first step
        self._session_cache = session_cache
        self._runners: dict[str, object] = {}
        self._runners_lock = threading.Lock()
        # ensemble serving: one EnsembleForecaster runtime per hosted
        # ensemble name (holds the shard's online fusion/anomaly state,
        # shared by the predict fan-in path and the step flush), plus
        # the anomaly-driven max_wait multipliers the flush worker
        # consults per model key (1.0 when absent)
        self._ensemble_runtimes: dict[str, object] = {}
        self._wait_scales: dict[str, float] = {}

    @property
    def sessions(self):
        """The shard-local session cache (created on first use)."""
        if self._session_cache is None:
            with self._runners_lock:
                if self._session_cache is None:
                    from repro.serving.sessions import SessionCache

                    self._session_cache = SessionCache(
                        telemetry=self.telemetry)
        return self._session_cache

    def _ensemble_spec(self, model_key: str):
        """The EnsembleSpec hosted under ``model_key``, or None when the
        key is a plain model (or the registry is a duck-typed stand-in
        without ensembles) — how the serve paths tell fan-out requests
        from single-model requests."""
        fn = getattr(self.registry, "ensemble", None)
        return fn(model_key) if fn is not None else None

    def _ensemble(self, name: str):
        """This shard's EnsembleForecaster runtime for ``name`` (built
        lazily; holds the online fusion weights and anomaly state)."""
        rt = self._ensemble_runtimes.get(name)
        if rt is None:
            with self._runners_lock:
                rt = self._ensemble_runtimes.get(name)
                if rt is None:
                    from repro.serving.ensemble import EnsembleForecaster

                    rt = EnsembleForecaster(self.registry, name)
                    self._ensemble_runtimes[name] = rt
        return rt

    def _note_anomaly(self, name: str, spec, rt) -> None:
        """Fold the ensemble's anomaly state into the flush worker's
        max_wait multipliers: while the fused stream is anomalous, the
        ensemble AND its members flush sooner (alert latency beats
        batch occupancy under extremes)."""
        scale = rt.fuser().wait_scale()
        keys = (name,) + tuple(spec.members)
        if scale == 1.0:
            for k in keys:
                self._wait_scales.pop(k, None)
        else:
            for k in keys:
                self._wait_scales[k] = scale
        self.telemetry.record_anomaly(rt.fuser().anomaly)

    def _wait_scale(self, model_key: str) -> float:
        if not self._wait_scales:
            return 1.0
        return self._wait_scales.get(model_key, 1.0)

    def _step_runner(self, model_key: str):
        runner = self._runners.get(model_key)
        if runner is None:
            cache = self.sessions   # resolve BEFORE taking the lock
            with self._runners_lock:
                runner = self._runners.get(model_key)
                if runner is None:
                    from repro.serving.sessions import \
                        RecurrentSessionRunner

                    # provider-backed: the runner re-resolves the
                    # registry key each flush, so weight hot-swaps are
                    # picked up without rebuilding the runner; it holds
                    # decode_slots device lanes.
                    # ensemble names resolve to the shard's stable
                    # EnsembleForecaster runtime (which re-resolves its
                    # members per call): composite per-member carries
                    # live under ONE client id in the same cache, so
                    # they spill/migrate as a unit
                    if self._ensemble_spec(model_key) is not None:
                        provider = lambda: self._ensemble(model_key)  # noqa: E731
                    else:
                        provider = lambda: self.registry.get(model_key)  # noqa: E731
                    runner = RecurrentSessionRunner(
                        provider, cache=cache,
                        num_slots=self.config.decode_slots)
                    self._runners[model_key] = runner
        return runner

    def spill_sessions(self, client_ids=None) -> int:
        """Spill lane-resident session carries (all models; optionally
        just ``client_ids``) into the shard's session cache, so
        ``sessions.export`` sees every live session — the migration /
        drain path. Returns the number of sessions spilled."""
        with self._runners_lock:
            runners = list(self._runners.values())
        return sum(r.spill(client_ids) for r in runners)

    def snapshot_sessions(self, client_ids=None) -> list:
        """Non-destructive snapshot of every live session on this
        shard — the durable-checkpoint path.  Lane-resident carries
        spill to the cache first (bitwise; the slot lock serializes
        this against the flush thread, so no quiesce and no stalled
        flush), then the cache is READ, not drained.  Returns
        ``(client_id, carry, nbytes, version)`` tuples."""
        self.spill_sessions(client_ids)
        return self.sessions.snapshot(client_ids)

    def session_clients(self) -> list[str]:
        """Every client with live session state on this shard: spill
        tier (cache) plus lane-resident sessions."""
        clients = set(self.sessions.clients())
        with self._runners_lock:
            runners = list(self._runners.values())
        for r in runners:
            clients.update(r.resident_clients())
        return sorted(clients)

    def slot_stats(self) -> dict:
        """Aggregate decode-slot occupancy over this shard's runners."""
        with self._runners_lock:
            runners = list(self._runners.values())
        agg = {"lanes": 0, "active": 0, "inserts": 0, "spills": 0,
               "expiries": 0, "kv_tokens_read": 0, "ssm_inplace_waves": 0,
               "lane_state_bytes": {}}
        for r in runners:
            for k, v in r.slot_stats().items():
                if k == "lane_state_bytes":
                    for kind, n in v.items():
                        agg[k][kind] = max(agg[k].get(kind, 0), n)
                else:
                    agg[k] += v
        return agg

    def session_lanes(self, model_key: str) -> dict[str, int]:
        """The decode lane each lane-resident session of ``model_key``
        occupies (a snapshot; empty before the model's first step)."""
        with self._runners_lock:
            runner = self._runners.get(model_key)
        return runner.lanes() if runner is not None else {}

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "EngineShard":
        with self._state_lock:
            if self._running:
                return self
            self._running = True
        self._thread = threading.Thread(
            target=self._worker, name=f"serving-shard-{self.shard_id}",
            daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        with self._state_lock:
            if not self._running:
                return
            self._running = False
        # any submit that saw _running under the lock has already enqueued,
        # and the worker drains queue + pending before exiting; submits
        # from here on raise instead of enqueueing into a dead engine
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def __enter__(self) -> "EngineShard":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _meta_for(self, model_key: str) -> dict:
        meta = self._trace_meta.get(model_key)
        if meta is None:
            meta = self._trace_meta[model_key] = {"model": model_key,
                                                  "shard": self.shard_id}
        return meta

    @staticmethod
    def _trace_gather(tracer, reqs):
        """One pass over a flush's requests collecting its tracing work:
        ``traced`` are upstream TraceContexts (cross-process requests)
        joining the shared FlushSpans record; ``deferred`` are
        (t_submit, t_enq) perf-counter pairs for in-process requests,
        folded into one ring block by ``Tracer.finish_block`` — their
        Trace objects only materialize when somebody reads the ring."""
        traced, deferred, fspans = [], [], None
        for r in reqs:
            if r.trace is not None:
                if fspans is None:
                    fspans = _FlushSpans()
                r.trace.attach_flush(fspans, r.t_enq)
                traced.append(r.trace)
            elif r.t_trace is not None and tracer is not None:
                deferred.append((r.t_trace, r.t_enq))
        if deferred and fspans is None:
            fspans = _FlushSpans()
        return traced, deferred, fspans

    # -- client API --------------------------------------------------------
    def submit(self, model_key: str, window,
               client_id: str | None = None, trace=None) -> Future:
        """Enqueue one window ([T, F] features or [T] token ids); returns
        a Future resolving to (forecast, p_extreme) scalars.
        ``client_id`` rides along into per-client telemetry attribution
        (the sharded mesh additionally routes on it; a single shard
        serves every client). ``trace`` is an upstream TraceContext
        (the mesh router starts one); with none given, a shard-level
        tracer opens its own."""
        spec = self._ensemble_spec(model_key)
        if spec is not None:
            return self._submit_ensemble(model_key, spec, window,
                                         client_id=client_id, trace=trace)
        # fully deferred in-process tracing: the client thread stashes ONE
        # clock stamp; the flush worker later folds the whole micro-batch
        # into a single trace block (Tracer.finish_block). No Trace object
        # is allocated on this path at all.
        tracer = self.tracer
        t_tr = (time.perf_counter()
                if trace is None and tracer is not None and tracer.enabled
                else None)
        try:
            payload = np.asarray(window)
            fc = self.registry.get(model_key)
            want_ndim = 2 if fc.feature_dim else 1
            if payload.ndim != want_ndim or payload.shape[0] < 1 or (
                    fc.feature_dim and payload.shape[1] != fc.feature_dim):
                raise ValueError(
                    f"{model_key!r} expects windows of shape "
                    f"{'[T>=1, ' + str(fc.feature_dim) + ']' if fc.feature_dim else '[T>=1]'}"
                    f", got {payload.shape}")
        except Exception:
            # a synchronous reject must not vanish from the trace ring:
            # record it as an error trace before re-raising (cold path, so
            # an eager Trace is fine here)
            if trace is not None:
                trace.finish(status="error")
            elif t_tr is not None:
                err = tracer.start("predict", t0=_TRACE_EPOCH + t_tr,
                                   meta=self._meta_for(model_key))
                if err is not None:
                    err.finish(status="error")
            raise
        bucket = self.config.bucket_len(payload.shape[0])
        if payload.shape[0] > bucket:
            # over-long window clamped to the largest length bucket: keep
            # the newest rows (causal model) so the compile set stays fixed
            payload = payload[-bucket:]
        req = _Request(payload, time.perf_counter(), client_id=client_id,
                       trace=trace, t_trace=t_tr)
        with self._state_lock:
            if not self._running:
                raise RuntimeError("engine is not running (use start() or a "
                                   "with-block)")
            self._queue.put((model_key, req))
        return req.future

    def predict(self, model_key: str, window, timeout: float | None = 30.0,
                client_id: str | None = None):
        return self.submit(model_key, window,
                           client_id=client_id).result(timeout=timeout)

    def _submit_ensemble(self, name: str, spec, window,
                         client_id: str | None = None,
                         trace=None) -> Future:
        """Fan one request across every ensemble member and join on a
        fan-in future: each member rides its OWN per-model bucket (so
        an N-member flush is exactly N fused per-model dispatches, each
        bitwise-identical to serving that member solo), and the last
        member's completion fuses the results with the shard's online
        EVT weights. The fan-in future resolves to the fused
        (forecast, p_extreme); per-member results, fusion weights and
        the alert/anomaly decision ride on it as attributes."""
        tracer = self.tracer
        if trace is None and tracer is not None and tracer.enabled:
            # eager trace (the fan-out already costs N submits): member
            # completions and the fuse land as spans on ONE trace
            trace = tracer.start("ensemble", meta=self._meta_for(name))
        rt = self._ensemble(name)
        members = spec.members
        n = len(members)
        fanin: Future = Future()
        fanin.client_id = client_id
        t0 = time.perf_counter()
        state = {"y": [0.0] * n, "p": [0.0] * n, "t": [0.0] * n,
                 "v": [None] * n, "done": 0}
        lock = threading.Lock()

        def _finish(exc=None):
            if not fanin.set_running_or_notify_cancel():
                return                      # client cancelled the fan-in
            if exc is not None:
                if trace is not None:
                    trace.finish(status="error")
                fanin.set_exception(exc)
                return
            try:
                ys = [np.asarray([state["y"][j]], np.float32)
                      for j in range(n)]
                ps = [np.asarray([state["p"][j]], np.float32)
                      for j in range(n)]
                fused = rt.fuse(ys, ps)
                self._note_anomaly(name, spec, rt)
            except Exception as e:  # noqa: BLE001 — spec swapped under us
                if trace is not None:
                    trace.finish(status="error")
                fanin.set_exception(e)
                return
            now = time.perf_counter()
            self.telemetry.record_ensemble(
                latency_s=now - t0, alerts=int(fused.alerts[0]),
                anomaly=fused.anomaly)
            fanin.model_version = tuple(state["v"])
            fanin.members = dict(zip(members, zip(state["y"], state["p"])))
            fanin.weights = np.asarray(fused.weights)
            fanin.alert = bool(fused.alerts[0])
            fanin.alert_threshold = fused.threshold
            fanin.anomaly = fused.anomaly
            if trace is not None:
                # member completion spans in completion order (a trace's
                # marks chain forward in time), then the fuse, then done
                for j in sorted(range(n), key=lambda j: state["t"][j]):
                    trace.mark("member", t=_TRACE_EPOCH + state["t"][j],
                               model=members[j])
                trace.mark("fuse")
                trace.finish()      # before set_result: a cross-process
                # done-callback exports the spans at delivery
            fanin.set_result((float(fused.forecast[0]),
                              float(fused.p_extreme[0])))

        def _member_cb(i):
            def cb(fut):
                done = failed = None
                with lock:
                    if state["done"] < 0:
                        return
                    try:
                        y, p = fut.result()
                    except Exception as e:  # noqa: BLE001
                        state["done"] = -1
                        failed = e
                    else:
                        state["y"][i] = float(y)
                        state["p"][i] = float(p)
                        state["t"][i] = time.perf_counter()
                        state["v"][i] = getattr(fut, "model_version", None)
                        state["done"] += 1
                        done = state["done"] == n
                if failed is not None:
                    _finish(failed)
                elif done:
                    _finish()
            return cb

        for i, m in enumerate(members):
            try:
                self.submit(m, window,
                            client_id=client_id).add_done_callback(
                                _member_cb(i))
            except Exception as e:  # noqa: BLE001 — sync member reject
                with lock:
                    if state["done"] >= 0:
                        state["done"] = -1
                        _finish(e)
                break
        return fanin

    def submit_step(self, model_key: str, client_id: str, x_t,
                    history=None, trace=None) -> Future:
        """Enqueue one streaming step for ``client_id``'s session:
        ``x_t`` is a single [F] feature vector (the newest observation),
        ``history`` an optional [T, F] window prefix replayed on a cache
        miss. Steps for a model group into ONE fused decode dispatch per
        flush — the batched Pallas/XLA decode path — instead of one
        dispatch per client. Returns a Future resolving to
        (forecast, p_extreme) scalars."""
        tracer = self.tracer
        t_tr = (time.perf_counter()       # deferred trace — see submit()
                if trace is None and tracer is not None and tracer.enabled
                else None)
        try:
            # ensemble names resolve to the shard's runtime (protocol-
            # compatible: validation below sees the members' shared
            # feature_dim); the step then rides the SAME queue/flush
            # machinery under the ensemble name
            fc = (self._ensemble(model_key)
                  if self._ensemble_spec(model_key) is not None
                  else self.registry.get(model_key))
            if not streams(fc):
                raise ValueError(
                    f"{model_key!r} does not support incremental session "
                    f"serving (needs step/init_carry/replay, and a cache "
                    f"that keeps a length per session)")
            shape, dtype = input_spec(fc)
            payload = np.asarray(x_t, dtype)
            if payload.ndim == len(shape) + 1 and payload.shape[0] == 1:
                payload = payload[0]
            if payload.shape != shape:
                raise ValueError(
                    f"{model_key!r} expects step inputs of shape "
                    f"{list(shape)}, got {list(payload.shape)}")
            if history is not None:
                # validate HERE, against this caller only: a malformed
                # history that first blew up inside the flush would fail
                # every other client's step sharing that fused batch
                history = np.asarray(history, dtype)
                if history.ndim != len(shape) + 1 or history.shape[0] < 1 \
                        or history.shape[1:] != shape:
                    want = ", ".join(["T>=1"] + [str(n) for n in shape])
                    raise ValueError(f"history must be [{want}], got "
                                     f"{list(history.shape)}")
            vocab = getattr(fc, "vocab", None)
            if vocab is not None and any(
                    a.size and (a.min() < 0 or a.max() >= vocab)
                    for a in (payload, history) if a is not None):
                raise ValueError(f"{model_key!r} takes token ids in "
                                 f"[0, {vocab})")
            if client_id is None:
                raise ValueError("streaming steps require a client_id "
                                 "(the session key)")
        except Exception:
            if trace is not None:
                trace.finish(status="error")    # see submit()
            elif t_tr is not None:
                err = tracer.start("step", t0=_TRACE_EPOCH + t_tr,
                                   meta=self._meta_for(model_key))
                if err is not None:
                    err.finish(status="error")
            raise
        req = _StepRequest(payload, time.perf_counter(), str(client_id),
                           history=history, trace=trace, t_trace=t_tr)
        with self._state_lock:
            if not self._running:
                raise RuntimeError("engine is not running (use start() or a "
                                   "with-block)")
            self._queue.put((model_key, req))
        return req.future

    def step(self, model_key: str, client_id: str, x_t, history=None,
             timeout: float | None = 30.0):
        """Blocking ``submit_step`` — one (forecast, p_extreme) tuple."""
        return self.submit_step(model_key, client_id, x_t,
                                history=history).result(timeout=timeout)

    def quiesce(self, timeout: float | None = 30.0) -> bool:
        """Block until every request enqueued before this call has been
        flushed (results delivered), without stopping the engine. Used
        by the transport worker to serialize session ``extract`` against
        in-flight streaming steps. Returns False on timeout; True
        immediately if the engine is not running (queue already
        drained)."""
        with self._state_lock:
            if not self._running:
                return True
            q = _Quiesce()
            self._queue.put((None, q))
        return q.event.wait(timeout)

    def warmup(self, model_key: str, lengths: tuple[int, ...] | None = None
               ) -> int:
        """Compile every (pow2 batch) x (length bucket) apply the hot path
        can hit, off the serving path. Returns #programs warmed."""
        spec = self._ensemble_spec(model_key)
        if spec is not None:
            # an ensemble's compile set IS its members' (fan-out serves
            # through their buckets); the runner build warms the
            # ensemble replay/slot programs on top (mostly cache hits)
            n = sum(self.warmup(m, lengths=lengths) for m in spec.members)
            if self._ensemble(model_key).feature_dim:
                self._step_runner(model_key)
            return n
        fc = self.registry.get(model_key)
        lens = lengths if lengths is not None else (fc.window,)
        max_b = self.config.max_batch
        # exactly the shapes bucket_batch can emit: the powers of two up
        # to max_batch (itself a power of two after __post_init__)
        if self.config.pad_batch:
            batches = sorted({min(1 << i, max_b)
                              for i in range(max_b.bit_length() + 1)})
        else:
            # unquantized batches: any size 1..max_batch can reach the
            # hot path, so all of them must be compiled here
            batches = list(range(1, max_b + 1))
        n = 0
        for t in {self.config.bucket_len(x) for x in lens}:
            for b in batches:
                fc.predict(*self._padded(fc, [np.zeros(
                    self._payload_shape(fc, t), self._payload_dtype(fc))] * b,
                    [t] * b, b, t))
                n += 1
        if streams(fc):
            # the runner: its ctor compiles the full-window replay and
            # the lane programs off the hot path
            self._step_runner(model_key)
        return n

    # -- batching internals ------------------------------------------------
    @staticmethod
    def _payload_shape(fc, t: int):
        return (t,) + input_spec(fc)[0]

    @staticmethod
    def _payload_dtype(fc):
        return input_spec(fc)[1]

    def _padded(self, fc, payloads, lengths, bucket_b: int, bucket_t: int):
        """Stack variable-length payloads into one right-padded batch of
        shape [bucket_b, bucket_t, ...]; padded rows get length 1."""
        shape = (bucket_b,) + self._payload_shape(fc, bucket_t)
        x = np.zeros(shape, self._payload_dtype(fc))
        out_len = np.ones((bucket_b,), np.int32)
        for i, (p, t) in enumerate(zip(payloads, lengths)):
            x[i, :t] = p
            out_len[i] = t
        return x, out_len

    def _flush_steps(self, model_key: str, reqs: list[_StepRequest]) -> None:
        """One batched decode flush: every queued step for ``model_key``
        becomes one fused generate over the session runner's decode
        lanes (``step_many``)."""
        tracer = self.tracer
        traced, deferred, fspans = self._trace_gather(tracer, reqs)
        if fspans is not None:
            t0f = fspans.stamp("queue")
        try:
            runner = self._step_runner(model_key)
            fc = runner._resolve()
            outs = runner.step_many([(r.client_id, r.payload, r.history)
                                     for r in reqs])
        except Exception as e:  # noqa: BLE001 — fail the steps, not the engine
            for r in reqs:
                r.future.set_exception(e)
            _finish_all(traced, status="error")
            if deferred:
                tracer.finish_block("step", self._meta_for(model_key),
                                    fspans, deferred, status="error")
            return
        with obs_trace.span("repro.engine.reply"):
            if fspans is not None:
                fspans.stamp("dispatch")
            now = time.perf_counter()
            version = getattr(fc, "version", None)
            # lane slots actually dispatched (a generate over every lane
            # per wave) — counted by the runner at the dispatch decision
            self.telemetry.record_step_batch([now - r.t_enq for r in reqs],
                                             n_padded=runner.last_step_slots,
                                             model=model_key)
            spec = self._ensemble_spec(model_key)
            if spec is not None:
                # the fuse happened inside the ensemble runtime's generate;
                # surface its alert/anomaly outcome into telemetry and the
                # flush worker's max_wait multipliers
                rt = self._ensemble(model_key)
                thr = rt.fuser().alert_threshold()
                self._note_anomaly(model_key, spec, rt)
                self.telemetry.record_ensemble(
                    alerts=sum(1 for _, p in outs if p >= thr), n=len(outs),
                    anomaly=rt.fuser().anomaly)
            if fspans is not None:
                # scatter + the umbrella flush span BEFORE set_result: the
                # transport worker's done-callback exports the trace, so
                # anything after delivery would be lost cross-process
                fspans.umbrella("flush", t0f, fspans.stamp("scatter"))
            for r, (y, p) in zip(reqs, outs):
                r.future.model_version = version
                r.future.client_id = r.client_id
                r.future.set_result((y, p))
            if fspans is not None:
                # exported traces (the transport worker's done-callback runs
                # inside set_result) materialized before this stamp and are
                # closed, so the reply span and finish only land on the
                # in-process traces — see obs.trace
                fspans.stamp("reply")
                _finish_all(traced)
                if deferred:
                    tracer.finish_block("step", self._meta_for(model_key),
                                        fspans, deferred)

    def _flush(self, model_key: str, bucket_t: int, reqs: list) -> None:
        # transition futures to RUNNING; drops client-cancelled requests
        # and guarantees the set_result/set_exception below cannot raise
        # InvalidStateError into the worker thread
        reqs = [r for r in reqs if r.future.set_running_or_notify_cancel()]
        if not reqs:
            return
        if bucket_t == _STEP_BUCKET:
            with obs_trace.span("repro.engine.flush.step"):
                self._flush_steps(model_key, reqs)
        else:
            with obs_trace.span("repro.engine.flush.predict"):
                self._flush_predict(model_key, bucket_t, reqs)

    def _flush_predict(self, model_key: str, bucket_t: int,
                       reqs: list[_Request]) -> None:
        tracer = self.tracer
        traced, deferred, fspans = self._trace_gather(tracer, reqs)
        if fspans is not None:
            t0f = fspans.stamp("queue")   # enqueue -> flush start
        try:
            with obs_trace.span("repro.engine.gather"):
                # one atomic reference per flush: the whole micro-batch
                # serves on these weights even if the registry swaps
                # mid-predict; the next flush re-resolves and picks up
                # the new version
                fc = self.registry.get(model_key)
                bucket_b = self.config.bucket_batch(len(reqs))
                x, lens = self._padded(fc, [r.payload for r in reqs],
                                       [r.length for r in reqs], bucket_b,
                                       bucket_t)
            if fspans is not None:
                fspans.stamp("gather", meta={"batch": len(reqs),
                                             "padded": bucket_b})
            forecast, p_extreme = fc.predict(x, lens)
        except Exception as e:  # noqa: BLE001 — fail the requests, not the engine
            for r in reqs:
                r.future.set_exception(e)
            _finish_all(traced, status="error")
            if deferred:
                tracer.finish_block("predict", self._meta_for(model_key),
                                    fspans, deferred, status="error")
            return
        with obs_trace.span("repro.engine.reply"):
            if fspans is not None:
                fspans.stamp("dispatch")
            now = time.perf_counter()
            version = getattr(fc, "version", None)
            published = getattr(fc, "published_at", None)
            staleness = (now - published) if published is not None else None
            self.telemetry.record_batch(len(reqs), bucket_b)
            self.telemetry.record_requests([now - r.t_enq for r in reqs],
                                           version=version,
                                           staleness_s=staleness,
                                           client_ids=[r.client_id
                                                       for r in reqs],
                                           model=model_key)
            if fspans is not None:
                # scatter + the umbrella flush span (overlapping the chained
                # queue/gather/dispatch/scatter spans) BEFORE set_result:
                # the transport worker's done-callback exports the trace, so
                # anything recorded after delivery would be lost cross-process
                fspans.umbrella("flush", t0f, fspans.stamp("scatter"))
            for i, r in enumerate(reqs):
                # attribution before set_result: a client that wakes on the
                # result always sees which model version produced it
                r.future.model_version = version
                r.future.client_id = r.client_id
                r.future.set_result((float(forecast[i]), float(p_extreme[i])))
            if fspans is not None:
                # exported traces (the transport worker's done-callback runs
                # inside set_result) materialized before this stamp and are
                # closed, so the reply span and finish only land on the
                # in-process traces — see obs.trace
                fspans.stamp("reply")
                _finish_all(traced)
                if deferred:
                    tracer.finish_block("predict", self._meta_for(model_key),
                                        fspans, deferred)

    def _flush_all(self) -> None:
        """Dispatch every pending group right now (max_batch chunks)."""
        for key in list(self._pending):
            reqs = self._pending.pop(key)
            while reqs:
                self._flush(key[0], key[1], reqs[:self.config.max_batch])
                del reqs[:self.config.max_batch]

    def _worker(self) -> None:
        cfg = self.config
        max_wait = cfg.max_wait_ms * 1e-3
        while self._running or not self._queue.empty() or self._pending:
            # drain everything already queued, then block briefly
            drained = False
            while True:
                try:
                    model_key, req = self._queue.get_nowait()
                except queue.Empty:
                    break
                drained = True
                if isinstance(req, _Quiesce):
                    # everything enqueued before the sentinel is in the
                    # pending map by now — flush it and wake the waiter
                    self._flush_all()
                    req.event.set()
                    continue
                key = (model_key,
                       _STEP_BUCKET if isinstance(req, _StepRequest)
                       else cfg.bucket_len(req.length))
                self._pending.setdefault(key, []).append(req)
            now = time.perf_counter()
            # flush full groups and expired groups; an anomalous
            # ensemble tightens its (and its members') max_wait so
            # alerts leave the queue sooner while the stream is extreme
            for key in list(self._pending):
                reqs = self._pending[key]
                while len(reqs) >= cfg.max_batch:
                    self._flush(key[0], key[1], reqs[:cfg.max_batch])
                    del reqs[:cfg.max_batch]
                wait = max_wait * self._wait_scale(key[0])
                if reqs and (now - reqs[0].t_enq >= wait
                             or not self._running):
                    self._flush(key[0], key[1], reqs)
                    reqs.clear()
                if not reqs:
                    del self._pending[key]
            if drained:
                continue
            # sleep until the next group deadline (or a short poll)
            timeout = max_wait if not self._pending else max(
                1e-4, min(r[0].t_enq + max_wait * self._wait_scale(k[0])
                          for k, r in self._pending.items())
                - time.perf_counter())
            try:
                with obs_trace.span("repro.engine.wait"):
                    model_key, req = self._queue.get(
                        timeout=min(timeout, 0.05))
            except queue.Empty:
                continue
            if isinstance(req, _Quiesce):
                self._flush_all()
                req.event.set()
                continue
            key = (model_key,
                   _STEP_BUCKET if isinstance(req, _StepRequest)
                   else cfg.bucket_len(req.length))
            self._pending.setdefault(key, []).append(req)


class ServingEngine(EngineShard):
    """Single-shard serving engine — the original public API
    (``submit`` / ``predict`` / ``warmup``), now a thin special case of
    ``EngineShard``. The sharded mesh (``repro.serving.router``) runs
    the same code path once per shard."""
