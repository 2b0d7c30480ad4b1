"""Socket transport for the serving mesh: each shard is an
``EngineShard`` (over its own replica ``ModelRegistry``, with a
shard-local ``SessionCache``) running in its OWN OS process — or on
another machine entirely — connected to the router process over a TCP
socket. This is the multi-node half of the paper's distributed story
applied to serving (training already distributes via async local SGD;
this distributes the forecast fleet).

``MultiProcessServingEngine`` mirrors the in-process
``ShardedServingEngine`` API (``submit`` / ``predict`` / ``step`` /
``warmup`` / ``add_shard`` / ``remove_shard`` / ``snapshot`` /
``version_vector``) and keeps the same guarantees across process
boundaries:

- weight publishes against the primary registry are PUSHED to each
  worker as serialized checkpoints (``ModelRegistry.save_bytes`` ->
  ``load_bytes`` with ``jax.device_put`` on the receiving side) under
  the ``max_skew`` staleness bound — every ``version_vector`` sample is
  taken under the same lock the push path holds, so the bound is
  observable atomically, exactly like ``ShardSwarm``;
- membership is live: a joining shard receives every hosted model and
  warms its compile set BEFORE the router assigns it traffic; a leaving
  shard is taken out of the router first, drains its queue (zero
  drops), and hands its session carries back for migration to the new
  owner shards;
- session affinity: ``step`` routes a client's streaming state to the
  worker process owning that client, where a shard-local
  ``SessionCache`` + the shard's batched decode path serve it O(1) —
  concurrent cross-process steps fuse into ONE decode dispatch per
  flush (``EngineShard.submit_step``), same as in-process;
- crash supervision: every worker is heartbeated (``ping``); a dead
  one (SIGKILL, OOM, unplugged host) is detected within the heartbeat
  budget, its pending futures fail fast with ``ConnectionError``
  instead of timing out, the router stops assigning it traffic, and a
  LOCAL worker is respawned — re-homing the session carries the
  survivors still hold (``restore`` is insert-if-absent) while missed
  sessions re-prime from client-supplied history on the next step. A
  REMOTE worker cannot be respawned from here; the mesh remembers its
  address (``awaiting_rejoin``) and re-adopts it on
  ``connect_shard``/``add_shard(addr=...)``. Crash/recover events land
  in the PR 6 ``EventLog`` and the ``crashes`` / ``respawns`` /
  ``rehomed_sessions`` counters.

Workers start two ways: ``spawn_shard`` forks a local process (the
convenience path: the child binds an ephemeral port and pipes it back),
or ``serve_shard`` runs standalone — ``python -m
repro.launch.shard_worker --port 7070`` on any host — and the router
dials in with ``connect_shard``. Both paths speak the same handshake:
the router's FIRST frame is a ``hello`` carrying the shard id, batcher
config and session budget; the worker builds its serving state from
that, so a standalone worker needs no configuration of its own.

Wire format (length-prefixed msgpack frames; see README):

    frame    := uint32_be payload_length ++ msgpack(payload)
    payload  := {"op": str, "id": int, ...}   # replies echo "id"
    ndarray  := {"nd": true, "dtype": str, "shape": [int...],
                 "data": bytes}
    weights  := npz checkpoint bytes (repro.checkpoint.io), so config,
                EVT calibration and model version ride along

Ops: ``hello`` / ``ping`` / ``publish`` / ``submit`` / ``step`` /
``warmup`` / ``stats`` / ``restore`` / ``extract`` / ``reset`` /
``count_start`` / ``count_stop`` / ``drain`` / ``bye``. Replies are
``result`` (forecast rows), ``ok`` (control) or ``error``. Responses
may arrive out of order — ``submit``/``step`` results resolve futures
by id as the worker's micro-batcher flushes them.
"""

from __future__ import annotations

import itertools
import multiprocessing as mp
import os
import socket
import struct
import threading
import time
from concurrent.futures import Future

import msgpack
import numpy as np

from repro.obs.trace import Tracer
from repro.obs.trace import now as _trace_now
from repro.serving.engine import BatcherConfig
from repro.serving.router import ConsistentRouter
from repro.serving.telemetry import _percentiles

_HDR = struct.Struct(">I")


# -- framing ---------------------------------------------------------------

def pack_array(a) -> dict:
    a = np.ascontiguousarray(a)
    return {"nd": True, "dtype": a.dtype.str, "shape": list(a.shape),
            "data": a.tobytes()}


def unpack_array(d: dict) -> np.ndarray:
    return np.frombuffer(bytearray(d["data"]),
                         dtype=np.dtype(d["dtype"])).reshape(d["shape"])


def _wire_window(window) -> np.ndarray:
    """Normalize a window to its serving dtype BEFORE framing: models
    compute in float32 (token payloads in int32), so shipping the
    caller's dtype as-is — float64 by default in numpy — doubles the
    frame bytes and hands the worker an off-dtype array. ``step``
    frames always normalized; ``submit`` frames now match."""
    a = np.asarray(window)
    if np.issubdtype(a.dtype, np.floating) and a.dtype != np.float32:
        return a.astype(np.float32)
    if np.issubdtype(a.dtype, np.integer) and a.dtype != np.int32:
        return a.astype(np.int32)
    return a


class Connection:
    """Length-prefixed msgpack frames over one socket; writes are
    locked (results are sent from flush-worker callbacks concurrently
    with control replies)."""

    def __init__(self, sock: socket.socket):
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock = sock
        self._rfile = sock.makefile("rb")
        self._wlock = threading.Lock()

    def send(self, msg: dict) -> None:
        data = msgpack.packb(msg, use_bin_type=True)
        with self._wlock:
            self._sock.sendall(_HDR.pack(len(data)) + data)

    def recv(self) -> dict | None:
        """One frame, or None on EOF/closed connection."""
        try:
            hdr = self._rfile.read(_HDR.size)
            if len(hdr) < _HDR.size:
                return None
            (n,) = _HDR.unpack(hdr)
            data = self._rfile.read(n)
            if len(data) < n:
                return None
            # strict_map_key=False: telemetry maps are keyed by int
            # model versions
            return msgpack.unpackb(data, raw=False, strict_map_key=False)
        except (OSError, ValueError):
            return None

    def close(self) -> None:
        try:
            self._rfile.close()
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass


def _pack_carry(carry):
    """An LSTM carry — a tuple of per-layer (h, c) arrays — as frames.
    Ensemble session carries are ``{member_key: member_carry}`` dicts
    (per-member state under ONE client id) and pack recursively, so a
    composite session migrates across processes as a unit."""
    if isinstance(carry, dict):
        return {k: _pack_carry(v) for k, v in carry.items()}
    return [[pack_array(np.asarray(h)), pack_array(np.asarray(c))]
            for h, c in carry]


def _unpack_carry(packed):
    import jax.numpy as jnp

    if isinstance(packed, dict):
        return {k: _unpack_carry(v) for k, v in packed.items()}
    return tuple((jnp.asarray(unpack_array(h)), jnp.asarray(unpack_array(c)))
                 for h, c in packed)


# -- worker process --------------------------------------------------------

class _ShardState:
    """One worker process's long-lived serving state. It outlives the
    router connection under ``serve_shard(forever=True)``: the replica
    registry, telemetry, tracer and session cache persist, so a router
    that restarts (or a mesh re-adopting a remote shard) finds weights
    and sessions still warm. Built lazily on the first ``hello`` frame,
    which carries the shard id, batcher config and session budget —
    the worker itself needs no configuration."""

    def __init__(self, state_dir=None):
        self.registry = None
        self.telemetry = None
        self.cache = None
        self.shard = None
        self.state_dir = state_dir
        # worker half of cross-process traces: requests whose frames
        # carry a trace id are adopted here, their spans exported back
        # in the result frame (the shard never STARTS traces — the
        # router owns that decision, so tracing-off stays zero-cost)
        self.tracer = Tracer()

    def configure(self, shard_id: int, config: BatcherConfig,
                  max_sessions: int) -> None:
        if self.shard is not None:
            # a reconnecting router may rename us; everything else
            # (weights, sessions, compile cache) is worth keeping
            self.shard.shard_id = shard_id
            return
        # heavy imports happen HERE, on the first hello
        from repro.serving.engine import EngineShard
        from repro.serving.registry import ModelRegistry
        from repro.serving.sessions import SessionCache

        from repro.serving.telemetry import Telemetry

        self.registry = ModelRegistry()
        self.telemetry = Telemetry()
        self.cache = SessionCache(max_sessions=max_sessions,
                                  telemetry=self.telemetry)
        self.shard = EngineShard(self.registry, config, self.telemetry,
                                 shard_id=shard_id,
                                 session_cache=self.cache)
        if self.state_dir:
            # a cold worker restart on this host comes back with the
            # store's last good weights before the router re-adopts it;
            # monotone replica versions make the router's later
            # force-push a safe no-op for anything already current
            try:
                from repro.serving.durable import (DurableStore,
                                                   restore_registry)

                restore_registry(DurableStore(self.state_dir),
                                 self.registry, device_put=True)
            except Exception:  # noqa: BLE001 — serve unprimed over not at all
                pass


def _serve_conn(conn: Connection, state: _ShardState) -> None:
    """Serve one router connection over ``state`` until ``bye``/EOF."""
    tracer = state.tracer
    draining = False
    counter_cm = None          # an installed dispatch.counting() block
    counter = None

    def _adopt(msg, op_name):
        tinfo = msg.get("trace")
        if not tinfo:
            return None
        ctx = tracer.adopt(tinfo["id"], op=op_name, t0=tinfo.get("t"),
                           parent=tinfo.get("parent"),
                           meta={"shard": state.shard.shard_id})
        if ctx is not None:
            # the wire + decode time: router send stamp -> now
            ctx.mark("transport")
        return ctx

    def _send_result(rid, fut, ctx=None) -> None:
        # runs as the future's done-callback, INSIDE set_result on the
        # flush thread: exporting here pops the trace before the
        # engine's post-set_result reply/finish bookkeeping runs (those
        # become no-ops), so the worker's spans travel in the result
        # frame and the router records the final reply span
        try:
            y, p = fut.result()
            out = {"op": "result", "id": rid, "y": y, "p": p,
                   "version": getattr(fut, "model_version", None)}
            if ctx is not None:
                out["trace"] = {"spans": tracer.export(ctx),
                                "t": _trace_now()}
            conn.send(out)
        except Exception as e:  # noqa: BLE001 — fail the request, not the worker
            if ctx is not None:
                tracer.export(ctx)   # don't leak the active trace
            try:
                conn.send({"op": "error", "id": rid,
                           "message": f"{type(e).__name__}: {e}"})
            except OSError:
                pass                 # router is gone; nobody to tell

    while True:
        msg = conn.recv()
        if msg is None:
            break
        op, rid = msg.get("op"), msg.get("id")
        try:
            if op == "hello":
                cfg = msg.get("config") or {}
                state.configure(
                    int(msg.get("shard", 0)),
                    BatcherConfig(
                        max_batch=cfg.get("max_batch", 32),
                        max_wait_ms=cfg.get("max_wait_ms", 2.0),
                        length_buckets=tuple(cfg.get("length_buckets")
                                             or ()),
                        pad_batch=cfg.get("pad_batch", True),
                        decode_slots=cfg.get("decode_slots", 64)),
                    int(msg.get("max_sessions", 4096)))
                state.shard.start()
                conn.send({"op": "ok", "id": rid, "pid": os.getpid(),
                           "shard": state.shard.shard_id})
                continue
            if op == "ping":
                # liveness probe: answered inline on the recv loop, so
                # a reply proves the worker is accepting frames (flush
                # work runs on its own thread and cannot block this)
                conn.send({"op": "ok", "id": rid})
                continue
            if op == "bye":
                draining = True
                # drain BEFORE acking: every queued request's result
                # frame hits the socket (FIFO) ahead of the goodbye, so
                # a router that stops with submits in flight still
                # resolves them — parity with the thread mesh's stop()
                if state.shard is not None:
                    state.shard.stop()
                conn.send({"op": "ok", "id": rid})
                break
            shard = state.shard
            if shard is None:
                raise RuntimeError(
                    "no hello yet: the first frame must be a hello "
                    "carrying shard id + config")
            registry, telemetry, cache = \
                state.registry, state.telemetry, state.cache
            if op == "publish":
                repeat = msg["model"] in registry
                registry.load_bytes(bytes(msg["ckpt"]), key=msg["model"],
                                    device_put=True)
                if repeat:           # pushes count as swaps, like swarm
                    telemetry.record_swap()     # pulls do in-process
                conn.send({"op": "ok", "id": rid,
                           "version": registry.version(msg["model"])})
            elif op == "ensemble":
                # spec sync rides its own op (specs are not weight
                # blobs): install is replica-style — stale versions are
                # skipped, so pushes racing a swap converge on the
                # newest spec. Members must already be published.
                registry.install_ensemble(msg["name"], msg["spec"],
                                          int(msg["version"]))
                conn.send({"op": "ok", "id": rid,
                           "version": registry.ensemble_version(
                               msg["name"])})
            elif op == "submit":
                if draining:
                    raise RuntimeError("shard is draining")
                ctx = _adopt(msg, "predict")
                fut = shard.submit(msg["model"], unpack_array(msg["window"]),
                                   client_id=msg.get("client"), trace=ctx)
                # resolves on the flush worker thread, out of order
                fut.add_done_callback(
                    lambda f, rid=rid, ctx=ctx: _send_result(rid, f, ctx))
            elif op == "step":
                if draining:
                    raise RuntimeError("shard is draining")
                # through the engine's batched decode path: every step
                # queued across the mesh's clients fuses into ONE decode
                # dispatch per flush, and a slow step no longer stalls
                # the recv loop (it used to run runner.step inline here)
                ctx = _adopt(msg, "step")
                hist = (unpack_array(msg["history"])
                        if msg.get("history") is not None else None)
                fut = shard.submit_step(msg["model"], msg["client"],
                                        unpack_array(msg["x"]),
                                        history=hist, trace=ctx)
                fut.add_done_callback(
                    lambda f, rid=rid, ctx=ctx: _send_result(rid, f, ctx))
            elif op == "warmup":
                lens = (tuple(msg["lengths"]) if msg.get("lengths")
                        else None)
                conn.send({"op": "ok", "id": rid,
                           "programs": shard.warmup(msg["model"],
                                                    lengths=lens)})
            elif op == "restore":
                # insert-if-absent: a migrated carry must never clobber
                # a fresher one a concurrent step already wrote here
                installed_ids = [
                    s["client"] for s in msg["sessions"]
                    if cache.put_new(s["client"],
                                     _unpack_carry(s["carry"]),
                                     s["nbytes"], version=s["version"])]
                if msg.get("durable") and installed_ids:
                    # checkpoint-sourced (not migration): count it, and
                    # count separately the carries stamped with a
                    # version this replica no longer hosts — those
                    # re-prime from history at their next step
                    hosted = {registry.version(k)
                              for k in registry.keys()}
                    ids = set(installed_ids)
                    telemetry.record_restore(
                        len(installed_ids),
                        stale=sum(1 for s in msg["sessions"]
                                  if s["client"] in ids
                                  and s["version"] not in hosted))
                conn.send({"op": "ok", "id": rid,
                           "installed": len(installed_ids)})
            elif op == "extract":
                # serialize against queued steps first: a step enqueued
                # before the membership flip must consume its carry
                # before we hand that carry to the new owner. Requested
                # sessions resident in a decode lane spill to the cache
                # so the export sees them (bitwise-identical carries)
                shard.quiesce(timeout=30.0)
                shard.spill_sessions(msg.get("clients"))
                out = [{"client": cid, "carry": _pack_carry(carry),
                        "nbytes": nbytes, "version": version}
                       for cid, carry, nbytes, version
                       in cache.export(msg.get("clients"))]
                conn.send({"op": "ok", "id": rid, "sessions": out})
            elif op == "snapshot":
                # durable-checkpoint export: NON-destructive (lanes
                # spill bitwise, the cache is read, nothing drained)
                # and no quiesce — a periodic checkpoint rides the slot
                # lock only, so it never stalls the flush pipeline
                out = [{"client": cid, "carry": _pack_carry(carry),
                        "nbytes": nbytes, "version": version}
                       for cid, carry, nbytes, version
                       in shard.snapshot_sessions(msg.get("clients"))]
                conn.send({"op": "ok", "id": rid, "sessions": out})
            elif op == "reconcile":
                # partition re-adoption: this worker kept serving state
                # across the partition (serve_shard --forever).
                # Sessions that moved on elsewhere — survivor copies
                # migrating in ("evict") or fresher checkpointed stream
                # versions ("index") — must beat its stale residents;
                # every other resident stays and resumes bitwise.
                evict = list(msg.get("evict") or [])
                index = msg.get("index") or {}
                affected = list(dict.fromkeys(evict + list(index)))
                shard.spill_sessions(affected)   # lanes -> cache, bitwise
                dropped = sum(1 for cid in evict if cache.drop(cid))
                kept = 0
                skip = set(evict)
                for cid, version in index.items():
                    if cid in skip:
                        continue
                    have = cache.peek_version(cid)
                    if have is None:
                        continue
                    if have < int(version):
                        dropped += int(cache.drop(cid))
                    else:
                        kept += 1
                conn.send({"op": "ok", "id": rid, "dropped": dropped,
                           "kept": kept})
            elif op == "stats":
                samples = telemetry.raw_samples()
                conn.send({
                    "op": "ok", "id": rid, "pid": os.getpid(),
                    "telemetry": telemetry.snapshot(),
                    "latency_s": samples["latency_s"],
                    "staleness_s": samples["staleness_s"],
                    "step_latency_s": samples["step_latency_s"],
                    "cache": cache.stats(),
                    # cache + lane-resident: the supervisor's crash
                    # repair extracts by this list, so sessions living
                    # in decode lanes must be visible here
                    "clients": shard.session_clients(),
                    "slots": shard.slot_stats(),
                    "versions": {k: registry.version(k)
                                 for k in registry.keys()}})
            elif op == "reset":
                telemetry.reset_clock()
                conn.send({"op": "ok", "id": rid})
            elif op == "count_start":
                # cross-process dispatch accounting: collectors are
                # per-process module globals, so the router cannot see
                # this worker's decode dispatches without asking
                if counter_cm is None:
                    from repro.kernels import dispatch as _dispatch

                    counter_cm = _dispatch.counting()
                    counter = counter_cm.__enter__()
                conn.send({"op": "ok", "id": rid})
            elif op == "count_stop":
                entries = []
                if counter_cm is not None:
                    shard.quiesce(timeout=30.0)   # count queued flushes
                    counter_cm.__exit__(None, None, None)
                    entries = [
                        {"backend": bk, "op": o, "impl": impl,
                         "shape": list(shape), "n": n}
                        for (bk, o, impl, shape), n
                        in counter.counts.items()]
                    counter_cm = counter = None
                conn.send({"op": "ok", "id": rid, "counts": entries})
            elif op == "drain":
                draining = True
                shard.stop()         # drains the queue: every queued
                # request's result frame is sent before this returns
                shard.spill_sessions()   # lanes -> spill tier, so the
                # full-cache export below carries every live session
                out = [{"client": cid, "carry": _pack_carry(carry),
                        "nbytes": nbytes, "version": version}
                       for cid, carry, nbytes, version in cache.export()]
                conn.send({"op": "ok", "id": rid, "sessions": out})
            else:
                raise ValueError(f"unknown op {op!r}")
        except Exception as e:  # noqa: BLE001 — fail the op, not the worker
            try:
                conn.send({"op": "error", "id": rid,
                           "message": f"{type(e).__name__}: {e}"})
            except OSError:
                break            # router is gone: nothing left to serve
    if counter_cm is not None:
        counter_cm.__exit__(None, None, None)
    if state.shard is not None:
        state.shard.stop()
    conn.close()


def serve_shard(host: str = "0.0.0.0", port: int = 0, *,
                forever: bool = False, on_bound=None,
                state_dir=None) -> None:
    """Run a shard worker in THIS process: bind, accept the router,
    serve until ``bye``/EOF. The standalone entry point behind
    ``python -m repro.launch.shard_worker`` — start it on any host and
    join it to a mesh with ``connect_shard("host:port")`` /
    ``add_shard(addr=...)``. With ``forever=True`` the worker outlives
    its router: serving state (weights, sessions) persists and the next
    connection resumes it. ``state_dir`` points at a ``DurableStore``
    root; a cold worker primes its replica registry from it on the
    first ``hello``. ``on_bound(port)`` reports the bound port
    (``spawn_shard`` pipes it back to the parent)."""
    import jax  # noqa: F401  (initialize this process's backend up front)

    srv = socket.create_server((host, port), backlog=1)
    if on_bound is not None:
        on_bound(srv.getsockname()[1])
    state = _ShardState(state_dir)
    try:
        while True:
            sock, _ = srv.accept()
            if not forever:
                srv.close()
            _serve_conn(Connection(sock), state)
            if not forever:
                break
    finally:
        try:
            srv.close()
        except OSError:
            pass


def _worker_main(pipe, host: str) -> None:
    """Entry point of one locally spawned shard worker process
    (``spawn`` context): report the bound port over the pipe, then
    serve one router connection. Configuration arrives in the router's
    ``hello`` frame — same handshake a standalone worker speaks."""
    def _report(port: int) -> None:
        pipe.send(port)
        pipe.close()

    serve_shard(host, 0, forever=False, on_bound=_report)


# -- router-side proxy -----------------------------------------------------

class RemoteShard:
    """Client proxy for one shard worker: the ``EngineShard`` submit
    surface plus the transport control ops, demultiplexing out-of-order
    replies onto per-request futures. ``process`` is the local
    ``mp.Process`` handle, or None for a worker joined by address
    (``addr`` then names it). Liveness is tracked two ways: the reader
    loop flags EOF (``_closed``) and stamps ``last_rx`` on every frame
    — the supervisor pings idle workers and treats a stale ``last_rx``
    / dead process / EOF as a crash."""

    def __init__(self, shard_id: int, process, conn: Connection,
                 addr: str | None = None):
        self.shard_id = shard_id
        self.process = process
        self.addr = addr
        self.pid = process.pid if process is not None else None
        self.versions: dict[str, int] = {}   # acked published versions
        self.ensemble_versions: dict[str, int] = {}   # acked spec versions
        self.last_rx = time.monotonic()      # newest frame from the worker
        self._slow_inflight = 0   # publish/warmup/drain calls in flight:
        # the worker's recv loop is busy, so a quiet wire is NOT a crash
        self._closed = False
        self._conn = conn
        # rid -> (future, TraceContext | None): the context stitches the
        # worker's exported spans back into the router-side trace
        self._pending: dict[int, tuple[Future, object]] = {}
        self._plock = threading.Lock()
        self._ids = itertools.count(1)
        self._reader = threading.Thread(
            target=self._read_loop, name=f"transport-proxy-{shard_id}",
            daemon=True)
        self._reader.start()

    def _read_loop(self) -> None:
        while True:
            msg = self._conn.recv()
            if msg is None:
                with self._plock:
                    # flagged INSIDE the lock: _request checks it there,
                    # so no future can be registered after this point —
                    # every pending one fails here, fast
                    self._closed = True
                    pending, self._pending = self._pending, {}
                for fut, ctx in pending.values():
                    if ctx is not None:
                        ctx.finish(status="error")
                    if not fut.done():
                        fut.set_exception(ConnectionError(
                            f"shard {self.shard_id} connection closed"))
                return
            self.last_rx = time.monotonic()
            with self._plock:
                entry = self._pending.pop(msg.get("id"), None)
            if entry is None:
                continue
            fut, ctx = entry
            if ctx is not None:
                # stitch the worker's half in, then close the trace
                # BEFORE set_result wakes the client: a caller reading
                # tracer.last() after result() sees the complete trace
                tinfo = msg.get("trace") or {}
                if tinfo.get("spans"):
                    ctx.tracer.add_spans(ctx, tinfo["spans"])
                if tinfo.get("t") is not None:
                    ctx.t_last = tinfo["t"]   # worker's send stamp
                ctx.mark("reply")             # wire + decode, back home
                ctx.finish(status="error" if msg["op"] == "error"
                           else "ok")
            if msg["op"] == "error":
                fut.set_exception(RuntimeError(
                    f"shard {self.shard_id}: {msg['message']}"))
            elif msg["op"] == "result":
                fut.model_version = msg.get("version")
                fut.set_result((msg["y"], msg["p"]))
            else:
                fut.set_result(msg)

    # -- liveness ----------------------------------------------------------
    def is_alive(self) -> bool:
        """False once the connection saw EOF or a local process died —
        the fast, authoritative signals; a remote hang only shows up as
        a stale ``last_rx`` (the supervisor's job)."""
        if self._closed:
            return False
        if self.process is not None and not self.process.is_alive():
            return False
        return True

    @property
    def slow_inflight(self) -> int:
        return self._slow_inflight

    def ping(self) -> Future:
        """Fire-and-forget liveness probe: any reply (this one's or any
        result frame) refreshes ``last_rx`` via the reader loop."""
        return self._request({"op": "ping"})

    def _request(self, msg: dict, trace=None) -> Future:
        rid = next(self._ids)
        fut: Future = Future()
        fut.set_running_or_notify_cancel()
        if trace is not None:
            # the frame carries the trace id + the parent span + the
            # send stamp; the worker adopts the id and records its half
            # from that stamp on (one machine, shared system clock)
            trace.mark("submit")
            msg["trace"] = {"id": trace.trace_id, "parent": trace.last_sid,
                            "t": trace.t_last}
        with self._plock:
            if self._closed or (self.process is not None
                                and not self.process.is_alive()):
                # fail FAST: a request registered after the reader saw
                # EOF (or the process died with bytes still in flight)
                # has nobody left to resolve it — it used to hang for
                # the full RPC timeout
                if trace is not None:
                    trace.finish(status="error")
                raise ConnectionError(
                    f"shard {self.shard_id} worker is gone (process dead "
                    f"or connection closed)")
            self._pending[rid] = (fut, trace)
        msg["id"] = rid
        try:
            self._conn.send(msg)
        except OSError as e:
            with self._plock:
                self._pending.pop(rid, None)
            if trace is not None:
                trace.finish(status="error")
            raise ConnectionError(
                f"shard {self.shard_id} send failed: {e}") from e
        return fut

    def _call(self, msg: dict, timeout: float = 60.0,
              slow: bool = False) -> dict:
        """Blocking request. ``slow=True`` marks ops that legitimately
        occupy the worker's recv loop for a while (publish device_put,
        warmup compiles, drain) so the supervisor's staleness check
        stands down instead of declaring a busy worker dead."""
        fut = self._request(msg)
        if not slow:
            return fut.result(timeout=timeout)
        with self._plock:
            self._slow_inflight += 1
        try:
            return fut.result(timeout=timeout)
        finally:
            with self._plock:
                self._slow_inflight -= 1

    # -- handshake ---------------------------------------------------------
    def hello(self, config: BatcherConfig | None = None,
              max_sessions: int = 4096) -> dict:
        """The first frame on every connection: ship shard id + batcher
        config + session budget; the worker builds (or renames) its
        serving state and acks with its pid."""
        config = config or BatcherConfig()
        reply = self._call({
            "op": "hello", "shard": self.shard_id,
            "config": {"max_batch": config.max_batch,
                       "max_wait_ms": config.max_wait_ms,
                       "length_buckets": list(config.length_buckets),
                       "pad_batch": config.pad_batch,
                       "decode_slots": config.decode_slots},
            "max_sessions": max_sessions}, timeout=300.0, slow=True)
        self.pid = reply.get("pid", self.pid)
        return reply

    # -- EngineShard surface ----------------------------------------------
    def submit(self, model_key: str, window, client_id=None,
               trace=None) -> Future:
        return self._request({"op": "submit", "model": model_key,
                              "client": client_id,
                              "window": pack_array(_wire_window(window))},
                             trace=trace)

    def submit_step(self, model_key: str, client_id: str, x_t,
                    history=None, trace=None) -> Future:
        msg = {"op": "step", "model": model_key, "client": client_id,
               "x": pack_array(np.asarray(x_t, np.float32))}
        if history is not None:
            msg["history"] = pack_array(np.asarray(history, np.float32))
        return self._request(msg, trace=trace)

    def step(self, model_key: str, client_id: str, x_t, history=None,
             trace=None):
        return self.submit_step(model_key, client_id, x_t, history=history,
                                trace=trace).result(timeout=60.0)

    def warmup(self, model_key: str, lengths=None) -> int:
        return self._call({"op": "warmup", "model": model_key,
                           "lengths": list(lengths) if lengths else None},
                          timeout=300.0, slow=True)["programs"]

    # -- transport control -------------------------------------------------
    def publish(self, model_key: str, ckpt: bytes) -> int:
        v = self._call({"op": "publish", "model": model_key,
                        "ckpt": ckpt}, timeout=300.0, slow=True)["version"]
        self.versions[model_key] = v
        return v

    def publish_ensemble(self, name: str, spec_wire: dict,
                         version: int) -> int:
        """Sync an ensemble spec (members/fusion knobs, not weights)."""
        v = self._call({"op": "ensemble", "name": name,
                        "spec": spec_wire, "version": version},
                       timeout=60.0)["version"]
        self.ensemble_versions[name] = v
        return v

    def stats(self) -> dict:
        return self._call({"op": "stats"})

    def reset_clock(self) -> None:
        self._call({"op": "reset"})

    def count_start(self) -> None:
        """Install a dispatch-count collector in the worker process."""
        self._call({"op": "count_start"})

    def count_stop(self):
        """Uninstall the worker's collector and return its counts as a
        ``DispatchCounts`` (queued flushes are counted first)."""
        from repro.kernels.dispatch import DispatchCounts

        counts = DispatchCounts()
        for e in self._call({"op": "count_stop"}, timeout=120.0)["counts"]:
            counts.add((e["backend"], e["op"], e["impl"],
                        tuple(e["shape"])), e["n"])
        return counts

    def restore(self, sessions: list[dict], durable: bool = False) -> int:
        """Install migrated session carries (insert-if-absent, one
        frame for the whole batch); returns how many were installed.
        ``durable=True`` marks checkpoint-sourced frames so the worker
        telemetry counts them (``restored_sessions``/``restored_stale``)
        instead of treating them as a live migration."""
        msg = {"op": "restore", "sessions": sessions}
        if durable:
            msg["durable"] = True
        return self._call(msg)["installed"]

    def extract(self, clients) -> list[dict]:
        return self._call({"op": "extract",
                           "clients": list(clients)})["sessions"]

    def snapshot_sessions(self, clients=None) -> list[dict]:
        """Read session frames WITHOUT removing them — the durable
        checkpoint path (``extract`` is the destructive migration
        path). No quiesce on the worker, so it never stalls a flush."""
        msg = {"op": "snapshot"}
        if clients is not None:
            msg["clients"] = list(clients)
        return self._call(msg, timeout=120.0)["sessions"]

    def reconcile(self, evict=(), index=None) -> dict:
        """Partition re-adoption: evict residents superseded by
        survivor copies (``evict``) or by fresher checkpointed stream
        versions (``index``: client -> version). Untouched residents
        stay and resume bitwise."""
        reply = self._call({"op": "reconcile", "evict": list(evict),
                            "index": dict(index or {})})
        return {"dropped": reply["dropped"], "kept": reply["kept"]}

    def drain(self) -> list[dict]:
        """Stop accepting work, finish the queue (every queued request
        resolves first), and return the worker's session carries for
        migration."""
        return self._call({"op": "drain"}, timeout=300.0,
                          slow=True)["sessions"]

    def abort(self) -> None:
        """Crash-path teardown: no goodbye. Closing the socket makes
        the reader loop fail every pending future immediately; a dead
        local process is reaped."""
        self._conn.close()
        if self.process is not None:
            self.process.join(5.0)
            if self.process.is_alive():
                self.process.terminate()
                self.process.join(5.0)

    def close(self, timeout: float = 60.0) -> None:
        try:
            # the bye ack arrives after the worker drains its queue, so
            # every in-flight submit future resolves before the socket
            # goes away
            self._call({"op": "bye"}, timeout=timeout)
        except Exception:  # noqa: BLE001 — already gone is fine
            pass
        self._conn.close()
        if self.process is not None:
            self.process.join(timeout)
            if self.process.is_alive():
                self.process.terminate()
                self.process.join(timeout)


def _refuse_on_tpu() -> None:
    """A TPU chip belongs to one process: a parent whose backend is the
    TPU holds its chips, so a spawned shard worker would fail on
    libtpu's lock or hang waiting for it. Refuse before spawning."""
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "local shard worker processes cannot share the TPU with this "
            "process (one process per chip): serve from one process with "
            "ShardedServingEngine, which places one replica on each chip")


def spawn_shard(shard_id: int, config: BatcherConfig | None = None,
                ctx=None, host: str = "127.0.0.1",
                max_sessions: int = 4096,
                spawn_timeout_s: float = 180.0) -> RemoteShard:
    """Start one shard worker process locally and connect to it — the
    single-machine convenience path over the same ``hello`` handshake a
    remote worker speaks. The child binds an ephemeral port and reports
    it back over a pipe before accepting the router's connection.
    Refused when this process runs on a TPU (one process per chip)."""
    _refuse_on_tpu()
    ctx = ctx or mp.get_context("spawn")
    parent_pipe, child_pipe = ctx.Pipe()
    proc = ctx.Process(target=_worker_main, args=(child_pipe, host),
                       name=f"shard-worker-{shard_id}", daemon=True)
    proc.start()
    child_pipe.close()
    if not parent_pipe.poll(spawn_timeout_s):
        proc.terminate()
        raise TimeoutError(
            f"shard worker {shard_id} did not report a port within "
            f"{spawn_timeout_s}s")
    port = parent_pipe.recv()
    parent_pipe.close()
    sock = socket.create_connection((host, port), timeout=30.0)
    # connect timeout ONLY: a timeout left on the socket poisons the
    # reader loop (makefile reads raise after 30 s of idle wire and the
    # proxy would treat a quiet-but-healthy worker as EOF)
    sock.settimeout(None)
    shard = RemoteShard(shard_id, proc, Connection(sock))
    try:
        shard.hello(config, max_sessions)
    except Exception:
        shard._conn.close()
        proc.terminate()
        raise
    return shard


def connect_shard(addr, shard_id: int = 0,
                  config: BatcherConfig | None = None,
                  max_sessions: int = 4096,
                  timeout_s: float = 30.0) -> RemoteShard:
    """Join a shard worker that is ALREADY listening — the remote-host
    path (``serve_shard`` / ``python -m repro.launch.shard_worker`` on
    the far machine). ``addr`` is ``"host:port"`` or a ``(host, port)``
    tuple. The ``hello`` handshake ships the shard id + config, so the
    worker needs no flags beyond where to listen."""
    if isinstance(addr, str):
        host, _, port = addr.rpartition(":")
        if not host or not port:
            raise ValueError(f"addr must be 'host:port', got {addr!r}")
        addr = (host, int(port))
    host, port = addr[0], int(addr[1])
    sock = socket.create_connection((host, port), timeout=timeout_s)
    sock.settimeout(None)      # see spawn_shard
    shard = RemoteShard(shard_id, None, Connection(sock),
                        addr=f"{host}:{port}")
    try:
        shard.hello(config, max_sessions)
    except Exception:
        shard._conn.close()
        raise
    return shard


# -- the multi-process mesh ------------------------------------------------

class MultiProcessServingEngine:
    """The sharded serving mesh over OS processes (and hosts): the
    ``ShardedServingEngine`` API, with every shard an ``EngineShard``
    worker process behind the socket transport. A process whose backend
    is the TPU cannot spawn local workers (one process per chip), so
    construction refuses there; ``ShardedServingEngine`` is the mesh for
    a TPU host.

    ``registry`` is the PRIMARY (defaults to a fresh ``ModelRegistry``):
    publishes against it — ``register`` / ``swap`` / ``load``, e.g. a
    ``WeightPublisher`` — are serialized via the checkpoint machinery
    and pushed to every worker whose acked version lags more than
    ``max_skew``, with a convergence sweep available via ``propagate``.
    Routing (client-affine + anonymous round-robin) and live membership
    behave exactly like the in-process mesh.

    Crash supervision: a background thread heartbeats every worker each
    ``heartbeat_s``. A worker is declared dead when its process exits,
    its connection hits EOF, or it answers nothing for ``miss_budget``
    heartbeats (with no slow op in flight). Repair fails the dead
    shard's pending futures immediately, shrinks the router (surviving
    shards keep serving, the dead shard's clients re-route), respawns a
    LOCAL worker in place — re-homing the session carries survivors
    hold — or parks a REMOTE shard in ``awaiting_rejoin`` until
    ``add_shard(addr=...)`` re-adopts it. Events land in ``events``
    (a ``repro.obs.EventLog``) and the ``crashes`` / ``respawns`` /
    ``rehomed_sessions`` counters.
    """

    def __init__(self, registry=None, config: BatcherConfig | None = None,
                 n_shards: int = 2, max_skew: int = 1,
                 max_sessions: int = 4096, host: str = "127.0.0.1",
                 tracer=None, heartbeat_s: float = 0.5,
                 miss_budget: int = 4, events=None,
                 supervise: bool = True, durable=None):
        from repro.serving.registry import ModelRegistry

        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if max_skew < 0:
            raise ValueError("max_skew must be >= 0")
        if heartbeat_s <= 0:
            raise ValueError("heartbeat_s must be > 0")
        if miss_budget < 1:
            raise ValueError("miss_budget must be >= 1")
        _refuse_on_tpu()      # start() spawns n_shards local workers
        self.registry = registry if registry is not None else ModelRegistry()
        self.config = config or BatcherConfig()
        # router-side tracer (repro.obs.Tracer | None): traces started
        # here propagate through the request frames, the workers record
        # their halves, and the stitched whole lands in this ring
        self.tracer = tracer
        self.max_skew = max_skew
        self.router = ConsistentRouter(range(n_shards))
        self.workers: dict[int, RemoteShard] = {}
        self.pulls = 0               # weight pushes to workers
        self.bytes_pulled = 0        # serialized checkpoint bytes shipped
        # crash supervision
        self.heartbeat_s = heartbeat_s
        self.miss_budget = miss_budget
        self.supervise = supervise
        self.events = events         # repro.obs.EventLog | None
        self.crashes = 0             # workers declared dead
        self.respawns = 0            # local workers respawned in place
        self.rehomed_sessions = 0    # carries migrated by joins/repairs
        # durable-state plane (repro.serving.durable.DurableStore | None)
        self.durable = None
        self.restored_sessions = 0   # carries re-installed from the store
        self.restored_stale = 0      # ...stamped with a no-longer-hosted
        #                              version; they re-prime from history
        self._rejoin: dict[int, str] = {}   # crashed remote: sid -> addr
        self._supervisor: threading.Thread | None = None
        self._sup_stop = threading.Event()
        self._host = host
        self._max_sessions = max_sessions
        self._ctx = mp.get_context("spawn")
        # push lock: publishes/pushes and version_vector — samples are
        # taken under it, so the skew bound is observable atomically.
        # route lock: submit/step routing. SEPARATE locks so a weight
        # push (serialize + synchronous worker acks) never stalls the
        # request intake; membership mutations take BOTH, always push
        # lock first (fixed order -> no deadlock).
        self._lock = threading.RLock()
        self._route_lock = threading.RLock()
        self._admin_lock = threading.RLock()
        self._anon_counters: dict[str, itertools.count] = {}
        self._warm_plan: dict[str, tuple | None] = {}
        self._attached = False
        self._stopped_versions: dict[int, dict] = {}
        if durable is not None:
            self.attach_durable(durable)

    def attach_durable(self, store) -> None:
        """Back this mesh with a ``DurableStore``: the primary registry
        commits every publish to it BEFORE acknowledgement (so the
        version vector never acks state the store could lose), and
        ``restore_from()`` / partition re-adoption read from it by
        default. Already-hosted models and ensembles commit now."""
        self.durable = store
        if hasattr(self.registry, "attach_durable"):
            self.registry.attach_durable(store)

    @property
    def n_shards(self) -> int:
        return len(self.workers) or len(self.router.shard_ids)

    @property
    def shard_ids(self) -> list[int]:
        return sorted(self.workers)

    @property
    def awaiting_rejoin(self) -> dict[int, str]:
        """Crashed REMOTE shards the supervisor cannot respawn from
        here: {shard_id: last known address}. Restart the worker on its
        host and call ``connect_shard(addr)`` to re-adopt it."""
        return dict(self._rejoin)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "MultiProcessServingEngine":
        with self._admin_lock:
            spawned = {sid: spawn_shard(sid, self.config, self._ctx,
                                        self._host, self._max_sessions)
                       for sid in self.router.shard_ids
                       if sid not in self.workers}
            with self._lock, self._route_lock:
                self.workers.update(spawned)
            with self._lock:
                for key in self.registry.keys():
                    self._push_locked(key, force=True)
                for name in self._ensemble_names():
                    self._push_ensemble_locked(name)
                if not self._attached:
                    self.registry.subscribe(self._on_publish)
                    if hasattr(self.registry, "subscribe_ensembles"):
                        self.registry.subscribe_ensembles(self._on_ensemble)
                    self._attached = True
        if self.supervise and self._supervisor is None:
            self._sup_stop.clear()
            self._supervisor = threading.Thread(
                target=self._supervise, name="mesh-supervisor", daemon=True)
            self._supervisor.start()
        return self

    def stop(self) -> None:
        # supervisor down FIRST: a repair racing the teardown must not
        # respawn workers we are about to close (repairs in flight see
        # the stop flag and skip the respawn)
        self._sup_stop.set()
        sup, self._supervisor = self._supervisor, None
        if sup is not None:
            sup.join()
        with self._admin_lock:
            with self._lock, self._route_lock:
                if self._attached:
                    self.registry.unsubscribe(self._on_publish)
                    if hasattr(self.registry, "unsubscribe_ensembles"):
                        self.registry.unsubscribe_ensembles(
                            self._on_ensemble)
                    self._attached = False
                workers, self.workers = dict(self.workers), {}
                # keep the fleet's last acked versions observable after
                # the processes are gone (version_vector post-stop)
                self._stopped_versions = {sid: dict(w.versions)
                                          for sid, w in workers.items()}
            for worker in workers.values():
                worker.close()

    def __enter__(self) -> "MultiProcessServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- crash supervision -------------------------------------------------
    def _supervise(self) -> None:
        budget = self.heartbeat_s * self.miss_budget
        while not self._sup_stop.wait(self.heartbeat_s):
            for sid, worker in list(self.workers.items()):
                try:
                    if not worker.is_alive():
                        # process dead or reader saw EOF: authoritative
                        self._repair(sid, worker)
                        continue
                    idle = time.monotonic() - worker.last_rx
                    if idle >= budget and worker.slow_inflight == 0:
                        # pings went unanswered for the whole budget
                        # (remote hang / network partition)
                        self._repair(sid, worker)
                    elif idle >= self.heartbeat_s * 0.5:
                        worker.ping()
                except ConnectionError:
                    self._repair(sid, worker)
                except Exception as e:  # noqa: BLE001 — supervision survives
                    if self.events is not None:
                        self.events.log("supervisor_error", shard=sid,
                                        error=f"{type(e).__name__}: {e}")

    def _repair(self, sid: int, worker: RemoteShard) -> None:
        """One dead worker's recovery: fail its pending futures NOW,
        shrink the router so survivors take its clients, then respawn
        in place (local) or park it for re-join (remote). Never raises
        — the supervisor must survive any repair outcome."""
        try:
            with self._admin_lock:
                if self.workers.get(sid) is not worker:
                    return       # already repaired / removed / replaced
                if worker.is_alive() and (time.monotonic() - worker.last_rx
                                          < self.heartbeat_s
                                          * self.miss_budget):
                    return       # false alarm: it answered meanwhile
                self.crashes += 1
                with self._lock, self._route_lock:
                    self.workers.pop(sid, None)
                    try:
                        self.router.remove_shard(sid)
                    except ValueError:
                        pass     # last shard: the router keeps the id so
                        # a respawn re-claims it; meanwhile routing to it
                        # fails fast (no live worker)
                worker.abort()   # reader EOF fails every pending future
                if self.events is not None:
                    self.events.log("shard_crash", shard=sid,
                                    remote=worker.addr is not None,
                                    pid=worker.pid)
                if worker.addr is not None:
                    # a remote worker cannot be respawned from here:
                    # remember where it lived and wait for a re-join
                    self._rejoin[sid] = worker.addr
                    if self.events is not None:
                        self.events.log("shard_await_rejoin", shard=sid,
                                        addr=worker.addr)
                    return
                if self._sup_stop.is_set():
                    return       # mesh is stopping: do not respawn
                replacement = spawn_shard(sid, self.config, self._ctx,
                                          self._host, self._max_sessions)
                moved = self._adopt_worker(sid, replacement)
                self.respawns += 1
                if self.events is not None:
                    self.events.log("shard_respawn", shard=sid,
                                    pid=replacement.pid, rehomed=moved)
        except Exception as e:  # noqa: BLE001 — supervision survives
            if self.events is not None:
                self.events.log("shard_respawn_failed", shard=sid,
                                error=f"{type(e).__name__}: {e}")

    # -- registry facade (WeightPublisher-compatible) ----------------------
    # Publishing THROUGH the mesh holds the push lock across the primary
    # publish and the worker pushes, so the skew bound is atomic in every
    # ``version_vector`` sample (like ``ShardSwarm``'s facade). Publishes
    # made directly against ``self.registry`` still propagate, one
    # subscription notify later.
    def register(self, key: str, forecaster, version: int | None = None):
        with self._lock:
            self.registry.register(key, forecaster, version)
            if not self._attached:   # no callback fired: push inline
                self._push_locked(key)
            return forecaster

    def swap(self, key: str, forecaster, version: int | None = None) -> int:
        with self._lock:
            v = self.registry.swap(key, forecaster, version)
            if not self._attached:
                self._push_locked(key)
            return v

    # ensemble specs ride the same facade shape: register/swap on the
    # primary, push to every worker atomically under the push lock (the
    # subscription fires with the RLock held, like model publishes)
    def register_ensemble(self, name: str, members, **opts):
        with self._lock:
            spec = self.registry.register_ensemble(name, members, **opts)
            if not self._attached:
                self._push_ensemble_locked(name)
            return spec

    def swap_ensemble(self, name: str, members, **opts) -> int:
        with self._lock:
            v = self.registry.swap_ensemble(name, members, **opts)
            if not self._attached:
                self._push_ensemble_locked(name)
            return v

    def ensemble(self, name: str):
        return self.registry.ensemble(name)

    def ensembles(self) -> dict:
        return self.registry.ensembles()

    def ensemble_version(self, name: str) -> int:
        return self.registry.ensemble_version(name)

    def get(self, key: str):
        return self.registry.get(key)

    def get_entry(self, key: str):
        return self.registry.get_entry(key)

    def version(self, key: str) -> int:
        return self.registry.version(key)

    def keys(self) -> list[str]:
        return self.registry.keys()

    def __contains__(self, key: str) -> bool:
        return key in self.registry

    # -- weight propagation ------------------------------------------------
    def _on_publish(self, key: str, version: int) -> None:
        # facade publishes arrive with the RLock already held on this
        # thread, so the push is atomic with the publish; direct primary
        # publishes take it here
        with self._lock:
            self._push_locked(key)

    def _push_locked(self, key: str, force: bool = False) -> int:
        entry = self.registry.get_entry(key)
        blob = None
        pushed = 0
        for worker in self.workers.values():
            have = worker.versions.get(key)
            behind = have is None or entry.version - have > self.max_skew
            if force:
                behind = have is None or have < entry.version
            if behind:
                if blob is None:     # serialize once per push round
                    blob = self.registry.save_bytes(key)
                try:
                    worker.publish(key, blob)      # synchronous ack
                except ConnectionError:
                    continue   # crashed mid-push: the supervisor will
                    # repair it, and a (re)join re-pushes with force
                self.pulls += 1
                self.bytes_pulled += len(blob)
                pushed += 1
        return pushed

    def _ensemble_names(self) -> list[str]:
        lister = getattr(self.registry, "ensembles", None)
        return lister() if lister is not None else []

    def _on_ensemble(self, name: str, spec, version: int) -> None:
        with self._lock:
            self._push_ensemble_locked(name)

    def _push_ensemble_locked(self, name: str, force: bool = False) -> int:
        spec = self.registry.ensemble(name)
        if spec is None:
            return 0
        version = self.registry.ensemble_version(name)
        wire = spec.to_wire()
        pushed = 0
        for worker in self.workers.values():
            have = worker.ensemble_versions.get(name)
            if not force and have is not None and have >= version:
                continue
            try:
                worker.publish_ensemble(name, wire, version)
            except ConnectionError:
                continue   # supervisor repairs it; rejoin re-pushes
            pushed += 1
        return pushed

    def propagate(self, key: str | None = None) -> int:
        """Push every worker up to the primary's newest version for
        ``key`` (or all keys); returns the number of pushes. An
        ensemble name resolves to its members' weights plus the spec
        itself (specs live in their own namespace, not the weight
        store, so ``_push_locked`` must never see one)."""
        with self._lock:
            spec = (self.registry.ensemble(key)
                    if key is not None and hasattr(self.registry,
                                                   "ensemble") else None)
            if spec is not None:
                n = sum(self._push_locked(m, force=True)
                        for m in spec.members)
                return n + self._push_ensemble_locked(key, force=True)
            keys = [key] if key is not None else self.registry.keys()
            n = sum(self._push_locked(k, force=True) for k in keys)
            if key is None:
                n += sum(self._push_ensemble_locked(name, force=True)
                         for name in self._ensemble_names())
            return n

    def version_vector(self, key: str) -> dict:
        """Atomic fleet snapshot {"primary": v, sid: acked_v, ...} —
        taken under the push lock, so the ``max_skew`` bound holds in
        every vector this returns. Dead workers awaiting repair are
        excluded: a corpse cannot ack a push, and its replacement
        re-syncs with force before taking traffic."""
        with self._lock:
            vec: dict = {"primary": self.registry.version(key)
                         if key in self.registry else 0}
            acked = ({sid: w.versions for sid, w in self.workers.items()
                      if w.is_alive()}
                     if self.workers else self._stopped_versions)
            for sid, versions in sorted(acked.items()):
                vec[sid] = versions.get(key, 0)
            return vec

    def skew(self, key: str) -> int:
        vec = self.version_vector(key)
        shard_vs = [v for k, v in vec.items() if k != "primary"]
        return max(shard_vs) - min(shard_vs) if shard_vs else 0

    def staleness(self, key: str) -> int:
        vec = self.version_vector(key)
        shard_vs = [v for k, v in vec.items() if k != "primary"]
        return vec["primary"] - min(shard_vs) if shard_vs else 0

    # -- durable state -----------------------------------------------------
    def checkpoint_state(self, store, weight_refs=None) -> dict:
        """One durable snapshot of the fleet, for ``DurableStore.commit``:
        hosted weight versions (re-serialized only when the version
        moved since the caller's last snapshot — ``weight_refs`` is the
        caller's ``{key: (version, blob_ref)}`` memo, mutated in
        place), ensemble specs, and every worker's session carries via
        the non-destructive ``snapshot`` op. Run off the hot path by a
        ``CheckpointDaemon``; a crashed worker is skipped (its carries
        stay whatever the previous snapshot holds — the supervisor is
        already repairing it)."""
        weight_refs = {} if weight_refs is None else weight_refs
        with self._lock:
            versions = {k: self.registry.version(k)
                        for k in self.registry.keys()}
            ensembles = {
                name: {"version": self.registry.ensemble_version(name),
                       "spec": self.registry.ensemble(name).to_wire()}
                for name in self._ensemble_names()}
        models = {}
        for key, v in sorted(versions.items()):
            memo = weight_refs.get(key)
            if memo is None or memo[0] != v or not store.has_blob(memo[1]):
                memo = (v, store.put_blob(self.registry.save_bytes(key)))
                weight_refs[key] = memo
            models[key] = {"version": v, "ref": memo[1]}
        frames: list[dict] = []
        for _sid, worker in sorted(self.workers.items()):
            try:
                frames.extend(worker.snapshot_sessions())
            except (ConnectionError, RuntimeError):
                continue
        from repro.serving.durable import pack_frames_blob

        return {"models": models, "ensembles": ensembles,
                "sessions": {"ref": store.put_blob(pack_frames_blob(frames)),
                             "count": len(frames)}}

    def restore_from(self, store=None) -> dict:
        """Cold-fleet restart from the durable tier: re-install the
        last good weight versions and ensemble specs into the primary
        registry (each load publishes, so workers converge through the
        normal push pipeline), force-converge every worker, then
        re-home the checkpointed session carries through the router's
        ownership hash. Carries stamped with a version that is no
        longer hosted count as ``restored_stale``: they install anyway
        and re-prime from history on their next step (the version
        fence in ``EngineShard._resolve_carry``). Call after
        ``start()``; returns a summary dict."""
        from repro.serving.durable import restore_registry

        store = store if store is not None else self.durable
        if store is None:
            raise ValueError(
                "no DurableStore — pass one or attach_durable() first")
        summary = restore_registry(store, self.registry)
        if summary is None:
            return {"seq": None, "models": {}, "ensembles": {},
                    "restored_sessions": 0, "restored_stale": 0}
        frames = summary.pop("session_frames")
        with self._lock:
            for key in self.registry.keys():
                self._push_locked(key, force=True)
            for name in self._ensemble_names():
                self._push_ensemble_locked(name, force=True)
            current = {self.registry.version(k)
                       for k in self.registry.keys()}
        stale = sum(1 for f in frames if f["version"] not in current)
        by_owner: dict[int, list] = {}
        with self._route_lock:
            for f in frames:
                sid = self.router.shard_for(str(f["client"]))
                by_owner.setdefault(sid, []).append(f)
        resumed = 0
        for sid, batch in sorted(by_owner.items()):
            worker = self.workers.get(sid)
            if worker is None:
                continue
            try:
                resumed += worker.restore(batch, durable=True)
            except (ConnectionError, RuntimeError):
                continue
        self.restored_sessions += resumed
        self.restored_stale += stale
        if self.events is not None:
            self.events.log("mesh_restore", seq=summary["seq"],
                            resumed=resumed, stale=stale)
        summary["restored_sessions"] = resumed
        summary["restored_stale"] = stale
        return summary

    # -- client API --------------------------------------------------------
    def shard_for(self, client_id: str) -> int:
        return self.router.shard_for(str(client_id))

    def _worker(self, sid: int) -> RemoteShard:
        worker = self.workers.get(sid)
        if worker is None:
            raise KeyError(
                f"router returned shard {sid} but this mesh has no such "
                f"worker (have {sorted(self.workers)}) — change "
                f"membership through add_shard/remove_shard")
        return worker

    def submit(self, model_key: str, window, client_id=None) -> Future:
        trace = (self.tracer.start("predict", meta={"model": model_key})
                 if self.tracer is not None else None)
        payload = np.asarray(window)
        with self._route_lock:
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
            return self._worker(sid).submit(model_key, payload,
                                            client_id=client_id,
                                            trace=trace)

    def predict(self, model_key: str, window, timeout: float | None = 60.0,
                client_id=None):
        return self.submit(model_key, window,
                           client_id=client_id).result(timeout=timeout)

    def submit_step(self, model_key: str, client_id: str, x_t,
                    history=None) -> Future:
        """Async streaming step, routed to the worker process owning
        ``client_id``. On the far side it rides the shard's batched
        decode path (``EngineShard.submit_step``), so N concurrent
        clients' steps fuse into one decode dispatch per flush."""
        trace = (self.tracer.start("step", meta={"model": model_key})
                 if self.tracer is not None else None)
        with self._route_lock:
            sid = self.router.shard_for(str(client_id))
            if trace is not None:
                trace.mark("route", shard=sid)
            worker = self._worker(sid)
        return worker.submit_step(model_key, str(client_id), x_t,
                                  history=history, trace=trace)

    def step(self, model_key: str, client_id: str, x_t, history=None):
        """One O(1) streaming step, served by the worker process owning
        ``client_id`` (its shard-local session cache holds the carry)."""
        return self.submit_step(model_key, client_id, x_t,
                                history=history).result(timeout=60.0)

    def warmup(self, model_key: str, lengths=None) -> int:
        self.propagate(model_key)
        self._warm_plan[model_key] = tuple(lengths) if lengths else None
        # snapshot: a shard joining mid-warmup must not break iteration
        workers = list(self.workers.values())
        if not workers:
            raise RuntimeError(
                "mesh has no live shards (call start() first, or every "
                "worker has crashed and repair is pending)")
        return max(worker.warmup(model_key, lengths=lengths)
                   for worker in workers)

    def reset_clock(self) -> None:
        for worker in list(self.workers.values()):
            worker.reset_clock()

    # -- live membership ---------------------------------------------------
    def _adopt_worker(self, sid: int, worker: RemoteShard) -> int:
        """Everything between "worker is connected" and "worker serves
        traffic": weight push, warm plan, router membership, and the
        migration of exactly the sessions the joiner wins. Shared by
        ``add_shard`` and crash respawn; caller holds the admin lock.
        Returns the number of re-homed sessions."""
        try:
            for key in self.registry.keys():
                blob = self.registry.save_bytes(key)
                worker.publish(key, blob)
                self.pulls += 1
                self.bytes_pulled += len(blob)
            # specs before the warm plan: warming an ensemble name on
            # the far side needs the spec installed there first
            for name in self._ensemble_names():
                worker.publish_ensemble(
                    name, self.registry.ensemble(name).to_wire(),
                    self.registry.ensemble_version(name))
            for model_key, lengths in list(self._warm_plan.items()):
                worker.warmup(model_key, lengths=lengths)
        except Exception:
            worker.close()
            raise
        with self._lock, self._route_lock:
            self.workers[sid] = worker
            for key in self.registry.keys():
                self._push_locked(key, force=True)  # catch up any
                # publish that raced the spawn, before taking traffic
            for name in self._ensemble_names():
                self._push_ensemble_locked(name)
            self.router.add_shard(sid)
        # migrate exactly the sessions the new shard wins, OUTSIDE
        # the locks (per-session RPCs must not stall the fleet's
        # intake): restores are insert-if-absent, so a fresher
        # carry written by a concurrent step always wins
        moved = 0
        incoming: list[dict] = []
        for old_sid, old_worker in list(self.workers.items()):
            if old_sid == sid:
                continue
            try:
                owned = [c for c in old_worker.stats()["clients"]
                         if self.router.shard_for(c) == sid]
                incoming.extend(old_worker.extract(owned) if owned else [])
            except (ConnectionError, RuntimeError):
                continue     # that worker is dying too — its own repair
                # will re-home whatever it held
        rejoin_frames: list[dict] = []
        if sid in self._rejoin and self.durable is not None:
            # partition re-adoption: the --forever worker kept its
            # residents; reconcile them against the store BEFORE the
            # survivor migration lands (evictions first, then the
            # insert-if-absent restores below settle precedence:
            # survivor copy > surviving resident > checkpointed frame)
            try:
                rejoin_frames = self._reconcile_rejoin(sid, worker,
                                                       incoming)
            except (ConnectionError, RuntimeError):
                rejoin_frames = []
        if incoming:
            moved += worker.restore(incoming)
        if rejoin_frames:
            with self._lock:
                current = {self.registry.version(k)
                           for k in self.registry.keys()}
            self.restored_sessions += worker.restore(rejoin_frames,
                                                     durable=True)
            self.restored_stale += sum(
                1 for f in rejoin_frames if f["version"] not in current)
        self.rehomed_sessions += moved
        return moved

    def _reconcile_rejoin(self, sid: int, worker: RemoteShard,
                          incoming: list[dict]) -> list[dict]:
        """A ``--forever`` worker re-adopted after a partition
        (``awaiting_rejoin``) kept its lane/cache-resident carries.
        Reconcile them against the durable store instead of discarding
        them: survivor copies (``incoming`` — they served the client
        THROUGH the partition) and fresher checkpointed stream versions
        evict the worker's stale residents; every other resident stays
        put and resumes bitwise. Returns the checkpointed frames this
        shard owns, for insert-if-absent re-install after the survivor
        migration (so survivors keep precedence)."""
        from repro.serving.durable import unpack_frames_blob

        frames: list[dict] = []
        found = self.durable.latest()
        if found is not None:
            sessions = found[1].get("sessions") or {}
            if sessions.get("ref"):
                frames = unpack_frames_blob(
                    self.durable.get_blob(sessions["ref"]))
        with self._route_lock:
            owned = [f for f in frames
                     if self.router.shard_for(str(f["client"])) == sid]
        evict = [s["client"] for s in incoming]
        worker.reconcile(evict=evict,
                         index={f["client"]: f["version"] for f in owned})
        skip = set(evict)
        return [f for f in owned if f["client"] not in skip]

    def add_shard(self, shard_id: int | None = None,
                  addr: str | tuple | None = None) -> int:
        """Grow the fleet by one worker: spawn a local process
        (default), or join a worker already listening on ``addr``
        (``"host:port"`` — the remote-host path, see ``serve_shard``).
        Either way the joiner receives every hosted model and warms its
        compile set BEFORE the router assigns it traffic. Returns the
        shard id."""
        with self._admin_lock:
            with self._lock:
                sid = (max(self.workers) + 1 if self.workers else 0) \
                    if shard_id is None else int(shard_id)
                if sid in self.workers:
                    raise ValueError(f"shard {sid} already exists")
            # the slow part (process spawn / dial, weight push, jit
            # warmup) happens while traffic keeps flowing to the fleet
            if addr is not None:
                worker = connect_shard(addr, sid, self.config,
                                       self._max_sessions)
            else:
                worker = spawn_shard(sid, self.config, self._ctx,
                                     self._host, self._max_sessions)
            moved = self._adopt_worker(sid, worker)
            self._rejoin.pop(sid, None)
            if self.events is not None:
                self.events.log("shard_join", shard=sid,
                                remote=addr is not None, rehomed=moved)
            return sid

    def connect_shard(self, addr, shard_id: int | None = None) -> int:
        """Join the shard worker listening at ``addr`` — sugar for
        ``add_shard(addr=...)``; also how a crashed remote shard
        re-joins (see ``awaiting_rejoin``)."""
        return self.add_shard(shard_id=shard_id, addr=addr)

    def remove_shard(self, shard_id: int) -> None:
        """Shrink the fleet by one worker process: the router stops
        assigning it traffic, its queue drains (zero drops), and its
        session carries migrate to the surviving owners."""
        sid = int(shard_id)
        with self._admin_lock:
            with self._lock, self._route_lock:
                if sid not in self.workers:
                    raise KeyError(f"no shard {sid}; have "
                                   f"{sorted(self.workers)}")
                if len(self.workers) == 1:
                    raise ValueError("cannot remove the last shard")
                self.router.remove_shard(sid)
                worker = self.workers.pop(sid)
            # lock released: traffic flows to survivors while the
            # departing worker finishes its queue
            sessions = worker.drain()
            by_owner: dict[int, list] = {}
            for session in sessions:
                by_owner.setdefault(
                    self.router.shard_for(session["client"]),
                    []).append(session)
            for owner_sid, batch in by_owner.items():
                self.workers[owner_sid].restore(batch)
            worker.close()

    # -- observation -------------------------------------------------------
    def shard_stats(self) -> dict[int, dict]:
        """Raw per-worker stats (telemetry snapshot, cache stats, hosted
        versions, resident session clients, worker pid). A worker that
        crashes between the membership snapshot and its RPC is skipped
        — the supervisor is already on it."""
        workers = dict(self.workers)     # snapshot vs live membership
        out: dict[int, dict] = {}
        for sid in sorted(workers):
            try:
                out[sid] = workers[sid].stats()
            except ConnectionError:
                continue
        return out

    def snapshot(self) -> dict:
        """Fleet-wide telemetry in the same shape as
        ``Telemetry.merge`` (``Telemetry.format`` accepts it), pooled
        from the worker processes' snapshots, plus transport and
        supervision counters."""
        stats = self.shard_stats()
        lat: list[float] = []
        stale: list[float] = []
        step_lat: list[float] = []
        totals = {"requests": 0, "batches": 0, "real_slots": 0,
                  "padded_slots": 0, "swaps": 0, "reprimes": 0,
                  "step_requests": 0, "step_batches": 0}
        by_version: dict[int, int] = {}
        by_client: dict[str, int] = {}
        by_shard: list[int] = []
        elapsed = 1e-9
        hits = misses = evictions = 0
        for sid, st in stats.items():
            tel = st["telemetry"]
            by_shard.append(tel["requests"])
            totals["requests"] += tel["requests"]
            totals["batches"] += tel["batches"]
            totals["swaps"] += tel["swaps"]
            totals["reprimes"] += tel["reprimes"]
            totals["step_requests"] += tel["step_requests"]
            totals["step_batches"] += tel["step_batches"]
            # occupancy reconstructed from the means the snapshot keeps
            totals["real_slots"] += int(round(
                tel["mean_batch"] * tel["batches"]))
            occ = tel["batch_occupancy"]
            totals["padded_slots"] += int(round(
                tel["mean_batch"] * tel["batches"] / occ)) if occ else 0
            elapsed = max(elapsed, tel["requests"]
                          / max(tel["throughput_rps"], 1e-9))
            for v, n in tel["requests_by_version"].items():
                v = int(v)
                by_version[v] = by_version.get(v, 0) + n
            for c, n in tel.get("requests_by_client", {}).items():
                by_client[c] = by_client.get(c, 0) + n
            lat.extend(st["latency_s"])
            stale.extend(st["staleness_s"])
            step_lat.extend(st.get("step_latency_s", ()))
            hits += st["cache"]["hits"]
            misses += st["cache"]["misses"]
            evictions += st["cache"]["evictions"]
        lookups = hits + misses
        # one sort per pooled list (see telemetry._percentiles)
        lat50, lat95, lat99 = _percentiles(lat, (50, 95, 99))
        stale50, stale95 = _percentiles(stale, (50, 95))
        step50, step95 = _percentiles(step_lat, (50, 95))
        return {
            "shards": len(stats),
            "requests": totals["requests"],
            "requests_by_shard": by_shard,
            "batches": totals["batches"],
            "throughput_rps": totals["requests"] / elapsed,
            "p50_ms": lat50 * 1e3,
            "p95_ms": lat95 * 1e3,
            "p99_ms": lat99 * 1e3,
            "mean_batch": (totals["real_slots"] / totals["batches"]
                           if totals["batches"] else 0.0),
            "batch_occupancy": (totals["real_slots"]
                                / totals["padded_slots"]
                                if totals["padded_slots"] else 0.0),
            "cache_hit_rate": hits / lookups if lookups else 0.0,
            "cache_evictions": evictions,
            "swaps": totals["swaps"],
            "reprimes": totals["reprimes"],
            "step_requests": totals["step_requests"],
            "step_batches": totals["step_batches"],
            "step_p50_ms": step50 * 1e3,
            "step_p95_ms": step95 * 1e3,
            "staleness_p50_s": stale50,
            "staleness_p95_s": stale95,
            "requests_by_version": by_version,
            "requests_by_client": by_client,
            "unique_clients": len(by_client),
            "pulls": self.pulls,
            "bytes_pulled": self.bytes_pulled,
            "crashes": self.crashes,
            "respawns": self.respawns,
            "rehomed_sessions": self.rehomed_sessions,
            "restored_sessions": self.restored_sessions,
            "restored_stale": self.restored_stale,
        }
