"""Spans for the serving stack and the trainer, on two clocks.

``span(name)`` opens a host span on the profiler's clock: it is
``jax.profiler.TraceAnnotation`` (jax imported on first use, so this
module stays importable without it). With no profiler running its cost
is the inactive TraceMe check, under a microsecond. A profiler trace
holds these spans on the same clock as the device's operations, so they
are what attributes the device's idle gaps to what the host was doing.
``SPANS`` names every span the program opens, with what it covers.

The ``Tracer`` ring below keeps per-request spans on its own clock,
``now()``: ``perf_counter`` anchored to the epoch once at import. That
clock is comparable across the processes of one machine (a request
stitched over the socket transport) but is not the profiler's, which
differs from it by a constant, so ring spans cannot be placed against
device gaps. The ring is for per-request views (queue wait, a request's
path across processes); ``span`` is for gap attribution.

A trace is born at ``Tracer.start`` (one per request), accumulates
spans as the request moves ``submit -> queue -> flush -> gather ->
dispatch -> scatter -> reply``, and is ``finish``-ed into a bounded
ring of completed traces. Spans are plain (t0, t1) pairs on ``now()``.

The recording path is built around the same amortization as the engine
it observes:

- One object, no registry, no lock: ``Trace`` is both the span store
  and the context handle threaded alongside the request (``start`` is a
  single allocation), and a request has exactly ONE writer at any time
  — the submitter records nothing after the enqueue (even its "submit"
  span is reconstructed by the flush worker from the request's own
  enqueue stamp), so the worker owns the trace outright. Recording is a
  clock read and a tuple append. An abandoned trace is garbage-collected
  with the request — there is no active table to leak. A ``closed``
  flag makes recording on a finished/exported trace a silent no-op.
- Per-flush, not per-request: the engine stamps one shared
  ``FlushSpans`` record per micro-batch (queue/gather/dispatch/scatter/
  reply — ONE clock read per stage per *flush*) and each traced request
  attaches to it with a single tuple append. Spans materialize lazily
  when a trace is read (``trace.spans``) or shipped (``export``) — the
  hot path never allocates Span objects.
- Fully deferred on the in-process hot path: when the engine's own
  tracer covers a request (no upstream context to stitch into), no
  Trace object exists during serving at all — the submitter stashes one
  clock stamp, the flush worker appends one ``(t_start, t_enq)`` pair,
  and the whole micro-batch completes as a single ``finish_block``
  (one ring append, one lock, per FLUSH). Trace objects materialize,
  once, when the ring is read. Per-request cost is ~one clock read on
  each side — which is what keeps always-on tracing inside the
  serving benchmark's 5% overhead budget.
- Trace ids are lazy too: only the cross-process path (which must ship
  an id in the request frame) ever pays for one. ``meta`` is taken as a
  prebuilt dict, by reference — hot callers share one dict per
  (model, shard) instead of building one per request.

Cross-process stitching: the socket transport ships the trace id + the
parent span id in its request frames, the worker ``adopt``s the id into
its own tracer (span ids offset so they never collide with the
router's), and the result frame carries the worker's materialized spans
back — ``add_spans`` merges them so one request yields ONE trace whose
spans cover submit->reply across the process boundary. Timestamps from
the two processes share the system clock (same machine), so
``Trace.gaps`` takes an epsilon for the residual skew; in-process
traces chain timestamps exactly (each ``mark`` starts where the
previous span ended) and have zero gaps by construction.

Disabling: a ``Tracer(enabled=False)`` (or ``tracer.enabled = False``)
returns ``None`` from ``start``/``adopt`` and every caller in the
serving stack guards on that — tracing off means no clock reads, no
allocations, nothing on the hot path.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque

# perf_counter anchored to the epoch once: span timestamps are monotonic
# within a process but comparable across processes on one machine
_EPOCH = time.time() - time.perf_counter()
_perf_counter = time.perf_counter


def now() -> float:
    """Wall-clock seconds from a monotonic source (see ``_EPOCH``)."""
    return _EPOCH + _perf_counter()


# every span the program opens with ``span``, and what it covers
SPANS = {
    "repro.engine.wait":
        "the flush thread blocked on its queue, waiting for traffic",
    "repro.engine.flush.predict":
        "one predict flush, from its running requests to the last reply",
    "repro.engine.flush.step":
        "one step flush, from its running requests to the last reply",
    "repro.engine.gather":
        "a predict flush's weights lookup and right-padded batch",
    "repro.engine.reply":
        "a flush's telemetry, results to the futures and trace finish",
    "repro.predict": "the jitted predict call (launch, not its wait)",
    "repro.readback":
        "the host waiting for and copying a forecast and alert back",
    "repro.decode_step": "one single-chunk decode step call",
    "repro.decode_replay": "one full-window replay (prefill) call",
    "repro.zoo.prefill":
        "a token session primed from its history: the prefill program's "
        "call, its cache of the session's positions built",
    "repro.slots_insert": "a carry written into a decode lane",
    "repro.slots_extract": "a carry read out of a decode lane",
    "repro.slots_generate": "one fused generate over the decode lanes",
    "repro.session_step_many":
        "a session runner's batched step: lanes, generate, readback",
    "repro.sessions.lane_miss":
        "one lane insert: the spill tier or history, the LRU lane's "
        "extract and spill, the insert",
    "repro.round": "one local-SGD round of the trainer",
    "repro.round.local":
        "the call of the round program, with the copy of its batches to "
        "the device; at tau 0 the program holds the model exchange",
    "repro.round.exchange":
        "the stale apply at tau >= 1: a round's average applied tau "
        "rounds late",
    "repro.round.loss": "the host waiting for the round's mean loss",
}

_annotation = None


def span(name: str):
    """A host span ``name`` (a key of ``SPANS``) on the profiler's
    clock, as a context manager: ``with span("repro.round"): ...``."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name)


# trace ids must be unique across the router and worker processes that
# share one stitched trace: pid + per-process counter (generated lazily
# — in-process traces never need one)
_ids = itertools.count(1)


def _new_trace_id() -> str:
    return f"{os.getpid():x}-{next(_ids)}"


class Span:
    """One named [t0, t1] interval — materialized from a trace's raw
    records when the trace is read, never allocated on the hot path."""

    __slots__ = ("name", "t0", "t1", "sid", "parent", "meta")

    def __init__(self, name: str, t0: float, t1: float, sid: int,
                 parent: int | None = None, meta: dict | None = None):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.sid = sid
        self.parent = parent
        self.meta = meta

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        d = {"name": self.name, "t0": self.t0, "t1": self.t1,
             "sid": self.sid}
        if self.parent is not None:
            d["parent"] = self.parent
        if self.meta:
            d["meta"] = self.meta
        return d

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, dur={self.dur * 1e3:.3f}ms, "
                f"sid={self.sid})")


class FlushSpans:
    """One micro-batch flush's span stamps, shared by every traced
    request in the batch: the engine stamps each stage ONCE and each
    request's trace holds a reference — per-flush cost, not
    per-request (see the module docstring)."""

    __slots__ = ("stamps", "umb")

    def __init__(self):
        self.stamps: list[tuple] = []     # (name, t, meta)
        self.umb: tuple | None = None     # (name, t0, t1)

    def stamp(self, name: str, meta: dict | None = None) -> float:
        """Record stage ``name`` at now (one clock read per flush
        stage; ``meta``, if given, is shared by reference). Returns the
        stamp so callers can chain umbrella spans off it."""
        t = _EPOCH + _perf_counter()
        self.stamps.append((name, t, meta))
        return t

    def umbrella(self, name: str, t0: float, t1: float) -> None:
        """The explicit [t0, t1] span overlapping the chained stamps
        (the engine's whole-flush span)."""
        self.umb = (name, t0, t1)


# raw record kinds in Trace._raw (materialized in insertion order):
#   ("m", name, t0, t1, meta)      eager span (mark / explicit span)
#   ("f", FlushSpans, t0, t_sub)   flush attach; expands to a "submit"
#                                  span [t0, t_sub] (the client-side
#                                  validate+enqueue, reconstructed from
#                                  the request's enqueue stamp so the
#                                  submitter never records) followed by
#                                  the record's stamps chained from t_sub
#   ("d", span_dict)               a span shipped from another process,
#                                  with its original sid/parent


class Trace:
    """One request's spans AND the context handle threaded alongside the
    request — a single allocation per traced request. ``t_last`` chains
    span boundaries (each ``mark`` records [t_last, now] and advances
    it), so in-process traces are gapless by construction. Recording is
    lock-free: see the module docstring's single-writer argument. The
    tracer hands out completed traces by reference, so treat them as
    read-only once finished."""

    __slots__ = ("tracer", "op", "meta", "status", "closed", "t_last",
                 "_tid", "_raw", "_sid_base", "_live_sid", "__weakref__")

    def __init__(self, tracer: "Tracer", op: str, meta: dict | None,
                 t0: float, sid_base: int = 0,
                 trace_id: str | None = None):
        self.tracer = tracer
        self.op = op
        self.meta = meta if meta is not None else {}
        self.status = "open"
        self.closed = False
        self.t_last = t0
        self._tid = trace_id
        self._raw: list[tuple] = []
        self._sid_base = sid_base
        self._live_sid = sid_base - 1

    # backward-compatible context alias (context and trace are one
    # object now; ``req.trace.trace`` still resolves)
    @property
    def trace(self) -> "Trace":
        return self

    @property
    def trace_id(self) -> str:
        tid = self._tid
        if tid is None:
            tid = self._tid = _new_trace_id()
        return tid

    @property
    def last_sid(self) -> int | None:
        """Sid of the last eagerly marked span (the frame-carried
        parent for cross-process stitching). Meaningful only before a
        flush record attaches — exactly when the transport reads it."""
        sid = self._live_sid
        return sid if sid >= self._sid_base else None

    # -- recording ---------------------------------------------------------
    def mark(self, name: str, t: float | None = None,
             **meta) -> int | None:
        """Record the span [t_last, t] (t defaults to now) and advance
        t_last to its end."""
        if self.closed:
            return None
        t = _EPOCH + _perf_counter() if t is None else t
        self._raw.append(("m", name, self.t_last, t, meta or None))
        self.t_last = t
        self._live_sid += 1
        return self._live_sid

    def span(self, name: str, t0: float | None = None,
             t1: float | None = None, **meta):
        """With (t0, t1): record an explicit span without moving
        ``t_last`` (umbrella spans overlapping the chained ones).
        With only a name: look up the first materialized span called
        ``name`` (None if absent)."""
        if t0 is None:
            for s in self.spans:
                if s.name == name:
                    return s
            return None
        if self.closed:
            return None
        self._raw.append(("m", name, t0, t1, meta or None))
        self._live_sid += 1
        return self._live_sid

    def attach_flush(self, flush: FlushSpans,
                     t_submit: float | None = None) -> None:
        """Join this request to a shared per-flush record: ONE tuple
        append, and the submitter is completely off the recording path.
        ``t_submit`` is the request's enqueue stamp as a RAW
        ``perf_counter`` reading (the engine's ``t_enq``) — it becomes
        the end of a reconstructed "submit" span [t_last, t_submit],
        and the flush's stamps chain from it at materialization."""
        if not self.closed:
            t0 = self.t_last
            t_sub = t0 if t_submit is None else _EPOCH + t_submit
            self._raw.append(("f", flush, t0, t_sub))

    def finish(self, status: str = "ok") -> "Trace | None":
        return self.tracer.finish(self, status=status)

    # -- reading -----------------------------------------------------------
    @property
    def spans(self) -> list[Span]:
        """Materialize the raw records, in recording order. Cheap
        relative to recording frequency: reading happens per scrape or
        per export, recording per request."""
        out: list[Span] = []
        sid = self._sid_base
        for rec in self._raw:
            kind = rec[0]
            if kind == "m":
                _, name, t0, t1, meta = rec
                out.append(Span(name, t0, t1, sid, None, meta))
                sid += 1
            elif kind == "f":
                _, flush, t0, prev = rec
                if prev > t0:
                    out.append(Span("submit", t0, prev, sid))
                    sid += 1
                for name, t, meta in flush.stamps:
                    out.append(Span(name, prev, t, sid, None, meta))
                    sid += 1
                    prev = t
                if flush.umb is not None:
                    name, u0, u1 = flush.umb
                    out.append(Span(name, u0, u1, sid))
                    sid += 1
            else:  # "d": shipped from another process, sid preserved
                d = rec[1]
                out.append(Span(d["name"], d["t0"], d["t1"],
                                d.get("sid", -1), d.get("parent"),
                                d.get("meta")))
        return out

    @property
    def t_start(self) -> float:
        spans = self.spans
        return min(s.t0 for s in spans) if spans else 0.0

    @property
    def t_end(self) -> float:
        spans = self.spans
        return max(s.t1 for s in spans) if spans else 0.0

    @property
    def duration(self) -> float:
        spans = self.spans
        if not spans:
            return 0.0
        return max(s.t1 for s in spans) - min(s.t0 for s in spans)

    def names(self) -> list[str]:
        return [s.name for s in sorted(self.spans, key=lambda s: s.t0)]

    def gaps(self, eps: float = 0.0) -> list[tuple[float, float]]:
        """Uncovered intervals inside [t_start, t_end] longer than
        ``eps`` — empty means the spans cover the request end to end."""
        spans = self.spans
        if not spans:
            return []
        out = []
        covered_to = None
        for s in sorted(spans, key=lambda s: s.t0):
            if covered_to is not None and s.t0 > covered_to + eps:
                out.append((covered_to, s.t0))
            covered_to = s.t1 if covered_to is None else max(covered_to,
                                                             s.t1)
        return out

    def to_dict(self) -> dict:
        spans = sorted(self.spans, key=lambda s: s.t0)
        return {"trace_id": self.trace_id, "op": self.op,
                "status": self.status, "meta": self.meta,
                "t_start": spans[0].t0 if spans else 0.0,
                "duration": (max(s.t1 for s in spans) - spans[0].t0
                             if spans else 0.0),
                "spans": [s.to_dict() for s in spans]}


# the context handle and the trace are one object (see Trace docstring);
# the old name stays importable for callers that annotate with it
TraceContext = Trace


class _TraceBlock:
    """A whole flush's deferred traces in one object: per request only a
    raw ``(t_start, t_enq)`` stamp pair (perf_counter clock), plus the
    shared ``FlushSpans`` record — the in-process serving hot path
    allocates NO Trace objects at all. ``Trace``s materialize (and are
    cached, so ids stay stable across reads) the first time the ring is
    read."""

    __slots__ = ("op", "meta", "flush", "entries", "status", "_traces")

    def __init__(self, op: str, meta: dict | None, flush: FlushSpans,
                 entries: list, status: str):
        self.op = op
        self.meta = meta
        self.flush = flush
        self.entries = entries          # [(t_start, t_enq) perf stamps]
        self.status = status
        self._traces: list[Trace] | None = None

    @property
    def n(self) -> int:
        return len(self.entries)

    def materialize(self, tracer: "Tracer") -> list[Trace]:
        if self._traces is None:
            out = []
            for t0, t_enq in self.entries:
                tr = Trace(tracer, self.op, self.meta, _EPOCH + t0)
                tr._raw.append(("f", self.flush, _EPOCH + t0,
                                _EPOCH + t_enq))
                tr.closed = True
                tr.status = self.status
                out.append(tr)
            self._traces = out
        return self._traces


def finish_all(traces, status: str = "ok") -> None:
    """Finish a whole flush's traces, taking each tracer's ring lock
    ONCE (traces in one flush almost always share a tracer)."""
    by_tracer: dict[int, tuple[Tracer, list[Trace]]] = {}
    for t in traces:
        by_tracer.setdefault(id(t.tracer), (t.tracer, []))[1].append(t)
    for tracer, group in by_tracer.values():
        tracer.finish_many(group, status=status)


class Tracer:
    """Bounded, thread-safe trace store: a ring of the most recent
    completed traces (active traces live only on their requests and are
    garbage-collected if abandoned — nothing to leak, nothing to
    evict)."""

    def __init__(self, capacity: int = 256, enabled: bool = True):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.enabled = enabled
        self.capacity = capacity
        self._lock = threading.Lock()
        # completed traces, oldest first: Trace entries interleaved with
        # _TraceBlock entries (a block counts as its n traces)
        self._done: deque = deque()
        self._count = 0
        # counters are advisory (updated without the lock; a concurrent
        # increment may occasionally be lost — recording must stay
        # lock-free, and monitoring does not need exact totals)
        self.started = 0
        self.finished = 0
        self.exported = 0

    def _evict(self) -> None:
        """Trim the ring to capacity (caller holds the lock). Blocks are
        trimmed entry-by-entry so capacity is exact, not block-granular."""
        while self._count > self.capacity:
            head = self._done[0]
            if isinstance(head, Trace):
                self._done.popleft()
                self._count -= 1
            else:
                drop = min(head.n, self._count - self.capacity)
                del head.entries[:drop]
                if head._traces is not None:
                    del head._traces[:drop]
                self._count -= drop
                if not head.entries:
                    self._done.popleft()

    # -- producing ---------------------------------------------------------
    def start(self, op: str, t0: float | None = None,
              meta: dict | None = None) -> Trace | None:
        """Open a new trace; returns None when tracing is disabled (all
        downstream recording is guarded on that). ``meta`` is kept by
        REFERENCE — hot callers pass one shared dict per (model, shard)
        rather than building a fresh one per request."""
        if not self.enabled:
            return None
        self.started += 1
        return Trace(self, op, meta,
                     _EPOCH + _perf_counter() if t0 is None else t0)

    def adopt(self, trace_id: str, op: str = "", t0: float | None = None,
              parent: int | None = None, sid_base: int = 64,
              meta: dict | None = None) -> Trace | None:
        """Open a trace under an EXISTING id — the worker side of a
        cross-process request. ``sid_base`` offsets this process's span
        ids so they never collide with the originator's; ``parent``
        (the frame-carried parent span id) is kept in the trace meta."""
        if not self.enabled:
            return None
        meta = dict(meta) if meta else {}
        if parent is not None:
            meta["parent_span"] = parent
        self.started += 1
        return Trace(self, op, meta, now() if t0 is None else t0,
                     sid_base=sid_base, trace_id=trace_id)

    def add_spans(self, trace: Trace, spans) -> None:
        """Stitch span dicts recorded by another process (the worker's
        half of a cross-process trace) into the trace, with their
        original sids."""
        if trace.closed:
            return
        for d in spans:
            trace._raw.append(("d", d))

    def export(self, trace: Trace) -> list[dict]:
        """Close the trace and return its materialized spans as dicts —
        the worker ships these back in the result frame. Later
        recording / ``finish`` calls become no-ops, so the engine's
        post-set_result bookkeeping is harmless on exported traces."""
        if trace.closed:
            return []
        trace.closed = True
        self.exported += 1
        return [s.to_dict() for s in trace.spans]

    def finish(self, trace: Trace, status: str = "ok") -> Trace | None:
        """Move the trace into the completed ring; returns it (or None
        when the trace was already exported/finished)."""
        if trace.closed:
            return None
        trace.closed = True
        trace.status = status
        with self._lock:
            self._done.append(trace)
            self._count += 1
            self.finished += 1
            self._evict()
        return trace

    def finish_many(self, traces, status: str = "ok") -> None:
        """``finish`` a whole flush's traces under one ring lock."""
        with self._lock:
            for trace in traces:
                if trace.closed:
                    continue
                trace.closed = True
                trace.status = status
                self._done.append(trace)
                self._count += 1
                self.finished += 1
            self._evict()

    def finish_block(self, op: str, meta: dict | None, flush: FlushSpans,
                     entries: list, status: str = "ok") -> None:
        """Complete a whole flush's DEFERRED traces in one shot: one
        ring append + one lock for the entire micro-batch, no Trace
        allocations (they materialize lazily when the ring is read).
        ``entries`` are raw perf_counter ``(t_start, t_enq)`` pairs —
        see ``_TraceBlock``."""
        if not entries:
            return
        block = _TraceBlock(op, meta, flush, entries, status)
        with self._lock:
            self._done.append(block)
            n = len(entries)
            self._count += n
            self.started += n     # deferred traces skip start() entirely
            self.finished += n
            self._evict()

    # -- reading -----------------------------------------------------------
    def traces(self, n: int | None = None) -> list[Trace]:
        """Most recent completed traces, oldest first (deferred blocks
        materialize here, once, with stable identities)."""
        with self._lock:
            out: list[Trace] = []
            for e in self._done:
                if isinstance(e, Trace):
                    out.append(e)
                else:
                    out.extend(e.materialize(self))
        return out if n is None else out[-n:]

    def last(self) -> Trace | None:
        out = self.traces(1)
        return out[-1] if out else None

    def clear(self) -> None:
        with self._lock:
            self._done.clear()
            self._count = 0

    def stats(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled, "started": self.started,
                    "finished": self.finished, "exported": self.exported,
                    "completed": self._count}
