"""Benchmark-backed kernel dispatch: Pallas kernel vs XLA lowering,
resolved per (backend, op, shape) at TRACE time.

The repo ships two implementations of each hot op — a Pallas kernel
(``kernels/<op>/kernel.py``, compiled on TPU, interpret-mode elsewhere)
and the pure-jnp math XLA lowers itself. Which one is faster depends on
the backend and the shape: on CPU the Pallas path only exists in
interpret mode (orders of magnitude slower — it stays available as an
explicitly forced fallback for kernel debugging), while on TPU the
fused kernel wins once the batch fills a sublane tile. This module owns
that decision so every caller — train-time ``rnn_features``, serve-time
``step``/``replay``/``predict`` — resolves it the same way:

- ``resolve(op, batch=..., hidden=...)`` consults a rule table keyed by
  backend. The default table encodes what ``benchmarks/bench_kernels``
  measures (its ``dispatch`` phase re-measures both impls and
  ``--tune-out`` writes a fresh table).
- The table can be replaced wholesale: ``load_table(path)`` /
  ``save_table(path)`` round-trip JSON, and the ``REPRO_DISPATCH_TABLE``
  env var points at a tuned table to load lazily on first resolve.
- ``REPRO_KERNEL_IMPL=pallas|xla`` (or ``force(impl)``) overrides every
  rule — the kill switch when a tuned table turns out wrong in prod.

Resolution happens while tracing (shapes are static there, and
``jax.default_backend()`` reflects any backend configured after
import — same lesson as the trace-time ``_on_tpu`` fix in the op
wrappers), so a compiled program bakes in one implementation and the
choice costs nothing at run time.
"""

from __future__ import annotations

import json
import os
import threading

import jax

from repro.kernels.lstm.ops import lstm_cell_padded
from repro.kernels.lstm.ref import lstm_cell_ref

# rule table: op -> backend -> list of {min_batch, min_hidden, impl}
# rules, first match wins, no match -> "xla". Backends not listed fall
# back to the "default" entry. Floors (not ranges) keep the table tiny
# and monotone: bigger shapes only ever move TOWARD the fused kernel.
DEFAULT_TABLE: dict = {
    "lstm_cell": {
        # CPU: XLA everywhere. At micro shapes the interpret-mode kernel
        # can LOOK competitive (dispatch overhead dominates both — see
        # bench_kernels' dispatch phase), but it interprets the grid
        # python-side, so it falls off a cliff as shapes grow and is
        # never the right default off-TPU.
        "cpu": [],
        # TPU: one sublane tile (8 rows) amortizes the kernel's weight
        # loads; below that the XLA fusion is at parity or better
        "tpu": [{"min_batch": 8, "min_hidden": 8, "impl": "pallas"}],
        "default": [],
    },
}

# reentrant: set_rules resolves the active table while holding it
_lock = threading.RLock()
_table: dict | None = None          # lazy: env table loads on first use


def _active_table() -> dict:
    global _table
    if _table is None:
        with _lock:
            if _table is None:
                path = os.environ.get("REPRO_DISPATCH_TABLE")
                _table = _load(path) if path else _copy(DEFAULT_TABLE)
    return _table


def _copy(table: dict) -> dict:
    return {op: {bk: [dict(r) for r in rules]
                 for bk, rules in per_op.items()}
            for op, per_op in table.items()}


def _load(path: str) -> dict:
    with open(path) as f:
        loaded = json.load(f)
    table = _copy(DEFAULT_TABLE)
    for op, per_op in loaded.items():
        table.setdefault(op, {}).update(
            {bk: [dict(r) for r in rules] for bk, rules in per_op.items()})
    return table


def load_table(path: str) -> dict:
    """Replace the active table with ``path``'s JSON (merged over the
    defaults, so a tuned table may override just one backend)."""
    global _table
    with _lock:
        _table = _load(path)
    return _table


def save_table(path: str, table: dict | None = None) -> None:
    """Persist ``table`` (default: the active one) as JSON — the output
    of a ``bench_kernels --tune-out`` run."""
    with open(path, "w") as f:
        json.dump(table if table is not None else _active_table(), f,
                  indent=2, sort_keys=True)


def set_rules(op: str, backend: str, rules: list[dict]) -> None:
    """Install dispatch rules for (op, backend) — the programmatic
    re-tune hook (``bench_kernels`` uses it before ``save_table``)."""
    with _lock:
        _active_table().setdefault(op, {})[backend] = \
            [dict(r) for r in rules]


def reset_table() -> None:
    """Back to the built-in defaults (drops env/file/set_rules state)."""
    global _table
    with _lock:
        _table = None


def resolve(op: str, *, batch: int, hidden: int,
            backend: str | None = None) -> str:
    """Pick ``"pallas"`` or ``"xla"`` for ``op`` at this shape. Call
    while tracing: ``batch``/``hidden`` are static shapes there and the
    backend is read when the surrounding program traces, not at import.
    """
    forced = os.environ.get("REPRO_KERNEL_IMPL")
    if forced:
        if forced not in ("pallas", "xla"):
            raise ValueError(
                f"REPRO_KERNEL_IMPL={forced!r}: must be 'pallas' or 'xla'")
        return forced
    per_op = _active_table().get(op, {})
    if backend is None:
        backend = jax.default_backend()
    rules = per_op.get(backend, per_op.get("default", []))
    for rule in rules:
        if batch >= rule.get("min_batch", 0) \
                and hidden >= rule.get("min_hidden", 0):
            return rule["impl"]
    return "xla"


class force:
    """Context manager pinning every resolve to one impl (tests and
    kernel debugging): ``with dispatch.force("pallas"): ...``."""

    def __init__(self, impl: str):
        if impl not in ("pallas", "xla"):
            raise ValueError(f"impl must be 'pallas' or 'xla', got {impl!r}")
        self.impl = impl
        self._saved: str | None = None

    def __enter__(self) -> "force":
        self._saved = os.environ.get("REPRO_KERNEL_IMPL")
        os.environ["REPRO_KERNEL_IMPL"] = self.impl
        return self

    def __exit__(self, *exc) -> None:
        if self._saved is None:
            os.environ.pop("REPRO_KERNEL_IMPL", None)
        else:
            os.environ["REPRO_KERNEL_IMPL"] = self._saved


# -- dispatch accounting ----------------------------------------------------
#
# resolve() fires at TRACE time (once per compilation), so the dispatch
# COUNT has to be recorded where the compiled function is invoked — the
# forecaster calls record() right before each jitted-fn call. Counting
# is opt-in: with no collector installed, record() is a truthiness
# check and an immediate return.

_collectors: list["DispatchCounts"] = []


class DispatchCounts:
    """Per-(backend, op, impl, shape) invocation counts, collected while
    installed via ``counting()``. ``shape`` is the (batch, hidden) the
    caller dispatched at — the padded shape, i.e. what actually ran."""

    def __init__(self):
        self.counts: dict[tuple, int] = {}

    def add(self, key: tuple, n: int = 1) -> None:
        self.counts[key] = self.counts.get(key, 0) + n

    def total(self, op: str | None = None) -> int:
        return sum(n for (bk, o, impl, shape), n in self.counts.items()
                   if op is None or o == op)

    def by_op(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for (bk, op, impl, shape), n in self.counts.items():
            out[op] = out.get(op, 0) + n
        return out

    def __getitem__(self, op: str) -> int:
        return self.total(op)

    def __repr__(self) -> str:
        return f"DispatchCounts({self.counts!r})"


def record(op: str, *, batch: int, hidden: int, impl: str | None = None,
           kernel_op: str | None = None, n: int = 1) -> None:
    """Count one dispatch of a compiled ``op`` at (batch, hidden).
    ``impl`` defaults to what ``resolve`` picks for ``kernel_op`` (or
    ``op``) at this shape — resolved only when a collector is installed,
    so the inactive path stays a single truthiness check."""
    if not _collectors:
        return
    if impl is None:
        impl = resolve(kernel_op or op, batch=batch, hidden=hidden)
    key = (jax.default_backend(), op, impl, (batch, hidden))
    with _lock:
        for c in _collectors:
            c.add(key, n)


class counting:
    """Collect dispatch counts inside a ``with`` block::

        with dispatch.counting() as counts:
            engine.submit_step(...)          # ... flush ...
        assert counts["slots_generate"] == 1   # one fused dispatch
        assert counts["decode_step"] == 0      # nothing per-session

    This is how tier-1 proves the steady-state decode contract: each
    step flush is exactly one ``slots_generate`` dispatch over the
    device-resident slot state (per-session ``decode_step`` stays
    zero; ``slots_insert`` fires only when a session enters a lane).
    Collectors nest (each sees every dispatch while installed)."""

    def __enter__(self) -> DispatchCounts:
        self._counts = DispatchCounts()
        with _lock:
            _collectors.append(self._counts)
        return self._counts

    def __exit__(self, *exc) -> None:
        with _lock:
            _collectors.remove(self._counts)


# -- dispatched ops ---------------------------------------------------------

def lstm_cell(x, h, c, wx, wh, b):
    """The dispatch-routed LSTM cell: x [B, I]; h, c [B, H]; gates
    packed [i, f, g, o]. Resolves Pallas-vs-XLA from the table at trace
    time; the XLA path is the exact expression ``repro.models.rnn``
    always used, so a "xla" resolution changes nothing numerically. The
    Pallas path shares ``ops.lstm_cell_padded`` (un-jitted, so it
    inlines into whatever program is tracing)."""
    if resolve("lstm_cell", batch=x.shape[0],
               hidden=h.shape[-1]) == "pallas":
        return lstm_cell_padded(x, h, c, wx, wh, b)
    return lstm_cell_ref(x, h, c, wx, wh, b)
