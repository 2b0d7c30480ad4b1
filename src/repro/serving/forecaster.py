"""One serving interface over every model: ``predict(windows, lengths)
-> (forecast, extreme_probability)``.

The interface itself is the ``Forecaster`` protocol below (batch
prediction) plus ``StreamingForecaster`` (adds O(1) incremental state:
explicit carries, replay, and decode-slot residency). Anything
satisfying them composes — the registry, engine, session runner and
mesh layers are written against the protocols, which is what lets
``repro.serving.ensemble.EnsembleForecaster`` (a model *set* fused by
EVT-weighted combination) serve through the exact same paths as a
single model.

Two concrete single-model implementations:

- ``LSTMForecaster`` — the paper model (2xLSTM + 3xFC, window 20). The
  forecast is the next-step normalized close; the extreme probability
  fuses the trained EVL sigmoid head with the EVT tail machinery of
  ``repro.extreme`` (eq. 3 GEV depth-into-tail + eq. 4 exceedance), with
  the eq. 1 indicator as the discrete alert. Streams sessions through
  the decode lanes, its carry the per-layer (h, c) state.

- ``ZooForecaster`` — any ``repro.models.model_zoo`` arch serving
  next-token prediction; the "extreme event" is an anomalously
  surprising continuation (surprisal in the EVT tail), the serving-side
  analogue of the paper's extreme-event indicator. A hybrid by layer
  pattern (granite-4.0-h) also streams sessions through the decode
  lanes, its carry the session's decode cache.

Both are calibrated by ``fit_tail`` over a reference score distribution,
so ``p_extreme`` is comparable across models hosted in one registry.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.extreme.evt import fit_tail, gev_cdf, tail_probability
from repro.extreme.indicators import indicator_sequence, quantile_thresholds
from repro.kernels import dispatch
from repro.models.rnn import (RNNConfig, init_rnn, init_rnn_carry,
                              rnn_apply_padded, rnn_step)
from repro.obs import trace as obs_trace

PyTree = Any


@runtime_checkable
class Forecaster(Protocol):
    """What the serving plane requires of a servable model: shape
    metadata plus batched prediction. Structural — ``LSTMForecaster``,
    ``ZooForecaster`` and ``EnsembleForecaster`` all satisfy it without
    inheriting anything."""

    kind: str

    @property
    def window(self) -> int: ...

    @property
    def feature_dim(self) -> int: ...

    def predict(self, windows, lengths=None): ...


@runtime_checkable
class StreamingForecaster(Forecaster, Protocol):
    """A ``Forecaster`` that also serves O(1) streaming sessions through
    device-resident decode lanes. Carries are opaque pytrees to callers
    (the LSTM's per-layer (h, c) tuples, a zoo hybrid's decode cache of
    SSM, conv and key/value state, an ensemble's {member: carry} dict);
    a step's input is one feature vector [F], or one token id where
    ``feature_dim`` is 0. This is the contract ``RecurrentSessionRunner``
    is written against: a session lives in a lane (``init_slots``), is
    primed from its history (``prefill``, the same as ``replay``), enters
    a lane (``insert``), is stepped with every other stepped lane in one
    program (``generate``), leaves it for the host's spill tier
    (``extract``, ``release``), and every lane program compiles off the
    serving path (``warm_slots``). ``step`` is the one-session
    functional step that lanes are bitwise-equal to."""

    @property
    def decode_width(self) -> int: ...

    def init_carry(self, batch: int = 1): ...

    def carry_nbytes(self, batch: int = 1) -> int: ...

    def step(self, x_t, carry): ...

    def replay(self, window, carry=None): ...

    def prefill(self, window, carry=None): ...

    def init_slots(self, num_slots: int): ...

    def insert(self, slots, lane: int, carry, donate=None): ...

    def extract(self, slots, lane: int): ...

    def release(self, slots, lane: int) -> None: ...

    def generate(self, slots, x, lanes=None, donate=None): ...

    def warm_slots(self, num_slots: int) -> int: ...


def input_spec(fc) -> tuple[tuple[int, ...], Any]:
    """(shape, dtype) of one time step of ``fc``'s input: a float32
    feature vector [F], or one int32 token id where ``feature_dim`` is
    0. A window stacks T of them."""
    F = fc.feature_dim
    return ((F,), np.float32) if F else ((), np.int32)


def streams(fc) -> bool:
    """Whether ``fc`` serves streaming sessions: the incremental
    contract, and (a token model) a decode cache with a length per row."""
    return all(hasattr(fc, a) for a in ("init_carry", "step", "replay")) \
        and getattr(fc, "streaming", True)


# One compiled function set per RNNConfig, shared by every forecaster
# instance with that config. This is what makes weight hot-swapping
# cheap: a freshly published version reuses the traced programs of the
# version it replaces (params — and the EVT tail calibration — are
# traced arguments, so only shapes key the jit cache), and the swap
# itself never compiles. The predict/step variants fuse the GEV alert
# head into the same program as the model apply: one dispatch per
# micro-batch flush, no eager tail math on the serving hot path (which
# is what lets concurrent mesh shards overlap their GIL-free compute).
_RNN_COMPILED: dict[RNNConfig, dict[str, Any]] = {}
_RNN_COMPILED_LOCK = threading.Lock()


def _donate_default() -> bool:
    """Carry donation default: on wherever it actually buys anything —
    i.e. off-CPU. XLA:CPU implements buffer donation as a warn + copy,
    so on CPU the default stays off and steady state is unchanged."""
    return jax.default_backend() != "cpu"


def _fused_alert(score, head, xi, scale, active, gamma):
    """Jit-side twin of ``_alert_probability``. ``active`` is a TRACED
    flag (uncalibrated forecasters pass False with dummy xi/scale): one
    compiled program serves both states, so a calibration flip — e.g.
    the first ``WeightPublisher`` publish re-calibrating an uncalibrated
    v1 — never compiles on the serving hot path."""
    z = (score - xi) / jnp.maximum(scale, 1e-8)
    p = jnp.where(active, gev_cdf(z, gamma), jnp.zeros_like(score))
    if head is not None:
        p = 1.0 - (1.0 - head) * (1.0 - p)
    return jnp.clip(p, 0.0, 1.0)


def _compiled_rnn(cfg: RNNConfig):
    """Compiled function set for ``cfg`` — double-checked lock, so
    concurrent first lookups (shard-join warmup races) build the
    wrappers exactly once instead of racing to the dict slot."""
    fns = _RNN_COMPILED.get(cfg)
    if fns is None:
        with _RNN_COMPILED_LOCK:
            fns = _RNN_COMPILED.get(cfg)
            if fns is None:
                fns = _build_rnn_fns(cfg)
                _RNN_COMPILED[cfg] = fns
    return fns


def _build_rnn_fns(cfg: RNNConfig):
    def predict(params, x, lens, xi, scale, active, gamma):
        y, u = rnn_apply_padded(params, x, lens, cfg=cfg)
        return y, _fused_alert(jnp.abs(y), u, xi, scale, active, gamma)

    def step(params, x_t, carry, xi, scale, active, gamma):
        y, u, carry = rnn_step(params, x_t, carry, cfg=cfg)
        return y, _fused_alert(jnp.abs(y), u, xi, scale, active,
                               gamma), carry

    # -- decode lane -----------------------------------------------
    # Every streaming step (a session's replay, a one-session step, a
    # generate over the lanes) runs the SAME step subgraph at one
    # fixed batch width, ``lane_step``. That is what makes step ==
    # replay == generate hold BITWISE: XLA compiles the fused step
    # differently at different batch shapes (measured: batch-N and
    # batch-1 programs disagree in the low bits), but within one
    # program each row's output is a pure function of that row, so
    # padding rows never perturb real sessions. The select that keeps
    # the carry of the lanes a call does not step is part of the
    # subgraph in every program: the CPU compiler expands the barriers
    # before it fuses, and a select fused onto the cell update in one
    # program but not in another contracts its multiply-adds
    # differently (measured: one ulp of the cell state). Measured
    # bitwise on the CPU at widths 2 and 8; at width 1 a replay still
    # drifts from the step.

    def lane_step(params, x, carry, mask, xi, scale, active, gamma):
        # x [W, F], carry [W, H]-stacked, mask [W] the lanes stepped
        x, carry, mask = jax.lax.optimization_barrier((x, carry, mask))
        y, p, c2 = step(params, x, carry, xi, scale, active, gamma)
        c2 = jax.tree_util.tree_map(
            lambda new, old: jnp.where(mask[:, None], new, old), c2, carry)
        return jax.lax.optimization_barrier((y, p, c2))

    def decode_step(params, x_t, carry, mask, xi, scale, active, gamma):
        # x_t [b, F], carry [b, H]-stacked, b <= width = len(mask)
        pad = mask.shape[0] - x_t.shape[0]
        xp = jnp.pad(x_t, ((0, pad), (0, 0)))
        cp = jax.tree_util.tree_map(
            lambda a: jnp.pad(a, ((0, pad), (0, 0))), carry)
        y, p, c2 = lane_step(params, xp, cp, mask, xi, scale, active, gamma)
        b = x_t.shape[0]
        return y[:b], p[:b], jax.tree_util.tree_map(lambda a: a[:b], c2)

    def decode_replay(params, window, carry, mask, xi, scale, active,
                      gamma):
        # window [b, T, F], b <= width = len(mask): one lax.scan over
        # the lane step, so a cache-miss replay is ONE dispatch instead
        # of O(window) host round trips. The scan is fully unrolled:
        # inside a rolled loop body XLA selects instructions
        # differently (FMA contraction, fusion shapes) than in the
        # standalone step program (window lengths are bounded by
        # cfg.window, so the unrolled programs stay small).
        pad = mask.shape[0] - window.shape[0]
        wp = jnp.pad(window, ((0, pad), (0, 0), (0, 0)))
        cp = jax.tree_util.tree_map(
            lambda a: jnp.pad(a, ((0, pad), (0, 0))), carry)

        def body(c, x_t):
            y, p, c2 = lane_step(params, x_t, c, mask, xi, scale, active,
                                 gamma)
            return c2, (y, p, c2)

        c2, (ys, ps, cs) = jax.lax.scan(
            body, cp, jnp.swapaxes(wp, 0, 1), unroll=wp.shape[1])
        b = window.shape[0]
        # EVERY per-step output, the intermediate carries included,
        # stays live in the result (sliced, like every other result):
        # were any of them dead code, XLA would prune parts of the
        # earlier iterations and re-fuse what remains differently from
        # the standalone step program, breaking bitwise parity
        # (measured: stacking y/p alone is not enough)
        return (ys[:, :b], ps[:, :b],
                jax.tree_util.tree_map(lambda a: a[:, :b], cs),
                jax.tree_util.tree_map(lambda a: a[:b], c2))

    # -- device-resident decode slots ------------------------------
    # The continuous-batching state: ``num_slots`` lanes of stacked
    # carries that LIVE on device. ``insert`` writes one session's
    # batch-1 carry into a lane (dynamic_update_slice with a TRACED
    # lane index — one compiled program serves every lane, and the
    # donating variant updates the slot state in place, no full-state
    # copy). ``extract`` is its inverse (spill / migration read).
    # ``generate`` steps ALL lanes in one dispatch: the slot state is
    # walked in static chunks of the decode-lane width, each chunk
    # running ``lane_step``.

    def slots_insert(slot_carry, carry, lane):
        # slot_carry [S, H]-stacked, carry [1, H]-stacked, lane int32
        return jax.tree_util.tree_map(
            lambda s, row: jax.lax.dynamic_update_slice(s, row, (lane, 0)),
            slot_carry, carry)

    def slots_extract(slot_carry, lane):
        return jax.tree_util.tree_map(
            lambda s: jax.lax.dynamic_slice(s, (lane, 0), (1, s.shape[1])),
            slot_carry)

    def slots_generate(params, x, slot_carry, step_mask, xi, scale,
                       active, gamma, width):
        # x [S, F], slot_carry [S, H]-stacked, S a static multiple of
        # ``width``. step_mask [S] marks the lanes this flush steps;
        # the others pass their carry through unchanged.
        S = x.shape[0]
        ys, ps, cs = [], [], []
        for lo in range(0, S, width):
            y, p, c2 = lane_step(
                params, x[lo:lo + width],
                jax.tree_util.tree_map(lambda a: a[lo:lo + width],
                                       slot_carry),
                step_mask[lo:lo + width], xi, scale, active, gamma)
            ys.append(y)
            ps.append(p)
            cs.append(c2)
        return jnp.concatenate(ys), jnp.concatenate(ps), \
            jax.tree_util.tree_map(
                lambda *parts: jnp.concatenate(parts, axis=0), *cs)

    # gamma is static: gev_log_cdf branches on it in Python, and it
    # is a per-deployment constant (one compile per distinct value)
    return {
        "predict": jax.jit(predict, static_argnames=("gamma",)),
        "decode_step": jax.jit(decode_step, static_argnames=("gamma",)),
        "decode_replay": jax.jit(decode_replay, static_argnames=("gamma",)),
        "slots_insert": jax.jit(slots_insert),
        # in-place lane write: the slot state is donated back to
        # itself, so an insert never copies the other lanes
        "slots_insert_donate": jax.jit(slots_insert, donate_argnums=(0,)),
        "slots_extract": jax.jit(slots_extract),
        "slots_generate": jax.jit(slots_generate,
                                  static_argnames=("gamma", "width")),
        # the steady-state program: slot carries donated in and out —
        # one dispatch per flush, zero allocation, zero host copies
        "slots_generate_donate": jax.jit(slots_generate,
                                         static_argnames=("gamma", "width"),
                                         donate_argnums=(2,)),
    }


def _alert_probability(score, tail: dict | None, gamma: float, head=None):
    """Fuse the EVT tail calibration with an optional learned head.

    ``score`` is the magnitude being judged (|forecast| or surprisal).
    GEV depth-into-tail (eq. 3) gives a monotone [0, 1] extremeness
    measure: ~0 below the calibrated threshold xi, exp(-1) at xi, -> 1
    deep in the tail. A learned sigmoid head (the paper's EVL head) is
    combined by noisy-OR so either detector can raise the alert.
    """
    score = jnp.asarray(score, jnp.float32)
    if tail is None:
        p_evt = jnp.zeros_like(score)
    else:
        z = (score - tail["xi"]) / max(tail["scale"], 1e-8)
        p_evt = gev_cdf(z, gamma)
    if head is not None:
        p_evt = 1.0 - (1.0 - jnp.asarray(head, jnp.float32)) * (1.0 - p_evt)
    return jnp.clip(p_evt, 0.0, 1.0)


@dataclasses.dataclass
class DecodeSlots:
    """Device-resident decode slot state: ``num_slots`` lanes of stacked
    carries held as device arrays, plus a host-side active-lane mask
    (and, for token models, each lane's session length). Sessions are
    written into lanes with ``insert`` (prefill → insert), stepped in
    place by ``generate`` (one fused dispatch for ALL lanes), and read
    out only on spill/migration (``extract``).
    ``num_slots`` is always a multiple of the owning forecaster's
    ``decode_width`` — ``init_slots`` rounds up — so generate can chunk
    the state at the lane width with no partial chunk."""

    carry: PyTree                # the carries, stacked on a lane axis
    num_slots: int
    active: Any                  # np.ndarray bool [num_slots], host-side
    lengths: Any = None          # np.ndarray int [num_slots], host-side

    @property
    def n_active(self) -> int:
        return int(self.active.sum())


@dataclasses.dataclass
class LSTMForecaster:
    """Paper LSTM behind the serving interface. ``tail`` holds the
    ``fit_tail`` parameters over |forecast| scores; ``eps`` the eq. 1
    indicator thresholds."""

    cfg: RNNConfig
    params: PyTree
    tail: dict | None = None
    eps: tuple[float, float] = (0.01, 0.01)
    gamma: float = 5.0
    # stamped by ModelRegistry.register/swap: monotone per-key version and
    # publication time (for staleness-at-serve-time telemetry)
    version: int = 0
    published_at: float | None = None
    # decode-lane width: EVERY streaming step/replay runs the fused step
    # at this fixed batch width (padded; larger batches chunk), which is
    # what keeps step == replay == generate bitwise-equal — XLA
    # compiles different batch shapes differently, one shared width
    # side-steps that. 8 = one TPU sublane tile; also the Pallas
    # kernel's block_b.
    decode_width: int = 8
    kind: str = dataclasses.field(default="lstm", init=False)

    def __post_init__(self):
        if self.decode_width < 1:
            raise ValueError(
                f"decode_width must be >= 1, got {self.decode_width}")
        self._fns = _compiled_rnn(self.cfg)

    # -- batched serving ---------------------------------------------------
    @property
    def window(self) -> int:
        return self.cfg.window

    @property
    def feature_dim(self) -> int:
        return self.cfg.input_dim

    def predict(self, windows, lengths=None):
        """windows [B, T, F] (right-padded), lengths [B] true lengths.
        Returns (forecast [B], p_extreme [B]) as float32 numpy arrays.
        One fused jit dispatch: model apply + GEV alert head."""
        windows = jnp.asarray(windows, jnp.float32)
        if lengths is None:
            lengths = jnp.full((windows.shape[0],), windows.shape[1],
                               jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        dispatch.record("predict", batch=int(windows.shape[0]),
                        hidden=self.cfg.hidden, kernel_op="lstm_cell")
        with obs_trace.span("repro.predict"):
            y, p = self._fns["predict"](self.params, windows, lengths,
                                        *self._tail_args(),
                                        gamma=float(self.gamma))
        with obs_trace.span("repro.readback"):
            return np.asarray(y), np.asarray(p)

    def _tail_args(self):
        """(xi, scale, active) for the fused alert: dummies + inactive
        when uncalibrated — same program either way."""
        if self.tail is None:
            return 0.0, 1.0, False
        return float(self.tail["xi"]), float(self.tail["scale"]), True

    def predict_detail(self, windows, lengths=None) -> dict:
        """Rich output: forecast, p_extreme, the eq. 1 indicator, and the
        eq. 4 exceedance probability P(Y > |forecast|)."""
        y, p = self.predict(windows, lengths)
        out = {"forecast": y, "p_extreme": p,
               "indicator": np.asarray(
                   indicator_sequence(y, self.eps[0], self.eps[1]))}
        if self.tail is not None:
            t = self.tail
            out["exceedance"] = np.asarray(jnp.clip(tail_probability(
                jnp.abs(y), t["xi"], t["scale"], t["tail_at_xi"],
                self.gamma), 0.0, 1.0))
        return out

    # -- incremental (session) serving ------------------------------------
    def init_carry(self, batch: int = 1):
        return init_rnn_carry(self.params, batch)

    def carry_nbytes(self, batch: int = 1) -> int:
        return sum(int(np.prod(h.shape)) * h.dtype.itemsize + int(
            np.prod(c.shape)) * c.dtype.itemsize
            for h, c in self.init_carry(batch))

    def step(self, x_t, carry):
        """One O(1) streaming step: x_t [B, F]. Returns
        (forecast [B], p_extreme [B], new_carry) — one fused dispatch
        of the lane step (padded to ``decode_width``; batches beyond
        the width chunk), bitwise-equal to ``generate`` stepping the
        same carries in lanes. The one-session functional API: no
        serving path calls it (sessions step through the lanes)."""
        x_t = jnp.asarray(x_t, jnp.float32)
        B = x_t.shape[0]
        W = self.decode_width
        if B > W:
            ys, ps, carries = [], [], []
            for lo in range(0, B, W):
                chunk = jax.tree_util.tree_map(lambda a: a[lo:lo + W],
                                               carry)
                y, p, c2 = self.step(x_t[lo:lo + W], chunk)
                ys.append(y), ps.append(p), carries.append(c2)
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.concatenate(leaves, axis=0), *carries)
            return np.concatenate(ys), np.concatenate(ps), stacked
        dispatch.record("decode_step", batch=W, hidden=self.cfg.hidden,
                        kernel_op="lstm_cell")
        with obs_trace.span("repro.decode_step"):
            y, p, carry = self._fns["decode_step"](
                self.params, x_t, carry, self._lane_mask(B),
                *self._tail_args(), gamma=float(self.gamma))
        return np.asarray(y), np.asarray(p), carry

    def _lane_mask(self, b: int) -> np.ndarray:
        """The first ``b`` of ``decode_width`` lanes: the rows a padded
        step or replay advances."""
        return np.arange(self.decode_width) < b

    def replay(self, window, carry=None):
        """Full-window recompute through the *same* per-step math the
        session path uses (this is what a cache miss executes), so cached
        incremental serving is bitwise-identical to it — as ONE jitted
        ``lax.scan`` dispatch, not a Python loop syncing the device every
        timestep (O(window) host round trips on every cache miss and
        swap re-prime). Runs at the decode-lane width, padded, like
        every step."""
        window = jnp.asarray(window, jnp.float32)
        B = window.shape[0]
        if carry is None:
            carry = self.init_carry(B)
        if window.shape[1] == 0:
            return None, None, carry
        W = self.decode_width
        if B > W:
            ys, ps, carries = [], [], []
            for lo in range(0, B, W):
                chunk = jax.tree_util.tree_map(lambda a: a[lo:lo + W],
                                               carry)
                y, p, c2 = self.replay(window[lo:lo + W], chunk)
                ys.append(y), ps.append(p), carries.append(c2)
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.concatenate(leaves, axis=0), *carries)
            return np.concatenate(ys), np.concatenate(ps), stacked
        dispatch.record("decode_replay", batch=W, hidden=self.cfg.hidden,
                        kernel_op="lstm_cell")
        with obs_trace.span("repro.decode_replay"):
            ys, ps, _, carry = self._fns["decode_replay"](
                self.params, window, carry, self._lane_mask(B),
                *self._tail_args(), gamma=float(self.gamma))
        return np.asarray(ys[-1]), np.asarray(ps[-1]), carry

    # -- device-resident decode slots (prefill / insert / generate) --------
    def init_slots(self, num_slots: int) -> DecodeSlots:
        """Allocate the device-resident slot state: ``num_slots`` lanes
        of zero carries (rounded up to a ``decode_width`` multiple) and
        an all-free active mask."""
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        W = self.decode_width
        S = -(-int(num_slots) // W) * W
        return DecodeSlots(carry=init_rnn_carry(self.params, S),
                           num_slots=S,
                           active=np.zeros((S,), bool))

    def prefill(self, window, carry=None):
        """The prefill phase of the slot lifecycle: replay a session's
        window into a batch-1 carry ready for ``insert``. Exactly
        ``replay`` (one ``decode_replay`` dispatch at the lane width),
        named for the prefill/insert/generate API — so a prefilled lane
        is bitwise-equal to the step-by-step session it replaces."""
        return self.replay(window, carry)

    def insert(self, slots: DecodeSlots, lane: int, carry,
               donate: bool | None = None) -> DecodeSlots:
        """Write a batch-1 ``carry`` into ``lane`` — a single
        ``dynamic_update_slice`` on device, no full-state host round
        trip. With donation (default off-CPU) the slot state is updated
        in place; either way ``slots.carry`` is rebound to the result,
        so callers must treat the previous value as consumed."""
        donate = _donate_default() if donate is None \
            else (donate and jax.default_backend() != "cpu")
        dispatch.record("slots_insert", batch=1, hidden=self.cfg.hidden,
                        kernel_op="lstm_cell")
        fn = self._fns["slots_insert_donate" if donate else "slots_insert"]
        with obs_trace.span("repro.slots_insert"):
            slots.carry = fn(slots.carry, carry, jnp.int32(lane))
        slots.active[lane] = True
        return slots

    def extract(self, slots: DecodeSlots, lane: int):
        """Read ``lane``'s batch-1 carry out of the slot state (spill /
        migration path) — a single ``dynamic_slice``; the lane content
        is left intact and the extracted carry is bitwise-identical to
        what ``insert`` + ``generate`` steps produced."""
        dispatch.record("slots_extract", batch=1, hidden=self.cfg.hidden,
                        kernel_op="lstm_cell")
        with obs_trace.span("repro.slots_extract"):
            return self._fns["slots_extract"](slots.carry, jnp.int32(lane))

    def release(self, slots: DecodeSlots, lane: int) -> None:
        """Mark ``lane`` free. Its stale carry stays on device and is
        overwritten by the next ``insert``."""
        slots.active[lane] = False

    def generate(self, slots: DecodeSlots, x, lanes=None,
                 donate: bool | None = None):
        """One fused dispatch stepping the slot state: x [num_slots, F]
        (rows for lanes not being stepped are ignored). ``lanes`` lists
        the lanes this call steps (default: every active lane); all
        other lanes pass their carry through unchanged. Returns
        (forecast [num_slots], p_extreme [num_slots], slots) — read only
        the rows for ``lanes``; other rows are garbage. With donation
        (default off-CPU) the slot carries are donated in and out, so a
        steady-state generate allocates nothing and copies nothing
        host-side."""
        donate = _donate_default() if donate is None \
            else (donate and jax.default_backend() != "cpu")
        x = np.asarray(x, np.float32)
        S = slots.num_slots
        if x.shape != (S, self.feature_dim):
            raise ValueError(f"generate expects x [{S}, "
                             f"{self.feature_dim}], got {x.shape}")
        mask = np.zeros((S,), bool)
        if lanes is None:
            mask[:] = slots.active
        else:
            mask[np.asarray(lanes, np.int64)] = True
        dispatch.record("slots_generate", batch=S, hidden=self.cfg.hidden,
                        kernel_op="lstm_cell")
        fn = self._fns["slots_generate_donate" if donate
                       else "slots_generate"]
        with obs_trace.span("repro.slots_generate"):
            y, p, carry = fn(self.params, x, slots.carry, mask,
                             *self._tail_args(), gamma=float(self.gamma),
                             width=self.decode_width)
        slots.carry = carry
        with obs_trace.span("repro.readback"):
            return np.asarray(y), np.asarray(p), slots

    def warm_slots(self, num_slots: int) -> int:
        """Compile the slot lifecycle programs (insert/extract/generate,
        plain and donating variants) off the serving path, against a
        throwaway slot state. Returns #programs compiled."""
        slots = self.init_slots(num_slots)
        F = self.feature_dim
        x = np.zeros((slots.num_slots, F), np.float32)
        self.insert(slots, 0, self.init_carry(1), donate=False)
        self.insert(slots, 0, self.init_carry(1), donate=True)
        self.extract(slots, 0)
        self.generate(slots, x, lanes=[0], donate=False)
        self.generate(slots, x, lanes=[0], donate=True)
        return 5

    # -- calibration -------------------------------------------------------
    def calibrate(self, windows, quantile: float = 0.95) -> "LSTMForecaster":
        """Fit the EVT tail + indicator thresholds on this model's own
        forecast distribution over a reference window set."""
        y, _ = self.predict(windows)
        self.tail = fit_tail(np.abs(y), q=quantile)
        self.eps = quantile_thresholds(y, q=quantile)
        return self

    def with_params(self, params: PyTree) -> "LSTMForecaster":
        """Unpublished successor serving ``params`` with this model's
        calibration carried over — the hot-swap constructor. Shares the
        compiled programs, so building one never traces or compiles."""
        return dataclasses.replace(self, params=params, version=0,
                                   published_at=None)


@dataclasses.dataclass
class LogitProbe:
    """An audit of the distribution a ``ZooForecaster`` serves, without
    reading back [rows, vocab] logits: the logits of vocabulary ``ids``
    [K], with the maximum and the log-sum-exp over the whole vocabulary,
    at every position a session's prefill computes and for every lane a
    generate steps. ``sink(kind, key, (logits [..., K], max [...], lse
    [...]))`` receives them as device arrays, before any readback:
    ``kind`` "prefill" with ``key`` the history's token ids (host int32
    [T]; arrays over its T positions), or "generate" with ``key`` the
    lanes stepped (arrays over every lane of the slot state)."""

    ids: Any
    sink: Any


def _zoo_answer(logits, vocab: int, xi, scale, active, gamma):
    """(greedy next token, alert, max, log-sum-exp) of logits [..., V]:
    the alert is the EVT tail probability of the surprisal of the greedy
    token, lse - max."""
    logits = logits[..., :vocab]
    mx = logits.max(-1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tok, _fused_alert(lse - mx, None, xi, scale, active, gamma), \
        mx, lse


def _lane_map(fn, *trees):
    """``fn(axis, *leaves)`` over a layer-pattern cache, on each entry's
    lane axis."""
    from repro.models.transformer import cache_lane_axis

    return {name: fn(cache_lane_axis(name), *(t[name] for t in trees))
            for name in trees[0]}


def _build_zoo_stream_fns(cfg, max_len: int, probed: bool, traced: dict):
    """The session programs of a layer-pattern hybrid, lanes of
    ``max_len`` positions: prefill of a history, one decode step of a
    batch of sessions, and the decode lanes' insert, extract and fused
    generate. ``probed``: the prefill and generate also return the
    ``LogitProbe`` outputs. Tracing the generate sets
    ``traced["ssm_inplace"]``: whether it updates the SSM state in
    place."""
    from repro.models import transformer as tfm

    V = cfg.vocab

    def probe_positions(params, h, ids):
        # [T, d] final hidden states -> probe outputs at every position,
        # 256 positions of logits [., V] at a time
        T, d = h.shape
        blk = min(T, 256)
        hp = jnp.pad(h, ((0, -T % blk), (0, 0))).reshape(-1, blk, d)

        def one(hb):
            lg = tfm.pattern_logits(cfg, params, hb)[:, :V]
            return (jnp.take(lg, ids, axis=1), lg.max(-1),
                    jax.nn.logsumexp(lg, axis=-1))

        lg, mx, lse = jax.lax.map(one, hp)
        return lg.reshape(-1, ids.shape[0])[:T], mx.reshape(-1)[:T], \
            lse.reshape(-1)[:T]

    def prefill(params, tokens, ids, xi, scale, active, gamma):
        h, cache = tfm.pattern_prefill(cfg, params, tokens, max_len)
        tok, p, _mx, _lse = _zoo_answer(
            tfm.pattern_logits(cfg, params, h[:, -1]), V, xi, scale, active,
            gamma)
        probe = probe_positions(params, h[0], ids) if probed else None
        return tok, p, probe, cache

    def decode(params, tokens, cache, xi, scale, active, gamma):
        logits, cache = tfm.lm_decode_step(cfg, params, tokens, cache)
        tok, p, _mx, _lse = _zoo_answer(logits, V, xi, scale, active, gamma)
        return tok, p, cache

    def slots_generate(params, tokens, slot_cache, step_mask, ids, xi, scale,
                       active, gamma):
        traced["ssm_inplace"] = tfm.ssm_decode_inplace(cfg)
        logits, slot_cache = tfm.lm_decode_step(cfg, params, tokens,
                                                slot_cache, active=step_mask)
        tok, p, mx, lse = _zoo_answer(logits, V, xi, scale, active, gamma)
        probe = (jnp.take(logits[:, :V], ids, axis=1), mx, lse) \
            if probed else None
        return tok, p, probe, slot_cache

    def slots_insert(slot_cache, carry, lane):
        return _lane_map(
            lambda ax, s, row: jax.lax.dynamic_update_slice_in_dim(
                s, row.astype(s.dtype), lane, ax), slot_cache, carry)

    def slots_extract(slot_cache, lane):
        return _lane_map(
            lambda ax, s: jax.lax.dynamic_slice_in_dim(s, lane, 1, ax),
            slot_cache)

    return {
        "prefill": jax.jit(prefill, static_argnames=("gamma",)),
        "decode": jax.jit(decode, static_argnames=("gamma",)),
        "slots_generate": jax.jit(slots_generate,
                                  static_argnames=("gamma",)),
        # the lanes' state (weights aside, most of the chip's memory) is
        # donated in and out: a wave updates it in place
        "slots_generate_donate": jax.jit(slots_generate,
                                         static_argnames=("gamma",),
                                         donate_argnums=(2,)),
        "slots_insert": jax.jit(slots_insert),
        "slots_insert_donate": jax.jit(slots_insert, donate_argnums=(0,)),
        "slots_extract": jax.jit(slots_extract),
    }


@dataclasses.dataclass
class ZooForecaster:
    """Any model-zoo arch behind the serving interface: forecast is the
    greedy next token; extreme probability is EVT-calibrated surprisal
    (of that token: log-sum-exp minus the largest logit).

    A hybrid by layer pattern (``cfg.layer_types``, granite-4.0-h) also
    serves streaming sessions through the decode lanes, as the LSTM
    does: its carry is the session's decode cache (SSM and conv state
    per Mamba2 layer, keys and values of ``window`` positions per
    attention layer, and the session's length), a step's input is one
    token id, a lane miss primes from the history by prefill, one
    ``generate`` steps every lane, and ``extract`` spills a lane's cache
    to host memory. Other archs keep one length for a whole batch, so
    they serve ``predict`` only (``streaming`` is False)."""

    cfg: Any                     # repro.configs.base.ArchConfig
    params: PyTree
    tail: dict | None = None
    gamma: float = 5.0
    version: int = 0
    published_at: float | None = None
    # the serving context: predict's default length bucket and, for
    # streaming, the positions a lane holds (a session's longest length)
    window: int = 32
    probe: LogitProbe | None = None
    kind: str = dataclasses.field(default="zoo", init=False)

    def __post_init__(self):
        from repro.models.model_zoo import build_model
        self._model = build_model(self.cfg)

        def _fwd(params, tokens, lengths):
            frames = None
            if self.cfg.family == "audio":
                # the audio frontend is stubbed repo-wide (spec): serve
                # with deterministic synthetic frame embeddings, as the
                # pre-subsystem serve launcher did
                frames = jax.random.normal(
                    jax.random.PRNGKey(0),
                    (tokens.shape[0], self.cfg.n_frames, self.cfg.d_model))
            logits, _ = self._model.forward(params, tokens, frames)
            idx = (lengths - 1)[:, None, None]
            last = jnp.take_along_axis(logits, jnp.broadcast_to(
                idx, (logits.shape[0], 1, logits.shape[2])), axis=1)[:, 0]
            last = last[:, :self.cfg.vocab]
            logp = jax.nn.log_softmax(last, axis=-1)
            tok = jnp.argmax(last, axis=-1)
            surprisal = -jnp.take_along_axis(logp, tok[:, None], 1)[:, 0]
            return tok.astype(jnp.int32), surprisal

        self._fwd = jax.jit(_fwd)
        self._traced: dict = {}
        if self.streaming:
            self._fns = _build_zoo_stream_fns(self.cfg, self.window,
                                              self.probe is not None,
                                              self._traced)
            self._ids = jnp.asarray(
                self.probe.ids if self.probe is not None else (), jnp.int32)

    @property
    def feature_dim(self) -> int:
        """0: the input is token ids, one int32 id a step, [T] a window."""
        return 0

    @property
    def streaming(self) -> bool:
        """Whether sessions stream through the decode lanes: the decode
        cache keeps a length per row (the layer-pattern hybrid's)."""
        return bool(self.cfg.layer_types)

    @property
    def decode_width(self) -> int:
        return 1                 # one generate steps every lane

    @property
    def ssm_inplace(self) -> bool:
        """Whether the compiled generate updates each Mamba2 layer's SSM
        state in place in the stacked lanes (the Pallas kernel, on a
        TPU); False before it is compiled and off a TPU."""
        return self._traced.get("ssm_inplace", False)

    @property
    def vocab(self) -> int:
        return self.cfg.vocab

    def predict(self, windows, lengths=None):
        """windows int32 [B, T] token ids (right-padded). Returns
        (next_token [B] as float32, p_extreme [B])."""
        tokens = jnp.asarray(windows, jnp.int32)
        if lengths is None:
            lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
        tok, surprisal = self._fwd(self.params, tokens,
                                   jnp.asarray(lengths, jnp.int32))
        p = _alert_probability(surprisal, self.tail, self.gamma)
        return np.asarray(tok, np.float32), np.asarray(p)

    def calibrate(self, windows, quantile: float = 0.95) -> "ZooForecaster":
        tokens = jnp.asarray(windows, jnp.int32)
        lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
        _, surprisal = self._fwd(self.params, tokens, lengths)
        self.tail = fit_tail(np.asarray(surprisal), q=quantile)
        return self

    def with_params(self, params: PyTree) -> "ZooForecaster":
        """Unpublished successor serving ``params`` with this model's
        calibration carried over — the hot-swap constructor. A shallow
        copy (NOT dataclasses.replace: ``__post_init__`` would rebuild
        and re-jit the forward) so the compiled ``_fwd`` is shared;
        params are traced arguments, so serving the clone never
        retraces."""
        import copy

        clone = copy.copy(self)
        clone.params = params
        clone.version = 0
        clone.published_at = None
        return clone

    # -- streaming sessions (layer-pattern hybrids) ------------------------
    def _tail_args(self):
        if self.tail is None:
            return 0.0, 1.0, False
        return float(self.tail["xi"]), float(self.tail["scale"]), True

    def _require_streaming(self):
        if not self.streaming:
            raise TypeError(f"{self.cfg.name} keeps one cache length per "
                            f"batch: it serves predict only")

    def _cache_shapes(self, batch: int):
        from repro.models import transformer as tfm

        return jax.eval_shape(lambda: tfm.init_cache(self.cfg, batch,
                                                     self.window))

    def init_carry(self, batch: int = 1):
        """Empty sessions: the decode cache of ``batch`` rows, length 0."""
        self._require_streaming()
        from repro.models import transformer as tfm

        carry = tfm.init_cache(self.cfg, batch, self.window)
        carry["len"] = np.zeros((batch,), np.int32)
        return carry

    def lane_state_bytes(self) -> dict:
        """One session's carry in bytes, by kind of state: ``ssm`` (the
        SSM states), ``conv`` (the conv inputs), ``kv`` (keys and values
        of every attention layer) and ``len``."""
        shapes = self._cache_shapes(1)
        nbytes = {k: int(np.prod(a.shape)) * a.dtype.itemsize
                  for k, a in shapes.items()}
        return {"ssm": nbytes["ssm"], "conv": nbytes["conv"],
                "kv": nbytes["k"] + nbytes["v"], "len": nbytes["len"]}

    def carry_nbytes(self, batch: int = 1) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in self._cache_shapes(batch).values())

    def _tokens(self, x, shape) -> np.ndarray:
        x = np.asarray(x, np.int32).reshape(shape)
        if x.size and (x.min() < 0 or x.max() >= self.cfg.vocab):
            raise ValueError(f"token ids must lie in [0, {self.cfg.vocab})")
        return x

    def prefill(self, window, carry=None):
        """A session's history, int32 [1, T] token ids, run through the
        model from empty state: (next token [1] as float32, p_extreme
        [1], the session's carry of T positions). With a ``probe``, its
        sink receives every position's probe outputs."""
        self._require_streaming()
        if carry is not None:
            raise ValueError("a history primes a session from empty state")
        tokens = np.asarray(window, np.int32)
        if tokens.ndim != 2 or tokens.shape[0] != 1:
            raise ValueError(f"prefill takes one history [1, T], got "
                             f"{tokens.shape}")
        tokens = self._tokens(tokens, tokens.shape)
        T = tokens.shape[1]
        dispatch.record("prefill", batch=1, hidden=self.cfg.d_model)
        with obs_trace.span("repro.zoo.prefill"):
            tok, p, probe, carry = self._fns["prefill"](
                self.params, tokens, self._ids, *self._tail_args(),
                gamma=float(self.gamma))
        carry["len"] = np.full((1,), T, np.int32)
        if self.probe is not None:
            self.probe.sink("prefill", tokens[0], probe)
        with obs_trace.span("repro.readback"):
            return np.asarray(tok, np.float32), np.asarray(p), carry

    replay = prefill

    def step(self, x_t, carry):
        """One token for each row of a batched carry: x_t int32 [B].
        Returns (next token [B] as float32, p_extreme [B], carry). The
        one-session functional API: no serving path calls it (sessions
        step through the lanes)."""
        self._require_streaming()
        tokens = self._tokens(x_t, (-1,))
        tok, p, carry = self._fns["decode"](self.params, tokens, carry,
                                            *self._tail_args(),
                                            gamma=float(self.gamma))
        return np.asarray(tok, np.float32), np.asarray(p), carry

    # -- decode lanes ------------------------------------------------------
    def init_slots(self, num_slots: int) -> DecodeSlots:
        """``num_slots`` lanes of empty sessions, on the device."""
        self._require_streaming()
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        from repro.models import transformer as tfm

        return DecodeSlots(carry=tfm.init_cache(self.cfg, num_slots,
                                                self.window),
                           num_slots=num_slots,
                           active=np.zeros((num_slots,), bool),
                           lengths=np.zeros((num_slots,), np.int64))

    def insert(self, slots: DecodeSlots, lane: int, carry,
               donate: bool | None = None) -> DecodeSlots:
        """Write a batch-1 carry (on the device or spilled to host) into
        ``lane``; with donation the lane state is updated in place."""
        donate = _donate_default() if donate is None \
            else (donate and jax.default_backend() != "cpu")
        dispatch.record("slots_insert", batch=1, hidden=self.cfg.d_model)
        fn = self._fns["slots_insert_donate" if donate else "slots_insert"]
        with obs_trace.span("repro.slots_insert"):
            slots.carry = fn(slots.carry, carry, jnp.int32(lane))
        slots.active[lane] = True
        slots.lengths[lane] = int(np.asarray(carry["len"]).reshape(-1)[0])
        return slots

    def extract(self, slots: DecodeSlots, lane: int):
        """``lane``'s carry, copied to host memory (a spilled session's
        cache is the size of the lane, too large to stay on the device);
        the lane is left intact."""
        dispatch.record("slots_extract", batch=1, hidden=self.cfg.d_model)
        with obs_trace.span("repro.slots_extract"):
            return jax.device_get(self._fns["slots_extract"](
                slots.carry, jnp.int32(lane)))

    def release(self, slots: DecodeSlots, lane: int) -> None:
        slots.active[lane] = False

    def generate(self, slots: DecodeSlots, x, lanes=None,
                 donate: bool | None = None):
        """One token for each lane in ``lanes`` (default: every active
        lane), in one program over the whole lane state: x int32
        [num_slots] (rows of other lanes are ignored; those lanes keep
        their state). Returns (next token [num_slots] as float32,
        p_extreme [num_slots], slots); read only the rows of ``lanes``.
        A lane whose session fills its ``window`` positions is refused."""
        donate = _donate_default() if donate is None \
            else (donate and jax.default_backend() != "cpu")
        S = slots.num_slots
        x = self._tokens(x, (-1,))
        if x.shape != (S,):
            raise ValueError(f"generate expects x [{S}], got {x.shape}")
        mask = np.zeros((S,), bool)
        if lanes is None:
            mask[:] = slots.active
        else:
            mask[np.asarray(lanes, np.int64)] = True
        stepped = np.flatnonzero(mask)
        full = stepped[slots.lengths[stepped] >= self.window]
        if len(full):
            raise ValueError(f"lanes {full.tolist()} hold sessions of "
                             f"{self.window} positions, the most a lane "
                             f"holds")
        dispatch.record("slots_generate", batch=S, hidden=self.cfg.d_model)
        fn = self._fns["slots_generate_donate" if donate
                       else "slots_generate"]
        with obs_trace.span("repro.slots_generate"):
            tok, p, probe, carry = fn(self.params, x, slots.carry, mask,
                                      self._ids, *self._tail_args(),
                                      gamma=float(self.gamma))
        slots.carry = carry
        slots.lengths[stepped] += 1
        if self.probe is not None:
            self.probe.sink("generate", stepped.tolist(), probe)
        with obs_trace.span("repro.readback"):
            return np.asarray(tok, np.float32), np.asarray(p), slots

    def warm_slots(self, num_slots: int) -> int:
        """Compile the lane programs (insert and generate, plain and
        donating, and extract) for ``num_slots`` lanes from their shapes
        alone: no second lane state is allocated. Returns #programs."""
        S = num_slots
        shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
        params = jax.tree.map(shape, self.params)
        lanes, one = self._cache_shapes(S), self._cache_shapes(1)
        lane = jax.ShapeDtypeStruct((), jnp.int32)
        tail = self._tail_args()
        for name in ("slots_insert", "slots_insert_donate"):
            self._fns[name].lower(lanes, one, lane).compile()
        self._fns["slots_extract"].lower(lanes, lane).compile()
        for name in ("slots_generate", "slots_generate_donate"):
            self._fns[name].lower(
                params, jax.ShapeDtypeStruct((S,), jnp.int32), lanes,
                jax.ShapeDtypeStruct((S,), jnp.bool_), self._ids, *tail,
                gamma=float(self.gamma)).compile()
        return 5


def build_lstm_forecaster(seed: int = 0, cfg: RNNConfig | None = None,
                          params: PyTree | None = None,
                          calibrate_ticker: str | None = "AAPL",
                          n_days: int = 400) -> LSTMForecaster:
    """Paper-config LSTM forecaster; freshly initialized unless ``params``
    is given, EVT-calibrated on a synthetic reference series."""
    if cfg is None:
        from repro.configs.paper_lstm import CONFIG
        cfg = CONFIG
    if params is None:
        params = init_rnn(jax.random.PRNGKey(seed), cfg)
    fc = LSTMForecaster(cfg=cfg, params=params)
    if calibrate_ticker is not None:
        from repro.data import load_stock, make_windows
        ohlcv = load_stock(calibrate_ticker, n_days=n_days)
        ds = make_windows(ohlcv, window=cfg.window)
        fc.calibrate(ds.x)
    return fc


def build_zoo_forecaster(arch: str, seed: int = 0, reduced: bool = True,
                         calibrate_batch: int = 8) -> ZooForecaster:
    """A zoo arch behind the serving interface, freshly initialised from
    ``seed``: its ``reduced`` smoke shapes or (``reduced=False``) its
    published widths, calibrated on ``calibrate_batch`` synthetic
    windows of its ``window`` (0: not calibrated)."""
    from repro.configs import get_config
    from repro.configs.base import reduced as reduce_cfg
    from repro.data.tokens import synthetic_token_batch
    from repro.models.model_zoo import build_model

    cfg = get_config(arch)
    if reduced:
        cfg = reduce_cfg(cfg)
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    fc = ZooForecaster(cfg=cfg, params=params)
    if calibrate_batch:
        toks = synthetic_token_batch(calibrate_batch, fc.window, cfg.vocab,
                                     seed=seed)
        fc.calibrate(toks)
    return fc
