"""Composable model-set serving: ``EnsembleSpec`` (a named model group
hosted by the registry), ``EnsembleFuser`` (online EVT-weighted fusion
of per-member ``(forecast, p_extreme)`` with an anomaly-aware alert
path), and ``EnsembleForecaster`` (the ``Forecaster`` protocol over N
registry members — fan out, fuse, and carry per-member session state
under ONE client id).

Fusion weighting (DESIGN): each member's weight is
``softmax(log(prior_m) - err_m / temperature)`` where ``prior_m`` comes
from the member's calibrated EVT tail fit (``1 / tail_scale`` — a
tighter calibrated tail is a sharper, more trusted alert head) and
``err_m`` is an exponentially-decayed rolling error. Errors are updated
online: self-supervised from each member's deviation against the
cross-member median consensus on every fused batch, or supervised via
``record_errors`` when ground truth arrives. The softmax is
max-subtracted and every input is clipped finite, so the weights are
ALWAYS convex (non-negative, sum to 1); a single-member ensemble gets
exactly weight 1.0, which — together with the ``M == 1`` fusion
shortcut that returns the member rows untouched — makes a singleton
ensemble bitwise-identical to serving that member solo on every path
(predict, step, replay, slots).

Anomaly-aware path: an EWMA of the fused ``p_extreme`` with
enter/exit hysteresis flips the fuser into *anomaly mode* when the
input stream itself turns extreme. In anomaly mode the alert threshold
is widened (scaled by ``anomaly_alert_scale`` < 1 — more sensitive)
and the engine tightens the batcher's ``max_wait`` for the ensemble
and its members (``wait_scale`` < 1 — alerts leave the queue sooner).

Every member runs through the EXISTING fused per-model machinery: an
ensemble ``predict`` is N per-model ``predict`` dispatches and an
ensemble session tick is N fused ``slots_generate`` dispatches — never
N×batch singles.
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any

import numpy as np

from repro.serving.forecaster import DecodeSlots

# rolling errors are clipped into [0, _BIG] (nan -> _BIG): exp(-_BIG)
# underflows to exactly 0.0, keeping the softmax finite for ANY history
_BIG = 1e6


@dataclasses.dataclass(frozen=True)
class EnsembleSpec:
    """A named model group plus its fusion/anomaly policy. Immutable —
    member swaps replace the whole spec atomically under the registry
    lock (monotone ensemble version), so readers never see a torn
    member list."""

    members: tuple[str, ...]
    # fusion weighting
    error_half_life: float = 64.0     # fused batches to halve an error
    temperature: float = 1.0          # err -> logit scale
    # alerting + anomaly-aware adaptation
    alert_threshold: float = 0.9
    anomaly_enter: float = 0.6        # fused-p EWMA >= enter: anomaly on
    anomaly_exit: float = 0.3         # fused-p EWMA < exit: anomaly off
    anomaly_alert_scale: float = 0.75  # threshold multiplier (<1: widen)
    anomaly_wait_scale: float = 0.25   # batcher max_wait multiplier
    anomaly_half_life: float = 16.0    # fused batches in the p EWMA

    def __post_init__(self):
        members = tuple(str(m) for m in self.members)
        if not members:
            raise ValueError("an ensemble needs at least one member")
        if len(set(members)) != len(members):
            raise ValueError(f"duplicate ensemble members: {members}")
        object.__setattr__(self, "members", members)
        if not 0.0 < self.anomaly_alert_scale <= 1.0:
            raise ValueError("anomaly_alert_scale must be in (0, 1]")
        if not 0.0 < self.anomaly_wait_scale <= 1.0:
            raise ValueError("anomaly_wait_scale must be in (0, 1]")
        if self.anomaly_exit > self.anomaly_enter:
            raise ValueError("anomaly_exit must be <= anomaly_enter "
                             "(hysteresis)")

    def to_wire(self) -> dict:
        """msgpack/JSON-able dict (the transport's ``ensemble`` op)."""
        d = dataclasses.asdict(self)
        d["members"] = list(self.members)
        return d

    @classmethod
    def from_wire(cls, d: dict) -> "EnsembleSpec":
        d = dict(d)
        d["members"] = tuple(d["members"])
        return cls(**d)


def fusion_weights(priors, errors, temperature: float = 1.0):
    """Convex fusion weights: ``softmax(log(prior) - err/temperature)``,
    max-subtracted. Non-finite or non-positive priors fall back to 1.0;
    errors are clipped into ``[0, 1e6]`` (nan counts as maximal error),
    so the result is non-negative and sums to 1 for ANY input history.
    A single member gets exactly ``[1.0]``."""
    priors = np.asarray(priors, np.float64).reshape(-1)
    errors = np.asarray(errors, np.float64).reshape(-1)
    if priors.shape != errors.shape:
        raise ValueError(f"priors {priors.shape} != errors {errors.shape}")
    n = priors.shape[0]
    if n == 0:
        raise ValueError("no members to weight")
    if n == 1:
        return np.ones((1,), np.float64)
    priors = np.where(np.isfinite(priors) & (priors > 0.0), priors, 1.0)
    errors = np.clip(np.nan_to_num(errors, nan=_BIG, posinf=_BIG,
                                   neginf=0.0), 0.0, _BIG)
    t = float(temperature)
    if not (math.isfinite(t) and t > 0.0):
        t = 1.0
    logits = np.log(priors) - errors / t
    logits -= logits.max()
    w = np.exp(logits)
    s = float(w.sum())
    if not (math.isfinite(s) and s > 0.0):
        return np.full((n,), 1.0 / n, np.float64)
    return w / s


@dataclasses.dataclass
class FusedResult:
    """One fused batch: per-row fused outputs plus the fusion/anomaly
    state they were produced under."""

    forecast: np.ndarray        # [B] float32
    p_extreme: np.ndarray       # [B] float32
    alerts: np.ndarray          # [B] bool (p_fused >= effective threshold)
    weights: np.ndarray         # [M] float64, convex
    threshold: float            # effective (anomaly-scaled) threshold
    anomaly: bool               # fuser was in anomaly mode for this batch


class EnsembleFuser:
    """Per-ensemble online fusion state: rolling per-member errors, the
    anomaly EWMA/hysteresis, and fused/alert counters. Lock-guarded —
    the predict fan-in callback and the step-flush worker fuse through
    the same instance."""

    def __init__(self, n_members: int, spec: EnsembleSpec):
        self.spec = spec
        self.n_members = int(n_members)
        self._lock = threading.Lock()
        self._err = np.zeros((self.n_members,), np.float64)
        self._alpha = 1.0 - 0.5 ** (1.0 / max(spec.error_half_life, 1e-9))
        self._p_alpha = 1.0 - 0.5 ** (1.0 / max(spec.anomaly_half_life,
                                                1e-9))
        self._p_ewma = 0.0
        self._anomaly = False
        self.fused = 0          # fused rows produced
        self.alerts = 0         # fused rows that alerted

    # -- state reads -------------------------------------------------------
    @property
    def anomaly(self) -> bool:
        return self._anomaly

    def errors(self) -> np.ndarray:
        with self._lock:
            return self._err.copy()

    def weights(self, priors=None) -> np.ndarray:
        if priors is None:
            priors = np.ones((self.n_members,), np.float64)
        with self._lock:
            return fusion_weights(priors, self._err, self.spec.temperature)

    def wait_scale(self) -> float:
        """Batcher ``max_wait`` multiplier: < 1 while anomalous (flush
        sooner — alert latency beats batch occupancy under extremes)."""
        return self.spec.anomaly_wait_scale if self._anomaly else 1.0

    def alert_threshold(self) -> float:
        """Effective alert threshold (anomaly mode widens the alert
        band by scaling the threshold down)."""
        scale = self.spec.anomaly_alert_scale if self._anomaly else 1.0
        return self.spec.alert_threshold * scale

    # -- state writes ------------------------------------------------------
    def record_errors(self, errs) -> None:
        """Supervised error update (ground truth arrived): EWMA the
        per-member absolute errors into the rolling state."""
        errs = np.asarray(errs, np.float64).reshape(-1)
        if errs.shape[0] != self.n_members:
            raise ValueError(f"expected {self.n_members} errors, got "
                             f"{errs.shape[0]}")
        errs = np.clip(np.nan_to_num(errs, nan=_BIG, posinf=_BIG,
                                     neginf=0.0), 0.0, _BIG)
        with self._lock:
            self._err = (1.0 - self._alpha) * self._err + self._alpha * errs

    def fuse(self, ys, ps, priors=None, update: bool = True,
             rows=None) -> FusedResult:
        """Fuse per-member forecasts ``ys`` / alert probabilities ``ps``
        (each a sequence of M arrays of shape [B]). With ``update``,
        also EWMA the self-supervised member errors (deviation from the
        cross-member median consensus) and advance the anomaly state —
        restricted to ``rows`` when given (the slots path fuses full
        lane vectors but only the stepped rows are real)."""
        ys = np.stack([np.asarray(y) for y in ys])          # [M, B]
        ps = np.stack([np.asarray(p) for p in ps])
        M = ys.shape[0]
        if M != self.n_members:
            raise ValueError(f"expected {self.n_members} members, got {M}")
        if priors is None:
            priors = np.ones((M,), np.float64)
        with self._lock:
            w = fusion_weights(priors, self._err, self.spec.temperature)
            if M == 1:
                # bitwise: a singleton ensemble IS its member
                y_f = np.asarray(ys[0], np.float32)
                p_f = np.asarray(ps[0], np.float32)
            else:
                y_f = (w @ ys.astype(np.float64)).astype(np.float32)
                p_f = (w @ ps.astype(np.float64)).astype(np.float32)
            scale = self.spec.anomaly_alert_scale if self._anomaly else 1.0
            thr = self.spec.alert_threshold * scale
            alerts = p_f >= thr
            was_anomaly = self._anomaly
            if update:
                yv = ys if rows is None else ys[:, rows]
                pv = p_f if rows is None else p_f[rows]
                av = alerts if rows is None else alerts[rows]
                if M > 1 and yv.shape[1]:
                    consensus = np.median(yv, axis=0)
                    dev = np.mean(np.abs(yv - consensus[None, :]), axis=1)
                    dev = np.clip(np.nan_to_num(dev, nan=_BIG, posinf=_BIG,
                                                neginf=0.0), 0.0, _BIG)
                    self._err = ((1.0 - self._alpha) * self._err
                                 + self._alpha * dev)
                if pv.size:
                    p_mean = float(np.mean(np.nan_to_num(pv, nan=0.0)))
                    self._p_ewma = ((1.0 - self._p_alpha) * self._p_ewma
                                    + self._p_alpha * p_mean)
                    if self._anomaly:
                        if self._p_ewma < self.spec.anomaly_exit:
                            self._anomaly = False
                    elif self._p_ewma >= self.spec.anomaly_enter:
                        self._anomaly = True
                    self.fused += int(pv.size)
                    self.alerts += int(av.sum())
        return FusedResult(forecast=y_f, p_extreme=p_f, alerts=alerts,
                           weights=w, threshold=thr, anomaly=was_anomaly)


@dataclasses.dataclass
class EnsembleSlots:
    """Per-member device decode-slot states sharing ONE lane numbering:
    lane ``i`` of every member belongs to the same client, so sessions
    spill/migrate as a unit (extract/insert walk all members at the
    same lane index)."""

    slots: dict[str, DecodeSlots]
    num_slots: int
    active: Any                 # np.ndarray bool [num_slots], host-side

    @property
    def n_active(self) -> int:
        return int(self.active.sum())


class EnsembleForecaster:
    """The ``Forecaster`` protocol over N registry members. Members are
    re-resolved from the registry on every call, so per-member hotswap
    and atomic spec (member-list) swaps are picked up mid-stream —
    ``version`` folds the spec version and every member version into
    one string, which is what makes the session runner re-prime carries
    after ANY swap. Session carries are ``{member_key: member_carry}``
    dicts under one client id; slotted serving uses ``EnsembleSlots``
    (one lane index across all members)."""

    kind = "ensemble"
    published_at: float | None = None

    def __init__(self, registry, name: str):
        self.registry = registry
        self.name = str(name)
        self._fuser: EnsembleFuser | None = None
        self._fuser_members: tuple[str, ...] = ()
        self._fuser_lock = threading.Lock()

    # -- member resolution -------------------------------------------------
    def spec(self) -> EnsembleSpec:
        spec = self.registry.ensemble(self.name)
        if spec is None:
            raise KeyError(f"no ensemble {self.name!r} in registry")
        return spec

    def _members(self):
        spec = self.spec()
        return spec, [(k, self.registry.get(k)) for k in spec.members]

    def fuser(self) -> EnsembleFuser:
        """The online fusion state for the CURRENT member set (rebuilt
        on atomic member swap — a new member list means a new error
        vector)."""
        spec = self.spec()
        with self._fuser_lock:
            if self._fuser is None or self._fuser_members != spec.members:
                self._fuser = EnsembleFuser(len(spec.members), spec)
                self._fuser_members = spec.members
            return self._fuser

    @staticmethod
    def _prior(member) -> float:
        """EVT prior from the member's calibrated tail fit: a tighter
        tail scale is a sharper alert head. Uncalibrated members get a
        neutral 1.0."""
        tail = getattr(member, "tail", None)
        if not tail:
            return 1.0
        return 1.0 / max(float(tail.get("scale", 1.0)), 1e-9)

    def fuse(self, ys, ps, update: bool = True, rows=None) -> FusedResult:
        spec, members = self._members()
        priors = [self._prior(m) for _, m in members]
        return self.fuser().fuse(ys, ps, priors=priors, update=update,
                                 rows=rows)

    # -- protocol surface --------------------------------------------------
    @property
    def version(self) -> str:
        """Spec version + every member version, folded into one
        hashable token — changes on ANY swap, which is what the session
        runner keys its re-prime on."""
        spec, members = self._members()
        mv = ",".join(f"{k}:{getattr(m, 'version', 0)}"
                      for k, m in members)
        return f"e{self.registry.ensemble_version(self.name)}|{mv}"

    @property
    def window(self) -> int:
        _, members = self._members()
        return members[0][1].window

    @property
    def feature_dim(self) -> int:
        _, members = self._members()
        return members[0][1].feature_dim

    @property
    def decode_width(self) -> int:
        _, members = self._members()
        return math.lcm(*(int(getattr(m, "decode_width", 1))
                          for _, m in members))

    def predict(self, windows, lengths=None):
        """Fan the batch across every member (one fused per-model
        ``predict`` dispatch each — N total) and fuse. Returns
        (forecast [B], p_extreme [B]) like any other forecaster."""
        _, members = self._members()
        ys, ps = [], []
        for _, m in members:
            y, p = m.predict(windows, lengths)
            ys.append(np.asarray(y))
            ps.append(np.asarray(p))
        fused = self.fuse(ys, ps)
        return fused.forecast, fused.p_extreme

    # -- incremental (session) serving ------------------------------------
    def init_carry(self, batch: int = 1):
        _, members = self._members()
        return {k: m.init_carry(batch) for k, m in members}

    def carry_nbytes(self, batch: int = 1) -> int:
        _, members = self._members()
        return sum(m.carry_nbytes(batch) for _, m in members)

    def _member_carry(self, carry, key: str, member, batch: int = 1):
        if isinstance(carry, dict) and key in carry:
            return carry[key]
        # spec swapped a member in since this carry was built: a fresh
        # carry here is only a stopgap — the runner's version-mismatch
        # re-prime rebuilds the whole dict from history on its next wave
        return member.init_carry(batch)

    def step(self, x_t, carry):
        _, members = self._members()
        ys, ps, new = [], [], {}
        for k, m in members:
            y, p, c2 = m.step(x_t, self._member_carry(carry, k, m,
                                                      len(x_t)))
            ys.append(y)
            ps.append(p)
            new[k] = c2
        fused = self.fuse(ys, ps)
        return fused.forecast, fused.p_extreme, new

    def replay(self, window, carry=None):
        """Full-window re-prime through every member's own replay (one
        fused dispatch each). Fusion runs with ``update=False`` — a
        replay re-derives a session, it is not live traffic, so it must
        not move the rolling error/anomaly state."""
        _, members = self._members()
        ys, ps, new = [], [], {}
        batch = np.asarray(window).shape[0]
        for k, m in members:
            mc = carry[k] if isinstance(carry, dict) and k in carry \
                else None
            y, p, c2 = m.replay(window, mc)
            ys.append(y)
            ps.append(p)
            new[k] = c2
        if ys and ys[0] is None:        # zero-length window: carry only
            return None, None, new
        del batch
        fused = self.fuse(ys, ps, update=False)
        return fused.forecast, fused.p_extreme, new

    # -- device-resident decode slots --------------------------------------
    def init_slots(self, num_slots: int) -> EnsembleSlots:
        """One lane numbering across every member: lane ``i`` in each
        member's slot state holds the same client. ``num_slots`` rounds
        up to the lcm of member decode widths so every member agrees on
        the lane count."""
        _, members = self._members()
        w = self.decode_width
        s = -(-int(num_slots) // w) * w
        return EnsembleSlots(
            slots={k: m.init_slots(s) for k, m in members},
            num_slots=s, active=np.zeros((s,), bool))

    def prefill(self, window, carry=None):
        return self.replay(window, carry)

    def insert(self, slots: EnsembleSlots, lane: int, carry,
               donate: bool | None = None) -> EnsembleSlots:
        _, members = self._members()
        for k, m in members:
            m.insert(slots.slots[k], lane,
                     self._member_carry(carry, k, m), donate=donate)
        slots.active[lane] = True
        return slots

    def extract(self, slots: EnsembleSlots, lane: int):
        _, members = self._members()
        return {k: m.extract(slots.slots[k], lane) for k, m in members}

    def release(self, slots: EnsembleSlots, lane: int) -> None:
        _, members = self._members()
        for k, m in members:
            m.release(slots.slots[k], lane)
        slots.active[lane] = False

    def generate(self, slots: EnsembleSlots, x, lanes=None,
                 donate: bool | None = None):
        """One fused ``slots_generate`` dispatch PER MEMBER (N total per
        tick), fused row-wise. Rows for lanes outside ``lanes`` are
        garbage (as in the single-model contract) and are excluded from
        the online error/anomaly update."""
        _, members = self._members()
        x = np.asarray(x, np.float32)
        rows = (np.flatnonzero(slots.active) if lanes is None
                else np.asarray(lanes, np.int64))
        ys, ps = [], []
        for k, m in members:
            ms = slots.slots[k]
            xm = x
            if ms.num_slots != x.shape[0]:
                xm = np.zeros((ms.num_slots, x.shape[1]), np.float32)
                xm[:x.shape[0]] = x
            y, p, _ = m.generate(ms, xm, lanes=rows, donate=donate)
            ys.append(np.asarray(y)[:slots.num_slots])
            ps.append(np.asarray(p)[:slots.num_slots])
        fused = self.fuse(ys, ps, rows=rows)
        return fused.forecast, fused.p_extreme, slots

    def warm_slots(self, num_slots: int) -> int:
        _, members = self._members()
        return sum(m.warm_slots(num_slots) for _, m in members
                   if hasattr(m, "warm_slots"))
