"""Asynchronous local SGD — the paper's technique as a first-class
distributed-training feature (DESIGN.md §2).

Production mapping (SPMD, multi-pod): a *worker* ("compute node" in the
paper) is one pod (or any data-parallel group). Params carry a leading
worker dim [W, ...]; within a worker, gradients sync over the ``data``
mesh axis every step (standard data parallel), while **across workers no
collective runs during a round** — workers drift apart for H local steps,
then *models* (not gradients) are averaged, exactly the paper's exchange
scheme. With the linearly-increasing sample schedule (s_i = a·i^p + b)
the number of cross-worker communications for K total iterations drops
from O(K) to O(sqrt(K)) (Remark 1).

Staleness (Definition 1): with ``tau >= 1`` the round-r average is applied
at round r+tau ("delayed parameter averaging") — the worker keeps its
local delta accumulated since round r:

    w_w  <-  avg(w^{(r)}) + (w_w - w_w^{(r)})        at end of round r+tau

so the consumed model contains every global update up to round r = current
- tau, satisfying Definition 1 with tau(t) = tau. Inside a ``lax.scan``
over rounds the all-reduce result is consumed tau iterations later, which
lets XLA overlap the collective with local compute — the TPU-native form
of the paper's "asynchrony by design".

Exchange modes (paper §VI.(iii) + footnote **):
    "model"    — local updates, average models at round end (the paper's).
    "gradient" — average gradients every step (classic sync SGD); H is
                 forced to 1. Implemented for the paper's model-vs-gradient
                 comparison.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.schedules import SampleSchedule, StepSizeSchedule
from repro.obs import trace as obs_trace
from repro.optim.optimizers import Optimizer, apply_updates

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LocalSGDConfig:
    n_workers: int = 2
    tau: int = 0                 # staleness (rounds); 0 = synchronous averaging
    exchange: str = "model"      # "model" | "gradient"
    schedule: SampleSchedule = SampleSchedule()   # s_i (global iterations)
    stepsize: StepSizeSchedule = StepSizeSchedule()

    def __post_init__(self):
        if self.exchange not in ("model", "gradient"):
            raise ValueError(f"unknown exchange mode {self.exchange!r}")
        if self.exchange == "gradient" and self.tau != 0:
            raise ValueError(
                "gradient exchange is synchronous SGD: every step is a "
                "collective, so delayed averaging (tau > 0) does not apply")


# --------------------------------------------------------------------------
# Building blocks
# --------------------------------------------------------------------------

def worker_mean(tree: PyTree) -> PyTree:
    """Average over the leading worker dim — the model exchange. Under
    pjit with the worker dim sharded on the 'pod' axis this lowers to one
    cross-pod all-reduce of the model."""
    return jax.tree.map(lambda a: jnp.mean(a, axis=0), tree)


def broadcast_to_workers(avg: PyTree, like: PyTree) -> PyTree:
    return jax.tree.map(
        lambda m, a: jnp.broadcast_to(m[None], a.shape).astype(a.dtype),
        avg, like)


def local_sgd_round(loss_fn: Callable, optimizer: Optimizer,
                    stacked_params: PyTree, stacked_opt: PyTree,
                    batches: PyTree, lr) -> tuple[PyTree, PyTree, jax.Array]:
    """One round: every worker runs H local steps, then models average.

    Args:
        loss_fn: (params, batch) -> scalar loss.
        stacked_params / stacked_opt: leading worker dim [W, ...].
        batches: pytree with leaves [W, H, ...] — worker-major microbatches.
        lr: scalar step size (bar-eta_i, constant within the round).

    Returns (new_stacked_params, new_stacked_opt, losses [W, H]).
    (The caller applies the averaging policy — sync or stale.)
    """
    def worker(params, opt_state, wbatches):
        def one_step(carry, batch):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            updates, opt_state = optimizer.update(grads, opt_state, params, lr)
            params = apply_updates(params, updates)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            one_step, (params, opt_state), wbatches)
        return params, opt_state, losses

    return jax.vmap(worker)(stacked_params, stacked_opt, batches)


def sync_step(loss_fn: Callable, optimizer: Optimizer,
              stacked_params: PyTree, stacked_opt: PyTree,
              batches: PyTree, lr, exchange: str = "gradient"):
    """Baseline synchronous step across workers.

    exchange="gradient": average worker gradients, then update the (shared)
    model — classic distributed SGD. exchange="model": update locally then
    average models (equivalent for plain SGD; differs under clipping /
    Adam, which is the paper's footnote-** comparison at H=1).
    """
    if exchange == "gradient":
        def worker_grad(params, batch):
            return jax.value_and_grad(loss_fn)(params, batch)
        losses, grads = jax.vmap(worker_grad)(stacked_params, batches)
        gavg = worker_mean(grads)
        params0 = jax.tree.map(lambda a: a[0], stacked_params)
        opt0 = jax.tree.map(lambda a: a[0], stacked_opt)
        updates, opt0 = optimizer.update(gavg, opt0, params0, lr)
        params0 = apply_updates(params0, updates)
        W = losses.shape[0]
        stacked_params = jax.tree.map(
            lambda m: jnp.broadcast_to(m[None], (W,) + m.shape), params0)
        stacked_opt = jax.tree.map(
            lambda m: jnp.broadcast_to(m[None], (W,) + m.shape), opt0)
        return stacked_params, stacked_opt, losses

    # model exchange at H=1
    batches1 = jax.tree.map(lambda b: b[:, None], batches)
    p, o, losses = local_sgd_round(loss_fn, optimizer, stacked_params,
                                   stacked_opt, batches1, lr)
    avg = worker_mean(p)
    return broadcast_to_workers(avg, p), o, losses[:, 0]


def round_program(loss_fn: Callable, optimizer: Optimizer, tau: int,
                  stacked_params: PyTree, stacked_opt: PyTree,
                  batches: PyTree, lr):
    """The whole model-exchange round, for one jit: the local steps, the
    workers' mean model and the round's mean loss. At ``tau`` 0 the mean
    is broadcast back, so it returns (params, opt, mean loss); above, it
    returns (params, opt, mean loss, this round's mean) for the stale
    apply ``tau`` rounds later. ``worker_mean`` and ``broadcast_to_workers``
    are read as module globals when the program is traced."""
    p, o, losses = local_sgd_round(loss_fn, optimizer, stacked_params,
                                   stacked_opt, batches, lr)
    avg = worker_mean(p)
    if tau == 0:
        return broadcast_to_workers(avg, p), o, jnp.mean(losses)
    return p, o, jnp.mean(losses), avg


@jax.jit
def _stale_apply(avg: PyTree, p: PyTree, snapshot: PyTree) -> PyTree:
    """w <- avg^{(r)} + (w - w^{(r)}): a round's average applied ``tau``
    rounds late, each worker keeping its delta since the snapshot."""
    return jax.tree.map(lambda a, w, s: (a[None] + (w - s)).astype(w.dtype),
                        avg, p, snapshot)


# --------------------------------------------------------------------------
# High-level trainer
# --------------------------------------------------------------------------

class AsyncLocalSGD:
    """Host-side round loop implementing the full technique: linearly
    increasing rounds, diminishing step size, model exchange, optional
    delayed (stale) averaging, and communication accounting."""

    def __init__(self, loss_fn: Callable, optimizer: Optimizer,
                 config: LocalSGDConfig):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.cfg = config
        # built per trainer: a trainer traces the module's worker_mean
        # as it stands when its first round runs
        self._round = jax.jit(
            lambda p, o, b, lr: round_program(
                loss_fn, optimizer, config.tau, p, o, b, lr))

        def sync(p, o, b, lr):
            p, o, losses = sync_step(loss_fn, optimizer, p, o, b, lr,
                                     exchange="gradient")
            return p, o, jnp.mean(losses)

        self._sync = jax.jit(sync)
        # (avg, snapshot, round index the average was computed at)
        self._avg_queue: list[tuple[PyTree, PyTree, int]] = []
        # accounting
        self.rounds_done = 0
        self.iterations_done = 0
        self.communications = 0
        # rounds whose model exchange ran inside the round program
        self.in_program_exchanges = 0
        self.loss_history: list[float] = []
        # Definition 1 audit trail: (round applied at, round averaged at),
        # i.e. each entry asserts "round r consumed the round r - tau avg"
        self.consumed_rounds: list[tuple[int, int]] = []

    def init(self, params: PyTree) -> tuple[PyTree, PyTree]:
        W = self.cfg.n_workers
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (W,) + a.shape), params)
        opt = jax.vmap(self.optimizer.init)(stacked)
        return stacked, opt

    def local_steps_for_round(self, i: int) -> int:
        if self.cfg.exchange == "gradient":
            return 1             # paper footnote **: gradient exchange
            # communicates every iteration, so a "round" is one step
        s_i = self.cfg.schedule.round_size(i)
        return max(1, s_i // self.cfg.n_workers)

    def lr_for_round(self) -> float:
        return float(self.cfg.stepsize(self.iterations_done))

    def run_round(self, stacked_params: PyTree, stacked_opt: PyTree,
                  batches: PyTree) -> tuple[PyTree, PyTree, float]:
        """batches leaves: [W, H, ...] with H = local_steps_for_round(r+1).

        A round is one program call and one scalar read back: the local
        steps, the model exchange at ``tau`` 0 and the mean loss all run
        in the round program. Only the stale apply (``tau`` >= 1) is a
        second call. The phases are host spans on the profiler's clock
        (``repro.obs.trace.SPANS``). The call copies numpy batches to
        the device itself: an explicit ``jax.device_put`` in front of it
        cost about 4% of the paper job's samples per second on a v5e."""
        with obs_trace.span("repro.round"):
            lr = self.lr_for_round()
            H = int(jax.tree_util.tree_leaves(batches)[0].shape[1])
            gradient = self.cfg.exchange == "gradient"
            if gradient:
                if H != 1:
                    raise ValueError(
                        f"exchange='gradient' forces H == 1 (communicate "
                        f"every iteration); got a round of H = {H} local "
                        f"steps")
                batches = jax.tree.map(lambda b: b[:, 0], batches)
            with obs_trace.span("repro.round.local"):
                p, o, loss, *avg = (self._sync if gradient else self._round)(
                    stacked_params, stacked_opt, batches, lr)
            self.iterations_done += H * self.cfg.n_workers
            self.rounds_done += 1
            self.communications += 1
            if avg:
                with obs_trace.span("repro.round.exchange"):
                    p = self._stale_exchange(p, avg[0])
            elif not gradient:
                self.in_program_exchanges += 1
            with obs_trace.span("repro.round.loss"):
                mean_loss = float(loss)
            self.loss_history.append(mean_loss)
            return p, o, mean_loss

    def _stale_exchange(self, p: PyTree, avg_now: PyTree) -> PyTree:
        """Queue this round's average and apply the one from ``tau``
        rounds ago."""
        self._avg_queue.append((avg_now, p, self.rounds_done))
        if len(self._avg_queue) > self.cfg.tau:
            avg_old, snap_old, round_old = self._avg_queue.pop(0)
            p = _stale_apply(avg_old, p, snap_old)
            self.consumed_rounds.append((self.rounds_done, round_old))
        return p

    def model_bytes(self, params: PyTree) -> int:
        one = jax.tree.map(lambda a: a[0], params)
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(one))

    def communication_bytes(self, params: PyTree) -> int:
        """Total bytes exchanged so far (model up + model down per worker
        per round — the paper's communication-cost metric)."""
        return self.communications * 2 * self.cfg.n_workers * \
            self.model_bytes(params)
