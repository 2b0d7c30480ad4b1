"""Activation sharding constraints (logical-axis layer).

Model code calls ``constrain(x, "batch", None, "model")`` with *logical*
axes; the launch layer installs a context mapping logical -> mesh axes
before tracing. Without a context (CPU smoke tests, single-device
examples) it is a no-op, so model code is mesh-agnostic.

This is required because sharding propagation alone picks degenerate
layouts here: the embedding table is (vocab='model', d_model='data')
sharded, and the gather output's d_model sharding beats the batch
sharding of the token operand — everything downstream ends up
batch-replicated. Constraining the block inputs/outputs pins the
batch axis (observed: 57 GiB -> ~2 GiB temp per chip on mamba2 train).
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

_CTX = threading.local()


def set_context(mesh, batch_axes) -> None:
    # ``with_sharding_constraint`` accepts only Auto mesh axes, and
    # ``jax.make_mesh`` builds Explicit ones: the constraints here are
    # hints to sharding propagation, so view the mesh with Auto axes
    _CTX.mesh = mesh.update(
        axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    _CTX.batch = batch_axes


def clear_context() -> None:
    _CTX.mesh = None
    _CTX.batch = None


@contextlib.contextmanager
def sharding_context(mesh, batch_axes):
    set_context(mesh, batch_axes)
    try:
        yield
    finally:
        clear_context()


def _resolve(axis, mesh_axes):
    if axis == "batch":
        return getattr(_CTX, "batch", None)
    if axis is None:
        return None
    # plain mesh axis name; drop if the mesh lacks it
    return axis if axis in mesh_axes else None


def constrain(x, *axes):
    """x with a with_sharding_constraint if a context is installed."""
    mesh = getattr(_CTX, "mesh", None)
    if mesh is None:
        return x
    if x.ndim != len(axes):
        raise ValueError(f"rank mismatch: {x.shape} vs axes {axes}")
    names = set(mesh.axis_names)
    spec = P(*[_resolve(a, names) for a in axes])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
