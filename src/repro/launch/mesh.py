"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax call.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh(shape, axes):
    """A device mesh whose axes are all ``Auto``: the sharding rules
    (``distributed.sharding``) place arrays with sharding constraints,
    which only Auto axes accept (``jax.make_mesh`` defaults to
    Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — DP
    across pods, FSDP within a pod, TP/EP on model."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (1 CPU device in the dev container) laid
    out as a (data, model) mesh — lets the same pjit code paths run in
    tests."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))


def make_placement_mesh(n_hosts: int, *, model: int = 1):
    """Abstract (data, model) mesh describing an ``n_hosts``-wide data
    axis *without touching device state* — the serving runtime's
    ``PlacementMap.from_mesh`` reads shard residency off it, so a
    simulated multi-host topology (tests, ``--hosts N`` benches on one
    machine) and a real pod deployment configure placement the same
    way: swap this for ``make_production_mesh()`` and nothing else
    changes."""
    return AbstractMesh((int(n_hosts), int(model)), ("data", "model"))
