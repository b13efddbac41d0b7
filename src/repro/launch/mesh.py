"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the "pod" axis
carries pure data parallelism (one gradient all-reduce per step crosses the
inter-pod links; no per-layer collective ever does).

Defined as functions (never module-level constants) so importing this module
touches no jax device state — smoke tests must keep seeing 1 CPU device.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1) -> jax.sharding.Mesh:
    """Mesh over whatever devices exist (smoke tests / examples: 1 CPU)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def dp_axes(mesh: jax.sharding.Mesh):
    """The data-parallel axes of a mesh (('pod','data') when multi-pod)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
