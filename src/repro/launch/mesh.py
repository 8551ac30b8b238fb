"""Mesh builders for launchers and the serving/annealing stack.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

None of the builders hard-code a device count: :func:`make_mesh` builds
any requested shape from however many devices actually exist (1 real chip,
a ``--xla_force_host_platform_device_count`` CPU fleet, a pod) and fails
with the actual-vs-requested counts when they don't match.  The historical
pod presets (:func:`make_production_mesh` / :func:`make_shrunken_mesh`)
are thin wrappers over it.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax

__all__ = [
    "parse_mesh_shape",
    "make_mesh",
    "make_spin_mesh",
    "make_production_mesh",
    "make_shrunken_mesh",
]


def parse_mesh_shape(spec: str) -> Tuple[int, ...]:
    """'8' → (8,); '2x16x16' → (2, 16, 16).  'x' or ',' separated."""
    parts = [p for p in spec.replace(",", "x").split("x") if p]
    if not parts:
        raise ValueError(f"empty mesh shape {spec!r}")
    try:
        shape = tuple(int(p) for p in parts)
    except ValueError:
        raise ValueError(f"bad mesh shape {spec!r}; want e.g. '8' or '2x16'")
    if any(d < 1 for d in shape):
        raise ValueError(f"mesh shape {spec!r} has non-positive dims")
    return shape


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A mesh of the requested shape over the devices that actually exist.

    Unlike a hard-coded ``jax.make_mesh((16, 16), ...)`` call, the error on
    a mismatch names both counts — the usual failure is launching a pod
    preset on a workstation (or forgetting XLA_FLAGS in a CPU run).
    """
    shape = tuple(int(d) for d in shape)
    if len(shape) != len(tuple(axes)):
        raise ValueError(f"mesh shape {shape} rank != axes {tuple(axes)}")
    need = 1
    for d in shape:
        need *= d
    have = len(jax.devices())
    if need > have:
        raise ValueError(
            f"mesh shape {shape} needs {need} devices but only {have} exist; "
            "shrink --mesh-shape or force more host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N"
        )
    # Auto axes: the programs place data with `with_sharding_constraint` and
    # NamedSharding, which refuse the Explicit axes jax.make_mesh defaults to.
    return jax.make_mesh(
        shape, tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
    )


def make_spin_mesh(spec: Optional[str] = None, *, axis: str = "model"):
    """1-D spin-sharding mesh from a ``--mesh-shape`` flag value.

    ``None``/'' takes every available device (the partition='spin' default);
    a spec must be 1-D — the annealer's spin axis shards over exactly one
    mesh axis (DESIGN.md §11).
    """
    from repro.sharding import spin_mesh

    if not spec:
        return spin_mesh(axis=axis)
    shape = parse_mesh_shape(spec)
    if len(shape) != 1:
        raise ValueError(
            f"--partition spin|auto wants a 1-D mesh, got shape {shape}"
        )
    return spin_mesh(shape[0], axis=axis)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_shrunken_mesh():
    """Elastic-degraded mesh (half a pod lost): 8×16 = 128 chips."""
    return make_mesh((8, 16), ("data", "model"))
