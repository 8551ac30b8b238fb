"""Logical-axis sharding rules (GSPMD) for the model/train/serve stack.

Every parameter and activation carries *logical* axis names; a rule table
maps them to mesh axes.  The mapping is divisibility-aware: if a dim is not
divisible by the mesh axis it would shard over, it stays replicated instead
of failing (e.g. whisper-tiny's 6 heads on a 16-way model axis) — real
frameworks need this to run heterogeneous model zoos on a fixed mesh.

Mesh axes (launch/mesh.py):
  single-pod:  ("data", "model")            = (16, 16)
  multi-pod:   ("pod", "data", "model")     = (2, 16, 16)  — pod is extra DP.

Default logical rules (overridable per call — §Perf iterates on these):
  batch    → ("pod", "data")     activations/input batch
  heads    → "model"             attention q heads (TP)
  kv_heads → "model"             KV heads (TP; replicated when indivisible)
  d_ff     → "model"             MLP hidden (TP)
  experts  → "model"             MoE experts (EP)
  vocab    → "model"             embedding/logits vocab dim
  kv_seq   → "model"             decode KV-cache sequence (SP / flash-decode)
  d_model  → None                replicated (Megatron-style row/col split
                                 covers the contracting dims already)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingRules",
    "DEFAULT_RULES",
    "logical_to_spec",
    "named_sharding",
    "constrain",
    "mesh_axis_size",
    "abstract_mesh",
    "spin_mesh",
    "mesh_fingerprint",
]

Axes = Tuple[Optional[str], ...]  # logical names per dim (None = replicated)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name → mesh axis (str) or tuple of mesh axes."""

    rules: Tuple[Tuple[str, Any], ...] = (
        ("batch", ("pod", "data")),
        ("heads", "model"),
        ("kv_heads", "model"),
        ("d_ff", "model"),
        ("experts", "model"),
        ("vocab", "model"),
        ("kv_seq", "model"),
        ("ssm_state", None),
        ("d_model", None),
        ("seq", None),
        ("d_head", None),
        ("layers", None),
    )

    def lookup(self, logical: Optional[str]):
        if logical is None:
            return None
        for k, v in self.rules:
            if k == logical:
                return v
        return None

    def replace(self, **kw) -> "ShardingRules":
        d = dict(self.rules)
        d.update(kw)
        return ShardingRules(rules=tuple(d.items()))


DEFAULT_RULES = ShardingRules()

# §Perf rule presets -------------------------------------------------------
# Weight-stationary serving (FSDP-style): no gradients exist, so the `data`
# axis is free — shard weights' d_model over it (params 16× smaller/device,
# 16× less HBM param traffic per token) and spread long KV over every free
# axis.  Used by the jamba long_500k hillclimb.
SERVE_WEIGHT_STATIONARY_RULES = DEFAULT_RULES.replace(
    d_model=("data",),
    kv_seq=("model", "data"),
)

# Megatron-SP + FSDP training: residual-stream activations sharded over
# `model` on the sequence dim (norms/elementwise 16× cheaper, activation
# stash 16× smaller); weights' d_model additionally sharded over `data`
# (FSDP).  Attention/MLP internals locally prefer head/d_ff sharding, so
# GSPMD places the SP all-gather/reduce-scatter at the layer boundaries.
TRAIN_FSDP_SP_RULES = DEFAULT_RULES.replace(
    d_model=("data",),
    seq=("model",),
)


def abstract_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str]):
    """A device-free `jax.sharding.AbstractMesh` (spec-construction tests)."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def spin_mesh(n_devices: Optional[int] = None, *, axis: str = "model") -> Mesh:
    """1-D mesh over the first ``n_devices`` host devices, for spin sharding.

    The annealer's model-parallel path (DESIGN.md §11) partitions the spin
    axis of a single instance over one mesh axis; this builds that mesh from
    however many devices exist — 1 real device and an 8-way
    ``--xla_force_host_platform_device_count`` CPU both work, no hard-coded
    counts.  ``n_devices=None`` takes every available device.
    """
    import numpy as np

    devs = jax.devices()
    k = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= k <= len(devs):
        raise ValueError(
            f"spin_mesh: need 1 <= n_devices <= {len(devs)}, got {k}"
        )
    return Mesh(np.asarray(devs[:k]), (axis,))


def mesh_fingerprint(mesh: Optional[Mesh]) -> tuple:
    """Hashable mesh identity (axis names/sizes + device ids).

    Executable caches and checkpoint fingerprints key on this: the same
    program lowered for a different device set or axis layout is a different
    executable, and a checkpoint written under one mesh shape must not be
    silently resumed under another.
    """
    if mesh is None:
        return ()
    return (
        tuple(zip(mesh.axis_names, mesh.devices.shape)),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def mesh_axis_size(mesh: Mesh, axis) -> int:
    """Total size of a mesh axis or tuple of axes, 1 if absent from mesh."""
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        size = 1
        for a in axis:
            size *= mesh_axis_size(mesh, a)
        return size
    return int(mesh.shape[axis]) if axis in mesh.shape else 1


def _present(mesh: Mesh, axis):
    """Filter an axis spec down to the axes actually present in the mesh."""
    if axis is None:
        return None
    if isinstance(axis, (tuple, list)):
        kept = tuple(a for a in axis if a in mesh.shape)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]
    return axis if axis in mesh.shape else None


def logical_to_spec(
    mesh: Mesh,
    shape: Sequence[int],
    axes: Axes,
    rules: ShardingRules = DEFAULT_RULES,
) -> P:
    """Build a PartitionSpec, dropping any assignment that doesn't divide.

    A mesh axis is used at most once across all dims (GSPMD requirement);
    first-come-first-served in dim order.
    """
    if len(axes) != len(shape):
        raise ValueError(f"axes {axes} rank != shape {shape}")
    used = set()
    out = []
    for dim, logical in zip(shape, axes):
        axis = _present(mesh, rules.lookup(logical))
        if axis is None:
            out.append(None)
            continue
        parts = list(axis) if isinstance(axis, tuple) else [axis]
        # keep only axes not already used by an earlier dim, then trim from
        # the right until the product divides the dim (graceful fallback:
        # e.g. kv_seq→("model","data") with data taken by batch still
        # shards over model).
        parts = [a for a in parts if a not in used]
        while parts and (
            mesh_axis_size(mesh, tuple(parts)) <= 1
            or dim % mesh_axis_size(mesh, tuple(parts)) != 0
        ):
            parts.pop()
        if not parts:
            out.append(None)
            continue
        used.update(parts)
        out.append(tuple(parts) if len(parts) > 1 else parts[0])
    # trim trailing Nones for tidy specs
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(
    mesh: Mesh, shape: Sequence[int], axes: Axes, rules: ShardingRules = DEFAULT_RULES
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(mesh, shape, axes, rules))


def constrain(x, mesh: Optional[Mesh], axes: Axes, rules: ShardingRules = DEFAULT_RULES):
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    if mesh is None or mesh.empty:
        return x
    spec = logical_to_spec(mesh, x.shape, axes, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
