"""Production meshes and logical-axis rules.

Single pod: (16, 16) over ("data", "model") — 256 chips (TPU v5e pod).
Multi-pod:  (2, 16, 16) over ("pod", "data", "model") — 512 chips; the
"pod" axis is the best-effort boundary (DESIGN.md §2).

Defined as functions, not module constants, so importing never touches jax
device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.models.partitioning import MeshRules


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis typed ``Auto``: sharding stays a
    compiler decision steered by ``with_sharding_constraint``, which only
    names Auto axes (``make_mesh`` otherwise defaults to Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for multi-device CPU tests."""
    return _auto_mesh(shape, axes)


#: Mesh axis the sharded simulation engine partitions the population over.
SHARD_AXIS = "shard"


def make_shard_mesh(n_shards: int):
    """1-D mesh over ``SHARD_AXIS`` for the sharded vectorized engine.

    On CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives
    the host enough devices to exercise this path without accelerators
    (DESIGN.md §8).
    """
    n_dev = len(jax.devices())
    if n_shards > n_dev:
        raise ValueError(
            f"requested {n_shards} shards but only {n_dev} JAX device(s) "
            "are visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} before "
            "importing jax")
    return _auto_mesh((n_shards,), (SHARD_AXIS,))


def rules_for(mesh, *, long_context: bool = False,
              pod_stacked: bool = False, profile: str = "2d") -> MeshRules:
    """Logical-role mapping for a mesh.

    long_context: batch=1 decode — every axis goes to the KV-cache sequence
    dim ("sp"), nothing to batch ("dp").
    pod_stacked: train state carries an explicit leading pod dim, so the
    FSDP role must exclude "pod" (it shards the stack dim instead).
    profile: "2d" (FSDP x TP) or "dp_only" (pure DP, params replicated).
    """
    names = mesh.axis_names
    if profile == "dp_only":
        dp = tuple(n for n in names if n != "pod" or not pod_stacked)
        if pod_stacked:
            dp = tuple(n for n in names if n != "pod")
        if long_context:
            return MeshRules(mesh, dp=(), tp=None, sp=tuple(names))
        return MeshRules(mesh, dp=dp, tp=None, sp=None)
    dp = tuple(n for n in names if n in ("pod", "data"))
    if pod_stacked:
        dp = tuple(n for n in dp if n != "pod")
    tp = "model" if "model" in names else None
    if long_context:
        return MeshRules(mesh, dp=(), tp=tp, sp=tuple(names))
    return MeshRules(mesh, dp=dp, tp=tp, sp=tp)


def pod_count(mesh) -> int:
    return mesh.shape.get("pod", 1)
