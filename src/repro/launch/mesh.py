"""Production mesh construction, and the batch-axis placement rule.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): (16, 16) = 256 chips per pod, data x model; multi-pod adds
the leading "pod" axis (2 pods = 512 chips).

:func:`pick_batch_axes` is the one rule for which data-parallel axes a
batch / slot / block dim shards over.  The serving placement
(``runtime.sharding``) and the kernel dispatch (``kernels.ops``, whose
``shard_map`` specs must match the operands' committed layout) both read it
from here, below either layer.
"""
from __future__ import annotations

import jax
import numpy as np

DP_AXES = ("pod", "data")        # outer batch axes when present


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh over the first prod(shape) devices (tests, elastic)."""
    n = int(np.prod(shape))
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(devs, axes)


def data_axes(mesh) -> tuple:
    """The data-parallel axes present in ``mesh``, outermost first."""
    return tuple(a for a in DP_AXES if a in mesh.axis_names)


def divisible(n: int, mesh, axes) -> bool:
    """Whether ``n`` splits evenly over the mesh axes ``axes`` (a name or a
    tuple of names; empty -> False)."""
    if not axes:
        return False
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= mesh.shape[a]
    return n % size == 0


def pick_batch_axes(B: int, mesh, dp):
    """Largest prefix of the data-parallel axes ``dp`` that divides the
    batch/slot dim ``B`` (falls back to any single axis, then replication).
    One shared decision for batch inputs, decode-slot tensors, the KV cache
    and the kernels' ``shard_map`` specs, so they stay co-sharded."""
    if divisible(B, mesh, dp):
        return dp
    for k in range(len(dp) - 1, 0, -1):
        if divisible(B, mesh, dp[:k]):
            return dp[:k]
    return next((a for a in dp if B % mesh.shape[a] == 0), None)
