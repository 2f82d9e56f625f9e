"""Divisibility-aware sharding annotations.

The model code calls ``shard(x, "batch", None, "tp")`` with *logical* axis
names; this module maps them onto whatever mesh is active and silently
drops axes that do not divide the corresponding dimension (e.g. smollm's
15 attention heads over a 16-way model axis).

Logical axes:
  "batch"  -> ("pod", "data") on multi-pod meshes, ("data",) single-pod
  "seq"    -> ("data",) (sequence parallelism, used when batch < data)
  "tp"     -> ("model",)
  "expert" -> ("model",)

With no active mesh (plain CPU tests) every call is a no-op, so the same
model code runs everywhere.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

LogicalAxis = Union[None, str, Tuple[str, ...]]


def _current() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def _seq_over_batch() -> bool:
    return getattr(_state, "seq_over_batch", False)


def _manual_axes() -> Tuple[str, ...]:
    return getattr(_state, "manual", ())


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], seq_over_batch: bool = False,
             manual: Tuple[str, ...] = ()):
    """Activate *mesh* for ``shard()`` calls made while tracing.

    seq_over_batch: route the "seq" logical axis onto the data axis
    (sequence parallelism) — used for long-context batch=1 shapes.

    manual: mesh axes that are MANUAL inside a surrounding shard_map
    (sharded runtime, DESIGN.md §8) — constraints must not reference
    them (each per-shard program already sees local arrays), so
    logical-axis resolution silently drops them and the remaining
    (GSPMD-auto) axes keep guiding the planner.
    """
    prev = getattr(_state, "mesh", None)
    prev_sp = getattr(_state, "seq_over_batch", False)
    prev_manual = getattr(_state, "manual", ())
    _state.mesh = mesh
    _state.seq_over_batch = seq_over_batch
    _state.manual = tuple(manual)
    try:
        yield
    finally:
        _state.mesh = prev
        _state.seq_over_batch = prev_sp
        _state.manual = prev_manual


def partitioned() -> bool:
    """Whether code traced now runs under GSPMD over more than one
    device: an active mesh with a non-manual axis wider than 1.  A
    Pallas kernel there would need a partitioning rule; inside a
    full-manual ``shard_map`` (or with no mesh) it sees local arrays."""
    mesh = _current()
    return mesh is not None and any(
        int(mesh.shape[a]) > 1 for a in mesh.axis_names
        if a not in _manual_axes())


def logical_to_mesh(mesh: Mesh, name: LogicalAxis) -> Tuple[str, ...]:
    if name is None:
        return ()
    if isinstance(name, tuple):
        out: Tuple[str, ...] = ()
        for n in name:
            out = out + logical_to_mesh(mesh, n)
        return out
    axes = tuple(a for a in mesh.axis_names if a not in _manual_axes())
    if name == "batch":
        return tuple(a for a in ("pod", "data") if a in axes)
    if name == "seq":
        return ("data",) if ("data" in axes and _seq_over_batch()) else ()
    if name == "sp":
        # Megatron sequence parallelism: the residual stream shards its
        # seq dim over the model axis between TP regions (+ the data axis
        # for long-context batch=1 shapes).
        out: Tuple[str, ...] = ()
        if "data" in axes and _seq_over_batch():
            out += ("data",)
        if "model" in axes:
            out += ("model",)
        return out
    if name == "tokens":
        # flattened (B*S) token dim: batch axes + model (moe dispatch)
        return tuple(a for a in ("pod", "data", "model") if a in axes)
    if name in ("tp", "expert"):
        return ("model",) if "model" in axes else ()
    if name in axes:          # raw mesh axis passthrough
        return (name,)
    return ()


def spec_for(mesh: Mesh, shape: Sequence[int], axes: Sequence[LogicalAxis]) -> P:
    """Build a PartitionSpec, dropping axes that don't divide the dim."""
    entries = []
    for dim, name in zip(shape, axes):
        mesh_axes = logical_to_mesh(mesh, name)
        size = math.prod(mesh.shape[a] for a in mesh_axes) if mesh_axes else 1
        if mesh_axes and dim % size == 0 and dim > 0:
            entries.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
        else:
            entries.append(None)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def shard(x: jax.Array, *axes: LogicalAxis) -> jax.Array:
    """with_sharding_constraint under the active mesh; no-op otherwise."""
    mesh = _current()
    if mesh is None:
        return x
    if len(axes) < x.ndim:
        axes = tuple(axes) + (None,) * (x.ndim - len(axes))
    spec = spec_for(mesh, x.shape, axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, shape: Sequence[int], *axes: LogicalAxis) -> NamedSharding:
    if len(axes) < len(shape):
        axes = tuple(axes) + (None,) * (len(shape) - len(axes))
    return NamedSharding(mesh, spec_for(mesh, shape, axes))
