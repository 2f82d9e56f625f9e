"""Production mesh construction (multi-pod dry-run target) and the
submesh partitioner of the cluster controller (DESIGN.md §9).

FUNCTIONS, not module-level constants — importing this module must not
touch jax device state (the dry-run sets XLA_FLAGS before first init).

Every mesh here has ``Auto`` axes: the step builders run full-manual or
partial-manual ``jax.shard_map`` over them and leave the remaining axes
to GSPMD placement (sharding/rules.py), which is what ``Auto`` means.
(``jax.make_mesh`` alone now defaults to ``Explicit`` axes.)
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

import jax
from jax.sharding import AxisType


def _mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def device_shares(weights: Sequence[float], n_devices: int) -> List[int]:
    """Device counts for per-group submeshes, honoring the scheduler's
    chip assignments (*weights*).

    Weighted max-min fill: every group gets at least one device, no
    group gets more than its assignment (cap = ceil(weight) — the
    scheduler already decided how many chips the group deserves; extra
    pool devices stay FREE for arrivals rather than over-sharding
    running groups), and while devices and headroom remain the next
    device goes to the group with the highest weight-per-allocated-
    device ratio.  Returns all-zeros when the pool cannot give every
    group a device (the controller falls back to time-multiplexed
    meshless execution).  Pure arithmetic — no jax.
    """
    k = len(weights)
    if k == 0:
        return []
    if n_devices < k:
        return [0] * k
    w = [max(float(x), 1e-9) for x in weights]
    caps = [max(1, int(math.ceil(x))) for x in w]
    shares = [1] * k
    left = min(n_devices, sum(caps)) - k
    while left > 0:
        best, best_r = -1, -1.0
        for i in range(k):
            if shares[i] >= caps[i]:
                continue
            r = w[i] / shares[i]
            if r > best_r:
                best, best_r = i, r
        if best < 0:
            break
        shares[best] += 1
        left -= 1
    assert sum(shares) <= n_devices
    assert all(1 <= s <= c for s, c in zip(shares, caps))
    return shares


def legal_stage_counts(n_devices: int) -> List[int]:
    """Stage counts that evenly tile an *n_devices* slice: its divisors."""
    return [p for p in range(1, n_devices + 1) if n_devices % p == 0]


def _check_stages(stages: int, n_devices: int, what: str) -> int:
    """Validate a pipeline depth against a device slice.

    Unlike the model-axis CLAMP in ``make_local_mesh`` (where a weaker
    degree is still the same program), silently lowering a pipeline
    depth would change which schedule the caller benchmarked/priced —
    so the partitioner REJECTS non-divisors, naming the legal choices.
    """
    stages = int(stages)
    if stages < 1:
        raise ValueError(f"stages must be >= 1, got {stages}")
    if n_devices % stages:
        raise ValueError(
            f"stages={stages} does not divide the {what} of {n_devices} "
            f"device(s); legal stage counts: {legal_stage_counts(n_devices)}")
    return stages


def partition_mesh(sizes: Sequence[int], devices: Optional[Sequence] = None,
                   axis: str = "data", stages: int = 1) -> List:
    """Partition the device pool into disjoint 1-D per-group submeshes.

    ``sizes[i]`` devices (consecutive in pool order, so groups that keep
    their size keep their devices across repartitions) become one
    ``(sizes[i],)`` mesh over *axis*.  The controller runs one
    ``ElasticEngine`` per returned submesh; disjointness is what lets
    groups execute concurrently (DESIGN.md §9).

    ``stages`` > 1 asserts that every slice can later be carved into
    that many pipeline stages (``stage_mesh``): a ValueError naming the
    legal divisors fires HERE, at partition time, rather than deep in
    runtime construction.  The returned submeshes stay 1-D — the
    runtime owns the (stage, data) reshape.
    """
    devices = list(devices if devices is not None else jax.devices())
    assert all(s >= 1 for s in sizes), sizes
    assert sum(sizes) <= len(devices), (sizes, len(devices))
    for s in sizes:
        _check_stages(stages, int(s), "group slice")
    out, cur = [], 0
    for s in sizes:
        out.append(_mesh((int(s),), (axis,), devices=devices[cur:cur + s]))
        cur += s
    return out


def stage_mesh(mesh, stages: int, axis: str = "data",
               stage_axis: str = "stage"):
    """Carve a group's 1-D submesh into a (*stage_axis*, *axis*) 2-D mesh.

    The P stage sub-slices are CONSECUTIVE runs of the submesh's device
    order (devices.reshape(P, n // P)), so each stage's activation
    handoff peer (stage i -> i+1) is its neighbouring slice — the same
    locality the controller's consecutive-pool partitioner preserves.
    Rejects depths that don't divide the slice, naming legal divisors.
    """
    devs = list(mesh.devices.flat)
    n = len(devs)
    stages = _check_stages(stages, n, "group submesh")
    return _mesh((stages, n // stages), (stage_axis, axis), devices=devs)


def make_local_mesh(model: int = 1, stages: int = 1):
    """Tiny mesh over whatever devices exist (tests).

    The requested model-parallel degree is clamped to the largest
    DIVISOR of the device count that is <= *model*: ``min(model, n)``
    alone still crashes whenever the clamp does not divide n (e.g. 3
    devices with model=2 -> a 1x2 mesh over 3 devices), and a
    non-divisor would make ``n // model`` drop devices — or hit the
    degenerate ``n // model == 0``.  Clamping to a divisor always
    yields a (data, model) mesh over exactly all n devices.

    ``stages`` is clamped the same way against the data slice
    (n // model); stages > 1 yields a (stage, data, model) mesh.
    """
    n = len(jax.devices())
    model = max(1, min(model, n))
    while n % model:
        model -= 1
    d = n // model
    stages = max(1, min(int(stages), d))
    while d % stages:
        stages -= 1
    if stages == 1:
        return _mesh((d, model), ("data", "model"))
    return _mesh((stages, d // stages, model), ("stage", "data", "model"))
