"""AdamW for LoRA adapter trees (the backbone is frozen — no state for it).

Plain functional implementation over pytrees; moments in f32 regardless of
param dtype (master-weight discipline from DESIGN.md §5).

Elastic extension (DESIGN.md §6): ``step`` may be a per-job vector of
shape (K,) instead of a scalar.  Bias correction (and a per-job lr, if
the schedule produces one) then broadcasts over the job axis.  Two leaf
layouts are supported:

  * stacked ``(..., K, d, r_pad)`` / ``(..., K, r_pad, d)`` — the job
    axis is -3 and the (K,) step broadcasts as (K, 1, 1);
  * packed ragged ``(..., d, R)`` / ``(..., R, d)`` with per-adapter
    rank segments (core/lora.RankLayout) — pass ``col_jobs`` (the
    layout's packed-column -> job map) and the per-job step is gathered
    per COLUMN, broadcasting along the rank axis of each leaf ("A"
    leaves carry it last, "B" leaves second-to-last).

This is what makes migration lossless: a job that joins a group at Adam
step k keeps the bias-correction (and schedule position) it would have
had training solo — and with the ragged layout its moments occupy
exactly its own padded segment, so fuse/unfuse moves them by copy.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array   # scalar int32, or (K,) int32 per-job (elastic mode)
    mu: Any
    nu: Any


def init(params, per_job: Optional[int] = None) -> AdamWState:
    """per_job=K builds a (K,) step vector for elastic per-job accounting;
    pair it with ``update(col_jobs=...)`` for packed ragged leaves, or
    rely on the job axis at -3 for stacked leaves."""
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    step = (jnp.zeros((), jnp.int32) if per_job is None
            else jnp.zeros((per_job,), jnp.int32))
    return AdamWState(step,
                      jax.tree.map(zeros, params),
                      jax.tree.map(zeros, params))


def _broadcast_job(x: jax.Array) -> jax.Array:
    """(K,) -> (K, 1, 1): aligns with the job axis (-3) of stacked leaves."""
    return x.reshape(x.shape + (1, 1))


def _is_a_leaf(path) -> bool:
    """True for "A"-keyed leaves (rank axis last); "B" leaves carry the
    rank axis at -2 (the shared core/lora.rank_axis_is_last rule)."""
    from repro.core.lora import rank_axis_is_last
    key = path[-1]
    name = getattr(key, "key", getattr(key, "name", None))
    if name is None:
        name = str(key)
    return rank_axis_is_last(str(name))


def _col_broadcast(vec: jax.Array, col_jobs, a_leaf: bool) -> jax.Array:
    """Per-job (K,) -> per-packed-column, aligned with the leaf's rank
    axis: (R,) for A-type leaves (last axis), (R, 1) for B-type."""
    cols = vec[jnp.asarray(col_jobs)]
    return cols if a_leaf else cols[:, None]


@jax.named_scope("optimizer")
def update(grads, state: AdamWState, params, *, lr, b1: float = 0.9,
           b2: float = 0.999, eps: float = 1e-8,
           weight_decay: float = 0.0,
           col_jobs: Optional[np.ndarray] = None
           ) -> Tuple[Any, AdamWState]:
    step = state.step + 1
    tf = jnp.float32
    s = step.astype(tf)
    lr_t = jnp.asarray(lr, tf)
    per_job = s.ndim >= 1
    ragged = per_job and col_jobs is not None
    if per_job and not ragged:                # stacked elastic mode
        s = _broadcast_job(s)
        if lr_t.ndim >= 1:
            lr_t = _broadcast_job(lr_t)

    def upd(g, m, v, p, s_leaf, lr_leaf):
        g = g.astype(tf)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** s_leaf)
        vhat = v / (1 - b2 ** s_leaf)
        delta = mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p.astype(tf)
        return (p.astype(tf) - lr_leaf * delta).astype(p.dtype), m, v

    if ragged:
        def upd_path(path, g, m, v, p):
            a = _is_a_leaf(path)
            s_leaf = _col_broadcast(s, col_jobs, a)
            lr_leaf = (_col_broadcast(lr_t, col_jobs, a)
                       if lr_t.ndim >= 1 else lr_t)
            return upd(g, m, v, p, s_leaf, lr_leaf)

        flat = jax.tree_util.tree_map_with_path(
            upd_path, grads, state.mu, state.nu, params)
    else:
        flat = jax.tree.map(lambda g, m, v, p: upd(g, m, v, p, s, lr_t),
                            grads, state.mu, state.nu, params)
    is_t = lambda t: isinstance(t, tuple)
    new_p = jax.tree.map(lambda t: t[0], flat, is_leaf=is_t)
    new_m = jax.tree.map(lambda t: t[1], flat, is_leaf=is_t)
    new_v = jax.tree.map(lambda t: t[2], flat, is_leaf=is_t)
    return new_p, AdamWState(step, new_m, new_v)
