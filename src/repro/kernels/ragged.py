"""Rank-bucketed ragged Pallas kernels for fused multi-LoRA (paper §3.3).

The masked kernels in ``fused_lora.py`` pad every adapter to the group
max rank and zero dead lanes — a K=8 group with ranks {4,...,4,64}
burns ~4x the LoRA FLOPs its members need.  These kernels make rank
heterogeneity free to within tile granularity: the grid enumerates only
the ACTIVE (token tile, rank tile) pairs of the packed ragged layout
(core/lora.RankLayout — per-adapter padded segments along one packed
rank axis), so work is Σ_k tiles_k · rank_tiles_k, never tiles · r_max.

Mechanics
  * The fused batch layout is static (tile-aligned per-job row counts),
    so the tile→adapter map and each adapter's true-rank tile count are
    HOST constants.  ``RaggedMeta`` flattens them into scalar-prefetched
    index vectors: flat step f covers token tile ``tile[f]`` × packed
    rank tile ``rtile[f]`` (``first[f]`` marks a token tile's first rank
    tile, ``lanes[f]`` its active lanes for sub-tile ranks).
  * Forward / dgrad grids are (out tiles, F) with the flat axis
    innermost: an output block's visits are consecutive over the rank
    tiles of its token tile, so the f32 accumulator stays VMEM-resident
    (the same revisiting-output contract as ``grouped_wgrad_pallas``) —
    zeroed at ``first[f]``, flushed when the token tile advances.
  * Wgrads flatten in (adapter, rank tile, token tile) order instead —
    token tiles innermost — so each packed (r_blk, block_o) gradient
    block accumulates over its segment's consecutive visits.
  * The rank-tile width is ``layout.multiple`` (16 with 128-row token
    tiles), so every per-adapter padded width is whole rank tiles by
    construction.
  * Rank tiles sit on the SECOND-TO-LAST axis of every block.  Mosaic
    needs a block's last two dims to be multiples of (8, 128) or the
    whole array dim; a 16-wide rank tile meets the sublane rule (8)
    and never the lane rule (128).  So the wrappers hand the kernels
    ``A`` transposed, ``(R, d_in)`` (a small XLA transpose of the
    adapter, not of activations), and the packed intermediates
    ``xa``/``dxa`` are written rank-major, ``(R, T)``.  The packed
    ``(d, R)/(R, d)`` storage is unchanged.

Validated in interpret mode on CPU against kernels/ref.py (see
tests/test_ragged_kernels.py: bit/tol-exact vs the masked max-rank
reference for fwd + dgrad + wgrad).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lora import RankLayout
from repro.kernels.fused_lora import _fit_block, pallas_call


@dataclass(frozen=True)
class RaggedMeta:
    """Static flattened grid metadata for one (batch layout, rank layout).

    ``tile_jobs`` maps each token tile to its adapter (the fused-batch
    contract: one adapter per tile, segments contiguous).  Hashable —
    the custom-VJP builders in kernels/ops.py key their caches on it.
    """
    tile_jobs: Tuple[int, ...]
    ranks: Tuple[int, ...]
    r_pads: Tuple[int, ...]
    offsets: Tuple[int, ...]
    r_blk: int

    @classmethod
    def build(cls, tile_jobs: Sequence[int],
              layout: RankLayout) -> "RaggedMeta":
        return cls(tuple(int(t) for t in tile_jobs), layout.ranks,
                   layout.r_pads, layout.offsets, layout.multiple)

    @property
    def num_jobs(self) -> int:
        return len(self.ranks)

    @property
    def total_r(self) -> int:
        return sum(self.r_pads)

    def _rt_of(self, k: int) -> Tuple[int, int]:
        """(first global rank tile, rank-tile count) of job k."""
        return self.offsets[k] // self.r_blk, self.r_pads[k] // self.r_blk

    # --------------------------------------------------- flat enumerations
    @cached_property
    def fwd_flat(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
        """(tile, rtile, first, lanes) in (token tile, rank tile) order —
        the forward/dgrad flat axis (rank tiles consecutive per token
        tile, so the output accumulator revisits consecutively)."""
        tile, rtile, first, lanes = [], [], [], []
        for t, k in enumerate(self.tile_jobs):
            rt0, n_rt = self._rt_of(k)
            for j in range(n_rt):
                tile.append(t)
                rtile.append(rt0 + j)
                first.append(1 if j == 0 else 0)
                lanes.append(int(np.clip(self.ranks[k] - j * self.r_blk,
                                         0, self.r_blk)))
        return (np.asarray(tile, np.int32), np.asarray(rtile, np.int32),
                np.asarray(first, np.int32), np.asarray(lanes, np.int32))

    @cached_property
    def wgrad_flat(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(tile, rtile, first) in (adapter, rank tile, token tile) order —
        the wgrad flat axis (token tiles consecutive per output block)."""
        tiles_of = [[] for _ in range(self.num_jobs)]
        for t, k in enumerate(self.tile_jobs):
            tiles_of[k].append(t)
        tile, rtile, first = [], [], []
        for k in range(self.num_jobs):
            rt0, n_rt = self._rt_of(k)
            for j in range(n_rt):
                for i, t in enumerate(tiles_of[k]):
                    tile.append(t)
                    rtile.append(rt0 + j)
                    first.append(1 if i == 0 else 0)
        return (np.asarray(tile, np.int32), np.asarray(rtile, np.int32),
                np.asarray(first, np.int32))

    @cached_property
    def visited_rows(self) -> np.ndarray:
        """(total_r,) bool — packed rank rows owned by adapters with at
        least one token tile.  Wgrad blocks of tile-less adapters are
        never visited (uninitialized memory); their true gradient is
        zero."""
        seen = np.zeros(self.num_jobs, bool)
        for k in self.tile_jobs:
            seen[k] = True
        return np.repeat(seen, np.asarray(self.r_pads, np.int64))


def _prefetch(meta_arrays) -> list:
    return [jnp.asarray(a) for a in meta_arrays]


def _nt(a, b):
    """a · bᵀ (contract the last dims), f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _rank_mask(v, n_lanes, axis: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
    return jnp.where(lane < n_lanes, v, 0.0)


# ------------------------------------------------------------------ fwd
def _fwd_kernel(tile_ref, rt_ref, first_ref, lanes_ref,
                x_ref, at_ref, b_ref, o_ref):
    f = pl.program_id(1)

    @pl.when(first_ref[f] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    xa = _nt(x_ref[...], at_ref[...])                  # (block_t, r_blk)
    xa = _rank_mask(xa, lanes_ref[f], 1).astype(x_ref.dtype)
    o_ref[...] += jnp.dot(xa, b_ref[...],
                          preferred_element_type=jnp.float32)


def ragged_lora_fwd(x: jax.Array, A: jax.Array, B: jax.Array,
                    meta: RaggedMeta, *, block_t: int = 128,
                    block_o: int = 512) -> jax.Array:
    """x: (T, d_in), A: (d_in, R), B: (R, d_out) packed ragged.

    Returns (T, d_out) *unscaled* LoRA output in f32 (caller scales and
    casts).  Grid = (dout tiles, Σ_k tiles_k·rank_tiles_k): only active
    rank tiles run — the padding waste of the masked kernel never
    launches."""
    T, d_in = x.shape
    d_out = B.shape[-1]
    assert T % block_t == 0 and T // block_t == len(meta.tile_jobs), \
        (T, block_t, len(meta.tile_jobs))
    block_o = _fit_block(d_out, block_o)
    tile, rtile, first, lanes = meta.fwd_flat
    grid = (d_out // block_o, len(tile))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d_in),
                         lambda j, f, tm, rt, fi, ln: (tm[f], 0)),
            pl.BlockSpec((meta.r_blk, d_in),
                         lambda j, f, tm, rt, fi, ln: (rt[f], 0)),
            pl.BlockSpec((meta.r_blk, block_o),
                         lambda j, f, tm, rt, fi, ln: (rt[f], j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_o),
                               lambda j, f, tm, rt, fi, ln: (tm[f], j)),
    )
    return pallas_call(
        _fwd_kernel,
        name="ragged_lora_fwd",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d_out), jnp.float32),
    )(*_prefetch((tile, rtile, first, lanes)), x, A.T, B)


# ---------------------------------------------------------------- dgrad
def _dgrad_kernel(tile_ref, rt_ref, first_ref, lanes_ref,
                  dy_ref, b_ref, at_ref, o_ref):
    f = pl.program_id(1)

    @pl.when(first_ref[f] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    dxa = _nt(dy_ref[...], b_ref[...])                 # dy · B[rt]ᵀ
    dxa = _rank_mask(dxa, lanes_ref[f], 1).astype(dy_ref.dtype)
    o_ref[...] += jnp.dot(dxa, at_ref[...],            # dxa · A[:, rt]ᵀ
                          preferred_element_type=jnp.float32)


def ragged_lora_dgrad(dy_s: jax.Array, A: jax.Array, B: jax.Array,
                      meta: RaggedMeta, *, block_t: int = 128,
                      block_i: int = 512) -> jax.Array:
    """dx = ((dy_s · B^T) masked) · A^T over active rank tiles only —
    one fused launch where the masked path needs two grouped-mm
    launches plus a full-width HBM intermediate.  dy_s: (T, d_out)
    pre-scaled cotangent; returns (T, d_in) f32."""
    T, d_out = dy_s.shape
    d_in = A.shape[0]
    assert T % block_t == 0 and T // block_t == len(meta.tile_jobs), \
        (T, block_t, len(meta.tile_jobs))
    block_i = _fit_block(d_in, block_i)
    tile, rtile, first, lanes = meta.fwd_flat
    grid = (d_in // block_i, len(tile))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d_out),
                         lambda j, f, tm, rt, fi, ln: (tm[f], 0)),
            pl.BlockSpec((meta.r_blk, d_out),
                         lambda j, f, tm, rt, fi, ln: (rt[f], 0)),
            pl.BlockSpec((meta.r_blk, block_i),
                         lambda j, f, tm, rt, fi, ln: (rt[f], j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_i),
                               lambda j, f, tm, rt, fi, ln: (tm[f], j)),
    )
    return pallas_call(
        _dgrad_kernel,
        name="ragged_lora_dgrad",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d_in), jnp.float32),
    )(*_prefetch((tile, rtile, first, lanes)), dy_s, B, A.T)


# ----------------------------------------- packed rank-major mm (xaᵀ, dxaᵀ)
def _rank_major_kernel(tile_ref, rt_ref, first_ref, lanes_ref, v_ref,
                       w_ref, o_ref):
    f = pl.program_id(0)
    out = _nt(w_ref[...], v_ref[...])                  # (r_blk, block_t)
    o_ref[...] = _rank_mask(out, lanes_ref[f], 0).astype(o_ref.dtype)


def _rank_major(v: jax.Array, Wt: jax.Array, meta: RaggedMeta,
                block_t: int) -> jax.Array:
    """(R, T) with out[seg_k, t] = Wt[seg_k] · v_t for k = adapter(t),
    rank-masked; other segments' entries are never visited (and never
    read).  Wt: (R, d) rank-major weight slab, v: (T, d)."""
    T, d = v.shape
    assert T == len(meta.tile_jobs) * block_t, (T, block_t,
                                                len(meta.tile_jobs))
    tile, rtile, first, lanes = meta.fwd_flat
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(len(tile),),
        in_specs=[
            pl.BlockSpec((block_t, d),
                         lambda f, tm, rt, fi, ln: (tm[f], 0)),
            pl.BlockSpec((meta.r_blk, d),
                         lambda f, tm, rt, fi, ln: (rt[f], 0)),
        ],
        out_specs=pl.BlockSpec((meta.r_blk, block_t),
                               lambda f, tm, rt, fi, ln: (rt[f], tm[f])),
    )
    return pallas_call(
        _rank_major_kernel,
        name="ragged_lora_rank_major",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((meta.total_r, T), v.dtype),
    )(*_prefetch((tile, rtile, first, lanes)), v, Wt)


def ragged_xa(x: jax.Array, A: jax.Array, meta: RaggedMeta, *,
              block_t: int = 128) -> jax.Array:
    """Packed compact intermediate, rank-major: xaᵀ (R, T) with
    xaᵀ[seg_k, t] = (x_t · A[:, seg_k])ᵀ for k = adapter(t),
    rank-masked.  Wgrad operand (dB)."""
    return _rank_major(x, A.T, meta, block_t)


def ragged_dxa(dy_s: jax.Array, B: jax.Array, meta: RaggedMeta, *,
               block_t: int = 128) -> jax.Array:
    """Packed masked cotangent of xa, rank-major: dxaᵀ (R, T) with
    dxaᵀ[seg_k, t] = B[seg_k] · dy_s_t, rank-masked.  Wgrad operand
    (dA)."""
    return _rank_major(dy_s, B, meta, block_t)


# ---------------------------------------------------------------- wgrad
def _wgrad_kernel(tile_ref, rt_ref, first_ref, ut_ref, v_ref, o_ref):
    f = pl.program_id(1)

    @pl.when(first_ref[f] == 1)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    # (r_blk, block_t) · (block_t, block_o) -> (r_blk, block_o)
    o_ref[...] += jnp.dot(ut_ref[...], v_ref[...],
                          preferred_element_type=jnp.float32)


def ragged_wgrad(ut: jax.Array, v: jax.Array, meta: RaggedMeta, *,
                 block_t: int = 128, block_o: int = 512) -> jax.Array:
    """Segment-aware ragged wgrad: out[seg_k] = Σ_{t: adapter(t)=k}
    ut[seg_k, t] · v_t.

    ut: (R, T) packed rank-major (xaᵀ or dxaᵀ), v: (T, d) dense.
    Returns (R, d) f32 — dB directly (ut=xaᵀ, v=dy_s), or dA TRANSPOSED
    (ut=dxaᵀ, v=x; caller transposes to (d_in, R)).  Flat grid in
    (adapter, rank tile, token tile) order: each output block's
    token-tile visits are consecutive, and only true-rank tiles of
    adapters that own tokens launch."""
    R, T = ut.shape
    d = v.shape[-1]
    assert R == meta.total_r and T == len(meta.tile_jobs) * block_t, \
        (T, R, block_t, len(meta.tile_jobs))
    block_o = _fit_block(d, block_o)
    tile, rtile, first = meta.wgrad_flat
    if len(tile) == 0:       # degenerate: no tokens at all
        return jnp.zeros((R, d), jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d // block_o, len(tile)),
        in_specs=[
            pl.BlockSpec((meta.r_blk, block_t),
                         lambda j, f, tm, rt, fi: (rt[f], tm[f])),
            pl.BlockSpec((block_t, block_o),
                         lambda j, f, tm, rt, fi: (tm[f], j)),
        ],
        out_specs=pl.BlockSpec((meta.r_blk, block_o),
                               lambda j, f, tm, rt, fi: (rt[f], j)),
    )
    out = pallas_call(
        _wgrad_kernel,
        name="ragged_lora_wgrad",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, d), jnp.float32),
    )(*_prefetch((tile, rtile, first)), ut, v)
    # adapters with zero token tiles are never visited — their output
    # rows are uninitialized memory; the true gradient is zero.
    vis = meta.visited_rows
    if bool(vis.all()):
        return out
    return jnp.where(jnp.asarray(vis)[:, None], out, 0.0)
