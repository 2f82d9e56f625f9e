"""Pallas TPU kernels for fused heterogeneous multi-LoRA (paper §3.3).

TPU adaptation of the paper's Triton kernel (see DESIGN.md §3):

* The SSM lays each group's tokens out contiguously per adapter and pads
  every job's token count to a multiple of ``block_t``, so each token tile
  belongs to exactly one adapter.  The tile→adapter map is a small int32
  vector delivered via **scalar prefetch** (``PrefetchScalarGridSpec``) —
  BlockSpec index_maps use it to DMA the right A_i/B_i slab into VMEM.
* Per grid step the compact ``(block_t, r_pad)`` intermediate lives only in
  a VMEM scratch buffer: ``ΔW = A_i B_iᵀ`` and full-size temporaries are
  never materialized (the paper's core kernel contract).
* ``r_pad`` is lane-aligned; a rank mask zeroes lanes ≥ r_i so heterogeneous
  ranks share one launch (rank-aware tiles).
* Grid = (token_tiles, dout_tiles) with dout fastest; the x·A product is
  computed once per token tile (at i_o == 0) and reused from scratch for
  all dout tiles — the VMEM analogue of Triton's shared-memory reuse.

Every launch goes through ``pallas_call`` below: interpreted where the
program is compiled for CPU (the test suite), compiled Mosaic on TPU.
Validated in interpret mode on CPU against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pallas_call(kernel, **kw):
    """``pl.pallas_call`` whose mode follows the platform the program is
    compiled for: the Pallas interpreter where it lowers for CPU,
    compiled Mosaic everywhere else.  ``lax.platform_dependent`` makes
    the choice at lowering, so one traced step is interpreted in the CPU
    test suite and compiled when its arrays live on a TPU (an AOT
    compile for a described TPU from a CPU host included).  This is the
    only place interpret mode is decided."""
    interpreted = pl.pallas_call(kernel, interpret=True, **kw)
    compiled = pl.pallas_call(kernel, **kw)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          default=compiled)
    return call


def _fit_block(n: int, cap: int) -> int:
    """Largest divisor of *n* that is <= cap (grid tiles must divide the
    dim exactly; min(cap, n) alone crashes for non-power-of-two dims,
    e.g. d_out=640 with the default 512)."""
    b = max(1, min(cap, n))
    while n % b:
        b -= 1
    return b


# ----------------------------------------------------------------- fwd
def _fused_lora_kernel(tile_map_ref, ranks_ref, x_ref, a_ref, b_ref,
                       o_ref, xa_scratch):
    i_t = pl.program_id(0)
    i_o = pl.program_id(1)

    @pl.when(i_o == 0)
    def _compute_xa():
        x = x_ref[...]
        a = a_ref[0]                                    # (d_in, r_pad)
        xa = jnp.dot(x, a, preferred_element_type=jnp.float32)
        rank = ranks_ref[tile_map_ref[i_t]]
        lane = jax.lax.broadcasted_iota(jnp.int32, xa.shape, 1)
        xa_scratch[...] = jnp.where(lane < rank, xa, 0.0)

    xa = xa_scratch[...].astype(x_ref.dtype)
    b = b_ref[0]                                        # (r_pad, block_o)
    o_ref[...] = jnp.dot(xa, b,
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def fused_lora_pallas(x: jax.Array, A: jax.Array, B: jax.Array,
                      tile_map: jax.Array, ranks: jax.Array,
                      *, block_t: int = 128, block_o: int = 512) -> jax.Array:
    """x: (T, d_in), A: (K, d_in, r_pad), B: (K, r_pad, d_out),
    tile_map: (T//block_t,) adapter id per token tile.

    Returns (T, d_out) *unscaled* LoRA output (scaling applied by caller).
    """
    T, d_in = x.shape
    K, _, r_pad = A.shape
    d_out = B.shape[-1]
    assert T % block_t == 0, (T, block_t)
    block_o = _fit_block(d_out, block_o)
    grid = (T // block_t, d_out // block_o)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # tile_map, ranks
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d_in), lambda i, j, tm, rk: (i, 0)),
            pl.BlockSpec((1, d_in, r_pad), lambda i, j, tm, rk: (tm[i], 0, 0)),
            pl.BlockSpec((1, r_pad, block_o), lambda i, j, tm, rk: (tm[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_o), lambda i, j, tm, rk: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_t, r_pad), jnp.float32)],
    )
    return pallas_call(
        _fused_lora_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d_out), x.dtype),
    )(tile_map, ranks, x, A, B)


# ------------------------------------------------------------ grouped wgrad
def _grouped_wgrad_kernel(tile_map_ref, x_ref, g_ref, o_ref):
    """dW[k] += x_tileᵀ · g_tile for the adapter k owning this token tile.

    Output blocks are *revisited*: the SSM layout sorts tokens by adapter,
    so all token tiles of one adapter are consecutive in the innermost
    grid dimension and the (1, d_in, block_o) accumulator stays resident
    in VMEM for the whole segment.  The accumulator is zeroed on the first
    tile of each segment (tile_map transition) and flushed to HBM by the
    pipeline when the output index changes."""
    i_t = pl.program_id(1)
    prev = tile_map_ref[jnp.maximum(i_t - 1, 0)]

    @pl.when((i_t == 0) | (prev != tile_map_ref[i_t]))
    def _zero_acc():
        o_ref[...] = jnp.zeros_like(o_ref)

    # (block_t, d_in)ᵀ · (block_t, block_o) -> (d_in, block_o), f32 accum
    acc = jax.lax.dot_general(x_ref[...], g_ref[...],
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] += acc[None]


def grouped_wgrad_pallas(x: jax.Array, g: jax.Array, tile_map: jax.Array,
                         num_adapters: int, *, block_t: int = 128,
                         block_o: int = 512) -> jax.Array:
    """Segment-aware wgrad: out[k] = Σ_{t: adapter(t)=k} x_tᵀ g_t.

    x: (T, d_in), g: (T, d_out), tile_map: (T//block_t,) *sorted* adapter
    id per token tile (SSM layout contract).  Returns (K, d_in, d_out) in
    f32 (master-weight gradient dtype).  Serves both LoRA wgrads:
    dA = grouped_wgrad(x, dxa) and dB = grouped_wgrad(xa, dy).

    Grid is (dout_tiles, token_tiles) — token tiles innermost so every
    output block's visits are consecutive (the revisiting-output
    accumulation contract; a (tiles, dout) order would interleave blocks
    and lose the VMEM-resident accumulator).
    """
    T, d_in = x.shape
    d_out = g.shape[-1]
    K = num_adapters
    assert T % block_t == 0, (T, block_t)
    block_o = _fit_block(d_out, block_o)
    grid = (d_out // block_o, T // block_t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d_in), lambda j, i, tm: (i, 0)),
            pl.BlockSpec((block_t, block_o), lambda j, i, tm: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, d_in, block_o),
                               lambda j, i, tm: (tm[i], 0, j)),
    )
    out = pallas_call(
        _grouped_wgrad_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, d_in, d_out), jnp.float32),
    )(tile_map, x, g)
    # adapters with zero token tiles are never visited — their output
    # block is uninitialized memory; the true gradient is zero.
    seg = jnp.zeros((K,), jnp.int32).at[tile_map].add(1)
    return jnp.where(seg[:, None, None] > 0, out, 0.0)


# ------------------------------------------------------------ dequant mm
def _dequant_mm_kernel(x_ref, w_ref, s_ref, o_ref):
    """y = (x @ w_q) * scale with the int8 tile cast IN-REGISTER.

    Per-output-channel scales commute with the contraction
    (x @ (q * s) == (x @ q) * s[None, :]), so the tile is multiplied by
    its ``(1, block_o)`` scale slice after the dot — a bf16 copy of the
    weight is never materialized, in VMEM or HBM."""
    w = w_ref[...].astype(x_ref.dtype)              # int8 -> compute dtype
    y = jnp.dot(x_ref[...], w, preferred_element_type=jnp.float32)
    o_ref[...] = (y * s_ref[...]).astype(o_ref.dtype)


def dequant_matmul_pallas(x: jax.Array, w_q: jax.Array, scale: jax.Array,
                          *, block_t: int = 128,
                          block_o: int = 512) -> jax.Array:
    """Fused dequantize-matmul for the quantized frozen backbone.

    x: (T, d_in) activations; w_q: (d_in, d_out) int8; scale: (d_out,)
    f32 per-output-channel.  Returns (T, d_out) in x.dtype.  The grid
    tiles T and d_out only — the contraction dim stays whole per tile,
    so every output element is one full-length f32-accumulated dot and
    the result is bit-identical to the XLA reference expression
    ``(x @ w_q.astype(x.dtype)) * scale``.
    """
    T, d_in = x.shape
    d_out = w_q.shape[-1]
    assert w_q.shape[0] == d_in and scale.shape == (d_out,), \
        (x.shape, w_q.shape, scale.shape)
    block_t = _fit_block(T, block_t)
    block_o = _fit_block(d_out, block_o)
    grid = (T // block_t, d_out // block_o)
    s2 = scale.reshape(1, d_out).astype(jnp.float32)
    return pallas_call(
        _dequant_mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d_in), lambda i, j: (i, 0)),
            pl.BlockSpec((d_in, block_o), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_o), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_o), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((T, d_out), x.dtype),
    )(x, w_q, s2)


# ------------------------------------------------------------- grouped mm
def _grouped_mm_kernel(tile_map_ref, x_ref, w_ref, o_ref):
    del tile_map_ref
    o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def grouped_matmul_pallas(x: jax.Array, W: jax.Array, tile_map: jax.Array,
                          *, block_t: int = 128,
                          block_o: int = 512) -> jax.Array:
    """y_t = x_t @ W[adapter(t)] with one adapter per token tile.
    Used for the dx passes of the custom VJP."""
    T, d_in = x.shape
    K, _, d_out = W.shape
    assert T % block_t == 0, (T, block_t)
    block_o = _fit_block(d_out, block_o)
    grid = (T // block_t, d_out // block_o)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d_in), lambda i, j, tm: (i, 0)),
            pl.BlockSpec((1, d_in, block_o), lambda i, j, tm: (tm[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_o), lambda i, j, tm: (i, j)),
    )
    return pallas_call(
        _grouped_mm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d_out), x.dtype),
    )(tile_map, x, W)
