"""jit-ready wrappers around the fused multi-LoRA kernels.

Two kernel families share this module:

``fused_lora`` — the legacy MASKED max-rank family over stacked
(K, d, r_pad) adapters (every adapter padded to the group max, dead
lanes zero-masked).  Kept as the reference/baseline path and for direct
callers with stacked state:
  * "pallas" — the TPU kernel (interpret-mode on CPU), custom VJP whose
    backward is grouped end-to-end: two grouped-mm launches for dx and
    two segment-aware grouped-wgrad launches for dA/dB (no one-hot
    densification over K anywhere in the hot path).
  * "xla"    — segment-dense formulation: the distributed/GSPMD path used
    by the dry-run (the CPU backend cannot compile Mosaic kernels).
    Same math; custom VJP with segment-dense batched-einsum wgrads.
  * "ref"    — gather oracle (tests, small scale).
  * "loop"   — per-adapter GEMM pair, the *unfused* baseline (Fig. 7).

``fused_lora_ragged`` — the RANK-BUCKETED RAGGED family over packed
(d, R)/(R, d) adapters with per-adapter padded segments
(core/lora.RankLayout), the production path (DESIGN.md §10): work is
proportional to each adapter's true padded rank, never K·r_max.
  * "pallas" — kernels/ragged.py: flat (token tile × rank tile) grids
    enumerating only active rank tiles via scalar-prefetched rank
    metadata; fused fwd and dgrad launches, packed ragged wgrads.
  * "xla"    — bucket-concatenated einsums: jobs grouped by padded
    width, one segment-dense batched GEMM pair per bucket (fallback:
    per-bucket one-hot combine for non-equal-segment layouts).
  * "ref"/"loop" — densify the packed pair to the stacked max-rank view
    and run the gather oracle / unfused baseline (tests, ablation).

Contract required by "pallas"/"xla": tokens sorted by adapter id,
contiguous segments, each segment length a multiple of block_t (the SSM
batch layout guarantees this — see core/ssm.py).

Interpret mode follows the backend: every Pallas launch lowers to the
Pallas interpreter where the program is compiled for CPU (the test
suite) and to compiled Mosaic on TPU (``kernels/fused_lora.pallas_call``
decides, at lowering).  There is no switch to set.  ``kernel_defaults``
picks the kernel family and token tile the same way for callers that
name neither.

Shard-local variants (DESIGN.md §8): under ``shard_map`` over a data
axis, each device holds a tile-aligned mini fused batch (per-adapter
segment offsets = global offsets / shards).  ``fused_lora`` with
``axis_name=...`` dispatches to custom VJPs whose forward and dx passes
are purely shard-local (per-token, bit-identical to solo), and whose
wgrads all-gather the token operands over the data axis, un-permute
them into the solo job-major row order, and evaluate the SAME wgrad
expressions as the solo VJPs at full shape — making sharded adapter
gradients bit-exact w.r.t. single-device execution (the paper's
lossless contract survives the mesh).  The cheaper partial-wgrad+psum
strategy lives one level up (core/ssm.py, grad_sync="psum").
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_impl
from repro.kernels import fused_lora as pk
from repro.kernels import ragged as rg
from repro.kernels.ragged import RaggedMeta


def kernel_defaults(impl: Optional[str] = None,
                    block_t: Optional[int] = None, *,
                    cpu_impl: str = "ref") -> Tuple[str, int]:
    """(impl, block_t) for a caller that names neither.  On TPU: the
    Pallas kernels over 128-row token tiles.  Elsewhere: *cpu_impl*
    (each entry point keeps its historical CPU default) over 8-row
    tiles, which keeps interpret-mode tests fast."""
    on_tpu = jax.default_backend() == "tpu"
    if impl is None:
        impl = "pallas" if on_tpu else cpu_impl
    if block_t is None:
        block_t = 128 if on_tpu else 8
    return impl, int(block_t)


def _tile_map(ids: jax.Array, block_t: int) -> jax.Array:
    return ids.reshape(ids.shape[0] // block_t, block_t)[:, 0]


def _group_sizes(ids: jax.Array, K: int) -> jax.Array:
    return jnp.bincount(ids, length=K)


def _int_zeros(a) -> np.ndarray:
    """float0 cotangents for integer operands (ids, ranks)."""
    return np.zeros(a.shape, jax.dtypes.float0)


# ------------------------------------------------------------------ xla
def _xla_forward(x, A, B, ids, ranks, scalings, equal_segments: bool):
    """Forward formulas shared by the solo and shard-local VJPs (sharing
    the literal expressions is what makes the sharded path bit-exact)."""
    T, d_in = x.shape
    K, _, r_pad = A.shape
    lane = jnp.arange(r_pad)

    if equal_segments and T % K == 0:
        buf = x.reshape(K, T // K, d_in)               # adapter-major
        xa = jnp.einsum("kcd,kdr->kcr", buf, A,
                        preferred_element_type=jnp.float32)
        xa = jnp.where(lane[None, None, :] < ranks[:, None, None],
                       xa, 0.0).astype(x.dtype)
        y = jnp.einsum("kcr,kro->kco", xa, B,
                       preferred_element_type=jnp.float32)
        y = y * scalings[:, None, None]
        return y.reshape(T, -1).astype(x.dtype)

    # fallback: dense over K with a one-hot combine (exact, no scatter)
    onehot = jax.nn.one_hot(ids, K, dtype=x.dtype)     # (T, K)
    xa = jnp.einsum("td,kdr->tkr", x, A,
                    preferred_element_type=jnp.float32)
    xa = jnp.where(lane[None, None, :] < ranks[None, :, None],
                   xa, 0.0).astype(x.dtype)
    # f32 operands: products of bf16 values are exact in f32, so this is
    # the bf16-in/f32-accumulate GEMM, and XLA:CPU has no bf16 x bf16 ->
    # f32 kernel for this mid-axis batch layout
    y = jnp.einsum("tkr,kro->tko", xa.astype(jnp.float32),
                   B.astype(jnp.float32))
    y = y * scalings[None, :, None]
    return jnp.einsum("tko,tk->to", y, onehot.astype(jnp.float32)
                      ).astype(x.dtype)


def _xla_equal_parts(x, A, B, ranks, scalings, dy):
    """(buf, dy_s, xa, dxa) of the equal-segment backward — per-token
    quantities, evaluated at whatever shape *x* has (local or gathered)."""
    T, d_in = x.shape
    K, _, r_pad = A.shape
    lane = jnp.arange(r_pad)
    C = T // K
    buf = x.reshape(K, C, d_in)
    dy_s = (dy.reshape(K, C, -1).astype(jnp.float32)
            * scalings[:, None, None])
    # recompute the compact intermediate (cheap: 2*T*d*r flops)
    xa = jnp.einsum("kcd,kdr->kcr", buf, A,
                    preferred_element_type=jnp.float32)
    xa = jnp.where(lane[None, None, :] < ranks[:, None, None],
                   xa, 0.0).astype(x.dtype)
    dxa = jnp.einsum("kco,kro->kcr", dy_s, B.astype(jnp.float32))
    dxa = jnp.where(lane[None, None, :] < ranks[:, None, None],
                    dxa, 0.0)
    return buf, dy_s, xa, dxa


def _xla_equal_wgrads(buf, dy_s, xa, dxa):
    # segment-dense wgrads: one batched GEMM pair, no K densify
    dA = jnp.einsum("kcd,kcr->kdr", buf.astype(jnp.float32), dxa)
    dB = jnp.einsum("kcr,kco->kro", xa.astype(jnp.float32), dy_s)
    return dA, dB


def _xla_fallback_parts(x, A, B, ids, ranks, scalings, dy):
    """(dy_k, xa, dxa) of the dense-over-K backward — the one-hot
    weighting in dy_k zeroes foreign-adapter terms, so dxa is already
    segment-sparse and dA/dB need no one-hot."""
    K, _, r_pad = A.shape
    lane = jnp.arange(r_pad)
    onehot = jax.nn.one_hot(ids, K, dtype=jnp.float32)
    dy_k = (dy.astype(jnp.float32)[:, None, :]
            * onehot[:, :, None] * scalings[None, :, None])
    xa = jnp.einsum("td,kdr->tkr", x, A,
                    preferred_element_type=jnp.float32)
    xa = jnp.where(lane[None, None, :] < ranks[None, :, None],
                   xa, 0.0).astype(x.dtype)
    dxa = jnp.einsum("tko,kro->tkr", dy_k, B.astype(jnp.float32))
    dxa = jnp.where(lane[None, None, :] < ranks[None, :, None],
                    dxa, 0.0)
    return dy_k, xa, dxa


def _xla_fallback_wgrads(x, dy_k, xa, dxa):
    dA = jnp.einsum("td,tkr->kdr", x.astype(jnp.float32), dxa)
    dB = jnp.einsum("tkr,tko->kro", xa.astype(jnp.float32), dy_k)
    return dA, dB


@functools.lru_cache(maxsize=4)
def _make_xla_fn(equal_segments: bool):
    """Build the custom-VJP segment-dense path (static segment layout).

    Forward — when the scheduler hands us EQUAL segments (the production
    layout: every job contributes the same padded row count), dispatch is
    a comm-free reshape (T, d) -> (K, T/K, d) followed by two dense
    batched einsums with bf16 inputs + f32 accumulation — FLOPs = the
    ideal 2*T*d*r and zero collectives (§Perf iteration 3b; scatter-based
    dispatch was collective-bound, ragged_dot's non-TPU fallback densified
    over all K adapters in f32).  Unequal segments fall back to a masked
    dense-over-K formulation (exact; K x r extra flops — fine for K<=8
    test-scale groups).

    Backward — hand-written instead of autodiffed: the equal-segment path
    gets segment-dense batched-einsum wgrads (dA[k] = buf[k]ᵀ·dxa[k],
    dB[k] = xa[k]ᵀ·dy[k]; ideal FLOPs, no K densification), where
    autodiff through the fallback would densify every wgrad over all K
    adapters regardless of layout.  Scalings are alpha/r constants that
    are never trained — stop-gradiented via a float0 cotangent."""

    @jax.custom_vjp
    def f(x, A, B, ids, ranks, scalings):
        return _xla_forward(x, A, B, ids, ranks, scalings, equal_segments)

    def _fwd(x, A, B, ids, ranks, scalings):
        return f(x, A, B, ids, ranks, scalings), (x, A, B, ids, ranks,
                                                  scalings)

    def _bwd(res, dy):
        x, A, B, ids, ranks, scalings = res
        T, d_in = x.shape
        K = A.shape[0]
        Af = A.astype(jnp.float32)

        if equal_segments and T % K == 0:
            buf, dy_s, xa, dxa = _xla_equal_parts(x, A, B, ranks, scalings,
                                                  dy)
            dx = jnp.einsum("kcr,kdr->kcd", dxa, Af).reshape(T, d_in)
            dA, dB = _xla_equal_wgrads(buf, dy_s, xa, dxa)
        else:
            dy_k, xa, dxa = _xla_fallback_parts(x, A, B, ids, ranks,
                                                scalings, dy)
            dx = jnp.einsum("tkr,kdr->td", dxa, Af)
            dA, dB = _xla_fallback_wgrads(x, dy_k, xa, dxa)

        # scalings are alpha/r constants — stop-gradient (never trained)
        return (dx.astype(x.dtype), dA.astype(A.dtype), dB.astype(B.dtype),
                _int_zeros(ids), _int_zeros(ranks),
                np.zeros(scalings.shape, jax.dtypes.float0))

    f.defvjp(_fwd, _bwd)
    return f


def fused_lora_xla(x, A, B, ids, ranks, scalings, capacity=None,
                   equal_segments: bool = False):
    """Segment-dense grouped GEMM pair — the GSPMD/dry-run path.

    See ``_make_xla_fn`` for the forward/backward contract; the custom
    VJP keeps wgrads segment-dense on the equal-segment production
    layout instead of autodiffing through the masked dense-over-K
    fallback."""
    del capacity  # segment capacity is implied by the equal-segment layout
    return _make_xla_fn(bool(equal_segments))(x, A, B, ids, ranks, scalings)


# ---------------------------------------------------------- shard-local
def gather_solo(t, axis_name: str, solo_pos, total: int):
    """Reassemble the full tensor in SOLO order from per-shard pieces.

    Each shard scatters its rows into a zero (total, ...) buffer at
    their solo positions (``solo_pos``, a sharded input — shard_map
    partial-auto supports neither all_gather nor axis_index on this
    backend, and the scatter+psum formulation needs no shard identity),
    then one psum completes the gather.  Bit-preserving: every output
    element is its true value plus exact zeros from the other shards,
    and adding 0.0 never rounds — regardless of psum order.
    """
    out = jnp.zeros((total,) + t.shape[1:], t.dtype)
    out = out.at[solo_pos].set(t, unique_indices=True)
    return jax.lax.psum(out, axis_name)


@functools.lru_cache(maxsize=32)
def _make_xla_sharded_fn(equal_segments: bool, axis_name: str,
                         total_tokens: int):
    """Shard-local xla VJP (DESIGN.md §8).

    Forward and dx run on the local token shard only (per-token math —
    bit-identical to the solo VJP's per-token values).  The wgrads
    reassemble x and the cotangent at FULL shape in solo token order
    (``gather_solo``) and evaluate the SAME wgrad expressions as
    ``_make_xla_fn`` — so the adapter gradient every shard computes is
    replicated AND bit-exact w.r.t. solo execution.  Nano-slices
    reassemble into the full-size buffer with exact-zero rows for the
    tokens of other slices, which leaves every wgrad value (and, on the
    full-batch n=1 path, every bit) unchanged.

    ``solo_pos``: (T_local,) solo token position of each local token —
    a traced operand (it rides the batch through nano slicing), with a
    float0 cotangent like the other integer operands.
    """
    @jax.custom_vjp
    def f(x, A, B, ids, ranks, scalings, solo_pos):
        return _xla_forward(x, A, B, ids, ranks, scalings, equal_segments)

    def _fwd(x, A, B, ids, ranks, scalings, solo_pos):
        return (f(x, A, B, ids, ranks, scalings, solo_pos),
                (x, A, B, ids, ranks, scalings, solo_pos))

    def _bwd(res, dy):
        x, A, B, ids, ranks, scalings, solo_pos = res
        T, d_in = x.shape
        K = A.shape[0]
        Af = A.astype(jnp.float32)

        # ---- local: dx (per-token, stays on this shard)
        if equal_segments and T % K == 0:
            _, _, _, dxa = _xla_equal_parts(x, A, B, ranks, scalings, dy)
            dx = jnp.einsum("kcr,kdr->kcd", dxa, Af).reshape(T, d_in)
        else:
            _, _, dxa = _xla_fallback_parts(x, A, B, ids, ranks, scalings,
                                            dy)
            dx = jnp.einsum("tkr,kdr->td", dxa, Af)

        # ---- global: wgrads from the solo-order full-shape tensors
        xg = gather_solo(x, axis_name, solo_pos, total_tokens)
        dyg = gather_solo(dy, axis_name, solo_pos, total_tokens)
        if equal_segments and total_tokens % K == 0:
            buf, dy_s, xa, gdxa = _xla_equal_parts(xg, A, B, ranks,
                                                   scalings, dyg)
            dA, dB = _xla_equal_wgrads(buf, dy_s, xa, gdxa)
        else:
            idg = gather_solo(ids, axis_name, solo_pos, total_tokens)
            dy_k, xa, gdxa = _xla_fallback_parts(xg, A, B, idg, ranks,
                                                 scalings, dyg)
            dA, dB = _xla_fallback_wgrads(xg, dy_k, xa, gdxa)

        return (dx.astype(x.dtype), dA.astype(A.dtype), dB.astype(B.dtype),
                _int_zeros(ids), _int_zeros(ranks),
                np.zeros(scalings.shape, jax.dtypes.float0),
                _int_zeros(solo_pos))

    f.defvjp(_fwd, _bwd)
    return f


# --------------------------------------------------------------- pallas
@functools.lru_cache(maxsize=32)
def _make_pallas_fn(block_t: int):
    """Build the custom-VJP pallas path for a static token-tile size.

    Backward = four grouped kernel launches, all segment-aware:
      dxa = dy_s ·g Bᵀ        (grouped-mm)      dx = dxa ·g Aᵀ (grouped-mm)
      dA  = Σ_seg xᵀ·dxa      (grouped-wgrad)   dB = Σ_seg xaᵀ·dy_s (grouped-wgrad)
    No one-hot einsums, no dense-over-K wgrads, and no d(scaling) launch:
    scalings are alpha/r constants that are never trained, so they are
    stop-gradiented (float0 cotangent) — one grouped-mm launch + einsum
    saved per backward."""

    @jax.custom_vjp
    def f(x, A, B, ids, ranks, scalings):
        y = pk.fused_lora_pallas(x, A, B, _tile_map(ids, block_t), ranks,
                                 block_t=block_t)
        return (y.astype(jnp.float32) * scalings[ids][:, None]).astype(x.dtype)

    def _fwd(x, A, B, ids, ranks, scalings):
        return f(x, A, B, ids, ranks, scalings), (x, A, B, ids, ranks,
                                                  scalings)

    def _bwd(res, dy):
        x, A, B, ids, ranks, scalings = res
        K = A.shape[0]
        tm = _tile_map(ids, block_t)
        dy_s = (dy.astype(jnp.float32) * scalings[ids][:, None]).astype(dy.dtype)

        # dx = ((dy_s @ B^T) * mask) @ A^T — two grouped-mm kernel launches
        dxa = pk.grouped_matmul_pallas(dy_s, jnp.swapaxes(B, 1, 2), tm,
                                       block_t=block_t)
        dxa = ref_impl.rank_mask(dxa.astype(jnp.float32), ids,
                                 ranks).astype(x.dtype)
        dx = pk.grouped_matmul_pallas(dxa, jnp.swapaxes(A, 1, 2), tm,
                                      block_t=block_t)

        # wgrads: segment-aware grouped accumulation (revisiting-output
        # kernels over the sorted token tiles — f32 accumulators)
        xa = pk.grouped_matmul_pallas(x, A, tm, block_t=block_t)
        xa = ref_impl.rank_mask(xa.astype(jnp.float32), ids,
                                ranks).astype(x.dtype)
        dA = pk.grouped_wgrad_pallas(x, dxa, tm, K, block_t=block_t)
        dB = pk.grouped_wgrad_pallas(xa, dy_s, tm, K, block_t=block_t)

        return (dx.astype(x.dtype), dA.astype(A.dtype), dB.astype(B.dtype),
                _int_zeros(ids), _int_zeros(ranks),
                np.zeros(scalings.shape, jax.dtypes.float0))

    f.defvjp(_fwd, _bwd)
    return f


def _fused_lora_pallas(x, A, B, ids, ranks, scalings, block_t):
    return _make_pallas_fn(int(block_t))(x, A, B, ids, ranks, scalings)


@functools.lru_cache(maxsize=32)
def _make_pallas_sharded_fn(block_t: int, axis_name: str,
                            total_tokens: int, full_batch: bool):
    """Shard-local pallas VJP (DESIGN.md §8): forward + dx are local
    grouped kernel launches over the shard's token tiles; wgrads
    reassemble the token operands at full shape in solo order
    (``gather_solo``) and re-run the SAME grouped-wgrad launches as the
    solo VJP.  The revisiting-output kernel needs the segment-sorted
    solo layout, which only the full batch guarantees (``full_batch``);
    a nano-slice's reassembled ids carry zeros in other slices' slots,
    so those drop to the order/value-invariant one-hot wgrads."""

    @jax.custom_vjp
    def f(x, A, B, ids, ranks, scalings, solo_pos):
        y = pk.fused_lora_pallas(x, A, B, _tile_map(ids, block_t), ranks,
                                 block_t=block_t)
        return (y.astype(jnp.float32) * scalings[ids][:, None]).astype(x.dtype)

    def _fwd(x, A, B, ids, ranks, scalings, solo_pos):
        return (f(x, A, B, ids, ranks, scalings, solo_pos),
                (x, A, B, ids, ranks, scalings, solo_pos))

    def _bwd(res, dy):
        x, A, B, ids, ranks, scalings, solo_pos = res
        K = A.shape[0]
        tm = _tile_map(ids, block_t)
        dy_s = (dy.astype(jnp.float32) * scalings[ids][:, None]).astype(dy.dtype)

        # ---- local: dx (two grouped-mm launches over the local tiles)
        dxa = pk.grouped_matmul_pallas(dy_s, jnp.swapaxes(B, 1, 2), tm,
                                       block_t=block_t)
        dxa = ref_impl.rank_mask(dxa.astype(jnp.float32), ids,
                                 ranks).astype(x.dtype)
        dx = pk.grouped_matmul_pallas(dxa, jnp.swapaxes(A, 1, 2), tm,
                                      block_t=block_t)

        # ---- global: wgrads from the solo-order full-shape tensors
        xg = gather_solo(x, axis_name, solo_pos, total_tokens)
        dyg_s = gather_solo(dy_s, axis_name, solo_pos, total_tokens)
        idg = gather_solo(ids, axis_name, solo_pos, total_tokens)
        if full_batch:
            tmg = _tile_map(idg, block_t)
            gdxa = pk.grouped_matmul_pallas(dyg_s, jnp.swapaxes(B, 1, 2),
                                            tmg, block_t=block_t)
            gdxa = ref_impl.rank_mask(gdxa.astype(jnp.float32), idg,
                                      ranks).astype(x.dtype)
            xag = pk.grouped_matmul_pallas(xg, A, tmg, block_t=block_t)
            xag = ref_impl.rank_mask(xag.astype(jnp.float32), idg,
                                     ranks).astype(x.dtype)
            dA = pk.grouped_wgrad_pallas(xg, gdxa, tmg, K, block_t=block_t)
            dB = pk.grouped_wgrad_pallas(xag, dyg_s, tmg, K,
                                         block_t=block_t)
        else:
            # dyg_s is already scaled — unit scalings avoid double-scaling
            ones = jnp.ones_like(scalings)
            dy_k, xa, gdxa = _xla_fallback_parts(xg, A, B, idg, ranks,
                                                 ones, dyg_s)
            dA, dB = _xla_fallback_wgrads(xg, dy_k, xa, gdxa)

        return (dx.astype(x.dtype), dA.astype(A.dtype), dB.astype(B.dtype),
                _int_zeros(ids), _int_zeros(ranks),
                np.zeros(scalings.shape, jax.dtypes.float0),
                _int_zeros(solo_pos))

    f.defvjp(_fwd, _bwd)
    return f


# ------------------------------------------------------- ragged (xla)
def _bucket_params(A, B, layout):
    """Static per-bucket dense views of a packed ragged pair: for each
    padded width rp, the member jobs and their stacked (K_b, d, rp) /
    (K_b, rp, d_out) slabs.  A bucket whose jobs are consecutive owns a
    CONTIGUOUS packed column range, so its slab is one reshape of one
    slice; pure static slicing either way — the compiler fuses the
    stack into the consuming einsum."""
    out = []
    for rp, jobs in layout.buckets:
        if _contiguous(jobs):
            o0 = layout.offsets[jobs[0]]
            Ab = jax.lax.slice_in_dim(
                A, o0, o0 + rp * len(jobs), axis=1
            ).reshape(A.shape[0], len(jobs), rp).transpose(1, 0, 2)
            Bb = jax.lax.slice_in_dim(
                B, o0, o0 + rp * len(jobs), axis=0
            ).reshape(len(jobs), rp, B.shape[-1])
        else:
            Ab = jnp.stack([jax.lax.slice_in_dim(
                A, layout.offsets[k], layout.offsets[k] + rp, axis=1)
                for k in jobs])
            Bb = jnp.stack([jax.lax.slice_in_dim(
                B, layout.offsets[k], layout.offsets[k] + rp, axis=0)
                for k in jobs])
        out.append((rp, jobs, Ab, Bb))
    return out


def _contiguous(jobs) -> bool:
    return all(b == a + 1 for a, b in zip(jobs, jobs[1:]))


def _bucket_rows(buf, jobs):
    """The bucket's job rows of a (K, C, ...) job-major tensor — one
    slice when the bucket is a consecutive job range, a static gather
    otherwise."""
    if _contiguous(jobs):
        return jax.lax.slice_in_dim(buf, jobs[0], jobs[-1] + 1, axis=0)
    return buf[jnp.asarray(jobs)]


def _assemble_jobs(pieces):
    """Per-job (C, ...) pieces (job order) -> (K, C, ...) job-major."""
    return jnp.stack(pieces, axis=0)


def _bucket_rank_mask(layout, rp, jobs):
    """(K_b, rp) bool lane mask, or None when every member fills its
    padded width (no masking work at all — the common aligned case)."""
    ranks = [layout.ranks[k] for k in jobs]
    if all(r == rp for r in ranks):
        return None
    lane = np.arange(rp)[None, :] < np.asarray(ranks)[:, None]
    return jnp.asarray(lane)


def _concat_pieces(pieces_a, pieces_b):
    """Per-job (d, rp_k)/(rp_k, d) gradient pieces (job order) -> packed."""
    return (jnp.concatenate(pieces_a, axis=-1),
            jnp.concatenate(pieces_b, axis=0))


def _ragged_equal_forward(x, A, B, scalings, layout):
    """Equal-segment ragged forward: one segment-dense batched GEMM pair
    PER RANK BUCKET — FLOPs = Σ_k 2·C·d·rp_k, the true-rank ideal the
    masked max-rank path misses by up to r_max/rp_k per member."""
    T, d_in = x.shape
    K = layout.num_jobs
    C = T // K
    buf = x.reshape(K, C, d_in)
    pieces = [None] * K
    for rp, jobs, Ab, Bb in _bucket_params(A, B, layout):
        xa = jnp.einsum("kcd,kdr->kcr", _bucket_rows(buf, jobs), Ab,
                        preferred_element_type=jnp.float32)
        m = _bucket_rank_mask(layout, rp, jobs)
        if m is not None:
            xa = jnp.where(m[:, None, :], xa, 0.0)
        xa = xa.astype(x.dtype)
        y = jnp.einsum("kcr,kro->kco", xa, Bb,
                       preferred_element_type=jnp.float32)
        y = y * scalings[jnp.asarray(jobs)][:, None, None]
        for i, k in enumerate(jobs):
            pieces[k] = y[i]
    return _assemble_jobs(pieces).reshape(T, -1).astype(x.dtype)


def _ragged_equal_bwd_parts(x, A, B, scalings, layout, dy):
    """Per-bucket recomputed backward intermediates of the equal path:
    yields (rp, jobs, Ab, buf_b, dy_s, xa, dxa) — shared by dx and the
    wgrads so solo and sharded VJPs evaluate literally the same
    expressions (the sharded bit-exactness contract)."""
    T, d_in = x.shape
    K = layout.num_jobs
    C = T // K
    buf = x.reshape(K, C, d_in)
    dyb = dy.reshape(K, C, -1)
    for rp, jobs, Ab, Bb in _bucket_params(A, B, layout):
        buf_b = _bucket_rows(buf, jobs)
        dy_s = (_bucket_rows(dyb, jobs).astype(jnp.float32)
                * scalings[jnp.asarray(jobs)][:, None, None])
        xa = jnp.einsum("kcd,kdr->kcr", buf_b, Ab,
                        preferred_element_type=jnp.float32)
        dxa = jnp.einsum("kco,kro->kcr", dy_s, Bb.astype(jnp.float32))
        m = _bucket_rank_mask(layout, rp, jobs)
        if m is not None:
            xa = jnp.where(m[:, None, :], xa, 0.0)
            dxa = jnp.where(m[:, None, :], dxa, 0.0)
        yield rp, jobs, Ab, buf_b, dy_s, xa.astype(x.dtype), dxa


def _ragged_equal_dx(x, A, B, scalings, layout, dy):
    T, d_in = x.shape
    pieces = [None] * layout.num_jobs
    for rp, jobs, Ab, buf_b, dy_s, xa, dxa in _ragged_equal_bwd_parts(
            x, A, B, scalings, layout, dy):
        dx_b = jnp.einsum("kcr,kdr->kcd", dxa, Ab.astype(jnp.float32))
        for i, k in enumerate(jobs):
            pieces[k] = dx_b[i]
    return _assemble_jobs(pieces).reshape(T, d_in)


def _ragged_equal_bwd(x, A, B, scalings, layout, dy):
    """Single-pass solo backward: dx + dA + dB from ONE evaluation of
    the per-bucket intermediates (the sharded VJP instead splits dx
    (local) from the wgrads (gathered), paying the recompute only where
    the operands genuinely differ)."""
    T, d_in = x.shape
    K = layout.num_jobs
    dx_p, dA_p, dB_p = [None] * K, [None] * K, [None] * K
    for rp, jobs, Ab, buf_b, dy_s, xa, dxa in _ragged_equal_bwd_parts(
            x, A, B, scalings, layout, dy):
        dx_b = jnp.einsum("kcr,kdr->kcd", dxa, Ab.astype(jnp.float32))
        dA_b = jnp.einsum("kcd,kcr->kdr", buf_b.astype(jnp.float32), dxa)
        dB_b = jnp.einsum("kcr,kco->kro", xa.astype(jnp.float32), dy_s)
        for i, k in enumerate(jobs):
            dx_p[k], dA_p[k], dB_p[k] = dx_b[i], dA_b[i], dB_b[i]
    dA, dB = _concat_pieces(dA_p, dB_p)
    return _assemble_jobs(dx_p).reshape(T, d_in), dA, dB


def _ragged_equal_wgrads(x, A, B, scalings, layout, dy):
    K = layout.num_jobs
    dA_p, dB_p = [None] * K, [None] * K
    for rp, jobs, Ab, buf_b, dy_s, xa, dxa in _ragged_equal_bwd_parts(
            x, A, B, scalings, layout, dy):
        dA_b = jnp.einsum("kcd,kcr->kdr", buf_b.astype(jnp.float32), dxa)
        dB_b = jnp.einsum("kcr,kco->kro", xa.astype(jnp.float32), dy_s)
        for i, k in enumerate(jobs):
            dA_p[k] = dA_b[i]
            dB_p[k] = dB_b[i]
    return _concat_pieces(dA_p, dB_p)


def _ragged_fallback_forward(x, A, B, ids, scalings, layout):
    """Dense-over-BUCKET fallback for layouts without equal segments
    (nano slices, test batches): exact for any ids, and still
    rank-aware — each bucket densifies over its own members at its own
    width (K_b · rp_b), never over all K at r_max."""
    T, _ = x.shape
    K = layout.num_jobs
    y = jnp.zeros((T, B.shape[-1]), jnp.float32)
    for rp, jobs, Ab, Bb in _bucket_params(A, B, layout):
        ji = jnp.asarray(jobs)
        table = np.full(K, len(jobs), np.int32)
        table[list(jobs)] = np.arange(len(jobs), dtype=np.int32)
        lids = jnp.asarray(table)[ids]        # bucket-local id (K_b = miss)
        onehot = jax.nn.one_hot(lids, len(jobs), dtype=jnp.float32)
        xa = jnp.einsum("td,kdr->tkr", x, Ab,
                        preferred_element_type=jnp.float32)
        m = _bucket_rank_mask(layout, rp, jobs)
        if m is not None:
            xa = jnp.where(m[None, :, :], xa, 0.0)
        xa = xa.astype(x.dtype)
        yb = jnp.einsum("tkr,kro->tko", xa, Bb,
                        preferred_element_type=jnp.float32)
        yb = yb * scalings[ji][None, :, None]
        y = y + jnp.einsum("tko,tk->to", yb, onehot)
    return y.astype(x.dtype)


def _ragged_fallback_bwd_parts(x, A, B, ids, scalings, layout, dy):
    """Per-bucket (rp, jobs, Ab, dy_k, xa, dxa) of the fallback backward
    — dy_k carries the bucket-local one-hot, so dxa is segment-sparse
    and the wgrads need no further masking."""
    K = layout.num_jobs
    for rp, jobs, Ab, Bb in _bucket_params(A, B, layout):
        ji = jnp.asarray(jobs)
        table = np.full(K, len(jobs), np.int32)
        table[list(jobs)] = np.arange(len(jobs), dtype=np.int32)
        lids = jnp.asarray(table)[ids]
        onehot = jax.nn.one_hot(lids, len(jobs), dtype=jnp.float32)
        dy_k = (dy.astype(jnp.float32)[:, None, :]
                * onehot[:, :, None] * scalings[ji][None, :, None])
        xa = jnp.einsum("td,kdr->tkr", x, Ab,
                        preferred_element_type=jnp.float32)
        dxa = jnp.einsum("tko,kro->tkr", dy_k, Bb.astype(jnp.float32))
        m = _bucket_rank_mask(layout, rp, jobs)
        if m is not None:
            xa = jnp.where(m[None, :, :], xa, 0.0)
            dxa = jnp.where(m[None, :, :], dxa, 0.0)
        yield rp, jobs, Ab, dy_k, xa.astype(x.dtype), dxa


def _ragged_fallback_dx(x, A, B, ids, scalings, layout, dy):
    dx = jnp.zeros(x.shape, jnp.float32)
    for rp, jobs, Ab, dy_k, xa, dxa in _ragged_fallback_bwd_parts(
            x, A, B, ids, scalings, layout, dy):
        dx = dx + jnp.einsum("tkr,kdr->td", dxa, Ab.astype(jnp.float32))
    return dx


def _ragged_fallback_wgrads(x, A, B, ids, scalings, layout, dy):
    K = layout.num_jobs
    dA_p, dB_p = [None] * K, [None] * K
    for rp, jobs, Ab, dy_k, xa, dxa in _ragged_fallback_bwd_parts(
            x, A, B, ids, scalings, layout, dy):
        dA_b = jnp.einsum("td,tkr->kdr", x.astype(jnp.float32), dxa)
        dB_b = jnp.einsum("tkr,tko->kro", xa.astype(jnp.float32), dy_k)
        for i, k in enumerate(jobs):
            dA_p[k] = dA_b[i]
            dB_p[k] = dB_b[i]
    return _concat_pieces(dA_p, dB_p)


def _ragged_fallback_bwd(x, A, B, ids, scalings, layout, dy):
    """Single-pass solo fallback backward (dx + dA + dB)."""
    K = layout.num_jobs
    dx = jnp.zeros(x.shape, jnp.float32)
    dA_p, dB_p = [None] * K, [None] * K
    for rp, jobs, Ab, dy_k, xa, dxa in _ragged_fallback_bwd_parts(
            x, A, B, ids, scalings, layout, dy):
        dx = dx + jnp.einsum("tkr,kdr->td", dxa, Ab.astype(jnp.float32))
        dA_b = jnp.einsum("td,tkr->kdr", x.astype(jnp.float32), dxa)
        dB_b = jnp.einsum("tkr,tko->kro", xa.astype(jnp.float32), dy_k)
        for i, k in enumerate(jobs):
            dA_p[k] = dA_b[i]
            dB_p[k] = dB_b[i]
    dA, dB = _concat_pieces(dA_p, dB_p)
    return dx, dA, dB


@functools.lru_cache(maxsize=64)
def _make_ragged_xla_fn(layout, equal_segments: bool):
    """Custom-VJP ragged xla path (static RankLayout).

    Forward — equal segments dispatch to one batched einsum pair per
    rank bucket (comm-free reshape + static gather of the bucket's
    segments); anything else falls back to the per-bucket one-hot
    combine.  Backward — hand-written bucket-dense wgrads mirroring the
    masked path's structure at true-rank widths; scalings are alpha/r
    constants, stop-gradiented via a float0 cotangent."""

    @jax.custom_vjp
    def f(x, A, B, ids, scalings):
        T = x.shape[0]
        if equal_segments and T % layout.num_jobs == 0:
            return _ragged_equal_forward(x, A, B, scalings, layout)
        return _ragged_fallback_forward(x, A, B, ids, scalings, layout)

    def _fwd(x, A, B, ids, scalings):
        return f(x, A, B, ids, scalings), (x, A, B, ids, scalings)

    def _bwd(res, dy):
        x, A, B, ids, scalings = res
        T = x.shape[0]
        if equal_segments and T % layout.num_jobs == 0:
            dx, dA, dB = _ragged_equal_bwd(x, A, B, scalings, layout, dy)
        else:
            dx, dA, dB = _ragged_fallback_bwd(x, A, B, ids, scalings,
                                              layout, dy)
        return (dx.astype(x.dtype), dA.astype(A.dtype), dB.astype(B.dtype),
                _int_zeros(ids),
                np.zeros(scalings.shape, jax.dtypes.float0))

    f.defvjp(_fwd, _bwd)
    return f


@functools.lru_cache(maxsize=64)
def _make_ragged_xla_sharded_fn(layout, equal_segments: bool,
                                axis_name: str, total_tokens: int):
    """Shard-local ragged xla VJP (DESIGN.md §8 contract, ragged
    storage): forward and dx run on the local token shard; the wgrads
    reassemble x and the cotangent at FULL shape in solo order
    (``gather_solo``) and evaluate the SAME per-bucket wgrad
    expressions as the solo VJP — replicated AND bit-exact w.r.t. solo
    execution.  Nano slices reassemble with exact-zero rows for other
    slices' tokens, which contribute exact zeros to every bucket."""

    @jax.custom_vjp
    def f(x, A, B, ids, scalings, solo_pos):
        T = x.shape[0]
        if equal_segments and T % layout.num_jobs == 0:
            return _ragged_equal_forward(x, A, B, scalings, layout)
        return _ragged_fallback_forward(x, A, B, ids, scalings, layout)

    def _fwd(x, A, B, ids, scalings, solo_pos):
        return (f(x, A, B, ids, scalings, solo_pos),
                (x, A, B, ids, scalings, solo_pos))

    def _bwd(res, dy):
        x, A, B, ids, scalings, solo_pos = res
        T = x.shape[0]
        # ---- local: dx (per-token, stays on this shard)
        if equal_segments and T % layout.num_jobs == 0:
            dx = _ragged_equal_dx(x, A, B, scalings, layout, dy)
        else:
            dx = _ragged_fallback_dx(x, A, B, ids, scalings, layout, dy)

        # ---- global: wgrads from the solo-order full-shape tensors
        xg = gather_solo(x, axis_name, solo_pos, total_tokens)
        dyg = gather_solo(dy, axis_name, solo_pos, total_tokens)
        if equal_segments and total_tokens % layout.num_jobs == 0:
            dA, dB = _ragged_equal_wgrads(xg, A, B, scalings, layout, dyg)
        else:
            idg = gather_solo(ids, axis_name, solo_pos, total_tokens)
            dA, dB = _ragged_fallback_wgrads(xg, A, B, idg, scalings,
                                             layout, dyg)
        return (dx.astype(x.dtype), dA.astype(A.dtype), dB.astype(B.dtype),
                _int_zeros(ids),
                np.zeros(scalings.shape, jax.dtypes.float0),
                _int_zeros(solo_pos))

    f.defvjp(_fwd, _bwd)
    return f


# ---------------------------------------------------- ragged (pallas)
@functools.lru_cache(maxsize=64)
def _make_ragged_pallas_fn(meta: RaggedMeta, block_t: int):
    """Custom-VJP ragged pallas path for a static (batch layout, rank
    layout).  Backward = one fused dgrad launch (dx) + two packed-mm
    launches (xa, dxa) + two ragged-wgrad launches (dA, dB) — every
    grid step is an active (token tile, rank tile) pair, so the whole
    backward does true-rank work.  Scalings stop-gradiented (float0)."""

    @jax.custom_vjp
    def f(x, A, B, ids, scalings):
        y = rg.ragged_lora_fwd(x, A, B, meta, block_t=block_t)
        return (y * scalings[ids][:, None]).astype(x.dtype)

    def _fwd(x, A, B, ids, scalings):
        return f(x, A, B, ids, scalings), (x, A, B, ids, scalings)

    def _bwd(res, dy):
        x, A, B, ids, scalings = res
        dy_s = (dy.astype(jnp.float32)
                * scalings[ids][:, None]).astype(dy.dtype)
        dx = rg.ragged_lora_dgrad(dy_s, A, B, meta, block_t=block_t)
        # packed intermediates are rank-major (R, T) — kernels/ragged.py
        xat = rg.ragged_xa(x, A, meta, block_t=block_t)
        dxat = rg.ragged_dxa(dy_s, B, meta,
                             block_t=block_t).astype(x.dtype)
        dA = rg.ragged_wgrad(dxat, x, meta, block_t=block_t)   # (R, d_in)
        dB = rg.ragged_wgrad(xat, dy_s, meta, block_t=block_t)  # (R, d_out)
        return (dx.astype(x.dtype), dA.T.astype(A.dtype),
                dB.astype(B.dtype), _int_zeros(ids),
                np.zeros(scalings.shape, jax.dtypes.float0))

    f.defvjp(_fwd, _bwd)
    return f


@functools.lru_cache(maxsize=64)
def _make_ragged_pallas_sharded_fn(meta_local: RaggedMeta,
                                   meta_solo: RaggedMeta, block_t: int,
                                   axis_name: str, total_tokens: int):
    """Shard-local ragged pallas VJP: forward + dx are local ragged
    launches over this shard's (token tile, rank tile) pairs; wgrads
    reassemble the token operands at full shape in solo order and
    re-run the SAME ragged launches under the static SOLO metadata.
    The solo metadata stays valid for nano slices too: reassembled
    buffers carry exact-zero rows for other slices' tokens, and a zero
    row contributes exact zeros to its segment's accumulator whatever
    segment the static map assigns it — so no dense fallback is needed
    anywhere (the masked pallas path needed one)."""

    @jax.custom_vjp
    def f(x, A, B, ids, scalings, solo_pos):
        y = rg.ragged_lora_fwd(x, A, B, meta_local, block_t=block_t)
        return (y * scalings[ids][:, None]).astype(x.dtype)

    def _fwd(x, A, B, ids, scalings, solo_pos):
        return (f(x, A, B, ids, scalings, solo_pos),
                (x, A, B, ids, scalings, solo_pos))

    def _bwd(res, dy):
        x, A, B, ids, scalings, solo_pos = res
        dy_s = (dy.astype(jnp.float32)
                * scalings[ids][:, None]).astype(dy.dtype)

        # ---- local: dx (one fused ragged dgrad launch)
        dx = rg.ragged_lora_dgrad(dy_s, A, B, meta_local,
                                  block_t=block_t)

        # ---- global: wgrads from the solo-order full-shape tensors
        xg = gather_solo(x, axis_name, solo_pos, total_tokens)
        dyg_s = gather_solo(dy_s, axis_name, solo_pos, total_tokens)
        xagt = rg.ragged_xa(xg, A, meta_solo, block_t=block_t)
        gdxat = rg.ragged_dxa(dyg_s, B, meta_solo,
                              block_t=block_t).astype(x.dtype)
        dA = rg.ragged_wgrad(gdxat, xg, meta_solo, block_t=block_t)
        dB = rg.ragged_wgrad(xagt, dyg_s, meta_solo, block_t=block_t)
        return (dx.astype(x.dtype), dA.T.astype(A.dtype),
                dB.astype(B.dtype), _int_zeros(ids),
                np.zeros(scalings.shape, jax.dtypes.float0),
                _int_zeros(solo_pos))

    f.defvjp(_fwd, _bwd)
    return f


def _tile_jobs_static(rows: Sequence[int], seq_len: int, block_t: int,
                      order: Optional[Sequence[int]] = None
                      ) -> Optional[Tuple[int, ...]]:
    """Static token-tile -> job map of a job-proportional batch (rows
    per job, segments in *order*).  None when any segment is not whole
    token tiles — the caller then falls back to the masked path."""
    order = list(order) if order is not None else list(range(len(rows)))
    out = []
    for j in order:
        toks = rows[j] * seq_len
        if toks % block_t:
            return None
        out.extend([j] * (toks // block_t))
    return tuple(out)


def fused_lora_ragged(x: jax.Array, A: jax.Array, B: jax.Array,
                      ids: jax.Array, scalings: jax.Array, layout,
                      *, impl: str = "xla", block_t: int = 128,
                      equal_segments: bool = False,
                      slice_rows: Optional[Tuple[int, ...]] = None,
                      seq_len: int = 1,
                      nano_order: Optional[Tuple[int, ...]] = None,
                      solo_rows: Tuple[int, ...] = (),
                      axis_name=None, solo_pos=None,
                      total_tokens: int = 0,
                      ranks: Optional[jax.Array] = None) -> jax.Array:
    """Fused heterogeneous multi-LoRA over PACKED RAGGED adapters.

    x (T, d_in), A (d_in, R), B (R, d_out) with R = Σ_k r_pad_k
    (``layout``: core/lora.RankLayout).  ``slice_rows`` is the static
    per-job row count of this batch when it is job-proportional (the
    full fused batch, or a job-aware nano slice) — required for the
    static-tile pallas metadata; ``nano_order`` the segment order
    inside a nano slice.  ``solo_rows`` is the full (local) batch's
    per-job rows — the solo wgrad geometry of the sharded path.  The
    sharded arguments mirror ``fused_lora``.
    """
    K = layout.num_jobs
    if impl in ("ref", "loop"):
        from repro.core.lora import unpack_dense
        Af, Bf = unpack_dense(A, B, layout)
        rk = ranks if ranks is not None \
            else jnp.asarray(layout.ranks, jnp.int32)
        fn = (ref_impl.fused_lora_loop if impl == "loop"
              else ref_impl.fused_lora_ref)
        return fn(x, Af.astype(x.dtype), Bf.astype(x.dtype), ids, rk,
                  scalings)
    if impl == "xla":
        if axis_name is not None:
            assert solo_pos is not None and total_tokens > 0
            return _make_ragged_xla_sharded_fn(
                layout, bool(equal_segments), axis_name,
                int(total_tokens))(x, A, B, ids, scalings, solo_pos)
        return _make_ragged_xla_fn(layout, bool(equal_segments))(
            x, A, B, ids, scalings)
    if impl == "pallas":
        T = x.shape[0]
        tile_jobs = None
        if slice_rows is not None and T % block_t == 0:
            is_slice = tuple(slice_rows) != tuple(solo_rows)
            tile_jobs = _tile_jobs_static(
                slice_rows, seq_len, block_t,
                order=nano_order if is_slice else None)
        if tile_jobs is None:
            # no static tile map (e.g. the unsharded contiguous nano
            # split): densify and take the masked pallas path — the
            # traced tile_map handles any tile-aligned layout
            assert axis_name is None, \
                "sharded ragged pallas needs a job-proportional batch"
            from repro.core.lora import unpack_dense
            Af, Bf = unpack_dense(A, B, layout)
            rk = ranks if ranks is not None \
                else jnp.asarray(layout.ranks, jnp.int32)
            return _fused_lora_pallas(x, Af.astype(x.dtype),
                                      Bf.astype(x.dtype), ids, rk,
                                      scalings, block_t)
        meta = RaggedMeta.build(tile_jobs, layout)
        if axis_name is not None:
            assert solo_pos is not None and total_tokens > 0
            solo_tiles = _tile_jobs_static(solo_rows, seq_len, block_t)
            assert solo_tiles is not None, (solo_rows, seq_len, block_t)
            meta_solo = RaggedMeta.build(solo_tiles, layout)
            return _make_ragged_pallas_sharded_fn(
                meta, meta_solo, int(block_t), axis_name,
                int(total_tokens))(x, A, B, ids, scalings, solo_pos)
        return _make_ragged_pallas_fn(meta, int(block_t))(
            x, A, B, ids, scalings)
    raise ValueError(f"unknown fused_lora_ragged impl {impl!r}")


# ------------------------------------------------------------- dispatch
def fused_lora(x: jax.Array, A: jax.Array, B: jax.Array, ids: jax.Array,
               ranks: jax.Array, scalings: jax.Array,
               impl: str = "ref", block_t: int = 128,
               capacity=None, equal_segments: bool = False,
               axis_name=None, solo_pos=None,
               total_tokens: int = 0, full_batch: bool = True) -> jax.Array:
    """Fused heterogeneous multi-LoRA: y_t = s_a ((x_t A_a) B_a), a=ids[t].

    x (T, d_in) -> (T, d_out). See module docstring for impl semantics.

    ``axis_name`` selects the shard-local variant: *x*/*ids* are this
    device's token shard inside a ``shard_map`` over that mesh axis;
    ``solo_pos`` holds each local token's position in the solo job-major
    layout and ``total_tokens`` the full fused-batch token count — the
    VJP wgrads reassemble the full tensors in solo order and stay
    bit-exact w.r.t. single-device execution.  ``full_batch=False``
    (nano-slices) marks the reassembled layout as not segment-sorted.
    Only the custom-VJP impls ("xla", "pallas") support it — the
    autodiffed "ref"/"loop" oracles have no hand-written backward to
    localize; use the partial-gradient+psum strategy (core/ssm.py
    grad_sync="psum") for those.
    """
    if axis_name is not None:
        assert solo_pos is not None and total_tokens > 0
        if impl == "xla":
            return _make_xla_sharded_fn(bool(equal_segments), axis_name,
                                        int(total_tokens))(
                x, A, B, ids, ranks, scalings, solo_pos)
        if impl == "pallas":
            return _make_pallas_sharded_fn(int(block_t), axis_name,
                                           int(total_tokens),
                                           bool(full_batch))(
                x, A, B, ids, ranks, scalings, solo_pos)
        raise ValueError(
            f"impl {impl!r} has no shard-local VJP; use impl='xla'/'pallas' "
            "or grad_sync='psum'")
    if impl == "pallas":
        return _fused_lora_pallas(x, A, B, ids, ranks, scalings, block_t)
    if impl == "xla":
        return fused_lora_xla(x, A, B, ids, ranks, scalings,
                              capacity=capacity,
                              equal_segments=equal_segments)
    if impl == "loop":
        return ref_impl.fused_lora_loop(x, A, B, ids, ranks, scalings)
    if impl == "ref":
        return ref_impl.fused_lora_ref(x, A, B, ids, ranks, scalings)
    raise ValueError(f"unknown fused_lora impl {impl!r}")


# ---------------------------------------------------------- dequant mm
@jax.checkpoint
def _dequant_xla(x, q, s):
    """XLA fallback: dequant folded into the dot, under ``jax.checkpoint``
    so any dequantized intermediate is RECOMPUTED in the backward pass
    instead of living in HBM across it (the backbone takes no gradient;
    only dx flows, and autodiff of this expression is exactly
    dy*scale @ q.T with q re-cast on the fly)."""
    y = jnp.dot(x, q.astype(x.dtype), preferred_element_type=jnp.float32)
    return (y * s.astype(jnp.float32)).astype(x.dtype)


@functools.lru_cache(maxsize=32)
def _make_dequant_pallas_fn(block_t: int, block_o: int):
    """Custom-VJP closure over the Pallas dequant-matmul kernel.

    The base weight is FROZEN: the backward emits float0 for the int8
    weights, zeros for the scales, and computes dx with a second fused
    launch — dx = (dy * scale) @ q.T, i.e. the same kernel against the
    transposed int8 slab with unit scales (the row scaling moved onto
    the cotangent, still never materializing a dequantized copy)."""

    @jax.custom_vjp
    def f(x, q, s):
        return pk.dequant_matmul_pallas(x, q, s, block_t=block_t,
                                        block_o=block_o)

    def fwd(x, q, s):
        return f(x, q, s), (q, s)

    def bwd(res, dy):
        q, s = res
        dys = (dy.astype(jnp.float32)
               * s.astype(jnp.float32)[None, :]).astype(dy.dtype)
        ones = jnp.ones((q.shape[0],), jnp.float32)
        dx = pk.dequant_matmul_pallas(dys, q.T, ones, block_t=block_t,
                                      block_o=block_o)
        return dx, _int_zeros(q), jnp.zeros_like(s)

    f.defvjp(fwd, bwd)
    return f


def dequant_matmul(x: jax.Array, q: jax.Array, scale: jax.Array,
                   impl: str = "xla", block_t: int = 128,
                   block_o: int = 512) -> jax.Array:
    """y = (x @ q) * scale for an int8 per-output-channel-quantized base
    projection (models/quant.QuantTensor storage).  x: (T, d_in); q:
    (d_in, d_out) int8; scale: (d_out,) f32 -> (T, d_out) in x.dtype.

    Both impls evaluate the SAME expression — a full-contraction dot on
    x.dtype operands with f32 accumulation, scaled per output channel —
    so they agree exactly; "pallas" tiles it in-register per (block_t,
    block_o) block, "xla" leans on ``jax.checkpoint`` to keep the
    dequant out of HBM across the backward."""
    if impl == "pallas":
        return _make_dequant_pallas_fn(int(block_t), int(block_o))(
            x, q, scale)
    if impl == "xla":
        return _dequant_xla(x, q, scale)
    raise ValueError(f"unknown dequant_matmul impl {impl!r}")
