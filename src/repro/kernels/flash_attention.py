"""Pallas TPU flash attention, forward and backward — the attention core
of every full-attention arch in the zoo for static (training / prefill)
geometry.  The (Sq x Skv) score tiles live in VMEM only; HBM sees q, k,
v, the output and one f32 log-sum-exp per query row and head.

Layout (no transposes, no K/V repeat):
  * q / out / dout / dq are viewed as ``(B, S, H*hd)`` and k / v / dk /
    dv as ``(B, S, KV*hd)`` — free reshapes of the model's
    ``(B, S, heads, hd)``.  Query heads ``kv*G .. kv*G+G-1`` share KV
    head ``kv`` (G = H // KV), so one ``(bq, G*hd)`` block at column
    block ``kv`` holds a whole GQA group, and the K/V block at column
    block ``kv`` is read once for all G heads: GQA by index map.
  * the log-sum-exp residual and the backward's ``D = rowsum(dout*out)``
    are ``(B, KV, G, S)`` f32, a free reshape of ``(B, H, S)``.

Kernels (all three grids are ``(B, KV, blocks)``; no output block is
revisited, so every grid axis is parallel):
  * forward   — one q block per step, K/V of the whole row resident; an
    online softmax over the kv chunks up to the diagonal with (m, l,
    acc) in VMEM scratch.  Writes out and lse.
  * dq        — one q block per step; recomputes ``p = exp(s - lse)``
    per kv chunk up to the diagonal and accumulates ``ds @ k``.
  * dk / dv   — one kv block per step, q and dout of the whole row
    resident; walks the q chunks from the diagonal down in the
    transposed orientation (``s^T = k q^T``, so lse and D are lane
    rows) and sums the G query heads of the group in VMEM.
  The flash-2 split: nothing of size S x S is ever written.

Causal skipping: kv chunks strictly above the diagonal are never
visited (the chunk loops stop at it); only chunks that straddle it pay
for the mask.

Every launch goes through ``fused_lora.pallas_call`` (interpreted where
the program is compiled for CPU).  ``models/attention.py`` picks this
path at lowering on TPU for static geometry and keeps its XLA custom
VJP everywhere else.  Validated in interpret mode against that VJP and
a float32 oracle (tests/test_flash_kernel.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_lora import pallas_call

NEG_BIG = -1e30
LANES = 128
BLOCK = 256                 # q and kv block (and chunk) length
RESIDENT_BYTES = 4 << 20    # one whole-row slab (S x G*hd) the dk/dv step holds
# the dk/dv step double-buffers two such slabs (q, dout): up to 16 MiB,
# the default scoped-VMEM limit, before its tiles and accumulators
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=48 << 20)
_NT = (((1,), (1,)), ((), ()))          # a @ b.T


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


# Per-row statistics (m, l, lse, D) are kept lane-replicated, (rows,
# 128): widening one to a tile's width repeats whole vregs, and a lane
# row of them is one aligned transpose away.
def _widen(x, n):
    """A lane-replicated (rows, 128) statistic at n lanes."""
    return jnp.tile(x, (1, n // LANES)) if n >= LANES else x[:, :n]


def _row(x):
    """Lane-replicated (n, 128) -> (1, n) lane row."""
    return x.T[:1]


def _replicate(row):
    """(1, n) lane row -> lane-replicated (n, 128)."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _keep(r0, c0, shape, rows_are_q: bool):
    """Causal keep-mask of one tile: key position <= query position."""
    r = r0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = c0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return c <= r if rows_are_q else r <= c


def _walk(lo, free, hi, tile):
    """Visit chunks [lo, free) unmasked, then [free, hi) masked."""
    jax.lax.fori_loop(lo, free, lambda j, c: (tile(j, False), c)[1], 0)
    jax.lax.fori_loop(free, hi, lambda j, c: (tile(j, True), c)[1], 0)


def _block(n: int) -> int:
    """The block length for a sequence of n: BLOCK, else one lane tile,
    else the whole (short) sequence."""
    return next(b for b in (BLOCK, LANES, n) if n % b == 0)


def _blocks(Sq, Skv, block_q, block_k):
    bq = min(block_q or _block(Sq), Sq)
    bk = min(block_k or _block(Skv), Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, Skv, bq, bk)
    return bq, bk


def _pad_lanes(x):
    """Zero-pad the head dim to whole lane tiles: a block's last dim must
    be a multiple of 128 (zero lanes add nothing to q.k, and give zero
    output and gradient lanes, which are cut off)."""
    w = -(-x.shape[-1] // LANES) * LANES
    if w == x.shape[-1]:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, w - x.shape[-1]),))


# ------------------------------------------------------------- forward
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *, causal: bool, scale: float,
                      G: int, hd: int, vd: int, block_q: int, block_k: int,
                      n_k: int):
    """One (batch, KV head, q block) step: all G heads of the group."""
    q0 = pl.program_id(2) * block_q
    m_scr[...] = jnp.full_like(m_scr, NEG_BIG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(j, masked):
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k, v = k_ref[rows, :], v_ref[rows, :]
        if masked:
            keep = _keep(q0, j * block_k, (block_q, block_k), True)
        for g in range(G):
            s = _dot_nt(q_ref[:, g * hd:(g + 1) * hd], k) * scale
            if masked:
                s = jnp.where(keep, s, NEG_BIG)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - _widen(m_new, block_k))
            corr = jnp.exp(m_prev - m_new)
            l_scr[g] = l_scr[g] * corr + p.sum(axis=-1, keepdims=True)
            m_scr[g] = m_new
            cols = slice(g * vd, (g + 1) * vd)
            acc_scr[:, cols] = acc_scr[:, cols] * _widen(corr, vd) + _dot(
                p.astype(v.dtype), v)

    if causal:
        _walk(0, (q0 + 1) // block_k, (q0 + block_q - 1) // block_k + 1,
              tile)
    else:
        _walk(0, n_k, n_k, tile)

    for g in range(G):
        l = l_scr[g]
        l = jnp.where(l == 0, 1.0, l)
        cols = slice(g * vd, (g + 1) * vd)
        o_ref[:, cols] = (acc_scr[:, cols] / _widen(l, vd)).astype(
            o_ref.dtype)
        lse_ref[g:g + 1, :] = _row(m_scr[g] + jnp.log(l))


def flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
              block_q: Optional[int] = None, block_k: Optional[int] = None):
    """q: (B, Sq, H, hd); k: (B, Skv, KV, hd); v: (B, Skv, KV, vd).
    Returns (out (B, Sq, H, vd), lse (B, H, Sq) f32).  Blocks default
    to ``_block`` of each length."""
    B, Sq, H, hd = q.shape
    Skv, KV, vd0 = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    assert not causal or Sq == Skv, (Sq, Skv)
    bq, bk = _blocks(Sq, Skv, block_q, block_k)
    scale = hd ** -0.5
    q, k, v = _pad_lanes(q), _pad_lanes(k), _pad_lanes(v)
    hd, vd = q.shape[-1], v.shape[-1]
    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, scale=scale, G=G, hd=hd,
        vd=vd, block_q=bq, block_k=bk, n_k=Skv // bk)
    out, lse = pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(B, KV, Sq // bq),
        in_specs=[
            pl.BlockSpec((None, bq, G * hd), lambda b, h, i: (b, i, h)),
            pl.BlockSpec((None, Skv, hd), lambda b, h, i: (b, 0, h)),
            pl.BlockSpec((None, Skv, vd), lambda b, h, i: (b, 0, h)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, G * vd), lambda b, h, i: (b, i, h)),
            pl.BlockSpec((None, None, G, bq), lambda b, h, i: (b, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, Sq, H * vd), q.dtype),
                   jax.ShapeDtypeStruct((B, KV, G, Sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((G, bq, LANES), jnp.float32),
                        pltpu.VMEM((G, bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, G * vd), jnp.float32)],
        compiler_params=_PARAMS,
    )(q.reshape(B, Sq, H * hd), k.reshape(B, Skv, KV * hd),
      v.reshape(B, Skv, KV * vd))
    return out.reshape(B, Sq, H, vd)[..., :vd0], lse.reshape(B, H, Sq)


# ------------------------------------------------------------ backward
def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
                     lse_scr, d_scr, dq_scr, *, causal: bool, scale: float,
                     G: int, hd: int, vd: int, block_q: int, block_k: int,
                     n_k: int):
    """One (batch, KV head, q block) step: dq of all G heads."""
    q0 = pl.program_id(2) * block_q
    dq_scr[...] = jnp.zeros_like(dq_scr)
    for g in range(G):
        lse_scr[g] = _replicate(lse_ref[g:g + 1, :])
        d_scr[g] = _replicate(d_ref[g:g + 1, :])

    def tile(j, masked):
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k, v = k_ref[rows, :], v_ref[rows, :]
        if masked:
            keep = _keep(q0, j * block_k, (block_q, block_k), True)
        for g in range(G):
            s = _dot_nt(q_ref[:, g * hd:(g + 1) * hd], k) * scale
            if masked:
                s = jnp.where(keep, s, NEG_BIG)
            p = jnp.exp(s - _widen(lse_scr[g], block_k))
            dp = _dot_nt(do_ref[:, g * vd:(g + 1) * vd], v)
            ds = (p * (dp - _widen(d_scr[g], block_k)) * scale).astype(
                k.dtype)
            cols = slice(g * hd, (g + 1) * hd)
            dq_scr[:, cols] = dq_scr[:, cols] + _dot(ds, k)

    if causal:
        _walk(0, (q0 + 1) // block_k, (q0 + block_q - 1) // block_k + 1,
              tile)
    else:
        _walk(0, n_k, n_k, tile)
    dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                      scale: float, G: int, hd: int, vd: int, block_q: int,
                      block_k: int, n_q: int):
    """One (batch, KV head, kv block) step: dk and dv summed over the G
    query heads of the group and every q chunk at or below the
    diagonal.  Scores are taken transposed, ``(block_k, block_q)``."""
    k0 = pl.program_id(2) * block_k
    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)
    k, v = k_ref[...], v_ref[...]

    def tile(i, masked):
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        if masked:
            keep = _keep(k0, i * block_q, (block_k, block_q), False)
        for g in range(G):
            q = q_ref[rows, g * hd:(g + 1) * hd]
            do = do_ref[rows, g * vd:(g + 1) * vd]
            row = g * n_q + i                       # lse/D are (G*n_q, bq)
            lse = lse_ref[pl.ds(row, 1), :]
            dd = d_ref[pl.ds(row, 1), :]
            st = _dot_nt(k, q) * scale
            if masked:
                st = jnp.where(keep, st, NEG_BIG)
            pt = jnp.exp(st - lse)
            dv_scr[...] += _dot(pt.astype(do.dtype), do)
            dpt = _dot_nt(v, do)
            dst = (pt * (dpt - dd) * scale).astype(q.dtype)
            dk_scr[...] += _dot(dst, q)

    if causal:
        # q chunks that straddle the diagonal need the mask; those
        # wholly below it do not; those wholly above are never visited
        lo = k0 // block_q
        free = (k0 + block_k - 1 + block_q - 1) // block_q
        jax.lax.fori_loop(lo, free, lambda i, c: (tile(i, True), c)[1], 0)
        jax.lax.fori_loop(free, n_q, lambda i, c: (tile(i, False), c)[1], 0)
    else:
        _walk(0, n_q, n_q, tile)
    dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
    dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd(q, k, v, out, lse, dout, *, causal: bool,
              block_q: Optional[int] = None, block_k: Optional[int] = None):
    """Gradients (dq, dk, dv) of ``flash_fwd``'s output, from its
    residuals (q, k, v, out, lse) and the output cotangent."""
    B, Sq, H, hd0 = q.shape
    Skv, KV, vd0 = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    bq, bk = _blocks(Sq, Skv, block_q, block_k)
    n_q = Sq // bq
    dout = dout.astype(q.dtype)
    D = jnp.einsum("bshd,bshd->bhs", dout.astype(jnp.float32),
                   out.astype(jnp.float32))
    scale = hd0 ** -0.5
    q, k, v, dout = (_pad_lanes(t) for t in (q, k, v, dout))
    hd, vd = q.shape[-1], v.shape[-1]
    qf = q.reshape(B, Sq, H * hd)
    kf, vf = k.reshape(B, Skv, KV * hd), v.reshape(B, Skv, KV * vd)
    dof = dout.reshape(B, Sq, H * vd)
    kw = dict(causal=causal, scale=scale, G=G, hd=hd, vd=vd,
              block_q=bq, block_k=bk)

    q_blk = lambda w: pl.BlockSpec((None, bq, G * w),
                                   lambda b, h, i: (b, i, h))
    row_kv = lambda w: pl.BlockSpec((None, Skv, w), lambda b, h, i: (b, 0, h))
    stat_blk = pl.BlockSpec((None, None, G, bq), lambda b, h, i: (b, h, 0, i))
    dq = pallas_call(
        functools.partial(_flash_dq_kernel, n_k=Skv // bk, **kw),
        name="flash_attention_dq",
        grid=(B, KV, n_q),
        in_specs=[q_blk(hd), row_kv(hd), row_kv(vd), q_blk(vd), stat_blk,
                  stat_blk],
        out_specs=q_blk(hd),
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((G, bq, LANES), jnp.float32),
                        pltpu.VMEM((G, bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, G * hd), jnp.float32)],
        compiler_params=_PARAMS,
    )(qf, kf, vf, dof, lse.reshape(B, KV, G, Sq), D.reshape(B, KV, G, Sq))

    row_q = lambda w: pl.BlockSpec((None, Sq, G * w),
                                   lambda b, h, j: (b, 0, h))
    kv_blk = lambda w: pl.BlockSpec((None, bk, w), lambda b, h, j: (b, j, h))
    stat_row = pl.BlockSpec((None, None, G * n_q, bq),
                            lambda b, h, j: (b, h, 0, 0))
    dk, dv = pallas_call(
        functools.partial(_flash_dkv_kernel, n_q=n_q, **kw),
        name="flash_attention_dkv",
        grid=(B, KV, Skv // bk),
        in_specs=[row_q(hd), kv_blk(hd), kv_blk(vd), row_q(vd), stat_row,
                  stat_row],
        out_specs=[kv_blk(hd), kv_blk(vd)],
        out_shape=[jax.ShapeDtypeStruct((B, Skv, KV * hd), k.dtype),
                   jax.ShapeDtypeStruct((B, Skv, KV * vd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, vd), jnp.float32)],
        compiler_params=_PARAMS,
    )(qf, kf, vf, dof, lse.reshape(B, KV, G * n_q, bq),
      D.reshape(B, KV, G * n_q, bq))
    return (dq.reshape(B, Sq, H, hd)[..., :hd0],
            dk.reshape(B, Skv, KV, hd)[..., :hd0],
            dv.reshape(B, Skv, KV, vd)[..., :vd0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=True, block_q=None, block_k=None):
    """Flash attention on the kernels alone (forward and backward)."""
    return flash_fwd(q, k, v, causal=causal, block_q=block_q,
                     block_k=block_k)[0]


def _fa_fwd(q, k, v, causal, block_q, block_k):
    out, lse = flash_fwd(q, k, v, causal=causal, block_q=block_q,
                         block_k=block_k)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, res, dout):
    return flash_bwd(*res, dout, causal=causal, block_q=block_q,
                     block_k=block_k)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def supports(q_shape, k_shape, v_shape, itemsize: int) -> bool:
    """Whether the kernels take this geometry on a TPU: self-attention
    over whole lane tiles of S, GQA groups, equal q/k and v head dims,
    and a whole-row q slab (S x G*hd, lane-padded) that fits the dk/dv
    step's VMEM."""
    B, S, H, hd = q_shape
    KV = k_shape[2]
    width = -(-hd // LANES) * LANES
    return (k_shape[1] == S and v_shape[-1] == hd and H % KV == 0
            and S % LANES == 0
            and S * (H // KV) * width * itemsize <= RESIDENT_BYTES)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True) -> jax.Array:
    """Float32 oracle: naive softmax attention, q (B, Sq, H, hd) over
    k/v (B, Skv, KV, d) with query head h reading KV head h // G."""
    G = q.shape[2] // k.shape[2]
    kr = jnp.repeat(k.astype(jnp.float32), G, axis=2)
    vr = jnp.repeat(v.astype(jnp.float32), G, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), kr) \
        * q.shape[-1] ** -0.5
    if causal:
        Sq, Skv = s.shape[-2:]
        mask = jnp.arange(Skv)[None, :] <= jnp.arange(Sq)[:, None]
        s = jnp.where(mask, s, NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, vr)
