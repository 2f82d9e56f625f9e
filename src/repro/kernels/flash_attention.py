"""Pallas TPU flash-attention kernel (forward) — the compute hot-spot of
every full-attention arch in the zoo (§Roofline: after iterations 0-5 all
train pairs are memory-bound, and the residual HBM term is dominated by
attention chunk traffic that a VMEM-resident kernel removes).

TPU adaptation (DESIGN.md §3 discipline):
  * grid = (batch*heads, q blocks); the kv loop is the innermost grid
    dim so q/accumulator tiles stay resident in VMEM across kv steps.
  * online softmax state (m, l, acc) lives in VMEM scratch; the (Sq x Skv)
    score matrix never touches HBM — on a real TPU this deletes the
    dominant memory-roofline term for train_4k/prefill_32k.
  * block shapes are MXU-aligned knobs (block_q x block_k, multiples of
    the 128 lane width at production sizes; tests use smaller tiles in
    interpret mode).
  * causal masking per tile via iota comparison; fully-masked tiles are
    skipped with pl.when on the block index (the TPU analogue of a GPU
    early-exit).

Validated against ref.py / models.attention in interpret mode
(tests/test_flash_kernel.py).  The training backward uses the XLA flash
custom VJP in models/attention.py; a Pallas backward is the natural next
kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_lora import pallas_call

NEG_BIG = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *,
                      causal: bool, scale: float, block_q: int,
                      block_k: int, n_kv: int):
    """One (q-block, kv-block) grid step for one (batch, head) pair."""
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # skip tiles strictly above the diagonal
        run = kv_i * block_k <= (q_i + 1) * block_q - 1

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0]                                   # (block_q, hd)
        k = k_ref[0]                                   # (block_k, hd)
        v = v_ref[0]                                   # (block_k, vd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = kv_i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_BIG)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kv_i == n_kv - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...]
                    / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128) -> jax.Array:
    """q: (BH, Sq, hd); k/v: (BH, Skv, hd) — flat (batch*heads) leading dim
    (GQA callers repeat kv heads; see models/attention._rep_heads).

    Returns (BH, Sq, vd).  Scores never materialize in HBM.
    """
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    vd = v.shape[-1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv)
    n_q, n_kv = Sq // block_q, Skv // block_k
    scale = hd ** -0.5

    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, n_kv=n_kv)

    return pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, vd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, vd), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, vd), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((BH, Sq, vd), q.dtype),
    )(q, k, v)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True) -> jax.Array:
    """Pure-jnp oracle: naive softmax attention over the flat-head layout."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Sq, Skv = s.shape[-2:]
        mask = jnp.arange(Skv)[None, :] <= jnp.arange(Sq)[:, None]
        s = jnp.where(mask[None], s, NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bst,btd->bsd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
