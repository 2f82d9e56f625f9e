"""Pallas flash-attention kernels vs oracles (interpret mode): a
shape/dtype sweep of the forward, a hypothesis property test, the
forward's log-sum-exp and the backward's gradients against the model's
XLA flash VJP and a float32 naive reference, and the dispatch that
picks the kernels only for static geometry lowered for TPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import ml_dtypes

pytest.importorskip("hypothesis")  # optional dep: skip, don't error
from hypothesis import given, settings, strategies as st

import repro.core  # noqa: F401  (models/attention imports via core)
from repro.kernels import flash_attention as fa
from repro.kernels.flash_attention import (flash_attention, flash_fwd,
                                           flash_attention_ref)
from repro.models import attention as attn

SWEEP = [
    # B, Sq, Skv, H, KV, hd, vd, causal, block_q, block_k, dtype
    (2, 64, 64, 1, 1, 16, 16, True, 16, 16, np.float32),
    (4, 128, 128, 1, 1, 32, 32, True, 32, 64, np.float32),
    (2, 64, 64, 1, 1, 16, 16, False, 16, 16, np.float32),
    (1, 32, 32, 1, 1, 64, 32, True, 8, 8, np.float32),  # vd != hd (MLA-like)
    (2, 64, 64, 1, 1, 16, 16, True, 16, 16, ml_dtypes.bfloat16),
    (3, 96, 96, 1, 1, 16, 16, True, 32, 32, np.float32),  # uneven grid
    (1, 64, 64, 6, 1, 16, 16, True, 16, 32, np.float32),  # GQA G=6
    (1, 64, 64, 8, 2, 16, 16, True, 32, 16, np.float32),  # GQA G=4
    (1, 32, 48, 4, 2, 16, 16, False, 16, 16, np.float32),  # Sq != Skv
]


def _arrays(rng, B, Sq, Skv, H, KV, hd, vd, dtype):
    def a(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(dtype))
    return a(B, Sq, H, hd), a(B, Skv, KV, hd), a(B, Skv, KV, vd)


@pytest.mark.parametrize("B,Sq,Skv,H,KV,hd,vd,causal,bq,bk,dtype", SWEEP)
def test_flash_kernel_matches_ref(B, Sq, Skv, H, KV, hd, vd, causal, bq,
                                  bk, dtype):
    rng = np.random.default_rng(0)
    q, k, v = _arrays(rng, B, Sq, Skv, H, KV, hd, vd, dtype)
    got, _ = flash_fwd(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = flash_attention_ref(q, k, v, causal=causal)
    assert got.shape == (B, Sq, H, vd) and got.dtype == q.dtype
    tol = 3e-2 if dtype == ml_dtypes.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_kernel_matches_model_attention():
    """Kernel == the model's chunked/XLA flash fwd on a GQA case: the
    output and the log-sum-exp residual, with no K/V repeat."""
    rng = np.random.default_rng(1)
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q, k, v = _arrays(rng, B, S, S, H, KV, hd, hd, np.float32)
    want, want_lse = attn._chunked_attention_fwd(
        q, k, v, q_offset=0, kv_len=S, causal=True, window=None, chunk=16)
    got, lse = flash_fwd(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=20, deadline=None)
@given(
    n_q=st.integers(1, 4),
    n_k=st.integers(1, 4),
    hd=st.sampled_from([8, 16]),
    causal=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_flash_kernel(n_q, n_k, hd, causal, seed):
    bq = bk = 16
    if causal and n_q != n_k:
        return            # causal requires aligned positions in this API
    rng = np.random.default_rng(seed)
    q, k, v = _arrays(rng, 1, n_q * bq, n_k * bk, 2, 1, hd, hd, np.float32)
    got, _ = flash_fwd(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- backward
def _lse_ref(q, k, causal):
    G = q.shape[2] // k.shape[2]
    kr = jnp.repeat(k.astype(jnp.float32), G, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), kr) \
        * q.shape[-1] ** -0.5
    if causal:
        S = s.shape[-1]
        s = jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], s,
                      fa.NEG_BIG)
    return jax.nn.logsumexp(s, axis=-1)


GRAD_CASES = [
    # B, S, H, KV, block, causal, padded tail, dtype
    (2, 32, 1, 1, 32, True, False, np.float32),    # G=1, one block
    (1, 64, 6, 1, 16, True, False, np.float32),    # G=6, four blocks
    (1, 64, 8, 1, 32, True, True, np.float32),     # G=8, two blocks, padding
    (2, 64, 12, 2, 16, True, True, np.float32),    # G=6 over 2 KV heads
    (1, 32, 8, 1, 16, False, False, np.float32),   # bidirectional
    (1, 64, 6, 1, 32, True, True, ml_dtypes.bfloat16),
]


@pytest.mark.parametrize("B,S,H,KV,blk,causal,pad,dtype", GRAD_CASES)
def test_flash_kernel_grads_match_xla_vjp_and_oracle(B, S, H, KV, blk,
                                                     causal, pad, dtype):
    """Forward, lse and the gradients of a weighted sum through the
    kernels' custom VJP == the XLA flash VJP and the f32 oracle.  With
    ``pad`` each row's tail carries zero weight, as the loss gives the
    padding of a right-padded row."""
    rng = np.random.default_rng(S + H)
    hd = 16
    q, k, v = _arrays(rng, B, S, S, H, KV, hd, hd, dtype)
    w = rng.standard_normal((B, S, H, hd)).astype(np.float32)
    if pad:
        for b in range(B):
            w[b, rng.integers(S // 4, S):] = 0.0
    w = jnp.asarray(w)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w)

    kern = lambda q, k, v: flash_attention(q, k, v, causal, blk, blk)
    xla = attn._make_flash(0, S, causal, None, blk)
    oracle = lambda q, k, v: flash_attention_ref(q, k, v, causal=causal)
    got = jax.grad(loss(kern), (0, 1, 2))(q, k, v)
    tol = 3e-2 if dtype == ml_dtypes.bfloat16 else 2e-4
    for ref in (xla, oracle):
        f32 = ref is oracle
        args = [t.astype(jnp.float32) for t in (q, k, v)] if f32 \
            else (q, k, v)
        want = jax.grad(loss(ref), (0, 1, 2))(*args)
        for g, h in zip(got, want):
            assert g.dtype == q.dtype
            scale = float(jnp.max(jnp.abs(h))) if dtype != np.float32 else 1
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(h, np.float32),
                                       rtol=tol, atol=tol * scale)
    out, lse = flash_fwd(q, k, v, causal=causal, block_q=blk, block_k=blk)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle(q, k, v), np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(_lse_ref(
        q, k, causal)), rtol=tol, atol=tol)


# ----------------------------------------------------------- dispatch
def _launches(platform, B=1, S=256, H=4, KV=2, hd=128, vd=None,
              window=None, traced_offset=False):
    """Flash launches in the lowering, for *platform*, of the gradient of
    ``chunked_attention`` at static (or traced-offset) geometry."""
    vd = vd or hd

    def f(q, k, v, off):
        q_offset = off if traced_offset else 0
        out = attn.chunked_attention(q, k, v, q_offset=q_offset, kv_len=S,
                                     causal=True, window=window)
        return out.astype(jnp.float32).sum()

    sd = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    args = (sd(B, S, H, hd), sd(B, S, KV, hd), sd(B, S, KV, vd),
            jax.ShapeDtypeStruct((), jnp.int32))
    grad = jax.jit(jax.grad(f, (0,)) if traced_offset
                   else jax.grad(f, (0, 1, 2)))
    text = jax.export.export(grad, platforms=[platform])(*args).mlir_module()
    return {n for n in ("flash_attention_fwd", "flash_attention_dq",
                        "flash_attention_dkv") if n in text}


ALL = {"flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv"}


@pytest.mark.parametrize("case,platform,kw,want", [
    ("static", "tpu", {}, ALL),
    ("lane padded head dim", "tpu", dict(hd=64), ALL),
    ("cpu", "cpu", {}, set()),
    ("window", "tpu", dict(window=64), set()),
    ("traced offset", "tpu", dict(traced_offset=True), set()),
    ("vd != hd", "tpu", dict(hd=192, vd=128), set()),
    ("S not in blocks", "tpu", dict(S=200), set()),
], ids=lambda x: x if isinstance(x, str) else "")
def test_dispatch_takes_kernel_only_for_static_tpu_geometry(case, platform,
                                                            kw, want):
    assert _launches(platform, **kw) == want


def test_dispatch_keeps_xla_under_a_partitioning_mesh(monkeypatch):
    monkeypatch.setattr(attn, "partitioned", lambda: True)
    assert _launches("tpu") == set()


def test_partitioned_reads_the_mesh_axes():
    from jax.sharding import AbstractMesh
    from repro.sharding import partitioned, use_mesh
    assert not partitioned()
    mesh = AbstractMesh((2, 2), ("data", "model"))
    with use_mesh(mesh):
        assert partitioned()                  # GSPMD over both axes
    with use_mesh(mesh, manual=("data",)):
        assert partitioned()                  # "model" is still GSPMD
    with use_mesh(mesh, manual=("data", "model")):
        assert not partitioned()              # full-manual shard_map
    with use_mesh(AbstractMesh((4, 1), ("data", "model")),
                  manual=("data",)):
        assert not partitioned()              # the auto axis is 1 wide
