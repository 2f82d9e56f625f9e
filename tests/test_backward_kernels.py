"""Backward-pass coverage for the grouped kernels (DESIGN.md §7).

The pallas custom VJP must produce the same gradients as autodiff of the
gather oracle with NO one-hot densification over K: dx via grouped-mm,
dA/dB via the segment-aware grouped-wgrad kernels.  The xla path's
custom VJP (segment-dense wgrads) is held to the same contract on both
its equal-segment and fallback layouts.  Plus the donation-safety
contract of the chunked device-resident loop: chunked ``run()`` is
bit-identical to step-at-a-time ``run()``.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import ml_dtypes

from repro.core.jobs import LoRAJobSpec
from repro.kernels import ops, ref
from repro.kernels.fused_lora import grouped_wgrad_pallas


def make_case(rng, T, K, d_in, d_out, r_pad, dtype, block_t):
    x = rng.standard_normal((T, d_in)).astype(dtype)
    A = (rng.standard_normal((K, d_in, r_pad)) * 0.3).astype(dtype)
    # B=0 is the LoRA init; offset so dB (and y, hence dx) are informative
    B = ((rng.standard_normal((K, r_pad, d_out)) * 0.3) + 0.1).astype(dtype)
    ranks = rng.integers(1, r_pad + 1, size=K).astype(np.int32)
    scal = (16.0 / ranks).astype(np.float32)
    tiles = rng.integers(0, K, size=T // block_t)
    ids = np.sort(np.repeat(tiles, block_t)).astype(np.int32)
    return (jnp.asarray(x), jnp.asarray(A), jnp.asarray(B),
            jnp.asarray(ids), jnp.asarray(ranks), jnp.asarray(scal))


def grad_pair(impl, x, A, B, ids, ranks, scal, block_t, **kw):
    def f_impl(x, A, B):
        y = ops.fused_lora(x, A, B, ids, ranks, scal, impl=impl,
                           block_t=block_t, **kw)
        return (y.astype(jnp.float32) ** 2).sum()

    def f_ref(x, A, B):
        y = ref.fused_lora_ref(x, A, B, ids, ranks, scal)
        return (y.astype(jnp.float32) ** 2).sum()

    got = jax.grad(f_impl, argnums=(0, 1, 2))(x, A, B)
    want = jax.grad(f_ref, argnums=(0, 1, 2))(x, A, B)
    return got, want


def assert_grads_close(got, want, dtype):
    # bf16 grads at magnitude ~1e3 carry ~0.5% rounding; normalize by the
    # gradient scale so the bound is relative to the tensor, not per-elem
    tol = 2e-2 if dtype == ml_dtypes.bfloat16 else 1e-5
    for name, g, w in zip("xAB", got, want):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        scale = max(float(np.abs(w).max()), 1e-6)
        np.testing.assert_allclose(g / scale, w / scale, rtol=0, atol=tol,
                                   err_msg=f"d{name}")


SWEEP = [
    # T, K, d_in, d_out, r_pad, dtype, block_t
    (64, 2, 32, 48, 8, np.float32, 8),
    (128, 4, 64, 64, 16, np.float32, 16),
    (128, 3, 48, 96, 8, ml_dtypes.bfloat16, 8),
    # non-power-of-two d_out: the _fit_block regression shape
    (64, 2, 32, 640, 8, np.float32, 8),
    # K > tiles so some adapters own zero tokens (empty-segment wgrads)
    (64, 6, 32, 64, 8, np.float32, 8),
]


@pytest.mark.parametrize("T,K,d_in,d_out,r_pad,dtype,block_t", SWEEP)
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_backward_matches_ref_grads(impl, T, K, d_in, d_out, r_pad, dtype,
                                    block_t):
    rng = np.random.default_rng(0)
    x, A, B, ids, ranks, scal = make_case(rng, T, K, d_in, d_out, r_pad,
                                          dtype, block_t)
    got, want = grad_pair(impl, x, A, B, ids, ranks, scal, block_t)
    assert_grads_close(got, want, dtype)


def test_xla_equal_segments_backward():
    """The production layout: every adapter contributes the same padded
    row count — wgrads go through the segment-dense batched einsums."""
    T, K, d_in, d_out, r_pad, bt = 64, 4, 32, 40, 8, 8
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((T, d_in)).astype(np.float32))
    A = jnp.asarray((rng.standard_normal((K, d_in, r_pad)) * 0.3)
                    .astype(np.float32))
    B = jnp.asarray(((rng.standard_normal((K, r_pad, d_out)) * 0.3) + 0.1)
                    .astype(np.float32))
    ranks = jnp.asarray([3, 8, 5, 1], jnp.int32)
    scal = jnp.asarray(16.0 / np.asarray(ranks), jnp.float32)
    ids = jnp.asarray(np.repeat(np.arange(K), T // K).astype(np.int32))
    got, want = grad_pair("xla", x, A, B, ids, ranks, scal, bt,
                          equal_segments=True)
    assert_grads_close(got, want, np.float32)


def test_grouped_wgrad_kernel_matches_ref():
    """The wgrad kernel in isolation, incl. an adapter with zero tiles
    (its never-visited output block must come back exactly zero)."""
    T, K, d_in, d_out, bt = 64, 4, 24, 40, 8
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((T, d_in)).astype(np.float32))
    g = jnp.asarray(rng.standard_normal((T, d_out)).astype(np.float32))
    tiles = np.sort(rng.choice([0, 1, 3], size=T // bt)).astype(np.int32)
    ids = np.repeat(tiles, bt).astype(np.int32)
    got = grouped_wgrad_pallas(x, g, jnp.asarray(tiles), K, block_t=bt)
    want = ref.grouped_wgrad_ref(x, g, jnp.asarray(ids), K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[2].any()          # adapter 2 owns no tiles


def test_scaling_gradient_is_stopped():
    """Scalings are alpha/r constants (never trained): the custom VJPs
    return a float0 cotangent, i.e. no d(scaling) kernel launch exists."""
    rng = np.random.default_rng(3)
    x, A, B, ids, ranks, scal = make_case(rng, 32, 2, 16, 16, 8,
                                          np.float32, 8)
    for impl in ("pallas", "xla"):
        g = jax.grad(lambda s: (ops.fused_lora(
            x, A, B, ids, ranks, s, impl=impl, block_t=8) ** 2).sum())(scal)
        assert jax.dtypes.result_type(g) == jax.dtypes.float0


def test_chunked_run_bit_identical_and_donation_safe(tiny_cfg, two_jobs):
    """Chunked device-resident run() (scan + donated adapters/opt state)
    must be bit-identical to the step-at-a-time loop — donation must not
    corrupt state that the runtime still reads (params, staged batches),
    and the scan body is the exact single train step."""
    from repro.elastic.runtime import GroupRuntime

    def trajectory(chunk_size):
        rt = GroupRuntime.from_specs(tiny_cfg, two_jobs,
                                     jax.random.PRNGKey(0), lr=1e-3,
                                     impl="ref", block_t=8, remat=False,
                                     seed=0, chunk_size=chunk_size)
        rep = rt.run(7)          # 7 % chunk != 0: exercises a partial chunk
        return rep, rt

    rep1, rt1 = trajectory(1)
    rep3, rt3 = trajectory(3)
    assert rep1.steps == rep3.steps == 7
    assert len(rep3.losses) == len(rep3.step_times) == 7
    assert np.array_equal(np.asarray(rep1.losses), np.asarray(rep3.losses))
    for a, b in zip(jax.tree.leaves(rt1.adapters),
                    jax.tree.leaves(rt3.adapters)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(rt1.opt_state),
                    jax.tree.leaves(rt3.opt_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # per-job bookkeeping advanced identically
    assert rt1.steps_done == rt3.steps_done
    # tail steps reuse the (n, 1) executable — compile keys stay capped
    # at two chunk lengths per n instead of one per distinct remainder
    assert set(rt3._step_cache) == {(1, 3), (1, 1)}


def test_donation_does_not_consume_caller_state(tiny_cfg, two_jobs):
    """run() donates adapter/opt buffers to the chunked step; the runtime
    must own a copy so caller-held restored/pre-built arrays survive."""
    from repro.core.ssm import SharedSuperModel
    from repro.elastic.runtime import GroupRuntime

    probe = SharedSuperModel(tiny_cfg, two_jobs, impl="ref", block_t=8)
    params, adapters = probe.init(jax.random.PRNGKey(0))
    before = jax.tree.map(lambda a: np.asarray(a).copy(), adapters)
    rt = GroupRuntime.from_specs(tiny_cfg, two_jobs, jax.random.PRNGKey(0),
                                 params=params, adapters=adapters,
                                 impl="ref", block_t=8, remat=False,
                                 chunk_size=2)
    rt.run(2)
    # the caller's arrays are still alive and unchanged post-donation
    for got, want in zip(jax.tree.leaves(adapters), jax.tree.leaves(before)):
        assert np.array_equal(np.asarray(got), want)


def test_pallas_mode_follows_backend():
    """Pallas is interpreted where the program is lowered for CPU and
    compiled (a Mosaic ``tpu_custom_call``) where it is lowered for TPU
    — decided at lowering, with no switch to set.  Cross-platform
    export lowers both from this CPU host without a chip."""
    from jax import export
    from repro.kernels.fused_lora import dequant_matmul_pallas
    x = jnp.ones((128, 256), jnp.bfloat16)
    q = jnp.ones((256, 512), jnp.int8)
    s = jnp.ones((512,), jnp.float32)
    fn = jax.jit(dequant_matmul_pallas)
    mods = {p: export.export(fn, platforms=[p])(x, q, s).mlir_module()
            for p in ("cpu", "tpu")}
    assert "tpu_custom_call" not in mods["cpu"]
    assert "tpu_custom_call" in mods["tpu"]
    # and the interpreted CPU program computes the kernel's result
    want = (np.asarray(x, np.float32) @ np.asarray(q, np.float32)
            * np.asarray(s))
    np.testing.assert_array_equal(np.asarray(fn(x, q, s), np.float32),
                                  want)


def test_valid_nano_counts_divisor_enumeration():
    """O(√rows) enumeration returns exactly the sorted divisors."""
    from repro.core.ssm import valid_nano_counts
    for rows in (1, 2, 12, 36, 97, 360, 3600):
        want = [n for n in range(1, rows + 1) if rows % n == 0]
        assert valid_nano_counts(rows) == want, rows
    assert valid_nano_counts(360, max_n=16) == [1, 2, 3, 4, 5, 6, 8, 9,
                                                10, 12, 15]
