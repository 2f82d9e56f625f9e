"""Heterogeneous-rank grad parity for the rank-bucketed ragged kernels
(DESIGN.md §10).

The ragged family (packed per-adapter-padded storage, true-rank tile
work) must produce the same forward values and the same dx/dA/dB as the
masked max-rank reference on every layout it claims: K ∈ {1, 4, 8},
mixed ranks including rank-1 and a rank >> the rest, empty adapters
(zero token tiles), equal and unequal segments, xla and
pallas-interpret.  The sharded grad_sync modes are covered by the
ragged scenario in tests/sharded_worker.py (real-mesh subprocess).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.lora import RankLayout, unpack_dense
from repro.kernels import ops, ref


def make_packed_case(rng, ranks, rows, d_in, d_out, seq, block_t,
                     dtype=np.float32):
    """Packed pair + dense view + job-major tile geometry.

    rows[k] sequences of seq tokens per job (0 = empty adapter); every
    segment tile-aligned (rows*seq % block_t == 0 by construction)."""
    layout = RankLayout(tuple(ranks), multiple=8)
    R = layout.total
    Ap = (rng.standard_normal((d_in, R)) * 0.3).astype(dtype)
    Bp = ((rng.standard_normal((R, d_out)) * 0.3) + 0.1).astype(dtype)
    act = np.asarray(layout.active_cols)
    Ap *= act[None, :].astype(dtype)       # kernel invariant: dead lanes 0
    Bp *= act[:, None].astype(dtype)
    tile_jobs = sum(([k] * (rows[k] * seq // block_t)
                     for k in range(len(ranks))), [])
    ids = np.repeat(tile_jobs, block_t).astype(np.int32)
    T = len(ids)
    x = (rng.standard_normal((T, d_in))).astype(dtype)
    scal = (16.0 / np.asarray(ranks)).astype(np.float32)
    return (layout, jnp.asarray(Ap), jnp.asarray(Bp), jnp.asarray(x),
            jnp.asarray(ids), jnp.asarray(scal), tuple(rows))


CASES = [
    # ranks, rows (0 = empty adapter), equal_segments
    ((4,), (2,), False),
    ((64,), (2,), True),
    ((4, 1, 64, 8), (2, 1, 3, 2), False),
    ((8, 8, 16, 8), (2, 2, 2, 2), True),
    ((4, 1, 64, 8), (2, 1, 3, 0), False),          # empty adapter
    ((4, 4, 4, 4, 4, 4, 4, 64), (1,) * 8, True),   # the bench layout
    ((2, 64, 1, 8, 32, 4, 16, 3), (1, 2, 1, 0, 2, 1, 1, 1), False),
]


def _ref_grads(x, Af, Bf, ids, rk, scal):
    def loss(x, Af, Bf):
        y = ref.fused_lora_ref(x, Af, Bf, ids, rk, scal)
        return (y.astype(jnp.float32) ** 2).sum()
    return (ref.fused_lora_ref(x, Af, Bf, ids, rk, scal),
            jax.grad(loss, argnums=(0, 1, 2))(x, Af, Bf))


@pytest.mark.parametrize("ranks,rows,eq", CASES)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_ragged_matches_masked_reference(impl, ranks, rows, eq):
    """fwd + dx + dA + dB of the ragged path == the gather oracle over
    the densified max-rank view, for every claimed layout."""
    rng = np.random.default_rng(hash((ranks, rows)) % 2**31)
    seq, bt, d_in, d_out = 8, 8, 32, 48
    layout, Ap, Bp, x, ids, scal, rows = make_packed_case(
        rng, ranks, rows, d_in, d_out, seq, bt)
    Af, Bf = unpack_dense(Ap, Bp, layout)
    rk = jnp.asarray(ranks, jnp.int32)
    want_y, want_g = _ref_grads(x, Af, Bf, ids, rk, scal)

    def loss(x, Ap, Bp):
        y = ops.fused_lora_ragged(x, Ap, Bp, ids, scal, layout, impl=impl,
                                  block_t=bt, equal_segments=eq,
                                  slice_rows=rows, seq_len=seq,
                                  solo_rows=rows)
        return (y.astype(jnp.float32) ** 2).sum()

    got_y = ops.fused_lora_ragged(x, Ap, Bp, ids, scal, layout, impl=impl,
                                  block_t=bt, equal_segments=eq,
                                  slice_rows=rows, seq_len=seq,
                                  solo_rows=rows)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    gx, gA, gB = jax.grad(loss, argnums=(0, 1, 2))(x, Ap, Bp)
    gAf, gBf = unpack_dense(gA, gB, layout, r_pad=Af.shape[-1])
    # normalize by the gradient scale (as test_backward_kernels does):
    # the bound is relative to the tensor, not per element
    for name, g, w in (("dx", gx, want_g[0]), ("dA", gAf, want_g[1]),
                       ("dB", gBf, want_g[2])):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        scale = max(float(np.abs(w).max()), 1e-6)
        np.testing.assert_allclose(g / scale, w / scale, rtol=0,
                                   atol=1e-5, err_msg=name)


def test_ragged_pallas_kernels_in_isolation():
    """The four ragged pallas launches against their dense oracles —
    incl. an empty adapter whose never-visited wgrad rows must come
    back exactly zero.  The packed intermediates come back rank-major,
    (R, T)."""
    from repro.kernels import ragged as rg
    rng = np.random.default_rng(5)
    seq, bt = 8, 8
    layout, Ap, Bp, x, ids, scal, rows = make_packed_case(
        rng, (4, 1, 64, 8), (2, 1, 3, 0), 32, 40, seq, bt)
    tile_jobs = np.asarray(ids).reshape(-1, bt)[:, 0]
    meta = rg.RaggedMeta.build(tile_jobs, layout)
    Af, Bf = unpack_dense(Ap, Bp, layout)
    rk = jnp.asarray((4, 1, 64, 8), jnp.int32)
    ones = jnp.ones((4,), jnp.float32)

    # fwd (unscaled)
    got = rg.ragged_lora_fwd(x, Ap, Bp, meta, block_t=bt)
    want = ref.fused_lora_ref(x, Af, Bf, ids, rk, ones)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    # xa / dxa packed intermediates (active segments only)
    xat = rg.ragged_xa(x, Ap, meta, block_t=bt)
    xa = np.asarray(xat).T
    dy = jnp.asarray(rng.standard_normal(got.shape).astype(np.float32))
    dxa = np.asarray(rg.ragged_dxa(dy, Bp, meta, block_t=bt)).T
    for k in range(4):
        off, rp = layout.slice_of(k)
        rows_k = np.asarray(ids) == k
        if not rows_k.any():
            continue
        want_xa = ref.rank_mask(
            np.asarray(x)[rows_k] @ np.asarray(Af)[k][:, :rp],
            jnp.zeros(int(rows_k.sum()), jnp.int32),
            jnp.asarray([int(rk[k])]))
        np.testing.assert_allclose(xa[rows_k, off:off + rp],
                                   np.asarray(want_xa), rtol=1e-5,
                                   atol=1e-5)
        want_dxa = ref.rank_mask(
            np.asarray(dy)[rows_k] @ np.asarray(Bf)[k][:rp, :].T,
            jnp.zeros(int(rows_k.sum()), jnp.int32),
            jnp.asarray([int(rk[k])]))
        np.testing.assert_allclose(dxa[rows_k, off:off + rp],
                                   np.asarray(want_dxa), rtol=1e-4,
                                   atol=1e-4)

    # ragged wgrad: dB = Σ_seg xa^T dy, empty adapter rows exactly zero
    dB = np.asarray(rg.ragged_wgrad(xat, dy, meta, block_t=bt))
    off3, rp3 = layout.slice_of(3)
    assert not dB[off3:off3 + rp3].any()       # job 3 owns no tokens
    for k in range(3):
        off, rp = layout.slice_of(k)
        rows_k = np.asarray(ids) == k
        want_dB = xa[rows_k, off:off + rp].T @ np.asarray(dy)[rows_k]
        np.testing.assert_allclose(dB[off:off + rp], want_dB,
                                   rtol=1e-4, atol=1e-4)


def test_ragged_without_static_rows_falls_back():
    """No job-proportional static geometry (slice_rows=None — e.g. the
    unsharded contiguous nano split): xla keeps the exact bucketed
    one-hot fallback, pallas densifies to the masked path — values
    unchanged either way."""
    rng = np.random.default_rng(9)
    seq, bt = 8, 8
    layout, Ap, Bp, x, ids, scal, rows = make_packed_case(
        rng, (4, 64), (2, 2), 32, 48, seq, bt)
    Af, Bf = unpack_dense(Ap, Bp, layout)
    rk = jnp.asarray((4, 64), jnp.int32)
    want = ref.fused_lora_ref(x, Af, Bf, ids, rk, scal)
    for impl in ("xla", "pallas"):
        got = ops.fused_lora_ragged(x, Ap, Bp, ids, scal, layout,
                                    impl=impl, block_t=bt,
                                    slice_rows=None, seq_len=seq)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_nano_slice_order_rank_desc_matches_job_order():
    """The rank-bucketed nano ordering is a pure permutation: applying
    the ragged kernel to a rank-desc-ordered slice produces exactly the
    per-token values of the job-ordered slice, re-ordered."""
    rng = np.random.default_rng(3)
    seq, bt = 8, 8
    ranks, rows = (4, 64, 8), (2, 2, 2)
    layout, Ap, Bp, x, ids, scal, rows = make_packed_case(
        rng, ranks, rows, 32, 48, seq, bt)
    order = tuple(sorted(range(3), key=lambda k: (-ranks[k], k)))
    assert order == (1, 2, 0)
    # permute rows into rank-desc segment order
    perm = np.concatenate([np.where(np.asarray(ids) == k)[0]
                           for k in order])
    xp, idsp = x[jnp.asarray(perm)], ids[jnp.asarray(perm)]
    y_job = ops.fused_lora_ragged(x, Ap, Bp, ids, scal, layout,
                                  impl="pallas", block_t=bt,
                                  slice_rows=rows, seq_len=seq,
                                  solo_rows=(4, 4, 4))  # marks a slice
    y_ord = ops.fused_lora_ragged(xp, Ap, Bp, idsp, scal, layout,
                                  impl="pallas", block_t=bt,
                                  slice_rows=rows, seq_len=seq,
                                  nano_order=order,
                                  solo_rows=(4, 4, 4))
    np.testing.assert_allclose(np.asarray(y_ord),
                               np.asarray(y_job)[perm],
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_uniform_rank_layout_dispatches_to_masked(monkeypatch, impl):
    """Homogeneous padded widths route MultiLoRA.apply to the MASKED
    family (the ragged bookkeeping is pure overhead when there is no
    padding waste to skip) — values still match the gather oracle, and
    mixed TRUE ranks under uniform padding (4 and 8 both pad to 8)
    stay safe via the rank mask.  Heterogeneous layouts must keep the
    ragged family."""
    from repro.core.lora import MultiLoRA
    real_ragged = ops.fused_lora_ragged
    rng = np.random.default_rng(11)
    seq, bt = 8, 8
    ranks = (4, 8, 8)                       # true ranks differ; pads don't
    layout, Ap, Bp, x, ids, scal, rows = make_packed_case(
        rng, ranks, (2, 1, 1), 32, 48, seq, bt)
    assert layout.is_uniform
    rk = jnp.asarray(ranks, jnp.int32)
    Af, Bf = unpack_dense(Ap, Bp, layout)
    want = ref.fused_lora_ref(x, Af, Bf, ids, rk, scal)

    def boom(*a, **k):
        raise AssertionError("uniform layout must not take the ragged path")

    monkeypatch.setattr(ops, "fused_lora_ragged", boom)
    B = x.shape[0] // seq
    ctx = MultiLoRA(adapter_ids=ids.reshape(B, seq)[:, 0], ranks=rk,
                    scalings=scal, impl=impl, block_t=bt, layout=layout,
                    rows_all=rows)
    y = ctx.apply(x.reshape(B, seq, -1), {"A": Ap, "B": Bp})
    np.testing.assert_allclose(np.asarray(y).reshape(x.shape[0], -1),
                               np.asarray(want), rtol=1e-5, atol=1e-5)

    # heterogeneous widths: the ragged family must still be the one called
    layout2, Ap2, Bp2, x2, ids2, scal2, rows2 = make_packed_case(
        rng, (4, 64), (2, 2), 32, 48, seq, bt)
    assert not layout2.is_uniform
    calls = []

    def spy(*a, **k):
        calls.append(1)
        return real_ragged(*a, **k)

    monkeypatch.setattr(ops, "fused_lora_ragged", spy)
    B2 = x2.shape[0] // seq
    ctx2 = MultiLoRA(adapter_ids=ids2.reshape(B2, seq)[:, 0],
                     ranks=jnp.asarray((4, 64), jnp.int32),
                     scalings=scal2, impl=impl, block_t=bt, layout=layout2,
                     rows_all=rows2)
    ctx2.apply(x2.reshape(B2, seq, -1), {"A": Ap2, "B": Bp2})
    assert calls, "heterogeneous layout must route to the ragged family"


def test_unsharded_nano_slices_use_exact_fallback(tiny_cfg, two_jobs):
    """The unsharded nano split is CONTIGUOUS, not job-proportional: a
    divisible sub-batch must not be described by scaled static tile
    geometry (a wrong map would apply the wrong adapter slabs).  Every
    impl must agree with ref across nano counts."""
    import dataclasses
    from repro.core.ssm import SharedSuperModel
    from repro.data.pipeline import FusedBatcher
    from repro.optim import adamw
    from repro.optim.schedule import constant

    # equal rows (2, 2) so nano=2 slices are single-job — the layout
    # that would fool a scaled-static-geometry heuristic
    jobs = [dataclasses.replace(two_jobs[0], batch_size=2),
            dataclasses.replace(two_jobs[1], batch_size=2)]
    outs = {}
    for impl in ("ref", "xla", "pallas"):
        ssm = SharedSuperModel(tiny_cfg, jobs, impl=impl, block_t=8)
        params, adapters = ssm.init(jax.random.PRNGKey(5))
        fb = FusedBatcher(jobs, tiny_cfg.vocab_size, block_t=8, seed=1)
        batch = {k: jnp.asarray(v) for k, v in fb.next_batch().items()}
        step = jax.jit(ssm.make_train_step(lr_fn=constant(1e-2),
                                           nano_batches=2, remat=False))
        opt = adamw.init(adapters, per_job=2)
        _, _, m = step(params, adapters, opt, batch)
        outs[impl] = np.asarray(m["per_job_loss"])
    np.testing.assert_allclose(outs["xla"], outs["ref"], rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(outs["pallas"], outs["ref"], rtol=1e-4,
                               atol=1e-5)
