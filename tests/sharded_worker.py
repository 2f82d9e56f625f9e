"""Multi-device worker for tests/test_sharded_runtime.py.

Runs in a SPAWNED subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set by the
``forced_devices`` fixture before jax imports.  Prints one JSON line per
scenario: {"name": ..., "ok": ..., "err": ...}.

Parity tolerances: the sharded step's cross-token reductions are
EXACT-by-construction (psum'ed integer denominators, scatter+psum wgrad
reassembly in solo order — kernels/ops.py), so the only sharded-vs-solo
divergence left is XLA:CPU's per-row codegen, which is not bit-stable
across batch shapes (the same row's forward loss differs in the last
ulp between an 8-row and a 2-row batch — measured in DESIGN.md §8).
Losses therefore compare at the suite's float32 lossless tolerance and
trainable state at the established near-exact criterion
(atol 2.5e-2 from Adam sign flips on near-zero coords, bulk within
1e-5), same as tests/test_lossless.py.
"""
import dataclasses
import json
import math
import traceback

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.jobs import LoRAJobSpec, tile_rows
from repro.elastic.migrate import JobTrainState
from repro.elastic.runtime import GroupRuntime
from repro.models import model as M

BT = 8
RESULTS = []


def scenario(fn):
    try:
        fn()
        RESULTS.append({"name": fn.__name__, "ok": True, "err": ""})
    except Exception:
        RESULTS.append({"name": fn.__name__, "ok": False,
                        "err": traceback.format_exc()[-2000:]})


def cfg_f32():
    return dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                               dtype="float32")


def losses_close(a, b):
    # rtol 1e-4: after a few Adam steps the backend's per-row ulp noise
    # is sign-amplified on near-zero coordinates (same effect the solo
    # lossless tests bound with atol=2.5e-2 on the STATE); real layout
    # bugs show up orders of magnitude above this (the clip-before-psum
    # denominator bug was 3e-2 relative).
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def state_close(ta, tb):
    # same structure as the solo lossless suite: flipped near-zero Adam
    # coordinates bounded by 2*lr, bulk agreeing tightly.  The bulk
    # fraction is 0.85 here (vs 0.97 solo-vs-solo): B matrices start at
    # zero, so EVERY coordinate is near zero for the first steps and
    # cross-batch-shape ulp noise from the backend flips more of them —
    # the 2.5e-2 bound plus loss-trajectory parity carry the signal.
    la, lb = jax.tree.leaves(ta), jax.tree.leaves(tb)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        np.testing.assert_allclose(x, y, atol=2.5e-2, rtol=0)
        frac = np.mean(np.abs(x - y) < 1e-5)
        assert frac > 0.85, (frac, x.shape, float(np.abs(x - y).max()))


def run_pair(jobs, mesh, *, steps=4, grad_sync="gather", impl="xla",
             chunk_size=2, seed=7):
    cfg = cfg_f32()
    kw = dict(lr=1e-2, impl=impl, block_t=BT, remat=False,
              chunk_size=chunk_size)
    solo = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(seed), **kw)
    solo.run(steps)
    sh = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(seed),
                                 mesh=mesh, grad_sync=grad_sync, **kw)
    sh.run(steps)
    return solo, sh


def compare(solo, sh):
    losses_close(solo.report.per_job_losses, sh.report.per_job_losses)
    state_close(solo.adapters, sh.adapters)
    state_close(solo.opt_state.mu, sh.opt_state.mu)
    state_close(solo.opt_state.nu, sh.opt_state.nu)
    assert np.array_equal(np.asarray(solo.opt_state.step),
                          np.asarray(sh.opt_state.step))
    assert solo.steps_done == sh.steps_done


def parity_k4_hetero_ranks():
    """K=4, heterogeneous ranks, equal rows, 2x2 mesh (4-way exec)."""
    jobs = [LoRAJobSpec(f"j{i}", rank=(2, 4, 8, 16)[i], batch_size=4,
                        seq_len=32) for i in range(4)]
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    solo, sh = run_pair(jobs, mesh)
    assert sh.data_shards == 4          # tp_mode="dp" folds both axes
    # equal layout -> per-shard equal segments (no dense-over-K fallback)
    ids = jnp.zeros((sh.batcher.total_rows() // 4,), jnp.int32)
    assert sh.ssm.lora_ctx(ids, axis_name="data").equal_segments
    compare(solo, sh)


def parity_k1_nondivisible_rows():
    """K=1: rows split WITHIN the job; batch 3 does not divide the
    4-way mesh -> padded to 4 (pads are exact zeros in loss and grad)."""
    jobs = [LoRAJobSpec("solo-job", rank=8, batch_size=3, seq_len=32)]
    mesh = jax.make_mesh((4,), ("data",))
    assert tile_rows(3, 32, BT, shards=4) == 4
    solo, sh = run_pair(jobs, mesh)
    assert sh.batcher.rows_per_job() == [4]
    compare(solo, sh)


def parity_unequal_segments():
    """Heterogeneous row counts -> per-shard unequal segments (the
    dense-over-K fallback path) on a 2-way mesh."""
    jobs = [LoRAJobSpec("big", rank=4, batch_size=4, seq_len=32),
            LoRAJobSpec("small", rank=8, batch_size=2, seq_len=32)]
    mesh = jax.make_mesh((2,), ("data",))
    solo, sh = run_pair(jobs, mesh)
    compare(solo, sh)


def parity_psum_mode():
    """grad_sync='psum' (classic DP all-reduce) with the autodiffed ref
    impl: float-associativity-close, not bit-structured."""
    jobs = [LoRAJobSpec("a", rank=4, batch_size=4, seq_len=32),
            LoRAJobSpec("b", rank=8, batch_size=4, seq_len=32)]
    mesh = jax.make_mesh((4,), ("data",))
    solo, sh = run_pair(jobs, mesh, grad_sync="psum", impl="ref")
    losses_close(solo.report.per_job_losses, sh.report.per_job_losses)
    state_close(solo.adapters, sh.adapters)


def parity_pallas_gather():
    """The pallas (interpret) shard-local VJP agrees with its solo
    trajectory too — the grouped wgrad kernels re-run at full shape."""
    jobs = [LoRAJobSpec("a", rank=4, batch_size=4, seq_len=32),
            LoRAJobSpec("b", rank=8, batch_size=4, seq_len=32)]
    mesh = jax.make_mesh((2,), ("data",))
    solo, sh = run_pair(jobs, mesh, impl="pallas", steps=2)
    compare(solo, sh)


def nano_regranulation_sharded():
    """Job-aware nano split on the sharded path is lossless (Eq. 2
    re-granulation) and snaps to divisors of per-shard per-job rows."""
    cfg = cfg_f32()
    jobs = [LoRAJobSpec("a", rank=4, batch_size=4, seq_len=32),
            LoRAJobSpec("b", rank=8, batch_size=4, seq_len=32)]
    mesh = jax.make_mesh((2,), ("data",))
    kw = dict(lr=1e-2, impl="xla", block_t=BT, remat=False, chunk_size=2)
    r1 = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(7),
                                 mesh=mesh, nano_batches=1, **kw)
    r1.run(2)
    r2 = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(7),
                                 mesh=mesh, nano_batches=2, **kw)
    r2.run(2)
    losses_close(r1.report.per_job_losses, r2.report.per_job_losses)
    state_close(r1.adapters, r2.adapters)
    # AIMD legal set: divisors of gcd of per-shard per-job rows
    r3 = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(7),
                                 mesh=mesh, adaptive_nano=True, **kw)
    rows_loc = [r // 2 for r in r3.batcher.rows_per_job()]
    g = math.gcd(*rows_loc)
    assert all(g % n == 0 for n in r3.aimd._legal), \
        (r3.aimd._legal, rows_loc)


def ragged_mixed_rank_parity():
    """Strongly mixed ranks (4 vs 64): the ragged sharded VJPs keep the
    solo trajectory in BOTH grad_sync modes, and the mesh runtime
    stores the ragged packed layout (8+64 lanes, not 2x64)."""
    jobs = [LoRAJobSpec("rag-a", rank=4, batch_size=4, seq_len=32),
            LoRAJobSpec("rag-b", rank=64, batch_size=4, seq_len=32)]
    mesh = jax.make_mesh((2,), ("data",))
    solo, sh = run_pair(jobs, mesh, steps=2)
    assert sh.ssm.layout.r_pads == (8, 64)
    for leaf in jax.tree.leaves(sh.adapters):
        assert 72 in leaf.shape[-2:], leaf.shape
    compare(solo, sh)
    solo2, sh2 = run_pair(jobs, mesh, grad_sync="psum", steps=2)
    losses_close(solo2.report.per_job_losses, sh2.report.per_job_losses)
    state_close(solo2.adapters, sh2.adapters)


def ragged_nano_rank_desc_order():
    """The rank-bucketed nano pipeline ordering (large-rank segments
    lead each slice) is a pure permutation: same losses and state as
    job order at the suite tolerance; and the ragged pallas path
    re-granulates losslessly on the sharded jobwise split."""
    cfg = cfg_f32()
    jobs = [LoRAJobSpec("o-a", rank=4, batch_size=4, seq_len=32),
            LoRAJobSpec("o-b", rank=64, batch_size=4, seq_len=32)]
    mesh = jax.make_mesh((2,), ("data",))
    kw = dict(lr=1e-2, impl="xla", block_t=BT, remat=False,
              chunk_size=2, mesh=mesh, nano_batches=2)
    r1 = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(7),
                                 nano_order="job", **kw)
    r1.run(2)
    r2 = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(7),
                                 nano_order="rank_desc", **kw)
    r2.run(2)
    losses_close(r1.report.per_job_losses, r2.report.per_job_losses)
    state_close(r1.adapters, r2.adapters)
    # ragged pallas: static per-slice tile metadata on the jobwise split
    kw_p = dict(kw, impl="pallas")
    p2 = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(7),
                                 nano_order="rank_desc", **kw_p)
    p2.run(2)
    losses_close(r1.report.per_job_losses, p2.report.per_job_losses)


def pipeline_parity_vs_single_submesh():
    """Stage-partitioned execution (DESIGN.md §15): a 2-stage x 4-way
    pipeline group over the full 8-device pool trains the SAME
    trajectory as the single-submesh 8-way DP execution of the same
    jobs — mixed ranks, nano slices doubling as pipeline micros, exact
    step accounting."""
    cfg = cfg_f32()
    jobs = [LoRAJobSpec("pl-a", rank=4, batch_size=8, seq_len=32),
            LoRAJobSpec("pl-b", rank=8, batch_size=8, seq_len=32)]
    kw = dict(lr=1e-2, impl="xla", block_t=BT, remat=False, chunk_size=2)
    # 8-way DP leaves 1 row/shard -> nano n=1; the pipeline's D=4 gives
    # 2 rows/shard -> n=2 micros.  Nano re-granulation is lossless
    # (Eq. 2; nano_regranulation_sharded), so trajectories still match.
    ref = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(7),
                                  mesh=jax.make_mesh((8,), ("data",)),
                                  nano_batches=1, **kw)
    ref.run(4)
    pl = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(7),
                                 mesh=jax.make_mesh((8,), ("data",)),
                                 tp_mode="pipeline", pipeline_stages=2,
                                 nano_batches=2, **kw)
    assert pl.pipeline_stages == 2 and pl.data_shards == 4
    assert pl.n == 2                     # micros cover the depth
    assert dict(pl.mesh.shape) == {"stage": 2, "data": 4}
    # residency: only the scanned stack shards over "stage"
    from repro.core.ssm import scanned_segment_index
    si = scanned_segment_index(cfg)
    for i, seg in enumerate(pl.adapters["segments"]):
        for leaf in jax.tree.leaves(seg):
            spec = leaf.sharding.spec
            want = ("stage",) if i == si else ()
            assert tuple(spec) == want, (i, tuple(spec))
    pl.run(4)
    compare(ref, pl)


def pipeline_migration_trajectory():
    """solo -> 2-stage pipeline group -> solo extraction is lossless:
    the stitched trajectory equals solo-throughout, and per-job Adam
    step accounting survives both moves (mixed ranks, P=2 x D=4)."""
    cfg = cfg_f32()
    job_a = LoRAJobSpec("pmig-a", rank=4, batch_size=8, seq_len=32)
    job_b = LoRAJobSpec("pmig-b", rank=8, batch_size=8, seq_len=32)
    k = 2
    key = jax.random.PRNGKey(3)
    params = M.init_model(jax.random.fold_in(key, 0), cfg)
    k_a, k_b = jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)
    kw = dict(lr=1e-2, impl="xla", block_t=BT, remat=False, chunk_size=2)

    def fresh(spec, kk):
        return JobTrainState.fresh(spec, cfg, kk, r_pad=8)

    ref = GroupRuntime.from_states(cfg, params, [fresh(job_a, k_a)], **kw)
    ref_losses = [l[0] for l in ref.run(3 * k).per_job_losses]

    ra = GroupRuntime.from_states(cfg, params, [fresh(job_a, k_a)], **kw)
    ra.run(k)
    merged = GroupRuntime.from_states(
        cfg, params, [ra.export(job_a.job_id), fresh(job_b, k_b)],
        mesh=jax.make_mesh((8,), ("data",)), tp_mode="pipeline",
        pipeline_stages=2, nano_batches=2, **kw)
    assert np.asarray(merged.opt_state.step).tolist() == [k, 0]
    merged.run(k)
    back = GroupRuntime.from_states(
        cfg, params, [merged.export(job_a.job_id)], **kw)
    back.run(k)

    got = ([l[0] for l in ra.report.per_job_losses]
           + [l[0] for l in merged.report.per_job_losses]
           + [l[0] for l in back.report.per_job_losses])
    losses_close(got, ref_losses)
    st = back.export(job_a.job_id)
    assert st.opt_step == 3 * k
    ref_st = ref.export(job_a.job_id)
    state_close(st.adapter, ref_st.adapter)
    state_close(st.mu, ref_st.mu)


def migration_across_meshes():
    """Elastic fuse/unfuse between a single-device runtime and a 4-way
    sharded group keeps the trajectory lossless and the per-job Adam
    step accounting exact."""
    cfg = cfg_f32()
    job_a = LoRAJobSpec("mig-a", rank=4, batch_size=2, seq_len=32)
    job_b = LoRAJobSpec("mig-b", rank=8, batch_size=2, seq_len=32)
    k = 2
    key = jax.random.PRNGKey(3)
    params = M.init_model(jax.random.fold_in(key, 0), cfg)
    k_a, k_b = jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)
    kw = dict(lr=1e-2, impl="xla", block_t=BT, remat=False, chunk_size=2)
    mesh = jax.make_mesh((4,), ("data",))

    def fresh(spec, kk):
        return JobTrainState.fresh(spec, cfg, kk, r_pad=8)

    ref = GroupRuntime.from_states(cfg, params, [fresh(job_a, k_a)], **kw)
    ref_losses = [l[0] for l in ref.run(3 * k).per_job_losses]

    ra = GroupRuntime.from_states(cfg, params, [fresh(job_a, k_a)], **kw)
    ra.run(k)
    merged = GroupRuntime.from_states(
        cfg, params, [ra.export(job_a.job_id), fresh(job_b, k_b)],
        mesh=mesh, **kw)
    assert np.asarray(merged.opt_state.step).tolist() == [k, 0]
    merged.run(k)
    back = GroupRuntime.from_states(
        cfg, params, [merged.export(job_a.job_id)], **kw)
    back.run(k)

    got = ([l[0] for l in ra.report.per_job_losses]
           + [l[0] for l in merged.report.per_job_losses]
           + [l[0] for l in back.report.per_job_losses])
    losses_close(got, ref_losses)
    st = back.export(job_a.job_id)
    assert st.opt_step == 3 * k
    ref_st = ref.export(job_a.job_id)
    state_close(st.adapter, ref_st.adapter)
    state_close(st.mu, ref_st.mu)


def gather_solo_bitexact():
    """scatter-to-solo-position + psum reassembly is bit-preserving."""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.kernels.ops import gather_solo

    mesh = jax.make_mesh((4,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 5), jnp.float32)
    perm = np.random.default_rng(0).permutation(16).astype(np.int32)

    def body(t, pos):
        return gather_solo(t, "data", pos, 16)

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=(P("data"), P("data")),
                              out_specs=P(), check_vma=False))
    out = f(x, jnp.asarray(perm))
    want = np.zeros_like(np.asarray(x))
    want[perm] = np.asarray(x)
    assert np.array_equal(np.asarray(out), want)


def local_mesh_clamps():
    from repro.launch.mesh import make_local_mesh
    for req, (d, m) in [(1, (8, 1)), (2, (4, 2)), (3, (4, 2)),
                        (5, (2, 4)), (8, (1, 8)), (16, (1, 8))]:
        mesh = make_local_mesh(model=req)
        assert dict(mesh.shape) == {"data": d, "model": m}, \
            (req, dict(mesh.shape))


def _controller(conc, seed=0, pool=None, **kw):
    from repro.cluster.controller import ClusterController
    cfg = cfg_f32()
    return ClusterController(lambda m: cfg, devices=pool, impl="xla",
                             block_t=BT, lr=1e-2, remat=False,
                             chunk_size=2, concurrency=conc, seed=seed,
                             **kw), cfg


def _two_group_jobs(cfg):
    return [[LoRAJobSpec(f"g{g}j{i}", rank=(4, 8)[i], batch_size=2,
                         seq_len=32, base_model=cfg.name)
             for i in range(2)] for g in range(2)]


def controller_concurrent_parity():
    """2 concurrent groups on disjoint submeshes: threaded execution is
    BIT-EXACT vs sequential execution of the same partition (same
    submesh shapes, same inputs, same executables — concurrency must
    change nothing but wall-clock)."""
    runs = {}
    for conc in ("threads", "sequential"):
        ctl, cfg = _controller(conc, pool=jax.devices()[:4])
        groups = _two_group_jobs(cfg)
        for js in groups:
            for j in js:
                ctl.submit(j)
        gkeys = [tuple(j.job_id for j in js) for js in groups]
        ctl.apply_grouping(gkeys, chips=[2, 2])
        devs = ctl.group_devices()
        assert all(len(d) == 2 for d in devs.values()), devs
        assert not (set(devs[gkeys[0]]) & set(devs[gkeys[1]])), devs
        ctl.run(6)
        runs[conc] = ctl
    for gk in runs["threads"].group_devices():
        rt_t = runs["threads"]._slots[gk].runtime(gk)
        rt_s = runs["sequential"]._slots[gk].runtime(gk)
        assert np.array_equal(np.asarray(rt_t.report.per_job_losses),
                              np.asarray(rt_s.report.per_job_losses)), gk
        for a, b in zip(jax.tree.leaves(rt_t.adapters),
                        jax.tree.leaves(rt_s.adapters)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), gk


def controller_repartition_migration():
    """Cross-mesh migration during a pool repartition is lossless: a
    job moving solo-submesh -> fused-wider-submesh -> solo reproduces
    the solo-throughout trajectory (float tolerance — submesh shapes
    change, DESIGN.md §8 backend caveat).  Per-job step and Adam
    accounting stay exact across both migrations."""
    k = 2
    ref, cfg = _controller("sequential", seed=3, pool=jax.devices()[:4])
    (j_a, j_b), _ = _two_group_jobs(cfg)
    ga, gab = (j_a.job_id,), (j_a.job_id, j_b.job_id)
    ref.submit(j_a)
    ref.apply_grouping([ga], chips=[1])
    ref.run(3 * k)
    ref_losses = [l[0] for l in
                  ref._slots[ga].runtime(ga).report.per_job_losses]

    ctl, _ = _controller("sequential", seed=3, pool=jax.devices()[:4])
    got = []
    ctl.submit(j_a)
    ctl.apply_grouping([ga], chips=[1])
    ctl.run(k)
    got += [l[0] for l in
            ctl._slots[ga].runtime(ga).report.per_job_losses]
    ctl.submit(j_b)                       # arrival -> repartition
    ctl.apply_grouping([gab], chips=[4])
    assert len(ctl.group_devices()[gab]) == 4
    ctl.run(k)
    got += [l[0] for l in
            ctl._slots[gab].runtime(gab).report.per_job_losses]
    st_b = ctl.remove_job(j_b.job_id)     # completion -> repartition
    assert st_b.steps_done == k and st_b.opt_step == k
    ctl.apply_grouping([ga], chips=[1])
    ctl.run(k)
    got += [l[0] for l in
            ctl._slots[ga].runtime(ga).report.per_job_losses]
    assert ctl.regroup_events >= 2, ctl.regroup_events
    assert ctl.steps_done(j_a.job_id) == 3 * k
    losses_close(got, ref_losses)
    st = ctl.job_state(j_a.job_id)
    ref_st = ref.job_state(j_a.job_id)
    assert st.opt_step == ref_st.opt_step == 3 * k
    state_close(st.adapter, ref_st.adapter)
    state_close(st.mu, ref_st.mu)

    # incremental regroup on a FULL pool: ensure_group must allocate
    # AFTER dissolving the superseded slot, so the freed devices are
    # reusable — a pre-dissolve allocation would land the new group
    # meshless despite a now-free pool
    ctl2, cfg2 = _controller("sequential", pool=jax.devices()[:2])
    (jx, jy), _ = _two_group_jobs(cfg2)
    ctl2.submit(jx)
    ctl2.submit(jy)
    ctl2.ensure_group((jx.job_id, jy.job_id), chips=2)
    assert len(ctl2.group_devices()[(jx.job_id, jy.job_id)]) == 2
    ctl2.ensure_group((jx.job_id,), chips=1)
    assert len(ctl2.group_devices()[(jx.job_id,)]) == 1


def controller_overlapped_migration():
    """Zero-stall regroup under load (DESIGN.md §11): two groups pump on
    disjoint 2-device submeshes while the 4-device merged destination is
    assembled + AOT-warmed in the background; the handoff fences the
    sources at a chunk boundary and the stall window contains NO
    compile.  Replay-exactness: the result matches a stop-the-world
    reference rebuilt at the very same fence steps (state_close — the
    submesh shapes change across the merge, DESIGN.md §8)."""
    import time

    ctl, cfg = _controller("threads", seed=3, pool=jax.devices()[:4])
    groups = _two_group_jobs(cfg)
    for js in groups:
        for j in js:
            ctl.submit(j)
    gkeys = [tuple(j.job_id for j in js) for js in groups]
    merged = gkeys[0] + gkeys[1]
    ctl.apply_grouping(gkeys, chips=[2, 2])
    devs = ctl.group_devices()
    assert not (set(devs[gkeys[0]]) & set(devs[gkeys[1]])), devs

    ctl.begin(100_000)            # effectively: pump until drained below
    t0 = time.monotonic()
    while min(ctl.steps_done(j) for j in merged) < 4:
        assert time.monotonic() - t0 < 300
        time.sleep(0.05)
    assert ctl.prewarm([merged], chips=[4]) == 1   # sources keep stepping
    ctl.apply_grouping([merged], chips=[4])
    ev = ctl.regroup_log[-1]
    assert ev.mode == "overlapped", ev.mode
    assert ev.compile_s == 0.0                     # warmed off-window
    assert ev.assemble_s > 0.0 and ev.stall_s > 0.0
    assert ev.groups_dissolved == 2 and ev.groups_built == 1
    assert sorted(ev.fence_steps) == sorted(merged)
    assert all(s >= 4 for s in ev.fence_steps.values()), ev.fence_steps
    assert len(ctl.group_devices()[merged]) == 4

    # let the merged pump train past the handoff, then drain the run
    w = ctl._workers[merged]
    while ctl.steps_done(merged[0]) - ev.fence_steps[merged[0]] < 4:
        assert time.monotonic() - t0 < 300 and w.exception is None, \
            w.exception
        time.sleep(0.05)
    assert w.fence(120) and (w.stop() or w.join(120))
    assert w.exception is None, w.exception
    ctl._workers, ctl._run_target, ctl._run_base = {}, 0, {}
    fence = ev.fence_steps
    extra = {j: ctl.steps_done(j) - fence[j] for j in merged}
    assert len(set(extra.values())) == 1, extra    # members step together
    r = next(iter(extra.values()))

    # stop-the-world reference cut at the SAME fence boundary
    ref, _ = _controller("sequential", seed=3, pool=jax.devices()[:4])
    for js in groups:
        for j in js:
            ref.submit(j)
    ref.apply_grouping(gkeys, chips=[2, 2])
    for gk in gkeys:
        ref._slots[gk].runtime(gk).run(fence[gk[0]])
    ref.apply_grouping([merged], chips=[4])
    ref._slots[merged].runtime(merged).run(r)
    for j in merged:
        a, b = ctl.job_state(j), ref.job_state(j)
        assert a.opt_step == b.opt_step, (j, a.opt_step, b.opt_step)
        assert a.steps_done == b.steps_done
        state_close(a.adapter, b.adapter)
        state_close(a.mu, b.mu)
        state_close(a.nu, b.nu)


def _ft_setup(fault_kind, phase):
    """Two 2-job groups on disjoint 2-device submeshes of the 8-device
    pool, periodic checkpoints every collected chunk, one scripted fault
    on group B's first member.  Returns (ctl, gkeys, jobs, plan)."""
    import tempfile

    from repro.cluster.faults import FaultPlan, FaultSpec

    plan = FaultPlan([FaultSpec(fault_kind, job_id="g1j0", at_step=4,
                                phase=phase)])
    ctl, cfg = _controller(
        "threads", seed=3, pool=jax.devices(),
        checkpoint_dir=tempfile.mkdtemp(prefix="ft_ckpt_"),
        checkpoint_every=1, fault_plan=plan,
        max_restarts=3, backoff_base_s=0.02, stuck_after=None)
    groups = _two_group_jobs(cfg)
    jobs = [dataclasses.replace(j, steps_budget=12)
            for js in groups for j in js]
    for j in jobs:
        ctl.submit(j)
    gkeys = [tuple(j.job_id for j in js) for js in groups]
    ctl.apply_grouping(gkeys, chips=[2, 2])
    return ctl, gkeys, jobs, plan


def _ft_reference(seed=3):
    """Fault-free sequential reference of the same partition."""
    ref, cfg = _controller("sequential", seed=seed, pool=jax.devices())
    groups = _two_group_jobs(cfg)
    for js in groups:
        for j in js:
            ref.submit(dataclasses.replace(j, steps_budget=12))
    gkeys = [tuple(j.job_id for j in js) for js in groups]
    ref.apply_grouping(gkeys, chips=[2, 2])
    for gk in gkeys:                 # drive runtimes directly: keeps the
        ref._slots[gk].runtime(gk).run(12)   # slots for state readback
    return ref, gkeys


def _ft_wait(cond, ctl, timeout=600):
    import time
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, "fault scenario hung"
        time.sleep(0.05)


def controller_fault_recovery():
    """Failure domains + supervised recovery (DESIGN.md §12): a worker
    killed MID-CHUNK is contained to its group — the other group's pump
    is never touched (same worker object, keeps stepping) — and the
    affected jobs restore from their periodic checkpoint onto a rebuilt
    submesh, replaying the EXACT batch stream: the post-restore loss
    trajectory equals the fault-free reference from the checkpoint step
    on, and steps lost never exceed the checkpoint period."""
    import time

    ctl, (ga, gb), jobs, plan = _ft_setup("worker_death", "inflight")
    ref, _ = _ft_reference()
    ref_losses = {gk: np.asarray(
        ref._slots[gk].runtime(gk).report.per_job_losses)
        for gk in (ga, gb)}

    ctl.begin(until_budget=True)
    w_a = ctl._workers[ga]
    recs = []
    _ft_wait(lambda: recs.extend(ctl.supervise(reschedule=False))
             or recs, ctl)
    rec = recs[0]
    assert rec.kind == "worker_death" and rec.gkey == gb, rec
    assert len(plan.fired) == 1
    # containment: A's pump is the SAME object, alive or finished clean,
    # and was never restarted
    assert ctl._workers[ga] is w_a
    assert w_a.exception is None
    # recovery: both members restored from checkpoint, bounded staleness
    assert sorted(rec.restored_from_checkpoint) == sorted(gb), rec
    assert not rec.restarted_fresh and not rec.poisoned
    period = 1 * 2                           # checkpoint_every * chunk
    assert all(0 <= lost <= period
               for lost in rec.steps_lost.values()), rec.steps_lost
    assert not ctl.quarantined                 # devices return to duty
    ckpt_step = min(ctl._parked[j].steps_done for j in gb)
    assert ckpt_step >= 4 - period

    # rebuild B on freed devices (A keeps its slice -> kept, not built)
    time.sleep(0.05)                           # let the retry backoff pass
    out = ctl.apply_grouping([ga, gb], chips=[2, 2])
    assert ga in out["keep"] and gb in out["build"], out
    _ft_wait(lambda: all(w.done.is_set()
                         for w in ctl._workers.values()), ctl)
    assert all(w.exception is None for w in ctl._workers.values())

    # replay-exactness: B's post-restore trajectory IS the reference's
    # from the checkpoint step on (same stream positions replayed)
    rt_b = ctl._slots[gb].runtime(gb)
    post = np.asarray(rt_b.report.per_job_losses)
    losses_close(post, ref_losses[gb][ckpt_step:])
    # A never faulted and never moved: bit-exact vs the reference
    rt_a = ctl._slots[ga].runtime(ga)
    assert np.array_equal(np.asarray(rt_a.report.per_job_losses),
                          ref_losses[ga])
    ctl.reap_completed()
    assert sorted(ctl.finished) == sorted(j.job_id for j in jobs)
    for j in jobs:
        assert ctl.steps_done(j.job_id) == 12
        a, b = ctl.job_state(j.job_id), ref.job_state(j.job_id)
        assert a.steps_done == b.steps_done
        state_close(a.adapter, b.adapter)


def controller_submesh_loss_containment():
    """A lost submesh is quarantined permanently: its devices never
    re-enter the pool, the rebuilt group lands on DISJOINT devices, and
    every job still completes its budget on the shrunken cluster."""
    import time

    ctl, (ga, gb), jobs, _ = _ft_setup("submesh_loss", "boundary")
    lost_devs = set(ctl.group_devices()[gb])
    ctl.begin(until_budget=True)
    recs = []
    _ft_wait(lambda: recs.extend(ctl.supervise(reschedule=False))
             or recs, ctl)
    rec = recs[0]
    assert rec.kind == "submesh_loss" and rec.gkey == gb, rec
    assert set(rec.quarantined_devices) == lost_devs
    assert ctl.quarantined == lost_devs
    avail = set(ctl.available_device_ids())
    assert not (avail & lost_devs)
    period = 1 * 2
    assert all(lost <= period for lost in rec.steps_lost.values()), rec

    time.sleep(0.05)
    ctl.apply_grouping([ga, gb], chips=[2, 2])
    new_devs = set(ctl.group_devices()[gb])
    assert new_devs and not (new_devs & lost_devs), (new_devs, lost_devs)
    _ft_wait(lambda: all(w.done.is_set()
                         for w in ctl._workers.values()), ctl)
    assert all(w.exception is None for w in ctl._workers.values())
    ctl.reap_completed()
    assert sorted(ctl.finished) == sorted(j.job_id for j in jobs)
    assert all(ctl.steps_done(j.job_id) == 12 for j in jobs)
    assert ctl.quarantined == lost_devs        # forever


def execution_backend_sharded():
    """ExecutionBackend measures on a real mesh without falling over."""
    from repro.cluster.execution import ExecutionBackend
    from repro.core.scheduler import Group

    cfg = cfg_f32()
    mesh = jax.make_mesh((2,), ("data",))
    be = ExecutionBackend(impl="xla", block_t=BT, mesh=mesh, seed=0)
    specs = [LoRAJobSpec("x1", rank=4, batch_size=2, seq_len=32,
                         base_model="tinyllama-1.1b"),
             LoRAJobSpec("x2", rank=8, batch_size=2, seq_len=32,
                         base_model="tinyllama-1.1b")]
    import repro.core.jobs as J
    group = Group(jobs=[J.JobRuntimeState(spec=s) for s in specs], chips=2)
    t = be.observe(cfg, group, predicted=1e-3, now=0.0)
    assert t is not None and t > 0
    assert be.records and be.records[0].measured == t
    # default impl='ref' has no shard-local VJP: the backend must fall
    # back to grad_sync='psum' instead of failing at measurement time
    be2 = ExecutionBackend(block_t=BT, mesh=mesh, seed=0)
    assert be2._engine_kwargs["grad_sync"] == "psum"


if __name__ == "__main__":
    for fn in (parity_k4_hetero_ranks, parity_k1_nondivisible_rows,
               parity_unequal_segments, parity_psum_mode,
               parity_pallas_gather, nano_regranulation_sharded,
               ragged_mixed_rank_parity, ragged_nano_rank_desc_order,
               pipeline_parity_vs_single_submesh,
               pipeline_migration_trajectory,
               migration_across_meshes, gather_solo_bitexact,
               local_mesh_clamps, execution_backend_sharded,
               controller_concurrent_parity,
               controller_repartition_migration,
               controller_overlapped_migration,
               controller_fault_recovery,
               controller_submesh_loss_containment):
        scenario(fn)
    for r in RESULTS:
        print("SCENARIO " + json.dumps(r))
