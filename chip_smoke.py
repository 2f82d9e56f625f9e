#!/usr/bin/env python3
"""Smoke run of the fused multi-LoRA trainer on a TPU.

    python3 chip_smoke.py             # one chip: phases A-D
    python3 chip_smoke.py --chips 4   # four chips: the multi-chip phase only

Drives the normal training path -- ``GroupRuntime.from_specs``, which
is what ``train_group`` and ``python -m repro.launch.train train`` run
-- on tinyllama-1.1b at full width (d_model 2048, d_ff 5632, 32000
vocab).  Weights and data are random, made from ``--seed``; nothing is
downloaded.

One chip, all 22 layers; K=4 jobs of 2 x 1024 tokens, 4 steps in
chunks of 2:
  A  ranks {64,16,8,4}; kernel family and token tile left to the
     platform, which picks the ragged Pallas kernels on 128-row tiles.
     Then the ragged op alone at the MLP's widths (2048 -> 5632, all
     8192 tokens), forward and VJP, against an f32 reference at
     "highest" matmul precision, for the Pallas and the XLA kernels:
     each job's part of every output (its rows of y and dx, its rank
     slice of dA and dB) within 2e-2 of that part's largest entry, and
     no gradient in padding lanes.  A dropped or misplaced token tile
     or rank tile moves a job's part by far more than bf16 rounding.
  B  ranks {16,8,4,2}: every rank pads to 16, so the uniform layout
     takes the masked Pallas family.
  C  A's group again from the same seed on impl="xla", the reference.
     Per-job losses of all 4 steps must agree with A within 2e-2
     relative.  Each job's adapter slices after step 4 must agree leaf
     by leaf: sum|pallas - xla| <= 0.5 * sum|xla - init|.  AdamW's
     first steps move each entry by about +-lr whatever the gradient's
     size, so rounding that flips a small gradient's sign moves that
     entry by 2 lr and 22 layers compound it; a wiring fault (a job's
     gradient missing, or taken from another job's tokens) moves the
     slice by about its whole update, 1.0.  C again with every matmul
     at "highest" precision prints what rounding alone moves.
  D  A's adapters published into an AdapterPool; a ServeEngine answers
     one request per adapter, 16 new tokens each, on 16-row tiles: at
     the 128-row default each adapter's one request pads to 128 rows,
     and that 512-row program needs ~15.9 GB of the chip's 16 GiB.

Four chips (``--chips 4``), depth cut to 4 layers, widths unchanged:
  A's group on a 4-device data-parallel mesh (tp_mode="dp",
  grad_sync="gather") against the same group on one device; a
  pipeline group (2 stages x 2-way data) against data parallelism on
  the same 4 devices; a ClusterController running two groups on 2+2
  chips concurrently.  Each comparison uses C's tolerances.  Both sides
  of a comparison run the Pallas kernels, so there is no precision
  control here: Mosaic refuses a bf16 matmul at "highest" precision.

Lines starting with "smoke" are smoke output, not benchmark numbers.
The last line is one JSON object naming the device.  The script exits
non-zero, without that line, when JAX finds no TPU, when it does not sit
in a checkout of the repository, or when any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

LR = 1e-3
LOSS_RTOL = 2e-2
ADAPTER_RTOL = 0.5
KERNEL_TOL = 2e-2
STEPS, CHUNK = 4, 2
RANKS_MIXED = (64, 16, 8, 4)
RANKS_UNIFORM = (16, 8, 4, 2)
SERVE_BLOCK_T = 16


def say(msg: str) -> None:
    print(f"smoke {msg}", flush=True)


def jobs_for(cfg, ranks, batch_size=2, seq_len=1024):
    from repro.core.jobs import LoRAJobSpec
    return [LoRAJobSpec(f"r{r}-{k}", rank=r, batch_size=batch_size,
                        seq_len=seq_len, base_model=cfg.name)
            for k, r in enumerate(ranks)]


def peak_gb() -> str:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    if any(p is None for p in peaks):
        return "n/a"
    return "/".join(f"{p / 1e9:.2f}" for p in peaks) + " GB"


def mosaic_calls(rt) -> int:
    """Mosaic kernel launches in the runtime's compiled chunk step."""
    return sum(c.as_text().count("tpu_custom_call")
               for c in rt._step_cache.values())


@dataclasses.dataclass
class Trained:
    """What a comparison needs of a trained group, held on the host."""
    losses: "np.ndarray"          # (STEPS, K)
    init: list                    # per job: {leaf path: slice} before step 1
    final: list                   # per job: {leaf path: slice} at the end


def job_slices(rt) -> list:
    """Each job's un-padded adapter slices, on the host."""
    import jax
    from repro.checkpoint.checkpoint import slice_job
    adapters = jax.device_get(rt.adapters)
    layout = rt.ssm.layout
    return [slice_job(adapters, layout.slice_of(k)[0], r)
            for k, r in enumerate(layout.ranks)]


def train(label, cfg, jobs, seed, **kw):
    """Build a group through the normal path, compile its chunk step,
    run STEPS steps and print what the smoke run checks."""
    import jax
    import numpy as np
    from repro.elastic.runtime import GroupRuntime
    rt = GroupRuntime.from_specs(cfg, jobs, jax.random.PRNGKey(seed),
                                 lr=LR, chunk_size=CHUNK, seed=seed, **kw)
    init = job_slices(rt)
    compile_s = rt.warm()
    rt.run(STEPS)
    losses = np.asarray(rt.report.per_job_losses, np.float64)
    assert losses.shape == (STEPS, len(jobs)), losses.shape
    assert np.isfinite(losses).all(), losses
    devs = sorted({d.id for leaf in jax.tree.leaves(rt.adapters)
                   for d in leaf.sharding.device_set})
    say(f"{label}: impl={rt.ssm.impl} block_t={rt.ssm.block_t} "
        f"r_pads={rt.ssm.layout.r_pads} devices={devs} "
        f"compile {compile_s:.1f} s, "
        f"{float(np.mean(rt.report.step_times)):.4f} s/step after compile, "
        f"per-job losses {np.round(losses, 4).tolist()}, "
        f"peak HBM so far {peak_gb()}")
    return rt, Trained(losses, init, job_slices(rt))


def adapter_gaps(ref: Trained, got: Trained) -> list:
    """Per job, the worst leaf's sum|got - ref| / sum|ref - init| over
    the job's own rank slice: how far *got*'s adapters sit from *ref*'s,
    in units of *ref*'s own update."""
    import numpy as np
    gaps = []
    for r_job, g_job, z_job in zip(ref.final, got.final, ref.init):
        worst = 0.0
        for path in r_job:
            r, g, z = (np.asarray(t[path], np.float64)
                       for t in (r_job, g_job, z_job))
            moved = np.abs(r - z).sum()
            assert moved > 0, f"the reference adapter {path} did not train"
            worst = max(worst, float(np.abs(g - r).sum() / moved))
        gaps.append(worst)
    return gaps


def precision_control(label, cfg, jobs, seed, ref: Trained, **kw) -> None:
    """Train *ref*'s group again with every matmul at "highest"
    precision and print the gap to *ref*: what rounding alone moves.
    Context for the adapter limit, not gated."""
    import jax
    with jax.default_matmul_precision("highest"):
        rt, hi = train(label, cfg, jobs, seed, **kw)
    del rt
    gaps = adapter_gaps(ref, hi)
    say(f"{label}: precision-only adapter gap per job "
        f"{[float(f'{g:.3e}') for g in gaps]} (not gated)")


def compare(label, ref: Trained, got: Trained) -> None:
    """Losses of every step within LOSS_RTOL; each job's adapters, leaf
    by leaf, within ADAPTER_RTOL of the reference's update."""
    import numpy as np
    loss_rel = float(np.max(np.abs(got.losses - ref.losses)
                            / np.abs(ref.losses)))
    for a, b in zip(ref.init, got.init):
        for path in a:
            assert np.array_equal(a[path], b[path]), \
                f"{label}: the runs must start alike ({path})"
    gaps = adapter_gaps(ref, got)
    say(f"{label}: max per-job loss rel diff {loss_rel:.2e} "
        f"(limit {LOSS_RTOL}), per job worst adapter leaf "
        f"sum|diff|/sum|update| {[float(f'{g:.3e}') for g in gaps]} "
        f"(limit {ADAPTER_RTOL})")
    assert loss_rel <= LOSS_RTOL, (label, loss_rel)
    assert max(gaps) <= ADAPTER_RTOL, (label, gaps)


def kernel_parity(layout, d_in: int, d_out: int, rows: int, seq_len: int,
                  block_t: int, seed: int) -> None:
    """The ragged multi-LoRA op alone -- forward and VJP at one
    projection's full width over the whole fused batch -- for the Pallas
    and the XLA kernels, against an f32 reference at "highest" matmul
    precision.  Each output's largest error must stay within KERNEL_TOL
    of that output's largest entry."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    K = layout.num_jobs
    seg = rows * seq_len
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    x = jax.random.normal(ks[0], (K * seg, d_in), bf)
    A = (jax.random.normal(ks[1], (d_in, layout.total))
         * d_in ** -0.5).astype(bf)
    B = (jax.random.normal(ks[2], (layout.total, d_out)) * 0.1).astype(bf)
    dy = jax.random.normal(ks[3], (K * seg, d_out), bf)
    ids = jnp.repeat(jnp.arange(K, dtype=jnp.int32), seg)
    scal = jnp.asarray([16.0 / r for r in layout.ranks], jnp.float32)
    per_job = (rows,) * K

    def fused(impl):
        return lambda x, A, B: ops.fused_lora_ragged(
            x, A, B, ids, scal, layout, impl=impl, block_t=block_t,
            equal_segments=True, slice_rows=per_job, seq_len=seq_len,
            solo_rows=per_job)

    def reference(x, A, B):
        out = []
        for k in range(K):
            off, r = layout.offsets[k], layout.ranks[k]
            xa = x[k * seg:(k + 1) * seg] @ A[:, off:off + r]
            out.append(xa @ B[off:off + r] * scal[k])
        return jnp.concatenate(out)

    def fwd_bwd(fn, dtype):
        def f(x, A, B):
            y, vjp = jax.vjp(fn, x, A, B)
            return (y,) + vjp(dy.astype(y.dtype))
        return [np.asarray(t, np.float32) for t in jax.jit(f)(
            *(t.astype(dtype) for t in (x, A, B)))]

    def parts(name, t):
        """*t*'s part of each job: its rows of y/dx, its rank slice of
        dA/dB."""
        spans = [slice(layout.offsets[k], layout.offsets[k] + r)
                 for k, r in enumerate(layout.ranks)]
        if name in ("y", "dx"):
            return [t[k * seg:(k + 1) * seg] for k in range(K)]
        return [t[:, s] if name == "dA" else t[s] for s in spans]

    padding = np.ones(layout.total, bool)
    for k, r in enumerate(layout.ranks):
        padding[layout.offsets[k]:layout.offsets[k] + r] = False
    with jax.default_matmul_precision("highest"):
        want = fwd_bwd(reference, jnp.float32)
    for impl in ("pallas", "xla"):
        got = fwd_bwd(fused(impl), bf)
        errs = {name: max(float(np.abs(g - w).max() / np.abs(w).max())
                          for g, w in zip(parts(name, g_), parts(name, w_)))
                for name, g_, w_ in zip(("y", "dx", "dA", "dB"), got, want)}
        say(f"A kernel parity, ragged {impl} vs f32 reference at "
            f"{d_in}->{d_out}, {K * seg} tokens: worst job's "
            f"max|err|/max|ref| "
            + ", ".join(f"{n} {e:.2e}" for n, e in errs.items())
            + f" (limit {KERNEL_TOL})")
        assert max(errs.values()) <= KERNEL_TOL, (impl, errs)
        assert not got[2][:, padding].any() and not got[3][padding].any(), \
            f"{impl}: gradient in padding lanes"


def check_placement(rt, devices) -> None:
    """Every leaf of the group's state spans the group's whole mesh."""
    import jax
    want = set(devices)
    for tree in (rt.params, rt.adapters, rt.opt_state):
        for leaf in jax.tree.leaves(tree):
            assert leaf.sharding.device_set == want, \
                (leaf.shape, leaf.sharding)


# --------------------------------------------------------------- one chip
def one_chip(cfg, seed: int, seq_len: int = 1024, kernels=None) -> None:
    import jax
    import numpy as np
    from repro.serve import AdapterPool, ServeEngine, ServeRequest

    kernels = dict(kernels or {})
    # ---- A: mixed ranks, platform-chosen kernels (ragged family)
    jobs_a = jobs_for(cfg, RANKS_MIXED, seq_len=seq_len)
    rt_a, run_a = train("A mixed ranks", cfg, jobs_a, seed, **kernels)
    calls = mosaic_calls(rt_a)
    say(f"A: impl={rt_a.ssm.impl} uniform={rt_a.ssm.layout.is_uniform} "
        f"tpu_custom_call in compiled step: {calls > 0} ({calls})")
    assert rt_a.ssm.impl == "pallas" and not rt_a.ssm.layout.is_uniform
    assert calls > 0, "phase A ran no compiled Pallas kernel"
    block_t, layout = rt_a.ssm.block_t, rt_a.ssm.layout
    pool = AdapterPool(cfg, capacity=len(jobs_a), multiple=layout.multiple)
    rt_a.publish_to(pool)
    del rt_a
    kernel_parity(layout, cfg.d_model, cfg.d_ff, jobs_a[0].batch_size,
                  seq_len, block_t, seed)

    # ---- B: uniform padded ranks (masked family)
    rt_b, _ = train("B uniform ranks", cfg,
                    jobs_for(cfg, RANKS_UNIFORM, seq_len=seq_len), seed,
                    **kernels)
    calls = mosaic_calls(rt_b)
    say(f"B: impl={rt_b.ssm.impl} uniform={rt_b.ssm.layout.is_uniform} "
        f"tpu_custom_call in compiled step: {calls > 0} ({calls})")
    assert rt_b.ssm.impl == "pallas" and rt_b.ssm.layout.is_uniform
    assert calls > 0, "phase B ran no compiled Pallas kernel"
    del rt_b

    # ---- C: A's group on the XLA reference kernels
    ref = dict(impl="xla", block_t=block_t)
    rt_c, run_c = train("C xla reference", cfg, jobs_a, seed, **ref)
    params = rt_c.params                 # same seed: A's backbone
    del rt_c
    precision_control("C at highest precision", cfg, jobs_a, seed, run_c,
                      **ref)
    compare("C vs A", run_c, run_a)

    # ---- D: serve A's adapters
    engine = ServeEngine(cfg, params, pool,
                         **{**kernels, "block_t": SERVE_BLOCK_T})
    rng = np.random.default_rng(seed)
    reqs = [ServeRequest(prompt=rng.integers(1, cfg.vocab_size, size=n,
                                             dtype=np.int32),
                         adapter=j.job_id, max_new_tokens=16)
            for n, j in zip((24, 40, 17, 64), jobs_a)]
    t0 = time.perf_counter()
    fused = engine.serve(reqs)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = engine.serve(reqs)
    batch_s = time.perf_counter() - t0
    for r, out, out2 in zip(reqs, fused, again):
        assert len(out.tokens) == r.max_new_tokens, (r.adapter, out.tokens)
        assert ((out.tokens >= 0) & (out.tokens < cfg.vocab_size)).all()
        assert np.array_equal(out.tokens, out2.tokens)
    solo = [engine.serve([r])[0] for r in reqs]
    agree = np.mean([np.mean(f.tokens == s.tokens)
                     for f, s in zip(fused, solo)])
    say(f"D serve: impl={engine.impl} block_t={engine.block_t} "
        f"{len(reqs)} requests x 16 new tokens, all complete and in "
        f"vocab; first batch {first_s:.1f} s (compile included), "
        f"{batch_s:.3f} s per batch after compile; fused-vs-solo token "
        f"agreement {agree:.3f} (not gated); peak HBM so far {peak_gb()}")


# ------------------------------------------------------------ four chips
def four_chips(cfg, seed: int, seq_len: int = 1024, kernels=None) -> None:
    import jax
    import numpy as np
    from repro.cluster.controller import ClusterController
    from repro.launch.mesh import partition_mesh

    kernels = dict(kernels or {})
    devices = jax.devices()[:4]
    mesh = partition_mesh([4], devices)[0]
    jobs = jobs_for(cfg, RANKS_MIXED, seq_len=seq_len)

    # ---- DP over 4 devices vs the same group on one device
    rt_1, run_1 = train("4chip one-device reference", cfg, jobs, seed,
                        **kernels)
    del rt_1
    rt_dp, run_dp = train("4chip dp", cfg, jobs, seed, mesh=mesh,
                          tp_mode="dp", grad_sync="gather", **kernels)
    check_placement(rt_dp, devices)
    del rt_dp
    compare("4chip dp vs one device", run_1, run_dp)

    # ---- pipeline P=2 x D=2 vs DP on the same 4 devices.  4 rows per
    # job so each data shard holds 2 and the 2 micro-batches are legal
    jobs4 = [dataclasses.replace(j, batch_size=4) for j in jobs]
    rt_d4, run_d4 = train("4chip dp (4 rows/job)", cfg, jobs4, seed,
                          mesh=mesh, tp_mode="dp", grad_sync="gather",
                          **kernels)
    del rt_d4
    rt_pp, run_pp = train("4chip pipeline 2x2", cfg, jobs4, seed,
                          mesh=mesh, tp_mode="pipeline", pipeline_stages=2,
                          nano_batches=2, grad_sync="gather", **kernels)
    check_placement(rt_pp, devices)
    del rt_pp
    compare("4chip pipeline vs dp", run_d4, run_pp)

    # ---- controller: two groups on 2+2 chips, concurrently
    ctl = ClusterController(lambda name: cfg, devices=devices, lr=LR,
                            chunk_size=CHUNK, seed=seed, **kernels)
    groups = [jobs_for(cfg, RANKS_MIXED[:2], seq_len=seq_len),
              jobs_for(cfg, RANKS_MIXED[2:], seq_len=seq_len)]
    groups = [[dataclasses.replace(j, job_id=f"g{g}-{j.job_id}")
               for j in js] for g, js in enumerate(groups)]
    for js in groups:
        for j in js:
            ctl.submit(j)
    gkeys = [tuple(j.job_id for j in js) for js in groups]
    ctl.apply_grouping(gkeys, chips=[2, 2])
    t0 = time.perf_counter()
    reports = ctl.run(STEPS)
    wall = time.perf_counter() - t0
    seen = []
    for gk in gkeys:
        rt = ctl._slots[gk].runtime(gk)
        ids = sorted(d.id for d in rt.mesh.devices.flat)
        check_placement(rt, list(rt.mesh.devices.flat))
        rep = reports[gk]
        assert rep.steps == STEPS, (gk, rep.steps)
        assert np.isfinite(np.asarray(rep.per_job_losses)).all(), gk
        say(f"4chip controller group {gk}: device ids {ids}, "
            f"impl={rt.ssm.impl}, {rep.steps} steps, last per-job "
            f"losses {[round(float(x), 4) for x in rep.per_job_losses[-1]]}")
        seen.append(set(ids))
    assert not (seen[0] & seen[1]), seen
    say(f"4chip controller: both groups finished {STEPS} steps "
        f"concurrently on disjoint devices in {wall:.1f} s "
        f"(compile included); peak HBM so far {peak_gb()}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit("chip_smoke.py: no src/repro beside this script; run it "
                 "from a checkout of the repository")
    sys.path.insert(0, src)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke.py: needs a TPU, JAX found "
                 f"{devices[0].platform}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke.py: --chips {args.chips} but JAX found "
                 f"{len(devices)} device(s)")
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache at {enable_compile_cache()}")
    say(f"devices: {len(devices)} x {devices[0].device_kind}")
    cfg = get_config("tinyllama-1.1b")
    if args.chips == 1:
        one_chip(cfg, args.seed)
    else:
        four_chips(dataclasses.replace(cfg, num_layers=4), args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
