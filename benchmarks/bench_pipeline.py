"""Pipeline-mode benchmark — stage-partitioned super-model with
cross-job nano-batch bubble filling (DESIGN.md §15).

Two headline claims, written to ``BENCH_pipeline.json``:

  * ``bubble``: MEASURED bubble fraction of the fused multi-job nano
    schedule vs the single-job GPipe schedule on the same group (same
    stages, same micro size, same total work).  The fused schedule
    streams every job's nano slices through ONE warm-up/cool-down ramp
    (sum(N_j) + P - 1 ticks); per-job GPipe pays the ramp once per job
    (sum(N_j + P - 1)).  The bubble is measured from the EXECUTED
    schedule: the pipeline step counts the (stage, tick) slots that
    carried a valid micro (the same mask that gates the loss) vs every
    slot its tick loop ran, and surfaces both through the chunk
    metrics (TrainReport.last_metrics) — wall-clock cannot observe the
    bubble on forced-host-device CPU, where all "devices" share the
    same cores and an idle stage frees nothing.  Wall-clock step times
    are still recorded for context.  Needs >= 4 devices (stage x data
    mesh) in THIS process; with fewer the bench fails rather than
    spawning a child (a child could not reach a chip the parent holds).
    On CPU, run it as a script with ``--devices 8``.

  * ``memory_constrained``: a config where DP alone CANNOT fit — the
    fully-replicated residency (tp_mode="dp") exceeds per-chip HBM at
    every flat placement of the group's chips — but the stage-
    partitioned residency (tp_mode="pipeline") fits.  The scheduler's
    pipeline fallback (AdapterScheduler.pipeline_depth) picks the
    depth; the analytic oracle prices the pipeline step vs the as-if
    DP step.  DP's effective step time on this config is infinite
    (it cannot run), so a finite pipeline step beats it by
    feasibility; the as-if ratio is recorded for honesty.

Run as a script to force a virtual device count (bench_controller's
pattern): ``python -m benchmarks.bench_pipeline --devices 8``.
"""
from __future__ import annotations

import os
import sys


def _peek_devices_arg(argv):
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--devices="):
            return a.split("=", 1)[1]
    return None


if __name__ == "__main__":
    _spec = _peek_devices_arg(sys.argv)
    if _spec:
        try:
            _need = int(_spec)
        except ValueError:
            _need = 0
        _flags = os.environ.get("XLA_FLAGS", "")
        if _need > 1 and \
                "xla_force_host_platform_device_count" not in _flags:
            os.environ["XLA_FLAGS"] = (
                f"{_flags} --xla_force_host_platform_device_count={_need}"
            ).strip()

import json
import pathlib
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import throughput as tp
from repro.core.jobs import JobRuntimeState, LoRAJobSpec
from repro.core.nanobatch import pipeline_tick_counts
from repro.core.scheduler import AdapterScheduler, Group, SchedulerConfig

from benchmarks.common import banner

ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT_PATH = ROOT / "BENCH_pipeline.json"

STAGES = 2
MICROS_PER_JOB = 2          # same micro size in both schedules


def _time_steps(rt, steps: int, reps: int) -> float:
    """Min-of-reps per-step wall time of a compiled runtime."""
    rt.run(steps)                                     # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        rt.run(steps)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best


def _occupancy(rt) -> tuple:
    """(useful, total) (stage, tick) slots of the last executed chunk,
    read from the step's instrumented counters."""
    m = rt.report.last_metrics
    useful = int(np.atleast_1d(m["pipe_useful_slots"])[-1])
    slots = int(np.atleast_1d(m["pipe_slots"])[-1])
    return useful, slots


def _bench_bubble(steps: int, reps: int) -> dict:
    """Measured multi-job vs single-job-GPipe bubble on one group."""
    from repro.elastic.runtime import GroupRuntime

    cfg = get_config("tinyllama-1.1b").reduced()
    jobs = [LoRAJobSpec("pa", rank=8, batch_size=16, seq_len=32),
            LoRAJobSpec("pb", rank=4, batch_size=16, seq_len=32)]
    kw = dict(lr=1e-3, impl="xla", block_t=8, remat=False,
              chunk_size=steps, tp_mode="pipeline",
              pipeline_stages=STAGES)

    def build(specs, n):
        rt = GroupRuntime.from_specs(
            cfg, specs, jax.random.PRNGKey(0),
            mesh=jax.make_mesh((len(jax.devices()),), ("data",)),
            nano_batches=n, **kw)
        assert rt.n == n, (rt.n, n)
        return rt

    # fused: both jobs' micros share ONE ramp
    multi = build(jobs, MICROS_PER_JOB * len(jobs))
    t_multi = _time_steps(multi, steps, reps)
    useful_m, slots_m = _occupancy(multi)
    # per-job GPipe: same stages, same 2-row micros, one ramp EACH
    useful_g = slots_g = 0
    t_gpipe_sum = 0.0
    for j in jobs:
        solo = build([j], MICROS_PER_JOB)
        t_gpipe_sum += _time_steps(solo, steps, reps)
        u, s = _occupancy(solo)
        useful_g += u
        slots_g += s
    bub_multi = 1.0 - useful_m / slots_m
    bub_gpipe = 1.0 - useful_g / slots_g

    nanos = [MICROS_PER_JOB] * len(jobs)
    n_multi = sum(nanos)
    ticks_multi, ticks_gpipe = pipeline_tick_counts(nanos, STAGES)
    assert slots_m == ticks_multi * STAGES, (slots_m, ticks_multi)
    assert slots_g == ticks_gpipe * STAGES, (slots_g, ticks_gpipe)
    model_multi = tp.pipeline_bubble_fraction(STAGES, n_multi)
    print(f"  slots: multi {useful_m}/{slots_m} useful   gpipe "
          f"{useful_g}/{slots_g}  (P={STAGES}, {MICROS_PER_JOB} "
          f"micros/job x {len(jobs)} jobs)")
    print(f"  bubble measured: multi {bub_multi:.3f} < gpipe "
          f"{bub_gpipe:.3f}   (model multi: {model_multi:.3f}; "
          f"ticks {ticks_multi} vs {ticks_gpipe})")
    print(f"  wall (shared-core CPU, context only): multi "
          f"{t_multi*1e3:.1f}ms  gpipe sum {t_gpipe_sum*1e3:.1f}ms")
    assert bub_multi < bub_gpipe, (bub_multi, bub_gpipe)
    return {
        "devices": len(jax.devices()), "stages": STAGES,
        "jobs": len(jobs), "micros_per_job": MICROS_PER_JOB,
        "useful_slots_multi": useful_m, "slots_multi": slots_m,
        "useful_slots_gpipe": useful_g, "slots_gpipe": slots_g,
        "ticks_multi": ticks_multi, "ticks_gpipe": ticks_gpipe,
        "bubble_multi_measured": bub_multi,
        "bubble_gpipe_measured": bub_gpipe,
        "bubble_multi_model": model_multi,
        "step_multi_wall_s": t_multi,
        "step_gpipe_sum_wall_s": t_gpipe_sum,
        "bubble_multi_lt_gpipe": bool(bub_multi < bub_gpipe),
    }


def _bench_memory_constrained() -> dict:
    """The fit-rescue story: DP-replicated residency bursts per-chip
    HBM; the smallest legal stage partition fits."""
    cfg = get_config("recurrentgemma-9b")
    chips = 8
    jobs = [LoRAJobSpec(f"m{i}", rank=16, batch_size=4, seq_len=2048,
                        base_model=cfg.name) for i in range(2)]
    sched = AdapterScheduler(cfg, SchedulerConfig(mem_tp_mode="dp"))
    g = Group([JobRuntimeState(spec=j) for j in jobs], chips)

    dp_fits = tp.memory_feasible(cfg, jobs, chips, tp_mode="dp")
    P = sched.pipeline_depth(g)
    assert not dp_fits and P is not None, (dp_fits, P)
    sched.annotate_stages(g)
    assert g.stages == P, (g.stages, P)
    pl_fits = tp.memory_feasible(cfg, jobs, chips, tp_mode="pipeline",
                                 stages=P)
    gb = 1e9
    mem_dp = tp.group_memory_bytes(cfg, jobs, chips, tp_mode="dp") / gb
    mem_pl = tp.group_memory_bytes(cfg, jobs, chips, tp_mode="pipeline",
                                   stages=P) / gb
    nano = 16
    dp_asif = tp.group_step_cost(cfg, jobs, chips,
                                 nano_batches=nano).total
    pl_step = tp.pipeline_step_cost(cfg, jobs, chips, stages=P,
                                    nano_batches=nano).total
    beats = (not dp_fits) or pl_step <= dp_asif
    print(f"  {cfg.name} x{chips} chips: dp residency {mem_dp:.1f}GB "
          f"(fits={dp_fits})   pipeline P={P} {mem_pl:.1f}GB "
          f"(fits={pl_fits})")
    print(f"  step: pipeline {pl_step*1e3:.1f}ms   dp-as-if "
          f"{dp_asif*1e3:.1f}ms (DP cannot run: effective inf) -> "
          f"pipeline_beats_dp={beats}")
    return {
        "model": cfg.name, "chips": chips, "jobs": len(jobs),
        "stages": P, "nano_batches": nano,
        "dp_fits": bool(dp_fits), "pipeline_fits": bool(pl_fits),
        "mem_dp_gb": mem_dp, "mem_pipeline_gb": mem_pl,
        "hbm_usable_gb": tp.V5E.hbm_capacity * 0.9 / gb,
        "scheduler_stages": g.stages,
        "dp_step_asif_s": dp_asif, "pipeline_step_s": pl_step,
        "pipeline_vs_dp_asif_x": dp_asif / pl_step,
        "pipeline_beats_dp": bool(beats),
    }


def run(quick: bool = False) -> dict:
    banner("Pipeline: multi-tenant bubble filling + fit rescue")
    steps = 2 if quick else 4
    reps = 2 if quick else 3
    out = {"config": {"devices": len(jax.devices()), "quick": quick,
                      "stages": STAGES,
                      "model": "tinyllama-1.1b-reduced"}}
    if len(jax.devices()) < 2 * STAGES:
        raise RuntimeError(
            f"bench_pipeline needs >= {2 * STAGES} devices, found "
            f"{len(jax.devices())}; on CPU run `python -m "
            "benchmarks.bench_pipeline --devices 8`")
    out["bubble"] = _bench_bubble(steps, reps)
    out["memory_constrained"] = _bench_memory_constrained()
    OUT_PATH.write_text(json.dumps(out, indent=2) + "\n")
    print(f"  wrote {OUT_PATH}")
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--devices", type=int, default=None,
                    help="force a virtual host device count (script "
                         "mode only; e.g. 8 for the CI leg)")
    a = ap.parse_args()
    run(quick=a.quick)
