"""One run of one cell: set up, check, measure, report.

Set-up builds the group through the program's normal entry,
``GroupRuntime.from_specs``, with the platform's kernel family and token
tile, ``remat=True``, ``chunk_size=4`` and AIMD off (its defaults), fed by
the benchmark's own traffic streams and weights made from the seed on
the device.  Its first chunk (compile included) is the warm-up and the
checked chunk: the same ``dispatch_chunk``/``collect_chunk`` calls and
feed as the window.  The window then runs whole chunks until
``--seconds`` have passed.  After it, the program's state is freed and
the reference follows the first chunk (``check.py``).
"""
from __future__ import annotations

import contextlib
import gc
import glob
import os
import shutil
import sys
import time
from typing import Callable, Dict, Optional

import numpy as np

import catalog
import check
import flops
import traffic_gen
import weights
import xtrace

LR = 1e-3                      # GroupRuntime's default learning rate
CHUNK = 4                      # and its default chunk_size


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def setup_jax(root: str):
    """Put the program on the path and JAX's compile cache inside the
    checkout, at a fixed path."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def chips(jax, n: int, require_tpu: bool) -> list:
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < n):
        raise NoChip(f"cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} devices; JAX found {len(devs)}")
    return devs[:n]


class CompileCounter:
    """Programs lowered, compiled, and found in the persistent cache."""

    def __init__(self):
        from jax import monitoring
        self.lowered = self.compiled = self.cache_hits = 0

        def duration(event, *_args, **_kw):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lowered += 1
            elif event == "/jax/core/compile/backend_compile_duration":
                self.compiled += 1

        def count(event, *_args, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        monitoring.register_event_duration_secs_listener(duration)
        monitoring.register_event_listener(count)

    def snapshot(self):
        return (self.lowered, self.compiled, self.cache_hits)


_COUNTER: Optional[CompileCounter] = None


def counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


def build(cell, seed: int, *, quantize=None,
          fault: Optional[Callable] = None):
    """The cell's ``GroupRuntime`` and its traffic streams."""
    from repro.core.ssm import SharedSuperModel
    from repro.elastic.runtime import GroupRuntime

    cfg = catalog.model_config(cell)
    tr = cell.traffic
    specs = traffic_gen.job_specs(tr, cfg.name)
    streams = traffic_gen.streams(tr, specs, cfg.vocab_size, seed)
    layout = SharedSuperModel(cfg, specs).layout
    params, adapters = weights.program_trees(
        cell.family, cfg, cell.model, [s.rank for s in specs], layout,
        seed)
    rt = GroupRuntime.from_specs(cfg, specs, None, params=params,
                                 adapters=adapters, streams=streams,
                                 quantize=quantize)
    # the runtime holds its own copies where it placed or quantized them
    import jax
    held = {id(a) for a in jax.tree.leaves((rt.params, rt.adapters))}
    for a in jax.tree.leaves((params, adapters)):
        if id(a) not in held:
            a.delete()
    if fault is not None:
        fault(rt)
    return rt, streams


def readout(rt, first_losses) -> Dict:
    """What the check needs of the program after its first chunk, on
    the host."""
    import jax
    ranks = [s.rank for s in rt.specs]
    lay = rt.ssm.layout
    return {"losses": np.asarray(first_losses, np.float64),
            "mu": check.job_leaves(jax.device_get(rt.opt_state.mu), lay,
                                   ranks),
            "p": check.job_leaves(jax.device_get(rt.adapters), lay, ranks)}


def free(rt) -> None:
    import jax
    for a in jax.tree.leaves((rt.params, rt.adapters, rt.opt_state,
                              rt._staged)):
        a.delete()
    rt._staged = None
    rt._step_cache.clear()
    gc.collect()


def peak_bytes(devices: list) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return None if any(p is None for p in peaks) else int(max(peaks))


class Ctx:
    """What a per-layer metric reader gets (``metrics/<name>.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run(root: str, workload: str, *, seed: int, seconds: float,
        trace: bool, t_start: float, require_tpu: bool = True,
        quantize: Optional[str] = None,
        fault: Optional[Callable] = None,
        bench_dir: Optional[str] = None) -> Dict:
    """One run; returns the result line's object.  Raises ``NoChip``
    before any work where the chips are missing."""
    cell = catalog.load_cell(root, workload, bench_dir)
    jax = setup_jax(root)
    devices = chips(jax, cell.chips, require_tpu)
    dev = devices[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    log(f"bench device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} bytes_limit={limit}")

    cc = counter()
    marks = [("start", time.perf_counter() - t_start)]
    rt, streams = build(cell, seed, quantize=quantize, fault=fault)
    marks.append(("built", time.perf_counter() - t_start))
    L = rt.chunk_size
    assert L == CHUNK, (L, CHUNK)
    pending = rt.dispatch_chunk(L, prefetch=L)
    marks.append(("dispatched", time.perf_counter() - t_start))
    rep = rt.collect_chunk(pending)
    marks.append(("collected", time.perf_counter() - t_start))
    first = readout(rt, rep.per_job_losses[:L])
    setup_s = time.perf_counter() - t_start
    lowered, compiled, hits = cc.snapshot()
    log(f"bench setup_s={setup_s!r} marks="
        + " ".join(f"{k}:{v:.2f}" for k, v in marks)
        + f" lowered={lowered} compiled={compiled} cache_hits={hits}"
        f" first_chunk_losses={np.round(first['losses'].sum(1), 4).tolist()}")
    for c in rt._step_cache.values():
        # the compiler's account of the step's HBM, beside the
        # allocator's peak that memory_peak_bytes reports
        ma = c.memory_analysis()
        log(f"bench step_hbm argument_bytes={ma.argument_size_in_bytes}"
            f" temp_bytes={ma.temp_size_in_bytes}"
            f" alias_bytes={ma.alias_size_in_bytes}")

    if trace:
        trace_dir = os.path.join(root, ".bench_trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = jax.profiler.TraceAnnotation
        for s in streams:
            s.span = span
        jax.profiler.start_trace(trace_dir)
    else:
        span = lambda name: contextlib.nullcontext()
    before = cc.snapshot()
    steps = 0
    tw0 = time.perf_counter()
    with span("bench_window"):
        while True:
            with span("bench_dispatch"):
                pending = rt.dispatch_chunk(L, prefetch=L)
            with span("bench_collect"):
                rep = rt.collect_chunk(pending)
            steps += L
            if time.perf_counter() - tw0 >= seconds:
                break
    tw1 = time.perf_counter()
    in_window = sum(a - b for a, b in zip(cc.snapshot()[:2], before[:2]))
    if trace:
        jax.profiler.stop_trace()
    window_s = tw1 - tw0
    window_steps = range(L, L + steps)
    tokens = sum(s.real_tokens[i] for s in streams for i in window_steps)
    losses = np.asarray(rep.losses[L:L + steps])
    failed = int(np.sum(~np.isfinite(losses)))
    peak = peak_bytes(devices)
    log(f"bench window_s={window_s!r} steps={steps} tokens={tokens} "
        f"compiles_in_window={in_window} peak_bytes={peak}")

    if trace:
        kernels = xtrace.kernel_names(
            "".join(c.as_text() for c in rt._step_cache.values()))
    free(rt)
    del rt

    result: Dict = {"attempted": steps, "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        tr = xtrace.load(sorted(files)[-1])
        lo, hi = xtrace.window(tr)
        lengths = np.concatenate([
            traffic_gen.StepLengths(cell.traffic, seed).of(0, k)
            for k in range(len(cell.traffic["jobs"]))])
        ctx = Ctx(trace=tr, lo=lo, hi=hi, kernels=kernels,
                  steps=steps, chips=len(devices), cell=cell,
                  peaks=flops.peaks(dev.device_kind),
                  step_doc_lengths=lengths)
        metrics = {}
        for m in cell.per_layer:
            v = catalog.metric_reader(m["name"], bench_dir)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = [xtrace.total(xtrace.busy(ops, lo, hi))
                for ops in tr.ops.values()]
        device["busy_s"] = float(np.mean(busy)) / 1e9 if busy else 0.0
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = xtrace.breakdown(tr, lo, hi, kernels)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"train_tokens_per_s": tokens / window_s,
                  "peak_hbm_gb": (peak or 0) / 1e9,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}

    ref = check.Reference(cell, seed, L, LR)
    numbers = check.compare(ref, first["losses"], first["mu"], first["p"])
    correct = check.verdict(numbers, cell.limits) and failed == 0
    log(f"bench reference_s={ref.seconds!r} leaves_left_out={ref.left_out}")
    for n in sorted(set(numbers) - set(cell.limits)):
        log(f"bench unlimited {n}={numbers[n]!r}")
    for n in cell.limits:
        log(f"check {n}={numbers[n]!r} limit={cell.limits[n]!r}")
    result.update(correct=bool(correct), metrics=metrics, device=device,
                  _numbers=numbers)
    result["check"] = {n: {"value": numbers[n], "limit": lim}
                       for n, lim in cell.limits.items()}
    return result
