"""GroupRuntime — one live fused group (Fig. 3 lifecycle, phases 2-3).

Refactors the old one-shot ``train.train_loop.train_group`` body into an
object that *owns* one SSM's training state — frozen backbone reference,
fused adapter stack, per-job AdamW state, fused batcher, AIMD nano-batch
controller, jitted step cache — and exposes ``run(steps)`` so an elastic
engine can interleave training with regrouping.  State enters and leaves
through ``JobTrainState`` (migrate.py), which is what makes join/leave/
migrate lossless.

Layer map: DESIGN.md §6.
"""
from __future__ import annotations

import copy
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.jobs import LoRAJobSpec
from repro.core.nanobatch import AIMDController
from repro.core.ssm import SharedSuperModel
from repro.data.pipeline import FusedBatcher, JobStream
from repro.elastic.migrate import JobTrainState, fuse_states, unfuse_state
from repro.kernels.ops import kernel_defaults
from repro.models import quant
from repro.optim import adamw
from repro.optim.schedule import constant


@dataclass
class TrainReport:
    steps: int = 0
    samples_per_step: int = 0             # true samples (tile padding excl.)
    losses: List[float] = field(default_factory=list)
    per_job_losses: List[np.ndarray] = field(default_factory=list)
    step_times: List[float] = field(default_factory=list)
    nano_history: List[int] = field(default_factory=list)
    # full metrics dict of the most recent collected chunk (host
    # numpy) — step-mode-specific observables (e.g. the pipeline
    # step's executed-schedule occupancy counters) surface here
    # without widening the report schema per mode
    last_metrics: Optional[Dict[str, np.ndarray]] = None

    @property
    def steps_per_sec(self) -> float:
        return 0.0 if not self.step_times else 1.0 / float(
            np.mean(self.step_times[1:] or self.step_times))

    @property
    def samples_per_sec(self) -> float:
        # each step consumes one fused batch of samples_per_step sequences
        return self.steps_per_sec * max(self.samples_per_step, 1)

    @property
    def last_step_time(self) -> float:
        return self.step_times[-1] if self.step_times else 0.0

    def measured_step_time(self, window: int = 8) -> float:
        """Robust recent step time: min over the last *window* steps
        (min discards jit-compile outliers after a (re)build)."""
        if not self.step_times:
            return 0.0
        return float(min(self.step_times[-window:]))


@dataclass
class PendingChunk:
    """One dispatched-but-uncollected chunk (async on device).

    ``dispatch_chunk`` returns this; the metrics leaves are jax arrays
    whose computation may still be running — nothing blocks until
    ``collect_chunk`` fetches them.  The controller keeps one pending
    chunk per group so disjoint submeshes compute concurrently
    (DESIGN.md §9)."""
    metrics: Any
    length: int
    t0: float
    count_aimd: bool = True
    # stream rng positions AS OF this chunk's data (captured before any
    # prefetch advances the batcher) — what the checkpoint hook must
    # persist so a restore resumes on exactly the next unseen tokens
    stream_states: Optional[List[str]] = None


class GroupRuntime:
    """Owns one fused group's live training state; ``run`` is re-entrant."""

    def __init__(self, cfg: ModelConfig, params, specs: Sequence[LoRAJobSpec],
                 adapters, opt_state, *,
                 streams: Optional[Sequence[JobStream]] = None,
                 steps_done: Optional[Dict[str, int]] = None,
                 lr: float = 1e-3, lr_fn: Optional[Callable] = None,
                 impl: Optional[str] = None,
                 block_t: Optional[int] = None,
                 nano_batches: int = 1, adaptive_nano: bool = False,
                 aimd_max_n: int = 16, nano_order: str = "job",
                 remat: bool = True, quantize: Optional[str] = None,
                 weight_decay: float = 0.0,
                 chunk_size: int = 4, scan_unroll: bool = False,
                 mesh=None, data_axis: str = "data",
                 grad_sync: str = "gather", tp_mode: str = "dp",
                 pipeline_stages: int = 1,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0,
                 publish_pool=None, publish_every: int = 0,
                 seed: int = 0):
        self.cfg = cfg
        self.specs = list(specs)
        # unnamed kernel impl / token tile follow the platform
        impl, block_t = kernel_defaults(impl, block_t)
        # sharded group execution (DESIGN.md §8): fused batch rows shard
        # over the mesh (every axis in tp_mode="dp", the data axis only
        # in tp_mode="auto" where the rest is GSPMD tensor parallelism);
        # adapters + optimizer state replicate.  mesh=None keeps
        # single-device semantics.
        self.data_axis = data_axis
        self.grad_sync = grad_sync
        self.tp_mode = tp_mode
        # tp_mode="pipeline": carve the group's 1-D submesh into a
        # (stage, data) 2-D mesh ONCE, here — placement, batch sharding
        # and the pipeline step all share the carved mesh (DESIGN.md §15)
        if tp_mode == "pipeline":
            if mesh is None:
                raise ValueError("tp_mode='pipeline' needs a mesh")
            from repro.launch.mesh import stage_mesh
            if "stage" not in mesh.axis_names:
                mesh = stage_mesh(mesh, pipeline_stages, axis=data_axis)
            self.pipeline_stages = int(mesh.shape["stage"])
            if self.pipeline_stages < 2:
                raise ValueError(
                    "tp_mode='pipeline' needs pipeline_stages >= 2 "
                    f"(got {self.pipeline_stages}); use tp_mode='dp'")
        else:
            self.pipeline_stages = 1
        self.mesh = mesh
        if mesh is None:
            D = 1
        elif tp_mode == "dp":
            import math
            D = int(math.prod(int(s) for s in mesh.shape.values()))
        else:
            D = int(mesh.shape[data_axis])
        if mesh is not None and grad_sync == "gather" \
                and impl in ("ref", "loop"):
            # fail at construction, not after staging/compile: the
            # autodiffed oracles have no shard-local VJP (DESIGN.md §8)
            raise ValueError(
                f"impl={impl!r} has no shard-local VJP for exact gathered "
                "wgrads; use impl='xla'/'pallas' or grad_sync='psum'")
        self.data_shards = D
        # quantized frozen backbone (models/quant): int8 codes + f32
        # per-channel scales replace the bf16 projection weights BEFORE
        # device placement, so the device-resident shard is half-size
        # and every fused step streams half the backbone bytes.  The
        # fuse/unfuse/migrate contract is untouched — adapters and
        # optimizer state never quantize.  Idempotent on pre-quantized
        # trees (a migrated group reuses the donor's QuantTensors).
        self.quantize = quantize
        params = quant.quantize_params(params, quantize)
        self.ssm = SharedSuperModel(cfg, self.specs, impl=impl,
                                    block_t=block_t, data_shards=D)
        self.batcher = FusedBatcher(self.specs, cfg.vocab_size,
                                    block_t=block_t, seed=seed,
                                    streams=streams, shards=D)
        # own (copy) the trainable state: run() donates these buffers to
        # the chunked step, which would otherwise silently invalidate
        # caller-held references to restored/pre-built arrays
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.data.pipeline import shard_permutation
            from repro.sharding import rules
            repl = NamedSharding(mesh, PartitionSpec())
            self._repl = repl
            if tp_mode == "pipeline":
                # each stage keeps ONLY its slice of the scanned layer
                # stack (backbone shard + every job's adapter/moment
                # slices) resident — the memory win pipeline mode buys
                from repro.core.ssm import scanned_segment_index
                self._scan_si = scanned_segment_index(cfg)
                self._stage_sh = NamedSharding(mesh,
                                               PartitionSpec("stage"))
                self.params = self._put_group_tree(params)
                self.adapters = self._put_group_tree(
                    jax.tree.map(jnp.array, adapters))
                self.opt_state = adamw.AdamWState(
                    jax.device_put(jnp.array(opt_state.step), repl),
                    self._put_group_tree(
                        jax.tree.map(jnp.array, opt_state.mu)),
                    self._put_group_tree(
                        jax.tree.map(jnp.array, opt_state.nu)))
            else:
                # tp_mode="dp": params replicate (full-manual shard_map);
                # "auto": the name-driven rules place them for GSPMD TP
                self.params = jax.device_put(
                    params, repl if tp_mode == "dp"
                    else rules.runtime_param_shardings(mesh, params))
                # copy BEFORE placing: device_put aliases when the source
                # already has the target sharding (e.g. state exported
                # from a runtime on the same mesh), and donation would
                # then delete the caller's buffers
                self.adapters = jax.device_put(
                    jax.tree.map(jnp.array, adapters), repl)
                self.opt_state = jax.device_put(
                    jax.tree.map(jnp.array, opt_state), repl)
            self._perm = shard_permutation(self.batcher.rows_per_job(), D)
            row_axes = (tuple(mesh.axis_names) if tp_mode == "dp"
                        else data_axis)
            self._batch_sharding = NamedSharding(
                mesh, PartitionSpec(None, row_axes))
        else:
            self.params = params
            self.adapters = jax.tree.map(jnp.array, adapters)
            self.opt_state = jax.tree.map(jnp.array, opt_state)
            self._perm = None
            self._batch_sharding = None
        self.steps_done: Dict[str, int] = dict(
            steps_done or {s.job_id: 0 for s in self.specs})
        self.lr_fn = lr_fn or constant(lr)
        # remat (jax.checkpoint on each scanned segment) is the
        # system-wide default — True everywhere (runtime, train_loop,
        # controller, execution backend): it caps the activation
        # high-water at ~one layer's working set + per-layer residuals,
        # which is what lets the memory-priced scheduler pack K jobs per
        # device, at the cost of one extra forward pass (~33% more
        # FLOPs) in the backward.  Fused groups are memory-bound at
        # exactly the compositions tLoRA targets, so trading spare
        # compute for HBM is the right default; flip remat=False only
        # for small models with chips to spare.  Numerics are identical
        # either way (recompute, not approximation), and the scheduler's
        # group_memory_bytes must be told the flag it prices
        # (SchedulerConfig.remat).
        self.remat = remat
        self.weight_decay = weight_decay
        # rank-aware nano pipeline: static job order of segments within
        # each (sharded, job-proportional) nano slice — "rank_desc"
        # leads every slice with its large-rank segments so their
        # bigger adapter-grad collectives overlap small-rank compute
        assert nano_order in ("job", "rank_desc"), nano_order
        self.nano_order = nano_order
        if D > 1 or self.pipeline_stages > 1:
            # legal nano counts must divide EVERY job's per-shard rows
            # (the job-aware nano split keeps per-slice composition
            # equal), and — for the ragged pallas kernels — keep every
            # job's per-slice token count on a rank-bucket tile
            # boundary (static tile metadata; ssm.valid_nano_counts)
            import math
            from repro.core.ssm import valid_nano_counts
            rows_loc = [r // D for r in self.batcher.rows_per_job()]
            nano_rows = math.gcd(*rows_loc)
            legal_kw = (dict(seg_rows=rows_loc,
                             seq_len=self.specs[0].seq_len,
                             block_t=block_t)
                        if impl == "pallas" else {})
            if self.pipeline_stages > 1:
                # the nano slices double as pipeline microbatches: the
                # count must cover the depth (n >= stages) or the tick
                # loop has more warm-up slots than micros to fill them
                legal_kw["stages"] = self.pipeline_stages
            legal = valid_nano_counts(nano_rows,
                                      min(nano_rows, aimd_max_n),
                                      **legal_kw)
        else:
            nano_rows = self.batcher.total_rows()
            legal = None
        self.n = nano_batches
        if self.pipeline_stages > 1:
            if not legal:
                raise ValueError(
                    f"no legal microbatch count covers pipeline depth "
                    f"{self.pipeline_stages} for per-shard rows "
                    f"{nano_rows} (aimd_max_n={aimd_max_n})")
            if self.n not in legal:
                # snap to the closest legal count; ties prefer MORE
                # micros — a deeper split shrinks the fill/drain bubble
                self.n = min(legal,
                             key=lambda l: (abs(l - nano_batches), -l))
        self.aimd = AIMDController(rows=nano_rows, n=self.n,
                                   max_n=min(nano_rows, aimd_max_n),
                                   legal=legal) \
            if adaptive_nano else None
        self.chunk_size = max(1, chunk_size)
        self.scan_unroll = scan_unroll
        self._step_cache: Dict[tuple, Callable] = {}
        # periodic per-job checkpointing (every N collected chunks)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = int(checkpoint_every)
        self._chunks_collected = 0
        # steps_done at each member's most recent checkpoint write
        self.last_checkpoint_step: Dict[str, int] = {}
        # zero-downtime serving publish (DESIGN.md §13): every N
        # collected chunks the members' host-resident snapshots flow
        # into a serve.AdapterPool at the chunk boundary — training
        # never pauses, the pool versions the swap
        self.publish_pool = publish_pool
        self.publish_every = int(publish_every)
        # prefetch buffer for the staged-next-chunk overlap; the rewind
        # marks let discard_staged un-consume a prefetched batch when a
        # handoff fence lands before it is dispatched
        self._staged: Optional[dict] = None
        self._staged_len = 0
        self._staged_rewind: List[str] = []
        self.report = TrainReport(
            samples_per_step=sum(s.batch_size for s in self.specs))

    # ------------------------------------------------------- constructors
    @classmethod
    def from_states(cls, cfg: ModelConfig, params,
                    states: Sequence[JobTrainState],
                    **kw) -> "GroupRuntime":
        """Fuse K portable job states into a live group (join/migrate)."""
        specs = [s.spec for s in states]
        # the ragged layout follows the SSM's per-adapter padding rule —
        # each member keeps its OWN padded width, so this fuse is a
        # copy into per-job segments regardless of the group's max rank
        probe = SharedSuperModel(cfg, specs, impl=kw.get("impl"),
                                 block_t=kw.get("block_t"))
        adapters, opt_state = fuse_states(cfg, states, probe.layout)
        # carry each member's live stream; only stream-less states (e.g.
        # restored checkpoints) start a fresh one
        streams = [s.stream if s.stream is not None
                   else JobStream(s.spec, cfg.vocab_size, kw.get("seed", 0))
                   for s in states]
        return cls(cfg, params, specs, adapters, opt_state,
                   streams=streams,
                   steps_done={s.spec.job_id: s.steps_done for s in states},
                   **kw)

    @classmethod
    def from_specs(cls, cfg: ModelConfig, specs: Sequence[LoRAJobSpec],
                   key, *, params=None, adapters=None,
                   **kw) -> "GroupRuntime":
        """Fresh fused init (the old train_group entry path).  Pre-built
        params/adapters (e.g. restored state) are used when given."""
        if params is None or adapters is None:
            probe = SharedSuperModel(cfg, list(specs),
                                     impl=kw.get("impl"),
                                     block_t=kw.get("block_t"))
            p, a = probe.init(key)
            params = params if params is not None else p
            adapters = adapters if adapters is not None else a
        opt_state = adamw.init(adapters, per_job=len(specs))
        return cls(cfg, params, specs, adapters, opt_state, **kw)

    # ----------------------------------------------------------- training
    @property
    def job_ids(self) -> List[str]:
        return [s.job_id for s in self.specs]

    def index_of(self, job_id: str) -> int:
        return self.job_ids.index(job_id)

    def _put_group_tree(self, tree):
        """Place a params/adapters/moments-structured tree (a dict with
        a ``segments`` list) under this runtime's group placement.  In
        pipeline mode the scanned segment's stacked leaves shard their
        leading cycle axis over "stage" (each stage holds only its
        layer slice); every other leaf — and every leaf in the other
        modes — replicates."""
        if self.tp_mode != "pipeline":
            return jax.device_put(tree, self._repl)
        out = {k: jax.device_put(v, self._repl)
               for k, v in tree.items() if k != "segments"}
        out["segments"] = [
            jax.device_put(s, self._stage_sh if i == self._scan_si
                           else self._repl)
            for i, s in enumerate(tree["segments"])]
        return out

    def _get_step(self, n: int, chunk: int, args) -> Callable:
        """Compiled chunked step for (nano_batches, chunk_len).  Adapters
        and optimizer state are donated: each chunk updates them in place
        on device, so the loop never re-allocates (or re-uploads) the
        trainable state between chunks.  AOT-compiled (lower().compile()
        against *args*) so jit time never lands inside the timed region —
        step_times and the AIMD signal stay compile-clean even on a
        group's very first chunk."""
        key = (n, chunk)
        if key not in self._step_cache:
            fn = self.ssm.make_train_step(lr_fn=self.lr_fn, nano_batches=n,
                                          remat=self.remat,
                                          weight_decay=self.weight_decay,
                                          steps=chunk,
                                          unroll=self.scan_unroll,
                                          mesh=self.mesh,
                                          data_axis=self.data_axis,
                                          grad_sync=self.grad_sync,
                                          tp_mode=self.tp_mode,
                                          pipeline_stages=self.pipeline_stages,
                                          nano_order=self.nano_order)
            jitted = jax.jit(fn, donate_argnums=(1, 2))
            if self.mesh is None or self.tp_mode != "auto":
                # full-manual shard_map (dp and pipeline): no GSPMD
                # axes to constrain
                self._step_cache[key] = jitted.lower(*args).compile()
            else:
                # trace with the mesh active so the backbone's logical
                # sharding constraints resolve onto its auto axes (TP /
                # sequence parallelism over "model"); the manual data
                # axis is excluded — inside shard_map it is local.
                from repro.sharding import use_mesh
                with use_mesh(self.mesh, manual=(self.data_axis,)):
                    self._step_cache[key] = jitted.lower(*args).compile()
        return self._step_cache[key]

    def _stage(self, n: int):
        """Stage the next *n* fused batches on device (leading chunk axis).

        Sharded mode permutes rows into the shard-major layout (each
        shard: every job's next rows/D rows, job-major — see
        data/pipeline.shard_permutation) and places each leaf with rows
        over the data axis, so the host->device transfer is already the
        final layout (no device-side reshard)."""
        batches = self.batcher.next_batches(n)
        if self.mesh is None:
            return {k: jnp.asarray(v) for k, v in batches.items()}
        return {k: jax.device_put(v[:, self._perm], self._batch_sharding)
                for k, v in batches.items()}

    def dispatch_chunk(self, length: Optional[int] = None, *,
                       prefetch: int = 0,
                       count_aimd: Optional[bool] = None) -> PendingChunk:
        """Dispatch one chunk of *length* steps asynchronously.

        Returns immediately after the jitted call — the computation runs
        on this runtime's devices in the background until
        ``collect_chunk`` fetches the metrics.  A batch pre-staged by a
        previous ``prefetch`` is consumed when its length matches;
        *prefetch* > 0 stages the NEXT chunk's batches right after
        dispatch, overlapping host data work with device compute.  The
        split exists so a controller can keep one pending chunk per
        group and round-robin across disjoint submeshes (DESIGN.md §9);
        ``run`` is the single-group convenience loop over it.

        Collect every pending chunk before ``export``/migration:
        adapters are already rebound to the in-flight result while
        ``steps_done`` lags until collection.
        """
        L = int(length or self.chunk_size)
        assert L >= 1
        if self._staged is not None:
            # a mismatched prefetch would orphan stream data the batcher
            # already consumed (breaking the lossless data contract), so
            # it is a caller bug — fail loudly instead of dropping it
            assert self._staged_len == L, (self._staged_len, L)
            staged, self._staged = self._staged, None
        else:
            staged = self._stage(L)
        step_fn = self._get_step(
            self.n, L, (self.params, self.adapters, self.opt_state, staged))
        t0 = time.perf_counter()
        # async dispatch: nothing below blocks until the metrics fetch
        self.adapters, self.opt_state, metrics = step_fn(
            self.params, self.adapters, self.opt_state, staged)
        # snapshot stream positions BEFORE prefetching: the checkpoint
        # hook fires at collect time, after the prefetch has advanced
        # the live streams past data this chunk never trained on —
        # persisting the live position would make a restore skip the
        # prefetched batches and silently fork the trajectory
        streams = None
        if self.checkpoint_every:
            from repro.checkpoint.checkpoint import stream_state
            streams = [stream_state(s) for s in self.batcher.streams]
        if prefetch > 0:                     # overlaps with device compute
            from repro.checkpoint.checkpoint import stream_state
            self._staged_rewind = [stream_state(s)
                                   for s in self.batcher.streams]
            self._staged = self._stage(prefetch)
            self._staged_len = prefetch
        return PendingChunk(metrics=metrics, length=L, t0=t0,
                            count_aimd=L > 1 if count_aimd is None
                            else count_aimd,
                            stream_states=streams)

    def collect_chunk(self, pending: PendingChunk,
                      log: Optional[Callable[[str], None]] = None
                      ) -> TrainReport:
        """Block on *pending*'s metrics and fold them into the report.

        One host sync per chunk; also advances per-job step accounting,
        feeds AIMD, and fires the periodic checkpoint hook."""
        log = log or (lambda s: None)
        rep = self.report
        L = pending.length
        host = jax.device_get(pending.metrics)  # the chunk's one host sync
        dt = (time.perf_counter() - pending.t0) / L
        losses = np.atleast_1d(np.asarray(host["loss"], np.float64))
        per_job = np.atleast_2d(np.asarray(host["per_job_loss"]))
        rep.last_metrics = {k: np.asarray(v) for k, v in host.items()}
        rep.steps += L
        rep.losses.extend(losses.tolist())
        rep.per_job_losses.extend(per_job)
        rep.step_times.extend([dt] * L)
        rep.nano_history.extend([self.n] * L)
        for jid in self.job_ids:
            self.steps_done[jid] += L
        # AIMD (Eq. 2) fed the chunk's mean step time — compile-clean
        # thanks to the AOT-compiled step.  Degenerate single-step
        # tails inside a longer run are skipped (un-amortized
        # dispatch/sync overhead would read as a spurious slowdown
        # inside the controller's 2% noise band); deliberate
        # chunk_size=1 observations are a uniform regime and count.
        if self.aimd is not None and pending.count_aimd:
            self.n = self.aimd.update(dt)
        log(f"steps {rep.steps - L:4d}..{rep.steps - 1:4d} "
            f"loss {losses[-1]:.4f} nano {self.n} dt {dt*1e3:.1f}ms/step")
        self._chunks_collected += 1
        if self.checkpoint_every and \
                self._chunks_collected % self.checkpoint_every == 0:
            self.save_checkpoints(stream_states=pending.stream_states)
        if self.publish_pool is not None and self.publish_every and \
                self._chunks_collected % self.publish_every == 0:
            self.publish_to(self.publish_pool)
        return rep

    def run(self, steps: int,
            log: Optional[Callable[[str], None]] = None,
            chunk_size: Optional[int] = None) -> TrainReport:
        """Advance the whole group by *steps* fused iterations.

        Chunked device-resident execution (DESIGN.md §7): steps run in
        chunks of ``chunk_size`` under one ``lax.scan`` dispatch, with at
        most ONE host sync per chunk — the stacked metrics fetch.  While a
        chunk executes asynchronously on device, the next chunk's batches
        are assembled and staged, double-buffering host data work behind
        device compute.  ``chunk_size=1`` degenerates to the step-at-a-time
        loop (same math — the scan body is the exact single train step).
        Mid-run remainder steps (steps % chunk) run through the (n, 1)
        executable one at a time: a tail-length scan would AOT-compile a
        seconds-scale one-off program per distinct remainder, so the
        compile key space stays capped.  A call with steps < chunk runs
        as ONE chunk of its own length instead — repeated short calls
        (an engine polling between horizons) reuse that one executable
        and keep feeding AIMD uniform observations.
        """
        if steps <= 0:
            return self.report
        chunk = max(1, chunk_size or self.chunk_size)

        def next_len(remaining: int) -> int:
            return chunk if remaining >= chunk else min(1, remaining)

        L = min(chunk, steps)
        done = 0
        while done < steps:
            nxt = next_len(steps - done - L)
            pending = self.dispatch_chunk(L, prefetch=nxt,
                                          count_aimd=L > 1 or chunk == 1)
            self.collect_chunk(pending, log=log)
            done += L
            L = nxt if nxt > 0 else L
        return self.report

    def discard_staged(self):
        """Drop a prefetched-but-undispatched batch, rewinding the data
        streams to their pre-stage positions.

        A handoff fence lands between chunks, where the prefetch for the
        never-to-run next chunk has already advanced the live streams.
        Exporting with that advance in place would skip data the job
        never trained on — rewinding first keeps the lossless contract's
        data half exact across a dissolve."""
        if self._staged is None:
            return
        from repro.checkpoint.checkpoint import restore_stream_state
        for s, mark in zip(self.batcher.streams, self._staged_rewind):
            restore_stream_state(s, mark)
        self._staged = None
        self._staged_len = 0

    def warm(self, lengths: Optional[Sequence[int]] = None) -> float:
        """AOT-compile the chunked step(s) this runtime will dispatch,
        off the training-critical path (DESIGN.md §11).

        Stages a probe batch purely for its shapes/shardings, then
        rewinds the streams — warming must not consume data, or the
        first real chunk would fork the trajectory.  Returns the wall
        seconds spent compiling (the regroup lifecycle's compile_s)."""
        from repro.checkpoint.checkpoint import (restore_stream_state,
                                                 stream_state)
        lengths = [self.chunk_size] if lengths is None else list(lengths)
        t0 = time.perf_counter()
        for L in lengths:
            L = max(1, int(L))
            if (self.n, L) in self._step_cache:
                continue
            marks = [stream_state(s) for s in self.batcher.streams]
            staged = self._stage(L)
            for s, mark in zip(self.batcher.streams, marks):
                restore_stream_state(s, mark)
            self._get_step(self.n, L, (self.params, self.adapters,
                                       self.opt_state, staged))
        return time.perf_counter() - t0

    def refresh_member(self, state: JobTrainState):
        """Replay-exact handoff of an overlapped migration: overwrite
        one member's packed slices (adapter + Adam moments + per-job
        Adam step), stream and step accounting with a FRESHER export of
        the same job.

        The double-buffered destination is assembled from a stale
        snapshot — good enough for layout/shapes/compile, which depend
        only on specs — while the source keeps stepping; once the source
        fences, its authoritative export lands here by pure copy
        (insert_job into the job's own padded segment), making the
        handoff bit-identical to a stop-the-world rebuild at the fence
        boundary.  Only legal before this runtime's first step and
        before any staging (a staged batch would hold the stale stream's
        data)."""
        assert self.report.steps == 0, \
            "refresh_member after stepping would discard trained state"
        assert self._staged is None, \
            "refresh_member after staging would train on stale data"
        from repro.checkpoint.checkpoint import insert_job
        idx = self.index_of(state.spec.job_id)
        off, r_cap = self.ssm.layout.slice_of(idx)
        r = state.spec.rank
        adapters = insert_job(self.adapters, off, r, state.adapter, r_cap)
        mu = insert_job(self.opt_state.mu, off, r, state.mu, r_cap)
        nu = insert_job(self.opt_state.nu, off, r, state.nu, r_cap)
        step = self.opt_state.step.at[idx].set(int(state.opt_step))
        if self.mesh is not None:
            adapters = self._put_group_tree(adapters)
            mu = self._put_group_tree(mu)
            nu = self._put_group_tree(nu)
            step = jax.device_put(step, self._repl)
        self.adapters = adapters
        self.opt_state = adamw.AdamWState(step, mu, nu)
        self.steps_done[state.spec.job_id] = state.steps_done
        if state.stream is not None:
            self.batcher.streams[idx] = copy.deepcopy(state.stream)

    # -------------------------------------------------------- checkpoints
    def save_checkpoints(self, directory: Optional[str] = None, *,
                         stream_states: Optional[List[str]] = None
                         ) -> List[str]:
        """Write every member's per-job checkpoint (adapter + Adam
        moments + per-job Adam step + data-stream rng position) to
        ``<dir>/<job_id>.npz`` — the portable format a job restores from
        into ANY controller partition (checkpoint/checkpoint.py).

        ``stream_states`` overrides the live rng positions — the
        periodic hook passes the pre-prefetch snapshot so the persisted
        position matches the persisted adapter state."""
        from repro.checkpoint.checkpoint import save_job, stream_state
        directory = directory or self.checkpoint_dir
        assert directory, "no checkpoint_dir configured"
        if stream_states is None:
            stream_states = [stream_state(s) for s in self.batcher.streams]
        step_vec = np.atleast_1d(np.asarray(
            jax.device_get(self.opt_state.step)))
        paths = []
        for idx, spec in enumerate(self.specs):
            off, _ = self.ssm.layout.slice_of(idx)
            path = os.path.join(directory, f"{spec.job_id}.npz")
            save_job(path, spec.job_id, off, spec.rank, self.adapters,
                     self.opt_state,
                     step=int(step_vec[idx % step_vec.size]),
                     meta={"steps_done": self.steps_done[spec.job_id],
                           "stream": stream_states[idx]})
            # bounded-staleness audit trail: the supervisor checks
            # measured steps-lost per fault against this high-water mark
            self.last_checkpoint_step[spec.job_id] = \
                self.steps_done[spec.job_id]
            paths.append(path)
        return paths

    # ---------------------------------------------------------- migration
    def export(self, job_id: str) -> JobTrainState:
        """Non-destructive snapshot of one member in portable form.

        The data stream is deep-copied so the snapshot's rng position is
        frozen at the snapshotted adapter/opt state — the live runtime
        advancing afterwards cannot corrupt it (and vice versa)."""
        idx = self.index_of(job_id)
        return unfuse_state(self.adapters, self.opt_state, idx,
                            self.specs[idx], layout=self.ssm.layout,
                            steps_done=self.steps_done[job_id],
                            stream=copy.deepcopy(self.batcher.streams[idx]))

    def export_all(self) -> List[JobTrainState]:
        return [self.export(jid) for jid in self.job_ids]

    # ----------------------------------------------------------- serving
    def publish_to(self, pool, job_ids: Optional[Sequence[str]] = None
                   ) -> Dict[str, int]:
        """Zero-downtime publish into a serving ``AdapterPool``.

        Exports each member's host-resident ``unfuse_state`` snapshot
        (non-destructive — ``export`` device_gets a copy, the live
        fused stack keeps training) and publishes it under the job id.
        Call between chunks, or let the ``publish_every`` hook fire it
        at collect time; an in-flight serving batch keeps the stack it
        was launched with, the next ``acquire`` sees the new version.
        Returns {job_id: published version}.
        """
        return {jid: pool.publish_state(self.export(jid))
                for jid in (job_ids if job_ids is not None
                            else self.job_ids)}
