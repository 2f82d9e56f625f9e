"""ElasticEngine — executes scheduler decisions on live training state.

The missing link between the analytic half of the repo (core/scheduler,
cluster/simulator) and the executing half (core/ssm, train): jobs arrive
and finish online, ``AdapterScheduler.schedule`` emits a new grouping,
and the engine diffs it against the running groups, migrating only the
jobs whose membership changed:

    arrival -> schedule -> diff old/new grouping -> migrate state -> run

Groups whose member set is unchanged keep their ``GroupRuntime`` (jitted
step cache included — no recompile, no state movement).  Changed groups
are dissolved member-by-member into ``JobTrainState``s and re-fused,
which is lossless (migrate.py).  Per-job step accounting (train steps
and Adam steps) survives every migration.

Layer map: DESIGN.md §6.
"""
from __future__ import annotations

import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.core import throughput as tp
from repro.core.jobs import JobRuntimeState, LoRAJobSpec
from repro.core.lora import pad_rank
from repro.core.scheduler import AdapterScheduler, SchedulerConfig
from repro.elastic.migrate import JobTrainState, diff_grouping
from repro.elastic.runtime import GroupRuntime, TrainReport
from repro.kernels.ops import kernel_defaults
from repro.models import model as M

GroupKey = Tuple[str, ...]


class ElasticEngine:
    """Full elastic lifecycle over one shared frozen backbone."""

    def __init__(self, cfg: ModelConfig, *, key=None, params=None,
                 scheduler: Optional[AdapterScheduler] = None,
                 impl: Optional[str] = None,
                 block_t: Optional[int] = None, lr: float = 1e-3,
                 lr_fn: Optional[Callable] = None, remat: bool = True,
                 quantize: Optional[str] = None,
                 nano_batches: int = 1, adaptive_nano: bool = False,
                 aimd_max_n: int = 16, nano_order: str = "job",
                 weight_decay: float = 0.0, chunk_size: int = 4,
                 mesh=None, data_axis: str = "data",
                 grad_sync: str = "gather", tp_mode: str = "dp",
                 pipeline_stages: int = 1,
                 checkpoint_dir=None, checkpoint_every: int = 0,
                 seed: int = 0):
        self.cfg = cfg
        self._key = key if key is not None else jax.random.PRNGKey(seed)
        self.params = params if params is not None else \
            M.init_model(jax.random.fold_in(self._key, 0), cfg)
        self.scheduler = scheduler or AdapterScheduler(cfg)
        impl, block_t = kernel_defaults(impl, block_t)
        self.block_t = block_t
        self.seed = seed
        # mesh: every group this engine builds runs sharded (DESIGN.md
        # §8); migration state (JobTrainState) is mesh-agnostic, so jobs
        # move losslessly between engines of different meshes.
        self._rt_kwargs = dict(impl=impl, block_t=block_t, lr=lr,
                               lr_fn=lr_fn, remat=remat, quantize=quantize,
                               nano_batches=nano_batches,
                               adaptive_nano=adaptive_nano,
                               aimd_max_n=aimd_max_n,
                               nano_order=nano_order,
                               weight_decay=weight_decay,
                               chunk_size=chunk_size, seed=seed,
                               mesh=mesh, data_axis=data_axis,
                               grad_sync=grad_sync, tp_mode=tp_mode,
                               pipeline_stages=pipeline_stages,
                               checkpoint_dir=checkpoint_dir,
                               checkpoint_every=checkpoint_every)
        self._parked: Dict[str, JobTrainState] = {}   # active, not grouped
        self._runtimes: Dict[GroupKey, GroupRuntime] = {}
        self.finished: Dict[str, JobTrainState] = {}
        self.regroup_events = 0        # groupings that MOVED running state

    # ----------------------------------------------------------- job set
    @property
    def job_ids(self) -> List[str]:
        ids = list(self._parked)
        for gkey in self._runtimes:
            ids.extend(gkey)
        return ids

    def _r_pad_solo(self, spec: LoRAJobSpec) -> int:
        # SSM padding rule for the stack this job would be born into
        return pad_rank(spec.rank, multiple=min(self.block_t, 16))

    def add_job(self, spec: LoRAJobSpec, key=None) -> JobTrainState:
        """Admit a new job (standard LoRA init, parked until grouped)."""
        assert spec.job_id not in self.job_ids \
            and spec.job_id not in self.finished, f"duplicate {spec.job_id}"
        # crc32, not hash(): Python's str hash is salted per process and
        # would make inits irreproducible across runs with the same seed
        key = key if key is not None else jax.random.fold_in(
            self._key, zlib.crc32(spec.job_id.encode()) % (2 ** 31))
        st = JobTrainState.fresh(spec, self.cfg, key,
                                 r_pad=self._r_pad_solo(spec),
                                 seed=self.seed)
        self._parked[spec.job_id] = st
        return st

    def admit(self, state: JobTrainState):
        """Admit a job with existing state (e.g. restored checkpoint)."""
        assert state.spec.job_id not in self.job_ids
        self._parked[state.spec.job_id] = state

    def remove_job(self, job_id: str) -> JobTrainState:
        """Decouple a job (its group, if any, is dissolved; peers park)."""
        return self._claim(job_id)

    # ----------------------------------------------------- state plumbing
    def _home(self, job_id: str) -> Optional[GroupKey]:
        for gkey in self._runtimes:
            if job_id in gkey:
                return gkey
        return None

    def _dissolve(self, gkey: GroupKey):
        rt = self._runtimes.pop(gkey)
        # a fence can land with the next chunk's batch prefetched; drop
        # it (rewinding the streams) so the exports don't carry stream
        # positions past data the group never trained on
        rt.discard_staged()
        for st in rt.export_all():
            self._parked[st.spec.job_id] = st

    def _claim(self, job_id: str) -> JobTrainState:
        if job_id in self._parked:
            return self._parked.pop(job_id)
        gkey = self._home(job_id)
        assert gkey is not None, f"unknown job {job_id}"
        self._dissolve(gkey)
        return self._parked.pop(job_id)

    # ------------------------------------------------------------ grouping
    def current_grouping(self) -> List[GroupKey]:
        return list(self._runtimes) + [(jid,) for jid in self._parked]

    def ensure_group(self, job_ids: Sequence[str]) -> GroupRuntime:
        """Guarantee a live runtime whose members are exactly *job_ids*,
        migrating members out of their current groups if needed."""
        gkey = tuple(job_ids)
        for existing in self._runtimes:
            if frozenset(existing) == frozenset(gkey):
                return self._runtimes[existing]
        had_running_state = any(self._home(j) is not None for j in gkey)
        states = [self._claim(j) for j in gkey]
        rt = self._build(states)
        self._runtimes[gkey] = rt
        if had_running_state:
            self.regroup_events += 1
        return rt

    def _build(self, states) -> GroupRuntime:
        try:
            return GroupRuntime.from_states(self.cfg, self.params, states,
                                            **self._rt_kwargs)
        except Exception:
            # infeasible group (e.g. mixed seq_len): re-park the claimed
            # states so no job's training state is lost
            for st in states:
                self._parked[st.spec.job_id] = st
            raise

    def set_grouping(self, groups: Sequence[Sequence[str]]) -> Dict[str, list]:
        """Apply a full grouping decision; returns the migration diff."""
        diff = diff_grouping(list(self._runtimes), groups)
        for gkey in diff["dissolve"]:
            self._dissolve(gkey)
        moved = bool(diff["dissolve"])
        for g in diff["build"]:
            gkey = tuple(g)
            had_running_state = any(self._home(j) is not None for j in gkey)
            states = [self._claim(j) for j in gkey]
            self._runtimes[gkey] = self._build(states)
            moved = moved or had_running_state
        if moved:
            self.regroup_events += 1
        return diff

    def reschedule(self, pressure: bool = False,
                   node_of: Optional[Callable[[str], int]] = None
                   ) -> List[GroupKey]:
        """arrival/completion hook: re-run Algorithm 1 over the active
        jobs and migrate live state to the new grouping."""
        jrs = []
        for jid in self.job_ids:
            spec = self._spec_of(jid)
            s = JobRuntimeState(spec=spec, steps_done=self.steps_done(jid))
            s.standalone_step_time = tp.standalone_step_time(
                self.cfg, spec,
                hw=self.scheduler.hw_for(max(spec.gpus, 1)),
                kernel_fused=self.scheduler.sched.kernel_fused,
                ragged_kernels=self.scheduler.sched.ragged_kernels)
            gkey = self._home(jid)
            if gkey is not None:
                s.current_step_time = \
                    self._runtimes[gkey].report.measured_step_time()
            jrs.append(s)
        groups = self.scheduler.schedule(jrs, node_of=node_of,
                                         pressure=pressure)
        grouping = [g.job_ids for g in groups]
        self.set_grouping(grouping)
        return [tuple(g) for g in grouping]

    def _spec_of(self, job_id: str) -> LoRAJobSpec:
        if job_id in self._parked:
            return self._parked[job_id].spec
        gkey = self._home(job_id)
        return self._runtimes[gkey].specs[
            self._runtimes[gkey].index_of(job_id)]

    # ----------------------------------------------------------- execution
    def run_group(self, job_ids: Sequence[str], steps: int,
                  log=None) -> TrainReport:
        return self.ensure_group(job_ids).run(steps, log=log)

    def run(self, steps: int, log=None) -> Dict[GroupKey, TrainReport]:
        """Advance every live group by *steps*; retire finished jobs."""
        # park any stragglers into singleton groups so everyone trains
        for jid in list(self._parked):
            self.ensure_group((jid,))
        reports = {gkey: rt.run(steps, log=log)
                   for gkey, rt in list(self._runtimes.items())}
        self.retire_finished()
        return reports

    def steps_done(self, job_id: str) -> int:
        if job_id in self._parked:
            return self._parked[job_id].steps_done
        if job_id in self.finished:
            return self.finished[job_id].steps_done
        gkey = self._home(job_id)
        return self._runtimes[gkey].steps_done[job_id]

    def job_state(self, job_id: str) -> JobTrainState:
        """Live snapshot (non-destructive) of any known job."""
        if job_id in self._parked:
            return self._parked[job_id]
        if job_id in self.finished:
            return self.finished[job_id]
        gkey = self._home(job_id)
        return self._runtimes[gkey].export(job_id)

    def retire_finished(self) -> List[str]:
        """Move jobs past their step budget out of the active set."""
        done = [jid for jid in self.job_ids
                if self.steps_done(jid) >= self._spec_of(jid).steps_budget]
        for jid in done:
            self.finished[jid] = self._claim(jid)
        return done
