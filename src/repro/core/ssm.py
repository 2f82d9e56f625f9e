"""Shared Super-Model (SSM) — the paper's core abstraction (§3.2).

``SharedSuperModel`` consolidates K LoRA jobs sharing one frozen backbone
into a single executable model:

  * backbone operators run once over the *union* of all jobs' batches
    (job-major concatenation, tile-aligned — see data/pipeline.FusedBatcher);
  * adapters stay job-private branches, packed ragged ``(L, d, R)`` /
    ``(L, R, d)`` with per-adapter padded rank segments
    (core/lora.RankLayout) and executed by the rank-bucketed ragged
    multi-LoRA kernels (§3.3) — a mixed-rank group does true-rank work,
    not K·r_max;
  * per-job loss normalization keeps forward/backward/optimizer semantics
    *identical* to isolated training (the paper's lossless claim —
    validated by tests/test_lossless.py).

The fused model is handed as ONE composite function to the existing
parallelism planner — here XLA GSPMD via ``jax.jit`` + ``NamedSharding``
(DESIGN.md §3: the JAX-native analogue of Megatron/Metis planning).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.core.jobs import LoRAJobSpec
from repro.core.lora import MultiLoRA, RankLayout, pad_rank
from repro.models import model as M
from repro.optim import adamw


@dataclass
class SharedSuperModel:
    """One fused group: frozen backbone + K stacked adapters."""
    cfg: ModelConfig
    jobs: List[LoRAJobSpec]
    # fused-LoRA kernel impl (ref|pallas|xla|loop) and token tile; None
    # = the platform's (ops.kernel_defaults: pallas/128 on TPU)
    impl: Optional[str] = None
    block_t: Optional[int] = None
    data_shards: int = 1         # data-parallel degree (DESIGN.md §8):
    #                              row counts pad so every job splits evenly
    #                              over the shards with per-shard tile
    #                              alignment; 1 = single-device semantics

    ranks: np.ndarray = field(init=False)
    scalings: np.ndarray = field(init=False)
    layout: RankLayout = field(init=False)

    def __post_init__(self):
        from repro.kernels.ops import kernel_defaults   # kernels import core
        assert self.jobs, "SSM needs at least one job"
        self.impl, self.block_t = kernel_defaults(self.impl, self.block_t)
        self.ranks = np.array([j.rank for j in self.jobs], np.int32)
        self.scalings = np.array([j.scaling for j in self.jobs], np.float32)
        # pad EACH job's rank to a small sublane multiple, NOT the token
        # tile (ranks are a contraction dim; padding 16 -> 128 would 8x
        # the LoRA flops — §Perf iteration 3 in EXPERIMENTS.md) and NOT
        # the group max: the packed ragged layout gives every adapter
        # its own padded segment, so a {4,...,4,64} group stores and
        # computes Σ r_pad_k lanes instead of K·64 (§3.3 rank-aware
        # tiles, taken into storage).
        self.layout = RankLayout(tuple(int(r) for r in self.ranks),
                                 multiple=min(self.block_t, 16))

    @property
    def r_pad(self) -> int:
        """Widest per-adapter padded rank (legacy name; the packed rank
        width is ``layout.total``)."""
        return self.layout.max_r_pad

    # -------------------------------------------------------------- build
    @property
    def num_jobs(self) -> int:
        return len(self.jobs)

    def init(self, key) -> Tuple[dict, dict]:
        """(frozen backbone params, trainable fused adapter stack)."""
        k1, k2 = jax.random.split(key)
        params = M.init_model(k1, self.cfg)
        adapters = M.init_adapters(k2, self.cfg,
                                   jnp.asarray(self.ranks),
                                   layout=self.layout)
        return params, adapters

    def _rows_for(self, job: LoRAJobSpec) -> int:
        """Tile/shard-aligned row count per job (mirrors FusedBatcher)."""
        from repro.core.jobs import tile_rows
        return tile_rows(job.batch_size, job.seq_len, self.block_t,
                         shards=self.data_shards)

    def rows_per_job(self) -> List[int]:
        return [self._rows_for(j) for j in self.jobs]

    def lora_ctx(self, adapter_ids: jax.Array, *,
                 axis_name: Optional[str] = None,
                 row_solo_pos: Optional[jax.Array] = None,
                 grad_sync: str = "gather",
                 nano_order: Optional[Tuple[int, ...]] = None) -> MultiLoRA:
        """Apply context.  With ``axis_name`` the context is shard-local:
        *adapter_ids* covers one data shard's rows, segment geometry is
        the per-shard layout (global rows / data_shards), and the exact
        wgrads reassemble solo order via *row_solo_pos*.  ``nano_order``
        is the static job order of segments inside a job-proportional
        nano slice (the rank-bucketed pipeline ordering)."""
        rows = self.rows_per_job()
        if axis_name is not None:
            rows = [r // self.data_shards for r in rows]
        return MultiLoRA(adapter_ids=adapter_ids,
                         ranks=jnp.asarray(self.ranks),
                         scalings=jnp.asarray(self.scalings),
                         impl=self.impl, block_t=self.block_t,
                         seg_rows=max(rows),
                         equal_segments=len(set(rows)) == 1,
                         layout=self.layout,
                         rows_all=tuple(rows),
                         nano_order=nano_order,
                         axis_name=axis_name,
                         row_solo_pos=row_solo_pos,
                         shards=self.data_shards,
                         local_rows=(sum(rows) if axis_name is not None
                                     else None),
                         grad_sync=grad_sync)

    # --------------------------------------------------------- train step
    def make_train_step(self, *, lr_fn: Callable, nano_batches: int = 1,
                        remat: bool = True,
                        weight_decay: float = 0.0,
                        steps: Optional[int] = None,
                        unroll: bool = False,
                        mesh=None, data_axis: str = "data",
                        grad_sync: str = "gather",
                        tp_mode: str = "dp",
                        pipeline_stages: int = 1,
                        nano_order: str = "job") -> Callable:
        """Build the fused train step (grad-accumulated over nano-batches).

        Nano-batching (§3.3) splits the fused batch along the batch dim
        into N slices executed under ``lax.scan``; adapter grads accumulate
        across slices and the optimizer applies once.  Per-job token
        denominators are computed over the FULL batch first, so the result
        is bit-comparable to N=1 (lossless under re-granulation).

        ``steps`` != None returns the *chunked* device-resident variant:
        a ``lax.scan`` over a (steps, ...) stack of pre-staged batches
        carrying (adapters, opt_state) on device, returning metrics as
        stacked arrays so the host syncs once per chunk instead of once
        per step (DESIGN.md §7).  Jit it with ``donate_argnums=(1, 2)``
        so each chunk reuses the adapter/optimizer buffers in place.
        ``unroll=True`` unrolls the chunk scan (XLA while-loop carries
        cost real per-iteration overhead on some backends; unrolling
        trades ~chunk× compile time for loop-free step code — the perf
        configuration used by benchmarks/bench_step_loop.py).

        ``mesh`` != None returns the SHARDED variant (DESIGN.md §8): the
        whole step (chunk scan included) runs under ``shard_map``, with
        fused batch rows sharded in the shard-major layout of
        ``data/pipeline.shard_permutation`` and adapters + optimizer
        state replicated (that IS the paper's memory win — §5).
        ``tp_mode`` places the non-data mesh axes: "dp" (default) folds
        EVERY mesh axis into execution-time row sharding (full-manual
        shard_map, collectives over the flattened axis tuple); "auto"
        keeps rows over *data_axis* only and leaves the remaining axes
        to GSPMD as partial-auto tensor parallelism driven by the
        name-driven rules + the backbone's sharding constraints —
        currently blocked on CPU XLA for scan-bearing models (see
        DESIGN.md §8 limitations); "pipeline" carves the submesh into
        ``pipeline_stages`` stage sub-slices and runs the scanned layer
        stack as a 1F1B-style pipeline whose microbatches are the
        job-wise nano slices — the large-backbone path (DESIGN.md §15):
        each stage holds 1/P of the scanned backbone + adapters + Adam
        moments, and because the whole schedule stays a fully-manual
        shard_map, the grad-through-scan limitation of "auto" never
        applies.  ``grad_sync`` picks the cross-shard
        gradient strategy: "gather" (default) makes adapter grads
        bit-exact w.r.t. solo execution via the shard-local kernel
        VJPs; "psum" reduces partial wgrads with one all-reduce per
        adapter leaf (cheaper, float-associativity-close instead of
        bit-equal, and the only mode the autodiffed "ref"/"loop" impls
        support).  ``nano_order`` picks the static job order of the
        segments inside each (sharded, job-proportional) nano slice:
        "job" (index order, the historical layout) or "rank_desc" — the
        rank-bucketed pipeline ordering of §3.3: large-rank segments
        lead each slice, so their (larger) adapter-gradient collectives
        issue earliest in the backward and overlap the small-rank
        segments' remaining compute.
        """
        cfg, K = self.cfg, self.num_jobs
        assert nano_order in ("job", "rank_desc"), nano_order
        if mesh is not None:
            if tp_mode == "pipeline":
                return self._make_pipeline_step(
                    lr_fn=lr_fn, nano_batches=nano_batches, remat=remat,
                    weight_decay=weight_decay, steps=steps, unroll=unroll,
                    mesh=mesh, data_axis=data_axis, grad_sync=grad_sync,
                    stages=pipeline_stages, nano_order=nano_order)
            return self._make_sharded_step(
                lr_fn=lr_fn, nano_batches=nano_batches, remat=remat,
                weight_decay=weight_decay, steps=steps, unroll=unroll,
                mesh=mesh, data_axis=data_axis, grad_sync=grad_sync,
                tp_mode=tp_mode, nano_order=nano_order)

        def train_step(params, adapters, opt_state, batch):
            denom = _per_job_token_counts(batch, K, causal=cfg.causal)

            def nano_loss(ad, nb):
                lora = self.lora_ctx(nb["adapter_ids"])
                return M.loss_fn(cfg, params, ad, lora, nb, remat=remat,
                                 per_job_denom=denom)

            grad_fn = jax.grad(nano_loss, has_aux=True)

            if nano_batches == 1:
                grads, aux = grad_fn(adapters, batch)
                per_job = aux["per_job"]
            else:
                nb_batch = _reshape_nano(batch, nano_batches)
                zero_g = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), adapters)

                def body(carry, nb):
                    g_acc, pj_acc = carry
                    g, aux = grad_fn(adapters, nb)
                    g_acc = jax.tree.map(
                        lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                    return (g_acc, pj_acc + aux["per_job"]), None

                (grads, per_job), _ = jax.lax.scan(
                    body, (zero_g, jnp.zeros((K,), jnp.float32)), nb_batch)

            lr = lr_fn(opt_state.step)
            new_adapters, new_opt = adamw.update(
                grads, opt_state, adapters, lr=lr,
                weight_decay=weight_decay,
                col_jobs=self.layout.col_jobs)
            metrics = {"loss": per_job.sum(), "per_job_loss": per_job,
                       "lr": lr}
            return new_adapters, new_opt, metrics

        if steps is None:
            return train_step

        def chunked_step(params, adapters, opt_state, batches):
            """batches: the train_step batch dict with a leading (steps,)
            chunk axis (FusedBatcher.next_batches).  The scan body is the
            exact single train_step, so per-step math is unchanged."""

            def body(carry, b):
                ad, opt = carry
                ad, opt, m = train_step(params, ad, opt, b)
                return (ad, opt), m

            (new_adapters, new_opt), metrics = jax.lax.scan(
                body, (adapters, opt_state), batches, unroll=unroll)
            return new_adapters, new_opt, metrics   # metrics stacked (steps,)

        return chunked_step

    def _make_sharded_step(self, *, lr_fn, nano_batches, remat,
                           weight_decay, steps, unroll, mesh, data_axis,
                           grad_sync, tp_mode,
                           nano_order: str = "job") -> Callable:
        """shard_map-wrapped train step — see make_train_step docstring.

        The body is the exact single-device train step evaluated on this
        shard's rows: per-job token denominators are psum'ed (integer-
        valued f32 sums — exact in any order), the loss the gradient
        flows through is the shard's partial (its cotangents are the
        same 1/denom scalars solo produces), and cross-token adapter
        wgrads are either gathered-exact (kernels/ops.py shard-local
        VJPs) or psum'ed.  The optimizer then updates replicated state
        identically on every shard.
        """
        from jax.sharding import PartitionSpec as P
        from repro.data.pipeline import shard_permutation

        cfg, K = self.cfg, self.num_jobs
        if tp_mode == "dp":
            # every mesh axis contributes row sharding (full manual)
            dp_axes = tuple(mesh.axis_names)
        else:
            assert tp_mode == "auto", tp_mode
            dp_axes = (data_axis,)
        axis = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        D = int(math.prod(int(mesh.shape[a]) for a in dp_axes))
        assert self.data_shards == D, \
            (f"SSM built for data_shards={self.data_shards}, mesh "
             f"executes {D}-way — construct SharedSuperModel("
             f"data_shards={D})")
        rows = self.rows_per_job()
        rows_loc = [r // D for r in rows]
        exact = grad_sync == "gather"
        if exact and self.impl in ("ref", "loop"):
            raise ValueError(
                f"impl={self.impl!r} has no shard-local VJP for exact "
                "gathered wgrads; use impl='xla'/'pallas' or "
                "grad_sync='psum'")
        # solo position of each shard-major row: shardmajor[p] holds solo
        # row perm[p], so the (R,) perm itself, sharded over the dp
        # axes, hands every shard its rows' solo positions (shard
        # identity without axis_index — unsupported under partial-auto
        # on this backend)
        perm = shard_permutation(rows, D)
        seg_order = None
        if nano_batches > 1:
            g = math.gcd(*rows_loc)
            assert g % nano_batches == 0, \
                (f"nano_batches={nano_batches} must divide every job's "
                 f"per-shard rows {rows_loc}")
            if self.impl == "pallas":
                # ragged kernel legality: every job's per-slice token
                # count must stay whole token tiles, or the static
                # rank-bucket tile metadata cannot describe the slice
                # (valid_nano_counts(seg_rows=...) pre-filters AIMD to
                # exactly this set)
                S = self.jobs[0].seq_len
                assert all((r * S) % (nano_batches * self.block_t) == 0
                           for r in rows_loc), \
                    (f"nano_batches={nano_batches} breaks rank-bucket "
                     f"tile alignment for per-shard rows {rows_loc} "
                     f"(seq_len={S}, block_t={self.block_t})")
            seg_order = tuple(
                sorted(range(K), key=lambda k: (-int(self.ranks[k]), k))
                if nano_order == "rank_desc" else range(K))
        # XLA's SPMD partitioner cannot take grad-through-scan inside a
        # partially-manual shard_map: with a live (>1) GSPMD "model"
        # axis the layer scan must unroll (same per-layer math — the
        # lossless contract is unaffected; see _apply_segment)
        auto = frozenset(a for a in mesh.axis_names if a not in dp_axes)
        unroll_layers = any(int(mesh.shape[a]) > 1 for a in auto)

        def train_step(params, adapters, opt_state, batch, row_solo):
            # batch: THIS shard's rows (shard-major layout, job-major
            # within the shard).  Denominators are global — psum of
            # integer-valued counts is exact; clip AFTER the psum (a
            # per-shard clip would inflate jobs whose shard slice is
            # all padding).
            denom = jnp.clip(jax.lax.psum(
                _per_job_token_counts(batch, K, causal=cfg.causal,
                                      clip=False), axis), 1)

            def nano_loss(ad, nb):
                nb = dict(nb)
                rp = nb.pop("_row_solo")
                lora = self.lora_ctx(nb["adapter_ids"],
                                     axis_name=axis,
                                     row_solo_pos=rp,
                                     grad_sync=grad_sync,
                                     nano_order=seg_order)
                return M.loss_fn(cfg, params, ad, lora, nb, remat=remat,
                                 per_job_denom=denom,
                                 unroll_layers=unroll_layers)

            grad_fn = jax.grad(nano_loss, has_aux=True)
            batch = dict(batch)
            batch["_row_solo"] = row_solo

            if nano_batches == 1:
                grads, aux = grad_fn(adapters, batch)
                per_job = aux["per_job"]
            else:
                nb_batch = _reshape_nano_jobwise(batch, nano_batches,
                                                 rows_loc, order=seg_order)
                zero_g = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), adapters)

                def body(carry, nb):
                    g_acc, pj_acc = carry
                    g, aux = grad_fn(adapters, nb)
                    g_acc = jax.tree.map(
                        lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                    return (g_acc, pj_acc + aux["per_job"]), None

                (grads, per_job), _ = jax.lax.scan(
                    body, (zero_g, jnp.zeros((K,), jnp.float32)), nb_batch)

            if not exact:
                # classic DP: one all-reduce per adapter leaf; metrics too
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g, axis), grads)
                per_job = jax.lax.psum(per_job, axis)
            lr = lr_fn(opt_state.step)
            new_adapters, new_opt = adamw.update(
                grads, opt_state, adapters, lr=lr,
                weight_decay=weight_decay,
                col_jobs=self.layout.col_jobs)
            metrics = {"loss": per_job.sum(), "per_job_loss": per_job,
                       "lr": lr}
            return new_adapters, new_opt, metrics

        if steps is None:
            inner, batch_lead = train_step, ()
        else:
            def chunked_step(params, adapters, opt_state, batches,
                             row_solo):
                def body(carry, b):
                    ad, opt = carry
                    ad, opt, m = train_step(params, ad, opt, b, row_solo)
                    return (ad, opt), m

                (new_adapters, new_opt), metrics = jax.lax.scan(
                    body, (adapters, opt_state), batches, unroll=unroll)
                return new_adapters, new_opt, metrics

            inner, batch_lead = chunked_step, (None,)

        row_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        batch_spec = P(*batch_lead, row_spec)

        def stepfn(params, adapters, opt_state, batches):
            b_specs = jax.tree.map(lambda _: batch_spec, batches)
            fn = jax.shard_map(inner, mesh=mesh,
                               in_specs=(P(), P(), P(), b_specs,
                                         P(row_spec)),
                               out_specs=(P(), P(), P()),
                               axis_names=frozenset(dp_axes),
                               check_vma=False)
            return fn(params, adapters, opt_state, batches,
                      jnp.asarray(perm, jnp.int32))

        return stepfn

    def _make_pipeline_step(self, *, lr_fn, nano_batches, remat,
                            weight_decay, steps, unroll, mesh, data_axis,
                            grad_sync, stages,
                            nano_order: str = "job") -> Callable:
        """Stage-partitioned pipeline train step (DESIGN.md §15).

        The group's submesh is carved into a (stage=P, data=D) 2-D mesh;
        the ONE scanned segment's backbone stacks, adapter slices and
        Adam moments shard their leading layer axis over "stage" (each
        stage holds ``repeats/P`` contiguous cycles), while everything
        unscanned (embed, ln_f, head, frontend, head/tail segments)
        replicates.  The batch shards rows over the data axis ONLY and
        REPLICATES over stage, so every stage sub-slice sees identical
        local rows — the pre/tail segments run redundantly on all
        stages (cheap: they are a few unscanned layers) and only the
        scanned stack pipelines.

        Schedule: the N job-wise nano slices become pipeline
        microbatches driven through T = N + P - 1 ticks; at tick t stage
        s runs micro ``clip(t - s, 0, N-1)`` on its local cycles and
        hands the activation to stage s+1 via ``lax.ppermute``.  With
        K jobs contributing nanos the fill/drain bubble (P-1 ticks) is
        paid ONCE for the whole multi-job schedule instead of once per
        job — the multi-tenant bubble-filling win priced by
        ``throughput.pipeline_bubble_fraction``.

        Losslessness: the differentiated loss is each device's LOCAL
        partial (psum transposes inflate cotangents by axis size — the
        same rule the DP sharded step follows), where-masked to the
        owning stage: CE + tail aux on the last stage, pre-segment aux
        on stage 0, scanned aux on valid ticks.  Spurious warm-up /
        cool-down computations (clipped micro indices) land outside the
        collected ``outs[P-1:P-1+N]`` window, so they receive exactly
        zero cotangent; ppermute's transpose chains the real cotangents
        back through the stages, which keeps adapter wgrads exact under
        grad_sync="gather" (the kernel VJPs' data-axis collectives run
        congruently on every stage row).
        """
        from jax.sharding import PartitionSpec as P
        from repro.data.pipeline import shard_permutation
        from repro.launch.mesh import stage_mesh
        from repro.models.layers import rms_norm

        cfg, K = self.cfg, self.num_jobs
        P_st = int(stages)
        assert P_st >= 2, f"pipeline needs stages >= 2, got {P_st}"
        if "stage" not in mesh.axis_names:
            mesh = stage_mesh(mesh, P_st, axis=data_axis)
        assert int(mesh.shape["stage"]) == P_st, (dict(mesh.shape), P_st)
        D = int(mesh.shape[data_axis])
        assert self.data_shards == D, \
            (f"SSM built for data_shards={self.data_shards}, pipeline "
             f"mesh executes {D}-way data parallel — construct "
             f"SharedSuperModel(data_shards={D})")
        exact = grad_sync == "gather"
        if exact and self.impl in ("ref", "loop"):
            raise ValueError(
                f"impl={self.impl!r} has no shard-local VJP for exact "
                "gathered wgrads; use impl='xla'/'pallas' or "
                "grad_sync='psum'")
        plan = M.segment_plan(cfg)
        si = scanned_segment_index(cfg)
        seg = plan[si]
        if seg.repeats % P_st:
            raise ValueError(
                f"stages={P_st} does not divide the scanned stack's "
                f"{seg.repeats} cycle(s); legal pipeline depths for "
                f"{cfg.name}: "
                f"{[p for p in range(1, seg.repeats + 1) if seg.repeats % p == 0]}")
        seg_local = dataclasses.replace(seg, repeats=seg.repeats // P_st)
        rows = self.rows_per_job()
        rows_loc = [r // D for r in rows]
        N = int(nano_batches)
        g = math.gcd(*rows_loc) if len(rows_loc) > 1 else rows_loc[0]
        assert g % N == 0, \
            (f"nano_batches={N} must divide every job's per-shard "
             f"rows {rows_loc}")
        if self.impl == "pallas":
            S_len = self.jobs[0].seq_len
            assert all((r * S_len) % (N * self.block_t) == 0
                       for r in rows_loc), \
                (f"nano_batches={N} breaks rank-bucket tile alignment "
                 f"for per-shard rows {rows_loc}")
        perm = shard_permutation(rows, D)
        seg_order = tuple(
            sorted(range(K), key=lambda k: (-int(self.ranks[k]), k))
            if nano_order == "rank_desc" else range(K))
        # static micro-split geometry: micro i holds rows [i*r_j/N,
        # (i+1)*r_j/N) of EVERY job (job-proportional, like the DP nano
        # split) so each micro is itself a mini fused batch
        idx_np = _nano_index(rows_loc, N, order=seg_order)
        inv_np = np.argsort(idx_np)
        B_loc = int(sum(rows_loc))
        Bm = B_loc // N
        ring_perm = [(i, (i + 1) % P_st) for i in range(P_st)]

        def train_step(params, adapters, opt_state, batch, row_solo):
            denom = jnp.clip(jax.lax.psum(
                _per_job_token_counts(batch, K, causal=cfg.causal,
                                      clip=False), data_axis), 1)
            s_idx = jax.lax.axis_index("stage")
            first = s_idx == 0
            last = s_idx == P_st - 1

            def nano_loss(ad, nb):
                nb = dict(nb)
                rp = nb.pop("_row_solo")
                lora_full = self.lora_ctx(nb["adapter_ids"],
                                          axis_name=data_axis,
                                          row_solo_pos=rp,
                                          grad_sync=grad_sync)
                ad_segs = ad["segments"]
                x, text_off = M.embed_inputs(cfg, params, nb)
                B, S, d = x.shape
                positions = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
                # ---- pre-scanned segments: full batch, every stage
                aux_pre = jnp.zeros((), jnp.float32)
                for i in range(si):
                    x, _, a = M._apply_segment(
                        cfg, plan[i], params["segments"][i], ad_segs[i],
                        lora_full, x, positions, None, None, False, remat)
                    aux_pre = aux_pre + a
                # ---- micro split (activations + per-row metadata only;
                # labels stay in original order for the tail)
                idx = jnp.asarray(idx_np, jnp.int32)
                x_m = jnp.take(x, idx, 0).reshape(N, Bm, S, d)
                ids_m = jnp.take(nb["adapter_ids"], idx, 0).reshape(N, Bm)
                rs_m = jnp.take(rp, idx, 0).reshape(N, Bm)
                pos_m = positions[:Bm]
                # ---- 1F1B tick loop over the scanned stack
                p_si, ad_si = params["segments"][si], ad_segs[si]
                recv = jnp.zeros((Bm, S, d), x.dtype)
                aux_scan = jnp.zeros((), jnp.float32)
                outs = []
                for t in range(N + P_st - 1):
                    m = jnp.clip(t - s_idx, 0, N - 1)
                    x_in = jnp.where(first, jnp.take(x_m, m, 0), recv)
                    lora_m = self.lora_ctx(jnp.take(ids_m, m, 0),
                                           axis_name=data_axis,
                                           row_solo_pos=jnp.take(rs_m, m, 0),
                                           grad_sync=grad_sync,
                                           nano_order=seg_order)
                    y, _, a = M._apply_segment(
                        cfg, seg_local, p_si, ad_si, lora_m, x_in,
                        pos_m, None, None, False, remat)
                    valid = (t - s_idx >= 0) & (t - s_idx <= N - 1)
                    aux_scan = aux_scan + jnp.where(valid, a, 0.0)
                    outs.append(y)
                    recv = jax.lax.ppermute(y, "stage", ring_perm)
                # last stage's valid outputs: ticks [P-1, P-1+N); undo
                # the micro permutation back to original local row order
                out = jnp.stack(outs[P_st - 1:P_st - 1 + N])
                out = out.reshape(B_loc, S, d)
                x = jnp.take(out, jnp.asarray(inv_np, jnp.int32), 0)
                # ---- tail: computed redundantly on every stage over the
                # reassembled buffer, loss masked to the owning stage
                aux_tail = jnp.zeros((), jnp.float32)
                for i in range(si + 1, len(plan)):
                    x, _, a = M._apply_segment(
                        cfg, plan[i], params["segments"][i], ad_segs[i],
                        lora_full, x, positions, None, None, False, remat)
                    aux_tail = aux_tail + a
                x = rms_norm(x, params["ln_f"], cfg.norm_eps)
                logits = M._logits(cfg, params, x)
                labels = nb["labels"]
                if text_off:
                    logits = logits[:, text_off:]
                if cfg.causal:
                    logits = logits[:, :-1]
                    labels = labels[:, 1:]
                mask = nb.get("loss_mask")
                if mask is not None:
                    mask = mask[:, -labels.shape[-1]:]
                from repro.models.layers import cross_entropy
                tok_loss = cross_entropy(logits, labels, mask=mask)
                seq_loss = tok_loss.sum(axis=-1)
                onehot = jax.nn.one_hot(nb["adapter_ids"], K,
                                        dtype=jnp.float32)
                per_job = (onehot.T @ seq_loss) / denom
                # LOCAL partial, where-masked to the owning stage — no
                # psum inside the differentiated loss
                total = (jnp.where(last, per_job.sum() + aux_tail, 0.0)
                         + jnp.where(first, aux_pre, 0.0) + aux_scan)
                aux_out = jnp.where(last, aux_tail, 0.0) \
                    + jnp.where(first, aux_pre, 0.0) + aux_scan
                return total, {"per_job": jnp.where(last, per_job, 0.0),
                               "aux": aux_out}

            grad_fn = jax.grad(nano_loss, has_aux=True)
            batch = dict(batch)
            batch["_row_solo"] = row_solo
            grads, aux = grad_fn(adapters, batch)
            # non-scanned segments compute on every stage but their
            # cotangents live only on the owning stage (pre -> stage 0,
            # tail -> stage P-1): psum them so the replicated adapter
            # slices update identically everywhere.  The scanned
            # segment's grads are its stage-local layer shards — no
            # stage collective.
            grads = _stage_psum_unscanned(grads, si, "stage")
            per_job = jax.lax.psum(aux["per_job"], ("stage", data_axis))
            if not exact:
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g, data_axis), grads)
            lr = lr_fn(opt_state.step)
            new_adapters, new_opt = adamw.update(
                grads, opt_state, adapters, lr=lr,
                weight_decay=weight_decay,
                col_jobs=self.layout.col_jobs)
            # executed-schedule occupancy: count the (stage, tick)
            # slots that carried a valid micro — the same mask that
            # gates the loss — vs every slot the tick loop ran.  This
            # is the MEASURED bubble bench_pipeline reports
            # (1 - useful/slots): it reads the schedule the step
            # actually executed, so it moves if the tick loop or micro
            # assignment ever changes.
            useful = jnp.zeros((), jnp.int32)
            for t in range(N + P_st - 1):
                useful = useful + ((t - s_idx >= 0)
                                   & (t - s_idx <= N - 1)
                                   ).astype(jnp.int32)
            metrics = {"loss": per_job.sum(), "per_job_loss": per_job,
                       "lr": lr,
                       "pipe_useful_slots":
                           jax.lax.psum(useful, "stage"),
                       "pipe_slots":
                           jnp.int32((N + P_st - 1) * P_st)}
            return new_adapters, new_opt, metrics

        if steps is None:
            inner, batch_lead = train_step, ()
        else:
            def chunked_step(params, adapters, opt_state, batches,
                             row_solo):
                def body(carry, b):
                    ad, opt = carry
                    ad, opt, m = train_step(params, ad, opt, b, row_solo)
                    return (ad, opt), m

                (new_adapters, new_opt), metrics = jax.lax.scan(
                    body, (adapters, opt_state), batches, unroll=unroll)
                return new_adapters, new_opt, metrics

            inner, batch_lead = chunked_step, (None,)

        batch_spec = P(*batch_lead, data_axis)
        mesh2 = mesh

        def stepfn(params, adapters, opt_state, batches):
            b_specs = jax.tree.map(lambda _: batch_spec, batches)
            p_specs = pipeline_stage_specs(cfg, params)
            ad_specs = pipeline_stage_specs(cfg, adapters)
            opt_specs = adamw.AdamWState(P(), ad_specs, ad_specs)
            fn = jax.shard_map(inner, mesh=mesh2,
                               in_specs=(p_specs, ad_specs, opt_specs,
                                         b_specs, P(data_axis)),
                               out_specs=(ad_specs, opt_specs, P()),
                               check_vma=False)
            return fn(params, adapters, opt_state, batches,
                      jnp.asarray(perm, jnp.int32))

        return stepfn

    # --------------------------------------------------------- serve steps
    def make_prefill_step(self, shape: InputShape, *, ring: bool = False,
                          with_cache: bool = True) -> Callable:
        def prefill_step(params, adapters, batch):
            lora = self.lora_ctx(batch["adapter_ids"])
            model_in = {k: v for k, v in batch.items()
                        if k not in ("adapter_ids", "labels", "loss_mask")}
            if with_cache:
                B = batch["adapter_ids"].shape[0]
                caches = M.init_caches(self.cfg, B, shape.seq_len, ring)
                logits, _, new_caches, _ = M.forward(
                    self.cfg, params, adapters, lora, model_in,
                    caches=caches, cache_pos=0, ring=ring)
                return logits[:, -1:], new_caches
            logits, _, _, _ = M.forward(self.cfg, params, adapters, lora,
                                        model_in)
            return logits[:, -1:], None

        return prefill_step

    def make_serve_step(self, *, ring: bool = False) -> Callable:
        def serve_step(params, adapters, caches, batch, pos):
            lora = self.lora_ctx(batch["adapter_ids"])
            logits, new_caches = M.decode_step(
                self.cfg, params, adapters, lora, batch["tokens"], pos,
                caches, ring=ring)
            return logits, new_caches
        return serve_step

    # ------------------------------------------------------------- inputs
    def decode_buf(self, shape: InputShape) -> int:
        return (min(shape.seq_len, self.cfg.sliding_window)
                if shape.sliding_window_variant else shape.seq_len)

    def init_decode_caches(self, shape: InputShape,
                           batch: Optional[int] = None) -> list:
        B = batch or shape.global_batch
        return M.init_caches(self.cfg, B, self.decode_buf(shape),
                             ring=shape.sliding_window_variant)


# --------------------------------------------------------------- helpers
def scanned_segment_index(cfg: ModelConfig) -> int:
    """Index of THE scanned segment in ``segment_plan`` — the layer
    stack pipeline mode partitions.  Exactly one is required (the plan
    builder emits at most one; zero means the model is too small/odd to
    pipeline)."""
    idx = [i for i, s in enumerate(M.segment_plan(cfg)) if s.scanned]
    if len(idx) != 1:
        raise ValueError(
            f"pipeline mode needs exactly one scanned segment; "
            f"{cfg.name} has {len(idx)}")
    return idx[0]


def pipeline_legal_stages(cfg: ModelConfig) -> List[int]:
    """Legal pipeline depths for *cfg*: divisors of the scanned stack's
    cycle count (each stage must hold a whole number of cycles)."""
    plan = M.segment_plan(cfg)
    idx = [i for i, s in enumerate(plan) if s.scanned]
    if len(idx) != 1:
        return [1]
    r = plan[idx[0]].repeats
    return [p for p in range(1, r + 1) if r % p == 0]


def pipeline_stage_specs(cfg: ModelConfig, tree: dict,
                         stage_axis: str = "stage"):
    """PartitionSpec tree for a params/adapters-structured *tree* under
    pipeline mode: the scanned segment's stacked leaves shard their
    leading layer axis over *stage_axis*; every other leaf replicates.
    Works for backbone params (QuantTensor leaves included — q and
    scale both carry the leading layer axis in scanned stacks), adapter
    trees, and (via tree_map) Adam moment trees."""
    from jax.sharding import PartitionSpec as P
    si = scanned_segment_index(cfg)
    st, rp = P(stage_axis), P()
    sub = lambda t, spec: jax.tree.map(lambda _: spec, t)
    out = {k: sub(v, rp) for k, v in tree.items() if k != "segments"}
    out["segments"] = [sub(s, st if i == si else rp)
                       for i, s in enumerate(tree["segments"])]
    return out


def _stage_psum_unscanned(grads: dict, si: int, axis: str) -> dict:
    """psum every NON-scanned segment's grads over the stage axis (their
    cotangents live only on the owning stage); the scanned segment's
    grads are that stage's layer shards and stay local."""
    reduce = lambda t: jax.tree.map(lambda g: jax.lax.psum(g, axis), t)
    out = {k: reduce(v) for k, v in grads.items() if k != "segments"}
    out["segments"] = [seg if i == si else reduce(seg)
                       for i, seg in enumerate(grads["segments"])]
    return out


def _per_job_token_counts(batch: dict, K: int, causal: bool,
                          clip: bool = True) -> jax.Array:
    """Full-batch per-job loss-token counts (denominators).

    ``clip=False`` returns the raw counts — REQUIRED for per-shard
    partials that are psum'ed into a global denominator: clipping must
    happen once on the global sum, or shards holding only padding rows
    would each contribute a spurious 1."""
    ids = batch["adapter_ids"]
    mask = batch.get("loss_mask")
    if mask is None:
        key = "labels" if "labels" in batch else "tokens"
        S = batch[key].shape[-1] - (1 if causal else 0)
        counts = jnp.full(ids.shape, S, jnp.float32)
    else:
        m = mask[:, 1:] if causal else mask
        counts = m.astype(jnp.float32).sum(-1)
    onehot = jax.nn.one_hot(ids, K, dtype=jnp.float32)
    raw = onehot.T @ counts
    return jnp.clip(raw, 1) if clip else raw


def _reshape_nano(batch: dict, n: int) -> dict:
    """(R, ...) -> (n, R/n, ...) for scan over nano-batches."""
    def f(x):
        assert x.shape[0] % n == 0, (x.shape, n)
        return x.reshape(n, x.shape[0] // n, *x.shape[1:])
    return jax.tree.map(f, batch)


def _reshape_nano_jobwise(batch: dict, n: int, rows: Sequence[int],
                          order: Optional[Sequence[int]] = None) -> dict:
    """Job-aware nano split for the sharded step: slice *i* takes rows
    ``[i*r_j/n, (i+1)*r_j/n)`` of EVERY job, so each slice is itself a
    job-major mini fused batch — the per-shard kernel contract (sorted
    contiguous segments, equal composition) survives re-granulation.
    The plain contiguous split would hand slices dominated by one job,
    whose ids break the equal-segment reshape dispatch.

    ``order`` permutes the job SEGMENTS inside each slice (default: job
    index order).  The rank-bucketed pipeline passes rank-descending
    order so every slice leads with its large-rank segments — their
    adapter-gradient collectives are the biggest, and issuing them
    first in the backward overlaps them against the small-rank
    segments' remaining compute.  Segments stay contiguous whatever the
    order, so the kernels' tile contract (one adapter per token tile)
    is preserved; adapter_ids ride the permutation as data.
    """
    idx = jnp.asarray(_nano_index(rows, n, order=order), jnp.int32)
    R = int(sum(rows))

    def f(x):
        assert x.shape[0] == R and all(r % n == 0 for r in rows), \
            (x.shape, rows, n)
        return jnp.take(x, idx, axis=0).reshape(n, R // n, *x.shape[1:])

    return jax.tree.map(f, batch)


def _nano_index(rows: Sequence[int], n: int,
                order: Optional[Sequence[int]] = None) -> np.ndarray:
    """Static row permutation of the job-proportional nano/micro split:
    slice *i* takes rows ``[i*r_j/n, (i+1)*r_j/n)`` of every job, with
    segments inside a slice in *order* (default: job index order).  The
    single source of the split geometry — shared by the nano-batch
    grad-accumulation scan AND the pipeline microbatch schedule (whose
    tail reassembles the original order via ``np.argsort``)."""
    order = list(order) if order is not None else list(range(len(rows)))
    assert sorted(order) == list(range(len(rows))), order
    offs = np.concatenate([[0], np.cumsum(rows)])
    return np.concatenate([
        np.arange(offs[j] + i * (rows[j] // n),
                  offs[j] + (i + 1) * (rows[j] // n))
        for i in range(n) for j in order])


def valid_nano_counts(rows: int, max_n: Optional[int] = None, *,
                      seg_rows: Optional[Sequence[int]] = None,
                      seq_len: int = 1,
                      block_t: int = 1,
                      stages: int = 1) -> List[int]:
    """Divisors of the fused row count (legal nano-batch counts), sorted
    ascending.  O(√rows) paired enumeration — this runs inside
    ``AIMDController.__post_init__`` on every regroup and *rows* reaches
    the thousands at production batch sizes.

    ``seg_rows`` extends the legal set to the RANK-BUCKET boundary
    constraint of the ragged kernels: with a job-proportional split
    every job's per-slice token count must stay a whole number of token
    tiles ((seg_rows[j] * seq_len) % (n * block_t) == 0 for all j), or
    the static per-slice tile→(job, rank-tile) metadata cannot describe
    the slice.  *rows* should then be the gcd of ``seg_rows`` (the
    divisibility base of the job-proportional split).

    ``stages`` > 1 adds the PIPELINE depth constraint: the nano slices
    double as pipeline microbatches, so their count must cover the
    pipeline depth (n >= stages) or the fill/drain bubble dominates the
    schedule — and the tick loop would run more warm-up ticks than it
    has real micros to fill them with."""
    small, large = [], []
    d = 1
    while d * d <= rows:
        if rows % d == 0:
            small.append(d)
            if d != rows // d:
                large.append(rows // d)
        d += 1
    out = small + large[::-1]
    if max_n is not None:
        out = [n for n in out if n <= max_n]
    if seg_rows is not None:
        out = [n for n in out
               if all((r * seq_len) % (n * block_t) == 0
                      for r in seg_rows)]
    if stages > 1:
        out = [n for n in out if n >= stages]
    return out
