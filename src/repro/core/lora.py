"""Multi-adapter LoRA parameters and application (paper §3.2-3.3).

K heterogeneous adapters (ranks r_1..r_K) over one frozen backbone are
stored *packed* along the rank axis with PER-ADAPTER padding — the
ragged layout that makes rank heterogeneity free (paper §3.3's
rank-aware tiles, taken all the way into storage):

    A: (d_in, R)   R = Σ_k r_pad_k;  job k owns columns
                   [off_k, off_k + r_pad_k), zero beyond rank r_k
    B: (R, d_out)  same row segments

``RankLayout`` is the single source of truth for the packing: per-job
padded widths (``pad_rank(r_k)`` — NOT the group max), column offsets,
and the rank-bucket grouping the ragged kernels iterate.  A K=8 group
with ranks {4,...,4,64} stores (and prices, and optimizes) 7·8 + 64
lanes instead of 8·64 — optimizer moments shrink by the same factor and
fuse/unfuse never round-trips through max-rank re-padding.

``MultiLoRA.apply(x, A, B)`` computes, per token t with adapter a(t):

    y_t = scaling[a] * ((x_t @ A[seg_a]) @ B[seg_a])

without ever materializing A B^T — the paper's fused-kernel contract.
Implementations: "ref" (pure jnp gather oracle over a densified stack),
"pallas" (rank-bucketed ragged TPU kernels via kernels/ops.py), "xla"
(bucket-concatenated segment-dense einsums), "loop" (one GEMM pair per
adapter — the unfused baseline of the Fig. 7 ablation).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import cached_property
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.jobs import LoRAJobSpec
from repro.models.quant import qdot


def pad_rank(r_max: int, multiple: int = 8) -> int:
    """Pad a rank so kernel tiles stay lane-aligned (128 on real TPU; 8 is
    plenty for interpret-mode tests and keeps smoke tests fast)."""
    return max(multiple, ((r_max + multiple - 1) // multiple) * multiple)


def rank_axis_is_last(leaf_name: str) -> bool:
    """THE one copy of the packed-leaf axis convention: adapter leaves
    named ``A`` carry the packed rank axis LAST (``(..., d, R)``),
    ``B`` leaves carry it second-to-last (``(..., R, d)``).  Everything
    that slices or broadcasts along the ragged rank axis (checkpoint
    slice/insert, AdamW per-column bias correction, test helpers) must
    route through this predicate so a future leaf rename cannot
    silently slice the wrong axis in one site but not another."""
    return leaf_name.endswith("A")


@dataclass(frozen=True)
class RankLayout:
    """Packed ragged rank layout of one fused group.

    Hashable/static (tuples only) so kernel builders can key their
    custom-VJP caches on it and bake the geometry into compiled
    programs — segment offsets, per-adapter rank-tile counts and the
    bucket grouping are all compile-time constants, never traced.

    ``pads`` overrides the per-job padded widths (uniform historical
    padding, e.g. a solo checkpoint written under r_pad=16); by default
    every job pads independently to ``pad_rank(rank, multiple)`` — the
    per-adapter rule that makes layouts composition-independent: a
    job's segment width never depends on who it is fused with.
    """
    ranks: Tuple[int, ...]
    multiple: int = 8
    pads: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        assert self.ranks, "layout needs at least one job"
        if self.pads is not None:
            assert len(self.pads) == len(self.ranks)
            for r, p in zip(self.ranks, self.pads):
                assert p >= r and p % self.multiple == 0, (r, p)

    @classmethod
    def for_jobs(cls, jobs: Sequence[LoRAJobSpec],
                 multiple: int = 8) -> "RankLayout":
        return cls(tuple(int(j.rank) for j in jobs), multiple)

    @classmethod
    def uniform(cls, ranks: Sequence[int], r_pad: int,
                multiple: Optional[int] = None) -> "RankLayout":
        """Every job padded to the same width (legacy max-rank padding —
        kept for masked-baseline benchmarks and uniform checkpoints)."""
        m = multiple or min(r_pad, 8)
        return cls(tuple(int(r) for r in ranks), m,
                   pads=tuple(r_pad for _ in ranks))

    # ------------------------------------------------------------ geometry
    @property
    def num_jobs(self) -> int:
        return len(self.ranks)

    @cached_property
    def r_pads(self) -> Tuple[int, ...]:
        if self.pads is not None:
            return self.pads
        return tuple(pad_rank(r, self.multiple) for r in self.ranks)

    @cached_property
    def is_uniform(self) -> bool:
        """True when every job pads to the same width.  The packed
        (d, K*rp) layout is then a free reshape away from the stacked
        (K, d, rp) layout, so the masked kernel family applies with
        zero padding waste — and it beats the ragged family there (no
        rank-bucket bookkeeping to amortize)."""
        return len(set(self.r_pads)) == 1

    @cached_property
    def offsets(self) -> Tuple[int, ...]:
        out, off = [], 0
        for p in self.r_pads:
            out.append(off)
            off += p
        return tuple(out)

    @property
    def total(self) -> int:
        return sum(self.r_pads)

    @property
    def max_r_pad(self) -> int:
        return max(self.r_pads)

    def slice_of(self, k: int) -> Tuple[int, int]:
        """(column offset, padded width) of job *k*'s segment."""
        return self.offsets[k], self.r_pads[k]

    @cached_property
    def buckets(self) -> Tuple[Tuple[int, Tuple[int, ...]], ...]:
        """((r_pad, job indices), ...) — jobs grouped by padded width,
        job order preserved within a bucket, buckets sorted descending
        (large-rank segments first: the overlap-friendly issue order)."""
        by: Dict[int, List[int]] = {}
        for k, p in enumerate(self.r_pads):
            by.setdefault(p, []).append(k)
        return tuple((p, tuple(by[p])) for p in sorted(by, reverse=True))

    @cached_property
    def col_jobs(self) -> np.ndarray:
        """(total,) packed column -> owning job index (AdamW per-job
        bias-correction broadcast over the ragged rank axis)."""
        return np.repeat(np.arange(self.num_jobs, dtype=np.int32),
                         np.asarray(self.r_pads, np.int64))

    @cached_property
    def active_cols(self) -> np.ndarray:
        """(total,) bool — lanes < the owning job's true rank."""
        lane = np.concatenate([np.arange(p) for p in self.r_pads])
        return lane < np.asarray(self.ranks)[self.col_jobs]


def init_adapter_pair(key, layout: RankLayout, d_in: int,
                      d_out: int) -> Dict[str, jax.Array]:
    """Standard LoRA init in the packed ragged layout: A ~ N(0, 1/r_pad_k),
    B = 0; lanes >= rank zero-masked.  Each job draws from its own
    folded key at its own padded width, so a job's init is independent
    of the group composition it is born into."""
    As, Bs = [], []
    for k, (r, rp) in enumerate(zip(layout.ranks, layout.r_pads)):
        kk = jax.random.fold_in(key, k)
        a = jax.random.normal(kk, (d_in, rp), jnp.float32) * (1.0 / rp) ** 0.5
        a = a * (jnp.arange(rp) < r).astype(jnp.float32)[None, :]
        As.append(a)
        Bs.append(jnp.zeros((rp, d_out), jnp.float32))
    return {"A": jnp.concatenate(As, axis=-1),
            "B": jnp.concatenate(Bs, axis=0)}


def unpack_dense(A: jax.Array, B: jax.Array, layout: RankLayout,
                 r_pad: Optional[int] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Packed (..., d, R)/(..., R, d) -> stacked (..., K, d, rm)/(..., K,
    rm, d) at a uniform width (default: the layout max).  The densified
    view the gather oracles and the masked-baseline kernels consume —
    and exactly the max-rank padding waste the ragged kernels avoid."""
    rm = r_pad or layout.max_r_pad
    As, Bs = [], []
    for k in range(layout.num_jobs):
        off, rp = layout.slice_of(k)
        w = min(rp, rm)
        a = jax.lax.slice_in_dim(A, off, off + w, axis=-1)
        b = jax.lax.slice_in_dim(B, off, off + w, axis=-2)
        pad = rm - w
        if pad:
            awidths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
            bwidths = [(0, 0)] * (b.ndim - 2) + [(0, pad), (0, 0)]
            a = jnp.pad(a, awidths)
            b = jnp.pad(b, bwidths)
        As.append(a)
        Bs.append(b)
    return jnp.stack(As, axis=-3), jnp.stack(Bs, axis=-3)


@dataclass
class MultiLoRA:
    """Apply context for one fused group: token→adapter map + impl choice."""
    adapter_ids: jax.Array            # (B,) int32 per-sequence adapter index
    ranks: jax.Array                  # (K,) int32
    scalings: jax.Array               # (K,) f32   alpha_i / r_i
    impl: str = "ref"                 # ref | pallas | xla | loop
    block_t: int = 128                # kernel token-tile (perf knob)
    seg_rows: Optional[int] = None    # static max rows per adapter segment
    #                                   (xla capacity; None = all rows)
    equal_segments: bool = False      # every adapter contributes seg_rows
    # ragged packed storage (per-adapter padded ranks): ``layout`` set
    # means A/B are packed (d, R)/(R, d) leaves and dispatch goes to the
    # rank-bucketed ragged kernels; None keeps the legacy stacked
    # (K, d, r_pad) contract for direct kernel callers.
    layout: Optional[RankLayout] = None
    rows_all: Optional[Tuple[int, ...]] = None   # static per-job rows of
    #                                   the full (local) fused batch
    nano_order: Optional[Tuple[int, ...]] = None  # static job order of the
    #                                   segments inside a job-proportional
    #                                   nano slice (rank-bucketed pipeline)
    # sharded group execution (DESIGN.md §8): set when this context is
    # applied inside a shard_map over a data axis.  adapter_ids then
    # covers THIS SHARD's rows only; ``row_solo_pos`` (traced, rides the
    # batch through nano slicing) is each local row's position in the
    # solo job-major layout — the exact wgrads scatter into that order;
    # ``shards`` x ``local_rows`` give the global row count and identify
    # full-batch (segment-sorted) vs nano-slice applications.
    axis_name: Optional[str] = None
    row_solo_pos: Optional[jax.Array] = None
    shards: int = 1
    local_rows: Optional[int] = None
    grad_sync: str = "gather"         # gather (exact wgrads) | psum

    @property
    def num_adapters(self) -> int:
        return int(self.ranks.shape[0])

    def token_ids(self, batch: int, seq: int) -> jax.Array:
        """Per-token adapter ids for an (batch, seq) activation."""
        return jnp.repeat(self.adapter_ids, seq)

    def _slice_rows(self, bsz: int) -> Optional[Tuple[int, ...]]:
        """Per-job rows of a job-proportional nano slice of size *bsz*
        (None when the batch is not such a slice).

        Only the SHARDED step's nano split is job-proportional
        (`_reshape_nano_jobwise`); the unsharded split is contiguous, so
        a sub-batch there must NOT be described by scaled static
        geometry — its segments belong to whichever jobs the cut landed
        on, and a wrong static tile map would silently apply the wrong
        adapter slabs."""
        if self.rows_all is None:
            return None
        total = sum(self.rows_all)
        if bsz == total:
            return tuple(self.rows_all)
        if self.axis_name is None:
            return None                      # unsharded nano: contiguous
        if bsz == 0 or total % bsz:
            return None
        f = total // bsz
        if any(r % f for r in self.rows_all):
            return None
        return tuple(r // f for r in self.rows_all)

    @jax.named_scope("lora")
    def apply(self, x: jax.Array, ab: Dict[str, jax.Array]) -> jax.Array:
        """x: (B, S, d_in) -> (B, S, d_out) LoRA delta (scaled), under the
        named scope ``lora`` with every kernel launch and the XLA ops
        around it."""
        from repro.kernels import ops  # late import: kernels are optional
        A, B = ab["A"], ab["B"]
        bsz, seq, d_in = x.shape
        xf = x.reshape(bsz * seq, d_in)
        ids = self.token_ids(bsz, seq)
        cap = min(self.seg_rows or bsz, bsz) * seq
        eq = (self.equal_segments
              and self.seg_rows is not None
              and bsz == self.seg_rows * self.num_adapters)
        # shard-local VJPs only when grads must be exact-by-gather; the
        # psum strategy reduces the plain impls' partial wgrads upstream
        axis = self.axis_name if self.grad_sync == "gather" else None
        solo_pos, total = None, 0
        if axis is not None:
            rp = self.row_solo_pos
            assert rp is not None, \
                ("sharded gather context needs row_solo_pos (each local "
                 "row's solo position) — see core/ssm lora_ctx")
            solo_pos = (rp[:, None] * seq
                        + jnp.arange(seq, dtype=rp.dtype)[None, :]).reshape(-1)
            total = self.shards * self.local_rows * seq
        if (self.layout is not None and self.layout.is_uniform
                and self.impl in ("xla", "pallas")):
            # Homogeneous padded widths: route to the MASKED family.
            # The ragged kernels only win when padding waste exists to
            # skip; with uniform r_pads their per-bucket bookkeeping is
            # pure overhead (~0.88x of masked).  The packed (d, K*rp)
            # pair reshapes losslessly into the stacked (K, d, rp)
            # contract, and lanes >= the true rank stay masked via
            # ``ranks`` — so uniform pads with differing ranks is safe.
            rp = self.layout.r_pads[0]
            K = self.layout.num_jobs
            A_st = A.reshape(*A.shape[:-1], K, rp)
            A_st = jnp.moveaxis(A_st, -2, -3)
            B_st = B.reshape(*B.shape[:-2], K, rp, B.shape[-1])
            out = ops.fused_lora(
                xf, A_st.astype(x.dtype), B_st.astype(x.dtype), ids,
                self.ranks, self.scalings, impl=self.impl,
                block_t=self.block_t, capacity=cap, equal_segments=eq,
                axis_name=axis, solo_pos=solo_pos, total_tokens=total,
                full_batch=bsz == self.local_rows)
        elif self.layout is not None:
            # solo_rows: the geometry of the SOLO-order reassembled batch
            # the sharded wgrads run under — GLOBAL per-job rows (each
            # job's shard slices concatenate back to rows_all * shards)
            solo_rows = tuple(self.rows_all or ())
            if axis is not None:
                solo_rows = tuple(r * self.shards for r in solo_rows)
            out = ops.fused_lora_ragged(
                xf, A.astype(x.dtype), B.astype(x.dtype), ids,
                self.scalings, self.layout, impl=self.impl,
                block_t=self.block_t, equal_segments=eq,
                slice_rows=self._slice_rows(bsz), seq_len=seq,
                nano_order=self.nano_order,
                solo_rows=solo_rows,
                axis_name=axis, solo_pos=solo_pos, total_tokens=total,
                ranks=self.ranks)
        else:
            out = ops.fused_lora(
                xf, A.astype(x.dtype), B.astype(x.dtype), ids,
                self.ranks, self.scalings, impl=self.impl,
                block_t=self.block_t, capacity=cap, equal_segments=eq,
                axis_name=axis, solo_pos=solo_pos, total_tokens=total,
                full_batch=bsz == self.local_rows)
        return out.reshape(bsz, seq, -1)


def proj(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
         lora: Optional[MultiLoRA] = None,
         ab: Optional[Dict[str, jax.Array]] = None) -> jax.Array:
    """Frozen dense projection + optional fused multi-LoRA delta.

    ``w`` may be a quantized ``models/quant.QuantTensor`` — ``qdot``
    fuses the int8 dequant into the base matmul; the LoRA delta path is
    untouched (adapters stay high precision and take the gradient)."""
    y = qdot(x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    if lora is not None and ab is not None:
        y = y + lora.apply(x, ab).astype(y.dtype)
    return y


# ---------------------------------------------------------------------
# Group-level parameter construction
# ---------------------------------------------------------------------
def group_ranks(jobs: Sequence[LoRAJobSpec]
                ) -> Tuple[jax.Array, jax.Array, RankLayout]:
    ranks = jnp.array([j.rank for j in jobs], jnp.int32)
    scal = jnp.array([j.scaling for j in jobs], jnp.float32)
    return ranks, scal, RankLayout.for_jobs(jobs)


def merge_adapter_pair(pairs: Sequence[Dict[str, jax.Array]],
                       layout: Optional[RankLayout] = None
                       ) -> Dict[str, jax.Array]:
    """Pack per-job (d, r_i) pairs into one ragged (d, R) pair — what
    Model Fuser does when forming a group's SSM.

    Sources may carry heterogeneous padding (each pair's trailing rank
    dim is whatever width its previous stack used); each job re-pads to
    ITS OWN destination width ``layout.r_pads[k]`` (default: per-job
    ``pad_rank`` of the source width) — never to the group max, so
    fusing a rank-4 job next to a rank-64 one is a copy, not a 16x
    inflation.  Shrinking is legal as long as the dropped lanes are
    zero — i.e. the pair was produced by ``extract_adapter`` (un-padded)
    or its padding lanes were never touched (the kernel rank-mask
    invariant)."""
    widths = [int(p["A"].shape[-1]) for p in pairs]
    layout = layout or RankLayout(tuple(widths))
    assert layout.num_jobs == len(pairs)
    As, Bs = [], []
    for p, rp in zip(pairs, layout.r_pads):
        a, b = p["A"], p["B"]
        pad_a = rp - a.shape[-1]
        if pad_a < 0:    # source wider than destination: drop zero lanes
            a, b = a[:, :rp], b[:rp, :]
            pad_a = 0
        As.append(jnp.pad(a, ((0, 0), (0, pad_a))))
        Bs.append(jnp.pad(b, ((0, pad_a), (0, 0))))
    return {"A": jnp.concatenate(As, axis=-1),
            "B": jnp.concatenate(Bs, axis=0)}


def extract_adapter(ab: Dict[str, jax.Array], layout: RankLayout,
                    idx: int, rank: Optional[int] = None
                    ) -> Dict[str, jax.Array]:
    """Pull job *idx*'s un-padded adapter out of the packed pair — used
    for per-job checkpointing and for decoupling a job from a group."""
    off, _ = layout.slice_of(idx)
    r = rank or layout.ranks[idx]
    return {"A": ab["A"][..., :, off:off + r],
            "B": ab["B"][..., off:off + r, :]}
