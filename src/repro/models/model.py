"""Model assembly: config -> init / forward / decode for every arch family.

One generic decoder/encoder assembly covers the whole zoo.  A config's
``layer_pattern`` is resolved into per-layer ``LayerSpec``s and segmented
into

    [unrolled head] + [scanned cycles] + [unrolled remainder]

where the scanned segment stacks each cycle position's params with a
leading ``n_cycles`` axis and runs under ``jax.lax.scan`` (+ per-layer
``jax.checkpoint`` in training) — this keeps HLO size flat for 80-layer
models across the 40 dry-run combos.

Frozen backbone params and trainable multi-LoRA adapter params are kept
in *separate* trees (the memory story of the paper: no optimizer state
for the backbone).  Adapter leaves are packed ragged ``(n_cycles, d, R)``
/ ``(n_cycles, R, d)`` with per-adapter padded rank segments
(core/lora.RankLayout) so the same scan slices them per layer and no
job pays the group-max rank in storage.

Modality frontends (audio conv codec, ViT) are stubs per the assignment:
``input_specs`` feeds precomputed frame/patch embeddings.
"""
from __future__ import annotations

import dataclasses
import zlib
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import (FULL_ATTN, LOCAL_ATTN, RGLRU, SSD,
                                InputShape, ModelConfig)
from repro.core.lora import (MultiLoRA, RankLayout, init_adapter_pair,
                             pad_rank)
from repro.models.attention import KVCache, attn_block, attn_init
from repro.models.layers import (cross_entropy, dense_init, dtype_of,
                                 embed_init, rms_norm, rms_norm_init,
                                 swiglu, swiglu_init)
from repro.models.mla import MLACache, mla_block, mla_init
from repro.models.moe import moe_ffn, moe_init
from repro.models.rglru import RGLRUCache, rglru_block, rglru_init
from repro.models.ssd import SSDCache, ssd_block, ssd_init
from repro.sharding import shard


# ----------------------------------------------------------------- specs
@dataclass(frozen=True)
class LayerSpec:
    mixer: str        # "attn" | "local_attn" | "mla" | "ssd" | "rglru"
    ffn: str          # "swiglu" | "moe" | "none"

    @property
    def lora_targets(self) -> Tuple[str, ...]:
        return {
            "attn": ("q", "k", "v", "o"),
            "local_attn": ("q", "k", "v", "o"),
            "mla": ("q", "kv_a", "o"),
            "ssd": ("ssd_in", "ssd_out"),
            "rglru": ("rg_in", "rg_gate", "rg_out"),
        }[self.mixer]


@dataclass(frozen=True)
class Segment:
    specs: Tuple[LayerSpec, ...]   # one cycle
    repeats: int                   # n_cycles (1 + not scanned => unrolled)
    scanned: bool


def layer_specs(cfg: ModelConfig) -> List[LayerSpec]:
    specs = []
    for i, kind in enumerate(cfg.layer_kinds()):
        if kind in (FULL_ATTN, LOCAL_ATTN):
            mixer = "mla" if cfg.use_mla else (
                "local_attn" if kind == LOCAL_ATTN else "attn")
        elif kind == SSD:
            mixer = "ssd"
        elif kind == RGLRU:
            mixer = "rglru"
        else:
            raise ValueError(kind)
        if mixer == "ssd":
            ffn = "none"                       # mamba2: mixer-only blocks
        elif cfg.num_experts and i >= cfg.first_k_dense:
            ffn = "moe"
        else:
            ffn = "swiglu"
        specs.append(LayerSpec(mixer, ffn))
    return specs


def segment_plan(cfg: ModelConfig) -> List[Segment]:
    """Head (first_k_dense) unrolled, then scanned cycles + remainder."""
    specs = layer_specs(cfg)
    segs: List[Segment] = []
    head = cfg.first_k_dense
    if head:
        segs.append(Segment(tuple(specs[:head]), 1, False))
        specs = specs[head:]
    cl = len(cfg.layer_pattern)
    n_full = len(specs) // cl
    if n_full:
        segs.append(Segment(tuple(specs[:cl]), n_full, True))
    rem = specs[n_full * cl:]
    if rem:
        segs.append(Segment(tuple(rem), 1, False))
    return segs


# ----------------------------------------------------------------- init
def _block_init(key, cfg: ModelConfig, spec: LayerSpec) -> dict:
    k1, k2 = jax.random.split(key)
    p: Dict[str, Any] = {"ln1": rms_norm_init(cfg.d_model)}
    if spec.mixer in ("attn", "local_attn"):
        p["attn"] = attn_init(k1, cfg)
    elif spec.mixer == "mla":
        p["attn"] = mla_init(k1, cfg)
    elif spec.mixer == "ssd":
        p["ssd"] = ssd_init(k1, cfg)
    elif spec.mixer == "rglru":
        p["rg"] = rglru_init(k1, cfg)
    if spec.ffn != "none":
        p["ln2"] = rms_norm_init(cfg.d_model)
        if spec.ffn == "moe":
            p["ffn"] = moe_init(k2, cfg)
        else:
            p["ffn"] = swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype_of(cfg))
    return p


def _seg_init(key, cfg: ModelConfig, seg: Segment) -> dict:
    out = {}
    for j, spec in enumerate(seg.specs):
        kj = jax.random.fold_in(key, j)
        if seg.scanned and seg.repeats > 1:
            keys = jax.random.split(kj, seg.repeats)
            out[str(j)] = jax.vmap(lambda k: _block_init(k, cfg, spec))(keys)
        elif seg.scanned:
            out[str(j)] = jax.tree.map(lambda x: x[None],
                                       _block_init(kj, cfg, spec))
        else:
            out[str(j)] = _block_init(kj, cfg, spec)
    return out


def init_model(key, cfg: ModelConfig) -> dict:
    """Frozen backbone parameter tree."""
    ks = jax.random.split(key, 8)
    dt = dtype_of(cfg)
    p: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt),
        "ln_f": rms_norm_init(cfg.d_model),
        "segments": [_seg_init(jax.random.fold_in(ks[1], i), cfg, seg)
                     for i, seg in enumerate(segment_plan(cfg))],
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_size, dt)
    if cfg.frontend_dim:
        # modality-frontend stub: project precomputed embeddings to d_model
        p["frontend"] = dense_init(ks[3], cfg.frontend_dim, cfg.d_model, dt)
    return p


def _block_adapter_init(key, cfg: ModelConfig, spec: LayerSpec,
                        layout: RankLayout) -> dict:
    dims = {
        "q": (cfg.d_model, cfg.q_dim),
        "k": (cfg.d_model, cfg.kv_dim),
        "v": (cfg.d_model, cfg.kv_dim),
        "o": (cfg.q_dim, cfg.d_model),
        "ssd_in": (cfg.d_model, 2 * cfg.ssm_d_inner
                   + 2 * 8 * cfg.ssm_state + cfg.ssm_nheads),
        "ssd_out": (cfg.ssm_d_inner, cfg.d_model),
        "rg_in": (cfg.d_model, cfg.lru_width),
        "rg_gate": (cfg.d_model, cfg.lru_width),
        "rg_out": (cfg.lru_width, cfg.d_model),
    }
    if spec.mixer == "mla":
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        dims.update({
            "q": (cfg.d_model, cfg.num_heads * qk),
            "kv_a": (cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_dim),
            "o": (cfg.num_heads * cfg.v_head_dim, cfg.d_model),
        })
    out = {}
    for t in spec.lora_targets:
        d_in, d_out = dims[t]
        # crc32, not hash(): salted str hashing would make adapter init
        # irreproducible across interpreter runs with the same seed
        kt = jax.random.fold_in(key, zlib.crc32(t.encode()) % 2**31)
        out[t] = init_adapter_pair(kt, layout, d_in, d_out)
    return out


def init_adapters(key, cfg: ModelConfig, ranks: jax.Array,
                  r_pad: Optional[int] = None,
                  layout: Optional[RankLayout] = None) -> dict:
    """Trainable adapter tree mirroring the segment structure.

    ranks: (K,) int32 per-job LoRA ranks.  Leaves are PACKED ragged —
    (n_cycles, d, R)/(n_cycles, R, d) with R = Σ_k r_pad_k — per the
    ``layout`` (default: per-adapter ``pad_rank``; ``r_pad`` forces a
    uniform padded width, the legacy max-rank rule).
    """
    if layout is None:
        rk = tuple(int(r) for r in np.asarray(jax.device_get(ranks)))
        layout = (RankLayout.uniform(rk, r_pad) if r_pad
                  else RankLayout(rk))
    segs = []
    for i, seg in enumerate(segment_plan(cfg)):
        ki = jax.random.fold_in(key, i)
        seg_tree = {}
        for j, spec in enumerate(seg.specs):
            kj = jax.random.fold_in(ki, j)
            if seg.scanned:
                keys = jax.random.split(kj, seg.repeats)
                seg_tree[str(j)] = jax.vmap(
                    lambda k: _block_adapter_init(k, cfg, spec, layout)
                )(keys)
            else:
                seg_tree[str(j)] = _block_adapter_init(kj, cfg, spec,
                                                       layout)
        segs.append(seg_tree)
    return {"segments": segs}


def adapter_param_count(cfg: ModelConfig, ranks: Sequence[int]) -> int:
    """Exact trainable-parameter count (un-padded ranks)."""
    total = 0
    layout = RankLayout(tuple(int(r) for r in ranks))
    for seg in segment_plan(cfg):
        for spec in seg.specs:
            tree = _block_adapter_init(jax.random.PRNGKey(0), cfg, spec,
                                       layout)
            for t, ab in tree.items():
                d_in = ab["A"].shape[0]
                d_out = ab["B"].shape[1]
                total += seg.repeats * sum(r * (d_in + d_out) for r in ranks)
    return total


# ----------------------------------------------------------------- caches
def init_block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     buf: int, ring: bool, layers: Optional[int] = None):
    dt = dtype_of(cfg)
    if spec.mixer in ("attn", "local_attn"):
        b = min(buf, cfg.sliding_window) if (spec.mixer == "local_attn" or ring) else buf
        return KVCache.init(batch, b, cfg.num_kv_heads, cfg.head_dim, dt,
                            layers=layers)
    if spec.mixer == "mla":
        b = min(buf, cfg.sliding_window) if ring else buf
        return MLACache.init(batch, b, cfg, dt, layers=layers)
    if spec.mixer == "ssd":
        return SSDCache.init(batch, cfg, layers=layers)
    if spec.mixer == "rglru":
        return RGLRUCache.init(batch, cfg, layers=layers)
    raise ValueError(spec.mixer)


def init_caches(cfg: ModelConfig, batch: int, buf: int, ring: bool) -> list:
    """Per-segment cache stacks matching segment_plan structure."""
    caches = []
    for seg in segment_plan(cfg):
        seg_c = {}
        for j, spec in enumerate(seg.specs):
            layers = seg.repeats if seg.scanned else None
            seg_c[str(j)] = init_block_cache(cfg, spec, batch, buf, ring,
                                             layers=layers)
        caches.append(seg_c)
    return caches


# ----------------------------------------------------------------- blocks
def apply_block(cfg: ModelConfig, spec: LayerSpec, p: dict, ad: dict,
                lora: Optional[MultiLoRA], x: jax.Array, positions,
                cache, cache_pos, ring: bool):
    """One pre-norm block. Returns (x, new_cache, aux_loss)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if spec.mixer in ("attn", "local_attn"):
        out, new_cache = attn_block(
            cfg, p["attn"], h, positions=positions, lora=lora, lora_ab=ad,
            cache=cache, cache_pos=cache_pos,
            local=(spec.mixer == "local_attn"),
            ring=ring or (spec.mixer == "local_attn" and cache is not None))
    elif spec.mixer == "mla":
        out, new_cache = mla_block(cfg, p["attn"], h, positions=positions,
                                   lora=lora, lora_ab=ad, cache=cache,
                                   cache_pos=cache_pos, ring=ring)
    elif spec.mixer == "ssd":
        out, new_cache = ssd_block(cfg, p["ssd"], h, lora=lora, lora_ab=ad,
                                   cache=cache)
    elif spec.mixer == "rglru":
        out, new_cache = rglru_block(cfg, p["rg"], h, lora=lora, lora_ab=ad,
                                     cache=cache)
    else:
        raise ValueError(spec.mixer)
    x = x + out
    if spec.ffn != "none":
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if spec.ffn == "moe":
            out2, aux = moe_ffn(cfg, p["ffn"], h2)
        else:
            out2 = swiglu(p["ffn"], h2)
        x = x + out2
    return x, new_cache, aux


def _apply_segment(cfg, seg: Segment, p: dict, ad: dict,
                   lora: Optional[MultiLoRA], x, positions,
                   caches, cache_pos, ring: bool, remat: bool,
                   unroll: bool = False):
    """Apply one segment; returns (x, new_caches, aux_sum).

    ``unroll`` replays scanned cycles as a python loop over statically
    sliced layers instead of ``lax.scan`` — same per-layer math, no scan
    in the autodiff path.  Used by the sharded runtime (DESIGN.md §8):
    XLA's SPMD partitioner cannot handle grad-through-scan inside a
    partially-manual shard_map (manual data axis + GSPMD "model" axis),
    so tensor-parallel sharded training unrolls the layer dimension.
    """
    if not seg.scanned:
        new_caches, aux = {}, jnp.zeros((), jnp.float32)
        for j, spec in enumerate(seg.specs):
            c = caches.get(str(j)) if caches else None
            x, nc, a = apply_block(cfg, spec, p[str(j)], ad.get(str(j), {}),
                                   lora, x, positions, c, cache_pos, ring)
            if nc is not None:
                new_caches[str(j)] = nc
            aux = aux + a
        return x, (new_caches or None), aux

    def cycle(x, layer_p, layer_ad, layer_c):
        new_c, aux = {}, jnp.zeros((), jnp.float32)
        for j, spec in enumerate(seg.specs):
            c = layer_c.get(str(j)) if layer_c else None
            x, nc, a = apply_block(cfg, spec, layer_p[str(j)],
                                   layer_ad.get(str(j), {}),
                                   lora, x, positions, c, cache_pos, ring)
            if nc is not None:
                new_c[str(j)] = nc
            aux = aux + a
        return x, new_c, aux

    if remat:
        cycle = jax.checkpoint(cycle)

    if unroll:
        aux = jnp.zeros((), jnp.float32)
        layer_caches = []
        for i in range(seg.repeats):
            sl = lambda t: jax.tree.map(lambda v: v[i], t)
            layer_c = sl(caches) if caches is not None else None
            x, new_c, a = cycle(x, sl(p), sl(ad), layer_c)
            aux = aux + a
            layer_caches.append(new_c)
        new_caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *layer_caches)
                      if caches is not None else None)
        return x, new_caches, aux

    def body(carry, xs):
        x, aux = carry
        layer_p, layer_ad, layer_c = xs
        x, new_c, a = cycle(x, layer_p, layer_ad, layer_c)
        return (x, aux + a), new_c

    xs = (p, ad, caches)
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        xs)
    if caches is None:
        new_caches = None
    return x, new_caches, aux


# ----------------------------------------------------------------- embed
def _sinusoid(S: int, d: int, offset=0) -> jax.Array:
    pos = (offset + jnp.arange(S))[:, None].astype(jnp.float32)
    dim = jnp.arange(0, d, 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10_000.0, dim / d)
    pe = jnp.zeros((S, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang))
    return pe


def embed_inputs(cfg: ModelConfig, params: dict, batch: dict,
                 pos_offset=0) -> Tuple[jax.Array, int]:
    """Resolve modality inputs to (B, S, d) activations.

    Returns (x, text_offset) where logits/labels align from text_offset on.
    """
    dt = dtype_of(cfg)
    if cfg.family == "audio":
        x = batch["frames"].astype(dt) @ params["frontend"]
        S = x.shape[1]
        x = x + _sinusoid(S, cfg.d_model, pos_offset).astype(dt)[None]
        return x, 0
    if cfg.family == "vlm" and "patches" in batch:
        pe = batch["patches"].astype(dt) @ params["frontend"]
        te = params["embed"][batch["tokens"]]
        return jnp.concatenate([pe, te], axis=1), pe.shape[1]
    return params["embed"][batch["tokens"]], 0


def _logits(cfg, params, x):
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = x @ head
    return shard(logits, "batch", "seq", "tp")


# ----------------------------------------------------------------- forward
def forward(cfg: ModelConfig, params: dict, adapters: Optional[dict],
            lora: Optional[MultiLoRA], batch: dict, *,
            caches: Optional[list] = None, cache_pos=None,
            ring: bool = False, remat: bool = False,
            unroll_layers: bool = False,
            logits_at: Optional[jax.Array] = None):
    """Full model. batch keys: tokens / frames / patches (+tokens).

    Returns (logits, aux_loss, new_caches, text_offset).
    logits: (B, S, vocab) — for VLM, S covers patches+text (slice by offset).
    ``logits_at`` (B,) keeps one position per row, taken BEFORE the
    vocabulary projection: logits are then (B, 1, vocab).  Serving
    prefill reads only each request's last prompt position, and the
    full (B, S, vocab) tensor would not fit a chip at serving batch.
    """
    # per-row cache_pos (B,) — batched serving decode where every
    # right-padded request sits at its own depth — only reaches the
    # token frontends (the audio sinusoid stub needs a shared offset)
    vec_pos = getattr(cache_pos, "ndim", 0) == 1
    if vec_pos:
        assert cfg.family not in ("audio",), \
            "per-row cache positions need token inputs"
    x, text_off = embed_inputs(cfg, params, batch,
                               pos_offset=(0 if vec_pos else cache_pos)
                               if cache_pos is not None else 0)
    B, S, _ = x.shape
    if vec_pos:
        positions = (cache_pos.astype(jnp.int32)[:, None]
                     + jnp.arange(S, dtype=jnp.int32)[None, :])
    elif cache_pos is not None:
        positions = cache_pos + jnp.arange(S)[None, :].astype(jnp.int32)
        positions = jnp.broadcast_to(positions, (B, S))
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :],
                                     (B, S))
    x = shard(x, "batch", "sp", None)

    ad_segs = adapters["segments"] if adapters else [{} for _ in segment_plan(cfg)]
    aux = jnp.zeros((), jnp.float32)
    new_caches = [] if caches is not None else None
    for i, seg in enumerate(segment_plan(cfg)):
        c = caches[i] if caches is not None else None
        x, nc, a = _apply_segment(cfg, seg, params["segments"][i],
                                  ad_segs[i], lora, x, positions,
                                  c, cache_pos, ring, remat,
                                  unroll=unroll_layers)
        aux = aux + a
        if new_caches is not None:
            new_caches.append(nc)
    if logits_at is not None:
        x = x[jnp.arange(B), logits_at][:, None]
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return _logits(cfg, params, x), aux, new_caches, text_off


def loss_fn(cfg: ModelConfig, params: dict, adapters: dict,
            lora: Optional[MultiLoRA], batch: dict, *,
            remat: bool = True,
            per_job_denom: Optional[jax.Array] = None,
            unroll_layers: bool = False
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Per-job-separated LM loss over a fused batch (lossless contract).

    Each job's loss is normalized over *its own* token count, so gradients
    w.r.t. job j's adapter are identical to training j alone (up to the
    backbone being frozen — which it is).  Total = sum_j loss_j.
    """
    logits, aux, _, off = forward(cfg, params, adapters, lora, batch,
                                  remat=remat, unroll_layers=unroll_layers)
    labels = batch["labels"]
    if off:
        logits = logits[:, off:]
    if cfg.causal:
        logits = logits[:, :-1]
        labels = labels[:, 1:]
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[:, -labels.shape[-1]:]
    tok_loss = cross_entropy(logits, labels, mask=mask)         # (B, S')
    seq_loss = tok_loss.sum(axis=-1)                            # (B,)
    seq_count = (jnp.full(seq_loss.shape, labels.shape[-1], jnp.float32)
                 if mask is None else mask.astype(jnp.float32).sum(-1))
    if lora is not None:
        K = lora.num_adapters
        onehot = jax.nn.one_hot(lora.adapter_ids, K, dtype=jnp.float32)  # (B,K)
        denom = (per_job_denom if per_job_denom is not None
                 else jnp.clip(onehot.T @ seq_count, 1))
        per_job = (onehot.T @ seq_loss) / denom
        total = per_job.sum() + aux
        axis = getattr(lora, "axis_name", None)
        if axis is not None and lora.grad_sync == "gather":
            # Sharded exact mode (DESIGN.md §8): the gradient flows
            # through the LOCAL partial above — its per-row cotangents
            # are the same 1/denom scalars solo execution produces, and
            # the kernel VJPs make the wgrads globally exact.  The
            # REPORTED per-job losses are recomputed at full shape from
            # the per-row losses reassembled in solo row order, so
            # metrics are bit-identical to the single-device step.
            # stop_gradient: metrics-only — no collective transposes in
            # the backward.
            from repro.kernels.ops import gather_solo
            rp = lora.row_solo_pos
            R = lora.shards * lora.local_rows
            sl = jax.lax.stop_gradient(gather_solo(seq_loss, axis, rp, R))
            sc = jax.lax.stop_gradient(gather_solo(seq_count, axis, rp, R))
            idg = gather_solo(lora.adapter_ids, axis, rp, R)
            oh_g = jax.nn.one_hot(idg, K, dtype=jnp.float32)
            per_job = (oh_g.T @ sl) / denom
            return total, {"per_job": per_job, "aux": aux,
                           "per_job_count": oh_g.T @ sc}
        return total, {"per_job": per_job, "aux": aux,
                       "per_job_count": onehot.T @ seq_count}
    total = seq_loss.sum() / jnp.clip(seq_count.sum(), 1) + aux
    return total, {"per_job": total[None], "aux": aux}


def decode_step(cfg: ModelConfig, params: dict, adapters: Optional[dict],
                lora: Optional[MultiLoRA], token: jax.Array, pos,
                caches: list, *, ring: bool = False,
                logits_at: Optional[jax.Array] = None):
    """One decode step. token: (B, 1..S) int32; pos: scalar position or a
    per-row ``(B,)`` vector (fused serving: each request at its own depth).

    Returns (logits (B, S, V), new_caches) — (B, 1, V) with
    ``logits_at`` (see ``forward``).
    """
    logits, _, new_caches, _ = forward(
        cfg, params, adapters, lora, {"tokens": token},
        caches=caches, cache_pos=pos, ring=ring, logits_at=logits_at)
    return logits, new_caches


# ----------------------------------------------------------------- inputs
def make_batch(cfg: ModelConfig, shape: InputShape, key=None,
               as_specs: bool = False, batch_override: Optional[int] = None):
    """Concrete arrays (tests) or ShapeDtypeStructs (dry-run) for one step.

    Training/prefill batch for train/prefill kinds; decode kind returns the
    single-token step inputs (caches built separately via init_caches).
    """
    B = batch_override or shape.global_batch
    S = shape.seq_len
    i32 = jnp.int32

    def tok(shp, vocab):
        if as_specs:
            return jax.ShapeDtypeStruct(shp, i32)
        k = key if key is not None else jax.random.PRNGKey(0)
        return jax.random.randint(k, shp, 0, vocab, i32)

    def emb(shp):
        if as_specs:
            return jax.ShapeDtypeStruct(shp, dtype_of(cfg))
        k = key if key is not None else jax.random.PRNGKey(1)
        return (jax.random.normal(k, shp, jnp.float32) * 0.02).astype(dtype_of(cfg))

    if shape.kind == "decode":
        return {"tokens": tok((B, 1), cfg.vocab_size)}

    batch: Dict[str, Any] = {}
    if cfg.family == "audio":
        batch["frames"] = emb((B, S, cfg.frontend_dim))
        batch["labels"] = tok((B, S), cfg.vocab_size)
    elif cfg.family == "vlm":
        P = cfg.num_patches
        batch["patches"] = emb((B, P, cfg.frontend_dim))
        batch["tokens"] = tok((B, S - P), cfg.vocab_size)
        batch["labels"] = tok((B, S - P), cfg.vocab_size)
    else:
        batch["tokens"] = tok((B, S), cfg.vocab_size)
        batch["labels"] = tok((B, S), cfg.vocab_size)
    return batch


def input_specs(cfg: ModelConfig, shape: InputShape,
                batch_override: Optional[int] = None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (dry-run)."""
    return make_batch(cfg, shape, as_specs=True, batch_override=batch_override)
