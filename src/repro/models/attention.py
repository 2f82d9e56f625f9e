"""Attention: GQA/MQA, flash (chunked online-softmax) with custom VJP,
KV caches.

One blockwise implementation serves every mode:
  * train / prefill  — q over its own k/v, causal or bidirectional,
                       optional sliding window; O(S·chunk) memory.
                       Training uses a FLASH CUSTOM VJP: backward
                       recomputes scores per KV chunk from (q,k,v,out,lse)
                       instead of storing them, and every dot runs with
                       bf16 inputs + f32 accumulation (§Perf iterations
                       1-2 in EXPERIMENTS.md).  Where the program lowers
                       for TPU and the geometry is static (no window, no
                       offset, whole lane-aligned blocks, no GSPMD axis
                       to partition over), that VJP's forward and
                       backward are the Pallas kernels of
                       kernels/flash_attention.py; elsewhere they are
                       the XLA chunk scans below.
  * decode           — q (S=1..n) over a cache buffer (full or ring).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.lora import MultiLoRA, proj
from repro.kernels import flash_attention as flash_kernel
from repro.models.layers import apply_rope, dense_init, rms_norm, rms_norm_init
from repro.sharding import partitioned, shard

NEG_BIG = -1e30
_F32_ATTN = False    # legacy f32-attention path (EXPERIMENTS.md §Perf A/B)
_USE_FLASH = True    # flash custom-VJP for training (§Perf iteration 2)


# ----------------------------------------------------------------- flash
def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      *, q_offset, kv_len, causal: bool,
                      window: Optional[int], chunk: int = 1024) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Skv, KV, hd). Returns (B, Sq, H, hd).

    q_offset: absolute position of q[0] (int or traced scalar) — or a
              per-row ``(B,)`` vector (batched decode over right-padded
              requests whose write heads sit at different positions).
    kv_len:   number of valid kv entries (<= Skv), traced ok; ``(B,)``
              per-row in the same batched-decode regime.
    window:   if set, keys with qpos - kpos >= window are masked out.

    Static geometry (training/prefill) routes through the flash custom
    VJP, on the Pallas kernels where the geometry allows (see
    ``_make_flash``); traced offsets (decode) use the plain scan (never
    differentiated).
    """
    if (_USE_FLASH and not _F32_ATTN
            and isinstance(q_offset, int) and isinstance(kv_len, int)):
        kernel = (q_offset == 0 and window is None
                  and kv_len == q.shape[1] == k.shape[1]
                  and not partitioned()
                  and flash_kernel.supports(q.shape, k.shape, v.shape,
                                            q.dtype.itemsize))
        fn = _make_flash(q_offset, kv_len, causal, window, chunk, kernel)
        return fn(q, k, v)
    out, _ = _chunked_attention_fwd(q, k, v, q_offset=q_offset,
                                    kv_len=kv_len, causal=causal,
                                    window=window, chunk=chunk)
    return out


def _rep_heads(t: jax.Array, G: int) -> jax.Array:
    """(B, c, KV, d) -> (B, c, H, d): chunk-local GQA head broadcast.

    Flat-H einsums keep every tensor 4-D with the full head dim — GSPMD
    shards H over the model axis cleanly instead of fighting the (KV, G)
    split (§Perf iteration 3: kills the 'involuntary full
    rematerialization' reshards).
    """
    if G == 1:
        return t
    rep = jnp.repeat(t, G, axis=2)
    return shard(rep, "batch", None, "tp")


def _chunked_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, q_offset, kv_len, causal: bool,
                           window: Optional[int], chunk: int = 1024):
    """Online-softmax chunk scan; returns (out (B,Sq,H,vd), lse (B,H,Sq))."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    vd = v.shape[-1]                       # may differ from hd (MLA)
    G = H // KV
    chunk = min(chunk, Skv)
    n_chunks = (Skv + chunk - 1) // chunk
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # MXU-friendly: keep q/k/v in their storage dtype (bf16 on TPU) and
    # accumulate in f32 via preferred_element_type — half the HBM traffic
    # and full-rate MXU vs f32xf32 dots (§Perf iteration 1).
    if _F32_ATTN:                  # A/B toggle for EXPERIMENTS.md §Perf
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    scale = hd ** -0.5
    # per-row geometry (batched serving decode): (B,) q_offset / kv_len
    # give every row its own causal frontier.  Masked keys contribute an
    # exact 0.0 to the online softmax (exp(NEG_BIG - m) underflows), so
    # a padded fused batch reproduces each row's solo attention.
    per_row = (getattr(q_offset, "ndim", 0) == 1
               or getattr(kv_len, "ndim", 0) == 1)
    if per_row:
        qpos = (jnp.reshape(jnp.asarray(q_offset), (-1, 1))
                + jnp.arange(Sq))                       # (B|1, Sq)
        qpos = jnp.broadcast_to(qpos, (B, Sq))
        kv_len_b = jnp.broadcast_to(
            jnp.reshape(jnp.asarray(kv_len), (-1, 1)), (B, 1))
    else:
        qpos = q_offset + jnp.arange(Sq)

    kc = k.reshape(B, n_chunks, chunk, KV, hd).swapaxes(0, 1)
    vc = v.reshape(B, n_chunks, chunk, KV, vd).swapaxes(0, 1)

    def body(carry, inputs):
        m, l, acc = carry
        ci, k_c, v_c = inputs
        kpos = ci * chunk + jnp.arange(chunk)
        s = jnp.einsum("bshd,bchd->bhsc", q, _rep_heads(k_c, G),
                       preferred_element_type=jnp.float32) * scale
        if per_row:
            valid = (kpos[None, None, :] < kv_len_b[:, :, None])
            if causal:
                valid = valid & (kpos[None, None, :] <= qpos[:, :, None])
            if window is not None:
                valid = valid & (kpos[None, None, :]
                                 > qpos[:, :, None] - window)
            # s: (B, H, Sq, chunk); valid: (B, Sq, chunk)
            s = jnp.where(valid[:, None], s, NEG_BIG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhsc,bchd->bshd", p.astype(q.dtype),
                            _rep_heads(v_c, G),
                            preferred_element_type=jnp.float32)
            acc = acc * corr.transpose(0, 2, 1)[..., None] + pv
            return (m_new, l, acc), None
        valid = (kpos[None, :] < kv_len)
        if causal:
            valid = valid & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            valid = valid & (kpos[None, :] > qpos[:, None] - window)
        # s: (B, H, Sq, chunk); valid: (Sq, chunk)
        s = jnp.where(valid[None, None, :, :], s, NEG_BIG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhsc,bchd->bshd", p.astype(q.dtype),
                        _rep_heads(v_c, G),
                        preferred_element_type=jnp.float32)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Sq), NEG_BIG, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, H, vd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc))
    lden = jnp.where(l == 0, 1.0, l)
    out = acc / lden.transpose(0, 2, 1)[..., None]
    lse = m + jnp.log(lden)                            # (B, H, Sq)
    return out.astype(q.dtype), lse


# --------------------------------------------------------- flash custom VJP
@functools.lru_cache(maxsize=256)
def _make_flash(q_offset: int, kv_len: int, causal: bool,
                window: Optional[int], chunk: int, kernel: bool = False):
    """Flash attention with hand-written backward (static geometry).

    Forward = the online-softmax chunk scan above (saves out + lse, never
    the (Sq x Skv) score matrix).  Backward re-walks the KV chunks,
    recomputing p = exp(s - lse) per chunk; every dot takes bf16 inputs
    with f32 accumulation.

    ``kernel``: the geometry is one the Pallas kernels take (the caller
    checks).  Forward and backward are then chosen at lowering: the
    kernels where the program is compiled for TPU, these XLA scans
    everywhere else.  Both save the same residuals.
    """

    def xla_fwd(q, k, v):
        return _chunked_attention_fwd(q, k, v, q_offset=q_offset,
                                      kv_len=kv_len, causal=causal,
                                      window=window, chunk=chunk)

    def xla_bwd(q, k, v, out, lse, dout):
        B, Sq, H, hd = q.shape
        Skv, KV = k.shape[1], k.shape[2]
        vd = v.shape[-1]
        G = H // KV
        ck = min(chunk, Skv)
        n_chunks = (Skv + ck - 1) // ck
        pad = n_chunks * ck - Skv
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        scale = hd ** -0.5
        qpos = q_offset + jnp.arange(Sq)

        # cotangents arrive in whatever dtype the downstream produced;
        # flash takes them in the storage dtype (bf16 dots on the MXU)
        dout = shard(dout.astype(q.dtype), "batch", None, "tp")
        # D_i = sum_d dout_i * out_i  (flash-2 backward identity)
        D = jnp.einsum("bshd,bshd->bhs", dout.astype(jnp.float32),
                       out.astype(jnp.float32))

        kc = k.reshape(B, n_chunks, ck, KV, hd).swapaxes(0, 1)
        vc = v.reshape(B, n_chunks, ck, KV, vd).swapaxes(0, 1)

        def body(dq_acc, inputs):
            ci, k_c, v_c = inputs
            kH, vH = _rep_heads(k_c, G), _rep_heads(v_c, G)
            kpos = ci * ck + jnp.arange(ck)
            s = jnp.einsum("bshd,bchd->bhsc", q, kH,
                           preferred_element_type=jnp.float32) * scale
            valid = (kpos[None, :] < kv_len)
            if causal:
                valid = valid & (kpos[None, :] <= qpos[:, None])
            if window is not None:
                valid = valid & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(valid[None, None, :, :], s, NEG_BIG)
            p = jnp.exp(s - lse[..., None])               # (B,H,Sq,c)
            pb = p.astype(q.dtype)
            dvH = jnp.einsum("bhsc,bshd->bchd", pb, dout,
                             preferred_element_type=jnp.float32)
            dp = jnp.einsum("bshd,bchd->bhsc", dout, vH,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - D[..., None]) * scale).astype(q.dtype)
            dq_acc = dq_acc + jnp.einsum(
                "bhsc,bchd->bshd", ds, kH,
                preferred_element_type=jnp.float32)
            dkH = jnp.einsum("bhsc,bshd->bchd", ds, q,
                             preferred_element_type=jnp.float32)
            # fold the GQA head broadcast back: sum over the G groups
            dk_c = dkH.reshape(B, ck, KV, G, hd).sum(axis=3)
            dv_c = dvH.reshape(B, ck, KV, G, vd).sum(axis=3)
            return dq_acc, (dk_c, dv_c)

        dq0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
        dq, (dk, dv) = jax.lax.scan(body, dq0,
                                    (jnp.arange(n_chunks), kc, vc))
        dk = dk.swapaxes(0, 1).reshape(B, n_chunks * ck, KV, hd)
        dv = dv.swapaxes(0, 1).reshape(B, n_chunks * ck, KV, vd)
        if pad:
            dk, dv = dk[:, :Skv], dv[:, :Skv]
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))

    fwd_impl, bwd_impl = xla_fwd, xla_bwd
    if kernel:
        fwd_impl = lambda *a: jax.lax.platform_dependent(
            *a, tpu=functools.partial(flash_kernel.flash_fwd, causal=causal),
            default=xla_fwd)
        bwd_impl = lambda *a: jax.lax.platform_dependent(
            *a, tpu=functools.partial(flash_kernel.flash_bwd, causal=causal),
            default=xla_bwd)

    @jax.custom_vjp
    def f(q, k, v):
        return fwd_impl(q, k, v)[0]

    def fwd(q, k, v):
        out, lse = fwd_impl(q, k, v)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        return bwd_impl(*res, dout)

    f.defvjp(fwd, bwd)
    return f


# ----------------------------------------------------------------- caches
class KVCache(NamedTuple):
    """Full or ring-buffer KV cache for one attention segment.

    k/v: (L?, B, buf, KV, hd) — leading layer axis added when stacked.
    ring=True => buf is a sliding window indexed modulo buf.
    """
    k: jax.Array
    v: jax.Array

    @staticmethod
    def init(batch, buf, kv_heads, hd, dtype, layers: Optional[int] = None):
        shape = (batch, buf, kv_heads, hd)
        if layers is not None:
            shape = (layers,) + shape
        z = jnp.zeros(shape, dtype)
        return KVCache(z, z)


def cache_update(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                 pos, ring: bool) -> KVCache:
    """Insert k/v (B, S, KV, hd) at absolute position *pos*.

    ``pos`` may be a per-row ``(B,)`` vector (batched serving decode:
    each right-padded request writes at its own head) — rows scatter
    independently; ring buffers only take a shared scalar position.
    """
    buf = cache.k.shape[1]
    S = k_new.shape[1]
    if getattr(pos, "ndim", 0) == 1:
        assert not ring, "per-row cache positions need a full (non-ring) buffer"
        rows = jnp.arange(cache.k.shape[0])[:, None]
        cols = pos[:, None] + jnp.arange(S)[None, :]
        k = cache.k.at[rows, cols].set(k_new.astype(cache.k.dtype))
        v = cache.v.at[rows, cols].set(v_new.astype(cache.v.dtype))
        return KVCache(k, v)
    if ring:
        idx = (pos + jnp.arange(S)) % buf
        k = cache.k.at[:, idx].set(k_new.astype(cache.k.dtype))
        v = cache.v.at[:, idx].set(v_new.astype(cache.v.dtype))
    else:
        k = jax.lax.dynamic_update_slice(
            cache.k, k_new.astype(cache.k.dtype), (0, pos, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache.v, v_new.astype(cache.v.dtype), (0, pos, 0, 0))
    return KVCache(k, v)


def decode_attention(q: jax.Array, cache: KVCache, pos, *,
                     window: Optional[int], ring: bool,
                     chunk: int = 2048) -> jax.Array:
    """q: (B, S=1.., H, hd) attending over the cache after update at pos.

    ``pos`` scalar, or ``(B,)`` per-row (non-ring only): kv_len and the
    causal frontier then mask per row, so a fused batch of requests at
    different depths attends exactly like each would solo.
    """
    if ring:
        assert getattr(pos, "ndim", 0) == 0, \
            "ring decode needs a shared scalar position"
        # ring buffer holds the last `buf` tokens; attention is permutation-
        # invariant over keys so order inside the ring doesn't matter.
        # Supports S=1 (decode) — prefill uses the cache-less path.
        buf = cache.k.shape[1]
        kv_len = jnp.minimum(pos + q.shape[1], buf)
        # remap: treat buffer as unordered set — attention is permutation-
        # invariant over keys, so masking by count suffices for a full ring.
        return chunked_attention(q, cache.k, cache.v,
                                 q_offset=kv_len - q.shape[1], kv_len=kv_len,
                                 causal=False, window=None, chunk=chunk)
    kv_len = pos + q.shape[1]
    return chunked_attention(q, cache.k, cache.v, q_offset=pos, kv_len=kv_len,
                             causal=True, window=window, chunk=chunk)


# ----------------------------------------------------------------- block
def attn_init(key, cfg) -> dict:
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.q_dim, dt),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim, dt),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim, dt),
        "wo": dense_init(ks[3], cfg.q_dim, cfg.d_model, dt),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.kv_dim,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.kv_dim,), jnp.float32)
    return p


def attn_block(cfg, params: dict, x: jax.Array, *,
               positions: jax.Array,
               lora: Optional[MultiLoRA] = None,
               lora_ab: Optional[dict] = None,
               cache: Optional[KVCache] = None,
               cache_pos=None,
               local: bool = False,
               ring: bool = False,
               chunk: int = 1024) -> Tuple[jax.Array, Optional[KVCache]]:
    """GQA attention with optional fused multi-LoRA on q/k/v/o.

    x: (B, S, d). Returns (out, new_cache).
    """
    B, S, _ = x.shape
    la = lora_ab or {}
    q = proj(x, params["wq"], params.get("bq"), lora, la.get("q"))
    k = proj(x, params["wk"], params.get("bk"), lora, la.get("k"))
    v = proj(x, params["wv"], params.get("bv"), lora, la.get("v"))
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    q = shard(q, "batch", "seq", "tp")
    k = shard(k, "batch", "seq", "tp")
    v = shard(v, "batch", "seq", "tp")

    if cfg.causal:  # rope only for decoder archs; encoder uses abs-pos embed
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    window = cfg.sliding_window if local else None
    if cache is not None:
        cache = cache_update(cache, k, v, cache_pos, ring)
        out = decode_attention(q, cache, cache_pos, window=window,
                               ring=ring, chunk=chunk)
    else:
        out = chunked_attention(q, k, v, q_offset=0, kv_len=S,
                                causal=cfg.causal, window=window, chunk=chunk)
    out = out.reshape(B, S, cfg.q_dim)
    y = proj(out, params["wo"], None, lora, la.get("o"))
    return shard(y, "batch", "sp", None), cache
