"""Quantized frozen backbone: symmetric per-channel int8 (DESIGN.md §14).

LoRA never updates base weights, so quantizing the frozen backbone is a
pure capacity-and-bandwidth win (QLoRA-style): int8 storage halves the
weight-streaming bytes that floor memory-bound fused groups and halves
the backbone HBM shard the scheduler must fit — roughly doubling
packable K per device.

Format — ``QuantTensor``: a registered pytree holding

  * ``q``     int8  ``(..., d_in, d_out)`` — rounded weight codes,
  * ``scale`` f32   ``(..., d_out)``       — one amax/127 scale PER
    OUTPUT CHANNEL (the contraction axis is reduced away), so the scale
    commutes with the matmul: ``x @ (q*s) == (x @ q) * s[None, :]`` and
    dequant can ride the kernel epilogue in-register.

``quantize_params`` walks a backbone tree and converts only the dense
projection weights the fused-LoRA contract targets (attention q/k/v/o,
MLA q/kv_a/kv_b/o, swiglu/gelu FFN mats, SSD + RGLRU in/out
projections).  Everything numerically fragile stays high precision:
embeddings, lm head, modality frontends, norms, biases, the MoE router,
RGLRU's f32 recurrence mats (w_a/w_i), conv stacks, SSD's
dt_bias/A_log/D — and the MoE 3-D expert slabs, which feed
``jax.lax.ragged_dot`` and would need a dense dequantized copy anyway
(their per-layer bytes are amortized over E experts; shared experts DO
quantize through their swiglu leaves).

Dispatch — ``qdot(x, w)`` is the drop-in matmul used by every consuming
site (core/lora.proj, models/layers.swiglu/gelu_mlp, models/mla):
plain arrays take the ordinary ``@``; QuantTensors route to
``kernels/ops.dequant_matmul`` under the process-wide impl knob
(``set_dequant_impl``): "pallas" = the fused in-register tile kernel,
"xla" (default) = the same expression under ``jax.checkpoint`` so the
dequant recomputes in the backward instead of living in HBM.  Both
evaluate identically (full-contraction f32-accumulated dot, per-channel
scale epilogue), so flipping the impl never changes numerics.

Scanned segments need no special casing: QuantTensor is a pytree, so
``lax.scan`` / per-layer slicing index ``q`` and ``scale`` leaf-wise,
and the sharding rules replicate the unknown leaf names (P()).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.tree_util import GetAttrKey, register_pytree_with_keys_class


@register_pytree_with_keys_class
@dataclasses.dataclass
class QuantTensor:
    """Int8 codes + f32 per-output-channel scales for one weight."""
    q: jax.Array          # int8, (..., d_in, d_out)
    scale: jax.Array      # f32,  (..., d_out)

    def tree_flatten_with_keys(self):
        return (((GetAttrKey("q"), self.q),
                 (GetAttrKey("scale"), self.scale)), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim


def quantize_array(w: jax.Array) -> QuantTensor:
    """Symmetric per-output-channel int8: scale = amax(|w|, contraction
    axis)/127, codes = round(w/scale) clipped to [-127, 127]."""
    wf = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / jnp.expand_dims(scale, -2)),
                 -127, 127).astype(jnp.int8)
    return QuantTensor(q=q, scale=scale)


def asarray(w: Any, dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """Materialize a dequantized copy (small decode-path absorbs only —
    the training hot path must go through ``qdot``). Plain arrays pass
    through untouched."""
    if not isinstance(w, QuantTensor):
        return w
    out = w.q.astype(jnp.float32) * jnp.expand_dims(w.scale, -2)
    return out.astype(dtype) if dtype is not None else out


# Leaf names eligible for quantization (2-D per layer; scanned stacks
# carry a leading layer axis).  MoE expert slabs reuse w_in/w_out but
# sit next to a "router" leaf — excluded by the walk below.
TARGET_LEAVES = frozenset({
    "wq", "wk", "wv", "wo",        # attention / MLA head projections
    "w_kv_a", "w_kv_b",            # MLA latent down/up
    "gate", "up", "down",          # swiglu / gelu FFN (incl. MoE shared)
    "w_x", "w_gate",               # RGLRU input / gate projections
    "w_in", "w_out",               # SSD in/out (MoE slabs excluded)
})


def _quantize_leaf(name: str, v: Any, in_moe: bool) -> Any:
    if isinstance(v, QuantTensor):
        return v                           # idempotent
    if in_moe and name in ("w_in", "w_out"):
        return v                           # ragged_dot expert slabs
    if name in TARGET_LEAVES and getattr(v, "ndim", 0) >= 2:
        return quantize_array(v)
    return v


def _walk(node: Any) -> Any:
    if isinstance(node, dict):
        in_moe = "router" in node          # a moe_init param dict
        return {k: _walk(v) if isinstance(v, (dict, list))
                else _quantize_leaf(k, v, in_moe)
                for k, v in node.items()}
    if isinstance(node, list):
        return [_walk(v) for v in node]
    return node


def quantize_params(params: dict, mode: Optional[str] = "int8") -> dict:
    """Quantize a frozen backbone tree. ``mode=None`` is the identity;
    only "int8" is implemented. Idempotent on already-quantized trees."""
    if mode is None:
        return params
    if mode != "int8":
        raise ValueError(f"unknown quantization mode {mode!r}")
    return _walk(params)


def is_quantized(params: dict) -> bool:
    return any(isinstance(l, QuantTensor)
               for l in jax.tree.leaves(
                   params, is_leaf=lambda x: isinstance(x, QuantTensor)))


def backbone_dtype(params: Optional[dict]) -> str:
    """Calibration-bucket tag for the backbone storage dtype."""
    return "int8" if params is not None and is_quantized(params) else "bf16"


# ------------------------------------------------------------- dispatch
_DEQUANT_IMPL = "xla"


def set_dequant_impl(impl: str) -> None:
    """Select the dequant-matmul kernel process-wide ("xla" | "pallas").

    Call BEFORE building train steps — the impl is baked into traced
    programs. Numerics are identical either way."""
    global _DEQUANT_IMPL
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown dequant impl {impl!r}")
    _DEQUANT_IMPL = impl


def get_dequant_impl() -> str:
    return _DEQUANT_IMPL


def qdot(x: jax.Array, w: Any) -> jax.Array:
    """``x @ w`` for a plain array or a QuantTensor (fused dequant)."""
    if not isinstance(w, QuantTensor):
        return x @ w
    from repro.kernels import ops
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = ops.dequant_matmul(x2, w.q, w.scale, impl=_DEQUANT_IMPL)
    return y.reshape(*lead, w.q.shape[-1])
