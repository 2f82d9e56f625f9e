"""Operations and bytes a fused multi-LoRA training step requires,
computed from the cell's shapes, and the chips' peaks.

"Required" is what the algorithm needs, whatever computes it:

* the frozen backbone: forward and input gradient of every projection
  (``4 * d_in * d_out`` per token, no weight gradient), the head the
  same, and causal attention (``4 * heads * head_dim * (t + 1)`` for
  the token at position t in its document, three times over for the
  forward and the input gradients of q, k and v), counted by the
  configuration's family (``families/<family>.py``);
* each job's adapters at its own rank: the forward ``x A``, ``(x A) B``,
  their two input gradients and two weight gradients,
  ``6 * r * (d_in + d_out)`` per token and projection.

Only real (loss-mask) tokens count toward a step's utilization;
recomputation and padding do not.  The LoRA kernels' own roofline
counts each job's rows as the kernels get them (every position of the
job's rows), since no kernel family can skip a row's padding.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Sequence

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")
BF16_BYTES = 2
F32_BYTES = 4


def peaks(device_kind: str) -> Dict:
    """The chip's peaks by ``device_kind``; an unknown chip is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def lora_flops(fam, m: Dict, tokens_by_rank: Dict[int, float]) -> float:
    """Forward, input and weight gradients of every adapter of the
    family *fam*, for ``{rank: tokens}``."""
    dims = fam.adapter_dims(m)
    per_rank_token = 6.0 * sum(di + do for di, do in dims.values())
    return m["num_hidden_layers"] * per_rank_token * sum(
        r * t for r, t in tokens_by_rank.items())


def lora_bytes(fam, m: Dict, tokens_by_rank: Dict[int, float]) -> float:
    """Least HBM traffic of the adapters' kernels: per projection, the
    forward reads x and writes the delta, the input gradient reads dy
    and writes dx, the weight gradient reads x and dy (bf16); every
    kernel reads A and B (bf16) and the weight gradient writes dA and
    dB (f32)."""
    dims = fam.adapter_dims(m)
    total = 0.0
    for r, t in tokens_by_rank.items():
        for di, do in dims.values():
            act = BF16_BYTES * t * 3 * (di + do)
            w = 3 * BF16_BYTES * r * (di + do) + F32_BYTES * r * (di + do)
            total += act + w
    return m["num_hidden_layers"] * total


def step_required_flops(fam, m: Dict, traffic: Dict,
                        doc_lengths: Sequence[int]) -> float:
    """Required FLOPs of one step over its real tokens, the family
    *fam* counting its backbone and attention: *doc_lengths* lists
    every row's document length, rows in job order."""
    n = np.asarray(doc_lengths, np.float64)
    rows = [int(j["rows"]) for j in traffic["jobs"]]
    offs = np.concatenate([[0], np.cumsum(rows)])
    by_rank: Dict[int, float] = {}
    for k, j in enumerate(traffic["jobs"]):
        r = int(j["rank"])
        by_rank[r] = by_rank.get(r, 0.0) + float(n[offs[k]:offs[k + 1]].sum())
    return (fam.backbone_flops_per_token(m) * float(n.sum())
            + fam.attention_flops(m, n) + lora_flops(fam, m, by_rank))


def lora_kernel_tokens(traffic: Dict) -> Dict[int, float]:
    """``{rank: tokens}`` the LoRA kernels take per step: each job's
    rows times the sequence length."""
    out: Dict[int, float] = {}
    for j in traffic["jobs"]:
        r = int(j["rank"])
        out[r] = out.get(r, 0.0) + int(j["rows"]) * int(traffic["seq_len"])
    return out
