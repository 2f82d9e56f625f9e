"""The dense GQA decoder family: pre-norm blocks of RMSNorm,
grouped-query attention with rotary embeddings and optional QKV bias,
then RMSNorm and a SwiGLU MLP; a final RMSNorm and an untied head.

Everything the benchmark knows of this family's shapes lives here: the
keys of its configuration file, its weights by name, its adapted
projections, its required FLOPs, and the name of its plain reference
(``reference/model.py``).  The generic code finds this module by the
configuration file's ``"family"`` (``catalog.family``).
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from weights import BF16, draw, job_adapter, proj_log2

REFERENCE = "reference.model"

# source config.json key -> ModelConfig field
HF_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


def model_fields(m: Dict) -> Dict:
    """The program's ``ModelConfig`` fields that the ``model`` block
    states."""
    kw = {field: m[key] for key, field in HF_KEYS.items() if key in m}
    kw["head_dim"] = m.get("head_dim", m["hidden_size"]
                           // m["num_attention_heads"])
    kw["attn_bias"] = bool(m["qkv_bias"])
    kw["family"] = "dense"
    return kw


# --------------------------------------------------------------- weights
def _widths(m: Dict):
    d = m["hidden_size"]
    hd = m.get("head_dim", d // m["num_attention_heads"])
    return d, m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd


def layer_shapes(m: Dict) -> Dict[str, tuple]:
    """(shape, log2 scale, dtype) of each weight of one layer, from the
    configuration file's ``model`` block."""
    d, qd, kvd = _widths(m)
    f = m["intermediate_size"]
    out = {
        "wq": ((d, qd), proj_log2(d), BF16),
        "wk": ((d, kvd), proj_log2(d), BF16),
        "wv": ((d, kvd), proj_log2(d), BF16),
        "wo": ((qd, d), proj_log2(qd), BF16),
        "gate": ((d, f), proj_log2(d), BF16),
        "up": ((d, f), proj_log2(d), BF16),
        "down": ((f, d), proj_log2(f), BF16),
        "ln1": ((d,), 3, jnp.float32),
        "ln2": ((d,), 3, jnp.float32),
    }
    if m["qkv_bias"]:
        out.update(bq=((qd,), 2, jnp.float32), bk=((kvd,), 2, jnp.float32),
                   bv=((kvd,), 2, jnp.float32))
    return out


def layer_weights(key, m: Dict, layer: int) -> Dict[str, jax.Array]:
    """Layer *layer*'s weights by name.  Norm leaves are the gain minus
    one (the program's storage); the reference adds the one back."""
    return {n: draw(key, f"layer.{n}", s, e, dt, layer)
            for n, (s, e, dt) in layer_shapes(m).items()}


def top_weights(key, m: Dict) -> Dict[str, jax.Array]:
    d, V = m["hidden_size"], m["vocab_size"]
    return {"embed": draw(key, "embed", (V, d), 6, BF16),
            "head": draw(key, "head", (d, V), proj_log2(d), BF16),
            "ln_f": draw(key, "ln_f", (d,), 3, jnp.float32)}


def adapter_dims(m: Dict) -> Dict[str, tuple]:
    """(d_in, d_out) of each adapted projection."""
    d, qd, kvd = _widths(m)
    return {"q": (d, qd), "k": (d, kvd), "v": (d, kvd), "o": (qd, d)}


def layer_tree(key, m: Dict, ranks: Sequence[int], layout, layer):
    """Layer *layer*'s backbone and packed adapter leaves in the
    program's layout (``models/model.init_model`` / ``init_adapters``):
    each job's pair zero-padded to its slot and the jobs side by side."""
    w = layer_weights(key, m, layer)
    attn = {n: w[n] for n in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if n in w}
    backbone = {"ln1": w["ln1"], "ln2": w["ln2"], "attn": attn,
                "ffn": {"gate": w["gate"], "up": w["up"], "down": w["down"]}}
    dims = adapter_dims(m)
    jobs = [job_adapter(dims, key, k, layer, r) for k, r in enumerate(ranks)]
    adapter = {}
    for t in dims:
        As, Bs = [], []
        for k, r in enumerate(ranks):
            _, rp = layout.slice_of(k)
            As.append(jnp.pad(jobs[k][t]["A"], ((0, 0), (0, rp - r))))
            Bs.append(jnp.pad(jobs[k][t]["B"], ((0, rp - r), (0, 0))))
        adapter[t] = {"A": jnp.concatenate(As, 1),
                      "B": jnp.concatenate(Bs, 0)}
    return backbone, adapter


# ----------------------------------------------------------------- FLOPs
def backbone_flops_per_token(m: Dict) -> float:
    """Forward + input gradient of the projections and the head."""
    d, qd, kvd = _widths(m)
    per_layer = d * qd + 2 * d * kvd + qd * d + 3 * d * m["intermediate_size"]
    return 4.0 * (m["num_hidden_layers"] * per_layer + d * m["vocab_size"])


def attention_flops(m: Dict, doc_lengths: Sequence[int]) -> float:
    """Causal attention over documents of these lengths: forward (q k^T
    and p v) and its input gradients, every layer."""
    _, qd, _ = _widths(m)
    n = np.asarray(doc_lengths, np.float64)
    fwd = 4.0 * qd * float(np.sum(n * (n + 1) / 2))
    return 3.0 * fwd * m["num_hidden_layers"]
