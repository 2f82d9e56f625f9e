"""Random weights from ``--seed``, one generator for the harness and the
reference alike.

Every matrix is named (``layer.wq``, ``embed``, ``job2.q.A``) and, for
layer l, drawn as ``normal(fold_in(fold_in(key, crc32(name)), l)) *
2**-e`` in float32, then cast to the type it is served in.  A power-of-two scale keeps the
draw exact under any fusion, so the reference, which makes each layer's
weights again by name, holds the same numbers the program was given.

Scales: a projection ``2**-round(log2(sqrt(d_in)))``, the embedding
``2**-6``, norm gains ``1 + 2**-3 z``, QKV biases ``2**-2 z``.  Adapters
are those of a job in training: ``A`` at the projection scale and ``B``
at ``2**-7``, so the LoRA delta moves every output from step 1.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from traffic_gen import seed_words

BF16 = jnp.bfloat16
B_SCALE_LOG2 = 7


def root_key(seed: int):
    w0, w1 = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(w0), w1)


def proj_log2(d_in: int) -> int:
    return int(round(math.log2(math.sqrt(d_in))))


def draw(key, name: str, shape, log2_scale: int, dtype=jnp.float32,
         layer: int = 0):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(jax.random.fold_in(k, layer), shape, jnp.float32)
    return (z * jnp.float32(2.0 ** -log2_scale)).astype(dtype)


def layer_shapes(m: Dict) -> Dict[str, tuple]:
    """(shape, log2 scale, dtype) of each weight of one layer, from the
    configuration file's ``model`` block."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hd = m.get("head_dim", d // m["num_attention_heads"])
    qd, kvd = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
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
    d = m["hidden_size"]
    hd = m.get("head_dim", d // m["num_attention_heads"])
    qd, kvd = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return {"q": (d, qd), "k": (d, kvd), "v": (d, kvd), "o": (qd, d)}


def job_adapter(key, m: Dict, job: int, layer: int, rank: int
                ) -> Dict[str, Dict[str, jax.Array]]:
    """Job *job*'s un-padded adapter pair of each target at one layer."""
    out = {}
    for t, (din, dout) in adapter_dims(m).items():
        base = f"job{job}.{t}"
        out[t] = {"A": draw(key, base + ".A", (din, rank), proj_log2(din),
                            layer=layer),
                  "B": draw(key, base + ".B", (rank, dout), B_SCALE_LOG2,
                            layer=layer)}
    return out


# ------------------------------------------------- the program's trees
def layer_tree(key, m: Dict, ranks: Sequence[int], layout, layer):
    """Layer *layer*'s backbone and packed adapter leaves in the
    program's layout (``models/model.init_model`` / ``init_adapters``):
    each job's pair zero-padded to its slot and the jobs side by side."""
    w = layer_weights(key, m, layer)
    attn = {n: w[n] for n in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if n in w}
    backbone = {"ln1": w["ln1"], "ln2": w["ln2"], "attn": attn,
                "ffn": {"gate": w["gate"], "up": w["up"], "down": w["down"]}}
    jobs = [job_adapter(key, m, k, layer, r) for k, r in enumerate(ranks)]
    adapter = {}
    for t in adapter_dims(m):
        As, Bs = [], []
        for k, r in enumerate(ranks):
            _, rp = layout.slice_of(k)
            As.append(jnp.pad(jobs[k][t]["A"], ((0, 0), (0, rp - r))))
            Bs.append(jnp.pad(jobs[k][t]["B"], ((0, rp - r), (0, 0))))
        adapter[t] = {"A": jnp.concatenate(As, 1),
                      "B": jnp.concatenate(Bs, 0)}
    return backbone, adapter


def program_trees(cfg, m: Dict, ranks: Sequence[int], layout, seed: int):
    """The backbone and packed adapter trees in the program's layout,
    made on the device: the program's tree shapes fix where each named
    weight goes (one scanned segment of single-layer cycles, leading
    axis = layer).  One compiled program draws a layer and writes it
    into the donated stacks in place, once per layer, so set-up compiles
    one layer's draw whatever the depth, and holds no more than the
    stacks and one layer's float32 draw."""
    from repro.models import model as M

    plan = M.segment_plan(cfg)
    assert len(plan) == 1 and plan[0].scanned and len(plan[0].specs) == 1, \
        "the benchmark's generator covers dense single-pattern stacks"
    L = plan[0].repeats
    key0 = jax.random.PRNGKey(0)
    want = jax.eval_shape(lambda: (
        M.init_model(key0, cfg),
        M.init_adapters(key0, cfg, jnp.asarray(ranks), layout=layout)))

    def fill(stacks, key, layer):
        one = layer_tree(key, m, ranks, layout, layer)
        return jax.tree.map(lambda s, x: s.at[layer].set(x), stacks, one)

    key = root_key(seed)
    seg = lambda t: t["segments"][0]["0"]
    stacks = jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        (seg(want[0]), seg(want[1]))))()
    fill = jax.jit(fill, donate_argnums=0)
    for layer in range(L):
        stacks = fill(stacks, key, jnp.int32(layer))
    top = jax.jit(lambda k: top_weights(k, m))(key)
    params = {"embed": top["embed"], "ln_f": top["ln_f"], "head": top["head"],
              "segments": [{"0": stacks[0]}]}
    adapters = {"segments": [{"0": stacks[1]}]}
    sig = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    assert sig((params, adapters)) == sig(want), \
        "weight trees differ from the program's layout"
    return params, adapters
