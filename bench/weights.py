"""Random weights from ``--seed``, one generator for the harness and the
reference alike.

Every matrix is named (``layer.<name>``, ``embed``, ``job2.q.A``) and,
for layer l, drawn as ``normal(fold_in(fold_in(key, crc32(name)), l))
* 2**-e`` in float32, then cast to the type it is served in.  A
power-of-two scale keeps the draw exact under any fusion, so the
reference, which makes each layer's weights again by name, holds the
same numbers the program was given.

Scales: a projection ``2**-round(log2(sqrt(d_in)))``, the embedding
``2**-6``, norm gains ``1 + 2**-3 z``, biases ``2**-2 z`` (the family
names each weight: ``families/<family>.py``).  Adapters are those of a
job in training: ``A`` at the projection scale and ``B`` at ``2**-7``,
so the LoRA delta moves every output from step 1.
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


def job_adapter(dims: Dict[str, tuple], key, job: int, layer: int,
                rank: int) -> Dict[str, Dict[str, jax.Array]]:
    """Job *job*'s un-padded adapter pair of each adapted projection at
    one layer, for the family's ``adapter_dims`` (``{target: (d_in,
    d_out)}``)."""
    out = {}
    for t, (din, dout) in dims.items():
        base = f"job{job}.{t}"
        out[t] = {"A": draw(key, base + ".A", (din, rank), proj_log2(din),
                            layer=layer),
                  "B": draw(key, base + ".B", (rank, dout), B_SCALE_LOG2,
                            layer=layer)}
    return out


# ------------------------------------------------- the program's trees
def program_trees(fam, cfg, m: Dict, ranks: Sequence[int], layout,
                  seed: int):
    """The backbone and packed adapter trees in the program's layout,
    made on the device by the family *fam* (``families/<family>.py``).

    The program's tree shapes fix where each named weight goes: every
    segment of ``models/model.segment_plan``, each layer drawn by its
    global index.  A scanned segment's stacks are filled in place, one
    compiled program drawing a layer and writing it into the donated
    stacks, so set-up compiles one layer's draw whatever the depth and
    holds no more than the stacks and one layer's float32 draw."""
    from repro.models import model as M

    key0 = jax.random.PRNGKey(0)
    want = jax.eval_shape(lambda: (
        M.init_model(key0, cfg),
        M.init_adapters(key0, cfg, jnp.asarray(ranks), layout=layout)))
    key = root_key(seed)

    def one(k, layer):
        return fam.layer_tree(k, m, ranks, layout, layer)

    top = jax.jit(lambda k: fam.top_weights(k, m))(key)
    params, adapters = dict(top, segments=[]), {"segments": []}
    base = 0
    for i, seg in enumerate(M.segment_plan(cfg)):
        n = len(seg.specs)
        sp, sa = {}, {}
        for j in range(n):
            shapes = (want[0]["segments"][i][str(j)],
                      want[1]["segments"][i][str(j)])
            at = lambda c, base=base, n=n, j=j: base + c * n + j
            if seg.scanned:
                sp[str(j)], sa[str(j)] = _stacked(one, shapes, key, at,
                                                  seg.repeats)
            else:
                sp[str(j)], sa[str(j)] = jax.jit(one)(key, jnp.int32(at(0)))
        params["segments"].append(sp)
        adapters["segments"].append(sa)
        base += n * seg.repeats
    sig = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    assert sig((params, adapters)) == sig(want), \
        "weight trees differ from the program's layout"
    return params, adapters


def _stacked(one, shapes, key, at, repeats: int):
    """A scanned segment's (backbone, adapter) stacks, cycle c holding
    layer ``at(c)``."""
    def fill(stacks, key, c):
        new = one(key, at(c))
        return jax.tree.map(lambda s, x: s.at[c].set(x), stacks, new)

    stacks = jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), shapes))()
    fill = jax.jit(fill, donate_argnums=0)
    for c in range(repeats):
        stacks = fill(stacks, key, jnp.int32(c))
    return stacks
