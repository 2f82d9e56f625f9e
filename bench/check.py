"""Whether the timed path trained correctly: the program's first chunk
against the plain float32 reference.

The harness drives the program's compiled chunk step through its first
chunk (``chunk_size`` steps, the same call and feed the window uses) and
keeps, for every job, the per-step losses, the adapter after the chunk
and AdamW's first moment after the chunk.  Once the window has closed
and the program's state is freed, the reference (``reference/model.py``)
makes the same weights, adapters and batches from the seed and trains
the same steps.  The numbers, each compared where the cell's limits
(``limits/<cell>.json``) give it a limit:

* ``loss_gap``: the largest relative gap of a job's loss at a step.
* ``moment_gap``: AdamW's first moment after the chunk, the gradients as
  the optimizer holds them.  By the worst leaf (one job's A or B of one
  projection in one layer): the gap between the program's norm and the
  reference's, over the larger of that leaf's reference norm and the
  median leaf's.
* ``update_gap``: the same for the change of the adapter over the chunk.
* ``moment_diff``: as ``moment_gap`` with the norm of the difference in
  the numerator.  The gaps of norms move little with a precision step
  (random rounding errors change a norm in second order); this number
  moves with it in first order and is what separates the program's own
  int8 backbone from the sound bfloat16 program on the chip.

Leaves whose first gradient in the reference is under a thousandth of
the median leaf's are left out of both leaf numbers: AdamW moves them by
rounding alone.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

import traffic_gen
import weights

LEAF_FLOOR = 1e-3
ROW_BUCKET = 128


def job_leaves(tree: Dict, layout, ranks) -> List[Dict[Tuple, np.ndarray]]:
    """Per job, ``{(layer, target, "A"|"B"): un-padded slice}`` from the
    program's packed adapter-shaped tree (host numpy).  Every segment of
    the tree is read, in order, each layer by its global index: a
    segment of n layers a cycle holds layer ``base + c * n + j`` at
    cycle c, slot j (``models/model.segment_plan``), its leaves stacked
    over the cycles where it is scanned."""
    out: List[Dict[Tuple, np.ndarray]] = [{} for _ in ranks]
    base = 0
    for seg in tree["segments"]:
        n, cycles = len(seg), 0
        for j in range(n):
            for t, ab in seg[str(j)].items():
                A, B = np.asarray(ab["A"]), np.asarray(ab["B"])
                if A.ndim == 2:                     # an unscanned layer
                    A, B = A[None], B[None]
                cycles = A.shape[0]
                for c in range(cycles):
                    for k, r in enumerate(ranks):
                        off, _ = layout.slice_of(k)
                        layer = base + c * n + j
                        out[k][(layer, t, "A")] = A[c, :, off:off + r]
                        out[k][(layer, t, "B")] = B[c, off:off + r, :]
        base += n * cycles
    return out


def leaf_gap(prog: List[Dict], ref: List[Dict], keep,
             diff: bool = False) -> float:
    """Worst leaf's |norm(prog) - norm(ref)| / max(norm(ref), median);
    with *diff*, norm(prog - ref) in the numerator."""
    rn = {(k, key): float(np.linalg.norm(v))
          for k, d in enumerate(ref) for key, v in d.items()}
    med = float(np.median([rn[i] for i in keep]))
    worst = 0.0
    for k, key in keep:
        p = prog[k][key].astype(np.float64)
        gap = (float(np.linalg.norm(p - ref[k][key])) if diff
               else abs(float(np.linalg.norm(p)) - rn[(k, key)]))
        worst = max(worst, gap / max(rn[(k, key)], med))
    return worst


class Reference:
    """The reference's run of the cell's first steps, from the seed
    (``fp8``: the control, in float8 matmuls).  The configuration's
    family names the reference module."""

    def __init__(self, cell, seed: int, steps: int, lr: float,
                 fp8: bool = False):
        import importlib

        import jax
        import jax.numpy as jnp
        from reference.adamw import adamw

        fam = cell.family
        row_loss_and_grad = importlib.import_module(
            fam.REFERENCE).row_loss_and_grad
        m = cell.model
        jobs = cell.traffic["jobs"]
        ranks = [int(j["rank"]) for j in jobs]
        rmax = max(ranks)
        alpha = float(cell.traffic["alpha"])
        L = int(m["num_hidden_layers"])
        key = weights.root_key(seed)
        top = jax.jit(lambda k: fam.top_weights(k, m))(key)
        one = jax.jit(lambda k, l: fam.layer_weights(k, m, l))
        layers = [one(key, jnp.int32(l)) for l in range(L)]
        # every job's adapters, zero-padded to the largest rank: padded
        # lanes add exact zeros to outputs and gradients alike
        dims = fam.adapter_dims(m)
        pads = jax.jit(lambda k, l: [
            {t: {"A": pad_a(ab["A"], rmax), "B": pad_b(ab["B"], rmax)}
             for t, ab in weights.job_adapter(dims, k, j, l, r).items()}
            for j, r in enumerate(ranks)])
        per_layer = [pads(key, jnp.int32(l)) for l in range(L)]
        starts = [[per_layer[l][k] for l in range(L)]
                  for k in range(len(ranks))]
        del per_layer
        data = traffic_gen.batches(cell.traffic, m["vocab_size"], seed,
                                   steps)
        fn = row_loss_and_grad(m, fp8)

        self.p0: List[Dict] = []
        self.losses = np.zeros((steps, len(jobs)))
        self.grad1: List[Dict] = []
        self.mu: List[Dict] = []
        self.p: List[Dict] = []
        t0 = time.perf_counter()
        for k, r in enumerate(ranks):
            p = starts[k]
            self.p0.append(unpad(p, r))
            mu = jax.tree.map(jnp.zeros_like, p)
            nu = jax.tree.map(jnp.zeros_like, p)
            scale = jnp.float32(alpha / r)
            for s in range(steps):
                b = data[s][k]
                tot, cnt, g = 0.0, 0.0, None
                for row in range(b["tokens"].shape[0]):
                    n = trimmed(b["loss_mask"][row])
                    l_, c_, g_ = fn(top, layers, p, b["tokens"][row, :n],
                                    b["loss_mask"][row, :n], scale)
                    tot, cnt = tot + l_, cnt + c_
                    g = g_ if g is None else jax.tree.map(jnp.add, g, g_)
                cnt = jnp.maximum(cnt, 1.0)
                g = jax.tree.map(lambda a: a / cnt, g)
                self.losses[s, k] = float(tot / cnt)
                if s == 0:
                    self.grad1.append(unpad(g, r))
                upd = jax.tree.map(
                    lambda p_, g_, m_, v_: adamw(p_, g_, m_, v_, s + 1, lr),
                    p, g, mu, nu)
                is_t = lambda x: isinstance(x, tuple)
                p = jax.tree.map(lambda u: u[0], upd, is_leaf=is_t)
                mu = jax.tree.map(lambda u: u[1], upd, is_leaf=is_t)
                nu = jax.tree.map(lambda u: u[2], upd, is_leaf=is_t)
            self.mu.append(unpad(mu, r))
            self.p.append(unpad(p, r))
        self.seconds = time.perf_counter() - t0


def trimmed(mask, bucket: int = ROW_BUCKET) -> int:
    """A right-padded row's length cut to its last kept token, rounded
    up to *bucket*: under causal attention the padding after a row's
    last kept token changes neither its loss nor any gradient, and a
    few bucket lengths keep the reference's compiled programs few."""
    n = int(np.flatnonzero(np.asarray(mask))[-1]) + 1
    return min(len(mask), -(-n // bucket) * bucket)


def pad_a(a, rmax):
    import jax.numpy as jnp
    return jnp.pad(a, [(0, 0), (0, rmax - a.shape[1])])


def pad_b(b, rmax):
    import jax.numpy as jnp
    return jnp.pad(b, [(0, rmax - b.shape[0]), (0, 0)])


def unpad(per_layer: List[Dict], r: int) -> Dict[Tuple, np.ndarray]:
    """Per-layer ``{t: {A, B}}`` at the padded rank -> the check's
    ``{(layer, t, "A"|"B"): un-padded host slice}``."""
    out = {}
    for l, pl in enumerate(per_layer):
        for t, ab in pl.items():
            out[(l, t, "A")] = np.asarray(ab["A"])[:, :r]
            out[(l, t, "B")] = np.asarray(ab["B"])[:r, :]
    return out


def compare(ref: Reference, prog_losses: np.ndarray, prog_mu: List[Dict],
            prog_p: List[Dict]) -> Dict[str, float]:
    """The three numbers of the module docstring."""
    steps = ref.losses.shape[0]
    pl = np.asarray(prog_losses, np.float64)[:steps]
    loss_gap = float(np.max(np.abs(pl - ref.losses) / np.abs(ref.losses)))
    g1 = {(k, key): float(np.linalg.norm(v))
          for k, d in enumerate(ref.grad1) for key, v in d.items()}
    med = float(np.median(list(g1.values())))
    keep = [i for i, n in g1.items() if n >= LEAF_FLOOR * med]
    ref.left_out = len(g1) - len(keep)
    delta = lambda ps: [{key: d[key].astype(np.float64)
                         - ref.p0[k][key].astype(np.float64) for key in d}
                        for k, d in enumerate(ps)]
    return {"loss_gap": loss_gap,
            "moment_gap": leaf_gap(prog_mu, ref.mu, keep),
            "update_gap": leaf_gap(delta(prog_p), delta(ref.p), keep),
            "moment_diff": leaf_gap(prog_mu, ref.mu, keep, diff=True)}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct where every number the cell's limits name is finite and
    within its limit."""
    return all(np.isfinite(numbers[n]) and numbers[n] <= lim
               for n, lim in limits.items())
