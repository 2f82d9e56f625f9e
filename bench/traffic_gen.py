"""The one traffic generator: a mix file's jobs and document lengths,
made into per-job token streams from ``--seed``.

Every step holds the same multiset of document lengths: the ``n``
stratified quantiles of the mix's length law, one per row of the fused
batch (``n`` rows in all).  The seed draws which row gets which length,
and every token id.  So each step, and each seed, trains the same number
of real tokens, and a rate differs between runs only by the system's
time, not by the lengths drawn.

One document per row, right-padded with id 0 and ``loss_mask`` 0, as
the program's own ``data/pipeline.JobStream`` lays rows out (its GSM8K
lognormal law, mu 5.1 and sigma 0.45 clipped at 16, is copied here as
the ``lognormal`` law).  Token ids are uniform over ``[3, vocab)`` of
the configuration's (sliced) vocabulary.
"""
from __future__ import annotations

import contextlib
import math
import types
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np


def lengths_multiset(law: Dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of the length law, clipped:
    ``exp(mu + sigma * z((i + 0.5) / n))`` for i < n."""
    assert law["law"] == "lognormal", law
    z = NormalDist()
    q = [math.exp(law["mu"] + law["sigma"] * z.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.asarray(q).astype(np.int64), law["min"], law["max"])


def seed_words(seed: int) -> List[int]:
    """Two 32-bit words from a seed of any size."""
    return [int(w) for w in np.random.SeedSequence(int(seed)
                                                   ).generate_state(2)]


class StepLengths:
    """Per step, the mix's length multiset in a seeded row order."""

    def __init__(self, traffic: Dict, seed: int):
        rows = [int(j["rows"]) for j in traffic["jobs"]]
        self.offsets = np.concatenate([[0], np.cumsum(rows)])
        self.base = lengths_multiset(traffic["lengths"], int(sum(rows)))
        self.words = seed_words(seed)

    def of(self, step: int, job: int) -> np.ndarray:
        perm = np.random.default_rng(self.words + [step, 1]).permutation(
            self.base.size)
        lo, hi = self.offsets[job], self.offsets[job + 1]
        return self.base[perm[lo:hi]]


class MixStream:
    """Token stream of one job of the mix, in the program's stream
    protocol: ``spec``, ``next_batch()`` and an ``_rng`` generator whose
    state the runtime snapshots when it stages a chunk ahead."""

    def __init__(self, spec, job: int, vocab: int, lengths: StepLengths,
                 seed: int):
        self.spec = spec
        self.job = job
        self.vocab = int(vocab)
        self.lengths = lengths
        self.step = 0
        self.real_tokens: List[int] = []     # loss-mask tokens per batch
        # host span around each batch; the harness sets a profiler
        # annotation in a traced run
        self.span = lambda name: contextlib.nullcontext()
        self._rng = np.random.default_rng(seed_words(seed) + [job, 2])

    def next_batch(self) -> Dict[str, np.ndarray]:
        with self.span("bench_traffic"):
            return self._next()

    def _next(self) -> Dict[str, np.ndarray]:
        B, S = self.spec.batch_size, self.spec.seq_len
        lens = self.lengths.of(self.step, self.job)
        toks = self._rng.integers(3, self.vocab, size=(B, S),
                                  dtype=np.int32)
        mask = np.arange(S)[None, :] < lens[:, None]
        toks = np.where(mask, toks, 0)
        self.step += 1
        self.real_tokens.append(int(mask.sum()))
        return {"tokens": toks, "labels": toks,
                "loss_mask": mask.astype(np.float32)}


def job_specs(traffic: Dict, config_name: str) -> list:
    from repro.core.jobs import LoRAJobSpec
    return [LoRAJobSpec(f"job{k}-r{j['rank']}", rank=int(j["rank"]),
                        batch_size=int(j["rows"]),
                        seq_len=int(traffic["seq_len"]),
                        alpha=float(traffic["alpha"]),
                        base_model=config_name)
            for k, j in enumerate(traffic["jobs"])]


def streams(traffic: Dict, specs: Sequence, vocab: int, seed: int
            ) -> List[MixStream]:
    lengths = StepLengths(traffic, seed)
    return [MixStream(s, k, vocab, lengths, seed)
            for k, s in enumerate(specs)]


def batches(traffic: Dict, vocab: int, seed: int,
            steps: int) -> List[List[Dict[str, np.ndarray]]]:
    """The first *steps* batches of every job, as the streams give them
    (``[step][job]``): what the reference trains on, without the
    program's job specs."""
    specs = [types.SimpleNamespace(batch_size=int(j["rows"]),
                                   seq_len=int(traffic["seq_len"]))
             for j in traffic["jobs"]]
    ss = streams(traffic, specs, vocab, seed)
    return [[s.next_batch() for s in ss] for _ in range(steps)]
