"""Batched multi-adapter serving (prefill + decode) over one SSM.

Thin compatibility wrapper over the real serving subsystem
(``repro.serve``: AdapterPool + ServeEngine, DESIGN.md §13).  Kept so
the historical ``serve_batch(cfg, jobs, reqs)`` entry point — adapter
ids indexing a job list, SSM-seeded weights — keeps working; new code
should publish adapters into an ``AdapterPool`` and call
``ServeEngine.serve`` directly.

The seed implementation had four decode-path bugs, all fixed by the
engine: it jitted ``make_serve_step`` twice and host-synced every
decoded token (now one jitted prefill+scan program, one host sync); it
LEFT-padded prompts but prefilled everyone at pos 0, so short prompts
ropes/cached at wrong absolute positions (now right padding + per-row
decode positions, fused == solo exactly); per-request
``max_new_tokens`` was ignored (now each row truncates to its own
budget); and neither the prompt width nor the KV buffer was tile
aligned, so the ragged Pallas kernels could not legally run (now both
align to ``block_t``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax

from repro.configs.base import ModelConfig
from repro.core.jobs import LoRAJobSpec
from repro.core.ssm import SharedSuperModel
from repro.serve import AdapterPool, ServeEngine, ServeRequest


@dataclass
class Request:
    prompt: np.ndarray           # (S,) int32
    adapter_id: int              # index into the job list
    max_new_tokens: int = 16


def pad_requests(reqs: Sequence[Request], pad_to: int) -> Dict[str, np.ndarray]:
    """RIGHT-pad prompts to a shared tile-aligned width.

    Right padding keeps column index == absolute position, which is
    what makes fused prefill exact (the seed left-padded AND prefilled
    at pos 0, shifting every short prompt's rope/cache positions).
    Returns tokens (B, S), adapter_ids (B,), and per-request lens (B,).
    """
    S = max(len(r.prompt) for r in reqs)
    S = ((max(S, pad_to) + pad_to - 1) // pad_to) * pad_to
    toks = np.zeros((len(reqs), S), np.int32)
    lens = np.zeros((len(reqs),), np.int32)
    for i, r in enumerate(reqs):
        toks[i, :len(r.prompt)] = r.prompt
        lens[i] = len(r.prompt)
    return {"tokens": toks, "lens": lens,
            "adapter_ids": np.array([r.adapter_id for r in reqs], np.int32)}


def serve_batch(cfg: ModelConfig, jobs: Sequence[LoRAJobSpec],
                reqs: Sequence[Request], *, impl: Optional[str] = None,
                block_t: Optional[int] = None, params=None, adapters=None,
                seed: int = 0, greedy: bool = True) -> List[np.ndarray]:
    """Prefill + decode a batch of adapter-tagged requests.

    Returns one array of generated token ids per request, each
    truncated to ITS OWN ``max_new_tokens`` (rows are ragged — the
    batch-max rectangle the seed returned padded short requests with
    tokens that were never really sampled for them).
    """
    ssm = SharedSuperModel(cfg, list(jobs), impl=impl, block_t=block_t)
    impl, block_t = ssm.impl, ssm.block_t
    if params is None or adapters is None:
        params, adapters = ssm.init(jax.random.PRNGKey(seed))

    pool = AdapterPool(cfg, capacity=max(len(jobs), 1),
                       multiple=ssm.layout.multiple)
    pool.publish_group(list(jobs), adapters, ssm.layout)
    engine = ServeEngine(cfg, params, pool, impl=impl, block_t=block_t,
                         greedy=greedy)
    results = engine.serve([
        ServeRequest(prompt=np.asarray(r.prompt, np.int32),
                     adapter=jobs[r.adapter_id].job_id,
                     max_new_tokens=r.max_new_tokens)
        for r in reqs])
    return [r.tokens for r in results]
