"""End-to-end multi-LoRA training loop (Fig. 3 lifecycle, phase 3).

Drives one fused group: data -> SSM train step -> AIMD nano-batch
adaptation -> per-job checkpoints.  Since the elastic refactor
(DESIGN.md §6) the loop body lives in ``elastic.runtime.GroupRuntime``;
``train_group`` remains the one-shot convenience entry point (build a
group, run N steps, hand back the state).  The step function is
(re)jitted when the AIMD controller changes N — an O(log N)-bounded
number of recompiles, each of which still makes training progress
(paper §3.3).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax

from repro.configs.base import ModelConfig
from repro.core.jobs import LoRAJobSpec
from repro.elastic.runtime import GroupRuntime, TrainReport

__all__ = ["train_group", "TrainReport", "GroupRuntime"]


def train_group(cfg: ModelConfig, jobs: Sequence[LoRAJobSpec], *,
                steps: int = 20, lr: float = 1e-3, seed: int = 0,
                impl: Optional[str] = None, block_t: Optional[int] = None,
                adaptive_nano: bool = True, nano_batches: int = 1,
                remat: bool = True, quantize: Optional[str] = None,
                chunk_size: int = 4,
                params=None, adapters=None,
                log: Optional[Callable[[str], None]] = None) -> Dict:
    """Train a fused group for *steps* iterations on the local device.

    Steps execute in device-resident chunks of ``chunk_size`` (one host
    sync per chunk — see GroupRuntime.run); ``chunk_size=1`` recovers the
    classic step-at-a-time loop."""
    rt = GroupRuntime.from_specs(cfg, list(jobs), jax.random.PRNGKey(seed),
                                 params=params, adapters=adapters,
                                 lr=lr, impl=impl, block_t=block_t,
                                 seed=seed, nano_batches=nano_batches,
                                 adaptive_nano=adaptive_nano, remat=remat,
                                 quantize=quantize, chunk_size=chunk_size)
    report = rt.run(steps, log=log)
    return {"ssm": rt.ssm, "params": rt.params, "adapters": rt.adapters,
            "opt_state": rt.opt_state, "report": report,
            "batcher": rt.batcher, "runtime": rt}
