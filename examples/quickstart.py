"""Quickstart: fuse three heterogeneous LoRA jobs over one frozen
backbone and train them jointly with the SSM (paper §3.2-3.3).

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.configs import get_config
from repro.core.jobs import LoRAJobSpec
from repro.train.train_loop import train_group


def main():
    # reduced tinyllama so this runs in seconds on CPU
    cfg = get_config("tinyllama-1.1b").reduced()

    # three tenants, heterogeneous ranks/batch sizes — the paper's setting
    jobs = [
        LoRAJobSpec("alice/math", rank=16, batch_size=2, seq_len=64),
        LoRAJobSpec("bob/code", rank=4, batch_size=4, seq_len=64),
        LoRAJobSpec("carol/chat", rank=8, batch_size=2, seq_len=64),
    ]

    out = train_group(cfg, jobs, steps=10, lr=5e-3, impl="ref", block_t=8,
                      adaptive_nano=True, log=print)

    rep = out["report"]
    print("\nper-job losses (first -> last step):")
    for k, job in enumerate(jobs):
        print(f"  {job.job_id:12s} rank={job.rank:2d} "
              f"{rep.per_job_losses[0][k]:.3f} -> "
              f"{rep.per_job_losses[-1][k]:.3f}")
    print(f"AIMD nano-batch trajectory: {rep.nano_history}")
    print(f"~{rep.steps_per_sec:.2f} fused steps/sec "
          f"({rep.tokens_per_sec:.1f} real tokens/sec) on this host")


if __name__ == "__main__":
    main()
