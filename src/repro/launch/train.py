"""CLI launcher: multi-LoRA training / serving / cluster simulation.

    python -m repro.launch.train train --arch tinyllama-1.1b --jobs 3 \
        --steps 20 --reduced
    python -m repro.launch.train serve --arch tinyllama-1.1b --reduced
    python -m repro.launch.train simulate --system tlora --chips 128
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core.jobs import LoRAJobSpec
from repro.launch.compile_cache import enable_compile_cache


def cmd_train(args):
    from repro.train.train_loop import train_group
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ranks = [16, 8, 4, 2]
    jobs = [LoRAJobSpec(f"job-{i}", rank=ranks[i % 4],
                        batch_size=args.batch_size, seq_len=args.seq_len,
                        base_model=args.arch)
            for i in range(args.jobs)]
    out = train_group(cfg, jobs, steps=args.steps, lr=args.lr,
                      impl=args.impl, block_t=args.block_t,
                      adaptive_nano=not args.no_aimd,
                      log=print)
    rep = out["report"]
    print(f"\nfinal loss {rep.losses[-1]:.4f}  "
          f"avg step {np.mean(rep.step_times[1:]):.3f}s  "
          f"{rep.tokens_per_sec:.1f} real tokens/s  "
          f"nano trajectory {rep.nano_history}")
    print(f"{rep.compiles} step compiles in {rep.compile_s:.1f}s  "
          f"padding {1 - rep.real_tokens / max(rep.row_tokens, 1):.1%} "
          f"of {rep.row_tokens} row tokens")


def cmd_serve(args):
    from repro.train.serve import Request, serve_batch
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rng = np.random.default_rng(0)
    jobs = [LoRAJobSpec(f"adapter-{i}", rank=r, batch_size=1,
                        base_model=args.arch)
            for i, r in enumerate((16, 8, 4, 2))]
    reqs = [Request(prompt=rng.integers(1, cfg.vocab_size, size=12,
                                        dtype=np.int32),
                    adapter_id=i % 4, max_new_tokens=args.tokens)
            for i in range(args.requests)]
    out = serve_batch(cfg, jobs, reqs, impl=args.impl, block_t=args.block_t)
    print(f"generated {len(out)} rows:")
    for i, row in enumerate(out):
        print(f"  req {i} [{jobs[i % 4].job_id}] {row.tolist()}")


def cmd_simulate(args):
    from repro.cluster.baselines import SYSTEMS, make_simulator
    from repro.cluster.metrics import compare, summarize
    from repro.cluster.simulator import ClusterConfig
    from repro.cluster.trace import TraceConfig, generate
    trace = generate(TraceConfig(months=1, jobs_per_month=args.jobs,
                                 seed=args.seed))
    systems = SYSTEMS if args.system == "all" else (args.system,)
    results = {}
    for s in systems:
        sim = make_simulator(s, ClusterConfig(total_chips=args.chips))
        results[s] = sim.run(trace)
        print(f"{s:20s} {json.dumps({k: round(v, 4) for k, v in summarize(results[s]).items()})}")
    if len(results) > 1 and "mlora" in results:
        print("\nvs mLoRA:")
        for name, d in compare(results).items():
            print(f"  {name:20s} throughput x{d['throughput_x']:.2f} "
                  f"JCT x{d['jct_speedup_x']:.2f} "
                  f"util +{d['utilization_delta']*100:.1f}pp")


def main():
    ap = argparse.ArgumentParser(prog="repro.launch.train")
    sub = ap.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("train")
    t.add_argument("--arch", default="tinyllama-1.1b", choices=ARCH_IDS)
    t.add_argument("--jobs", type=int, default=3)
    t.add_argument("--steps", type=int, default=10)
    t.add_argument("--batch-size", type=int, default=2)
    t.add_argument("--seq-len", type=int, default=64)
    t.add_argument("--lr", type=float, default=1e-3)
    # --impl / --block-t unset: the platform's (pallas/128 on TPU)
    t.add_argument("--impl", default=None,
                   choices=("ref", "pallas", "xla", "loop"))
    t.add_argument("--block-t", type=int, default=None)
    t.add_argument("--no-aimd", action="store_true")
    t.add_argument("--reduced", action="store_true")
    t.set_defaults(fn=cmd_train)

    s = sub.add_parser("serve")
    s.add_argument("--arch", default="tinyllama-1.1b", choices=ARCH_IDS)
    s.add_argument("--requests", type=int, default=8)
    s.add_argument("--tokens", type=int, default=8)
    s.add_argument("--impl", default=None,
                   choices=("ref", "pallas", "xla", "loop"))
    s.add_argument("--block-t", type=int, default=None)
    s.add_argument("--reduced", action="store_true")
    s.set_defaults(fn=cmd_serve)

    c = sub.add_parser("simulate")
    c.add_argument("--system", default="all")
    c.add_argument("--chips", type=int, default=128)
    c.add_argument("--jobs", type=int, default=120)
    c.add_argument("--seed", type=int, default=0)
    c.set_defaults(fn=cmd_simulate)

    args = ap.parse_args()
    enable_compile_cache()
    args.fn(args)


if __name__ == "__main__":
    main()
