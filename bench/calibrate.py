#!/usr/bin/env python3
"""Readings that set a cell's correctness limits (``limits/<cell>.json``).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 --fault-seeds 1,2,3 --out readings.jsonl

In one process, on the chip: the sound program on every ``--seeds``
seed (the lower readings); the controls, the precision below the
configurations' bfloat16 (the upper readings): the program's own int8
frozen backbone on every ``--control-seeds`` seed, and the reference
in float8 put in the program's place on every ``--fp8-seeds`` seed;
and each fault of ``faults.py`` on every ``--fault-seeds`` seed.  Each run is a whole
harness run with a token window, its check numbers compared with the
reference.  One JSON line per run goes to ``--out`` and to standard
output.
"""
import time

T0 = time.perf_counter()

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def fp8_control(workload: str, seed: int) -> dict:
    """The reference in float8 put in the program's place: its first
    chunk compared with the float32 reference's."""
    import catalog
    import check
    import harness
    t = time.perf_counter()
    cell = catalog.load_cell(ROOT, workload)
    harness.setup_jax(ROOT)
    L = harness.CHUNK
    ref = check.Reference(cell, seed, L, harness.LR)
    ctl = check.Reference(cell, seed, L, harness.LR, fp8=True)
    numbers = check.compare(ref, ctl.losses, ctl.mu, ctl.p)
    return {"workload": workload, "run": "fp8", "seed": seed,
            "correct": check.verdict(numbers, cell.limits),
            "numbers": numbers, "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--fp8-seeds", default="",
                    help="seeds of the reference in float8 put in the "
                         "program's place")
    ap.add_argument("--faults", default="half_batch,answer_altered")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import faults
    import harness
    seeds = lambda s: [int(x) for x in s.split(",") if x]
    plan = [("sound", s, {}) for s in seeds(args.seeds)]
    plan += [("int8", s, {"quantize": "int8"})
             for s in seeds(args.control_seeds)]
    plan += [(f, s, {"fault": faults.FAULTS[f]})
             for f in args.faults.split(",") if f
             for s in seeds(args.fault_seeds)]
    plan += [("fp8", s, None) for s in seeds(args.fp8_seeds)]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        for name, seed, kw in plan:
            if kw is None:
                row = fp8_control(args.workload, seed)
                print(json.dumps(row), flush=True)
                out.write(json.dumps(row) + "\n")
                continue
            t = time.perf_counter()
            try:
                r = harness.run(ROOT, args.workload, seed=seed, seconds=0.0,
                                trace=False, t_start=t, **kw)
                row = {"workload": args.workload, "run": name, "seed": seed,
                       "correct": r["correct"],
                       "numbers": r["_numbers"],
                       "setup_s": r["metrics"]["setup_s"]["value"],
                       "seconds": time.perf_counter() - t}
            except Exception as e:              # a control that crashes
                row = {"workload": args.workload, "run": name, "seed": seed,
                       "error": f"{type(e).__name__}: {e}"[:500]}
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
