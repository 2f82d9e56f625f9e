#!/usr/bin/env python3
"""The on-chip benchmark of the fused multi-LoRA trainer.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips JAX finds (set-up,
the checked first chunk, the measured window, the reference check; see
``harness.py``) and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``/``failed`` (training steps of the
window, and those whose loss was not finite), ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``check``: each
number compared, with its limit.  The same numbers end standard error.

Exits 2, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, and 1 where the program is missing.
"""
import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the TPU runtime logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    try:
        result = harness.run(ROOT, args.workload, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except FileNotFoundError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    result.pop("_numbers", None)          # every number, gated or not
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
