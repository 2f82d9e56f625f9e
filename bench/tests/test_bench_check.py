"""The correctness check, end to end on the CPU at a size a test run can
hold: the harness drives the program's timed path (look for a chip
skipped) on two tiny cells shaped like the benchmark's, one per
configuration file (InternLM2's mixed ranks; Qwen1.5's QKV bias and
uniform ranks), and the reference follows.

* Sound runs come out correct: the reference is tied to ``GroupRuntime``
  (per-job losses, first moments and adapter updates, bias included).
* The control, the reference in float8 matmuls put in the program's
  place (the precision below the configurations' bfloat16), comes out
  not correct.
* Each fault of ``faults.py`` planted in the timed path comes out not
  correct.

All runs happen in one child process (the harness sets JAX's compile
cache, which must not leak into other tests)."""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

SHRINK = {"hidden_size": 64, "intermediate_size": 128,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 1024}
TINY_CELLS = {
    # cell: (configuration file, jobs as (rank, rows), seq_len)
    "tiny-internlm2.mixed": ("internlm2-20b", [(4, 8), (8, 4), (16, 4)], 128),
    "tiny-qwen1.5.uniform": ("qwen1.5-110b", [(8, 4), (8, 4), (8, 8)], 128),
}
# Limits of the tiny cells, set as the real cells' are from chip runs,
# here from CPU readings: the largest of 12 sound seeds per cell (lower),
# the least of the float8 control and of the faults on 3 seeds (upper).
#   loss_gap   lower 4.57e-4, upper 3.09e-3 (float8)
#   moment_gap lower 6.14e-3, upper 3.32e-2 (float8)
#   update_gap lower 8.01e-3, upper 0.72 (answer_altered; float8 reads
#              2.20e-2, under 3x the lower reading)
#   moment_diff lower 2.72e-2, upper 0.244 (float8)
# At this size the program's int8 backbone reads under 2x the lower
# reading on every number, so the float8 control stands in for it here.
TINY_LIMITS = {"loss_gap": 1.2e-3, "moment_gap": 0.015, "update_gap": 0.05,
               "moment_diff": 0.07}
SEEDS = (3, 2 ** 31 + 77)
SCENARIOS = [("sound", True), ("fp8", False), ("unchanged", False),
             ("half_batch", False), ("answer_altered", False)]


def write_checkout(root: str, limits=TINY_LIMITS) -> None:
    """A checkout holding the tiny cells as files: BENCHMARK.json, the
    shrunk configurations, their traffic and limits, and the program."""
    bench = os.path.join(root, "bench")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, d), exist_ok=True)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    os.symlink(os.path.join(BENCH, "metrics"), os.path.join(bench, "metrics"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"], spec["workloads"] = [], []
    for cell, (cfg_name, jobs, seq) in TINY_CELLS.items():
        cfg = json.load(open(os.path.join(BENCH, "configs",
                                          cfg_name + ".json")))
        name, mix = cell.split(".", 1)[0], cell.split(".", 1)[1]
        cfg["name"] = name
        cfg["model"].update(SHRINK)
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)
        traffic = {"why": "test", "seq_len": seq, "alpha": 16.0,
                   "jobs": [{"rank": r, "rows": b} for r, b in jobs],
                   "lengths": {"law": "lognormal", "mu": 2.8, "sigma": 0.45,
                               "min": 4, "max": seq},
                   "chips": 1}
        with open(os.path.join(bench, "traffic", mix + ".json"), "w") as f:
            json.dump(traffic, f)
        with open(os.path.join(bench, "limits", cell + ".json"), "w") as f:
            json.dump(limits, f)
        spec["workloads"].append({"name": cell, "config": name,
                                  "traffic": mix, "chips": 1, "why": "t"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


CHILD = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1])
import catalog, check, harness, faults
root, seeds, scenarios = sys.argv[2], json.loads(sys.argv[3]), json.loads(sys.argv[4])
for cell in json.loads(sys.argv[5]):
    c = catalog.load_cell(root, cell, root + "/bench")
    for seed in seeds:
        for name in scenarios:
            if name == "fp8":
                ref = check.Reference(c, seed, harness.CHUNK, harness.LR)
                ctl = check.Reference(c, seed, harness.CHUNK, harness.LR,
                                      fp8=True)
                nums = check.compare(ref, ctl.losses, ctl.mu, ctl.p)
                ok = check.verdict(nums, c.limits)
            else:
                kw = {} if name == "sound" else {"fault": faults.FAULTS[name]}
                r = harness.run(root, cell, seed=seed, seconds=0.05,
                                trace=False, t_start=time.perf_counter(),
                                require_tpu=False, bench_dir=root + "/bench",
                                **kw)
                ok, nums = r["correct"], r["_numbers"]
            print("RESULT " + json.dumps([cell, seed, name, ok, nums]),
                  flush=True)
"""


def run_child(root, cells, seeds, scenarios, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", CHILD, BENCH, root, json.dumps(seeds),
         json.dumps(scenarios), json.dumps(cells)],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line[len("RESULT "):])
            for line in out.stdout.splitlines() if line.startswith("RESULT ")]


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    write_checkout(root)
    rows = run_child(root, list(TINY_CELLS), list(SEEDS),
                     [s for s, _ in SCENARIOS])
    return {(c, s, n): (ok, nums) for c, s, n, ok, nums in rows}


@pytest.mark.parametrize("cell", list(TINY_CELLS))
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("scenario,want", SCENARIOS,
                         ids=[s for s, _ in SCENARIOS])
def test_check_verdict(results, cell, seed, scenario, want):
    ok, numbers = results[(cell, seed, scenario)]
    assert ok is want, numbers
