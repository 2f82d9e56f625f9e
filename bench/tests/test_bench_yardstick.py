"""The benchmark's yardstick on the CPU: FLOP and byte counts against
hand counts, the trace reduction on a small synthetic trace, the
traffic generator, the peaks table, and cells found by name."""
import json
import os
import shutil

import numpy as np
import pytest

import catalog
import flops
from families import dense_gqa
import traffic_gen
import xtrace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 3,
        "vocab_size": 10, "qkv_bias": True}


# ----------------------------------------------------------------- flops
def test_backbone_flops_hand_count():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    want = 4 * (3 * per_layer + 8 * 10)
    assert dense_gqa.backbone_flops_per_token(TINY) == want


def test_attention_flops_hand_count():
    # one document of 3 tokens: positions attend to 1, 2, 3 keys;
    # q k^T and p v each 2*H*hd per key; forward + 2x for the gradients
    fwd = 2 * 2 * 2 * 4 * (1 + 2 + 3)
    assert dense_gqa.attention_flops(TINY, [3]) == 3 * fwd * 3


@pytest.mark.parametrize("rank,tokens", [(1, 1), (4, 5), (16, 32)])
def test_lora_flops_and_bytes_hand_count(rank, tokens):
    # q (8->8), k (8->4), v (8->4), o (8->8): sum(d_in + d_out) = 56
    assert flops.lora_flops(dense_gqa, TINY, {rank: tokens}) == \
        3 * 6 * rank * tokens * 56
    act = 2 * tokens * 3 * 56
    w = (3 * 2 + 4) * rank * 56
    assert flops.lora_bytes(dense_gqa, TINY, {rank: tokens}) == \
        3 * (act + w)


def test_step_flops_counts_real_tokens_and_each_rank():
    traffic = {"jobs": [{"rank": 2, "rows": 1}, {"rank": 4, "rows": 2}]}
    lengths = [3, 5, 7]
    want = (dense_gqa.backbone_flops_per_token(TINY) * 15
            + dense_gqa.attention_flops(TINY, lengths)
            + flops.lora_flops(dense_gqa, TINY, {2: 3, 4: 12}))
    assert flops.step_required_flops(dense_gqa, TINY, traffic,
                                     lengths) == want
    assert flops.lora_kernel_tokens(
        dict(traffic, seq_len=8)) == {2: 8, 4: 16}


def test_peaks_known_and_unknown():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


# ----------------------------------------------------------------- trace
def _xspace(device_ops, spans):
    """A text-proto XSpace: *device_ops* {plane: [(text, start, dur)]},
    *spans* [(name, start, dur)], times in ns."""
    planes = []
    for pid, (plane, ops) in enumerate(device_ops.items(), 1):
        evs = "".join(f"events {{ metadata_id: {i} offset_ps: {s * 1000} "
                      f"duration_ps: {d * 1000} }}\n"
                      for i, (_, s, d) in enumerate(ops, 1))
        meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                       f"name: {json.dumps(t)} }} }}\n"
                       for i, (t, _, _) in enumerate(ops, 1))
        planes.append(f'planes {{ id: {pid} name: "{plane}"\n'
                      f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n'
                      f'{evs}}}\n{meta}}}\n')
    evs = "".join(f"events {{ metadata_id: {i} offset_ps: {s * 1000} "
                  f"duration_ps: {d * 1000} }}\n"
                  for i, (_, s, d) in enumerate(spans, 1))
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n'
                   for i, (n, _, _) in enumerate(spans, 1))
    planes.append(f'planes {{ id: 99 name: "/host:CPU"\n'
                  f'lines {{ id: 1 name: "python3" timestamp_ns: 0\n{evs}}}'
                  f'\n{meta}}}\n')
    return "".join(planes)


KERNEL = ('%branch_0_fun.7 = f32[8,8]{1,0} custom-call(bf16[8,8]{1,0} %x), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
WHILE = "%while.3 = (s32[], f32[2]{0}) while((s32[], f32[2]{0}) %t)"
GATHER = "%all-gather-start.2 = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %g)"


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    dev = {
        "/device:TPU:0": [(FUSION, 100, 200), (KERNEL, 250, 100),
                          (WHILE, 100, 800), (GATHER, 340, 60),
                          (FUSION, 600, 100)],
        "/device:TPU:1": [(FUSION, 100, 400), (GATHER, 450, 150)],
    }
    spans = [("bench_window", 100, 900), ("bench_dispatch", 100, 50),
             ("bench_collect", 150, 850), ("bench_traffic", 720, 30)]
    return xtrace.from_profile(ProfileData.from_text_proto(
        _xspace(dev, spans)))


def test_parse_instruction():
    assert xtrace.parse_instruction(FUSION) == ("fusion.1", "fusion")
    assert xtrace.parse_instruction(WHILE) == ("while.3", "while")
    assert xtrace.parse_instruction(GATHER) == ("all-gather-start.2",
                                                "all-gather-start")
    assert xtrace.parse_instruction(KERNEL)[1] == "custom-call"


def test_trace_reads_ops_without_containers_and_spans(trace):
    names = [o.name for o in trace.ops["/device:TPU:0"]]
    assert "while.3" not in names and len(names) == 4
    assert xtrace.window(trace) == (100, 1000)


def test_idle_union_and_gaps(trace):
    lo, hi = xtrace.window(trace)
    # chip 0 busy [100,400) and [600,700) (the kernel and the gather
    # overlap the first), idle 600 of 900
    b0 = xtrace.busy(trace.ops["/device:TPU:0"], lo, hi)
    assert b0 == [(100, 400), (600, 700)]
    assert xtrace.gaps(b0, lo, hi) == [(400, 600), (700, 1000)]
    b1 = xtrace.busy(trace.ops["/device:TPU:1"], lo, hi)
    assert xtrace.total(b1) == 500


def test_kernel_sums(trace):
    import kernels
    lo, hi = xtrace.window(trace)
    names = {"branch_0_fun.7": "_fwd_kernel"}
    assert kernels.lora_time_ns(trace.ops["/device:TPU:0"], names,
                                lo, hi) == 100
    assert kernels.lora_time_ns(trace.ops["/device:TPU:0"],
                                {"branch_0_fun.7": "_dequant_mm_kernel"},
                                lo, hi) == 0


def test_breakdown_names_gaps_by_host_span(trace):
    lo, hi = xtrace.window(trace)
    bd = xtrace.breakdown(trace, lo, hi, {"branch_0_fun.7": "_fwd_kernel"})
    ops = dict(bd["device_ops"])
    assert ops["fusion.1"] == pytest.approx((200 + 100 + 400) / 1e9)
    assert ops["_fwd_kernel:branch_0_fun.7"] == pytest.approx(100 / 1e9)
    assert bd["idle_gaps"][0] == ["bench_collect", pytest.approx(400 / 1e9)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_metric_readers_on_the_synthetic_trace(trace):
    lo, hi = xtrace.window(trace)
    cell = catalog.Cell("c", 1, {"family": "dense_gqa", "model": TINY},
                        {"jobs": [{"rank": 4, "rows": 1}], "seq_len": 8},
                        {}, [], [])
    ctx_kw = dict(trace=trace, lo=lo, hi=hi,
                  kernels={"branch_0_fun.7": "_fwd_kernel"}, steps=2,
                  chips=2, cell=cell, peaks=flops.peaks("TPU v5 lite"),
                  step_doc_lengths=np.array([5]), tokens=10)
    ctx = type("Ctx", (), ctx_kw)
    read = lambda n: catalog.metric_reader(n)(ctx)
    assert read("device_idle_share") == pytest.approx(
        100 * ((1 - 400 / 900) + (1 - 500 / 900)) / 2)
    assert read("lora_kernel_ms_per_step") == pytest.approx(
        (100 + 0) / 2 / 1e6 / 2)
    want_mfu = 100 * flops.step_required_flops(dense_gqa, TINY,
                                               cell.traffic, [5]) \
        * 2 / (900e-9 * 2 * 197e12)
    assert read("step_mfu") == pytest.approx(want_mfu)
    least = max(flops.lora_flops(dense_gqa, TINY, {4: 8}) / 197e12,
                flops.lora_bytes(dense_gqa, TINY, {4: 8}) / 819e9)
    assert read("lora_kernel_roofline") == pytest.approx(
        100 * least * 2 / 100e-9)


def test_readers_find_nothing_and_return_none():
    empty = xtrace.Trace({"/device:TPU:0": [
        xtrace.Op("fusion.1", "fusion", 0, 10, FUSION)]}, [])
    ctx = type("Ctx", (), dict(trace=empty, lo=0, hi=10, kernels={},
                               steps=1, chips=1))
    for n in ("lora_kernel_ms_per_step", "lora_kernel_roofline"):
        assert catalog.metric_reader(n)(ctx) is None


def test_kernel_names_from_compiled_text():
    import base64
    body = base64.b64encode(b"MLIR\x00stable_mosaic\x00_wgrad_kernel\x00"
                            ).decode()
    hlo = (f'  %branch_0_fun.9 = f32[8]{{0}} custom-call(f32[8]{{0}} %a), '
           f'custom_call_target="tpu_custom_call", backend_config='
           f'{{"custom_call_config":{{"body":"{body}"}}}}\n'
           f'  %fusion.2 = f32[8]{{0}} fusion(f32[8]{{0}} %a)\n')
    assert xtrace.kernel_names(hlo) == {"branch_0_fun.9": "_wgrad_kernel"}


# --------------------------------------------------------------- traffic
MIX = os.path.join(BENCH, "traffic", "mixed8_short.json")


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 2 ** 40 + 3])
def test_traffic_repeats_per_seed_and_stays_in_vocab(seed):
    tr = json.load(open(MIX))
    a = traffic_gen.batches(tr, 11569, seed, 3)
    b = traffic_gen.batches(tr, 11569, seed, 3)
    # the program's specs give the harness the very same batches
    specs = traffic_gen.job_specs(tr, "x")
    ss = traffic_gen.streams(tr, specs, 11569, seed)
    for k, s in enumerate(ss):
        np.testing.assert_array_equal(s.next_batch()["tokens"],
                                      a[0][k]["tokens"])
    for sa, sb in zip(a, b):
        for ja, jb in zip(sa, sb):
            for k in ja:
                np.testing.assert_array_equal(ja[k], jb[k])
            toks = ja["tokens"][ja["loss_mask"] > 0]
            assert toks.min() >= 3 and toks.max() < 11569
            assert np.all(ja["tokens"][ja["loss_mask"] == 0] == 0)
    other = traffic_gen.batches(tr, 11569, seed + 1, 1)
    assert not np.array_equal(other[0][0]["tokens"], a[0][0]["tokens"])


def test_every_step_and_seed_holds_the_same_lengths():
    tr = json.load(open(MIX))
    base = np.sort(traffic_gen.lengths_multiset(tr["lengths"], 32))
    assert base.min() >= 16 and base.max() <= 512
    assert 170 < base.mean() < 195              # GSM8K-like, mean ~181
    for seed in (1, 2 ** 33):
        sl = traffic_gen.StepLengths(tr, seed)
        for step in range(3):
            got = np.concatenate([sl.of(step, k) for k in range(8)])
            np.testing.assert_array_equal(np.sort(got), base)


# ----------------------------------------------------- cells found by name
def test_a_cell_added_as_files_is_found_by_name(tmp_path):
    """A new cell needs only a BENCHMARK.json entry, its config, traffic
    and limits files, and (for a new metric) a reader file."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    for d in ("configs", "traffic", "limits", "metrics"):
        (bench / d).mkdir(parents=True)
    shutil.copy(os.path.join(BENCH, "configs", "qwen1.5-110b.json"),
                bench / "configs" / "qwen1.5-110b.json")
    tr = json.load(open(MIX))
    tr["jobs"] = [{"rank": 16, "rows": 16}]
    (bench / "traffic" / "solo16.json").write_text(json.dumps(tr))
    (bench / "limits" / "qwen1.5-110b.solo16.json").write_text(
        json.dumps({"loss_gap": 0.1, "moment_gap": 0.1, "update_gap": 0.1}))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["workloads"].append({"name": "qwen1.5-110b.solo16",
                              "config": "qwen1.5-110b", "traffic": "solo16",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new_metric", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "x", "moves": "train_tokens_per_s",
                              "workloads": ["qwen1.5-110b.solo16"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = catalog.load_cell(str(root), "qwen1.5-110b.solo16", str(bench))
    assert cell.traffic["jobs"] == [{"rank": 16, "rows": 16}]
    assert cell.model["hidden_size"] == 8192
    assert "new_metric" in [m["name"] for m in cell.per_layer]
    assert catalog.metric_reader("new_metric", str(bench))(None) == 42.0
    cfg = catalog.model_config(cell)
    assert (cfg.d_model, cfg.d_ff, cfg.num_layers, cfg.vocab_size,
            cfg.attn_bias, cfg.rope_theta) == (8192, 49152, 3, 19008,
                                               True, 1e6)
    with pytest.raises(KeyError):
        catalog.load_cell(str(root), "qwen1.5-110b.nothing", str(bench))


@pytest.mark.parametrize("cell", [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]])
def test_every_cell_of_the_benchmark_loads(cell):
    c = catalog.load_cell(ROOT, cell)
    cfg = catalog.model_config(c)
    assert cfg.num_layers == c.model["num_hidden_layers"]
    assert set(c.limits) <= {"loss_gap", "moment_gap", "update_gap",
                             "moment_diff"}
    for m in c.per_layer:
        assert callable(catalog.metric_reader(m["name"]))


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "internlm2-20b.mixed8_short", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300, cwd=ROOT)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
