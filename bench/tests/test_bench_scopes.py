"""The program's named scopes and host spans as the benchmark reads
them, on the CPU: the scope of an op path, the modules and spans a
profile records, the seven readers on the synthetic trace of
``test_bench_yardstick.py`` extended with a scope map and ``tlora.*``
spans, every matmul of a compiled step under a scope, and the
``TrainReport`` counters against the traffic's own count."""
import glob
import json
import os

import numpy as np
import pytest

import catalog
import flops
import scopes
import traffic_gen
import xtrace
from test_bench_yardstick import FUSION, GATHER, KERNEL, TINY, WHILE, _xspace

STEP = "jit(chunked_step)/while/body/closed_call"
READERS = ("attention_ms_per_step", "mlp_ms_per_step", "mlp_roofline",
           "head_loss_ms_per_step", "lora_layer_ms_per_step",
           "remat_ms_per_step", "host_stage_ms_per_step")


# -------------------------------------------------------------- scopes
@pytest.mark.parametrize("path,scope", [
    (f"{STEP}/jvp(mlp)/dot_general", "mlp"),
    (f"{STEP}/transpose(jvp(attention))/lora/ragged_lora_fwd/pallas_call",
     "lora"),
    (f"{STEP}/jvp(attention)/checkpoint/rematted_computation/dot_general",
     "attention"),
    (f"{STEP}/transpose(jvp(head_loss))/dot_general", "head_loss"),
    (f"{STEP}/optimizer/sqrt", "optimizer"),
    (f"{STEP}/jvp(embed)/gather", "embed"),
    (f"{STEP}/dynamic_update_slice", None),
    ("", None),
    (None, None),
])
def test_scope_is_the_innermost_vocabulary_component(path, scope):
    assert scopes.scope_of(path) == scope


@pytest.mark.parametrize("name,path,remat", [
    ("fusion.462.remat2", f"{STEP}/jvp(mlp)/mul", True),
    ("fusion.7.remat", None, True),
    ("copy.3.remat2.1", None, True),
    ("dot_general.6",
     f"{STEP}/jvp(mlp)/checkpoint/rematted_computation/dot_general", True),
    ("fusion.466", f"{STEP}/transpose(jvp(mlp))/dot_general", False),
    ("rematerialize.1", None, False),
])
def test_recomputed_ops_are_flagged_both_ways(name, path, remat):
    assert scopes.is_remat(name, path) is remat


def test_op_paths_from_a_compiled_module():
    """The decoder of ``HloModuleProto`` the readers use names every
    instruction with its ``op_name``: both dots of a scoped gradient
    carry the scope, forward and transposed."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ x).sum()

    compiled = jax.jit(jax.grad(f)).lower(jnp.ones((8, 8))).compile()
    paths = matmul_paths(compiled)
    assert all(scopes.scope_of(p) == "mlp" for p in paths.values()), paths
    assert {p.split("/", 1)[1] for p in paths.values()} >= {
        "jvp(mlp)/dot_general", "transpose(jvp(mlp))/dot_general"}


def _cpu_profile(trace_dir):
    """Profile a jitted function with named scopes, a checkpoint and a
    program span on the CPU; returns the ``.xplane.pb`` path."""
    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("attention"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("mlp"):
            y = jax.checkpoint(lambda y: jnp.sin(y @ w) @ w)(y)
        return y.sum()

    g = jax.jit(jax.grad(f))
    x = w = jnp.ones((32, 32))
    g(x, w).block_until_ready()
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("tlora.stage", chunk=3):
        g(x, w).block_until_ready()
    jax.profiler.stop_trace()
    return sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]


def test_modules_and_program_spans_read_from_a_profile(tmp_path,
                                                       monkeypatch):
    path = _cpu_profile(tmp_path / ".bench_trace" / "c")
    sc = scopes.load(path)
    assert {scopes.scope_of(p) for p in sc.paths.values()} >= {
        "attention", "mlp"}
    assert any("rematted_computation" in p and scopes.scope_of(p) == "mlp"
               for p in sc.paths.values())
    assert [s.name for s in sc.spans] == ["tlora.stage"]
    assert sc.spans[0].end > sc.spans[0].start
    # the readers find the file where the harness leaves it
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    ctx = _ctx(xtrace.Trace({}, []), cell_name="c")
    assert scopes.of(ctx) is ctx.scoped
    assert ctx.scoped.paths == sc.paths
    assert scopes.of(_ctx(xtrace.Trace({}, []), cell_name="d")) is None


# ------------------------------------------------------------- readers
REMAT = "%fusion.9.remat2 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
PATHS = {
    "fusion.1": f"{STEP}/transpose(jvp(mlp))/dot_general",
    "branch_0_fun.7":
        f"{STEP}/transpose(jvp(attention))/lora/cond/branch_0_fun/pallas_call",
    "all-gather-start.2":
        f"{STEP}/checkpoint/rematted_computation/attention/all_gather",
    "fusion.9.remat2": f"{STEP}/jvp(mlp)/mul",
}


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    dev = {
        "/device:TPU:0": [(FUSION, 100, 200), (KERNEL, 250, 100),
                          (WHILE, 100, 800), (GATHER, 340, 60),
                          (FUSION, 600, 100)],
        "/device:TPU:1": [(FUSION, 100, 400), (GATHER, 450, 150),
                          (REMAT, 800, 50)],
    }
    spans = [("bench_window", 100, 900), ("bench_dispatch", 100, 50),
             ("bench_collect", 150, 850), ("bench_traffic", 720, 30),
             ("tlora.dispatch", 100, 50), ("tlora.stage", 110, 20),
             ("tlora.collect", 150, 850), ("tlora.wait", 150, 560),
             ("tlora.stage", 960, 140)]
    return ProfileData.from_text_proto(_xspace(dev, spans))


def _ctx(trace, scoped=None, cell_name="c"):
    lo, hi = (100, 1000) if trace.spans else (0, 1)
    cell = catalog.Cell(cell_name, 1, {"model": TINY},
                        {"jobs": [{"rank": 4, "rows": 1}], "seq_len": 8},
                        {}, [], [])
    kw = dict(trace=trace, lo=lo, hi=hi,
              kernels={"branch_0_fun.7": "_fwd_kernel"}, steps=2, chips=2,
              cell=cell, peaks=flops.peaks("TPU v5 lite"),
              step_doc_lengths=np.array([5]))
    if scoped is not None:
        kw["scoped"] = scoped
    return type("Ctx", (), kw)


def test_program_spans_beside_the_harness_spans(profile):
    trace = xtrace.from_profile(profile)
    assert [s.name for s in trace.spans] == [
        "bench_window", "bench_dispatch", "bench_collect", "bench_traffic"]
    assert [(s.name, s.start, s.end) for s in scopes.program_spans(profile)
            ] == [("tlora.dispatch", 100, 150), ("tlora.stage", 110, 130),
                  ("tlora.collect", 150, 1000), ("tlora.wait", 150, 710),
                  ("tlora.stage", 960, 1100)]


def test_new_readers_on_the_synthetic_trace(profile):
    trace = xtrace.from_profile(profile)
    ctx = _ctx(trace, scopes.Scoped(PATHS, scopes.program_spans(profile)))
    read = {n: catalog.metric_reader(n)(ctx) for n in READERS}
    ms = lambda chip0, chip1: (chip0 + chip1) / 2 / 1e6 / 2
    # mlp: fusion.1 (200 + 100 | 400) and the XLA-rematted fusion (50)
    assert read["mlp_ms_per_step"] == pytest.approx(ms(300, 450))
    # the LoRA launch inside attention is lora, not attention
    assert read["lora_layer_ms_per_step"] == pytest.approx(ms(100, 0))
    assert read["attention_ms_per_step"] == pytest.approx(ms(60, 150))
    # both kinds of recomputation: checkpoint's gather and XLA's .remat2
    assert read["remat_ms_per_step"] == pytest.approx(ms(60, 200))
    assert read["head_loss_ms_per_step"] is None
    # stage spans inside the window: 20 and 40 of 140 (clipped at 1000)
    assert read["host_stage_ms_per_step"] == pytest.approx(60 / 1e6 / 2)
    # one row of 8 tokens; d 8, d_ff 16, 3 layers: bytes bound it
    mlp = catalog.metric_reader("mlp_roofline").__globals__
    f = 3 * 4 * 8 * 16 * 8 * 3
    b = 2 * 3 * (2 * 3 * 8 * 16 + 4 * 8 * 8)
    assert (mlp["mlp_flops"](TINY, 8), mlp["mlp_bytes"](TINY, 8)) == (f, b)
    least = max(f / 197e12, b / 819e9)
    assert read["mlp_roofline"] == pytest.approx(
        100 * least / (ms(300, 450) / 1e3))
    # the scoped breakdown names ops by scope and gaps by both spans
    bd = scopes.breakdown(ctx, ctx.scoped)
    names = dict(bd["device_ops"])
    assert names["mlp:fusion.1"] == pytest.approx(700 / 1e9)
    assert "lora:_fwd_kernel:branch_0_fun.7" in names
    assert names["mlp:fusion.9.remat2"] == pytest.approx(50 / 1e9)
    assert bd["idle_gaps"] == [
        ["bench_collect/tlora.collect", pytest.approx(300 / 1e9)],
        ["bench_collect/tlora.wait", pytest.approx(200 / 1e9)],
        ["bench_collect/tlora.wait", pytest.approx(200 / 1e9)],
        ["bench_collect/tlora.collect", pytest.approx(150 / 1e9)]]
    assert bd["unscoped_share"] == 0.0
    unscoped = scopes.breakdown(ctx, scopes.Scoped({}, []))
    assert unscoped["unscoped_share"] == 1.0


def test_new_readers_return_none_without_scopes_or_spans(profile):
    trace = xtrace.from_profile(profile)
    bare = _ctx(trace, scopes.Scoped({}, []))
    for n in READERS:
        if n != "remat_ms_per_step":
            assert catalog.metric_reader(n)(bare) is None, n
    # XLA's own rematerialization is named by the op, scopes or not
    assert catalog.metric_reader("remat_ms_per_step")(bare) == \
        pytest.approx(50 / 2 / 1e6 / 2)
    no_file = _ctx(trace, cell_name="no-such-cell")
    for n in READERS:
        assert catalog.metric_reader(n)(no_file) is None, n


# ------------------------------------------- the program, compiled here
def matmul_paths(*compiled) -> dict:
    """Every dot, convolution and custom call of the compiled modules,
    by instruction name, with its ``op_name`` path as the readers decode
    it from the module (None where it has none)."""
    import re
    out = {}
    for c in compiled:
        for m in c.runtime_executable().hlo_modules():
            paths = scopes.hlo_module_paths(
                m.as_serialized_hlo_module_proto())
            for n in re.findall(r"^\s*(?:ROOT )?%(\S+) = [^\n]*? "
                                r"(?:dot|convolution|custom-call)\(",
                                m.to_string(), re.M):
                out[n] = paths.get(n)
    return out


def _assert_scoped(paths: dict):
    assert len(paths) > 20
    assert {n: p for n, p in paths.items()
            if scopes.scope_of(p) is None} == {}
    assert {scopes.scope_of(p) for p in paths.values()} >= {
        "attention", "mlp", "lora", "head_loss"}
    remat = {scopes.scope_of(p) for n, p in paths.items()
             if scopes.is_remat(n, p)}
    assert remat >= {"attention", "mlp"}


def _tiny_cfg():
    import dataclasses
    from repro.configs import get_config
    return dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                               vocab_size=128)


@pytest.mark.parametrize("ranks", [(32, 8), (4, 4)],
                         ids=["ragged", "masked"])
def test_every_matmul_of_the_step_is_under_a_scope(ranks):
    """The chunked step of a dense model with remat, interpreted Pallas
    kernels of both families: every dot, convolution and custom call
    falls under a vocabulary scope, and the recomputed forward is
    flagged."""
    import jax
    from repro.core.jobs import LoRAJobSpec
    from repro.elastic.runtime import GroupRuntime
    jobs = [LoRAJobSpec(f"j{k}", rank=r, batch_size=2, seq_len=32)
            for k, r in enumerate(ranks)]
    rt = GroupRuntime.from_specs(_tiny_cfg(), jobs, jax.random.PRNGKey(0),
                                 impl="pallas", block_t=16, chunk_size=2,
                                 remat=True)
    assert rt.ssm.layout.is_uniform == (len(set(ranks)) == 1)
    rt.warm()
    _assert_scoped(matmul_paths(*rt._step_cache.values()))


PIPELINE = """
import json, sys
sys.path[:0] = {paths!r}
import jax
import test_bench_scopes as T
from repro.core.jobs import LoRAJobSpec
from repro.elastic.runtime import GroupRuntime
jobs = [LoRAJobSpec(f"j{{k}}", rank=r, batch_size=4, seq_len=32)
        for k, r in enumerate((32, 8))]
rt = GroupRuntime.from_specs(
    T._tiny_cfg(), jobs, jax.random.PRNGKey(0), impl="pallas", block_t=16,
    chunk_size=2, remat=True, mesh=jax.make_mesh((4,), ("data",)),
    tp_mode="pipeline", pipeline_stages=2, nano_batches=2)
assert dict(rt.mesh.shape) == {{"stage": 2, "data": 2}}
rt.warm()
print(json.dumps(T.matmul_paths(*rt._step_cache.values())))
"""


def test_every_matmul_of_the_pipeline_step_is_under_a_scope():
    """The same on the 2-stage x 2-way pipeline step, in a process of its
    own with 4 host devices: its last stage's head and loss run under
    ``head_loss`` too."""
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.dirname(here)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = PIPELINE.format(paths=[here, bench, os.path.join(
        os.path.dirname(bench), "src")])
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    _assert_scoped(json.loads(out.stdout.strip().splitlines()[-1]))


MIX = {"jobs": [{"rank": 4, "rows": 2}, {"rank": 8, "rows": 1}],
       "seq_len": 32, "alpha": 16.0,
       "lengths": {"law": "lognormal", "mu": 2.5, "sigma": 0.6, "min": 4,
                   "max": 32}}


def test_report_counts_the_tokens_the_streams_made():
    """Three chunks of 2, 2 and 1 steps over the benchmark's streams, the
    first two staged ahead as the harness stages them: the report counts
    the real tokens of the steps trained (not of a prefetched batch),
    their rows, and one compile per step-cache miss."""
    import jax
    from repro.elastic.runtime import GroupRuntime
    cfg = _tiny_cfg()
    specs = traffic_gen.job_specs(MIX, cfg.name)
    streams = traffic_gen.streams(MIX, specs, cfg.vocab_size, 2 ** 40 + 7)
    rt = GroupRuntime.from_specs(cfg, specs, jax.random.PRNGKey(1),
                                 streams=streams, chunk_size=2)
    rep = rt.collect_chunk(rt.dispatch_chunk(2, prefetch=2))
    assert rep.real_tokens == sum(s.real_tokens[i] for s in streams
                                  for i in range(2))
    assert len(streams[0].real_tokens) == 4          # a chunk staged ahead
    rt.collect_chunk(rt.dispatch_chunk(2, prefetch=0))
    rep = rt.collect_chunk(rt.dispatch_chunk(1))
    steps = 5
    want = sum(s.real_tokens[i] for s in streams for i in range(steps))
    assert 0 < want < steps * 3 * 32
    assert rep.real_tokens == want
    assert rep.row_tokens == steps * rt.batcher.total_rows() * 32
    assert rep.compiles == len(rt._step_cache) == 2
    assert rep.compile_s > 0
    assert rep.tokens_per_sec == pytest.approx(
        want / float(np.sum(rep.step_times)))
