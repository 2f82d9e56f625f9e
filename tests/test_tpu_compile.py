"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode cannot see Mosaic's block-shape and VMEM rules, so these
tests compile — without running — the kernels at tinyllama-1.1b widths
(d_model 2048, d_ff 5632, GQA k/v 256) for a v5e that is described, not
attached: the five ragged launches for mixed ranks {64,16,8,4} (packed
R = 112 at 16-wide rank tiles), the masked forward with its grouped
wgrad, the dequant matmul, the flash-attention forward and backward at
the benchmark cells' attention shapes, and one whole chunked Pallas
train step.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU compiler.  Compiles happen in
the test's own process.
"""
import functools
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.jobs import LoRAJobSpec
from repro.core.lora import RankLayout
from repro.kernels import fused_lora as pk
from repro.kernels import ragged as rg

CFG = get_config("tinyllama-1.1b")
RANKS = (64, 16, 8, 4)
BT = 128
ROWS, SEQ = 2, 1024                      # per job: 2 x 1024 tokens
T = len(RANKS) * ROWS * SEQ
# (d_in, d_out) of the adapted projections: q/o, k/v, gate/up, down
WIDTHS = [(2048, 2048), (2048, 256), (2048, 5632), (5632, 2048)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executables cannot be read back from the
    # persistent cache here; keep it out of the way for this module
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def meta():
    layout = RankLayout(RANKS, multiple=16)
    assert layout.total == 112
    tiles = [k for k in range(len(RANKS)) for _ in range(ROWS * SEQ // BT)]
    return rg.RaggedMeta.build(tiles, layout)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF = jnp.bfloat16


@pytest.mark.parametrize("d_in,d_out", WIDTHS)
@pytest.mark.parametrize("kernel", ["fwd", "dgrad", "xa", "dxa", "wgrad"])
def test_ragged_kernel_compiles(one_chip, meta, kernel, d_in, d_out):
    R = meta.total_r
    x, A, B = (T, d_in), (d_in, R), (R, d_out)
    dy, ut = (T, d_out), (R, T)
    kw = dict(meta=meta, block_t=BT)
    fn, shapes = {
        "fwd": (functools.partial(rg.ragged_lora_fwd, **kw), [x, A, B]),
        "dgrad": (functools.partial(rg.ragged_lora_dgrad, **kw),
                  [dy, A, B]),
        "xa": (functools.partial(rg.ragged_xa, **kw), [x, A]),
        "dxa": (functools.partial(rg.ragged_dxa, **kw), [dy, B]),
        "wgrad": (functools.partial(rg.ragged_wgrad, **kw), [ut, dy]),
    }[kernel]
    _compile(fn, one_chip, *[(s, BF) for s in shapes])


@pytest.mark.parametrize("d_in,d_out", WIDTHS)
def test_masked_fwd_and_grouped_wgrad_compile(one_chip, d_in, d_out):
    K, r_pad = len(RANKS), 16
    tiles = (T // BT,)

    def fwd_and_wgrad(x, A, B, tm, ranks, g):
        y = pk.fused_lora_pallas(x, A, B, tm, ranks, block_t=BT)
        return y, pk.grouped_wgrad_pallas(x, g, tm, K, block_t=BT)

    _compile(fwd_and_wgrad, one_chip, ((T, d_in), BF),
             ((K, d_in, r_pad), BF), ((K, r_pad, d_out), BF),
             (tiles, jnp.int32), ((K,), jnp.int32), ((T, r_pad), BF))


@pytest.mark.parametrize("d_in,d_out", WIDTHS)
def test_dequant_matmul_compiles(one_chip, d_in, d_out):
    _compile(functools.partial(pk.dequant_matmul_pallas, block_t=BT),
             one_chip, ((T, d_in), BF), ((d_in, d_out), jnp.int8),
             ((d_out,), jnp.float32))


def test_full_width_chunked_pallas_step_compiles(one_chip):
    """tinyllama-1.1b at full width, K=4 mixed ranks, 2 steps per chunk:
    the whole chunked train step compiles for one v5e chip, fits its
    HBM, and runs its LoRA projections as Mosaic kernels."""
    from repro.core.ssm import SharedSuperModel
    from repro.data.pipeline import FusedBatcher
    from repro.optim import adamw
    from repro.optim.schedule import constant

    jobs = [LoRAJobSpec(f"j{k}", rank=r, batch_size=ROWS, seq_len=SEQ,
                        base_model=CFG.name) for k, r in enumerate(RANKS)]
    ssm = SharedSuperModel(CFG, jobs, impl="pallas", block_t=BT)
    assert not ssm.layout.is_uniform           # the ragged family
    params, adapters = jax.eval_shape(ssm.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda a: adamw.init(a, per_job=len(jobs)),
                         adapters)
    batches = FusedBatcher(jobs, CFG.vocab_size, block_t=BT).next_batches(2)
    on_chip = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    step = ssm.make_train_step(lr_fn=constant(1e-4), steps=2, remat=True)
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        on_chip(params), on_chip(adapters), on_chip(opt),
        on_chip(batches)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the attention core runs on the flash kernels (head dim 64, padded
    # to one lane tile), forward, recomputed forward and backward
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert re.search(rf"%{name}(\.\d+)? = ", text), name
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("B,S,H,KV", [(32, 512, 48, 8), (8, 1024, 64, 8)],
                         ids=["internlm2-20b", "qwen1.5-110b"])
def test_flash_kernels_compile_at_cell_shapes(one_chip, B, S, H, KV):
    """The flash forward and both backward launches at the benchmark
    cells' attention shapes (rows x seq, query/KV heads, hd 128)."""
    from repro.kernels import flash_attention as fa
    hd = 128

    def fwd_bwd(q, k, v, dout):
        out, lse = fa.flash_fwd(q, k, v, causal=True)
        return fa.flash_bwd(q, k, v, out, lse, dout, causal=True)

    q, kv = ((B, S, H, hd), BF), ((B, S, KV, hd), BF)
    text = _compile(fwd_bwd, one_chip, q, kv, kv, q).as_text()
    calls = re.findall(r"%(\S+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert sorted(c.split(".")[0] for c in calls) == [
        "flash_attention_dkv", "flash_attention_dq", "flash_attention_fwd"]


def _pallas_launches():
    """(launch, kernel function, name, is a LoRA kernel, fn, shapes)."""
    from repro.kernels import flash_attention as fa
    layout = RankLayout(RANKS, multiple=16)
    meta_s = rg.RaggedMeta.build([k for k in range(4) for _ in range(2)],
                                 layout)
    R, T_s, d, K, rp = meta_s.total_r, 4 * 2 * BT, 256, len(RANKS), 16
    q, kv = ((2, 256, 2, 128), BF), ((2, 256, 1, 128), BF)
    qkv = [q, kv, kv]
    residuals = qkv + [q, ((2, 2, 256), jnp.float32), q]   # out, lse, dout
    kw = dict(meta=meta_s, block_t=BT)
    return [
        ("ragged fwd", rg._fwd_kernel, "ragged_lora_fwd", True,
         functools.partial(rg.ragged_lora_fwd, **kw),
         [((T_s, d), BF), ((d, R), BF), ((R, d), BF)]),
        ("ragged dgrad", rg._dgrad_kernel, "ragged_lora_dgrad", True,
         functools.partial(rg.ragged_lora_dgrad, **kw),
         [((T_s, d), BF), ((d, R), BF), ((R, d), BF)]),
        ("ragged xa", rg._rank_major_kernel, "ragged_lora_rank_major", True,
         functools.partial(rg.ragged_xa, **kw), [((T_s, d), BF),
                                                 ((d, R), BF)]),
        ("ragged wgrad", rg._wgrad_kernel, "ragged_lora_wgrad", True,
         functools.partial(rg.ragged_wgrad, **kw), [((R, T_s), BF),
                                                    ((T_s, d), BF)]),
        ("masked fwd", pk._fused_lora_kernel, "fused_lora_fwd", True,
         functools.partial(pk.fused_lora_pallas, block_t=BT),
         [((T_s, d), BF), ((K, d, rp), BF), ((K, rp, d), BF),
          ((T_s // BT,), jnp.int32), ((K,), jnp.int32)]),
        ("masked wgrad", pk._grouped_wgrad_kernel, "grouped_lora_wgrad",
         True, lambda x, g, tm: pk.grouped_wgrad_pallas(x, g, tm, K,
                                                        block_t=BT),
         [((T_s, d), BF), ((T_s, rp), BF), ((T_s // BT,), jnp.int32)]),
        ("masked dx", pk._grouped_mm_kernel, "grouped_lora_matmul", True,
         functools.partial(pk.grouped_matmul_pallas, block_t=BT),
         [((T_s, rp), BF), ((K, rp, d), BF), ((T_s // BT,), jnp.int32)]),
        ("dequant", pk._dequant_mm_kernel, "dequant_matmul", False,
         functools.partial(pk.dequant_matmul_pallas, block_t=BT),
         [((T_s, d), BF), ((d, d), jnp.int8), ((d,), jnp.float32)]),
        ("flash", fa._flash_fwd_kernel, "flash_attention_fwd", False,
         lambda q, k, v: fa.flash_fwd(q, k, v, causal=True)[0], qkv),
        ("flash dq", fa._flash_dq_kernel, "flash_attention_dq", False,
         lambda *res: fa.flash_bwd(*res, causal=True)[0], residuals),
        ("flash dkv", fa._flash_dkv_kernel, "flash_attention_dkv", False,
         lambda *res: fa.flash_bwd(*res, causal=True)[1:], residuals),
    ]


@pytest.mark.parametrize("launch", range(11), ids=lambda i: [
    "ragged_fwd", "ragged_dgrad", "ragged_xa", "ragged_wgrad", "masked_fwd",
    "masked_wgrad", "masked_dx", "dequant", "flash", "flash_dq",
    "flash_dkv"][i])
def test_pallas_launch_names(one_chip, launch):
    """Every Pallas launch carries its kernel's name: the Mosaic call's
    instruction is named by it, and the benchmark's ``kernels.is_lora``
    classifies the name as it classifies the kernel function's symbol.
    Every LoRA kernel's name says ``lora``; the dequant and flash
    kernels' do not.  The dq and dk/dv launches are compiled each
    alone: the other backward launch is dead code there."""
    import re
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import kernels
    import xtrace
    _, kernel_fn, name, lora, fn, shapes = _pallas_launches()[launch]
    text = _compile(fn, one_chip, *shapes).as_text()
    calls = re.findall(r"%(\S+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert calls and all(re.fullmatch(rf"{name}(\.\d+)?", c)
                         for c in calls), calls
    assert ("lora" in name) is lora
    assert kernels.is_lora(name) is kernels.is_lora(kernel_fn.__name__) \
        is lora
    # the benchmark's decoder finds a name for every launch (which one
    # depends on the Python call sites recorded in the Mosaic body)
    assert set(xtrace.kernel_names(text)) == set(calls)
