"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode cannot see Mosaic's block-shape and VMEM rules, so these
tests compile — without running — the kernels at tinyllama-1.1b widths
(d_model 2048, d_ff 5632, GQA k/v 256) for a v5e that is described, not
attached: the five ragged launches for mixed ranks {64,16,8,4} (packed
R = 112 at 16-wide rank tiles), the masked forward with its grouped
wgrad, the dequant matmul, and one whole chunked Pallas train step.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU compiler.  Compiles happen in
the test's own process.
"""
import functools
import os

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
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
