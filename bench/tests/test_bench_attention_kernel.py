"""``attention_kernel_ms_per_step`` on a synthetic trace: it sums the
flash launches and nothing else, and the LoRA metric leaves them out."""
import pytest

import catalog
import xtrace
from test_bench_yardstick import FUSION, _xspace


def _call(name):
    return (f'%{name} = bf16[8,8]{{1,0}} custom-call(bf16[8,8]{{1,0}} %x), '
            f'custom_call_target="tpu_custom_call"')


NAMES = {"flash_attention_fwd.1": "flash_attention_fwd",
         "flash_attention_dkv.2": "flash_attention_dkv",
         "ragged_lora_fwd.3": "ragged_lora_fwd"}
# what ``xtrace.kernel_names`` decodes on the chip: a flash body holds no
# symbol named for a kernel, so only the instruction names the launch
DECODED = {"flash_attention_fwd.1": "pallas_call",
           "flash_attention_dkv.2": "pallas_call",
           "ragged_lora_fwd.3": "fused_lora_ragged"}


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    dev = {
        "/device:TPU:0": [(FUSION, 100, 100),
                          (_call("flash_attention_fwd.1"), 200, 300),
                          (_call("ragged_lora_fwd.3"), 500, 50),
                          (_call("flash_attention_dkv.2"), 600, 200)],
        "/device:TPU:1": [(_call("flash_attention_fwd.1"), 100, 100)],
    }
    spans = [("bench_window", 100, 900)]
    return xtrace.from_profile(ProfileData.from_text_proto(
        _xspace(dev, spans)))


def _ctx(trace, kernels, steps=2):
    lo, hi = xtrace.window(trace)
    return type("Ctx", (), dict(trace=trace, lo=lo, hi=hi, kernels=kernels,
                                steps=steps, chips=2))


@pytest.mark.parametrize("names", [NAMES, DECODED],
                         ids=["named", "decoded"])
def test_flash_launches_are_summed_per_step_and_chip(trace, names):
    read = catalog.metric_reader("attention_kernel_ms_per_step")
    # chip 0: 300 + 200 ns of flash, chip 1: 100 ns; mean 300 ns, 2 steps
    assert read(_ctx(trace, names)) == pytest.approx(300 / 2 / 1e6)
    lora = catalog.metric_reader("lora_kernel_ms_per_step")
    assert lora(_ctx(trace, names)) == pytest.approx(50 / 2 / 2 / 1e6)


def test_no_flash_launch_reads_none():
    read = catalog.metric_reader("attention_kernel_ms_per_step")
    lora_only = xtrace.Trace({"/device:TPU:0": [xtrace.Op(
        "ragged_lora_fwd.3", "custom-call", 0, 10,
        _call("ragged_lora_fwd.3"))]}, [])
    ctx = type("Ctx", (), dict(trace=lora_only, lo=0, hi=10, steps=1,
                               chips=1, kernels=DECODED))
    assert read(ctx) is None
    ctx.steps = 0
    assert read(ctx) is None
