"""lora_kernel_ms_per_step (ms): device time of the LoRA Pallas kernels
(``kernels.is_lora``) in the traced window, per step, mean over chips."""
import numpy as np

import kernels


def read(ctx):
    per_chip = [kernels.lora_time_ns(ops, ctx.kernels, ctx.lo, ctx.hi)
                for ops in ctx.trace.ops.values()]
    if not ctx.steps or not any(per_chip):
        return None
    return float(np.mean(per_chip)) / 1e6 / ctx.steps
