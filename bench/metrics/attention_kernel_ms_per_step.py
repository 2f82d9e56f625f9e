"""attention_kernel_ms_per_step (ms): device time of the Pallas launches
named for ``flash`` (the attention core's forward, dq and dk/dv kernels
of ``kernels/flash_attention.py``), in the traced window, per step, mean
over chips.  A launch's instruction carries the name given to its
``pallas_call``; the name decoded from its Mosaic body is read too.
None where no such launch ran."""
import numpy as np

import xtrace


def read(ctx):
    def flash(o):
        return o.is_pallas and any(
            "flash" in n.lower() for n in (o.name, ctx.kernels.get(o.name, "")))

    per_chip = [xtrace.op_time(ops, ctx.lo, ctx.hi, flash)
                for ops in ctx.trace.ops.values()]
    if not ctx.steps or not any(per_chip):
        return None
    return float(np.mean(per_chip)) / 1e6 / ctx.steps
