"""device_idle_share (%): the share of the traced window in which no
operation runs on a chip, mean over the cell's chips.  Busy is the union
of the chip's op intervals (``xtrace.busy``)."""
import numpy as np

import xtrace


def read(ctx):
    if not ctx.trace.ops:
        return None
    w = ctx.hi - ctx.lo
    idle = [1.0 - xtrace.total(xtrace.busy(ops, ctx.lo, ctx.hi)) / w
            for ops in ctx.trace.ops.values()]
    return 100.0 * float(np.mean(idle))
