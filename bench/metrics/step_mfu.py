"""step_mfu (%): the whole step's share of the chips' bf16 peak.
Required FLOPs of a step over its real tokens (``flops.py`` and the
configuration's family: backbone forward and input gradient, causal
attention, every job's LoRA at its own rank; no recomputation, no
padding) times the steps of the traced window, over its length times
chips times peak."""
import flops


def read(ctx):
    if not ctx.steps:
        return None
    per_step = flops.step_required_flops(ctx.cell.family, ctx.cell.model,
                                         ctx.cell.traffic,
                                         ctx.step_doc_lengths)
    seconds = (ctx.hi - ctx.lo) / 1e9
    return 100.0 * per_step * ctx.steps / (
        seconds * ctx.chips * ctx.peaks["bf16_flops_per_s"])
