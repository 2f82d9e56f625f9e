"""lora_kernel_roofline (%): the least time the chips could take for the
LoRA work of the traced window, over the time the LoRA kernels took.
The least time is max(FLOPs / peak FLOP/s, bytes / HBM bandwidth) of
each job's rows at its own rank (``flops.lora_flops``/``lora_bytes``),
the same work whichever kernel family runs it.  At ranks up to 128 the
bytes bound it (arithmetic intensity r FLOP/byte, under the chip's
ridge), so this is a share of the memory roofline."""
import flops
import kernels


def read(ctx):
    used = sum(kernels.lora_time_ns(ops, ctx.kernels, ctx.lo, ctx.hi)
               for ops in ctx.trace.ops.values())
    if not ctx.steps or not used:
        return None
    tok = flops.lora_kernel_tokens(ctx.cell.traffic)
    fam, m = ctx.cell.family, ctx.cell.model
    least = max(flops.lora_flops(fam, m, tok) / ctx.peaks["bf16_flops_per_s"],
                flops.lora_bytes(fam, m, tok) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.steps / (used / 1e9)
