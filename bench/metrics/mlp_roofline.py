"""mlp_roofline (%): the least time the chips could take for the MLP
work of the traced window, over ``mlp_ms_per_step``.  The work is the
forward and the input gradient of the three projections (gate, up,
down: ``4 * d * d_ff`` FLOPs per token each, every layer) over every
row position as the kernels get them (``flops.lora_kernel_tokens``'
count); recomputation is not counted, so remat shows as a lower share.
Least time is max(FLOPs / peak FLOP/s, bytes / HBM bandwidth), the
bytes being the three weights read twice and x, y, dy, dx moved once
(bf16)."""
import flops
import scopes


def mlp_flops(m, tokens):
    return 3 * 4.0 * m["hidden_size"] * m["intermediate_size"] * tokens \
        * m["num_hidden_layers"]


def mlp_bytes(m, tokens):
    d, f = m["hidden_size"], m["intermediate_size"]
    return flops.BF16_BYTES * m["num_hidden_layers"] * (
        2 * 3 * d * f + 4 * tokens * d)


def read(ctx):
    ms = scopes.scope_ms_per_step(ctx, "mlp")
    if ms is None:
        return None
    tok = sum(flops.lora_kernel_tokens(ctx.cell.traffic).values())
    m = ctx.cell.model
    least = max(mlp_flops(m, tok) / ctx.peaks["bf16_flops_per_s"],
                mlp_bytes(m, tok) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
