"""lora_layer_ms_per_step (ms): device time of the ops whose innermost
named scope is ``lora`` (``scopes.py``: every LoRA kernel launch and the
XLA ops around it, gathers, pads and segment sums), in the traced
window, per step, mean over chips.  Its excess over
``lora_kernel_ms_per_step`` is the glue around the kernels."""
import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "lora")
