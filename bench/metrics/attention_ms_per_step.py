"""attention_ms_per_step (ms): device time of the ops whose innermost
named scope is ``attention`` (``scopes.py``: the attention mixer with
its norm, backbone q/k/v/o projections, rope and the attention core,
forward, recomputation and backward; LoRA launches inside it are
``lora``), in the traced window, per step, mean over chips."""
import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "attention")
