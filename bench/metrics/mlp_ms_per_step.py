"""mlp_ms_per_step (ms): device time of the ops whose innermost named
scope is ``mlp`` (``scopes.py``: the FFN with its norm, its three GEMMs
and the activation, forward, recomputation and backward), in the
traced window, per step, mean over chips."""
import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "mlp")
