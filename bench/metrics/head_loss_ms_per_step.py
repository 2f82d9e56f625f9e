"""head_loss_ms_per_step (ms): device time of the ops whose innermost
named scope is ``head_loss`` (``scopes.py``: the final norm, the
vocabulary projection, cross-entropy and the per-job reduction, forward
and backward), in the traced window, per step, mean over chips."""
import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "head_loss")
