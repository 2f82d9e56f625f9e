"""remat_ms_per_step (ms): device time of recomputed ops, any scope
(``scopes.is_remat``: ``jax.checkpoint``'s ``rematted_computation`` in
the op's path, or XLA's ``.remat`` suffix on its name), in the traced
window, per step, mean over chips."""
import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, lambda sc, o: sc.remat(o))
