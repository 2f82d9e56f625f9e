"""Plain AdamW, the optimizer every family's reference steps with: b1
0.9, b2 0.999, eps 1e-8, no weight decay, each job with its own step
count."""
import jax.numpy as jnp


def adamw(p, g, mu, nu, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One AdamW step (no weight decay) at 1-based *step*."""
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    mhat = mu / (1 - b1 ** step)
    vhat = nu / (1 - b2 ** step)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps), mu, nu
