"""Plain float32 reference of a dense GQA decoder trained with per-job
LoRA adapters.

Written from the published architecture, not from the program: pre-norm
blocks of RMSNorm, grouped-query attention with rotary embeddings
(rotate-half form, theta from the configuration) and optional QKV bias,
then RMSNorm and a SwiGLU MLP; a final RMSNorm and an untied head over
the (sliced) vocabulary.  A job's adapter adds ``alpha / r * (x A) B``
to each of the q, k, v and o projections.  The loss is next-token cross
entropy over the positions a row's ``loss_mask`` keeps, each job's mean
taken over its own tokens; AdamW (``reference/adamw.py``) then steps
each job with its own step count.

Every matmul runs at float32 "highest" precision.  Weights arrive in
the type they are served in and are widened to float32 exactly.  A call
handles one row and one layer, so the reference fits on the chip at any
batch and depth.

``fp8=True`` is the control: every matmul's operands (projections,
adapters, attention, head) are rounded to float8 e4m3 with a per-tensor
power-of-two scale before the product, the precision step below the
configurations' bfloat16 that would tempt a later change.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x: (S, heads, hd); rotate-half rotary embedding at positions 0.."""
    S, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


E4M3_MAX = 448.0


def to_fp8(x):
    """Round to float8 e4m3 under a per-tensor power-of-two scale that
    puts the largest magnitude just under the format's maximum.  The
    gradient passes straight through."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = jax.lax.stop_gradient(2.0 ** jnp.floor(jnp.log2(E4M3_MAX / amax)))
    q = (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s
    return x + jax.lax.stop_gradient(q - x)


def matmul(fp8: bool):
    if not fp8:
        return lambda a, b: a @ b
    return lambda a, b: to_fp8(a) @ to_fp8(b)


def lora(mm, x, ab, scale):
    return scale * mm(mm(x, ab["A"]), ab["B"])


def block(m: Dict, w: Dict, ad: Dict, x, scale, fp8: bool = False):
    """One decoder layer on one row x: (S, d)."""
    S, d = x.shape
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim", d // H)
    f = lambda n: w[n].astype(F32)
    mm = matmul(fp8)
    r8 = to_fp8 if fp8 else (lambda t: t)
    h = rms_norm(x, 1.0 + f("ln1"), m["rms_norm_eps"])
    q = mm(h, f("wq")) + lora(mm, h, ad["q"], scale)
    k = mm(h, f("wk")) + lora(mm, h, ad["k"], scale)
    v = mm(h, f("wv")) + lora(mm, h, ad["v"], scale)
    if m["qkv_bias"]:
        q, k, v = q + f("bq"), k + f("bk"), v + f("bv")
    q = rope(q.reshape(S, H, hd), m["rope_theta"])
    k = rope(k.reshape(S, KV, hd), m["rope_theta"])
    v = v.reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", r8(q), r8(k)) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", r8(p), r8(v)).reshape(S, H * hd)
    x = x + mm(a, f("wo")) + lora(mm, a, ad["o"], scale)
    h = rms_norm(x, 1.0 + f("ln2"), m["rms_norm_eps"])
    return x + mm(jax.nn.silu(mm(h, f("gate"))) * mm(h, f("up")), f("down"))


def head_loss(m: Dict, ln_f, head, x, tokens, mask, fp8: bool = False
              ) -> Tuple[jax.Array, jax.Array]:
    """(summed loss, token count) of one row from the last layer's
    output x: position t predicts token t+1 where ``mask[t+1]`` is set."""
    x = rms_norm(x, 1.0 + ln_f.astype(F32), m["rms_norm_eps"])
    logits = matmul(fp8)(x[:-1], head.astype(F32))
    tgt, keep = tokens[1:], mask[1:].astype(F32)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, tgt[:, None], -1)[:, 0]
    return jnp.sum(nll * keep), jnp.sum(keep)


def row_loss_and_grad(m: Dict, fp8: bool = False):
    """(top, layers, ads, tokens, mask, scale) -> (loss sum, count,
    d loss sum / d ads), one layer at a time: a forward pass keeps each
    layer's input, then each layer's vector-Jacobian product (its
    forward computed again) carries the gradient back.  One compiled
    program per layer's pass serves every layer, and only one layer's
    weights are widened to float32 at a time."""
    def hi(f):
        def g(*a):
            with jax.default_matmul_precision("highest"):
                return f(*a)
        return jax.jit(g)

    embed = hi(lambda e, tokens: e[tokens].astype(F32))
    fwd = hi(lambda w, ad, x, scale: block(m, w, ad, x, scale, fp8))
    bwd = hi(lambda w, ad, x, scale, gy: jax.vjp(
        lambda x_, a_: block(m, w, a_, x_, scale, fp8), x, ad)[1](gy))
    head = hi(lambda ln_f, h, x, tokens, mask: jax.value_and_grad(
        lambda x_: head_loss(m, ln_f, h, x_, tokens, mask, fp8),
        has_aux=True)(x))

    def f(top, layers, ads, tokens, mask, scale):
        xs = [embed(top["embed"], tokens)]
        for w, ad in zip(layers, ads):
            xs.append(fwd(w, ad, xs[-1], scale))
        (loss, count), gx = head(top["ln_f"], top["head"], xs.pop(),
                                 tokens, mask)
        grads = [None] * len(layers)
        for l in reversed(range(len(layers))):
            gx, grads[l] = bwd(layers[l], ads[l], xs[l], scale, gx)
        return loss, count, grads
    return f

