"""Plain float32 pieces that every reference model shares.

Nothing here imports the program.  The references compute in float32 with
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matrix product
otherwise runs in one bfloat16 pass).  ``lowp``, where given, is the dtype the
*control* rounds every matrix-product operand to before the product: the same
reference, one precision step below what the configuration states.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: AdamW as the configurations state it (the program's defaults, written out).
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}
#: Weight of the z-loss term, 1e-4 * mean(logsumexp(logits)**2).
Z_LOSS = 1e-4
RMS_EPS = 1e-6


#: The control's rounding: operands of the forward products in ``lowp``,
#: the cotangents that reach a product in the backward pass in this.
LOWP_GRAD = {"float8_e4m3fn": "float8_e5m2"}


def quantize(x, dtype):
    """Round ``x`` to ``dtype`` with one scale for the whole tensor (its largest
    magnitude onto the largest finite value), and back to float32."""
    x = x.astype(F32)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * s).astype(dtype).astype(F32) / s


def rounded(x, lowp):
    """``x`` rounded to ``lowp`` in the forward pass; its cotangent passes
    unchanged (the backward products round their own inputs)."""
    if lowp is None:
        return x.astype(F32)
    x = x.astype(F32)
    return x + jax.lax.stop_gradient(quantize(x, lowp) - x)


@jax.custom_vjp
def _round_cotangent_e5m2(y):
    return y


_round_cotangent_e5m2.defvjp(lambda y: (y, None), lambda _, g: (quantize(g, jnp.float8_e5m2),))


def mm(spec: str, *ops, lowp=None):
    """einsum in float32 at the highest precision.

    With ``lowp`` (the control) every operand is rounded to it first, and the
    cotangent of the result to ``LOWP_GRAD[lowp]``: a per-tensor-scaled
    low-precision product in both passes.
    """
    out = jnp.einsum(spec, *[rounded(o, lowp) for o in ops], precision="highest")
    if lowp is not None:
        assert jnp.dtype(lowp).name in LOWP_GRAD, lowp
        out = _round_cotangent_e5m2(out)
    return out


def rms_norm(x, scale):
    """RMSNorm with the scale stored as an offset from 1: x / rms(x) * (1 + scale)."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS) * (1.0 + scale)


def lm_loss(h, head_w, labels, mask, *, lowp=None, rows: int = 1024):
    """Causal-LM loss from the last hidden states, in blocks of ``rows`` tokens.

    h: (N, d) after the final norm; head_w: (d, V); labels, mask: (N,).
    Returns cross entropy + Z_LOSS * mean(lse**2), both averaged over the
    mask.  Each block is checkpointed, so the (rows, V) logits exist for one
    block at a time, in the forward and the backward pass alike.
    """
    n = h.shape[0]
    rows = min(rows, n)
    assert n % rows == 0, (n, rows)
    blocks = (h.reshape(n // rows, rows, -1), labels.reshape(n // rows, rows), mask.reshape(n // rows, rows))

    @jax.checkpoint
    def block(args):
        hb, lb, mb = args
        logits = mm("nd,dv->nv", hb, head_w, lowp=lowp)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - picked) * mb), jnp.sum(lse * lse * mb)

    nll, z = jax.lax.map(block, blocks)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll) / denom + Z_LOSS * jnp.sum(z) / denom


def learning_rate(step, peak: float, warmup: int, total: int, final_frac: float = 0.1):
    """Linear warm-up from 0 to ``peak`` over ``warmup`` steps, then a cosine
    decay to ``final_frac * peak`` at ``total``.  ``step`` counts updates
    already made (0 for the first)."""
    step = float(step)
    if step < warmup:
        return peak * step / max(warmup, 1)
    t = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return peak * (final_frac + (1 - final_frac) * 0.5 * (1 + math.cos(math.pi * t)))


def adamw_update(params, grads, m, v, t: int, lr: float):
    """One AdamW update with global-norm clipping; ``t`` is 1 for the first.

    Returns (params, m, v, clipped grads): the gradients as the moments take
    them, after clipping.
    """
    c = ADAMW
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, c["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, x: c["b1"] * a + (1 - c["b1"]) * x, m, g)
    v = jax.tree.map(lambda a, x: c["b2"] * a + (1 - c["b2"]) * x * x, v, g)
    bc1, bc2 = 1 - c["b1"] ** t, 1 - c["b2"] ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + c["eps"]) + c["weight_decay"] * p),
        params, m, v,
    )
    return params, m, v, g


def leaf_norms(tree) -> jax.Array:
    """Float32 Frobenius norm of every leaf, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))) for x in jax.tree.leaves(tree)])


def normal(key, shape, std):
    return jax.random.normal(key, shape, F32) * std
