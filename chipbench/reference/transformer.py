"""Plain float32 pre-norm decoder with grouped-query attention and RoPE.

One block:

    a = x + Attn(RMSNorm(x)),  Attn = softmax(q k^T / sqrt(hd) + causal) v W_o
    y = a + W_down (silu(W_gate h) * (W_up h)),  h = RMSNorm(a)

q and k are rotated by RoPE in the rotate-half form (the first and second
halves of each head pair up), with frequencies theta^(-2i/hd).  Query head j
reads key/value head j // (n_heads / n_kv_heads).

Departures of the configuration from its source are listed under ``assumed``
in its file (no embedding, attention or residual multipliers, no logits
scaling, untied embeddings).  Attention runs over blocks of queries, each
checkpointed, so no (S, S) score matrix of the whole sequence is held.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, lm_loss, mm, rms_norm


def rope(x, theta: float):
    """x: (B, S, H, hd), rotated by position along S."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, x, cfg, lowp=None, q_block: int = 512):
    B, S, d = x.shape
    H, Hkv = cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg.get("head_dim_") or d // H
    theta = cfg.get("rope_theta", 10000.0)
    q = rope(mm("bsd,dhk->bshk", x, p["wq"], lowp=lowp), theta)
    k = rope(mm("bsd,dhk->bshk", x, p["wk"], lowp=lowp), theta)
    v = mm("bsd,dhk->bshk", x, p["wv"], lowp=lowp)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    qb = math.gcd(S, q_block)
    qs = jnp.moveaxis(q.reshape(B, S // qb, qb, H, hd), 1, 0)

    @jax.checkpoint
    def one_block(args):
        i, q_i = args
        s = mm("bqhd,bkhd->bhqk", q_i, k, lowp=lowp) / math.sqrt(hd)
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm("bhqk,bkhd->bqhd", w, v, lowp=lowp)

    o = jax.lax.map(one_block, (jnp.arange(S // qb), qs))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, hd)
    return mm("bshk,hkd->bsd", o, p["wo"], lowp=lowp)


def mlp(p, x, lowp=None):
    g = mm("bsd,df->bsf", x, p["wg"], lowp=lowp)
    u = mm("bsd,df->bsf", x, p["wi"], lowp=lowp)
    return mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["wo"], lowp=lowp)


def hidden(params, tokens, cfg, lowp=None):
    """Token ids (B, S) -> final-normed hidden states (B, S, d)."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    if list(cfg["pattern"]) != ["attn"]:
        raise ValueError("this reference is for a stack of attention blocks")
    for u in range(cfg["n_layers"]):
        p = jax.tree.map(lambda a: a[u], params["layers"]["scan"]["block0"])  # noqa: B023

        @jax.checkpoint
        def block(p, x):
            a = x + attention(p["attn"], rms_norm(x, p["norm1"]["scale"]), cfg, lowp)
            return a + mlp(p["mlp"], rms_norm(a, p["norm2"]["scale"]), lowp)

        x = block(p, x)
    return rms_norm(x, params["final_norm"]["scale"])


def loss(params, batch, cfg, lowp=None):
    """The training loss of one batch: cross entropy plus the z-loss."""
    h = hidden(params, batch["tokens"], cfg, lowp)
    d = h.shape[-1]
    return lm_loss(
        h.reshape(-1, d), params["lm_head"]["w"], batch["labels"].reshape(-1),
        batch["loss_mask"].reshape(-1).astype(F32), lowp=lowp,
    )
