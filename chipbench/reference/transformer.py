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

As a model family of the benchmark (``chipbench/reference/layout.py``) it
lays out and counts layers of kind ``attn`` with an FFN of ``mlp``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import layout
from .common import F32, lm_loss, mm, rms_norm

#: The cut of the harness's whole runs on the CPU (tests/chipbench), over the
#: configuration's sizes.
TINY = {"n_layers": 2, "d_model": 32, "n_heads": 2, "n_kv_heads": 1, "head_dim_": 16, "d_ff": 64, "vocab": 128,
        "chunk": 8, "remat": "none"}
#: The cut of the control's test on the CPU.
SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 1, "head_dim_": 16, "d_ff": 128, "vocab": 256}


def _check(kind: str, ffn: str) -> None:
    if (kind, ffn) != ("attn", "mlp"):
        raise ValueError(f"no dense-decoder reference for a layer of kind {kind!r} with an FFN of {ffn!r}")


def attention_shapes(cfg: dict) -> dict:
    d, H = cfg["d_model"], cfg["n_heads"]
    hd, hkv = cfg.get("head_dim_") or d // H, cfg["n_kv_heads"]
    s = 1.0 / math.sqrt(d)
    return {"wq": ((d, H, hd), s), "wk": ((d, hkv, hd), s), "wv": ((d, hkv, hd), s),
            "wo": ((H, hd, d), 1.0 / math.sqrt(H * hd))}


def mlp_shapes(d: int, f: int) -> dict:
    s = 1.0 / math.sqrt(d)
    return {"wi": ((d, f), s), "wo": ((f, d), 1.0 / math.sqrt(f)), "wg": ((d, f), s)}


def layer_shapes(cfg: dict, kind: str, ffn: str) -> dict:
    """(shape, std) of every leaf of one layer."""
    _check(kind, ffn)
    d = cfg["d_model"]
    return {"norm1": layout.norm(d), "attn": attention_shapes(cfg), "norm2": layout.norm(d),
            "mlp": mlp_shapes(d, cfg["d_ff"])}


def _heads(cfg: dict) -> tuple[int, int, int]:
    d, H = cfg["d_model"], cfg["n_heads"]
    return H, cfg.get("n_kv_heads") or H, cfg.get("head_dim_") or d // H


def attention_params(cfg: dict) -> int:
    H, Hkv, hd = _heads(cfg)
    return cfg["d_model"] * H * hd * 2 + cfg["d_model"] * Hkv * hd * 2


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """q.k and p.v over the causal half: on average (S+1)/2 keys a token."""
    H, _, hd = _heads(cfg)
    return 2 * 2 * H * hd * (seq_len + 1) / 2


def layer_params(cfg: dict, kind: str, ffn: str) -> dict[str, int]:
    """Weights of one layer, split by the scope that uses them."""
    _check(kind, ffn)
    return {"attention": attention_params(cfg), "mlp": 3 * cfg["d_model"] * cfg["d_ff"]}


def forward_flops_per_token(cfg: dict, kind: str, ffn: str, seq_len: int) -> dict[str, float]:
    """Forward operations per token of one layer, by scope: 2 per weight, and
    attention's products of queries with keys and of weights with values."""
    out = {k: 2.0 * v for k, v in layer_params(cfg, kind, ffn).items()}
    out["attention"] += attention_flops_per_token(cfg, seq_len)
    return out


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


def block(p, x, cfg, lowp=None):
    """One pre-norm block: x (B, S, d) -> (B, S, d)."""
    a = x + attention(p["attn"], rms_norm(x, p["norm1"]["scale"]), cfg, lowp)
    return a + mlp(p["mlp"], rms_norm(a, p["norm2"]["scale"]), lowp)


def hidden(params, tokens, cfg, lowp=None):
    """Token ids (B, S) -> final-normed hidden states (B, S, d)."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    for kind, ffn, p in layout.stack_layers(params["layers"], cfg):
        _check(kind, ffn)
        x = jax.checkpoint(lambda p, x: block(p, x, cfg, lowp))(p, x)
    return rms_norm(x, params["final_norm"]["scale"])


def loss(params, batch, cfg, lowp=None):
    """The training loss of one batch: cross entropy plus the z-loss."""
    h = hidden(params, batch["tokens"], cfg, lowp)
    d = h.shape[-1]
    return lm_loss(
        h.reshape(-1, d), params["lm_head"]["w"], batch["labels"].reshape(-1),
        batch["loss_mask"].reshape(-1).astype(F32), lowp=lowp,
    )
