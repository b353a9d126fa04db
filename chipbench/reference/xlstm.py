"""Plain float32 xLSTM language model (arXiv:2405.04517).

sLSTM steps through time one token at a time, as the paper writes it, with
the paper's stabiliser state m (per head; gates from the input and from the
previous h through a block-diagonal recurrent matrix):

    m_t = max(log f_t + m_{t-1}, i~_t)
    i_t = exp(i~_t - m_t),  f_t = exp(log f_t + m_{t-1} - m_t)
    c_t = f_t c_{t-1} + i_t tanh(z~_t),  n_t = f_t n_{t-1} + i_t
    h_t = sigmoid(o~_t) * c_t / max(n_t, 1)

mLSTM (per head, matrix memory) in the paper's recurrent form:

    m_t = max(log f_t + m_{t-1}, i~_t)
    C_t = f_t C_{t-1} + i_t k_t v_t^T,  n_t = f_t n_{t-1} + i_t k_t
    h_t = C_t^T q_t / max(|n_t . q_t|, exp(-m_t))

and in its parallel form (the paper's appendix), which gives the same h:
with F_t = sum_{s<=t} log f_s and log D_ts = F_t - F_s + i~_s for s <= t,
m_t = max_s log D_ts,

    C~_ts = (q_t . k_s) exp(log D_ts - m_t)
    h_t = sum_s C~_ts v_s / max(|sum_s C~_ts|, exp(-m_t))

The runs use the parallel form, a few matrix products per block of queries
instead of a step through the sequence per token; :func:`mlstm_recurrent`
is kept as its witness.

It follows the repository's xLSTM blocks (``repro.models.xlstm``), which
are not the paper's published blocks.  Departures from the paper: log f =
log sigmoid(f~) (with a constant +1 on the mLSTM forget pre-activation,
which has no learned bias), i~ capped at 15, the sLSTM normaliser floored at
1 with m_0 = 0, q scaled by 1/sqrt(head size), no convolution, no
up-projection blocks and no MLP (d_ff = 0), RMSNorm in place of the group
norm, and heads of d_model / n_heads.

The sLSTM's backward pass goes through :func:`time_scan`, which checkpoints
blocks of time steps, so the whole sequence's states are never held at once;
the parallel mLSTM checkpoints each block of queries.

As a model family of the benchmark (``chipbench/reference/layout.py``) it
lays out and counts layers of kind ``slstm`` and ``mlstm``, with no FFN.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import layout
from .common import F32, lm_loss, mm, rms_norm, rounded

ICAP = 15.0

#: The cut of the harness's whole runs on the CPU (tests/chipbench), over the
#: configuration's sizes.
TINY = {"n_layers": 4, "d_model": 32, "n_heads": 2, "n_kv_heads": 2, "vocab": 128, "chunk": 8, "remat": "none"}
#: The cut of the control's test on the CPU.
SMALL = {"n_layers": 4, "d_model": 64, "n_heads": 2, "vocab": 256, "chunk": 16}


def _check(kind: str, ffn: str) -> None:
    if kind not in MIXERS or ffn != "none":
        raise ValueError(f"no xLSTM reference for a layer of kind {kind!r} with an FFN of {ffn!r}")


def layer_shapes(cfg: dict, kind: str, ffn: str) -> dict:
    """(shape, std) of every leaf of one layer."""
    _check(kind, ffn)
    d, H = cfg["d_model"], cfg["n_heads"]
    hd, s = d // H, 1.0 / math.sqrt(d)
    if kind == "mlstm":
        mixer = {
            "wq": ((d, H, hd), s), "wk": ((d, H, hd), s), "wv": ((d, H, hd), s),
            "wi": ((d, H), s), "wf": ((d, H), s),
            "wo_gate": ((d, d), s), "out_norm": layout.norm(d), "wo": ((d, d), s),
        }
    else:
        mixer = {
            "wx": ((d, 4, H, hd), s), "r": ((4, H, hd, hd), 1.0 / math.sqrt(hd)),
            "b": ((4, H, hd), 0.1), "out_norm": layout.norm(d), "wo": ((d, d), s),
        }
    return {"norm1": layout.norm(d), kind: mixer}


def layer_params(cfg: dict, kind: str, ffn: str) -> dict[str, int]:
    """Weights of one layer, under the scope of its mixer."""
    _check(kind, ffn)
    d, H = cfg["d_model"], cfg["n_heads"]
    hd = d // H
    if kind == "mlstm":
        return {"mlstm": 3 * d * H * hd + 2 * d * H + 2 * d * d}
    return {"slstm": 4 * d * d + 4 * H * hd * hd + d * d}


def forward_flops_per_token(cfg: dict, kind: str, ffn: str, seq_len: int) -> dict[str, float]:
    """Forward operations per token of one layer: 2 per weight; the mLSTM's
    recurrent form per head adds k v^T into the memory and q^T C out (2 hd^2
    each); the sLSTM's recurrent matrix is among its weights."""
    out = {k: 2.0 * v for k, v in layer_params(cfg, kind, ffn).items()}
    if kind == "mlstm":
        H = cfg["n_heads"]
        hd = cfg["d_model"] // H
        out["mlstm"] += 2 * 2 * H * hd * hd
    return out


def time_scan(step, carry, xs, block: int = 64):
    """``lax.scan`` over the leading (time) axis, checkpointed per block."""
    S = jax.tree.leaves(xs)[0].shape[0]
    block = math.gcd(S, block)
    xb = jax.tree.map(lambda a: a.reshape(S // block, block, *a.shape[1:]), xs)

    @jax.checkpoint
    def inner(c, x):
        return jax.lax.scan(step, c, x, unroll=4)

    carry, ys = jax.lax.scan(inner, carry, xb)
    return carry, jax.tree.map(lambda a: a.reshape(S, *a.shape[2:]), ys)


def slstm(p, x, cfg, lowp=None):
    """x: (B, S, d), already normed -> (B, S, d)."""
    B, S, d = x.shape
    H = cfg["n_heads"]
    hd = d // H
    gx = jnp.moveaxis(mm("bsd,dghk->bsghk", x, p["wx"], lowp=lowp), 1, 0)  # (S, B, 4, H, hd)

    def step(carry, g_t):
        h, c, n, m = carry
        g = g_t + mm("bhk,ghkl->bghl", h, p["r"], lowp=lowp) + p["b"]
        gi, gf, gz, go = (g[:, j] for j in range(4))
        log_f = jax.nn.log_sigmoid(gf)
        it = jnp.minimum(gi, ICAP)
        m_new = jnp.maximum(log_f + m, it)
        i_p = jnp.exp(it - m_new)
        f_p = jnp.exp(log_f + m - m_new)
        c = f_p * c + i_p * jnp.tanh(gz)
        n = f_p * n + i_p
        h = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1.0)
        return (h, c, n, m_new), h

    zero = jnp.zeros((B, H, hd), F32)
    _, hs = time_scan(step, (zero, zero, zero, zero), gx)
    y = rms_norm(jnp.moveaxis(hs, 0, 1).reshape(B, S, d), p["out_norm"]["scale"])
    return mm("bsd,de->bse", y, p["wo"], lowp=lowp)


def _mlstm_in(p, x, cfg, lowp):
    """q (scaled), k, v: (B, S, H, hd); log f, i~: (B, S, H)."""
    H = cfg["n_heads"]
    hd = x.shape[-1] // H
    q = mm("bsd,dhk->bshk", x, p["wq"], lowp=lowp) / math.sqrt(hd)
    k = mm("bsd,dhk->bshk", x, p["wk"], lowp=lowp)
    v = mm("bsd,dhk->bshk", x, p["wv"], lowp=lowp)
    log_f = jax.nn.log_sigmoid(mm("bsd,dh->bsh", x, p["wf"], lowp=lowp) + 1.0)
    log_i = jnp.minimum(mm("bsd,dh->bsh", x, p["wi"], lowp=lowp), ICAP)
    return q, k, v, log_f, log_i


def _mlstm_out(p, h, x, lowp):
    og = jax.nn.sigmoid(mm("bsd,de->bse", x, p["wo_gate"], lowp=lowp))
    h = rms_norm(h, p["out_norm"]["scale"]) * og
    return mm("bsd,de->bse", h, p["wo"], lowp=lowp)


def mlstm(p, x, cfg, lowp=None, q_block: int = 512):
    """The parallel form.  x: (B, S, d), already normed -> (B, S, d)."""
    B, S, d = x.shape
    q, k, v, log_f, log_i = _mlstm_in(p, x, cfg, lowp)
    F = jnp.cumsum(log_f, axis=1)  # (B, S, H)
    qb = math.gcd(S, q_block)
    blocks = jax.tree.map(lambda a: jnp.moveaxis(a.reshape(B, S // qb, qb, *a.shape[2:]), 1, 0), (q, F))

    @jax.checkpoint
    def one_block(args):
        i, (q_i, F_i) = args
        rows = i * qb + jnp.arange(qb)
        log_d = F_i[:, :, None, :] - F[:, None, :, :] + log_i[:, None, :, :]  # (B, qb, S, H)
        log_d = jnp.where((rows[:, None] >= jnp.arange(S)[None, :])[None, :, :, None], log_d, -jnp.inf)
        m = jax.lax.stop_gradient(jnp.max(log_d, axis=2))  # (B, qb, H); h does not depend on it
        c = mm("bqhk,bshk->bqsh", q_i, k, lowp=lowp) * jnp.exp(log_d - m[:, :, None, :])
        den = jnp.maximum(jnp.abs(jnp.sum(c, axis=2)), jnp.exp(-m))
        return mm("bqsh,bshk->bqhk", c, v, lowp=lowp) / den[..., None]

    h = jax.lax.map(one_block, (jnp.arange(S // qb), blocks))
    h = jnp.moveaxis(h, 0, 1).reshape(B, S, d)
    return _mlstm_out(p, h, x, lowp)


def mlstm_recurrent(p, x, cfg, lowp=None):
    """The recurrent form, one token at a time.  x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    H = cfg["n_heads"]
    hd = d // H
    q, k, v, log_f, log_i = _mlstm_in(p, x, cfg, lowp)

    def step(carry, inp):
        C, n, m = carry
        q_t, k_t, v_t, lf, li = inp
        m_new = jnp.maximum(lf + m, li)
        f_p = jnp.exp(lf + m - m_new)
        i_p = jnp.exp(li - m_new)
        kr, vr = rounded(k_t, lowp), rounded(v_t, lowp)
        C = f_p[..., None, None] * C + i_p[..., None, None] * (kr[..., :, None] * vr[..., None, :])
        n = f_p[..., None] * n + i_p[..., None] * k_t
        num = jnp.sum(rounded(q_t, lowp)[..., :, None] * rounded(C, lowp), axis=-2)
        den = jnp.abs(jnp.sum(q_t * n, axis=-1))
        h = num / jnp.maximum(den, jnp.exp(-m_new))[..., None]
        return (C, n, m_new), h

    tm = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    carry = (jnp.zeros((B, H, hd, hd), F32), jnp.zeros((B, H, hd), F32), jnp.zeros((B, H), F32))
    _, hs = time_scan(step, carry, (tm(q), tm(k), tm(v), tm(log_f), tm(log_i)))
    return _mlstm_out(p, jnp.moveaxis(hs, 0, 1).reshape(B, S, d), x, lowp)


MIXERS = {"slstm": slstm, "mlstm": mlstm}


def hidden(params, tokens, cfg, lowp=None):
    """Token ids (B, S) -> final-normed hidden states (B, S, d)."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    for kind, ffn, p in layout.stack_layers(params["layers"], cfg):
        _check(kind, ffn)

        @jax.checkpoint
        def block(p, x, kind=kind):
            return x + MIXERS[kind](p[kind], rms_norm(x, p["norm1"]["scale"]), cfg, lowp)

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
