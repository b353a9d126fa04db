"""DeepSeek-V2's decoder in plain float32: latent attention (MLA) and an
expert layer that holds a share of the routed experts (arXiv:2405.04434
sections 2.1-2.2, and the model's published ``modeling_deepseek.py``).

One block, pre-norm: ``a = x + MLA(RMSNorm(x))``, then ``a + FFN(RMSNorm(a))``
with the FFN a SwiGLU MLP of ``dense_d_ff`` in the first ``first_dense``
layers and the expert layer in the others.

MLA, per token x, with dn = ``head_dim``, dr = ``qk_rope_dim``, dv =
``v_head_dim`` and a latent of r = ``kv_lora_rank``:

    c = RMSNorm(x W_dkv)                      the latent, shared by every head
    k_R = RoPE(x W_kr)                        one RoPE key of dr, shared too
    [k_C,h ; v_h] = c W_ukv,h                 per head, dn and dv wide
    q_h = x W_q,h = [q_C,h ; q_R,h], q_R,h rotated by RoPE
    o_h = softmax(q_h . [k_C,h ; k_R] * s + causal) v_h
    y = concat_h(o_h) W_o

RoPE is YaRN's (``DeepseekV2YarnRotaryEmbedding``): the frequencies
theta^(-2i/dr) are kept below the dimension floor(dr ln(L / (b_fast 2 pi)) /
(2 ln theta)), divided by the factor above ceil(dr ln(L / (b_slow 2 pi)) /
(2 ln theta)), and blended by a linear ramp in between (L the original
context).  cos and sin are scaled by mscale(factor, mscale) /
mscale(factor, mscale_all_dim), with mscale(f, m) = 0.1 m ln f + 1, and
s = mscale(factor, mscale_all_dim)^2 / sqrt(dn + dr).  The pairs are the
rotate-half pairs (first and second half of the dr dimensions); the
published code pairs neighbours, a fixed permutation of the columns of W_q's
RoPE part and of W_kr (listed under ``assumed`` in the configuration).

The expert layer: softmax router over all ``n_experts``, the top ``top_k``
of each token with their probabilities as weights (renormalised only where
``norm_topk_prob``), and the held experts [first, stop) of ``held_experts``
each computed as a SwiGLU MLP of ``moe_d_ff`` over every token, masked by
its weight.  The shared experts are one SwiGLU MLP of ``n_shared_experts *
moe_d_ff``.  What the experts held elsewhere give is left out, as the
program leaves it out.  The balance term E * sum_e frac_e * mean_prob_e
(frac_e the share of the slots routed to expert e, mean_prob_e the router's
mean probability of e) of every expert layer is added to the loss with
weight ``aux_loss_alpha``, taken within each sequence and averaged over them
where ``seq_aux`` (the published model's), else over the whole batch.

As a model family of the benchmark (``chipbench/reference/layout.py``) it
lays out and counts layers of kind ``mla`` with an FFN of ``dense_mlp`` or
``moe``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import layout, transformer
from .common import F32, lm_loss, mm, rms_norm

#: The cut of the harness's whole runs on the CPU (tests/chipbench): 4 of 32
#: experts held, as the cell holds 8 of 64.
TINY = {"n_layers": 3, "d_model": 32, "n_heads": 2, "n_kv_heads": 2, "head_dim_": 16, "qk_rope_dim": 8,
        "v_head_dim": 16, "kv_lora_rank": 16, "d_ff": 16, "moe_d_ff": 16, "dense_d_ff": 64, "vocab": 128,
        "n_experts": 32, "held_experts": [0, 4], "chunk": 8, "remat": "none"}
#: The cut of the control's test on the CPU.
SMALL = {"n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim_": 16, "qk_rope_dim": 16,
         "v_head_dim": 16, "kv_lora_rank": 32, "d_ff": 32, "moe_d_ff": 32, "dense_d_ff": 128, "vocab": 256,
         "n_experts": 32, "held_experts": [0, 4]}
def _check(kind: str, ffn: str) -> None:
    if kind != "mla" or ffn not in ("dense_mlp", "moe"):
        raise ValueError(f"no DeepSeek-V2 reference for a layer of kind {kind!r} with an FFN of {ffn!r}")


def _widths(cfg: dict) -> tuple[int, int, int, int, int, int]:
    """d, H, dn, dr, dv, r."""
    return (cfg["d_model"], cfg["n_heads"], cfg["head_dim_"], cfg["qk_rope_dim"], cfg["v_head_dim"],
            cfg["kv_lora_rank"])


def held(cfg: dict) -> tuple[int, int]:
    """[first, stop) of the routed experts held here."""
    return tuple(cfg.get("held_experts") or (0, cfg["n_experts"]))


def mla_shapes(cfg: dict) -> dict:
    d, H, dn, dr, dv, r = _widths(cfg)
    s = 1.0 / math.sqrt(d)
    return {"wq": ((d, H, dn + dr), s), "w_dkv": ((d, r), s), "w_kr": ((d, dr), s), "kv_norm": layout.norm(r),
            "w_ukv": ((r, H, dn + dv), 1.0 / math.sqrt(r)), "wo": ((H, dv, d), 1.0 / math.sqrt(H * dv))}


def moe_shapes(cfg: dict) -> dict:
    d, f = cfg["d_model"], cfg["moe_d_ff"]
    lo, hi = held(cfg)
    s = 1.0 / math.sqrt(d)
    return {"router": {"w": ((d, cfg["n_experts"]), s)}, "wi": ((hi - lo, d, f), s), "wg": ((hi - lo, d, f), s),
            "wo": ((hi - lo, f, d), 1.0 / math.sqrt(f)),
            "shared": transformer.mlp_shapes(d, cfg["n_shared_experts"] * f)}


def layer_shapes(cfg: dict, kind: str, ffn: str) -> dict:
    """(shape, std) of every leaf of one layer."""
    _check(kind, ffn)
    d = cfg["d_model"]
    out = {"norm1": layout.norm(d), "attn": mla_shapes(cfg), "norm2": layout.norm(d)}
    if ffn == "dense_mlp":
        out["mlp"] = transformer.mlp_shapes(d, cfg["dense_d_ff"])
    else:
        out["moe"] = moe_shapes(cfg)
    return out


def mla_params(cfg: dict) -> int:
    d, H, dn, dr, dv, r = _widths(cfg)
    return d * H * (dn + dr) + d * r + d * dr + r * H * (dn + dv) + H * dv * d


def experts_params(cfg: dict) -> int:
    """The held routed experts' weights of one layer."""
    lo, hi = held(cfg)
    return (hi - lo) * 3 * cfg["d_model"] * cfg["moe_d_ff"]


def layer_params(cfg: dict, kind: str, ffn: str) -> dict[str, int]:
    """Weights of one layer, split by the scope that uses them: every held weight."""
    _check(kind, ffn)
    d = cfg["d_model"]
    if ffn == "dense_mlp":
        return {"attention": mla_params(cfg), "mlp": 3 * d * cfg["dense_d_ff"]}
    shared = 3 * d * cfg["n_shared_experts"] * cfg["moe_d_ff"]
    return {"attention": mla_params(cfg), "moe": d * cfg["n_experts"] + shared + experts_params(cfg)}


def experts_used(cfg: dict) -> float:
    """Held experts a token uses on average: top_k of n_experts, the held share of them."""
    lo, hi = held(cfg)
    return cfg["top_k"] * (hi - lo) / cfg["n_experts"]


def forward_flops_per_token(cfg: dict, kind: str, ffn: str, seq_len: int) -> dict[str, float]:
    """Forward operations per token of one layer, by scope: 2 per weight a
    token uses (the router, the shared experts and the held experts at their
    expected use), and attention's q.k over dn + dr and p.v over dv, on the
    causal half."""
    _check(kind, ffn)
    d, H, dn, dr, dv, _ = _widths(cfg)
    out = {"attention": 2.0 * mla_params(cfg) + 2 * H * (dn + dr + dv) * (seq_len + 1) / 2}
    if ffn == "dense_mlp":
        out["mlp"] = 2.0 * 3 * d * cfg["dense_d_ff"]
    else:
        shared = 3 * d * cfg["n_shared_experts"] * cfg["moe_d_ff"]
        per_expert = 3 * d * cfg["moe_d_ff"]
        out["moe"] = 2.0 * (d * cfg["n_experts"] + shared + experts_used(cfg) * per_expert)
    return out


def experts_work_per_step(cfg: dict, batch: int, seq_len: int) -> tuple[float, float]:
    """(operations, least HBM bytes) of the held experts' grouped products in
    one training step, all expert layers: 3x the forward's 2 per weight for
    the rows routed to them (at their expected count); their weights read in
    bfloat16 by the forward and the backward pass and their float32 gradients
    written, and each routed row's bfloat16 input and output read or written
    once in each direction."""
    from chipbench.flops import layer_kinds

    n = sum(ffn == "moe" for _, ffn in layer_kinds(cfg))
    rows = batch * seq_len * experts_used(cfg)
    per_expert = 3 * cfg["d_model"] * cfg["moe_d_ff"]
    ops = 3.0 * 2 * per_expert * rows
    nbytes = experts_params(cfg) * (2 + 2 + 4) + 2 * 2 * 2 * cfg["d_model"] * rows
    return n * ops, n * nbytes


# -- the forward pass ------------------------------------------------------------


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_freqs(cfg: dict) -> jax.Array:
    """The dr/2 rotary frequencies, YaRN's where ``yarn_factor`` is set."""
    dr, theta = cfg["qk_rope_dim"], cfg.get("rope_theta", 10000.0)
    base = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr))
    factor = cfg.get("yarn_factor", 0.0)
    if not factor:
        return base
    L = cfg["yarn_original_len"]
    low = max(math.floor(dr * math.log(L / (cfg["yarn_beta_fast"] * 2 * math.pi)) / (2 * math.log(theta))), 0)
    high = min(math.ceil(dr * math.log(L / (cfg["yarn_beta_slow"] * 2 * math.pi)) / (2 * math.log(theta))), dr - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
    return (base / factor) * ramp + base * (1.0 - ramp)


def rope(x, cfg: dict):
    """x: (B, S, H, dr), rotated by position along S (rotate-half pairs)."""
    S = x.shape[1]
    ang = jnp.arange(S, dtype=F32)[:, None] * rope_freqs(cfg)  # (S, dr/2)
    factor = cfg.get("yarn_factor", 0.0)
    m = yarn_mscale(factor, cfg.get("yarn_mscale", 1.0)) / yarn_mscale(factor, cfg.get("yarn_mscale_all_dim", 0.0))
    cos, sin = (m * jnp.cos(ang))[None, :, None], (m * jnp.sin(ang))[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def score_scale(cfg: dict) -> float:
    t = yarn_mscale(cfg.get("yarn_factor", 0.0), cfg.get("yarn_mscale_all_dim", 0.0))
    return t * t / math.sqrt(cfg["head_dim_"] + cfg["qk_rope_dim"])


def mla(p, x, cfg, lowp=None, q_block: int = 256):
    """x (B, S, d) -> (B, S, d).  Attention runs over blocks of queries, each
    checkpointed, so no (S, S) score matrix of the whole sequence is held."""
    B, S, _ = x.shape
    _, H, dn, dr, dv, _ = _widths(cfg)
    q = mm("bsd,dhk->bshk", x, p["wq"], lowp=lowp)
    c = rms_norm(mm("bsd,dr->bsr", x, p["w_dkv"], lowp=lowp), p["kv_norm"]["scale"])
    k_r = rope(mm("bsd,dk->bsk", x, p["w_kr"], lowp=lowp)[:, :, None, :], cfg)
    kv = mm("bsr,rhk->bshk", c, p["w_ukv"], lowp=lowp)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], cfg)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (B, S, H, dr))], axis=-1)
    v = kv[..., dn:]
    scale = score_scale(cfg)
    qb = math.gcd(S, q_block)
    qs = jnp.moveaxis(q.reshape(B, S // qb, qb, H, dn + dr), 1, 0)

    @jax.checkpoint
    def one_block(args):
        i, q_i = args
        s = mm("bqhd,bkhd->bhqk", q_i, k, lowp=lowp) * scale
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm("bhqk,bkhd->bqhd", w, v, lowp=lowp)

    o = jax.lax.map(one_block, (jnp.arange(S // qb), qs))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, dv)
    return mm("bshk,hkd->bsd", o, p["wo"], lowp=lowp)


def by_rows(f, x, *more, rows: int = 2048):
    """``f`` over blocks of ``rows`` tokens, each block checkpointed, so the
    wide intermediates (an MLP's hidden units) exist for one block at a time:
    x (B, S, d) and ``more`` (B, S, ...) -> (B, S, d)."""
    B, S, d = x.shape
    rows = math.gcd(B * S, rows)
    blocks = [a.reshape(B * S // rows, 1, rows, *a.shape[2:]) for a in (x, *more)]
    return jax.lax.map(jax.checkpoint(lambda args: f(*args)), tuple(blocks)).reshape(B, S, d)


def moe(p, x, cfg, lowp=None):
    """The held share of the expert layer, and the shared experts: x (B, S, d)
    -> (y, each sequence's slots routed to each expert (B, E), the router's
    probabilities summed over each sequence's tokens (B, E)).  Each held expert runs on every token,
    masked by the weight the router gave it there (0 where it is not among
    the token's top k)."""
    E, K = cfg["n_experts"], cfg["top_k"]
    probs = jax.nn.softmax(mm("bsd,de->bse", x, p["router"]["w"], lowp=lowp), axis=-1)
    w, ids = jax.lax.top_k(probs, K)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    lo, hi = held(cfg)
    gates = jnp.stack([jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1) for e in range(lo, hi)], axis=-1)

    def experts(xb, gb):
        y = transformer.mlp(p["shared"], xb, lowp)
        for j in range(hi - lo):
            y = y + gb[..., j:j + 1] * transformer.mlp({k: p[k][j] for k in ("wi", "wg", "wo")}, xb, lowp)
        return y

    counts = jnp.sum(jax.nn.one_hot(ids, E, dtype=F32), axis=(1, 2))
    return by_rows(experts, x, gates), counts, jnp.sum(probs, axis=1)


def balance(counts, prob_sum, cfg: dict):
    """The balance term of a batch, from each sequence's routed slots (B, E)
    and summed router probabilities (B, E): E * sum_e frac_e * mean_prob_e,
    frac_e the share of the slots routed to expert e and mean_prob_e the
    router's mean probability of e, within each sequence and averaged over
    them where ``seq_aux``, else over the whole batch."""
    if not cfg.get("seq_aux", False):
        counts, prob_sum = jnp.sum(counts, 0, keepdims=True), jnp.sum(prob_sum, 0, keepdims=True)
    slots = jnp.sum(counts, -1, keepdims=True)
    return jnp.mean(cfg["n_experts"] * jnp.sum(counts / slots * prob_sum / (slots / cfg["top_k"]), -1))


def block(p, x, cfg, lowp=None):
    """One pre-norm block: x (B, S, d) -> (x, balance term).  The batch runs a
    sequence at a time, each checkpointed."""
    E = cfg["n_experts"]

    def one(xb):
        a = xb + mla(p["attn"], rms_norm(xb, p["norm1"]["scale"]), cfg, lowp)
        h = rms_norm(a, p["norm2"]["scale"])
        if "mlp" in p:
            return a + by_rows(lambda hb: transformer.mlp(p["mlp"], hb, lowp), h), jnp.zeros(E), jnp.zeros(E)
        y, counts, prob_sum = moe(p["moe"], h, cfg, lowp)
        return a + y, counts, prob_sum

    y, counts, prob_sum = jax.lax.map(jax.checkpoint(one), x[:, None])
    if "mlp" in p:
        return y[:, 0], jnp.zeros((), F32)
    return y[:, 0], balance(counts[:, 0], prob_sum[:, 0], cfg)


def loss(params, batch, cfg, lowp=None):
    """The training loss of one batch: cross entropy, the z-loss and the
    experts' balance terms.  The layers run in the program's order
    (``layout.stack``), each checkpointed; the repeats of the scanned blocks
    run under a scan, so their gradients go straight into the stacked leaves."""
    layers = params["layers"]
    step = jax.checkpoint(lambda p, x: block(p, x, cfg, lowp))
    entries = layout.stack(cfg)
    for e in entries:
        _check(e.kind, e.ffn)

    def run(group, x, total, ps=None):
        for e in entries:
            if e.group == group:
                x, b = step((ps or layers[group])[e.name], x)
                total = total + b
        return x, total

    x = jnp.take(params["embed"]["table"], batch["tokens"], axis=0)
    x, total = run("prefix", x, jnp.zeros((), F32))
    if "scan" in layers:
        (x, total), _ = jax.lax.scan(lambda c, ps: (run("scan", *c, ps), None), (x, total), layers["scan"])
    x, total = run("remainder", x, total)
    h = rms_norm(x, params["final_norm"]["scale"])
    d = h.shape[-1]
    return lm_loss(
        h.reshape(-1, d), params["lm_head"]["w"], batch["labels"].reshape(-1),
        batch["loss_mask"].reshape(-1).astype(F32), lowp=lowp,
    ) + cfg.get("aux_loss_alpha", 1e-2) * total
