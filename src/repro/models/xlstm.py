"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory, parallelizable) and
sLSTM (scalar memory, sequential) with exponential gating.

mLSTM cell (per head, head dims dk = dv = d):

    C_t = f_t * C_{t-1} + i_t * v_t k_t^T        (matrix memory)
    n_t = f_t * n_{t-1} + i_t * k_t              (normalizer)
    h_t = (C_t q_t) / max(|n_t . q_t|, 1)

Training uses a **chunkwise-parallel** formulation: within a chunk the
contribution is an attention-like masked product with gate-decay weights; the
chunk boundary state (C, n) carries across chunks via ``lax.scan``. Gate
exponents run in fp32 with log-sigmoid forget gates (log f <= 0) and a
soft cap on the input-gate exponent instead of the paper's running-max
stabilizer — equivalent at smoke scale, simpler to tile (documented in
DESIGN.md). Decode is the O(1) recurrence above.

sLSTM is inherently sequential (h feeds back into the gates), so training
runs ``lax.scan`` over time — the compiled while-loop's trip count is
attributed by the device-plane tree exactly like Ruby's event loop in the
paper.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .modules import ArraySpec, rms_norm, rms_norm_spec

_ICAP = 15.0  # soft cap on input-gate exponent (fp32-safe)


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------


def mlstm_spec(cfg) -> dict:
    d, H = cfg.d_model, cfg.n_heads
    hd = d // H
    return {
        "wq": ArraySpec((d, H, hd), ("embed", "q_heads", "head")),
        "wk": ArraySpec((d, H, hd), ("embed", "q_heads", "head")),
        "wv": ArraySpec((d, H, hd), ("embed", "q_heads", "head")),
        "wi": ArraySpec((d, H), ("embed", "q_heads")),
        "wf": ArraySpec((d, H), ("embed", "q_heads")),
        "wo_gate": ArraySpec((d, d), ("embed", "embed_out")),
        "out_norm": rms_norm_spec(d),
        "wo": ArraySpec((d, d), ("embed", "embed_out")),
    }


def _mlstm_gates(params, x):
    xf = x.astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(jnp.einsum("bsd,dh->bsh", xf, params["wf"]) + 1.0)
    log_i = jnp.minimum(jnp.einsum("bsd,dh->bsh", xf, params["wi"]), _ICAP)
    return log_i, log_f


def mlstm(params, x, cfg, *, state=None, scope: str = "mlstm"):
    """Chunkwise-parallel mLSTM. x: (B,S,D) -> (B,S,D), new state."""
    B, S, D = x.shape
    H = cfg.n_heads
    hd = D // H
    L = min(cfg.chunk, S)
    n_chunks = (S + L - 1) // L
    assert S % L == 0, f"seq {S} must be divisible by chunk {L}"
    scale = 1.0 / math.sqrt(hd)
    with jax.named_scope(scope):
        with jax.named_scope("qkv_proj"):
            q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype)) * scale
            k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
            v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
        log_i, log_f = _mlstm_gates(params, x)

        if state is None:
            C0 = jnp.zeros((B, H, hd, hd), jnp.float32)
            n0 = jnp.zeros((B, H, hd), jnp.float32)
        else:
            C0, n0 = state["C"], state["n"]

        # (n_chunks, B, L, ...) for scan
        def chunked(t):
            return jnp.moveaxis(t.reshape(B, n_chunks, L, *t.shape[2:]), 1, 0)

        qc, kc, vc = chunked(q.astype(jnp.float32)), chunked(k.astype(jnp.float32)), chunked(v.astype(jnp.float32))
        lic, lfc = chunked(log_i), chunked(log_f)

        def body(carry, args):
            C, n = carry
            qb, kb, vb, li, lf = args  # (B,L,H,k) / gates (B,L,H)
            cumf = jnp.cumsum(lf, axis=1)  # (B,L,H)
            with jax.named_scope("intra"):
                # w_ij = exp(cumf_i - cumf_j + li_j) for j <= i
                Eij = cumf[:, :, None] - cumf[:, None, :] + li[:, None, :]  # (B,L,L,H)
                mask = jnp.tril(jnp.ones((L, L), bool))
                # Mask before exp: above the diagonal Eij grows with the
                # chunk and overflows, and the inf would turn the masked-out
                # gradient (0 * inf) into NaN.
                w = jnp.exp(jnp.where(mask[None, :, :, None], Eij, -jnp.inf))
                s = jnp.einsum("blhk,bmhk->blmh", qb, kb) * w
                num_intra = jnp.einsum("blmh,bmhk->blhk", s, vb)
                den_vec = jnp.einsum("blmh,bmhk->blhk", w, kb)
                den_intra = jnp.einsum("blhk,blhk->blh", qb, den_vec)
            with jax.named_scope("inter"):
                decay = jnp.exp(cumf)  # (B,L,H)
                num_inter = jnp.einsum("blhk,bhkv->blhv", qb, C) * decay[..., None]
                den_inter = jnp.einsum("blhk,bhk->blh", qb, n) * decay
            with jax.named_scope("normalize"):
                den = jnp.abs(den_intra + den_inter)
                h = (num_intra + num_inter) / jnp.maximum(den, 1.0)[..., None]
            with jax.named_scope("state_update"):
                decay_end = jnp.exp(cumf[:, -1])  # (B,H)
                wj = jnp.exp(cumf[:, -1:, :] - cumf + li)  # (B,L,H)
                C_new = decay_end[..., None, None] * C + jnp.einsum("blh,blhk,blhv->bhkv", wj, kb, vb)
                n_new = decay_end[..., None] * n + jnp.einsum("blh,blhk->bhk", wj, kb)
            return (C_new, n_new), h

        # checkpoint: the (B,L,L,H) intra-chunk weights must not be saved per
        # chunk for backward (profiler-identified memory term, §Perf).
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable, prevent_cse=False)
        with jax.named_scope("chunk_scan"):
            (C_f, n_f), h = jax.lax.scan(body, (C0, n0), (qc, kc, vc, lic, lfc))
        h = jnp.moveaxis(h, 0, 1).reshape(B, S, D).astype(x.dtype)
        with jax.named_scope("out"):
            og = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, params["wo_gate"].astype(x.dtype)))
            h = rms_norm(params["out_norm"], h, scope="out_norm") * og
            y = jnp.einsum("bsd,de->bse", h, params["wo"].astype(x.dtype))
        return y, {"C": C_f, "n": n_f}


def mlstm_step(params, x_t, state, cfg, *, scope: str = "mlstm"):
    """O(1) decode step. x_t: (B,1,D)."""
    B, _, D = x_t.shape
    H = cfg.n_heads
    hd = D // H
    scale = 1.0 / math.sqrt(hd)
    with jax.named_scope(scope):
        q = jnp.einsum("bsd,dhk->bshk", x_t, params["wq"].astype(x_t.dtype))[:, 0].astype(jnp.float32) * scale
        k = jnp.einsum("bsd,dhk->bshk", x_t, params["wk"].astype(x_t.dtype))[:, 0].astype(jnp.float32)
        v = jnp.einsum("bsd,dhk->bshk", x_t, params["wv"].astype(x_t.dtype))[:, 0].astype(jnp.float32)
        log_i, log_f = _mlstm_gates(params, x_t)
        i_t, f_t = jnp.exp(log_i[:, 0]), jnp.exp(log_f[:, 0])  # (B,H)
        C = f_t[..., None, None] * state["C"] + i_t[..., None, None] * jnp.einsum("bhk,bhv->bhkv", k, v)
        n = f_t[..., None] * state["n"] + i_t[..., None] * k
        num = jnp.einsum("bhkv,bhk->bhv", C, q)
        den = jnp.abs(jnp.einsum("bhk,bhk->bh", n, q))
        h = (num / jnp.maximum(den, 1.0)[..., None]).reshape(B, 1, D).astype(x_t.dtype)
        og = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x_t, params["wo_gate"].astype(x_t.dtype)))
        h = rms_norm(params["out_norm"], h, scope="out_norm") * og
        y = jnp.einsum("bsd,de->bse", h, params["wo"].astype(x_t.dtype))
        return y, {"C": C, "n": n}


def init_mlstm_state(cfg, batch: int) -> dict:
    hd = cfg.d_model // cfg.n_heads
    return {
        "C": jnp.zeros((batch, cfg.n_heads, hd, hd), jnp.float32),
        "n": jnp.zeros((batch, cfg.n_heads, hd), jnp.float32),
    }


def abstract_mlstm_state(cfg, batch: int) -> dict:
    hd = cfg.d_model // cfg.n_heads
    return {
        "C": jax.ShapeDtypeStruct((batch, cfg.n_heads, hd, hd), jnp.float32),
        "n": jax.ShapeDtypeStruct((batch, cfg.n_heads, hd), jnp.float32),
    }


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------


def slstm_spec(cfg) -> dict:
    d, H = cfg.d_model, cfg.n_heads
    hd = d // H
    return {
        # input projections for 4 gates (i, f, z, o)
        "wx": ArraySpec((d, 4, H, hd), ("embed", None, "q_heads", "head")),
        # per-head recurrent (block-diagonal) projections
        "r": ArraySpec((4, H, hd, hd), (None, "q_heads", "head", "head_out"), jnp.float32, "normal", 0.02),
        "b": ArraySpec((4, H, hd), (None, "q_heads", "head"), jnp.float32, "zeros"),
        "out_norm": rms_norm_spec(d),
        "wo": ArraySpec((d, d), ("embed", "embed_out")),
    }


def slstm(params, x, cfg, *, state=None, scope: str = "slstm"):
    """Sequential sLSTM over time (lax.scan). x: (B,S,D)."""
    B, S, D = x.shape
    H = cfg.n_heads
    hd = D // H
    with jax.named_scope(scope):
        with jax.named_scope("in_proj"):
            gx = jnp.einsum("bsd,dghk->bsghk", x.astype(jnp.float32), params["wx"].astype(jnp.float32))
        if state is None:
            state = init_slstm_state_arrays(B, H, hd)
        h0, c0, n0, m0 = state["h"], state["c"], state["n"], state["m"]
        gx_t = jnp.moveaxis(gx, 1, 0)  # (S,B,4,H,hd)

        def step(carry, g_t):
            h, c, n, m = carry
            rec = jnp.einsum("bhk,ghkl->bghl", h, params["r"]) + params["b"]
            gi, gf, gz, go = [(g_t[:, j] + rec[:, j]) for j in range(4)]
            log_f = jax.nn.log_sigmoid(gf)
            m_new = jnp.maximum(log_f + m, jnp.minimum(gi, _ICAP))
            i_p = jnp.exp(jnp.minimum(gi, _ICAP) - m_new)
            f_p = jnp.exp(log_f + m - m_new)
            z = jnp.tanh(gz)
            o = jax.nn.sigmoid(go)
            c_new = f_p * c + i_p * z
            n_new = f_p * n + i_p
            h_new = o * c_new / jnp.maximum(n_new, 1.0)
            return (h_new, c_new, n_new, m_new), h_new

        with jax.named_scope("time_scan"):
            (h_f, c_f, n_f, m_f), hs = jax.lax.scan(step, (h0, c0, n0, m0), gx_t)
        y = jnp.moveaxis(hs, 0, 1).reshape(B, S, D).astype(x.dtype)
        with jax.named_scope("out"):
            y = rms_norm(params["out_norm"], y, scope="out_norm")
            y = jnp.einsum("bsd,de->bse", y, params["wo"].astype(x.dtype))
        return y, {"h": h_f, "c": c_f, "n": n_f, "m": m_f}


def slstm_step(params, x_t, state, cfg, *, scope: str = "slstm"):
    y, new_state = slstm(params, x_t, cfg, state=state, scope=scope)
    return y, new_state


def init_slstm_state_arrays(batch: int, H: int, hd: int) -> dict:
    z = lambda: jnp.zeros((batch, H, hd), jnp.float32)
    return {"h": z(), "c": z(), "n": z(), "m": z()}


def init_slstm_state(cfg, batch: int) -> dict:
    return init_slstm_state_arrays(batch, cfg.n_heads, cfg.d_model // cfg.n_heads)


def abstract_slstm_state(cfg, batch: int) -> dict:
    hd = cfg.d_model // cfg.n_heads
    sh = (batch, cfg.n_heads, hd)
    return {k: jax.ShapeDtypeStruct(sh, jnp.float32) for k in ("h", "c", "n", "m")}
