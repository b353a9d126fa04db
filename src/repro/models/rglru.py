"""Griffin / RecurrentGemma recurrent block: causal conv1d + RG-LRU.

RG-LRU recurrence (arXiv:2402.19427):

    r_t = sigmoid(W_a x_t + b_a)            (recurrence gate)
    i_t = sigmoid(W_x x_t + b_x)            (input gate)
    a_t = exp(-c * softplus(Lambda) * r_t)  (c = 8)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Training runs the scan in parallel via ``jax.lax.associative_scan`` on the
affine pairs (a, b) — the same blocked formulation the Pallas kernel tiles
into VMEM (``repro.kernels.rglru_scan``). Decode is an O(1) state update.

Block structure (Griffin):  x -> [linear_x -> conv1d -> RG-LRU] * gelu(linear_gate) -> linear_out
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .modules import ArraySpec

_C = 8.0


def rglru_spec(width: int) -> dict:
    return {
        "lam": ArraySpec((width,), ("state",), jnp.float32, "normal", 0.8),
        "wa": ArraySpec((width, width), ("state", "state_out")),
        "ba": ArraySpec((width,), ("state",), jnp.float32, "zeros"),
        "wx": ArraySpec((width, width), ("state", "state_out")),
        "bx": ArraySpec((width,), ("state",), jnp.float32, "zeros"),
    }


def recurrent_block_spec(cfg) -> dict:
    d, w = cfg.d_model, cfg.lru_width or cfg.d_model
    return {
        "in_x": {"w": ArraySpec((d, w), ("embed", "state"))},
        "in_gate": {"w": ArraySpec((d, w), ("embed", "state"))},
        "conv_w": ArraySpec((cfg.conv_width, w), ("conv", "state")),
        "conv_b": ArraySpec((w,), ("state",), jnp.float32, "zeros"),
        "lru": rglru_spec(w),
        "out": {"w": ArraySpec((w, d), ("state", "embed"))},
    }


def _gates(params, x):
    """a_t (log-space) and gated input for the recurrence. x: (B,S,W)."""
    with jax.named_scope("gates"):
        xf = x.astype(jnp.float32)
        r = jax.nn.sigmoid(jnp.einsum("bsw,wv->bsv", xf, params["wa"]) + params["ba"])
        i = jax.nn.sigmoid(jnp.einsum("bsw,wv->bsv", xf, params["wx"]) + params["bx"])
        log_a = -_C * jax.nn.softplus(params["lam"]) * r  # <= 0
        a = jnp.exp(log_a)
        # sqrt(1-a^2) in a numerically safe form
        beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
        return a, beta * (i * xf)


def rglru(params, x, *, h0=None, scope: str = "rg_lru", impl: str = "xla", chunk: int = 256):
    """Parallel RG-LRU over sequence. x: (B,S,W) -> (B,S,W), final state.

    XLA path: **blocked** scan — ``lax.scan`` over sequence chunks carrying h,
    with an in-chunk ``associative_scan``, body checkpointed. A monolithic
    associative_scan over S=4096 keeps O(S log S) fp32 residuals for the
    backward pass, which the device-plane profiler flagged as the dominant
    memory term of recurrentgemma train_4k (§Perf). This mirrors exactly how
    the Pallas kernel tiles the recurrence into VMEM.
    """
    with jax.named_scope(scope):
        a, b = _gates(params, x)
        if impl in ("pallas", "pallas_interpret"):
            from repro.kernels import ops as kops

            h = kops.rglru_scan(a, b, interpret=(impl == "pallas_interpret"))
            return h.astype(x.dtype), h[:, -1].astype(jnp.float32)
        if h0 is not None:
            b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))
        B, S, W = a.shape
        L = min(chunk, S)

        def combine(p, q):
            a1, b1 = p
            a2, b2 = q
            return a1 * a2, a2 * b1 + b2

        if S % L != 0 or S == L:
            with jax.named_scope("assoc_scan"):
                _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
            return h.astype(x.dtype), h[:, -1]

        n = S // L
        ac = jnp.moveaxis(a.reshape(B, n, L, W), 1, 0)
        bc = jnp.moveaxis(b.reshape(B, n, L, W), 1, 0)

        def body(h_in, ab):
            ach, bch = ab  # (B, L, W)
            with jax.named_scope("chunk_assoc_scan"):
                acc_a, acc_b = jax.lax.associative_scan(combine, (ach, bch), axis=1)
            h = acc_a * h_in[:, None] + acc_b  # carry-in folded per position
            return h[:, -1], h

        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable, prevent_cse=False)
        with jax.named_scope("chunk_scan"):
            h_last, hs = jax.lax.scan(body, jnp.zeros((B, W), jnp.float32), (ac, bc))
        h = jnp.moveaxis(hs, 0, 1).reshape(B, S, W)
        return h.astype(x.dtype), h_last


def rglru_step(params, x_t, h_prev):
    """One decode step. x_t: (B,1,W); h_prev: (B,W)."""
    with jax.named_scope("rg_lru"):
        a, b = _gates(params, x_t)
        h = a[:, 0] * h_prev.astype(jnp.float32) + b[:, 0]
        return h[:, None].astype(x_t.dtype), h


def causal_conv1d(params, x, *, scope: str = "conv1d"):
    """Depthwise causal conv, width W_c. x: (B,S,W)."""
    with jax.named_scope(scope):
        w = params["conv_w"].astype(x.dtype)  # (Wc, W)
        Wc = w.shape[0]
        pad = jnp.pad(x, ((0, 0), (Wc - 1, 0), (0, 0)))
        y = sum(pad[:, i : i + x.shape[1]] * w[i] for i in range(Wc))
        return y + params["conv_b"].astype(x.dtype)


def causal_conv1d_step(params, x_t, conv_state):
    """Decode: conv_state holds the last Wc-1 inputs. x_t: (B,1,W)."""
    with jax.named_scope("conv1d"):
        w = params["conv_w"].astype(x_t.dtype)
        Wc = w.shape[0]
        window = jnp.concatenate([conv_state, x_t], axis=1)  # (B, Wc, W)
        y = jnp.einsum("bcw,cw->bw", window, w)[:, None] + params["conv_b"].astype(x_t.dtype)
        return y, window[:, 1:]


def recurrent_block(params, x, cfg, *, scope: str = "recurrent_block"):
    """Full Griffin temporal-mixing block (training/prefill). x: (B,S,D)."""
    with jax.named_scope(scope):
        with jax.named_scope("in_proj"):
            xb = jnp.einsum("bsd,dw->bsw", x, params["in_x"]["w"].astype(x.dtype))
            gb = jnp.einsum("bsd,dw->bsw", x, params["in_gate"]["w"].astype(x.dtype))
        xb = causal_conv1d(params, xb)
        h, _ = rglru(
            params["lru"], xb, chunk=cfg.chunk,
            impl="xla" if cfg.attention_impl == "auto" else cfg.attention_impl,
        )
        with jax.named_scope("gate"):
            y = h * jax.nn.gelu(gb, approximate=True)
        with jax.named_scope("out_proj"):
            return jnp.einsum("bsw,wd->bsd", y, params["out"]["w"].astype(x.dtype))


def init_recurrent_state(cfg, batch: int, dtype=jnp.bfloat16) -> dict:
    w = cfg.lru_width or cfg.d_model
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, w), dtype),
        "h": jnp.zeros((batch, w), jnp.float32),
    }


def abstract_recurrent_state(cfg, batch: int, dtype=jnp.bfloat16) -> dict:
    w = cfg.lru_width or cfg.d_model
    return {
        "conv": jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, w), dtype),
        "h": jax.ShapeDtypeStruct((batch, w), jnp.float32),
    }


def recurrent_block_step(params, x_t, state: dict, cfg, *, scope: str = "recurrent_block"):
    """Decode step: O(1) in sequence length. x_t: (B,1,D)."""
    with jax.named_scope(scope):
        with jax.named_scope("in_proj"):
            xb = jnp.einsum("bsd,dw->bsw", x_t, params["in_x"]["w"].astype(x_t.dtype))
            gb = jnp.einsum("bsd,dw->bsw", x_t, params["in_gate"]["w"].astype(x_t.dtype))
        xb, conv_state = causal_conv1d_step(params, xb, state["conv"])
        h_seq, h = rglru_step(params["lru"], xb, state["h"])
        with jax.named_scope("gate"):
            y = h_seq * jax.nn.gelu(gb, approximate=True)
        with jax.named_scope("out_proj"):
            out = jnp.einsum("bsw,wd->bsd", y, params["out"]["w"].astype(x_t.dtype))
        return out, {"conv": conv_state, "h": h}
