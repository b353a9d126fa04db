"""Attention: GQA/MQA with RoPE/M-RoPE, qk-norm, sliding windows, KV cache,
and latent attention (MLA, :func:`mla`: keys and values from a shared latent).

The training/prefill core takes one of three paths, chosen by
:func:`attention_path` from the platform and the shapes:

* ``splash``  — JAX's block-sparse splash kernel (``kernels.ops.splash_attention``)
                on a TPU where the shapes fit it;
* ``full``    — pure-jnp math that materializes the S×T scores (the reference,
                and what the CPU and the dry-run run);
* ``chunked`` — the same math as an online softmax over query chunks above
                ``cfg.chunk_threshold``, so 32k-token prefill never holds the
                full score matrix.

``cfg.attention_impl`` is ``auto`` (this rule); ``xla`` forces the jnp paths;
``pallas`` and ``pallas_interpret`` force the splash kernel, compiled or in
interpret mode (CPU correctness tests).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.sharding.ctx import current_sharding_ctx, shard_activation

from .modules import ArraySpec, apply_mrope, apply_rope, rms_norm, rms_norm_spec, yarn_freqs, yarn_mscale

NEG_INF = -2.0e38


def attention_spec(cfg) -> dict:
    hd = cfg.head_dim
    spec = {
        "wq": ArraySpec((cfg.d_model, cfg.n_heads, hd), ("embed", "q_heads", "head")),
        "wk": ArraySpec((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head")),
        "wv": ArraySpec((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head")),
        "wo": ArraySpec((cfg.n_heads, hd, cfg.d_model), ("q_heads", "head", "embed")),
    }
    if cfg.qk_norm:
        spec["q_norm"] = rms_norm_spec(hd, "head")
        spec["k_norm"] = rms_norm_spec(hd, "head")
    return spec


def _project_qkv(params, x, cfg, positions):
    with jax.named_scope("qkv_proj"):
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(params["q_norm"], q, scope="q_norm")
        k = rms_norm(params["k_norm"], k, scope="k_norm")
    with jax.named_scope("rope"):
        if cfg.mrope:
            q = apply_mrope(q, positions, cfg.rope_theta)
            k = apply_mrope(k, positions, cfg.rope_theta)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask(q_idx, k_idx, window: int | None):
    m = k_idx[None, :] <= q_idx[:, None]
    if window is not None:
        m &= (q_idx[:, None] - k_idx[None, :]) < window
    return m


def _attend_full(q, k, v, cfg, *, q_offset: int = 0, window: int | None = None, scale: float | None = None):
    """q, k: (B,S,Hq,D), (B,T,Hkv,D); v: (B,T,Hkv,Dv). Materializes (B,Hkv,G,S,T).
    Scores are q.k times ``scale``, 1/sqrt(D) by default."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    with jax.named_scope("scores"):
        s = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
        mask = _mask(jnp.arange(S) + q_offset, jnp.arange(T), window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    with jax.named_scope("pv"):
        o = jnp.einsum("bkgst,btkd->bskgd", p, v)
    return o.reshape(B, S, Hq, v.shape[-1])


def _attend_chunked(q, k, v, cfg, *, window: int | None = None, scale: float | None = None):
    """Online-softmax over query chunks: memory O(chunk * T), same math as
    the flash kernel (the Pallas kernel additionally tiles T into VMEM).

    The chunk body is ``jax.checkpoint``-ed: without it, differentiating the
    scan saves every chunk's (Bq, T) score/probability residuals — i.e. the
    full S x T matrix again — which the device-plane profiler exposed as the
    dominant train_4k memory term (§Perf iteration 1)."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    C = min(cfg.chunk, S)
    n_chunks = (S + C - 1) // C
    pad = n_chunks * C - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = q.reshape(B, n_chunks, C, Hkv, G, D)
    qg = jnp.moveaxis(qg, 1, 0)  # (n_chunks, B, C, Hkv, G, D)
    Dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    k_idx = jnp.arange(T)

    # Sliding-window: each q-chunk only attends to the last `window` keys, so
    # slice a (window + C)-long KV strip per chunk instead of streaming all T
    # keys — 32k-prefill score traffic drops by T/(window+C) (§Perf cell C).
    use_strip = window is not None and (window + C) < T
    Lk = min(window + C, T) if window is not None else T

    def body(_, args):
        i, qc = args
        if getattr(cfg, "attn_cp", False):
            # Context parallelism: when heads don't divide the TP axis the
            # attention math replicates across 'model'; sharding the q-chunk
            # rows instead splits score/pv compute 16-ways (§Perf cell B).
            qc = shard_activation(qc, (None, "ctx_chunk", None, None, None))
        if use_strip:
            kstart = jnp.clip(i * C + C - Lk, 0, T - Lk)
            kc = jax.lax.dynamic_slice(k, (0, kstart, 0, 0), (k.shape[0], Lk, Hkv, D))
            vc = jax.lax.dynamic_slice(v, (0, kstart, 0, 0), (v.shape[0], Lk, Hkv, Dv))
            kidx = kstart + jnp.arange(Lk)
        else:
            kc, vc, kidx = k, v, k_idx
        with jax.named_scope("chunk_scores"):
            s = jnp.einsum("bckgd,btkd->bkgct", qc, kc).astype(jnp.float32) * scale
            q_idx = i * C + jnp.arange(C)
            m = kidx[None, :] <= q_idx[:, None]
            if window is not None:
                m &= (q_idx[:, None] - kidx[None, :]) < window
            s = jnp.where(m[None, None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(qc.dtype)
        with jax.named_scope("chunk_pv"):
            o = jnp.einsum("bkgct,btkd->bckgd", p, vc)
        return None, o

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable, prevent_cse=False)
    with jax.named_scope("q_chunk_scan"):
        _, o = jax.lax.scan(body, None, (jnp.arange(n_chunks), qg))
    o = jnp.moveaxis(o, 0, 1).reshape(B, n_chunks * C, Hkv, G, Dv)
    if pad:
        o = o[:, :S]
    return o.reshape(B, S, Hq, Dv)


def attention_path(cfg, S: int, T: int, Hq: int, Hkv: int, D: int, platform: str, Dv: int | None = None) -> str:
    """The training/prefill attention core for these shapes (q/k heads of
    ``D``, v heads of ``Dv``, ``D`` where not given): ``splash``, ``chunked``
    or ``full``.  ``auto`` takes the kernel on a TPU when its tiles fit the
    shapes and no mesh of several devices is installed (a Mosaic kernel is
    not partitioned for one)."""
    impl = cfg.attention_impl
    if impl in ("pallas", "pallas_interpret"):
        return "splash"
    if impl == "auto" and platform == "tpu":
        from repro.kernels.splash_attention import splash_fits  # Pallas: imported only where it can run

        mesh, _ = current_sharding_ctx()
        if splash_fits(S, T, Hq, Hkv, D, Dv) and (mesh is None or mesh.size == 1):
            return "splash"
    return "chunked" if S > cfg.chunk_threshold else "full"


def _attend(q, k, v, cfg, *, window: int | None = None, scale: float | None = None):
    """The attention core on the path :func:`attention_path` picks."""
    _, S, Hq, D = q.shape
    path = attention_path(cfg, S, k.shape[1], Hq, k.shape[2], D, jax.default_backend(), v.shape[-1])
    if path == "splash":
        from repro.kernels import ops as kops

        return kops.splash_attention(q, k, v, window=window, scale=scale,
                                     interpret=cfg.attention_impl == "pallas_interpret")
    if path == "chunked":
        return _attend_chunked(q, k, v, cfg, window=window, scale=scale)
    return _attend_full(q, k, v, cfg, window=window, scale=scale)


def attention(params, x, cfg, positions, *, window: int | None = None, scope: str = "attention"):
    """Training/prefill self-attention. x: (B,S,D) -> (B,S,D)."""
    with jax.named_scope(scope):
        q, k, v = _project_qkv(params, x, cfg, positions)
        o = _attend(q, k, v, cfg, window=window)
        with jax.named_scope("out_proj"):
            return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2): training/prefill in the expanded form
# ---------------------------------------------------------------------------


def mla_spec(cfg) -> dict:
    """wq (d, H, dn + dr); the latent's down-projection w_dkv (d, r) and its
    norm; the shared RoPE key w_kr (d, dr); per-head keys and values from the
    latent, w_ukv (r, H, dn + dv); wo (H, dv, d).  dn is ``head_dim``, dr
    ``qk_rope_dim``, dv ``v_head_dim`` and r ``kv_lora_rank``."""
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.head_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq": ArraySpec((d, H, dn + dr), ("embed", "q_heads", "head")),
        "w_dkv": ArraySpec((d, r), ("embed", "kv_lora")),
        "w_kr": ArraySpec((d, dr), ("embed", "head")),
        "kv_norm": rms_norm_spec(r, "kv_lora"),
        "w_ukv": ArraySpec((r, H, dn + dv), ("kv_lora", "q_heads", "head")),
        "wo": ArraySpec((H, dv, d), ("q_heads", "head", "embed")),
    }


def mla_scale(cfg) -> float:
    """The score scale: 1/sqrt(dn + dr), times YaRN's temperature squared."""
    m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return m * m / math.sqrt(cfg.head_dim + cfg.qk_rope_dim)


def _mla_rope(x, positions, cfg):
    """RoPE on the last axis of x (..., S, H, dr), with YaRN's frequencies and
    its cos/sin factor where the configuration stretches the context."""
    if not cfg.yarn_factor:
        return apply_rope(x, positions, cfg.rope_theta)
    freqs = yarn_freqs(x.shape[-1], cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_len, cfg.yarn_beta_fast,
                       cfg.yarn_beta_slow)
    out = apply_rope(x, positions, cfg.rope_theta, freqs=freqs)
    f = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return out if f == 1.0 else (out.astype(jnp.float32) * f).astype(x.dtype)


def mla(params, x, cfg, positions, *, scope: str = "attention"):
    """Latent attention, training/prefill. x: (B,S,d) -> (B,S,d).

    c = RMSNorm(x W_dkv) is the latent every head reads; k_R = RoPE(x W_kr)
    the RoPE key all heads share.  Head h has [k_C,h ; v_h] = c W_ukv,h and
    q_h = [q_C,h ; RoPE(q_R,h)] = x W_q,h, attends with k_h = [k_C,h ; k_R],
    and the heads' outputs go through W_o.  Keys and values are expanded per
    head, so the core is multi-head attention with q/k heads of dn + dr and
    v heads of dv, on the path :func:`attention_path` picks."""
    dn, dr = cfg.head_dim, cfg.qk_rope_dim
    with jax.named_scope(scope):
        with jax.named_scope("q_proj"):
            q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
        with jax.named_scope("kv_down"):
            c = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"].astype(x.dtype))
            k_r = jnp.einsum("bsd,dk->bsk", x, params["w_kr"].astype(x.dtype))
        c = rms_norm(params["kv_norm"], c, scope="kv_norm")
        with jax.named_scope("kv_up"):
            kv = jnp.einsum("bsr,rhk->bshk", c, params["w_ukv"].astype(x.dtype))
        k_c, v = kv[..., :dn], kv[..., dn:]
        with jax.named_scope("rope"):
            q = jnp.concatenate([q[..., :dn], _mla_rope(q[..., dn:], positions, cfg)], axis=-1)
            k_r = _mla_rope(k_r[:, :, None, :], positions, cfg)
            k = jnp.concatenate([k_c, jnp.broadcast_to(k_r, (*k_c.shape[:3], dr))], axis=-1)
        o = _attend(q, k, v, cfg, scale=mla_scale(cfg))
        with jax.named_scope("out_proj"):
            return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Decode path (one new token against a KV cache)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    # Hybrid archs only cache their attention window (sub-quadratic decode).
    L = min(max_len, cfg.window) if cfg.window else max_len
    shape = (batch, L, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def abstract_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    L = min(max_len, cfg.window) if cfg.window else max_len
    shape = (batch, L, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
    }


def decode_attention(params, x, cache: dict, pos, cfg, *, window: int | None = None, scope: str = "attention"):
    """One-token decode. x: (B,1,D); pos: () int32 current position.

    Returns (y, new_cache). The cache ring-buffers over the window for
    windowed (hybrid) attention; for full attention it is max_len long.
    """
    with jax.named_scope(scope):
        B = x.shape[0]
        L = cache["k"].shape[1]
        positions = jnp.full((B, 1), pos, jnp.int32) if not cfg.mrope else jnp.full((B, 1, 3), pos, jnp.int32)
        q, k_new, v_new = _project_qkv(params, x, cfg, positions)
        slot = jnp.mod(pos, L) if window else jnp.minimum(pos, L - 1)
        with jax.named_scope("cache_update"):
            k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
            v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
        Hq, D = q.shape[2], q.shape[3]
        Hkv = k.shape[2]
        G = Hq // Hkv
        qg = q.reshape(B, Hkv, G, D)
        with jax.named_scope("scores"):
            s = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(q.dtype)).astype(jnp.float32)
            s *= 1.0 / math.sqrt(D)
            t_idx = jnp.arange(L)
            if window:
                # Ring buffer: valid slots are the last `window` positions.
                age = jnp.mod(pos - t_idx, L)
                valid = (age >= 0) & (age < jnp.minimum(pos + 1, L))
            else:
                valid = t_idx <= pos
            s = jnp.where(valid[None, None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        with jax.named_scope("pv"):
            o = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(q.dtype)).reshape(B, 1, Hq, D)
        with jax.named_scope("out_proj"):
            y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
        return y, {"k": k, "v": v}
