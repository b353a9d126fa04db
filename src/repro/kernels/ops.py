"""jit'd public wrappers around the Pallas kernels.

Layout conventions match the model code: attention takes (B, S, H, D) and
returns the same; the kernel works in (B, H, S, D). ``interpret=True`` runs
the kernel body on CPU (tests); on TPU ``interpret=False`` compiles via
Mosaic. The XLA reference path used by the dry-run lives in the model code.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_bhsd
from .fused_rmsnorm import fused_rmsnorm_pallas
from .rglru_scan import rglru_scan_pallas
from .splash_attention import splash_blocks, splash_fits, splash_kernel


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, T, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    with jax.named_scope("flash_attention"):
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        o = flash_attention_bhsd(
            qh, kh, vh, causal=causal, window=window, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
        return jnp.swapaxes(o, 1, 2)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def splash_attention(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, T, Hkv, D)
    v: jax.Array,
    *,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal (optionally sliding-window) attention through JAX's splash
    kernel, differentiable: GQA without repeating K/V, fully-masked blocks
    skipped in compute and DMA.  Scores are q.k / sqrt(D); softmax statistics
    and accumulators are float32 inside the kernel."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    if not splash_fits(S, T, Hq, Hkv, D):
        raise ValueError(f"splash_attention does not take S={S}, T={T}, Hq={Hq}, Hkv={Hkv}, D={D}")
    with jax.named_scope("splash_attention"):
        kernel = splash_kernel(S, T, Hq, window, splash_blocks(S, T), interpret)
        # The kernel does not scale the scores: scale q once, in float32.
        q = (q.astype(jnp.float32) * (1.0 / math.sqrt(D))).astype(q.dtype)
        o = jax.vmap(kernel)(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
        return jnp.swapaxes(o, 1, 2)


@functools.partial(jax.jit, static_argnames=("block_s", "block_w", "interpret"))
def rglru_scan(
    a: jax.Array,  # (B, S, W)
    b: jax.Array,
    *,
    block_s: int = 128,
    block_w: int = 512,
    interpret: bool = False,
) -> jax.Array:
    with jax.named_scope("rglru_scan"):
        return rglru_scan_pallas(a, b, block_s=block_s, block_w=block_w, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def fused_rmsnorm(
    x: jax.Array,
    scale: jax.Array,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    with jax.named_scope("fused_rmsnorm"):
        return fused_rmsnorm_pallas(x, scale, eps=eps, block_rows=block_rows, interpret=interpret)
