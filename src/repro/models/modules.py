"""Parameter system + primitive modules.

Models are pure functions over a params pytree (nested dicts of arrays). Each
parameter is declared by an :class:`ArraySpec` carrying **logical axis names**
(``"embed"``, ``"mlp"``, ``"q_heads"``, ``"expert"``, ...). The sharding layer
(``repro.sharding.rules``) maps logical axes onto mesh axes per parallelism
strategy, so re-sharding never touches model code — that is what §Perf
iterates on.

Every module body runs under ``jax.named_scope`` so the compiled HLO carries
the module call-path in ``op_name`` metadata — the device-plane "call-stack"
that ``repro.core.hlo_tree`` attributes cost to.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArraySpec:
    """Declarative parameter: shape + logical axes + initializer."""

    shape: tuple[int, ...]
    logical: tuple[str | None, ...]
    dtype: Any = jnp.float32
    init: str = "normal"  # normal | zeros | ones | embed
    scale: float | None = None  # overrides fan-in scaling

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)

    def initializer(self, key: jax.Array) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "embed":
            s = self.scale if self.scale is not None else 1.0
            return (jax.random.normal(key, self.shape) * s).astype(self.dtype)
        # fan-in scaled normal (truncation unnecessary for smoke-scale runs)
        fan_in = self.shape[0] if len(self.shape) > 1 else max(self.shape[-1], 1)
        if len(self.shape) >= 2:
            fan_in = int(math.prod(self.shape[:-1])) if self.init == "normal_fan_full" else self.shape[0]
        s = self.scale if self.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, self.shape) * s).astype(self.dtype)


def is_spec(x) -> bool:
    return isinstance(x, ArraySpec)


def init_params(spec_tree, key: jax.Array):
    """Materialize concrete parameters from a spec tree (smoke tests/training)."""
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [leaf.initializer(k) for leaf, k in zip(leaves, keys, strict=True)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(spec_tree):
    """ShapeDtypeStruct stand-ins (dry-run: no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        spec_tree,
        is_leaf=is_spec,
    )


def param_count(spec_tree) -> int:
    return sum(int(math.prod(s.shape)) for s in jax.tree.leaves(spec_tree, is_leaf=is_spec))


def stack_specs(spec_tree, n: int, axis_name: str = "layers"):
    """Stack a per-layer spec ``n`` times along a leading 'layers' axis (scan)."""
    return jax.tree.map(
        lambda s: ArraySpec((n,) + s.shape, (axis_name,) + s.logical, s.dtype, s.init, s.scale),
        spec_tree,
        is_leaf=is_spec,
    )


# ---------------------------------------------------------------------------
# Numerics helpers
# ---------------------------------------------------------------------------


def rms_norm(params, x, *, eps: float = 1e-6, scope: str = "rms_norm"):
    with jax.named_scope(scope):
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


def rms_norm_spec(dim: int, logical: str = "embed") -> dict:
    return {"scale": ArraySpec((dim,), (logical,), jnp.float32, "zeros")}


def dense(params, x, spec: str, *, scope: str = "dense"):
    """einsum-based projection; ``spec`` is the einsum equation."""
    with jax.named_scope(scope):
        w = params["w"]
        y = jnp.einsum(spec, x, w.astype(x.dtype))
        if "b" in params:
            y = y + params["b"].astype(x.dtype)
        return y


def dense_spec(
    shape: tuple[int, ...],
    logical: tuple[str | None, ...],
    *,
    bias: bool = False,
    bias_axes: tuple | None = None,
    dtype=jnp.float32,
    scale: float | None = None,
) -> dict:
    out = {"w": ArraySpec(shape, logical, dtype, "normal", scale)}
    if bias:
        bshape = shape[-1:] if bias_axes is None else None
        blog = logical[-1:] if bias_axes is None else bias_axes
        out["b"] = ArraySpec(bshape or shape[-1:], blog, dtype, "zeros")
    return out


ACTIVATIONS: dict[str, Callable] = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
}


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's temperature for a context stretched ``factor`` times: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, factor: float, original_len: int, beta_fast: float,
               beta_slow: float) -> jax.Array:
    """YaRN's rotary frequencies (``DeepseekV2YarnRotaryEmbedding``): the
    frequencies that turn more than ``beta_fast`` times over ``original_len``
    positions are kept, those that turn fewer than ``beta_slow`` times are
    divided by ``factor``, and a linear ramp over the dimensions blends them
    in between."""

    def dim_of(rotations: float) -> float:
        return head_dim * math.log(original_len / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    kept = rope_freqs(head_dim, theta)
    return kept / factor * ramp + kept * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0, freqs: jax.Array | None = None) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S).  ``freqs``
    (D/2,) replaces the plain frequencies theta^(-2i/D)."""
    if freqs is None:
        freqs = rope_freqs(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]  # head axis
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float, sections: tuple[int, int, int] = None) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): the head dim splits into 3 sections rotated
    by (temporal, height, width) position streams. positions: (..., S, 3)."""
    d2 = x.shape[-1] // 2
    if sections is None:
        t = d2 - 2 * (d2 // 4)
        sections = (t, d2 // 4, d2 // 4)
    freqs = rope_freqs(x.shape[-1], theta)  # (d2,)
    parts = []
    start = 0
    for i, sec in enumerate(sections):
        pos_i = positions[..., i]  # (..., S)
        ang = pos_i[..., None].astype(jnp.float32) * freqs[start : start + sec]
        parts.append(ang)
        start += sec
    angles = jnp.concatenate(parts, axis=-1)[..., None, :]  # (..., S, 1, d2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embedding_spec(vocab: int, d_model: int) -> dict:
    return {"table": ArraySpec((vocab, d_model), ("vocab", "embed"), jnp.float32, "embed", 0.02)}


def embed(params, tokens, *, scope: str = "embed"):
    with jax.named_scope(scope):
        return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x, *, scope: str = "lm_head"):
    with jax.named_scope(scope):
        return jnp.einsum("...d,vd->...v", x, params["table"].astype(x.dtype))


def lm_head_spec(vocab: int, d_model: int) -> dict:
    return {"w": ArraySpec((d_model, vocab), ("embed", "vocab"), jnp.float32, "normal")}


def lm_head(params, x, *, scope: str = "lm_head"):
    with jax.named_scope(scope):
        return jnp.einsum("...d,dv->...v", x, params["w"].astype(x.dtype))
