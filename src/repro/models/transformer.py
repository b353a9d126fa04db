"""Block assembly: pattern-cycled layers under ``lax.scan`` + decode path.

Layer stacks run as ``lax.scan`` over **pattern units** so heterogeneous
architectures (Griffin's rec,rec,attn; xLSTM's slstm,mlstm,... cycles) stay
scan-compatible: one unit = one full pattern repetition, its parameters
stacked along a leading 'layers' axis. Layers that do not fit whole units
(``first_dense`` prefix layers, pattern remainders) are applied unrolled.

Remat policy applies to the scan body (one unit), the standard
compile-time/memory trade at 90+ layers.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import rglru as rec_mod
from . import xlstm as xlstm_mod
from .mlp import mlp, mlp_spec
from .moe import moe, moe_capacity, moe_spec, routing_counters
from .modules import rms_norm, rms_norm_spec, stack_specs
from repro.sharding.ctx import current_sharding_ctx, shard_activation


# ---------------------------------------------------------------------------
# Per-layer spec / apply
# ---------------------------------------------------------------------------


def _ffn_kind(cfg, layer_idx: int) -> str:
    if layer_idx < cfg.first_dense:
        return "dense_mlp"
    if cfg.n_experts:
        return "moe"
    if cfg.d_ff == 0:
        return "none"
    return "mlp"


def layer_kind(cfg, layer_idx: int) -> str:
    return cfg.pattern[layer_idx % len(cfg.pattern)]


def block_spec(cfg, kind: str, ffn: str) -> dict:
    d = cfg.d_model
    spec: dict[str, Any] = {"norm1": rms_norm_spec(d)}
    if kind == "attn":
        spec["attn"] = attn_mod.attention_spec(cfg)
    elif kind == "mla":
        spec["attn"] = attn_mod.mla_spec(cfg)
    elif kind == "rec":
        spec["rec"] = rec_mod.recurrent_block_spec(cfg)
    elif kind == "slstm":
        spec["slstm"] = xlstm_mod.slstm_spec(cfg)
    elif kind == "mlstm":
        spec["mlstm"] = xlstm_mod.mlstm_spec(cfg)
    else:
        raise ValueError(f"unknown layer kind {kind}")
    if ffn == "mlp":
        spec["norm2"] = rms_norm_spec(d)
        spec["mlp"] = mlp_spec(d, cfg.d_ff)
    elif ffn == "dense_mlp":
        spec["norm2"] = rms_norm_spec(d)
        spec["mlp"] = mlp_spec(d, cfg.dense_d_ff or 4 * d)
    elif ffn == "moe":
        spec["norm2"] = rms_norm_spec(d)
        spec["moe"] = moe_spec(cfg)
    return spec


def zero_stats(cfg) -> dict:
    """The statistics a layer adds to the step's: the MoE balance loss, and
    with experts the routing counters (``stack_apply``)."""
    stats = {"lb_loss": jnp.zeros((), jnp.float32)}
    if cfg.n_experts:
        stats["moe_held_tokens"] = jnp.zeros((), jnp.float32)
        stats["moe_load_max"] = jnp.zeros((), jnp.float32)
    return stats


def _add_stats(acc: dict, stats: dict) -> dict:
    """Sums over layers, but the largest load."""
    return {k: jnp.maximum(v, stats[k]) if k == "moe_load_max" else v + stats[k] for k, v in acc.items()}


def block_apply(params, x, cfg, kind: str, ffn: str, positions, *, scope: str):
    """One residual block (training/prefill). Returns (x, stats) (:func:`zero_stats`)."""
    stats = zero_stats(cfg)
    with jax.named_scope(scope):
        h = rms_norm(params["norm1"], x, scope="pre_norm")
        if kind == "attn":
            y = attn_mod.attention(params["attn"], h, cfg, positions, window=cfg.window)
        elif kind == "mla":
            y = attn_mod.mla(params["attn"], h, cfg, positions)
        elif kind == "rec":
            y = rec_mod.recurrent_block(params["rec"], h, cfg)
        elif kind == "slstm":
            y, _ = xlstm_mod.slstm(params["slstm"], h, cfg)
        elif kind == "mlstm":
            y, _ = xlstm_mod.mlstm(params["mlstm"], h, cfg)
        else:
            raise ValueError(kind)
        x = x + y
        x = shard_activation(x, ("batch", None, None))
        if ffn in ("mlp", "dense_mlp"):
            h2 = rms_norm(params["norm2"], x, scope="pre_mlp_norm")
            x = x + mlp(params["mlp"], h2, act=cfg.act)
        elif ffn == "moe":
            h2 = rms_norm(params["norm2"], x, scope="pre_moe_norm")
            y2, aux = _apply_moe(params["moe"], h2, cfg)
            x = x + y2
            stats = {"lb_loss": aux["lb_loss"], "moe_held_tokens": aux["held_tokens"],
                     "moe_load_max": aux["load_max"]}
        x = shard_activation(x, ("batch", None, None))
    return x, stats


def _apply_moe(params, h, cfg):
    """The expert layer: explicit shard_map EP (static capacity) where
    cfg.moe_impl asks for it and the mesh allows it, else :func:`_moe_for_mesh`."""
    if cfg.moe_impl == "shard_map":
        mesh, rules = current_sharding_ctx()
        if mesh is not None and "model" in mesh.shape and cfg.n_experts % mesh.shape["model"] == 0:
            from .moe_shard_map import moe_shard_map

            _refuse_held_share(cfg)
            batch = rules.get("batch", ("data",))
            data_axes = (batch,) if isinstance(batch, str) else tuple(batch)
            y, aux = moe_shard_map(params, h, cfg, mesh=mesh, data_axes=data_axes)
            return y, _with_counters(aux, h, cfg)
    return _moe_for_mesh(params, h, cfg)


def _moe_for_mesh(params, h, cfg):
    """The dropless expert layer on one device.  On a mesh of several, the
    static-capacity dispatch, whose (E, C, D) expert buffer GSPMD shards over
    the experts (``expert_buf``) and whose slots over the batch: no dropless
    exchange across devices is built yet, so there the slots past capacity
    are dropped."""
    mesh, _ = current_sharding_ctx()
    if mesh is None or mesh.size == 1:
        return moe(params, h, cfg)
    _refuse_held_share(cfg)
    y, aux = moe_capacity(params, h, cfg)
    return y, _with_counters(aux, h, cfg)


def _refuse_held_share(cfg) -> None:
    if cfg.held_experts:
        raise ValueError("a layer that holds a share of the experts (held_experts) runs dropless on one "
                         "device; the dispatches across devices hold every expert")


def _with_counters(aux, h, cfg) -> dict:
    """A static-capacity dispatch's aux with the dropless one's routing counters."""
    slots = h.shape[0] * h.shape[1] * cfg.top_k
    return dict(aux, **routing_counters(aux["expert_frac"] * slots, cfg))


def block_decode(params, x, state, pos, cfg, kind: str, ffn: str, *, scope: str):
    """One residual block, single-token decode. Returns (x, new_state)."""
    with jax.named_scope(scope):
        h = rms_norm(params["norm1"], x, scope="pre_norm")
        if kind == "attn":
            y, new_state = attn_mod.decode_attention(params["attn"], h, state, pos, cfg, window=cfg.window)
        elif kind == "rec":
            y, new_state = rec_mod.recurrent_block_step(params["rec"], h, state, cfg)
        elif kind == "slstm":
            y, new_state = xlstm_mod.slstm_step(params["slstm"], h, state, cfg)
        elif kind == "mlstm":
            y, new_state = xlstm_mod.mlstm_step(params["mlstm"], h, state, cfg)
        else:
            raise ValueError(kind)
        x = x + y
        if ffn in ("mlp", "dense_mlp"):
            h2 = rms_norm(params["norm2"], x, scope="pre_mlp_norm")
            x = x + mlp(params["mlp"], h2, act=cfg.act)
        elif ffn == "moe":
            h2 = rms_norm(params["norm2"], x, scope="pre_moe_norm")
            y2, _ = _moe_for_mesh(params["moe"], h2, cfg)
            x = x + y2
    return x, new_state


def layer_state_init(cfg, kind: str, batch: int, max_len: int, abstract: bool = False):
    if kind == "mla":
        raise NotImplementedError("latent attention (mla) has no decode state: its latent cache is not built yet")
    if kind == "attn":
        fn = attn_mod.abstract_kv_cache if abstract else attn_mod.init_kv_cache
        return fn(cfg, batch, max_len)
    if kind == "rec":
        fn = rec_mod.abstract_recurrent_state if abstract else rec_mod.init_recurrent_state
        return fn(cfg, batch)
    if kind == "mlstm":
        fn = xlstm_mod.abstract_mlstm_state if abstract else xlstm_mod.init_mlstm_state
        return fn(cfg, batch)
    if kind == "slstm":
        fn = xlstm_mod.abstract_slstm_state if abstract else xlstm_mod.init_slstm_state
        return fn(cfg, batch)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Stack layout: prefix (unrolled) + scan units + remainder (unrolled)
# ---------------------------------------------------------------------------


class StackLayout:
    """Partition of n_layers into [prefix | n_units x pattern | remainder]."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.prefix = list(range(cfg.first_dense))
        body = cfg.n_layers - cfg.first_dense
        p = len(cfg.pattern)
        self.n_units = body // p
        self.unit_kinds = tuple(cfg.pattern)
        rem = body % p
        self.remainder = [cfg.first_dense + self.n_units * p + i for i in range(rem)]
        self.rem_kinds = tuple(cfg.pattern[i] for i in range(rem))

    def describe(self) -> str:
        return (
            f"prefix={len(self.prefix)} scan={self.n_units}x{self.unit_kinds} "
            f"remainder={self.rem_kinds}"
        )


def stack_spec(cfg) -> dict:
    lay = StackLayout(cfg)
    spec: dict[str, Any] = {}
    if lay.prefix:
        spec["prefix"] = {
            f"layer{i}": block_spec(cfg, layer_kind(cfg, i), _ffn_kind(cfg, i)) for i in lay.prefix
        }
    if lay.n_units:
        unit = {
            f"block{j}": block_spec(cfg, k, _ffn_kind(cfg, cfg.first_dense + j))
            for j, k in enumerate(lay.unit_kinds)
        }
        spec["scan"] = stack_specs(unit, lay.n_units)
    if lay.remainder:
        spec["remainder"] = {
            f"layer{i}": block_spec(cfg, layer_kind(cfg, i), _ffn_kind(cfg, i)) for i in lay.remainder
        }
    return spec


def stack_apply(params, x, cfg, positions):
    """Full layer stack forward. Returns (x, stats): the layers' balance loss
    summed, and with experts the routing counters (:func:`zero_stats`)."""
    lay = StackLayout(cfg)
    total = zero_stats(cfg)
    for i in lay.prefix:
        x, stats = block_apply(
            params["prefix"][f"layer{i}"], x, cfg, layer_kind(cfg, i), _ffn_kind(cfg, i),
            positions, scope=f"layer{i}",
        )
        total = _add_stats(total, stats)

    if lay.n_units:
        def unit_body(carry, unit_params):
            h, acc = carry
            for j, kind in enumerate(lay.unit_kinds):
                with jax.named_scope(f"unit_block{j}_{kind}"):
                    h, stats = block_apply(
                        unit_params[f"block{j}"], h, cfg, kind,
                        _ffn_kind(cfg, cfg.first_dense + j), positions, scope=f"block{j}",
                    )
                    acc = _add_stats(acc, stats)
            return (h, acc), None

        body = unit_body
        if cfg.remat != "none":
            policy = {
                "full": jax.checkpoint_policies.nothing_saveable,
                "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            }[cfg.remat]
            body = jax.checkpoint(unit_body, policy=policy, prevent_cse=False)
        # Cast matrix weights to bf16 BEFORE the scan: FSDP all-gathers inside
        # the loop then move bf16, not fp32 — halves weight traffic (§Perf A).
        scan_params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16) if (a.dtype == jnp.float32 and a.ndim >= 3) else a,
            params["scan"],
        )
        with jax.named_scope("layers"):
            (x, total), _ = jax.lax.scan(body, (x, total), scan_params)

    for i in lay.remainder:
        x, stats = block_apply(
            params["remainder"][f"layer{i}"], x, cfg, layer_kind(cfg, i), _ffn_kind(cfg, i),
            positions, scope=f"layer{i}",
        )
        total = _add_stats(total, stats)
    return x, total


def stack_decode(params, x, states, pos, cfg):
    """Single-token decode through the stack. Returns (x, new_states)."""
    lay = StackLayout(cfg)
    new_states: dict[str, Any] = {}
    if lay.prefix:
        new_states["prefix"] = {}
        for i in lay.prefix:
            key = f"layer{i}"
            x, s = block_decode(
                params["prefix"][key], x, states["prefix"][key], pos, cfg,
                layer_kind(cfg, i), _ffn_kind(cfg, i), scope=key,
            )
            new_states["prefix"][key] = s

    if lay.n_units:
        def unit_body(h, scan_in):
            unit_params, unit_state = scan_in
            out_states = {}
            for j, kind in enumerate(lay.unit_kinds):
                key = f"block{j}"
                with jax.named_scope(f"unit_block{j}_{kind}"):
                    h, s = block_decode(
                        unit_params[key], h, unit_state[key], pos, cfg, kind,
                        _ffn_kind(cfg, cfg.first_dense + j), scope=key,
                    )
                out_states[key] = s
            return h, out_states

        # identical bf16 weight cast as stack_apply (prefill/decode consistency)
        scan_params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16) if (a.dtype == jnp.float32 and a.ndim >= 3) else a,
            params["scan"],
        )
        with jax.named_scope("layers"):
            x, scan_states = jax.lax.scan(unit_body, x, (scan_params, states["scan"]))
        new_states["scan"] = scan_states

    if lay.remainder:
        new_states["remainder"] = {}
        for i in lay.remainder:
            key = f"layer{i}"
            x, s = block_decode(
                params["remainder"][key], x, states["remainder"][key], pos, cfg,
                layer_kind(cfg, i), _ffn_kind(cfg, i), scope=key,
            )
            new_states["remainder"][key] = s
    return x, new_states


def stack_state(cfg, batch: int, max_len: int, abstract: bool = False):
    """Decode-state pytree matching the params layout."""
    lay = StackLayout(cfg)
    states: dict[str, Any] = {}
    if lay.prefix:
        states["prefix"] = {
            f"layer{i}": layer_state_init(cfg, layer_kind(cfg, i), batch, max_len, abstract)
            for i in lay.prefix
        }
    if lay.n_units:
        def stack_one(j_kind):
            j, kind = j_kind
            one = layer_state_init(cfg, kind, batch, max_len, abstract)
            if abstract:
                return jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct((lay.n_units,) + s.shape, s.dtype), one
                )
            return jax.tree.map(lambda a: jnp.broadcast_to(a, (lay.n_units,) + a.shape).copy(), one)

        states["scan"] = {f"block{j}": stack_one((j, k)) for j, k in enumerate(lay.unit_kinds)}
    if lay.remainder:
        states["remainder"] = {
            f"layer{i}": layer_state_init(cfg, layer_kind(cfg, i), batch, max_len, abstract)
            for i in lay.remainder
        }
    return states
