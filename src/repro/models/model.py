"""Top-level Model API: spec/init/forward/loss/decode/input-specs.

``Model`` is the single entry point the launcher, dry-run, trainer, server,
benchmarks and tests all share. The forward pass runs entirely under
``jax.named_scope`` tags, giving the device-plane profiler a stable component
vocabulary across all ten architectures.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.sharding.ctx import shard_activation

from . import transformer as tfm
from .modules import (
    ArraySpec,
    abstract_params,
    embed,
    embedding_spec,
    init_params,
    is_spec,
    lm_head,
    lm_head_spec,
    param_count,
    rms_norm,
    rms_norm_spec,
    unembed,
)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- parameters -----------------------------------------------------------

    def spec(self) -> dict:
        cfg = self.cfg
        spec: dict[str, Any] = {}
        if cfg.input_mode == "tokens":
            spec["embed"] = embedding_spec(cfg.vocab, cfg.d_model)
        else:
            # Modality frontend is a STUB: inputs arrive as precomputed
            # frame/patch embeddings (assignment note for [audio]/[vlm]).
            spec["embed_proj"] = {"w": ArraySpec((cfg.d_model, cfg.d_model), ("embed", "embed_out"))}
        spec["layers"] = tfm.stack_spec(cfg)
        spec["final_norm"] = rms_norm_spec(cfg.d_model)
        if not cfg.tied_embeddings:
            spec["lm_head"] = lm_head_spec(cfg.vocab, cfg.d_model)
        return spec

    def init(self, key: jax.Array) -> dict:
        return init_params(self.spec(), key)

    def abstract_params(self) -> dict:
        return abstract_params(self.spec())

    @property
    def n_params(self) -> int:
        return param_count(self.spec())

    @property
    def n_active_params(self) -> int:
        """Per-token active parameters (MoE: routed experts count k/E)."""
        cfg = self.cfg
        total = self.n_params
        if not cfg.n_experts:
            return total
        spec = self.spec()
        routed = 0
        def count_routed(path, s):
            nonlocal routed
            if "moe" in path and any(ax == "expert" for ax in s.logical) and "router" not in path:
                routed += int(math.prod(s.shape))
        _walk_spec(spec, (), count_routed)
        active_routed = routed * cfg.top_k / cfg.n_experts
        return int(total - routed + active_routed)

    # -- forward / loss ------------------------------------------------------------

    def _trunk(self, params, batch) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        if cfg.input_mode == "tokens":
            x = embed(params["embed"], batch["tokens"]).astype(jnp.bfloat16)
        else:
            x = jnp.einsum(
                "bsd,de->bse", batch["embeds"].astype(jnp.bfloat16),
                params["embed_proj"]["w"].astype(jnp.bfloat16),
            )
        if cfg.input_mode == "tokens" and not cfg.tied_embeddings:
            pass
        if cfg.tied_embeddings:
            x = x * math.sqrt(cfg.d_model)  # gemma convention
        x = shard_activation(x, ("batch", None, None))
        positions = batch.get("positions")
        if positions is None:
            S = x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), x.shape[:2])
            if cfg.mrope:
                positions = jnp.broadcast_to(positions[..., None], (*x.shape[:2], 3))
        x, stats = tfm.stack_apply(params["layers"], x, cfg, positions)
        x = rms_norm(params["final_norm"], x, scope="final_norm")
        return x, stats

    def logits_fn(self, params, x):
        cfg = self.cfg
        if cfg.tied_embeddings:
            logits = unembed(params["embed"], x)
        else:
            logits = lm_head(params["lm_head"], x)
        if cfg.logit_softcap:
            logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
        return logits

    def forward(self, params, batch) -> tuple[jax.Array, jax.Array]:
        """-> (logits (B,S,V), moe load-balance loss)."""
        logits, stats = self._forward(params, batch)
        return logits, stats["lb_loss"]

    def _forward(self, params, batch) -> tuple[jax.Array, dict]:
        with jax.named_scope("model"):
            x, stats = self._trunk(params, batch)
            return self.logits_fn(params, x), stats

    def loss(self, params, batch) -> tuple[jax.Array, dict]:
        """Causal-LM cross entropy (+ z-loss + MoE aux).  With experts the aux
        also carries the step's routing counters: ``moe_held_tokens``, the
        slots routed to held experts summed over the expert layers, and
        ``moe_load_max``, the largest of the layers' held-expert load over its
        mean."""
        with jax.named_scope("loss"):
            logits, stats = self._forward(params, batch)
            lb = stats["lb_loss"]
            labels = batch["labels"]
            mask = batch.get("loss_mask")
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(lsm, labels[..., None], axis=-1)[..., 0]
            if mask is None:
                mask = jnp.ones_like(nll)
            denom = jnp.maximum(mask.sum(), 1.0)
            ce = (nll * mask).sum() / denom
            zl = 1e-4 * ((jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) ** 2) * mask).sum() / denom
            total = ce + zl + self.cfg.aux_loss_alpha * lb
            counters = {k: v for k, v in stats.items() if k.startswith("moe_")}
            return total, {"ce": ce, "z_loss": zl, "lb_loss": lb, **counters}

    # -- decode -----------------------------------------------------------------------

    def init_decode_state(self, batch: int, max_len: int) -> dict:
        return tfm.stack_state(self.cfg, batch, max_len, abstract=False)

    def abstract_decode_state(self, batch: int, max_len: int) -> dict:
        return tfm.stack_state(self.cfg, batch, max_len, abstract=True)

    def decode_step(self, params, batch, state: dict, pos) -> tuple[jax.Array, dict]:
        """One new token for every sequence. batch: {'tokens': (B,1)} or
        {'embeds': (B,1,D)}; pos: () int32. -> (logits (B,V), new state)."""
        cfg = self.cfg
        with jax.named_scope("decode"):
            if cfg.input_mode == "tokens":
                x = embed(params["embed"], batch["tokens"]).astype(jnp.bfloat16)
            else:
                x = jnp.einsum(
                    "bsd,de->bse", batch["embeds"].astype(jnp.bfloat16),
                    params["embed_proj"]["w"].astype(jnp.bfloat16),
                )
            if cfg.tied_embeddings:
                x = x * math.sqrt(cfg.d_model)
            x, new_state = tfm.stack_decode(params["layers"], x, state, pos, cfg)
            x = rms_norm(params["final_norm"], x, scope="final_norm")
            logits = self.logits_fn(params, x)
            return logits[:, 0], new_state

    # -- dry-run input specs ------------------------------------------------------------

    def input_specs(self, shape: ShapeSpec) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of this workload."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16
        if shape.kind in ("train", "prefill"):
            batch: dict[str, Any] = {}
            if cfg.input_mode == "tokens":
                batch["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
            else:
                batch["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), bf16)
            if cfg.mrope:
                batch["positions"] = jax.ShapeDtypeStruct((B, S, 3), i32)
            if shape.kind == "train":
                batch["labels"] = jax.ShapeDtypeStruct((B, S), i32)
                batch["loss_mask"] = jax.ShapeDtypeStruct((B, S), f32)
            return batch
        # decode: one new token against a state of length S
        batch = {}
        if cfg.input_mode == "tokens":
            batch["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
        else:
            batch["embeds"] = jax.ShapeDtypeStruct((B, 1, cfg.d_model), bf16)
        return batch

    # -- cost model ---------------------------------------------------------------------

    def model_flops(self, shape: ShapeSpec) -> float:
        """MODEL_FLOPS napkin: 6*N*D (dense) / 6*N_active*D (MoE); decode uses
        D = new tokens (global_batch) and 2*N_active (no backward)."""
        n = self.n_active_params
        if shape.kind == "train":
            return 6.0 * n * shape.tokens
        if shape.kind == "prefill":
            return 2.0 * n * shape.tokens
        return 2.0 * n * shape.global_batch


def _walk_spec(tree, path, fn):
    if is_spec(tree):
        fn(path, tree)
        return
    if isinstance(tree, dict):
        for k, v in tree.items():
            _walk_spec(v, path + (k,), fn)
