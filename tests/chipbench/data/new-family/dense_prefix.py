"""A dense decoder whose first ``first_dense`` layers have an MLP of their own
width, ``dense_d_ff`` (DeepSeekMoE's dense first layer, arXiv:2401.06066),
and whose other layers have one of ``d_ff``.  Every layer is the pre-norm
block of ``chipbench/reference/transformer.py``.

A model family of the benchmark (``chipbench/reference/layout.py``) that the
tests add to a copy of it as a file of its own: layers of kind ``attn`` with
an FFN of ``dense_mlp`` or ``mlp``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layout, transformer
from .common import F32, lm_loss, rms_norm

#: The configuration is at CPU size already.
TINY = {"chunk": 8, "remat": "none"}
SMALL = {"chunk": 8}


def _width(cfg: dict, kind: str, ffn: str) -> int:
    if kind != "attn" or ffn not in ("dense_mlp", "mlp"):
        raise ValueError(f"no reference for a layer of kind {kind!r} with an FFN of {ffn!r}")
    return cfg["dense_d_ff"] if ffn == "dense_mlp" else cfg["d_ff"]


def layer_shapes(cfg: dict, kind: str, ffn: str) -> dict:
    d = cfg["d_model"]
    return {"norm1": layout.norm(d), "attn": transformer.attention_shapes(cfg), "norm2": layout.norm(d),
            "mlp": transformer.mlp_shapes(d, _width(cfg, kind, ffn))}


def layer_params(cfg: dict, kind: str, ffn: str) -> dict[str, int]:
    return {"attention": transformer.attention_params(cfg), "mlp": 3 * cfg["d_model"] * _width(cfg, kind, ffn)}


def forward_flops_per_token(cfg: dict, kind: str, ffn: str, seq_len: int) -> dict[str, float]:
    out = {k: 2.0 * v for k, v in layer_params(cfg, kind, ffn).items()}
    out["attention"] += transformer.attention_flops_per_token(cfg, seq_len)
    return out


def loss(params, batch, cfg, lowp=None):
    x = jnp.take(params["embed"]["table"], batch["tokens"], axis=0)
    for kind, ffn, p in layout.stack_layers(params["layers"], cfg):
        _width(cfg, kind, ffn)
        x = jax.checkpoint(lambda p, x: transformer.block(p, x, cfg, lowp))(p, x)
    h = rms_norm(x, params["final_norm"]["scale"])
    d = h.shape[-1]
    return lm_loss(
        h.reshape(-1, d), params["lm_head"]["w"], batch["labels"].reshape(-1),
        batch["loss_mask"].reshape(-1).astype(F32), lowp=lowp,
    )
