"""Operations and bytes that a training step *requires*, from the shapes alone.

These are the yardstick of ``mfu.train`` and of the layers' roofline shares.
They count what the algorithm needs, whatever implements it: every matrix
product of the forward pass once, the backward pass as twice the forward,
the causal half of attention (the masked half is not work), and no
recomputation.  A multiply-add is two operations.  Elementwise work is left
out, so the counts are lower bounds and a share stays under 100% when the
time is measured right.

Bytes are the least HBM traffic of a layer in one step: its weights read in
bfloat16 by the forward and again by the backward pass, their float32
gradients written once, and the layer's bfloat16 input and output read or
written once in each direction.
"""

from __future__ import annotations


def _heads(cfg: dict) -> tuple[int, int, int]:
    d, H = cfg["d_model"], cfg["n_heads"]
    return H, cfg.get("n_kv_heads") or H, cfg.get("head_dim_") or d // H


def layer_params(cfg: dict, kind: str) -> dict[str, int]:
    """Weights of one layer of ``kind``, split by the scope that uses them."""
    d = cfg["d_model"]
    H, Hkv, hd = _heads(cfg)
    if kind == "attn":
        return {"attention": d * H * hd * 2 + d * Hkv * hd * 2, "mlp": 3 * d * cfg["d_ff"]}
    if kind == "mlstm":
        return {"mlstm": 3 * d * H * (d // H) + 2 * d * H + 2 * d * d}
    if kind == "slstm":
        hd = d // H
        return {"slstm": 4 * d * d + 4 * H * hd * hd + d * d}
    raise ValueError(kind)


def forward_flops_per_token(cfg: dict, kind: str, seq_len: int) -> dict[str, float]:
    """Forward operations per token of one layer of ``kind``, by scope.

    Matrix products count 2 per weight; on top of that:
    * attention: q.k and p.v over the causal half, on average (S+1)/2 keys;
    * mLSTM: the recurrent form per head, k v^T into the memory and q^T C out
      (2 hd^2 each);
    * sLSTM: nothing further (its recurrent matrix is among the weights).
    """
    H, _, hd = _heads(cfg)
    out = {k: 2.0 * v for k, v in layer_params(cfg, kind).items()}
    if kind == "attn":
        out["attention"] += 2 * 2 * H * hd * (seq_len + 1) / 2
    elif kind == "mlstm":
        hd = cfg["d_model"] // H
        out["mlstm"] += 2 * 2 * H * hd * hd
    return out


def layer_kinds(cfg: dict) -> list[str]:
    pattern = list(cfg["pattern"])
    return [pattern[i % len(pattern)] for i in range(cfg["n_layers"])]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Required training operations per token of the whole model: 3x forward."""
    fwd = 2.0 * cfg["d_model"] * cfg["vocab"]  # the output head; the embedding is a gather
    for kind in layer_kinds(cfg):
        fwd += sum(forward_flops_per_token(cfg, kind, seq_len).values())
    return 3.0 * fwd


def scope_flops_per_step(cfg: dict, scope: str, batch: int, seq_len: int) -> float:
    """Required training operations of one step under ``scope``, all layers."""
    per_token = sum(forward_flops_per_token(cfg, k, seq_len).get(scope, 0.0) for k in layer_kinds(cfg))
    return 3.0 * per_token * batch * seq_len


def scope_bytes_per_step(cfg: dict, scope: str, batch: int, seq_len: int) -> float:
    """Least HBM bytes of one training step under ``scope``, all layers."""
    total = 0.0
    for kind in layer_kinds(cfg):
        n = layer_params(cfg, kind).get(scope)
        if n is None:
            continue
        weights = n * (2 + 2 + 4)  # bf16 read forward, bf16 read backward, f32 gradient written
        acts = 2 * 2 * 2 * cfg["d_model"] * batch * seq_len  # bf16 in and out, forward and backward
        total += weights + acts
    return total
