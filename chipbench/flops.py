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

What one layer holds and needs comes from the configuration's model family
(``chipbench/reference/<reference>.py``, as ``layout.family`` finds it):
``layer_params(cfg, kind, ffn)``, its weights by scope, and
``forward_flops_per_token(cfg, kind, ffn, seq_len)``, its forward operations
per token by scope.  Here are the totals over the stack.
"""

from __future__ import annotations

from chipbench.reference import layout


def layer_kinds(cfg: dict) -> list[tuple[str, str]]:
    """(kind, FFN kind) of every layer of the stack, a scanned block once for each repeat."""
    return [(e.kind, e.ffn) for e in layout.stack(cfg) for _ in range(e.repeats or 1)]


def train_flops_per_token(fam, cfg: dict, seq_len: int) -> float:
    """Required training operations per token of the whole model: 3x forward."""
    fwd = 2.0 * cfg["d_model"] * cfg["vocab"]  # the output head; the embedding is a gather
    for kind, ffn in layer_kinds(cfg):
        fwd += sum(fam.forward_flops_per_token(cfg, kind, ffn, seq_len).values())
    return 3.0 * fwd


def scope_flops_per_step(fam, cfg: dict, scope: str, batch: int, seq_len: int) -> float:
    """Required training operations of one step under ``scope``, all layers."""
    per_token = sum(fam.forward_flops_per_token(cfg, k, f, seq_len).get(scope, 0.0) for k, f in layer_kinds(cfg))
    return 3.0 * per_token * batch * seq_len


def scope_bytes_per_step(fam, cfg: dict, scope: str, batch: int, seq_len: int) -> float:
    """Least HBM bytes of one training step under ``scope``, all layers."""
    total = 0.0
    for kind, ffn in layer_kinds(cfg):
        n = fam.layer_params(cfg, kind, ffn).get(scope)
        if n is None:
            continue
        weights = n * (2 + 2 + 4)  # bf16 read forward, bf16 read backward, f32 gradient written
        acts = 2 * 2 * 2 * cfg["d_model"] * batch * seq_len  # bf16 in and out, forward and backward
        total += weights + acts
    return total
