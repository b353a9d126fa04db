"""The parameter tree the program takes, built from the seed by the benchmark.

The benchmark, not the program, makes the weights, so the references can
start from them without taking anything the program made.  The tree follows
the program's published layout: ``embed.table`` (V, d), the layer stack under
``layers``, ``final_norm.scale`` (d,) and ``lm_head.w`` (d, V).  The stack is
laid out as the program's ``StackLayout`` does it:

* ``prefix.layer<i>``: the ``first_dense`` leading layers, one by one;
* ``scan.block<j>``: one block for each kind of the layer pattern, with one
  leading axis over the whole repeats of the pattern that follow;
* ``remainder.layer<i>``: the layers after the last whole repeat.

What one layer holds comes from the configuration's model family, the module
``chipbench/reference/<reference>.py`` (:func:`family`), by the layer's kind
and its FFN kind.  Each leaf is drawn from its own key, ``fold_in(key, i)``
for the i-th leaf in ``jax.tree`` order, with the standard deviation given
beside its shape.
"""

from __future__ import annotations

import importlib
import math
from typing import NamedTuple

import jax

from .common import normal

#: Norm scales are stored as offsets from 1; a spread of 0.1 keeps the norms
#: away from the identity, so a model that ignored them would differ.
NORM_STD = 0.1


def family(name: str):
    """The model family a configuration names (its ``reference``):
    ``chipbench/reference/<name>.py``.  It gives ``loss``, ``layer_shapes``,
    ``layer_params``, ``forward_flops_per_token``, ``TINY`` and ``SMALL``."""
    return importlib.import_module(f"{__package__}.{name}")


def norm(d: int, std=NORM_STD) -> dict:
    return {"scale": ((d,), std)}


class Layer(NamedTuple):
    """One entry of the stack: ``layers[group][name]``, of ``kind`` (the
    pattern's) with an FFN of ``ffn``; ``repeats`` is the length of the leading
    axis of a scanned block, None for a layer of its own."""

    group: str
    name: str
    kind: str
    ffn: str
    repeats: int | None


def ffn_kind(cfg: dict, i: int) -> str:
    """The FFN of layer ``i``, as the program's ``transformer._ffn_kind`` names it."""
    if i < cfg.get("first_dense", 0):
        return "dense_mlp"
    if cfg.get("n_experts", 0):
        return "moe"
    if cfg["d_ff"] == 0:
        return "none"
    return "mlp"


def stack(cfg: dict) -> list[Layer]:
    """The entries of the stack in the order the program applies them."""
    pattern = list(cfg["pattern"])
    p, first = len(pattern), cfg.get("first_dense", 0)
    units, rest = divmod(cfg["n_layers"] - first, p)
    out = [Layer("prefix", f"layer{i}", pattern[i % p], ffn_kind(cfg, i), None) for i in range(first)]
    if units:
        out += [Layer("scan", f"block{j}", k, ffn_kind(cfg, first + j), units) for j, k in enumerate(pattern)]
    tail = range(first + units * p, first + units * p + rest)
    return out + [Layer("remainder", f"layer{i}", pattern[i % p], ffn_kind(cfg, i), None) for i in tail]


def stack_layers(params: dict, cfg: dict):
    """-> (kind, ffn, weights) of every layer in the order they run; ``params``
    is the ``layers`` subtree.  A scanned block yields one layer per repeat,
    the whole pattern's blocks for each repeat in turn."""
    entries = stack(cfg)
    for e in entries:
        if e.group == "prefix":
            yield e.kind, e.ffn, params[e.group][e.name]
    scanned = [e for e in entries if e.group == "scan"]
    for u in range(scanned[0].repeats if scanned else 0):
        for e in scanned:
            yield e.kind, e.ffn, jax.tree.map(lambda a: a[u], params[e.group][e.name])  # noqa: B023
    for e in entries:
        if e.group == "remainder":
            yield e.kind, e.ffn, params[e.group][e.name]


def tree_shapes(fam, cfg: dict) -> dict:
    """(shape, std) of every leaf of the whole model of family ``fam``."""
    d, V = cfg["d_model"], cfg["vocab"]

    def stacked(t, n):
        return {k: stacked(v, n) if isinstance(v, dict) else ((n, *v[0]), v[1]) for k, v in t.items()}

    layers: dict = {}
    for e in stack(cfg):
        one = fam.layer_shapes(cfg, e.kind, e.ffn)
        layers.setdefault(e.group, {})[e.name] = one if e.repeats is None else stacked(one, e.repeats)
    return {
        "embed": {"table": ((V, d), 1.0)},
        "layers": layers,
        "final_norm": norm(d),
        "lm_head": {"w": ((d, V), 1.0 / math.sqrt(d))},
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(fam, cfg: dict, key) -> dict:
    """The weights of one seed: every leaf float32, normal with its std."""
    shapes = tree_shapes(fam, cfg)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_leaf)
    vals = [normal(jax.random.fold_in(key, i), shape, std) for i, (shape, std) in enumerate(leaves)]
    return jax.tree.unflatten(treedef, vals)
