"""The parameter tree the program takes, built from the seed by the benchmark.

The benchmark, not the program, makes the weights, so the references can
start from them without taking anything the program made.  The tree follows
the program's published layout: ``embed.table`` (V, d), the layer stack under
``layers.scan.block<j>`` with one leading axis over the repeats of the layer
pattern, ``final_norm.scale`` (d,) and ``lm_head.w`` (d, V).  Each leaf is
drawn from its own key, ``fold_in(key, i)`` for the i-th leaf in a fixed
order, with the standard deviation given beside its shape.
"""

from __future__ import annotations

import math

import jax

from .common import normal

#: Norm scales are stored as offsets from 1; a spread of 0.1 keeps the norms
#: away from the identity, so a model that ignored them would differ.
NORM_STD = 0.1


def _norm(d: int, std=NORM_STD) -> dict:
    return {"scale": ((d,), std)}


def block_shapes(cfg: dict, kind: str) -> dict:
    """(shape, std) of every leaf of one block of ``kind``."""
    d, H = cfg["d_model"], cfg["n_heads"]
    s = 1.0 / math.sqrt(d)
    if kind == "attn":
        hd = cfg.get("head_dim_") or d // H
        hkv = cfg["n_kv_heads"]
        f = cfg["d_ff"]
        return {
            "norm1": _norm(d),
            "attn": {
                "wq": ((d, H, hd), s), "wk": ((d, hkv, hd), s), "wv": ((d, hkv, hd), s),
                "wo": ((H, hd, d), 1.0 / math.sqrt(H * hd)),
            },
            "norm2": _norm(d),
            "mlp": {"wi": ((d, f), s), "wo": ((f, d), 1.0 / math.sqrt(f)), "wg": ((d, f), s)},
        }
    hd = d // H
    if kind == "mlstm":
        return {
            "norm1": _norm(d),
            "mlstm": {
                "wq": ((d, H, hd), s), "wk": ((d, H, hd), s), "wv": ((d, H, hd), s),
                "wi": ((d, H), s), "wf": ((d, H), s),
                "wo_gate": ((d, d), s), "out_norm": _norm(d), "wo": ((d, d), s),
            },
        }
    if kind == "slstm":
        return {
            "norm1": _norm(d),
            "slstm": {
                "wx": ((d, 4, H, hd), s), "r": ((4, H, hd, hd), 1.0 / math.sqrt(hd)),
                "b": ((4, H, hd), 0.1), "out_norm": _norm(d), "wo": ((d, d), s),
            },
        }
    raise ValueError(f"no reference for layer kind {kind!r}")


def tree_shapes(cfg: dict) -> dict:
    """(shape, std) of every leaf of the whole model."""
    pattern = list(cfg["pattern"])
    units, rest = divmod(cfg["n_layers"], len(pattern))
    if rest or cfg.get("first_dense", 0):
        raise ValueError("the references take whole repeats of the layer pattern only")
    d, V = cfg["d_model"], cfg["vocab"]

    def stacked(t):
        return {k: stacked(v) if isinstance(v, dict) else ((units, *v[0]), v[1]) for k, v in t.items()}

    return {
        "embed": {"table": ((V, d), 1.0)},
        "layers": {"scan": {f"block{j}": stacked(block_shapes(cfg, k)) for j, k in enumerate(pattern)}},
        "final_norm": _norm(d),
        "lm_head": {"w": ((d, V), 1.0 / math.sqrt(d))},
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(cfg: dict, key) -> dict:
    """The weights of one seed: every leaf float32, normal with its std."""
    shapes = tree_shapes(cfg)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_leaf)
    vals = [normal(jax.random.fold_in(key, i), shape, std) for i, (shape, std) in enumerate(leaves)]
    return jax.tree.unflatten(treedef, vals)
