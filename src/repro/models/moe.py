"""Mixture-of-Experts: shared + routed experts, top-k routing, two dispatches.

DeepSeekMoE-style fine-grained experts: ``n_shared`` always-on experts plus
``n_experts`` routed experts with top-k softmax gating.  The top-k weights
are renormalised to sum to 1, or left as the raw probabilities where
``norm_topk_prob`` is off (DeepSeek-V2).

A layer may hold a share of the routed experts, ``held_experts`` = [first,
stop), as one chip of an expert-parallel deployment does: the router still
scores all ``n_experts`` and picks the top k of them, and the layer computes
only its own experts' part of the result (plus the shared experts).  The
part the other experts give would come from the chips that hold them; nothing
here stands in for them or for their exchange.

Two dispatches; ``transformer._moe_for_mesh`` takes the one for the
installed mesh:

* :func:`moe` — **dropless**: the model's path on one device (no mesh, or a
  mesh of one), in training, prefill and decode.

  1. flatten tokens, route, take top-k -> T*k slots tagged with expert ids;
  2. ``argsort`` the slots by expert, the held experts first, the others
     after them; count each held expert's slots;
  3. one grouped matmul per projection (:func:`grouped_swiglu`: megablox
     ``gmm`` on one TPU, ``jax.lax.ragged_dot`` elsewhere) runs each held
     expert on exactly its slots: no capacity, no slot dropped;
  4. results scale by the router weights and segment-add back to tokens.

  The slot buffer is T*k rows whatever the routing, so any split of the
  slots over the experts fits it.

* :func:`moe_capacity` — **static capacity**: every expert gets a buffer of
  ``capacity_factor * T*k/E`` rows and the slots beyond it are *dropped*.
  It is the model's path on a mesh of several devices: the (E, C, D) buffer
  is annotated with a sharding constraint (``expert_buf``) so GSPMD places
  the token->expert exchange at that seam, and the slots stay sharded over
  the batch.  Its static shapes are also what an explicit all-to-all needs:
  ``moe_shard_map`` is its sharded form (``tests/test_moe_shard_map.py``
  compares the two), and the ``moe_imbalance`` fault scenario runs it to
  show capacity overflow (``dropped_frac``).  It holds every expert.

Both add the Switch-style balance term E * sum_e frac_e * mean_prob_e over
all ``n_experts``, over the whole batch or, with ``seq_aux``, within each
sequence and averaged over them; the model weighs it by ``aux_loss_alpha``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding.ctx import current_sharding_ctx, shard_activation

from .modules import ACTIVATIONS, ArraySpec
from .mlp import mlp, mlp_spec


def held_range(cfg) -> tuple[int, int]:
    """[first, stop) of the routed experts this layer holds: all of them
    unless the configuration gives ``held_experts``."""
    return tuple(cfg.held_experts) if cfg.held_experts else (0, cfg.n_experts)


def moe_spec(cfg) -> dict:
    """The router over all ``n_experts``; the held experts' weights."""
    lo, hi = held_range(cfg)
    d, f, e = cfg.d_model, cfg.moe_d_ff, hi - lo
    spec = {
        "router": {"w": ArraySpec((d, cfg.n_experts), ("embed", "expert"), jnp.float32)},
        "wi": ArraySpec((e, d, f), ("expert", "embed", "mlp")),
        "wg": ArraySpec((e, d, f), ("expert", "embed", "mlp")),
        "wo": ArraySpec((e, f, d), ("expert", "mlp", "embed")),
    }
    if cfg.n_shared_experts:
        spec["shared"] = mlp_spec(d, cfg.n_shared_experts * cfg.moe_d_ff)
    return spec


def _route(params, xt, cfg):
    """-> softmax probabilities (T, E) and the top-k weights and ids (T, K)."""
    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"]["w"])
        probs = jax.nn.softmax(logits, axis=-1)
        with jax.named_scope("top_k"):
            gate_w, gate_ids = jax.lax.top_k(probs, cfg.top_k)  # (T,K)
            if cfg.norm_topk_prob:
                gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)  # renorm over selected
    return probs, gate_w, gate_ids


def _balance(probs, flat_ids, n_experts: int, seqs: int = 1):
    """Switch-style load balancing, E * sum_e fraction_e * prob_e, over each
    of ``seqs`` equal runs of the tokens (the sequences, where ``seq_aux``
    asks for DeepSeek-V2's per-sequence term) and averaged over them;
    -> (loss, the batch's counts (E,), their fractions)."""
    ids = flat_ids.reshape(seqs, -1)
    counts = jax.vmap(lambda i: jnp.zeros((n_experts,), jnp.float32).at[i].add(1.0))(ids)
    mean_prob = probs.reshape(seqs, -1, n_experts).mean(1)
    loss = n_experts * jnp.mean(jnp.sum(counts / ids.shape[1] * mean_prob, -1))
    total = counts.sum(0)
    return loss, total, total / flat_ids.shape[0]


def routing_counters(counts, cfg) -> dict:
    """From each expert's slots in a step (E,): ``held_tokens``, the slots
    routed to the held experts, and ``load_max``, the held experts' largest
    count over their mean (1 is an even load)."""
    lo, hi = held_range(cfg)
    mine = counts[lo:hi]
    return {"held_tokens": mine.sum(), "load_max": mine.max() / jnp.maximum(mine.mean(), 1e-9)}


#: The grouped-matmul tiles (rows, contracted, output columns) on a TPU: the
#: fastest of those swept at DeepSeek-V2-Lite's expert shapes on a TPU v5e
#: (``benchmarks/moe_grouped.py``, PERF.md).
GMM_TILING = (512, 512, 1408)


def grouped_swiglu(xs, params, sizes, act: str = "silu", *, kernel: bool | None = None):
    """Each held expert's SwiGLU over its rows of ``xs`` (N, D): the rows
    sorted by expert, ``sizes`` (G,) rows for each, one grouped matmul a
    projection.  Rows past the held experts' come out 0.

    On a TPU, where the rows fill whole tiles and no mesh of several devices
    is installed (a Mosaic kernel is not partitioned for one), the products
    run in Pallas's megablox ``gmm`` kernel (``kernel`` forces the choice;
    interpret mode off a TPU); elsewhere in XLA's ``jax.lax.ragged_dot``.
    Neither writes the rows past the groups, in the forward or the backward
    pass, so those rows are masked out of the inputs and outputs of every
    product."""
    f = ACTIVATIONS[act]
    N = xs.shape[0]
    mine = (jnp.arange(N) < jnp.sum(sizes))[:, None]
    if kernel is None:
        mesh, _ = current_sharding_ctx()
        kernel = (jax.default_backend() == "tpu" and N % GMM_TILING[0] == 0
                  and (mesh is None or mesh.size == 1))
    if kernel:
        from jax.experimental.pallas.ops.tpu.megablox import gmm  # Pallas: imported only where it runs

        # the rows held elsewhere are one more group, which gmm skips
        groups = jnp.concatenate([sizes, (N - jnp.sum(sizes))[None]]).astype(jnp.int32)
        interpret = jax.default_backend() != "tpu"

        def product(a, w):
            tiles = (GMM_TILING[0], min(GMM_TILING[1], w.shape[1]), min(GMM_TILING[2], w.shape[2]))
            return gmm(a, w.astype(a.dtype), groups, a.dtype, tiles, interpret=interpret)
    else:

        def product(a, w):
            return jax.lax.ragged_dot(a, w.astype(a.dtype), sizes)

    xs = jnp.where(mine, xs, 0)
    h, g = product(xs, params["wi"]), product(xs, params["wg"])
    return jnp.where(mine, product(jnp.where(mine, f(g) * h, 0), params["wo"]), 0)


def moe(params, x, cfg, *, scope: str = "moe"):
    """Dropless expert layer over the held experts. x: (B, S, D) -> (B, S, D), aux.

    aux: ``lb_loss``; ``expert_frac`` (E,), the share of slots routed to each
    expert; ``held_tokens``, the slots routed to the held experts; and
    ``load_max``, the held experts' largest count over their mean.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    lo, hi = held_range(cfg)
    G = hi - lo
    T = B * S
    with jax.named_scope(scope):
        xt = x.reshape(T, D)
        probs, gate_w, gate_ids = _route(params, xt, cfg)
        with jax.named_scope("dispatch"):
            flat_ids = gate_ids.reshape(-1)  # (T*K,)
            local = flat_ids - lo
            held = (local >= 0) & (local < G)
            group = jnp.where(held, local, G)  # experts held elsewhere sort after the held ones
            order = jnp.argsort(group)  # stable
            sizes = jnp.bincount(group, length=G + 1)[:G].astype(jnp.int32)
            token_of_slot = order // K
            xs = xt[token_of_slot]  # (T*K, D), the held experts' slots first, by expert
        with jax.named_scope("experts"):
            y_s = grouped_swiglu(xs, params, sizes, cfg.act)
        with jax.named_scope("combine"):
            w_sorted = jnp.where(held, gate_w.reshape(-1), 0.0)[order]
            y = jnp.zeros((T, D), x.dtype).at[token_of_slot].add(y_s * w_sorted[:, None].astype(x.dtype))
        if cfg.n_shared_experts:
            y = y + mlp(params["shared"], xt, act=cfg.act, scope="shared_experts")
        with jax.named_scope("aux_loss"):
            lb_loss, counts, frac = _balance(probs, flat_ids, E, B if cfg.seq_aux else 1)
            aux = {"lb_loss": lb_loss, "expert_frac": frac, **routing_counters(counts, cfg)}
        return y.reshape(B, S, D), aux


def _capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    # round up to a multiple of 8 for lane-friendly layouts
    return max(8, (c + 7) // 8 * 8)


def moe_capacity(params, x, cfg, *, ep_constraint=None, scope: str = "moe"):
    """Static-capacity expert layer over all experts. x: (B, S, D) -> (B, S, D),
    aux dict with load-balance stats/loss and ``dropped_frac``.

    1. route, top-k -> (T*k) slots; 2. ``argsort`` slots by expert id, rank
    within expert = position - first occurrence of that expert; 3. slots with
    rank >= capacity are *dropped*, survivors scatter into a dense (E, C, D)
    buffer; 4. one batched einsum per projection runs all experts
    (E,C,D)x(E,D,F) — the tensor the **EP** sharding rule shards over the
    'model' axis; 5. results scale by router weights and segment-add back.

    ``ep_constraint`` (optional callable) applies a sharding constraint to the
    (E, C, D) expert buffers — installed by the sharding layer.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    C = _capacity(T, cfg)
    f = ACTIVATIONS[cfg.act]
    with jax.named_scope(scope):
        xt = x.reshape(T, D)
        probs, gate_w, gate_ids = _route(params, xt, cfg)
        with jax.named_scope("dispatch"):
            flat_ids = gate_ids.reshape(-1)  # (T*K,)
            order = jnp.argsort(flat_ids)  # stable
            sorted_ids = flat_ids[order]
            starts = jnp.searchsorted(sorted_ids, jnp.arange(E), side="left")
            rank = jnp.arange(T * K) - starts[sorted_ids]
            valid = rank < C
            slot = jnp.where(valid, sorted_ids * C + rank, E * C)  # E*C == drop bucket
            token_of_slot = order // K
            # Keep the (T*K, D) slot tensor sharded over the data axis: without
            # this constraint GSPMD replicates the gather output per device
            # (profiler-identified memory term on qwen3-moe train, §Perf A.3).
            slot_vals = shard_activation(xt[token_of_slot], ("batch", None))
            buf = jnp.zeros((E * C, D), x.dtype)
            buf = buf.at[slot].add(slot_vals, mode="drop")
            buf = buf.reshape(E, C, D)
            # EP: pin the expert buffer to the expert-parallel axis so the
            # token->expert exchange is an explicit collective at this seam.
            buf = shard_activation(buf, ("expert_buf", None, None))
            if ep_constraint is not None:
                buf = ep_constraint(buf)
        with jax.named_scope("experts"):
            h = jnp.einsum("ecd,edf->ecf", buf, params["wi"].astype(x.dtype))
            g = jnp.einsum("ecd,edf->ecf", buf, params["wg"].astype(x.dtype))
            h = f(g) * h
            y_e = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(x.dtype))
            y_e = shard_activation(y_e, ("expert_buf", None, None))
            if ep_constraint is not None:
                y_e = ep_constraint(y_e)
        with jax.named_scope("combine"):
            y_slots = y_e.reshape(E * C, D)
            gathered = jnp.where(valid[:, None], y_slots[jnp.clip(slot, 0, E * C - 1)], 0.0)
            gathered = shard_activation(gathered, ("batch", None))
            w_sorted = gate_w.reshape(-1)[order]
            contrib = gathered * w_sorted[:, None].astype(x.dtype)
            y = jnp.zeros((T, D), x.dtype).at[token_of_slot].add(contrib)
            y = shard_activation(y, ("batch", None))
        if cfg.n_shared_experts:
            y = y + mlp(params["shared"], xt, act=cfg.act, scope="shared_experts")
        with jax.named_scope("aux_loss"):
            lb_loss, _, frac = _balance(probs, flat_ids, E, B if cfg.seq_aux else 1)
            dropped = 1.0 - valid.sum() / (T * K)
        aux = {"lb_loss": lb_loss, "dropped_frac": dropped, "expert_frac": frac}
        return y.reshape(B, S, D), aux
