"""The reference training run that a cell's first steps are compared with.

From the seed's weights and the batches the program was fed, take ``steps``
AdamW steps in plain float32 and record what the comparison reads: each
step's loss, every leaf's norm of the first gradient as the moments take it
(after clipping), and every leaf's norm of the change of the parameters over
all the steps.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from . import layout
from .common import adamw_update, learning_rate, leaf_norms


def run_reference(reference: str, cfg: dict, params0, batches, hyper: dict, *, lowp=None) -> dict:
    """-> {"loss": [...], "grad_norms": (n_leaves,), "change_norms": (n_leaves,)} as
    numpy, and the seconds it took to compile (``compile_s``) and to step (``steps_s``).

    ``params0`` is consumed (its buffers are donated).  ``hyper`` holds
    ``lr``, ``warmup`` and ``total_steps`` of the learning-rate schedule.
    """
    loss_fn = layout.family(reference).loss
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        grad_fn = jax.jit(jax.value_and_grad(lambda p, b: loss_fn(p, b, cfg, lowp)))
        grad_fn = grad_fn.lower(params0, batches[0]).compile()
        update = jax.jit(adamw_update, donate_argnums=(0, 2, 3))
        t1 = time.perf_counter()
        change = jax.jit(lambda p, p0: leaf_norms(jax.tree.map(jnp.subtract, p, p0)))
        p0 = jax.tree.map(jnp.copy, params0)
        params = params0
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for t, batch in enumerate(batches, start=1):
            loss, grads = grad_fn(params, batch)
            lr = learning_rate(t - 1, hyper["lr"], hyper["warmup"], hyper["total_steps"])
            params, m, v, g = update(params, grads, m, v, jnp.float32(t), jnp.float32(lr))
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = np.asarray(leaf_norms(g))
            del grads, g
        out = {"loss": losses, "grad_norms": grad_norms, "change_norms": np.asarray(change(params, p0)),
               "compile_s": t1 - t0, "steps_s": time.perf_counter() - t1}
        del params, p0, m, v
    return out
