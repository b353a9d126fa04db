"""The held experts' grouped matrix products on one TPU, at the shapes of the
``train.deepseek-v2-lite.s4096`` cell: 4 x 4096 tokens, 6 of 64 experts a
token, experts 0-7 held (about 12288 of the 98304 slots), d 2048, expert
width 1408, bf16.

Two implementations of ``models.moe``'s ``experts`` step (SwiGLU over the
held experts' rows of a slot buffer sorted by expert, rows past them left
out) are timed, forward and forward with the backward (``jax.vjp``), device
work only:

* ``ragged_dot``: ``models.moe.grouped_swiglu`` on XLA's grouped product
  ``jax.lax.ragged_dot``, the model's path off a TPU;
* ``gmm_<tm>x<tk>x<tn>``: Pallas megablox ``gmm`` with its tiling, told the
  row count of the slots held elsewhere as one more group that it skips;
  ``models.moe.GMM_TILING``, the fastest here, is the model's on a TPU.

Both mask the rows past the held experts' out of every product, as the
model does: the TPU writes neither kernel's rows there.

Rows are ``name,us_per_call,derived`` CSV like ``benchmarks.run``; a tiling
the chip refuses is a row with its error.

  python benchmarks/moe_grouped.py                  # on a TPU
  python benchmarks/moe_grouped.py --compile-only   # compile for a described v5e, no chip
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/moe_grouped.py`
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.models.moe import grouped_swiglu  # noqa: E402

T, K, E, G, D, F = 4 * 4096, 6, 64, 8, 2048, 1408
N = T * K
TILINGS = [(128, 128, 128), (256, 512, 128), (512, 512, 128), (512, 1024, 256), (512, 512, 1408)]


def routed_sizes():
    """Slots a step routes to each held expert, and to the rest: a seeded
    uniform router's top 6 of 64 for every token."""
    logits = jax.random.normal(jax.random.key(0), (T, E))
    _, ids = jax.lax.top_k(logits, K)
    group = jnp.minimum(ids.reshape(-1), G)
    return jnp.bincount(group, length=G + 1).astype(jnp.int32)


def ragged_experts(xs, wi, wg, wo, sizes):
    return grouped_swiglu(xs, {"wi": wi, "wg": wg, "wo": wo}, sizes[:G], kernel=False)


def gmm_experts(tiling):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def tile(k, n):
        tm, tk, tn = tiling
        return tm, min(tk, k), min(tn, n)

    def experts(xs, wi, wg, wo, sizes):
        # as grouped_swiglu: the rows of the skipped group stay unwritten, so keep them out of both passes
        mine = (jnp.arange(N) < jnp.sum(sizes[:G]))[:, None]
        xs = jnp.where(mine, xs, 0)
        h = gmm(xs, wi, sizes, jnp.bfloat16, tile(D, F))
        g = gmm(xs, wg, sizes, jnp.bfloat16, tile(D, F))
        a = jnp.where(mine, jax.nn.silu(g) * h, 0)
        return jnp.where(mine, gmm(a, wo, sizes, jnp.bfloat16, tile(F, D)), 0)

    return experts


def us_per_call(f, *args, reps: int = 5, n: int = 10) -> float:
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(n):
            out = f(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) / n * 1e6)
    return statistics.median(times)


def main(compile_only: bool = False):
    if compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])

        def arr(shape, dtype, _key):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        sizes = jax.ShapeDtypeStruct((G + 1,), jnp.int32, sharding=chip)
    else:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("moe_grouped: no TPU found; run with --compile-only off a TPU")

        def arr(shape, dtype, key):
            return (jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5).astype(dtype)

        sizes = routed_sizes()
        yield f"held_rows,{int(jnp.sum(sizes[:G]))},per_expert={[int(s) for s in sizes[:G]]}"
    ks = jax.random.split(jax.random.key(1), 5)
    args = (arr((N, D), jnp.bfloat16, ks[0]), arr((G, D, F), jnp.bfloat16, ks[1]), arr((G, D, F), jnp.bfloat16, ks[2]),
            arr((G, F, D), jnp.bfloat16, ks[3]))
    dy = arr((N, D), jnp.bfloat16, ks[4])
    want = None if compile_only else jax.jit(ragged_experts)(*args, sizes).astype(jnp.float32)
    cases = [("ragged_dot", ragged_experts)] + [(f"gmm_{'x'.join(map(str, t))}", gmm_experts(t)) for t in TILINGS]
    for name, experts in cases:
        fwd = jax.jit(experts)

        @jax.jit
        def fwd_bwd(xs, wi, wg, wo, sizes, dy, experts=experts):
            y, pull = jax.vjp(lambda *w: experts(*w, sizes), xs, wi, wg, wo)
            return y, pull(dy)

        try:
            if compile_only:
                t = time.perf_counter()
                fwd.lower(*args, sizes).compile()
                fwd_bwd.lower(*args, sizes, dy).compile()
                yield f"{name},0,compiled_s={time.perf_counter() - t:.2f}"
                continue
            f_us = us_per_call(fwd, *args, sizes)
            fb_us = us_per_call(fwd_bwd, *args, sizes, dy)
            err = float(jnp.max(jnp.abs(fwd(*args, sizes).astype(jnp.float32) - want)))
        except Exception as e:  # noqa: BLE001 - a tiling the chip refuses is a row, not the end
            yield f"{name},nan,error={type(e).__name__}: {str(e).splitlines()[0][:120]}"
            continue
        yield f"{name},{f_us + fb_us:.1f},fwd_us={f_us:.1f};fwd_bwd_us={fb_us:.1f};max_abs_diff_vs_ragged_dot={err:.3g}"


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    print("name,us_per_call,derived")
    for line in main(ap.parse_args().compile_only):
        print(line, flush=True)
