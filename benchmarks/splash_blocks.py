"""Tile sweep of the splash attention kernel on one TPU, at granite-3-8b's
attention (batch 1, S=4096, 32 q and 8 kv heads of 128, causal, bf16).

For each tiling it times the forward kernel and the forward with the backward
(``jax.vjp``), device work only, and prints ``name,us_per_call,derived`` CSV
rows like ``benchmarks.run``.  ``fwd_bwd`` is what a training step without
recompute pays; under ``remat="full"`` a step pays ``fwd + fwd_bwd``.  The
last rows time the model's two paths as a training step calls them:
``kernels.ops.splash_attention`` at its own tiles and the XLA path
(``models.attention._attend_full``).

  python benchmarks/splash_blocks.py                  # on a TPU
  python benchmarks/splash_blocks.py --compile-only   # compile for a described v5e, no chip
"""

from __future__ import annotations

import argparse
import itertools
import os
import statistics
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/splash_blocks.py`
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.splash_attention import splash_kernel  # noqa: E402
from repro.models.attention import _attend_full  # noqa: E402

B, S, HQ, HKV, D = 1, 4096, 32, 8, 128


def tilings():
    """(block_q, block_kv, block_kv_compute, fused backward), capped at S."""
    sizes = [b for b in (256, 512, 1024) if b <= S]
    for bq, bkv, bc, fused in itertools.product(sizes, sizes, (256, 512), (False, True)):
        if bc <= bkv:
            yield bq, bkv, bc, fused


def blocks_of(bq, bkv, bc, fused):
    return splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bc,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bc,
        block_q_dq=None if fused else bq, block_kv_dq=None if fused else bkv,
        use_fused_bwd_kernel=fused,
    )


def fwd_and_vjp(attend):
    fwd = jax.jit(attend)

    @jax.jit
    def fwd_bwd(q, k, v, do):
        o, pull = jax.vjp(attend, q, k, v)
        return o, pull(do)

    return fwd, fwd_bwd


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
    else:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("splash_blocks: no TPU found; run with --compile-only off a TPU")

        def arr(shape, dtype, key):
            return jax.random.normal(key, shape, dtype)

    ks = jax.random.split(jax.random.key(0), 8)
    heads = (arr((B, HQ, S, D), jnp.bfloat16, ks[0]), arr((B, HKV, S, D), jnp.bfloat16, ks[1]),
             arr((B, HKV, S, D), jnp.bfloat16, ks[2]), arr((B, HQ, S, D), jnp.bfloat16, ks[3]))
    model = (arr((B, S, HQ, D), jnp.bfloat16, ks[4]), arr((B, S, HKV, D), jnp.bfloat16, ks[5]),
             arr((B, S, HKV, D), jnp.bfloat16, ks[6]), arr((B, S, HQ, D), jnp.bfloat16, ks[7]))
    cfg = get_config("granite-3-8b")
    cases = [(f"splash_bq{bq}_bkv{bkv}_bc{bc}_{'fused' if fused else 'split'}",
              jax.vmap(splash_kernel(S, S, HQ, None, blocks_of(bq, bkv, bc, fused))), heads)
             for bq, bkv, bc, fused in tilings()]
    cases += [("model_splash_attention", ops.splash_attention, model),
              ("model_xla_full", lambda q, k, v: _attend_full(q, k, v, cfg), model)]
    for name, attend, (q, k, v, do) in cases:
        fwd, fwd_bwd = fwd_and_vjp(attend)
        try:
            if compile_only:
                t = time.perf_counter()
                fwd.lower(q, k, v).compile()
                fwd_bwd.lower(q, k, v, do).compile()
                yield f"{name},0,compiled_s={time.perf_counter() - t:.2f}"
                continue
            f_us = us_per_call(fwd, q, k, v)
            fb_us = us_per_call(fwd_bwd, q, k, v, do)
        except Exception as e:  # noqa: BLE001 - a tiling the chip refuses is a row, not the end
            yield f"{name},nan,error={type(e).__name__}: {str(e).splitlines()[0][:120]}"
            continue
        yield f"{name},{f_us + fb_us:.1f},fwd_us={f_us:.1f};fwd_bwd_us={fb_us:.1f}"


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    print("name,us_per_call,derived")
    for line in main(ap.parse_args().compile_only):
        print(line, flush=True)
