"""Bring-up check on one TPU: train, serve and the Pallas kernels at full width.

Run from the repository root on a machine with a TPU:

    python3 chip_smoke.py

One process holds the chip and drives the main path through the entry points
a user calls:

* train: ``repro.launch.train.Trainer`` on xlstm-125m at its published
  widths (12 layers, d_model 768, vocab 50304), batch 8 x seq 2048, 5 steps,
  with the out-of-process profiler daemon sampling beside it;
* serve: ``repro.launch.serve.BatchedServer`` on the same config, 8 requests
  on a decode batch of 8;
* kernels: the Pallas flash-attention, splash-attention and RG-LRU scan
  kernels compiled for the chip (never interpreted) and compared with
  ``repro.kernels.ref``.

Weights and data are random, made from fixed seeds.  Each phase checks what
it produced; a failed phase makes the script exit 1 after the others ran.
With no TPU it exits 1 before any phase.  The times it prints are host
wall-clock times that include compilation, not device metrics.  The last line
of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import json
import math
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "xlstm-125m"

# Attention: bf16 inputs and a bf16 output, so each side rounds its output to
# bf16 (half an ulp is 2**-9 of |o|), and the kernel may round its softmax
# weights to bf16 for the second matmul (2**-9 of each term).  Together that
# stays below 1e-2 of the output's scale; 2e-2 leaves twice that, while a wrong
# mask or a lost block moves outputs by tenths.
ATTN_TOL = 2e-2
# RG-LRU scan: f32 in and out, the same recurrence h = a*h + b in the same
# order on both sides; only a fused multiply-add may round differently, by an
# ulp per step, and a decay of at most 0.99 bounds the sum to 100 ulps
# (about 1.2e-5 of |h|).  1e-4 leaves eight times that.
SCAN_TOL = 1e-4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def train_phase(out_dir: str, *, smoke: bool = False, batch: int = 8, seq: int = 2048, steps: int = 5) -> dict:
    """Train ``steps`` steps with the daemon profiler attached; check losses,
    the device plane and the daemon's tree."""
    import jax

    from repro.core.hlo_tree import load_device_tree
    from repro.launch.train import Trainer, TrainJobConfig

    job = TrainJobConfig(
        arch=ARCH, smoke=smoke, steps=steps, global_batch=batch, seq_len=seq,
        out_dir=out_dir, resume=False, profile=True, profile_backend="daemon",
    )
    trainer = Trainer(job)
    cfg = trainer.cfg
    print(f"[train] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, vocab {cfg.vocab}; "
          f"batch {batch} x seq {seq}, {steps} steps", flush=True)
    summary = trainer.run()
    losses = [m["loss"] for m in trainer.metrics_log]
    print(f"[train] losses: {losses}", flush=True)
    check(len(losses) == steps, f"{len(losses)} steps logged, expected {steps}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    tree = load_device_tree(os.path.join(out_dir, "device_tree.json"))
    kind = jax.devices()[0].device_kind
    print(f"[train] device_tree.json: device_kind={tree.device_kind!r} flops={tree.total('flops')!r} "
          f"bytes={tree.total('bytes')!r} call_sites={tree.node_count()}", flush=True)
    check(tree.total("flops") > 0, "device plane has no flops")
    check(tree.device_kind == kind, f"device plane says {tree.device_kind!r}, the device is {kind!r}")
    samples = summary.get("profile_samples", 0)
    print(f"[train] profiler daemon tree: {samples!r} samples", flush=True)
    check(samples > 0, "the profiler daemon returned an empty tree")
    return summary


def serve_phase(*, smoke: bool = False, batch: int = 8, n_requests: int = 8, max_new: int = 12) -> dict:
    """Serve ``n_requests`` greedy requests; check every one got ``max_new`` tokens."""
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import BatchedServer, Request
    from repro.models import Model

    cfg = get_config(ARCH, smoke=smoke)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, rng.integers(3, 10)).astype(np.int32), max_new=max_new)
        for i in range(n_requests)
    ]
    server = BatchedServer(Model(cfg), batch=batch, max_len=128, seed=0)
    stats = server.run(reqs)
    print(f"[serve] {cfg.name}: requests_done={stats['requests_done']} decode_steps={stats['decode_steps']}",
          flush=True)
    check(stats["requests_done"] == n_requests, f"{stats['requests_done']} of {n_requests} requests done")
    for r in reqs:
        check(len(r.out) == max_new, f"request {r.rid} got {len(r.out)} tokens, expected {max_new}")
        check(all(0 <= t < cfg.vocab for t in r.out), f"request {r.rid} has tokens outside the vocabulary")
    return stats


def _compare(name: str, got, want, tol: float) -> None:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
    excess = float(np.max(np.abs(got - want) - tol * (1.0 + np.abs(want))))
    print(f"[kernels] {name}: max |kernel - ref| = {float(np.max(np.abs(got - want)))!r} "
          f"(tolerance {tol} * (1 + |ref|))", flush=True)
    check(excess <= 0, f"{name}: kernel and reference differ beyond tolerance")


def kernel_phase() -> None:
    """Compile each kernel for the chip, run it, and compare with the reference."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    key = jax.random.key(0)

    def flash(q, k, v, window):
        return ops.flash_attention.lower(q, k, v, causal=True, window=window, interpret=False)

    def splash(q, k, v, window):
        return ops.splash_attention.lower(q, k, v, window=window)

    # (label, wrapper, batch, seq, q heads, kv heads, head dim, window)
    attention_cases = [
        ("flash_attention causal, qwen3-4b widths", flash, 1, 2048, 32, 8, 128, None),
        ("flash_attention windowed, recurrentgemma-9b local attention", flash, 1, 4096, 16, 1, 256, 2048),
        ("flash_attention causal, short seq 64", flash, 1, 64, 32, 8, 128, None),
        ("splash_attention causal, granite-3-8b widths", splash, 1, 4096, 32, 8, 128, None),
    ]
    for i, (label, lower, b, s, hq, hkv, d, window) in enumerate(attention_cases):
        kq, kk, kv = jax.random.split(jax.random.fold_in(key, i), 3)
        q = jax.random.normal(kq, (b, s, hq, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, s, hkv, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, s, hkv, d), jnp.bfloat16)
        compiled = lower(q, k, v, window).compile()
        check("tpu_custom_call" in compiled.as_text(), f"{label}: no tpu_custom_call in the compiled program")
        got = compiled(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = ref.attention_ref(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), causal=True, window=window
            ).swapaxes(1, 2)
        _compare(label, got, want, ATTN_TOL)

    ka, kb = jax.random.split(jax.random.fold_in(key, len(attention_cases)))
    shape = (2, 2048, 4096)
    a = jax.random.uniform(ka, shape, jnp.float32, 0.5, 0.99)
    bb = jax.random.normal(kb, shape, jnp.float32)
    compiled = ops.rglru_scan.lower(a, bb, interpret=False).compile()
    check("tpu_custom_call" in compiled.as_text(), "rglru_scan: no tpu_custom_call in the compiled program")
    got = compiled(a, bb)
    with jax.default_matmul_precision("highest"):
        want = ref.rglru_ref(a, bb)
    _compare("rglru_scan, W=4096", got, want, SCAN_TOL)


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} device_kind={dev.device_kind} count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this check runs only on a TPU", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    print(f"compile cache: {cache_dir}", flush=True)

    failed = []

    def phase(name, fn, *args, **kw):
        t0 = time.perf_counter()
        try:
            fn(*args, **kw)
        except Exception:  # noqa: BLE001 - report each phase, then fail the run
            traceback.print_exc()
            failed.append(name)
        print(f"wall time (host clock, includes compilation) {name}: "
              f"{time.perf_counter() - t0!r} s{' FAILED' if name in failed else ''}", flush=True)

    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as out_dir:
        phase("train", train_phase, out_dir)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"device memory after train: {stats!r}", flush=True)
    print(f"device memory peak_bytes_in_use after train: {peak!r}", flush=True)
    if not (peak and peak > 1e9):
        print("chip_smoke: train phase peaked below 1 GB of device memory", file=sys.stderr)
        failed.append("train memory")
    phase("serve", serve_phase)
    phase("kernels", kernel_phase)

    n_cached = sum(len(files) for _d, _s, files in os.walk(cache_dir))
    print(f"compile cache: {n_cached} files in {cache_dir}", flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                               "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
