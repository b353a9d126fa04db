"""Batched serving driver: prefill + decode with continuous batching (lite).

A fixed-size decode batch is kept full from a request queue: finished
sequences are replaced by queued prompts (their prefill runs as masked decode
steps of the shared batch, which keeps one compiled step function — the
approach used by TPU serving stacks when prefill traffic is light). The
host-plane sampler + dominance detector watch the loop exactly like training:
a stuck decode (e.g. a dead host in a multi-pod serving cell) trips the
watchdog's hang rule.

CLI:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --requests 16

Out-of-process profiling (attach `python -m repro.profilerd` from another
terminal — the serving loop only publishes raw frames):
  PYTHONPATH=src python -m repro.launch.serve --profile --backend daemon \\
      --spool /tmp/serve.spool
"""

from __future__ import annotations

import argparse
import json
import threading
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import DominanceDetector, Rule, SamplerConfig, WatchdogLoop, make_sampler
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import make_serve_step
from repro.models import Model


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (P,) int32
    max_new: int = 16
    out: list[int] = field(default_factory=list)
    done: bool = False


class ServeMetrics:
    """Serving counters shared between the decode loop and scrapers.

    One lock guards the counters: the decode loop takes it once per step
    (`record_step`), dashboards/scrapers take it to read (`snapshot`).  That
    makes this the serving loop's lock-convoy seam — a scraper that holds the
    lock too long parks the decode thread in ``record_step``, which is
    exactly the contention profile the fault corpus injects and the
    profiler's dominance rules are scored on.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.steps = 0
        self.requests_done = 0
        self.step_wall_s = 0.0

    def record_step(self, *, done_now: int, wall_s: float) -> None:
        with self._lock:
            self.steps += 1
            self.requests_done += done_now
            self.step_wall_s += wall_s

    def snapshot(self) -> dict:
        with self._lock:
            mean = self.step_wall_s / self.steps if self.steps else 0.0
            return {
                "steps": self.steps,
                "requests_done": self.requests_done,
                "mean_step_s": mean,
            }


class BatchedServer:
    def __init__(self, model: Model, *, batch: int = 4, max_len: int = 128, seed: int = 0):
        self.model = model
        self.batch = batch
        self.max_len = max_len
        self.params = model.init(jax.random.key(seed))
        self.state = model.init_decode_state(batch, max_len)
        self.step_fn = jax.jit(make_serve_step(model), donate_argnums=(2,))
        self.slots: list[Request | None] = [None] * batch
        # per-slot progress: how many prompt tokens already consumed
        self.consumed = [0] * batch
        self.pos = 0
        self.steps = 0
        self.metrics = ServeMetrics()

    def _admit(self, queue: list[Request]) -> None:
        for i in range(self.batch):
            if self.slots[i] is None and queue:
                self.slots[i] = queue.pop(0)
                self.consumed[i] = 0

    def run(self, requests: list[Request]) -> dict:
        queue = list(requests)
        t0 = time.time()
        self._admit(queue)
        vocab = self.model.cfg.vocab
        while any(s is not None for s in self.slots) or queue:
            t_step = time.time()
            tokens = np.zeros((self.batch, 1), np.int32)
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                if self.consumed[i] < len(req.prompt):
                    tokens[i, 0] = req.prompt[self.consumed[i]]  # prefill-as-decode
                else:
                    tokens[i, 0] = req.out[-1] if req.out else req.prompt[-1]
            next_tok, self.state = self.step_fn(
                self.params, {"tokens": jnp.asarray(tokens)}, self.state, jnp.int32(self.pos)
            )
            next_tok = np.asarray(next_tok)
            self.pos += 1
            self.steps += 1
            done_before = sum(1 for r in requests if r.done)
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                if self.consumed[i] < len(req.prompt):
                    self.consumed[i] += 1
                    continue
                req.out.append(int(next_tok[i]) % vocab)
                if len(req.out) >= req.max_new:
                    req.done = True
                    self.slots[i] = None
                    self._admit(queue)
            self.metrics.record_step(
                done_now=sum(1 for r in requests if r.done) - done_before,
                wall_s=time.time() - t_step,
            )
            if self.pos >= self.max_len - 1:
                break  # context exhausted for this demo server
        wall = time.time() - t0
        done = [r for r in requests if r.done]
        return {
            "requests_done": len(done),
            "decode_steps": self.steps,
            "wall_s": wall,
            "steps_per_s": self.steps / max(wall, 1e-9),
            "batch": self.batch,
            "metrics": self.metrics.snapshot(),
        }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--backend", default="thread", choices=("thread", "daemon"),
                    help="profiler backend (daemon = out-of-process repro.profilerd)")
    ap.add_argument("--spool", default=None,
                    help="daemon backend: spool path for an externally-attached profilerd")
    ap.add_argument("--push", default=None, metavar="URL",
                    help="daemon backend: regional aggregator the spawned "
                         "profilerd pushes sealed epochs to (profilerd aggregate)")
    ap.add_argument("--push-node", default=None,
                    help="node name reported to the aggregator (default: hostname)")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full)
    model = Model(cfg)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, rng.integers(3, 10)).astype(np.int32),
                max_new=args.max_new)
        for i in range(args.requests)
    ]
    sampler = (
        make_sampler(
            SamplerConfig(period_s=0.1, backend=args.backend, spool_path=args.spool,
                          push_url=args.push, push_node=args.push_node)
        )
        if args.profile
        else None
    )
    wd = None
    if sampler:
        det = DominanceDetector([Rule(threshold=0.95, consecutive=3, min_window_total=8)])
        wd = WatchdogLoop(sampler, det, interval_s=1.0)
        sampler.start()
        wd.start()
    server = BatchedServer(model, batch=args.batch, max_len=128)
    stats = server.run(reqs)
    if sampler:
        wd.stop()
        tree = sampler.stop()
        stats["profile_samples"] = tree.total()
    print(json.dumps(stats, indent=1))


if __name__ == "__main__":
    main()
