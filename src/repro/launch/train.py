"""Training driver: profiling-first train loop with fault tolerance.

Wires every subsystem together the way a production job would:

* data pipeline (prefetch thread) -> jit'd train_step (donated buffers);
* **host-plane sampler** running for the whole job (the paper's external
  profiler — zero instrumentation of the step function);
* **watchdog**: dominance detector over sampler windows; an anomaly triggers
  warn -> emergency checkpoint (paper §V-D flow) -> optional abort so the
  launcher can restart from the checkpoint;
* periodic async checkpoints + exact resume (params, optimizer, data
  position, step);
* heartbeat file per step — the launcher's process-level hang detector.

CLI (CPU-scale by default — full configs are exercised via the dry-run):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --steps 30
"""

from __future__ import annotations

import argparse
import json
import os
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import (
    DominanceDetector,
    Rule,
    SamplerConfig,
    WatchdogLoop,
    make_sampler,
    write_report,
)
from repro.data import DataConfig, Pipeline, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import make_train_step
from repro.models import Model
from repro.optim import AdamWConfig, adamw_init, cosine_schedule


@dataclass
class TrainJobConfig:
    arch: str = "xlstm-125m"
    smoke: bool = True
    steps: int = 30
    global_batch: int = 8
    seq_len: int = 64
    lr: float = 3e-3
    warmup: int = 10
    grad_accum: int = 1
    seed: int = 0
    out_dir: str = "/tmp/repro_train"
    ckpt_every: int = 20
    profile: bool = True
    # "thread" = in-process StackSampler; "daemon" = raw-frame agent + external
    # repro.profilerd process (see src/repro/profilerd/).
    profile_backend: str = "thread"
    # Daemon backend: explicit spool path means an external
    # `python -m repro.profilerd attach --spool ...` drains it; when None a
    # daemon subprocess is spawned automatically.
    spool_path: str | None = None
    # Daemon backend: regional aggregator URL the spawned profilerd pushes
    # sealed epochs to (`profilerd aggregate`); node name defaults to hostname.
    push_url: str | None = None
    push_node: str | None = None
    sample_period_s: float = 0.2
    watchdog_threshold: float = 0.95
    # Extra detector rules appended to the defaults (e.g. a pattern-scoped
    # rule for a known livelock signature — far more robust than tuning the
    # generic threshold).
    extra_rules: list | None = None
    heartbeat_timeout_s: float = 600.0
    resume: bool = True


class Trainer:
    def __init__(self, job: TrainJobConfig):
        self.job = job
        self.cfg = get_config(job.arch, smoke=job.smoke)
        self.model = Model(self.cfg)
        os.makedirs(job.out_dir, exist_ok=True)
        self.ckpt = CheckpointManager(os.path.join(job.out_dir, "ckpt"))
        self.data = Pipeline(
            SyntheticLM(
                DataConfig(
                    vocab=self.cfg.vocab, seq_len=job.seq_len,
                    global_batch=job.global_batch, seed=job.seed,
                )
            )
        )
        self.metrics_log: list[dict] = []
        self.step = 0
        self.params = None
        self.opt_state = None
        self._heartbeat_path = os.path.join(job.out_dir, "heartbeat")

        lr_fn = cosine_schedule(job.lr, warmup_steps=job.warmup, total_steps=max(job.steps, 2))
        self._train_step = jax.jit(
            make_train_step(self.model, lr_fn, AdamWConfig(), grad_accum=job.grad_accum),
            donate_argnums=(0, 1),
        )

        # -- profiling plane (the paper's toolchain, always on) -------------
        self.sampler = (
            make_sampler(
                SamplerConfig(
                    period_s=job.sample_period_s,
                    backend=job.profile_backend,
                    spool_path=job.spool_path,
                    push_url=job.push_url,
                    push_node=job.push_node,
                )
            )
            if job.profile
            else None
        )
        self.detector = DominanceDetector(
            [
                # generic livelock/hang rule (paper's 90%-class threshold)
                Rule(threshold=job.watchdog_threshold, consecutive=2, min_window_total=8),
                # input starvation: the prefetch worker should never dominate
                Rule(pattern="_prefetch_worker", threshold=0.6, consecutive=2,
                     min_window_total=8, self_only=False, kind="INPUT_STARVATION"),
            ]
            + list(job.extra_rules or []),
        )
        self.detector.add_callback(self._on_anomaly)
        self.watchdog = WatchdogLoop(self.sampler, self.detector, interval_s=1.0) if self.sampler else None
        self.anomalies: list = []
        self._step_exe = None  # compiled on the first step, from its batch

    # -- fault-tolerance hooks ---------------------------------------------------

    def _on_anomaly(self, event) -> None:
        self.anomalies.append(event)
        print(f"[watchdog] {event.describe()} -> emergency checkpoint")
        self.ckpt.save_emergency(lambda: (self.step, self._state_tree()), event)

    def _touch_heartbeat(self) -> None:
        with open(self._heartbeat_path, "w") as f:
            f.write(f"{self.step} {time.time()}")

    def _compile_step(self, batch: dict) -> None:
        """AOT-compile the train step for this run's shapes; the loop calls it.

        With profiling on, the compiled program is also the device plane:
        costed into a CallTree by ``op_name`` path and dropped beside the
        host profile, where the daemon/server merge it onto the sampled host
        tree (``?plane=merged``).  It also lands in the launcher's per-target
        daemon dir (``REPRO_PROFILERD_OUT``) where the shared daemon's lazy
        discovery picks it up.  A failure here fails the run.
        """
        self._step_exe = self._train_step.lower(self.params, self.opt_state, batch).compile()
        if not self.job.profile:
            return
        from repro.core.hlo_tree import save_device_tree, tree_from_compiled

        tree = tree_from_compiled(self._step_exe, device_kind=jax.devices()[0].device_kind)
        dests = [os.path.join(self.job.out_dir, "device_tree.json")]
        env_out = os.environ.get("REPRO_PROFILERD_OUT")
        if env_out:
            dests.append(os.path.join(env_out, "device_tree.json"))
        for p in dests:
            os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
            save_device_tree(tree, p, meta={"arch": self.cfg.name, "source": "train"})
        print(f"[train] device plane: {dests[0]} ({tree.node_count()} call sites, {tree.device_kind})")

    def _state_tree(self) -> dict:
        return {
            "params": self.params,
            "opt": self.opt_state,
            "data": {"next_step": np.asarray(self.data.next_step)},
        }

    # -- init / resume -------------------------------------------------------------

    def initialize(self) -> None:
        restored = self.ckpt.restore_latest() if self.job.resume else None
        if restored is not None:
            step, tree, manifest = restored
            self.step = step
            self.params = jax.tree.map(jnp.asarray, tree["params"])
            self.opt_state = jax.tree.map(jnp.asarray, tree["opt"])
            self.data.load_state_dict({"next_step": int(tree["data"]["next_step"])})
            print(f"[train] resumed from step {step} (tag={manifest['tag']})")
        else:
            self.params = self.model.init(jax.random.key(self.job.seed))
            self.opt_state = adamw_init(self.params)

    # -- loop --------------------------------------------------------------------------

    def run(self) -> dict:
        self.initialize()
        if self.sampler:
            self.sampler.start()
        if self.watchdog:
            self.watchdog.start()
        t0 = time.time()
        try:
            while self.step < self.job.steps:
                batch = {k: jnp.asarray(v) for k, v in next(self.data).items()}
                if self._step_exe is None:
                    # Before the step call: donation invalidates the argument
                    # buffers, and lowering only needs their avals anyway.
                    self._compile_step(batch)
                self.params, self.opt_state, metrics = self._step_exe(
                    self.params, self.opt_state, batch
                )
                self.step += 1
                self._touch_heartbeat()
                if self.step % self.job.ckpt_every == 0 or self.step == self.job.steps:
                    self.ckpt.save(self.step, self._state_tree())
                m = {k: float(v) for k, v in metrics.items() if jnp.ndim(v) == 0}
                m["step"] = self.step
                self.metrics_log.append(m)
                if self.step % 5 == 0 or self.step == 1:
                    print(f"[train] step {self.step}: loss={m['loss']:.4f} lr={m['lr']:.2e}")
        finally:
            if self.watchdog:
                self.watchdog.stop()
            host_tree = self.sampler.stop() if self.sampler else None
            self.ckpt.wait()
            self.data.close()
        wall = time.time() - t0
        tokens = self.step * self.job.global_batch * self.job.seq_len
        summary = {
            "arch": self.cfg.name,
            "steps": self.step,
            "wall_s": wall,
            "tokens_per_s": tokens / max(wall, 1e-9),
            "final_loss": self.metrics_log[-1]["loss"] if self.metrics_log else None,
            "first_loss": self.metrics_log[0]["loss"] if self.metrics_log else None,
            "anomalies": [e.describe() for e in self.anomalies],
        }
        with open(os.path.join(self.job.out_dir, "metrics.json"), "w") as f:
            json.dump({"summary": summary, "steps": self.metrics_log}, f, indent=1)
        if host_tree is not None:
            summary["profile_samples"] = host_tree.total()
            if host_tree.total() > 0:
                write_report(host_tree, self.job.out_dir, "host_profile")
                summary["host_profile"] = os.path.join(self.job.out_dir, "host_profile.html")
        return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--full", action="store_true", help="full config (default: smoke)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--out", default="/tmp/repro_train")
    ap.add_argument("--no-resume", action="store_true")
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
    job = TrainJobConfig(
        arch=args.arch,
        smoke=not args.full,
        steps=args.steps,
        global_batch=args.batch,
        seq_len=args.seq,
        lr=args.lr,
        grad_accum=args.grad_accum,
        out_dir=args.out,
        resume=not args.no_resume,
        profile_backend=args.backend,
        spool_path=args.spool,
        push_url=args.push,
        push_node=args.push_node,
    )
    use_compile_cache()
    summary = Trainer(job).run()
    print(json.dumps(summary, indent=1))


if __name__ == "__main__":
    main()
