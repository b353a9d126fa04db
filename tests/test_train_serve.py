"""End-to-end integration: trainer loop (+resume, +watchdog), server, launcher."""

import json
import os
import sys

import numpy as np
import pytest

from repro.launch.train import Trainer, TrainJobConfig


def job(tmp_path, **kw):
    base = dict(
        arch="xlstm-125m",
        smoke=True,
        steps=6,
        global_batch=4,
        seq_len=32,
        lr=1e-2,
        out_dir=str(tmp_path),
        ckpt_every=3,
        profile=True,
        sample_period_s=0.05,
        resume=True,
    )
    base.update(kw)
    return TrainJobConfig(**base)


class TestTrainer:
    def test_loss_decreases_and_artifacts_written(self, tmp_path):
        summary = Trainer(job(tmp_path, steps=8)).run()
        assert summary["steps"] == 8
        assert summary["final_loss"] < summary["first_loss"]
        assert os.path.exists(tmp_path / "metrics.json")
        assert os.path.exists(tmp_path / "heartbeat")
        # host-plane profile written (the always-on paper toolchain)
        assert os.path.exists(tmp_path / "host_profile.html")

    def test_checkpoint_resume_exact(self, tmp_path):
        t1 = Trainer(job(tmp_path, steps=6))
        t1.run()
        # second run continues from step 6 checkpoint, runs to 9
        t2 = Trainer(job(tmp_path, steps=9))
        t2.run()
        assert t2.step == 9
        with open(tmp_path / "metrics.json") as f:
            log = json.load(f)
        steps = [m["step"] for m in log["steps"]]
        assert steps == [7, 8, 9]

    def test_resume_reproduces_uninterrupted_run(self, tmp_path):
        """train(6)+resume(4) == train(10) bit-for-bit on the loss curve."""
        a = tmp_path / "a"
        b = tmp_path / "b"
        Trainer(job(a, steps=5, ckpt_every=5, profile=False)).run()
        Trainer(job(a, steps=10, ckpt_every=5, profile=False)).run()
        Trainer(job(b, steps=10, ckpt_every=10, profile=False)).run()
        with open(a / "metrics.json") as f:
            la = json.load(f)["steps"]
        with open(b / "metrics.json") as f:
            lb = json.load(f)["steps"]
        la = {m["step"]: m["loss"] for m in la}
        lb = {m["step"]: m["loss"] for m in lb}
        for s in (6, 8, 10):
            assert la[s] == pytest.approx(lb[s], rel=1e-4), f"divergence at step {s}"

    def test_device_plane_written_with_device_kind(self, tmp_path):
        import jax

        from repro.core.hlo_tree import load_device_tree

        Trainer(job(tmp_path, steps=1)).run()
        tree = load_device_tree(str(tmp_path / "device_tree.json"))
        assert tree.device_kind == jax.devices()[0].device_kind
        assert tree.total("flops") > 0

    def test_device_plane_failure_fails_the_run(self, tmp_path, monkeypatch):
        from repro.core import hlo_tree

        def broken(*_a, **_kw):
            raise ValueError("unparseable HLO")

        monkeypatch.setattr(hlo_tree, "tree_from_compiled", broken)
        trainer = Trainer(job(tmp_path, steps=2))
        with pytest.raises(ValueError, match="unparseable HLO"):
            trainer.run()
        assert trainer.step == 0


class TestServer:
    def test_batched_serving_completes_requests(self):
        from repro.configs import get_config
        from repro.launch.serve import BatchedServer, Request
        from repro.models import Model

        cfg = get_config("gemma-2b", smoke=True)
        model = Model(cfg)
        rng = np.random.default_rng(0)
        reqs = [
            Request(rid=i, prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32), max_new=4)
            for i in range(6)
        ]
        server = BatchedServer(model, batch=3, max_len=64)
        stats = server.run(reqs)
        assert stats["requests_done"] == 6
        assert all(len(r.out) == 4 for r in reqs)
        assert all(0 <= t < cfg.vocab for r in reqs for t in r.out)

    def test_continuous_batching_reuses_slots(self):
        from repro.configs import get_config
        from repro.launch.serve import BatchedServer, Request
        from repro.models import Model

        cfg = get_config("xlstm-125m", smoke=True)
        model = Model(cfg)
        reqs = [Request(rid=i, prompt=np.array([1, 2, 3], np.int32), max_new=2) for i in range(5)]
        server = BatchedServer(model, batch=2, max_len=64)
        stats = server.run(reqs)
        assert stats["requests_done"] == 5  # 5 requests through 2 slots


class TestLauncher:
    def _script(self, tmp_path, hang: bool):
        """A child that heartbeats, then either finishes or hangs forever."""
        p = tmp_path / "child.py"
        hb = tmp_path / "heartbeat"
        marker = tmp_path / "attempts.txt"
        p.write_text(
            f"""
import os, sys, time
hb = {str(hb)!r}
marker = {str(marker)!r}
with open(marker, 'a') as f:
    f.write('x')
attempts = os.path.getsize(marker)
for i in range(3):
    open(hb, 'w').write(str(i))
    time.sleep(0.05)
if {hang!r} and attempts == 1:
    time.sleep(3600)   # first attempt hangs after heartbeats stop
open(hb, 'w').write('done')
"""
        )
        return p, hb, marker

    def test_restart_on_hang_then_success(self, tmp_path):
        from repro.launch.launcher import LaunchConfig, Launcher

        script, hb, marker = self._script(tmp_path, hang=True)
        cfg = LaunchConfig(
            cmd=[sys.executable, str(script)],
            workdir=str(tmp_path),
            heartbeat_path=str(hb),
            heartbeat_timeout_s=1.0,
            poll_s=0.1,
            max_restarts=2,
            backoff_s=0.1,
        )
        rep = Launcher(cfg).run()
        assert rep.exit_code == 0
        assert rep.restarts == 1  # hung once, restarted, completed
        assert marker.read_text() == "xx"

    def test_clean_job_no_restarts(self, tmp_path):
        from repro.launch.launcher import LaunchConfig, Launcher

        script, hb, _ = self._script(tmp_path, hang=False)
        cfg = LaunchConfig(
            cmd=[sys.executable, str(script)],
            workdir=str(tmp_path),
            heartbeat_path=str(hb),
            heartbeat_timeout_s=5.0,
            poll_s=0.1,
        )
        rep = Launcher(cfg).run()
        assert rep.exit_code == 0 and rep.restarts == 0

    def test_shared_profilerd_daemon_per_node(self, tmp_path):
        """profile_dir starts ONE watch daemon for the whole job; it attaches
        the child's spool as it appears and publishes the merged fleet tree
        that rendezvous then just collects."""
        from repro.launch.launcher import LaunchConfig, Launcher

        src_root = os.path.join(os.path.dirname(__file__), "..", "src")
        p = tmp_path / "child.py"
        hb = tmp_path / "heartbeat"
        p.write_text(
            f"""
import os, sys, time
sys.path.insert(0, {os.path.abspath(src_root)!r})
from repro.core import SamplerConfig, make_sampler
s = make_sampler(SamplerConfig(backend="thread"))  # env routes to the daemon
s.start()
def launcher_child_busy_loop():
    t0 = time.monotonic(); x = 0
    while time.monotonic() - t0 < 1.0:
        x += 1
        if x % 100000 == 0:
            open({str(hb)!r}, 'w').write(str(x))
launcher_child_busy_loop()
s.stop()
"""
        )
        cfg = LaunchConfig(
            cmd=[sys.executable, str(p)],
            workdir=str(tmp_path),
            heartbeat_path=str(hb),
            heartbeat_timeout_s=20.0,
            poll_s=0.1,
            profile_dir=str(tmp_path / "prof"),
            profile_period_s=0.05,
            env={"JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": os.path.abspath(src_root)},
        )
        launcher = Launcher(cfg)
        rep = launcher.run()
        assert rep.exit_code == 0
        assert len(launcher._daemons) == 1  # one shared daemon, not one per spool
        fleet_tree = os.path.join(cfg.profile_dir, "fleet.d", "tree.json")
        assert os.path.exists(fleet_tree)
        # The child's DaemonBackend reads its artifacts where the shared
        # daemon publishes them (REPRO_PROFILERD_OUT -> per-target dir).
        target_dir = os.path.join(cfg.profile_dir, "fleet.d", "targets", "attempt0")
        assert os.path.exists(os.path.join(target_dir, "tree.json"))
        tstatus = json.load(open(os.path.join(target_dir, "status.json")))
        assert tstatus["done"] and tstatus["n_stacks"] > 0
        merged = os.path.join(cfg.profile_dir, "merged_tree.json")
        assert os.path.exists(merged)
        tree = json.load(open(merged))
        names = json.dumps(tree)
        assert "launcher_child_busy_loop" in names
        assert any("merged 1 host tree" in e for e in rep.events)

    def test_gives_up_after_budget(self, tmp_path):
        from repro.launch.launcher import LaunchConfig, Launcher

        p = tmp_path / "bad.py"
        p.write_text("import sys; sys.exit(3)")
        cfg = LaunchConfig(
            cmd=[sys.executable, str(p)],
            workdir=str(tmp_path),
            heartbeat_path=str(tmp_path / "hb"),
            heartbeat_timeout_s=5.0,
            poll_s=0.05,
            max_restarts=2,
            backoff_s=0.01,
        )
        rep = Launcher(cfg).run()
        assert rep.exit_code == 3
        assert rep.restarts == 3  # budget exhausted
