"""``chip_smoke.py`` off the chip: it refuses to run, and its train and serve
phases work at the smoke configs through the same functions."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("alone", [False, True], ids=["in-repo", "script-alone"])
def test_fails_without_a_tpu(tmp_path, alone):
    script = os.path.join(REPO, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_train_phase_checks_losses_device_plane_and_daemon(tmp_path):
    summary = chip_smoke.train_phase(str(tmp_path), smoke=True, batch=2, seq=32, steps=3)
    assert summary["steps"] == 3
    assert summary["profile_samples"] > 0


def test_serve_phase_answers_every_request():
    stats = chip_smoke.serve_phase(smoke=True, batch=4, n_requests=8, max_new=4)
    assert stats["requests_done"] == 8


def test_a_wrong_answer_fails_the_comparison():
    import numpy as np

    want = np.linspace(-3, 3, 64, dtype=np.float32)
    chip_smoke._compare("close", want + 1e-3, want, chip_smoke.ATTN_TOL)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke._compare("off", want + 0.1, want, chip_smoke.ATTN_TOL)
