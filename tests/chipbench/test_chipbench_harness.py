"""The harness off the chip: it refuses to run, every cell resolves, and a run
whose timed path is broken underneath comes out not correct."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402
from chipbench.reference import layout  # noqa: E402

BENCH = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
CELLS = [c["name"] for c in BENCH["workloads"]]


def _run_cli(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0], "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_exits_nonzero_off_the_chip():
    r = _run_cli(REPO)
    assert r.returncode != 0 and _no_result(r.stdout), r.stderr[-2000:]
    assert "no accelerator" in r.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cli(tmp_path)
    assert r.returncode != 0 and _no_result(r.stdout)
    assert "the program is not here" in r.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves(cell):
    c, config, traffic = harness.resolve(BENCH, cell)
    assert os.path.isfile(harness.driver_path(traffic))
    assert os.path.isfile(os.path.join(harness.HERE, "reference", f"{config['reference']}.py"))
    family = layout.family(config["reference"])
    for name in ("loss", "layer_shapes", "layer_params", "forward_flops_per_token", "TINY", "SMALL"):
        assert hasattr(family, name), name
    assert traffic["limits"] and set(traffic["limits"]) <= {"loss_gap", "grad_gap", "change_gap"}
    metrics = harness.per_layer_for(BENCH, c)
    assert metrics, "every cell reports a per-layer metric"
    for m in metrics:
        reader = harness.load_module(harness.metric_path(m["name"]), "m")
        assert callable(reader.read)
    names = {m["name"] for m in harness.end_to_end_for(BENCH, c)}
    assert "setup_s" in names and len(names) >= 2


# -- a whole run at a tiny size, on the CPU ------------------------------------

#: The cells' limits come from their own sizes on the chip.  At the family's
#: ``TINY`` widths the program's bfloat16 reads more (a sound run of the
#: transformer on the CPU: loss_gap 0.0023, grad_gap 0.00084, change_gap
#: 0.0015; of the tests' dense-prefix family 0.0022, 0.0031, 0.0041), and
#: the faults read grad_gap and change_gap 1 (unchanged state), loss_gap 0.16,
#: grad_gap 0.16 and change_gap 0.20 (half batch).
TINY_LIMITS = {"loss_gap": 0.02, "grad_gap": 0.5, "change_gap": 0.05}


def tiny_run(cell: str, fault=None, monkeypatch=None) -> dict:
    """Drive a whole run of ``cell`` on the CPU, its model cut to its family's
    ``TINY`` widths, with ``fault`` wrapped around the program's train step."""
    import jax

    import repro.launch.train as program_train

    c, config, traffic = harness.resolve(BENCH, cell)
    config = dict(config, model=dict(config["model"], **layout.family(config["reference"]).TINY))
    traffic = dict(traffic, batch=2, seq_len=32, limits=TINY_LIMITS)
    if fault is not None:
        real = program_train.make_train_step
        monkeypatch.setattr(program_train, "make_train_step", lambda *a, **k: fault(real(*a, **k)))
    harness.setup_jax()
    ctx = harness.Context(BENCH, c, config, traffic, 2**33 + 7, 1.0, False, time.perf_counter())
    driver = harness.load_module(harness.driver_path(traffic), "chipbench_driver_train")
    line = harness.result_line(ctx, driver.run(ctx), jax.devices())
    json.dumps(line)
    return line


def unchanged_state(step):
    """A step that computes as usual but returns its state unchanged."""

    def broken(params, opt_state, batch):
        _, _, metrics = step(params, opt_state, batch)
        return params, opt_state, metrics

    return broken


def half_batch(step):
    """A step that leaves half of the batch out and averages over the rest."""
    import jax

    def broken(params, opt_state, batch):
        return step(params, opt_state, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch))

    return broken


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = tiny_run(cell)
    assert line["correct"], line["checks"]
    assert line["device"]["platform"] == "cpu" and line["attempted"] > 0


@pytest.mark.parametrize("fault", [unchanged_state, half_batch], ids=["unchanged_state", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_step_is_not_correct(cell, fault, monkeypatch):
    line = tiny_run(cell, fault, monkeypatch)
    assert not line["correct"], line["checks"]
