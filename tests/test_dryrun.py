"""Dry-run machinery tests.

The full production sweeps run via the CLI (results/ records); here we verify
the machinery end-to-end in a subprocess (XLA device-count forcing must happen
before jax init, hence no in-process test) on the cheapest real cells, plus
unit-test the pieces that don't need 512 devices.
"""

import json
import os
import subprocess
import sys

import pytest

from jax.sharding import AxisType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


def run_dryrun(args, timeout=420):
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", *args],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.slow
def test_dryrun_cheapest_cell_single_pod(tmp_path):
    r = run_dryrun(
        ["--arch", "xlstm-125m", "--shape", "decode_32k", "--mesh", "single", "--out", str(tmp_path)]
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    cell = json.load(open(tmp_path / "xlstm-125m__decode_32k__16x16__tp_fsdp.json"))
    assert cell["status"] == "ok"
    assert cell["chips"] == 256
    assert cell["roofline"]["t_step_s"] > 0
    assert cell["memory_analysis"]["fits_hbm_16g"]
    assert cell["tree_metrics"]["ops"] > 0


@pytest.mark.slow
def test_dryrun_multi_pod_mesh_shards_pod_axis(tmp_path):
    r = run_dryrun(
        ["--arch", "xlstm-125m", "--shape", "decode_32k", "--mesh", "multi", "--out", str(tmp_path)]
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    cell = json.load(open(tmp_path / "xlstm-125m__decode_32k__2x16x16__tp_fsdp.json"))
    assert cell["status"] == "ok"
    assert cell["chips"] == 512


def test_skip_rule_for_full_attention_long_context():
    from repro.launch.dryrun import run_cell

    # applicability check happens before any mesh/jax work
    cell = run_cell("qwen3-4b", "long_500k", False, verbose=False)
    assert cell["status"] == "skip"
    assert "quadratic" in cell["reason"]


def test_batch_shardings_shard_batch_dim_only():
    import jax

    import jax.numpy as jnp

    from repro.launch.dryrun import batch_shardings

    class MeshStub:
        shape = {"data": 2, "model": 1}

    # real 1-device mesh for NamedSharding construction
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    sh = batch_shardings(batch, mesh, ("data",))
    assert sh["tokens"].spec[0] in ("data", ("data",))


def test_state_shardings_prefer_head_axis():
    import jax
    import jax.numpy as jnp

    from repro.launch.dryrun import state_shardings

    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    state = {"scan": {"block0": {"k": jax.ShapeDtypeStruct((12, 4, 128, 16, 64), jnp.bfloat16)}}}
    sh = state_shardings(state, mesh, ("data",))
    spec = sh["scan"]["block0"]["k"].spec
    assert spec[0] is None  # layer-stack axis unsharded
    assert spec[1] in ("data", ("data",))  # batch
    assert spec[3] == "model"  # heads
