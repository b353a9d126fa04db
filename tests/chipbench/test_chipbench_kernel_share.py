"""``attention.kernel_share`` on the recorded XLA-path traces and on traces built by hand."""

import glob
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402
from chipbench.readings import Readings  # noqa: E402
from chipbench.trace import Trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = sorted(glob.glob(os.path.join(DATA, "*.trace.json.gz")))
MS = 1e6  # ns
LAYER = "jit(train_step)/train_step/fwd_bwd/jvp(loss)/model/layers/while/body/closed_call/unit_block0_attn/block0"
KERNEL = LAYER + "/attention/jit(splash_attention)/splash_attention/vmap(jit(_splash_attention))/splash_mha_fwd/pallas_call"
BACKWARD = KERNEL.replace("jvp(loss)", "transpose(jvp(loss))").replace("splash_mha_fwd", "splash_mha_dkv")


def share(m):
    return harness.load_module(harness.metric_path("attention.kernel_share"), "chipbench_metric_kernel_share").read(m)


def readings(ops) -> Readings:
    tr = Trace(ops=ops, programs=[(0, 10 * MS, "jit_train_step", 0)],
               host=[(0, 10 * MS, "chipbench.window", "main")], window=(0.0, 10 * MS))
    return Readings(tr, steps=1, tokens=4096, window_s=0.01, model={}, traffic={"batch": 1, "seq_len": 4096},
                    peaks={"flops": 1e12, "hbm_bytes_per_s": 1e11})


@pytest.mark.parametrize("path", RECORDED, ids=[os.path.basename(p) for p in RECORDED])
def test_nothing_to_read_on_the_xla_path(path):
    """The recorded windows ran the XLA path: scores and pv, no kernel."""
    with open(path.replace(".trace.json.gz", ".result.json")) as f:
        rec = json.load(f)
    m = Readings(Trace.load(path), **rec["readings"])
    assert any("/attention/scores/" in o[3] for o in m.trace.ops)
    assert share(m) is None


def test_every_layer_in_the_kernel_reads_100():
    m = readings([
        (0, 2 * MS, "splash_mha_fwd.1", KERNEL, 0),
        (2 * MS, 5 * MS, "splash_mha_dkv.1", BACKWARD, 0),
        (5 * MS, 6 * MS, "fusion.7", LAYER + "/attention/out_proj/dot_general", 0),  # outside the core
        (6 * MS, 9 * MS, "fusion.8", LAYER + "/mlp/up_proj/dot_general", 0),
    ])
    assert share(m) == pytest.approx(100.0)


def test_a_layer_that_falls_back_lowers_the_share():
    m = readings([
        (0, 3 * MS, "splash_mha_fwd.1", KERNEL, 0),
        (3 * MS, 4 * MS, "fusion.2", LAYER.replace("block0", "block1") + "/attention/scores/dot_general", 0),
        (4 * MS, 5 * MS, "fusion.3", LAYER.replace("block0", "block1") + "/attention/pv/dot_general", 0),
        (5 * MS, 6 * MS, "while.4", LAYER.replace("block0", "block2") + "/attention/q_chunk_scan/while", 0),
    ])
    assert share(m) == pytest.approx(50.0)
    assert share(readings([])) is None
