"""The per-layer readers, on a trace built by hand and on one recorded on the chip."""

import glob
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import harness  # noqa: E402
from chipbench.readings import Readings, breakdown  # noqa: E402
from chipbench.trace import Trace, _hlo_paths, in_scope, scope_time  # noqa: E402

BENCH = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6  # ns
ATTN = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim_": 2, "d_ff": 6, "vocab": 10,
        "n_layers": 1, "pattern": ["attn"]}


def read(name, m):
    return harness.load_module(harness.metric_path(name), "chipbench_metric_" + name.replace(".", "_")).read(m)


def hand_trace() -> Readings:
    """10 ms window; three train steps at [0,3], [4,7], [8,9.5] ms."""
    ops = [
        (0, 2 * MS, "fusion.1", "jit(train_step)/train_step/fwd_bwd/jvp(model)/layers/mlp/up_proj/dot_general", 0),
        (2 * MS, 3 * MS, "fusion.2", "jit(train_step)/train_step/fwd_bwd/transpose(jvp(model))/attention/x", 0),
        (4 * MS, 7 * MS, "fusion.3", "jit(train_step)/mlp/down_proj", 0),
        (5 * MS, 6 * MS, "fusion.4", "jit(train_step)/mlp/nested", 0),  # inside fusion.3: counted once
        (8 * MS, 9.5 * MS, "while.5", "jit(train_step)/jvp(slstm)/time_scan/while", 0),
    ]
    programs = [(0, 3 * MS, "jit_train_step", 0), (4 * MS, 7 * MS, "jit_train_step", 0),
                (8 * MS, 9.5 * MS, "jit_train_step", 0), (3.2 * MS, 3.3 * MS, "jit_make", 0)]
    host = [(0, 10 * MS, "chipbench.window", "main"), (3 * MS, 4 * MS, "Trainer.run", "main"),
            (3.1 * MS, 3.9 * MS, "next", "main")]
    tr = Trace(ops=ops, programs=programs, host=host, window=(0.0, 10 * MS))
    traffic = {"batch": 2, "seq_len": 3}
    peaks = {"flops": 1e12, "hbm_bytes_per_s": 1e11}
    return Readings(tr, steps=2, tokens=12, window_s=0.01, model=ATTN, reference="transformer", traffic=traffic,
                    peaks=peaks, counters={"agent": 0.001, "daemon": None})


def test_scope_matching():
    assert in_scope("jit(f)/transpose(jvp(model))/attention/x", "attention")
    assert in_scope("a/jvp(slstm)/time_scan/while", "slstm/time_scan")
    assert not in_scope("a/attention_bias/x", "attention")
    assert not in_scope("a/slstm/in_proj/time_scan", "slstm/time_scan")


def test_hlo_paths_read_a_custom_call_over_lines():
    """The compiled HLO of a Pallas kernel with kernel metadata and a cost
    estimate, for a TPU v5e (described, not attached; its Mosaic body cut): the
    metadata JSON breaks the custom call over four lines."""
    with open(os.path.join(DATA, "pallas-custom-call.hlo")) as f:
        paths = _hlo_paths(f.read())
    assert paths == {
        "add.1": "jit(f)/attention/toy_kernel/add",
        "add.0": "jit(f)/attention/toy_kernel/add",
        "x.1": "x",
        "toy_double.1": "jit(f)/attention/toy_kernel/toy_double/pallas_call",
        "broadcast_add_fusion": "jit(f)/attention/toy_kernel/add",
    }


def test_readers_on_a_hand_built_trace():
    m = hand_trace()
    assert m.busy_s == pytest.approx(7.5e-3)
    assert read("idle_share.train", m) == pytest.approx(25.0)
    assert read("step_gap_ms.train", m) == pytest.approx(1.0)
    assert scope_time(m.trace, "mlp") == pytest.approx(5 * MS)
    # mlp of two steps: 2 * 3 * 144 * 6 = 5184 operations, 2 * (72 * 8 + 2 * 4 * 6 * 4) = 1536 bytes;
    # bytes bound: 1536 / 1e11 s over 5 ms of device time
    assert read("mlp_roofline", m) == pytest.approx(100 * 1536 / 1e11 / 5e-3)
    # 1056 operations a token * 12 tokens / 0.01 s / 1e12
    assert read("mfu.train", m) == pytest.approx(100 * 1056 * 12 / 0.01 / 1e12)
    assert read("profiler.agent_cpu_share", m) == pytest.approx(10.0)
    assert read("profiler.daemon_cpu_share", m) is None  # not found: no number, never 0
    # attention of two steps: 2 * 3 * 128 * 6 = 4608 operations over 1 ms; 2 * (48 * 8 + 2 * 4 * 6 * 4) = 1152
    # bytes.  Bytes bound: 1152 / 1e11 s over 1 ms of device time
    assert read("attention_roofline", m) == pytest.approx(100 * 1152 / 1e11 / 1e-3)
    bd = breakdown(m.trace)
    assert bd["idle_gaps"][0] == ["next", pytest.approx(1e-3)]
    # fusion.3 holds fusion.4, so it is not among the ops; fusion.1 took longest
    assert [k.split()[0] for k, _ in bd["device_ops"]] == ["fusion.1", "while.5", "fusion.2", "fusion.4"]


def test_trace_save_load_and_cut(tmp_path):
    tr = hand_trace().trace
    tr.save(tmp_path / "t.json.gz")
    back = Trace.load(tmp_path / "t.json.gz")
    assert (back.ops, back.programs, back.host, back.window) == (tr.ops, tr.programs, tr.host, tr.window)
    cut = back.cut(4 * MS, 7.5 * MS)
    assert [o[2] for o in cut.ops] == ["fusion.3", "fusion.4"] and cut.window == (4 * MS, 7.5 * MS)
    assert [p[0] for p in cut.programs] == [4 * MS]


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.trace.json.gz")))
#: Per-step metrics that a cut of one step has in common with its whole window.
PER_STEP = ("mfu.train", "attention_roofline", "mlp_roofline")


@pytest.mark.parametrize("path", RECORDED, ids=[os.path.basename(p) for p in RECORDED])
def test_readers_on_a_recorded_trace(path):
    """A traced window recorded on the chip, beside what its readers take
    (``readings``) and give (``metrics``, and ``busy_s``).  A whole window's
    ``metrics`` are what its run printed.  A window cut down to one train step
    (``run_metrics`` beside it: what the run printed for its whole window)
    reads its per-step metrics within a tenth of the whole window's."""
    with open(path.replace(".trace.json.gz", ".result.json")) as f:
        rec = json.load(f)
    m = Readings(Trace.load(path), **rec["readings"])
    assert m.busy_s == pytest.approx(rec["busy_s"], rel=1e-9)
    got = {}
    for spec in BENCH["per_layer"]:
        v = read(spec["name"], m)
        if v is not None:
            got[spec["name"]] = v
    assert got == pytest.approx(rec["metrics"], rel=1e-9)
    for name, v in rec.get("run_metrics", {}).items():
        if name in PER_STEP:
            assert got[name] == pytest.approx(v, rel=0.1), name
