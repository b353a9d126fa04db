"""The control comes out not correct: the reference computed one precision step
below the configuration's (per-tensor-scaled float8 products, float8 e5m2
cotangents), put in the program's place, against the float32 reference.

On the chip ``chipbench/calibrate.py`` reads the same at each cell's own size;
here the models are cut to a size a CPU test holds, their family's ``SMALL``.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import compare, harness  # noqa: E402
from chipbench.drivers.train import ENDLESS, batch_maker, seed_key  # noqa: E402
from chipbench.reference import layout  # noqa: E402
from chipbench.reference.train import run_reference  # noqa: E402

BENCH = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
CELLS = [c["name"] for c in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    c, config, traffic = harness.resolve(BENCH, cell)
    family = layout.family(config["reference"])
    model = dict(config["model"], **family.SMALL)
    B, S, n = 2, 64, int(traffic["check_steps"])
    hyper = {"lr": float(traffic["lr"]), "warmup": int(traffic["lr_warmup"]), "total_steps": ENDLESS}
    key = seed_key(12345)
    make = batch_maker(B, S, model["vocab"])
    batches = [make(jax.random.fold_in(key, 1), k) for k in range(n)]
    weights = lambda: layout.init_params(family, model, jax.random.fold_in(key, 0))  # noqa: E731
    ref = run_reference(config["reference"], model, weights(), batches, hyper)
    control = run_reference(config["reference"], model, weights(), batches, hyper,
                            lowp=jnp.dtype(config["dtypes"]["control"]))
    checks = compare.checks(control, ref, traffic["limits"])
    print("NUMBERS", [(ch.name, ch.value) for ch in checks])
    assert not all(ch.ok for ch in checks), [(ch.name, ch.value, ch.limit) for ch in checks]
