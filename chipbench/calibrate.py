#!/usr/bin/env python3
"""Readings that the limits of a training cell are set from; not part of a run.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11 12 13

For each seed, at the cell's own sizes, it runs the float32 reference and,
each in the program's place, compares with it:

* ``control``: the reference with every matrix-product operand rounded to the
  configuration's ``dtypes.control`` (one precision step below what the
  program computes in);
* ``half_batch``: the reference with half of each batch left out of the loss
  (the mean taken over the rest).

A state left unchanged reads 1 on ``change_gap`` by construction and needs
no run.  One JSON line per seed and reading goes to stdout.  The program's
own readings come from the benchmark's runs (their ``checks``).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def half_batch(batch):
    """The batch with the second half of its rows (of its positions, for one row) out of the loss."""
    mask = batch["loss_mask"]
    B, S = mask.shape
    mask = mask.at[B // 2 :].set(0.0) if B > 1 else mask.at[:, S // 2 :].set(0.0)
    return dict(batch, loss_mask=mask)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench import compare, harness

    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell, config, traffic = harness.resolve(bench, args.workload)
    harness.use_program()
    harness.find_devices(cell["chips"])
    harness.setup_jax()
    import jax
    import jax.numpy as jnp

    from chipbench.drivers.train import ENDLESS, batch_maker, seed_key
    from chipbench.reference import layout
    from chipbench.reference.train import run_reference

    model, B, S = config["model"], int(traffic["batch"]), int(traffic["seq_len"])
    n = int(traffic["check_steps"])
    hyper = {"lr": float(traffic["lr"]), "warmup": int(traffic["lr_warmup"]), "total_steps": ENDLESS}
    family = layout.family(config["reference"])
    weights = jax.jit(lambda k: layout.init_params(family, model, k))
    make_batch = batch_maker(B, S, model["vocab"])

    lowp = jnp.dtype(config["dtypes"]["control"])
    for seed in args.seeds:
        key = seed_key(seed)
        wkey, dkey = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
        batches = [make_batch(dkey, k) for k in range(n)]
        t = time.perf_counter()
        ref = run_reference(config["reference"], model, weights(wkey), batches, hyper)
        ref_s = {"all": time.perf_counter() - t, "compile": ref["compile_s"], "steps": ref["steps_s"]}
        runs = {
            "control": run_reference(config["reference"], model, weights(wkey), batches, hyper, lowp=lowp),
            "half_batch": run_reference(config["reference"], model, weights(wkey), [half_batch(b) for b in batches],
                                        hyper),
        }
        for what, got in runs.items():
            print(json.dumps({"workload": args.workload, "seed": seed, "reading": what,
                              "numbers": compare.numbers(got, ref), "reference_s": ref_s,
                              "loss": got["loss"], "reference_loss": ref["loss"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
