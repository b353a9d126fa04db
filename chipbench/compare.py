"""The comparison that decides ``correct`` for a training cell.

The program's first steps are compared with the reference's, from the same
weights and batches:

* ``loss_gap``: the largest |program loss - reference loss| over the steps;
* ``grad_gap``: the first gradient as the optimizer took it (after
  clipping), leaf by leaf: the largest gap between the program's norm and the
  reference's, over the larger of the reference's norm of that leaf and the
  median leaf's;
* ``change_gap``: the same for the change of the parameters over all the
  steps, leaving out leaves whose reference gradient is under a thousandth of
  the median leaf's: those move under AdamW by round-off alone.

A number is compared where the cell's traffic file gives it a limit (``limits``).
"""

from __future__ import annotations

import numpy as np

from chipbench.harness import Check

#: A leaf whose reference gradient norm is under this share of the median
#: leaf's has no change to compare.
STILL_LEAF = 1e-3


def _gap(prog, ref, keep=None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    scale = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / scale))


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    """prog, ref: {"loss": [...], "grad_norms": [...], "change_norms": [...]}."""
    g = np.asarray(ref["grad_norms"], np.float64)
    keep = g >= STILL_LEAF * np.median(g)
    return {
        "loss_gap": float(np.max(np.abs(np.asarray(prog["loss"]) - np.asarray(ref["loss"])))),
        "grad_gap": _gap(prog["grad_norms"], ref["grad_norms"]),
        "change_gap": _gap(prog["change_norms"], ref["change_norms"], keep),
    }


def checks(prog: dict, ref: dict, limits: dict) -> list[Check]:
    """The numbers that have a limit in the cell; a number without one is not
    compared (PERF.md says which, and why)."""
    nums = numbers(prog, ref)
    return [Check(k, nums[k], float(v)) for k, v in limits.items()]
