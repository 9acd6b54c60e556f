"""The comparison that decides ``correct``: what every family's check shares.

What the timed path produced in its first steps (the batches the step
received, the losses it reported, its optimizer state after one step and its
parameters after three) is set against the plain references of the cell's
family (``bench/families/<family>.py``, whose ``compare`` adds the numbers of
its own samples, such as the batches' rows). Each number is compared with a
limit of its own, kept per cell in ``bench/limits/<cell>.json``. The numbers
of a training step, from :func:`train_numbers`:

* ``loss_gap`` -- the largest relative gap of a step's loss.
* ``grad_gap`` -- the first gradient, as the optimizer got it (clipped),
  recovered from the first moment after one step (``mu / (1 - beta1)``): the
  worst leaf's gap between the program's norm and the reference's, over the
  larger of that leaf's reference norm and the median leaf's.
* ``update_gap`` -- the same for the parameters' change over three steps,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (they move by round-off alone).
* ``grad_gap_median``, ``update_gap_median`` -- the median leaf's gap of
  each: steady from seed to seed where the worst leaf swings, and the
  numbers that tell a step run in a lower precision from the program's.

A cell's limits file names the numbers it compares.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

CHECKED_STEPS = 3


def host_leaves(tree) -> List[np.ndarray]:
    """A state's leaves as host arrays, in the tree's order."""
    import jax

    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tree))]


def max_abs(a: np.ndarray, b: np.ndarray) -> float:
    if a.shape != b.shape:
        return float("inf")
    return float(np.abs(a.astype(np.float64) - b).max())


def leaf_gaps(prog: List[np.ndarray], ref: List[np.ndarray],
              keep: Sequence[bool] = ()) -> np.ndarray:
    """Per leaf: |norm(prog) - norm(ref)| / max(norm(ref), median norm(ref));
    leaves not kept read NaN."""
    if len(prog) != len(ref):
        return np.full(len(ref), np.inf)
    pn = np.array([np.linalg.norm(p.astype(np.float64)) for p in prog])
    rn = np.array([np.linalg.norm(r.astype(np.float64)) for r in ref])
    keep = np.asarray(keep, bool) if len(keep) else np.ones(len(rn), bool)
    med = float(np.median(rn[keep]))
    return np.where(keep, np.abs(pn - rn) / np.maximum(rn, med), np.nan)


def gap_leaves(prog: Dict, ref: Dict, beta1: float):
    """Per-leaf gaps of the first gradient and of the change over three
    steps (NaN where a leaf is left out)."""
    g_prog = [m / (1.0 - beta1) for m in prog["mu1"]]
    rnorm = np.array([np.linalg.norm(g.astype(np.float64)) for g in ref["g1"]])
    moving = rnorm >= 1e-3 * np.median(rnorm)
    d_prog = [a.astype(np.float64) - b for a, b in zip(prog["p3"], prog["p0"])]
    d_ref = [a.astype(np.float64) - b for a, b in zip(ref["p3"], ref["p0"])]
    return leaf_gaps(g_prog, ref["g1"]), leaf_gaps(d_prog, d_ref, moving)


def train_numbers(prog: Dict, ref: Dict, beta1: float) -> Dict[str, float]:
    """The training step's numbers. ``prog``/``ref`` hold ``losses``, ``p0``
    and ``p3`` (parameter leaves before step 1 and after step 3) and, for the
    program, ``mu1`` (first-moment leaves after step 1), for the reference
    ``g1`` (its clipped first gradient)."""
    grad, update = gap_leaves(prog, ref, beta1)
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))) if len(lp) == len(lr)
        else float("inf"),
        "grad_gap": float(np.nanmax(grad)),
        "update_gap": float(np.nanmax(update)),
        "grad_gap_median": float(np.nanmedian(grad)),
        "update_gap_median": float(np.nanmedian(update)),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is finite and within its limit, and every
    limit has its number."""
    return all(k in numbers and np.isfinite(numbers[k]) and numbers[k] <= lim
               for k, lim in limits.items())
