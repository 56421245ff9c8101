"""The comparisons that decide ``correct``.

Each number compares what the window produced with the plain reference
of the cell's labeler (``first_losses``, ``retrain`` and ``top1_margin``
of its module in ``bench/labelers``) or with the generator's ground
truth, and has a limit of its own in ``bench/limits/<cell>.json``; a
cell compares the numbers its limits file names.

* ``fit_step1_loss_gap``: every retrain of every window campaign.  The
  reference retrains from scratch at float32 ``HIGHEST`` on the same
  labeled rows from the same campaign seed; the number is the widest
  relative gap between the first step's loss in the fused retrain and in
  the reference.  Later steps are not compared: AdamW's first update is
  about ``lr * sign(g)`` per weight, so gradient entries that rounding
  moves across zero flip a whole step, and the losses of steps 2-4 part
  by as much in sound runs as in the control (``PERF.md``).
* ``label_gap``: the commit's sweep.  The reference retrains on the
  labeled rows of the campaign's last retrain and predicts a class for
  every row the commit machine-labeled; the number is the share whose
  committed label differs, for the worst committed window campaign.
* ``pool_error``: the share of the committed pool whose label differs
  from the ground truth, for the worst committed window campaign.
* ``failed``: window campaigns that raised or did not commit.
* ``human_all_commits``: window campaigns that gave up on machine labels.

:func:`control_readings` reads the same numbers for the control: the
reference computed in bfloat16 put in place of the program's retrain
and commit sweep.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

# a gap that cannot be read at all (a retrain missing, a step missing)
BROKEN = 1.0e9
# the reference follows a retrain's first steps; the check compares the
# first (``fit_step1_loss_gap``), the later ones are read for the look
FIT_STEPS = 4


def fit_loss_gaps(cell, x, y, runs, dtype=None) -> np.ndarray:
    """Widest relative loss gap at each of the first :data:`FIT_STEPS`
    steps over every retrain of the window: the program's losses, or
    with ``dtype`` the reference's own at that precision (the control)."""
    ref_losses = cell.labeler.first_losses
    worst = np.zeros(FIT_STEPS)
    for run in runs:
        if not run.committed:
            continue
        if len(run.losses) != len(run.train_sizes):
            return np.full(FIT_STEPS, BROKEN)
        for r, n in enumerate(run.train_sizes):
            rows = run.B_idx[:n]
            ref = ref_losses(cell.config, run.seed, x[rows], y[rows],
                             FIT_STEPS)
            if dtype is None:
                got = np.asarray(run.losses[r], np.float64)[:FIT_STEPS]
            else:
                got = ref_losses(cell.config, run.seed, x[rows], y[rows],
                                 FIT_STEPS, dtype)
            if len(got) < FIT_STEPS or not np.all(np.isfinite(got)):
                return np.full(FIT_STEPS, BROKEN)
            worst = np.maximum(worst, np.abs(got - ref) / np.abs(ref))
    return worst


class Retrains:
    """The reference's retrains of a window's campaigns, each run once:
    ``get(run, n, dtype)`` retrains from ``run``'s campaign seed on its
    first ``n`` labeled rows and returns the weights."""

    def __init__(self, cell, x, y):
        self.cell, self.x, self.y = cell, x, y
        self._memo = {}

    def get(self, run, n: int, dtype: str = "float32"):
        key = (run.seed, int(n), dtype)
        if key not in self._memo:
            rows = run.B_idx[:n]
            self._memo[key] = self.cell.labeler.retrain(
                self.cell.config, run.seed, self.x[rows], self.y[rows],
                dtype)
        return self._memo[key]

    def top1_margin(self, run, n: int, rows, dtype: str = "float32"):
        """The reference's top-1 class and margin of pool ``rows`` under
        the retrain of ``get(run, n, dtype)``."""
        return self.cell.labeler.top1_margin(self.get(run, n, dtype),
                                             self.x[rows], dtype)


def _committed(runs) -> List:
    return [r for r in runs if r.committed]


def label_gap(refs: Retrains, runs, dtype=None) -> float:
    """Widest share, over the committed window campaigns, of the
    machine-labeled rows whose committed label is not the class that the
    reference's retrain on the rows of the campaign's last retrain
    predicts; with ``dtype``, the share for the
    reference's own retrain and sweep at that precision (the control)."""
    worst = 0.0
    for run in _committed(runs):
        rows = np.nonzero(run.machine_mask)[0]
        if not len(rows):
            continue
        n = run.train_sizes[-1]
        want, _ = refs.top1_margin(run, n, rows)
        if dtype is None:
            got = run.labels[rows]
        else:
            got, _ = refs.top1_margin(run, n, rows, dtype)
        worst = max(worst, float(np.mean(got != want)))
    return worst


def pool_error(y, runs) -> float:
    errs = [float(np.mean(r.labels != y)) for r in runs if r.committed]
    return max(errs) if errs else BROKEN


def readings(cell, x, y, runs: List, refs=None) -> Dict[str, float]:
    """Every number the cell's limits name, read off the window."""
    refs = refs or Retrains(cell, x, y)
    read = {
        "fit_step1_loss_gap":
            lambda: float(fit_loss_gaps(cell, x, y, runs)[0]),
        "label_gap": lambda: label_gap(refs, runs),
        "pool_error": lambda: pool_error(y, runs),
        "failed": lambda: float(sum(not r.committed for r in runs)),
        "human_all_commits": lambda: float(sum(
            r.committed and r.decision != "hybrid" for r in runs)),
    }
    out = {}
    for name in cell.limits["numbers"]:
        if name not in read:
            raise KeyError(f"unknown check {name!r}")
        out[name] = read[name]()
    return out


def run_checks(cell, x, y, runs: List) -> Dict[str, Dict[str, float]]:
    limits = cell.limits["numbers"]
    return {name: {"value": value, "limit": float(limits[name])}
            for name, value in readings(cell, x, y, runs).items()}


def control_readings(cell, x, y, runs: List, dtype: str = "bfloat16",
                     refs=None) -> Dict[str, float]:
    """The control's numbers on the window's own retrains and commits:
    the reference at ``dtype`` in the program's place."""
    refs = refs or Retrains(cell, x, y)
    read = {
        "fit_step1_loss_gap": lambda: float(
            fit_loss_gaps(cell, x, y, runs, dtype=dtype)[0]),
        "label_gap": lambda: label_gap(refs, runs, dtype),
    }
    return {name: read[name]() for name in cell.limits["numbers"]
            if name in read}
