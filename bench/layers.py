"""Shared arithmetic of the per-layer readers: span seconds per campaign,
and the model FLOPs a campaign's records imply."""
from __future__ import annotations

from typing import List, Optional

from bench import flops

# the program's campaign-loop spans, and the layer spans nested in them
LOOP_SPANS = ("bootstrap", "iteration", "commit")
LAYER_SPANS = ("fit", "sweep", "kcenter")


def measured_runs(data) -> List:
    """Committed window campaigns not under the profiler (all of them when
    the profiled one is the only one)."""
    runs = [r for r in data["runs"] if r.committed]
    rest = [r for r in runs if r is not data["profiled"]]
    return rest or runs


def mean_span(data, names) -> Optional[float]:
    runs = measured_runs(data)
    if not runs:
        return None
    return sum(sum(r.spans.get(n, 0.0) for n in names)
               for r in runs) / len(runs)


def campaign_flops(run, cell) -> float:
    """Model FLOPs the campaign's records imply: every retrain over its
    labeled rows, the test-set pass after each retrain, the ranking pass
    over the unlabeled rows before each acquisition, and the commit's
    passes over the test set and the rest of the pool."""
    c, lab = cell.config, cell.config["labeler"]
    macs = cell.labeler.macs_per_row(c)
    pool, T = c["pool"], len(run.T_idx)
    total = 0.0
    for n in run.train_sizes:
        total += flops.fit_flops(n, lab["epochs"], macs)
        total += flops.score_flops(T, macs)
    for n in run.train_sizes[:-1]:   # each acquisition follows a retrain
        total += flops.score_flops(pool - T - n, macs)
    # the commit scores the test set and every row outside the labeled set
    total += flops.score_flops(pool - run.train_sizes[-1], macs)
    return total
