"""Faults planted beneath a run, to show that the checks catch them.

Each is a context manager that patches the program before the run builds
its engines, and undoes the patch on exit:

* ``unchanged``: every training step returns the state it was given;
* ``half_batch``: every training step sees half its batch, the loss and
  gradient taken as the mean over the rest;
* ``answer``: the commit's machine labels are altered where the sweep
  produces them (each shifted by one class);
* ``last_page``: as ``answer``, for the rows of the commit sweep's last
  page alone;

One chip holds a cell, so the exchange between chips cannot be left out.
"""
from __future__ import annotations

import contextlib

import numpy as np

FAULTS = ("unchanged", "half_batch", "answer", "last_page")


@contextlib.contextmanager
def _patched(obj, attr: str, value):
    orig = getattr(obj, attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        setattr(obj, attr, orig)


def _step_fault(kind: str):
    from repro.training import fit_device
    real = fit_device.make_train_step

    def make(model, tc, mesh=None, jit=True):
        step = real(model, tc, mesh=mesh, jit=False)

        def faulty(state, batch):
            if kind == "half_batch":
                batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return step(state, batch)
            _new, metrics = step(state, batch)
            return state, metrics

        return faulty

    return _patched(fit_device, "make_train_step", make)


def _answer_fault(last_page: bool):
    from repro.core.task import LiveTask
    real = LiveTask.machine_label_sweep

    def faulty(self, idx, metric="margin", **kw):
        order, top1 = real(self, idx, metric, **kw)
        top1 = np.array(top1)
        n = len(top1)
        lo = (n - 1) // self.sweep_page * self.sweep_page if last_page else 0
        top1[lo:] = (top1[lo:] + 1) % self.num_classes
        return order, top1

    return _patched(LiveTask, "machine_label_sweep", faulty)


def plant(kind: str):
    """The context manager that plants fault ``kind``."""
    if kind in ("unchanged", "half_batch"):
        return _step_fault(kind)
    if kind in ("answer", "last_page"):
        return _answer_fault(kind == "last_page")
    raise KeyError(f"unknown fault {kind!r}; known: {FAULTS}")
