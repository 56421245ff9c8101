"""From a profiler trace to busy time, idle gaps and kernel time.

:func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into plain event lists; everything else works on those lists, so the
reductions are checked in the tests, on hand-built events and on a small
recorded trace, without a chip.  An event is ``(name, start_ns, duration_ns)``.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]

# host annotations the harness writes around its calls into the layers
ANNOTATION_PREFIX = "bench:"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An operation's name without its HLO text (``%fusion.7 = f32[...]
    fusion(...)`` -> ``%fusion.7``) or a program's without its hash
    (``jit_program(5514...)`` -> ``jit_program``)."""
    name = name.split(" = ", 1)[0]
    return name.split("(", 1)[0] if name.endswith(")") else name


def load_xplane(path: str) -> Dict:
    """``{"ops": {plane: [events]}, "programs": {plane: [events]},
    "host": [events]}`` from a trace: the ``XLA Ops`` and ``XLA Modules``
    lines of every TPU plane (operations, nested in control flow, and the
    whole programs that hold them), and the harness's own host annotations
    (names that start with :data:`ANNOTATION_PREFIX`)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict = {"ops": {}, "programs": {}, "host": []}
    lines = {"XLA Ops": "ops", "XLA Modules": "programs"}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in lines:
                    out[lines[line.name]][plane.name] = [
                        (short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which some device operation ran."""
    spans = clip(union((s, s + d) for _, s, d in events), lo, hi)
    return sum(e - s for s, e in spans)


def idle_gaps(events: Sequence[Event], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` in which no device operation ran."""
    gaps, t = [], lo
    for s, e in clip(union((s, s + d) for _, s, d in events), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_at(host: Sequence[Event], t0: float, t1: float) -> str:
    """The innermost harness annotation that covers the middle of
    ``[t0, t1]`` (its name without the prefix), or ``"outside"``."""
    mid = 0.5 * (t0 + t1)
    best, best_len = "outside", float("inf")
    for name, s, d in host:
        if s <= mid <= s + d and d < best_len:
            best, best_len = name[len(ANNOTATION_PREFIX):], d
    return best


def longest_gaps(events: Sequence[Event], host: Sequence[Event], lo: float,
                 hi: float, top: int = 10) -> List[List]:
    """The ``top`` longest idle gaps, each ``[label, seconds]``, labelled
    by what the harness was doing on the host at the gap's middle."""
    gaps = sorted(idle_gaps(events, lo, hi), key=lambda g: g[0] - g[1])
    return [[label_at(host, s, e), (e - s) * 1e-9] for s, e in gaps[:top]]


def top_ops(events: Sequence[Event], lo: float, hi: float,
            top: int = 10) -> List[List]:
    """Device operations by total time inside ``[lo, hi]``, each
    ``[name, seconds]``."""
    tot: Dict[str, float] = {}
    for name, s, d in events:
        inside = min(s + d, hi) - max(s, lo)
        if inside > 0:
            tot[name] = tot.get(name, 0.0) + inside
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns * 1e-9] for name, ns in ranked]


def op_seconds(events: Sequence[Event], match: str, lo: float,
               hi: float) -> float:
    """Seconds inside ``[lo, hi]`` of device operations whose name holds
    ``match``."""
    return 1e-9 * sum(min(s + d, hi) - max(s, lo) for name, s, d in events
                      if match in name and min(s + d, hi) > max(s, lo))


def window_of(host: Sequence[Event], name: str) -> Tuple[float, float]:
    """``(start, end)`` of the host annotation ``name`` (with prefix)."""
    for n, s, d in host:
        if n == name:
            return s, s + d
    raise KeyError(f"no host annotation {name!r} in the trace")
