"""One benchmark run: one cell, one seed, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs``:
the pool and the labeler, whose ``labeler.arch`` names its module in
``bench/labelers``) and a traffic mix (``bench/traffic``: the
acquisition metric, the accuracy target, the labeling service and the
driver that runs the window, ``bench/drivers``).  Set-up fixes the
host allocator's thresholds (:func:`pin_malloc`), generates the pool
from ``--seed``, builds one shared engine bundle, warms every fit
and scoring bucket a campaign on this pool can reach, and runs two
warm-up campaigns.  The window then runs campaigns back to back, each at
a campaign seed of its own, until ``--seconds`` have passed; the
campaign in flight finishes and the window ends at its commit.

Once the window has closed, the checks of :mod:`bench.checks` compare
what the window produced with the plain references, and the run prints
the result line.  With ``--trace 1`` every campaign carries the
program's metrics registry, the first window campaign runs under the
JAX profiler, and the line holds the per-layer metrics
(``bench/metrics``) and the trace's breakdown instead of the end-to-end
ones.
"""
from __future__ import annotations

import argparse
import contextlib
import ctypes
import ctypes.util
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

SEED_MOD = 2 ** 31 - 1   # campaign seeds must fit JAX's 32-bit key seeds
WARM_CAMPAIGNS = 2       # warm-up campaigns in every set-up

# what the harness runs; a cell whose files ask for anything else is
# refused rather than run as something it does not say
SUPPORTED = {("traffic", "mode"): "sync", ("traffic", "annotation"): "oracle",
             ("labeler", "dtype"): "float32"}
# what a labeler module, ``bench/labelers/<labeler.arch>.py``, provides
# (``bench/labelers/mlp.py`` says what each does)
LABELER = ("pool", "engines", "task", "first_losses", "retrain",
           "top1_margin", "macs_per_row")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]*")   # a file name, no path
# glibc's malloc thresholds for the run (mallopt(3)): the values that its
# dynamic rule raises them to after the process frees a 32 MiB block
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3
MALLOC = {M_MMAP_THRESHOLD: 32 << 20, M_TRIM_THRESHOLD: 64 << 20}


class NoDevice(RuntimeError):
    """The machine does not hold the accelerator the cell asks for."""


# -- the cell ------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    labeler: object = None   # the module of ``config["labeler"]["arch"]``


def _for_cell(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def read(*parts):
        with open(os.path.join(root, *parts)) as f:
            return json.load(f)

    cell = Cell(name=name, chips=int(w["chips"]),
                config=read(conf["file"]),
                traffic=read("bench", "traffic", w["traffic"] + ".json"),
                limits=read("bench", "limits", name + ".json"),
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name))
    files = {"traffic": cell.traffic, "labeler": cell.config["labeler"]}
    for (part, key), want in SUPPORTED.items():
        if files[part].get(key) != want:
            raise ValueError(f"{name}: {part} {key}={files[part].get(key)!r}"
                             f" is not run by this harness (only {want!r})")
    cell.labeler = load_labeler(name, cell.config["labeler"].get("arch"),
                                root)
    return cell


def load_labeler(cell: str, arch, root: str = ROOT):
    """The module of labeler family ``arch``, or a ``ValueError`` that
    names the key."""
    if not (isinstance(arch, str) and NAME.fullmatch(arch)
            and os.path.isfile(os.path.join(root, "bench", "labelers",
                                            arch + ".py"))):
        raise ValueError(f"{cell}: labeler arch={arch!r} has no module "
                         f"bench/labelers/{arch}.py")
    mod = load_module("labelers", arch, root)
    missing = [f for f in LABELER if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"{cell}: labeler arch={arch!r}: "
                         f"bench/labelers/{arch}.py lacks {missing}")
    return mod


def load_module(kind: str, name: str, root: str = ROOT):
    """``bench/<kind>/<name>.py`` as a module (drivers, metric readers,
    labelers)."""
    path = os.path.join(root, "bench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the device ------------------------------------------------------------------


def device_check(chips: int) -> Dict:
    """The attached accelerator, or :class:`NoDevice`.  Never falls back
    to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU attached (JAX platform "
                       f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, "
                       f"{len(devs)} attached")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def pin_malloc() -> None:
    """Fix glibc's mmap and trim thresholds for the rest of the process.

    Left dynamic, they start at 128 KiB and rise the first time the
    process frees a large mapped block: while compiling, when the profiler
    stops, or at some point of the window.  Until then every host gather
    of more than 128 KiB maps fresh pages and faults each one in, and a
    campaign takes about a third longer, so a run's speed followed whether
    it compiled.  Fixed at the values the rise ends at, every run starts in
    the state a long-running process reaches."""
    libc = ctypes.CDLL(ctypes.util.find_library("c"))
    for option, value in MALLOC.items():
        if libc.mallopt(option, value) != 1:
            raise RuntimeError(f"mallopt({option}, {value}) refused")


# -- campaigns -----------------------------------------------------------------


@dataclasses.dataclass
class CampaignRun:
    """What one campaign of the window did and produced."""

    seed: int
    t0: float = 0.0
    t1: float = 0.0
    steps_s: List[float] = dataclasses.field(default_factory=list)
    error: str = ""
    decision: str = ""
    labels: Optional[np.ndarray] = None
    machine_mask: Optional[np.ndarray] = None
    B_idx: Optional[np.ndarray] = None
    T_idx: Optional[np.ndarray] = None
    S_size: int = 0
    train_sizes: List[int] = dataclasses.field(default_factory=list)
    losses: List = dataclasses.field(default_factory=list)
    spans: Dict[str, float] = dataclasses.field(default_factory=dict)
    # the registry's counter series, as ``snapshot()["counters"]`` lists
    # them (name, labels, value); ``repro.obs.export.snapshot_counter``
    # sums them by name and labels
    counters: List[Dict] = dataclasses.field(default_factory=list)

    @property
    def committed(self) -> bool:
        return not self.error and self.labels is not None


def annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation("bench:" + name)


def _wrap(obj, attr: str, label: str) -> None:
    """Put a host trace annotation around ``obj.attr``'s calls."""
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        with annotate(label, True):
            return fn(*a, **kw)

    setattr(obj, attr, wrapped)


def span_totals(snapshot) -> Dict[str, float]:
    """Seconds per span name in a snapshot of the program's registry."""
    out: Dict[str, float] = {}
    for h in snapshot["histograms"]:
        if h["name"] == "span_seconds":
            name = h["labels"].get("name", "")
            out[name] = out.get(name, 0.0) + float(h["sum"])
    return out


class Env:
    """The shared state of a run: pool, engine bundle, recorders."""

    def __init__(self, cell: Cell, seed: int, trace: bool):
        self.cell, self.trace = cell, trace
        self.set_pool(seed)
        from repro.core import SERVICES
        self.service = SERVICES[cell.traffic["service"]]
        self.bundle = cell.labeler.engines(cell.config, cell.traffic)
        self._run: Optional[CampaignRun] = None   # the campaign recording
        self._record()

    def set_pool(self, seed: int) -> None:
        """Generate the pool of ``seed`` (rows and ground truth)."""
        self.seed = seed
        self.x, self.y = self.cell.labeler.pool(self.cell.config, seed)

    def _record(self) -> None:
        """Keep what the window's fits return, for the checks once the
        window has closed (references only: nothing is copied or fetched
        inside the window)."""
        fit = self.bundle.fit
        orig_fit = fit.fit

        def fit_recorded(rng, x, y):
            params, losses = orig_fit(rng, x, y)
            if self._run is not None:
                self._run.losses.append(losses)
            return params, losses

        fit.fit = fit_recorded

    def close(self) -> None:
        self.bundle.close()

    # -- warm-up -------------------------------------------------------------

    def fit_buckets(self) -> List[int]:
        """A representative labeled-set size for every fit bucket a
        campaign on this pool can reach (from the seed set up to the whole
        pool but the test set)."""
        from repro.training.fit_device import fit_plan
        c, lab = self.cell.config, self.cell.config["labeler"]
        lo = max(int(round(0.01 * c["pool"])), 8)
        hi = c["pool"] - max(int(round(0.05 * c["pool"])), 16)
        sizes, n = {}, lo
        while True:
            sizes.setdefault(fit_plan(min(n, hi), lab["batch"])[2], min(n, hi))
            if n >= hi:
                break
            n *= 2
        return sorted(sizes.values())

    def score_buckets(self) -> List:
        """Every scoring pack a sweep page (or its last, short page) and
        the test-set pass can take."""
        from repro.core.scoring import pack_shape
        t = self.cell.traffic
        return sorted({pack_shape(r, t["score_microbatch"])
                       for r in range(1, t["sweep_page"] + 1)})

    def warm(self) -> None:
        import jax
        b = self.bundle
        b.fit.warm(self.fit_buckets())
        params = b.model.init(jax.random.key(0))
        b.scoring.warm(params, self.score_buckets())

    # -- one campaign --------------------------------------------------------

    def run_campaign(self, seed: int) -> CampaignRun:
        """One campaign at campaign seed ``seed``, bootstrap to commit, on
        a fresh task of the labeler over the shared bundle."""
        from repro.core import MCALCampaign, MCALConfig
        c, t = self.cell.config, self.cell.traffic
        run = CampaignRun(seed=seed)
        task = self.cell.labeler.task(c, t, self.x, self.y, seed,
                                      self.bundle)
        cfg = MCALConfig(eps_target=t["eps"], metric=t["metric"],
                         l_metric=t["l_metric"], seed=seed)
        camp = MCALCampaign(task, self.service, cfg)
        registry = None
        if self.trace:
            from repro.obs import MetricsRegistry
            registry = MetricsRegistry()
            camp.attach_metrics(registry)
            for attr, label in (("train", "fit"), ("score", "score"),
                                ("predict", "score"),
                                ("topk_candidates", "sweep"),
                                ("kcenter_candidates", "kcenter"),
                                ("anchor_features", "sweep"),
                                ("machine_label_sweep", "sweep")):
                _wrap(task, attr, label)
            _wrap(camp, "search", "search")
        self._run = run
        run.t0 = time.perf_counter()
        try:
            with annotate("campaign", self.trace):
                s = time.perf_counter()
                with annotate("bootstrap", self.trace):
                    camp.bootstrap()
                run.steps_s.append(time.perf_counter() - s)
                while not camp.done:
                    s = time.perf_counter()
                    with annotate("iteration", self.trace):
                        camp.iteration()
                    run.steps_s.append(time.perf_counter() - s)
                s = time.perf_counter()
                with annotate("commit", self.trace):
                    res = camp.commit()
                run.steps_s.append(time.perf_counter() - s)
            run.decision = res.decision
            run.labels = np.asarray(res.labels)
            run.machine_mask = np.asarray(res.machine_mask, bool)
            run.B_idx = np.asarray(camp.pool.B_idx, np.int64)
            run.T_idx = np.asarray(camp.pool.T_idx, np.int64)
            run.S_size = int(res.S_size)
            run.train_sizes = [int(n) for n in camp.train_sizes]
        except Exception as e:   # a failed campaign is counted, not fatal
            run.error = f"{type(e).__name__}: {e}"
        finally:
            run.t1 = time.perf_counter()
            self._run = None
            camp.close()
        if registry is not None:
            snapshot = registry.snapshot()
            run.spans = span_totals(snapshot)
            run.counters = list(snapshot["counters"])
        return run


def window_seeds(seed: int):
    """The campaign seeds of a run: the warm-up campaigns' first, then one
    per window campaign, all distinct."""
    base = seed % SEED_MOD
    i = 0
    while True:
        yield (base + i) % SEED_MOD
        i += 1


# -- the run -------------------------------------------------------------------


def end_to_end(cell: Cell, runs: List[CampaignRun], window_s: float,
               setup_s: float) -> Dict:
    committed = [r for r in runs if r.committed]
    steps = [s for r in runs for s in r.steps_s]
    values = {
        "campaign_s": window_s / max(len(committed), 1),
        "iter_s_p90": (statistics.quantiles(steps, n=10)[-1]
                       if len(steps) >= 2 else max(steps, default=0.0)),
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def traced_layers(cell: Cell, runs: List[CampaignRun],
                  window_s: float, trace_dir: str, setup_compile_s: float,
                  device: Dict) -> (Dict, Dict, float, float):
    """Per-layer metrics, the breakdown, and busy and window seconds of
    the profiled campaign.  Busy time and the breakdown are by device
    program (``XLA Modules``: the fused retrain, the sweep's page program,
    the k-center loop), since operations nest in their loops; kernel time
    is by operation."""
    from bench import flops, trace_reduce as tr
    events = tr.load_xplane(tr.find_xplane(trace_dir))
    lo, hi = tr.window_of(events["host"], "bench:campaign")
    planes = sorted(events["programs"])[:cell.chips]
    programs = [events["programs"][p] for p in planes]
    busy = sum(tr.busy_ns(p, lo, hi) for p in programs) / max(len(planes), 1)
    ops = [e for p in planes for e in events["ops"].get(p, [])]
    data = {
        "cell": cell, "runs": runs, "profiled": runs[0],
        "window_s": window_s, "chips": cell.chips,
        "peak": flops.device_peak(device["kind"]),
        "setup_compile_s": setup_compile_s,
        "device_ops": ops, "trace_window": (lo, hi),
        "busy_s": busy * 1e-9, "traced_s": (hi - lo) * 1e-9,
    }
    metrics = {}
    for m in cell.per_layer:
        value = load_module("metrics", m["name"]).read(data)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    first = programs[0] if programs else []
    breakdown = {
        "device_ops": tr.top_ops(first, lo, hi),
        "idle_gaps": tr.longest_gaps(first, events["host"], lo, hi),
    }
    return metrics, breakdown, busy * 1e-9, (hi - lo) * 1e-9


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None,
         check_device: Callable[[int], Dict] = device_check) -> int:
    """Run one cell and print its result line; returns the exit code.
    ``t_start`` is when the process started (set-up counts from it);
    ``check_device`` is the look for a chip, which tests replace to drive
    a run on the CPU."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    pin_malloc()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"no program under {ROOT}/src/repro", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cell = load_cell(args.workload)
    try:
        device = check_device(cell.chips)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3

    import jax
    from repro.launch.cache import enable_compile_cache
    from bench.compile_stats import CompileStats
    from bench import checks
    cache_dir = enable_compile_cache()
    # cache every program, however fast it compiles, so that a run after
    # the first builds nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    stats = CompileStats()
    t_init = time.perf_counter()
    env = Env(cell, args.seed, trace=bool(args.trace))
    driver = load_module("drivers", cell.traffic["driver"])
    seeds = window_seeds(args.seed)
    trace_dir = None
    try:
        t_env = time.perf_counter()
        env.warm()
        t_warm = time.perf_counter()
        # warm-up campaigns: campaigns take labeled-set sizes of their
        # own, and some programs follow them; a fixed count keeps set-up
        # the same work on every seed
        for _ in range(WARM_CAMPAIGNS):
            built = stats.snapshot()[1]
            warm_run = env.run_campaign(next(seeds))
            print(f"# warm-up campaign seed {warm_run.seed}: "
                  f"{warm_run.decision or warm_run.error}, "
                  f"{stats.snapshot()[1] - built} programs built",
                  flush=True)
        setup_s = time.perf_counter() - t_start
        print(f"# set-up: start to device {t_init - t_start:.3f} s, pool "
              f"and engines {t_env - t_init:.3f} s, bucket warm-up "
              f"{t_warm - t_env:.3f} s, warm-up campaigns "
              f"{time.perf_counter() - t_warm:.3f} s", flush=True)
        c_setup = stats.snapshot()
        on_first = None
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            on_first = _profiler(trace_dir)
        t0 = time.perf_counter()
        runs = driver.run_window(env, seeds, args.seconds, on_first)
        window_s = max(r.t1 for r in runs) - t0
        c_window = stats.snapshot()
        print(f"# window: {len(runs)} campaigns in {window_s:.3f} s; "
              f"programs built in the window: "
              f"{c_window[1] - c_setup[1]} "
              f"({c_window[0] - c_setup[0]:.3f} s, "
              f"{c_window[3] - c_setup[3]} persistent-cache misses); "
              f"set-up {setup_s:.3f} s with {c_setup[1]} programs built "
              f"({c_setup[0]:.3f} s); compile cache {cache_dir}",
              flush=True)
        device["memory_peak_bytes"] = memory_peak(cell.chips)
        print(f"# memory_peak_bytes {device['memory_peak_bytes']}",
              flush=True)
        for r in runs:
            print(f"# campaign seed {r.seed}: "
                  + (f"failed: {r.error}" if r.error else
                     f"{r.decision}, {len(r.steps_s)} steps "
                     f"in {r.t1 - r.t0:.3f} s, labeled set "
                     f"{r.train_sizes[-1]}, machine-labeled "
                     f"{r.S_size}"),
                  flush=True)
        result = {"correct": False, "attempted": len(runs),
                  "failed": sum(not r.committed for r in runs)}
        if args.trace:
            metrics, breakdown, busy_s, traced_s = traced_layers(
                cell, runs, window_s, trace_dir, c_setup[0], device)
            device["busy_s"], device["window_s"] = busy_s, traced_s
        else:
            metrics = end_to_end(cell, runs, window_s, setup_s)
            breakdown = None
        env.close()
        found = checks.run_checks(cell, env.x, env.y, runs)
    finally:
        env.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        stats.close()
    result["correct"] = all(v["value"] <= v["limit"] for v in found.values())
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = found
    for name, v in found.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['value'] <= v['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def _profiler(trace_dir: str):
    """A context-manager factory that traces one campaign into
    ``trace_dir``; a profiler that fails to start fails the run."""
    @contextlib.contextmanager
    def traced():
        import jax
        jax.profiler.start_trace(trace_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    return traced
