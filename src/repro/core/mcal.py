"""The MCAL driver (paper Alg. 1) + architecture selection + budget variant.

One campaign = one (task, labeling service, MCALConfig).  The loop:

  bootstrap:  human-label a test set T (test_frac) and a random seed set B0
              (delta0_frac); train; measure eps_T(S^theta) over the theta grid.
  iterate:    fit the per-theta truncated power laws and the training-cost
              model from the measurement history; joint-search (|B|, theta)
              for the predicted minimum cost C*; once C* stabilizes
              (|dC*| <= stability_tol) adapt delta (Alg. 1 line 20) and stop
              when |B| has reached B_opt; otherwise acquire delta more
              samples ranked by M(.), human-label, retrain, re-measure.
  bail-out:   if training spend exceeds bailout_frac of the full human-
              labeling cost while no feasible machine labeling exists, label
              everything with humans (the paper's ImageNet behaviour).
  commit:     rank the remaining pool by L(.), machine-label the largest
              prefix the *measured* test-set error curve admits within
              eps_target, human-label the residual.

Cost-accounting convention (Eqn. 1): predicted C = (|X| - |S|) * C_h +
training spend so far + future training cost — human labels for T, B and the
residual are all inside (|X| - |S|).

``select_architecture`` runs several campaigns over a shared pool/ledger
(labels bought once, every candidate trains) until all their C* estimates
stabilize, then continues only the argmin-C* campaign — the paper's
CNN18/Res18/Res50 selection.  ``budget`` in MCALConfig switches the search
to the budget-constrained variant (min error s.t. cost <= budget).
"""
from __future__ import annotations

import contextlib
import dataclasses
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import selection as sel
from repro.core.cost import (CostLedger, LabelQuality, LabelingService,
                             TrainCostModel)
from repro.core.powerlaw import PowerLaw, fit_power_law
from repro.core.search import SearchResult, adapt_delta, budget_search, joint_search
from repro.faults.errors import StragglerTimeout
from repro.trace.store import sanitize as _trace_sanitize

DEFAULT_THETAS = tuple(round(0.05 * i, 2) for i in range(1, 21))

# campaign state_dict schema version.  v1: pre-trace checkpoints (no
# version field); v2: adds "version" + the "trace" append cursor.
STATE_VERSION = 2


@dataclasses.dataclass(frozen=True)
class MCALConfig:
    eps_target: float = 0.05
    thetas: Tuple[float, ...] = DEFAULT_THETAS
    delta0_frac: float = 0.01
    test_frac: float = 0.05
    metric: str = "margin"          # M(.)
    l_metric: str = "margin"        # L(.)
    stability_tol: float = 0.05     # Delta (Alg. 1 line 19)
    beta: float = 0.05              # delta-adaptation slack (line 20)
    bailout_frac: float = 0.10      # exploration tax x%
    bailout_min_s: float = 0.25     # "cannot machine-label any": |S*|/|X| floor
    cost_exponent: int = 1          # per-iteration cost ~ |B|^exponent
    max_iters: int = 200
    min_fit_points: int = 3
    seed: int = 0
    keep_surface: bool = False
    budget: Optional[float] = None  # set -> budget-constrained variant
    sweep_async: bool = False       # overlap the M(.) sweep with the
                                    # host-side fits + joint search
    fit_async: bool = False         # defer each retrain + its measurement
                                    # sweep onto the fit-engine worker,
                                    # synchronizing at the next consumer
    label_quality: Optional[LabelQuality] = None
                                    # noisy annotation-service economics:
                                    # residual aggregated-label error is
                                    # folded into the accuracy target and
                                    # future human labels are priced
                                    # repeats-inclusive in the joint
                                    # search (None = perfect labels)


@dataclasses.dataclass
class IterationRecord:
    i: int
    B_size: int
    delta: int
    eps_theta: Dict[float, float]
    cstar: float
    B_opt: int
    theta_opt: float
    feasible: bool
    stable: bool
    human_spent: float
    training_spent: float
    search: Optional[SearchResult] = None

    def to_dict(self) -> Dict:
        """JSON form — the ``iteration`` trace-event payload and the
        ``state_dict`` history entry.  ``search`` surfaces (the optional
        keep_surface grids) are in-memory only and never serialized."""
        return {
            "i": int(self.i), "B_size": int(self.B_size),
            "delta": int(self.delta),
            "eps_theta": {str(t): float(e)
                          for t, e in self.eps_theta.items()},
            "cstar": float(self.cstar), "B_opt": int(self.B_opt),
            "theta_opt": float(self.theta_opt),
            "feasible": bool(self.feasible), "stable": bool(self.stable),
            "human_spent": float(self.human_spent),
            "training_spent": float(self.training_spent)}

    @classmethod
    def from_dict(cls, d: Dict) -> "IterationRecord":
        return cls(
            i=int(d["i"]), B_size=int(d["B_size"]), delta=int(d["delta"]),
            eps_theta={float(t): float(e)
                       for t, e in d["eps_theta"].items()},
            cstar=float(d["cstar"]), B_opt=int(d["B_opt"]),
            theta_opt=float(d["theta_opt"]), feasible=bool(d["feasible"]),
            stable=bool(d["stable"]),
            human_spent=float(d["human_spent"]),
            training_spent=float(d["training_spent"]))


@dataclasses.dataclass
class MCALResult:
    labels: np.ndarray
    machine_mask: np.ndarray
    ledger: Dict
    history: List[IterationRecord]
    decision: str                  # hybrid | human_all
    B_size: int
    S_size: int
    theta_final: float
    measured_error: float          # vs groundtruth (simulation oracle)
    arch_name: str = ""

    @property
    def total_cost(self) -> float:
        return self.ledger["total"]

    def to_dict(self, with_history: bool = True) -> Dict:
        """JSON form — the ``commit`` trace-event payload.  The label
        arrays stay out (they are the campaign's product, not its
        decision record); ``pool_size`` preserves their shape so
        :meth:`from_dict` round-trips."""
        d = {
            "decision": str(self.decision), "B_size": int(self.B_size),
            "S_size": int(self.S_size),
            "theta_final": float(self.theta_final),
            "measured_error": float(self.measured_error),
            "arch_name": str(self.arch_name),
            "pool_size": int(len(self.labels)),
            "ledger": {k: (int(v) if isinstance(v, (int, np.integer))
                           else float(v))
                       for k, v in self.ledger.items()},
        }
        if with_history:
            d["history"] = [r.to_dict() for r in self.history]
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "MCALResult":
        n = int(d.get("pool_size", 0))
        return cls(
            labels=np.full(n, -1, np.int64),
            machine_mask=np.zeros(n, bool), ledger=dict(d["ledger"]),
            history=[IterationRecord.from_dict(r)
                     for r in d.get("history", [])],
            decision=str(d["decision"]), B_size=int(d["B_size"]),
            S_size=int(d["S_size"]),
            theta_final=float(d["theta_final"]),
            measured_error=float(d["measured_error"]),
            arch_name=str(d.get("arch_name", "")))


def _fitted_payload(laws: Dict[float, PowerLaw],
                    cm: TrainCostModel) -> Dict:
    """The persistable form of one round of power-law/cost fits — shared
    by ``state_dict`` and the ``powerlaw_fit`` trace event so a replayed
    fit is byte-identical to a checkpointed one."""
    return {
        # np.inf (plain power law) is not strict JSON -> None
        "laws": {str(t): {
            "alpha": law.alpha, "gamma": law.gamma,
            "k": None if not np.isfinite(law.k) else law.k,
            "resid_std": law.resid_std, "n_points": law.n_points}
            for t, law in laws.items()},
        "cost_model": {"c_u": cm.c_u, "exponent": cm.exponent},
    }


def oracle_labels(task, idx: np.ndarray) -> np.ndarray:
    """TRUE labels for evaluation only.  Tasks expose ``oracle_labels``
    precisely so measurement never routes through ``human_label`` — with
    a noisy annotation service attached, that path returns aggregated
    noisy votes AND consumes priced annotation requests, so using it as
    the free evaluation oracle both corrupted ``measured_error`` and
    bypassed ``CostLedger.pay_human`` for the requests it burned."""
    fn = getattr(task, "oracle_labels", None)
    return fn(idx) if fn is not None else task.human_label(idx)


class SharedPool:
    """Label store shared across campaigns (arch selection buys labels once)."""

    def __init__(self, pool_size: int, ledger: Optional[CostLedger] = None):
        self.pool_size = pool_size
        self.labels = np.full(pool_size, -1, np.int64)
        self.is_test = np.zeros(pool_size, bool)
        self.in_B = np.zeros(pool_size, bool)
        self.T_idx: Optional[np.ndarray] = None
        self.B_idx: np.ndarray = np.zeros((0,), np.int64)
        self.ledger = ledger or CostLedger()

    def buy_labels(self, task, idx: np.ndarray, service: LabelingService):
        """THE charging site: every purchased label pays through
        ``CostLedger.pay_human`` at the service's tier rates — with an
        annotation service on the task, repeats-inclusive (the per-call
        vote count the service reports, so adaptive-repeats batches are
        charged exactly what they consumed)."""
        idx = np.asarray(idx, np.int64)
        fresh = idx[self.labels[idx] < 0]
        if len(fresh):
            ann = getattr(task, "annotation", None)
            v0 = ann.votes_bought if ann is not None else 0
            self.labels[fresh] = task.human_label(fresh)
            votes = (ann.votes_bought - v0) if ann is not None \
                else len(fresh)
            self.ledger.pay_human(len(fresh), service, votes=votes)

    def unlabeled_candidates(self) -> np.ndarray:
        mask = (~self.is_test) & (~self.in_B)
        return np.nonzero(mask)[0]


class MCALCampaign:
    def __init__(self, task, service: LabelingService, cfg: MCALConfig,
                 shared: Optional[SharedPool] = None):
        self.task = task
        self.service = service
        self.cfg = cfg
        self.pool = shared or SharedPool(task.pool_size)
        self.rng = np.random.default_rng(cfg.seed)
        self.history: List[IterationRecord] = []
        # per-theta (B, eps) measurement history
        self.eps_hist: Dict[float, List[Tuple[int, float]]] = {
            t: [] for t in cfg.thetas}
        self.train_sizes: List[int] = []
        self.train_costs: List[float] = []
        self.delta = 0
        self.cstar_old: Optional[float] = None
        self.stable = False
        self.done = False
        # this campaign's own training spend: C* predictions compare
        # architectures as if each were running alone (the shared ledger
        # still collects every candidate's spend as the exploration tax)
        self.own_training = 0.0
        self.freeze_delta = False   # exploration keeps delta at delta0
        self.decision = "hybrid"
        self.B_opt = 0
        self.theta_opt = 0.0
        # k-center anchor cache: features of B under the CURRENT classifier
        # (invalidated every retrain, rebuilt from B_idx on demand/resume)
        self._anchor_feats: Optional[np.ndarray] = None
        # in-flight async M(.) sweep: (submitted_k, SweepFuture)
        self._pending: Optional[Tuple[int, object]] = None
        # in-flight async retrain + measurement: (|B| at submit, FitFuture)
        self._fit_pending: Optional[Tuple[int, object]] = None
        # memoized power-law/cost fits: (history key, laws, cost model)
        self._fit_models_cache: Optional[Tuple] = None
        # commit-sweep cursor wiring (set by the launcher, not MCALConfig:
        # these are process-local restart plumbing, not campaign policy)
        self.sweep_checkpoint_every = 0          # pages between cursor cuts
        self.on_sweep_checkpoint = None          # callback(SweepCheckpoint)
        self.resume_sweep_checkpoint = None      # cursor to resume from
        # straggler wall budgets for the async folds (seconds; None =
        # wait forever, the pre-resilience behavior).  Launcher-set
        # plumbing like the cursors above (--sweep-timeout/--fit-timeout)
        self.sweep_timeout = None
        self.fit_timeout = None
        self._iter = 0
        # campaign event bus (attach_trace): None = tracing off
        self.trace = None
        # runtime metrics registry (attach_metrics): None = metrics off
        self.metrics = None
        # chaos injector (attach_faults): None = injection off
        self.faults = None
        # streaming health engine (attach_health): None = monitoring off
        self.health = None

    def attach_trace(self, trace) -> None:
        """Wire the campaign event bus through every engine family: this
        driver's decision sites, the shared ledger's charging sites, the
        annotation broker (vote rounds, top-ups, quality snapshots), and
        the task's sweep/fit runtimes (cursor cuts, submit/fold
        timestamps).  Call before ``bootstrap``/``load_state_dict`` so
        the trace opens with the campaign's first event."""
        self.trace = trace
        self.pool.ledger.trace = trace
        self.pool.ledger.trace_name = "campaign"
        ann = getattr(self.task, "annotation", None)
        if ann is not None and hasattr(ann, "attach_trace"):
            ann.attach_trace(trace)
        if hasattr(self.task, "attach_trace"):
            self.task.attach_trace(trace)

    def attach_metrics(self, metrics) -> None:
        """Wire a runtime metrics registry (``repro.obs``) through the
        campaign: loop-phase spans (bootstrap/iteration/commit) here,
        engine hot-path telemetry via the task's ``attach_metrics``, and
        the annotation broker's queue/EM counters.  Orthogonal to
        :meth:`attach_trace` — metric events are OBSERVABILITY_KINDS, so
        an instrumented campaign's decision stream diffs clean against
        an uninstrumented sibling's."""
        self.metrics = metrics
        ann = getattr(self.task, "annotation", None)
        if ann is not None and hasattr(ann, "attach_metrics"):
            ann.attach_metrics(metrics)
        if hasattr(self.task, "attach_metrics"):
            self.task.attach_metrics(metrics)

    def attach_faults(self, faults, retry=None) -> None:
        """Wire a :class:`repro.faults.FaultInjector` (and optional
        :class:`~repro.faults.RetryPolicy`) through every fault site this
        campaign owns: the annotation request path (per-service or
        per-session), the task's sweep/fit broker workers, the trace
        store's flush path, and this driver's own mid-iteration kill
        point.  Call AFTER ``attach_trace``/``attach_metrics`` so fault/
        retry events ride the same surfaces.  All injected telemetry is
        OBSERVABILITY_KINDS — a chaos run whose retries succeed stays
        diff-clean against its fault-free sibling."""
        self.faults = faults
        if self.trace is not None:
            faults.attach_trace(self.trace)
            if hasattr(self.trace, "attach_faults"):
                self.trace.attach_faults(faults)
        if self.metrics is not None:
            faults.attach_metrics(self.metrics)
        ann = getattr(self.task, "annotation", None)
        if ann is not None and hasattr(ann, "attach_faults"):
            ann.attach_faults(faults, retry)
        if hasattr(self.task, "attach_faults"):
            self.task.attach_faults(faults, retry)

    def attach_health(self, health) -> None:
        """Wire a :class:`repro.obs.health.HealthEngine` to this
        campaign's iteration boundary: after every iteration the engine
        samples the ledger/fit state and emits its hysteresis-gated
        ``alert`` events.  Call AFTER ``attach_trace``/``attach_metrics``
        — the engine inherits this campaign's trace and registry unless
        it already has its own.  Alert kinds are OBSERVABILITY_KINDS, so
        a monitored campaign's decision stream diffs clean against a
        monitor-off sibling's."""
        self.health = health
        if health.trace is None and self.trace is not None:
            health.attach_trace(self.trace)
        if health.metrics is None and self.metrics is not None:
            health.attach_metrics(self.metrics)

    def _mspan(self, name: str):
        """A named campaign-phase span, or a no-op context when metrics
        are off (the ``trace is None`` convention, span-shaped)."""
        if self.metrics is None:
            return contextlib.nullcontext()
        return self.metrics.span(name)

    def _emit(self, kind: str, **payload) -> None:
        if self.trace is not None:
            self.trace.emit(kind, **_trace_sanitize(payload))

    # -- bootstrap ----------------------------------------------------------
    def bootstrap(self, *, adopt: bool = False):
        with self._mspan("bootstrap"):
            return self._bootstrap_impl(adopt=adopt)

    def _bootstrap_impl(self, *, adopt: bool = False):
        X = self.task.pool_size
        p = self.pool
        if self.trace is not None:
            # config = campaign policy (decisions must match across
            # sibling runs); runtime = execution mode (scheduling only,
            # normalized out by trace diff)
            cfgd = dataclasses.asdict(self.cfg)
            runtime = {"sweep_async": cfgd.pop("sweep_async"),
                       "fit_async": cfgd.pop("fit_async")}
            self._emit("campaign_begin", config=cfgd, runtime=runtime,
                       pool_size=int(X),
                       arch=getattr(self.task, "arch_name", ""))
        if not adopt:
            T_size = max(int(round(self.cfg.test_frac * X)), 16)
            p.T_idx = self.rng.choice(X, T_size, replace=False)
            p.is_test[p.T_idx] = True
            p.buy_labels(self.task, p.T_idx, self.service)
            delta0 = max(int(round(self.cfg.delta0_frac * X)), 8)
            b0 = self.rng.choice(p.unlabeled_candidates(), delta0,
                                 replace=False)
            p.in_B[b0] = True
            p.B_idx = b0
            p.buy_labels(self.task, b0, self.service)
        self.delta = len(p.B_idx)
        self._emit("bootstrap", T_size=int(len(p.T_idx)),
                   B_size=int(len(p.B_idx)), adopt=bool(adopt))
        self._train_and_measure()

    # -- internals ----------------------------------------------------------
    def _train_and_measure(self):
        p = self.pool
        self._anchor_feats = None   # the representation moves every retrain
        nB = len(p.B_idx)
        if self.cfg.fit_async and hasattr(self.task, "submit_train"):
            # Defer the retrain + its L(.) measurement sweep onto the fit
            # engine's worker thread: the retrain dispatch overlaps the
            # measurement's host-side paging, and in architecture
            # selection every candidate's retrain runs concurrently.
            # The training cost is paid UP FRONT (it must be known
            # without training — deterministic c_u * |B| pricing; a
            # measured-cost task falls through to the synchronous path),
            # so the shared ledger every sibling campaign's records and
            # bailout/budget checks read is never stale while the fit is
            # in flight.  _sync_fit() folds the measurement at the next
            # consumer (the top of iteration()/search()/commit()), so
            # iteration records are identical to the synchronous
            # campaign's.
            c = (self.task.train_cost(nB)
                 if hasattr(self.task, "train_cost") else None)
            if c is not None:
                self._pay_training(nB, c)
                T_idx, labels_T = p.T_idx, p.labels[p.T_idx]

                def measure():
                    stats_T, _ = self.task.score(T_idx)
                    return stats_T, self.task.eval_correct(T_idx, labels_T)

                self._fit_pending = (nB, self.task.submit_train(
                    p.B_idx, p.labels[p.B_idx], then=measure))
                return
        c = self.task.train(p.B_idx, p.labels[p.B_idx])
        self._pay_training(nB, c)
        stats_T, _ = self.task.score(p.T_idx)
        correct = self.task.eval_correct(p.T_idx, p.labels[p.T_idx])
        self._record_measurement(nB, stats_T, correct)

    def _pay_training(self, nB: int, c: float):
        p = self.pool
        p.ledger.pay_training(c)
        self.own_training += c
        self.train_sizes.append(nB)
        self.train_costs.append(c)

    def _record_measurement(self, nB: int, stats_T, correct):
        curve = sel.machine_label_error_curve(
            stats_T, correct, self.cfg.thetas, self.cfg.l_metric)
        for t, e in zip(self.cfg.thetas, curve):
            self.eps_hist[t].append((nB, float(e)))
        # emitted at fold time on the MAIN thread (under fit_async the
        # fold happens at the next consumer), so the decision stream is
        # position-identical to the synchronous campaign's
        self._emit("measure", B=int(nB),
                   eps={str(t): float(e)
                        for t, e in zip(self.cfg.thetas, curve)})

    def _sync_fit(self):
        """Fold an in-flight async retrain (``fit_async``): collect its
        measurement sweep from the worker and record it exactly as the
        synchronous path would have (the training cost was already paid
        at submit time)."""
        if self._fit_pending is None:
            return
        nB, fut = self._fit_pending
        self._fit_pending = None
        try:
            _c, (stats_T, correct) = fut.result(self.fit_timeout)
        except StragglerTimeout:
            if self.metrics is not None:
                self.metrics.inc("straggler_timeouts_total", engine="fit")
            raise
        self._record_measurement(nB, stats_T, correct)

    def _fit_models(self) -> Tuple[Dict[float, PowerLaw], TrainCostModel]:
        """Fit the per-theta truncated power laws + the training-cost
        model, memoized on the measurement-history key (iteration() reads
        the fits several times per loop, and a resumed campaign restores
        the persisted fits into this cache so it starts without refits)."""
        key = (len(self.train_sizes),
               sum(len(v) for v in self.eps_hist.values()))
        if self._fit_models_cache is not None \
                and self._fit_models_cache[0] == key:
            return self._fit_models_cache[1], self._fit_models_cache[2]
        laws = {}
        for t, pts in self.eps_hist.items():
            sizes = [s for s, _ in pts]
            errs = [e for _, e in pts]
            laws[t] = fit_power_law(sizes, errs,
                                    truncated=len(pts) >= self.cfg.min_fit_points)
        cm = TrainCostModel(exponent=self.cfg.cost_exponent).fit(
            self.train_sizes, self.train_costs)
        self._fit_models_cache = (key, laws, cm)
        # once per fresh measurement-history key (the memo guarantees
        # it), so state-saving and non-saving runs emit identically
        self._emit("powerlaw_fit", train_points=int(key[0]),
                   **_fitted_payload(laws, cm))
        return laws, cm

    # -- noisy-annotation economics ---------------------------------------
    def _quality(self) -> LabelQuality:
        return self.cfg.label_quality or LabelQuality()

    def _effective_service(self) -> LabelingService:
        """Future human labels priced repeats-inclusive: what every
        prediction (joint search, delta adaptation, bailout/budget
        thresholds) must use, or machine labeling looks worse than it is
        relative to a fictional one-vote-per-label service."""
        return self._quality().effective_service(self.service)

    def search(self, keep_surface: Optional[bool] = None) -> SearchResult:
        self._sync_fit()
        with self._mspan("search"):
            return self._search_impl(keep_surface)

    def _search_impl(self, keep_surface: Optional[bool]) -> SearchResult:
        """The power-law and cost-model fits, then the joint (or budget)
        search over them (the ``search`` span)."""
        laws, cm = self._fit_models()
        p = self.pool
        kw = dict(pool_size=self.task.pool_size, test_size=len(p.T_idx),
                  current_B=len(p.B_idx), spent=self.own_training,
                  laws=laws, cost_model=cm, delta=self.delta,
                  service=self._effective_service())
        if self.cfg.budget is not None:
            res = budget_search(budget=self.cfg.budget, **kw)
        else:
            # residual aggregated-label error eats into the target: even
            # a perfect classifier measured against service labels cannot
            # beat the annotators, so the machine-label slice must clear
            # the rest
            res = joint_search(
                eps_target=self._quality().effective_target(
                    self.cfg.eps_target),
                keep_surface=self.cfg.keep_surface
                if keep_surface is None else keep_surface, **kw)
        self._emit("search", cost=res.cost, B_opt=int(res.B_opt),
                   theta_opt=float(res.theta_opt),
                   machine_labeled=int(res.machine_labeled),
                   feasible=bool(res.feasible),
                   human_all_cost=res.human_all_cost)
        return res

    # -- one loop body --------------------------------------------------------
    def iteration(self, *, acquire: bool = True,
                  forced_acquisition: Optional[np.ndarray] = None):
        with self._mspan("iteration"):
            rec = self._iteration_impl(acquire=acquire,
                                       forced_acquisition=forced_acquisition)
        if self.metrics is not None:
            self.metrics.inc("campaign_iterations_total")
        if self.health is not None:
            self.health.tick_campaign(self)
        return rec

    def _iteration_impl(self, *, acquire: bool = True,
                        forced_acquisition: Optional[np.ndarray] = None):
        assert not self.done
        if self.faults is not None:
            # the kill point sits BEFORE any mutation of this iteration
            # (and before the async-fit fold), so an InjectedKill here
            # leaves the campaign exactly at the previous iteration's
            # committed state — what the autosave sidecar persists
            self.faults.check("campaign.iteration")
        self._sync_fit()   # fold last iteration's async retrain first:
        p = self.pool      # everything below reads its params/measurement
        X = self.task.pool_size
        # async overlap: launch this iteration's M(.) sweep (device) before
        # the host-side power-law fits + joint search below; acquire()
        # synchronizes at the fold.  The sweep is submitted at the current
        # delta — prefix-stable rankings (top-k, greedy k-center) let
        # acquire() trim to any smaller final take; a larger adapted delta
        # falls back to a synchronous re-rank.
        self._pending = None
        if (acquire and forced_acquisition is None and self.cfg.sweep_async
                and self.cfg.metric != "random"
                and hasattr(self.task, "submit_candidates")):
            cand = p.unlabeled_candidates()
            k = min(self.delta, len(cand))
            if k > 0:
                anchors = (self._anchor_features()
                           if self.cfg.metric == "kcenter" else None)
                self._pending = (k, self.task.submit_candidates(
                    self.cfg.metric, k, cand, anchors=anchors))
        res = self.search()
        self.B_opt, self.theta_opt = res.B_opt, res.theta_opt

        # stability (line 19) + delta adaptation (line 20)
        stable_now = (self.cstar_old is not None and res.cost > 0 and
                      abs(res.cost - self.cstar_old) / res.cost
                      <= self.cfg.stability_tol)
        if stable_now:
            self.stable = True
        self.cstar_old = res.cost

        rec = IterationRecord(
            i=self._iter, B_size=len(p.B_idx), delta=self.delta,
            eps_theta={t: self.eps_hist[t][-1][1] for t in self.cfg.thetas},
            cstar=res.cost, B_opt=res.B_opt, theta_opt=res.theta_opt,
            feasible=res.feasible, stable=self.stable,
            human_spent=p.ledger.human, training_spent=p.ledger.training,
            search=res if self.cfg.keep_surface else None)
        self.history.append(rec)
        self._emit("iteration", **rec.to_dict())
        self._iter += 1

        if self.cfg.budget is not None:
            # budget variant: stop training when the next acquisition would
            # break the budget (reserve the residual human labels' worth).
            # Acquisition labels are priced repeats-inclusive.
            next_spend = (self.delta *
                          self._effective_service().price_per_label +
                          self._fit_models()[1].iteration_cost(
                              len(p.B_idx) + self.delta))
            if p.ledger.total + float(next_spend) > self.cfg.budget:
                self._finish("budget")
                self._drop_pending()
                return rec
        else:
            # bail-out (paper §5.1 footnote): exploration tax exceeded while
            # the classifier still cannot machine-label any meaningful
            # fraction (ImageNet behaviour) -> human-label everything.
            human_all = X * self._effective_service().price_per_label
            no_meaningful_S = (not res.feasible or res.theta_opt == 0.0 or
                               res.machine_labeled < self.cfg.bailout_min_s * X)
            if no_meaningful_S and \
                    p.ledger.training > self.cfg.bailout_frac * human_all:
                self.decision = "human_all"
                self._finish("bailout")
                self._drop_pending()
                return rec

        if self.stable and not self.freeze_delta:
            nd = adapt_delta(
                current_B=len(p.B_idx), B_opt=res.B_opt, cstar=res.cost,
                spent=self.own_training, pool_size=X, test_size=len(p.T_idx),
                machine_labeled=res.machine_labeled,
                cost_model=self._fit_models()[1],
                service=self._effective_service(), beta=self.cfg.beta)
            if nd > 0:
                self.delta = nd

        # Alg. 1 line 9: continue only while growing B is predicted to
        # reduce cost (C* < C(B_opt + delta) <=> B_opt > |B|).  Gated on the
        # fit having min_fit_points and a stable C* so one noisy early fit
        # cannot end the campaign at a bad |B|.  Exploration-frozen
        # campaigns (arch selection) never self-terminate.
        enough = len(self.train_sizes) >= self.cfg.min_fit_points
        if enough and self.stable and res.feasible and \
                res.B_opt <= len(p.B_idx) and not self.freeze_delta:
            self._finish("converged")
            self._drop_pending()
            return rec

        if self._iter >= self.cfg.max_iters:
            self._finish("max_iters")
            self._drop_pending()
            return rec

        if acquire:
            self.acquire(forced_acquisition)
        return rec

    def acquire(self, forced: Optional[np.ndarray] = None):
        """Buy delta labels ranked by M(.), retrain, re-measure.  If
        ``iteration`` launched an async ranking sweep, synchronize here
        (the fold) and trim its prefix-stable ranking to the final take."""
        p = self.pool
        cand = p.unlabeled_candidates()
        pending, self._pending = self._pending, None
        if len(cand) == 0:
            if pending is not None:
                pending[1].cancel()
            self._finish("pool_exhausted")
            return
        if forced is not None:
            if pending is not None:
                pending[1].cancel()
            pick = np.asarray(forced, np.int64)
        else:
            take = min(self.delta, len(cand))
            if self.stable and self.B_opt > len(p.B_idx):
                take = min(take, self.B_opt - len(p.B_idx))
            pick = None
            if pending is not None:
                if take <= pending[0]:
                    try:
                        out = pending[1].result(self.sweep_timeout)
                    except StragglerTimeout:
                        if self.metrics is not None:
                            self.metrics.inc("straggler_timeouts_total",
                                             engine="sweep")
                        raise
                    full = out[0] if isinstance(out, tuple) else out
                    pick = np.asarray(full[:take], np.int64)
                else:   # adapted delta outgrew the submitted sweep
                    pending[1].cancel()
            if pick is None:   # no sweep in flight, or delta grew past it
                pick = self._rank_candidates(take, cand)
        if self.trace is not None:
            # the full index set would dominate the trace; a CRC over the
            # ordered picks still pins the acquisition bit-exactly across
            # sibling runs (sync vs async must select identically)
            pick_arr = np.ascontiguousarray(np.asarray(pick, np.int64))
            self._emit("acquisition", n=int(len(pick_arr)),
                       digest=int(zlib.crc32(pick_arr.tobytes())),
                       forced=bool(forced is not None))
        p.buy_labels(self.task, pick, self.service)
        p.in_B[pick] = True
        p.B_idx = np.concatenate([p.B_idx, pick])
        self._train_and_measure()

    def _finish(self, reason: str):
        """End the loop; the ``done`` event records WHY (budget | bailout
        | converged | max_iters | pool_exhausted | fleet_ceiling |
        quarantined)."""
        self.done = True
        self._emit("done", reason=reason)

    def _drop_pending(self):
        """Cancel (best-effort) and forget an in-flight async M(.) sweep —
        early loop exits must not leave a pool sweep burning the device."""
        if self._pending is not None:
            self._pending[1].cancel()
            self._pending = None

    def _anchor_features(self) -> Optional[np.ndarray]:
        """k-center anchor set: features of the human-labeled set B under
        the CURRENT classifier (the covered set in the live representation
        space).  Cached per training round — the representation moves
        every retrain — and rebuilt from ``B_idx`` alone, so resumed
        campaigns recover it with one feature sweep."""
        p = self.pool
        if len(p.B_idx) == 0:
            return None
        if self._anchor_feats is None:
            if hasattr(self.task, "anchor_features"):
                self._anchor_feats = self.task.anchor_features(p.B_idx)
            else:
                self._anchor_feats = np.asarray(
                    self.task.score(p.B_idx)[1], np.float32)
        return self._anchor_feats

    def _rank_candidates(self, k: int, cand: np.ndarray) -> np.ndarray:
        """M(.): pick ``k`` of ``cand``.  Engine-backed tasks take sweep
        fast paths — uncertainty metrics via the paged device top-k sink
        (no pool-wide stats transfer), k-center via the device greedy
        farthest-point engine over sweep-emitted device features
        (``core.selection_device``); random and tasks without an engine
        fall back to the host reference path."""
        if k <= 0:
            return np.zeros((0,), np.int64)
        if self.cfg.metric in sel.UNCERTAINTY_METRICS and \
                hasattr(self.task, "topk_candidates"):
            return self.task.topk_candidates(self.cfg.metric, k, cand)
        if self.cfg.metric == "kcenter" and \
                hasattr(self.task, "kcenter_candidates"):
            pick, _ = self.task.kcenter_candidates(
                k, cand, anchors=self._anchor_features())
            return pick
        stats = feats = None
        if self.cfg.metric in sel.UNCERTAINTY_METRICS or \
                self.cfg.metric == "kcenter":
            stats, feats = self.task.score(cand)
        anchors = (self._anchor_features() if self.cfg.metric == "kcenter"
                   else None)
        return sel.select_for_training(
            self.cfg.metric, k, stats=stats, features=feats,
            candidates=cand, anchors=anchors, rng=self.rng)

    def propose_acquisition(self, k: int) -> np.ndarray:
        """Rank candidates by this campaign's M(.) without committing."""
        self._sync_fit()
        cand = self.pool.unlabeled_candidates()
        return self._rank_candidates(min(k, len(cand)), cand)

    def _machine_label(self, idx: np.ndarray):
        """L(.): one scoring sweep over ``idx`` -> (rows most-confident-
        first, machine labels row-aligned with ``idx``).  Sweep-capable
        tasks stream ``idx`` through the paged pool-sweep runtime (only
        the rank field + top1 per row reach the host); the predicted
        labels come from the same sweep's top1, so committing a campaign
        costs a single pool pass.  Cursor-capable tasks additionally cut a
        resumable ``SweepCheckpoint`` every ``sweep_checkpoint_every``
        pages (and resume one), so a preempted commit sweep restarts
        mid-pool from the launcher's ``--state`` file."""
        if hasattr(self.task, "machine_label_sweep"):
            kw = {}
            if self.sweep_checkpoint_every or \
                    self.resume_sweep_checkpoint is not None:
                kw = dict(checkpoint=self.resume_sweep_checkpoint,
                          checkpoint_every=self.sweep_checkpoint_every,
                          on_checkpoint=self.on_sweep_checkpoint)
                self.resume_sweep_checkpoint = None   # consumed
            order, pred = self.task.machine_label_sweep(
                idx, self.cfg.l_metric, **kw)
            return np.asarray(order, np.int64), np.asarray(pred, np.int64)
        stats, _ = self.task.score(idx)
        order = sel.rank_for_machine_labeling(stats, self.cfg.l_metric)
        return order, np.asarray(stats.top1, np.int64)

    # -- commit ----------------------------------------------------------------
    def commit(self) -> MCALResult:
        with self._mspan("commit"):
            return self._commit_impl()

    def _commit_impl(self) -> MCALResult:
        self._sync_fit()
        p = self.pool
        X = self.task.pool_size
        remaining = p.unlabeled_candidates()
        machine_mask = np.zeros(X, bool)

        if self.cfg.budget is not None and len(remaining):
            # afford as many residual human labels as the budget allows;
            # machine-label the most confident rest (accuracy is what gives)
            afford = max(self.cfg.budget - p.ledger.total, 0.0)
            n_human = min(
                int(afford / self._effective_service().price_per_label),
                len(remaining))
            m = len(remaining) - n_human
            order, pred = self._machine_label(remaining)
            S_idx = remaining[order[:m]]
            residual = remaining[order[m:]]
            if m:
                p.labels[S_idx] = pred[order[:m]]
                machine_mask[S_idx] = True
            p.buy_labels(self.task, residual, self.service)
            gt = oracle_labels(self.task, np.arange(X))
            return self._emit_commit(MCALResult(
                labels=p.labels.copy(), machine_mask=machine_mask,
                ledger=p.ledger.snapshot(), history=self.history,
                decision="budget", B_size=len(p.B_idx), S_size=int(m),
                theta_final=m / max(len(remaining), 1),
                measured_error=float(np.mean(p.labels != gt)),
                arch_name=getattr(self.task, "arch_name", "")))

        if self.decision == "human_all" or self.theta_opt <= 0.0 \
                or len(remaining) == 0:
            p.buy_labels(self.task, remaining, self.service)
            self.decision = "human_all"
            theta_final, S_size = 0.0, 0
        else:
            # measured (not predicted) feasibility at the final model
            stats_T, _ = self.task.score(p.T_idx)
            correct = self.task.eval_correct(p.T_idx, p.labels[p.T_idx])
            fine = np.linspace(0.01, 1.0, 100)
            curve = sel.machine_label_error_curve(
                stats_T, correct, fine, self.cfg.l_metric)
            S_frac = fine * len(remaining) / X
            # the human-labeled (1 - S/X) share carries the annotation
            # service's residual aggregated-label error; the machine slice
            # must fit in what is left of the target
            overall = S_frac * curve + \
                (1.0 - S_frac) * self._quality().residual_error
            ok = np.nonzero(overall <= self.cfg.eps_target)[0]
            theta_final = float(fine[ok[-1]]) if len(ok) else 0.0
            m = int(round(theta_final * len(remaining)))
            if m <= 0:
                p.buy_labels(self.task, remaining, self.service)
                self.decision = "human_all"
                theta_final, S_size = 0.0, 0
            else:
                order, pred = self._machine_label(remaining)
                S_idx = remaining[order[:m]]
                residual = remaining[order[m:]]
                p.labels[S_idx] = pred[order[:m]]
                machine_mask[S_idx] = True
                p.buy_labels(self.task, residual, self.service)
                S_size = m

        # evaluation oracle — NEVER human_label: with an annotation
        # service that would burn (uncharged) requests and compare against
        # noisy votes (see oracle_labels)
        gt = oracle_labels(self.task, np.arange(X))
        measured_error = float(np.mean(p.labels != gt))
        return self._emit_commit(MCALResult(
            labels=p.labels.copy(), machine_mask=machine_mask,
            ledger=p.ledger.snapshot(), history=self.history,
            decision=self.decision, B_size=len(p.B_idx), S_size=S_size,
            theta_final=theta_final, measured_error=measured_error,
            arch_name=getattr(self.task, "arch_name", "")))

    def _emit_commit(self, res: MCALResult) -> MCALResult:
        """The terminal decision event; flushed immediately — a campaign
        that committed must never lose its commit to the write buffer."""
        if self.trace is not None:
            self._emit("commit", **res.to_dict(with_history=False))
            self.trace.flush()
        return res

    def run(self) -> MCALResult:
        self.bootstrap()
        while not self.done:
            self.iteration()
        return self.commit()

    def close(self) -> None:
        """Idempotent campaign teardown: cancel any in-flight async sweep
        or retrain, then join the task's owned broker threads (shared
        fleet engines stay up — the fleet owns them).  A closed campaign
        keeps its results; only its async machinery is gone."""
        self._drop_pending()
        if self._fit_pending is not None:
            self._fit_pending[1].cancel()
            self._fit_pending = None
        if hasattr(self.task, "close"):
            self.task.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- campaign fault tolerance ------------------------------------------
    def state_dict(self) -> Dict:
        """JSON-serializable loop state: a preempted labeling campaign
        resumes mid-loop from this (the classifier itself is retrained from
        the persisted label set — labels are the expensive thing)."""
        self._sync_fit()
        p = self.pool
        fitted = None
        if self.train_sizes:
            laws, cm = self._fit_models()
            fitted = _fitted_payload(laws, cm)
        state = {
            # schema version: loaders reject anything newer than they
            # understand instead of failing on a missing/renamed key
            "version": STATE_VERSION,
            # fitted power-law/cost state + the engines' pack-shape compile
            # cache keys: a resumed paper-scale replay starts without
            # refits and prewarms its compiled programs upfront.
            "fitted": fitted,
            "pack_keys": (self.task.pack_cache_keys()
                          if hasattr(self.task, "pack_cache_keys")
                          else None),
            # the full iteration trace (minus any keep_surface search
            # payloads) + the acquisition RNG stream: a resumed campaign
            # reports the whole trajectory and --metric random draws
            # continue where the preempted stream stopped.
            "history": [r.to_dict() for r in self.history],
            "rng": self.rng.bit_generator.state,
            # annotation-service runtime state (None without a noisy
            # oracle): per-worker confusion estimates, the pending-request
            # cursor, and the repeats ledger — with the persisted label
            # store this is exactly what makes a preempted noisy-oracle
            # campaign replay future requests bit-identically.
            "annotation": (self.task.annotation.state_dict()
                           if getattr(self.task, "annotation", None)
                           is not None else None),
            "labels": p.labels.tolist(),
            "is_test": np.nonzero(p.is_test)[0].tolist(),
            "B_idx": p.B_idx.tolist(),
            "ledger": p.ledger.as_dict(),
            "eps_hist": {str(t): v for t, v in self.eps_hist.items()},
            "train_sizes": self.train_sizes,
            "train_costs": self.train_costs,
            "delta": self.delta,
            "cstar_old": self.cstar_old,
            "stable": self.stable,
            "own_training": self.own_training,
            "iter": self._iter,
            # decision state: a campaign resumed after bail-out must still
            # know it chose human_all (and an exploration-frozen campaign
            # that it is frozen) — these were silently dropped before.
            "done": bool(self.done),
            "decision": self.decision,
            "B_opt": int(self.B_opt),
            "theta_opt": float(self.theta_opt),
            "freeze_delta": bool(self.freeze_delta),
        }
        # the trace append cursor: flush FIRST so the persisted cursor
        # always points inside the file, then record where appends resume
        # (TraceStore.resume truncates anything the checkpoint never saw)
        if self.trace is not None:
            self._emit("state_save", iter=self._iter,
                       B_size=int(len(p.B_idx)))
            self.trace.flush()
            state["trace"] = {"next_seq": int(self.trace.next_seq)}
        else:
            state["trace"] = None
        return state

    def load_state_dict(self, s: Dict):
        v = int(s.get("version", 1))
        if v > STATE_VERSION:
            raise ValueError(
                f"campaign state has schema version {v}, but this build "
                f"understands at most version {STATE_VERSION} — it was "
                f"written by a newer repro package; resume with that "
                f"version (or re-run the campaign) instead")
        # fold any in-flight async retrain first: discarding its future
        # while the worker still trains would race the resume retrain
        # below on the same task/engine buffers
        self._sync_fit()
        p = self.pool
        p.labels = np.asarray(s["labels"], np.int64)
        p.is_test[:] = False
        p.is_test[np.asarray(s["is_test"], np.int64)] = True
        p.T_idx = np.asarray(s["is_test"], np.int64)
        p.B_idx = np.asarray(s["B_idx"], np.int64)
        p.in_B[:] = False
        p.in_B[p.B_idx] = True
        p.ledger = CostLedger.from_dict(s["ledger"])
        if self.trace is not None:
            # from_dict built a fresh ledger object: re-wire the bus so
            # post-resume charges keep emitting
            p.ledger.trace = self.trace
            p.ledger.trace_name = "campaign"
        ann = getattr(self.task, "annotation", None)
        if ann is not None and s.get("annotation") is not None:
            ann.load_state_dict(s["annotation"])
            if self.trace is not None and hasattr(ann, "attach_trace"):
                ann.attach_trace(self.trace)
        self.eps_hist = {float(t): [tuple(x) for x in v]
                         for t, v in s["eps_hist"].items()}
        self.train_sizes = list(s["train_sizes"])
        self.train_costs = list(s["train_costs"])
        self.delta = int(s["delta"])
        self.cstar_old = s["cstar_old"]
        self.stable = bool(s["stable"])
        self.own_training = float(s["own_training"])
        self._iter = int(s["iter"])
        # decision state (absent in pre-sweep checkpoints -> fresh defaults)
        self.done = bool(s.get("done", False))
        self.decision = str(s.get("decision", "hybrid"))
        self.B_opt = int(s.get("B_opt", 0))
        self.theta_opt = float(s.get("theta_opt", 0.0))
        self.freeze_delta = bool(s.get("freeze_delta", False))
        # iteration trace + acquisition RNG stream (absent in pre-PR4
        # checkpoints -> empty history / reseeded stream, as before)
        self.history = [IterationRecord.from_dict(r)
                        for r in s.get("history", [])]
        if "rng" in s:
            self.rng = np.random.default_rng()
            self.rng.bit_generator.state = s["rng"]
        self._pending = None
        self._fit_pending = None
        # restore the fitted power-law/cost state into the memo cache so
        # the first search() after resume runs without a single refit
        self._fit_models_cache = None
        fitted = s.get("fitted")
        if fitted:
            laws = {float(t): PowerLaw(
                alpha=f["alpha"], gamma=f["gamma"],
                k=np.inf if f["k"] is None else f["k"],
                resid_std=f["resid_std"], n_points=int(f["n_points"]))
                for t, f in fitted["laws"].items()}
            cm = TrainCostModel(c_u=fitted["cost_model"]["c_u"],
                                exponent=int(fitted["cost_model"]["exponent"]))
            key = (len(self.train_sizes),
                   sum(len(v) for v in self.eps_hist.values()))
            self._fit_models_cache = (key, laws, cm)
        # retrain the classifier on the persisted label set
        self._anchor_feats = None
        self.task.train(p.B_idx, p.labels[p.B_idx])
        # prewarm the engines' pack-shape compile caches (best understood
        # as paying the resumed campaign's compiles upfront)
        if hasattr(self.task, "prewarm_caches"):
            self.task.prewarm_caches(s.get("pack_keys"))
        if self.cfg.metric == "kcenter":
            # one feature sweep over B_idx rebuilds the k-center anchor
            # state under the freshly retrained classifier
            self._anchor_features()
        # observability only: replay filters this out, so a preempted-
        # and-resumed campaign's decision stream equals the continuous
        # run's (the resume retrain above charges nothing — its cost was
        # paid before the checkpoint)
        self._emit("resume", iter=self._iter, B_size=int(len(p.B_idx)))


def run_mcal(task, service: LabelingService,
             cfg: MCALConfig = MCALConfig(),
             trace: Optional[object] = None,
             metrics: Optional[object] = None,
             faults: Optional[object] = None,
             retry: Optional[object] = None,
             health: Optional[object] = None) -> MCALResult:
    camp = MCALCampaign(task, service, cfg)
    if trace is not None:
        camp.attach_trace(trace)
    if metrics is not None:
        camp.attach_metrics(metrics)
    if faults is not None:
        camp.attach_faults(faults, retry)
    if health is not None:
        # last: the engine inherits whatever trace/metrics are attached
        camp.attach_health(health)
    return camp.run()


def select_architecture(
    tasks: Dict[str, object], service: LabelingService,
    cfg: MCALConfig = MCALConfig(), max_explore_iters: int = 24,
) -> Tuple[str, MCALResult, Dict[str, List[IterationRecord]]]:
    """Paper §4 extension: explore all candidate classifiers over a shared
    pool until every campaign's C* stabilizes, then continue the argmin-C*
    campaign alone.  Labels are bought once; every candidate pays its own
    training cost into the shared ledger (the exploration tax)."""
    names = list(tasks)
    pool = SharedPool(tasks[names[0]].pool_size)
    camps = {n: MCALCampaign(tasks[n], service, cfg, shared=pool)
             for n in names}
    for c in camps.values():
        c.freeze_delta = True       # exploration stays at delta0
    camps[names[0]].bootstrap()
    for n in names[1:]:
        camps[n].bootstrap(adopt=True)

    def argmin_cstar():
        cs = {n: camps[n].cstar_old if camps[n].cstar_old is not None
              else np.inf for n in names}
        return min(cs, key=cs.get)

    rounds, leader_votes, last_leader = 0, 0, None
    while rounds < max_explore_iters:
        # leader rotates: its M(.) picks the next acquisition for everyone
        leader = camps[names[rounds % len(names)]]
        # elect early once the C* ranking is confidently settled: every
        # campaign has a fit and the argmin is unchanged 3 rounds running
        # ("trains each classifier up to the point where it is able to
        # confidently predict which architecture achieves the lowest cost")
        if all(c.stable for c in camps.values()) or leader_votes >= 3:
            break
        pick = leader.propose_acquisition(leader.delta)
        for i, n in enumerate(names):
            # every campaign adopts the same acquisition; only one mutates B
            camps[n].iteration(acquire=(i == 0), forced_acquisition=pick)
            if i == 0:
                continue
            camps[n]._train_and_measure()
        for c in camps.values():
            # fold async retrains before the election reads the histories
            # (with fit_async every candidate's retrain ran concurrently)
            c._sync_fit()
        cur = argmin_cstar()
        enough = all(len(c.train_sizes) >= cfg.min_fit_points
                     for c in camps.values())
        leader_votes = leader_votes + 1 if (enough and cur == last_leader) else 0
        last_leader = cur
        if any(c.done for c in camps.values()):
            break
        rounds += 1

    for c in camps.values():
        c._sync_fit()
    cstars = {n: camps[n].cstar_old if camps[n].cstar_old is not None
              else np.inf for n in names}
    winner = min(cstars, key=cstars.get)
    wc = camps[winner]
    wc.freeze_delta = False
    wc.stable = False   # re-establish C* stability in the continuation
    while not wc.done:
        wc.iteration()
    result = wc.commit()
    histories = {n: camps[n].history for n in names}
    return winner, result, histories
