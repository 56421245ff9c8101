"""Launcher-level smoke tests for the labeling campaign CLI.

The paper benchmarks every M(.) metric including the random baseline;
the launcher must accept exactly the selection module's metric set plus
``random`` (previously missing from the argparse choices).
"""
import pytest

from repro.core import selection
from repro.launch.label import METRIC_CHOICES, build_parser


def test_metric_choices_cover_selection_metrics_plus_random():
    assert set(METRIC_CHOICES) == set(selection.METRICS) | {"random"}


@pytest.mark.parametrize("metric", sorted(set(selection.METRICS) |
                                          {"random"}))
def test_launcher_accepts_every_metric(metric):
    args = build_parser().parse_args(["--metric", metric])
    assert args.metric == metric


def test_launcher_rejects_unknown_metric():
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--metric", "bogus"])


def test_launcher_defaults():
    args = build_parser().parse_args([])
    assert args.metric == "margin" and args.service == "amazon"
    assert not args.live and args.budget is None
    assert args.sweep_page == 8192 and not args.sweep_async


def test_launcher_sweep_flags():
    args = build_parser().parse_args(["--sweep-page", "4096",
                                      "--sweep-async"])
    assert args.sweep_page == 4096 and args.sweep_async


def test_launcher_fit_flags():
    args = build_parser().parse_args([])
    assert args.fit_fused and not args.fit_async and not args.fit_resident
    args = build_parser().parse_args(["--no-fit-fused"])
    assert not args.fit_fused
    args = build_parser().parse_args(["--fit-async", "--fit-resident"])
    assert args.fit_async and args.fit_resident


def test_launcher_state_flags():
    args = build_parser().parse_args([])
    assert args.state == "" and args.sweep_ckpt_pages == 0
    assert args.iters_per_run == 0
    args = build_parser().parse_args(
        ["--state", "/tmp/s.json", "--sweep-ckpt-pages", "4",
         "--iters-per-run", "2"])
    assert args.state == "/tmp/s.json" and args.sweep_ckpt_pages == 4
    assert args.iters_per_run == 2


def test_launcher_annotation_flags():
    args = build_parser().parse_args([])
    assert args.annotator_noise == 0.0 and args.annotator_workers == 5
    assert args.label_repeats == 1 and not args.adaptive_repeats
    assert args.annotator_aggregate == "majority" and args.max_repeats == 0
    args = build_parser().parse_args(
        ["--annotator-noise", "0.2", "--label-repeats", "3",
         "--annotator-workers", "7", "--annotator-spammers", "0.1",
         "--annotator-aggregate", "ds", "--adaptive-repeats",
         "--max-repeats", "5", "--repeat-confidence", "0.8"])
    assert args.annotator_noise == 0.2 and args.label_repeats == 3
    assert args.annotator_workers == 7 and args.annotator_spammers == 0.1
    assert args.annotator_aggregate == "ds" and args.adaptive_repeats
    assert args.max_repeats == 5 and args.repeat_confidence == 0.8


def test_launcher_rejects_unknown_aggregator():
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--annotator-aggregate", "mode"])


def test_build_annotation_off_for_perfect_oracle():
    from repro.core import AMAZON
    from repro.launch.label import build_annotation
    args = build_parser().parse_args([])
    assert build_annotation(args, 10, AMAZON) is None


def test_build_annotation_constructs_service():
    from repro.core import AMAZON
    from repro.launch.label import build_annotation
    args = build_parser().parse_args(
        ["--annotator-noise", "0.2", "--label-repeats", "3",
         "--annotator-aggregate", "ds"])
    svc = build_annotation(args, 10, AMAZON)
    assert svc is not None
    assert svc.policy.repeats == 3 and svc.policy.aggregator == "ds"
    assert svc.pricing is AMAZON
    assert svc.pool.cfg.num_classes == 10
    q = svc.expected_quality()
    assert q.avg_repeats == 3.0 and q.residual_error > 0.0
    # repeats alone (no noise) still needs the service: votes are charged
    args = build_parser().parse_args(["--label-repeats", "2"])
    assert build_annotation(args, 10, AMAZON) is not None


def test_launcher_mesh_flag_and_parse():
    from repro.launch.label import build_mesh
    args = build_parser().parse_args([])
    assert args.mesh == "" and build_mesh("") is None
    args = build_parser().parse_args(["--mesh", "data=1"])
    assert args.mesh == "data=1"
    mesh = build_mesh("data=1")
    assert mesh.axis_names == ("data",)
    assert mesh.devices.shape == (1,)


def test_mesh_campaign_smoke_under_forced_host_devices(tmp_path):
    """ROADMAP open item: --mesh data=N builds the host mesh and hands it
    to the scoring + fit engines.  One live iteration under 4 forced host
    devices must run and checkpoint (subprocess: device count is fixed at
    first jax init, so the flag cannot be set in-process)."""
    import json
    import os
    import subprocess
    import sys

    state = tmp_path / "mesh_state.json"
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"),
               PYTHONPATH="src" + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.label", "--live",
         "--pool", "400", "--classes", "4", "--mesh", "data=4",
         "--iters-per-run", "1", "--state", str(state)],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    report = json.loads(out.stdout)
    assert report["resumable"] and os.path.exists(state)


def test_run_campaign_state_file_preempt_and_resume(tmp_path):
    """Launcher-level fault tolerance: a campaign preempted by
    --iters-per-run resumes from its --state file and finishes with the
    economics of an uninterrupted run; the state file is consumed on
    completion."""
    import os

    import numpy as np
    import pytest as _pytest

    from repro.core import AMAZON, MCALConfig, make_emulated_task
    from repro.launch.label import run_campaign

    cfg = MCALConfig(seed=0)
    state = str(tmp_path / "state.json")

    def task():
        return make_emulated_task("cifar10", "resnet18", seed=0,
                                  pool_size=4000, sweep_page=512)

    plain, _ = run_campaign(task(), AMAZON, cfg)

    res, camp = run_campaign(task(), AMAZON, cfg, state_path=state,
                             iters_per_run=2)
    assert res is None and os.path.exists(state)   # preempted, resumable
    hops = 1
    while res is None:
        res, camp = run_campaign(task(), AMAZON, cfg, state_path=state,
                                 sweep_ckpt_pages=2, iters_per_run=2)
        hops += 1
        assert hops < 50
    assert hops > 1                                # actually resumed
    assert not os.path.exists(state)               # spent on completion
    assert res.total_cost == _pytest.approx(plain.total_cost, rel=1e-9)
    assert res.S_size == plain.S_size and res.B_size == plain.B_size
    np.testing.assert_array_equal(res.labels, plain.labels)
    # the full iteration trace survives the hops (history is persisted)
    assert len(res.history) == len(plain.history)
    assert [r.cstar for r in res.history] == \
        [r.cstar for r in plain.history]
    assert [r.B_size for r in res.history] == \
        [r.B_size for r in plain.history]


def test_run_campaign_resume_preserves_random_metric_stream(tmp_path):
    """--metric random draws from the campaign RNG; the persisted
    bit-generator state makes a preempted run's acquisitions identical
    to an uninterrupted one."""
    import numpy as np
    import pytest as _pytest

    from repro.core import AMAZON, MCALConfig, make_emulated_task
    from repro.launch.label import run_campaign

    cfg = MCALConfig(seed=0, metric="random", max_iters=8)
    state = str(tmp_path / "state.json")

    def task():
        return make_emulated_task("cifar10", "resnet18", seed=0,
                                  pool_size=4000, sweep_page=512)

    plain, plain_camp = run_campaign(task(), AMAZON, cfg)
    res = None
    while res is None:
        res, camp = run_campaign(task(), AMAZON, cfg, state_path=state,
                                 iters_per_run=2)
    np.testing.assert_array_equal(camp.pool.B_idx, plain_camp.pool.B_idx)
    assert res.total_cost == _pytest.approx(plain.total_cost, rel=1e-9)
    np.testing.assert_array_equal(res.labels, plain.labels)


def test_compile_cache_leaves_a_set_dir_to_jax(monkeypatch, tmp_path):
    import jax
    from repro.launch.cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_ignored_dir(monkeypatch):
    import os

    import jax
    from repro.launch import cache
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cache.DEFAULT_CACHE_DIR == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == cache.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == cache.DEFAULT_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_dryrun_pins_itself_and_its_children_to_the_cpu():
    """The dry-run forces 512 host devices; JAX_PLATFORMS=cpu beside
    XLA_FLAGS keeps it and every --sweep child off an attached chip."""
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c",
         "import os, repro.launch.dryrun; print(os.environ['JAX_PLATFORMS'])"],
        env=env, capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "cpu"
