"""``chip_smoke.py`` rehearsed on the CPU.

The script itself must refuse to run without a TPU.  Its phases 2-7 (and
the mesh comparison behind ``--chips``) are driven here directly at a
3,000-row, 4-class pool, with the Pallas kernels interpreted, so a wrong
path, argument or check fails here rather than on the chip.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE = (3000, 4, 0)          # pool rows, classes, seed


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def solo(cs, tmp_path_factory):
    work = str(tmp_path_factory.mktemp("chip_smoke"))
    return work, cs.phase_solo(work, *SHAPE)


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--workdir", str(tmp_path / "work")],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert "[1 device] FAIL" in out.stdout and "no TPU attached" in out.stdout
    for line in out.stdout.splitlines():
        assert not line.startswith("{"), line     # no result line
    assert not (tmp_path / "work").exists()       # stopped at phase 1


def test_result_line_is_the_contract(cs, monkeypatch, capsys, tmp_path):
    """With every phase passing, the last line is the device JSON."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "jax": "0.9.0"}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(cs, "CompileStats", lambda: None)
    monkeypatch.setattr(cs, "phase_device", lambda chips: dev)
    for name in ("engines", "solo", "async", "kcenter", "fleet",
                 "profile"):
        monkeypatch.setattr(cs, f"phase_{name}", lambda *a: "stub")
    assert cs.main(["--workdir", str(tmp_path / "work")]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_phase_engines(cs):
    detail = cs.phase_engines(*SHAPE)
    assert "top1 identical" in detail and "argmax flips=0" in detail


def test_phase_solo(solo):
    assert solo[1].startswith("decision=")


def test_phase_async_diffs_clean_against_solo(cs, solo):
    assert cs.phase_async(solo[0], *SHAPE).endswith(
        "trace diff vs sync clean")


def test_phase_kcenter(cs, tmp_path):
    assert "pairwise kernel" in cs.phase_kcenter(str(tmp_path), *SHAPE)


def test_phase_fleet(cs, tmp_path):
    assert cs.phase_fleet(str(tmp_path), *SHAPE).endswith(
        "traces concurrent vs serial clean")


def test_phase_profile_writes_xplane(cs, tmp_path):
    assert "xplane file" in cs.phase_profile(str(tmp_path), *SHAPE)


def test_phase_mesh_against_one_device(cs, tmp_path):
    """The --chips comparison on the one CPU device (a data=1 mesh)."""
    assert "labels equal 3000/3000" in cs.phase_mesh(str(tmp_path), *SHAPE,
                                                     1)
