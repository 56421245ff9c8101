"""The harness refuses to measure without a TPU, and without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def test_no_tpu_exits_nonzero_before_any_measurement(capsys, monkeypatch):
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    built = []
    monkeypatch.setattr(harness, "Env",
                        lambda *a, **k: built.append(a) or None)
    rc = harness.main(["--workload", "cifar10-r18feat.margin", "--seed",
                       str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in out.err
    assert not out.out.strip() and not built


def test_device_check_never_falls_back_to_the_cpu():
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(harness.NoDevice):
        harness.device_check(1)


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "cifar10-r18feat.margin", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_every_cell_loads_with_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert os.path.exists(os.path.join(
            harness.BENCH, "drivers", cell.traffic["driver"] + ".py"))
        for m in cell.per_layer:
            assert os.path.exists(os.path.join(
                harness.BENCH, "metrics", m["name"] + ".py")), m["name"]
        assert set(cell.limits["numbers"]) >= {"fit_step1_loss_gap", "pool_error"}


# each key a cell's files may ask for, ``arch`` for a labeler family that
# has no module in ``bench/labelers``
@pytest.mark.parametrize("part,key", [
    ("labeler", "arch"), ("labeler", "dtype"), ("traffic", "annotation"),
    ("traffic", "mode")])
def test_cell_asking_for_what_the_harness_does_not_run_is_refused(
        tmp_path, part, key):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    name = "cifar10-r18feat.margin"
    harness.load_cell(name, root=str(tmp_path))
    path = (tmp_path / "bench" / "traffic" / "margin.json" if
            part == "traffic" else
            tmp_path / "bench" / "configs" / "cifar10-r18feat.json")
    data = json.loads(path.read_text())
    (data if part == "traffic" else data["labeler"])[key] = "other"
    path.write_text(json.dumps(data))
    with pytest.raises(ValueError, match=key):
        harness.load_cell(name, root=str(tmp_path))
