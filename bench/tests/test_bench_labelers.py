"""The labeler seam: a configuration names its labeler family by
``labeler.arch``, and the harness finds the family's module in
``bench/labelers``.  A new family runs a window from new files alone; the
``mlp`` module computes what the benchmark's reference computed before it
moved there."""
import hashlib
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402
from conftest import fake_device, small_cell  # noqa: E402

# a labeler family that is the ``mlp`` family under another name, and
# says when it builds a campaign's task
TWIN = '''"""The mlp labeler under another name."""
import os

from bench.harness import load_module

_mlp = load_module("labelers", "mlp", os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
pool, engines = _mlp.pool, _mlp.engines
first_losses, retrain = _mlp.first_losses, _mlp.retrain
top1_margin, macs_per_row = _mlp.top1_margin, _mlp.macs_per_row


def task(*args):
    print("# twin task", flush=True)
    return _mlp.task(*args)
'''


def _digests(root):
    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_labeler_runs_a_window_from_new_files_alone(
        small, capsys, monkeypatch, tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())

    # new files: the labeler module, a configuration that names it and
    # the limits of a cell on it
    bench = tmp_path / "bench"
    (bench / "labelers" / "twin.py").write_text(TWIN)
    conf = json.loads((bench / "configs" / "cifar10-r18feat.json")
                      .read_text())
    conf["name"], conf["labeler"]["arch"] = "cifar10-twin", "twin"
    (bench / "configs" / "cifar10-twin.json").write_text(json.dumps(conf))
    shutil.copy(bench / "limits" / "cifar10-r18feat.margin.json",
                bench / "limits" / "cifar10-twin.margin.json")
    # new entries: the configuration and the cell
    spec["configs"].append(dict(spec["configs"][0], name="cifar10-twin",
                                file="bench/configs/cifar10-twin.json"))
    spec["workloads"].append(dict(spec["workloads"][0],
                                  name="cifar10-twin.margin",
                                  config="cifar10-twin"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(tmp_path)
    assert {k: after[k] for k in before if k != "BENCHMARK.json"} == \
        {k: v for k, v in before.items() if k != "BENCHMARK.json"}
    old = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert spec[key][:len(old[key])] == old[key]

    monkeypatch.setattr(harness, "load_cell",
                        lambda name: small_cell(name, str(tmp_path)))
    rc = harness.main(["--workload", "cifar10-twin.margin", "--seed",
                       str(2 ** 31 + 13), "--seconds", "0", "--trace", "0"],
                      check_device=fake_device)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"], result["checks"]
    assert {"campaign_s", "iter_s_p90", "setup_s"} <= set(result["metrics"])
    # every campaign, the warm-up ones with the window's, ran on the twin
    tasks = sum(line == "# twin task" for line in out)
    assert tasks == harness.WARM_CAMPAIGNS + result["attempted"]


def test_a_labeler_module_that_lacks_a_function_is_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    labelers = tmp_path / "bench" / "labelers"
    text = (labelers / "mlp.py").read_text()
    (labelers / "mlp.py").write_text(text.replace("def macs_per_row(",
                                                  "def _macs_per_row("))
    with pytest.raises(ValueError, match="arch='mlp'.*macs_per_row"):
        harness.load_cell("cifar10-r18feat.margin", root=str(tmp_path))


# the losses of the first four steps of a retrain at float32, computed at
# this size by the reference before it moved into the ``mlp`` module
PINNED_FIRST_LOSSES = [2.239614486694336, 1.678013801574707,
                       1.4306378364562988, 1.332650899887085]


def test_mlp_reference_reads_as_before_the_move():
    mlp = harness.load_module("labelers", "mlp")
    config = {"pool": 300, "classes": 5, "features": 16, "difficulty": 0.3,
              "hard_frac": 0.25,
              "labeler": {"hidden": 32, "depth": 2, "batch": 64,
                          "epochs": 3, "learning_rate": 0.01,
                          "weight_decay": 1e-4}}
    x, y = mlp.pool(config, 2 ** 31 + 41)
    got = mlp.first_losses(config, 2 ** 31 - 7, x[:200], y[:200], 4)
    np.testing.assert_allclose(got, PINNED_FIRST_LOSSES, rtol=1e-5)
    # the control is the same arithmetic one precision lower
    low = mlp.first_losses(config, 2 ** 31 - 7, x[:200], y[:200], 4,
                           "bfloat16")
    assert 0 < np.max(np.abs(low - got) / got) < 0.02
