"""The benchmark is driven by data: a configuration, a traffic mix and a
per-layer metric are files found by name (CPU)."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
CONFIGS = sorted((BENCH / "configs").glob("*.json"))

PROBE = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import run, traffic_gen
bench, cell, cfg, traffic = run.load_cell("new-model.new-mix")
progs, off = traffic_gen.schedule(traffic, 5, 10)
view = type("V", (), {"answer": 41})()
print(json.dumps({"config": cfg["name"], "traffic": traffic["name"],
                  "programs": len(progs),
                  "metric": run.read_metric("new_metric", view)}))
"""


def test_new_files_are_found_by_name(tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix, a
    metric and a cell by new files and new entries only; the unchanged
    harness serves them."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    cfg = json.loads((BENCH / "configs" / "qwen2-1.5b.json").read_text())
    cfg["name"] = "new-model"
    (root / "benchmark" / "configs" / "new-model.json").write_text(
        json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "chat-control.json").read_text())
    mix["name"] = "new-mix"
    mix["rate_per_s"] = 1.0
    (root / "benchmark" / "traffic" / "new-mix.json").write_text(
        json.dumps(mix))
    (root / "benchmark" / "metrics" / "new_metric.py").write_text(
        "def read(v):\n    return v.answer + 1\n")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", PROBE,
                          str(root / "benchmark")], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"config": "new-model", "traffic": "new-mix",
                   "programs": round(1.0 * (mix["ramp_s"] + 10)),
                   "metric": 42}
    for p, b in before.items():
        assert p.read_bytes() == b, f"{p} was edited"


# a new architecture's reference module: here the dense one, re-exported,
# with each call of ``gaps`` recorded in the file {log!r}
PROBE_MODULE = '''"""The dense reference, each ``gaps`` call recorded."""
from reference import *  # noqa: F401,F403
from reference import gaps as _gaps


def gaps(*args, **kw):
    with open({log!r}, "a") as f:
        f.write("gaps\\n")
    return _gaps(*args, **kw)
'''


def test_a_new_reference_module_is_found_by_name(tmp_path):
    """A copy of the benchmark gains a reference module, a configuration
    that names it and a cell, by new files and new entries only; the
    unchanged harness serves the cell through the CPU rehearsal, compares
    with that module, and finds the run correct."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    log = tmp_path / "gaps.log"
    (root / "benchmark" / "reference_probe.py").write_text(
        PROBE_MODULE.format(log=str(log)))
    cfg = json.loads((BENCH / "configs" / "stablelm-3b-pp2.json")
                     .read_text())
    cfg.update(name="new-arch", reference="reference_probe")
    (root / "benchmark" / "configs" / "new-arch.json").write_text(
        json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "new-arch", "source": cfg["source"],
                             "file": "benchmark/configs/new-arch.json",
                             "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append({"name": "new-arch.swe-workers",
                               "config": "new-arch",
                               "traffic": "swe-workers", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the copy holds no program: it comes from the repository's src
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "new-arch.swe-workers", "--seed", "11", "--seconds", "8",
         "--rehearsal"], cwd=root, capture_output=True, text=True, env=env,
        timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["rehearsal"] is True and got["correct"] is True, got
    assert log.read_text().count("gaps") > 0, "the probe's gaps never ran"
    for p, b in before.items():
        assert p.read_bytes() == b, f"{p} was edited"


def test_a_reference_module_with_no_file_fails_with_its_name():
    with pytest.raises(run.Failure, match="no_such_reference"):
        run.reference_of({"name": "x", "reference": "no_such_reference"})


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_served_dims_of_the_cut_config_are_the_files(path):
    """The registry config, cut by the file's reference module, serves
    the dims the file states (no engine is built)."""
    from repro.configs import get_config
    cfgf = json.loads(path.read_text())
    ref = run.reference_of(cfgf)
    cfg = ref.program_config(get_config(cfgf["registry"]), cfgf["model"])
    assert ref.served_dims(cfg) == ref.shape_of(cfgf["model"])


def test_every_listed_file_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert (BENCH / "configs" / f"{w['config']}.json").is_file()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_run_outside_a_full_checkout_fails(tmp_path):
    """Only BENCHMARK.json and the benchmark's files: no program, no
    result line, a non-zero exit."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env=env, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_run_without_a_tpu_fails(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "needs 1 TPU chip" in out.stderr
    assert '"correct"' not in out.stdout
