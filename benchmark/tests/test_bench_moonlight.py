"""The Moonlight configuration file states its cut, and its cell runs
through the harness on the CPU rehearsal (smoke size, interpreted
kernels), compared with its reference module, in bfloat16 as served and
in float32 (CPU).

The rehearsals run in a copy of the benchmark, so that what they write
stays out of the checkout."""
from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = "moonlight-16b-a3b-ep8pp2"
FILE = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
#: the file's own keys; every other top-level key is the published config's
OWN = {"name", "source", "registry", "reference", "model", "reduced",
       "published", "deployment", "assumed", "departures", "precision",
       "serve", "check"}

# the rehearsal served in float32: the registry's smoke config with a
# float32 compute dtype (and so a float32 KV pool), then the harness's CLI
F32_PROBE = """
import dataclasses, sys
sys.path.insert(0, "benchmark")
import repro.configs as rc
_get = rc.get_config
rc.get_config = lambda name, smoke=False: dataclasses.replace(
    _get(name, smoke=smoke), compute_dtype="float32")
import run
sys.exit(run.main())
"""


def test_the_file_states_the_cut():
    published = {k: v for k, v in FILE.items() if k not in OWN}
    # the published keys at the top level are the model block's, as served
    assert published == {k: FILE["model"][k] for k in published}
    assert set(FILE["reduced"]) == set(FILE["published"])
    for k in FILE["reduced"]:
        assert FILE["published"][k] != published[k]
    assert published["num_hidden_layers"] == 13
    assert FILE["model"]["router_experts"] == FILE["published"][
        "n_routed_experts"] == 64
    assert "16 chips" in FILE["deployment"]
    # the stored row is wider than the 576 values the model keeps
    assert any("640" in d and "576" in d for d in FILE["departures"])


def checkout(tmp_path: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark and its ``BENCHMARK.json``."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    return root


def rehearse(root: pathlib.Path, cmd: list[str]) -> tuple[dict, str]:
    # the copy holds no program: it comes from the repository's src
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, *cmd, "--workload", f"{NAME}.swe-workers",
         "--seed", "11", "--seconds", "8", "--rehearsal"], cwd=root,
        capture_output=True, text=True, env=env, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["rehearsal"] is True, got
    assert got["counts"]["decode_tokens"] > 0, got
    assert got["check"]["max_gap_sd"]["limit"] == FILE["check"]["max_gap_sd"]
    # how many programs finish a turn in the window depends on the CPU's
    # speed; the reference has to read the tokens of at least one
    n = re.search(r"compared (\d+) served tokens of (\d+) programs",
                  out.stdout)
    assert n and int(n[1]) > 0 and int(n[2]) > 0, out.stdout[-2000:]
    return got, out.stdout


def test_the_cell_rehearses_through_the_harness(tmp_path):
    """In bfloat16 the served path decodes and the reference module reads
    every compared token through the router's ties (a near-tied top-6
    choice may go either way in bfloat16): the run reads ``correct``."""
    got, _ = rehearse(checkout(tmp_path), ["benchmark/run.py"])
    assert got["correct"] is True, got


def test_the_cell_reads_correct_in_float32(tmp_path):
    """Served in float32, the whole path (chunked prefill, absorbed
    latent decode, held experts, tier moves) computes the reference's
    function: every compared token is the reference's first choice, so
    the run reads ``correct``. Summation order moves a float32 logit by
    ~1e-6 of its position's deviation; 1e-3 leaves room for a near-tie
    and lies far under the bfloat16 readings."""
    got, _ = rehearse(checkout(tmp_path), ["-c", F32_PROBE])
    assert got["correct"] is True, got
    assert got["check"]["max_gap_sd"]["value"] < 1e-3, got
