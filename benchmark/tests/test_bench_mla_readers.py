"""The latent/expert configuration's readers on a synthetic trace: the
latent kernel's roofline share, the decode step's share of the peak with
the held experts' part from the program's ``model.sync`` counts, and the
rows each held expert sees; each reads nothing where the trace or the
spans do not match the decode steps recorded (CPU)."""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
from types import SimpleNamespace

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import counts  # noqa: E402
import counts_mla  # noqa: E402
import program_spans as ps  # noqa: E402
import reference_mla  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

TABLE = tr.names()
OPS, MODULES = TABLE["ops_line"], TABLE["modules_line"]
CFG = json.loads((BENCH / "configs" / "moonlight-16b-a3b-ep8pp2.json")
                 .read_text())
DIMS = dataclasses.replace(reference_mla.shape_of(CFG["model"]), layers=3)
PEAKS = counts.peaks("TPU v5 lite")
CALLS = [(1.0, [100, 37]), (2.0, [101, 38, 500])]   # two decode steps
SYNC = [{"held_copies": 10, "copies": 24, "moe_layers": 2},
        {"held_copies": 14, "copies": 36, "moe_layers": 2}]


def trace(kernel_calls=6, steps=2):
    ev = [(OPS, "%paged_mla_decode.3 = ...", 1000 * i, 400)
          for i in range(kernel_calls)]
    ev += [(MODULES, "jit__decode_step_impl(1)", 10_000 * i, 5000)
           for i in range(steps)]
    return ev


@pytest.fixture
def view(tmp_path, monkeypatch):
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    spans = [("model.sync", 100 * i, 100 * i + 50, a)
             for i, a in enumerate(SYNC)]
    spans.append(("model.sync", 900, 950, {}))        # a prefill-only step
    events = trace()
    monkeypatch.setattr(ps, "read_file", lambda path: (len(events), spans))
    return SimpleNamespace(trace=events, table=TABLE, t_trace=(0.0, 3.0),
                           trace_root=tmp_path, dims=DIMS, page=16,
                           peaks=PEAKS,
                           capture=SimpleNamespace(decode_calls=CALLS))


def test_the_latent_kernel_has_a_role():
    assert TABLE["kernels"]["mla_decode_attention"] == ["%paged_mla_decode*"]
    assert "%paged_mla_decode.3" not in str(TABLE["kernels"]
                                            ["decode_attention"])


def test_mla_roofline_share(view, capsys):
    least = 0.0
    for _, lens in CALLS:
        f, b = counts_mla.mla_decode_attention(lens, DIMS, page=16,
                                               n_tab=32 if max(lens) > 256
                                               else 8)
        least += 3 * counts.roofline_seconds(f, b, PEAKS)[0]
    got = run.read_metric("mla_decode_attn_roofline", view)
    assert got == pytest.approx(100 * least / 2.4e-6)
    assert "6 kernel calls, bound memory" in capsys.readouterr().out


def test_mla_roofline_reads_nothing_on_a_mismatch(view):
    view.trace = trace(kernel_calls=5)
    assert run.read_metric("mla_decode_attn_roofline", view) is None


def test_decode_step_mfu_counts_the_held_copies(view):
    flops = sum(counts_mla.decode_step_flops(DIMS, lens, 0)
                for _, lens in CALLS) + 2.0 * 3 * DIMS.d_model \
        * DIMS.expert_ff * (10 + 14)
    got = run.read_metric("moe_decode_step_mfu", view)
    assert got == pytest.approx(100 * flops / (10e-6 * PEAKS["bf16_flops"]))


def test_decode_step_mfu_reads_nothing_without_counts(view, monkeypatch):
    monkeypatch.setattr(ps, "read_file", lambda path: (len(view.trace), []))
    assert run.read_metric("moe_decode_step_mfu", view) is None


def test_held_expert_rows(view, capsys):
    # 24 held copies over 2 steps x 2 MoE layers x 8 held experts
    assert run.read_metric("held_expert_rows", view) == pytest.approx(
        24 / (4 * DIMS.held))
    assert "24 of 60 copies" in capsys.readouterr().out


def test_counts_of_the_latent_kernel():
    f, b = counts_mla.mla_decode_attention([16], DIMS, page=16, n_tab=1)
    assert f == 2 * 16 * (576 + 512) * 16
    # one page of 576-wide bf16 rows, read once for key and value
    assert b == 16 * 576 * 2 + 16 * 576 * 2 + 16 * 512 * 4 + 2 * 16 * 4 + 8
