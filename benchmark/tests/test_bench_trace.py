"""Trace reduction, peaks and FLOP/byte counts of the benchmark (CPU)."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import counts  # noqa: E402
import trace_reduce as tr  # noqa: E402

TABLE = tr.names()
OPS, MODS = TABLE["ops_line"], TABLE["modules_line"]


# the kernel's op as a v5e trace names it (its HLO text), and an op
# that only takes its result
KERNEL = ("%paged_decode_attention.5 = (f32[11,32,1,80]) custom-call("
          "s32[11,128] %compare_select_fusion.3)")
USER = ("%get-tuple-element.9 = f32[11,32,1,80] get-tuple-element("
        "%paged_decode_attention.5), index=0")


def synthetic():
    """Two decode steps and one prefill on one device, in ns."""
    return [
        (MODS, "jit__decode_step_impl(1)", 0, 1000),
        (OPS, "fusion.1", 0, 400),
        (OPS, KERNEL, 300, 500),                    # overlaps fusion.1
        (MODS, "jit__decode_step_impl(1)", 2000, 1000),
        (OPS, "fusion.1", 2000, 400),
        (OPS, KERNEL, 2400, 500),
        (OPS, USER, 2900, 10),
        (MODS, "jit_forward(7)", 5000, 3000),
        (OPS, "convolution.3", 5000, 3000),
    ]


def test_union_counts_overlap_once():
    assert tr.union_seconds([(0, 10), (5, 10), (30, 5)]) == 20e-9
    assert tr.union_seconds([(0, 10), (2, 3)]) == 10e-9
    assert tr.union_seconds([]) == 0.0


def test_busy_program_and_kernel_time():
    ev = synthetic()
    # ops: [0, 800) + [2000, 2910) + [5000, 8000)
    assert tr.busy_seconds(ev, TABLE) == pytest.approx(4710e-9)
    assert tr.program_time(ev, TABLE, "decode_step") == (2000e-9, 2)
    assert tr.program_time(ev, TABLE, "prefill") == (3000e-9, 1)
    assert tr.kernel_time(ev, TABLE, "decode_attention") == (1000e-9, 2)
    top = tr.top_ops(ev, TABLE, k=2)
    assert top == [["convolution.3", 3000e-9], [KERNEL, 1000e-9]]


def test_idle_gaps_named_by_innermost_host_span():
    ev = synthetic()
    host = [("bench.engine_step", 0, 9000),
            ("bench.driver_idle", 2900, 5000)]
    gaps = tr.idle_gaps(ev, TABLE, host)
    # gaps: [800, 2000) 1200 ns and [2910, 5000) 2090 ns
    assert gaps == [["bench.driver_idle", 2090e-9],
                    ["bench.engine_step", 1200e-9]]
    assert tr.idle_gaps(ev, TABLE, [])[0] == ["unattributed", 2090e-9]


def test_recorded_trace_roundtrip(tmp_path):
    """A trace recorded here (CPU) loads; CPU planes are not devices."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.test_span"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes, host = tr.load(str(tmp_path))
    assert planes == {}
    assert any(n == "bench.test_span" for n, _, _ in host)
    tr.dump([(OPS, "x", 0, 5)], str(tmp_path / "names.tsv"))
    assert (tmp_path / "names.tsv").read_text().startswith(OPS)


def test_peaks_keyed_by_device_kind():
    src = json.loads((BENCH / "peaks.json").read_text())["source"]
    assert "TPU v5e" in src and "Google Cloud" in src
    pk = counts.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_decode_attention_counts_by_hand():
    """qwen2 widths, two rows of 100 and 17 tokens, 16-token pages."""
    H, KV, Dh, page = 12, 2, 128, 16
    flops, nbytes = counts.decode_attention([100, 17], H=H, KV=KV, Dh=Dh,
                                            page=page, n_tab=8)
    assert flops == 4 * 12 * 128 * (100 + 17)
    pages = 7 + 2                           # ceil(100/16) + ceil(17/16)
    kv = 2 * pages * 16 * 2 * 128 * 2               # k and v, bf16
    q = 2 * 12 * 128 * 2
    out = 2 * 12 * 128 * 4 + 2 * 2 * 12 * 4         # f32 acc, m and l
    tables = 2 * 8 * 4 + 2 * 4
    assert nbytes == kv + q + out + tables
    t, bound = counts.roofline_seconds(flops, nbytes,
                                       counts.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_decode_step_flops_by_hand():
    dims = (2, 8, 2, 1, 4, 16, 32, 1e4, 1.0, 1e-6, True, True)
    L, D, H, KV, Dh, F, V = dims[:7]
    per_layer = D * H * Dh * 2 + D * KV * Dh * 2 + 3 * D * F
    assert counts.matmul_params(dims) == L * per_layer + D * V
    f = counts.decode_step_flops(dims, [3, 5])
    assert f == 2 * (L * per_layer + D * V) * 2 + 4 * L * H * Dh * (4 + 6)


def _name_files(root: pathlib.Path, added: dict) -> pathlib.Path:
    """``root`` holding a copy of the name table and the ``added`` files
    (file name -> contents)."""
    shutil.copy(BENCH / "kernel_names.json", root)
    for name, table in added.items():
        (root / name).write_text(json.dumps(table))
    return root


def test_name_files_add_roles(tmp_path):
    """A ``kernel_names.<name>.json`` adds roles by a new file; the
    table's own roles stay as they are."""
    root = _name_files(tmp_path, {
        "kernel_names.mla.json": {
            "programs": {"mla_decode_step": ["jit__mla_decode*"]},
            "kernels": {"mla_decode_attention": ["%mla_decode*"]}},
        "kernel_names.moe.json": {"kernels": {"moe_gmm": ["%gmm*"]}}})
    table = tr.names(root)
    assert table["programs"] == {**TABLE["programs"],
                                 "mla_decode_step": ["jit__mla_decode*"]}
    assert table["kernels"] == {**TABLE["kernels"],
                                "mla_decode_attention": ["%mla_decode*"],
                                "moe_gmm": ["%gmm*"]}
    assert {k: v for k, v in table.items() if k not in tr.ROLE_GROUPS} == \
        {k: v for k, v in TABLE.items() if k not in tr.ROLE_GROUPS}
    ev = [(OPS, "%gmm.3 = custom-call()", 0, 700)]
    assert tr.kernel_time(ev, table, "moe_gmm") == (7e-7, 1)


@pytest.mark.parametrize("added,err", [
    ({"kernel_names.a.json": {"kernels": {"decode_attention": ["%x*"]}}},
     "kernels role 'decode_attention' is defined twice"),
    ({"kernel_names.a.json": {"programs": {"p": ["a*"]}},
      "kernel_names.b.json": {"programs": {"p": ["b*"]}}},
     "kernel_names.b.json: programs role 'p' is defined twice"),
    ({"kernel_names.a.json": {"ops_line": "XLA Ops"}},
     "keys other than"),
], ids=["table-role", "two-files", "other-key"])
def test_a_role_defined_twice_is_an_error(tmp_path, added, err):
    with pytest.raises(ValueError, match=err):
        tr.names(_name_files(tmp_path, added))
