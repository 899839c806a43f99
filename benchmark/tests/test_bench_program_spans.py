"""Readers of the program's own spans (``program_spans.py``): idle time
under spans, self time less children, the tier-move rates, on a
synthetic trace; a trace whose device events are not the run's is
refused; a recorded CPU trace has spans and their arguments but no
device plane, so nothing reads (CPU)."""
from __future__ import annotations

import pathlib
import sys
from types import SimpleNamespace

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import program_spans as ps  # noqa: E402
import trace_reduce as tr  # noqa: E402

TABLE = tr.names()
OPS = TABLE["ops_line"]

# device ops, ns: busy [0, 100) [300, 400) [1000, 1100)
TRACE = [(OPS, "fusion.1", 0, 100), (OPS, "fusion.2", 300, 100),
         (OPS, "fusion.3", 1000, 100), ("XLA Modules", "jit_x", 0, 1100)]
SPANS = sorted([
    ("engine.step", 0, 1200, {"prefill_tokens": 0, "decode_rows": 2}),
    ("engine.admit", 10, 700, {}),
    ("sched.schedule", 20, 690, {}),
    ("kv.restore", 50, 650, {"bytes": 600, "padded_bytes": 800,
                             "priced_s": 1e-7}),
    ("kv.restore_pad", 50, 250, {}),
    ("kv.h2d", 250, 500, {}),
    ("kv.scatter", 500, 650, {}),
    ("engine.execute", 700, 1150, {}),
    ("model.sync", 900, 1150, {}),
    ("engine.step", 1300, 1400, {"prefill_tokens": 0, "decode_rows": 0}),
    ("engine.admit", 1310, 1390, {}),
    ("kv.stage_out", 1500, 1700, {"bytes": 400, "padded_bytes": 400}),
    ("kv.gather", 1500, 1520, {}),
    ("kv.d2h", 1520, 1700, {}),
], key=lambda x: (x[1], -x[2]))


@pytest.fixture
def view(tmp_path, monkeypatch):
    """A run's view whose newest trace holds ``SPANS`` and as many device
    events as it reduced."""
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(ps, "read_file", lambda path: (len(TRACE), SPANS))
    return SimpleNamespace(trace=TRACE, table=TABLE, t_trace=(0.0, 1.7e-6),
                           trace_root=tmp_path)


def test_idle_by_innermost_span(view):
    got = ps.idle_by_span(view, SPANS)
    want = {"kv.restore_pad": 150, "kv.h2d": 150, "kv.scatter": 150,
            "sched.schedule": 40, "engine.admit": 90, "engine.execute": 200,
            "model.sync": 150, "engine.step": 70, "none": 200,
            "kv.gather": 20, "kv.d2h": 180}
    assert got == pytest.approx({k: w * 1e-9 for k, w in want.items()})
    assert sum(got.values()) == pytest.approx(1400e-9)   # all idle named


def test_idle_under_the_tier_moves(view):
    # restore [50, 650) less busy [50, 100) and [300, 400); stage-out 200
    assert ps.idle_under(view, SPANS, ps.TIER_MOVES) == pytest.approx(650e-9)
    assert ps.tier_idle_share(view) == pytest.approx(650 / 1700)


def test_move_rates_and_their_split(view, capsys):
    assert ps.move_rate(view, "kv.restore",
                        ("kv.restore_pad", "kv.h2d", "kv.scatter"),
                        priced="priced_s") == pytest.approx(1.0)
    out = capsys.readouterr().out
    assert "1 moves, 600 bytes (padded 800)" in out
    assert "kv.h2d 0.000000 s" in out and "wall over priced 6.00" in out
    assert ps.move_rate(view, "kv.stage_out",
                        ("kv.gather", "kv.d2h")) == pytest.approx(2.0)


def test_sched_time_is_admit_less_its_tier_moves_per_busy_step(view):
    # the busy step's admit [10, 700) less kv.restore [50, 650): 90 ns;
    # the idle step (no prefill, no decode) counts for neither
    assert ps.sched_ms_per_step(view) == pytest.approx(90e-6)
    outer = SPANS[0]
    assert [x[0] for x in ps.inside(SPANS, outer)][:3] == [
        "engine.admit", "sched.schedule", "kv.restore"]


def test_trace_of_another_run_is_refused(view):
    assert ps.spans(view) == SPANS
    other = SimpleNamespace(trace=TRACE[:-1], table=TABLE,
                            t_trace=view.t_trace, trace_root=view.trace_root)
    assert ps.spans(other) is None
    for read in (ps.tier_idle_share, ps.sched_ms_per_step):
        assert read(other) is None
    assert ps.move_rate(other, "kv.restore", ()) is None


def test_recorded_cpu_trace_has_args_but_reads_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.obs import span
    jax.profiler.start_trace(str(tmp_path))
    with span("kv.restore", program="p7", bytes=4096) as sp:
        jnp.ones((8, 8)).sum().block_until_ready()
        sp.set_metadata(priced_s=0.5)
    jax.profiler.stop_trace()
    n_dev, got = ps.read_file(ps.newest(tmp_path))
    assert n_dev is None
    (restore,) = [x for x in got if x[0] == "kv.restore"]
    assert restore[3] == {"program": "p7", "bytes": 4096, "priced_s": 0.5}
    v = SimpleNamespace(trace=TRACE, table=TABLE, t_trace=(0.0, 1.0),
                        trace_root=tmp_path)
    assert ps.spans(v) is None and ps.tier_idle_share(v) is None
