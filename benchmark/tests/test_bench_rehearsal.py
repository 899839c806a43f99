"""The whole run on the CPU rehearsal path (smoke size, interpreted
kernels): a sound run is ``correct``; with the timed path broken
underneath the harness, it is not; the float8 control, compared in the
program's place, reads above the program (CPU)."""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

CELL = "stablelm-3b-pp2.swe-workers"


def token_altered(eng):
    """Every fifth decode step, the first row's produced token is off by
    one where the runtime produces it."""
    rt, V = eng.backend.runtime, eng.backend.cfg.vocab_size
    inner, n = rt.decode_batch, [0]

    def bad(params, pids):
        out = inner(params, pids)
        n[0] += 1
        if n[0] % 5 == 0:
            rt._last[pids[0]] = (rt._last[pids[0]] + 1) % V
        return out
    rt.decode_batch = bad


def kv_state_unchanged(eng):
    """Every step returns the KV pools unchanged: prefill and decode
    compute, but no key or value is ever written."""
    rt = eng.backend.runtime

    def keep_pools(inner):
        def bad(*args, **kw):
            k, v = rt.k_pages, rt.v_pages
            out = inner(*args, **kw)
            rt.k_pages, rt.v_pages = k, v
            return out
        return bad
    rt.prefill = keep_pools(rt.prefill)
    rt.decode_batch = keep_pools(rt.decode_batch)


def rehearse(fault=None, control=False, seed=11):
    return run.run(["--workload", CELL, "--seed", str(seed), "--seconds",
                    "8", "--rehearsal"], on_engine=fault, control=control)


def test_sound_rehearsal_is_correct_and_control_reads_higher():
    out = rehearse()
    chk = out["check"]
    assert out["rehearsal"] and "metrics" not in out and "device" not in out
    assert out["counts"]["decode_tokens"] > 0
    assert chk["max_gap_sd"]["value"] <= chk["max_gap_sd"]["limit"]
    assert out["correct"] is True
    # the control goes through the same comparison with the float8
    # forward's tokens in place of the program's
    ctl = rehearse(control=True)
    assert ctl["control"] is True
    assert ctl["program_max_gap_sd"] <= ctl["check"]["max_gap_sd"]["limit"]
    assert ctl["check"]["max_gap_sd"]["value"] > ctl["program_max_gap_sd"]


@pytest.mark.parametrize("fault", [token_altered, kv_state_unchanged],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(fault):
    out = rehearse(fault)
    chk = out["check"]["max_gap_sd"]
    assert chk["value"] > chk["limit"]
    assert out["correct"] is False
