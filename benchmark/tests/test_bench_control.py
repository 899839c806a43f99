"""The comparison's control at the configurations' real widths, with two
layers so a CPU test holds it: the float8 forward's first choices lie
further below the float32 reference's best than each configuration's
limit allows, while the reference read against itself lies at 0 (CPU)."""
from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402

CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
SEQ = 1024


@pytest.mark.parametrize("name", CONFIGS)
def test_float8_control_breaks_the_limit(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    dims = list(reference.shape_of(cfg["model"]))
    dims[0] = 2                                    # two layers
    dims = tuple(dims)
    params = reference.make_weights(dims, 7,
                                    cfg["model"]["initializer_range"])
    rng = np.random.default_rng(7)
    seq = rng.integers(0, dims[6], SEQ).astype(np.int32)
    at = np.arange(SEQ // 2, SEQ)
    # the reference's own first choices read 0 against itself
    own = reference.gaps(dims, params, seq, at, np.zeros_like(at), SEQ,
                         control=False)
    h = reference.hidden(dims, False, params, seq)
    _, best = reference.pair_gaps(dims, False, params, h, at, at * 0)
    again = reference.gaps(dims, params, seq, at, np.asarray(best), SEQ)
    assert float(again.max()) == 0.0
    assert own.max() > 0
    ctl = reference.gaps(dims, params, seq, at, np.zeros_like(at), SEQ,
                         control=True)
    limit = cfg["check"]["max_gap_sd"]
    assert float(ctl.max()) > limit, (float(ctl.max()), limit)
