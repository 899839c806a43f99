#!/usr/bin/env python3
"""The comparison's control, on the chip: one run of a cell (as
``run.py`` makes it, same arguments) in which, at every position
compared, the token a float8 forward of the reference puts first takes
the place of the program's, and ``correct`` is decided by the same
comparison. A sound limit makes it read ``correct: false``; a limit the
control does not break cannot tell float8 from the configuration's
bfloat16. ``PERF.md`` records both readings.

    python3 benchmark/control.py --workload <cell> --seed <n> --seconds <s>

Prints the run's result; the program's own reading of the same run is
``program_max_gap_sd``. Not part of a benchmark run.
"""
from __future__ import annotations

import json
import sys

import run

if __name__ == "__main__":
    try:
        out = run.run(sys.argv[1:], control=True)
    except run.Failure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
    print(json.dumps(out), flush=True)
