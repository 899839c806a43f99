#!/usr/bin/env python3
"""One-off rate sweep of an open-loop cell, to find the highest rate the
system sustains (the knee) before a cell's rate is fixed in its traffic
file. Not part of a benchmark run.

    python3 benchmark/sweep.py --workload <cell> --seed <n> \
        --seconds <window per rate> --rates 0.25,0.5,1

Builds the engine once, then for each rate serves the cell's traffic at
that rate for the file's ramp and the window, prints the window's
statistics and the backlog at its end, and lets the backlog drain (at
most ``--drain`` seconds) before the next rate. A rate whose backlog
grows through the window is past the knee.
"""
from __future__ import annotations

import argparse
import json
import sys

import driver as drv
import run
import traffic_gen


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=90.0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal at the smoke size; not a measurement")
    args = ap.parse_args()
    _, cell, cfgf, traffic = run.load_cell(args.workload)
    from repro.launch.serve import enable_compile_cache
    enable_compile_cache()
    eng, sv, _ = run.build(cfgf, args.seed, args.rehearsal)
    if args.rehearsal:
        traffic = run.rehearsal_traffic(
            traffic, sv["max_len"] / cfgf["serve"]["max_len"], sv["ramp_s"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t = dict(traffic, rate_per_s=rate, population_seed=
                 traffic["population_seed"] + i)
        progs, off = traffic_gen.schedule(t, args.seed, args.seconds)
        for p in progs:
            p.pid = f"r{i}-{p.pid}"
        if i == 0:
            run.warm(eng, sv, progs)
        ref: list = []
        d = drv.Driver(eng, run.make_request_fn(ref))
        ref.append(d)
        t0 = d.now()
        d.open_loop(progs, off, t0)
        w0, w1 = t0 + t["ramp_s"], t0 + t["ramp_s"] + args.seconds
        d.run_until(w1)
        e2e = drv.end_to_end(d, w0, w1)
        waits = [r.admitted - r.due for r in d.turns.values()
                 if w0 <= r.admitted < w1]
        backlog = len(eng.scheduler.waiting)
        print(json.dumps({
            "rate_per_s": rate, **e2e,
            "queue_wait_mean_s": sum(waits) / max(len(waits), 1),
            "waiting_at_end": backlog, "running_at_end": len(eng.running),
            "turns_due": sum(1 for r in d.turns.values()
                             if w0 <= r.due < w1)}), flush=True)
        while d.now() < w1 + args.drain and (eng.has_work or d._heap):
            d.run_until(min(d.now() + 1.0, w1 + args.drain))
        if eng.has_work or d._heap:
            print(f"rate {rate}: backlog did not drain in {args.drain} s; "
                  f"stopping", flush=True)
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
