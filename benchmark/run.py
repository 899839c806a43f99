#!/usr/bin/env python3
"""Benchmark of the served path on one TPU chip: one cell per run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>
    python3 benchmark/run.py --workload <cell> --seconds 3 --rehearsal

A run builds the engine the normal entry point builds
(``repro.launch.serve.build_jax_engine``: Engine -> JaxModelBackend ->
PagedKVRuntime -> compiled Pallas kernels) at the cell's configuration,
hands it weights made from ``--seed``, warms every program shape the
cell's traffic can reach, serves the traffic unmeasured for the file's
``ramp_s`` seconds, then measures for ``--seconds`` on the wall clock.
``setup_s`` runs from process start to the first arrival of the ramp.
With ``--trace 1`` a profiler trace covers the window and the run
reports the per-layer metrics instead.

After the window the harness compares what the timed path produced with
a float32 reference (``capture.py``; the reference module the
configuration file names, ``reference.py`` by default) and prints each
compared number beside its limit, last on standard error and last in
the result. The result is the last line of standard output. The harness
knows an architecture only through that module: a new one joins by a
new module, a new configuration file and a new cell.

``--rehearsal`` runs the same path on the CPU at the registry's smoke
size with interpreted kernels; it is not a measurement and prints no
device metric. Without it, a run that finds no TPU, or fewer chips than
the cell asks for, fails.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import counts  # noqa: E402
import driver as drv  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402
import warmup  # noqa: E402
from capture import Capture  # noqa: E402

OUT_DIR = ROOT / "benchmark_out"
CHECK_PROGRAMS = 6            # programs compared after the window
# the rehearsal's engine settings, in place of the file's serve block
REHEARSAL = {"max_len": 1024, "max_batch": 4, "chunk_size": 128,
             "kv_blocks": 1024, "dram_gb": 1.0, "ramp_s": 10}


class Failure(Exception):
    """A run that cannot produce a result."""


def say(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------- inputs
def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise Failure(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = json.loads((HERE / "configs" / f"{cell['config']}.json")
                     .read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return bench, cell, cfg, traffic


def rehearsal_traffic(traffic: dict, f: float, ramp_s: float) -> dict:
    """The cell's traffic with its lengths and its closed loop's workers
    scaled by ``f`` (the rehearsal's max_len over the cell's), at least one
    token of output a turn, and the rehearsal's ramp: turns end within a
    short run of the interpreted kernels."""
    t = json.loads(json.dumps(traffic))
    t["ramp_s"] = ramp_s
    for grp in ("tokens", "prompt", "output"):
        for k in ("mean", "std", "min", "cap", "median", "max"):
            if k in t.get(grp, {}):
                t[grp][k] = max(1, int(t[grp][k] * f))
    for k in ("min_turn_tokens", "min_new_tokens", "min_output_tokens",
              "workers"):
        if k in t:
            t[k] = max(1, int(t[k] * f))
    t["max_len"] = max(1, int(t["max_len"] * f))
    return t


def reference_of(cfgf: dict):
    """The reference module a configuration file names by its
    ``reference`` key (``benchmark/<stem>.py``; ``reference`` where the
    key is absent), loaded by path. Its interface is in ``reference.py``'s
    docstring."""
    stem = cfgf.get("reference", "reference")
    path = HERE / f"{stem}.py"
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", stem) or \
            not path.is_file():
        raise Failure(f"no reference module {stem!r} "
                      f"(benchmark/{stem}.py) for {cfgf.get('name')!r}")
    name = f"bench_reference_{stem}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def pools(rt) -> dict:
    """The runtime's KV pools: its array attributes named ``*_pages``."""
    import jax
    return {k: v for k, v in vars(rt).items()
            if k.endswith("_pages") and isinstance(v, jax.Array)}


def build(cfgf: dict, seed: int, rehearsal: bool):
    """The engine of the normal entry point, at the configuration file's
    sizes, holding the benchmark's weights from ``seed``; with the
    reference module and its dims."""
    import jax
    from repro.configs import get_config
    from repro.core.ttl import TTLConfig
    from repro.launch.serve import build_jax_engine
    from repro.serving.engine import EngineConfig
    from repro.serving.offload import OffloadConfig
    from repro.serving.prefix import PrefixConfig

    ref = reference_of(cfgf)
    sv = dict(cfgf["serve"])
    cfg = get_config(cfgf["registry"], smoke=rehearsal)
    budget = (sv.get("kv_budget_gb") or 0.0) * 1e9    # 0: the default
    if rehearsal:
        sv.update(REHEARSAL)
        budget = sv["kv_blocks"] * sv["block_size"] * cfg.kv_bytes_per_token(2)
    else:
        cfg = ref.program_config(cfg, cfgf["model"])
        want = ref.shape_of(cfgf["model"])
        if ref.served_dims(cfg) != want:
            raise Failure(f"registry {cfgf['registry']} serves "
                          f"{ref.served_dims(cfg)}, the file states {want}")
        for k, v in cfgf["precision"].items():
            if (getattr(cfg, k) or cfg.compute_dtype) != v:
                raise Failure(f"{k}: program {getattr(cfg, k)!r}, file {v!r}")
    dims = ref.served_dims(cfg)
    ecfg = EngineConfig(
        policy=sv["policy"], chips=1, max_batch=sv["max_batch"],
        chunk_size=sv["chunk_size"], block_size=sv["block_size"],
        kv_budget_bytes=budget,
        offload=OffloadConfig(dram_bytes=sv["dram_gb"] * 1e9),
        prefix=PrefixConfig() if sv["prefix"] else None,
        ttl=TTLConfig(**sv["ttl"]))
    eng = build_jax_engine(cfg, ecfg, max_len=sv["max_len"],
                           seed=seed & 0x7FFFFFFF, allow_cpu=rehearsal)
    be = eng.backend
    if be.runtime.interpret and not rehearsal:
        raise Failure("kernels would run interpreted on the chip")
    # the program's own weights make way for the benchmark's
    shapes = jax.tree.map(lambda x: (x.shape, x.dtype), be.params)
    be.params = None
    params = ref.make_weights(dims, seed, cfgf["model"]["initializer_range"])
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    if got != shapes:
        raise Failure(f"weight layout differs from the program's: "
                      f"{got} vs {shapes}")
    be.params = params
    jax.block_until_ready(params)
    return eng, sv, ref, dims


def warm(eng, sv: dict, progs: list, rows: int) -> None:
    """Compiles every program shape the traffic can reach: decode batches
    of 1 to ``rows`` rows, each prefill chunk, each tier move."""
    import jax
    from repro.serving.backend import compile_stats
    be, rt = eng.backend, eng.backend.runtime
    page = rt.page_size
    min_prompt = min(p.context_at(0) for p in progs)
    max_total = max(p.total_tokens() for p in progs)
    phases = [
        ("prefill", lambda: warmup.warm_prefill(
            rt, be.params, warmup.prefill_calls(
                sv["max_len"], sv["chunk_size"], page), sv["max_len"])),
        ("decode", lambda: warmup.warm_decode(
            rt, be.params, rows,
            warmup.decode_tables(min_prompt, max_total, page))),
        ("tiers", lambda: warmup.warm_tiers(rt, math.ceil(max_total / page))),
        ("streams", lambda: be._stream("warmup"))]
    for name, fn in phases:
        t, c = time.perf_counter(), compile_stats()
        fn()
        jax.block_until_ready(pools(rt))
        c1 = compile_stats()
        say(f"warm-up {name}: {time.perf_counter() - t:.3f} s, compiling "
            f"{c1['seconds'] - c['seconds']:.3f} s, cache hits "
            f"{c1['cache_hits'] - c['cache_hits']} misses "
            f"{c1['cache_misses'] - c['cache_misses']}")
    be._streams.clear()


def make_request_fn(d_ref: list):
    from repro.core.types import Request

    def make(prog, k: int, due: float):
        t = prog.turns[k]
        plen = prog.context_at(k)
        return Request(
            program_id=prog.pid, turn_idx=k, prompt_len=plen,
            output_len=t.output_tokens, arrival_time=due,
            program_arrival_time=d_ref[0].program_due[prog.pid],
            tool=t.tool, tool_duration=t.tool_s,
            is_last_turn=k == len(prog.turns) - 1,
            shared_prefix_len=min(prog.shared_prefix_tokens, plen),
            shared_prefix_id=prog.shared_prefix_id)
    return make


# ---------------------------------------------------------------- counters
def counters(eng) -> dict:
    st, be = eng.scheduler.stats, eng.backend
    return {"ttl_hits": st.ttl_hits, "pins": st.pins,
            "expiries": st.ttl_expiries, "preemptions": st.preemptions,
            "prefix_hits": st.prefix_hits, "reloads": st.offload_reloads,
            "recomputes": st.full_recomputes,
            "prefill_tokens": be.prefill_tokens_computed,
            "decode_tokens": be.decode_tokens_computed,
            "demotions": be.demotions, "restores": be.restores,
            "shortfall_tokens": be.shortfall_tokens,
            "cow_splits": be.runtime.cow_splits}


# ---------------------------------------------------------- per-layer view
@dataclasses.dataclass
class View:
    """What a per-layer metric reader may read."""
    driver: drv.Driver
    w0: float
    w1: float
    c0: dict                 # counters at the window's start and end
    c1: dict
    capture: Capture
    reference: object        # the configuration's reference module
    dims: tuple
    page: int
    peaks: dict
    trace: list | None = None        # device events of the traced part
    host: list | None = None         # host spans of the traced part
    t_trace: tuple | None = None     # its (start, end) on the driver clock
    trace_prefill_tokens: int = 0
    table: dict | None = None


def read_metric(name: str, view: View):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


# ------------------------------------------------------------- correctness
def pick_programs(d: drv.Driver, n: int, seed: int
                  ) -> tuple[list[str], dict[str, str]]:
    """Programs to compare, drawn from the seed among those with a
    finished turn: the one with the longest finished context, and one of
    each admission path where the window had it (shared preamble adopted,
    TTL pin, host-tier restore, recompute or preemption), the rest at
    random."""
    rng = np.random.default_rng(seed)
    done = [r for r in d.turns.values() if not math.isnan(r.end)]
    if not done:
        return [], {}
    by_len = max(done, key=lambda r: r.prompt_len + len(r.tokens))
    picked, why = [by_len.pid], {"longest": by_len.pid}
    paths = {
        "preamble": lambda q: q.served_from_shared and q.turn_idx == 0,
        "pin": lambda q: q.served_from_pin,
        "restore": lambda q: q.cached_prefix > 0 and not q.served_from_pin
        and not q.served_from_shared,
        "recompute": lambda q: (q.turn_idx > 0 and q.cached_prefix == 0)
        or q.preemptions > 0,
    }
    for path, test in paths.items():
        cands = sorted({r.pid for r in done if test(r.req)})
        if cands and len(picked) < n:
            pid = cands[rng.integers(len(cands))]
            why[path] = pid
            if pid not in picked:
                picked.append(pid)
    rest = sorted({r.pid for r in done} - set(picked))
    rng.shuffle(rest)
    return picked + rest[:max(0, n - len(picked))], why


def compare(cap: Capture, pids: list[str], ref, dims, params,
            max_len: int, control: bool = False) -> dict:
    """The widest gap, in standard deviations of the reference's logits,
    by which a token the program produced lies below the reference's best
    at its position, over every token of the programs ``pids``.

    With ``control`` the tokens under test are those the float8 forward
    of the reference puts first at the same positions, in place of the
    program's; the program's own reading is kept as ``program_max_gap_sd``.
    """
    chains = cap.replay(set(pids))
    out = {"max_gap_sd": 0.0, "tokens": 0, "decode_rows": set()}
    if control:
        out["program_max_gap_sd"] = 0.0
    for pid in pids:
        for c in chains.get(pid, []):
            at, tok = np.asarray(c.at), np.asarray(c.tok)
            g = ref.gaps(dims, params, c.seq, at, tok, max_len)
            out["tokens"] += len(g)
            out["decode_rows"].update(r for r in c.decode_rows if r)
            if control:
                out["program_max_gap_sd"] = max(out["program_max_gap_sd"],
                                                float(g.max()))
                g = ref.gaps(dims, params, c.seq, at, tok, max_len,
                             control=True)
            out["max_gap_sd"] = max(out["max_gap_sd"], float(g.max()))
    out["decode_rows"] = sorted(out["decode_rows"])
    return out


# -------------------------------------------------------------------- run
def run(argv=None, on_engine=None, control: bool = False) -> dict:
    """One run; returns the result. ``on_engine(engine)`` runs before the
    capture is attached (tests plant faults there); with ``control`` the
    float8 forward's tokens are compared in place of the program's
    (``control.py``)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal at the smoke size, interpreted "
                         "kernels; not a measurement")
    args = ap.parse_args(argv)
    bench, cell, cfgf, traffic = load_cell(args.workload)

    from repro.launch.serve import enable_compile_cache
    from repro.serving.backend import compile_stats
    enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    if not args.rehearsal and (dev.platform != "tpu"
                               or len(devs) < cell["chips"]):
        raise Failure(f"needs {cell['chips']} TPU chip(s); JAX found "
                      f"{len(devs)} {dev.platform} device(s)")
    if args.rehearsal:
        say("REHEARSAL: smoke config, interpreted kernels on "
            f"{dev.platform}; not a measurement")
    say(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)}")

    eng, sv, ref, dims = build(cfgf, args.seed, args.rehearsal)
    if args.rehearsal:
        traffic = rehearsal_traffic(
            traffic, sv["max_len"] / cfgf["serve"]["max_len"], sv["ramp_s"])
    be, rt = eng.backend, eng.backend.runtime
    progs, offsets = traffic_gen.schedule(traffic, args.seed, args.seconds)
    t = time.perf_counter()
    # a closed loop runs at most one turn of each worker at a time
    rows = min(sv["max_batch"], traffic.get("workers", sv["max_batch"]))
    warm(eng, sv, progs, rows)
    say(f"warm-up: {time.perf_counter() - t:.3f} s; pool {rt.n_pages} "
        f"pages of {rt.page_size} tokens")
    if on_engine is not None:
        on_engine(eng)              # tests plant faults under the capture
    cap = Capture(rt)

    d_ref: list = []
    d = drv.Driver(eng, make_request_fn(d_ref))
    d_ref.append(d)
    cap.clock = d.now
    t_ramp = d.now()
    setup_s = time.perf_counter() - T_START
    if traffic["loop"] == "open":
        d.open_loop(progs, offsets, t_ramp)
    else:
        d.closed_loop(progs, traffic["workers"], traffic["stagger_s"],
                      t_ramp)
    w0 = t_ramp + traffic["ramp_s"]
    w1 = w0 + args.seconds
    d.run_until(w0)
    c0 = counters(eng)
    k0 = compile_stats()
    view = View(d, w0, w1, c0, {}, cap, ref, dims, rt.page_size,
                {} if args.rehearsal else counts.peaks(dev.device_kind))
    if args.trace:
        view.table = trace_reduce.names()
        tdir = OUT_DIR / "trace" / args.workload
        shutil.rmtree(tdir, ignore_errors=True)
        annotate(eng, d)
        p0 = be.prefill_tokens_computed
        jax.profiler.start_trace(str(tdir))
        ts = d.now()
        d.run_until(w1)
        te = d.now()
        view.trace_prefill_tokens = be.prefill_tokens_computed - p0
        jax.profiler.stop_trace()
        view.t_trace = (ts, te)
    d.run_until(w1)
    k1 = compile_stats()
    view.c1 = counters(eng)
    e2e = drv.end_to_end(d, w0, w1)
    due = [r for r in d.turns.values() if w0 <= r.due < w1]
    late = [r.submitted - r.due for r in due]
    ramp_turns = sum(1 for r in d.turns.values() if r.due < w0)
    say(f"ramp: {traffic['ramp_s']} s, {ramp_turns} turns due; window "
        f"{args.seconds} s: {len(due)} turns due, {e2e['n_programs']} "
        f"programs ended, {e2e['n_turns']} first tokens, "
        f"{e2e['n_gaps']} token gaps, {e2e['n_tokens']} tokens")
    say(f"generator lateness in the window: mean "
        f"{np.mean(late) if late else 0.0:.6f} s, max "
        f"{max(late) if late else 0.0:.6f} s")
    n_comp = sum(k1[k] - k0[k] for k in ("cache_hits", "cache_misses"))
    say(f"compiles inside the window: {n_comp} "
        f"({k1['seconds'] - k0['seconds']:.6f} s tracing and compiling)")
    if n_comp:
        raise Failure(f"{n_comp} programs compiled inside the measured "
                      f"window: the warm-up missed a shape")
    say("counters over the window: " + json.dumps(
        {k: view.c1[k] - c0[k] for k in c0}))
    say("steps in the window: " + json.dumps(step_summary(d, w0, w1)))
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    metrics = {}
    names = [m for m in bench["end_to_end"] if m["name"] != "setup_s"] \
        if not args.trace else bench["per_layer"]
    for m in names:
        if "workloads" in m and args.workload not in m["workloads"]:
            continue
        if args.trace:
            if view.trace is None:
                view.trace, view.host = trace_view(tdir, view.table)
            v = read_metric(m["name"], view)
        else:
            v = e2e[m["name"]]
        if v is None or (isinstance(v, float) and math.isnan(v)):
            if args.trace and not args.rehearsal:
                raise Failure(f"per-layer metric {m['name']} read nothing; "
                              f"trace names by device time:\n"
                              + nearest_names(tdir))
            continue
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if not args.trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        device["busy_s"] = trace_reduce.busy_seconds(view.trace, view.table)
        device["window_s"] = view.t_trace[1] - view.t_trace[0]
        breakdown = {
            "device_ops": trace_reduce.top_ops(view.trace, view.table),
            "idle_gaps": trace_reduce.idle_gaps(view.trace, view.table,
                                                view.host)}
        say(f"trace: busy {device['busy_s']:.6f} s of "
            f"{device['window_s']:.6f} s")

    # correctness: free the serving state, then the reference
    for name in pools(rt):
        setattr(rt, name, None)
    be.host_caches.clear()
    pids, why = pick_programs(d, CHECK_PROGRAMS, args.seed)
    res = compare(cap, pids, ref, dims, be.params, sv["max_len"], control)
    limit = cfgf["check"]["max_gap_sd"]
    say(f"compared {res['tokens']} served tokens of {len(pids)} programs "
        f"({', '.join(pids)}; by path {why}); decode batch sizes "
        f"{res['decode_rows']}")
    correct = limit is not None and res["tokens"] > 0 \
        and res["max_gap_sd"] <= limit
    check = {"max_gap_sd": {"value": res["max_gap_sd"], "limit": limit}}
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": sum(r.rejected for r in due)}
    if control:
        say(f"CONTROL: float8 tokens compared in place of the program's; "
            f"the program's own max_gap_sd {res['program_max_gap_sd']!r}")
        out["control"] = True
        out["program_max_gap_sd"] = res["program_max_gap_sd"]
    if args.rehearsal:
        out["rehearsal"] = True
        out["counts"] = {k: view.c1[k] - c0[k] for k in c0}
    else:
        out["metrics"] = metrics
        out["device"] = device
        if breakdown is not None:
            out["breakdown"] = breakdown
    out["check"] = check
    for k, v in check.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    return out


def step_summary(d: drv.Driver, w0: float, w1: float) -> dict:
    """How the window's engine steps went: their count, wall and execute
    times, and the time the driver spent outside them."""
    st = [s for s in d.steps if w0 <= s.start < w1]
    if not st:
        return {"steps": 0}
    wall = np.asarray([s.end - s.start for s in st])
    ex = np.asarray([s.exec_s for s in st])
    q = lambda a, p: float(np.percentile(a, p))  # noqa: E731
    return {"steps": len(st), "wall_sum_s": float(wall.sum()),
            "wall_p50_s": q(wall, 50), "wall_p95_s": q(wall, 95),
            "wall_max_s": float(wall.max()), "exec_p50_s": q(ex, 50),
            "exec_p95_s": q(ex, 95), "emitted_mean":
            float(np.mean([s.emitted for s in st]))}


def nearest_names(tdir, k: int = 30) -> str:
    """The trace's op and program names that took the most device time
    (from ``names.tsv``), for mending the name table."""
    path = tdir / "names.tsv"
    if not path.exists():
        return "(no names.tsv)"
    return "\n".join(path.read_text().splitlines()[:k])


def trace_view(tdir, table):
    planes, host = trace_reduce.load(str(tdir))
    events = next(iter(planes.values()), [])
    trace_reduce.dump(events, str(tdir / "names.tsv"))
    return events, host


def annotate(eng, d: drv.Driver) -> None:
    """Host spans around the calls into each layer, on the profiler's
    clock, for attributing the device's idle gaps."""
    import jax

    def wrap(obj, attr, name):
        fn = getattr(obj, attr)
        if fn is None:
            return

        def inner(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        setattr(obj, attr, inner)
    be, rt = eng.backend, eng.backend.runtime
    wrap(eng, "step", "bench.engine_step")
    wrap(be, "execute", "bench.backend_execute")
    wrap(rt, "prefill", "bench.runtime_prefill")
    wrap(rt, "decode_batch", "bench.runtime_decode")
    # the scheduler holds the backend's tier hooks as bound methods
    wrap(eng.scheduler, "on_demote", "bench.tier_demote")
    wrap(eng.scheduler, "on_reload", "bench.tier_restore")
    wrap(d, "_sleep", "bench.driver_idle")
    wrap(d, "_deliver", "bench.driver_deliver")


def main() -> int:
    try:
        out = run()
    except Failure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
