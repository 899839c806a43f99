"""Serving launcher: run an agent workload through the Continuum engine.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b \
        --policy continuum --workload swe-bench -n 60 --rate 0.05 \
        [--offload-gb 200] [--trace trace.json] [--engines 2]

``--backend sim`` (the default) runs the virtual-clock simulation backend
(cost-model timed; the scheduler code is the production code).
``--backend jax`` serves for real on one TPU chip — Engine ->
JaxModelBackend -> PagedKVRuntime -> the compiled Pallas kernels — with
seeded random weights at the arch's full published width::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --backend jax --workload bfcl -n 4 --max-len 8192 --offload-gb 8

It refuses a host without a TPU; ``--smoke`` swaps in the arch's smoke
config and lets the CPU run it with interpreted kernels (a rehearsal,
not a measurement; give it ``--kv-budget-gb``, as the CPU reports no
memory limit). The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` at the root
of the checkout.

Observability front door::

    PYTHONPATH=src python -m repro.launch.serve --http-port 8321 \
        --http-linger 60 --slo-ttft 2.0 ...

starts the telemetry plane plus :class:`repro.obs.server.ObsServer`
before the run (``/metrics``, ``/healthz``, ``/traces``, ``/audit/<id>``,
SSE ``/events``) and keeps serving for ``--http-linger`` seconds after
the workload drains, so scrapers (and the CI ``http-smoke`` job) can
read the final state.

Cluster mode (``--cluster``) runs ``--engines`` replicas as one
:class:`~repro.serving.cluster.Cluster` — shared virtual clock, KV-aware
routing and cross-replica migration — instead of independent engines
behind a session router.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.policies import POLICIES
from repro.serving.engine import Engine, EngineConfig
from repro.serving.offload import OffloadConfig
from repro.serving.profiler import HardwareProfile
from repro.serving.router import Router
from repro.sim.runner import run_workload
from repro.sim.workload import WORKLOADS, generate_programs, load_trace

CLUSTER_ROUTERS = ("round_robin", "sticky", "kv_aware", "kv_aware_migrate")

#: fixed compile-cache directory of this checkout (git-ignored); the
#: cache key includes the path, so it must not move between runs
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache before anything compiles:
    ``JAX_COMPILATION_CACHE_DIR`` wins where it is set (JAX reads it
    itself), else :data:`COMPILE_CACHE_DIR`. Every compile is kept, not
    only those over a second: a served run compiles hundreds of small
    programs (one per bucketed shape). Returns the directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def build_jax_engine(cfg: ModelConfig, ecfg: EngineConfig, *, max_len: int,
                     seed: int = 0, allow_cpu: bool = False) -> Engine:
    """The served path on one device: an Engine over a JaxModelBackend
    (seeded random weights, ``max_len``-token programs) over a
    PagedKVRuntime. On a TPU the kernels run compiled and the hardware
    profile is the device's published peaks; anything else is refused
    unless ``allow_cpu`` (the interpreted rehearsal on a smoke config)."""
    import jax
    from repro.serving.backend import JaxModelBackend
    from repro.serving.profiler import hardware_profile
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        hw, interpret = hardware_profile(dev.device_kind), False
    elif allow_cpu:
        if not ecfg.kv_budget_bytes:
            raise ValueError("the CPU rehearsal needs an explicit KV budget "
                             "(the CPU reports no memory limit)")
        hw, interpret = HardwareProfile(), True
    else:
        raise RuntimeError(
            f"the jax backend serves on a TPU, but JAX found "
            f"{dev.platform!r} ({dev.device_kind}); use --backend sim, or "
            f"--smoke for the CPU rehearsal")
    backend = JaxModelBackend(cfg, rng=jax.random.PRNGKey(seed),
                              max_len=max_len, page_size=ecfg.block_size,
                              interpret=interpret)
    return Engine(cfg, ecfg, hw, backend=backend)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--policy", default="continuum", choices=list(POLICIES))
    ap.add_argument("--workload", default="swe-bench",
                    choices=list(WORKLOADS))
    ap.add_argument("--trace", help="replay a recorded JSON trace instead")
    ap.add_argument("-n", type=int, default=60)
    ap.add_argument("--rate", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=8)
    ap.add_argument("--engines", type=int, default=1)
    ap.add_argument("--router", default=None,
                    help="placement policy: session | round_robin | "
                         "least_loaded (multi-engine), or one of "
                         f"{'/'.join(CLUSTER_ROUTERS)} with --cluster")
    ap.add_argument("--cluster", action="store_true",
                    help="run --engines replicas as one Cluster (shared "
                         "clock, KV-aware routing, cross-replica KV "
                         "migration) instead of independent engines")
    ap.add_argument("--offload-gb", type=float, default=0.0,
                    help="host-DRAM tier capacity (0 = offload disabled)")
    ap.add_argument("--ssd-gb", type=float, default=0.0,
                    help="SSD spillover tier below DRAM (needs --offload-gb)")
    ap.add_argument("--kv-budget-gb", type=float, default=None,
                    help="KV pool size (default: 40 for --backend sim; "
                         "what fits on the chip for --backend jax)")
    ap.add_argument("--max-batch", type=int, default=48)
    ap.add_argument("--chunk-size", type=int, default=2048)
    ap.add_argument("--backend", default="sim", choices=("sim", "jax"),
                    help="sim: virtual clock; jax: real generation on "
                         "one TPU chip (single engine only)")
    ap.add_argument("--max-len", type=int, default=8192,
                    help="--backend jax: longest program (tokens, all "
                         "turns) the backend holds; longer ones are "
                         "refused")
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's smoke config; with --backend "
                         "jax this allows the CPU (interpreted kernels)")
    ap.add_argument("--cost-source", default="analytic",
                    choices=("analytic", "roofline"),
                    help="roofline: calibrate the TTL cost model from the "
                         "compiled HLO of the real config (lower+compile "
                         "only — scanned layers keep it seconds on CPU)")
    ap.add_argument("--trace-out",
                    help="write a Perfetto-loadable trace of the run "
                         "(enables the telemetry plane); the raw event "
                         "stream lands next to it as <path>.jsonl and "
                         "the TTL audit as <path>.audit.json")
    ap.add_argument("--metrics-out",
                    help="write the Prometheus text exposition of the "
                         "run's metrics (enables the telemetry plane); "
                         "a JSON snapshot lands next to it as "
                         "<path>.json")
    ap.add_argument("--http-port", type=int, default=None,
                    help="serve the live telemetry plane over HTTP "
                         "(/metrics, /healthz, /traces, /audit, /events; "
                         "0 = ephemeral port, printed at startup); "
                         "enables the telemetry plane")
    ap.add_argument("--http-linger", type=float, default=0.0,
                    help="keep the HTTP server up this many wall seconds "
                         "after the run drains (CI scrape window)")
    ap.add_argument("--slo-ttft", type=float, default=None,
                    help="per-tenant TTFT SLO target seconds (enables "
                         "burn-rate monitoring)")
    ap.add_argument("--slo-jct", type=float, default=None,
                    help="per-tenant JCT SLO target seconds")
    ap.add_argument("--slo-objective", type=float, default=0.95,
                    help="compliance fraction for the SLO targets")
    args = ap.parse_args()

    if args.router is None:
        args.router = "kv_aware_migrate" if args.cluster else "session"
    if args.backend == "jax" and (args.engines > 1 or args.cluster):
        ap.error("--backend jax serves one engine in one process (a chip "
                 "belongs to one engine); drop --engines/--cluster")
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.trace:
        programs = load_trace(args.trace)
    else:
        spec = WORKLOADS[args.workload]
        if args.backend == "jax":
            spec = dataclasses.replace(spec, max_context=args.max_len)
        programs = generate_programs(spec, n=args.n, rate_jps=args.rate,
                                     seed=args.seed)
    if args.backend == "jax":
        too_long = [p.program_id for p in programs
                    if p.total_tokens() > args.max_len]
        if too_long:
            ap.error(f"{len(too_long)} programs exceed --max-len "
                     f"{args.max_len} tokens (e.g. {too_long[0]}); raise "
                     f"--max-len or shorten the workload")
    if args.cluster and args.router == "kv_aware_migrate" \
            and not args.offload_gb:
        # migration stages KV through the host tier on both ends
        print("note: --cluster with kv_aware_migrate needs an offload "
              "tier; defaulting --offload-gb 8", file=sys.stderr)
        args.offload_gb = 8.0
    off = OffloadConfig(dram_bytes=args.offload_gb * 1e9,
                        ssd_bytes=args.ssd_gb * 1e9) \
        if args.offload_gb else None
    # calibrate once and share: every replica serves the same model, so the
    # roofline compile (the expensive part) must not repeat per engine
    cost = None
    if args.cost_source == "roofline":
        from repro.serving.profiler import CostModel
        cost = CostModel.from_roofline(cfg, chips=args.chips)
    id_prefix = "r" if args.cluster else "e"
    if args.backend == "jax":
        enable_compile_cache()
        # one chip: chips=1, and the KV budget is what fits on it
        engines = [build_jax_engine(cfg, EngineConfig(
            policy=args.policy, chips=1, offload=off,
            max_batch=args.max_batch, chunk_size=args.chunk_size,
            kv_budget_bytes=(args.kv_budget_gb or 0.0) * 1e9),
            max_len=args.max_len, seed=args.seed, allow_cpu=args.smoke)]
    else:
        engines = [Engine(cfg, EngineConfig(
            policy=args.policy, chips=args.chips, offload=off,
            max_batch=args.max_batch, chunk_size=args.chunk_size,
            kv_budget_bytes=(args.kv_budget_gb or 40.0) * 1e9),
            HardwareProfile(),
            cost=cost, engine_id=f"{id_prefix}{i}")
            for i in range(args.engines)]

    cluster = None
    if args.cluster:
        from repro.serving.cluster import Cluster, ClusterConfig
        assert args.router in CLUSTER_ROUTERS, \
            f"--cluster router must be one of {CLUSTER_ROUTERS}"
        cluster = Cluster(engines, ClusterConfig(n_replicas=args.engines,
                                                 router=args.router))

    tel = None
    if args.trace_out or args.metrics_out or args.http_port is not None \
            or args.slo_ttft is not None or args.slo_jct is not None:
        from repro.obs import Telemetry
        tel = Telemetry()
        if cluster is not None:
            cluster.attach_telemetry(tel)
        else:
            for e in engines:
                e.attach_telemetry(tel)
        if args.slo_ttft is not None or args.slo_jct is not None:
            from repro.obs.slo import default_objectives
            tel.enable_slo(default_objectives(args.slo_ttft, args.slo_jct,
                                              args.slo_objective))

    server = None
    if args.http_port is not None:
        from repro.obs.server import ObsServer
        clock_fn = (lambda: cluster.clock.now) if cluster is not None \
            else (lambda: max(e.clock for e in engines))
        server = ObsServer(tel, port=args.http_port, clock=clock_fn)
        server.start()
        print(json.dumps({"obs_http": server.url()}), flush=True)

    if cluster is not None:
        s = cluster.run(programs, max_seconds=1e7)
    else:
        router = Router(engines, policy=args.router)
        s = run_workload(programs, engines, router, max_seconds=1e7)
    if tel is not None:
        import pathlib
        if args.trace_out:
            from repro.obs import export as obs_export
            p = pathlib.Path(args.trace_out)
            p.parent.mkdir(parents=True, exist_ok=True)
            obs_export.export_file(tel.trace, p)
            tel.trace.save_jsonl(p.with_suffix(p.suffix + ".jsonl"))
            p.with_suffix(p.suffix + ".audit.json").write_text(
                json.dumps(tel.audit.to_json(), indent=2, sort_keys=True)
                + "\n")
        if args.metrics_out:
            p = pathlib.Path(args.metrics_out)
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(tel.metrics.exposition())
            p.with_suffix(p.suffix + ".json").write_text(
                json.dumps(tel.metrics.snapshot(), indent=2,
                           sort_keys=True) + "\n")
    st = engines[0].scheduler.stats
    out = {
        "policy": args.policy, "n_programs": s.n_programs,
        "avg_jct_s": round(s.avg_jct, 1), "p95_jct_s": round(s.p95_jct, 1),
        "throughput_jobs_per_min": round(s.throughput_jobs_per_s * 60, 2),
        "avg_queueing_s": round(s.avg_queueing, 1),
        "ttl": {"pins": st.pins, "hits": st.ttl_hits,
                "expiries": st.ttl_expiries,
                "deadlock_evictions": st.deadlock_evictions},
    }
    if cluster is not None:
        out["cluster"] = {
            "replicas": args.engines, "router": args.router,
            "migrations": cluster.stats.migrations,
            "migrated_tokens": cluster.stats.migrated_tokens,
            "cold_rehomes": cluster.stats.cold_rehomes,
        }
    if engines[0].kvstore is not None:
        ks = engines[0].kvstore
        out["kvstore"] = {
            "demotions": st.demotions,
            "reloads": st.offload_reloads,
            "reload_seconds": round(st.reload_seconds, 1),
            "recompute_seconds": round(st.recompute_seconds, 1),
            "tier_usage": {t: ks.usage()[t]["used_blocks"]
                           for t in ("dram", "ssd")},
            "bytes_moved": {c: round(v["bytes_moved"] / 1e9, 2)
                            for c, v in ks.transfer.usage().items()},
        }
    if args.backend == "jax":
        import jax
        be = engines[0].backend
        dev = jax.devices()[0]
        out["device"] = {"platform": dev.platform, "kind": dev.device_kind}
        out["backend"] = {
            "prefill_tokens": be.prefill_tokens_computed,
            "decode_tokens": be.decode_tokens_computed,
            "demotions": be.demotions, "restores": be.restores,
            "cow_splits": be.runtime.cow_splits,
            "shortfall_tokens": be.shortfall_tokens}
    if tel is not None and tel.slo is not None:
        slo = tel.slo.status()
        out["slo"] = {"alerting": [t for t in slo["tenants"]
                                   if t["alerting"]],
                      "tenants": len(slo["tenants"])}
    print(json.dumps(out, indent=1), flush=True)
    if server is not None:
        if args.http_linger > 0:
            time.sleep(args.http_linger)
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
