#!/usr/bin/env python3
"""Bring-up smoke of the served path on one TPU chip.

Serves a small BFCL-shaped agent fleet through the engine the normal
entry point builds (``repro.launch.serve.build_jax_engine``): Engine ->
JaxModelBackend -> PagedKVRuntime -> compiled Pallas kernels, at the full
published width of qwen2-1.5b with seeded random weights, at the engine's
default KV budget, with the DRAM tier, TTL pins and the shared-prefix
index on. Then it checks one program's served logits, after prefill and
after decode steps through the pages, against a float32 reference
forward.

    python chip_smoke.py            # one TPU chip; fails without one
    python chip_smoke.py --smoke    # CPU rehearsal: smoke config, small sizes

Prints what it measured, then, as its last line, one JSON object
``{"ok": true, "device": {...}}``. A failed phase or check exits non-zero
before that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    arch_smoke: bool
    max_len: int           # longest program the backend holds (tokens)
    chunk: int             # prefill token budget per engine step
    max_batch: int
    programs: int          # fleet size, the solo program included
    tokens_mean: int       # per program, before the shared preamble
    tokens_std: int
    share_ratio: float     # preamble = share_ratio * tokens_mean tokens
    output_frac: float
    max_context: int       # generator cap on a program's own tokens
    rate: float            # fleet arrivals per virtual second
    fleet_start: float     # virtual seconds the solo program runs alone
    dram_gb: float         # host DRAM tier
    ttl_unit_s: float      # cold-start Exp(u) mean of the TTL model
    ref_decode: int        # decode steps compared with the reference
    kv_blocks: int = 0     # KV pool in blocks; 0 = the engine's default


#: full width: 3-6 turns, contexts up to 8,192 tokens with a 1,536-token
#: shared preamble. With a 25 GB/s host link a context of that size
#: reloads in milliseconds, so Eq. 2 prices a pin at about u*ln(G/u):
#: milliseconds for u = 1 ms. A pause while the engine is busy expires and
#: demotes; a pause while it idles returns before the scheduler looks, a
#: hit.
FULL = Sizes(arch_smoke=False, max_len=8192, chunk=2048, max_batch=8,
             programs=7, tokens_mean=5120, tokens_std=1280, share_ratio=0.3,
             output_frac=0.1, max_context=7000, rate=0.25, fleet_start=60.0,
             dram_gb=8.0, ttl_unit_s=1e-3, ref_decode=8)
#: the same shape on the smoke config, small enough for interpreted
#: kernels; its KV is ~200x smaller, and so is the TTL unit. The CPU
#: reports no memory limit, so the pool is set (the default would fill
#: 16 GB of host memory with a 2-layer model's pages)
SMOKE = Sizes(arch_smoke=True, max_len=1024, chunk=128, max_batch=8,
              programs=4, tokens_mean=640, tokens_std=96, share_ratio=0.25,
              output_frac=0.05, max_context=800, rate=1.0, fleet_start=60.0,
              dram_gb=1.0, ttl_unit_s=1e-6, ref_decode=4, kv_blocks=1024)

#: BFCL's tool mix with each tool's lognormal mean scaled so the pause
#: averages ~1 s (0.45*1.5 + 0.35*0.8 + 0.1*0.05 + 0.1*0.3 = 0.99 s)
TOOLS = (("web_search", 0.45, 1.5, 0.9), ("fetch_url", 0.35, 0.8, 1.8),
         ("calculator", 0.1, 0.05, 0.4), ("finish", 0.1, 0.3, 0.6))

#: served logits (bf16 weights, activations and KV) against the float32
#: reference, as max |error| over the reference's largest |logit|. On a
#: v5e the full-width run measured 0.008 at worst; the smoke config and
#: a narrow 28-layer qwen2 measured 0.005 and 0.019 on the CPU. Random
#: weights attend diffusely, so faults move logits little: on that narrow
#: config (CPU) a decode one position late read 0.030-0.036 and a table
#: slot pointing at an unwritten page 0.070-0.076, while pages swapped
#: within a program are invisible (attention is order-free). At full
#: width on a v5e (3,000-token prompt) the first two read 0.016-0.018 and
#: 0.010-0.011 and pass: this check catches gross faults only.
REF_REL_TOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def fleet(sz: Sizes, seed: int):
    """BFCL-shaped programs: a solo program first, alone for
    ``fleet_start`` virtual seconds, then the rest with overlapping
    Poisson arrivals. The first fleet program is a retried session: its
    first prompt is the shared preamble alone, so its admission matches
    the whole prompt, adopts ``prompt_len - 1`` tokens mid-page and
    recomputes the last one into the shared page (copy-on-write)."""
    from repro.sim.workload import BFCL, generate_programs
    spec = dataclasses.replace(
        BFCL, mean_turns=4.5, std_turns=0.75, tokens_mean=sz.tokens_mean,
        tokens_std=sz.tokens_std, output_frac=sz.output_frac,
        max_context=sz.max_context, tools=TOOLS)
    progs = generate_programs(spec, n=sz.programs, rate_jps=sz.rate,
                              seed=seed, share_ratio=sz.share_ratio)
    progs[0].arrival_time = 0.0
    for p in progs[1:]:
        p.arrival_time += sz.fleet_start
    progs[1].turns[0].new_tokens = progs[1].shared_prefix_tokens
    for p in progs:
        assert 3 <= p.num_turns <= 6, (p.program_id, p.num_turns)
        assert p.total_tokens() <= sz.max_len, (p.program_id,
                                                p.total_tokens())
    assert progs[1].shared_prefix_tokens % 16 == 0
    return progs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal: smoke config and small sizes, "
                         "interpreted kernels; not a chip result")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sz = SMOKE if args.smoke else FULL

    from repro.launch.serve import build_jax_engine, enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.smoke:
        fail(f"no TPU: JAX found {dev.platform!r} ({dev.device_kind}); "
             f"run on a TPU host, or --smoke for the CPU rehearsal")
    if args.smoke:
        log("REHEARSAL (--smoke): smoke config on "
            f"{dev.platform}, interpreted kernels — not a chip result")
    log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(jax.devices())}")
    log(f"compile cache: {cache_dir}")

    from repro.configs import get_config
    from repro.core.ttl import TTLConfig
    from repro.models.reference import compare_logits, reference_logits
    from repro.serving.backend import compile_stats
    from repro.serving.engine import EngineConfig
    from repro.serving.offload import OffloadConfig
    from repro.serving.prefix import PrefixConfig
    from repro.sim.runner import run_workload
    from repro.sim.workload import request_for_turn

    cfg = get_config("qwen2-1.5b", smoke=sz.arch_smoke)
    log(f"model: {cfg.name} L={cfg.num_layers} d={cfg.d_model} "
        f"H={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} params={cfg.param_count():,} "
        f"({cfg.param_dtype} weights, {cfg.compute_dtype} compute)")
    phases: dict[str, dict] = {}

    def phase(name: str, t0: float, c0: float) -> None:
        phases[name] = {"wall_s": time.perf_counter() - t0,
                        "compile_s": compile_stats()["seconds"] - c0}
        log(f"phase {name}: wall {phases[name]['wall_s']:.3f} s, "
            f"compile {phases[name]['compile_s']:.3f} s")

    # ---- phase 1: weights + engine at the default KV budget -------------
    t0, c0 = time.perf_counter(), compile_stats()["seconds"]
    ecfg = EngineConfig(
        policy="continuum", chips=1, max_batch=sz.max_batch,
        kv_budget_bytes=sz.kv_blocks * 16 * cfg.kv_bytes_per_token(2),
        chunk_size=sz.chunk, block_size=16,
        offload=OffloadConfig(dram_bytes=sz.dram_gb * 1e9),
        prefix=PrefixConfig(),
        ttl=TTLConfig(cold_start_k=1 << 30, exp_unit_mean=sz.ttl_unit_s,
                      max_ttl=1.0))
    eng = build_jax_engine(cfg, ecfg, max_len=sz.max_len, seed=args.seed,
                           allow_cpu=args.smoke)
    be, rt = eng.backend, eng.backend.runtime
    if rt.interpret and not args.smoke:
        fail("kernels would run interpreted on the chip path")
    be.verify_staging = True            # every restore checked bit for bit
    rt.verify_copies = True             # every COW split checked bit for bit
    jax.block_until_ready((be.params, rt.k_pages, rt.v_pages))
    phase("init", t0, c0)
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    held = sum(x.nbytes for x in jax.tree.leaves(be.params))
    pool = rt.k_pages.nbytes + rt.v_pages.nbytes
    log(f"kv budget: params held {held:,} B; pool {pool:,} B = "
        f"{rt.n_pages} pages of {rt.page_size} tokens "
        f"({eng.blocks.total} accounting blocks); device bytes_limit "
        f"{limit if limit is None else f'{limit:,}'} B; "
        f"kernels {'interpreted' if rt.interpret else 'compiled'}")

    # ---- phase 2: serve the fleet ----------------------------------------
    progs = fleet(sz, args.seed)
    log(f"workload: {len(progs)} programs, turns "
        f"{[p.num_turns for p in progs]}, tokens "
        f"{[p.total_tokens() for p in progs]}, preamble "
        f"{progs[0].shared_prefix_tokens}")
    t0, c0 = time.perf_counter(), compile_stats()["seconds"]
    summary = run_workload(progs, [eng], max_seconds=1e7)
    phase("serve", t0, c0)
    st = eng.scheduler.stats
    steps = be.step_seconds
    log(f"served: {summary.n_programs} programs, avg JCT "
        f"{summary.avg_jct:.3f} s on the engine clock, {eng.steps} steps")
    log(f"step seconds (device-synchronised, compile excluded): n="
        f"{len(steps)} min={min(steps):.6f} median="
        f"{statistics.median(steps):.6f} max={max(steps):.6f}")
    log(f"tokens computed: prefill {be.prefill_tokens_computed} "
        f"decode {be.decode_tokens_computed}")
    staging_ok = all(ok for _, ok in be.staging_checks)
    cow_ok = all(rt.copy_checks)
    log(f"ttl: pins {st.pins} hits {st.ttl_hits} expiries "
        f"{st.ttl_expiries} demotions {be.demotions} restores "
        f"{be.restores} (bit-exact {sum(ok for _, ok in be.staging_checks)}"
        f"/{len(be.staging_checks)}) cow_splits {rt.cow_splits} "
        f"(bit-exact {sum(rt.copy_checks)}/{len(rt.copy_checks)}) "
        f"prefix_hits {st.prefix_hits} shortfall_tokens "
        f"{be.shortfall_tokens}")
    finished = [ps for ps in eng.programs.values() if ps.finish_time >= 0]
    if len(finished) != len(progs) or eng.rejected:
        fail(f"{len(finished)}/{len(progs)} programs finished, "
             f"{eng.rejected} rejected")
    if st.ttl_hits < 1:
        fail("no TTL hit")
    if be.restores < 1 or not be.staging_checks or not staging_ok:
        fail("no bit-exact demotion/restore")
    if rt.cow_splits < 1 or not rt.copy_checks or not cow_ok:
        fail("no bit-exact copy-on-write split")

    # ---- phase 3: served logits against the float32 reference ------------
    t0, c0 = time.perf_counter(), compile_stats()["seconds"]
    req = request_for_turn(progs[0], 0, 0.0)
    toks = be.prompt_tokens(req, 0, req.prompt_len)
    pid = "reference-check"
    for a in range(0, len(toks), sz.chunk):
        chunk = toks[a:a + sz.chunk]
        logits = rt.prefill(be.params, pid, chunk,
                            pad_to=1 << (len(chunk) - 1).bit_length(),
                            max_len=be.max_len)
    outs, fed = [np.asarray(logits)], []
    for _ in range(sz.ref_decode):
        fed.append(int(rt._last_token(be.params, pid)))
        outs.append(np.asarray(rt.decode_batch(be.params, [pid])[0]))
    rt.evict(pid, force=True)
    ref = reference_logits(cfg, be.params, np.concatenate([toks, fed]),
                           sz.ref_decode + 1)
    checks = [compare_logits(o, r, REF_REL_TOL) for o, r in zip(outs, ref)]
    phase("reference", t0, c0)
    worst = max(c["rel_err"] for c in checks)
    log(f"reference: {len(toks)}-token prompt, prefill + "
        f"{sz.ref_decode} decode steps vs float32 highest-precision "
        f"forward: rel err per position "
        f"{[round(c['rel_err'], 6) for c in checks]}, worst {worst:.6f} "
        f"(limit {REF_REL_TOL})")
    if not all(c["ok"] for c in checks):
        fail(f"served logits off the float32 reference: worst {worst}")

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"memory: peak_bytes_in_use {peak} of bytes_limit "
        f"{stats.get('bytes_limit')}")
    if not args.smoke and (peak is None or limit is None or peak >= limit):
        fail("device peak memory not under its limit")
    cs = compile_stats()
    log(f"compile: {cs['seconds']:.3f} s total, persistent cache hits "
        f"{cs['cache_hits']} misses {cs['cache_misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
