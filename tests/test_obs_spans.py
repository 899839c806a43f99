"""Program spans on the profiler's clock (``repro.obs.span``).

A smoke-size engine on the real ``JaxModelBackend`` (interpreted
kernels) serves a program through a demotion and a restore, and a
second program adopting its shared preamble through a copy-on-write
split, once under ``jax.profiler.start_trace`` and once with nothing
recording. The trace must hold every span of the served path with its
arguments, nested as the calls are; the untraced run must produce the
same bytes.
"""
import glob
import math

import jax
import numpy as np
import pytest

from repro.obs import span

# span -> the arguments it must carry
REQUIRED = {
    "engine.step": {"step", "running", "admitted", "prefill_tokens",
                    "decode_rows"},
    "engine.admit": set(), "engine.compose": set(),
    "engine.execute": set(), "engine.advance": set(),
    "sched.schedule": {"waiting", "admits"},
    "sched.admit": {"program", "turn", "source", "cached", "wait_s",
                    "priced_reload_s"},
    "sched.retention": {"program", "turn", "tool", "ttl", "pinned",
                        "prefill_reload", "t_bar"},
    "kv.stage_out": {"program", "pages", "bytes", "padded_bytes"},
    "kv.gather": set(), "kv.d2h": set(),
    "kv.restore": {"program", "pages", "staged_pages", "bytes",
                   "padded_bytes", "priced_s"},
    "kv.h2d": set(), "kv.scatter": set(),
    "kv.cow_split": {"src_page"},
    "model.prefill": {"program", "start", "tokens", "pad_to"},
    "model.decode": {"rows", "n_tab"},
    "model.sync": set(),
}
PREFIXES = ("engine.", "sched.", "kv.", "model.")


def build():
    from repro.configs import get_config
    from repro.core.ttl import TTLConfig
    from repro.serving.backend import JaxModelBackend
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.offload import OffloadConfig
    from repro.serving.prefix import PrefixConfig
    from repro.serving.profiler import HardwareProfile
    cfg = get_config("qwen2-1.5b", smoke=True)
    backend = JaxModelBackend(cfg, rng=jax.random.PRNGKey(0), max_len=256,
                              page_size=16)
    kvpt = backend.runtime.cfg.kv_bytes_per_token(2)
    # no pin (max_ttl 0): a finished tool turn is demoted to the DRAM tier
    ecfg = EngineConfig(max_batch=4, chunk_size=128, block_size=16,
                        kv_budget_bytes=96 * 16 * kvpt,
                        offload=OffloadConfig(dram_bytes=64 * 16 * kvpt),
                        prefix=PrefixConfig(), ttl=TTLConfig(max_ttl=0.0))
    return Engine(cfg, ecfg, HardwareProfile(), backend=backend)


def serve(eng) -> dict:
    """Program ``a`` (a 32-token shared preamble, a tool call) is demoted
    when its turn ends; ``b``, whose prompt is that preamble alone,
    adopts it and recomputes its last token into the shared page (COW);
    ``a`` returns and is restored from the host copy."""
    from repro.core.types import Request
    be = eng.backend
    out = {"tokens": [], "staged": None}
    now = 0.0

    def run(until):
        nonlocal now
        for _ in range(60):
            ev = eng.step(now)
            out["tokens"].extend(int(be.runtime._last[r.program_id])
                                 for r in eng.running
                                 if r.program_id in be.runtime._last)
            now += max(ev.duration, 1e-3)
            if until():
                return
        raise AssertionError("the engine did not get there")

    eng.submit(Request("a", 0, 40, 3, 0.0, 0.0, tool="ls",
                       tool_duration=0.1, shared_prefix_len=32,
                       shared_prefix_id="sys"), now)
    run(lambda: "a" in be.host_caches)
    out["staged"] = be.host_caches["a"]
    eng.submit(Request("b", 0, 32, 2, now, now, is_last_turn=True,
                       shared_prefix_len=32, shared_prefix_id="sys"), now)
    eng.submit(Request("a", 1, 40 + 3 + 20, 2, now, 0.0, is_last_turn=True,
                       shared_prefix_len=32, shared_prefix_id="sys"), now)
    run(lambda: not eng.has_work)
    out["k"] = np.asarray(be.runtime.k_pages)
    out["v"] = np.asarray(be.runtime.v_pages)
    out["decisions"] = eng.scheduler.stats
    return out


def program_spans(trace_dir) -> list[tuple]:
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    got = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    got.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return got


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("trace")
    traced_eng = build()
    jax.profiler.start_trace(str(trace_dir))
    try:
        traced = serve(traced_eng)
    finally:
        jax.profiler.stop_trace()
    plain = serve(build())
    return traced, plain, program_spans(trace_dir), traced_eng


def test_every_span_carries_its_arguments(runs):
    traced, _, spans, eng = runs
    seen = {}
    for name, _, _, args in spans:
        seen.setdefault(name, []).append(args)
    assert set(seen) == set(REQUIRED), set(seen) ^ set(REQUIRED)
    for name, need in REQUIRED.items():
        assert any(need <= set(a) for a in seen[name]), (name, seen[name])
    assert eng.backend.demotions == 1 and eng.backend.restores == 1
    assert eng.backend.runtime.cow_splits >= 1
    (adm,) = [a for a in seen["sched.admit"] if a["source"] == "offload"]
    assert adm["program"] == "a" and adm["turn"] == 1
    (rst,) = seen["kv.restore"]
    assert rst["priced_s"] == pytest.approx(adm["priced_reload_s"])
    assert rst["priced_s"] > 0


def test_restore_bytes_are_the_staged_pages(runs):
    traced, _, spans, eng = runs
    k, v, n = traced["staged"]
    (rst,) = [a for name, _, _, a in spans if name == "kv.restore"]
    (out,) = [a for name, _, _, a in spans if name == "kv.stage_out"]
    pages = math.ceil(n / 16)
    width = 1 << (pages - 1).bit_length()
    # the host copy is the gather's whole power-of-two buffer; the spans
    # count its real pages and their padded width
    assert k.shape[1] == v.shape[1] == width
    real = k[:, :pages].nbytes + v[:, :pages].nbytes
    assert rst["pages"] == rst["staged_pages"] == out["pages"] == pages
    assert rst["bytes"] == out["bytes"] == real
    assert rst["padded_bytes"] == out["padded_bytes"] \
        == k.nbytes + v.nbytes == real // pages * width
    be = eng.backend
    assert be.restore_bytes == be.stage_out_bytes == real
    assert be.restore_seconds > 0 and be.stage_out_seconds > 0
    assert be.restores_truncated == 0


def test_spans_nest_as_the_calls_do(runs):
    _, _, spans, _ = runs

    def inside(inner, outer):
        return [o for o in spans if o[0] == outer
                and o[1] <= inner[1] and inner[2] <= o[2]]
    h2d = [s for s in spans if s[0] == "kv.h2d"]
    assert len(h2d) == 2                    # one per pool, k then v
    (rst,) = inside(h2d[0], "kv.restore")
    assert inside(h2d[1], "kv.restore") == [rst]
    (scatter,) = [s for s in spans if s[0] == "kv.scatter"]
    assert inside(scatter, "kv.restore") == [rst]
    (adm,) = inside(rst, "engine.admit")
    (step,) = inside(adm, "engine.step")
    assert inside(rst, "sched.admit") and inside(rst, "sched.schedule")
    # both pools' copies to the device precede the one scatter dispatch
    assert h2d[0][2] <= h2d[1][1] <= h2d[1][2] <= scatter[1]
    (d2h,) = [s for s in spans if s[0] == "kv.d2h"]
    (out,) = inside(d2h, "kv.stage_out")
    assert inside(out, "sched.retention") and inside(out, "engine.advance")
    for cow in (s for s in spans if s[0] == "kv.cow_split"):
        assert inside(cow, "model.prefill") or inside(cow, "model.decode")
    for sync in (s for s in spans if s[0] == "model.sync"):
        assert inside(sync, "engine.execute")


def test_without_a_profiler_nothing_records_and_outputs_match(runs):
    traced, plain, _, _ = runs
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert span("engine.step", step=1) is span("kv.h2d")     # shared no-op
    assert traced["tokens"] == plain["tokens"]
    assert np.array_equal(traced["k"], plain["k"])
    assert np.array_equal(traced["v"], plain["v"])
    assert traced["decisions"] == plain["decisions"]


def test_tier_moves_feed_the_registry():
    from repro.obs import Telemetry
    tel = Telemetry()
    eng = build()
    eng.attach_telemetry(tel)
    assert not hasattr(eng.backend.runtime, "obs_clock")
    serve(eng)
    text = tel.metrics.exposition()
    be = eng.backend
    for direction, nbytes in (("d2h", be.stage_out_bytes),
                              ("h2d", be.restore_bytes)):
        assert (f'continuum_tier_bytes_total{{replica="engine0",'
                f'direction="{direction}"}} {nbytes}') in text
        assert (f'continuum_tier_move_seconds_count{{replica="engine0",'
                f'direction="{direction}"}} 1') in text
    assert "continuum_page_cow_splits_total" in text
    assert not [e for e in tel.trace.events
                if e[0] == "i" and e[3] in ("stage_out", "restore",
                                            "cow_split")]
