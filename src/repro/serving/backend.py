"""Execution backends for the engine.

- ``SimBackend`` (in engine.py): virtual clock, analytic cost model —
  cluster-scale studies.
- ``JaxModelBackend`` (here): REAL model execution over a
  :class:`~repro.serving.paged_runtime.PagedKVRuntime`. Every prefill
  chunk and decode token runs through the model with the program's KV in
  refcounted physical pages; step duration is the wall time until the
  device has finished the step, less the time JAX spent compiling in it
  (a warm server compiles nothing; :func:`compile_stats` reports it). On
  a TPU this is the production path (compiled Pallas kernels); on the
  CPU it runs small models with interpreted kernels
  (examples/quickstart.py).

The scheduler/TTL logic is identical under both backends — that is the
point: the paper's contribution is exercised unchanged.

Physical staging (PR 4): the engine's demote/reload hooks land here as
``offload_program``/``restore_program``. A demotion batch-gathers the
program's scattered pages into contiguous staging buffers through the
``page_copy`` Pallas kernel (``PagedKVRuntime.stage_out``) and moves
them to host memory in ONE bulk copy; a reload scatters them back
(``restore``). There are no ad-hoc per-request cache copies: TTL-expiry
demotion, preemption demotion, and pressure eviction all take the same
staging path, and COW prefix adoption maps admissions onto already-
resident shared pages. Prompt token ids are drawn per (stream, absolute
position) — programs sharing a preamble share the exact token ids, so
radix prefix hits are physically bit-identical pages, not just
accounting entries.
"""
from __future__ import annotations

import math
import time
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.obs.spans import span
from repro.serving.paged_runtime import PAGED_FAMILIES, PagedKVRuntime, _pow2
from repro.serving.prefix import (PrefixConfig, RadixPrefixIndex,
                                  request_block_hashes)

_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration"))   # includes cache reads
_compile = {"seconds": 0.0, "cache_hits": 0, "cache_misses": 0}
_spans: list[tuple[float, float]] = []     # compile spans not yet counted
_watching: list[bool] = []


def _on_span(event: str, start: float, end: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _spans.append((start, end))


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _compile["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _compile["cache_misses"] += 1


def compile_stats() -> dict:
    """This process's JAX compile work since the first JaxModelBackend:
    wall seconds spent tracing, lowering and compiling (or reading the
    persistent cache) — nested spans, such as an inner jit traced inside
    an outer one, counted once — and persistent-cache hits and misses."""
    if _spans:
        spans = sorted(_spans)
        _spans.clear()
        lo, hi = spans[0]
        for s, e in spans[1:]:
            if s > hi:
                _compile["seconds"] += hi - lo
                lo = s
            hi = max(hi, e)
        _compile["seconds"] += hi - lo
    return dict(_compile)


def _watch_compiles() -> None:
    if not _watching:
        _watching.append(True)
        jax.monitoring.register_event_time_span_listener(_on_span)
        jax.monitoring.register_event_listener(_on_event)


def kv_pool_budget(cfg: ModelConfig, *, limit: float, held_bytes: float,
                   max_len: int, chunk: int, max_batch: int,
                   extra_pages: int, page_size: int) -> float:
    """Bytes of KV pool P (k and v, one copy) that fit in ``limit``
    device bytes beside everything else the served path holds. With W
    the weights as held (``held_bytes``: their real dtype) and C their
    compute-dtype copy, which every step casts:

    - decode step: W + 2P + C + batch logits — it returns new pools while
      the old ones are alive (nothing is donated); so do a restore and a
      COW split, with less beside them;
    - prefill forward: W + P + C + scratch (in and out) + the extend
      attention scores; its page write: W + 2P + scratch.

    P is the largest pool meeting all three inside 90% of ``limit`` (the
    rest for the allocator and small buffers), less ``extra_pages`` pages
    the runtime adds past the budget (COW headroom)."""
    kv_item = jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype).itemsize
    cast = cfg.param_count() * jnp.dtype(cfg.compute_dtype).itemsize
    T = _pow2(max_len)                                 # scratch bucket cap
    kv_per_pos = 2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim \
        * kv_item                                      # k + v
    scratch = 2 * T * kv_per_pos                       # in + out
    scores = 2 * min(chunk, T) * T * cfg.num_heads * 4  # f32 s and p
    logits = 2 * max_batch * cfg.vocab_size * 4
    free = 0.9 * limit - held_bytes
    pool = min((free - max(cast + logits, scratch)) / 2,
               free - cast - scratch - scores)
    return max(pool - extra_pages * page_size * kv_per_pos, 0.0)


def _padded(nbytes: int, pages: int) -> int:
    """Bytes of ``pages`` pages of ``nbytes`` at the power-of-two width
    a tier move stages them at."""
    return nbytes // pages * _pow2(pages)


class JaxModelBackend:
    """Real generation; per-program KV in a PagedKVRuntime's physical
    pages (so a TTL hit genuinely reuses the computed cache, an eviction
    genuinely loses it, and a demotion genuinely stages it out through
    the page_copy kernel)."""

    def __init__(self, cfg: ModelConfig, params=None, rng=None,
                 max_len: int = 4096, runtime: PagedKVRuntime | None = None,
                 n_pages: Optional[int] = None, page_size: int = 16,
                 interpret: bool | None = None):
        _watch_compiles()
        if runtime is None:
            if cfg.family not in PAGED_FAMILIES or \
                    cfg.local_global_alternating:
                raise ValueError(
                    f"JaxModelBackend requires a uniform-attention family "
                    f"(got {cfg.family}); use SimBackend for SSM/hybrid "
                    f"archs")
            runtime = PagedKVRuntime(
                cfg, n_pages=n_pages or max(64, 2 * max_len // page_size),
                page_size=page_size, interpret=interpret)
        self.cfg = cfg
        self.runtime = runtime
        self.model = runtime.model
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.params = params if params is not None else self.model.init(rng)
        self.max_len = max_len
        self._rng = rng
        self._streams: dict[str, np.ndarray] = {}  # stream -> token ids
        # staged-out host copies: program_id -> (np k, np v, tokens); the
        # buffers are the page_copy gather's (L, pow2(pages), page, KV, Dh)
        self.host_caches: dict[str, tuple] = {}
        # page-stamped radix mirror of the scheduler's accounting index
        # (enable_prefix_sharing); None = no cross-program sharing
        self.prefix_index: Optional[RadixPrefixIndex] = None
        self._step = 0                  # logical clock for radix LRU
        self.prefill_tokens_computed = 0  # TTL savings show up here
        self.decode_tokens_computed = 0
        self.demotions = 0
        self.restores = 0
        # restores that kept fewer pages than were staged (the store's
        # usable prefix shrank): the device-side re-pad drops the rest
        self.restores_truncated = 0
        # tier moves: KV bytes of the programs' real pages (k + v) and the
        # host wall of each move, stage-out through evict, restore through
        # the scatter dispatch (``kv.stage_out``/``kv.restore`` spans)
        self.stage_out_bytes = 0
        self.restore_bytes = 0
        self.stage_out_seconds = 0.0
        self.restore_seconds = 0.0
        self.shortfall_tokens = 0       # defensive recompute (cache lost)
        # differential harness: verify every restore round-trips bit-exact
        self.verify_staging = False
        self.staging_checks: list[tuple[str, bool]] = []
        self.step_seconds: list[float] = []   # device-synchronised steps

    # ------------------------------------------------------- memory budget
    def default_kv_budget(self, hw, chunk: int, max_batch: int,
                          extra_pages: int) -> float:
        """:func:`kv_pool_budget` for this backend on its device: the
        limit is ``bytes_limit`` from ``memory_stats()`` where the device
        reports one, else ``hw.hbm_bytes``; the weights count at the
        bytes they occupy."""
        stats = jax.devices()[0].memory_stats() or {}
        return kv_pool_budget(
            self.cfg, limit=float(stats.get("bytes_limit") or hw.hbm_bytes),
            held_bytes=sum(x.nbytes for x in jax.tree.leaves(self.params)),
            max_len=self.max_len, chunk=chunk, max_batch=max_batch,
            extra_pages=extra_pages, page_size=self.runtime.page_size)

    # --------------------------------------------------- physical sharing
    def enable_prefix_sharing(self) -> RadixPrefixIndex:
        """Attach a page-stamped radix index to the runtime: admissions
        the scheduler serves from its (accounting) radix index are
        realized as shared physical pages here, and page-pool pressure
        LRU-evicts unreferenced shared paths."""
        if self.prefix_index is None:
            self.prefix_index = RadixPrefixIndex(
                PrefixConfig(block_size=self.runtime.page_size))
            self.runtime.attach_index(self.prefix_index)
            self.runtime.on_pressure = self._relieve_pressure
        return self.prefix_index

    def _relieve_pressure(self, need: int) -> None:
        """Page-pool pressure: LRU-evict unreferenced shared radix paths
        until `need` pages are actually free. A single evict round may
        free zero pages (the node's pages can still be program-held), so
        keep evicting until the free list recovers or nothing evictable
        remains."""
        rt = self.runtime
        while len(rt.free) < need and self.prefix_index is not None:
            if self.prefix_index.evict(max(need, 4)) <= 0:
                return

    def drop_prefix_chain(self, hashes: tuple, keep_blocks: int) -> int:
        """Scheduler accounting-index eviction propagated to the
        page-stamped mirror: drop the same hash chain (beyond
        ``keep_blocks``) so the two radix trees cannot drift apart — the
        mirror would otherwise hold physical pages for paths accounting
        already freed, and later page-pool pressure would evict *different*
        paths the scheduler still serves (the ``shortfall_tokens``
        defensive recomputes). The mirror's ``on_evict_node`` derefs the
        dropped nodes' physical pages."""
        if self.prefix_index is None:
            return 0
        return self.prefix_index.evict_chain(hashes, keep_blocks)

    # ------------------------------------------------------ token streams
    def _stream(self, name: str) -> np.ndarray:
        """Deterministic token ids for a content stream, one id per
        absolute position (stable across turns and across programs that
        share the stream); kept on the host, where slicing compiles
        nothing."""
        s = self._streams.get(name)
        if s is None:
            key = jax.random.fold_in(
                self._rng, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            s = np.asarray(jax.random.randint(key, (self.max_len,), 0,
                                              self.cfg.vocab_size))
            self._streams[name] = s
        return s

    def prompt_tokens(self, req, start: int, end: int) -> np.ndarray:
        """Prompt ids for positions [start, end): positions inside the
        shared preamble draw from the shared stream — the physical basis
        for COW sharing — the rest from the program's own stream."""
        assert 0 <= start < end <= self.max_len, (start, end, self.max_len)
        shared = min(req.shared_prefix_len, req.prompt_len) \
            if req.shared_prefix_id else 0
        parts = []
        if start < shared:
            parts.append(self._stream(req.shared_prefix_id)
                         [start:min(end, shared)])
        if end > shared:
            parts.append(self._stream(req.program_id)[max(start, shared):end])
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    # --------------------------------------------------- engine KV hooks
    def drop_program(self, program_id: str) -> None:
        """Called on eviction/unpin: the cache is genuinely gone."""
        if program_id in self.runtime.programs:
            self.runtime.evict(program_id, force=True)
        self.host_caches.pop(program_id, None)

    def drop_host_copy(self, program_id: str) -> None:
        """Tier-store eviction (LRU pressure victim): only the host copy
        dies; any live device cache stays untouched."""
        self.host_caches.pop(program_id, None)

    def offload_program(self, program_id: str) -> None:
        """Demotion (TTL expiry or preemption): batch-gather the
        program's scattered pages into contiguous staging buffers
        (``page_copy`` gather kernel), move them to host memory in one
        copy, free the device pages. HBM is freed; the context is NOT
        lost — paired with the TieredKVStore entry the scheduler created
        for this program."""
        rt = self.runtime
        e = rt.programs.get(program_id)
        if e is None or e.length == 0:
            return
        t0 = time.perf_counter()
        with span("kv.stage_out", program=program_id,
                  pages=len(e.pages)) as sp:
            k, v, n = rt.stage_out(program_id)
            self.host_caches[program_id] = (k, v, n)
            rt.evict(program_id, force=True)
            nbytes = (k.nbytes + v.nbytes) // k.shape[1] * len(e.pages)
            if sp is not None:
                sp.set_metadata(bytes=nbytes, padded_bytes=_padded(
                    nbytes, len(e.pages)))
        self._note_move("d2h", nbytes, time.perf_counter() - t0)
        self.demotions += 1

    def _note_move(self, direction: str, nbytes: int, seconds: float) -> None:
        """Tier-move counters, and the registry's when telemetry is
        attached (``d2h`` = stage-out, ``h2d`` = restore)."""
        if direction == "d2h":
            self.stage_out_bytes += nbytes
            self.stage_out_seconds += seconds
        else:
            self.restore_bytes += nbytes
            self.restore_seconds += seconds
        obs = self.runtime.obs
        if obs is not None:
            key = (self.runtime.obs_replica, direction)
            obs.tier_bytes.inc(nbytes, key)
            obs.tier_move_seconds.observe(seconds, key)

    def restore_program(self, program_id: str,
                        tokens: Optional[int] = None,
                        priced_s: float = 0.0) -> None:
        """Offload-tier reload: scatter the staged host copy back into
        freshly allocated physical pages. ``tokens`` (the store entry's
        usable prefix — it shrinks when suffix blocks were dropped under
        tier pressure) truncates the restore; the engine recomputes the
        rest. ``priced_s`` is the reload time the scheduler priced for
        it, recorded beside the measured move in the ``kv.restore``
        span."""
        entry = self.host_caches.pop(program_id, None)
        if entry is None:
            return                       # lost copy: engine recomputes
        k, v, staged = entry
        n = staged if tokens is None else min(staged, int(tokens))
        if n <= 0:
            return
        ps = self.runtime.page_size
        pages = math.ceil(n / ps)
        staged_pages = math.ceil(staged / ps)
        nbytes = (k.nbytes + v.nbytes) // k.shape[1] * pages
        t0 = time.perf_counter()
        with span("kv.restore", program=program_id, pages=pages,
                  staged_pages=staged_pages, bytes=nbytes,
                  padded_bytes=_padded(nbytes, pages), priced_s=priced_s):
            ids = self.runtime.restore(program_id, k, v, n)
        self._note_move("h2d", nbytes, time.perf_counter() - t0)
        if pages < staged_pages:
            self.restores_truncated += 1
        if self.verify_staging:          # differential harness: bit-exact?
            back_k, back_v = self.runtime.read_pages(ids)
            ok = bool(np.array_equal(back_k[:, :pages], k[:, :pages])) \
                and bool(np.array_equal(back_v[:, :pages], v[:, :pages]))
            self.staging_checks.append((program_id, ok))
        self.restores += 1

    # ------------------------------------------------------------ execute
    def _req_hashes(self, req):
        return request_block_hashes(req, self.runtime.page_size)

    @staticmethod
    def _bucket(n: int) -> int:
        """Pad chunk lengths to powers of two: bounds XLA recompilation to
        O(log max_chunk) shapes (the TPU serving constraint)."""
        return max(16, _pow2(n))

    def _materialize(self, req, target: int, expected: int) -> None:
        """Ensure the program's pages cover [0, target) — recompute any
        gap from the deterministic streams (defensive: a lost host copy
        or a truncated restore self-heals here). The forward pass runs at
        a bucketed length; only the real tokens' KV lands in pages.

        ``expected`` is how many leading tokens the *engine* believes are
        already materialized (the admission's cached prefix during
        prefill; everything during decode) — recomputing below it is a
        shortfall, counted so truncated restores and lost copies are
        visible in the differential report. Recomputed generated-token
        positions draw from the program stream, not the actual sampled
        ids — a documented divergence from an unpreempted run that the
        counter makes measurable."""
        rt = self.runtime
        e = rt.programs.get(req.program_id)
        start = e.length if e is not None else 0
        if start < target:
            toks = self.prompt_tokens(req, start, target)
            rt.prefill(self.params, req.program_id, toks,
                       pad_to=self._bucket(target - start),
                       max_len=self.max_len)
            self.prefill_tokens_computed += target - start
            if start < min(target, expected):
                self.shortfall_tokens += min(target, expected) - start

    def execute(self, prefill, decode) -> float:
        t0 = time.perf_counter()
        c0 = compile_stats()["seconds"]
        rt = self.runtime
        self._step += 1
        now = float(self._step)
        for work in prefill:
            req = work.req
            pid = req.program_id
            if work.context == 0 and pid in rt.programs:
                # full recompute: the engine decided the old cache is
                # unusable (preemption / expiry without a tier copy)
                rt.evict(pid, force=True)
            if pid not in rt.programs and work.context > 0 \
                    and req.served_from_shared \
                    and self.prefix_index is not None:
                # radix admission -> shared physical pages (COW adoption)
                rt.adopt_prefix(self.prefix_index, pid, self._req_hashes(req),
                                now=now, max_tokens=work.context)
            self._materialize(req, work.context + work.chunk,
                              expected=work.context)
            if work.context + work.chunk >= req.prompt_len \
                    and self.prefix_index is not None:
                # prompt complete: publish / dedup into the shared index
                rt.publish_prefix(self.prefix_index, pid,
                                  self._req_hashes(req), now=now)
        decode_pids = []
        for req in decode:
            pid = req.program_id
            # pages must cover every position a decode step attends to:
            # prompt + already-generated tokens (minus the pending one) —
            # and at decode time the engine believes ALL of them exist
            target = req.prompt_len + max(req.generated - 1, 0)
            self._materialize(req, target, expected=target)
            decode_pids.append(pid)
        if decode_pids:
            # the whole decode batch through ONE fused step per layer
            # (bit-identical to the per-program loop — see decode_batch)
            rt.decode_batch(self.params, decode_pids)
            self.decode_tokens_computed += len(decode_pids)
        # the step ends when the device is done: the pools are the last
        # thing every step writes, the next tokens the last it reads
        with span("model.sync"):
            jax.block_until_ready((rt.k_pages, rt.v_pages,
                                   [rt._last[p] for p in decode_pids]))
        dt = time.perf_counter() - t0 - (compile_stats()["seconds"] - c0)
        dt = max(dt, 1e-6)
        self.step_seconds.append(dt)
        return dt
