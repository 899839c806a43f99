"""Paged KV runtime: physical page pools + block tables, decoded through
the Pallas paged-attention kernel.

This is the layer where Continuum's mechanism is visible at the memory
system level: a program's KV lives in scattered physical pages; *pinning*
keeps the pages allocated and the block table alive across the tool-call
gap, so the next turn decodes against the same physical pages (zero
recompute, zero copy); *eviction* derefs the pages back toward the free
list.

Pages are *refcounted*: a radix-index prefix hit maps a new program's
block table onto the same physical page ids another program already
filled (``adopt_prefix``), and the first divergent write to a shared
page triggers a copy-on-write split through the ``page_copy`` Pallas
kernel — the prefix is shared in HBM for real, not just in accounting.
``stage_out``/``restore`` batch-gather scattered pages into contiguous
staging buffers (one bulk DMA) for tier moves through the
:mod:`repro.serving.kvstore` store, and scatter them back from those
same buffers.

Works for the uniform-attention families (dense/moe/audio/vlm). The
model names the pools (``Model.cache_keys``): ``k_pages`` and ``v_pages``
(L, P, page, KV, Dh) for grouped-query attention, one ``c_pages`` (L, P,
page, W) for latent attention, whose row is the latent and the rotary
key (``MLAConfig.cache_width``); pool layer i is model layer i. Every
path below handles the pools as a tuple in that order. The
engine-level BlockManager does the accounting; this runtime holds the
actual arrays (on TPU: HBM pools consumed by the kernel's
scalar-prefetch block tables; on CPU: interpret mode).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import resolve_interpret
from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_mla_decode)
from repro.kernels.page_copy import (append_tokens, copy_pages, gather_pages,
                                     scatter_pages)
from repro.models import attention as attn_mod
from repro.models.common import cast_params, rms_norm, take_layer
from repro.models.mlp import mlp_apply
from repro.models.moe import moe_dropless
from repro.models.transformer import Model
from repro.obs.spans import span


#: families whose per-token KV lives in uniform pages (the runtime's —
#: and therefore JaxModelBackend's — supported set)
PAGED_FAMILIES = ("dense", "moe", "audio", "vlm")


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _pad_ids(ids: list[int], n: int, *rest: list[int]) -> list[np.ndarray]:
    """Pad index lists to length ``n`` by repeating their last entry, so
    the padded slots redo the last real copy (same source, same target,
    same bytes) and a bucketed shape changes nothing."""
    return [np.asarray(x + x[-1:] * (n - len(x)), np.int32)
            for x in (ids,) + rest]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_span(caches, pools, ids, *, interpret):
    """Pages ``ids`` (n,) -> scratch positions [0, n*page) of the
    contiguous (L, 1, T, *row) caches, one per pool, through the page_copy
    gather."""
    def one(cache, pages):
        L, _, page = pages.shape[:3]
        g = gather_pages(pages, ids, interpret=interpret)
        g = g.reshape((L, 1, ids.shape[0] * page) + pages.shape[3:])
        return jax.lax.dynamic_update_slice_in_dim(
            cache, g.astype(cache.dtype), 0, axis=2)
    return tuple(map(one, caches, pools))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scatter_span(pools, caches, ids, blocks, lo, hi, *, interpret):
    """Scratch positions [lo, hi) -> physical pages: ``ids`` (n,) hold the
    program's logical blocks ``blocks`` (n,). Positions of those pages
    outside [lo, hi) keep the pool's bytes (gathered, merged, scattered
    back through the page_copy kernels)."""
    page = pools[0].shape[2]
    pos = blocks[:, None] * page + jnp.arange(page)          # (n, page)
    src = jnp.clip(pos, 0, caches[0].shape[2] - 1)

    def one(pages, cache):
        live = ((pos >= lo) & (pos < hi)).reshape(
            (1,) + pos.shape + (1,) * (pages.ndim - 3))
        new = cache[:, 0][:, src].astype(pages.dtype)    # (L, n, page, *row)
        old = gather_pages(pages, ids, interpret=interpret)
        return scatter_pages(pages, jnp.where(live, new, old), ids,
                             interpret=interpret)
    return tuple(map(one, pools, caches))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _restore_pages(pools, staged, ids, n, *, interpret):
    """Staged (L, W, page, *row) buffers, one per pool -> physical pages
    ``ids`` (W,), padded by repeating the last of the n kept pages: slot
    i scatters staged page min(i, n - 1), so a padded slot redoes the
    last kept page's copy and never carries a page past the kept
    prefix."""
    take = jnp.minimum(jnp.arange(ids.shape[0]), n - 1)

    def one(pages, buf):
        return scatter_pages(pages, buf[:, take], ids, interpret=interpret)
    return tuple(map(one, pools, staged))


@dataclasses.dataclass
class ProgramEntry:
    pages: list[int]
    length: int
    pinned: bool = False


class PagedKVRuntime:
    def __init__(self, cfg: ModelConfig, n_pages: int = 64,
                 page_size: int = 16, interpret: bool | None = None):
        assert cfg.family in PAGED_FAMILIES and \
            not cfg.local_global_alternating, "uniform-attention families"
        self.cfg = cfg
        self.model = Model(cfg)
        self.page_size = page_size
        self.n_pages = n_pages
        self.interpret = resolve_interpret(interpret)
        # one jitted batched decode step; jax.jit retraces per (B, n_tab)
        self._decode_step = jax.jit(self._decode_step_impl)
        # one jitted prefill forward per (chunk bucket, scratch bucket, mode)
        self._forward = jax.jit(self.model.forward, static_argnames=("mode",))
        self.cache_keys = self.model.cache_keys()
        self._set_pools(self._zero_pools(n_pages))
        self.free: list[int] = list(range(n_pages))
        self.refs: dict[int, int] = {}             # page id -> holders
        self.programs: dict[str, ProgramEntry] = {}
        self._last: dict[str, jax.Array] = {}      # last token per program
        self.cow_splits = 0
        # called with a page deficit when the free list runs dry — the
        # owner (an engine backend) LRU-evicts unreferenced radix-held
        # pages before the allocation is retried (page-pool pressure)
        self.on_pressure = None  # type: Optional[callable]
        # differential-harness hooks: when set, every COW split is
        # verified bit-exact (copied page == source page) and recorded
        self.verify_copies = False
        self.copy_checks: list[bool] = []
        # telemetry (repro.obs): COW splits count on the owning
        # replica's registry; the owning backend reports its tier moves
        # through the same handle
        self.obs = None
        self.obs_replica = ""
        # the last decode step's expert copies, int32 [routed to a held
        # expert, all], summed over its MoE layers (None: no MoE)
        self.moe_copies = None

    # ------------------------------------------------------------- pools
    @property
    def pools(self) -> tuple:
        """The physical pools, in ``cache_keys`` order."""
        return tuple(getattr(self, f"{k}_pages") for k in self.cache_keys)

    def _set_pools(self, pools) -> None:
        for k, p in zip(self.cache_keys, pools):
            setattr(self, f"{k}_pages", p)

    def _zero_pools(self, n_pages: int) -> tuple:
        """Empty pools of ``n_pages`` pages: each cache leaf's (L, 1, T,
        *row) as (L, n_pages, page, *row)."""
        shapes = self.model.cache_shapes(1, self.page_size)
        return tuple(jnp.zeros((shapes[k][0][0], n_pages)
                               + shapes[k][0][2:], jnp.dtype(shapes[k][1]))
                     for k in self.cache_keys)

    # ------------------------------------------------------------- alloc
    def _alloc_page(self) -> int:
        if not self.free and self.on_pressure is not None:
            self.on_pressure(1)
        if not self.free:
            raise MemoryError("out of KV pages")
        pi = self.free.pop()
        self.refs[pi] = 1
        return pi

    def _deref(self, pi: int) -> None:
        self.refs[pi] -= 1
        assert self.refs[pi] >= 0, (pi, self.refs[pi])
        if self.refs[pi] == 0:
            del self.refs[pi]
            self.free.append(pi)

    def _ensure_capacity(self, e: ProgramEntry, new_len: int) -> None:
        need = math.ceil(new_len / self.page_size)
        while len(e.pages) < need:
            e.pages.append(self._alloc_page())

    def grow(self, n_pages_total: int) -> None:
        """Grow the physical pools to ``n_pages_total`` pages (no-op if
        already at least that big). The engine calls this at wiring time
        so the page pool covers its accounting block pool 1:1 — the
        BlockManager's admission control then guarantees the runtime
        never OOMs before accounting does. The pools hold nothing yet, so
        they are dropped before the larger ones are allocated: the device
        never holds both."""
        if n_pages_total <= self.n_pages:
            return
        assert not self.refs, "grow() is a wiring-time call: pages are held"
        self._set_pools((None,) * len(self.cache_keys))
        self._set_pools(self._zero_pools(n_pages_total))
        self.free.extend(range(self.n_pages, n_pages_total))
        self.n_pages = n_pages_total

    def _writable_page(self, e: ProgramEntry, idx: int) -> int:
        """The physical page for e's logical block `idx`, made exclusive:
        a shared page (refs > 1) is COW-split through the page_copy
        kernel before the first write lands on it."""
        pi = e.pages[idx]
        if self.refs.get(pi, 1) == 1:
            return pi
        with span("kv.cow_split", src_page=pi):
            new = self._alloc_page()
            src = jnp.asarray([pi], jnp.int32)
            dst = jnp.asarray([new], jnp.int32)
            self._set_pools(copy_pages(p, src, dst, interpret=self.interpret)
                            for p in self.pools)
        if self.verify_copies:          # differential harness: bit-exact?
            self.copy_checks.append(all(
                bool(jnp.array_equal(p[:, new], p[:, pi]))
                for p in self.pools))
        self.refs[pi] -= 1
        e.pages[idx] = new
        self.cow_splits += 1
        if self.obs is not None:
            self.obs.cow_splits.inc(1.0, (self.obs_replica,))
        return new

    def evict(self, program_id: str, force: bool = False) -> bool:
        """Deref the program's pages. A *pinned* program (TTL retention in
        flight) refuses eviction unless ``force=True`` — returning False
        instead of silently freeing pages the next turn depends on."""
        e = self.programs.get(program_id)
        if e is None:
            return True
        if e.pinned and not force:
            return False
        del self.programs[program_id]
        for pi in e.pages:
            self._deref(pi)
        self._last.pop(program_id, None)
        return True

    def pin(self, program_id: str) -> None:
        self.programs[program_id].pinned = True

    def unpin(self, program_id: str) -> None:
        self.programs[program_id].pinned = False

    def pages_of(self, program_id: str) -> list[int]:
        return list(self.programs[program_id].pages)

    def page_ref(self, pi: int) -> int:
        return self.refs.get(pi, 0)

    # ----------------------------------------------- physical prefix sharing
    def attach_index(self, index) -> None:
        """Wire a :class:`~repro.serving.prefix.RadixPrefixIndex` to this
        runtime: LRU eviction of a page-stamped node derefs its physical
        pages here (freeing them once no program references them)."""
        def _on_evict(node):
            for pi in (node.page_ids or []):
                self._deref(pi)
        index.on_evict_node = _on_evict

    def adopt_prefix(self, index, program_id: str,
                     hashes: tuple[int, ...], now: float = 0.0,
                     max_tokens: Optional[int] = None) -> int:
        """Radix hit → shared physical pages: match `hashes` against the
        page-stamped index and create `program_id`'s entry referencing
        the SAME page ids (refcount bump, zero copy). Returns the shared
        token count (0 = miss). The first divergent write COW-splits.

        ``max_tokens`` caps the adopted length below the block boundary
        (the scheduler charges at most ``prompt_len - 1`` cached tokens,
        so the last prompt token is recomputed *into the shared page* —
        the append that exercises the COW split)."""
        blocks, node = index.acquire(hashes, now)
        if node is None:
            return 0
        ids = index.path_page_ids(node)
        index.release(node)      # physical safety lives in self.refs now
        if ids is None or len(ids) < blocks:
            return 0
        tokens = blocks * self.page_size
        if max_tokens is not None and max_tokens < tokens:
            tokens = max_tokens
        blocks = math.ceil(tokens / self.page_size)
        if blocks == 0:
            return 0
        ids = ids[:blocks]
        for pi in ids:
            self.refs[pi] += 1
        self.programs[program_id] = ProgramEntry(list(ids), tokens)
        return tokens

    def publish_prefix(self, index, program_id: str,
                       hashes: tuple[int, ...], now: float = 0.0) -> int:
        """Publish this program's full pages into a page-stamped radix
        index. Newly inserted blocks hand the tree its own reference;
        blocks already present dedup: the program's duplicate pages are
        swapped for the tree's canonical ones and its copies deref'd.
        Returns the number of deduplicated pages."""
        e = self.programs[program_id]
        full = min(len(hashes), e.length // self.page_size)
        if full == 0:
            return 0
        hs = tuple(hashes[:full])
        new, dup, node = index.insert(hs, None, 0, now,
                                      page_ids=e.pages[:full])
        if node is None:
            return 0
        if new:                  # the tree holds a ref on every new page
            for pi in e.pages[full - new:full]:
                self.refs[pi] += 1
        canonical = index.path_page_ids(node)
        index.release(node)      # tree retention is LRU, not a lock
        if canonical is None:    # mixed page-stamped/accounting-only path
            return 0
        deduped = 0
        shared = full - new      # leading blocks already in the tree
        for i in range(shared):
            mine, theirs = e.pages[i], canonical[i]
            if mine != theirs:
                self.refs[theirs] += 1
                self._deref(mine)
                e.pages[i] = theirs
                deduped += 1
        return deduped

    # ------------------------------------------------------- tier staging
    def read_pages(self, ids: list[int]) -> tuple[np.ndarray, ...]:
        """Pages ``ids`` as contiguous (L, pow2(n), page, *row) host
        buffers, one per pool, the padded slots repeating the last page:
        one gather kernel per pool, at a power-of-two width so tier moves
        compile O(log) shapes, then one D2H copy each. Spans:
        ``kv.gather`` (the dispatches; their device work is the trace's
        ``jit_gather_pages``), ``kv.d2h`` (the blocking copies)."""
        (pad,) = _pad_ids(list(ids), _pow2(len(ids)))
        with span("kv.gather"):
            pad = jnp.asarray(pad)
            bufs = [gather_pages(p, pad, interpret=self.interpret)
                    for p in self.pools]
        with span("kv.d2h"):
            return tuple(np.asarray(b) for b in bufs)

    def stage_out(self, program_id: str) -> tuple:
        """The program's scattered pages as contiguous host staging
        buffers, one per pool (:meth:`read_pages`, padding included, so
        :meth:`restore` copies them back as they are) — the unit a tier
        move DMAs to host DRAM in one transfer — then its length."""
        e = self.programs[program_id]
        return (*self.read_pages(e.pages), e.length)

    def restore(self, program_id: str, *staged_and_length) -> list[int]:
        """Scatter staged buffers (:meth:`stage_out`'s, one per pool, W
        pages wide, W a power of two; the length last) back into freshly
        allocated physical pages: the leading ``ceil(length / page)`` = n
        pages, fewer than were staged where the usable prefix shrank.
        Each buffer goes to the device as it is, with no host copy
        (``kv.h2d``, once per pool: the copy's dispatch, as the transfer
        completes asynchronously); one dispatch for every pool
        (``kv.scatter``, the trace's ``jit__restore_pages``) pads on the
        device, slot i carrying staged page min(i, n - 1)."""
        *staged, length = staged_and_length
        W = staged[0].shape[1]
        n = math.ceil(length / self.page_size)
        assert W == _pow2(W) and 0 < n <= W, (W, n)
        stale = self.programs.pop(program_id, None)
        if stale is not None:           # defensive: never leak pages
            for pi in stale.pages:
                self._deref(pi)
        pages: list[int] = []
        try:
            for _ in range(n):
                pages.append(self._alloc_page())
        except MemoryError:             # roll back the partial allocation
            for pi in pages:
                self._deref(pi)
            raise
        (ids,) = _pad_ids(pages, W)
        on_device = []
        for buf in staged:
            with span("kv.h2d"):
                on_device.append(jax.device_put(buf))
        with span("kv.scatter"):
            self._set_pools(_restore_pages(
                self.pools, tuple(on_device), jnp.asarray(ids),
                jnp.asarray(n, jnp.int32), interpret=self.interpret))
        self.programs[program_id] = ProgramEntry(pages, length)
        return pages

    # ----------------------------------------------------------- prefill
    def prefill(self, params, program_id: str, tokens: jax.Array,
                pad_to: Optional[int] = None,
                max_len: Optional[int] = None) -> jax.Array:
        """Run the model's prefill and scatter the contiguous per-layer KV
        into this program's (scattered) physical pages. Returns the final
        *real* position's logits and seeds the program's greedy
        continuation (so a chunked prefill's last chunk leaves decode
        ready to run).

        ``pad_to`` pads the forward pass to a bucketed length (causal
        attention makes the trailing junk tokens invisible to the real
        ones, and only the real KV is scattered into pages) — callers use
        power-of-two buckets to bound XLA recompilation to
        O(log max_chunk) shapes, the TPU serving constraint. The scratch
        is the power-of-two bucket of the padded end, capped at that of
        ``max_len`` (the longest program the caller holds, which prices
        the scratch). A padded chunk that would overrun the cap runs as
        exact power-of-two pieces instead, so every forward shape is a
        pair of powers of two, however the chunks fall."""
        tokens = np.asarray(tokens, np.int32)     # padded on the host
        S = tokens.shape[-1]
        e = self.programs.setdefault(program_id, ProgramEntry([], 0))
        start = e.length
        Sp = max(pad_to or S, S)
        if max_len is not None and start + Sp > _pow2(max_len):
            assert start + S <= max_len, (start, S, max_len)
            i = 0
            for bit in reversed(range(S.bit_length())):
                if S >> bit & 1:
                    logits = self.prefill(params, program_id,
                                          tokens[i:i + (1 << bit)],
                                          max_len=max_len)
                    i += 1 << bit
            return logits
        with span("model.prefill", program=program_id, start=start,
                  tokens=S, pad_to=Sp):
            self._ensure_capacity(e, start + S)       # pages for REAL tokens only
            T = _pow2(max(len(e.pages) * self.page_size, start + Sp))
            tokens = np.pad(tokens, (0, Sp - S))
            cache = self.model.init_cache(1, T)
            if start:
                # re-materialize existing pages into the contiguous scratch
                cache = self._gather_into(cache, e)
            logits, cache = self._forward(
                params, tokens=tokens.reshape(1, Sp), cache=cache,
                cache_len=jnp.asarray(start, jnp.int32),
                mode="extend" if start else "prefill",
                logits_at=jnp.asarray(S - 1, jnp.int32))
            self._scatter_from(cache, e, start, S)
            e.length = start + S
            self._last[program_id] = jnp.argmax(logits[0, 0]).astype(jnp.int32)
            return logits[0, 0]

    def _scatter_from(self, cache, e: ProgramEntry, start: int, count: int):
        """Copy each cache leaf's [:, 0, start:start+count] into its
        pool's physical pages."""
        ps = self.page_size
        blocks = list(range(start // ps, (start + count - 1) // ps + 1))
        ids = [self._writable_page(e, b) for b in blocks]  # COW-split shared
        ids, blocks = _pad_ids(ids, _pow2(len(ids)), blocks)
        self._set_pools(_scatter_span(
            self.pools, tuple(cache[k] for k in self.cache_keys),
            jnp.asarray(ids), jnp.asarray(blocks),
            jnp.asarray(start, jnp.int32),
            jnp.asarray(start + count, jnp.int32), interpret=self.interpret))

    def _gather_into(self, cache, e: ProgramEntry):
        """Pages covering [0, e.length) -> the scratch's leading positions.
        Positions from e.length on may receive stale page bytes: the
        forward overwrites the chunk's own span and masks the rest."""
        ps = self.page_size
        n = math.ceil(e.length / ps)
        keys = self.cache_keys
        T = cache[keys[0]].shape[2]
        (ids,) = _pad_ids(e.pages[:n], min(_pow2(n), T // ps))
        cache.update(zip(keys, _gather_span(
            tuple(cache[k] for k in keys), self.pools, jnp.asarray(ids),
            interpret=self.interpret)))
        return cache

    # ------------------------------------------------------------ decode
    def _decode_step_impl(self, params, pools, toks, tables, lens,
                          app_pages, app_offs):
        """One fused decode step for a whole batch: toks (B,) last tokens;
        tables (B, n_tab) sentinel-0-padded ragged block tables; lens (B,)
        CURRENT lengths (the kernel attends over the old pages; the new
        token's own row is merged analytically); app_pages/app_offs (B,)
        where each sequence's new row lands. The leading dense layers,
        then one ``lax.scan`` over the stacked blocks (pool layer i is
        model layer i); one paged attention kernel per layer for ALL B
        programs, and ONE ``append_tokens`` scatter for all B x L new rows
        — the pools are consumed in their native layout (no per-layer
        slice, no transpose, no dtype-cast copy of the pool, ROADMAP
        4(a)). Returns the logits, the next tokens, the pools and the
        step's expert copies (``moe_copies``)."""
        cfg = self.cfg
        cparams = cast_params(params, self.model.specs(), cfg.compute_dtype)
        x = cparams["embed"][toks][:, None].astype(cfg.compute_dtype)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        attend = self._mla_attend if cfg.mla else self._gqa_attend

        def layer(x, li, p):
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            a, rows = attend(p["attn"], h, pools, tables, lens, li)
            x = x + a
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            held = jnp.zeros((), jnp.int32)
            if "router" in p["mlp"]:
                f, held = moe_dropless(p["mlp"], h2, cfg)
            else:
                f = mlp_apply(p["mlp"], h2, cfg.activation)
            return x + f, (rows, held)

        first_k = cfg.moe.first_k_dense if cfg.moe else 0
        dense = []
        for i in range(first_k):             # leading dense layers
            x, out = layer(x, jnp.asarray(i, jnp.int32),
                           take_layer(cparams["dense_layers"], i))
            dense.append(out)
        x, (rows, held) = jax.lax.scan(
            lambda x, inp: layer(x, *inp), x,
            (jnp.arange(first_k, cfg.num_layers, dtype=jnp.int32),
             cparams["blocks"]))
        if dense:                            # their rows lead the scan's
            rows = jax.tree.map(
                lambda r, *d: jnp.concatenate([jnp.stack(d), r]), rows,
                *[out[0] for out in dense])
        # rows: every layer's new-token row per pool (L, B, *row),
        # scattered into the (exclusive) append pages in ONE aliased call
        pools = append_tokens(pools, rows, app_pages, app_offs,
                              interpret=self.interpret)
        x = rms_norm(x, cparams["final_norm"], cfg.norm_eps)
        head = cparams["embed"].T if cfg.tie_embeddings else cparams["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))[:, 0]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        copies = None
        if cfg.moe is not None:          # [to a held expert, to any expert]
            n_all = (cfg.num_layers - first_k) * cfg.moe.top_k * toks.shape[0]
            copies = jnp.stack([jnp.sum(held), jnp.int32(n_all)])
        return logits, nxt, pools, copies

    def _gqa_attend(self, p, h, pools, tables, lens, li):
        """Grouped-query attention of one layer over the K/V pools: the
        kernel's online-softmax state, with the new token's own (k, v) —
        not yet in any page — merged in exactly. Returns the attention
        output and the new token's (k, v) rows."""
        cfg = self.cfg
        B = h.shape[0]
        KV, Dh, H = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
        G = H // KV
        scale = 1.0 / math.sqrt(Dh)
        k_pages, v_pages = pools
        q, k, v = attn_mod.qkv_project(p, h, cfg, lens[:, None])
        qd = q[:, 0]                               # (B, H, Dh)
        k_new, v_new = k[:, 0], v[:, 0]            # (B, KV, Dh)
        acc, m, l = paged_decode_attention(
            qd, k_pages, v_pages, tables, lens, layer=li, scale=scale,
            interpret=self.interpret, return_residuals=True)
        qg = qd.reshape(B, KV, G, Dh).astype(jnp.float32)
        kf = k_new.astype(jnp.float32)
        vf = v_new.astype(jnp.float32)
        s_self = jnp.einsum("bkgd,bkd->bkg", qg, kf) * scale
        m2 = jnp.maximum(m, s_self)
        alpha = jnp.exp(m - m2)
        p_self = jnp.exp(s_self - m2)
        acc2 = acc * alpha[..., None] + p_self[..., None] * vf[:, :, None, :]
        l2 = l * alpha + p_self
        o = (acc2 / jnp.maximum(l2, 1e-30)[..., None]).reshape(B, H, Dh)
        return attn_mod.out_project(p, o.astype(h.dtype)[:, None]), \
            (k_new, v_new)

    def _mla_attend(self, p, h, pools, tables, lens, li):
        """Absorbed latent attention of one layer over the latent pool:
        each head's query is taken into the latent space (q_nope W_kb^T,
        then its rotary part, zero-padded to the row's stored width),
        attends over the rows [c, k_pe] of the
        pages and over the new token's own row, exactly merged; the
        latent output (attention over c) leaves through W_vb and W_o.
        Returns the attention output and the new token's row."""
        cfg = self.cfg
        a = cfg.mla
        R, Dn = a.kv_lora_rank, a.qk_nope_head_dim
        scale = 1.0 / math.sqrt(a.qk_head_dim)
        (c_pages,) = pools
        pos = lens[:, None]
        q = attn_mod.mla_query(p, h, cfg, pos)[:, 0]         # (B, H, Dn+Dr)
        row = attn_mod.mla_latent(p, h, cfg, pos)[:, 0]      # (B, W)
        q_lat = jnp.einsum("bhk,rhk->bhr", q[..., :Dn], p["wkb"])
        pad = jnp.zeros(q.shape[:2] + (a.cache_width - a.cache_dim,), q.dtype)
        qf = jnp.concatenate([q_lat, q[..., Dn:], pad], axis=-1)  # (B, H, W)
        acc, m, l = paged_mla_decode(qf, c_pages, tables, lens, layer=li,
                                     scale=scale, value_dim=R,
                                     interpret=self.interpret)
        rf = row.astype(jnp.float32)
        s_self = jnp.einsum("bhd,bd->bh", qf.astype(jnp.float32), rf) * scale
        m2 = jnp.maximum(m, s_self)
        alpha = jnp.exp(m - m2)
        p_self = jnp.exp(s_self - m2)
        acc2 = acc * alpha[..., None] + p_self[..., None] * rf[:, None, :R]
        l2 = l * alpha + p_self
        o_lat = acc2 / jnp.maximum(l2, 1e-30)[..., None]     # (B, H, R)
        o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(h.dtype), p["wvb"])
        return attn_mod.out_project(p, o[:, None]), (row,)

    def decode_batch(self, params, program_ids: list[str]) -> list[jax.Array]:
        """One decode step for the WHOLE batch through one fused kernel
        step per layer. Returns each program's next-token logits, in
        ``program_ids`` order.

        Per-row results are independent of batch composition and of the
        table padding width (dead table slots never reach the compute or
        the accumulators), so ``decode_batch(ids)`` is bit-identical to
        ``[decode(pid) for pid in ids]`` in any order."""
        if not program_ids:
            return []
        assert len(set(program_ids)) == len(program_ids), \
            "duplicate program ids in one decode batch"
        with span("model.decode", rows=len(program_ids)) as sp:
            entries = [self.programs[pid] for pid in program_ids]
            ps = self.page_size
            for e in entries:
                self._ensure_capacity(e, e.length + 1)
                # every append page must be exclusive BEFORE the tables are
                # built: a COW split mid-batch would leave some row's table
                # pointing at the stale shared page
                self._writable_page(e, e.length // ps)
            B = len(entries)
            # ragged tables, padded to a pow2 width with the valid sentinel
            # page 0 (the kernel's DMA index map reads EVERY slot — see
            # kernels/decode_attention: garbage padding is an OOB fetch on
            # hardware); pow2 bucketing bounds XLA retraces to O(log pages)
            max_pages = max(len(e.pages) for e in entries)
            n_tab = 1 << max(0, max_pages - 1).bit_length()
            if sp is not None:
                sp.set_metadata(n_tab=n_tab)
            tables = np.zeros((B, n_tab), np.int32)
            for i, e in enumerate(entries):
                tables[i, :len(e.pages)] = e.pages
            lens = np.asarray([e.length for e in entries], np.int32)
            app_pages = np.asarray([e.pages[e.length // ps] for e in entries],
                                   np.int32)
            app_offs = np.asarray([e.length % ps for e in entries], np.int32)
            assert len(set(app_pages.tolist())) == B, \
                "append pages must be pairwise distinct (COW resolved above)"
            toks = jnp.stack([self._last_token(params, pid)
                              for pid in program_ids])
            logits, nxt, pools, self.moe_copies = self._decode_step(
                params, self.pools, toks, jnp.asarray(tables),
                jnp.asarray(lens), jnp.asarray(app_pages),
                jnp.asarray(app_offs))
            self._set_pools(pools)
            for i, pid in enumerate(program_ids):
                self.programs[pid].length += 1
                self._last[pid] = nxt[i]
            return [logits[i] for i in range(B)]

    def decode(self, params, program_id: str) -> jax.Array:
        """One decode step for the program's last token, attention served by
        the Pallas paged kernel against the (possibly pinned) pages.
        Delegates to :meth:`decode_batch` — sequential and batched decode
        share one code path, so they are bit-identical by construction."""
        return self.decode_batch(params, [program_id])[0]

    def seed_token(self, program_id: str, tok: int) -> None:
        self._last[program_id] = jnp.asarray(tok, jnp.int32)

    def _last_token(self, params, program_id: str) -> jax.Array:
        return self._last[program_id]

    # ---------------------------------------------------------- invariants
    def check(self, index=None) -> None:
        """Assert page-refcount conservation (tests / debugging): every
        page's refcount equals the number of program block-table slots
        plus radix-tree stamps referencing it; free pages carry no refs;
        free + referenced partitions the pool exactly."""
        held: dict[int, int] = {}
        for e in self.programs.values():
            for pi in e.pages:
                held[pi] = held.get(pi, 0) + 1
        if index is not None:
            stack = [index.root]
            while stack:
                n = stack.pop()
                stack.extend(n.children.values())
                for pi in (n.page_ids or []):
                    held[pi] = held.get(pi, 0) + 1
        assert held == self.refs, \
            {"expected": held, "refs": self.refs}
        free = set(self.free)
        assert len(free) == len(self.free), "free list has duplicates"
        assert free.isdisjoint(self.refs), free & set(self.refs)
        assert len(free) + len(self.refs) == self.n_pages, \
            (len(free), len(self.refs), self.n_pages)
