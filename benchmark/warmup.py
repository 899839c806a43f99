"""Warm-up of every program shape a cell's traffic can reach, before the
measured window, through the runtime's own entry points.

The shapes follow the paged runtime's buckets (``PagedKVRuntime``):

- decode: one jitted step per (batch rows B, block-table width n_tab),
  n_tab the power of two of the longest row's pages; B runs from 1 to
  ``max_batch``, n_tab over the powers of two the traffic's shortest
  prompt and longest program span;
- prefill: a chunk of S tokens at context ``start`` runs one forward at
  (Sp = max(16, pow2(S)), T = pow2(max(pages * page, start + Sp)),
  prefill or extend), gathers the existing ``start`` tokens' pages
  (width min(pow2(pages), T / page)) and scatters the chunk's pages
  (width pow2(pages touched)); a chunk that would overrun pow2(max_len)
  runs as unpadded power-of-two pieces;
- tier moves: page gathers and scatters at power-of-two widths, and the
  one-page copy-on-write split.

``prefill_calls`` enumerates every (start, S) the engine can issue (any
start, any chunk up to ``chunk``) and keeps a few calls that together
reach every forward, gather and scatter shape. Each warm-up call runs on
a scratch program entry over pages that are handed back afterwards; the
runtime's bookkeeping is restored, so serving starts from an empty pool.
"""
from __future__ import annotations

import math

import numpy as np


def pow2(x):
    """Elementwise next power of two (>= 1)."""
    x = np.maximum(np.asarray(x, np.int64), 1)
    return np.left_shift(1, np.ceil(np.log2(x)).astype(np.int64))


def _shape_keys(start, S, Sp, P2, page):
    """(forward, gather, scatter) shape keys of prefill calls."""
    pages = -(-(start + S) // page)
    T = pow2(np.maximum(pages * page, start + Sp))
    fwd = Sp * 2 + (start > 0) + T * 8192
    g = np.where(start > 0, np.minimum(pow2(-(-start // page)), T // page),
                 0)
    nb = (start + S - 1) // page - start // page + 1
    return fwd, T * 8192 + g, T * 8192 + pow2(nb)


def prefill_calls(max_len: int, chunk: int, page: int
                  ) -> list[tuple[int, int, bool]]:
    """(start, S, padded) calls that reach every prefill shape the engine
    can produce for programs up to ``max_len`` tokens in chunks up to
    ``chunk``. ``padded`` calls pass the engine's bucket as ``pad_to``;
    unpadded ones are the power-of-two pieces of an overrunning chunk."""
    P2 = int(pow2(max_len))
    cand: dict[tuple, tuple] = {}       # shape key -> a call reaching it

    def add(start, S, Sp, padded):
        keys = _shape_keys(start, S, Sp, P2, page)
        for kind, k in zip(("f", "g", "s"), keys):
            if kind == "g":
                live = start > 0
                k, st, s_ = k[live], start[live], S[live]
            else:
                st, s_ = start, S
            u, i = np.unique(k, return_index=True)
            for key, j in zip(u.tolist(), i.tolist()):
                cand.setdefault((kind, key), (int(st[j]), int(s_[j]),
                                              padded))

    starts = np.arange(max_len, dtype=np.int64)
    for S in range(1, chunk + 1):
        Sp = max(16, int(pow2(S)))
        st = starts[(starts + S <= max_len) & (starts + Sp <= P2)]
        if len(st):
            add(st, np.full_like(st, S), Sp, True)
    for b in range(chunk.bit_length()):
        S = 1 << b
        st = starts[(starts + S <= max_len) & (starts > P2 - 2 * chunk)]
        if len(st):
            add(st, np.full_like(st, S), S, False)
    # one call per distinct (start, S) that some key needs
    return sorted(set(cand.values()))


def decode_tables(min_prompt: int, max_total: int, page: int) -> list[int]:
    """Block-table widths the decode step sees: powers of two from the
    shortest prompt's pages to the longest program's."""
    lo = int(pow2(math.ceil((min_prompt + 1) / page)))
    hi = int(pow2(math.ceil(max_total / page)))
    out, n = [], lo
    while n <= hi:
        out.append(n)
        n *= 2
    return out


class Scratch:
    """Scratch program entries on the runtime; ``close`` hands every page
    and table back exactly as they were."""

    def __init__(self, rt):
        self.rt = rt
        self._free = list(rt.free)
        self._refs = dict(rt.refs)
        self._programs = dict(rt.programs)
        self._last = dict(rt._last)
        self.n = 0

    def entry(self, length: int, pages: list[int]):
        from repro.serving.paged_runtime import ProgramEntry
        self.n += 1
        pid = f"warmup-{self.n}"
        for p in pages:
            self.rt.refs[p] = self.rt.refs.get(p, 0) + 1
        self.rt.programs[pid] = ProgramEntry(list(pages), length)
        return pid

    def close(self) -> None:
        rt = self.rt
        rt.free[:] = self._free
        rt.refs.clear()
        rt.refs.update(self._refs)
        rt.programs.clear()
        rt.programs.update(self._programs)
        rt._last.clear()
        rt._last.update(self._last)


def warm_prefill(rt, params, calls, max_len: int) -> None:
    page = rt.page_size
    for start, S, padded in calls:
        sc = Scratch(rt)
        pid = sc.entry(start, list(range(1, 1 + math.ceil(start / page))))
        rt.free[:] = [p for p in rt.free if p not in rt.refs]
        toks = np.zeros(S, np.int32)
        pad = max(16, int(pow2(S))) if padded else None
        rt.prefill(params, pid, toks, pad_to=pad, max_len=max_len)
        sc.close()


def warm_decode(rt, params, max_batch: int, tables: list[int]) -> None:
    """One decode step per (B, n_tab): row i holds n_tab pages, the first
    n_tab - 1 shared by all rows, the last its own append page."""
    import jax.numpy as jnp
    for n_tab in tables:
        for B in range(1, max_batch + 1):
            sc = Scratch(rt)
            shared = list(range(1, n_tab))
            own = list(range(n_tab, n_tab + B))
            pids = [sc.entry(n_tab * rt.page_size - 1, shared + [o])
                    for o in own]
            rt.free[:] = [p for p in rt.free if p not in rt.refs]
            for p in pids:
                rt._last[p] = jnp.zeros((), jnp.int32)
            rt.decode_batch(params, pids)
            sc.close()


def warm_tiers(rt, max_pages: int) -> None:
    """Stage-out gathers and restore scatters at every power-of-two width
    up to ``max_pages``, and one copy-on-write split."""
    n = 1
    while n <= pow2(max_pages):
        sc = Scratch(rt)
        pid = sc.entry(n * rt.page_size, list(range(1, n + 1)))
        rt.free[:] = [p for p in rt.free if p not in rt.refs]
        staged = rt.stage_out(pid)
        rt.evict(pid, force=True)
        rt.restore(pid, *staged)
        sc.close()
        n *= 2
    sc = Scratch(rt)
    a = sc.entry(rt.page_size // 2, [1])
    sc.entry(rt.page_size // 2, [1])
    rt.free[:] = [p for p in rt.free if p not in rt.refs]
    rt._writable_page(rt.programs[a], 0)
    sc.close()
