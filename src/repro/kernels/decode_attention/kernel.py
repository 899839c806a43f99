"""Pallas TPU paged decode attention (vLLM-style block tables).

This is the kernel through which Continuum's TTL-retained KV pages are
consumed on the next turn: the block table holds *physical* page ids, so a
TTL hit means the new request's table points at the pinned pages — no
recompute, no copy.

Scalar-prefetch design: the block table rides as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``); each grid step's K/V page is fetched
from HBM into VMEM by the *index map* reading the table — i.e. the page
indirection happens in the DMA engine, never in the compute path. Grid
(B, n_pages) with the page dimension innermost/sequential: online softmax
accumulates per batch row in VMEM scratch; one (page, KV, D) block serves
ALL query heads of that row (every kv head's G = H/KV query heads share
the single page fetch — the GQA arithmetic-intensity win, and pages are
consumed in their native pool layout so no transpose copy is ever made).

The pools may carry a stacked leading layer dimension
(L, P, page, KV, D): ``layer`` then rides as a third scalar-prefetch
operand and the index map selects the layer *and* the page in the same
DMA — a layer-scanned decode step reads the shared pool directly, with no
per-layer slice materialization (ROADMAP item 4(a)).

Ragged-block-table contract (THE latent-bug fix): Pallas evaluates block
index maps for EVERY grid step — including dead steps whose compute the
kernel body skips via ``pl.when(ip * page >= seq_len)``. The DMA therefore
fetches ``pages[tab[b, ip]]`` even for padding slots of a ragged batch; a
garbage page id there is an out-of-bounds HBM access on hardware (fault or
silent corruption — interpret mode clamps, which is why the bug stayed
latent). The contract is:

- live slots (``ip * page < seq_len``) MUST hold valid physical page ids;
- dead slots MAY hold anything: :func:`sanitize_block_tables` rewrites
  them to the always-valid sentinel page 0 before the table reaches the
  index map, so every DMA in the grid is in-bounds by construction.

The wrapper applies the sanitizer unconditionally — callers padding with
the sentinel themselves (the paged runtime does) pass through unchanged.

VMEM per step: page (page, KV, D)*2 + q (KV, G, D) + acc fp32 ≈
page=64, KV=8, D=128, G=4: ~600 KB.

The latent mode (``paged_mla_decode_kernel``) serves absorbed
multi-head latent attention: one pool whose row [c, k_pe] is the key of
every head and whose leading columns c are the value, so each grid step
makes one DMA; the same grid, table contract and online softmax.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -2.0e38


def sanitize_block_tables(block_tables, seq_lens, page: int) -> jnp.ndarray:
    """Rewrite dead (b, ip) table slots (``ip * page >= seq_lens[b]``) to
    the valid sentinel page 0. Live slots pass through untouched — they
    must already be valid physical page ids (caller contract). After this,
    every id the DMA index map can read is in-bounds for any non-empty
    pool."""
    n_pages = block_tables.shape[1]
    ip = jnp.arange(n_pages, dtype=jnp.int32)
    live = ip[None, :] * page < jnp.asarray(seq_lens, jnp.int32)[:, None]
    return jnp.where(live, block_tables, 0).astype(jnp.int32)


def _paged_step(lens_ref, m_ref, l_ref, acc_ref, o_ref, m_out_ref,
                l_out_ref, scores, *, page: int, n_pages: int,
                normalize: bool):
    """The online softmax over a row's pages, shared by both modes:
    ``scores()`` gives this page's (KV, G, page) scores and a function
    of the probabilities giving their (KV, G, Dv) weighted values."""
    b = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[b]
    live = ip * page < seq_len

    @pl.when(live)
    def _compute():
        s, weigh = scores()
        pos = ip * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
        s = jnp.where(pos < seq_len, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[:, :, None])
        l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(p, axis=2)
        acc_ref[...] = acc_ref[...] * jnp.exp(m_prev - m_new)[:, :, None] \
            + weigh(p)
        m_ref[...] = m_new

    @pl.when(ip == n_pages - 1)
    def _finalize():
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]
        if normalize:
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l[:, :, None]).astype(o_ref.dtype)
        else:
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _decode_kernel(tables_ref, lens_ref, layer_ref, q_ref, k_ref, v_ref,
                   o_ref, m_out_ref, l_out_ref, m_ref, l_ref, acc_ref, *,
                   scale: float, page: int, n_pages: int, normalize: bool):
    def scores():
        q = q_ref[0].astype(jnp.float32)               # (KV, G, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (page, KV, D)
        v = v_ref[0, 0].astype(jnp.float32)
        # batched over kv heads: (KV,G,D) x (page,KV,D) -> (KV,G,page)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,)))) * scale
        # (KV,G,page) x (page,KV,D) -> (KV,G,D)
        return s, lambda p: jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))))

    _paged_step(lens_ref, m_ref, l_ref, acc_ref, o_ref, m_out_ref,
                l_out_ref, scores, page=page, n_pages=n_pages,
                normalize=normalize)


def _latent_kernel(tables_ref, lens_ref, layer_ref, q_ref, c_ref,
                   o_ref, m_out_ref, l_out_ref, m_ref, l_ref, acc_ref, *,
                   scale: float, page: int, n_pages: int, value_dim: int):
    def scores():
        q = q_ref[0, 0].astype(jnp.float32)            # (H, D)
        c = c_ref[0, 0].astype(jnp.float32)            # (page, D)
        # every head against the one latent key: (H,D) x (page,D) -> (H,page)
        s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ()))) * scale
        # the value is the key's leading value_dim columns: (H,page) x
        # (page,Dv) -> (H,Dv), from the same page fetch
        return s[None], lambda p: jax.lax.dot_general(
            p[0], c[:, :value_dim], (((1,), (0,)), ((), ())))[None]

    _paged_step(lens_ref, m_ref, l_ref, acc_ref, o_ref, m_out_ref,
                l_out_ref, scores, page=page, n_pages=n_pages,
                normalize=False)


def paged_decode_attention_kernel(q, k_pages, v_pages, block_tables, seq_lens,
                                  *, layer=None, scale: float | None = None,
                                  interpret: bool | None = None,
                                  return_residuals: bool = False):
    """q (B, H, D); k/v_pages (P, page, KV, D) or layer-stacked
    (L, P, page, KV, D) with ``layer`` (int or traced scalar) selecting
    the layer in the DMA index map; block_tables (B, n_pages);
    seq_lens (B,).

    Default: the normalized attention output (B, H, D). With
    ``return_residuals=True``: ``(acc, m, l)`` — the UNnormalized fp32
    accumulator (B, KV, G, D) and the per-(kv-head, q-head) running max /
    denominator (B, KV, G) — so a caller can merge further online-softmax
    terms (e.g. the just-computed token's own k/v, not yet in any page)
    exactly, then normalize.

    Ragged batches: dead table slots are sanitized to sentinel page 0
    before the pallas call (see module docstring for the contract)."""
    interpret = resolve_interpret(interpret)
    if k_pages.ndim == 4:
        k_pages = k_pages[None]
        v_pages = v_pages[None]
    L, P, page, KV, D = k_pages.shape
    B, H, _ = q.shape
    n_pages = block_tables.shape[1]
    assert n_pages >= 1, "block table must cover at least one page"
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    # kernel-side guarantee: no dead slot's garbage ever reaches the DMA
    tables = sanitize_block_tables(block_tables, seq_lens, page)
    lay = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
    # (B, KV, G, D): all G query heads of a kv head share one page fetch
    qr = q.reshape(B, KV, G, D)

    kern = functools.partial(_decode_kernel, scale=scale, page=page,
                             n_pages=n_pages,
                             normalize=not return_residuals)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,             # block_tables, seq_lens, layer
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, KV, G, D),
                         lambda b, ip, tab, lens, lay: (b, 0, 0, 0)),
            # page indirection happens here: the DMA index map reads the
            # (sanitized) table — and the layer scalar — for every step
            pl.BlockSpec((1, 1, page, KV, D),
                         lambda b, ip, tab, lens, lay:
                         (lay[0], tab[b, ip], 0, 0, 0)),
            pl.BlockSpec((1, 1, page, KV, D),
                         lambda b, ip, tab, lens, lay:
                         (lay[0], tab[b, ip], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, KV, G, D),
                         lambda b, ip, tab, lens, lay: (b, 0, 0, 0)),
            pl.BlockSpec((1, KV, G),
                         lambda b, ip, tab, lens, lay: (b, 0, 0)),
            pl.BlockSpec((1, KV, G),
                         lambda b, ip, tab, lens, lay: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G, D), jnp.float32),
        ],
    )
    o_dtype = jnp.float32 if return_residuals else q.dtype
    out, m, l = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, G, D), o_dtype),
                   jax.ShapeDtypeStruct((B, KV, G), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, G), jnp.float32)],
        interpret=interpret,
    )(tables, seq_lens, lay, qr, k_pages, v_pages)
    if return_residuals:
        return out, m, l
    return out.reshape(B, H, D)


def paged_mla_decode_kernel(q, c_pages, block_tables, seq_lens, *,
                            layer=None, scale: float, value_dim: int,
                            interpret: bool | None = None):
    """The latent mode: absorbed multi-head latent attention decode.
    q (B, H, D) — each head's query in the latent space, [q_nope W_kb^T,
    q_pe]; c_pages (P, page, D) or layer-stacked (L, P, page, D), one
    row [c, k_pe] per token shared by every head; its leading
    ``value_dim`` columns (c) are also the value, so a page is fetched
    once for both. Same grid, table sanitizing and online softmax as the
    grouped-query mode; ``scale`` is given (1/sqrt of the unabsorbed
    query-key width).

    Returns the UNnormalized fp32 accumulator (B, H, value_dim) and the
    per-head running max and denominator (B, H), for the caller to merge
    the new token's own row and normalize."""
    interpret = resolve_interpret(interpret)
    if c_pages.ndim == 3:
        c_pages = c_pages[None]
    L, P, page, D = c_pages.shape
    B, H, _ = q.shape
    n_pages = block_tables.shape[1]
    assert n_pages >= 1, "block table must cover at least one page"
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    tables = sanitize_block_tables(block_tables, seq_lens, page)
    lay = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
    kern = functools.partial(_latent_kernel, scale=scale, page=page,
                             n_pages=n_pages, value_dim=value_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,             # block_tables, seq_lens, layer
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, H, D),
                         lambda b, ip, tab, lens, lay: (b, 0, 0, 0)),
            # one DMA per page: the latent row is key and value both
            pl.BlockSpec((1, 1, page, D),
                         lambda b, ip, tab, lens, lay:
                         (lay[0], tab[b, ip], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, H, value_dim),
                         lambda b, ip, tab, lens, lay: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda b, ip, tab, lens, lay: (b, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda b, ip, tab, lens, lay: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, H), jnp.float32),
            pltpu.VMEM((1, H), jnp.float32),
            pltpu.VMEM((1, H, value_dim), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, H, value_dim), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, H), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, H), jnp.float32)],
        interpret=interpret,
    )(tables, seq_lens, lay, q.reshape(B, 1, H, D), c_pages)
    return acc[:, 0], m[:, 0], l[:, 0]
