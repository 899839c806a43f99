"""Pallas TPU page gather/scatter for KV tier moves.

Tier demotion (HBM→DRAM) and promotion (DRAM→HBM) move a program's KV
pages, but those pages are *scattered* across the physical pools —
issuing one small DMA per page would serialize on link latency. These
kernels batch the indirection: the page-id table rides as a
scalar-prefetch operand, and each grid step's source (gather) or
destination (scatter) page is selected by the *index map* reading the
table — the indirection is resolved in the DMA engine, never in the
compute path (same scalar-prefetch design as the paged decode kernel).

- ``page_gather_kernel``: scattered pages → one contiguous staging
  buffer, ready for a single bulk D2H transfer.
- ``page_scatter_kernel``: a contiguous staging buffer (e.g. just
  reloaded H2D) → scattered physical pages. The pool is aliased
  in-place (``input_output_aliases``), so untouched pages keep their
  contents — which is also what makes this the copy-on-write split
  primitive: gather the shared page, scatter into the fresh one.
- ``token_append_kernel``: the batched-decode append unit — one new
  token's row per sequence in every pool (K and V, or the one latent
  pool), all B sequences and all L layers, scattered into each
  sequence's (exclusive) append page in ONE aliased call, instead of
  B x L whole-pool ``.at[].set`` copies.

Layout is the pools' native (L, P, page, *row): ``row`` is (KV, Dh) for
grouped-query K/V pools and (D,) for a latent pool; grid (n, L) with one
(page, *row) block per step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _copy_kernel(tab_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def _scatter_kernel(tab_ref, staging_ref, pool_ref, out_ref):
    out_ref[...] = staging_ref[...]


def _page_block(pages):
    """One (1, 1, page, *row) block of the pool and its trailing zeros."""
    return (1, 1) + pages.shape[2:], (0,) * (pages.ndim - 2)


def page_gather_kernel(pages, page_ids, *,
                       interpret: bool | None = None):
    """pages (L, P, page, *row); page_ids (n,) int32 →
    staging (L, n, page, *row): staging[:, i] = pages[:, page_ids[i]]."""
    interpret = resolve_interpret(interpret)
    L = pages.shape[0]
    n = page_ids.shape[0]
    block, z = _page_block(pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                        # the page-id table
        grid=(n, L),
        in_specs=[
            # the DMA index map reads the table: page indirection in-engine
            pl.BlockSpec(block, lambda i, l, tab: (l, tab[i]) + z),
        ],
        out_specs=pl.BlockSpec(block, lambda i, l, tab: (l, i) + z),
    )
    return pl.pallas_call(
        _copy_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, n) + pages.shape[2:], pages.dtype),
        interpret=interpret,
    )(page_ids.astype(jnp.int32), pages)


def page_scatter_kernel(pages, staging, page_ids, *,
                        interpret: bool | None = None):
    """pages (L, P, page, *row); staging (L, n, page, *row);
    page_ids (n,) int32 → pages with pages[:, page_ids[i]] = staging[:, i]
    (pool aliased in place; other pages untouched)."""
    interpret = resolve_interpret(interpret)
    L = pages.shape[0]
    n = page_ids.shape[0]
    assert staging.shape == (L, n) + pages.shape[2:], \
        (staging.shape, pages.shape)
    block, z = _page_block(pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, L),
        in_specs=[
            pl.BlockSpec(block, lambda i, l, tab: (l, i) + z),
            # the pool rides along only to be aliased into the output;
            # its block mapping mirrors the output's so the pair is 1:1
            pl.BlockSpec(block, lambda i, l, tab: (l, tab[i]) + z),
        ],
        out_specs=pl.BlockSpec(block, lambda i, l, tab: (l, tab[i]) + z),
    )
    return pl.pallas_call(
        _scatter_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        # operand 2 (after the scalar table and staging) is the pool;
        # alias it so unvisited pages keep their contents
        input_output_aliases={2: 0},
        interpret=interpret,
    )(page_ids.astype(jnp.int32), staging, pages)


def _append_kernel(tab_ref, off_ref, *refs):
    b = pl.program_id(0)
    off = off_ref[b]
    n = len(refs) // 3                  # token rows, pools in, pools out
    for tok_ref, in_ref, out_ref in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        # write the token row into slot `off` of the page, pass the rest
        row = jax.lax.broadcasted_iota(jnp.int32, in_ref.shape, 2)
        out_ref[...] = jnp.where(row == off, tok_ref[...], in_ref[...])


def token_append_kernel(pools, toks, page_ids, offsets, *,
                        interpret: bool | None = None):
    """Batched-decode append: pools, a tuple of (L, P, page, *row);
    toks, one (L, B, *row) per pool — the B new tokens' rows for every
    layer; page_ids (B,) the page each sequence appends into; offsets
    (B,) the in-page slot. One grid step per (sequence, layer) writes one
    token row into every aliased pool. Returns the tuple of pools.

    Caller contract: ``page_ids`` are pairwise distinct and exclusively
    owned (COW splits resolved before the call) — the aliased blocks would
    otherwise race."""
    interpret = resolve_interpret(interpret)
    L = pools[0].shape[0]
    B = page_ids.shape[0]
    n = len(pools)
    tok_specs, page_specs, rows = [], [], []
    for pool, tok in zip(pools, toks):
        assert tok.shape == (L, B) + pool.shape[3:], (tok.shape, pool.shape)
        block, z = _page_block(pool)
        # the token row rides as (L, B, 1, *row): its block's trailing
        # dims are the array's whole row, as the chip's tiling wants
        rows.append(tok[:, :, None].astype(pool.dtype))
        tok_specs.append(pl.BlockSpec(
            (1, 1, 1) + pool.shape[3:],
            lambda b, l, tab, off, z=z: (l, b) + z))
        page_specs.append(pl.BlockSpec(
            block, lambda b, l, tab, off, z=z: (l, tab[b]) + z))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # page-id table, offsets
        grid=(B, L),
        in_specs=tok_specs + page_specs,
        out_specs=page_specs,
    )
    return tuple(pl.pallas_call(
        _append_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # the pools follow the two scalar tables and the token rows; alias
        # them so unvisited pages keep their contents
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=interpret,
    )(page_ids.astype(jnp.int32), offsets.astype(jnp.int32), *rows, *pools))
