"""Jitted wrappers for the page gather/scatter/append Pallas kernels.

``interpret=None`` (the default) resolves per-platform through
:func:`repro.kernels.resolve_interpret`: interpret mode on CPU hosts, the
compiled Mosaic path on accelerators.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.page_copy.kernel import (page_gather_kernel,
                                            page_scatter_kernel,
                                            token_append_kernel)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pages(pages, page_ids, *, interpret: bool | None = None):
    """Batch-gather scattered physical pages into one contiguous staging
    buffer (the D2H tier-move unit): (L, n, page, KV, Dh)."""
    return page_gather_kernel(pages, page_ids, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_pages(pages, staging, page_ids, *, interpret: bool | None = None):
    """Scatter a contiguous staging buffer back into physical pages
    (the H2D reload unit); the pool is updated in place."""
    return page_scatter_kernel(pages, staging, page_ids, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def copy_pages(pages, src_ids, dst_ids, *, interpret: bool | None = None):
    """Copy pages src_ids → dst_ids inside one pool (the COW-split
    primitive): gather the shared pages, scatter into the fresh ones."""
    staging = page_gather_kernel(pages, jnp.asarray(src_ids, jnp.int32),
                                 interpret=interpret)
    return page_scatter_kernel(pages, staging,
                               jnp.asarray(dst_ids, jnp.int32),
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def append_tokens(pools, toks, page_ids, offsets, *,
                  interpret: bool | None = None):
    """Append one new token's row per sequence into its (exclusively
    owned, pairwise-distinct) append page, in every pool, all B sequences
    and all L layers in one aliased call: pools, a tuple of (L, P, page,
    *row); toks, one (L, B, *row) per pool; page_ids, offsets (B,).
    Returns the updated pools."""
    return token_append_kernel(tuple(pools), tuple(toks), page_ids, offsets,
                               interpret=interpret)
