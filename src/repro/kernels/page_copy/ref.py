"""Pure-jnp oracles for the page gather/scatter kernels."""
from __future__ import annotations

import jax.numpy as jnp


def page_gather_ref(pages, page_ids) -> jnp.ndarray:
    """pages (L, P, page, *row); page_ids (n,) → (L, n, page, *row)."""
    return pages[:, page_ids]


def page_scatter_ref(pages, staging, page_ids) -> jnp.ndarray:
    """pages (L, P, page, *row); staging (L, n, page, *row);
    page_ids (n,) → pages with rows page_ids replaced by staging."""
    return pages.at[:, page_ids].set(staging)


def copy_pages_ref(pages, src_ids, dst_ids) -> jnp.ndarray:
    """pages[:, dst_ids[i]] = pages[:, src_ids[i]] (COW split oracle)."""
    return pages.at[:, dst_ids].set(pages[:, src_ids])


def append_tokens_ref(pools, toks, page_ids, offsets):
    """pools, a tuple of (L, P, page, *row); toks, one (L, B, *row) per
    pool; page_ids/offsets (B,) → pools with
    pool[:, page_ids[b], offsets[b]] = tok[:, b]."""
    return tuple(p.at[:, page_ids, offsets].set(t.astype(p.dtype))
                 for p, t in zip(pools, toks))
