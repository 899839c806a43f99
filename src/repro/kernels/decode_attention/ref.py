"""Pure-jnp oracle for paged GQA decode attention."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, seq_lens,
                               *, layer=None,
                               scale: float | None = None) -> jnp.ndarray:
    """q (B, H, D); k/v_pages (P, page, KV, D) or layer-stacked
    (L, P, page, KV, D) with ``layer`` selecting the layer; block_tables
    (B, max_pages) int32 (physical page per logical block); seq_lens (B,)
    -> out (B, H, D).

    Same ragged-table contract as the kernel: dead slots (beyond
    ``seq_lens``) are sanitized to page 0 before the gather, so garbage
    padding is harmless here too.
    """
    if k_pages.ndim == 5:
        li = 0 if layer is None else layer
        k_pages = k_pages[li]
        v_pages = v_pages[li]
    B, H, D = q.shape
    P, page, KV, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    G = H // KV
    scale = scale if scale is not None else 1.0 / (D ** 0.5)

    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    ip = jnp.arange(max_pages, dtype=jnp.int32)
    live = ip[None, :] * page < seq_lens[:, None]
    block_tables = jnp.where(live, block_tables, 0).astype(jnp.int32)

    # gather each sequence's logical KV (B, max_pages*page, KV, D)
    kg = k_pages[block_tables].reshape(B, max_pages * page, KV, D)
    vg = v_pages[block_tables].reshape(B, max_pages * page, KV, D)
    qg = q.reshape(B, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, kg.astype(jnp.float32)) * scale
    pos = jnp.arange(max_pages * page)
    mask = pos[None] < seq_lens[:, None]
    s = jnp.where(mask[:, None, None], s, -2.0e38)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p, vg.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def paged_mla_decode_ref(q, c_pages, block_tables, seq_lens, *, layer=None,
                         scale: float, value_dim: int):
    """The latent mode's oracle: q (B, H, D); c_pages (P, page, D) or
    (L, P, page, D) -> (acc (B, H, value_dim), m (B, H), l (B, H)), the
    unnormalized float32 accumulator and softmax statistics over each
    row's first ``seq_lens`` positions."""
    if c_pages.ndim == 4:
        c_pages = c_pages[0 if layer is None else layer]
    B, H, D = q.shape
    P, page, _ = c_pages.shape
    n = block_tables.shape[1]
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    live = jnp.arange(n)[None, :] * page < seq_lens[:, None]
    tables = jnp.where(live, block_tables, 0).astype(jnp.int32)
    c = c_pages[tables].reshape(B, n * page, D).astype(jnp.float32)
    s = jnp.einsum("bhd,btd->bht", q.astype(jnp.float32), c,
                   precision=jax.lax.Precision.HIGHEST) * scale
    valid = jnp.arange(n * page)[None] < seq_lens[:, None]
    s = jnp.where(valid[:, None], s, -2.0e38)
    m = jnp.max(s, -1)
    p = jnp.where(valid[:, None], jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum("bht,btd->bhd", p, c[..., :value_dim],
                     precision=jax.lax.Precision.HIGHEST)
    return acc, m, jnp.sum(p, -1)
