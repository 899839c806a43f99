"""Jitted wrapper for the paged decode attention Pallas kernel.

``interpret=None`` (the default) resolves per-platform through
:func:`repro.kernels.resolve_interpret`: interpret mode on CPU hosts, the
compiled Mosaic path on accelerators.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention.kernel import (
    paged_decode_attention_kernel, paged_mla_decode_kernel)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "return_residuals"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                           layer=None, scale=None, interpret=None,
                           return_residuals=False):
    return paged_decode_attention_kernel(q, k_pages, v_pages, block_tables,
                                         seq_lens, layer=layer, scale=scale,
                                         interpret=interpret,
                                         return_residuals=return_residuals)


@functools.partial(jax.jit, static_argnames=("scale", "value_dim",
                                             "interpret"))
def paged_mla_decode(q, c_pages, block_tables, seq_lens, *, layer=None,
                     scale, value_dim, interpret=None):
    """The latent mode, under its own name in a device trace."""
    return paged_mla_decode_kernel(q, c_pages, block_tables, seq_lens,
                                   layer=layer, scale=scale,
                                   value_dim=value_dim, interpret=interpret)
