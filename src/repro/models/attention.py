"""Attention: GQA with RoPE / bias / QK-norm / softcap / sliding window.

Three execution paths, all static-shape (TPU/XLA friendly):

- ``attend_causal``: training/prefill full-sequence causal attention,
  chunked over query blocks (memory-efficient attention). The inner loop
  over KV blocks uses ``lax.cond`` so blocks above the causal diagonal are
  skipped *at runtime*; the roofline analyzer weights conditional branches
  by 1/n_branches which recovers the expected triangle cost.
- ``attend_windowed``: sliding-window causal attention; for query block i
  only the static ``window + q_chunk`` KV slice is touched (exact FLOPs).
- ``attend_decode``: new-token attention against a (possibly ring) KV
  cache, dense over the cache with length masking (decode caches are full
  in the dry-run shapes, so dense == exact).

Layouts: q (B, S, H, Dh); k/v (B, S, KV, Dh); caches (B, S_max, KV, Dh).
The value head size may differ from the query/key one (latent
attention: keys 192 wide, values 128).

Multi-head latent attention (DeepSeek-V2/V3, ``cfg.mla``): the cache
holds one row per token, ``[RMSNorm(c), rope(k_pe)]`` (``kv_lora_rank``
+ ``qk_rope_head_dim`` values, shared by all heads, zero-padded to
``cache_width``); prefill and extend
expand it to per-head keys and values through ``wkb``/``wvb``
(``mla_expand``) and attend as above. The paged decode absorbs
``wkb`` into the query and ``wvb`` into the output instead
(``serving/paged_runtime.py``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec, apply_rope, rms_norm, rope_tables, softcap

NEG_INF = -2.0e38  # fp32 mask value (safe under bf16->fp32 upcast)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def attention_specs(cfg: ModelConfig, dtype: str) -> dict:
    D, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # head-split weights contract over D (q/k/v) or over H*Dh (o): their
    # init fan-in is that, not the second-to-last dim
    specs = {
        "wq": ParamSpec((D, H, Dh), ("embed", "q_heads", "head_dim"),
                        dtype=dtype, fan_in=D),
        "wk": ParamSpec((D, KV, Dh), ("embed", "kv_heads", "head_dim"),
                        dtype=dtype, fan_in=D),
        "wv": ParamSpec((D, KV, Dh), ("embed", "kv_heads", "head_dim"),
                        dtype=dtype, fan_in=D),
        "wo": ParamSpec((H, Dh, D), ("q_heads", "head_dim", "embed"),
                        dtype=dtype, fan_in=H * Dh),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((H, Dh), ("q_heads", "head_dim"), init="zeros", dtype=dtype)
        specs["bk"] = ParamSpec((KV, Dh), ("kv_heads", "head_dim"), init="zeros", dtype=dtype)
        specs["bv"] = ParamSpec((KV, Dh), ("kv_heads", "head_dim"), init="zeros", dtype=dtype)
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((Dh,), ("head_dim",), init="zeros", dtype=dtype)
        specs["k_norm"] = ParamSpec((Dh,), ("head_dim",), init="zeros", dtype=dtype)
    return specs


def mla_specs(cfg: ModelConfig, dtype: str) -> dict:
    """Latent attention without a query low-rank (``q_lora_rank`` 0):
    q = x wq; [c, k_pe] = x wkv_a, c normalised by ``kv_norm``; per head
    k_nope = c wkb, v = c wvb; o = attention wo."""
    a, D, H = cfg.mla, cfg.d_model, cfg.num_heads
    assert a.q_lora_rank == 0, "a query low-rank projection is not built"
    R, Dn, Dr, Dv = (a.kv_lora_rank, a.qk_nope_head_dim, a.qk_rope_head_dim,
                     a.v_head_dim)
    return {
        "wq": ParamSpec((D, H, Dn + Dr), ("embed", "q_heads", "head_dim"),
                        dtype=dtype, fan_in=D),
        "wkv_a": ParamSpec((D, R + Dr), ("embed", "kv_latent"), dtype=dtype),
        "kv_norm": ParamSpec((R,), ("kv_latent",), init="zeros", dtype=dtype),
        "wkb": ParamSpec((R, H, Dn), ("kv_latent", "q_heads", "head_dim"),
                         dtype=dtype, fan_in=R),
        "wvb": ParamSpec((R, H, Dv), ("kv_latent", "q_heads", "head_dim"),
                         dtype=dtype, fan_in=R),
        "wo": ParamSpec((H, Dv, D), ("q_heads", "head_dim", "embed"),
                        dtype=dtype, fan_in=H * Dv),
    }


def mla_query(p: dict, x: jax.Array, cfg: ModelConfig,
              positions: jax.Array) -> jax.Array:
    """x (B,S,D) -> q (B,S,H,Dn+Dr), rotary embedding on the trailing Dr."""
    Dn, Dr = cfg.mla.qk_nope_head_dim, cfg.mla.qk_rope_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    cos, sin = rope_tables(positions, Dr, cfg.rope_theta)
    return jnp.concatenate([q[..., :Dn], apply_rope(q[..., Dn:], cos, sin)],
                           axis=-1)


def mla_latent(p: dict, x: jax.Array, cfg: ModelConfig,
               positions: jax.Array) -> jax.Array:
    """x (B,S,D) -> the cache rows (B,S,W): [RMSNorm(c), rope(k_pe)],
    zero-padded to the stored width W (``MLAConfig.cache_width``)."""
    a = cfg.mla
    R, Dr = a.kv_lora_rank, a.qk_rope_head_dim
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c = rms_norm(kv[..., :R], p["kv_norm"], cfg.norm_eps)
    cos, sin = rope_tables(positions, Dr, cfg.rope_theta)
    k_pe = apply_rope(kv[..., None, R:], cos, sin)[..., 0, :]
    pad = jnp.zeros(c.shape[:-1] + (a.cache_width - a.cache_dim,), c.dtype)
    return jnp.concatenate([c, k_pe, pad], axis=-1)


def mla_expand(p: dict, lat: jax.Array, cfg: ModelConfig
               ) -> tuple[jax.Array, jax.Array]:
    """Cache rows (B,T,W) -> per-head keys (B,T,H,Dn+Dr) (the rotary
    part shared by every head) and values (B,T,H,Dv)."""
    R, Dr = cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    c, k_pe = lat[..., :R], lat[..., R:R + Dr]
    k_nope = jnp.einsum("btr,rhk->bthk", c, p["wkb"])
    v = jnp.einsum("btr,rhk->bthk", c, p["wvb"])
    k_pe = jnp.broadcast_to(k_pe[:, :, None], k_nope.shape[:3] + k_pe.shape[-1:])
    return jnp.concatenate([k_nope, k_pe], axis=-1), v


def qkv_project(p: dict, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x (B,S,D) -> q (B,S,H,Dh), k/v (B,S,KV,Dh) with RoPE applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_emb == "rope":
        cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta, cfg.rope_fraction)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def out_project(p: dict, o: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


# ---------------------------------------------------------------------------
# core block attention (one q block vs one kv block), GQA via reshape
# ---------------------------------------------------------------------------
def _block_attn(q, k, v, mask, scale, cap):
    """q (B,Q,H,Dh), k/v (B,T,KV,Dh), mask (B,1,1,Q,T) or None.

    Returns (out (B,Q,H,Dh), m (B,KV,G,Q), l (B,KV,G,Q)) fp32 stats."""
    B, Q, H, Dh = q.shape
    KV, Dv = k.shape[2], v.shape[-1]
    G = H // KV
    if k.dtype != q.dtype:          # fp8 KV cache: upcast at the MXU edge
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    qg = q.reshape(B, Q, KV, G, Dh)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k).astype(jnp.float32) * scale
    if cap > 0:
        s = softcap(s, cap)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                     # (B,KV,G,Q)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                     # (B,KV,G,Q)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype), v)
    return o.reshape(B, Q, H, Dv), m, l


def _combine(acc_o, acc_m, acc_l, o, m, l):
    """Online-softmax merge of two partial blocks."""
    new_m = jnp.maximum(acc_m, m)
    c1 = jnp.exp(acc_m - new_m)
    c2 = jnp.exp(m - new_m)
    new_l = acc_l * c1 + l * c2
    B, KV, G, Q = new_m.shape
    c1o = jnp.transpose(c1, (0, 3, 1, 2)).reshape(B, Q, KV * G)[..., None].astype(acc_o.dtype)
    c2o = jnp.transpose(c2, (0, 3, 1, 2)).reshape(B, Q, KV * G)[..., None].astype(acc_o.dtype)
    new_o = acc_o * c1o + o * c2o
    return new_o, new_m, new_l


def _finalize(o, m, l):
    B, KV, G, Q = l.shape
    denom = jnp.transpose(l, (0, 3, 1, 2)).reshape(B, Q, KV * G)[..., None]
    return (o / jnp.maximum(denom, 1e-30).astype(o.dtype))


# Remat the per-block attention in training paths: the backward pass then
# recomputes the (Q x KV-block) probability matrices instead of saving every
# block's probs (which costs O(S^2) fp32 per layer — the reason flash
# attention exists; this is the XLA-level equivalent).
_block_attn_remat = jax.checkpoint(_block_attn, static_argnums=(4, 5))


# ---------------------------------------------------------------------------
# full causal attention (train / prefill), q-chunked with cond-skipped blocks
# ---------------------------------------------------------------------------
def attend_causal(q, k, v, *, scale: float, cap: float = 0.0,
                  q_chunk: int = 1024, kv_chunk: int = 1024,
                  kv_len=None) -> jax.Array:
    """Causal attention over the full sequence. kv_len: optional (B,) valid
    lengths for padded batches (keys at pos >= kv_len are masked)."""
    B, S, H, Dh = q.shape
    KV, Dv = k.shape[2], v.shape[-1]
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, S)
    # pad S to multiples
    nq = math.ceil(S / q_chunk)
    nk = math.ceil(S / kv_chunk)
    Sq, Sk = nq * q_chunk, nk * kv_chunk
    if Sq != S:
        q = jnp.pad(q, ((0, 0), (0, Sq - S), (0, 0), (0, 0)))
    if Sk != S:
        k = jnp.pad(k, ((0, 0), (0, Sk - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sk - S), (0, 0), (0, 0)))
    eff_len = jnp.full((B,), S, jnp.int32) if kv_len is None else kv_len.astype(jnp.int32)

    qs = q.reshape(B, nq, q_chunk, H, Dh).transpose(1, 0, 2, 3, 4)   # (nq,B,Q,H,Dh)
    # stream K/V blocks as scan xs: the loop reads one (B, ck, KV, Dh) block
    # per step instead of dynamic-slicing a (possibly resharded) full K
    # inside the loop body (XLA otherwise re-gathers full K per block).
    ks = k.reshape(B, nk, kv_chunk, KV, Dh).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kv_chunk, KV, Dv).transpose(1, 0, 2, 3, 4)

    def q_body(_, qi_blk):
        qi, q_blk = qi_blk
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def kv_body(carry, kv_in):
            acc_o, acc_m, acc_l = carry
            kj, k_blk, v_blk = kv_in
            k_start = kj * kv_chunk

            def do(carry):
                acc_o, acc_m, acc_l = carry
                k_pos = k_start + jnp.arange(kv_chunk)
                mask = (k_pos[None, :] <= q_pos[:, None])[None, None, None]
                mask = mask & (k_pos[None, None, None, None, :] < eff_len[:, None, None, None, None])
                o, m, l = _block_attn_remat(q_blk, k_blk, v_blk, mask, scale, cap)
                return _combine(acc_o, acc_m, acc_l, o, m, l)

            # skip blocks entirely above the causal diagonal
            carry = jax.lax.cond(k_start <= qi * q_chunk + q_chunk - 1, do,
                                 lambda c: c, (acc_o, acc_m, acc_l))
            return carry, None

        init = (jnp.zeros((B, q_chunk, H, Dv), q.dtype),
                jnp.full((B, KV, H // KV, q_chunk), NEG_INF, jnp.float32),
                jnp.zeros((B, KV, H // KV, q_chunk), jnp.float32))
        (o, m, l), _ = jax.lax.scan(kv_body, init, (jnp.arange(nk), ks, vs))
        return None, _finalize(o, m, l)

    _, outs = jax.lax.scan(q_body, None, (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, Dv)
    return out[:, :S]


# ---------------------------------------------------------------------------
# sliding-window causal attention (gemma2 local layers): exact-FLOPs slices
# ---------------------------------------------------------------------------
def attend_windowed(q, k, v, *, scale: float, window: int, cap: float = 0.0,
                    q_chunk: int = 1024) -> jax.Array:
    B, S, H, Dh = q.shape
    if S <= window:
        return attend_causal(q, k, v, scale=scale, cap=cap, q_chunk=q_chunk,
                             kv_chunk=q_chunk)
    q_chunk = min(q_chunk, S)
    nq = math.ceil(S / q_chunk)
    Sq = nq * q_chunk
    if Sq != S:
        q = jnp.pad(q, ((0, 0), (0, Sq - S), (0, 0), (0, 0)))
    span = window + q_chunk  # static KV span per q chunk
    # left-pad K/V so every chunk's span is in range
    kp = jnp.pad(k, ((0, 0), (span, Sq - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (span, Sq - S), (0, 0), (0, 0)))
    qs = q.reshape(B, nq, q_chunk, H, Dh).transpose(1, 0, 2, 3, 4)

    def q_body(_, qi_blk):
        qi, q_blk = qi_blk
        q_pos = qi * q_chunk + jnp.arange(q_chunk)
        k_start = qi * q_chunk + q_chunk - span + span  # index into padded
        k_blk = jax.lax.dynamic_slice_in_dim(kp, k_start, span, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(vp, k_start, span, axis=1)
        k_pos = (qi * q_chunk + q_chunk - span) + jnp.arange(span)
        rel_ok = (k_pos[None, :] <= q_pos[:, None]) & \
                 (k_pos[None, :] > q_pos[:, None] - window) & (k_pos[None, :] >= 0)
        mask = rel_ok[None, None, None]
        o, m, l = _block_attn_remat(q_blk, k_blk, v_blk, mask, scale, cap)
        return None, _finalize(o, m, l)

    _, outs = jax.lax.scan(q_body, None, (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, Dh)
    return out[:, :S]


# ---------------------------------------------------------------------------
# decode: new tokens vs cache
# ---------------------------------------------------------------------------
def write_cache(cache_k, cache_v, k_new, v_new, cache_len, *, ring: bool = False):
    """Write k/v (B,C,KV,Dh) at per-sequence offsets cache_len (B,) or scalar.

    Non-ring caches use dynamic_update_slice (in-place friendly — XLA can
    alias the donated cache buffer). Ring caches (sliding-window layers,
    capacity == window) use modulo scatter."""
    W = cache_k.shape[1]
    k_new = k_new.astype(cache_k.dtype)   # fp8 KV cache: quantize on write
    v_new = v_new.astype(cache_v.dtype)

    if not ring:
        return (write_rows(cache_k, k_new, cache_len),
                write_rows(cache_v, v_new, cache_len))

    if jnp.ndim(cache_len) == 0:
        start = jnp.asarray(cache_len, jnp.int32) % W
        idx = (start + jnp.arange(k_new.shape[1])) % W  # wraps; later writes win
        ck = cache_k.at[:, idx].set(k_new)
        cv = cache_v.at[:, idx].set(v_new)
        return ck, cv

    def one(ck, cv, kn, vn, ln):
        idx = (ln + jnp.arange(kn.shape[0])) % W
        return ck.at[idx].set(kn), cv.at[idx].set(vn)

    ck, cv = jax.vmap(one)(cache_k, cache_v, k_new, v_new, cache_len.astype(jnp.int32))
    return ck, cv


def write_rows(cache, new, cache_len):
    """Write rows ``new`` (B,C,...) into ``cache`` (B,W,...) at offsets
    ``cache_len`` (B,) or scalar, by dynamic_update_slice (in place on a
    donated buffer)."""
    W, C = cache.shape[1], new.shape[1]
    new = new.astype(cache.dtype)
    if jnp.ndim(cache_len) == 0:
        start = jnp.minimum(jnp.asarray(cache_len, jnp.int32), W - C)
        return jax.lax.dynamic_update_slice_in_dim(cache, new, start, 1)
    return jax.vmap(lambda c, n, ln: jax.lax.dynamic_update_slice_in_dim(
        c, n, jnp.minimum(ln, W - C), 0))(cache, new,
                                          cache_len.astype(jnp.int32))


def attend_decode(q, cache_k, cache_v, kv_len, *, scale: float,
                  cap: float = 0.0, window: int = 0) -> jax.Array:
    """q (B,C,H,Dh) new queries at absolute positions kv_len..kv_len+C-1
    (per batch); cache (B,T,KV,Dh) already contains the new keys.

    Dense over the cache with validity masking. For ring caches (window>0)
    the cache capacity T == window and all slots are valid once warm."""
    B, C, H, Dh = q.shape
    T = cache_k.shape[1]
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    slot = jnp.arange(T)[None, :]                       # (1,T)
    total = kv_len + C                                  # (B,) valid count incl. new
    if window > 0 and T == window:
        # ring cache: slot s holds absolute position p ≡ s (mod W), the
        # largest such p < total. valid iff p >= 0 and p > total - 1 - window.
        n_wrap = (total[:, None] - 1 - slot) // T
        abs_pos = slot + jnp.maximum(n_wrap, 0) * T
        valid = (abs_pos < total[:, None]) & \
            (abs_pos >= jnp.maximum(total - window, 0)[:, None])
        # causal vs each query row
        q_pos = kv_len[:, None, None] + jnp.arange(C)[None, :, None]  # (B,C,1)
        mask = valid[:, None, :] & (abs_pos[:, None, :] <= q_pos)
        mask = mask & (abs_pos[:, None, :] > q_pos - window)
    else:
        q_pos = kv_len[:, None, None] + jnp.arange(C)[None, :, None]  # (B,C,1)
        pos = slot                                       # (1,T) absolute = slot
        mask = (pos[:, None, :] <= q_pos) & (pos[:, None, :] < total[:, None, None])
        if window > 0:
            mask = mask & (pos[:, None, :] > q_pos - window)
    mask = mask[:, None, None]                           # (B,1,1,C,T)
    o, m, l = _block_attn(q, cache_k, cache_v, mask, scale, cap)
    return _finalize(o, m, l)
