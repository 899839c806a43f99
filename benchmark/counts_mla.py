"""Operations and bytes the latent-attention decode needs, from shapes,
lengths and the expert copies the program counted (``reference_mla``'s
dims); the peaks and the roofline arithmetic are ``counts.py``'s."""
from __future__ import annotations

import math


def mla_decode_attention(lens, d, *, page: int, n_tab: int,
                         kv_bytes: int = 2, q_bytes: int = 2
                         ) -> tuple[float, float]:
    """FLOPs and HBM bytes one layer's latent decode-attention call
    (``paged_mla_decode``) needs for rows of current lengths ``lens``
    (the new token's own row is not in the pages): per valid position
    and head, the score over the whole row (kv_lora + rope wide) and the
    weighted value over its leading kv_lora columns, 2·H·(576 + 512);
    the latent pages that cover those positions, read once for key and
    value both; q in, the float32 accumulator and the two softmax
    statistics out, and the block tables and lengths."""
    B, H, D, Dv = len(lens), d.heads, d.kv_lora + d.rope, d.kv_lora
    flops = sum(2.0 * H * (D + Dv) * n for n in lens)
    pages = sum(math.ceil(n / page) for n in lens)
    latent = 1.0 * pages * page * D * kv_bytes
    q = B * H * D * q_bytes
    out = B * H * Dv * 4 + 2 * B * H * 4
    tables = B * n_tab * 4 + B * 4
    return flops, latent + q + out + tables


def decode_step_flops(d, lens, held_copies: int) -> float:
    """Model FLOPs of one batched decode step over rows of current
    lengths ``lens`` whose token-copies routed to the held experts number
    ``held_copies`` (summed over the MoE layers): per row and layer the
    absorbed latent attention's projections (wq, wkv_a, the query's
    absorption of wkb, the output's of wvb, wo) and its scores and values
    over the context and the new token; the dense layers' MLP; per MoE
    layer the router and the shared experts; each held copy's expert
    MLP; and the head."""
    D, H, R, Dr = d.d_model, d.heads, d.kv_lora, d.rope
    proj = D * H * (d.nope + Dr) + D * (R + Dr) + H * d.nope * R \
        + H * R * d.v_head + H * d.v_head * D
    n_moe = d.layers - d.first_dense
    per_row = d.layers * proj + d.first_dense * 3 * D * d.dense_ff \
        + n_moe * (D * d.router_experts + 3 * D * d.expert_ff * d.shared) \
        + D * d.vocab
    attn = 2.0 * H * (2 * R + Dr) * d.layers * sum(n + 1 for n in lens)
    return 2.0 * per_row * len(lens) + attn \
        + 2.0 * 3 * D * d.expert_ff * held_copies
