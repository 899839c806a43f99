"""Operations and bytes the algorithm needs, from shapes and lengths, and
the device peaks they are held against (``peaks.json``)."""
from __future__ import annotations

import json
import math
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``. A kind not in the table is
    an error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json")
    return table[device_kind]


def decode_attention(lens, *, H: int, KV: int, Dh: int, page: int,
                     n_tab: int, kv_bytes: int = 2, q_bytes: int = 2
                     ) -> tuple[float, float]:
    """FLOPs and HBM bytes one layer's paged decode-attention call needs
    for rows of current lengths ``lens`` (the new token's own k/v is not
    in the pages): scores and weighted values over each row's valid
    positions, 2*2*H*Dh per position; the K and V pages that cover those
    positions, q in, the float32 accumulator and the two softmax
    statistics out, and the block tables and lengths."""
    B = len(lens)
    flops = sum(4.0 * H * Dh * n for n in lens)
    pages = sum(math.ceil(n / page) for n in lens)
    kv = 2.0 * pages * page * KV * Dh * kv_bytes
    q = B * H * Dh * q_bytes
    out = B * H * Dh * 4 + 2 * B * H * 4
    tables = B * n_tab * 4 + B * 4
    return flops, kv + q + out + tables


def roofline_seconds(flops: float, nbytes: float, pk: dict
                     ) -> tuple[float, str]:
    """The least time on the chip, and which bound sets it."""
    tc = flops / pk["bf16_flops"]
    tm = nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def matmul_params(dims: tuple) -> int:
    """Parameters that enter a matmul per token: every layer's
    projections and MLP, and the head (tied or not), not the embedding
    gather or the norms."""
    L, D, H, KV, Dh, F, V = dims[:7]
    per_layer = D * H * Dh * 2 + D * KV * Dh * 2 + 3 * D * F
    return L * per_layer + D * V


def decode_step_flops(dims: tuple, lens) -> float:
    """Model FLOPs of one batched decode step over rows of current
    lengths ``lens``: 2 x matmul parameters per row, plus attention
    scores and values over each row's context including its new token,
    4 x L x H x Dh per position."""
    L, D, H, KV, Dh = dims[:5]
    return 2.0 * matmul_params(dims) * len(lens) + \
        4.0 * L * H * Dh * sum(n + 1 for n in lens)
