"""Explicit all-to-all expert parallelism via shard_map (beyond-paper path,
``MoEConfig.sharding_mode = "ep_a2a"``).

The GSPMD path (moe.py) lets the partitioner derive the EP exchange from
sharding constraints; it materializes a replicated (G, E·C, D) combine
buffer (one all-gather per layer, §Perf cell B). This path instead writes
the canonical EP schedule by hand inside ``shard_map``:

    per shard: route -> sort-based local dispatch -> all_to_all (send each
    expert-shard its token slabs) -> local expert FFN -> all_to_all back ->
    local combine.

Wire bytes per device: 2 x Tg·k·cf·D (dispatch + return), the EP minimum —
vs the GSPMD baseline's gather-everything (measured 16x worse before the
§Perf B1 fix, ~2-4x worse after). The trade: a fixed per-(shard-pair)
capacity (C_pair), so imbalance drops more tokens than global capacity
would (standard hardware-EP behavior, same knob as DeepSpeed-MoE/GShard).

Numerics match moe.py up to capacity-drop differences (tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import activation_fn
from repro.models.moe import _capacity, _dispatch_plan, route


def moe_apply_a2a(p: dict, x: jax.Array, cfg: ModelConfig, mesh,
                  expert_axis: str = "model",
                  batch_axes=("data",)) -> jax.Array:
    """x (B, S, D) -> (B, S, D). Requires E % mesh[expert_axis] == 0 and
    router weights replicated."""
    m = cfg.moe
    B, S, D = x.shape
    E, k = m.num_experts, m.top_k
    n_ep = mesh.shape[expert_axis]
    E_loc = E // n_ep
    act = activation_fn(cfg.activation)
    cd = jnp.dtype(cfg.compute_dtype)

    def shard_fn(xs, router, w1, w3, w2):
        # xs: (B_loc, S, D) tokens of this data shard (replicated over EP
        # axis); w*: (E_loc, ...) this EP shard's experts
        Bl = xs.shape[0]
        T = Bl * S
        xt = xs.reshape(T, D)
        probs, ids = route(router, xt, cfg)

        # local slot plan against ALL experts; C_pair = this shard's
        # per-expert capacity (global per-expert capacity = n_ep * C_pair,
        # matching the GSPMD path's grouped capacity)
        C_pair = _capacity(T, cfg)
        src, dest = _dispatch_plan(ids.reshape(-1), E, C_pair)
        tok = jnp.where(src >= T * k, T, src // k)
        xp = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
        send = jnp.take(xp, tok, axis=0)               # (E*C_pair, D)
        # regroup by destination EP shard: (n_ep, E_loc*C_pair, D)
        send = send.reshape(n_ep, E_loc * C_pair, D)
        recv = jax.lax.all_to_all(send, expert_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv: (n_ep, E_loc*C_pair, D) — slabs from every source shard
        xe = recv.reshape(n_ep, E_loc, C_pair, D).transpose(1, 0, 2, 3) \
            .reshape(E_loc, n_ep * C_pair, D).astype(cd)

        h = act(jnp.einsum("ecd,edf->ecf", xe, w1)) * \
            jnp.einsum("ecd,edf->ecf", xe, w3)
        ye = jnp.einsum("ecf,efd->ecd", h.astype(cd), w2).astype(cd)

        # return path: inverse regroup + all_to_all back
        back = ye.reshape(E_loc, n_ep, C_pair, D).transpose(1, 0, 2, 3) \
            .reshape(n_ep, E_loc * C_pair, D)
        ret = jax.lax.all_to_all(back, expert_axis, split_axis=0,
                                 concat_axis=0, tiled=False)
        yb = ret.reshape(E * C_pair, D)
        yp = jnp.concatenate([yb, jnp.zeros((1, D), yb.dtype)], axis=0)
        out_rows = jnp.take(yp, dest, axis=0).reshape(T, k, D)
        out = jnp.sum(out_rows * probs[..., None].astype(yb.dtype), axis=1)
        return out.reshape(Bl, S, D)

    batch_spec = P(tuple(batch_axes))
    specs = dict(in_specs=(batch_spec, P(), P(expert_axis), P(expert_axis),
                           P(expert_axis)),
                 out_specs=batch_spec)
    fn = jax.shard_map(shard_fn, mesh=mesh, check_vma=False, **specs)
    router = {kk: p[kk] for kk in ("router", "router_bias") if kk in p}
    out = fn(x, router, p["w1"].astype(cd), p["w3"].astype(cd),
             p["w2"].astype(cd))
    if m.num_shared_experts:
        from repro.models.mlp import mlp_apply
        out = out + mlp_apply({kk: v.astype(cd) for kk, v in p["shared"].items()},
                              x.astype(cd), cfg.activation)
    return out
