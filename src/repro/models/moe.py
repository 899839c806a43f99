"""Mixture-of-Experts FFN: a dropless layer for the served path, and
static-capacity (GShard-style) dispatch under a device mesh.

**Dropless** (``moe_dropless``; every single-device forward, so prefill,
extend and the paged decode): the router scores all experts, each token
keeps its top-k, and the copies routed to the experts this chip holds
(``cfg.moe.held``) are sorted by expert and run through grouped matmuls
(``jax.lax.ragged_dot``). There is no capacity: a token's output depends
on that token alone, so a decode row is the same in any batch. Copies
routed to experts held elsewhere add nothing here, which is this chip's
share of an expert-parallel layer with the exchange left out.

**Static capacity** (``moe_apply`` under a mesh: sharded training and the
dry-run). Why static capacity: the dry-run must lower with static shapes,
and the HLO FLOP count must reflect *active* compute (top_k × capacity_factor), not
all-experts-dense. One-hot dispatch einsums are avoided (they cost
O(T² · top_k · D) — quadratic in tokens); instead we compute each token-copy's
slot with a cumsum over a (T·top_k, E) one-hot int8 matrix (cheap, int ops)
and use gather/scatter (bytes, not FLOPs) to build (E, C, D) expert batches.

Sharding modes (cfg.moe.sharding_mode):
- "tp": experts replicated, per-expert hidden dim sharded over "model".
- "ep": expert dim sharded over "model"; GSPMD inserts the dispatch
  collectives (the paper-faithful baseline for the MoE cells).
- "ep_a2a": explicit shard_map all-to-all expert parallelism (beyond-paper
  hillclimb path, see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain
from repro.models.common import ParamSpec, activation_fn
from repro.models.mlp import mlp_apply, mlp_specs


def moe_specs(cfg: ModelConfig, dtype: str) -> dict:
    """The router over every expert (and its correction bias, with
    sigmoid scoring), the held experts' weights, the shared experts as
    one MLP of their summed width."""
    m = cfg.moe
    D, F, E, Eh = cfg.d_model, m.d_ff_expert, m.num_experts, m.n_held
    ex_axis = "experts"
    specs = {
        "router": ParamSpec((D, E), ("embed", "experts_router"), dtype="float32", keep_dtype=True),
        "w1": ParamSpec((Eh, D, F), (ex_axis, "embed", "moe_mlp"), dtype=dtype),
        "w3": ParamSpec((Eh, D, F), (ex_axis, "embed", "moe_mlp"), dtype=dtype),
        "w2": ParamSpec((Eh, F, D), (ex_axis, "moe_mlp", "embed"), dtype=dtype),
    }
    if m.scoring == "sigmoid":
        specs["router_bias"] = ParamSpec((E,), ("experts_router",),
                                         init="zeros", dtype="float32",
                                         keep_dtype=True)
    if m.num_shared_experts:
        specs["shared"] = mlp_specs(D, F * m.num_shared_experts, dtype)
    return specs


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(tokens * m.top_k * m.capacity_factor / m.num_experts)
    return max(8, (c + 7) // 8 * 8)  # pad to 8 for TPU-friendly shapes


def route(p: dict, x: jax.Array, cfg: ModelConfig):
    """x (T, D) -> (weights (T, k) float32, expert_ids (T, k)), scored in
    float32 over every expert. ``softmax``: softmax over the top-k logits
    (Qwen3/Mixtral). ``sigmoid`` (DeepSeek-V3 ``noaux_tc``, one group):
    the top-k of sigmoid score + correction bias, weighted by the scores
    without the bias, normalised over the k chosen (``norm_topk``); both
    then scaled by ``routed_scaling``."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + p["router_bias"], m.top_k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        if m.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    else:
        top_logits, ids = jax.lax.top_k(logits, m.top_k)
        w = jax.nn.softmax(top_logits, axis=-1)
    return w * m.routed_scaling, ids


def moe_dropless(p: dict, x: jax.Array, cfg: ModelConfig
                 ) -> tuple[jax.Array, jax.Array]:
    """x (..., D) -> (y (..., D), the token-copies routed to a held expert
    (int32)). Each copy to a held expert is computed; the rest add
    nothing. The held copies are sorted by expert (the others last, past
    the groups, where ``ragged_dot`` writes zeros) and run through the
    held experts' grouped matmuls; each token sums its k weighted copies
    in float32, then adds the shared experts."""
    m = cfg.moe
    D = x.shape[-1]
    xt = x.reshape(-1, D)
    T, k, Eh = xt.shape[0], m.top_k, m.n_held
    w, ids = route(p, xt, cfg)
    local = ids.reshape(-1) - m.held_start
    held = (local >= 0) & (local < Eh)
    key = jnp.where(held, local, Eh)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(Eh), axis=0, dtype=jnp.int32)
    rows = jnp.take(xt, order // k, axis=0)
    act = activation_fn(cfg.activation)
    h = act(jax.lax.ragged_dot(rows, p["w1"], sizes)) \
        * jax.lax.ragged_dot(rows, p["w3"], sizes)
    y = jax.lax.ragged_dot(h.astype(rows.dtype), p["w2"], sizes)
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(T, k, D)
    wt = jnp.where(held.reshape(T, k), w, 0.0)
    out = jnp.sum(y.astype(jnp.float32) * wt[..., None], axis=1)
    out = out.astype(x.dtype).reshape(x.shape)
    if m.num_shared_experts:
        out = out + mlp_apply(p["shared"], x, cfg.activation)
    return out, jnp.sum(held, dtype=jnp.int32)


def _dispatch_plan(flat_ids: jax.Array, E: int, C: int):
    """Sort-based dispatch plan for one group (MegaBlocks-style, no scatter).

    flat_ids (N,) expert id per token-copy. Returns:
      src (E*C,):  source copy index for each expert slot (N = padded/empty)
      dest (N,):   destination slot (in [0, E*C]) per copy (E*C = dropped)
    All index tensors are 1-D — no O(N*D) scatter index materialization.
    """
    N = flat_ids.shape[0]
    order = jnp.argsort(flat_ids, stable=True)                  # (N,)
    sorted_ids = jnp.take(flat_ids, order)
    bounds = jnp.searchsorted(sorted_ids, jnp.arange(E + 1))    # (E+1,)
    pos_sorted = jnp.arange(N) - jnp.take(bounds, sorted_ids)   # rank within expert
    keep_sorted = pos_sorted < C
    dest_sorted = jnp.where(keep_sorted, sorted_ids * C + pos_sorted, E * C)
    inv = jnp.argsort(order)                                    # copy -> sorted pos
    dest = jnp.take(dest_sorted, inv)                           # (N,)

    slots = jnp.arange(E * C)
    e = slots // C
    c = slots % C
    counts = bounds[1:] - bounds[:-1]                           # (E,)
    valid = c < jnp.take(counts, e)
    sorted_pos = jnp.take(bounds[:-1], e) + c
    src = jnp.where(valid, jnp.take(order, jnp.clip(sorted_pos, 0, N - 1)), N)
    return src, dest


def _num_groups(T: int) -> int:
    """Dispatch groups = size of the data axes (GShard 'groups'): each data
    shard dispatches its own tokens with a *local* capacity, so the one-hot
    cumsum and the scatter stay shard-local (no cross-shard collective)."""
    from repro.dist.sharding import current_rules
    rules = current_rules()
    if not rules or rules.get("__mesh__") is None:
        return 1
    mesh = rules["__mesh__"]
    ax = rules.get("act_batch")
    if ax is None:
        return 1
    axes = ax if isinstance(ax, (tuple, list)) else (ax,)
    g = 1
    for a in axes:
        g *= mesh.shape[a]
    return g if g > 1 and T % g == 0 else 1


MAX_GROUP_TOKENS = 8192  # sub-chunk groups beyond this (bounds dispatch bufs)


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x (B, S, D) -> (B, S, D). On one device, the dropless layer. Under
    a mesh: grouped (per-data-shard) dispatch; groups larger than
    MAX_GROUP_TOKENS are processed in scanned sub-chunks so the (E*C, D)
    dispatch buffers stay bounded (32k-prefill would otherwise
    materialize ~5 GB/layer)."""
    from repro.dist.sharding import current_rules
    m = cfg.moe
    if (current_rules() or {}).get("__mesh__") is None:
        return moe_dropless(p, x, cfg)[0]
    assert m.n_held == m.num_experts, "capacity dispatch holds every expert"
    B, S, D = x.shape
    T = B * S
    if m.sharding_mode == "ep_a2a":
        from repro.dist.sharding import current_rules
        rules = current_rules() or {}
        mesh = rules.get("__mesh__")
        if (mesh is not None and "model" in mesh.axis_names
                and m.num_experts % mesh.shape["model"] == 0):
            from repro.models.moe_a2a import moe_apply_a2a
            ax = rules.get("act_batch") or ()
            axes = tuple(ax) if isinstance(ax, (tuple, list)) else (ax,)
            return moe_apply_a2a(p, x, cfg, mesh, expert_axis="model",
                                 batch_axes=axes)
    G = _num_groups(T)
    Tg = T // G
    if Tg > MAX_GROUP_TOKENS and Tg % 2 == 0:
        n_sub = 2
        while Tg // n_sub > MAX_GROUP_TOKENS and (Tg // n_sub) % 2 == 0:
            n_sub *= 2
        xs = x.reshape(G, n_sub, Tg // n_sub, D).transpose(1, 0, 2, 3)

        def body(_, xc):
            return None, _moe_group(p, xc, cfg)

        _, ys = jax.lax.scan(body, None, xs)
        return ys.transpose(1, 0, 2, 3).reshape(B, S, D)
    return _moe_group(p, x.reshape(G, Tg, D), cfg).reshape(B, S, D)


def _moe_group(p: dict, xt: jax.Array, cfg: ModelConfig) -> jax.Array:
    """xt (G, Tg, D) -> (G, Tg, D)."""
    m = cfg.moe
    G, Tg, D = xt.shape
    T = G * Tg
    E, k = m.num_experts, m.top_k
    C = _capacity(Tg, cfg)                           # local capacity per group

    xt = constrain(xt, "act_batch", None, None)
    probs, ids = route(p, xt.reshape(T, D), cfg)     # (T,k)
    probs, ids = probs.reshape(G, Tg, k), ids.reshape(G, Tg, k)

    # --- per-group sort-based dispatch plan (1-D index work only)
    flat = ids.reshape(G, Tg * k)
    src, dest = jax.vmap(lambda f: _dispatch_plan(f, E, C))(flat)

    # --- gather rows into expert batches: (G, E*C, D); copy n comes from
    # token n // k, so no (Tg*k, D) repeat is materialized
    def gather_rows(xg, src_g):
        xp = jnp.concatenate([xg, jnp.zeros((1, D), xg.dtype)], axis=0)
        tok = jnp.where(src_g >= Tg * k, Tg, src_g // k)
        return jnp.take(xp, tok, axis=0)

    buf = jax.vmap(gather_rows)(xt, src)                     # (G, E*C, D)
    # E-major flat dim constrained to the expert axis: each (data, model)
    # device gathers only ITS experts' rows from ITS group's (local) tokens
    # — the EP dispatch becomes slicing, not gather-full-then-slice (§Perf)
    buf = constrain(buf, "act_batch", "experts", None)
    # (G, E, C, D) -> (E, G*C, D): the G->E transpose is the EP exchange;
    # keep it (and its backward) in the compute dtype — fp32 here doubles
    # the dominant EP collective (§Perf cell B)
    xe = buf.reshape(G, E, C, D).transpose(1, 0, 2, 3).reshape(E, G * C, D)
    xe = constrain(xe.astype(cfg.compute_dtype), "experts", "moe_capacity", None)

    act = activation_fn(cfg.activation)
    cd = jnp.dtype(cfg.compute_dtype)
    h = act(jnp.einsum("ecd,edf->ecf", xe, p["w1"])) * jnp.einsum("ecd,edf->ecf", xe, p["w3"])
    h = constrain(h.astype(cd), "experts", "moe_capacity", None)
    ye = jnp.einsum("ecf,efd->ecd", h, p["w2"])
    ye = constrain(ye.astype(cd), "experts", "moe_capacity", None)

    # --- combine: inverse exchange, gather per copy, weight, sum over k
    # (combine-side E-sharding was tried and REFUTED in §Perf cell B iter 3:
    # it turns one replicated all-gather into sum-over-k partial all-reduces
    # of token-sized buffers, a net regression — see EXPERIMENTS.md)
    yb = ye.reshape(E, G, C, D).transpose(1, 0, 2, 3).reshape(G, E * C, D)
    yb = constrain(yb, "act_batch", None, None)

    def gather_out(yg, dest_g):
        yp = jnp.concatenate([yg, jnp.zeros((1, D), yg.dtype)], axis=0)
        return jnp.take(yp, dest_g, axis=0)

    out_rows = jax.vmap(gather_out)(yb, dest)                # (G, Tg*k, D)
    out_rows = out_rows.reshape(G, Tg, k, D) * probs[..., None].astype(ye.dtype)
    out = jnp.sum(out_rows, axis=2)                          # (G, Tg, D)

    if m.num_shared_experts:
        out = out + mlp_apply(p["shared"], xt, cfg.activation)
    return out.astype(xt.dtype)


def aux_load_balance_loss(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Switch-style load-balance auxiliary loss (used in training examples)."""
    m = cfg.moe
    xt = x.reshape(-1, x.shape[-1])
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, ids = jax.lax.top_k(logits, m.top_k)
    frac = jnp.mean(jax.nn.one_hot(ids[:, 0], m.num_experts), axis=0)
    return m.num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
