"""Weights from the seed, and the plain reference forward that decides
``correct``, for DeepSeek-V3-style configurations: multi-head latent
attention, leading dense layers, then sigmoid-routed experts with shared
experts (Moonlight-16B-A3B). The interface is ``reference.py``'s (see its
docstring); a configuration file names this module by ``"reference":
"reference_mla"``.

The dims (``shape_of``) hold the chip's share as well as the widths:
the router scores ``router_experts`` experts and keeps the top
``num_experts_per_tok``, of which this chip computes those among its
``n_routed_experts`` held experts from ``first_held_expert`` on; the
vocabulary is the slice the file states.

The weights, in the layout the program serves: ``embed``, ``final_norm``,
an untied ``lm_head``; ``dense_layers`` and ``blocks``, each a stacked
tree ``{ln1, ln2, attn/{wq, wkv_a, kv_norm, wkb, wvb, wo}, mlp}``, the
dense layers' ``mlp/{w1, w3, w2}``, the expert layers' ``mlp/{router,
router_bias, w1, w3, w2, shared/{w1, w3, w2}}`` (the held experts
stacked, the two shared experts as one MLP of twice the width).

The forward is the published one, plain and whole, in float32 with every
matmul at ``Precision.HIGHEST``: per layer RMSNorm (``1 + w`` gain, the
program's convention); latent attention not absorbed — q = h wq split
into 128 nope and 64 rope columns, [c, k_pe] = h wkv_a, c RMSNorm'd,
rotate-half rotary embedding on q_pe and on k_pe (one for all heads),
per head k_nope = c wkb and v = c wvb, causal softmax over
[k_nope, k_pe] scaled by 1/sqrt(192), o wo; then the dense SwiGLU, or
the router over every expert (sigmoid of h router; the top-k of score +
``router_bias`` chosen; each chosen weighted by its score without the
bias, normalised over all k chosen, times ``routed_scaling_factor``),
each held expert's SwiGLU over the tokens weighted by its weight where
chosen, plus the shared experts. With ``fp8`` it is the control: every
matmul input, and the cached rows [c, k_pe], rounded to float8 e4m3 as
``reference.py`` does.

``gaps`` reads each served token through the router's ties. A top-6
choice is a discontinuity: where a chosen and an unchosen expert lie
within bfloat16's rounding of each other, the program may take either,
and with random weights a swapped expert of ~0.4 weight moves the
token's logits by up to ~2 standard deviations. So each compared
position's own forward is run again (``branch_gaps``), opening a
branch at every expert layer where a swap involving a held expert lies
closer than ``ROUTE_TIE`` in selection value; the token's gap is the
least over the branches. The rest of the sequence keeps the main
forward's routing. Without a tie it is the strict read; float8, whose
rounding moves the selection values ~10× further, still reads its flips
(PERF.md §2).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import (BIAS_STD, HIGHEST, NORM_STD, PAIR_BLOCK, QUERY_BLOCK,
                       _fp8, _mm, _rms, _rope, root_key)

#: the published settings this module implements, and nothing else
SUPPORTED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "n_group": 1, "topk_group": 1, "q_lora_rank": None,
             "tie_word_embeddings": False, "hidden_act": "silu"}


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_lora: int
    nope: int
    rope: int
    v_head: int
    dense_ff: int
    expert_ff: int
    router_experts: int
    top_k: int
    held: int
    held_start: int
    shared: int
    vocab: int
    theta: float
    eps: float
    scaling: float
    first_dense: int
    norm_topk: bool


def shape_of(model: dict) -> Dims:
    """The dims from a config file's ``model`` block (Hugging Face key
    names; ``n_routed_experts`` is the count held here, the router's
    width is ``router_experts``)."""
    for k, v in SUPPORTED.items():
        if model.get(k) != v:
            raise ValueError(f"reference_mla implements {k}={v!r}, the file "
                             f"states {model.get(k)!r}")
    return Dims(
        model["num_hidden_layers"], model["hidden_size"],
        model["num_attention_heads"], model["kv_lora_rank"],
        model["qk_nope_head_dim"], model["qk_rope_head_dim"],
        model["v_head_dim"], model["intermediate_size"],
        model["moe_intermediate_size"], model["router_experts"],
        model["num_experts_per_tok"], model["n_routed_experts"],
        model["first_held_expert"], model["n_shared_experts"],
        model["vocab_size"], float(model["rope_theta"]),
        float(model["rms_norm_eps"]), float(model["routed_scaling_factor"]),
        model["first_k_dense_replace"], bool(model["norm_topk_prob"]))


def served_dims(cfg) -> Dims | None:
    """The dims of ``shape_of`` read from a registry ``ModelConfig``;
    None where it serves no latent attention (the harness then refuses
    the run, naming both)."""
    a, m = getattr(cfg, "mla", None), cfg.moe
    if a is None or m is None:
        return None
    return Dims(
        cfg.num_layers, cfg.d_model, cfg.num_heads, a.kv_lora_rank,
        a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim, m.dense_d_ff,
        m.d_ff_expert, m.num_experts, m.top_k, m.n_held, m.held_start,
        m.num_shared_experts, cfg.vocab_size, float(cfg.rope_theta),
        float(cfg.norm_eps), float(m.routed_scaling), m.first_k_dense,
        bool(m.norm_topk))


def program_config(cfg, model: dict):
    """The registry config cut to the file's sizes: its depth, its
    vocabulary slice and the experts this chip holds."""
    moe = cfg.moe
    if moe is not None and hasattr(moe, "held"):
        moe = dataclasses.replace(moe, held=model["n_routed_experts"],
                                  held_start=model["first_held_expert"])
    return dataclasses.replace(cfg, num_layers=model["num_hidden_layers"],
                               vocab_size=model["vocab_size"], moe=moe)


def weight_shapes(d: Dims) -> dict:
    """(shape, fan_in or init kind) per leaf, in the served layout."""
    D, H, R = d.d_model, d.heads, d.kv_lora

    def attn(n):
        return {"wq": ((n, D, H, d.nope + d.rope), D),
                "wkv_a": ((n, D, R + d.rope), D),
                "kv_norm": ((n, R), "norm"),
                "wkb": ((n, R, H, d.nope), R),
                "wvb": ((n, R, H, d.v_head), R),
                "wo": ((n, H, d.v_head, D), H * d.v_head)}

    def mlp(shape, F):
        return {"w1": (shape + (D, F), D), "w3": (shape + (D, F), D),
                "w2": (shape + (F, D), F)}

    fk, nm, Fs = d.first_dense, d.layers - d.first_dense, \
        d.expert_ff * d.shared
    return {
        "embed": ((d.vocab, D), "embed"), "final_norm": ((D,), "norm"),
        "lm_head": ((D, d.vocab), D),
        "dense_layers": {"ln1": ((fk, D), "norm"), "ln2": ((fk, D), "norm"),
                         "attn": attn(fk), "mlp": mlp((fk,), d.dense_ff)},
        "blocks": {"ln1": ((nm, D), "norm"), "ln2": ((nm, D), "norm"),
                   "attn": attn(nm),
                   "mlp": {"router": ((nm, D, d.router_experts), D),
                           "router_bias": ((nm, d.router_experts), "bias"),
                           **mlp((nm, d.held), d.expert_ff),
                           "shared": mlp((nm,), Fs)}}}


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make_weights(d: Dims, key: jax.Array, embed_std: float) -> dict:
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    leaves, treedef = jax.tree.flatten(weight_shapes(d), is_leaf=is_leaf)
    out = []
    for i, (shape, kind) in enumerate(leaves):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        std = {"embed": embed_std, "norm": NORM_STD,
               "bias": BIAS_STD}.get(kind)
        out.append(z * (std if std is not None else 1.0 / math.sqrt(kind)))
    return jax.tree.unflatten(treedef, out)


def make_weights(d: Dims, seed: int, embed_std: float) -> dict:
    """The model's float32 weights from ``seed``, made on the default
    device in one jitted call: embeddings N(0, embed_std), matrices
    N(0, 1/fan_in), norm gains 1 + N(0, 0.1) (stored as the offset), the
    router's correction bias N(0, 0.1)."""
    return _make_weights(d, root_key(seed), float(embed_std))


# ---------------------------------------------------------------- forward
def _swiglu(h, w1, w3, w2, fp8):
    return _mm(jax.nn.silu(_mm(h, w1, fp8)) * _mm(h, w3, fp8), w2, fp8)


def _query(d: Dims, fp8: bool, h, a, pos):
    """Per-head queries (S, H, nope + rope), rotary on the rope part."""
    H, Dn = d.heads, d.nope
    q = _mm(h, a["wq"].reshape(-1, H * (Dn + d.rope)), fp8).reshape(
        h.shape[0], H, -1)
    return jnp.concatenate(
        [q[..., :Dn], _rope(q[..., Dn:], pos, d.theta, 1.0)], -1)


def _keys_values(d: Dims, fp8: bool, h, a, pos):
    """Per-head keys (S, H, nope + rope) and values (S, H, v_head),
    expanded from the cached row [c, k_pe]."""
    S, H, R, Dn = h.shape[0], d.heads, d.kv_lora, d.nope
    kv = _mm(h, a["wkv_a"], fp8)
    c = _rms(kv[:, :R], a["kv_norm"], d.eps)
    k_pe = _rope(kv[:, None, R:], pos, d.theta, 1.0)[:, 0]
    if fp8:                                   # the cached row, in float8
        c, k_pe = _fp8(c, -1), _fp8(k_pe, -1)
    k = jnp.concatenate([
        _mm(c, a["wkb"].reshape(R, H * Dn), fp8).reshape(S, H, Dn),
        jnp.broadcast_to(k_pe[:, None], (S, H, d.rope))], -1)
    v = _mm(c, a["wvb"].reshape(R, H * d.v_head), fp8).reshape(
        S, H, d.v_head)
    return k, v


def _attention(d: Dims, fp8: bool, h, a):
    """Causal latent attention over one sequence; with its keys and
    values, for the routing-tolerant read."""
    S = h.shape[0]
    pos = jnp.arange(S)
    q = _query(d, fp8, h, a, pos)
    k, v = _keys_values(d, fp8, h, a, pos)
    outs = []
    for b0 in range(0, S, QUERY_BLOCK):
        qb = q[b0:b0 + QUERY_BLOCK]
        s = jnp.einsum("qhd,thd->hqt", qb, k, precision=HIGHEST) \
            / math.sqrt(d.nope + d.rope)
        qpos = b0 + jnp.arange(qb.shape[0])
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqt,thd->qhd", pr, v, precision=HIGHEST)
                    .reshape(-1, d.heads * d.v_head))
    return _mm(jnp.concatenate(outs, 0),
               a["wo"].reshape(d.heads * d.v_head, -1), fp8), k, v


def _route(d: Dims, fp8: bool, h, m):
    """The router over every expert: sigmoid scores (…, E) and the
    selection values, score + correction bias."""
    scores = jax.nn.sigmoid(_mm(h, m["router"], fp8))
    return scores, scores + m["router_bias"]


def _experts(d: Dims, fp8: bool, h, m, ids, scores):
    """The held experts and the shared experts for rows ``h`` (N, D)
    routed to ``ids`` (N, top_k): each chosen expert weighted by its
    score, normalised over all chosen, times the scaling factor; only
    the held ones computed."""
    w = jnp.take_along_axis(scores, ids, -1)
    if d.norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * d.scaling

    def one(out, e):
        w1, w3, w2, ge = e
        we = jnp.sum(jnp.where(ids == ge, w, 0.0), -1)
        return out + we[:, None] * _swiglu(h, w1, w3, w2, fp8), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (m["w1"], m["w3"], m["w2"],
                           d.held_start + jnp.arange(d.held)))
    s = m["shared"]
    return out + _swiglu(h, s["w1"], s["w3"], s["w2"], fp8)


def _layer(d: Dims, fp8: bool, moe: bool, x, p):
    o, k, v = _attention(d, fp8, _rms(x, p["ln1"], d.eps), p["attn"])
    x = x + o
    h = _rms(x, p["ln2"], d.eps)
    m = p["mlp"]
    if moe:
        scores, sel = _route(d, fp8, h, m)
        f = _experts(d, fp8, h, m, jax.lax.top_k(sel, d.top_k)[1], scores)
    else:
        f = _swiglu(h, m["w1"], m["w3"], m["w2"], fp8)
    return x + f, (k, v)


@functools.partial(jax.jit, static_argnums=(0, 1))
def forward(d: Dims, fp8: bool, params: dict, tokens: jax.Array):
    """Final-norm hidden states (S, D) of one sequence, and each layer's
    keys and values (the dense layers', the expert layers')."""
    x = params["embed"][tokens]
    x, kv_dense = jax.lax.scan(functools.partial(_layer, d, fp8, False), x,
                               params["dense_layers"])
    x, kv_moe = jax.lax.scan(functools.partial(_layer, d, fp8, True), x,
                             params["blocks"])
    return _rms(x, params["final_norm"], d.eps), kv_dense, kv_moe


def hidden(d: Dims, fp8: bool, params: dict, tokens: jax.Array):
    """Final-norm hidden states (S, D) of one sequence."""
    return forward(d, fp8, params, tokens)[0]


@functools.partial(jax.jit, static_argnums=(0, 1))
def pair_gaps(d: Dims, fp8: bool, params: dict, h: jax.Array,
              at: jax.Array, tok: jax.Array):
    """For each (position ``at``, token ``tok``): how far the token's
    logit lies below the best logit there, in standard deviations of that
    position's logits; and the token this forward puts first."""
    logits = _mm(h[at], params["lm_head"], fp8)
    best = jnp.max(logits, -1)
    sd = jnp.std(logits, -1)
    got = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
    return (best - got) / sd, jnp.argmax(logits, -1).astype(jnp.int32)


# ------------------------------------------------- routing-tolerant read
#: The router's tie band, in selection value (sigmoid score + bias).
#: Where a chosen expert and an unchosen one lie closer than this, a
#: forward in bfloat16 may choose either. The served path's flips at the
#: published widths lay within 1e-2 of a tie at 4-6 layers and within
#: 2e-2 at 8 (the deeper layers carry earlier positions' flips through
#: attention); 3e-2 leaves room for 13. Float8's widest gap did not move
#: between bands of 2e-2 and 5e-2 (CPU; PERF.md §2).
ROUTE_TIE = 3e-2
#: routings one position may follow at once (the closest ties first)
BRANCHES = 64
#: positions per compiled block of the read
BRANCH_POSITIONS = 16
#: the swaps considered, as (rank chosen, rank not chosen), 0-based
_SWAPS = ((-2, 0), (-2, 1), (-1, 0), (-1, 1))


def _own_attention(d: Dims, h, a, pos, k_main, v_main):
    """Attention of rows ``h`` (N, D) at positions ``pos`` over the main
    forward's keys and values before each row's position and the row's
    own key and value."""
    N, H = h.shape[0], d.heads
    q = _query(d, False, h, a, pos)
    k, v = _keys_values(d, False, h, a, pos)
    scale = 1.0 / math.sqrt(d.nope + d.rope)
    s = jnp.einsum("nhd,thd->nht", q, k_main, precision=HIGHEST) * scale
    s = jnp.where(jnp.arange(k_main.shape[0]) < pos[:, None, None], s,
                  -jnp.inf)
    s_own = jnp.sum(q * k, -1) * scale
    top = jnp.maximum(jnp.max(s, -1), s_own)
    p, p_own = jnp.exp(s - top[..., None]), jnp.exp(s_own - top)
    o = (jnp.einsum("nht,thd->nhd", p, v_main, precision=HIGHEST)
         + p_own[..., None] * v) / (jnp.sum(p, -1) + p_own)[..., None]
    return _mm(o.reshape(N, H * d.v_head),
               a["wo"].reshape(H * d.v_head, -1), False)


def _branch_layer(d: Dims, moe: bool, tie: float, pos, carry, layer):
    """One layer of the positions' own forwards, each in ``BRANCHES``
    slots. An expert layer first opens, for every live slot whose
    choice has a tie (a swap of a chosen and an unchosen expert, one of
    them held, closer than ``tie``), a slot that takes the swap;
    free slots go to the closest ties first."""
    x, ids, live = carry                # (P, B, D), (P, B, k), (P, B)
    p, k_main, v_main = layer
    P, B, D = x.shape
    rows = lambda t: t.reshape((P * B,) + t.shape[2:])
    x = x + _own_attention(d, _rms(rows(x), p["ln1"], d.eps), p["attn"],
                           jnp.repeat(pos, B), k_main, v_main
                           ).reshape(P, B, D)
    m = p["mlp"]
    if not moe:
        h = _rms(x, p["ln2"], d.eps)
        return (x + _swiglu(h, m["w1"], m["w3"], m["w2"], False), ids,
                live), None
    k = d.top_k
    _, sel = _route(d, False, _rms(x, p["ln2"], d.eps), m)
    vals, top = jax.lax.top_k(sel, k + 2)
    ids = top[..., :k]
    a = jnp.array([k + i for i, _ in _SWAPS])
    b = jnp.array([k + j for _, j in _SWAPS])
    held = lambda e: (e >= d.held_start) & (e < d.held_start + d.held)
    near = live[..., None] & (vals[..., a] - vals[..., b] < tie) \
        & (held(top[..., a]) | held(top[..., b]))
    key = jnp.where(near, vals[..., a] - vals[..., b], jnp.inf
                    ).reshape(P, B * len(_SWAPS))
    order = jnp.argsort(key, -1)
    free = jnp.argsort(live, -1, stable=True)        # free slots first
    r = jnp.arange(B * len(_SWAPS))
    take = (r < (B - jnp.sum(live, -1))[:, None]) \
        & jnp.isfinite(jnp.take_along_axis(key, order, -1))
    slot = jnp.where(take, jnp.take_along_axis(
        free, jnp.minimum(r, B - 1)[None], -1), B)
    parent, swap = order // len(_SWAPS), order % len(_SWAPS)
    top_p = jnp.take_along_axis(top, parent[..., None], 1)
    swapped = jnp.where(
        jnp.arange(k) == a[swap][..., None],
        jnp.take_along_axis(top_p, b[swap][..., None], -1),
        jnp.take_along_axis(ids, parent[..., None], 1))
    at = jnp.arange(P)[:, None]
    x = x.at[at, slot].set(jnp.take_along_axis(x, parent[..., None], 1),
                           mode="drop")
    ids = ids.at[at, slot].set(swapped, mode="drop")
    live = live.at[at, slot].set(True, mode="drop")
    h = _rms(x, p["ln2"], d.eps)
    scores, _ = _route(d, False, h, m)
    f = _experts(d, False, rows(h), m, rows(ids), rows(scores))
    return (x + f.reshape(P, B, D), ids, live), None


@functools.partial(jax.jit, static_argnums=(0, 7))
def branch_gaps(d: Dims, params: dict, tokens: jax.Array, kv_dense,
                kv_moe, at: jax.Array, tok: jax.Array,
                tie: float) -> jax.Array:
    """For each (position ``at``, token ``tok``): the least gap, as
    ``pair_gaps`` reads it, over the routings the position's own forward
    may take through the router's ties closer than ``tie``, the rest of
    the sequence as the main forward has it (its keys and values)."""
    P, B, D = at.shape[0], BRANCHES, d.d_model
    x = jnp.broadcast_to(params["embed"][tokens[at]][:, None], (P, B, D))
    live = jnp.zeros((P, B), bool).at[:, 0].set(True)
    ids = jnp.zeros((P, B, d.top_k), jnp.int32)
    carry = (x, ids, live)
    carry, _ = jax.lax.scan(
        functools.partial(_branch_layer, d, False, tie, at), carry,
        (params["dense_layers"], *kv_dense))
    (x, _, live), _ = jax.lax.scan(
        functools.partial(_branch_layer, d, True, tie, at), carry,
        (params["blocks"], *kv_moe))
    h = _rms(x, params["final_norm"], d.eps).reshape(P * B, D)
    logits = _mm(h, params["lm_head"], False).reshape(P, B, -1)
    got = jnp.take_along_axis(logits, tok[:, None, None], -1)[..., 0]
    g = (jnp.max(logits, -1) - got) / jnp.std(logits, -1)
    return jnp.min(jnp.where(live, g, jnp.inf), -1)


def gaps(d: Dims, params: dict, seq: np.ndarray, at: np.ndarray,
         tok: np.ndarray, pad_to: int, control: bool = False) -> np.ndarray:
    """Gap of each token ``tok[i]`` served after context ``seq[:at[i]+1]``:
    ``reference.gaps``'s reading (padding to ``pad_to``, the control's
    tokens with ``control``), read through the router's ties: the least
    gap over the routings of the position's own forward that a tie
    allows (``branch_gaps``). Where no choice of the position has a tie
    it is ``pair_gaps``'s."""
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq)] = seq
    toks = jnp.asarray(toks)
    _, kv_dense, kv_moe = forward(d, False, params, toks)
    h8 = hidden(d, True, params, toks) if control else None
    out = []
    for i in range(0, len(at), BRANCH_POSITIONS):
        n = min(BRANCH_POSITIONS, len(at) - i)
        a = np.full(BRANCH_POSITIONS, at[i], np.int32)
        t = np.full(BRANCH_POSITIONS, tok[i], np.int32)
        a[:n], t[:n] = at[i:i + n], tok[i:i + n]
        a, t = jnp.asarray(a), jnp.asarray(t)
        if control:
            _, t = pair_gaps(d, True, params, h8, a, t)
        g = branch_gaps(d, params, toks, kv_dense, kv_moe, a, t,
                        ROUTE_TIE)
        out.append(np.asarray(g)[:n])
    return np.concatenate(out) if out else np.zeros(0)
