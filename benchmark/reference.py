"""Weights from the seed, and the plain reference forward that decides
``correct``: the reference module of dense transformer configurations.

A configuration file names its reference module by ``"reference":
"<stem>"`` (the module ``benchmark/<stem>.py``; this one, ``reference``,
where the key is absent). ``run.py`` knows an architecture only through
that module's interface:

- ``shape_of(model) -> dims``: the hashable dims the forward needs, from
  the file's ``model`` block;
- ``served_dims(cfg) -> dims``: the same dims, read from the registry
  ``ModelConfig`` the program serves;
- ``program_config(cfg, model) -> ModelConfig``: the registry config cut
  to the file's sizes;
- ``make_weights(dims, seed, embed_std)``: the weights the program is
  handed, in the tree it serves;
- ``gaps(dims, params, seq, at, tok, pad_to, control=False)``: each
  served token's gap below the reference's best (below).

Nothing here imports the system under test. The weights are made by the
benchmark (one jitted call, on the device, in float32 as the program
holds them) in the layout the program serves: a stacked layer tree
``blocks/{ln1, ln2, attn/{wq, wk, wv, wo, bq?, bk?, bv?}, mlp/{w1, w3,
w2}}`` beside ``embed``, ``final_norm`` and an untied ``lm_head``. The
program is handed these weights; the reference reads the same ones.

The reference is a contiguous forward in float32 with every matmul at
``Precision.HIGHEST``: RMSNorm with a ``1 + w`` gain (the program's
norm convention), rotary embeddings on the leading ``rope_fraction`` of
each head (rotate-half), grouped-query causal attention, SwiGLU, and a
tied or untied head. With ``fp8`` set it is the control: every matmul
input (weights per output channel, activations per row) and the keys
and values rounded to float8 e4m3, the step below bfloat16 that a later
change might take.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 1024
NORM_STD = 0.1          # random norm gains around 1, biases around 0
BIAS_STD = 0.1


def shape_of(model: dict) -> tuple:
    """The hashable dims the forward needs, from a config file's
    ``model`` block (Hugging Face key names)."""
    H = model["num_attention_heads"]
    return (model["num_hidden_layers"], model["hidden_size"], H,
            model["num_key_value_heads"],
            model.get("head_dim", model["hidden_size"] // H),
            model["intermediate_size"], model["vocab_size"],
            float(model["rope_theta"]),
            float(model.get("partial_rotary_factor", 1.0)),
            float(model.get("rms_norm_eps", model.get("layer_norm_eps"))),
            bool(model["tie_word_embeddings"]),
            bool(model.get("use_qkv_bias", model.get("qkv_bias", False))))


def served_dims(cfg) -> tuple:
    """The dims of ``shape_of`` read from a registry ``ModelConfig``."""
    return (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size, float(cfg.rope_theta),
            float(cfg.rope_fraction), float(cfg.norm_eps),
            bool(cfg.tie_embeddings), bool(cfg.qkv_bias))


def program_config(cfg, model: dict):
    """The registry config cut to the file's sizes: its depth."""
    return dataclasses.replace(cfg, num_layers=model["num_hidden_layers"])


def root_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def weight_shapes(dims: tuple) -> dict:
    """(shape, fan_in or init kind) per leaf, in the served layout."""
    L, D, H, KV, Dh, F, V, _, _, _, tie, bias = dims
    attn = {"wq": ((L, D, H, Dh), D), "wk": ((L, D, KV, Dh), D),
            "wv": ((L, D, KV, Dh), D), "wo": ((L, H, Dh, D), H * Dh)}
    if bias:
        attn.update(bq=((L, H, Dh), "bias"), bk=((L, KV, Dh), "bias"),
                    bv=((L, KV, Dh), "bias"))
    tree = {"embed": ((V, D), "embed"), "final_norm": ((D,), "norm"),
            "blocks": {"ln1": ((L, D), "norm"), "ln2": ((L, D), "norm"),
                       "attn": attn,
                       "mlp": {"w1": ((L, D, F), D), "w3": ((L, D, F), D),
                               "w2": ((L, F, D), F)}}}
    if not tie:
        tree["lm_head"] = ((D, V), D)
    return tree


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make_weights(dims: tuple, key: jax.Array, embed_std: float) -> dict:
    spec = weight_shapes(dims)
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=is_leaf)
    out = []
    for i, (shape, kind) in enumerate(leaves):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        std = {"embed": embed_std, "norm": NORM_STD,
               "bias": BIAS_STD}.get(kind)
        out.append(z * (std if std is not None else 1.0 / math.sqrt(kind)))
    return jax.tree.unflatten(treedef, out)


def make_weights(dims: tuple, seed: int, embed_std: float) -> dict:
    """The model's float32 weights from ``seed``, made on the default
    device in one jitted call: embeddings N(0, embed_std) (the source's
    ``initializer_range``), matrices N(0, 1/fan_in), norm gains and
    biases as above.

    The embedding scale matters with tied embeddings: at N(0, 1) the
    input token's own row dominates the final hidden state, every
    greedy token repeats its input at a margin no rounding can close,
    and the comparison would see nothing."""
    return _make_weights(dims, root_key(seed), float(embed_std))


# ---------------------------------------------------------------- forward
def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """(S, K) @ (K, N) in float32 at the highest precision; the control
    rounds the activations per row and the weights per column first."""
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta, frac):
    """Rotate-half rotary embedding on the leading ``frac`` of the head."""
    Dh = x.shape[-1]
    rot = int(Dh * frac) // 2 * 2
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def _layer(dims, fp8, x, p):
    L, D, H, KV, Dh, F, V, theta, frac, eps, tie, bias = dims
    S = x.shape[0]
    pos = jnp.arange(S)
    a = p["attn"]
    h = _rms(x, p["ln1"], eps)
    q = _mm(h, a["wq"].reshape(D, H * Dh), fp8).reshape(S, H, Dh)
    k = _mm(h, a["wk"].reshape(D, KV * Dh), fp8).reshape(S, KV, Dh)
    v = _mm(h, a["wv"].reshape(D, KV * Dh), fp8).reshape(S, KV, Dh)
    if bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, pos, theta, frac), _rope(k, pos, theta, frac)
    if fp8:
        k, v = _fp8(k, -1), _fp8(v, -1)
    G = H // KV
    outs = []
    for b0 in range(0, S, QUERY_BLOCK):
        qb = q[b0:b0 + QUERY_BLOCK].reshape(-1, KV, G, Dh)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HIGHEST) \
            / math.sqrt(Dh)
        qpos = b0 + jnp.arange(qb.shape[0])
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", pr, v, precision=HIGHEST)
                    .reshape(-1, H * Dh))
    o = jnp.concatenate(outs, 0)
    x = x + _mm(o, a["wo"].reshape(H * Dh, D), fp8)
    h = _rms(x, p["ln2"], eps)
    m = p["mlp"]
    g = jax.nn.silu(_mm(h, m["w1"], fp8)) * _mm(h, m["w3"], fp8)
    return x + _mm(g, m["w2"], fp8), None


@functools.partial(jax.jit, static_argnums=(0, 1))
def hidden(dims: tuple, fp8: bool, params: dict, tokens: jax.Array):
    """Final-norm hidden states (S, D) of one sequence."""
    eps = dims[9]
    x = params["embed"][tokens]
    x, _ = jax.lax.scan(functools.partial(_layer, dims, fp8), x,
                        params["blocks"])
    return _rms(x, params["final_norm"], eps)


def _head(dims, params):
    return params["embed"].T if dims[10] else params["lm_head"]


@functools.partial(jax.jit, static_argnums=(0, 1))
def pair_gaps(dims: tuple, fp8: bool, params: dict, h: jax.Array,
              at: jax.Array, tok: jax.Array):
    """For each (position ``at``, token ``tok``): how far the token's
    logit lies below the best logit there, in standard deviations of that
    position's logits; and the token this forward puts first."""
    logits = _mm(h[at], _head(dims, params), fp8)
    best = jnp.max(logits, -1)
    sd = jnp.std(logits, -1)
    got = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
    return (best - got) / sd, jnp.argmax(logits, -1).astype(jnp.int32)


PAIR_BLOCK = 256


def gaps(dims: tuple, params: dict, seq: np.ndarray, at: np.ndarray,
         tok: np.ndarray, pad_to: int, control: bool = False) -> np.ndarray:
    """Gap of each token ``tok[i]`` served after context ``seq[:at[i]+1]``,
    read by the float32 reference as ``pair_gaps`` defines it. ``seq`` is
    padded to ``pad_to`` tokens (causal: the padding changes nothing
    before it), so every sequence runs one compiled program.

    ``control``: the tokens are not ``tok`` but those the float8 forward
    puts first at each position, and the reference reads their gaps (the
    control's reading)."""
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq)] = seq
    toks = jnp.asarray(toks)
    h = hidden(dims, False, params, toks)
    h8 = hidden(dims, True, params, toks) if control else None
    out = []
    for i in range(0, len(at), PAIR_BLOCK):
        n = min(PAIR_BLOCK, len(at) - i)
        a = np.full(PAIR_BLOCK, at[i], np.int32)
        t = np.full(PAIR_BLOCK, tok[i], np.int32)
        a[:n], t[:n] = at[i:i + n], tok[i:i + n]
        a, t = jnp.asarray(a), jnp.asarray(t)
        if control:
            _, t = pair_gaps(dims, True, params, h8, a, t)
        g, _ = pair_gaps(dims, False, params, h, a, t)
        out.append(np.asarray(g)[:n])
    return np.concatenate(out) if out else np.zeros(0)
