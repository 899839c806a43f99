"""Shared model building blocks: param specs, norms, RoPE, activations.

Parameters are plain nested dicts of jnp arrays. Each model exposes a *spec
tree* of :class:`ParamSpec` mirroring the param tree; specs carry logical
sharding axes that ``repro.dist.sharding`` maps onto mesh axes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]            # logical axis names, len == ndim
    init: str = "normal"                    # normal | zeros | ones | embed | small
    scale: float = 1.0
    dtype: str = "float32"
    keep_dtype: bool = False                # numerics-sensitive: never downcast
    fan_in: int = 0                         # 0 -> shape[-2]

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_tree_map(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=is_spec)


def init_param(spec: ParamSpec, key: jax.Array) -> jax.Array:
    dtype = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    fan_in = spec.fan_in or (spec.shape[-2] if len(spec.shape) >= 2
                             else spec.shape[-1])
    if spec.init == "embed":
        std = 1.0
        fan_in = 1
    else:
        std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, spec.shape, jnp.float32) * std * spec.scale).astype(dtype)


def init_params(spec_tree, rng: jax.Array):
    """Materialize a param tree from a spec tree with per-leaf folded keys."""
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(rng, len(leaves))
    vals = [init_param(s, k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(spec_tree):
    """ShapeDtypeStruct tree (no allocation) for .lower()."""
    return spec_tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)), spec_tree)


def param_bytes(spec_tree) -> int:
    leaves = jax.tree.leaves(spec_tree, is_leaf=is_spec)
    return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize for s in leaves)


def param_count(spec_tree) -> int:
    leaves = jax.tree.leaves(spec_tree, is_leaf=is_spec)
    return sum(int(np.prod(s.shape)) for s in leaves)


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return x
    return jnp.tanh(x / cap) * cap


def activation_fn(name: str) -> Callable[[jax.Array], jax.Array]:
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------
def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                fraction: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables; positions (...,) -> (..., rot_dim/2)."""
    rot_dim = int(head_dim * fraction) // 2 * 2
    freqs = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, Dh); cos/sin: (B, S, rot/2) or (S, rot/2)."""
    rot = cos.shape[-1] * 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    cos, sin = cos[..., None, :], sin[..., None, :]  # broadcast over head dim
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2, xp], axis=-1)


def sinusoidal_emb(positions: jax.Array, dim: int) -> jax.Array:
    """(...,) int positions -> (..., dim) sinusoidal embedding (musicgen)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


def cast_params(params, spec_tree, compute_dtype: str):
    """Cast params to the compute dtype, except keep_dtype leaves.

    The cast output is sharding-constrained back to the param layout so the
    FSDP per-layer all-gathers move bf16 — XLA otherwise hoists the convert
    past the gather and ships fp32 (2x DCN/ICI bytes, §Perf cell B)."""
    from repro.dist.sharding import constrain
    cd = jnp.dtype(compute_dtype)

    def one(p, s: ParamSpec):
        if s.keep_dtype:
            return p
        return constrain(p.astype(cd), *s.axes)

    return jax.tree.map(one, params, spec_tree, is_leaf=lambda x: is_spec(x))


def take_layer(tree, idx):
    """Select index `idx` along leading (stacked) dim of every leaf."""
    return jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(x, idx, 0, keepdims=False), tree)
