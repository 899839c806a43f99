"""Logical-axis sharding rules.

Model code names tensor dimensions with *logical* axes ("embed", "mlp",
"act_batch", "cache_seq", ...). A *rules* dict maps each logical axis to a
mesh axis (or tuple of mesh axes, or None). :func:`logical_to_spec` turns a
tuple of logical axes into a ``PartitionSpec`` while enforcing the two GSPMD
invariants that are easy to violate by hand:

- a mesh axis may appear at most once in a spec (duplicates are dropped,
  first occurrence wins);
- a dimension is only sharded if its size divides the mesh-axis product
  (non-divisible assignments are dropped, never padded silently).

:func:`default_rules` derives per-(config, mesh, step-kind) rules: tensor
parallelism over "model", batch data-parallelism over "data" (+"pod"),
FSDP on the embed dim only in training, KV-head vs sequence fallback for
the cache, and MoE expert placement.

``axis_rules(...)`` installs rules for the duration of a traced step;
``constrain(x, *axes)`` is a no-op outside that context, so model code runs
unchanged in single-device tests.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

_SPECIAL_PREFIX = "__"          # rules keys like "__mesh__" are not axes

_state = threading.local()


def _mesh_axis_sizes(mesh) -> dict:
    """axis name -> size, for real Meshes and duck-typed test doubles."""
    return dict(mesh.shape)


def logical_to_spec(axes: Sequence[str | None], rules: dict,
                    shape: Sequence[int] | None = None,
                    mesh=None) -> P:
    """Map logical ``axes`` (one entry per tensor dim) to a PartitionSpec.

    ``rules[name]`` may be a mesh-axis name, a tuple of them, or None.
    With ``shape`` (and a mesh, from the arg or ``rules["__mesh__"]``),
    assignments whose mesh-axis product does not divide the dim are
    trimmed from the right until divisible (usually to nothing).
    """
    mesh = mesh if mesh is not None else rules.get("__mesh__")
    sizes = _mesh_axis_sizes(mesh) if mesh is not None else None
    used: set[str] = set()
    out: list[Any] = []
    for i, name in enumerate(axes):
        target = rules.get(name) if isinstance(name, str) else None
        if target is None or (isinstance(name, str)
                              and name.startswith(_SPECIAL_PREFIX)):
            out.append(None)
            continue
        raw = list(target) if isinstance(target, (tuple, list)) else [target]
        cand: list[str] = []
        for a in raw:   # dedup against earlier dims AND within this tuple
            if a not in used and a not in cand \
                    and (sizes is None or a in sizes):
                cand.append(a)
        if shape is not None and sizes is not None:
            while cand:
                prod = 1
                for a in cand:
                    prod *= sizes[a]
                if prod and shape[i] % prod == 0:
                    break
                cand.pop()                       # trim from the right
        if not cand:
            out.append(None)
            continue
        used.update(cand)
        out.append(cand[0] if len(cand) == 1 else tuple(cand))
    return P(*out)


# ---------------------------------------------------------------------------
# rules context (installed per traced step by models/steps.py)
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def axis_rules(rules: dict):
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield rules
    finally:
        _state.rules = prev


def current_rules() -> dict | None:
    return getattr(_state, "rules", None)


def constrain(x: jax.Array, *axes: str | None) -> jax.Array:
    """``with_sharding_constraint`` under the active rules; identity when no
    rules (or no mesh) are installed — model code stays test-runnable."""
    rules = current_rules()
    if not rules:
        return x
    mesh = rules.get("__mesh__")
    if mesh is None or getattr(x, "ndim", None) != len(axes):
        return x
    spec = logical_to_spec(axes, rules, shape=x.shape, mesh=mesh)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_shardings(spec_tree, mesh, rules: dict):
    """NamedSharding tree for a ParamSpec tree (divisibility-checked)."""
    def one(s):
        return NamedSharding(
            mesh, logical_to_spec(s.axes, rules, shape=s.shape, mesh=mesh))

    return jax.tree.map(one, spec_tree,
                        is_leaf=lambda v: hasattr(v, "axes"))


# ---------------------------------------------------------------------------
# default rules
# ---------------------------------------------------------------------------
# Shard the per-expert FFN dim over "data" (expert-FSDP) above this many
# expert parameters per layer — the 235B-class configs where even one
# layer's expert bank exceeds a chip's HBM share.
_MOE_FSDP_PARAM_THRESHOLD = 1e9


def default_rules(cfg: ModelConfig, mesh, step_kind: str = "train") -> dict:
    """Per-(config, mesh, step-kind) logical->mesh axis rules.

    step_kind: "train" | "prefill" | "decode" | "decode_long".
    Only ``mesh.axis_names`` and ``mesh.shape`` are consulted, so tests can
    pass lightweight mesh stand-ins.
    """
    names = tuple(mesh.axis_names)
    sizes = _mesh_axis_sizes(mesh)
    msize = sizes.get("model", 1)
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    train = step_kind == "train"
    long_decode = step_kind == "decode_long"

    H, KV = cfg.num_heads, cfg.num_kv_heads

    rules: dict[str, Any] = {
        "__mesh__": mesh,
        # ---- params: tensor parallelism over "model" -------------------
        "q_heads": "model",
        "kv_heads": "model" if KV % msize == 0 else None,
        "head_dim": None,
        # latent attention's shared latent (kv_lora + rope) dim: replicated
        "kv_latent": None,
        "mlp": "model",
        "embed_out": "model",
        "vocab": "model",
        "layers": None,
        "embed_concat": None,
        # FSDP over the d_model dim of every param — training only (the
        # serving path keeps params fully resident for latency).
        "embed": (("data",) if "data" in names else None) if train else None,
        # ---- activations ----------------------------------------------
        "act_batch": None if long_decode else (data_axes or None),
        "act_seq": (data_axes or None) if long_decode else None,
        "act_embed": None,
        "act_heads": "model",
        "act_kv_heads": "model" if KV % msize == 0 else None,
        # ---- KV cache: kv-head TP when divisible, else ride seq --------
        "cache_kv_heads": "model" if KV % msize == 0 else None,
        # ---- SSM / RWKV -------------------------------------------------
        "conv_dim": "model",
        "ssm_heads": "model",
        "ssm_state": None,
        "ssm_groups": None,
        "rwkv_heads": "model",
        "rwkv_k": None,
        "rwkv_v": None,
        # RWKV head counts are usually not model-divisible; per-chunk fp32
        # tensors ride the chunk dim instead (see models/rwkv6.py).
        "rwkv_chunks": "model",
    }
    rules["cache_seq"] = "model" if rules["cache_kv_heads"] is None else None
    if long_decode:
        # batch=1: nothing to shard there; spread the cache over everything
        seq_axes = data_axes + (("model",) if rules["cache_kv_heads"] is None
                                else ())
        rules["cache_seq"] = seq_axes or None

    # TP head padding: when H doesn't divide the model axis, the attention
    # core pads Q heads up to a multiple of the axis (models/transformer.py)
    # rather than replicating the whole (B,S,H,Dh) tensor.
    rules["__attn_head_pad__"] = msize if (msize > 1 and H % msize) else 0

    # ---- MoE ---------------------------------------------------------------
    if cfg.moe is not None:
        m = cfg.moe
        ep = m.num_experts % msize == 0 and m.sharding_mode != "tp"
        rules["experts"] = "model" if ep else None
        rules["experts_router"] = None
        rules["moe_capacity"] = None
        expert_params = 3 * m.num_experts * cfg.d_model * m.d_ff_expert
        if ep and expert_params > _MOE_FSDP_PARAM_THRESHOLD \
                and "data" in names:
            rules["moe_mlp"] = ("data",)       # expert-FSDP for the giants
        else:
            rules["moe_mlp"] = None if ep else "model"
    return rules
