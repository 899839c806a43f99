"""Float32 reference logits for checking the served path.

The reference is the plainest forward the model has: contiguous, no
cache, float32 compute under ``precision="highest"`` (on a TPU the
default float32 matmul is a single bf16 pass). Served logits are compared
with it position by position — logits, not argmax, since random weights
give near-ties that a correct path may break either way.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.transformer import Model


def reference_logits(cfg: ModelConfig, params, tokens, n_last: int
                     ) -> np.ndarray:
    """(n_last, vocab) float32 logits of the last ``n_last`` positions of
    ``tokens`` (1-D), from a float32 contiguous forward of ``cfg``."""
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32",
                                kv_cache_dtype="")
    tokens = jnp.asarray(tokens, jnp.int32).reshape(1, -1)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(
            Model(cfg32).forward, static_argnames=("mode", "logits_slice"))(
                params, tokens=tokens, mode="train", logits_slice=n_last)
    return np.asarray(logits[0], np.float32)


def compare_logits(got, ref, rel_tol: float) -> dict:
    """Max absolute logit error, scaled by the reference's largest
    |logit|, against ``rel_tol``. Returns the numbers and the verdict."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    return {"max_abs_err": err, "ref_max_abs": scale,
            "rel_err": err / scale, "rel_tol": rel_tol,
            "ok": bool(err <= rel_tol * scale)}
