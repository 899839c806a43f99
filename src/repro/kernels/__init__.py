# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Shared kernel-package plumbing.

Every Pallas kernel in this tree takes an ``interpret`` flag. Its
*default* is derived here, in one place, from the runtime platform: the
compiled Mosaic path on a TPU, interpret mode (kernel body executed by
the Pallas interpreter — correct everywhere, fast nowhere) on anything
else. The kernels are written for the TPU only, so no other accelerator
gets the compiled path. Callers that need to force a mode (tests pinning
interpret semantics, the chip path insisting on compiled kernels) pass an
explicit bool; passing ``None`` means "whatever this platform wants".
"""
from __future__ import annotations


def default_interpret() -> bool:
    """True iff Pallas kernels should run in interpret mode here: a TPU
    runs the compiled kernel path, every other backend interprets."""
    import jax
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """``None`` -> the platform default; explicit bools pass through."""
    return default_interpret() if interpret is None else bool(interpret)


__all__ = ["default_interpret", "resolve_interpret"]
