"""Program spans on the profiler's clock.

``span(name, **args)`` marks a stretch of host work of the served path
(``engine.*``, ``sched.*``, ``kv.*``, ``model.*``). While a profiler
session records (``jax.profiler.start_trace``/``trace``), it opens a
``jax.profiler.TraceAnnotation``: the span lands in the session's
``.xplane.pb`` beside the device ops, on the same clock, with ``args``
as the event's stats; spans opened inside it on the same thread are its
children. While nothing records, it costs one ``is_enabled()`` check and
returns a shared no-op context.

The context manager yields the annotation (or ``None`` when off), so an
argument known only at the end, or costly to compute, is added behind
that test::

    with span("kv.restore", program=pid) as sp:
        ...
        if sp is not None:
            sp.set_metadata(bytes=k.nbytes + v.nbytes)

The annotation class is jaxlib's ``TraceMe``, which
``jax.profiler.TraceAnnotation`` subclasses without change; taking it
from jaxlib keeps ``import jax`` off the virtual-clock simulator's path.
"""
from __future__ import annotations

import contextlib

from jaxlib._profiler import TraceMe as _Annotation

_OFF = contextlib.nullcontext()
_enabled = _Annotation.is_enabled


def span(name: str, **args):
    """A profiler span named ``name`` carrying ``args`` while a profiler
    session records; a shared no-op context otherwise."""
    if _enabled():
        return _Annotation(name, **args)
    return _OFF
