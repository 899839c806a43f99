"""Reduction of a profiler trace to device times.

A trace is read into plain events, ``(line, name, start_ns, dur_ns)``
per device plane, so the arithmetic below runs on a recorded trace and
on synthetic ones alike. Which events belong to which program or kernel
is decided by the name table ``kernel_names.json`` beside this file, and
by the roles each ``kernel_names.<name>.json`` beside it adds.
"""
from __future__ import annotations

import fnmatch
import glob
import json
import os
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


ROLE_GROUPS = ("programs", "kernels")


def names(here: pathlib.Path = HERE) -> dict:
    """The name table ``kernel_names.json`` in ``here``, with the roles
    that every ``kernel_names.*.json`` there adds under ``programs`` and
    ``kernels``, so a new kernel's trace names come in a new file. A role
    defined twice, or another key in an added file, is an error."""
    table = json.loads((here / "kernel_names.json").read_text())
    for path in sorted(here.glob("kernel_names.*.json")):
        extra = json.loads(path.read_text())
        if set(extra) - set(ROLE_GROUPS):
            raise ValueError(f"{path.name}: keys other than {ROLE_GROUPS}: "
                             f"{sorted(set(extra) - set(ROLE_GROUPS))}")
        for group, roles in extra.items():
            for role, pats in roles.items():
                if role in table[group]:
                    raise ValueError(f"{path.name}: {group} role {role!r} "
                                     f"is defined twice")
                table[group][role] = pats
    return table


def load(trace_dir: str) -> tuple[dict[str, list[tuple]], list[tuple]]:
    """The newest ``.xplane.pb`` under ``trace_dir``: its device planes,
    plane name -> [(line, event name, start_ns, dur_ns)], and the host
    spans the harness annotated, [(name, start_ns, end_ns)]."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    table = names()
    prof = ProfileData.from_file(files[-1])
    dev, host = {}, []
    for plane in prof.planes:
        on_device = plane.name.startswith(table["device_plane_prefix"])
        ev = []
        for line in plane.lines:
            for e in line.events:
                if on_device:
                    ev.append((line.name, e.name, int(e.start_ns),
                               int(e.duration_ns)))
                elif e.name.startswith(table["host_span_prefix"]):
                    host.append((e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns)))
        if on_device:
            dev[plane.name] = ev
    return dev, host


def union_seconds(intervals) -> float:
    """Length of the union of (start_ns, dur_ns) intervals, in seconds."""
    total, end = 0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def matches(name: str, patterns) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def busy_seconds(events, table: dict) -> float:
    """Seconds in which some operation ran on the device: the union of
    the op-line events."""
    return union_seconds((s, d) for line, _, s, d in events
                         if line == table["ops_line"])


def program_time(events, table: dict, role: str) -> tuple[float, int]:
    """Device seconds and call count of the programs named for ``role``
    in the table (events on the module line)."""
    pats = table["programs"][role]
    hits = [d for line, n, _, d in events
            if line == table["modules_line"] and matches(n, pats)]
    return sum(hits) / 1e9, len(hits)


def kernel_time(events, table: dict, role: str) -> tuple[float, int]:
    """Device seconds and call count of the kernels named for ``role``
    (events on the op line)."""
    pats = table["kernels"][role]
    hits = [d for line, n, _, d in events
            if line == table["ops_line"] and matches(n, pats)]
    return sum(hits) / 1e9, len(hits)


def top_ops(events, table: dict, k: int = 10) -> list[list]:
    """The ``k`` op names that took the most device time: [[name, s]],
    each name cut to its first 120 characters (a v5e trace names an op
    by its whole HLO text)."""
    acc: dict[str, int] = {}
    for line, n, _, d in events:
        if line == table["ops_line"]:
            acc[n] = acc.get(n, 0) + d
    top = sorted(acc.items(), key=lambda x: -x[1])[:k]
    return [[n[:120], d / 1e9] for n, d in top]


def idle_gaps(events, table: dict, host_spans, k: int = 10) -> list[list]:
    """The ``k`` longest gaps with no device op, each named by the
    innermost host span (``(name, start_ns, end_ns)`` on the trace's
    clock) that covers at least half of it: [[name, s]]."""
    iv = sorted((s, s + d) for line, _, s, d in events
                if line == table["ops_line"])
    gaps, end = [], None
    for s, e in iv:
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        best, width = "unattributed", None
        for n, hs, he in host_spans:
            if 2 * (min(b, he) - max(a, hs)) >= b - a and \
                    (width is None or he - hs < width):
                best, width = n, he - hs
        out.append([best, (b - a) / 1e9])
    return out


def dump(events, path: str) -> None:
    """Per (line, name): count and device seconds, for reading a trace by
    hand."""
    acc: dict[tuple, list] = {}
    for line, n, _, d in events:
        a = acc.setdefault((line, n), [0, 0])
        a[0] += 1
        a[1] += d
    rows = sorted(acc.items(), key=lambda x: -x[1][1])
    with open(path, "w") as f:
        for (line, n), (c, d) in rows[:400]:
            f.write(f"{line}\t{n}\t{c}\t{d / 1e9:.6f}\n")
