#!/usr/bin/env python3
"""Program spans: the host spans the served program opens itself
(``repro.obs.span``: ``engine.*``, ``sched.*``, ``kv.*``, ``model.*``),
read back with their arguments from a traced run's ``.xplane.pb``, and
the arithmetic the per-layer metrics built on them share.

A span is ``(name, start_ns, end_ns, args)`` on the trace's clock, the
clock of the device ops. The readers return None where the trace holds
no device plane (a CPU run), where its device events are not the ones
the run reduced, or where the program opened no such spans.

    python3 benchmark/program_spans.py <trace dir>

prints every reading of the newest trace under ``<trace dir>``.
"""
from __future__ import annotations

import bisect
import glob
import os
import pathlib
import sys
from types import SimpleNamespace

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

TRACE_ROOT = HERE.parent / "benchmark_out" / "trace"
PREFIXES = ("engine.", "sched.", "kv.", "model.")
TIER_MOVES = ("kv.restore", "kv.stage_out")


def newest(root) -> str | None:
    files = glob.glob(os.path.join(str(root), "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def read_file(path: str) -> tuple[int | None, list[tuple]]:
    """The number of events on the file's first device plane (None
    without one) and the program's spans, in start order (a parent
    before its children)."""
    from jax.profiler import ProfileData
    prefix = trace_reduce.names()["device_plane_prefix"]
    n_dev, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(prefix):
            if n_dev is None:
                n_dev = sum(1 for line in plane.lines for _ in line.events)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    s = int(e.start_ns)
                    spans.append((e.name, s, s + int(e.duration_ns),
                                  dict(e.stats)))
    spans.sort(key=lambda x: (x[1], -x[2]))
    return n_dev, spans


def spans(v) -> list[tuple] | None:
    """The program's spans in the newest trace under ``v.trace_root``
    (default: the traced run's directory), if that trace is the one ``v``
    reduced: its first device plane holds as many events as
    ``v.trace``. Read once per view."""
    if "program_spans" not in vars(v):
        path = newest(getattr(v, "trace_root", TRACE_ROOT))
        v.program_spans = None
        if path is not None and v.trace:
            n_dev, sp = read_file(path)
            if sp and n_dev == len(v.trace):
                v.program_spans = sp
    return v.program_spans


# ------------------------------------------------------------- intervals
def merged(iv) -> list[tuple]:
    """The union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: list[list] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [tuple(x) for x in out]


def overlap(a, b) -> int:
    """Length shared by two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy(v) -> list[tuple]:
    """Intervals in which some operation ran on the device."""
    ops = v.table["ops_line"]
    return merged((s, s + d) for line, _, s, d in v.trace if line == ops)


def idle_under(v, sp, names) -> float:
    """Seconds with no device op while a span named in ``names`` is
    open."""
    under = merged((s, e) for n, s, e, _ in sp if n in names)
    return (sum(e - s for s, e in under) - overlap(under, busy(v))) / 1e9


def inside(sp, outer) -> list[tuple]:
    """The spans that ``outer`` contains, itself left out (``sp`` in
    start order)."""
    _, s0, e0, _ = outer
    lo = bisect.bisect_left(sp, s0, key=lambda x: x[1])
    hi = bisect.bisect_right(sp, e0, key=lambda x: x[1])
    return [x for x in sp[lo:hi] if x[2] <= e0 and x is not outer]


def innermost(sp) -> list[tuple]:
    """The time the spans cover, cut into ``(start, end, name)`` pieces
    named by the innermost span open in each."""
    segs, stack, t = [], [], None
    for name, s, e, _ in sp:
        while stack and stack[-1][0] <= s:
            end, n = stack.pop()
            if end > t:
                segs.append((t, end, n))
                t = end
        if stack and s > t:
            segs.append((t, s, stack[-1][1]))
        t = s
        stack.append((e, name))
    while stack:
        end, n = stack.pop()
        if end > t:
            segs.append((t, end, n))
            t = end
    return segs


def idle_by_span(v, sp) -> dict[str, float]:
    """Device-idle seconds of the traced stretch (first to last op or
    span) by the innermost program span open in them; ``none`` for idle
    time under no program span."""
    b = busy(v)
    lo = min(b[0][0] if b else sp[0][1], sp[0][1])
    hi = max(b[-1][1] if b else 0, max(e for _, _, e, _ in sp))
    idle, t = [], lo
    for s, e in b:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if hi > t:
        idle.append((t, hi))
    out: dict[str, float] = {}
    segs = innermost(sp)
    j = 0
    for a, z in idle:
        named = 0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < z:
            w = min(z, segs[k][1]) - max(a, segs[k][0])
            if w > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + w / 1e9
                named += w
            k += 1
        if z - a > named:
            out["none"] = out.get("none", 0.0) + (z - a - named) / 1e9
    return dict(sorted(out.items(), key=lambda x: -x[1]))


# -------------------------------------------------------------- readings
def move_rate(v, name: str, parts, priced: str | None = None):
    """GB/s of the tier moves ``name``: their ``bytes`` over their host
    wall; prints their count, bytes, padded bytes, the wall of each
    child span in ``parts`` and, with ``priced``, the seconds the
    scheduler priced them at."""
    sp = spans(v)
    moves = [x for x in sp or () if x[0] == name]
    if not moves:
        return None
    wall = sum(e - s for _, s, e, _ in moves)
    nbytes = sum(a["bytes"] for *_, a in moves)
    split = dict.fromkeys(parts, 0)
    for m in moves:
        for n, s, e, _ in inside(sp, m):
            if n in split:
                split[n] += e - s
    msg = (f"{name}: {len(moves)} moves, {nbytes} bytes (padded "
           f"{sum(a['padded_bytes'] for *_, a in moves)}), wall "
           f"{wall / 1e9:.6f} s; " + ", ".join(
               f"{n} {t / 1e9:.6f} s" for n, t in split.items()))
    if priced:
        p = sum(a[priced] for *_, a in moves)
        msg += f"; priced {p:.6f} s, wall over priced " + (
            f"{wall / 1e9 / p:.2f}" if p else "n/a")
    print(msg, flush=True)
    return nbytes / wall                  # bytes per ns = GB/s


def tier_idle_share(v):
    """Device-idle seconds under an open tier move over the traced
    window; prints the window's device-idle seconds by innermost span."""
    sp = spans(v)
    if sp is None:
        return None
    table = idle_by_span(v, sp)
    total = sum(table.values())
    in_step = idle_under(v, sp, ("engine.step",))
    print(f"device idle {total:.6f} s by innermost program span: " + ", ".join(
        f"{n} {t:.6f} s ({100 * t / total:.1f}%)" for n, t in table.items()),
        flush=True)
    if in_step:
        below = 1 - table.get("engine.step", 0.0) / in_step
        print(f"idle inside engine.step {in_step:.6f} s, under a span "
              f"below it {100 * below:.1f}%", flush=True)
    return idle_under(v, sp, TIER_MOVES) / (v.t_trace[1] - v.t_trace[0])


def sched_ms_per_step(v):
    """Milliseconds of ``engine.admit`` outside its ``kv.*`` spans per
    non-idle ``engine.step``: the scheduler's own host time."""
    sp = spans(v)
    if sp is None:
        return None
    steps = [x for x in sp if x[0] == "engine.step"
             and x[3].get("prefill_tokens", 0) + x[3].get("decode_rows", 0)]
    if not steps:
        return None
    total = 0
    for st in steps:
        for adm in (x for x in inside(sp, st) if x[0] == "engine.admit"):
            kv = merged((s, e) for n, s, e, _ in inside(sp, adm)
                        if n.startswith("kv."))
            total += adm[2] - adm[1] - sum(e - s for s, e in kv)
    print(f"sched_ms_per_step: {len(steps)} non-idle steps, engine.admit "
          f"outside kv.* {total / 1e9:.6f} s", flush=True)
    return total / 1e6 / len(steps)


def main(trace_dir: str) -> int:
    planes, _ = trace_reduce.load(trace_dir)
    events = next(iter(planes.values()), [])
    ops = [(s, s + d) for line, _, s, d in events
           if line == trace_reduce.names()["ops_line"]]
    if not ops:
        print("no device plane in the trace", file=sys.stderr)
        return 1
    window = (0.0, (max(e for _, e in ops) - min(s for s, _ in ops)) / 1e9)
    v = SimpleNamespace(trace=events, table=trace_reduce.names(),
                        t_trace=window, trace_root=trace_dir)
    for name, fn in (("restore_gbps", lambda: move_rate(
            v, "kv.restore", ("kv.restore_pad", "kv.h2d", "kv.scatter"),
            priced="priced_s")),
            ("demote_gbps", lambda: move_rate(
                v, "kv.stage_out", ("kv.gather", "kv.d2h"))),
            ("tier_idle_share", lambda: tier_idle_share(v)),
            ("sched_ms_per_step", lambda: sched_ms_per_step(v))):
        print(f"{name} {fn()!r}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
