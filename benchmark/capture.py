"""What the timed path produced, captured by the harness from outside the
program, and its comparison with the reference.

``Capture`` wraps five methods of the engine's paged runtime instance
(prefill, the batched decode step, the copy-on-write split, tier
stage-out and restore) and logs, in order, every write of token ids into
physical KV pages and every token the program put first (its greedy
argmax) together with the pages holding its context. Decoded token ids
stay device scalars (the ones the program already holds) until the
window has closed. ``replay`` then resolves them and follows the page
writes on the host, so each produced token is paired with the exact ids
that its context holds, whether they came from a prefill, a decode, a
shared preamble adopted from another program, a TTL pin or a restore
from the host tier.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np


@dataclasses.dataclass
class Chain:
    """One context of a program and the tokens produced along it:
    ``at[i]`` is the last context position of token ``tok[i]``."""
    seq: np.ndarray
    at: list
    tok: list
    decode_rows: list       # batch size of the step that produced each


class Capture:
    def __init__(self, runtime):
        self.rt = runtime
        self.log: list[tuple] = []
        self.decode_calls: list[tuple[float, list[int]]] = []
        self.clock = None       # set by the harness: host time of a call
        rt = runtime
        self._orig = {n: getattr(rt, n) for n in (
            "prefill", "decode_batch", "_writable_page", "stage_out",
            "restore")}
        rt.prefill = self._prefill
        rt.decode_batch = self._decode_batch
        rt._writable_page = self._writable_page
        rt.stage_out = self._stage_out
        rt.restore = self._restore

    # ---------------------------------------------------------- wrappers
    def _prefill(self, params, pid, tokens, pad_to=None, max_len=None):
        e = self.rt.programs.get(pid)
        start = e.length if e is not None else 0
        out = self._orig["prefill"](params, pid, tokens, pad_to=pad_to,
                                    max_len=max_len)
        toks = np.asarray(tokens, np.int32)
        self.log.append(("prefill", pid, start, toks,
                         list(self.rt.programs[pid].pages),
                         self.rt._last[pid], 0))
        return out

    def _decode_batch(self, params, pids):
        rt = self.rt
        fed = [rt._last[p] for p in pids]
        lens = [rt.programs[p].length for p in pids]
        out = self._orig["decode_batch"](params, pids)
        for p, n, f in zip(pids, lens, fed):
            self.log.append(("decode", p, n, f, list(rt.programs[p].pages),
                             rt._last[p], len(pids)))
        if self.clock is not None:
            self.decode_calls.append((self.clock(), lens))
        return out

    def _writable_page(self, e, idx):
        old = e.pages[idx]
        new = self._orig["_writable_page"](e, idx)
        if new != old:
            self.log.append(("copy", old, new))
        return new

    def _stage_out(self, pid):
        self.log.append(("stage", pid, list(self.rt.programs[pid].pages)))
        return self._orig["stage_out"](pid)

    def _restore(self, pid, *staged):
        pages = self._orig["restore"](pid, *staged)
        self.log.append(("restore", pid, list(pages)))
        return pages

    # ------------------------------------------------------------ replay
    def replay(self, keep: set[str] | None = None) -> dict[str, list[Chain]]:
        """Follow the logged page writes and return, for each program in
        ``keep`` (all when None), its contexts and produced tokens."""
        refs = [e[5] for e in self.log if e[0] in ("prefill", "decode")]
        refs += [e[3] for e in self.log if e[0] == "decode"]
        vals = {id(r): int(v) for r, v in zip(refs, jax.device_get(refs))}
        ps = self.rt.page_size
        pages = np.full((self.rt.n_pages, ps), -1, np.int64)
        staged: dict[str, np.ndarray] = {}
        chains: dict[str, list[Chain]] = {}
        for e in self.log:
            kind = e[0]
            if kind == "copy":
                pages[e[2]] = pages[e[1]]
            elif kind == "stage":
                staged[e[1]] = pages[e[2]].copy()
            elif kind == "restore":
                snap = staged.pop(e[1])
                pages[e[2]] = snap[:len(e[2])]
            elif kind == "prefill":
                _, pid, start, toks, pl, out, rows = e
                pos = np.arange(start, start + len(toks))
                pages[np.asarray(pl)[pos // ps], pos % ps] = toks
                self._note(chains, keep, pages, pl, pid,
                           start + len(toks) - 1, vals[id(out)], rows)
            else:
                _, pid, n, fed, pl, out, rows = e
                pages[pl[n // ps], n % ps] = vals[id(fed)]
                self._note(chains, keep, pages, pl, pid, n, vals[id(out)],
                           rows)
        return chains

    @staticmethod
    def _note(chains, keep, pages, pl, pid, at, tok, rows) -> None:
        if keep is not None and pid not in keep:
            return
        ctx = pages[np.asarray(pl)].reshape(-1)[:at + 1]
        assert (ctx >= 0).all(), f"{pid}: context position never written"
        cs = chains.setdefault(pid, [])
        for c in cs:
            n = min(len(c.seq), len(ctx))
            if np.array_equal(c.seq[:n], ctx[:n]):
                if len(ctx) > len(c.seq):
                    c.seq = ctx
                break
        else:
            c = Chain(ctx, [], [], [])
            cs.append(c)
        c.at.append(at)
        c.tok.append(tok)
        c.decode_rows.append(rows)
