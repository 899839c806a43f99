"""Traffic generation for the benchmark: one general generator, driven by
the parameters of a traffic file (``benchmark/traffic/<name>.json``).

The agent shape is a copy of the repository's synthetic agent traces
(``repro.sim.workload.generate_programs``): turns ~ N(mean, std) with a
floor, tokens per program ~ N(mean, std) with a floor and a cap, later
turns shorter (1.25 -> 0.75 of the per-turn share), a share of each
turn's tokens generated, a lognormal tool pause per turn drawn from a
weighted palette, and an optional shared preamble on the first turn. A
``prompt`` key instead gives single-turn requests with lognormal prompt
and output lengths.

Steadiness: the population of programs and the gaps between arrivals
are drawn once from the file's ``population_seed``; a run's ``--seed``
only permutes them. Every seed then serves the same work in another
order, so runs differ by ordering, not by how much there is to do.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Turn:
    new_tokens: int          # appended to the context before this turn
    output_tokens: int       # generated by this turn
    tool: Optional[str]      # tool called after it (None: last turn)
    tool_s: float            # tool duration, waited out by the driver


@dataclasses.dataclass
class Program:
    pid: str
    turns: list[Turn]
    shared_prefix_tokens: int = 0
    shared_prefix_id: Optional[str] = None

    def context_at(self, k: int) -> int:
        """Prompt length of turn ``k``: the whole history plus its new
        tokens."""
        n = sum(t.new_tokens + t.output_tokens for t in self.turns[:k])
        return n + self.turns[k].new_tokens

    def total_tokens(self) -> int:
        return sum(t.new_tokens + t.output_tokens for t in self.turns)


def lognormal_mu(mean: float, sigma: float) -> float:
    """mu of a lognormal with linear mean ``mean`` and log-space sigma."""
    return math.log(max(mean, 1e-9)) - 0.5 * sigma ** 2


def palette_mean(tools: list) -> float:
    """Weighted mean tool duration of a palette of
    ``[name, weight, mean_s, sigma]`` rows."""
    w = sum(t[1] for t in tools)
    return sum(t[1] * t[2] for t in tools) / w


def _agent_program(spec: dict, i: int, rng: np.random.Generator) -> Program:
    tk, tn = spec["turns"], spec["tokens"]
    n_turns = max(tk["min"], int(round(rng.normal(tk["mean"], tk["std"]))))
    total = min(max(tn["min"], rng.normal(tn["mean"], tn["std"])), tn["cap"])
    per_turn = total / n_turns
    tools = spec["tools"]
    names = [t[0] for t in tools]
    weights = np.asarray([t[1] for t in tools], float)
    weights /= weights.sum()
    turns = []
    for k in range(n_turns):
        frac = 1.25 - 0.5 * (k / max(n_turns - 1, 1))
        tok = max(spec["min_turn_tokens"], int(per_turn * frac))
        out = max(spec["min_output_tokens"], int(tok * spec["output_frac"]))
        new = max(spec["min_new_tokens"], tok - out)
        if k == n_turns - 1:
            tool, dur = None, 0.0
        else:
            ti = int(rng.choice(len(names), p=weights))
            _, _, mean, sigma = tools[ti]
            tool = names[ti]
            dur = float(rng.lognormal(lognormal_mu(mean, sigma), sigma))
        turns.append(Turn(new, out, tool, dur))
    share = spec.get("share", {"ratio": 0.0})
    shared = int(share["ratio"] * tn["mean"]) // 16 * 16
    prefix_id = None
    if shared:
        turns[0].new_tokens += shared
        prefix_id = f"preamble-{i % share['groups']}"
    return Program(f"p{i}", turns, shared, prefix_id)


def _single_program(spec: dict, i: int, rng: np.random.Generator) -> Program:
    def draw(d: dict) -> int:
        x = rng.lognormal(math.log(d["median"]), d["sigma"])
        return int(min(max(x, d["min"]), d["max"]))
    return Program(f"p{i}", [Turn(draw(spec["prompt"]),
                                  draw(spec["output"]), None, 0.0)])


def population(spec: dict, n: int) -> list[Program]:
    """The traffic file's fixed population of ``n`` programs, in draw
    order. Every program fits ``max_len`` tokens (the longest a served
    program may be); the caps in the file are chosen so that none is
    clipped."""
    rng = np.random.default_rng(spec["population_seed"])
    make = _single_program if "prompt" in spec else _agent_program
    progs = [make(spec, i, rng) for i in range(n)]
    for p in progs:
        if p.total_tokens() > spec["max_len"]:
            raise ValueError(f"{p.pid}: {p.total_tokens()} tokens exceed "
                             f"max_len {spec['max_len']}")
    return progs


def schedule(spec: dict, seed: int, seconds: float
             ) -> tuple[list[Program], np.ndarray]:
    """The run's programs in the order they start, and, for an open loop,
    their arrival offsets in seconds.

    Open loop: the ramp and the window hold ``rate_per_s * (ramp_s +
    seconds)`` arrivals, with exponential gaps scaled to fill them,
    the first at offset 0. Closed loop: the file's ``population`` programs, in
    the order the workers take them. The population and the multiset of
    gaps are fixed by the file and the window; ``seed`` permutes both. A
    closed loop's seed permutes each round of ``workers`` programs within
    itself, so every seed starts the same programs, on other workers."""
    rng = np.random.default_rng(seed)
    if spec["loop"] == "closed":
        progs = population(spec, spec["population"])
        w = spec["workers"]
        order = [b + i for b in range(0, len(progs), w)
                 for i in rng.permutation(min(w, len(progs) - b))]
        return [progs[i] for i in order], np.zeros(0)
    span = spec["ramp_s"] + seconds
    n = max(1, round(spec["rate_per_s"] * span))
    progs = population(spec, n)
    gaps = np.random.default_rng(spec["population_seed"] + 1).exponential(
        1.0, n)
    gaps *= span / gaps.sum()
    progs = [progs[i] for i in rng.permutation(n)]
    gaps = rng.permutation(gaps)
    return progs, np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
