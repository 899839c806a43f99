"""Traffic generator and window arithmetic of the benchmark (CPU)."""
from __future__ import annotations

import json
import math
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import driver as drv  # noqa: E402
import traffic_gen as tg  # noqa: E402

TRAFFIC = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
AGENT = [n for n in TRAFFIC
         if "prompt" not in json.loads((BENCH / "traffic" / f"{n}.json")
                                       .read_text())]


def spec(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_programs(name):
    a, oa = tg.schedule(spec(name), 2**33 + 7, 30)
    b, ob = tg.schedule(spec(name), 2**33 + 7, 30)
    assert [p.pid for p in a] == [p.pid for p in b]
    assert [p.turns for p in a] == [p.turns for p in b]
    np.testing.assert_array_equal(oa, ob)


@pytest.mark.parametrize("name", TRAFFIC)
def test_seeds_permute_one_population(name):
    """Another seed serves the same programs and gaps in another order."""
    a, oa = tg.schedule(spec(name), 1, 30)
    b, ob = tg.schedule(spec(name), 2, 30)
    assert [p.pid for p in a] != [p.pid for p in b]
    key = lambda ps: sorted((p.pid, p.total_tokens()) for p in ps)
    assert key(a) == key(b)
    if len(oa):
        span = spec(name)["ramp_s"] + 30
        gaps = lambda o: np.sort(np.diff(np.append(o, span)))
        np.testing.assert_allclose(gaps(oa), gaps(ob), rtol=0, atol=1e-9)


def test_closed_loop_seeds_start_the_same_programs():
    """A closed loop's seed reorders each round of ``workers`` programs:
    every seed's workers start the same programs, in another order."""
    s = spec("swe-workers")
    w = s["workers"]
    a, _ = tg.schedule(s, 1, 30)
    b, _ = tg.schedule(s, 2**33 + 5, 30)
    for k in range(0, len(a), w):
        ra, rb = [p.pid for p in a[k:k + w]], [p.pid for p in b[k:k + w]]
        assert ra != rb and sorted(ra) == sorted(rb)


@pytest.mark.parametrize("name", AGENT)
def test_agent_draws_match_file(name):
    s = spec(name)
    progs = tg.population(s, 3000)
    turns = np.asarray([len(p.turns) for p in progs], float)
    assert abs(turns.mean() - s["turns"]["mean"]) < 0.15
    assert turns.min() >= s["turns"]["min"]
    own = np.asarray([p.total_tokens() - p.shared_prefix_tokens
                      for p in progs], float)
    assert abs(np.median(own) - s["tokens"]["mean"]) < 0.06 * \
        s["tokens"]["mean"]
    assert max(p.total_tokens() for p in progs) <= s["max_len"]
    tools = [t.tool_s for p in progs for t in p.turns if t.tool]
    assert tg.palette_mean(s["tools"]) == pytest.approx(s["tool_mean_s"],
                                                        rel=1e-3)
    assert np.mean(tools) == pytest.approx(s["tool_mean_s"], rel=0.12)
    outs = [t.output_tokens / (t.output_tokens + t.new_tokens)
            for p in progs for t in p.turns[1:]]
    assert np.median(outs) == pytest.approx(s["output_frac"], rel=0.1)
    assert all(p.turns[-1].tool is None for p in progs)


def test_chat_draws_match_file():
    s = spec("chat-control")
    progs = tg.population(s, 4000)
    prompts = np.asarray([p.turns[0].new_tokens for p in progs])
    outs = np.asarray([p.turns[0].output_tokens for p in progs])
    assert np.median(prompts) == pytest.approx(s["prompt"]["median"],
                                               rel=0.05)
    assert prompts.min() >= s["prompt"]["min"]
    assert prompts.max() <= s["prompt"]["max"]
    assert np.median(outs) == pytest.approx(s["output"]["median"], rel=0.06)
    assert all(len(p.turns) == 1 and p.turns[0].tool is None for p in progs)


def test_open_loop_offsets_fill_the_span():
    s = spec("bfcl-agent")
    progs, off = tg.schedule(s, 9, 40)
    assert len(progs) == round(s["rate_per_s"] * (s["ramp_s"] + 40))
    assert off[0] == 0 and np.all(np.diff(off) > 0)
    assert off[-1] < s["ramp_s"] + 40


# ------------------------------------------------------------ window math
def _driver_with(turns, program_end, program_due):
    d = drv.Driver.__new__(drv.Driver)
    d.turns = {(t.pid, t.turn): t for t in turns}
    d.program_end = program_end
    d.program_due = program_due
    return d


def test_window_statistics_cover_all_events():
    turns = []
    for i in range(100):
        r = drv.TurnRecord(f"p{i}", 0, due=float(i), prompt_len=10)
        # first token 0.5 s after due, then tokens 0.1 s apart
        r.tokens = [i + 0.5 + 0.1 * k for k in range(5)]
        turns.append(r)
    ends = {f"p{i}": i + 0.9 for i in range(100)}
    dues = {f"p{i}": float(i) for i in range(100)}
    d = _driver_with(turns, ends, dues)
    m = drv.end_to_end(d, 10.0, 60.0)
    # programs whose last turn ends in [10, 60): p10 .. p59 (ends i + 0.9)
    assert m["n_programs"] == 50
    assert m["jct_mean_s"] == pytest.approx(0.9)
    # first tokens i + 0.5 in [10, 60): i = 10 .. 59
    assert m["n_turns"] == 50
    assert m["ttft_p95_s"] == pytest.approx(0.5)
    # gaps end at i + 0.6 .. i + 0.9; those ending in the window
    assert m["n_gaps"] == 4 * 50
    assert m["itl_p95_s"] == pytest.approx(0.1)
    assert m["output_tok_per_s"] == pytest.approx(5 * 50 / 50.0)


def test_tail_is_over_all_requests():
    turns = []
    for i in range(40):
        r = drv.TurnRecord(f"p{i}", 0, due=0.0, prompt_len=1)
        r.tokens = [1.0 + (9.0 if i < 2 else 0.0)]
        turns.append(r)
    d = _driver_with(turns, {}, {})
    m = drv.end_to_end(d, 0.0, 100.0)
    # 2 of 40 first tokens wait 10 s: the 95th percentile sees them
    assert m["ttft_p95_s"] == pytest.approx(
        np.percentile([10.0] * 2 + [1.0] * 38, 95))
    assert math.isnan(m["jct_mean_s"])


@pytest.mark.parametrize("name,key", [("turn_ttft_p95_s", "ttft_p95_s"),
                                      ("token_gap_p95_s", "itl_p95_s")])
def test_tail_readers_read_the_window(name, key):
    """The per-layer tails are the window's tails over all its events,
    and nothing where the window holds none."""
    import run
    turns = []
    for i in range(30):
        r = drv.TurnRecord(f"p{i}", 0, due=float(i), prompt_len=1)
        r.tokens = [i + 0.2 + 0.05 * i + 0.3 * k for k in range(3)]
        turns.append(r)
    d = _driver_with(turns, {}, {})
    view = type("V", (), {"driver": d, "w0": 5.0, "w1": 25.0})()
    assert run.read_metric(name, view) == drv.end_to_end(d, 5.0, 25.0)[key]
    view.w0, view.w1 = 100.0, 110.0
    assert run.read_metric(name, view) is None


# ----------------------------------------------------------- the driver
class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeReq:
    def __init__(self, pid, k, prompt, out, due):
        self.program_id, self.turn_idx = pid, k
        self.prompt_len, self.output_len = prompt, out
        self.generated = 0
        self.request_id = id(self)
        self.arrival_time = due


class FakeEngine:
    """One request at a time; each step takes ``dt`` and emits a token."""

    def __init__(self, clock, dt):
        self.clock, self.dt = clock, dt
        self.q, self.rejected = [], 0
        self.backend = type("B", (), {"step_seconds": []})()

    @property
    def has_work(self):
        return bool(self.q)

    def submit(self, req, now):
        self.q.append(req)

    def step(self, now):
        ev = type("E", (), {})()
        r = self.q[0]
        ev.admitted = [r] if r.generated == 0 else []
        self.clock.t += self.dt
        self.backend.step_seconds.append(self.dt * 0.9)
        r.generated += 1
        ev.idle = False
        ev.finished = []
        if r.generated >= r.output_len:
            ev.finished = [self.q.pop(0)]
        return ev


def test_driver_stamps_due_times_and_waits_tools():
    clock = FakeClock()
    eng = FakeEngine(clock, dt=1.0)
    p = tg.Program("a", [tg.Turn(10, 2, "t", 5.0), tg.Turn(10, 2, None, 0)])
    q = tg.Program("b", [tg.Turn(10, 1, None, 0.0)])
    d = drv.Driver(eng, lambda prog, k, due: FakeReq(prog.pid, k, 10, 2 if
                                                      prog.pid == "a" else 1,
                                                      due),
                   clock=clock, sleep=clock.sleep)
    d.open_loop([p, q], [0.0, 0.5], 0.0)
    d.run_until(100.0)
    a0, a1, b0 = d.turns[("a", 0)], d.turns[("a", 1)], d.turns[("b", 0)]
    assert a0.tokens == [1.0, 2.0] and a0.end == 2.0
    # b was due at 0.5 but the engine was busy: delivered late at 1.0
    assert b0.due == 0.5 and b0.submitted == 1.0
    assert d.lateness[1] == pytest.approx(0.5)
    # b's turn runs after a's first turn in this one-at-a-time engine
    assert b0.tokens == [3.0] and b0.tokens[0] - b0.due == 2.5
    # a's next turn is due at its last token plus the tool's 5 s, waited
    assert a1.due == 7.0 and a1.tokens == [8.0, 9.0]
    assert d.program_end == {"a": 9.0, "b": 3.0}
    assert [s.exec_s for s in d.steps] == pytest.approx([0.9] * 5)


def test_closed_loop_starts_next_program_at_end():
    clock = FakeClock()
    eng = FakeEngine(clock, dt=1.0)
    progs = [tg.Program(f"w{i}", [tg.Turn(10, 2, None, 0.0)])
             for i in range(3)]
    d = drv.Driver(eng, lambda prog, k, due: FakeReq(prog.pid, k, 10, 2,
                                                      due),
                   clock=clock, sleep=clock.sleep)
    d.closed_loop(progs, workers=1, stagger_s=0.0, start=0.0)
    d.run_until(100.0)
    assert d.program_due == {"w0": 0.0, "w1": 2.0, "w2": 4.0}
    assert d.program_end == {"w0": 2.0, "w1": 4.0, "w2": 6.0}
