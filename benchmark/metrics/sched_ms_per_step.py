"""Scheduler + TTL: host milliseconds of ``engine.admit`` outside its
``kv.*`` spans (the tier moves admission triggers), per non-idle
``engine.step``: queue pick, radix match, TTL bookkeeping and block
accounting, from the program's own spans."""
import program_spans


def read(v):
    return program_spans.sched_ms_per_step(v)
