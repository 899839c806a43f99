"""Scheduler + TTL: TTL hits over the returning turns (turn > 0)
admitted in the window. Counter: ``Scheduler.stats.ttl_hits``."""


def read(v):
    back = [r for r in v.driver.turns.values()
            if r.turn > 0 and v.w0 <= r.admitted < v.w1]
    if not back:
        return None
    return (v.c1["ttl_hits"] - v.c0["ttl_hits"]) / len(back)
