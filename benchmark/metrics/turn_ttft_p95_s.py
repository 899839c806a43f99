"""Scheduler + TTL: 95th percentile, over the turns whose first token
falls in the window, of first token minus the time the turn was due
(queueing behind the pool, restore and re-prefill included), on the
driver's clock. Past the knee, where the queue sets it, a tail swings
with small changes and is read here, not held to a bound."""
import driver


def read(v):
    e = driver.end_to_end(v.driver, v.w0, v.w1)
    return e["ttft_p95_s"] if e["n_turns"] else None
