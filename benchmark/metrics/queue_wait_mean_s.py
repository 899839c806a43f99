"""Scheduler + TTL: mean wait from a turn being due to its admission
(the step that puts it in ``engine.running``), over the turns admitted
in the window, on the driver's clock."""


def read(v):
    w = [r.admitted - r.due for r in v.driver.turns.values()
         if v.w0 <= r.admitted < v.w1]
    return sum(w) / len(w) if w else None
