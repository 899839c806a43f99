"""Engine: wall time of ``Engine.step`` outside its ``backend.execute``
(the backend's own device-synchronised step time, compile excluded),
averaged over the non-idle steps that start in the window."""


def read(v):
    s = [x.end - x.start - x.exec_s for x in v.driver.steps
         if v.w0 <= x.start < v.w1]
    return 1e3 * sum(s) / len(s) if s else None
