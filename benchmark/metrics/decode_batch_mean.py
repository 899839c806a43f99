"""Engine: decode rows per non-idle step in the window (steps without a
decode count as 0), from the harness's record of each decode call."""


def read(v):
    steps = [x for x in v.driver.steps if v.w0 <= x.start < v.w1]
    if not steps:
        return None
    rows = sum(len(lens) for t, lens in v.capture.decode_calls
               if v.w0 <= t < v.w1)
    return rows / len(steps)
