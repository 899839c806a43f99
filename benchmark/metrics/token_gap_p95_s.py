"""Engine: 95th percentile of the gaps between consecutive output
tokens of one turn, over the gaps that end in the window, on the
driver's clock: a step that waits on a tier move or a prefill chunk
shows here."""
import driver


def read(v):
    e = driver.end_to_end(v.driver, v.w0, v.w1)
    return e["itl_p95_s"] if e["n_gaps"] else None
