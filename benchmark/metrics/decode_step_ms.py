"""Model step (``PagedKVRuntime.decode_batch``): device time per call of
the jitted decode step, from the trace."""
import trace_reduce


def read(v):
    s, n = trace_reduce.program_time(v.trace, v.table, "decode_step")
    return 1e3 * s / n if n else None
