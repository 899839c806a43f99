"""Model step (``PagedKVRuntime.prefill``): device time of the prefill
forward and its page gather and scatter programs in the trace, per
1,000 prefill tokens the backend computed while it was recorded."""
import trace_reduce


def read(v):
    s, n = trace_reduce.program_time(v.trace, v.table, "prefill")
    if not n or not v.trace_prefill_tokens:
        return None
    return 1e3 * s / (v.trace_prefill_tokens / 1e3)
